"""Engine runtime: median of `result.fetch` + `result.unpack` per study of the
script cell."""

from benchmark.layers._program_spans import median_ms


def read(ctx):
    return median_ms(ctx, ("result.fetch", "result.unpack"))
