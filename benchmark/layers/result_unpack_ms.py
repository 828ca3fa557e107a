"""Engine runtime: median time of `result.unpack` per launch: the engine's
`finalize(host)`, a deferred chunk flush included."""

from benchmark.layers._program_spans import median_ms


def read(ctx):
    return median_ms(ctx, ("result.unpack",))
