"""Engine runtime: median time of `result.fetch` per launch: `jax.device_get`
of the output tree after the wait, so transfer only."""

from benchmark.layers._program_spans import median_ms


def read(ctx):
    return median_ms(ctx, ("result.fetch",))
