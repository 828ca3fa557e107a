"""Engine runtime: median time of `launch.runner` per launch: the cache key
(`tobytes()` of every table) and the `RUNTIME.runner` lookup."""

from benchmark.layers._program_spans import median_ms


def read(ctx):
    return median_ms(ctx, ("launch.runner",))
