"""Engine runtime: median time of the whole `launch` span per study of the
script cell (`run_lifted` entry until the future exists)."""

from benchmark.layers._program_spans import median_ms


def read(ctx):
    return median_ms(ctx, ("launch",))
