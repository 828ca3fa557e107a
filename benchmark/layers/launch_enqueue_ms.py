"""Engine runtime: median time of `launch.enqueue` per launch: the jitted
call(s) of `drive_chunks` until they return."""

from benchmark.layers._program_spans import median_ms


def read(ctx):
    return median_ms(ctx, ("launch.enqueue",))
