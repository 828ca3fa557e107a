"""Engine runtime: median time of `launch.operands` per launch: replica keys,
the initial carry (`init_state`, `stack_axis`), sharding, traced scalars."""

from benchmark.layers._program_spans import median_ms


def read(ctx):
    return median_ms(ctx, ("launch.operands",))
