"""Engine runtime: median of `launch` less its three children per launch: what
no child names (mesh selection, `checkpoint_ctx`, the LTE `consts_np` copies)."""

from benchmark.layers._program_spans import median_ms


def read(ctx):
    return median_ms(
        ctx, ("launch",),
        minus=("launch.runner", "launch.operands", "launch.enqueue"),
    )
