"""Engine step: the `while`'s own time an iteration (its duration less its children's):
sequencing, the scalar core's loose instructions, the condition (`loop.own_us`).  Read
from shortened replays of the run's last launch, not from the measured window
(`_explain.py`)."""

from benchmark.layers._explain import loop


def read(ctx):
    return loop("own_us")
