"""Engine step: time an iteration in operations with no scope whose name starts with
`copy` (`loop.copies_us`): the carry the compiler moves between layouts and buffers.  Read
from shortened replays of the run's last launch, not from the measured window
(`_explain.py`)."""

from benchmark.layers._explain import loop


def read(ctx):
    return loop("copies_us")
