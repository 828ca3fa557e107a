"""Engine step: device events an iteration (`loop.events_per_step`): these loops are
latency-bound, the count is the cost.  Read from shortened replays of the run's last
launch, not from the measured window (`_explain.py`)."""

from benchmark.layers._explain import loop


def read(ctx):
    return loop("events_per_step")
