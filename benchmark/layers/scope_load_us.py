"""Engine step: self time an iteration under the scope whose last component is `load`:
the hop walk that scatter-adds each flow's surviving rate into its links' loads, the
bulk of a fluid relaxation round.  Read from shortened replays of the run's last launch,
not from the measured window (`_explain.py`)."""

from benchmark.layers._explain import scope_us


def read(ctx):
    return scope_us("load")
