"""Engine step: self time an iteration under the loop body's own scope (last component
`step`), outside the scopes inside it.  Read from shortened replays of the run's last
launch, not from the measured window (`_explain.py`)."""

from benchmark.layers._explain import scope_us


def read(ctx):
    return scope_us("step")
