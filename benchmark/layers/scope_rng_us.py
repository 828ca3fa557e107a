"""Engine step: self time an iteration under the step's key derivation and draws (last
component `rng`).  Read from shortened replays of the run's last launch, not from the
measured window (`_explain.py`)."""

from benchmark.layers._explain import scope_us


def read(ctx):
    return scope_us("rng")
