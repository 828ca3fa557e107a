"""Engine step: self time an iteration under the scope whose last component is `sense`:
the per-node medium of the BSS step's per-node arm (each node's busy time, NAV and
contention grant, the interference each receiver's frame collects, the lock onto a
starting frame, the slots a contender keeps).  Read from shortened replays of the
run's last launch, not from the measured window (`_explain.py`)."""

from benchmark.layers._explain import scope_us


def read(ctx):
    return scope_us("sense")
