"""Engine step: self time an iteration under the scope whose last component is `rts`:
the RTS/CTS arm of the BSS step's per-node arm (a CTS due after an RTS, the DATA due
after a CTS, the NAV from an overheard RTS or CTS, the wait for a CTS).  Read from
shortened replays of the run's last launch, not from the measured window
(`_explain.py`)."""

from benchmark.layers._explain import scope_us


def read(ctx):
    return scope_us("rts")
