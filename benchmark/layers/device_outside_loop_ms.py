"""Device: busy time a launch outside the loop, all programs: the init program, and the
advance program's prologue and epilogue (`outside_loop_ms` summed).  Read from shortened
replays of the run's last launch, not from the measured window (`_explain.py`)."""

from benchmark.layers._explain import outside_loop_ms


def read(ctx):
    return outside_loop_ms()
