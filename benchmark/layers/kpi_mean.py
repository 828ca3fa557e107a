"""Simulated statistic: the reference module's `kpi` of each launch (mean
delivered megabits, or echoes, per replica), averaged over the window.  A
change that only makes the simulator faster must not move it."""


def read(ctx):
    return ctx["counters"].get("kpi_mean")
