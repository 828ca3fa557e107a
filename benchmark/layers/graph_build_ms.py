"""Script front door: median wall time from `main(argv)`'s entry until `lift()`
is called (CommandLine, object graph, helpers, Simulator.Run up to the seam)."""

import statistics


def read(ctx):
    mains, lifts = ctx["starts"].get("main"), ctx["starts"].get("lift")
    if not mains or not lifts or len(mains) != len(lifts):
        return None
    return statistics.median(b - a for a, b in zip(mains, lifts)) * 1e3
