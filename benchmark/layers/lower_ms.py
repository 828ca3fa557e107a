"""Lift / lower: median wall time of `lift.lift()` (discovery + `lower_*`)."""

import statistics


def read(ctx):
    spans = ctx["spans"].get("lift")
    return statistics.median(spans) * 1e3 if spans else None
