"""Engine runtime: the host part of `run_lifted` in a study: its median wall
time less the device busy time per study (traced studies)."""

import statistics


def read(ctx):
    spans = ctx["spans"].get("traced_run_lifted")
    trace, n = ctx["trace"], ctx["record"].get("trace_studies")
    if not spans or not trace or not n or not trace["busy_s"]:
        return None
    return statistics.median(spans) * 1e3 - trace["busy_s"] / n * 1e3
