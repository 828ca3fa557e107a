"""Engine runtime: median wall time of `run_lifted(block=False)`, call to return
(runner lookup, key and carry set-up, enqueue), over the window's launches."""

import statistics


def read(ctx):
    spans = ctx["spans"].get("dispatch")
    return statistics.median(spans) * 1e3 if spans else None
