"""Engine runtime: median wall time from device done to numpy on the host (one
batched D2H and the engine's unpack), over the window's launches."""

import statistics


def read(ctx):
    spans = ctx["spans"].get("fetch_unpack")
    return statistics.median(spans) * 1e3 if spans else None
