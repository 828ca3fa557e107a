"""Simulated statistic: the most loaded link's utilisation, averaged over the window's
launches and replicas (the result's `max_util`): how near the fluid gate, a delivery of
min(1, rate / load), the offered load sits.  A change that only makes the simulator
faster must not move it.  A result without the field (another engine's) gives nothing
to read."""

import numpy as np


def read(ctx):
    outs = ctx["record"].get("outs") or []
    if not outs or any("max_util" not in o for o in outs):
        return None
    return float(np.concatenate(
        [np.asarray(o["max_util"], float).ravel() for o in outs]).mean())
