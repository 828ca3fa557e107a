"""Engine step: share of the bottleneck's slots that sent a packet, from the first
flow's start to the horizon, over the window's launches and replicas (`delivered`
counts departures from the queue, one a backlogged slot).  Near 1 says the queue
never ran dry: congestion control under loss did the work, not an idle link.  A
change that only makes the simulator faster must not move it."""

import numpy as np

from benchmark.layers._dumbbell import window


def read(ctx):
    w = window(ctx)
    if w is None:
        return None
    ph = w["physics"]
    slot_s = (ph["segment_bytes"] + ph["header_bytes"]) * 8 / ph["bottleneck_rate_bps"]
    slots = (w["sim_s"] - ph["flow_start_s"]) / slot_s
    replicas = sum(np.asarray(o["delivered"]).shape[0] for o in w["outs"])
    if slots <= 0 or not replicas:
        return None
    return w["delivered"] / (slots * replicas)
