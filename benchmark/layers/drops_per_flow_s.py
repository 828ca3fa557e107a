"""Engine step: packets the bottleneck queue dropped, a flow a simulated second, over
the window's launches and replicas: the loss events that drive the window rules
(slow start's overshoot, then one burst of tail drops a sawtooth).  A change that only
makes the simulator faster must not move it."""

import numpy as np

from benchmark.layers._dumbbell import window


def read(ctx):
    w = window(ctx)
    if w is None:
        return None
    flow_replicas = sum(np.asarray(o["drops"]).size for o in w["outs"])
    if not flow_replicas:
        return None
    drops = sum(float(np.sum(o["drops"])) for o in w["outs"])
    return drops / (flow_replicas * w["sim_s"])
