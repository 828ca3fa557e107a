"""Engine step: iterations of the BSS event loop a launch took (`steps`: every
replica advanced to its own next event, until the slowest is done), median over the
window's launches.  `step_us` times one; this counts them."""

import numpy as np


def read(ctx):
    steps = [o["steps"] for o in ctx["record"].get("outs") or [] if "steps" in o]
    return float(np.median(steps)) if steps else None
