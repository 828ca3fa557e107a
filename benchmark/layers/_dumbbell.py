"""What the two dumbbell readers share: the window's results as the program of a
`dumbbell` launch returns them (`delivered`, `drops`: (replicas, flows) counts;
`goodput_mbps`: `delivered` x segment bits over the simulated time), and the
simulated seconds of a launch, read back from those two fields and the segment size,
which no result states: the deployment's configuration file does (a reader's context
does not carry the cell's configuration, so the file is opened by its name).  A
result without the fields (another engine's) gives nothing to read."""

import json
import os

import numpy as np

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "configs", "tcp-dumbbell-8flow-cubic.json",
)


def window(ctx):
    """`dict(outs, delivered, sim_s, physics)` of the window (`delivered` summed over
    launches, replicas and flows; `sim_s` of one launch), or None."""
    outs = ctx["record"].get("outs") or []
    fields = ("delivered", "drops", "goodput_mbps")
    if not outs or any(f not in o for o in outs for f in fields):
        return None
    delivered = sum(float(np.sum(o["delivered"])) for o in outs)
    megabits = sum(float(np.sum(o["goodput_mbps"], dtype=np.float64)) for o in outs)
    if not delivered or not megabits:
        return None
    with open(CONFIG) as f:
        cfg = json.load(f)
    physics = dict(cfg["physics"], **cfg["topology"])
    # goodput = delivered x segment bits / sim_s / 1e6, summed over the same rows
    sim_s = delivered * physics["segment_bytes"] * 8 / 1e6 / megabits
    return dict(outs=outs, delivered=delivered, sim_s=sim_s, physics=physics)
