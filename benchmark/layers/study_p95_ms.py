"""Script front door: 95th percentile (nearest rank) of every study of the
window; in a one-client closed loop it is mostly collector and allocator
pauses, which is why it is not an end-to-end metric here."""

import math


def read(ctx):
    walls = ctx["counters"].get("study_walls_s")
    if not walls:
        return None
    return walls[max(math.ceil(0.95 * len(walls)) - 1, 0)] * 1e3
