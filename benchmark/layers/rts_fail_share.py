"""Engine step: the share of RTS frames that no CTS answered in time, pooled over the
window's launches: the program's `cts_timeout` over its `tx_rts`.  With hidden senders
most of these are RTS that overlapped another at the receiver.  A program that does not
count them (one with no RTS, or one before the per-node arm) gives nothing to read."""

import numpy as np


def read(ctx):
    outs = ctx["record"].get("outs") or []
    if not outs or any("tx_rts" not in o for o in outs):
        return None
    sent = sum(float(np.sum(o["tx_rts"])) for o in outs)
    if not sent:
        return None
    return sum(float(np.sum(o["cts_timeout"])) for o in outs) / sent
