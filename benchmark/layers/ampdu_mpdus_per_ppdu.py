"""Engine step: MPDUs per PPDU over the window's launches: the program's `tx_mpdus`
(MPDUs the data PPDUs carried, counted in the A-MPDU arm of the BSS step) over its
`tx_data` (those PPDUs).  1 would mean aggregation did nothing; a program that does
not count `tx_mpdus` gives nothing to read."""

import numpy as np


def read(ctx):
    outs = ctx["record"].get("outs") or []
    if not outs or any("tx_mpdus" not in o for o in outs):
        return None
    ppdus = sum(float(np.sum(o["tx_data"])) for o in outs)
    if not ppdus:
        return None
    return sum(float(np.sum(o["tx_mpdus"])) for o in outs) / ppdus
