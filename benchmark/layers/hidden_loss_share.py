"""Engine step: the share of the frames senders opened an exchange with (RTS, and DATA)
that their destination did not decode while a node the sender cannot sense was on the
air, pooled over the window's launches: the program's `rx_lost_hidden` over its
`tx_rts` + `tx_data`.  A program that does not count them gives nothing to read."""

import numpy as np


def read(ctx):
    outs = ctx["record"].get("outs") or []
    if not outs or any("rx_lost_hidden" not in o for o in outs):
        return None
    sent = sum(float(np.sum(o["tx_rts"]) + np.sum(o["tx_data"])) for o in outs)
    if not sent:
        return None
    return sum(float(np.sum(o["rx_lost_hidden"])) for o in outs) / sent
