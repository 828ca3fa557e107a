"""Mesh: device time inside cross-device collectives over device busy time, in
the traced launches, averaged over the devices.  Nothing where the trace holds
no collective (one chip)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["collective_s"] or not trace["busy_s"]:
        return None
    return 100.0 * trace["collective_s"] / trace["busy_s"]
