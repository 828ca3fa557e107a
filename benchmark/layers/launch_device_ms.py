"""Engine step: device busy time per study, from the traced studies."""


def read(ctx):
    trace, n = ctx["trace"], ctx["record"].get("trace_studies")
    if not trace or not n or not trace["busy_s"]:
        return None
    return trace["busy_s"] / n * 1e3
