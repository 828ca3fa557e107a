"""Engine step: device time of the engine's `while` per iteration (one TTI, or
one BSS event step over all replicas), from the traced launches."""


def read(ctx):
    trace, n = ctx["trace"], ctx["record"].get("trace_iterations")
    if not trace or not n or not trace["while_s"]:
        return None
    return trace["while_s"] / n * 1e6
