"""The program's own spans and compile events, cut to the measured window.

The harness times each layer from outside (`dispatch`, `fetch_unpack`, the
script driver's patched `lift` and `run_lifted`).  The program records the
inside of the launch path itself, always on, in memory: `tpudes.obs.spans`
(names in PERF.md section 3) and `CompileTelemetry.xla_events`.  Both clocks
are `time.perf_counter`, so the window is cut by the harness's own spans:
from the first start to the last end of those not set aside as `traced_`.

A reading is a median over the window's launches: spans are grouped by their
request id (a `launch`'s id, which its children and its `result.*` carry),
summed per launch, and the median of the sums is reported in ms.  A program
without the spans or the events (the parent of the PR that added them) reads
as None: the metric is left out of the line.
"""

import statistics

XLA_COMPILE = "/jax/core/compile/backend_compile_duration"


def window(ctx):
    """`(t0, t1)` of the measured window, or None without harness spans."""
    starts, durations = ctx["starts"], ctx["spans"]
    edges = [
        (s, s + d)
        for name in starts if not name.startswith("traced_")
        for s, d in zip(starts[name], durations[name])
    ]
    if not edges:
        return None
    return min(a for a, _ in edges), max(b for _, b in edges)


def ring():
    """The program's closed spans, or None where it records none."""
    try:
        from tpudes.obs import spans
    except ImportError:
        return None
    return spans.snapshot()


def median_ms(ctx, plus, minus=()):
    """Median over the window's launches of (time in the spans named in
    `plus`) - (time in those named in `minus`), in ms."""
    entries, cut = ring(), window(ctx)
    if entries is None or cut is None:
        return None
    sums, counted = {}, set()
    for e in entries:
        if not cut[0] <= e.start <= cut[1]:
            continue
        if e.name in plus:
            counted.add(e.request)
            sums[e.request] = sums.get(e.request, 0.0) + (e.end - e.start)
        elif e.name in minus:
            sums[e.request] = sums.get(e.request, 0.0) - (e.end - e.start)
    if not counted:
        return None
    return statistics.median(sums[r] for r in counted) * 1e3


def xla_compiles(ctx):
    """XLA programs built (or loaded from the persistent cache) inside the
    window, the eager `jnp` programs of the launch path included."""
    from tpudes.obs.device import CompileTelemetry

    events = getattr(CompileTelemetry, "xla_events", None)
    cut = window(ctx)
    if events is None or cut is None:
        return None
    return float(sum(
        1 for t, event, *_ in events(since=cut[0])
        if event == XLA_COMPILE and t <= cut[1]
    ))
