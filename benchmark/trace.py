"""From a `jax.profiler` trace to numbers: the benchmark's own reduction.

`load_xplane` reads the `.xplane.pb` a traced window wrote into plain lists;
`reduce_trace` works on those lists alone, so the test under `tests/` checks it
on a small recorded trace without a chip.

A TPU trace has one plane per device (`/device:TPU:<n>`) whose `XLA Ops` line
holds one event per executed HLO operation, properly nested (a `while` spans
its body's operations), and a `/host:CPU` plane that holds the harness's own
`bench:<name>` spans (`jax.profiler.TraceAnnotation`) on the same clock.

- busy: per device, the union of the operation intervals inside the window,
  i.e. the sum of the outermost events; averaged over the devices.
- window: first `bench:` span's start to the last one's end.
- step: the outermost `while` operations (the engine's loop over TTIs or BSS
  events).
- collectives: operations whose HLO opcode is a cross-device collective.
- idle gaps: on the first device, each gap between outermost operations goes to
  the innermost `bench:` span that covers its middle.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
COLLECTIVES = (
    "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
    "collective-permute", "collective-broadcast",
)
OUTSIDE = "_outside_every_span_"


def op_name(event_name: str) -> str:
    """`%while.139 = (s32[64]...) while(...)` -> `while.139`."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def load_xplane(trace_dir: str) -> dict:
    """`{"devices": {plane: [(name, start_ns, dur_ns)]}, "host": [...]}` from
    the newest `.xplane.pb` under `trace_dir`."""
    import jax

    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not files:
        return {"devices": {}, "host": []}
    data = jax.profiler.ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    ]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX)
                ]
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def _nest(events: list, t0: float, t1: float):
    """Clip to the window and walk the nesting: a list of
    `(name, start, end, depth, self_ns)`, one entry per event."""
    clipped = []
    for name, start, dur in events:
        s, e = max(start, t0), min(start + dur, t1)
        if e > s:
            clipped.append((s, -(e - s), name, e))
    clipped.sort()
    out, stack = [], []       # stack of [name, start, end, child_ns, depth]

    def close(item):
        name, s, e, child, depth = item
        out.append((name, s, e, depth, max(e - s - child, 0.0)))

    for s, _, name, e in clipped:
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            e = min(e, stack[-1][2])
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0, len(stack)])
    while stack:
        close(stack.pop())
    return out


def reduce_trace(events: dict, top: int = 10) -> dict | None:
    """The numbers the per-layer readers and the result line's `device` and
    `breakdown` take; None where no device operation was traced."""
    spans = events["host"]
    if not spans or not events["devices"]:
        return None
    t0 = min(s for _, s, _ in spans)
    t1 = max(s + d for _, s, d in spans)
    busy, whiles, collectives, per_op = [], [], [], {}
    first_gaps = None
    for plane in sorted(events["devices"]):
        nested = _nest(events["devices"][plane], t0, t1)
        outer = sorted((s, e) for _, s, e, depth, _ in nested if depth == 0)
        busy.append(sum(e - s for s, e in outer))
        loops = sorted(
            (s, e) for name, s, e, _, _ in nested if name.startswith("while")
        )
        total, covered_to = 0.0, t0
        for s, e in loops:          # nested whiles count once, by the outer one
            if s >= covered_to:
                total += e - s
                covered_to = e
        whiles.append(total)
        collectives.append(sum(
            e - s for name, s, e, _, _ in nested
            if name.startswith(COLLECTIVES)
        ))
        for name, _, _, _, self_ns in nested:
            per_op[name] = per_op.get(name, 0.0) + self_ns
        if first_gaps is None:
            edges = [t0] + [x for s, e in outer for x in (s, e)] + [t1]
            first_gaps = [
                (edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]
            ]
    if not any(busy):
        return None
    n = len(busy)
    by_span: dict[str, float] = {}
    for s, e in first_gaps:
        mid = 0.5 * (s + e)
        covering = [(dd, name) for name, ss, dd in spans if ss <= mid < ss + dd]
        # spans nest (study > main > lift): the innermost one owns the gap
        owner = min(covering)[1][len(SPAN_PREFIX):] if covering else OUTSIDE
        by_span[owner] = by_span.get(owner, 0.0) + (e - s)

    def ranked(table: dict, scale: float):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns * scale] for name, ns in rows if ns > 0]

    return dict(
        window_s=(t1 - t0) * 1e-9,
        busy_s=sum(busy) / n * 1e-9,
        while_s=sum(whiles) / n * 1e-9,
        collective_s=sum(collectives) / n * 1e-9,
        devices=n,
        device_ops=ranked(per_op, 1e-9 / n),
        idle_gaps=ranked(by_span, 1e-9),
    )
