"""The program reads its own device trace by its own names.

A ``jax.profiler`` trace of a lifted run holds everything a by-hand
reading of a loop step needs: on each ``/device:TPU:<n>`` plane the
``XLA Ops`` events, whose METADATA carries ``tf_op`` (the
``jax.named_scope`` path: ``tpudes.<engine>.step`` from
:func:`~tpudes.parallel.runtime.scoped_while_loop`, ``.rng`` from
:func:`~tpudes.parallel.runtime.step_keys`, the engines' own ``.ampdu``
/ ``.cc`` / ``.queue``), the ``XLA Modules`` events
(``jit_tpudes_<engine>_init`` / ``_advance``,
:func:`~tpudes.parallel.runtime.jit_advance`), and on ``/host:CPU`` the
``tpudes:<span>`` events of :mod:`tpudes.obs.spans`, on the device's
clock.  This module turns one such trace into one table:

- :func:`load` reads an ``.xplane.pb`` into plain lists (the proto
  module is imported here, inside the call, never at ``import
  tpudes``);
- :func:`reduce` is pure on those lists (tier-1 tests it on the CPU):
  a loop step split by innermost ``tpudes.*`` scope, the ``while``'s
  own time, the copies, the device time outside the loop by program
  name and by innermost scope, every idle gap of the first device
  SPLIT over the innermost ``tpudes:`` span the host was in, the
  ``launch`` span's arguments;
  a trace that is cut, or whose names another tree wrote, yields
  ``withheld`` and no number;
- :class:`session` takes the trace (a profiler window of its own, in
  a temporary directory, with the loop of every launch of its thread
  stopped at ``max_iterations`` so that the trace stays under the
  profiler's event limit); :func:`replay` runs the process's last
  launch again inside one, :data:`LAUNCHES` times, the first not read;
  ``python -m tpudes.obs --explain <dir or .xplane.pb>`` prints the
  table of a trace the operator took.

Off, it costs a launch one attribute store (``run_lifted`` keeps what
it was called with on ``RUNTIME.last_lifted``) and one ``is None`` test
(``Launch.drive`` asks ``RUNTIME.explain``): no span is added to the
launch path and no scope to a loop, so no executable changes.  The
names and the metric that reads each are listed in PERF.md section 3.
"""

from __future__ import annotations

import bisect
import glob
import itertools
import os
import re
import statistics
import threading

__all__ = ["OUTSIDE", "format_table", "load", "reduce", "replay", "session"]

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "tpudes:"
SCOPE_PREFIX = "tpudes."
#: idle time under no ``tpudes:`` span: the caller's own code
OUTSIDE = "_outside_every_span_"
#: device time of an operation that no program's module event covers
NO_PROGRAM = "_no_program_"
#: device time outside the loop of an operation under no ``tpudes.*`` scope
NO_SCOPE = "_no_scope_"
#: where a session stops every launch's loop, and how often
#: :func:`replay` launches.  Stopping the profiler costs about 30 us a
#: device event on a v5e, so the slowest loop's reading (the dumbbell
#: slot's 200 events: 6 launches of 128 slots) stays under 10 s, where
#: 4096 slots took 84 s; a step's parts read within 1% of a whole
#: launch's (PERF.md section 6, PR 37)
MAX_ITERATIONS = 128
LAUNCHES = 6
#: the replay's first launch follows ``start_trace`` and is not read
WARM_UP = 1
#: how many of a step's largest operations the table lists
TOP = 10


# --- the trace as plain lists ----------------------------------------------


def _xplane_pb2():
    """The xplane proto module, from the installed ``tensorflow``
    package's file WITHOUT importing the package: ``import
    tensorflow`` takes 13 s and the generated module needs only
    ``google.protobuf`` (0.15 s)."""
    import importlib.util
    import sys

    dotted = "tensorflow.tsl.profiler.protobuf.xplane_pb2"
    if dotted in sys.modules:
        return sys.modules[dotted]
    package = importlib.util.find_spec("tensorflow")
    if package is None or not package.submodule_search_locations:
        raise ImportError("no tensorflow package to take xplane_pb2 from")
    path = os.path.join(
        list(package.submodule_search_locations)[0],
        "tsl", "profiler", "protobuf", "xplane_pb2.py",
    )
    spec = importlib.util.spec_from_file_location(
        "tsl.profiler.protobuf.xplane_pb2", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def op_name(event_name: str) -> str:
    """``%while.139 = (s32[64]...) while(...)`` -> ``while.139``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def _stat_value(plane, stat):
    """One XStat's value; a ``ref_value`` points into ``stat_metadata``."""
    which = stat.WhichOneof("value")
    if which == "ref_value":
        return plane.stat_metadata[stat.ref_value].name
    return getattr(stat, which) if which else None


def load(path: str) -> dict:
    """The newest ``.xplane.pb`` under the directory ``path`` (or the
    file ``path``) as plain lists, times in ns on the trace's clock::

        {"devices": {plane: [(op name, tf_op, start, duration)]},
         "modules": {plane: [(program name, start, duration)]},
         "host":    [(span name, start, duration, args)]}

    ``devices`` holds the ``XLA Ops`` line of each ``/device:TPU:<n>``
    plane with ``tf_op`` from the event METADATA's stats (a
    ``str_value``, or a ``ref_value`` into the plane's
    ``stat_metadata``); ``modules`` its ``XLA Modules`` line; ``host``
    the ``/host:CPU`` events whose name starts with ``tpudes:`` (the
    prefix removed) with their arguments."""
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(
            path, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices, modules, host = {}, {}, []
    for plane in space.planes:
        if plane.name.startswith(DEVICE_PLANE):
            tf_op_ids = {
                k for k, v in plane.stat_metadata.items() if v.name == "tf_op"
            }
            described = {}      # metadata id -> (op name, tf_op)
            for mid, md in plane.event_metadata.items():
                tf_op = ""
                for stat in md.stats:
                    if stat.metadata_id in tf_op_ids:
                        tf_op = _stat_value(plane, stat) or ""
                described[mid] = (op_name(md.name), tf_op)
            for line in plane.lines:
                t0 = line.timestamp_ns
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        described[e.metadata_id]
                        + (t0 + e.offset_ps * 1e-3, e.duration_ps * 1e-3)
                        for e in line.events
                    ]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [
                        (described[e.metadata_id][0],
                         t0 + e.offset_ps * 1e-3, e.duration_ps * 1e-3)
                        for e in line.events
                    ]
        elif plane.name == HOST_PLANE:
            names = {
                mid: md.name[len(SPAN_PREFIX):]
                for mid, md in plane.event_metadata.items()
                if md.name.startswith(SPAN_PREFIX)
            }
            stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
            for line in plane.lines:
                t0 = line.timestamp_ns
                host += [
                    (names[e.metadata_id],
                     t0 + e.offset_ps * 1e-3, e.duration_ps * 1e-3,
                     {stat_names.get(s.metadata_id, str(s.metadata_id)):
                      _stat_value(plane, s) for s in e.stats})
                    for e in line.events if e.metadata_id in names
                ]
    return {"devices": devices, "modules": modules,
            "host": sorted(host, key=lambda e: e[1])}


# --- the reduction -----------------------------------------------------------


def scope_of(tf_op: str) -> str | None:
    """The INNERMOST ``tpudes.*`` component of a ``tf_op`` path, None
    where it has none.  A component counts when it STARTS with
    ``tpudes.``: ``vmap(tpudes.lte_sm.lane)``, jax's wrapping of the
    first scope under a ``vmap``, reads under the scope around it."""
    for part in reversed(tf_op.split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part.split(":", 1)[0]
    return None


def scopes_in(text: str) -> set[str]:
    """Every ``tpudes.*`` scope a lowered program's text names."""
    return set(re.findall(r"(?<![\w.(])tpudes\.[A-Za-z0-9_.]*[A-Za-z0-9_]", text))


def steps(tf_op: str) -> bool:
    """Whether a ``tf_op`` path runs inside an engine's loop body: one of
    its components is a ``tpudes.*`` scope whose last part is ``step``
    (:func:`~tpudes.parallel.runtime.scoped_while_loop`)."""
    return any(
        part.startswith(SCOPE_PREFIX) and part.split(":", 1)[0].endswith(".step")
        for part in tf_op.split("/")
    )


def program_name(module_event: str) -> str:
    """``jit_tpudes_bss_advance(1234)`` -> ``jit_tpudes_bss_advance``."""
    return re.sub(r"\(\d+\)$", "", module_event)


class _Loop:
    """One outermost ``while`` of a device plane, filled by :func:`_walk`:
    ``ops[name] = [self ns, events, tf_op, direct child]``."""

    __slots__ = ("name", "tf_op", "start", "end", "own", "ops")

    def __init__(self, name, tf_op, start, end):
        self.name, self.tf_op, self.start, self.end = name, tf_op, start, end
        self.own = 0.0
        self.ops: dict[str, list] = {}

    def iterations(self) -> int | None:
        """The modal number of events an operation has that is a direct
        child of the loop: the body's operations run once an iteration,
        those of a conditional's branch fewer times, those of an inner
        loop more often, and they are children of THAT operation."""
        counts: dict[int, int] = {}
        for _, n, _, direct in self.ops.values():
            if direct:
                counts[n] = counts.get(n, 0) + 1
        if not counts:
            return None
        return max(counts.items(), key=lambda kv: (kv[1], kv[0]))[0]


def _walk(ops: list):
    """One pass over a plane's ``XLA Ops`` events, which nest (a
    ``while`` spans its body's operations): ``(outer, loops)``, the
    depth-0 operations as ``(start, end, name, tf_op)`` and every outermost
    ``while`` as a :class:`_Loop` (a ``while`` inside one counts in it,
    as an operation).  An operation's self time is its duration less
    its children's, so a loop's parts sum to its duration."""
    if any(a[2] > b[2] for a, b in itertools.pairwise(ops)):
        ops = sorted(ops, key=lambda e: (e[2], -e[3]))
    outer, loops = [], []
    stack = []        # [end, children ns, name, tf_op, start]
    loop, loop_depth = None, 0

    def close():
        nonlocal loop
        end, children, name, tf_op, start = stack.pop()
        self_ns = max(end - start - children, 0.0)
        if loop is None:
            return
        depth = len(stack)
        if depth == loop_depth:
            loop.own = self_ns
            loop = None
            return
        row = loop.ops.get(name)
        if row is None:
            loop.ops[name] = [self_ns, 1, tf_op, depth == loop_depth + 1]
        else:
            row[0] += self_ns
            row[1] += 1

    for name, tf_op, start, duration in ops:
        while stack and stack[-1][0] <= start:
            close()
        end = start + duration
        if stack:
            parent = stack[-1]
            if end > parent[0]:
                end = parent[0]
            parent[1] += end - start
        else:
            outer.append((start, end, name, tf_op))
        if loop is None and name.startswith("while"):
            loop, loop_depth = _Loop(name, tf_op, start, end), len(stack)
            loops.append(loop)
        stack.append([end, 0.0, name, tf_op, start])
    while stack:
        close()
    return outer, loops


def _split(gap, spans) -> dict[str, float]:
    """One idle gap ``(start, end)`` split by overlap over the
    innermost (shortest) of ``spans`` open at each moment of it; what
    no span covers goes to :data:`OUTSIDE`."""
    g0, g1 = gap
    inside = [(s, s + d, name) for name, s, d, _ in spans if s < g1 and s + d > g0]
    edges = sorted({g0, g1, *(
        t for s, e, _ in inside for t in (s, e) if g0 < t < g1
    )})
    shares: dict[str, float] = {}
    for a, b in zip(edges, edges[1:]):
        covering = [(e - s, name) for s, e, name in inside if s <= a and b <= e]
        owner = min(covering)[1] if covering else OUTSIDE
        shares[owner] = shares.get(owner, 0.0) + (b - a)
    return shares


class _Withheld(Exception):
    """Why the trace at hand gives no number (:func:`reduce` reports it)."""


def _mean(rows: list[dict]) -> dict:
    """Key by key mean over the device planes (a key one lacks counts 0)."""
    keys = sorted({k for row in rows for k in row})
    return {k: sum(row.get(k, 0.0) for row in rows) / len(rows) for k in keys}


def _median(rows: list[dict]) -> dict:
    """Key by key median over the launches (a key one lacks counts 0):
    one launch that the host's scheduler held up does not move it."""
    keys = sorted({k for row in rows for k in row})
    return {k: statistics.median(row.get(k, 0.0) for row in rows) for k in keys}


def _plane(plane: str, ops: list, runs: list, starts: list):
    """One device plane's numbers.  ``runs`` are its program runs as
    ``(program, start, end)``, ``starts`` the launches' starts."""
    outer, loops = _walk(ops)
    outer = [o for o in outer if o[0] >= starts[0]]
    loops = [lp for lp in loops if lp.start >= starts[0]]
    # a program may run other outermost whiles beside its engine's loop
    # (a shortest-path scan before it, a delay sum after it): where one
    # holds the loop body's scope, that one is the loop, and the others
    # are device time outside it
    stepped = [lp for lp in loops
               if any(steps(row[2]) for row in lp.ops.values())]
    loops = stepped or loops
    if runs:
        # an outermost operation runs once a program run: more often,
        # and a loop's event was lost, its body reading as outermost
        often: dict[str, int] = {}
        for _, _, name, _ in outer:
            often[name] = often.get(name, 0) + 1
        name, n = max(often.items(), key=lambda kv: kv[1], default=("", 0))
        if n > len(runs):
            raise _Withheld(
                f"the trace is cut: outermost operation {name} has {n} "
                f"events on {plane} where {len(runs)} programs ran"
            )

    def launch_of(t):
        return bisect.bisect_right(starts, t) - 1

    with_loop = {launch_of(lp.start) for lp in loops}
    for i in range(len(starts)):
        if i not in with_loop:
            raise _Withheld(
                f"the trace is cut: launch {i + 1} of {len(starts)} has no "
                f"loop on {plane}"
            )
    iterations = n_events = 0
    parts = {"own": 0.0, "copies": 0.0, "unscoped": 0.0}
    names: dict[str, set] = {}
    by_op: dict[tuple, float] = {}
    for lp in loops:
        n = lp.iterations()
        if not n:
            raise _Withheld(f"{lp.name} on {plane} holds no operation")
        iterations += n
        parts["own"] += lp.own
        for name, (self_ns, count, tf_op, _) in lp.ops.items():
            scope = scope_of(tf_op)
            part = scope or ("copies" if name.startswith("copy") else "unscoped")
            parts[part] = parts.get(part, 0.0) + self_ns
            names.setdefault(part, set()).add(name)
            by_op[name, scope] = by_op.get((name, scope), 0.0) + self_ns
            n_events += count

    def program_at(t):
        return next((p for p, a, b in runs if a <= t < b), NO_PROGRAM)

    outside: list[dict] = [{} for _ in starts]
    by_scope: list[dict] = [{} for _ in starts]
    for s, e, _, tf_op in outer:
        for rows, key in ((outside, program_at(s)),
                          (by_scope, scope_of(tf_op) or NO_SCOPE)):
            row = rows[launch_of(s)]
            row[key] = row.get(key, 0.0) + (e - s)
    for lp in loops:
        outside[launch_of(lp.start)][program_at(lp.start)] -= lp.end - lp.start
        by_scope[launch_of(lp.start)][scope_of(lp.tf_op) or NO_SCOPE] -= (
            lp.end - lp.start)
    return dict(
        outer=outer, names=names, by_op=by_op, iterations=iterations,
        parts_us={k: v / iterations * 1e-3 for k, v in parts.items()},
        loop=dict(
            step_us=sum(lp.end - lp.start for lp in loops) / iterations * 1e-3,
            iterations=iterations / len(starts),
            events_per_step=n_events / iterations,
        ),
        outside_ms={k: v * 1e-6 for k, v in _median(outside).items()},
        outside_scopes_ms={k: v * 1e-6 for k, v in _median(by_scope).items()},
    )


def reduce(events: dict) -> dict:
    """One table from :func:`load`'s lists (a :class:`session` adds
    ``lowered``, the scopes its launches' advance programs name in
    their lowered text, ``launch_args``, ``runtime``, ``max_iterations``,
    ``warm_up`` and ``foreign``).  Launches are the ``tpudes:launch``
    events, in sequence, less the first ``warm_up`` of them: a launch
    owns the trace from its start to the next one's.

    - ``loop``: of the outermost ``while`` loops (those whose operations
      run under a loop body's ``tpudes.*.step`` scope, where one does:
      :func:`steps`), per iteration, in us,
      over every launch's iterations and averaged over the device
      planes: ``step_us``; ``own_us`` (the ``while``'s duration less its
      children's); ``scopes[scope] = {"us", "ops"}``, self time and
      distinct operations under each innermost ``tpudes.*`` scope;
      ``copies_us`` (no scope, name starts with ``copy``);
      ``unscoped_us`` (the rest); these sum to ``step_us``.  Also
      ``iterations`` (a launch, counted from the trace),
      ``events_per_step``, ``top`` (the :data:`TOP` largest operations
      as ``[name, scope, us]``) and ``no_event`` (scopes of the lowered
      text on no device event: a scalar condition, a draw fused into
      its consumer; None without the lowered text);
    - a launch, each number the MEDIAN over the launches:
      ``outside_loop_ms[program]``, device busy time outside the loop;
      ``outside_scopes_ms[scope]``, the same time by the innermost
      ``tpudes.*`` scope of each operation outside the loop (a ``while``
      there whole, by its own scope), :data:`NO_SCOPE` for none;
      ``idle_ms[span]``, the first device's idle time, each gap split
      over the innermost ``tpudes:`` span open on the host;
      ``wall_ms``, ``busy_ms``, a launch's share of the trace and the
      first device's busy time in it; ``idle_each_ms``, every launch's
      idle time, so that the scatter behind the medians shows;
    - ``launch``: the last launch's span arguments and the runtime's
      ``init_programs``, ``hits``, ``misses``; ``shortened_to`` where
      a session stopped the loops early, ``warm_up`` where it left
      launches out.

    ``withheld`` is None, or says why every number above is None: the
    trace is cut (an outermost operation more often than programs ran,
    a launch without a loop), its names are stale (a scope on a
    device event that the tree's own lowered program does not name: the
    persistent compile cache served another tree's executable), or a
    launch of another thread ran in the session's window."""
    host = events.get("host") or []
    warm_up = int(events.get("warm_up") or 0)
    launches = sorted(
        (e for e in host if e[0] == "launch"), key=lambda e: e[1]
    )[warm_up:]
    devices = events.get("devices") or {}
    launch = dict(launches[-1][3]) if launches else {}
    launch.update((events.get("launch_args") or [{}])[-1])
    runtime = events.get("runtime") or {}
    launch.update(
        (k, runtime[k]) for k in ("init_programs", "hits", "misses") if k in runtime
    )
    if events.get("max_iterations") is not None:
        launch["shortened_to"] = int(events["max_iterations"])
    if warm_up:
        launch["warm_up"] = warm_up
    table = dict(
        withheld=None, loop=None, outside_loop_ms=None,
        outside_scopes_ms=None, idle_ms=None,
        idle_each_ms=None, wall_ms=None, busy_ms=None, launch=launch,
        launches=len(launches), devices=len(devices), took_s=None,
    )
    try:
        _fill(table, events, launches)
    except _Withheld as why:
        table.update(withheld=str(why))
    return table


def _fill(table: dict, events: dict, launches: list) -> None:
    """:func:`reduce`'s numbers into ``table``, or :class:`_Withheld`
    before the first of them."""
    host, devices = events.get("host") or [], events.get("devices") or {}
    if events.get("foreign"):
        raise _Withheld(
            f"{events['foreign']} launches of other threads ran, whole, in the "
            "session's window: the table would mix them with its own"
        )
    if not launches:
        raise _Withheld("no tpudes:launch event on the host plane")
    if not any(devices.values()):
        raise _Withheld("no /device:TPU plane with XLA Ops events")
    starts = [e[1] for e in launches]
    planes = [
        _plane(plane, devices[plane], [
            (program_name(name), s, s + d)
            for name, s, d in (events.get("modules") or {}).get(plane, ())
            if s >= starts[0]
        ], starts)
        for plane in sorted(devices)
    ]
    seen = {k for p in planes for k in p["parts_us"] if k.startswith(SCOPE_PREFIX)}
    outside = {k for p in planes for k in p["outside_scopes_ms"]} - {NO_SCOPE}
    lowered = events.get("lowered")
    known = None if lowered is None else set().union(*lowered.values())
    if known is not None and not seen | outside <= known:
        raise _Withheld(
            "the names are stale: device events carry "
            f"{sorted((seen | outside) - known)}, "
            "which this tree's lowered program does not name (another tree's "
            "executable, from the persistent compile cache?)"
        )

    parts = _mean([p["parts_us"] for p in planes])
    by_op: dict[tuple, float] = {}
    for p in planes:
        for key, ns in p["by_op"].items():
            by_op[key] = by_op.get(key, 0.0) + ns
    iterations = sum(p["iterations"] for p in planes)
    table["loop"] = dict(
        _mean([p["loop"] for p in planes]),
        own_us=parts["own"], copies_us=parts["copies"],
        unscoped_us=parts["unscoped"],
        scopes={
            k: {"us": parts[k], "ops": max(len(p["names"].get(k, ())) for p in planes)}
            for k in sorted(seen)
        },
        top=[
            [name, scope, ns / iterations * 1e-3]
            for (name, scope), ns in sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
        ],
        no_event=None if known is None else sorted(known - seen - outside),
    )
    table["outside_loop_ms"] = _mean([p["outside_ms"] for p in planes])
    table["outside_scopes_ms"] = _mean([p["outside_scopes_ms"] for p in planes])

    # the first device's gaps, launch by launch
    t_end = max(
        [s + d for _, s, d, _ in host]
        + [ops[-1][2] + ops[-1][3] for ops in devices.values() if ops]
    )
    idle, busy, wall = [], [], []
    for r0, r1 in zip(starts, starts[1:] + [t_end]):
        inside = sorted((s, e) for s, e, _, _ in planes[0]["outer"] if r0 <= s < r1)
        busy.append(sum(e - s for s, e in inside))
        wall.append(r1 - r0)
        edges = [r0] + [t for s, e in inside for t in (s, min(e, r1))] + [r1]
        row: dict[str, float] = {}
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                for name, ns in _split((g0, g1), host).items():
                    row[name] = row.get(name, 0.0) + ns
        idle.append(row)
    table["idle_ms"] = {k: v * 1e-6 for k, v in _median(idle).items()}
    table["idle_each_ms"] = [sum(row.values()) * 1e-6 for row in idle]
    table["wall_ms"] = statistics.median(wall) * 1e-6
    table["busy_ms"] = statistics.median(busy) * 1e-6


def format_table(table: dict) -> str:
    """The table as the lines ``python -m tpudes.obs --explain`` prints."""
    out = [f"launches {table['launches']}  devices {table['devices']}  "
           f"launch {table['launch']}"]
    if table.get("took_s"):
        out.append("the reading took (s): " + "  ".join(
            f"{k} {v:.2f}" for k, v in table["took_s"].items()))
    if table["withheld"]:
        return "\n".join(out + [f"WITHHELD: {table['withheld']}"])
    loop = table["loop"]
    out.append(
        f"a launch: {table['wall_ms']:.3f} ms of the trace, first device "
        f"busy {table['busy_ms']:.3f} ms; {loop['iterations']:.0f} "
        f"iterations of {loop['step_us']:.3f} us, "
        f"{loop['events_per_step']:.1f} events each"
    )
    rows = [("the while's own", loop["own_us"], "")]
    rows += [(k, v["us"], f"{v['ops']} operations")
             for k, v in loop["scopes"].items()]
    rows += [("copies (no scope)", loop["copies_us"], ""),
             ("other (no scope)", loop["unscoped_us"], "")]
    out += [f"  {name:<28}{us:10.3f} us  {note}" for name, us, note in rows]
    if loop["no_event"]:
        out.append(f"  in the lowered text, on no event: {loop['no_event']}")
    out.append("largest operations of a step (us):")
    out += [f"  {name:<44}{us:8.3f}  {scope or '-'}"
            for name, scope, us in loop["top"]]
    out.append("device busy outside the loop, a launch (ms):")
    out += [f"  {k:<44}{v:8.3f}" for k, v in table["outside_loop_ms"].items()]
    out.append("the same time by scope (ms):")
    out += [f"  {k:<44}{v:8.3f}" for k, v in table["outside_scopes_ms"].items()]
    out.append("first device idle, a launch, by the host's span (ms; medians "
               "over launches that idled " + " ".join(
                   f"{v:.3f}" for v in table["idle_each_ms"]) + "):")
    out += [f"  {k:<44}{v:8.3f}" for k, v in table["idle_ms"].items()]
    return "\n".join(out)


# --- how a trace comes to be -------------------------------------------------


class _Probed(Exception):
    """Stops an engine's ``call`` once :func:`_lowered_scopes` has what
    it was handed."""


def _lowered_scopes(fn, call, carry, bound, ops) -> set[str]:
    """The scopes in the lowered text of an advance program, lowered
    the way its engine calls it (``call`` knows the argument order; the
    carry is shapes only, the launch donated the arrays)."""
    got = []

    def probe(*args):
        got.append(scopes_in(fn.lower(*args).as_text(debug_info=True)))
        raise _Probed

    try:
        call(probe, carry, bound, ops)
    except _Probed:
        pass
    return got[0]


class session:
    """``with explain.session() as s: ...`` traces
    the launches of the block in a ``jax.profiler`` window of its own
    (Python tracer off, a temporary directory removed on exit) and
    sets ``s.table = reduce(load(dir))`` on exit.  While it is open
    every launch of the thread that opened it stops its loop at
    ``max_iterations``
    (:meth:`~tpudes.parallel.runtime.Launch.drive` hands the advance
    program its bound as a traced operand, so the clip costs no
    compile and serves every engine): two 24 038-slot launches at 200
    events a slot overflow the profiler's 6.29 M events, and every
    event costs the profiler's stop about 30 us (:data:`MAX_ITERATIONS`).
    The table says that its launches were shortened
    (``launch["shortened_to"]``) and what the reading cost
    (``took_s``); ``max_iterations=None`` leaves the launches whole.
    The first ``warm_up`` launches of the block are traced and not read.

    A shortened launch's RESULT is that of the shortened run and
    carries no mark of it, which is why the clip is bound to the
    thread: a launch of another thread (a server's) that falls into the
    window runs whole, and the table is then withheld, because the
    trace holds that launch too."""

    def __init__(self, max_iterations: int | None = MAX_ITERATIONS,
                 warm_up: int = 0):
        self.max_iterations = max_iterations
        self.warm_up = int(warm_up)
        self.table = None
        self.foreign = 0
        self._thread = None
        self._programs: dict[int, tuple] = {}
        self._dir = None
        self._opened = None

    def __enter__(self) -> "session":
        import tempfile
        import time

        import jax

        from tpudes.parallel.runtime import RUNTIME

        if RUNTIME.explain is not None:
            raise RuntimeError("an explain session is already open")
        self._dir = tempfile.mkdtemp(prefix="tpudes-explain-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        try:
            jax.profiler.start_trace(self._dir, profiler_options=options)
        except RuntimeError as e:
            self._remove()
            raise RuntimeError(
                "tpudes.obs.explain: a jax.profiler trace is already "
                "running in this process; stop it, or read what it wrote "
                "with explain.reduce(explain.load(<its directory>))"
            ) from e
        self._opened = time.perf_counter()
        self._thread = threading.get_ident()
        RUNTIME.explain = self
        return self

    def shorten(self, launch, call, bounds) -> list[int]:
        """From ``Launch.drive``: the segment ends clipped to
        ``max_iterations``, and (once a program) what the exit needs to
        lower ``launch.fn`` again: the carry as shapes, the operands.
        Another thread's launch keeps its bounds and is counted."""
        bounds = list(bounds)
        if threading.get_ident() != self._thread:
            self.foreign += 1
            return bounds
        if id(launch.fn) not in self._programs:
            import jax

            shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=x.sharding
                ) if isinstance(x, jax.Array) else x,
                launch.carry,
            )
            self._programs[id(launch.fn)] = (
                launch.engine, launch.fn, call, shapes, bounds[-1], launch.ops
            )
        if self.max_iterations is None:
            return bounds
        clipped: list[int] = []
        for bound in bounds:
            bound = min(int(bound), int(self.max_iterations))
            if not clipped or bound > clipped[-1]:
                clipped.append(bound)
        return clipped

    def _remove(self) -> None:
        import shutil

        shutil.rmtree(self._dir, ignore_errors=True)

    def __exit__(self, exc_type, exc, tb) -> None:
        import time

        import jax
        import numpy as np

        from tpudes.obs import spans
        from tpudes.parallel.runtime import RUNTIME

        RUNTIME.explain = None
        # what the reading cost: the block itself, then each step here
        marks = [self._opened, time.perf_counter()]
        try:
            jax.profiler.stop_trace()
            marks.append(time.perf_counter())
            if exc_type is not None:
                return
            events = load(self._dir)
            marks.append(time.perf_counter())
            events["lowered"] = {
                f"tpudes_{engine}_advance": _lowered_scopes(
                    fn, call, shapes, np.int32(bound), ops
                )
                for engine, fn, call, shapes, bound, ops
                in self._programs.values()
            } or None
            marks.append(time.perf_counter())
            events["launch_args"] = [
                dict(sp.args) for sp in spans.snapshot()
                if sp.name == "launch" and sp.start >= self._opened
            ]
            events["runtime"] = RUNTIME.stats()
            events["max_iterations"] = self.max_iterations
            events["warm_up"] = self.warm_up
            events["foreign"] = self.foreign
            self.table = reduce(events)
            marks.append(time.perf_counter())
            self.table["took_s"] = dict(zip(
                ("block", "stop_trace", "load", "lower", "reduce"),
                (b - a for a, b in zip(marks, marks[1:])),
            ))
        finally:
            self._programs.clear()
            self._remove()


def replay(max_iterations: int | None = MAX_ITERATIONS):
    """Run the process's LAST ``run_lifted`` launch again
    :data:`LAUNCHES` times, blocking, inside a :class:`session` that
    does not read the first :data:`WARM_UP` of them, and return its
    table; None where nothing was launched.  The launches get the key,
    mesh and engine arguments the last one had (but never
    ``block=False``, and no checkpoint: a replay persists nothing)."""
    from tpudes.parallel.lift import run_lifted
    from tpudes.parallel.runtime import RUNTIME

    last = RUNTIME.last_lifted
    if last is None:
        return None
    kind, prog, replicas, key, mesh, engine_kwargs = last
    kwargs = {
        k: v for k, v in engine_kwargs.items()
        if k not in ("block", "checkpoint")
    }
    with session(max_iterations, warm_up=WARM_UP) as s:
        for _ in range(LAUNCHES):
            run_lifted(kind, prog, replicas, key, mesh, **kwargs)
    # the replays are not the caller's last launch
    RUNTIME.last_lifted = last
    return s.table
