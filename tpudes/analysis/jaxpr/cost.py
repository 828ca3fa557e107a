"""Scale-complexity cost model over abstract traces.

Everything here operates on ``jax.make_jaxpr`` output only — shapes
and dtypes, never values, never a compile — so re-tracing a manifest
entry at a handful of scale-axis points costs trace time (tens of
milliseconds each), and the growth-exponent fits in JXL007 and the
``--cost`` report are CPU-safe in CI.

Three metrics per trace:

- :func:`total_buffer_bytes` — every buffer the trace materialises
  (consts, inputs, all eqn outputs, nested sub-jaxprs included).
- :func:`peak_live_bytes` — a linear-scan liveness walk: inputs and
  consts live for the whole program (donation is not modelled, so this
  is an upper bound on the working set), each eqn output from its
  birth to its last use, and a call-like eqn (scan/while/pjit body)
  contributes its body's internal peak at the call site.  This is the
  abstract analogue of XLA's ``memory_analysis().temp_size_in_bytes``
  and is cross-checked against it in the test-suite.
- :func:`widest_buffer_bytes` — the single largest buffer any eqn
  materialises.  This is the sharpest scale signal: additive
  lower-order terms make a peak-live log-log fit of an O(axis^2)
  kernel converge to 2 strictly from below, while the dominant dense
  table itself grows at exactly its true exponent (and it is the
  buffer a sparse rewrite must eliminate — no rematerialization
  schedule shrinks a single table).
- :func:`flop_estimate` — FLOP-weighted op count (dot_general at
  2·M·N·K, transcendentals at 8/element, reductions at input size,
  everything else at output size; scan bodies multiplied by trip
  count, while bodies counted once — trip counts are not abstract).

The JXL007 *memory exponent* of an axis is the max of the peak-live
and widest-buffer fits.

:func:`fit_exponent` turns per-axis metric series into log-log growth
exponents, and :func:`scale_report` assembles the full ``--cost``
report with 10^5/10^6-node projections for node-like axes — the
ROADMAP-item-2 worklist generator.
"""

from __future__ import annotations

import math

#: fitted-exponent grace over the declared budget before JXL007
#: fires: log-log fits at tiny trace shapes wobble by O(0.1) from the
#: constant and lower-order terms, so a linear-by-design kernel can
#: fit at 1.1–1.2 on a budget of 1.0 without being a finding
FIT_TOLERANCE = 0.25

#: node counts the ``--cost`` report projects device bytes at — the
#: ROADMAP item-2 scale targets
PROJECTION_NODES = (10**5, 10**6)


def _dtype_itemsize(dtype) -> int:
    import numpy as np

    try:
        return int(np.dtype(dtype).itemsize)
    except TypeError:  # exotic extended dtypes — 4 is the engine norm
        return int(getattr(dtype, "itemsize", 4))


def aval_bytes(v) -> int:
    """Byte size of one var's abstract value (0 for non-array avals,
    e.g. tokens)."""
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    size = 1
    for d in shape:
        size *= int(d)
    return size * _dtype_itemsize(dtype)


def _vars_size(vs) -> int:
    total = 0
    for v in vs:
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", None)
        if shape is None:
            continue
        sz = 1
        for d in shape:
            sz *= int(d)
        total += sz
    return total


def _const_bytes(closed_jaxpr) -> int:
    import numpy as np

    return sum(int(np.asarray(c).nbytes) for c in closed_jaxpr.consts)


def _eqn_sub_jaxprs(eqn):
    from .trace import _sub_jaxprs

    subs = []
    for p in eqn.params.values():
        subs.extend(_sub_jaxprs(p))
    return subs


def total_buffer_bytes(closed_jaxpr) -> int:
    """Sum of every buffer the trace materialises: consts, top-level
    inputs, and all eqn outputs including nested sub-jaxprs (bodies
    counted once, unweighted by trip count — this is a *shape-growth*
    metric, not a bandwidth model)."""
    from .trace import walk_eqns

    total = _const_bytes(closed_jaxpr)
    total += sum(aval_bytes(v) for v in closed_jaxpr.jaxpr.invars)
    for eqn in walk_eqns(closed_jaxpr.jaxpr):
        total += sum(aval_bytes(v) for v in eqn.outvars)
    return total


def _internal_peak(jaxpr) -> int:
    """Peak bytes of buffers BORN inside this jaxpr.  Its inputs are
    bound to buffers the caller already counts, so only eqn outputs
    (and, recursively, sub-jaxpr internals at their call eqn) enter
    the live set.  A var is live from its defining eqn to its last
    use; outputs that escape the jaxpr stay live to the end."""
    from jax.extend import core

    n = len(jaxpr.eqns)
    if n == 0:
        return 0
    last_use = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, core.Literal):
                last_use[id(v)] = i
    escapes = set()
    for v in jaxpr.outvars:
        if not isinstance(v, core.Literal):
            escapes.add(id(v))
    live = 0
    peak = 0
    dead_at = [[] for _ in range(n)]
    for i, eqn in enumerate(jaxpr.eqns):
        born = 0
        for v in eqn.outvars:
            b = aval_bytes(v)
            born += b
            if id(v) not in escapes:
                # last use is >= the birth index, so the death list we
                # append to has not been processed yet (unused values
                # die at their own eqn)
                dead_at[last_use.get(id(v), i)].append(b)
        inner = sum(_internal_peak(s) for s in _eqn_sub_jaxprs(eqn))
        live += born
        if live + inner > peak:
            peak = live + inner
        for b in dead_at[i]:
            live -= b
    return peak


def peak_live_bytes(closed_jaxpr) -> int:
    """Linear-scan liveness peak over the whole trace, in bytes:
    consts and inputs held for the full program (no donation
    modelling — an upper bound), plus the internal peak of the eqn
    graph (:func:`_internal_peak`)."""
    base = _const_bytes(closed_jaxpr)
    base += sum(aval_bytes(v) for v in closed_jaxpr.jaxpr.invars)
    return base + _internal_peak(closed_jaxpr.jaxpr)


def widest_buffer_bytes(closed_jaxpr) -> int:
    """Byte size of the single largest buffer any eqn (nested
    included) materialises — the tile/HBM pressure metric, and the
    cleanest growth-exponent signal (see module docstring)."""
    from .trace import walk_eqns

    best = max(
        (aval_bytes(v) for v in closed_jaxpr.jaxpr.invars), default=0
    )
    for eqn in walk_eqns(closed_jaxpr.jaxpr):
        for v in eqn.outvars:
            b = aval_bytes(v)
            if b > best:
                best = b
    return best


#: transcendental/special-function primitives costed above one flop
#: per element
_EXPENSIVE_ELEMENTWISE = frozenset(
    {"exp", "exp2", "expm1", "log", "log1p", "log2", "sin", "cos",
     "tan", "asin", "acos", "atan", "atan2", "sinh", "cosh", "tanh",
     "erf", "erfc", "erf_inv", "logistic", "pow", "integer_pow",
     "sqrt", "rsqrt", "cbrt", "digamma", "lgamma"}
)


def _jaxpr_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        subs = _eqn_sub_jaxprs(eqn)
        if subs:
            inner = sum(_jaxpr_flops(s) for s in subs)
            if name == "scan":
                inner *= max(int(eqn.params.get("length", 1)), 1)
            # while trip counts are not abstract: body counted once
            total += inner
            continue
        if name == "dot_general":
            dn = eqn.params.get("dimension_numbers")
            k = 1
            if dn is not None:
                (lhs_contract, _), _ = dn
                lhs_shape = getattr(
                    getattr(eqn.invars[0], "aval", None), "shape", ()
                )
                for d in lhs_contract:
                    k *= int(lhs_shape[d])
            total += 2.0 * k * _vars_size(eqn.outvars)
        elif name == "conv_general_dilated":
            total += 2.0 * _vars_size(eqn.invars)
        elif (
            name.startswith("reduce_")
            or name.startswith("cum")
            or name.startswith("arg")
            or name == "sort"
        ):
            total += _vars_size(eqn.invars)
        elif name in _EXPENSIVE_ELEMENTWISE:
            total += 8.0 * _vars_size(eqn.outvars)
        else:
            total += _vars_size(eqn.outvars)
    return total


def flop_estimate(closed_jaxpr) -> float:
    """FLOP-weighted op count of the trace (see module docstring for
    the per-primitive weights)."""
    return _jaxpr_flops(closed_jaxpr.jaxpr)


def shape_signature(closed_jaxpr) -> tuple:
    """(shape, dtype) of every input, output and eqn output in
    traversal order — equal signatures across scale-axis points mean
    the axis does not actually scale the program (the JXL007 dead-axis
    finding)."""
    from .trace import walk_eqns

    sig = []
    for v in list(closed_jaxpr.jaxpr.invars) + list(
        closed_jaxpr.jaxpr.outvars
    ):
        aval = getattr(v, "aval", None)
        sig.append(
            (
                tuple(getattr(aval, "shape", ())),
                str(getattr(aval, "dtype", "")),
            )
        )
    for eqn in walk_eqns(closed_jaxpr.jaxpr):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            sig.append(
                (
                    tuple(getattr(aval, "shape", ())),
                    str(getattr(aval, "dtype", "")),
                )
            )
    return tuple(sig)


def fit_exponent(points, values) -> float:
    """Least-squares slope of log(value) against log(point) — the
    growth exponent k of value ~ point^k.  Zero values clamp to one
    byte/flop to keep the logs finite (constant series fit to 0)."""
    xs = [math.log(float(p)) for p in points]
    ys = [math.log(max(float(v), 1.0)) for v in values]
    n = len(xs)
    mx = sum(xs) / n
    my = sum(ys) / n
    denom = sum((x - mx) ** 2 for x in xs)
    if denom == 0.0:
        return 0.0
    return sum(
        (x - mx) * (y - my) for x, y in zip(xs, ys)
    ) / denom


def project_bytes(points, values, exponent, at_value) -> int:
    """Power-law projection anchored at the largest traced point:
    value(x) = value(p_max) · (x / p_max)^k."""
    p_last = float(points[-1])
    v_last = float(values[-1])
    return int(v_last * (float(at_value) / p_last) ** exponent)


def format_bytes(n) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024.0 or unit == "PiB":
            if unit == "B":
                return f"{int(n)} B"
            return f"{n:.1f} {unit}"
        n /= 1024.0


def axis_metrics(axis) -> dict:
    """Trace ``axis.build`` at every declared point and fit the growth
    exponents.  Returns the per-axis row of the cost report (rounded
    exponents — finding messages built from these must be
    byte-deterministic for the baseline ratchet)."""
    from .trace import trace_entry

    pts, peaks, widests, totals, fls, sigs = [], [], [], [], [], []
    for p in axis.points:
        cj = trace_entry(axis.build(p))
        pts.append(int(p))
        peaks.append(int(peak_live_bytes(cj)))
        widests.append(int(widest_buffer_bytes(cj)))
        totals.append(int(total_buffer_bytes(cj)))
        fls.append(float(_jaxpr_flops(cj.jaxpr)))
        sigs.append(shape_signature(cj))
    dead = all(s == sigs[0] for s in sigs[1:])
    peak_exp = 0.0 if dead else round(fit_exponent(pts, peaks), 4)
    widest_exp = 0.0 if dead else round(fit_exponent(pts, widests), 4)
    mem_exp = max(peak_exp, widest_exp)
    flop_exp = 0.0 if dead else round(fit_exponent(pts, fls), 4)
    row = {
        "axis": axis.name,
        "points": pts,
        "peak_live_bytes": peaks,
        "widest_buffer_bytes": widests,
        "total_buffer_bytes": totals,
        "flops": fls,
        "mem_exponent": mem_exp,
        "peak_exponent": peak_exp,
        "widest_exponent": widest_exp,
        "flop_exponent": flop_exp,
        "mem_budget": float(axis.mem_budget),
        "dead": dead,
        "over_budget": (
            not dead and mem_exp > axis.mem_budget + FIT_TOLERANCE
        ),
    }
    if axis.nodes_per_unit and not dead:
        proj = {}
        for nodes in PROJECTION_NODES:
            x = nodes / float(axis.nodes_per_unit)
            b = project_bytes(pts, peaks, mem_exp, x)
            proj[f"1e{int(round(math.log10(nodes)))}_nodes"] = {
                "bytes": b,
                "human": format_bytes(b),
            }
        row["projected"] = proj
    return row


def scale_report(manifests=None) -> dict:
    """The ``--cost`` report: every declared scale axis of every
    manifest's base-variant entries, traced and fitted, with
    10^5/10^6-node byte projections for node-like axes and the
    over-budget ``worklist`` — the entries ROADMAP item 2 (sparse
    wired graphs) must rewrite before they meet a million-node
    topology."""
    if manifests is None:
        from .manifest import load_manifests

        manifests = load_manifests()
    rows = []
    for man, _line in manifests:
        base = man.variants()[0]
        for entry in base.build():
            for axis in entry.scale_axes:
                row = axis_metrics(axis)
                row = {
                    "engine": man.engine,
                    "path": man.path,
                    "entry": entry.name,
                    **row,
                }
                rows.append(row)
    worklist = sorted(
        f"{r['engine']}/{r['entry']}:{r['axis']}"
        for r in rows
        if r["over_budget"]
    )
    return {
        "version": 1,
        "fit_tolerance": FIT_TOLERANCE,
        "projection_nodes": list(PROJECTION_NODES),
        "entries": rows,
        "worklist": worklist,
    }
