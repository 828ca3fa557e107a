"""Abstract-tracing utilities for the jaxpr passes.

Everything here is ``jax.make_jaxpr`` only — no ``jax.jit``, no
compile, no device execution — so the ``--jaxpr`` gate is CPU-safe and
costs trace time (tens of milliseconds per tiny-shape entry), not
XLA compile time.
"""

from __future__ import annotations

import hashlib


def trace_entry(entry):
    """``jax.make_jaxpr(entry.fn)(*entry.args)`` → ClosedJaxpr."""
    import jax

    return jax.make_jaxpr(entry.fn)(*entry.args)


def trace_entries_x64(build):
    """Build a variant's entries AND trace them inside an
    ``enable_x64`` context.  Rebuilding inside the context matters:
    build-time constants (``jnp.asarray`` of host f64 tables) only
    reveal an unpinned dtype when the builder itself runs under x64
    semantics — tracing pre-built f32 arrays would hide them."""
    import jax  # noqa: F401  (jax must import before the context)
    from jax import enable_x64

    out = []
    with enable_x64(True):
        for entry in build():
            out.append((entry, trace_entry(entry)))
    return out


def _sub_jaxprs(value):
    """Jaxprs nested anywhere in one eqn-param value (while/cond/scan
    bodies, pjit, custom_* rules, pallas_call kernels — any primitive
    that closes over sub-jaxprs, present or future)."""
    from jax.extend import core

    out = []
    stack = [value]
    while stack:
        v = stack.pop()
        if isinstance(v, core.ClosedJaxpr):
            out.append(v.jaxpr)
        elif isinstance(v, core.Jaxpr):
            out.append(v)
        elif isinstance(v, (list, tuple)):
            stack.extend(v)
        elif isinstance(v, dict):
            stack.extend(v.values())
    return out


def walk_eqns(jaxpr):
    """Yield every eqn in ``jaxpr`` and all nested sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in _sub_jaxprs(p):
                yield from walk_eqns(sub)


def primitive_names(closed_jaxpr) -> set:
    return {eqn.primitive.name for eqn in walk_eqns(closed_jaxpr.jaxpr)}


def f64_primitives(closed_jaxpr) -> set:
    """Primitive names (plus the pseudo-name ``const``) producing a
    float64 value anywhere in the trace — under an x64 trace of
    explicitly-f32 operands, every one is a creation site that did not
    pin its dtype (the silent-f64-promotion contract)."""
    import numpy as np

    out = set()
    for eqn in walk_eqns(closed_jaxpr.jaxpr):
        for v in eqn.outvars:
            dtype = getattr(getattr(v, "aval", None), "dtype", None)
            if dtype is not None and str(dtype) == "float64":
                out.add(eqn.primitive.name)
    for c in closed_jaxpr.consts:
        if getattr(np.asarray(c), "dtype", None) == np.float64:
            out.add("const")
    return out


#: reductions whose accumulator dtype IS their output dtype — a bf16
#: output means a bf16 accumulator, which the PR 6 precision policy
#: forbids (compute low, ACCUMULATE f32).  Max/min reductions are
#: exact at any width and stay exempt.
ACCUMULATING_PRIMS = frozenset(
    {"reduce_sum", "reduce_prod", "cumsum", "cumprod", "dot_general",
     "conv_general_dilated", "reduce_window_sum"}
)


def bf16_accumulators(closed_jaxpr) -> set:
    """Accumulating primitives whose output is bfloat16."""
    out = set()
    for eqn in walk_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name not in ACCUMULATING_PRIMS:
            continue
        for v in eqn.outvars:
            dtype = getattr(getattr(v, "aval", None), "dtype", None)
            if dtype is not None and str(dtype) == "bfloat16":
                out.add(eqn.primitive.name)
    return out


def large_consts(closed_jaxpr, budget: int):
    """``(shape, dtype, nbytes)`` for closure constants above the byte
    budget — values the builder baked into the program instead of
    passing as runtime operands."""
    import numpy as np

    out = []
    for c in closed_jaxpr.consts:
        arr = np.asarray(c)
        if arr.nbytes > budget:
            out.append((arr.shape, str(arr.dtype), int(arr.nbytes)))
    return out


def arg_leaf_slices(args: tuple):
    """Per-argument ``(start, stop)`` ranges into the flattened invar
    list (make_jaxpr flattens pytree args in order)."""
    import jax

    slices, pos = [], 0
    for a in args:
        n = len(jax.tree_util.tree_leaves(a))
        slices.append((pos, pos + n))
        pos += n
    return slices


def arg_leaf_paths(arg):
    """Human-readable keypath per leaf of one argument pytree."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(arg)
    return [jax.tree_util.keystr(path) for path, _ in flat]


def used_invar_ids(closed_jaxpr) -> set:
    """ids of top-level invars consumed by some eqn or returned.
    Sub-jaxprs bind their own vars, so a top-level scan is complete."""
    used = set()
    for eqn in closed_jaxpr.jaxpr.eqns:
        for v in eqn.invars:
            used.add(id(v))
    for v in closed_jaxpr.jaxpr.outvars:
        used.add(id(v))
    return used


def unused_arg_leaves(entry, closed_jaxpr, argnum: int):
    """Keypaths of ``entry.args[argnum]``'s leaves whose invar is never
    consumed (the value was dead at trace time)."""
    slices = arg_leaf_slices(entry.args)
    start, stop = slices[argnum]
    used = used_invar_ids(closed_jaxpr)
    invars = closed_jaxpr.jaxpr.invars
    paths = arg_leaf_paths(entry.args[argnum])
    return [
        paths[i - start]
        for i in range(start, stop)
        if id(invars[i]) not in used
    ]


def unaliasable_donated_leaves(entry, closed_jaxpr, argnum: int):
    """Keypaths of donated leaves with no shape/dtype-matching output
    leaf: XLA cannot alias them, so the donation frees nothing and the
    runtime warns per call on accelerators."""
    import jax

    slices = arg_leaf_slices(entry.args)
    start, stop = slices[argnum]
    paths = arg_leaf_paths(entry.args[argnum])
    outs = {}
    for v in closed_jaxpr.jaxpr.outvars:
        aval = getattr(v, "aval", None)
        sig = (getattr(aval, "shape", None), str(getattr(aval, "dtype", "")))
        outs[sig] = outs.get(sig, 0) + 1
    missing = []
    leaves = jax.tree_util.tree_leaves(entry.args[argnum])
    for i in range(start, stop):
        leaf = leaves[i - start]
        sig = (
            tuple(getattr(leaf, "shape", ())),
            str(getattr(leaf, "dtype", "")),
        )
        if outs.get(sig, 0) > 0:
            outs[sig] -= 1
        else:
            missing.append(paths[i - start])
    return missing


#: primitives with FLOAT outputs whose gradient is zero (or undefined)
#: almost everywhere — a gradient path running only through these is
#: structurally dead.  Comparison/argmax/int-cast severing needs no
#: listing: their outputs are not floating, so liveness never crosses
#: them (see _diff_walk).
NONDIFF_PRIMS = frozenset(
    {"stop_gradient", "round", "floor", "ceil", "sign",
     "round_nearest_even"}
)


def _is_float_var(v) -> bool:
    import numpy as np

    dtype = getattr(getattr(v, "aval", None), "dtype", None)
    if dtype is None:
        return False
    try:
        return np.issubdtype(dtype, np.inexact)
    except TypeError:  # ml_dtypes (bfloat16) — inexact by definition
        return "float" in str(dtype)


def _diff_walk(jaxpr, live: set) -> None:
    """Propagate differentiable liveness (by var id, in ``live``)
    through one jaxpr's eqns in order.  Liveness crosses an eqn when a
    live FLOAT invar feeds it and the primitive carries gradients:
    non-float outputs (comparisons, argmax, float→int casts) and
    :data:`NONDIFF_PRIMS` sever the path.  Call-like eqns whose single
    sub-jaxpr aligns 1:1 with the invars (pjit/closed_call/remat)
    recurse precisely; other sub-jaxpr carriers (scan/while/cond) are
    treated as differentiable pass-through — conservative: a hard op
    hidden inside a loop body is missed, one outside is not."""
    from jax.extend import core

    for eqn in jaxpr.eqns:
        in_live = any(
            not isinstance(v, core.Literal)
            and id(v) in live
            and _is_float_var(v)
            for v in eqn.invars
        )
        if not in_live:
            continue
        if eqn.primitive.name in NONDIFF_PRIMS:
            continue
        subs = []
        for p in eqn.params.values():
            subs.extend(_sub_jaxprs(p))
        if len(subs) == 1 and len(subs[0].invars) == len(eqn.invars):
            sub = subs[0]
            sub_live = set(live)
            for ev, sv in zip(eqn.invars, sub.invars):
                if (
                    not isinstance(ev, core.Literal)
                    and id(ev) in live
                    and _is_float_var(ev)
                ):
                    sub_live.add(id(sv))
            # scan feeds its carry outputs back into its carry inputs:
            # iterate the body walk to a FIXED POINT, or liveness that
            # only enters the carry on iteration k>0 (the fluid
            # fixed-point relaxation's cap→util→lfrac→lg chain) is
            # missed
            if eqn.primitive.name == "scan":
                nc = int(eqn.params.get("num_consts", 0))
                ncar = int(eqn.params.get("num_carry", 0))
                while True:
                    before = len(sub_live)
                    _diff_walk(sub, sub_live)
                    for ov, iv in zip(
                        sub.outvars[:ncar], sub.invars[nc:nc + ncar]
                    ):
                        if (
                            not isinstance(ov, core.Literal)
                            and id(ov) in sub_live
                        ):
                            sub_live.add(id(iv))
                    if len(sub_live) == before:
                        break
            else:
                _diff_walk(sub, sub_live)
            for ov, sv in zip(eqn.outvars, sub.outvars):
                if (
                    not isinstance(sv, core.Literal)
                    and id(sv) in sub_live
                    and _is_float_var(ov)
                ):
                    live.add(id(ov))
            continue
        for v in eqn.outvars:
            if _is_float_var(v):
                live.add(id(v))


def grad_severed_leaves(entry, closed_jaxpr, argnum: int):
    """Keypaths of ``entry.args[argnum]``'s FLOAT leaves with no
    differentiable path to any float output of the trace — their
    ``jax.grad`` is structurally zero (a hard op severs every path),
    the JXL006 finding."""
    jaxpr = closed_jaxpr.jaxpr
    slices = arg_leaf_slices(entry.args)
    start, stop = slices[argnum]
    invars = jaxpr.invars
    paths = arg_leaf_paths(entry.args[argnum])
    out = []
    for i in range(start, stop):
        root = invars[i]
        if not _is_float_var(root):
            continue  # integer operands carry no gradient by design
        live = {id(root)}
        _diff_walk(jaxpr, live)
        if not any(
            id(v) in live and _is_float_var(v) for v in jaxpr.outvars
        ):
            out.append(paths[i - start])
    return out


def fingerprint(closed_jaxpr) -> str:
    """Canonical identity of a traced program: the pretty-printed jaxpr
    (var names are assigned deterministically in traversal order, so
    structurally identical traces print identically) plus a digest of
    every constant's bytes.  Two builds with equal fingerprints compile
    to the same executable — the JXL004 comparison."""
    import numpy as np

    h = hashlib.sha256(str(closed_jaxpr.jaxpr).encode())
    for c in closed_jaxpr.consts:
        arr = np.asarray(c)
        h.update(str((arr.shape, str(arr.dtype))).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def variant_fingerprints(entries) -> dict:
    """``{entry_name: fingerprint}`` for a built entry list."""
    return {e.name: fingerprint(trace_entry(e)) for e in entries}
