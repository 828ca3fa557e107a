"""The audited sparse-ops allowlist (JXL008).

JXL001's blanket gather/scatter ban protected the wired step kernel
while every engine was dense; ROADMAP item 2 (million-node sparse
wired graphs, CSR adjacency) needs gathers — but *only* gathers whose
index handling is a stated, machine-checked contract.  This module is
that contract surface: every gather / scatter / dynamic-slice site in
the traced engine programs must match a :class:`SparseSite` registered
here, and the registration is verified against the jaxpr itself, not
against comments:

- ``mode`` — the eqn's ``GatherScatterMode`` must be present and equal
  the declared one (``promise_in_bounds`` demands the index provenance
  below actually holds; ``fill_or_drop`` / ``clip`` are self-bounding
  at the cost of a mask/clamp).  ``dynamic_slice`` carries no mode
  param — XLA clamps its start indices, so those sites declare
  ``clip``.
- ``provenance`` — the index operand is walked backward through the
  jaxpr (across pjit/scan/while bodies) to its terminal roots, each
  classified (:data:`PROVENANCE_KINDS`); every root kind found must be
  declared.  A site registered as ``("operand",)`` whose index
  suddenly arrives from an unclamped arithmetic chain or a baked
  const table is a *contradicted contract*, not a pass.
- ``unique_indices`` — scatter sites declare whether the engine
  guarantees non-colliding indices; the eqn param must agree (a
  replace-scatter silently reading ``unique_indices=False`` is a
  nondeterminism hazard on TPU).

The provenance walk is a lint, not a proof: roots bound outside a
sub-jaxpr classify as ``operand`` (their in-bounds guarantee lives in
the engine's program validation — e.g. ``WiredProgram.__post_init__``
rejects ``paths >= n_links`` — and the registration ``note`` names
it), and unrecognised computations classify as ``unknown:<prim>``,
which no site should declare.
"""

from __future__ import annotations

from dataclasses import dataclass
from fnmatch import fnmatch

#: classification vocabulary for index-operand terminal roots
PROVENANCE_KINDS = (
    "operand",    # runtime operand / outer-frame binding (validated
                  # at program-build time; the note says where)
    "const",      # closure constant baked into the trace
    "iota",       # lax.iota — in-bounds by construction when sized
                  # by the indexed axis
    "clamp",      # lax.clamp — explicitly bounded
    "mod",        # lax.rem — bounded by the modulus
    "argreduce",  # argmax/argmin — bounded by the reduced axis size
)


@dataclass(frozen=True)
class SparseSite:
    """One registered sparse-access site.

    ``engine`` is the manifest engine name (exact); ``entry`` is an
    ``fnmatch`` glob over ``variant/entry`` tags; ``primitive`` an
    fnmatch glob over primitive names (``gather``, ``scatter*``,
    ``dynamic_slice``, ``dynamic_update_slice``).  ``mode`` is the
    required GatherScatterMode (lowercase enum name), ``provenance``
    the allowed root kinds, ``unique_indices`` the declared scatter
    uniqueness (None = not asserted, only valid for accumulating
    scatters where collisions are well-defined).  ``note`` names the
    in-bounds argument a human should go read."""

    site: str
    engine: str
    entry: str
    primitive: str
    mode: str
    provenance: tuple
    unique_indices: object = None
    note: str = ""


#: primitives the JXL008 audit covers
SPARSE_PRIMS = (
    "gather",
    "scatter*",
    "dynamic_slice",
    "dynamic_update_slice",
)


def is_sparse_prim(name: str) -> bool:
    return any(fnmatch(name, pat) for pat in SPARSE_PRIMS)


# --- index-provenance walk -------------------------------------------------

#: primitives classified AS a terminal root kind
_TERMINAL = {
    "iota": "iota",
    "clamp": "clamp",
    "rem": "mod",
    "argmax": "argreduce",
    "argmin": "argreduce",
}

#: value-preserving / bounds-preserving computations the walk recurses
#: through to the real roots.  max/min/add/sub/div are recursed (the
#: BOUND argument is typically a literal); anything not listed and not
#: terminal classifies as unknown and fails any contract.
_PASS_THROUGH = frozenset(
    {"add", "sub", "mul", "div", "neg", "max", "min", "abs",
     "floor", "ceil", "round", "sign",
     "broadcast_in_dim", "reshape", "squeeze", "expand_dims",
     "transpose", "rev", "slice", "concatenate", "pad",
     "convert_element_type", "stop_gradient", "copy", "device_put",
     "reduce_max", "reduce_min", "reduce_sum", "cumsum", "sort",
     "and", "or", "xor", "not", "eq", "ne", "lt", "le", "gt", "ge",
     "select_n", "gather", "dynamic_slice", "squeeze"}
)


class _Frame:
    """One jaxpr's def/use context for the provenance walk."""

    __slots__ = ("defs", "bindings", "const_ids")

    def __init__(self, jaxpr, outer_eqn=None, outer_frame=None,
                 const_ids=()):
        from jax.extend import core

        self.defs = {}
        for eqn in jaxpr.eqns:
            for i, v in enumerate(eqn.outvars):
                self.defs[id(v)] = (eqn, i)
        self.bindings = {}
        if (
            outer_eqn is not None
            and outer_frame is not None
            and len(jaxpr.invars) == len(outer_eqn.invars)
        ):
            for sv, ov in zip(jaxpr.invars, outer_eqn.invars):
                if not isinstance(ov, core.Literal):
                    self.bindings[id(sv)] = (ov, outer_frame)
        self.const_ids = set(const_ids)


def _eqn_subs(eqn):
    from .trace import _sub_jaxprs

    subs = []
    for p in eqn.params.values():
        subs.extend(_sub_jaxprs(p))
    return subs


def classify_roots(var, frame) -> set:
    """Terminal-root kinds of the value ``var`` within ``frame``.
    Literal roots are dropped (a literal index is trivially audited by
    shape checking at trace time)."""
    from jax.extend import core

    kinds = set()
    stack = [(var, frame)]
    seen = set()
    while stack:
        v, fr = stack.pop()
        if isinstance(v, core.Literal):
            continue
        key = (id(v), id(fr))
        if key in seen:
            continue
        seen.add(key)
        got = fr.defs.get(id(v))
        if got is None:
            bind = fr.bindings.get(id(v))
            if bind is not None:
                stack.append(bind)
            elif id(v) in fr.const_ids:
                kinds.add("const")
            else:
                kinds.add("operand")
            continue
        eqn, out_idx = got
        name = eqn.primitive.name
        if name in _TERMINAL:
            kinds.add(_TERMINAL[name])
            continue
        subs = _eqn_subs(eqn)
        if subs:
            # call-like eqn (pjit/scan/remat): the value is the
            # corresponding sub-jaxpr output; recurse inside with the
            # invars bound 1:1 when they align
            if len(subs) == 1 and len(subs[0].outvars) == len(
                eqn.outvars
            ):
                sub = subs[0]
                sfr = _Frame(sub, outer_eqn=eqn, outer_frame=fr)
                stack.append((sub.outvars[out_idx], sfr))
            else:
                kinds.add(f"unknown:{name}")
            continue
        if name == "select_n":
            # the predicate (invars[0]) does not flow into the VALUE;
            # only the branches do
            for iv in eqn.invars[1:]:
                stack.append((iv, fr))
            continue
        if name in ("gather", "dynamic_slice"):
            # an index read out of a table: the VALUES come from the
            # table operand (the inner indices are audited at their
            # own site)
            stack.append((eqn.invars[0], fr))
            continue
        if name in _PASS_THROUGH:
            for iv in eqn.invars:
                stack.append((iv, fr))
            continue
        kinds.add(f"unknown:{name}")
    return kinds


def _index_operands(eqn):
    name = eqn.primitive.name
    if name == "gather":
        return eqn.invars[1:2]
    if name.startswith("scatter"):
        return eqn.invars[1:2]
    if name == "dynamic_slice":
        return eqn.invars[1:]
    if name == "dynamic_update_slice":
        return eqn.invars[2:]
    return []


def _eqn_mode(eqn) -> str:
    name = eqn.primitive.name
    if name in ("dynamic_slice", "dynamic_update_slice"):
        return "clip"  # XLA clamps dynamic-slice start indices
    mode = eqn.params.get("mode")
    if mode is None:
        return "unspecified"
    return getattr(mode, "name", str(mode)).lower()


def _collect_sparse_eqns(closed_jaxpr):
    """Every sparse eqn in the trace, paired with the frame of the
    jaxpr that contains it (nested bodies included)."""
    out = []
    top = _Frame(
        closed_jaxpr.jaxpr,
        const_ids=[id(v) for v in closed_jaxpr.jaxpr.constvars],
    )

    def walk(jaxpr, frame):
        for eqn in jaxpr.eqns:
            if is_sparse_prim(eqn.primitive.name):
                out.append((eqn, frame))
            for sub in _eqn_subs(eqn):
                walk(sub, _Frame(sub, outer_eqn=eqn,
                                 outer_frame=frame))

    walk(closed_jaxpr.jaxpr, top)
    return out


# --- the registry ----------------------------------------------------------

#: every audited sparse-access site in the registered engine traces.
#: Adding a gather to an engine means adding (and passing) a row here
#: — see README "Static analysis".  Rows were generated by running the
#: audit against the live manifests and then reviewed: each ``note``
#: names the in-bounds argument the provenance classification leans
#: on.
SPARSE_SITES: tuple = (
    # -- bss: slot-window views over per-replica state ----------------
    SparseSite(
        site="bss.slot_window",
        engine="bss", entry="*/advance",
        primitive="dynamic_slice", mode="clip",
        provenance=("operand",),
        note="window starts are slot counters carried in the advance "
             "state; XLA clamps dynamic-slice starts, so a horizon "
             "overrun reads the last window instead of OOB",
    ),
    # -- lte_sm + shared traffic stage --------------------------------
    SparseSite(
        site="lte_sm.serving_term",
        engine="lte_sm", entry="traffic/*",
        primitive="gather", mode="fill_or_drop",
        provenance=("operand",),
        note="serving-cell table lookups keyed by UE state operands; "
             "FILL_OR_DROP masks any out-of-range id with the "
             "sentinel fill value (-2^31 / nan), which the downstream "
             "masked reductions discard",
    ),
    SparseSite(
        site="lte_sm.traffic_cursor",
        engine="lte_sm", entry="traffic/*",
        primitive="gather", mode="promise_in_bounds",
        provenance=("operand",),
        note="per-entity epoch cursors from tpudes.traffic kernels; "
             "in-bounds because the cursor is a bounded count of "
             "epoch boundaries (see TrafficProgram horizon contract)",
    ),
    SparseSite(
        site="traffic.table_lookup",
        engine="traffic", entry="base/*",
        primitive="gather", mode="fill_or_drop",
        provenance=("operand",),
        note="same kernels as lte_sm.serving_term, traced standalone",
    ),
    SparseSite(
        site="traffic.cursor",
        engine="traffic", entry="base/*",
        primitive="gather", mode="promise_in_bounds",
        provenance=("operand",),
        note="same kernels as lte_sm.traffic_cursor, traced standalone",
    ),
    # -- tcp dumbbell: per-flow ring buffers --------------------------
    SparseSite(
        site="dumbbell.ring_window",
        engine="dumbbell", entry="*/advance",
        primitive="dynamic_slice", mode="clip",
        provenance=("operand", "mod"),
        note="ring-buffer cursors reduced mod the ring length before "
             "the slice",
    ),
    SparseSite(
        site="dumbbell.ring_read",
        engine="dumbbell", entry="*/advance",
        primitive="gather", mode="promise_in_bounds",
        provenance=("operand", "mod"),
        note="ring reads at cursor mod ring-length — in-bounds by the "
             "modulus",
    ),
    SparseSite(
        site="dumbbell.ring_write",
        engine="dumbbell", entry="*/advance",
        primitive="scatter*", mode="fill_or_drop",
        provenance=("operand", "mod"),
        unique_indices=True,
        note="one write per flow per step at distinct mod-cursors; "
             "uniqueness is asserted to XLA (unique_indices=True)",
    ),
    # -- as_flows SPF tables (and the diff loss over the same program)
    SparseSite(
        site="as_flows.path_tables",
        engine="as_flows", entry="*/run",
        primitive="gather", mode="promise_in_bounds",
        provenance=("const", "operand"),
        note="edge/path id tables validated at program build (every "
             "id < 2E by construction in toy_as_program/BRITE import)",
    ),
    SparseSite(
        site="as_flows.epoch_window",
        engine="as_flows", entry="*/run",
        primitive="dynamic_slice", mode="clip",
        provenance=("const", "operand"),
        note="epoch window starts from the scan counter",
    ),
    SparseSite(
        site="as_flows.relax_scatter",
        engine="as_flows", entry="*/run",
        primitive="scatter*", mode="fill_or_drop",
        provenance=("const", "iota", "operand"),
        unique_indices=False,
        note="SPF relaxation writes: iota/edge-table rooted, "
             "collision-free by construction but NOT asserted to XLA "
             "(scatter-min/-add are order-insensitive; the replace "
             "scatter writes disjoint iota rows) — declaring "
             "unique_indices=True upstream is a known follow-up",
    ),
    SparseSite(
        site="as_flows.link_table",
        engine="as_flows", entry="*/run",
        primitive="gather", mode="clip",
        provenance=("const", "operand"),
        note="the compact link table's binary search (jnp.searchsorted "
             "of the path ids into the sorted links in use) reads at "
             "midpoints XLA clamps",
    ),
    SparseSite(
        site="diff.as_loss_tables",
        engine="diff", entry="*",
        primitive="gather", mode="promise_in_bounds",
        provenance=("const", "operand"),
        note="the differentiable AS loss traces the as_flows kernels; "
             "same in-bounds argument as as_flows.path_tables",
    ),
    SparseSite(
        site="diff.as_loss_window",
        engine="diff", entry="*",
        primitive="dynamic_slice", mode="clip",
        provenance=("const", "operand"),
        note="as_flows.epoch_window through the loss wrapper",
    ),
    SparseSite(
        site="diff.as_loss_scatter",
        engine="diff", entry="*",
        primitive="scatter*", mode="fill_or_drop",
        provenance=("const", "iota", "operand"),
        unique_indices=False,
        note="as_flows.relax_scatter through the loss wrapper",
    ),
    SparseSite(
        site="diff.as_loss_link_table",
        engine="diff", entry="*",
        primitive="gather", mode="clip",
        provenance=("const", "operand"),
        note="as_flows.link_table through the loss wrapper",
    ),
    # -- device FlowMonitor packet rings (tpudes/obs/flowmon.py) ------
    # One site per engine: flow_ring_write's dynamic_update_slice at
    # ring slot ``step % FLOW_RING_CAP`` — the start index is the
    # engine's monotonic step counter reduced by lax.rem, so every
    # write is in-bounds by the modulus; XLA clamps DUS starts anyway
    # (mode clip).  LTE is the exception that proves the vmap hazard:
    # its per-TTI step is replica-vmapped with a batched ring, so the
    # DUS batching rule lowers the ring write to a scatter (still mod-
    # rooted, still clip-moded).
    SparseSite(
        site="dumbbell.flow_ring",
        engine="dumbbell", entry="obs/advance",
        primitive="dynamic_update_slice", mode="clip",
        provenance=("operand", "mod"),
        note="FlowMonitor ring write at slot t % FLOW_RING_CAP "
             "(tpudes/obs/flowmon.py flow_ring_write)",
    ),
    SparseSite(
        site="bss.flow_ring",
        engine="bss", entry="obs/advance",
        primitive="dynamic_update_slice", mode="clip",
        provenance=("operand", "mod"),
        note="FlowMonitor ring write at slot step % FLOW_RING_CAP "
             "(tpudes/obs/flowmon.py flow_ring_write)",
    ),
    SparseSite(
        site="lte_sm.flow_ring",
        engine="lte_sm", entry="obs/advance",
        primitive="scatter", mode="clip",
        provenance=("operand", "mod"),
        note="FlowMonitor ring write at slot t % FLOW_RING_CAP; the "
             "replica vmap of the step batches the ring, so the DUS "
             "batching rule lowers it to scatter — indices stay "
             "mod-bounded",
    ),
    SparseSite(
        site="wired.flow_ring",
        engine="wired", entry="obs/advance",
        primitive="dynamic_update_slice", mode="clip",
        provenance=("operand", "mod"),
        note="FlowMonitor ring write at slot t % FLOW_RING_CAP; rides "
             "the no-gather kernel through the JXL001 contract "
             "relaxation (verified registered sites only)",
    ),
    # -- wired / hybrid: one-time init packet-table expansion ---------
    SparseSite(
        site="wired.init_paths",
        engine="wired", entry="*/init",
        primitive="gather", mode="promise_in_bounds",
        provenance=("const",),
        note="per-packet hop tables gathered from the validated paths "
             "array — WiredProgram.__post_init__ rejects any path "
             "entry >= n_links; init is one-time, outside the "
             "no-gather step-kernel contract",
    ),
    SparseSite(
        site="wired_space.init_paths",
        engine="wired_space", entry="*/init",
        primitive="gather", mode="promise_in_bounds",
        provenance=("const",),
        note="hybrid space-lane init uses the same validated "
             "packet-table expansion as wired.init_paths",
    ),
)


def sites_for(engine: str, tag: str, prim: str):
    """Registered sites matching one eqn (``tag`` is
    ``variant/entry``)."""
    return [
        s
        for s in SPARSE_SITES
        if s.engine == engine
        and fnmatch(tag, s.entry)
        and fnmatch(prim, s.primitive)
    ]


def _check_site(site, eqn, kinds, mode) -> list:
    """Contract problems of one (site, eqn) pairing — empty means the
    site audits this eqn."""
    problems = []
    if mode != site.mode:
        problems.append(
            f"mode is '{mode}' but site '{site.site}' declares "
            f"'{site.mode}'"
        )
    undeclared = sorted(kinds - set(site.provenance))
    if undeclared:
        problems.append(
            f"index provenance {undeclared} not in site "
            f"'{site.site}' contract {sorted(site.provenance)}"
        )
    if site.unique_indices is not None and eqn.primitive.name.startswith(
        "scatter"
    ):
        actual = bool(eqn.params.get("unique_indices", False))
        if actual != bool(site.unique_indices):
            problems.append(
                f"unique_indices is {actual} but site "
                f"'{site.site}' declares {bool(site.unique_indices)}"
            )
    return problems


def audit_entry(engine: str, tag: str, closed_jaxpr) -> list:
    """JXL008 audit of one traced entry: every sparse eqn must match a
    registered site whose contract the jaxpr upholds.

    Returns audit records ``{prim, mode, kinds, ok, site, problems}``
    — one per sparse eqn.  ``ok=False`` with ``site=None`` is an
    unaudited site; ``ok=False`` with a site is a contradicted
    contract."""
    records = []
    for eqn, frame in _collect_sparse_eqns(closed_jaxpr):
        prim = eqn.primitive.name
        kinds = set()
        for iv in _index_operands(eqn):
            kinds |= classify_roots(iv, frame)
        mode = _eqn_mode(eqn)
        cands = sites_for(engine, tag, prim)
        rec = {
            "prim": prim,
            "mode": mode,
            "kinds": sorted(kinds),
            "ok": False,
            "site": None,
            "problems": [],
        }
        if not cands:
            rec["problems"] = ["unregistered sparse site"]
        else:
            best = None
            for site in cands:
                problems = _check_site(site, eqn, kinds, mode)
                if not problems:
                    rec["ok"] = True
                    rec["site"] = site.site
                    break
                if best is None or len(problems) < len(best[1]):
                    best = (site, problems)
            if not rec["ok"]:
                rec["site"] = best[0].site
                rec["problems"] = best[1]
        records.append(rec)
    return records


def entry_is_audited(records) -> bool:
    return all(r["ok"] for r in records)
