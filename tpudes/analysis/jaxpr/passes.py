"""JXL001–JXL008: trace-aware contract passes over the device-engine
surface.

The AST passes see Python syntax; these see the *programs the engines
actually hand to XLA*.  Every registered engine front-end exports a
trace manifest (:mod:`tpudes.analysis.jaxpr.manifest`); each rule
abstractly traces the manifest's canonical tiny-shape entries with
``jax.make_jaxpr`` (no compile, CPU-safe under ``JAX_PLATFORMS=cpu``)
and lints the resulting jaxprs.  Findings ride the ordinary
``Pass``/``Finding``/baseline/suppression machinery, anchored at the
engine module's ``trace_manifest`` definition line.

Run via ``python -m tpudes.analysis --jaxpr`` (the pass family is NOT
part of the default AST-only run — tracing costs a jax import).
"""

from __future__ import annotations

from tpudes.analysis.base import Finding, Pass
from tpudes.analysis.jaxpr import cost as C
from tpudes.analysis.jaxpr import sparse_registry as SR
from tpudes.analysis.jaxpr import trace as T

#: primitives that have no business in ANY device-engine program:
#: host callbacks re-enter Python from inside the executable (a
#: dispatch-rate killer and un-Mosaic-able), infeed/outfeed bind the
#: program to a host feed loop
FORBIDDEN_EVERYWHERE = frozenset(
    {"pure_callback", "io_callback", "debug_callback", "callback",
     "infeed", "outfeed"}
)


def _is_gatherish(prim: str) -> bool:
    return prim == "gather" or prim.startswith("scatter")


class JaxprContractPass(Pass):
    """Trace every registered engine manifest and lint the jaxprs.

    ``manifests`` may be injected (the fixture tests run synthetic
    engines through the exact production rule code); the default is
    the real registry.
    """

    name = "jaxpr-contracts"
    project_wide = True
    codes = {
        "JXL001": "forbidden primitive in a device-engine trace "
                  "(gather/scatter in no-gather kernels; host "
                  "callbacks/infeed anywhere)",
        "JXL002": "dtype discipline: unpinned float64 under ambient "
                  "x64, or a bf16-mode reduction accumulating in bf16",
        "JXL003": "large constant baked into the traced program "
                  "(should be a runtime operand)",
        "JXL004": "cache-key hygiene: dead static key component, "
                  "missing key component, or declared-traced operand "
                  "tracing as a constant",
        "JXL005": "donation audit: donated carry leaf unused or "
                  "unaliasable, or a donatable carry never donated",
        "JXL006": "grad-hygiene: a declared-differentiable operand of "
                  "a surrogate-flagged trace has a structurally-zero "
                  "gradient (round/argmax/int-cast/stop_gradient "
                  "severs every path — annotate straight-through)",
        "JXL007": "scale-growth: an entry's fitted memory growth "
                  "exponent exceeds its declared per-axis budget "
                  "(superlinear device bytes before HBM finds out), "
                  "or a declared scale axis never changes the traced "
                  "shapes (dead axis)",
        "JXL008": "sparse-site audit: a gather/scatter/dynamic-slice "
                  "has no registered SparseSite contract, or the "
                  "jaxpr contradicts the registered contract (mode, "
                  "index provenance, scatter uniqueness)",
    }

    def __init__(self, manifests=None):
        self._manifests = manifests

    def _load(self):
        if self._manifests is not None:
            return self._manifests
        from tpudes.analysis.jaxpr.manifest import load_manifests

        return load_manifests()

    def check_project(self, mods):
        findings = []
        for man, line in self._load():
            findings.extend(lint_manifest(man, line))
        return findings


def lint_manifest(man, line: int = 1) -> list:
    """All JXL findings for one manifest (the unit the fixture tests
    drive directly)."""
    out = []

    def emit(code, msg):
        out.append(Finding(man.path, line, 1, code, msg))

    variants = man.variants()
    base_fp = None
    for vi, variant in enumerate(variants):
        entries = variant.build()
        traced = [(e, T.trace_entry(e)) for e in entries]
        if vi == 0:
            # the base variant's fingerprints double as the JXL004
            # comparison side — computed from THESE traces so the base
            # entries are never traced twice
            base_fp = {e.name: T.fingerprint(cj) for e, cj in traced}

        for entry, cj in traced:
            tag = f"{man.engine}/{variant.name}/{entry.name}"
            prims = T.primitive_names(cj)

            # JXL008 — sparse-site audit: every gather/scatter/
            # dynamic-slice must match a registered SparseSite whose
            # contract (mode, index provenance, scatter uniqueness)
            # the jaxpr upholds
            records = SR.audit_entry(
                man.engine, f"{variant.name}/{entry.name}", cj
            )
            seen_msgs = set()
            for rec in records:
                if rec["ok"]:
                    continue
                if rec["site"] is None:
                    msg = (
                        f"{tag}: unaudited sparse site — '{rec['prim']}' "
                        f"(mode {rec['mode']}, index roots "
                        f"{rec['kinds']}) has no registered SparseSite; "
                        "add a machine-checked contract in "
                        "analysis/jaxpr/sparse_registry.py"
                    )
                else:
                    msg = (
                        f"{tag}: sparse-site contract contradicted — "
                        f"'{rec['prim']}' vs '{rec['site']}': "
                        + "; ".join(rec["problems"])
                    )
                if msg not in seen_msgs:
                    seen_msgs.add(msg)
                    emit("JXL008", msg)

            # JXL001 — forbidden primitives
            for p in sorted(prims & FORBIDDEN_EVERYWHERE):
                emit("JXL001", f"{tag}: host primitive '{p}' inside "
                               "the device program")
            if man.no_gather and entry.kernel:
                # the blanket ban relaxed into the audit: a gatherish
                # eqn that passes a registered SparseSite contract is
                # allowed even in a no-gather kernel (the path the
                # CSR wired rewrite lands through); everything else
                # still fires
                bad = sorted(
                    {
                        r["prim"]
                        for r in records
                        if not r["ok"] and _is_gatherish(r["prim"])
                    }
                )
                for p in bad:
                    emit(
                        "JXL001",
                        f"{tag}: '{p}' in a no-gather step kernel — "
                        "the wired contract is one-hot/masked-"
                        "reduction forms only (XLA:CPU serializes "
                        "gathers; Mosaic tiling forbids them), "
                        "unless the site carries a verified "
                        "sparse_registry contract",
                    )

            # JXL007 — scale growth: re-trace the entry along each
            # declared axis and fit the peak-live/widest-buffer
            # growth exponents against the declared budget.  Base
            # variant only: axes describe the program, not the
            # variant, and tracing is the expensive part.
            if vi == 0:
                for ax in entry.scale_axes:
                    if len(ax.points) < 2:
                        emit(
                            "JXL007",
                            f"{tag}: scale axis '{ax.name}' declares "
                            "fewer than 2 points — growth cannot be "
                            "fitted",
                        )
                        continue
                    m = C.axis_metrics(ax)
                    if m["dead"]:
                        emit(
                            "JXL007",
                            f"{tag}: scale axis '{ax.name}' never "
                            "changes the traced shapes across points "
                            f"{m['points']} — dead axis declaration "
                            "(the manifest claims a scaling the "
                            "program does not have)",
                        )
                    elif m["over_budget"]:
                        emit(
                            "JXL007",
                            f"{tag}: scale axis '{ax.name}' fitted "
                            f"memory exponent "
                            f"{m['mem_exponent']:.2f} exceeds budget "
                            f"{ax.mem_budget:g} (peak-live "
                            f"{m['peak_exponent']:.2f}, widest "
                            f"buffer {m['widest_exponent']:.2f}) — "
                            "superlinear device bytes; run --jaxpr "
                            "--cost for the 1e5/1e6-node projections",
                        )

            # JXL002 — bf16 accumulator policy
            if variant.bf16:
                for p in sorted(T.bf16_accumulators(cj)):
                    emit(
                        "JXL002",
                        f"{tag}: '{p}' accumulates in bfloat16 — the "
                        "mixed-precision policy computes low and "
                        "accumulates f32 (use preferred_element_type "
                        "or an explicit f32 cast)",
                    )

            # JXL003 — baked-in large constants
            for shape, dtype, nbytes in T.large_consts(
                cj, man.const_budget
            ):
                emit(
                    "JXL003",
                    f"{tag}: baked constant {dtype}{list(shape)} "
                    f"({nbytes} B > {man.const_budget} B budget) — "
                    "pass it as a runtime operand so value flips "
                    "don't recompile",
                )

            # JXL004 — declared-traced operand burned to a constant
            for opname, argnum in sorted(entry.traced.items()):
                dead = T.unused_arg_leaves(entry, cj, argnum)
                n_leaves = len(T.arg_leaf_paths(entry.args[argnum]))
                if dead and len(dead) == n_leaves:
                    emit(
                        "JXL004",
                        f"{tag}: declared-traced operand '{opname}' "
                        "is unused in the trace — the builder closed "
                        "over a concrete value, so runtime flips "
                        "cannot reach the program",
                    )

            # JXL005 — donation audit
            for argnum in entry.donate:
                for path in T.unused_arg_leaves(entry, cj, argnum):
                    emit(
                        "JXL005",
                        f"{tag}: donated carry leaf '{path}' is never "
                        "consumed — dead state riding the donated "
                        "buffer",
                    )
                for path in T.unaliasable_donated_leaves(
                    entry, cj, argnum
                ):
                    emit(
                        "JXL005",
                        f"{tag}: donated leaf '{path}' has no "
                        "shape/dtype-matching output — XLA cannot "
                        "alias it, the donation frees nothing",
                    )
            for argnum in entry.carry:
                if argnum not in entry.donate:
                    emit(
                        "JXL005",
                        f"{tag}: carry argnum {argnum} is never "
                        "donated — a per-call state copy on "
                        "accelerators (wrap the jit in "
                        "donate_argnums)",
                    )

            # JXL006 — grad hygiene on surrogate-flagged variants:
            # every declared-differentiable operand leaf must keep a
            # gradient path to the outputs; a round/argmax/integer
            # cast/stop_gradient severing every path makes jax.grad
            # return structural zeros — the silent way a calibration
            # "converges" by never moving
            if variant.surrogate:
                for argnum in entry.grad_wrt:
                    for path in T.grad_severed_leaves(entry, cj, argnum):
                        emit(
                            "JXL006",
                            f"{tag}: differentiable operand leaf "
                            f"'{path}' has no gradient path to the "
                            "outputs — a hard op (round/argmax/int "
                            "cast/stop_gradient) severs every route; "
                            "wrap it straight-through "
                            "(tpudes.diff.ste) or soften it behind "
                            "the Surrogacy flag",
                        )

        # JXL002 — f64 under ambient x64 (rebuild inside the context so
        # build-time asarray boundaries are exercised too).  A trace
        # that fails to TYPE under x64 is the worst version of the
        # finding: some unpinned creation/accumulation site widened a
        # loop carry until the program stopped being well-formed.
        try:
            traced64 = T.trace_entries_x64(variant.build)
        except (TypeError, ValueError) as e:
            # jax's own typing errors only: a missing import or renamed
            # API must crash the lint, never pose as a finding
            emit(
                "JXL002",
                f"{man.engine}/{variant.name}: trace fails under "
                f"ambient x64 ({type(e).__name__}) — an unpinned "
                "dtype widens the program until it no longer "
                "type-checks; pin creation sites and integer "
                "reductions (.sum(dtype=jnp.int32))",
            )
            traced64 = []
        for entry, cj64 in traced64:
            tag = f"{man.engine}/{variant.name}/{entry.name}"
            for p in sorted(T.f64_primitives(cj64)):
                emit(
                    "JXL002",
                    f"{tag}: '{p}' produces float64 when ambient x64 "
                    "is enabled — an unpinned dtype at the creating "
                    "site makes results depend on global config (pin "
                    "jnp.float32)",
                )

    # JXL004 — cache-key hygiene over the declared flips
    if man.flips is not None and base_fp is not None:
        for fname, flip in sorted(man.flips().items()):
            flip_fp = T.variant_fingerprints(flip.build())
            same = flip_fp == base_fp
            if flip.key_differs and same:
                emit(
                    "JXL004",
                    f"{man.engine}: cache-key component '{fname}' is "
                    "dead — flipping it provably leaves every traced "
                    "program identical, so it only causes spurious "
                    "recompiles",
                )
            elif not flip.key_differs and not same:
                changed = sorted(
                    k for k in base_fp if flip_fp.get(k) != base_fp[k]
                )
                emit(
                    "JXL004",
                    f"{man.engine}: '{fname}' changes the traced "
                    f"program ({', '.join(changed)}) but is NOT a "
                    "cache-key component — a stale runner would serve "
                    "the wrong executable",
                )
    return out


#: the pass family ``--jaxpr`` appends to a run (kept out of
#: BUILTIN_PASSES: tracing costs a jax import + ~a second per engine)
JAXPR_PASSES = [JaxprContractPass]
