"""Trace-aware analysis (tpudes.analysis.jaxpr): planted-defect
fixtures for JXL001–JXL005 in both directions, the wired no-gather
acceptance pair, cache-key hygiene on the real engines, and the
dead-key fix regressions.

Fixture manifests run through the exact production rule code
(lint_manifest), so a rule that stops firing on its planted defect
fails here before it silently stops gating the engines.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpudes.analysis.jaxpr import (  # noqa: E402
    FlipSpec,
    ScaleAxis,
    TraceEntry,
    TraceManifest,
    TraceVariant,
    lint_manifest,
)

SYNTH = "tpudes/parallel/synthetic.py"


def _manifest(entries_fn, flips=None, **kw):
    return TraceManifest(
        engine="synth",
        path=SYNTH,
        variants=lambda: [TraceVariant("base", entries_fn)],
        flips=flips,
        **kw,
    )


def _codes(findings):
    return [f.code for f in findings]


# --- JXL001 forbidden primitives -------------------------------------------


def test_jxl001_gather_fires_only_under_no_gather_contract():
    x = jnp.arange(8, dtype=jnp.float32)
    idx = jnp.asarray([3, 1], jnp.int32)

    def kernel(v):
        return jnp.take(v, idx)

    entries = lambda: [TraceEntry("step", kernel, (x,))]  # noqa: E731
    armed = lint_manifest(_manifest(entries, no_gather=True))
    assert any(
        f.code == "JXL001" and "gather" in f.message for f in armed
    ), armed
    # same trace without the contract: no finding
    assert "JXL001" not in _codes(lint_manifest(_manifest(entries)))


def test_jxl001_gather_ban_spares_init_entries():
    x = jnp.arange(8, dtype=jnp.float32)
    idx = jnp.asarray([3, 1], jnp.int32)
    entries = lambda: [  # noqa: E731
        TraceEntry("init", lambda: jnp.take(x, idx), (), kernel=False)
    ]
    assert "JXL001" not in _codes(
        lint_manifest(_manifest(entries, no_gather=True))
    )


def test_jxl001_callback_forbidden_everywhere():
    def kernel(v):
        return jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct((3,), np.float32), v
        )

    entries = lambda: [  # noqa: E731
        TraceEntry("step", kernel, (jnp.zeros(3, jnp.float32),))
    ]
    found = lint_manifest(_manifest(entries))
    assert any(
        f.code == "JXL001" and "callback" in f.message for f in found
    ), found


def test_planted_gather_in_wired_step_fires_and_real_kernel_is_clean():
    """ISSUE acceptance: a jnp.take smuggled into the wired step body
    must produce the JXL001 finding, and today's kernels must not."""
    from tpudes.parallel import wired

    prog = wired._trace_prog()
    init_state, advance = wired.build_wired_advance(prog, wired._TRACE_R)
    carry = init_state(jax.random.PRNGKey(0))
    P = int(carry["hop"].shape[1])
    no_ing = jnp.full((wired._TRACE_R, P), -1, jnp.int32)
    cols = jnp.arange(P, dtype=jnp.int32)

    def bad_advance(c, ih, ir, t_grant):
        c, metrics = advance(c, ih, ir, t_grant)
        # the smuggled dynamic lookup: per-packet delivery slots read
        # back through a gather instead of the one-hot algebra
        c = dict(c, deliver=jnp.take(c["deliver"], cols, axis=1))
        return c, metrics

    planted = _manifest(
        lambda: [
            TraceEntry(
                "advance", bad_advance,
                (carry, no_ing, no_ing, jnp.int32(8)),
            )
        ],
        no_gather=True,
    )
    found = lint_manifest(planted)
    assert any(
        f.code == "JXL001" and "gather" in f.message for f in found
    ), found

    # the real manifest stays gather-free (its only expected findings
    # are the baselined JXL005 egress-buffer entries)
    real = [
        f for f in lint_manifest(wired.trace_manifest())
        if f.code == "JXL001"
    ]
    assert real == []


# --- JXL002 dtype discipline ------------------------------------------------


def test_jxl002_unpinned_f64_fires_and_pinned_is_clean():
    def leaky(x):
        return jnp.zeros(3) + x  # unpinned: f64 under ambient x64

    def pinned(x):
        return jnp.zeros(3, jnp.float32) + x

    x = jnp.ones(3, jnp.float32)
    found = lint_manifest(
        _manifest(lambda: [TraceEntry("step", leaky, (x,))])
    )
    assert any(
        f.code == "JXL002" and "float64" in f.message for f in found
    ), found
    assert "JXL002" not in _codes(
        lint_manifest(_manifest(lambda: [TraceEntry("step", pinned, (x,))]))
    )


def test_jxl002_bf16_accumulator_fires_and_f32_accumulator_is_clean():
    x = jnp.ones((4, 4), jnp.float32)

    def bad(v):
        lo = v.astype(jnp.bfloat16)
        return lo @ lo  # dot_general accumulating at bf16

    def good(v):
        lo = v.astype(jnp.bfloat16)
        return jnp.einsum(
            "ij,jk->ik", lo, lo, preferred_element_type=jnp.float32
        )

    def run(fn, bf16):
        man = TraceManifest(
            engine="synth", path=SYNTH,
            variants=lambda: [
                TraceVariant(
                    "bf16", lambda: [TraceEntry("step", fn, (x,))],
                    bf16=bf16,
                )
            ],
        )
        return lint_manifest(man)

    found = run(bad, True)
    assert any(
        f.code == "JXL002" and "bfloat16" in f.message for f in found
    ), found
    assert "JXL002" not in _codes(run(good, True))
    # the accumulator check only arms on bf16-tagged variants
    assert "JXL002" not in _codes(run(bad, False))


def test_jxl002_x64_trace_failure_is_a_finding():
    def fragile(x):
        def body(c):
            # the loop carry widens: i32 in, sum-promoted i64 out
            return (c * jnp.ones((2,), jnp.int32)).sum()

        return jax.lax.while_loop(lambda c: c < x, body, jnp.int32(0))

    found = lint_manifest(
        _manifest(
            lambda: [TraceEntry("step", fragile, (jnp.int32(5),))]
        )
    )
    assert any(
        f.code == "JXL002" and "fails under ambient x64" in f.message
        for f in found
    ), found


# --- JXL003 baked-in constants ----------------------------------------------


def test_jxl003_large_const_fires_and_operand_form_is_clean():
    big = jnp.asarray(np.arange(4096, dtype=np.float32))  # 16 KiB

    def baked(x):
        return x + big

    def operand(x, table):
        return x + table

    x = jnp.ones(4096, jnp.float32)
    found = lint_manifest(
        _manifest(lambda: [TraceEntry("step", baked, (x,))])
    )
    assert any(
        f.code == "JXL003" and "baked constant" in f.message
        for f in found
    ), found
    assert "JXL003" not in _codes(
        lint_manifest(
            _manifest(lambda: [TraceEntry("step", operand, (x, big))])
        )
    )
    # raising the budget silences it (per-manifest knob)
    assert "JXL003" not in _codes(
        lint_manifest(
            _manifest(
                lambda: [TraceEntry("step", baked, (x,))],
                const_budget=1 << 20,
            )
        )
    )


# --- JXL004 cache-key hygiene ----------------------------------------------


def _affine(scale_val: float):
    scale = jnp.float32(scale_val)

    def fn(x):
        return x * scale

    return fn


def test_jxl004_dead_key_component_fires():
    x = jnp.ones(3, jnp.float32)
    entries = lambda v=1.0: [  # noqa: E731
        TraceEntry("step", _affine(v), (x,))
    ]
    man = _manifest(
        lambda: entries(),
        flips=lambda: {
            # key separates the flip, but the trace is identical
            "dead_field": FlipSpec(build=lambda: entries(), key_differs=True),
        },
    )
    found = lint_manifest(man)
    assert any(
        f.code == "JXL004" and "dead" in f.message for f in found
    ), found


def test_jxl004_live_component_and_honest_exclusion_are_clean():
    x = jnp.ones(3, jnp.float32)
    entries = lambda v: [TraceEntry("step", _affine(v), (x,))]  # noqa: E731
    man = _manifest(
        lambda: entries(1.0),
        flips=lambda: {
            "live_field": FlipSpec(
                build=lambda: entries(2.0), key_differs=True
            ),
            "excluded_field": FlipSpec(
                build=lambda: entries(1.0), key_differs=False
            ),
        },
    )
    assert "JXL004" not in _codes(lint_manifest(man))


def test_jxl004_missing_key_component_fires():
    x = jnp.ones(3, jnp.float32)
    entries = lambda v: [TraceEntry("step", _affine(v), (x,))]  # noqa: E731
    man = _manifest(
        lambda: entries(1.0),
        flips=lambda: {
            # flip changes the program but the key does not separate it
            "forgotten": FlipSpec(
                build=lambda: entries(2.0), key_differs=False
            ),
        },
    )
    found = lint_manifest(man)
    assert any(
        f.code == "JXL004" and "NOT a cache-key component" in f.message
        for f in found
    ), found


def test_jxl004_constant_burned_traced_operand_fires():
    x = jnp.ones(3, jnp.float32)
    burned_scale = jnp.float32(2.0)

    def burned(x, scale):
        return x * burned_scale  # ignores the declared operand

    def honest(x, scale):
        return x * scale

    def run(fn):
        return lint_manifest(
            _manifest(
                lambda: [
                    TraceEntry(
                        "step", fn, (x, jnp.float32(2.0)),
                        traced={"scale": 1},
                    )
                ]
            )
        )

    found = run(burned)
    assert any(
        f.code == "JXL004" and "'scale'" in f.message for f in found
    ), found
    assert "JXL004" not in _codes(run(honest))


# --- JXL005 donation audit ---------------------------------------------------


def test_jxl005_unused_donated_leaf_fires():
    def fn(carry, x):
        return dict(a=carry["a"] + x, b=jnp.zeros(3, jnp.float32))

    carry = dict(
        a=jnp.zeros(3, jnp.float32), b=jnp.ones(3, jnp.float32)
    )
    found = lint_manifest(
        _manifest(
            lambda: [
                TraceEntry(
                    "advance", fn, (carry, jnp.float32(1.0)),
                    donate=(0,), carry=(0,),
                )
            ]
        )
    )
    assert any(
        f.code == "JXL005" and "never consumed" in f.message
        for f in found
    ), found


def test_jxl005_undonated_carry_and_unaliasable_leaf_fire():
    def fn(carry, x):
        return carry + x

    args = (jnp.zeros(3, jnp.float32), jnp.float32(1.0))
    found = lint_manifest(
        _manifest(
            lambda: [TraceEntry("advance", fn, args, carry=(0,))]
        )
    )
    assert any(
        f.code == "JXL005" and "never donated" in f.message
        for f in found
    ), found

    def shrink(carry):
        return carry[:2]  # donated buffer has no same-shape output

    found = lint_manifest(
        _manifest(
            lambda: [
                TraceEntry(
                    "advance", shrink, (jnp.zeros(3, jnp.float32),),
                    donate=(0,),
                )
            ]
        )
    )
    assert any(
        f.code == "JXL005" and "cannot alias" in f.message
        for f in found
    ), found


def test_jxl005_proper_donated_carry_is_clean():
    def fn(carry, x):
        return carry + x

    assert "JXL005" not in _codes(
        lint_manifest(
            _manifest(
                lambda: [
                    TraceEntry(
                        "advance", fn,
                        (jnp.zeros(3, jnp.float32), jnp.float32(1.0)),
                        donate=(0,), carry=(0,),
                    )
                ]
            )
        )
    )


# --- real-surface checks -----------------------------------------------------


#: the baselined-by-design findings: four JXL005 egress buffers
#: (protocol-overwritten at every window start; dropping them from the
#: input carry would break the carry-in == carry-out chunk-handoff
#: shape) plus the two JXL007 superlinear wired step kernels (the
#: dense one-hot tables ROADMAP item 2 exists to replace)
_EXPECTED_REAL = {"JXL005", "JXL007"}


@pytest.mark.parametrize(
    "module",
    ["replicated", "lte_sm", "tcp_dumbbell", "as_flows", "wired",
     "hybrid"],
)
def test_real_manifest_lints_clean_modulo_baseline(module):
    import importlib

    mod = importlib.import_module(f"tpudes.parallel.{module}")
    found = lint_manifest(mod.trace_manifest())
    unexpected = [f for f in found if f.code not in _EXPECTED_REAL]
    assert unexpected == [], unexpected
    for f in found:
        if f.code == "JXL005":
            assert "eg_" in f.message, f  # only the known egress entries
        else:
            # only the wired engines carry the known quadratic axis
            assert module in ("wired", "hybrid"), f
            assert "scale axis 'n_nodes'" in f.message, f
    if module in ("wired", "hybrid"):
        # ISSUE acceptance: the dense one-hot step kernel must fire
        # JXL007 out of the box, pointing at the --cost report
        jxl7 = [f for f in found if f.code == "JXL007"]
        assert len(jxl7) == 1, found
        assert "exceeds budget" in jxl7[0].message
        assert "--jaxpr --cost" in jxl7[0].message


def test_wired_dead_key_fix_shares_one_runner():
    """Regression for the JXL004-found dead components: programs
    differing only in slot_s / link_owner must hit the SAME cached
    wired runner (they compile identical kernels)."""
    from tpudes.parallel.runtime import RUNTIME
    from tpudes.parallel.wired import run_wired, wired_chain

    prog = wired_chain(n_links=3, n_flows=2, n_slots=40)
    key = jax.random.PRNGKey(7)
    RUNTIME.clear("wired")
    base = run_wired(prog, key)
    misses = RUNTIME.misses
    twin = dataclasses.replace(
        prog, slot_s=0.5,
        link_owner=np.asarray([0, 1, 1], np.int32),
    )
    out = run_wired(twin, key)
    assert RUNTIME.misses == misses  # cache hit: no new runner
    np.testing.assert_array_equal(
        out["deliver_slot"], base["deliver_slot"]
    )


def test_dumbbell_red_knobs_out_of_fifo_key():
    """Regression: in fifo mode the RED parameters never reach the
    program — flipping them must reuse the cached runner."""
    from tpudes.parallel.runtime import RUNTIME
    from tpudes.parallel.tcp_dumbbell import (
        dumbbell_prog_key,
        run_tcp_dumbbell,
    )
    from tpudes.parallel.programs import toy_dumbbell_program

    prog = toy_dumbbell_program(n_flows=2, n_slots=30)
    twin = dataclasses.replace(prog, red_qw=0.5, red_max_p=0.9)
    assert dumbbell_prog_key(prog) == dumbbell_prog_key(twin)
    # ...while a RED-mode program still keys on them
    red = dataclasses.replace(prog, qdisc="red")
    red2 = dataclasses.replace(red, red_qw=0.5)
    assert dumbbell_prog_key(red) != dumbbell_prog_key(red2)

    key = jax.random.PRNGKey(3)
    RUNTIME.clear("dumbbell")
    base = run_tcp_dumbbell(prog, key, replicas=2)
    misses = RUNTIME.misses
    out = run_tcp_dumbbell(twin, key, replicas=2)
    assert RUNTIME.misses == misses
    np.testing.assert_array_equal(out["delivered"], base["delivered"])


def test_dumbbell_variant_set_is_keyed_and_the_assignment_is_not():
    """The slot compiles the window rules of the variants assigned
    (ISSUE 38): an assignment INSIDE the base program's set leaves every
    trace identical and the runner's key equal; another set is another
    program and another key (a live component).  The manifest keeps both
    ends of what a set can compile on the lint surface: one variant's
    rules (``var`` is then no declared-traced operand: nothing reads
    it) and all seventeen's."""
    from tpudes.analysis.jaxpr import trace as T
    from tpudes.parallel import tcp_dumbbell

    man = tcp_dumbbell.trace_manifest()
    variants = {v.name: v for v in man.variants()}
    assert list(variants)[0] == "base"
    assert {"obs", "one_variant", "all_variants"} <= set(variants)
    base = T.variant_fingerprints(variants["base"].build())
    flips = man.flips()
    inside, other = flips["variant_idx"], flips["variant_set"]
    assert not inside.key_differs
    assert T.variant_fingerprints(inside.build()) == base
    assert other.key_differs
    assert T.variant_fingerprints(other.build())["advance"] != base["advance"]
    (one,) = [e for e in variants["one_variant"].build() if e.kernel]
    (every,) = [e for e in variants["all_variants"].build() if e.kernel]
    assert "var" not in one.traced and every.traced["var"] == 2
    assert len(set(np.asarray(every.args[2]))) == len(tcp_dumbbell.VARIANTS)


# --- JXL006 grad hygiene (ISSUE-15) ----------------------------------------


def _surrogate_manifest(entries_fn):
    return TraceManifest(
        engine="synth",
        path=SYNTH,
        variants=lambda: [
            TraceVariant("base", entries_fn, surrogate=True)
        ],
    )


def test_jxl006_severed_gradient_fires_and_ste_is_clean():
    """A round() in the only path to the output kills the gradient —
    JXL006 fires; the straight-through annotation (tpudes.diff.ste)
    restores a soft path and is clean."""
    from tpudes.diff.surrogate import ste

    x = jnp.ones((3,), jnp.float32)

    def severed(x):
        return jnp.sum(jnp.round(x) * 2.0)

    def annotated(x):
        return jnp.sum(ste(jnp.round(x), x) * 2.0)

    found = lint_manifest(
        _surrogate_manifest(
            lambda: [TraceEntry("loss", severed, (x,), kernel=False,
                               grad_wrt=(0,))]
        )
    )
    assert "JXL006" in _codes(found)
    assert "straight-through" in found[0].message
    assert "JXL006" not in _codes(
        lint_manifest(
            _surrogate_manifest(
                lambda: [TraceEntry("loss", annotated, (x,),
                                    kernel=False, grad_wrt=(0,))]
            )
        )
    )


def test_jxl006_integer_cast_and_stop_gradient_sever():
    x = jnp.ones((2,), jnp.float32)

    def int_cast(x):
        return jnp.sum(x.astype(jnp.int32).astype(jnp.float32))

    def stopped(x):
        return jnp.sum(jax.lax.stop_gradient(x) * 3.0)

    for fn in (int_cast, stopped):
        found = lint_manifest(
            _surrogate_manifest(
                lambda fn=fn: [TraceEntry("loss", fn, (x,),
                                          kernel=False, grad_wrt=(0,))]
            )
        )
        assert "JXL006" in _codes(found), fn.__name__


def test_jxl006_scan_carry_feedback_path_is_live():
    """Regression for the fixed-point liveness: an operand whose only
    gradient route enters through a scan CARRY on iteration k>0 (the
    fluid cap→util→lfrac→lg chain) must count as live."""
    x = jnp.ones((3,), jnp.float32)

    def through_carry(x):
        def body(c, _):
            lf, acc = c
            # acc only sees x via the PREVIOUS iteration's lf
            return (lf + x, acc + jnp.sum(lf)), None

        (lf, acc), _ = jax.lax.scan(
            body, (jnp.zeros((3,), jnp.float32), jnp.float32(0.0)),
            None, length=3,
        )
        return acc

    assert "JXL006" not in _codes(
        lint_manifest(
            _surrogate_manifest(
                lambda: [TraceEntry("loss", through_carry, (x,),
                                    kernel=False, grad_wrt=(0,))]
            )
        )
    )


def test_jxl006_only_audits_surrogate_variants():
    """The same severed trace on a plain (non-surrogate) variant is
    out of scope — legacy engines quantize by design."""
    x = jnp.ones((3,), jnp.float32)

    def severed(x):
        return jnp.sum(jnp.round(x))

    assert "JXL006" not in _codes(
        lint_manifest(
            _manifest(
                lambda: [TraceEntry("loss", severed, (x,),
                                    kernel=False, grad_wrt=(0,))]
            )
        )
    )


def test_diff_manifest_is_clean_and_its_flips_hold():
    """The real diff-subsystem manifest: every exposed operand keeps a
    live gradient path (JXL006), the surrogate/loss flips are honest
    cache-key components (JXL004), the traces carry no stray f64
    (JXL002), its sparse sites are all audited (JXL008) and its scale
    axis stays linear (JXL007) — the ratchet stays ZERO."""
    from tpudes.diff import as_grad

    found = lint_manifest(as_grad.trace_manifest())
    assert found == [], [f.message for f in found]


# --- JXL007 scale growth (ISSUE-16 tentpole) --------------------------------


def _axis_manifest(build, **axkw):
    """A one-entry manifest whose entry declares one scale axis over
    ``build`` (value -> TraceEntry)."""

    def entries():
        return [
            dataclasses.replace(
                build(4), scale_axes=(ScaleAxis("n", build, **axkw),)
            )
        ]

    return _manifest(entries)


def _quad_entry(v):
    # the planted defect: an outer product materializes an O(n^2)
    # buffer while in/out stay O(n)
    return TraceEntry(
        "step", lambda x: jnp.outer(x, x).sum(),
        (jnp.ones(int(v), jnp.float32),),
    )


def _lin_entry(v):
    return TraceEntry(
        "step", lambda x: (x * 2.0).sum(),
        (jnp.ones(int(v), jnp.float32),),
    )


def test_jxl007_quadratic_axis_fires_and_linear_is_clean():
    found = lint_manifest(_axis_manifest(_quad_entry, points=(2, 8)))
    hits = [f for f in found if f.code == "JXL007"]
    assert len(hits) == 1, found
    assert "exceeds budget" in hits[0].message
    assert "widest buffer 2.00" in hits[0].message
    assert "JXL007" not in _codes(
        lint_manifest(_axis_manifest(_lin_entry, points=(2, 8)))
    )


def test_jxl007_declared_budget_silences_known_superlinear():
    # the bss n_sta pattern: O(n^2) pairwise geometry is the model's
    # contract — declaring mem_budget=2.0 makes the fit an assertion,
    # not a finding
    assert "JXL007" not in _codes(
        lint_manifest(
            _axis_manifest(_quad_entry, points=(2, 8), mem_budget=2.0)
        )
    )


def test_jxl007_dead_axis_declaration_fires():
    def dead(v):  # ignores v: the manifest claims a scaling it lacks
        return _lin_entry(4)

    found = lint_manifest(_axis_manifest(dead, points=(2, 8)))
    assert any(
        f.code == "JXL007" and "dead axis" in f.message for f in found
    ), found


def test_jxl007_single_point_axis_cannot_fit():
    found = lint_manifest(_axis_manifest(_lin_entry, points=(4,)))
    assert any(
        f.code == "JXL007" and "fewer than 2 points" in f.message
        for f in found
    ), found


# --- JXL008 sparse-site audit (ISSUE-16 tentpole) ---------------------------


def _take_entries():
    x = jnp.arange(8, dtype=jnp.float32)
    idx = jnp.asarray([3, 1], jnp.int32)
    return [TraceEntry("step", lambda v, i: v[i], (x, idx))]


def _synth_site(**over):
    from tpudes.analysis.jaxpr.sparse_registry import SparseSite

    kw = dict(
        site="synth.window", engine="synth", entry="*/step",
        primitive="gather", mode="promise_in_bounds",
        provenance=("operand",),
    )
    kw.update(over)
    return SparseSite(**kw)


def test_jxl008_unregistered_gather_fires():
    found = lint_manifest(_manifest(_take_entries))
    hits = [f for f in found if f.code == "JXL008"]
    assert len(hits) == 1, found
    assert "unaudited sparse site" in hits[0].message
    assert "sparse_registry" in hits[0].message


def test_jxl008_registered_contract_passes(monkeypatch):
    from tpudes.analysis.jaxpr import sparse_registry as SR

    monkeypatch.setattr(
        SR, "SPARSE_SITES", SR.SPARSE_SITES + (_synth_site(),)
    )
    assert "JXL008" not in _codes(lint_manifest(_manifest(_take_entries)))


@pytest.mark.parametrize(
    "over, fragment",
    [
        ({"mode": "clip"}, "mode"),
        ({"provenance": ("iota",)}, "provenance"),
    ],
)
def test_jxl008_contradicted_contract_fires(monkeypatch, over, fragment):
    """A registered site whose declared mode/provenance the jaxpr does
    not uphold is a finding, not a free pass — the contract is
    machine-checked, never trusted."""
    from tpudes.analysis.jaxpr import sparse_registry as SR

    monkeypatch.setattr(
        SR, "SPARSE_SITES", SR.SPARSE_SITES + (_synth_site(**over),)
    )
    found = lint_manifest(_manifest(_take_entries))
    hits = [f for f in found if f.code == "JXL008"]
    assert len(hits) == 1, found
    assert "contract contradicted" in hits[0].message
    assert fragment in hits[0].message


def test_jxl001_gather_ban_relaxed_by_verified_contract(monkeypatch):
    """The ISSUE-16 relaxation: under no_gather, a gather with a
    VERIFIED sparse_registry contract passes JXL001 (the audit
    replaces the blanket ban); an unregistered one still fires both."""
    from tpudes.analysis.jaxpr import sparse_registry as SR

    found = lint_manifest(_manifest(_take_entries, no_gather=True))
    assert "JXL001" in _codes(found) and "JXL008" in _codes(found)

    monkeypatch.setattr(
        SR, "SPARSE_SITES", SR.SPARSE_SITES + (_synth_site(),)
    )
    clean = lint_manifest(_manifest(_take_entries, no_gather=True))
    assert "JXL001" not in _codes(clean), clean
    assert "JXL008" not in _codes(clean), clean


def test_lte_serving_term_gather_is_audited():
    """ISSUE acceptance: the LTE serving-term gather is a REGISTERED
    allowlist entry whose contract (fill_or_drop mode, operand-rooted
    indices) the traced jaxpr upholds."""
    from tpudes.analysis.jaxpr import sparse_registry as SR
    from tpudes.analysis.jaxpr.trace import trace_entry
    from tpudes.parallel import lte_sm

    man = lte_sm.trace_manifest()
    variant = next(v for v in man.variants() if v.name == "traffic")
    entry = next(
        e for e in variant.build() if e.name == "traffic_advance"
    )
    records = SR.audit_entry(
        man.engine, f"{variant.name}/{entry.name}", trace_entry(entry)
    )
    assert records, "the serving-term gathers must be visible"
    assert all(r["ok"] for r in records), records
    sites = {r["site"] for r in records}
    assert "lte_sm.serving_term" in sites
    serving = [r for r in records if r["site"] == "lte_sm.serving_term"]
    assert all(r["mode"] == "fill_or_drop" for r in serving)
    assert all(r["kinds"] == ["operand"] for r in serving)


# --- cost model: peak-live / widest-buffer / FLOP accounting ----------------


def _cost():
    from tpudes.analysis.jaxpr import cost

    return cost


def test_buffer_accounting_pinned_on_tiny_jaxprs():
    cost = _cost()
    x = jnp.ones(4, jnp.float32)

    cj = jax.make_jaxpr(lambda v: (v * 2.0).sum())(x)
    assert cost.total_buffer_bytes(cj) == 36  # in 16 + mul 16 + sum 4
    assert cost.peak_live_bytes(cj) == 36  # nothing dies before the sum
    assert cost._jaxpr_flops(cj.jaxpr) == 8.0  # 4 mul + 4-element sum

    def chain(v):
        a = v * 2.0
        b = a + 1.0
        return b * 3.0

    cj = jax.make_jaxpr(chain)(x)
    assert cost.total_buffer_bytes(cj) == 64
    # liveness: `a` dies when `b` is born, so at most two 16 B
    # intermediates coexist on top of the held input
    assert cost.peak_live_bytes(cj) == 48


def test_widest_buffer_sees_the_quadratic_intermediate():
    cost = _cost()
    cj = jax.make_jaxpr(lambda v: jnp.outer(v, v))(
        jnp.ones(4, jnp.float32)
    )
    assert cost.widest_buffer_bytes(cj) == 64  # the 4x4 f32 table
    for n, widest in ((2, 16), (8, 256)):
        cj = jax.make_jaxpr(lambda v: jnp.outer(v, v).sum())(
            jnp.ones(n, jnp.float32)
        )
        assert cost.widest_buffer_bytes(cj) == widest  # exact n^2 * 4


def test_scan_body_costs_scale_with_length():
    cost = _cost()

    def fn(v):
        def body(c, _):
            return c * 2.0, c.sum()

        _, ys = jax.lax.scan(body, v, None, length=8)
        return ys

    cj = jax.make_jaxpr(fn)(jnp.ones(4, jnp.float32))
    assert cost.total_buffer_bytes(cj) == 84
    assert cost.peak_live_bytes(cj) == 84
    assert cost._jaxpr_flops(cj.jaxpr) == 64.0  # (4 mul + 4 sum) x 8


def test_fit_and_projection_are_exact_on_power_laws():
    cost = _cost()
    assert cost.fit_exponent([2, 4, 8], [4, 16, 64]) == pytest.approx(2.0)
    assert cost.fit_exponent([2, 8], [6, 24]) == pytest.approx(1.0)
    # projection anchors at the largest measured point
    assert cost.project_bytes([2, 4], [8, 32], 2.0, 8) == pytest.approx(128.0)


def test_peak_live_upper_bounds_xla_temp_allocation():
    """Cross-check against the HLO machinery the LTE kernel tests use:
    the abstract liveness walk assumes zero fusion, so it must never
    report LESS than what XLA actually allocates for temps."""
    cost = _cost()

    def fn(x):
        a = jnp.sin(x)
        b = a * x
        return b.sum()

    x = jnp.ones((256,), jnp.float32)
    compiled = jax.jit(fn).lower(x).compile()
    analysis = compiled.memory_analysis()
    if analysis is None:  # pragma: no cover - backend-dependent
        return
    cj = jax.make_jaxpr(fn)(x)
    assert cost.peak_live_bytes(cj) >= analysis.temp_size_in_bytes


def test_wired_scale_report_projects_the_csr_worklist():
    """ISSUE acceptance: the --cost report fits the wired dense
    one-hot step kernel at >= 2.0 in the joint (links, packets) axis
    and projects its bytes at 1e5/1e6 nodes — the ROADMAP item-2
    worklist."""
    from tpudes.analysis.jaxpr.cost import scale_report
    from tpudes.parallel import wired

    # restrict the manifest to the joint axis: the n_links/n_flows
    # marginals are already fitted (and held linear) by the lint in
    # test_real_manifest_lints_clean_modulo_baseline[wired]
    man = wired.trace_manifest()
    base = man.variants()[0]
    entries = [
        dataclasses.replace(
            e,
            scale_axes=tuple(
                a for a in e.scale_axes if a.name == "n_nodes"
            ),
        )
        for e in base.build()
    ]
    slim = dataclasses.replace(
        man, variants=lambda: [TraceVariant("base", lambda: entries)]
    )
    rep = scale_report(manifests=[(slim, 0)])
    assert rep["worklist"] == ["wired/advance:n_nodes"]
    (quad,) = [r for r in rep["entries"] if r["axis"] == "n_nodes"]
    assert quad["mem_exponent"] >= 1.99
    assert quad["over_budget"] and not quad["dead"]
    proj = quad["projected"]
    assert set(proj) == {"1e5_nodes", "1e6_nodes"}
    assert proj["1e6_nodes"]["bytes"] > proj["1e5_nodes"]["bytes"] > 0
    assert proj["1e6_nodes"]["human"].endswith("iB")
