"""lte_tti_sinr memory-shape regression + the ISSUE-6 mixed-precision
error budget.

Memory shape: the dense (E, U, RB) intermediate was materialized
because the serving-signal ``take_along_axis`` was a SECOND consumer of
it — the fix gathers the serving term directly and contracts the total
over E with one einsum.

Exactness contract (why not plain ``assert_array_equal`` on the whole
kernel): XLA fuses the old form's broadcast-multiply into its reduce
using FMA, so the old total's bits are a property of that one fusion —
no O(U·RB) reformulation (einsum, matmul, sequential or pairwise
re-accumulation; all were measured) reproduces them.  What this file
pins instead:

- the serving-signal term is BIT-exact vs the old gather (same single
  multiply, same rounding);
- the einsum total stays within a 4-ULP envelope of the old form and
  is NO FURTHER from the float64 ground truth than the old form was —
  the drift is re-rounding, not error;
- the compiled program's temp allocation is strictly below the dense
  (E, U, RB) tensor the old form paid.
"""

import jax
import jax.numpy as jnp
import numpy as np

from tpudes.parallel.kernels import lte_tti_sinr


def _dense_reference(tx_psd_w, gain, serving, noise_psd_w):
    """The pre-fix form: materializes the (E, U, RB) seen tensor."""
    seen = tx_psd_w[:, None, :] * gain[:, :, None]
    total = jnp.sum(seen, axis=0)
    sig = jnp.take_along_axis(seen, serving[None, :, None], axis=0)[0]
    return sig / (total - sig + noise_psd_w)


def _scenario(e=7, u=210, rb=100, seed=0):
    rng = np.random.default_rng(seed)
    tx_psd = jnp.asarray(
        rng.uniform(1e-18, 1e-15, size=(e, rb)), jnp.float32
    )
    gain = jnp.asarray(
        rng.uniform(1e-12, 1e-7, size=(e, u)), jnp.float32
    )
    serving = jnp.asarray(rng.integers(0, e, size=(u,)), jnp.int32)
    return tx_psd, gain, serving, 1e-20


def test_serving_signal_term_bit_exact():
    tx_psd, gain, serving, _ = _scenario()

    def new_sig(tx_psd, gain, serving):
        u = jnp.arange(gain.shape[1])
        return tx_psd[serving] * gain[serving, u][:, None]

    def old_sig(tx_psd, gain, serving):
        seen = tx_psd[:, None, :] * gain[:, :, None]
        return jnp.take_along_axis(seen, serving[None, :, None], axis=0)[0]

    np.testing.assert_array_equal(
        np.asarray(jax.jit(new_sig)(tx_psd, gain, serving)),
        np.asarray(jax.jit(old_sig)(tx_psd, gain, serving)),
    )


def _ulp_distance(a: np.ndarray, b: np.ndarray) -> int:
    """Max distance in representable-float steps between f32 arrays."""
    ia = np.asarray(a, np.float32).view(np.int32).astype(np.int64)
    ib = np.asarray(b, np.float32).view(np.int32).astype(np.int64)
    return int(np.abs(ia - ib).max())


def test_total_within_ulp_envelope_and_f64_accuracy():
    for seed, shape in ((0, (7, 210, 100)), (1, (2, 3, 5)), (2, (3, 8, 25))):
        tx_psd, gain, serving, noise = _scenario(*shape, seed=seed)
        new = np.asarray(
            jax.jit(lte_tti_sinr, static_argnums=3)(
                tx_psd, gain, serving, noise
            )
        )
        old = np.asarray(
            jax.jit(_dense_reference, static_argnums=3)(
                tx_psd, gain, serving, noise
            )
        )
        assert _ulp_distance(new, old) <= 4, (
            f"seed {seed}: einsum drifted {_ulp_distance(new, old)} ULP "
            "from the dense form — that is re-rounding no longer, "
            "something changed semantically"
        )
        # float64 oracle: same-order accuracy (the old form's fused
        # FMA skips one rounding, so it can be marginally closer — a
        # 2x envelope distinguishes re-rounding from a real error)
        tx64, g64 = np.asarray(tx_psd, np.float64), np.asarray(gain, np.float64)
        sv = np.asarray(serving)
        seen = tx64[:, None, :] * g64[:, :, None]
        total = seen.sum(axis=0)
        sig = seen[sv, np.arange(g64.shape[1])]
        oracle = sig / (total - sig + noise)
        err_new = np.abs(new - oracle).max()
        err_old = np.abs(old - oracle).max()
        assert err_new <= err_old * 2.0 + 1e-12, (
            f"seed {seed}: new max err {err_new} vs old {err_old}"
        )


def test_peak_memory_has_no_dense_intermediate():
    """The compiled HLO must not allocate an (E, U, RB) buffer: the
    biggest live temp should be O(U·RB)."""
    tx_psd, gain, serving, noise = _scenario()
    e, u = gain.shape
    rb = tx_psd.shape[1]
    compiled = (
        jax.jit(lte_tti_sinr, static_argnums=3)
        .lower(tx_psd, gain, serving, noise)
        .compile()
    )
    analysis = compiled.memory_analysis()
    if analysis is None:  # pragma: no cover - backend-dependent
        return
    dense_bytes = 4 * e * u * rb
    assert analysis.temp_size_in_bytes < dense_bytes, (
        f"temp allocation {analysis.temp_size_in_bytes} B suggests the "
        f"(E,U,RB) intermediate ({dense_bytes} B) is back"
    )


# --- ISSUE-6: the bf16/f32 mixed-precision error budget -----------------
#
# Policy (tpudes/parallel/kernels_pallas.py): PRODUCTS and ratios at
# bf16, every REDUCTION/accumulator and transcendental at f32.  bf16
# keeps f32's 8-bit exponent (the 1e-18 W/Hz PSDs and 1e-12 gains stay
# representable — f16 would flush them to zero) and pays 8 mantissa
# bits, so the budget below is a handful of 2^-8 relative steps.

BF16_EPS = 2.0 ** -8  # half-ulp at 1.0


def test_lte_tti_sinr_bf16_relative_budget():
    """The mixed-precision SINR stays within a few bf16 ulps of the f32
    kernel — products rounded, einsum still f32-accumulating."""
    for seed, shape in ((0, (7, 210, 100)), (3, (3, 24, 25))):
        tx_psd, gain, serving, noise = _scenario(*shape, seed=seed)
        f32 = np.asarray(
            jax.jit(lte_tti_sinr, static_argnums=3)(
                tx_psd, gain, serving, noise
            )
        )
        bf16 = np.asarray(
            jax.jit(
                lambda a, b, c: lte_tti_sinr(
                    a, b, c, noise, dtype=jnp.bfloat16
                )
            )(tx_psd, gain, serving)
        )
        rel = np.abs(bf16 - f32) / np.maximum(np.abs(f32), 1e-30)
        assert rel.max() <= 8 * BF16_EPS, (
            f"seed {seed}: bf16 SINR drifted {rel.max():.2e} rel — "
            "beyond the 8-ulp product-rounding budget"
        )


def test_cqi_bf16_within_one_index():
    """bf16 SINR rounding can flip a CQI only AT an efficiency
    boundary, and only by one index."""
    from tpudes.ops.lte import cqi_from_sinr

    sinr = jnp.asarray(
        np.logspace(-2, 3, 4001, dtype=np.float32)
    )
    f32 = np.asarray(cqi_from_sinr(sinr))
    bf16 = np.asarray(cqi_from_sinr(sinr, dtype=jnp.bfloat16))
    assert np.abs(bf16.astype(int) - f32.astype(int)).max() <= 1
    # and only a small fraction of the sweep sits on a boundary
    assert (bf16 != f32).mean() < 0.05


def test_mi_bf16_budget_and_f32_reduction():
    """Per-RB MI at bf16: |Δmi| bounded by the bf16 half-ulp scaled
    through the log2 slope (the normalized MI lives in [0, 1])."""
    from tpudes.ops.lte import mi_per_rb

    sinr = jnp.asarray(np.logspace(-2, 3, 2001, dtype=np.float32))
    qm = jnp.full_like(sinr, 6.0)
    f32 = np.asarray(mi_per_rb(sinr, qm))
    bf16 = np.asarray(mi_per_rb(sinr, qm, dtype=jnp.bfloat16))
    assert bf16.dtype == np.float32  # the f32-reduction half of the policy
    # d(mi)/d(s) = 1/(qm ln2 (Γ+s)) ≤ ~0.6/Γ per unit s; a relative
    # bf16 step δ·s moves mi by at most δ/(qm ln2) ≈ δ/4.16 — budget 2δ
    assert np.abs(bf16 - f32).max() <= 2 * BF16_EPS


def test_tb_bler_ecr_bf16_budget():
    """BLER at bf16: the waterfall argument z moves by at most the MI
    budget over sigma; pin the resulting BLER band around the 10 %
    design point and exactness far from the cliff."""
    from tpudes.ops.lte import tb_bler_ecr

    ecr = jnp.full((101,), 0.5, jnp.float32)
    tb = jnp.full((101,), 5000.0, jnp.float32)
    mi = jnp.asarray(np.linspace(0.3, 0.7, 101, dtype=np.float32))
    f32 = np.asarray(tb_bler_ecr(mi, ecr, tb))
    bf16 = np.asarray(tb_bler_ecr(mi, ecr, tb, dtype=jnp.bfloat16))
    sigma = 1.4 / np.sqrt(5000.0)
    # max slope of the Gaussian CDF is 1/(sigma*sqrt(2pi))
    budget = 2 * BF16_EPS * 0.7 / (sigma * np.sqrt(2 * np.pi))
    assert np.abs(bf16 - f32).max() <= budget
    # far from the waterfall both saturate (BLER≈1 at MI far below the
    # code rate, ≈0 far above — well past any bf16 perturbation)
    np.testing.assert_allclose(f32[:10], 1.0, atol=1e-12)
    np.testing.assert_allclose(bf16[:10], 1.0, atol=1e-12)
    assert f32[-10:].max() < 1e-12 and bf16[-10:].max() < 1e-12


def test_erfc_within_f32_budget_of_math_erfc():
    """The BLER tail's erfc is built from ops every lowering has (the
    Pallas-TPU lowering has no erf/erfc in jax 0.9.0), shared by the
    fused kernel and its XLA twin.  Its absolute error against
    ``math.erfc`` stays below 2^-21 over the whole waterfall (XLA's own
    f32 erfc is the same size) — orders of magnitude inside the bf16
    BLER band this file budgets, and below the decode coin's 2^-23
    granularity times a handful."""
    import math

    from tpudes.ops.lte import erfc

    x = np.linspace(-6.0, 10.0, 40001).astype(np.float32)
    ref = np.array([math.erfc(float(v)) for v in x])
    got = np.asarray(jax.jit(erfc)(jnp.asarray(x)), np.float64)
    assert np.abs(got - ref).max() <= 2.0 ** -21
    # and relative accuracy where a TB actually decodes (BLER > 1e-6)
    live = ref > 2e-6
    assert (np.abs(got - ref)[live] / ref[live]).max() <= 2e-6
    # through the waterfall: device BLER vs the float64 closed form
    # (z itself is f32 arithmetic: |Δz| ~ 1e-6 at the sweep's edges
    # times the Gaussian slope ≤ 0.4)
    from tpudes.ops.lte import BLER_DISPERSION, BLER_TARGET_Q, tb_bler_ecr

    mi = np.linspace(0.3, 0.7, 101).astype(np.float32)
    dev = np.asarray(
        tb_bler_ecr(
            jnp.asarray(mi), jnp.full((101,), 0.5, jnp.float32),
            jnp.full((101,), 5000.0, jnp.float32),
        ),
        np.float64,
    )
    sigma = BLER_DISPERSION / math.sqrt(5000.0)
    host = np.array([
        0.5 * math.erfc(
            (float(m) - (0.5 - BLER_TARGET_Q * sigma)) / sigma
            / math.sqrt(2.0)
        )
        for m in mi
    ])
    assert np.abs(dev - host).max() <= 5e-6


def test_dtype_none_and_f32_identical():
    """dtype=jnp.float32 must be the EXACT legacy arithmetic — the
    casts are no-ops, not a third rounding mode."""
    from tpudes.ops.lte import cqi_from_sinr, mi_per_rb

    tx_psd, gain, serving, noise = _scenario(3, 24, 25, seed=5)
    np.testing.assert_array_equal(
        np.asarray(lte_tti_sinr(tx_psd, gain, serving, noise)),
        np.asarray(
            lte_tti_sinr(tx_psd, gain, serving, noise, dtype=jnp.float32)
        ),
    )
    sinr = jnp.asarray(np.logspace(-2, 2, 501, dtype=np.float32))
    np.testing.assert_array_equal(
        np.asarray(cqi_from_sinr(sinr)),
        np.asarray(cqi_from_sinr(sinr, dtype=jnp.float32)),
    )
    np.testing.assert_array_equal(
        np.asarray(mi_per_rb(sinr, jnp.full_like(sinr, 4.0))),
        np.asarray(
            mi_per_rb(sinr, jnp.full_like(sinr, 4.0), dtype=jnp.float32)
        ),
    )
