"""LTE TTI kernels — per-RB SINR, CQI mapping, MI-based TB error model.

Reference parity: src/lte/model/lte-spectrum-phy.{h,cc},
lte-interference.{h,cc}, lte-mi-error-model.{h,cc}, and the CQI
generation in lte-ue-phy / lte-amc (upstream paths; mount empty at
survey — SURVEY.md §0, §2.6, §3.4).  SURVEY.md calls this TTI path "the
most natural Pallas/XLA kernel in the whole reference": everything from
MultiModelSpectrumChannel::StartTx to GetTbDecodificationStats is dense
array math over the RB grid.

TPU-first design: one jitted call per TTI evaluates EVERY cell and UE at
once — (T transmitters × RB) PSDs and (T × U) gains in, per-UE
(SINR, CQI, MI, BLER, decode coin flips) out.  No per-UE Python, no
per-RB loops; the replica axis is one more vmap.

Error-model note (documented deviation): upstream's LteMiErrorModel
interpolates vendor-fit BLER curves (PiroEW2010) from large LUTs that
could not be read (empty mount).  This module uses the same *structure*
— per-RB mutual information → effective MI → TB BLER with HARQ-IR MI
accumulation — with a principled analytic model: normalized MI from
Shannon capacity with the LENA SNR gap Γ = -ln(5·BER)/1.5, and a
finite-blocklength Gaussian waterfall calibrated so a CQI-matched
transport block sees the standard 10 % first-transmission BLER target.
Tests validate the structural properties (monotonicity, waterfall,
HARQ gain, f32↔f64 parity), not bitwise LUT equality.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as _np

# --- constants (3GPP TS 36.211/36.213 public values) -----------------------

RB_BANDWIDTH_HZ = 180e3          # 12 subcarriers × 15 kHz
RE_PER_RB_DATA = 120.0           # ~168 REs/RB/TTI minus PDCCH + RS overhead
TTI_S = 1e-3
BOLTZMANN_T = 1.380649e-23 * 290.0

#: TS 36.213 Table 7.2.3-1 — CQI index → spectral efficiency (bits/RE).
#: Index 0 = out of range (not schedulable).
CQI_EFFICIENCY = [
    0.0, 0.1523, 0.2344, 0.3770, 0.6016, 0.8770, 1.1758, 1.4766,
    1.9141, 2.4063, 2.7305, 3.3223, 3.9023, 4.5234, 5.1152, 5.5547,
]

#: Per-MCS spectral efficiency (bits/RE), MCS 0-28, interpolating the
#: TS 36.213 I_TBS ladder between the CQI anchor points; modulation
#: order Qm is 2 (MCS<10), 4 (MCS<17), 6 (MCS≥17).
MCS_EFFICIENCY = [
    # QPSK (0-9)
    0.1523, 0.1943, 0.2344, 0.3008, 0.3770, 0.4385, 0.5879, 0.7402,
    0.9023, 1.0273,
    # 16-QAM (10-16)
    1.1758, 1.3262, 1.4766, 1.6953, 1.9141, 2.1602, 2.4063,
    # 64-QAM (17-28)
    2.5703, 2.7305, 3.0293, 3.3223, 3.6094, 3.9023, 4.2129, 4.5234,
    4.8193, 5.1152, 5.3320, 5.5547,
]
MCS_QM = [2.0] * 10 + [4.0] * 7 + [6.0] * 12
#: effective code rate per MCS: efficiency / modulation order
MCS_ECR = [e / q for e, q in zip(MCS_EFFICIENCY, MCS_QM)]

#: LENA CQI mapping SNR gap Γ = -ln(5·BER)/1.5 at target BER 5e-5
#: (Piro et al., the lte-amc "PiroEW2010" model).
SNR_GAP = -math.log(5.0 * 5e-5) / 1.5

#: Gaussian-waterfall dispersion: σ = DISPERSION/√tb_bits.  The decode
#: margin is set so a CQI-matched TB has 10 % first-tx BLER (the LTE
#: link-adaptation target).
BLER_DISPERSION = 1.4
BLER_TARGET_Q = 1.281551  # Φ⁻¹(0.9): Q(1.2816) = 0.1

# numpy at module scope so importing never pins a JAX backend (same rule
# as ops/wifi_error.py)
_CQI_EFF = _np.array(CQI_EFFICIENCY, dtype=_np.float32)
_MCS_EFF = _np.array(MCS_EFFICIENCY, dtype=_np.float32)
_MCS_QM = _np.array(MCS_QM, dtype=_np.float32)
_MCS_ECR = _np.array(MCS_ECR, dtype=_np.float32)
#: CQI → highest MCS whose efficiency does not exceed the CQI's
_CQI_TO_MCS = _np.array(
    [
        max([m for m in range(29) if MCS_EFFICIENCY[m] <= CQI_EFFICIENCY[c]] or [0])
        for c in range(16)
    ],
    dtype=_np.int32,
)


def noise_psd_w(noise_figure_db: float) -> float:
    """Thermal noise PSD (W/Hz) at the given receiver noise figure."""
    return float(10.0 ** (noise_figure_db / 10.0) * BOLTZMANN_T)


def tbs_bits(mcs, n_rb):
    """Transport-block size in bits for an MCS over n_rb resource blocks
    (efficiency × data REs; the TS 36.213 TBS-table analog)."""
    return jnp.floor(jnp.asarray(_MCS_EFF)[mcs] * n_rb * RE_PER_RB_DATA)


def tbs_bits_py(mcs: int, n_rb: int) -> int:
    return int(MCS_EFFICIENCY[mcs] * n_rb * RE_PER_RB_DATA)


# --- per-TTI SINR ----------------------------------------------------------


def tti_sinr(
    tx_psd_w: jax.Array,   # (T, RB) transmit PSD per transmitter over RBs
    gain: jax.Array,       # (T, U) linear path gain transmitter→receiver
    serving: jax.Array,    # (U,) int32: index into T of each rx's server
    noise_psd: float,
) -> jax.Array:
    """(U, RB) per-RB SINR: serving-cell signal over other-cell
    interference + thermal noise (LteInterference chunk processing,
    dense over the grid; SURVEY.md §3.4).

    Works for downlink (T = eNBs, U = UEs) and uplink (T = UEs, U = eNB
    listening ports) alike — the caller orients the gain matrix.
    """
    seen = tx_psd_w[:, None, :] * gain[:, :, None]        # (T, U, RB)
    total = jnp.sum(seen, axis=0)                         # (U, RB)
    sig = jnp.take_along_axis(seen, serving[None, :, None], axis=0)[0]
    return sig / (total - sig + noise_psd)


def tti_sinr_py(tx_psd_w, gain, serving, noise_psd):
    """Float64 scalar-loop oracle for :func:`tti_sinr` (SURVEY.md §4:
    tolerance-based PHY validation)."""
    t, rb = len(tx_psd_w), len(tx_psd_w[0])
    u = len(serving)
    out = [[0.0] * rb for _ in range(u)]
    for ui in range(u):
        for r in range(rb):
            total = sum(tx_psd_w[ti][r] * gain[ti][ui] for ti in range(t))
            sig = tx_psd_w[serving[ui]][r] * gain[serving[ui]][ui]
            out[ui][r] = sig / (total - sig + noise_psd)
    return out


# --- CQI -------------------------------------------------------------------


def cqi_from_sinr(sinr: jax.Array, dtype=None, surrogate=None) -> jax.Array:
    """Wideband CQI from mean per-RB SINR: spectral efficiency
    log2(1 + SINR/Γ) mapped to the highest CQI the efficiency supports
    (lte-amc CreateCqiFeedbacks, PiroEW2010 mapping).

    ``dtype`` (e.g. ``jnp.bfloat16``) selects the mixed-precision mode:
    the gapped SINR ratio is computed at that precision while the log2
    transcendental and the table comparison stay f32 — the engine's
    compute-in-low/accumulate-in-f32 policy.  The CQI error budget this
    buys is at most ±1 index at efficiency-boundary SINRs
    (tests/test_ops_lte_kernels.py pins it).

    ``surrogate`` (a :class:`tpudes.diff.Surrogacy`, duck-typed — ops
    never imports diff) replaces the 16-level comparison staircase
    with a temperature-controlled sigmoid sum so ``jax.grad`` sees a
    smooth CQI; the return becomes FLOAT (soft index, or the hard
    index straight-through when ``surrogate.ste``).  ``surrogate=None``
    is the identical legacy integer program."""
    x = sinr if dtype is None else sinr.astype(dtype)
    se = jnp.log2((1.0 + x / SNR_GAP).astype(jnp.float32))
    # highest cqi with efficiency <= se
    eff = jnp.asarray(_CQI_EFF)                            # (16,)
    if surrogate is None:
        return jnp.sum(
            (eff[None, :] <= se[..., None]) & (eff[None, :] > 0.0), axis=-1
        )
    hard = jnp.sum(
        ((eff[None, :] <= se[..., None]) & (eff[None, :] > 0.0)).astype(
            jnp.float32
        ),
        axis=-1,
    )
    from tpudes.diff.surrogate import soft_staircase  # lazy: diff is optional

    soft = soft_staircase(
        se, _CQI_EFF[1:], _np.ones(15, _np.float32), surrogate.temp
    )
    return surrogate.blend(hard, soft)


def eff_from_sinr(sinr: jax.Array, surrogate=None) -> jax.Array:
    """Quantized spectral efficiency (bits/RE) the CQI ladder grants at
    this SINR: ``CQI_EFFICIENCY[cqi_from_sinr(sinr)]`` written as a
    staircase so a surrogate can smooth it — the hard point of the
    SINR→CQI→MCS→rate chain the diff engines differentiate through.
    ``surrogate=None`` keeps the exact staircase (zero gradient a.e.)."""
    se = jnp.log2(1.0 + sinr / SNR_GAP)
    steps = _CQI_EFF[1:] - _CQI_EFF[:-1]                   # (15,)
    hard = jnp.sum(
        steps * (se[..., None] >= _CQI_EFF[1:]).astype(jnp.float32),
        axis=-1,
    )
    if surrogate is None:
        return hard
    from tpudes.diff.surrogate import soft_staircase

    soft = soft_staircase(se, _CQI_EFF[1:], steps, surrogate.temp)
    return surrogate.blend(hard, soft)


#: modulation-order ladder anchors: the granted efficiency at which Qm
#: steps 2→4 (first 16-QAM MCS) and 4→6 (first 64-QAM MCS)
_QM_EDGES = _np.array(
    [MCS_EFFICIENCY[10], MCS_EFFICIENCY[17]], dtype=_np.float32
)


def qm_from_eff(eff: jax.Array, surrogate=None) -> jax.Array:
    """Modulation order from granted spectral efficiency: the 2/4/6
    staircase at the 16-QAM/64-QAM boundary efficiencies (the
    ``MCS_QM`` ladder as a function of efficiency instead of an
    integer MCS gather, so the diff chain can smooth it)."""
    steps = _np.array([2.0, 2.0], _np.float32)
    hard = 2.0 + jnp.sum(
        steps * (eff[..., None] >= _QM_EDGES).astype(jnp.float32), axis=-1
    )
    if surrogate is None:
        return hard
    from tpudes.diff.surrogate import soft_staircase

    soft = 2.0 + soft_staircase(eff, _QM_EDGES, steps, surrogate.temp)
    return surrogate.blend(hard, soft)


def decode_ok(coin: jax.Array, bler: jax.Array, surrogate=None) -> jax.Array:
    """TB decode indicator: the hard threshold ``coin >= bler`` (what
    :func:`tti_phy_step` wires in — bit-identical legacy trace at
    ``surrogate=None``), or its temperature-smoothed sigmoid so a
    SAMPLED-decode diff program keeps gradients flowing through the
    BLER waterfall instead of dying at the comparison.  (The
    expected-KPI chain in :mod:`tpudes.diff.lte_grad` needs no coin at
    all — its decode expectation is ``1 − BLER``.)  Returns bool when
    ``surrogate=None``, f32 in [0, 1] otherwise."""
    if surrogate is None:
        return coin >= bler
    hard = (coin >= bler).astype(jnp.float32)
    from tpudes.diff.surrogate import soft_sigmoid

    soft = soft_sigmoid(coin - bler, surrogate.gate_temp)
    return surrogate.blend(hard, soft)


def cqi_from_sinr_py(sinr: float) -> int:
    se = math.log2(1.0 + sinr / SNR_GAP)
    cqi = 0
    for c in range(1, 16):
        if CQI_EFFICIENCY[c] <= se:
            cqi = c
    return cqi


def mcs_from_cqi(cqi: jax.Array) -> jax.Array:
    return jnp.asarray(_CQI_TO_MCS)[cqi]


def mcs_from_cqi_py(cqi: int) -> int:
    return int(_CQI_TO_MCS[cqi])


# --- MI-based error model --------------------------------------------------


def mi_per_rb(sinr: jax.Array, qm: jax.Array, dtype=None) -> jax.Array:
    """Normalized per-RB mutual information in [0, 1]: gapped Shannon
    capacity capped at the modulation order (the MIESM structure of
    LteMiErrorModel with an analytic MI curve — see module docstring).

    ``dtype`` selects the mixed-precision mode (same policy as
    :func:`cqi_from_sinr`: ratio at ``dtype``, log2 and the final
    normalization in f32)."""
    x = sinr if dtype is None else sinr.astype(dtype)
    cap = jnp.log2((1.0 + x / SNR_GAP).astype(jnp.float32))
    return jnp.minimum(cap, qm) / qm


def erfc(x: jax.Array) -> jax.Array:
    """Complementary error function from ops every lowering has (abs,
    mul/add, one divide, one exp, one select): ``lax.erfc`` has no
    Pallas-TPU (Mosaic) lowering in jax 0.9.0, and the fused TTI kernel
    and its plain-XLA twin must run ONE definition of the BLER tail.

    Chebyshev fit of ``erfc(|x|) = t·exp(-x² + P(t))``, ``t = 1/(1 +
    |x|/2)`` (Numerical Recipes ``erfcc``; fractional error < 1.2e-7 in
    exact arithmetic).  In f32 the absolute error against
    ``math.erfc`` stays below 2^-21 — the same size as XLA's own f32
    ``erfc`` — pinned by tests/test_ops_lte_kernels.py."""
    a = jnp.abs(x)
    t = 1.0 / (1.0 + 0.5 * a)
    poly = -1.26551223 + t * (1.00002368 + t * (0.37409196 + t * (
        0.09678418 + t * (-0.18628806 + t * (0.27886807 + t * (
            -1.13520398 + t * (1.48851587 + t * (
                -0.82215223 + t * 0.17087277))))))))
    tail = t * jnp.exp(poly - a * a)
    return jnp.where(x >= 0.0, tail, 2.0 - tail)


def tb_bler_ecr(
    mi_eff: jax.Array, ecr: jax.Array, tb_bits_: jax.Array, dtype=None
) -> jax.Array:
    """:func:`tb_bler` on a pre-gathered effective code rate — the form
    the fused device kernel uses (its per-UE MCS is static, so the
    table gather happens once at build time instead of per TTI).

    ``dtype`` selects the mixed-precision mode: the waterfall argument
    ``z`` is computed at that precision while the dispersion sqrt and
    the erfc tail stay f32.  The BLER budget this buys is |Δmi| ≤ the
    dtype's half-ulp at 1.0 propagated through the waterfall slope
    (tests/test_ops_lte_kernels.py pins it)."""
    sigma = BLER_DISPERSION / jnp.sqrt(jnp.maximum(tb_bits_, 24.0))
    margin = BLER_TARGET_Q * sigma
    if dtype is None:
        z = (mi_eff - (ecr - margin)) / sigma
    else:
        z = (
            (mi_eff.astype(dtype) - (ecr - margin).astype(dtype))
            / sigma.astype(dtype)
        ).astype(jnp.float32)
    return jnp.clip(0.5 * erfc(z / math.sqrt(2.0)), 0.0, 1.0)


def tb_bler(mi_eff: jax.Array, mcs: jax.Array, tb_bits_: jax.Array) -> jax.Array:
    """TB block-error rate from effective MI: Gaussian waterfall around
    the code rate with finite-blocklength dispersion, margin calibrated
    to 10 % BLER when MI exactly matches the code rate
    (GetTbDecodificationStats analog)."""
    return tb_bler_ecr(mi_eff, jnp.asarray(_MCS_ECR)[mcs], tb_bits_)


def tb_bler_py(mi_eff: float, mcs: int, tb_bits_: float) -> float:
    ecr = MCS_ECR[mcs]
    sigma = BLER_DISPERSION / math.sqrt(max(tb_bits_, 24.0))
    margin = BLER_TARGET_Q * sigma
    z = (mi_eff - (ecr - margin)) / sigma
    return min(max(0.5 * math.erfc(z / math.sqrt(2.0)), 0.0), 1.0)


def mi_eff_py(sinr_rbs, qm: float) -> float:
    if not sinr_rbs:
        return 0.0
    total = 0.0
    for s in sinr_rbs:
        total += min(math.log2(1.0 + s / SNR_GAP), qm) / qm
    return total / len(sinr_rbs)


# --- fused TTI PHY step ----------------------------------------------------


def tti_phy_step(
    tx_psd_w: jax.Array,   # (T, RB) data PSD actually transmitted this TTI
    ref_psd_w: jax.Array,  # (T, RB) full-power reference PSD (RS-like)
    gain: jax.Array,       # (T, U)
    serving: jax.Array,    # (U,) int32
    alloc: jax.Array,      # (U, RB) bool: RBs carrying this UE's TB
    mcs: jax.Array,        # (U,) int32
    tb_bits_: jax.Array,   # (U,) float32 (0 → no TB this TTI)
    mi_acc: jax.Array,     # (U,) float32 accumulated HARQ-IR MI
    key: jax.Array,
    noise_psd: float,
    ref_gain: jax.Array | None = None,  # (T, U) gain for CQI measurement
):
    """One TTI of the LTE PHY for every receiver at once.

    Data decoding uses the PSD actually transmitted this TTI (real
    interference); CQI is measured from ``ref_psd_w``, the full-load
    reference-signal PSD, as upstream UEs measure RS under the
    worst-case all-cells-loaded assumption — otherwise an idle serving
    cell could never report a CQI and an idle interferer would inflate
    one.  ``ref_gain`` (default: ``gain``) lets the CQI measurement see
    a different interference geometry than data decoding — uplink SRS
    sounding is orthogonal within a cell, so the UL caller passes a
    gain matrix with co-served transmitters masked out.

    Returns ``(ok, bler, cqi, mi_new)``:
      ok     (U,) bool — TB decoded this TTI (False where tb_bits==0)
      bler   (U,) float32 — the BLER each draw was taken against
      cqi    (U,) int32 — wideband CQI measured this TTI
      mi_new (U,) float32 — accumulated MI including this transmission
    """
    sinr = tti_sinr(tx_psd_w, gain, serving, noise_psd)    # (U, RB)
    qm = jnp.asarray(_MCS_QM)[mcs]                         # (U,)
    mi_rb = mi_per_rb(sinr, qm[:, None])                   # (U, RB)
    n_alloc = jnp.sum(alloc, axis=1)
    mi_eff = jnp.sum(jnp.where(alloc, mi_rb, 0.0), axis=1) / jnp.maximum(
        n_alloc, 1.0
    )
    mi_new = jnp.minimum(mi_acc + mi_eff, 1.0)             # HARQ-IR cap
    bler = tb_bler(mi_new, mcs, tb_bits_)
    coin = jax.random.uniform(key, bler.shape)
    has_tb = tb_bits_ > 0.0
    ok = has_tb & decode_ok(coin, bler)
    ref_sinr = tti_sinr(
        ref_psd_w, gain if ref_gain is None else ref_gain, serving, noise_psd
    )
    # subband-aware wideband CQI: average only where the serving cell's
    # reference actually transmits (under FFR each cell's RS occupies
    # its subband; averaging silent RBs would report zero-signal CQI)
    ref_on = jnp.take(ref_psd_w > 0.0, serving, axis=0)    # (U, RB)
    n_on = jnp.sum(ref_on, axis=1)
    mean_sinr = jnp.where(
        n_on > 0,
        jnp.sum(jnp.where(ref_on, ref_sinr, 0.0), axis=1)
        / jnp.maximum(n_on, 1),
        jnp.mean(ref_sinr, axis=1),
    )
    cqi = cqi_from_sinr(mean_sinr)
    return ok, bler, cqi, mi_new
