"""NIST error-rate + interference kernel validation.

Mirrors upstream's wifi-error-rate-models-test.cc strategy: known-SNR
spot checks against the float64 closed-form oracle, monotonicity in SNR,
and frame-level PER with deterministic interference layouts."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudes.ops import wifi_error as WE
from tpudes.ops import interference as I


def test_modes_registry_shape():
    assert len(WE.ALL_MODES) == 20
    m = WE.MODES_BY_NAME["OfdmRate54Mbps"]
    assert m.constellation == 64 and m.rate_class == WE.RATE_3_4
    assert WE.MODES_BY_NAME["OfdmRate6Mbps"].data_rate_bps == 6_000_000


@pytest.mark.parametrize("mode_name", ["OfdmRate6Mbps", "OfdmRate12Mbps", "OfdmRate24Mbps", "OfdmRate54Mbps", "VhtMcs9", "HeMcs11"])
def test_kernel_matches_float64_oracle(mode_name):
    m = WE.MODES_BY_NAME[mode_name]
    for snr_db in [2.0, 8.0, 15.0, 25.0, 35.0]:
        snr = 10 ** (snr_db / 10)
        nbits = 12000.0
        want = WE.chunk_success_rate_py(snr, nbits, m.constellation, m.rate_class)
        got = float(
            WE.mode_chunk_success_rate(
                jnp.float32(snr), jnp.float32(nbits), jnp.int32(m.index)
            )
        )
        assert got == pytest.approx(want, abs=2e-3), (mode_name, snr_db)


def test_success_monotone_in_snr():
    snr = 10 ** (jnp.linspace(-2.0, 35.0, 100) / 10.0)
    succ = np.asarray(
        WE.chunk_success_rate(snr, 8000.0, jnp.float32(64), jnp.int32(WE.RATE_3_4))
    )
    assert np.all(np.diff(succ) >= -1e-6)
    assert succ[0] < 1e-3 and succ[-1] > 0.999


def test_higher_order_modulation_needs_more_snr():
    # at a mid SNR, BPSK1/2 succeeds where 64QAM3/4 fails
    snr = jnp.float32(10 ** (8.0 / 10))
    bpsk = float(WE.chunk_success_rate(snr, 4000.0, jnp.float32(2), jnp.int32(WE.RATE_1_2)))
    qam64 = float(WE.chunk_success_rate(snr, 4000.0, jnp.float32(64), jnp.int32(WE.RATE_3_4)))
    assert bpsk > 0.99 and qam64 < 0.05


@pytest.mark.slow  # ISSUE-21 tier-1 budget: runs in CI's slow-overflow step
def test_vmap_over_modes_and_snr_grid():
    snr = 10 ** (jnp.linspace(0, 30, 16) / 10)
    modes = jnp.arange(len(WE.ALL_MODES), dtype=jnp.int32)
    grid = jax.vmap(
        lambda mi: WE.mode_chunk_success_rate(snr, 8000.0, mi)
    )(modes)
    assert grid.shape == (20, 16)
    assert bool(jnp.all((grid >= 0) & (grid <= 1)))


# --- interference chunking -------------------------------------------------


def _mk_frame(signal_dbm=-60.0, noise_dbm=-93.97, k=4):
    signal_w = 10 ** ((signal_dbm - 30) / 10)
    noise_w = 10 ** ((noise_dbm - 30) / 10)
    return dict(
        signal_w=jnp.float32(signal_w),
        frame_start=jnp.float32(0.0),
        frame_end=jnp.float32(1e-3),
        mode_index=jnp.int32(WE.MODES_BY_NAME["OfdmRate6Mbps"].index),
        data_rate_bps=jnp.float32(6e6),
        noise_w=jnp.float32(noise_w),
        int_power_w=jnp.zeros(k, jnp.float32),
        int_start=jnp.zeros(k, jnp.float32),
        int_end=jnp.zeros(k, jnp.float32),
        int_mask=jnp.zeros(k, jnp.float32),
    )


def test_clean_frame_matches_single_chunk():
    f = _mk_frame()
    got = float(I.frame_success_rate(**f))
    snr = float(f["signal_w"] / f["noise_w"])
    want = WE.chunk_success_rate_py(snr, 6e6 * 1e-3, 2, WE.RATE_1_2)
    assert got == pytest.approx(want, rel=1e-3)


def test_strong_interferer_kills_frame():
    f = _mk_frame()
    f["int_power_w"] = f["int_power_w"].at[0].set(float(f["signal_w"]))  # 0 dB SIR
    f["int_start"] = f["int_start"].at[0].set(0.0)
    f["int_end"] = f["int_end"].at[0].set(1e-3)
    f["int_mask"] = f["int_mask"].at[0].set(1.0)
    got = float(I.frame_success_rate(**f))
    assert got < 1e-3


def test_partial_overlap_product_of_chunks():
    # interferer covers half the frame: success = clean(half) * hit(half)
    f = _mk_frame(signal_dbm=-70.0)
    f["int_power_w"] = f["int_power_w"].at[0].set(float(f["signal_w"]) / 10)
    f["int_start"] = f["int_start"].at[0].set(0.5e-3)
    f["int_end"] = f["int_end"].at[0].set(1e-3)
    f["int_mask"] = f["int_mask"].at[0].set(1.0)
    got = float(I.frame_success_rate(**f))

    snr_clean = float(f["signal_w"] / f["noise_w"])
    snr_hit = float(f["signal_w"] / (f["noise_w"] + f["signal_w"] / 10))
    nbits_half = 6e6 * 0.5e-3
    want = WE.chunk_success_rate_py(snr_clean, nbits_half, 2, WE.RATE_1_2) * \
        WE.chunk_success_rate_py(snr_hit, nbits_half, 2, WE.RATE_1_2)
    assert got == pytest.approx(want, rel=5e-3)


def test_padding_interferers_are_inert():
    f = _mk_frame()
    clean = float(I.frame_success_rate(**f))
    # garbage in padded slots must not change the result
    f["int_power_w"] = jnp.full_like(f["int_power_w"], 1.0)
    f["int_start"] = jnp.full_like(f["int_start"], 0.2e-3)
    f["int_end"] = jnp.full_like(f["int_end"], 0.9e-3)
    # mask stays 0
    got = float(I.frame_success_rate(**f))
    assert got == pytest.approx(clean, rel=1e-6)


def test_batched_frames_jit():
    f = _mk_frame()
    batch = {k: jnp.broadcast_to(v, (32,) + v.shape) for k, v in f.items()}
    out = jax.jit(I.batch_frame_success_rate)(**batch)
    assert out.shape == (32,)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_thermal_noise():
    # -94 dBm for 20 MHz at 7 dB noise figure (the classic 802.11 floor)
    n = I.thermal_noise_w(20e6, noise_figure_db=7.0)
    dbm = 10 * math.log10(n) + 30
    assert dbm == pytest.approx(-93.97, abs=0.1)


def test_nist_qam_ber_reference_values():
    """Upstream NIST closed forms (ADVICE r1 high): 16-QAM BER is
    0.375*erfc(sqrt(snr/10)) — no extra 1/2 factor; same family for
    64/256-QAM.  Checks both the jnp kernel and the f64 oracle."""
    snr = 10.0  # 10 dB linear
    want16 = 0.375 * math.erfc(math.sqrt(snr / 10.0))
    got16 = float(WE.uncoded_ber(jnp.asarray(snr), jnp.asarray(16.0)))
    assert got16 == pytest.approx(want16, rel=1e-5)

    # 64-QAM: upstream Get64QamBer uses z = sqrt(snr/(7*3)) = sqrt(snr/21)
    # (ADVICE r2 medium — NOT the generic sqrt(snr/42)); prefactor 7/24
    want64 = (7.0 / 24.0) * math.erfc(math.sqrt(snr / 21.0))
    got64 = float(WE.uncoded_ber(jnp.asarray(snr), jnp.asarray(64.0)))
    assert got64 == pytest.approx(want64, rel=1e-5)

    # 256-QAM: z = sqrt(snr/60), prefactor 15/64; 1024-QAM: z =
    # sqrt(snr/155), prefactor 31/160
    want256 = (15.0 / 64.0) * math.erfc(math.sqrt(snr / 60.0))
    got256 = float(WE.uncoded_ber(jnp.asarray(snr), jnp.asarray(256.0)))
    assert got256 == pytest.approx(want256, rel=1e-5)
    want1024 = (31.0 / 160.0) * math.erfc(math.sqrt(snr / 155.0))
    got1024 = float(WE.uncoded_ber(jnp.asarray(snr), jnp.asarray(1024.0)))
    assert got1024 == pytest.approx(want1024, rel=1e-5)
    # f64 oracle and jnp kernel agree end-to-end on every QAM order
    for m in (16, 64, 256, 1024):
        oracle = WE.chunk_success_rate_py(snr, 4000.0, m, WE.RATE_3_4)
        kernel = float(
            WE.chunk_success_rate(
                jnp.asarray(snr), jnp.asarray(4000.0), jnp.asarray(float(m)),
                jnp.asarray(WE.RATE_3_4),
            )
        )
        assert kernel == pytest.approx(oracle, rel=2e-3)

    # the f64 oracle must produce the success rate implied by the fixed
    # closed form end-to-end (catches a re-introduced 0.5 factor)
    nbits = 1000.0
    p = min(max(want16, 0.0), 0.5)
    dd = math.sqrt(4.0 * p * (1.0 - p))
    pe = WE.B_FACTOR_TABLE[WE.RATE_1_2] * sum(
        c * dd**e
        for c, e in zip(WE.PE_COEFFS_TABLE[WE.RATE_1_2], WE.PE_EXPONENTS_TABLE[WE.RATE_1_2])
        if c > 0
    )
    want_sr = math.exp(nbits * math.log1p(-min(pe, 1.0 - 1e-12)))
    got_sr = WE.chunk_success_rate_py(snr, nbits, 16, WE.RATE_1_2)
    assert got_sr == pytest.approx(want_sr, rel=1e-9)
    # and the jnp kernel must agree with the oracle
    got_kernel = float(WE.chunk_success_rate(
        jnp.asarray(snr), jnp.asarray(nbits), jnp.asarray(16.0), jnp.asarray(WE.RATE_1_2)))
    assert got_kernel == pytest.approx(got_sr, rel=1e-4)


def test_bpsk_qpsk_ber_reference_values():
    snr = 4.0
    assert float(WE.uncoded_ber(jnp.asarray(snr), jnp.asarray(2.0))) == pytest.approx(
        0.5 * math.erfc(math.sqrt(snr)), rel=1e-5)
    assert float(WE.uncoded_ber(jnp.asarray(snr), jnp.asarray(4.0))) == pytest.approx(
        0.5 * math.erfc(math.sqrt(snr / 2.0)), rel=1e-5)
