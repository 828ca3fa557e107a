"""HT (802.11n) BSS on the replica axis vs the sequential DES.

The aggregated analog of test_replicated.py: the same saturated HT BSS
(QoS + A-MPDU under BlockAck, HtMcs rates) is run (a) scalar with the
full ADDBA/BA machinery, (b) lowered onto the replica axis where every
data exchange is a backlog-sized A-MPDU with per-MPDU decode.  Parity
is statistical (SURVEY.md §4) on delivered-frame counts.
"""

import jax
import numpy as np
import pytest
from dataclasses import replace

from tpudes.core import Seconds, Simulator
from tpudes.core.rng import RngSeedManager
from tpudes.parallel.replicated import lower_bss, run_replicated_bss

N_STAS = 4
SIM_TIME = 1.6
RADIUS = 16.0      # solid SNR for HtMcs7 — losses come from collisions
#: moderate load — both engines deliver ~the offered traffic (tight pin)
INTERVAL_MODERATE = 0.002
#: deep saturation — 512 B / 0.5 ms per STA (×2 with echoes ≈ 66 Mbps
#: offered) saturates single-MPDU HtMcs7; queues build, A-MPDUs fill
INTERVAL_SATURATED = 0.0005


def _reset_world():
    from tpudes.core.world import reset_world

    reset_world()


def _build_ht_bss(interval=INTERVAL_MODERATE):
    """The shared config-#3 factory in HT trim (one 16 m ring)."""
    from tpudes.scenarios import build_bss

    return build_bss(
        N_STAS, SIM_TIME, radii=(RADIUS,), interval_s=interval,
        data_mode="HtMcs7", standard="80211n",
    )


def _lowered_program(interval=INTERVAL_MODERATE):
    _reset_world()
    sta_devices, ap_device, clients, _ = _build_ht_bss(interval)
    prog = lower_bss(
        [sta_devices.Get(i) for i in range(N_STAS)], ap_device, clients, SIM_TIME
    )
    _reset_world()
    return prog


def _des_counts(interval, runs):
    counts = []
    for run in range(1, runs + 1):
        _reset_world()
        RngSeedManager.SetRun(run)
        _, _, _, rx = _build_ht_bss(interval)
        Simulator.Stop(Seconds(SIM_TIME))
        Simulator.Run()
        counts.append(rx[0])
    _reset_world()
    return np.array(counts, dtype=np.float64)


def test_ht_lowering_fields():
    from tpudes.ops.wifi_error import MODES_BY_NAME

    prog = _lowered_program()
    assert prog.data_mode_idx == MODES_BY_NAME["HtMcs7"].index
    # QoS AC_BE: AIFS = SIFS + 3 slots = 43 µs
    assert prog.aifs_us == 43
    # subframe: delimiter(4) + [512+8+20+8+24] + FCS(4), padded to 4
    assert prog.subframe_bytes == 580
    # 65535 // 580 = 112, capped at the 64-frame BlockAck window
    assert prog.max_mpdus == 64


@pytest.mark.slow  # ISSUE-21 tier-1 budget: runs in CI's slow-overflow step
def test_ht_statistical_parity_moderate_load():
    """At ~70% utilization both engines deliver close to the offered
    load — a tight cross-engine pin of the HT timing + decode path."""
    des = _des_counts(INTERVAL_MODERATE, 5)
    prog = _lowered_program(INTERVAL_MODERATE)
    out = run_replicated_bss(prog, 128, jax.random.PRNGKey(11))
    assert out["all_done"]
    rep = np.asarray(out["srv_rx"], dtype=np.float64)

    offered = N_STAS * int((SIM_TIME - 1.0) / INTERVAL_MODERATE + 1)
    assert 0 < rep.mean() <= offered
    assert 0 < des.mean() <= offered
    assert abs(des.mean() - rep.mean()) <= 0.10 * des.mean() + 2.0, (
        f"DES mean {des.mean():.1f} vs replicated mean {rep.mean():.1f} "
        f"(des {des}, rep std {rep.std():.1f})"
    )


@pytest.mark.slow  # ISSUE-21 tier-1 budget: runs in CI's slow-overflow step
def test_ht_statistical_parity_saturated():
    """Deep saturation: same order of delivered traffic.  The host DES
    has high run-to-run spread here (a collided ADDBA handshake stalls
    that peer's aggregation for ADDBA_RETRY_S = 1 s, i.e. the rest of
    the window), so the pin is deliberately loose — ±35%."""
    des = _des_counts(INTERVAL_SATURATED, 5)
    prog = _lowered_program(INTERVAL_SATURATED)
    out = run_replicated_bss(prog, 128, jax.random.PRNGKey(11))
    assert out["all_done"]
    rep = np.asarray(out["srv_rx"], dtype=np.float64)
    assert abs(des.mean() - rep.mean()) <= 0.35 * des.mean(), (
        f"DES mean {des.mean():.1f} vs replicated mean {rep.mean():.1f} "
        f"(des {des}, rep std {rep.std():.1f})"
    )


def test_aggregation_outperforms_single_mpdu():
    """Under saturation an aggregated BSS must deliver materially more
    than the same scenario forced to single-MPDU exchanges."""
    prog = _lowered_program(INTERVAL_SATURATED)
    agg = run_replicated_bss(prog, 64, jax.random.PRNGKey(3))
    single = run_replicated_bss(
        replace(prog, max_mpdus=1, subframe_bytes=0), 64, jax.random.PRNGKey(3)
    )
    a = float(np.asarray(agg["srv_rx"]).mean())
    s = float(np.asarray(single["srv_rx"]).mean())
    assert a > 1.5 * s, f"aggregated {a:.1f} vs single-MPDU {s:.1f}"


def test_ht_deterministic_and_bounded():
    prog = _lowered_program()
    a = run_replicated_bss(prog, 32, jax.random.PRNGKey(7))
    b = run_replicated_bss(prog, 32, jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(a["srv_rx"]), np.asarray(b["srv_rx"]))
    cli = np.asarray(a["cli_rx"]).sum(axis=1)
    srv = np.asarray(a["srv_rx"])
    assert (cli <= srv).all()
