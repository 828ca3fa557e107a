"""Plain reference of the full-buffer LTE downlink deployment (`kind: lte_sm`).

A straightforward float64 numpy TTI loop over a handful of replicas: Friis gain
from the configuration's own positions, strongest-cell attachment, flat SINR under
full load, the PiroEW2010 CQI mapping, one PF winner per cell per TTI, one HARQ-IR
process per UE (8-TTI round trip, 4 transmissions), a Gaussian-waterfall block
error rate around the code rate.  It imports nothing of `tpudes` and takes nothing
the program made: the tables below are the public 3GPP TS 36.213 values and the
constants of the error model the program documents in its own docstrings.

`compare` reduces what the timed path returned (every launch of the window) and
this loop's output to the numbers that decide `correct`.
"""

from __future__ import annotations

import math

import numpy as np
from scipy.special import erfc

RB_BANDWIDTH_HZ = 180e3
RE_PER_RB_DATA = 120.0
BOLTZMANN_T = 1.380649e-23 * 290.0
SPEED_OF_LIGHT = 299792458.0

#: TS 36.213 table 7.2.3-1: CQI index -> bits per resource element
CQI_EFFICIENCY = np.array([
    0.0, 0.1523, 0.2344, 0.3770, 0.6016, 0.8770, 1.1758, 1.4766,
    1.9141, 2.4063, 2.7305, 3.3223, 3.9023, 4.5234, 5.1152, 5.5547,
])
#: MCS 0..28: bits per resource element, between the CQI anchor points
MCS_EFFICIENCY = np.array([
    0.1523, 0.1943, 0.2344, 0.3008, 0.3770, 0.4385, 0.5879, 0.7402,
    0.9023, 1.0273,
    1.1758, 1.3262, 1.4766, 1.6953, 1.9141, 2.1602, 2.4063,
    2.5703, 2.7305, 3.0293, 3.3223, 3.6094, 3.9023, 4.2129, 4.5234,
    4.8193, 5.1152, 5.3320, 5.5547,
])
MCS_QM = np.array([2.0] * 10 + [4.0] * 7 + [6.0] * 12)
#: SNR gap of the CQI mapping at a target bit error rate of 5e-5
SNR_GAP = -math.log(5.0 * 5e-5) / 1.5
BLER_DISPERSION = 1.4
BLER_TARGET_Q = 1.281551


def rbg_size_for(n_rb: int) -> int:
    """TS 36.213 table 7.1.6.1-1, type-0 resource block groups."""
    if n_rb <= 10:
        return 1
    if n_rb <= 26:
        return 2
    if n_rb <= 63:
        return 3
    return 4


def link_budget(cfg: dict) -> dict:
    """Positions -> Friis gain -> serving cell -> SINR -> CQI -> MCS tables."""
    ph, topo = cfg["physics"], cfg["topology"]
    enb = np.asarray(topo["enb_positions"], float)
    ue = np.asarray(topo["ue_positions"], float)
    d = np.sqrt(((enb[:, None, :] - ue[None, :, :]) ** 2).sum(-1))  # (E, U)
    lam = SPEED_OF_LIGHT / ph["carrier_hz"]
    loss_db = -10.0 * np.log10(
        lam * lam / (16.0 * math.pi ** 2 * d * d * ph["system_loss"])
    )
    gain = 10.0 ** (-np.maximum(loss_db, ph["min_loss_db"]) / 10.0)
    serving = np.argmax(gain, axis=0)
    n_rb = int(ph["n_rb"])
    psd = 10.0 ** ((ph["enb_tx_power_dbm"] - 30.0) / 10.0) / (
        n_rb * RB_BANDWIDTH_HZ
    )
    seen = psd * gain
    sig = seen[serving, np.arange(ue.shape[0])]
    noise = 10.0 ** (ph["ue_noise_figure_db"] / 10.0) * BOLTZMANN_T
    sinr = sig / (seen.sum(axis=0) - sig + noise)
    se = np.log2(1.0 + sinr / SNR_GAP)
    cqi = ((CQI_EFFICIENCY[None, 1:] <= se[:, None])).sum(axis=1)
    mcs = np.array([
        max([m for m in range(29) if MCS_EFFICIENCY[m] <= CQI_EFFICIENCY[c]]
            or [0])
        for c in cqi
    ])
    qm = MCS_QM[mcs]
    eff = MCS_EFFICIENCY[mcs]
    return dict(
        gain=gain, serving=serving, sinr=sinr, cqi=cqi, mcs=mcs,
        eff=eff, ecr=eff / qm, mi=np.minimum(se, qm) / qm,
        eligible=cqi >= 1, n_rb=n_rb,
    )


def tb_bler(mi: np.ndarray, ecr: np.ndarray, tb_bits: np.ndarray) -> np.ndarray:
    sigma = BLER_DISPERSION / np.sqrt(np.maximum(tb_bits, 24.0))
    z = (mi - (ecr - BLER_TARGET_Q * sigma)) / sigma
    return np.clip(0.5 * erfc(z / math.sqrt(2.0)), 0.0, 1.0)


def simulate(cfg: dict, horizon_s: float, replicas: int, seed: int) -> dict:
    """`replicas` independent runs of `horizon_s` simulated seconds; returns
    per-replica per-UE counters under the program's field names."""
    ph = cfg["physics"]
    if ph["scheduler"] != "pf":
        raise ValueError("the reference schedules proportional-fair only")
    lb = link_budget(cfg)
    n_rb, rbg = lb["n_rb"], rbg_size_for(lb["n_rb"])
    n_rbg = (n_rb + rbg - 1) // rbg
    alpha, rtt, max_tx = ph["pf_alpha"], ph["harq_rtt_ttis"], ph["harq_max_tx"]
    n_ttis = int(round(horizon_s * 1000.0))
    R, U = int(replicas), lb["serving"].shape[0]
    cells = [np.flatnonzero(lb["serving"] == c)
             for c in range(int(lb["serving"].max()) + 1)]
    eff, ecr, mi0 = lb["eff"][None, :], lb["ecr"][None, :], lb["mi"][None, :]
    elig = np.broadcast_to(lb["eligible"][None, :], (R, U))
    rate0 = np.floor(lb["eff"] * rbg * RE_PER_RB_DATA) * 1000.0
    rng = np.random.default_rng(seed)
    rows = np.arange(R)

    avg = np.ones((R, U))
    pend = np.zeros((R, U), bool)
    p_mi, p_tbb = np.zeros((R, U)), np.zeros((R, U))
    p_nrbg = np.zeros((R, U), np.int64)
    p_txc = np.zeros((R, U), np.int64)
    p_due = np.zeros((R, U), np.int64)
    rx_bits = np.zeros((R, U))
    new_tbs, retx, drops, ok_cnt = (np.zeros((R, U), np.int64) for _ in range(4))

    for t in range(n_ttis):
        # retransmissions that are due, admitted in UE order up to the grid
        due = pend & (p_due <= t) & elig
        req = np.where(due, p_nrbg, 0)
        retx_fit = np.zeros((R, U), bool)
        new_nrbg = np.zeros((R, U), np.int64)
        is_winner = np.zeros((R, U), bool)
        metric = np.where(elig & ~pend, rate0[None, :] / np.maximum(avg, 1.0),
                          -np.inf)
        for idx in cells:
            fit = due[:, idx] & (np.cumsum(req[:, idx], axis=1) <= n_rbg)
            retx_fit[:, idx] = fit
            rem = n_rbg - np.where(fit, req[:, idx], 0).sum(axis=1)
            m = metric[:, idx]
            best = np.argmax(m, axis=1)          # lowest UE index on ties
            has = (m[rows, best] > -np.inf) & (rem > 0)
            w = idx[best]
            is_winner[rows[has], w[has]] = True
            new_nrbg[rows[has], w[has]] = rem[has]
        new_nrb = np.minimum(new_nrbg * rbg, n_rb)
        tb_new = np.floor(eff * new_nrb * RE_PER_RB_DATA)
        tx = retx_fit | is_winner
        tbb = np.where(retx_fit, p_tbb, tb_new)
        mi = np.where(retx_fit, np.minimum(p_mi + mi0, 1.0), mi0)
        ok = tx & (rng.random((R, U)) >= tb_bler(mi, ecr, tbb))
        fail = tx & ~ok
        txc = np.where(retx_fit, p_txc + 1, 1)
        dropped = fail & (txc >= max_tx)
        repend = fail & ~dropped
        served = np.where(ok, tbb, 0.0)
        avg = (1.0 - alpha) * avg + alpha * served * 1000.0
        pend = (pend & ~retx_fit) | repend
        p_mi = np.where(repend, mi, p_mi)
        p_tbb = np.where(repend, tbb, p_tbb)
        p_nrbg = np.where(repend, np.where(retx_fit, p_nrbg, new_nrbg), p_nrbg)
        p_txc = np.where(repend, txc, p_txc)
        p_due = np.where(repend, t + rtt, p_due)
        rx_bits += served
        new_tbs += is_winner
        retx += retx_fit
        drops += dropped
        ok_cnt += ok
    return dict(rx_bits=rx_bits, new_tbs=new_tbs, retx=retx, drops=drops,
                ok=ok_cnt, cqi=lb["cqi"], n_ttis=n_ttis)


def reference_replicas(traffic: dict) -> int:
    return int(traffic.get("reference_replicas", 8))


def criterion(out: dict) -> str | None:
    """`lena-simple.py`'s own exit criterion restated on the lifted result: None
    where it holds, else what failed."""
    if not np.asarray(out["rx_bits"]).sum() > 0:
        return "aggregate DL Mbps > 0"
    return None


def kpi(out: dict) -> float:
    """Mean delivered megabits per replica (the simulated statistic a
    speed-only change must not move)."""
    return float(np.asarray(out["rx_bits"], float).sum(axis=-1).mean() / 1e6)


def compare(cfg: dict, traffic: dict, outs: list, expected_rows: int,
            seed: int, ref: dict | None = None) -> dict:
    """Numbers that decide `correct`, from every launch the window finished."""
    horizon = float(traffic["horizon_s"])
    if ref is None:
        ref = simulate(cfg, horizon, reference_replicas(traffic), seed)
    n_ttis = ref["n_ttis"]
    rx = np.concatenate([np.asarray(o["rx_bits"], float) for o in outs])
    U = ref["rx_bits"].shape[1]
    live = (rx.sum(axis=1) > 0) if rx.ndim == 2 and rx.shape[1] == U else []
    numbers = {"rows_missing": float(expected_rows - int(np.sum(live)))}
    if not np.any(live):
        return numbers
    dev_u = rx.mean(axis=0) / n_ttis
    ref_u = ref["rx_bits"].mean(axis=0) / n_ttis
    scale = np.maximum(ref_u, np.median(ref_u))
    numbers["ue_rate_gap"] = float(np.max(np.abs(dev_u - ref_u) / scale))
    numbers["agg_rate_gap"] = float(abs(dev_u.sum() - ref_u.sum()) / ref_u.sum())

    def fail_share(o):
        sent = float(np.sum(o["new_tbs"]) + np.sum(o["retx"]))
        return float(np.sum(o["retx"]) + np.sum(o["drops"])) / max(sent, 1.0)

    dev_fail = float(np.mean([fail_share(o) for o in outs]))
    numbers["harq_fail_gap"] = abs(dev_fail - fail_share(ref))
    per_replica = rx.sum(axis=1) / n_ttis
    numbers["replica_gap"] = float(
        np.max(np.abs(per_replica - ref_u.sum())) / ref_u.sum()
    )
    return numbers
