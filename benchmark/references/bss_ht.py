"""Plain reference of the saturated 802.11n BSS (`kind: bss`, `reference: bss_ht`).

A scalar discrete-event loop, one replica at a time, in float64: an AP and N stations
inside mutual sensing range, EDCA contention for one access category (AIFS = SIFS +
AIFSN slots, binary exponential backoff CWmin..CWmax), HT-mixed PPDU timing, A-MPDU
aggregation under an established BlockAck agreement, per-MPDU decode at the PPDU's
SINR, a compressed BlockAck as the response, retransmission of what the BlockAck did
not acknowledge with the retry counted PER MPDU (802.11-2016 10.24.7, ns-3's
BlockAckManager, the repo's host MAC), UDP echo requests upstream with the echoes
queued at the AP, beacons.  It imports nothing of `tpudes` and takes nothing the
program made: positions, physics and every MAC constant come from the configuration
file; the NIST error model's constants are the public NIST/ns-3 ones.  The sibling
`bss.py` is loaded by path for what the two deployments share (rounding for the
controls, the legacy OFDM airtime of BlockAck and beacon, Boltzmann's constant, the
64-QAM divisor, `criterion`, `kpi`).

Departures from upstream ns-3, each also a comment where it happens:
  D1 the BlockAck agreement is taken as established (no ADDBA exchange), association
     and ARP are not modelled, one access category (AC_BE) carries all traffic, the
     beacon too; no TXOP limit (one PPDU per access).
  D2 the medium is one `busy_until` on a 1 us clock; propagation delay is folded
     into the exchange; BlockAcks always decode.
  D3 slots are credited to every waiting contender from the medium's `busy_until`
     (a sender sitting out its BlockAck timeout is credited with them too).
  D4 a backoff drawn after a transmission is discarded when the queue is empty: the
     next frame to arrive takes the medium at once if it has been idle for AIFS,
     else draws anew.
  D5 the AP serves the lowest-numbered station with echoes waiting (upstream's queue
     is first in, first out over all destinations) and aggregates only to it.
  D6 two PPDUs that start in the same microsecond are each decoded at their own
     destination against the other's power (upstream locks onto the first preamble).
  D7 the error integral runs over the whole PPDU airtime, preamble included, at the
     payload rate; each of k subframes takes an equal share: p_mpdu = psr ** (1/k).

Controls and faults, never part of a benchmark run: `precision="matmul_bfloat16"`
rounds the operands of the received-power sum to bfloat16, as a TPU matmul at default
precision does; `"bfloat16"` rounds the whole chain; `retry_limit=0` drops a frame at
its first failure; `max_mpdus=1` sends one MPDU per PPDU (no aggregation).
"""

from __future__ import annotations

import importlib.util
import math
import os

import numpy as np


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location("bench_references_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


legacy = _sibling("bss")

INF = 2 ** 30

#: HT MCS at 20 MHz, one stream, long guard interval: name -> (constellation,
#: bit rate); all that this deployment needs is MCS 7 (64-QAM, rate 5/6)
HT_MODES = {"HtMcs7": (64, 65e6)}
#: K=7 convolutional code punctured to 5/6 (NistErrorRateModel::CalculatePe, b=5):
#: union-bound factor, distance-spectrum weights from free distance 4
PE_FACTOR_5_6 = 1.0 / 10.0
PE_COEFFS_5_6 = [92.0, 528.0, 8694.0, 79453.0, 792114.0, 7375573.0, 67884974.0,
                 610875423.0, 5427275376.0, 47664215639.0]
PE_EXPONENTS_5_6 = [4.0, 5.0, 6.0, 7.0, 8.0, 9.0, 10.0, 11.0, 12.0, 13.0]


def success_rate(snr: float, nbits: float, mode: str, q=float) -> float:
    """NIST chunk success rate (1 - pe)^nbits of `nbits` at linear `snr`."""
    m, _ = HT_MODES[mode]
    if not snr > 0.0:     # a rounded power sum can leave less than the signal
        return 0.0
    z = q(math.sqrt(q(snr / legacy.QAM_DIVISOR[m])))
    ber = q(q(2.0 * (1.0 - 1.0 / math.sqrt(m)) / math.log2(m)) * q(math.erfc(z)))
    p = min(max(ber, 0.0), 0.5)
    d = q(math.sqrt(q(4.0 * p * q(1.0 - p))))
    log_d = q(math.log(max(d, 1e-35)))
    pe = 0.0
    for a, e in zip(PE_COEFFS_5_6, PE_EXPONENTS_5_6):
        pe = q(pe + q(math.exp(q(q(math.log(a)) + q(e * log_d)))))
    pe = min(max(q(PE_FACTOR_5_6 * pe), 0.0), 1.0 - 1e-12)
    return q(math.exp(q(nbits * q(math.log1p(-pe)))))


def ampdu_us(k: int, ph: dict) -> int:
    """Airtime of an HT-mixed PPDU that carries `k` subframes."""
    bits = ph["service_tail_bits"] + 8 * ph["subframe_bytes"] * k
    return ph["preamble_us"] + math.ceil(
        bits / ph["data_bits_per_symbol"]) * ph["symbol_us"]


def link_table(cfg: dict, precision: str = "float64") -> dict:
    """Pairwise received power (W), noise floor and per-frame constants."""
    ph, q = cfg["physics"], legacy._rounder(precision)
    pos = np.asarray(cfg["topology"]["positions"], float)
    n = pos.shape[0]
    d = np.sqrt(((pos[:, None, :] - pos[None, :, :]) ** 2).sum(-1))
    np.fill_diagonal(d, 1.0)
    rx_dbm = ph["tx_power_dbm"] - (
        ph["reference_loss_db"]
        + 10.0 * ph["path_loss_exponent"] * np.log10(np.maximum(d, 1.0))
    )
    rx_w = [[0.0 if i == j else q(10.0 ** ((rx_dbm[i, j] - 30.0) / 10.0))
             for j in range(n)] for i in range(n)]
    noise_w = q(10.0 ** (ph["noise_figure_db"] / 10.0)
                * legacy.BOLTZMANN * 290.0 * ph["bandwidth_hz"])
    subframe = (ph["delimiter_bytes"] + ph["mac_header_bytes"]
                + ph["llc_ip_udp_bytes"] + ph["packet_bytes"] + ph["fcs_bytes"])
    subframe = (subframe + 3) // 4 * 4          # padded to 4 bytes
    if subframe != ph["subframe_bytes"]:
        raise ValueError(f"subframe_bytes {ph['subframe_bytes']}, parts give {subframe}")
    return dict(
        n=n, rx_w=rx_w, noise_w=noise_w, q=q, mode=ph["data_mode"],
        operand=legacy._bf16 if precision == "matmul_bfloat16" else float,
        detectable=(rx_dbm >= ph["rx_sensitivity_dbm"]).tolist(),
        max_mpdus=max(1, min(ph["block_ack_window"],
                             ph["max_ampdu_bytes"] // ph["subframe_bytes"])),
        resp_dur=legacy.ppdu_us(ph["block_ack_bytes"], ph["ack_mode"]),
        beacon_dur=legacy.ppdu_us(ph["beacon_bytes"], ph["beacon_mode"]),
        psr={},                 # (tx, rx, k) -> success rate of a PPDU sent alone
    )


def _arrivals(cfg: dict, horizon_us: int) -> list:
    """Every application arrival (and beacon timer) before the horizon, in time
    order: (time, node); node 0 is the AP and its arrivals are beacons."""
    ph, n = cfg["physics"], len(cfg["topology"]["positions"])
    out = [(t, 0) for t in range(0, horizon_us, int(ph["beacon_interval_us"]))]
    for i in range(1, n):
        first = int(ph["client_start_us"] + ph["client_stagger_us"] * (i - 1))
        out += [(t, i) for t in range(first, horizon_us, int(ph["interval_us"]))]
    out.sort()
    return out


def simulate_one(cfg: dict, link: dict, horizon_us: int, rng,
                 retry_limit: int | None = None,
                 max_mpdus: int | None = None) -> dict:
    """One replica: returns the program's per-replica counters."""
    ph = cfg["physics"]
    n, q, rx_w, noise_w = link["n"], link["q"], link["rx_w"], link["noise_w"]
    slot, sifs = int(ph["slot_us"]), int(ph["sifs_us"])
    aifs = sifs + int(ph["aifsn"]) * slot
    cw_min, cw_max = int(ph["cw_min"]), int(ph["cw_max"])
    limit = int(ph["retry_limit"]) if retry_limit is None else retry_limit
    cap = link["max_mpdus"] if max_mpdus is None else max_mpdus
    rate_per_us = HT_MODES[link["mode"]][1] * 1e-6
    resp_dur, detectable = link["resp_dur"], link["detectable"]
    arrivals = _arrivals(cfg, horizon_us) + [(INF, 0)]
    ptr = 0
    # a queued MPDU is its retry count; head of line first
    queue = [[] for _ in range(n)]      # requests waiting at each station
    echo = [[] for _ in range(n)]       # echoes waiting at the AP, per station
    bcn_pend = 0
    waiting = np.zeros(n, np.int64)     # frames at each node (AP: beacons + echoes)
    backoff = np.zeros(n, np.int64)
    hold = np.zeros(n, np.int64)
    cw = np.full(n, cw_min, np.int64)
    immediate = np.zeros(n, bool)
    busy_until = t = 0
    srv_rx = tx_data = tx_mpdus = drops = 0
    cli_rx = [0] * n
    tx_t, stale = None, True

    def draw(i):
        return int(rng.random() * (cw[i] + 1))

    while True:
        if stale:
            base = np.maximum(busy_until, hold)
            when = np.where(immediate, np.maximum(t, base),
                            base + aifs + backoff * slot)
            tx_t = np.where(waiting > 0, np.maximum(when, t), INF)
            stale = False
        tc, ta = int(tx_t.min()), arrivals[ptr][0]
        nxt = min(ta, tc)
        if nxt >= horizon_us:
            break
        if ta <= tc:
            # application arrivals (and the beacon timer) at this instant
            idle = nxt >= busy_until + aifs
            while arrivals[ptr][0] == nxt:
                i = arrivals[ptr][1]
                ptr += 1
                if i == 0:
                    bcn_pend += 1
                else:
                    queue[i].append(0)
                waiting[i] += 1
                if waiting[i] == 1:          # became head of line (D4)
                    immediate[i] = idle
                    if idle:
                        tx_t[i] = max(nxt, busy_until, hold[i])
                    else:
                        backoff[i] = draw(i)
                        tx_t[i] = (max(busy_until, hold[i]) + aifs
                                   + backoff[i] * slot)
            t = nxt
            continue
        winners = np.flatnonzero(tx_t == nxt).tolist()
        # D3: slots since the medium went idle, credited to all who wait
        elapsed = max((nxt - busy_until - aifs) // slot, 0)
        others = waiting > 0
        others[winners] = False
        for i in np.flatnonzero(others & immediate).tolist():
            backoff[i] = draw(i)   # a zero-backoff grant cut short draws anew
        counting = others & ~immediate
        backoff[counting] = np.maximum(backoff[counting] - elapsed, 0)
        immediate[others] = False
        beacon = 0 in winners and bcn_pend > 0
        occupancy = 0
        for i in winners:
            if i == 0 and beacon:
                bcn_pend -= 1
                waiting[0] -= 1
                cw[0] = cw_min
                occ = link["beacon_dur"]
                hold[0] = nxt + occ
            else:
                # D5: the lowest-numbered station with echoes waiting
                dst = next(j for j in range(n) if echo[j]) if i == 0 else 0
                mine = echo[dst] if i == 0 else queue[i]
                k = max(min(len(mine), cap), 1)
                dur = ampdu_us(k, ph)
                exch = dur + sifs + resp_dur
                tx_data += 1
                tx_mpdus += k
                if len(winners) == 1 and (i, dst, k) in link["psr"]:
                    psr = link["psr"][i, dst, k]
                else:
                    total = 0.0
                    for w in winners:
                        total = q(total + link["operand"](rx_w[w][dst]))
                    sig = rx_w[i][dst]
                    sinr = q(sig / q(noise_w + q(total - sig)))
                    # D7: the whole airtime at the payload rate
                    psr = success_rate(sinr, rate_per_us * dur, link["mode"], q)
                    if len(winners) == 1:
                        link["psr"][i, dst, k] = psr
                heard = detectable[i][dst] and dst not in winners     # D6
                p_mpdu = psr ** (1.0 / k) if heard else 0.0
                acked = rng.random(k) < p_mpdu
                n_ok = int(acked.sum())
                # what the BlockAck did not acknowledge stays at the head, its
                # own retry count one higher; past the limit it is dropped
                kept = [r + 1 for r, ok in zip(mine[:k], acked)
                        if not ok and r + 1 <= limit]
                lost = k - n_ok - len(kept)
                mine[:k] = kept
                drops += lost
                waiting[i] -= n_ok + lost
                if i == 0:
                    cli_rx[dst] += n_ok
                else:
                    srv_rx += n_ok
                    echo[i] += [0] * n_ok
                    waiting[0] += n_ok
                if n_ok:
                    cw[i] = cw_min
                    occ = exch
                    hold[i] = nxt + occ
                else:
                    # no BlockAck comes: the medium frees after the PPDU, the
                    # sender sits out its BlockAck timeout
                    occ = dur
                    hold[i] = nxt + exch + slot + 4
                    cw[i] = min(2 * (cw[i] + 1) - 1, cw_max) if kept else cw_min
            backoff[i] = draw(i)
            immediate[i] = False
            occupancy = max(occupancy, occ)
        busy_until = nxt + occupancy
        t = nxt
        stale = True
    return dict(srv_rx=srv_rx, cli_rx=cli_rx, tx_data=tx_data, drops=drops,
                tx_mpdus=tx_mpdus)


def simulate(cfg: dict, horizon_s: float, replicas: int, seed: int,
             precision: str = "float64", retry_limit: int | None = None,
             max_mpdus: int | None = None) -> dict:
    link = link_table(cfg, precision)
    rng = np.random.default_rng(seed)
    runs = [simulate_one(cfg, link, int(horizon_s * 1e6), rng, retry_limit,
                         max_mpdus)
            for _ in range(int(replicas))]
    return dict(
        {k: np.array([r[k] for r in runs])
         for k in ("srv_rx", "cli_rx", "tx_data", "drops", "tx_mpdus")},
        all_done=True,
    )


reference_replicas = legacy.reference_replicas
criterion = legacy.criterion        # the same script, the same exit criterion
kpi = legacy.kpi


def compare(cfg: dict, traffic: dict, outs: list, expected_rows: int,
            seed: int, ref: dict | None = None) -> dict:
    """Numbers that decide `correct`, from every launch the window finished; the
    legacy deployment's, but for the scale of `sta_echo_gap`: here the typical
    station gets no echo at all (D5: the AP never reaches the higher-numbered ones),
    so a station's mean echo count is held against what it offers in the horizon,
    a difference in its delivery ratio."""
    horizon_s = float(traffic["horizon_s"])
    if ref is None:
        ref = simulate(cfg, horizon_s, reference_replicas(traffic), seed)
    done = [o for o in outs if o["all_done"]]
    n = np.asarray(ref["cli_rx"]).shape[1]
    rows = sum(
        int(np.sum(np.asarray(o["srv_rx"]) > 0)) for o in done
        if np.asarray(o["cli_rx"]).ndim == 2
        and np.asarray(o["cli_rx"]).shape[1] == n
    )
    numbers = {"rows_missing": float(expected_rows - rows)}
    if rows == 0:
        return numbers

    def mean(field):
        return np.concatenate([np.asarray(o[field], float) for o in done]).mean(0)

    def rel(dev, want):
        return float(abs(dev - want) / max(abs(want), 1e-9))

    numbers["srv_rx_gap"] = rel(mean("srv_rx"), ref["srv_rx"].mean())
    offered = np.bincount(
        [i for _, i in _arrivals(cfg, int(horizon_s * 1e6))], minlength=n
    )[1:]
    numbers["sta_echo_gap"] = float(np.max(
        np.abs(mean("cli_rx")[1:] - ref["cli_rx"].mean(axis=0)[1:])
        / np.maximum(offered, 1)
    ))
    ppdus = float(ref["tx_data"].mean())
    numbers["tx_data_gap"] = rel(mean("tx_data"), ppdus)
    numbers["drops_gap"] = float(
        abs(mean("drops") - ref["drops"].mean()) / ppdus
    )
    return numbers
