"""Plain reference of the infrastructure-BSS deployment (`kind: bss`).

A scalar discrete-event loop, one replica at a time, in float64: an AP and N
stations inside mutual sensing range, 802.11a DCF (slot 9 us, SIFS 16, DIFS 34,
CW 15..1023, 7 retries), log-distance loss, the NIST OFDM error-rate model, UDP
echo requests upstream with the echo queued at the AP, beacons.  The medium is one
`busy_until`; acknowledgements always decode; association and ARP are not modelled.
It imports nothing of `tpudes` and takes nothing the program made: positions and
physics come from the configuration file, the error-model constants are the public
NIST/ns-3 ones.

Controls, never part of a benchmark run: `precision="bfloat16"` rounds every result
of the received-power / SINR / error-rate chain to bfloat16; `"matmul_bfloat16"`
rounds only the operands of the received-power sum, as a TPU matmul at default
precision does (the defect PR 21 found on the chip); `retry_limit=0` breaks the MAC's
retransmission guarantee.
"""

from __future__ import annotations

import math

import numpy as np

SLOT, SIFS, DIFS = 9, 16, 34
CW_MIN, CW_MAX, RETRY_LIMIT = 15, 1023, 7
INF = 2 ** 30
BOLTZMANN = 1.380649e-23

#: 802.11a OFDM modes: name -> (constellation, coding class, bit rate)
OFDM_MODES = {
    "OfdmRate6Mbps": (2, 0, 6e6), "OfdmRate9Mbps": (2, 2, 9e6),
    "OfdmRate12Mbps": (4, 0, 12e6), "OfdmRate18Mbps": (4, 2, 18e6),
    "OfdmRate24Mbps": (16, 0, 24e6), "OfdmRate36Mbps": (16, 2, 36e6),
    "OfdmRate48Mbps": (64, 1, 48e6), "OfdmRate54Mbps": (64, 2, 54e6),
}
#: K=7 convolutional code, per puncturing (1/2, 2/3, 3/4): union-bound factor,
#: distance-spectrum weights and their exponents (NistErrorRateModel::CalculatePe)
PE_FACTOR = [1.0 / 2.0, 1.0 / 4.0, 1.0 / 6.0]
PE_COEFFS = [
    [36.0, 211.0, 1404.0, 11633.0, 77433.0, 502690.0, 3322763.0,
     21292910.0, 134365911.0, 0.0],
    [3.0, 70.0, 285.0, 1276.0, 6160.0, 27128.0, 117019.0,
     498860.0, 2103891.0, 8784123.0],
    [42.0, 201.0, 1492.0, 10469.0, 62935.0, 379644.0, 2253373.0,
     13073811.0, 75152755.0, 428005675.0],
]
PE_EXPONENTS = [
    [10.0, 12.0, 14.0, 16.0, 18.0, 20.0, 22.0, 24.0, 26.0, 28.0],
    [6.0, 7.0, 8.0, 9.0, 10.0, 11.0, 12.0, 13.0, 14.0, 15.0],
    [5.0, 6.0, 7.0, 8.0, 9.0, 10.0, 11.0, 12.0, 13.0, 14.0],
]
QAM_DIVISOR = {16: 10.0, 64: 21.0}


def _rounder(precision: str):
    """Identity for float64; round-to-bfloat16 after every operation for the
    control."""
    if precision in ("float64", "matmul_bfloat16"):
        return float
    if precision != "bfloat16":
        raise ValueError(f"unknown precision {precision!r}")
    return _bf16


def _bf16(x: float) -> float:
    from ml_dtypes import bfloat16

    return float(np.float32(x).astype(bfloat16))


def success_rate(snr: float, nbits: float, mode: str, q=float) -> float:
    """NIST chunk success rate (1 - pe)^nbits of one frame at linear `snr`."""
    m, cls, _ = OFDM_MODES[mode]
    if not snr > 0.0:     # a rounded power sum can leave less than the signal
        return 0.0
    if m == 2:
        ber = q(0.5 * math.erfc(q(math.sqrt(snr))))
    elif m == 4:
        ber = q(0.5 * math.erfc(q(math.sqrt(q(snr / 2.0)))))
    else:
        z = q(math.sqrt(q(snr / QAM_DIVISOR[m])))
        ber = q(q(2.0 * (1.0 - 1.0 / math.sqrt(m)) / math.log2(m))
                * q(math.erfc(z)))
    p = min(max(ber, 0.0), 0.5)
    d = q(math.sqrt(q(4.0 * p * q(1.0 - p))))
    log_d = q(math.log(max(d, 1e-35)))
    pe = 0.0
    for a, e in zip(PE_COEFFS[cls], PE_EXPONENTS[cls]):
        if a > 0.0:
            pe = q(pe + q(math.exp(q(q(math.log(a)) + q(e * log_d)))))
    pe = min(max(q(PE_FACTOR[cls] * pe), 0.0), 1.0 - 1e-12)
    return q(math.exp(q(nbits * q(math.log1p(-pe)))))


def ppdu_us(size_bytes: int, mode: str) -> int:
    ndbps = OFDM_MODES[mode][2] * 4e-6
    return 20 + math.ceil((16 + 8 * size_bytes + 6) / ndbps) * 4


def link_table(cfg: dict, precision: str = "float64") -> dict:
    """Pairwise received power (W), noise floor and per-frame constants."""
    ph, q = cfg["physics"], _rounder(precision)
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
                * BOLTZMANN * 290.0 * ph["bandwidth_hz"])
    mode = ph["data_mode"]
    data_bytes = ph["packet_bytes"] + 8 + 20 + 8 + 24 + 4
    data_dur = ppdu_us(data_bytes, mode)
    return dict(
        n=n, rx_w=rx_w, noise_w=noise_w, q=q, mode=mode,
        operand=_bf16 if precision == "matmul_bfloat16" else float,
        detectable=rx_dbm >= ph["rx_sensitivity_dbm"],
        data_dur=data_dur, nbits=OFDM_MODES[mode][2] * data_dur * 1e-6,
        resp_dur=ppdu_us(14, ph["ack_mode"]),
        beacon_dur=ppdu_us(ph["beacon_bytes"], "OfdmRate6Mbps"),
    )


def _arrivals(cfg: dict, horizon_us: int):
    ph, n = cfg["physics"], len(cfg["topology"]["positions"])
    start = [0] + [int(ph["client_start_us"] + ph["client_stagger_us"] * i)
                   for i in range(n - 1)]
    interval = [int(ph["beacon_interval_us"])] + [int(ph["interval_us"])] * (n - 1)
    stop = [INF] + [horizon_us] * (n - 1)
    return start, interval, stop


def simulate_one(cfg: dict, link: dict, horizon_us: int, rng,
                 retry_limit: int = RETRY_LIMIT) -> dict:
    """One replica: returns the program's per-replica counters."""
    n, q, rx_w, noise_w = link["n"], link["q"], link["rx_w"], link["noise_w"]
    start, interval, stop = _arrivals(cfg, horizon_us)
    exch = link["data_dur"] + SIFS + link["resp_dur"]
    next_arr = list(start)
    queue = [0] * n               # requests waiting at each station
    ap_pend = [0] * n             # echoes waiting at the AP, per station
    bcn_pend = 0
    backoff, hold, cw, retries = [0] * n, [0] * n, [CW_MIN] * n, [0] * n
    immediate = [False] * n
    busy_until = t = 0
    srv_rx = tx_data = drops = 0
    cli_rx = [0] * n

    def has_frame(i):
        return (bcn_pend > 0 or any(ap_pend)) if i == 0 else queue[i] > 0

    def tx_time(i):
        if not has_frame(i):
            return INF
        base = max(busy_until, hold[i])
        when = max(t, base) if immediate[i] else base + DIFS + backoff[i] * SLOT
        return max(when, t)

    while t < horizon_us:
        tx_t = [tx_time(i) for i in range(n)]
        tc, ta = min(tx_t), min(next_arr)
        nxt = min(ta, tc)
        if nxt >= horizon_us:
            break
        if ta <= tc:
            # application arrivals (and the beacon timer) at this instant
            idle = nxt >= busy_until + DIFS
            for i in range(n):
                if next_arr[i] != nxt:
                    continue
                had = has_frame(i)
                if i == 0:
                    bcn_pend += 1
                else:
                    queue[i] += 1
                adv = next_arr[i] + interval[i]
                next_arr[i] = INF if adv >= stop[i] else adv
                if not had:
                    immediate[i] = idle
                    if not idle:
                        backoff[i] = int(rng.random() * (cw[i] + 1))
            t = max(t, nxt)
            continue
        winners = [i for i in range(n) if tx_t[i] == nxt]
        elapsed = max((nxt - busy_until - DIFS) // SLOT, 0)
        for i in range(n):
            if i in winners or not has_frame(i):
                continue
            if immediate[i]:      # a zero-backoff grant cut short redraws
                backoff[i] = int(rng.random() * (cw[i] + 1))
                immediate[i] = False
            else:
                backoff[i] = max(backoff[i] - elapsed, 0)
        beacon = 0 in winners and bcn_pend > 0
        echo_dst = next((j for j in range(n) if ap_pend[j] > 0), 0)
        occupancy = 0
        for i in winners:
            if i == 0 and beacon:
                bcn_pend -= 1
                retries[0], cw[0] = 0, CW_MIN
                occ = link["beacon_dur"]
                hold[0] = nxt + occ
            else:
                dst = echo_dst if i == 0 else 0
                tx_data += 1
                total = 0.0
                for w in winners:
                    total = q(total + link["operand"](rx_w[w][dst]))
                sig = rx_w[i][dst]
                sinr = q(sig / q(noise_w + q(total - sig)))
                psr = success_rate(sinr, link["nbits"], link["mode"], q)
                ok = (link["detectable"][i][dst] and dst not in winners
                      and rng.random() < psr)
                if ok:
                    if i == 0:
                        cli_rx[dst] += 1
                        ap_pend[dst] -= 1
                    else:
                        srv_rx += 1
                        queue[i] -= 1
                        ap_pend[i] += 1
                    retries[i], cw[i] = 0, CW_MIN
                    occ = exch
                    hold[i] = nxt + occ
                else:
                    occ = link["data_dur"]
                    hold[i] = nxt + exch + SLOT + 4
                    if retries[i] + 1 > retry_limit:
                        drops += 1
                        if i == 0:
                            ap_pend[dst] -= 1
                        else:
                            queue[i] -= 1
                        retries[i], cw[i] = 0, CW_MIN
                    else:
                        retries[i] += 1
                        cw[i] = min(2 * (cw[i] + 1) - 1, CW_MAX)
            backoff[i] = int(rng.random() * (cw[i] + 1))
            immediate[i] = False
            occupancy = max(occupancy, occ)
        busy_until = nxt + occupancy
        t = max(t, nxt)
    return dict(srv_rx=srv_rx, cli_rx=cli_rx, tx_data=tx_data, drops=drops)


def simulate(cfg: dict, horizon_s: float, replicas: int, seed: int,
             precision: str = "float64", retry_limit: int = RETRY_LIMIT) -> dict:
    link = link_table(cfg, precision)
    rng = np.random.default_rng(seed)
    runs = [simulate_one(cfg, link, int(horizon_s * 1e6), rng, retry_limit)
            for _ in range(int(replicas))]
    return dict(
        srv_rx=np.array([r["srv_rx"] for r in runs]),
        cli_rx=np.array([r["cli_rx"] for r in runs]),
        tx_data=np.array([r["tx_data"] for r in runs]),
        drops=np.array([r["drops"] for r in runs]),
        all_done=True,
    )


def reference_replicas(traffic: dict) -> int:
    return int(traffic.get("reference_replicas", 32))


def criterion(out: dict) -> str | None:
    """`wifi-bss.py`'s own exit criterion restated on the lifted result: None
    where it holds, else what failed."""
    if not (out["all_done"] and np.asarray(out["srv_rx"]).mean() > 0):
        return "all_done and srv_rx.mean() > 0"
    return None


def kpi(out: dict) -> float:
    """Mean echo requests decoded at the server per replica."""
    return float(np.asarray(out["srv_rx"], float).mean())


def compare(cfg: dict, traffic: dict, outs: list, expected_rows: int,
            seed: int, ref: dict | None = None) -> dict:
    """Numbers that decide `correct`, from every launch the window finished."""
    if ref is None:
        ref = simulate(cfg, float(traffic["horizon_s"]),
                       reference_replicas(traffic), seed)
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

    def pooled(field):
        return np.concatenate([np.asarray(o[field], float) for o in done])

    def rel(dev, want):
        return float(abs(dev - want) / max(abs(want), 1e-9))

    numbers["srv_rx_gap"] = rel(pooled("srv_rx").mean(), ref["srv_rx"].mean())
    dev_sta = pooled("cli_rx").mean(axis=0)[1:]
    ref_sta = ref["cli_rx"].mean(axis=0)[1:]
    numbers["sta_echo_gap"] = float(np.max(
        np.abs(dev_sta - ref_sta) / np.maximum(ref_sta, np.median(ref_sta))
    ))
    numbers["tx_data_gap"] = rel(pooled("tx_data").mean(), ref["tx_data"].mean())
    offered = float(np.mean(ref["tx_data"]))
    numbers["drops_gap"] = float(
        abs(pooled("drops").mean() - ref["drops"].mean()) / offered
    )
    return numbers
