"""Plain reference of the hidden-terminal IBSS (`kind: bss`, `reference: bss_hidden`).

An event loop in float64 numpy, replicas along the leading axis: ad hoc stations
with the received power of every pair taken from the configuration's loss matrix,
one PacketSink station, OnOff CBR senders into it, 802.11a DCF (slot 9 us, SIFS 16,
DIFS 34, CW 15..1023, 7 retries) with carrier sense per station (a station senses
the frames that reach it at rx sensitivity), the NAV from an overheard RTS or CTS,
the RTS -> CTS -> DATA -> ACK exchange above the RTS threshold (DATA -> ACK below
it), CTS and ACK timeouts, and at every receiver the NIST OFDM error model over the
SINR of each stretch of a frame, the interference summed over every frame on the air
in that stretch.  It imports nothing of `tpudes` and takes nothing the program made:
the loss matrix, the flows, the PHY and every MAC constant come from the
configuration file (node numbers as upstream's, the sink among them); frame airtimes
and the NIST constants come from the sibling `bss.py`, loaded by path.

How a station proceeds (802.11 DCF as the repo's host MAC states it): a frame that
reaches an empty queue is sent at once if the station has sensed the medium idle,
and its NAV clear, for DIFS; else it draws a backoff in [0, CW].  The countdown runs
from the end of what the station senses (physical busy, NAV, its own transmission or
timeout) plus DIFS; when that end moves, the slots already counted are kept.  A
station that is not transmitting and not receiving locks onto the strongest frame
that starts and reaches it, and decodes it at its end with the product of the
stretches' success rates; a station that starts to transmit drops what it receives.
The receiver of an RTS answers with a CTS a SIFS after it, of a DATA frame with an
ACK (and delivers the frame once, whatever its retries); the sender of the RTS sends
the DATA a SIFS after the CTS.  A CTS or ACK that does not come by tx + SIFS +
response + slot + 4 us fails the attempt: retry count up (one count for RTS and DATA
failures alike, as the host MAC keeps it), CW doubled, the frame dropped past 7
retries; success or a drop resets CW.  The MAC queue has no limit, as the host MAC's.

Departures from upstream ns-3 (and from the repo's host DES), each also a comment
where it happens:
  D1 802.11a OFDM at a constant rate (6 Mbit/s, the control rate too) in place of
     upstream's 802.11b DSSS with the ARF manager; ARP and upstream's echo packets
     that resolve it are not modelled.
  D2 a 1 us clock: the OnOff send interval is truncated to whole us (3733 and 3731 us
     for 3733.333 and 3731.965), the count of sends an on period is the host's (268);
     propagation delay is zero (upstream places the three stations on one point).
  D3 a station senses exactly what reaches it at rx sensitivity (-101 dBm), no energy
     threshold; two frames that start in the same us at one receiver lock it onto the
     stronger (the host PHY locks onto the first it processes).
  D4 events of one us are taken in a fixed order: arrivals first, alone; then the
     stretch that ends is charged to every frame being received, frames that end are
     decoded, timeouts fire, and the us's transmissions start (what is on the air is
     reckoned from the last such step, so an arrival never hides a frame's end).
  D5 a backoff drawn after an exchange is kept when the queue is empty; the next frame
     to arrive is sent at once on an idle medium or draws anew.

Controls, never part of a benchmark run: `carrier_sense="shared"` lets every station
sense every transmission (one medium, the BSS step before the per-node arm: no hidden
node); `precision="bfloat16"` rounds each received power and the interference sum to
bfloat16; `rts_threshold=` overrides the configuration's.
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
RTS, CTS, DATA, ACK = 1, 2, 3, 4
IDLE, WAIT_CTS, DATA_DUE, WAIT_ACK = 0, 1, 2, 3


def lifted_order(cfg: dict) -> list:
    """Node numbers as the lifted program holds them: the sink first, then the
    others in upstream's order."""
    n, sink = len(cfg["physics"]["loss_db"]), int(cfg["physics"]["sink"])
    return [sink] + [i for i in range(n) if i != sink]


def arrivals(cfg: dict, horizon_us: int) -> list:
    """Per station (upstream numbering) the sorted send times in us before the
    horizon: a send at each on period's start, then one a send interval while on
    (strictly before the period's end, as the host's OnOffApplication)."""
    ph = cfg["physics"]
    out = [[] for _ in ph["loss_db"]]
    bits = 8 * int(ph["packet_bytes"])
    for f in ph["flows"]:
        iv_ns = bits * 10 ** 9 // int(f["rate_bps"])
        on_ns = round(float(f["on_s"]) * 1e9)
        sends = -(-on_ns // iv_ns)
        iv_us = iv_ns // 1000                                   # D2
        cycle_us = round((float(f["on_s"]) + float(f["off_s"])) * 1e6)
        opened = round(float(f["start_s"]) * 1e6)
        while opened < horizon_us:
            out[int(f["sender"])] += [
                t for t in (opened + m * iv_us for m in range(sends))
                if t < horizon_us
            ]
            opened += cycle_us
    return out


def tables(cfg: dict, horizon_s: float, carrier_sense: str = "pairs",
           precision: str = "float64", rts_threshold: int | None = None) -> dict:
    """Everything a replica needs, stations in the lifted order: received power
    (W) of each pair, which pairs reach (can be decoded) and sense each other, the
    noise floor, frame airtimes, bit rates and NAV fields, the timeouts, and each
    station's send times."""
    ph = cfg["physics"]
    order = lifted_order(cfg)
    n = len(order)
    if precision not in ("float64", "bfloat16"):
        raise ValueError(f"unknown precision {precision!r}")
    q = legacy._bf16 if precision == "bfloat16" else float
    loss = np.asarray(ph["loss_db"], float)[np.ix_(order, order)]
    rx_dbm = float(ph["tx_power_dbm"]) - loss
    power = [[0.0 if i == j else q(10.0 ** ((rx_dbm[i, j] - 30.0) / 10.0))
              for j in range(n)] for i in range(n)]
    reach = [[i != j and bool(rx_dbm[i, j] >= float(ph["rx_sensitivity_dbm"]))
              for j in range(n)] for i in range(n)]
    sense = [[i != j and (carrier_sense == "shared" or reach[i][j])
              for j in range(n)] for i in range(n)]
    noise = (10.0 ** (float(ph["noise_figure_db"]) / 10.0) * legacy.BOLTZMANN
             * 290.0 * float(ph["bandwidth_hz"]))
    sifs, slot = int(ph["sifs_us"]), int(ph["slot_us"])
    data_bytes = (int(ph["packet_bytes"]) + int(ph["llc_ip_udp_bytes"])
                  + int(ph["mac_header_bytes"]) + int(ph["fcs_bytes"]))
    threshold = int(ph["rts_threshold"]) if rts_threshold is None else rts_threshold
    mode = [None, ph["control_mode"], ph["control_mode"], ph["data_mode"],
            ph["control_mode"]]
    air = [0, legacy.ppdu_us(int(ph["rts_bytes"]), mode[RTS]),
           legacy.ppdu_us(int(ph["cts_bytes"]), mode[CTS]),
           legacy.ppdu_us(data_bytes, mode[DATA]),
           legacy.ppdu_us(int(ph["ack_bytes"]), mode[ACK])]
    # the NAV an overheard frame sets: RTS and CTS only (the host MAC leaves the
    # duration field of DATA and ACK at 0)
    nav_rts = 3 * sifs + air[CTS] + air[DATA] + air[ACK]
    horizon = int(round(horizon_s * 1e6))
    sends = arrivals(cfg, horizon)
    return dict(
        n=n, horizon=horizon, power=power, reach=reach, sense=sense, noise=noise,
        q=q, mode=mode, air=air, nav=[0, nav_rts, nav_rts - sifs - air[CTS], 0, 0],
        bits=[0.0] + [legacy.OFDM_MODES[m][2] * 1e-6 for m in mode[1:]],
        slot=slot, sifs=sifs, difs=int(ph["difs_us"]), cw_min=int(ph["cw_min"]),
        cw_max=int(ph["cw_max"]), limit=int(ph["retry_limit"]),
        opener=RTS if data_bytes > threshold else DATA,
        cts_wait=air[RTS] + sifs + air[CTS] + slot + 4,
        ack_wait=air[DATA] + sifs + air[ACK] + slot + 4,
        sends=[sends[i] + [INF] for i in order],
    )


def run_replica(T: dict, rng) -> dict:
    """One replica of the IBSS: station 0 is the sink, every other station sends
    its frames to it.  Returns the program's per-replica counters."""
    n, horizon, P, noise, q = T["n"], T["horizon"], T["power"], T["noise"], T["q"]
    slot, sifs, difs = T["slot"], T["sifs"], T["difs"]
    cw_min, cw_max, limit = T["cw_min"], T["cw_max"], T["limit"]
    air, nav_of, bits_of, mode_of = T["air"], T["nav"], T["bits"], T["mode"]
    reach, sense, sends = T["reach"], T["sense"], T["sends"]
    nodes, senders = range(n), range(1, n)
    ok_memo = {}

    def decodes(snr, bits, mode):
        """NIST success of `bits` at linear `snr`: (1 - pe) a bit, to the power."""
        key = (snr, mode)
        if key not in ok_memo:
            ok_memo[key] = legacy.success_rate(snr, 1.0, mode)
        per_bit = ok_memo[key]
        return per_bit ** bits if per_bit > 0.0 else 0.0

    queue, backoff, hold, retries = [0] * n, [0] * n, [0] * n, [0] * n
    cs, nav, cw, immediate = [0] * n, [0] * n, [cw_min] * n, [False] * n
    phase, due, got = [IDLE] * n, [INF] * n, [False] * n
    resp_at, resp_kind, resp_to = [INF] * n, [0] * n, [0] * n
    air_end, air_kind, air_to, overlapped = [0] * n, [0] * n, [0] * n, [False] * n
    locked, p_ok = [-1] * n, [1.0] * n
    ptr, nxt = [0] * n, [sends[i][0] for i in nodes]
    flow_rx, tx_by = [0] * n, [0] * n
    tx_rts = cts_timeout = lost_hidden = drops = 0
    t = t_air = 0                  # t_air: the air is reckoned up to here (D4)

    def draw(i):
        return int(rng.random() * (cw[i] + 1))

    while True:
        idle = [max(cs[i], nav[i], hold[i]) for i in nodes]
        grant, other = [INF] * n, INF
        for i in senders:
            if queue[i] > 0 and phase[i] == IDLE:
                g = max(t, idle[i]) if immediate[i] else idle[i] + difs + backoff[i] * slot
                grant[i] = g = max(g, t)
                other = min(other, g)
        for i in nodes:
            if phase[i] != IDLE:
                other = min(other, due[i])
            other = min(other, resp_at[i], air_end[i] if air_end[i] > t_air else INF)
        first = min(nxt)
        now = min(first, other)
        if now >= horizon:
            break

        if first <= other:
            # arrivals: a step of their own when they come first (D4); a frame
            # that finds the queue empty goes at once on a medium idle for DIFS
            for i in senders:
                if nxt[i] == now:
                    ptr[i] += 1
                    nxt[i] = sends[i][ptr[i]]
                    queue[i] += 1
                    if queue[i] == 1:
                        immediate[i] = now >= idle[i] + difs
                        if not immediate[i]:
                            backoff[i] = draw(i)
            t = now
            continue

        # the stretch (t_air, now] is charged to every frame being received, at the
        # interference of every other frame on the air in it
        on = [air_end[k] > t_air for k in nodes]
        span = now - t_air
        for j in nodes:
            s = locked[j]
            if s < 0 or span <= 0:
                continue
            interference = 0.0
            for k in nodes:
                if on[k] and k != s:
                    interference = q(interference + P[k][j])
            frame = air_kind[s]
            p_ok[j] *= decodes(P[s][j] / (noise + interference), bits_of[frame] * span,
                               mode_of[frame])
        for i in nodes:
            if on[i] and any(on[k] and not sense[k][i] for k in nodes if k != i):
                overlapped[i] = True

        # frames that end now: decoded or lost where they are locked on
        decoded, acked = [False] * n, [False] * n
        for j in nodes:
            s = locked[j]
            if s < 0 or not (on[s] and air_end[s] == now):
                continue
            locked[j] = -1
            if not rng.random() < p_ok[j]:
                continue
            frame = air_kind[s]
            if air_to[s] != j:
                if nav_of[frame]:                        # an overheard RTS or CTS
                    nav[j] = max(nav[j], now + nav_of[frame])
                continue
            decoded[s] = True
            if frame == RTS:
                resp_at[j], resp_kind[j], resp_to[j] = now + sifs, CTS, s
            elif frame == DATA:
                resp_at[j], resp_kind[j], resp_to[j] = now + sifs, ACK, s
                if not got[s]:                          # once, whatever its retries
                    flow_rx[s] += 1
                    got[s] = True
            elif frame == CTS and phase[j] == WAIT_CTS:
                phase[j], due[j] = DATA_DUE, now + sifs
            elif frame == ACK and phase[j] == WAIT_ACK:
                acked[j] = True
        for i in nodes:
            if on[i] and air_end[i] == now:
                if air_kind[i] in (RTS, DATA) and not decoded[i] and overlapped[i]:
                    lost_hidden += 1
                overlapped[i] = False

        # success, or a timeout: the attempt is over
        ended = [False] * n
        for i in senders:
            failed = phase[i] in (WAIT_CTS, WAIT_ACK) and due[i] == now
            if not (acked[i] or failed):
                continue
            ended[i] = True
            finish = acked[i]
            if failed:
                cts_timeout += phase[i] == WAIT_CTS
                retries[i] += 1
                hold[i] = now
                if retries[i] > limit:
                    drops += 1
                    finish = True
                else:
                    cw[i] = min(2 * (cw[i] + 1) - 1, cw_max)
            if finish:
                queue[i] -= 1
                got[i] = False
                retries[i] = 0
                cw[i] = cw_min
            phase[i] = IDLE
            backoff[i] = draw(i)
            immediate[i] = False

        # the us's transmissions start: answers, DATA a SIFS after a CTS, grants
        going = [False] * n
        for i in nodes:
            if resp_at[i] == now:
                frame, to = resp_kind[i], resp_to[i]
                resp_at[i] = INF
            elif phase[i] == DATA_DUE and due[i] == now:
                frame, to = DATA, 0
            elif grant[i] == now and not ended[i]:
                frame, to = T["opener"], 0
            else:
                continue
            going[i] = True
            air_kind[i], air_to[i], air_end[i] = frame, to, now + air[frame]
            hold[i] = max(hold[i], air_end[i])
            immediate[i] = False
            locked[i] = -1                              # a transmitter stops receiving
            if frame == DATA:
                tx_by[i] += 1
                phase[i], due[i] = WAIT_ACK, now + T["ack_wait"]
            elif frame == RTS:
                tx_rts += 1
                phase[i], due[i] = WAIT_CTS, now + T["cts_wait"]
        if any(going):
            for j in nodes:
                # a free station locks onto the strongest frame that starts and
                # reaches it (D3); a station that senses a start is busy to its end
                if not going[j] and locked[j] < 0:
                    best = -1
                    for k in nodes:
                        if going[k] and reach[k][j] and (best < 0 or P[k][j] > P[best][j]):
                            best = k
                    if best >= 0:
                        locked[j], p_ok[j] = best, 1.0
                for k in nodes:
                    if going[k] and sense[k][j]:
                        cs[j] = max(cs[j], air_end[k])
            # a waiting station whose idle start moved keeps the slots it counted;
            # a frame that was to go at once draws a backoff instead
            for i in senders:
                if (queue[i] > 0 and phase[i] == IDLE and not ended[i] and not going[i]
                        and grant[i] < INF and max(cs[i], nav[i], hold[i]) > idle[i]):
                    if immediate[i]:
                        backoff[i] = draw(i)
                        immediate[i] = False
                    else:
                        backoff[i] = max(backoff[i] - max((now - idle[i] - difs) // slot, 0), 0)
        t = t_air = now

    return dict(flow_rx=flow_rx, tx_by=tx_by, tx_rts=tx_rts, cts_timeout=cts_timeout,
                rx_lost_hidden=lost_hidden, drops=drops, backlog=queue)


#: replicas a chunk: each chunk has a seed of its own, so that the numbers do not
#: depend on how many processes share the work
CHUNK = 8


def _chunk(cfg: dict, horizon_s: float, seed: int, index: int, count: int,
           control: dict) -> list:
    T = tables(cfg, horizon_s, **control)
    rng = np.random.default_rng(np.random.SeedSequence((int(seed) & 0xFFFFFFFF,
                                                        int(seed) >> 32, index)))
    return [run_replica(T, rng) for _ in range(count)]


def simulate(cfg: dict, horizon_s: float, replicas: int, seed: int,
             workers: int | None = None, **control) -> dict:
    """`replicas` independent runs; per-station fields in the lifted order.  The
    replicas go in chunks of CHUNK, each with a seed of its own, to `workers`
    processes (None: one a CPU, up to 12, where the run is long enough to pay
    for them; 1: this process)."""
    chunks = [(i, min(CHUNK, replicas - i * CHUNK))
              for i in range(-(-int(replicas) // CHUNK))]
    if workers is None:
        workers = 1 if replicas * horizon_s <= 160 else min(os.cpu_count() or 1, 12)
    if workers <= 1 or len(chunks) == 1:
        runs = [r for i, c in chunks for r in _chunk(cfg, horizon_s, seed, i, c, control)]
    else:
        runs = _in_processes(cfg, horizon_s, seed, chunks, control, workers)
    out = {k: np.array([r[k] for r in runs]) for k in runs[0]}
    out["all_done"] = True
    return out


def _in_processes(cfg, horizon_s, seed, chunks, control, workers) -> list:
    """The chunks over `workers` fresh Python processes running this file (no
    fork of the calling process: it may hold a device runtime's threads)."""
    import json
    import subprocess
    import sys

    shares = [chunks[w::workers] for w in range(workers)]
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu", OMP_NUM_THREADS="1"),
        )
        for share in shares if share
    ]
    for proc, share in zip(procs, shares):
        proc.stdin.write(json.dumps(dict(cfg=cfg, horizon_s=horizon_s, seed=seed,
                                         chunks=share, control=control)))
        proc.stdin.close()
    by_chunk = {}
    for proc in procs:
        got = json.loads(proc.stdout.read())
        if proc.wait() != 0:
            raise RuntimeError("a reference worker failed")
        by_chunk.update({int(k): v for k, v in got.items()})
    return [r for i, _ in chunks for r in by_chunk[i]]


def reference_replicas(traffic: dict) -> int:
    return int(traffic.get("reference_replicas", 32))


def criterion(out: dict) -> str | None:
    """`wifi-hidden-terminal.py`'s own exit criterion restated on the lifted
    result: None where it holds, else what failed."""
    flows = np.asarray(out["flow_rx"])[:, 1:]
    if not (out["all_done"] and flows.sum(1).mean() > 0):
        return "all_done and flow_rx[:, 1:].sum(1).mean() > 0"
    return None


def kpi(out: dict) -> float:
    """Mean data frames delivered to the sink per replica, both flows."""
    return float(np.asarray(out["flow_rx"], float)[:, 1:].sum(1).mean())


def compare(cfg: dict, traffic: dict, outs: list, expected_rows: int,
            seed: int, ref: dict | None = None) -> dict:
    """Numbers that decide `correct`: every launch of the window pooled against the
    reference's replicas (backoff draws are random on both sides).  Each gap is
    |device mean - reference mean| per sender (the worst of the senders) over what
    the sender was offered in the horizon, so that every number reads on one scale:
    a share of the offered frames."""
    horizon_s = float(traffic["horizon_s"])
    if ref is None:
        ref = simulate(cfg, horizon_s, reference_replicas(traffic), seed)
    order = lifted_order(cfg)
    n = len(order)
    done = [o for o in outs if o["all_done"]]
    rows = sum(
        int(np.sum(np.asarray(o["flow_rx"])[:, 1:].sum(1) > 0)) for o in done
        if np.asarray(o.get("flow_rx", np.zeros((0, 0)))).ndim == 2
        and np.asarray(o["flow_rx"]).shape[1] == n
    )
    numbers = {"rows_missing": float(expected_rows - rows)}
    if rows == 0:
        return numbers
    offered = np.array([len(s) for s in arrivals(cfg, int(round(horizon_s * 1e6)))],
                       float)[order][1:]

    def dev(field):
        return np.concatenate([np.asarray(o[field], float) for o in done]).mean(0)

    def per_sender(field):
        """The worst sender's |device - reference| over what it was offered."""
        d, r = dev(field)[1:], np.asarray(ref[field], float).mean(0)[1:]
        return float(np.max(np.abs(d - r) / offered))

    def total(field):
        """|device - reference| of a per-replica count over all frames offered."""
        return float(abs(dev(field) - np.asarray(ref[field], float).mean())
                     / offered.sum())

    numbers["flow_rx_gap"] = per_sender("flow_rx")
    numbers["tx_data_gap"] = per_sender("tx_by")
    numbers["backlog_gap"] = per_sender("backlog")
    numbers["tx_rts_gap"] = total("tx_rts")
    numbers["cts_timeout_gap"] = total("cts_timeout")
    numbers["drops_gap"] = total("drops")
    return numbers


if __name__ == "__main__":
    # a worker of `_in_processes`: its chunks from stdin, their replicas to stdout
    import json
    import sys

    job = json.load(sys.stdin)
    json.dump({str(i): _chunk(job["cfg"], job["horizon_s"], job["seed"], i, c,
                              job["control"])
               for i, c in job["chunks"]}, sys.stdout)
