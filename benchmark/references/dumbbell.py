"""Plain reference of the TCP dumbbell deployment (`kind: dumbbell`, `reference: dumbbell`).

A float64 numpy slot loop: N bulk TCP senders on the left, each behind its own access
link, one bottleneck with a drop-tail queue, N sinks on the right, every sender running
CUBIC as RFC 8312 section 4 and upstream's `src/internet/model/tcp-cubic.cc` state it.
Time advances in slots of one packet's serialization on the bottleneck.  It imports
nothing of `tpudes` and takes nothing the program made: rates, delays, queue, segment
and header bytes, start times, CUBIC's C and beta and the initial window come from the
configuration file; the random order of a slot's arrivals comes from its own `numpy`
generator seeded by `seed`.  Replicas are the leading axis of every array.

The slot model (what the engine documents, restated; one slot, in order):
  1 the ACKs and loss notices due now arrive.  An ACK outside a recovery window grows
    the window: slow start adds one segment; congestion avoidance is CUBIC's: at the
    first ACK after a cut the epoch starts (K = cbrt((W_max - cwnd) / C), origin W_max,
    or K = 0 and origin cwnd when the window is already past W_max; W_est = cwnd), the
    target is W_cubic one smoothed RTT ahead, origin + C (t + SRTT - epoch - K)^3, the
    TCP-friendly estimate W_est gains 3 (1 - beta) / (1 + beta) / cwnd an ACK (RFC 8312
    eq. 4 with t / RTT counted in ACKs, as upstream counts it), and the window gains
    1 / cnt, cnt = cwnd / (target - cwnd) (100 cwnd when the target is not above it),
    at most cwnd / (W_est - cwnd) in the TCP-friendly region, at least 2: half a
    segment an ACK at most.  A loss notice outside a recovery window cuts once: fast
    convergence (W_max = cwnd (1 + beta) / 2 when the window was still below the last
    W_max, else cwnd), ssthresh = max(beta cwnd, 2), cwnd = ssthresh, the epoch ends,
    and a recovery window of one base RTT opens in which ACKs do not grow the window
    and further notices do not cut.  Every notice takes its packet out of flight.
  2 a backlogged bottleneck sends the packet at the HEAD of its queue; the ACK is due
    `ack_lag` slots later (twice the bottleneck delay and four access delays) and
    carries the RTT sample base RTT + backlog x slot.
  3 every started sender emits floor(cwnd) - in flight packets, at most what its access
    link carries in a slot; the slot's packets reach the queue interleaved in a seeded
    random order, one by one, and one that finds the queue full is dropped: its loss
    notice is due `ack_lag` slots later.
The queue is a ring of flow ids in order of arrival; step 2 is first in, first out with
`service="fifo"`, and under D10 a draw from the ring.

Departures from upstream ns-3 (`tcp-variants-comparison.cc`, TcpSocketBase, TcpCubic):
  D1 no HyStart (upstream's CUBIC has it on by default): slow start ends at the first
     loss.
  D2 no SACK, no retransmission queue: a lost packet leaves the flight at its notice
     and the bulk source sends a new one in its place; goodput counts departures from
     the bottleneck.
  D3 no delayed ACKs: every delivered segment is acknowledged by itself; the receive
     window never limits (upstream's 128 KiB buffers would at 87 segments a flow).
  D4 no retransmission timeout: loss notices are clocked, so the ACK clock never stalls.
  D5 a loss is noticed `ack_lag` slots after the drop (dupack timing without counting
     three duplicates), and a recovery window lasts one BASE RTT, not until the
     highest sequence sent at the cut is acknowledged.
  D6 the access links' serialization is a cap of access rate / bottleneck rate packets
     a flow a slot; ACKs ride an uncongested reverse path.
  D7 the window is a real number of segments and grows by 1 / cnt an ACK (RFC 8312's
     form) where upstream counts ACKs up to an integer cnt; the first epoch's
     `CntClamp` is unreachable (no congestion avoidance before a loss).
  D8 time is whole slots: a start time is rounded down to its slot.
  D9 the target looks one SMOOTHED RTT ahead (RFC 6298's SRTT with alpha = 1/8, as RFC
     8312 section 4.1 words it); upstream's tcp-cubic.cc and Linux add their minimum
     RTT there.
  D10 the order of service, shared with the engine and NOT upstream's: with `service:
     draw` in the configuration a backlogged slot sends a packet drawn uniformly from
     the queue (so a flow's packet leaves in proportion to its occupancy) and not the
     head.  `service="fifo"` is upstream's order; PERF.md section 7 has the gap between
     the two, measured, for the fidelity queue.

Controls and faults, never part of a benchmark run: `precision="bfloat16"` rounds the
window state (cwnd, ssthresh, W_max, K, origin, W_est) to bfloat16 after every update:
a half-width carry; `variant="newreno"` runs NewReno's rules in CUBIC's place (one
segment an RTT, halve at a loss); `fast_convergence=False`; `cut_per_loss=True` cuts at
every slot that brings a loss notice, inside a recovery window too; `service="fifo"`
serves the head of the queue (D10).
"""

from __future__ import annotations

import math

import numpy as np


def geometry(cfg: dict, horizon_s: float) -> dict:
    """Slots, lags and caps of the dumbbell, from the configuration alone."""
    ph, topo = cfg["physics"], cfg["topology"]
    wire_bits = (ph["segment_bytes"] + ph["header_bytes"]) * 8
    slot_s = wire_bits / ph["bottleneck_rate_bps"]
    # data: bottleneck propagation + far access; ACK: access, bottleneck, access;
    # the near access link's delay is on the way into the queue
    ack_lag_s = 2.0 * ph["bottleneck_delay_s"] + 4.0 * ph["access_delay_s"]
    base_rtt_s = ack_lag_s + slot_s
    n = int(topo["n_flows"])
    starts = [topo["flow_start_s"] + topo["flow_stagger_s"] * i for i in range(n)]
    return dict(
        n_flows=n, slot_s=slot_s, base_rtt_s=base_rtt_s,
        n_slots=int(math.ceil(horizon_s / slot_s)),
        ack_lag=max(1, int(round(ack_lag_s / slot_s))),
        recovery_slots=max(1, int(round(base_rtt_s / slot_s))),
        burst=max(1, int(ph["access_rate_bps"] // ph["bottleneck_rate_bps"])),
        queue=int(ph["queue_packets"]),
        start=np.array([int(s / slot_s) for s in starts]),
        stop=int(horizon_s / slot_s),
        seg_bits=ph["segment_bytes"] * 8,
    )


def _rounder(precision: str):
    if precision == "float64":
        return lambda x: x
    if precision != "bfloat16":
        raise ValueError(f"unknown precision {precision!r}")
    from ml_dtypes import bfloat16

    return lambda x: x.astype(np.float32).astype(bfloat16).astype(np.float64)


def simulate(cfg: dict, horizon_s: float, replicas: int, seed: int,
             precision: str = "float64", variant: str = "cubic",
             fast_convergence: bool | None = None,
             cut_per_loss: bool = False, service: str | None = None) -> dict:
    """`replicas` independent dumbbells for `horizon_s`; returns the program's
    per-replica fields: goodput_mbps, delivered, drops (R, F), mean_queue (R,),
    cwnd_final (R, F), and `sent`, `inflight`, `queued`, `unacked`, `unnoticed`
    for the conservation law."""
    ph, g = cfg["physics"], geometry(cfg, horizon_s)
    service = ph["service"] if service is None else service
    if variant not in ("cubic", "newreno") or service not in ("draw", "fifo"):
        raise ValueError(f"unknown variant {variant!r} or service {service!r}")
    draw = service == "draw"
    q = _rounder(precision)
    R, F, Q, lag = int(replicas), g["n_flows"], g["queue"], g["ack_lag"]
    L = lag + 1
    slot_s, base_rtt = g["slot_s"], g["base_rtt_s"]
    C, beta = float(ph["cubic_c"]), float(ph["cubic_beta"])
    fast = bool(ph["fast_convergence"]) if fast_convergence is None else fast_convergence
    friendly = 3.0 * (1.0 - beta) / (1.0 + beta)
    floor_w = float(ph["min_window_segments"])
    cubic = variant == "cubic"
    rng = np.random.default_rng(seed)

    cwnd = q(np.full((R, F), float(ph["initial_window_segments"])))
    ssthresh = np.full((R, F), np.inf)
    w_max, k, origin, w_est = (np.zeros((R, F)) for _ in range(4))
    epoch = np.full((R, F), -1.0)            # < 0: no epoch open
    srtt = np.zeros((R, F))                  # RFC 6298's; 0: no sample yet
    inflight, delivered, drops, sent = (np.zeros((R, F), np.int64) for _ in range(4))
    recover_until = np.zeros((R, F), np.int64)
    ack_buf = np.zeros((L, R, F), np.int64)
    loss_buf = np.zeros((L, R, F), np.int64)
    rtt_buf = np.full((L, R), base_rtt)
    ring = np.zeros((R, Q), np.int64)        # the bottleneck queue: flow ids, FIFO
    head, count = np.zeros(R, np.int64), np.zeros(R, np.int64)
    qsum = np.zeros(R)
    rows = np.arange(R)

    for t in range(g["n_slots"]):
        now, i, j = t * slot_s, t % L, (t + lag) % L
        # 1. what is due now
        acks, losses = ack_buf[i].copy(), loss_buf[i].copy()
        ack_buf[i] = 0
        loss_buf[i] = 0
        inflight -= acks + losses
        got = acks > 0
        if got.any():
            sample = rtt_buf[i][:, None]
            srtt = np.where(got, np.where(
                srtt > 0.0, 0.875 * srtt + 0.125 * sample, sample), srtt)
            open_ = t >= recover_until
            for nth in range(int(acks.max())):         # one ACK at a time
                grow = open_ & (acks > nth)
                slow = grow & (cwnd < ssthresh)
                ca = grow & ~slow
                cwnd = np.where(slow, q(cwnd + 1.0), cwnd)
                if not ca.any():
                    continue
                if not cubic:                          # fault: NewReno's increase
                    cwnd = np.where(ca, q(cwnd + 1.0 / cwnd), cwnd)
                    continue
                new = ca & (epoch < 0.0)
                below = w_max > cwnd
                epoch = np.where(new, now, epoch)
                k = np.where(new, q(np.where(
                    below, np.cbrt(np.maximum(w_max - cwnd, 0.0) / C), 0.0)), k)
                origin = np.where(new, np.where(below, w_max, cwnd), origin)
                w_est = np.where(new, cwnd, w_est)
                ahead = now + srtt - epoch
                target = origin + C * (ahead - k) ** 3
                w_est = np.where(ca, q(w_est + friendly / cwnd), w_est)
                cnt = np.where(
                    target > cwnd, cwnd / np.maximum(target - cwnd, 1e-12),
                    100.0 * cwnd)
                cnt = np.where(
                    w_est > cwnd,
                    np.minimum(cnt, cwnd / np.maximum(w_est - cwnd, 1e-12)), cnt)
                cwnd = np.where(ca, q(cwnd + 1.0 / np.maximum(cnt, 2.0)), cwnd)
        lost = losses > 0
        if lost.any():
            cut = lost if cut_per_loss else lost & (t >= recover_until)
            if cubic:
                w_max = np.where(cut, q(np.where(
                    fast & (cwnd < w_max), cwnd * (1.0 + beta) / 2.0, cwnd)), w_max)
                after = cwnd * beta
            else:
                after = cwnd / 2.0
            ssthresh = np.where(cut, q(np.maximum(after, floor_w)), ssthresh)
            cwnd = np.where(cut, ssthresh, cwnd)
            epoch = np.where(cut, -1.0, epoch)
            recover_until = np.where(cut, t + g["recovery_slots"], recover_until)
        # 2. the head of the queue leaves
        backlog = count.copy()
        qsum += backlog
        busy = np.flatnonzero(backlog > 0)
        if busy.size:
            if draw:        # D9: a packet drawn from the queue goes to its head
                pick = (head[busy] + (rng.random(busy.size) * backlog[busy])
                        .astype(np.int64)) % Q
                front = ring[busy, head[busy]]
                ring[busy, head[busy]] = ring[busy, pick]
                ring[busy, pick] = front
            flow = ring[busy, head[busy]]
            head[busy] = (head[busy] + 1) % Q
            count[busy] -= 1
            delivered[busy, flow] += 1
            ack_buf[j, busy, flow] += 1
        rtt_buf[j] = base_rtt + backlog * slot_s
        # 3. the senders' packets arrive, interleaved, one by one
        live = (t >= g["start"]) & (t < g["stop"])
        want = np.clip(np.floor(cwnd).astype(np.int64) - inflight, 0, g["burst"])
        want = np.where(live[None, :], want, 0)
        most = int(want.max())
        if most == 0:
            continue
        inflight += want
        sent += want
        valid = (np.arange(most)[None, None, :] < want[:, :, None]).reshape(R, -1)
        keys = np.where(valid, rng.random(valid.shape), 2.0)
        order = np.argsort(keys, axis=1)
        flows = order // most                  # flow of the p-th packet to arrive
        n_arr, free = want.sum(axis=1), Q - count
        pos = np.arange(valid.shape[1])[None, :]
        r, p = np.nonzero(pos < np.minimum(n_arr, free)[:, None])
        ring[r, (head[r] + count[r] + p) % Q] = flows[r, p]
        count += np.minimum(n_arr, free)
        r, p = np.nonzero((pos >= free[:, None]) & (pos < n_arr[:, None]))
        if r.size:                             # the queue was full: tail drop
            np.add.at(drops, (r, flows[r, p]), 1)
            np.add.at(loss_buf[j], (r, flows[r, p]), 1)

    sim_s = g["n_slots"] * slot_s
    queued = np.zeros((R, F), np.int64)
    for p in range(Q):
        inside = p < count
        np.add.at(queued, (rows[inside], ring[inside, (head[inside] + p) % Q]), 1)
    return dict(
        goodput_mbps=delivered * g["seg_bits"] / sim_s / 1e6,
        delivered=delivered, drops=drops, mean_queue=qsum / g["n_slots"],
        cwnd_final=cwnd, sent=sent, inflight=inflight, queued=queued,
        unnoticed=loss_buf.sum(axis=0), unacked=ack_buf.sum(axis=0),
    )


def reference_replicas(traffic: dict) -> int:
    return int(traffic.get("reference_replicas", 32))


def criterion(out: dict) -> str | None:
    """`tcp-variants.py`'s own exit criterion restated on the lifted result (mean
    aggregate goodput above zero): None where it holds, else what failed."""
    if not np.asarray(out["goodput_mbps"]).sum() > 0:
        return "goodput > 0"
    return None


def kpi(out: dict) -> float:
    """Mean aggregate goodput, Mbit/s a replica."""
    return float(np.asarray(out["goodput_mbps"], float).sum(axis=-1).mean())


def jain(goodput: np.ndarray) -> np.ndarray:
    """Jain's fairness index over the flows of each replica."""
    total, squares = goodput.sum(axis=1), (goodput ** 2).sum(axis=1)
    return total ** 2 / np.maximum(goodput.shape[1] * squares, 1e-30)


def compare(cfg: dict, traffic: dict, outs: list, expected_rows: int,
            seed: int, ref: dict | None = None) -> dict:
    """Numbers that decide `correct`, from every launch the window finished, each
    a mean over all their replicas against the reference's replicas."""
    horizon_s = float(traffic["horizon_s"])
    if ref is None:
        ref = simulate(cfg, horizon_s, reference_replicas(traffic), seed)
    n = int(cfg["topology"]["n_flows"])
    done = [
        o for o in outs
        if np.asarray(o["goodput_mbps"]).ndim == 2
        and np.asarray(o["goodput_mbps"]).shape[1] == n
        and np.asarray(o["drops"]).shape == np.asarray(o["goodput_mbps"]).shape
    ]
    rows = sum(int(np.asarray(o["goodput_mbps"]).shape[0]) for o in done)
    numbers = {"rows_missing": float(expected_rows - rows)}
    if rows == 0:
        return numbers

    def pooled(field):
        return np.concatenate([np.asarray(o[field], float) for o in done])

    goodput, want = pooled("goodput_mbps"), np.asarray(ref["goodput_mbps"], float)
    agg = float(want.sum(axis=1).mean())
    numbers["agg_goodput_gap"] = float(abs(goodput.sum(axis=1).mean() - agg) / agg)
    numbers["flow_goodput_gap"] = float(
        np.max(np.abs(goodput.mean(axis=0) - want.mean(axis=0))) / (agg / n))
    numbers["drops_gap"] = float(
        abs(pooled("drops").sum(axis=1).mean() - ref["drops"].sum(axis=1).mean())
        / ref["delivered"].sum(axis=1).mean())
    numbers["queue_gap"] = float(
        abs(pooled("mean_queue").mean() - ref["mean_queue"].mean())
        / cfg["physics"]["queue_packets"])
    numbers["jain_gap"] = float(abs(jain(goodput).mean() - jain(want).mean()))
    return numbers
