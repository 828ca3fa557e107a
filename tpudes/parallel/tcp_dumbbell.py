"""Replica-axis execution of the TCP dumbbell (BASELINE config #2).

Lowers a dumbbell object graph — N left leaves bulk-sending TCP through
one bottleneck toward N right leaves (tcp-variants-comparison's shape;
SURVEY.md §2.7/§2.9) — to a device-resident **packet-slot** program: one
``lax.scan`` step per bottleneck serialization time τ (= pkt_bytes·8/C),
per-replica per-flow state in (R, F) arrays, all SEVENTEEN
TcpCongestionOps variants (the full upstream family incl. BBR, DCTCP,
H-TCP, YeAH, LEDBAT and TCP-LP) as vector rules in one fused step: a
table keyed by variant id, of which a launch compiles the rules of the
variants it assigns and selects among them on the traced per-flow ids.
A RED root
qdisc on the bottleneck lowers too: EWMA average queue, early
drop/CE-mark (RFC 3168 ECE triggers the variant's loss response; DCTCP
scales its cut by the marked fraction), gentle mode, hard-drop forced
region.

The slot model (each deviation documented, mirrored on replicated.py's
timing-model contract):
- the bottleneck serves exactly one packet per slot when backlogged
  (work-conserving FIFO); *which* flow's head departs is drawn with
  probability proportional to per-flow queue occupancy — FIFO in
  expectation, not in exact order.
- the access links are required to be faster than the bottleneck (the
  lowering rejects otherwise); their delay folds into the base RTT and
  their serialization into a per-slot send-burst cap.
- ACKs ride the uncongested reverse path: ack arrival = departure slot
  + base-lag slots; reverse-direction queueing is not modeled.
- loss detection is dupack-timed: a tail-dropped packet triggers one
  window reduction per RTT (NewReno-style recovery window
  ``recover_until``); every lost packet individually leaves the flight
  so the ACK clock never stalls.  RTO timeouts are not modeled (with a
  clocked recovery window they are unreachable for backlogged flows).
- RTT samples (Vegas/Veno) are base_rtt + queue_wait with queue_wait
  approximated by the instantaneous backlog at departure.

The scalar DES (real TcpSocketBase over PointToPointNetDevice) stays
the per-packet oracle; tests assert statistical parity of per-variant
goodput, not per-packet equality.
"""

from __future__ import annotations

import functools
import math
import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from tpudes.fuzz.envelope import FuzzEnvelope

# variant ids (order is the vector-rule dispatch table; the full
# upstream tcp-variants-comparison family, tcp_congestion.TCP_VARIANTS)
VARIANTS = ("TcpNewReno", "TcpCubic", "TcpScalable", "TcpHighSpeed",
            "TcpVegas", "TcpVeno", "TcpLinuxReno", "TcpBic", "TcpWestwood",
            "TcpIllinois", "TcpHybla", "TcpBbr", "TcpDctcp", "TcpHtcp",
            "TcpYeah", "TcpLedbat", "TcpLp")
(V_NEWRENO, V_CUBIC, V_SCALABLE, V_HIGHSPEED, V_VEGAS, V_VENO,
 V_LINUXRENO, V_BIC, V_WESTWOOD, V_ILLINOIS, V_HYBLA, V_BBR,
 V_DCTCP, V_HTCP, V_YEAH, V_LEDBAT, V_LP) = range(17)

INIT_CWND = 10.0          # segments (tcp_congestion.TcpSocketState default)
SSTHRESH0 = 1e9
CUBIC_C = 0.4
CUBIC_BETA = 0.7
SCALABLE_AI = 50.0
SCALABLE_MD = 0.125
HS_LOW_WINDOW = 38.0
VEGAS_ALPHA, VEGAS_BETA, VEGAS_GAMMA = 2.0, 4.0, 1.0
VENO_BETA = 3.0
BIC_BETA, BIC_LOW_WND, BIC_MAX_INCR, BIC_SMIN = 0.8, 14.0, 16.0, 0.01
ILL_ALPHA_MAX, ILL_ALPHA_MIN = 10.0, 0.3
ILL_BETA_MAX, ILL_BETA_MIN = 0.5, 0.125
HYBLA_RRTT = 0.025
BBR_HIGH_GAIN = 2.89
BBR_CYCLE_GAINS = (1.25, 0.75, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0)
BBR_STARTUP, BBR_DRAIN, BBR_PROBE_BW = range(3)
BBR_BW_DECAY = 0.98       # per-round decaying-max ≈ the 10-round window
DCTCP_G = 0.0625
HTCP_DELTA_B = 1.0        # s: low-speed regime boundary
HTCP_DEFAULT_BACKOFF = 0.5
YEAH_ALPHA, YEAH_QMAX, YEAH_RHO = 80.0, 8.0, 0.125
LEDBAT_TARGET_S, LEDBAT_GAIN = 0.1, 1.0
LP_INFERENCE_FRAC = 0.15


#: the documented-faithful fuzz region (see :mod:`tpudes.fuzz`): the
#: tcp-variants-comparison dumbbell shape lower_dumbbell accepts —
#: access faster than the bottleneck, packet-mode droptail queue, one
#: SendSize, all flows left→right — across the full 17-variant family
#: ("mixed" assigns variants round-robin from the drawn one)
FUZZ_ENVELOPE = FuzzEnvelope(
    engine="dumbbell",
    axes={
        "n_flows": ("int", 2, 4),
        "variant": ("choice", VARIANTS),
        "variant_mix": ("choice", ("homogeneous", "mixed")),
        "bottleneck_mbps": ("choice", (3, 5, 10)),
        "bottleneck_delay_ms": ("choice", (5, 10, 20)),
        "queue_pkts": ("choice", (25, 50, 100)),
        "seg_bytes": ("choice", (500, 1000)),
        "sim_ms": ("int", 900, 2500),
        "replicas": ("int", 2, 9),
        "chunk_divisor": ("choice", (2, 3)),
        "key_seed": ("int", 0, 2**16),
        # ISSUE-14 traffic draws (appended): app-limited flows from
        # the drawn workload model; "off" keeps the bulk source
        "traffic": ("choice", ("off", "cbr", "mmpp", "onoff", "trace")),
        "tr_burst": ("float", 0.1, 0.6),
        "tr_phase": ("float", 0.0, 1.0),
    },
    # sim_ms floor 8: even at the fastest slot (500 B @ 10 Mbps,
    # 0.432 ms) the shrunk horizon lands under 32 slots
    floors={"replicas": 1, "n_flows": 1, "sim_ms": 8},
    doc="single-bottleneck dumbbell, bulk TCP left→right, 17 variants",
)


@dataclass(frozen=True)
class DumbbellProgram:
    """Static description of one dumbbell scenario on the replica axis."""

    n_flows: int
    variant_idx: np.ndarray      # (F,) index into VARIANTS
    start_slot: np.ndarray       # (F,) first slot each flow may send
    stop_slot: np.ndarray        # (F,) no new packets at/after this slot
    max_pkts: np.ndarray         # (F,) segment budget (INT32_MAX = unlimited)
    slot_s: float                # τ: bottleneck serialization time
    n_slots: int                 # simulation horizon in slots
    ack_lag: int                 # slots from departure to ack arrival
    queue_cap: int               # bottleneck queue capacity (packets)
    burst_cap: int               # per-flow packets enqueueable per slot
    base_rtt_s: float            # unloaded RTT (for Vegas/Veno diff)
    seg_bytes: int               # application payload per packet
    #: (F,) ECN-capable flows (variant REQUIRES_ECN or UseEcn socket
    #: attribute): the AQM marks their packets instead of early-dropping
    ecn: np.ndarray = None
    #: bottleneck AQM: "fifo" (tail drop) or "red"
    qdisc: str = "fifo"
    red_min_th: float = 5.0
    red_max_th: float = 15.0
    red_max_p: float = 0.02      # 1 / LInterm
    red_qw: float = 0.002
    red_gentle: bool = True
    red_use_ecn: bool = False
    red_use_hard_drop: bool = True
    #: device-resident workload (tpudes.traffic.TrafficProgram over the
    #: F flows): None = the legacy bulk source (infinite application
    #: backlog, bit-identical compile).  With a program, each flow is
    #: APP-LIMITED: it may only keep ``delivered + inflight`` below the
    #: workload's cumulative offered segments (closed-form on device),
    #: so bursts and think-times shape the congestion dynamics.  Model
    #: id + params are traced operands — only ``traffic.shape_key()``
    #: enters the runner cache key.
    traffic: object = None

    @property
    def buf_len(self) -> int:
        return self.ack_lag + 2


class UnliftableDumbbellError(ValueError):
    """The object graph is not a dumbbell this lowering can faithfully
    represent; callers fall back to the scalar DES."""


def lower_dumbbell(sim_end_s: float) -> DumbbellProgram:
    """Lower the live object graph (NodeList) to a DumbbellProgram.

    Discovers the bottleneck as the unique p2p link whose BOTH endpoint
    nodes forward (≥3 interfaces, no applications); flows are
    BulkSendApplications on leaf nodes whose sink lives across the
    bottleneck.  Rejects shapes the slot model cannot represent.
    """
    from tpudes.models.applications import BulkSendApplication, PacketSink
    from tpudes.models.internet.ipv4 import Ipv4L3Protocol
    from tpudes.models.internet.tcp import TcpL4Protocol
    from tpudes.models.p2p import PointToPointNetDevice
    from tpudes.network.node import NodeList

    nodes = [NodeList.GetNode(i) for i in range(NodeList.GetNNodes())]

    def n_ifaces(node):
        ipv4 = node.GetObject(Ipv4L3Protocol)
        return len(ipv4.interfaces) - 1 if ipv4 else 0  # minus loopback

    routers = [n for n in nodes if n_ifaces(n) >= 3 and n.GetNApplications() == 0]
    router_ids = {id(n) for n in routers}
    candidates = []
    for n in routers:
        for d in range(n.GetNDevices()):
            dev = n.GetDevice(d)
            if not isinstance(dev, PointToPointNetDevice):
                continue
            ch = dev.GetChannel()
            peer = ch.GetPeer(dev)
            if id(peer.GetNode()) in router_ids and peer.GetNode() is not n:
                candidates.append((dev, peer, ch))
    # each link appears once from each endpoint; a true dumbbell has
    # exactly one router-router link
    links = {id(c[2]) for c in candidates}
    if not candidates:
        raise UnliftableDumbbellError("no router-router bottleneck link found")
    if len(links) > 1:
        raise UnliftableDumbbellError(
            f"{len(links)} router-router links (multi-path topology); the "
            "slot model represents exactly one bottleneck"
        )
    bdev, bpeer, bchan = candidates[0]
    left_router, right_router = bdev.GetNode(), bpeer.GetNode()
    bn_rate = float(bdev.data_rate.GetBitRate())
    bn_delay_s = bchan.GetDelay().GetSeconds()
    qs = bdev.GetQueue().max_size
    if qs.mode != qs.PACKETS:
        raise UnliftableDumbbellError(
            "slot model counts queue capacity in packets (byte-mode queue)"
        )
    queue_cap = int(qs.value)

    # sinks by (address, port) so each bulk app can be paired; any app
    # kind the slot model does not represent is cross-traffic that would
    # silently vanish from the shared queue — reject, don't drop
    sinks = {}
    for node in nodes:
        for a in range(node.GetNApplications()):
            app = node.GetApplication(a)
            if not isinstance(app, (BulkSendApplication, PacketSink)):
                raise UnliftableDumbbellError(
                    f"unmodeled application {type(app).__name__} on node "
                    f"{node.GetId()} (cross-traffic would be dropped)"
                )
            if isinstance(app, PacketSink):
                port = app.local.GetPort()
                ipv4 = node.GetObject(Ipv4L3Protocol)
                for iface in ipv4.interfaces[1:]:
                    for addr in iface.addresses:
                        sinks[(addr.GetLocal().addr, port)] = node

    def access_router(leaf):
        """The router a leaf's single access link attaches to."""
        acc = leaf.GetDevice(0)
        if not isinstance(acc, PointToPointNetDevice):
            raise UnliftableDumbbellError("leaf access link is not p2p")
        return acc.GetChannel().GetPeer(acc).GetNode()

    flows, variants, starts, stops, budgets, ecns = [], [], [], [], [], []
    seg_sizes, access_rates, access_delays = set(), set(), []
    directions: set[bool] = set()
    for node in nodes:
        for a in range(node.GetNApplications()):
            app = node.GetApplication(a)
            if not isinstance(app, BulkSendApplication):
                continue
            dst = app.remote  # InetSocketAddress
            sink_node = sinks.get((dst.GetIpv4().addr, dst.GetPort()))
            if sink_node is None:
                raise UnliftableDumbbellError(
                    f"bulk sender on node {node.GetId()} has no matching sink"
                )
            if n_ifaces(node) != 1 or n_ifaces(sink_node) != 1:
                raise UnliftableDumbbellError(
                    "bulk flows must run leaf-to-leaf (one access interface)"
                )
            # every flow must cross the bottleneck, all in the SAME
            # direction: a same-side flow never touches the modeled
            # queue, and opposing flows queue on the two different link
            # directions — both would be silent mis-lowerings
            src_r, dst_r = access_router(node), access_router(sink_node)
            if {src_r, dst_r} != {left_router, right_router}:
                raise UnliftableDumbbellError(
                    f"flow node{node.GetId()}→node{sink_node.GetId()} does "
                    "not cross the bottleneck; the slot model represents "
                    "one shared queue"
                )
            directions.add(src_r is left_router)
            acc = node.GetDevice(0)
            access_rates.add(float(acc.data_rate.GetBitRate()))
            access_delays.append(acc.GetChannel().GetDelay().GetSeconds())
            sink_acc = sink_node.GetDevice(0)
            access_delays.append(sink_acc.GetChannel().GetDelay().GetSeconds())
            tcp = node.GetObject(TcpL4Protocol)
            vname = tcp.GetAttribute("SocketType") if tcp else "TcpNewReno"
            if vname not in VARIANTS:
                raise UnliftableDumbbellError(f"unknown TCP variant {vname}")
            seg_sizes.add(int(app.send_size))
            flows.append(app)
            from tpudes.models.internet.tcp_congestion import TCP_VARIANTS

            ecns.append(
                bool(getattr(tcp, "use_ecn", False))
                or bool(getattr(TCP_VARIANTS[vname], "REQUIRES_ECN", False))
            )
            variants.append(VARIANTS.index(vname))
            starts.append(app.start_time.GetSeconds())
            stops.append(
                app.stop_time.GetSeconds()
                if app.stop_time.GetTimeStep() > 0
                else sim_end_s
            )
            budgets.append(int(app.max_bytes) if app.max_bytes else 0)
    if not flows:
        raise UnliftableDumbbellError("no TCP bulk flows found")
    if len(directions) > 1:
        raise UnliftableDumbbellError(
            "flows cross the bottleneck in both directions; the slot "
            "model represents one direction of one shared queue"
        )
    if len(seg_sizes) > 1:
        raise UnliftableDumbbellError(
            f"flows must share one SendSize — the slot is one on-wire "
            f"packet time (got {sorted(seg_sizes)})"
        )
    if len(access_rates) != 1:
        raise UnliftableDumbbellError(
            f"access links must share one rate (got {sorted(access_rates)})"
        )
    access_rate = access_rates.pop()
    if access_rate <= bn_rate:
        raise UnliftableDumbbellError(
            "access links must be faster than the bottleneck for the "
            "slot model (queueing would form at the leaves)"
        )
    seg = max(seg_sizes) if seg_sizes else 536
    pkt_bits = (seg + 40) * 8  # +IPv4/TCP headers on the wire
    slot_s = pkt_bits / bn_rate
    acc_d = float(np.mean(access_delays)) if access_delays else 0.0
    # after leaving the queue: prop + far access (data), then the ack's
    # reverse trip (access + bottleneck prop + access)
    ack_lag_s = 2.0 * bn_delay_s + 4.0 * acc_d
    base_rtt_s = ack_lag_s + slot_s

    # --- bottleneck AQM (traffic-control root qdisc on the tx device
    # of the modeled direction) -----------------------------------------
    from tpudes.models.traffic_control import (
        FifoQueueDisc,
        RedQueueDisc,
        TrafficControlLayer,
    )

    src_is_left = directions.pop()
    tx_dev = bdev if src_is_left else bpeer
    tcl = tx_dev.GetNode().GetObject(TrafficControlLayer)
    qd = tcl.GetRootQueueDisc(tx_dev) if tcl is not None else None
    qdisc_kind, red_kw = "fifo", {}
    if isinstance(qd, RedQueueDisc):
        qdisc_kind = "red"
        queue_cap = int(qd.max_packets)
        red_kw = dict(
            red_min_th=float(qd.min_th),
            red_max_th=float(qd.max_th),
            red_max_p=1.0 / float(qd.l_interm),
            red_qw=float(qd.qw),
            red_gentle=bool(qd.gentle),
            red_use_ecn=bool(qd.use_ecn),
            red_use_hard_drop=bool(qd.use_hard_drop),
        )
    elif isinstance(qd, FifoQueueDisc):
        queue_cap = int(qd.max_packets)
    elif qd is not None:
        raise UnliftableDumbbellError(
            f"bottleneck qdisc {type(qd).__name__} has no slot-model "
            "analog (fifo and RED are modeled)"
        )
    return DumbbellProgram(
        n_flows=len(flows),
        variant_idx=np.asarray(variants, np.int32),
        start_slot=np.asarray(
            [int(s / slot_s) for s in starts], np.int32
        ),
        stop_slot=np.asarray(
            [int(min(s, sim_end_s) / slot_s) for s in stops], np.int32
        ),
        max_pkts=np.asarray(
            [(b + seg - 1) // seg if b else 2**31 - 1 for b in budgets],
            np.int32,
        ),
        slot_s=slot_s,
        n_slots=int(math.ceil(sim_end_s / slot_s)),
        ack_lag=max(1, int(round(ack_lag_s / slot_s))),
        queue_cap=queue_cap,
        burst_cap=max(1, int(access_rate / bn_rate)),
        base_rtt_s=base_rtt_s,
        seg_bytes=seg,
        ecn=np.asarray(ecns, bool),
        qdisc=qdisc_kind,
        **red_kw,
    )


# --- the window rules: a table keyed by variant id --------------------------
#
# A slot program is built for the SET of variants a launch assigns
# (``present``: static, part of the runner's key); the per-flow ids
# ``var`` stay a traced operand inside it.  ``_RULES`` gives each
# variant its increase rule, its ssthresh rule, the shared estimators
# it reads and the side leaves its own rules keep.  A program traces
# the rules and estimators of ``present`` only, and its selects range
# over ``present`` only.  A rule's arithmetic is elementwise over
# (replica, flow), so a flow's window is bit for bit what the program
# built for all seventeen gives it: only rules whose result that
# program's select discarded are gone.


class _Flows:
    """The traced per-flow variant ids, seen through the static set of
    variants the program was built for."""

    def __init__(self, var, present):
        self.var, self.present = var, present

    def __contains__(self, v):
        return v in self.present

    def mask(self, *ids):
        """The flows that run one of ``ids`` (those of them that are
        present; one at least): a fresh compare on the traced ids, or
        ``True`` where the program was built for nothing else (no
        compare at all)."""
        ids = [v for v in ids if v in self.present]
        if len(ids) == len(self.present):
            return True
        m = self.var == ids[0]
        for v in ids[1:]:
            m = m | (self.var == v)
        return m


def _where(mask, x, y):
    """``jnp.where`` under a :meth:`_Flows.mask`."""
    return x if mask is True else jnp.where(mask, x, y)


def _both(mask, x):
    """``mask & x`` under a :meth:`_Flows.mask`."""
    return x if mask is True else mask & x


def _select(cases, default=0):
    """``jnp.select`` over ``(mask, value)`` cases, first match wins; a
    ``True`` mask (the program's only variant) is the whole answer."""
    if cases[0][0] is True:
        return cases[0][1]
    return jnp.select(
        [m for m, _ in cases], [x for _, x in cases], default
    )


class _Call:
    """What the rules of one call share: the flows, the operands, the
    side leaves as they came in (``st``) and those written so far
    (``out``), and the values more than one rule reads, each traced
    once, where the first rule asks for it."""

    def __init__(self, flows, st, **operands):
        self.flows, self.st, self.out = flows, st, {}
        self.__dict__.update(operands)

    @functools.cached_property
    def sampled(self):
        return self.ar > 0

    @functools.cached_property
    def inc_reno(self):
        return self.a / self.w

    @functools.cached_property
    def diff(self):
        # vegas / veno / yeah backlog estimate from the shared rtt sample
        st = self.st
        return self.w * (
            1.0 - st["base_rtt"] / jnp.maximum(self.rtt_s, st["base_rtt"])
        )

    @functools.cached_property
    def rho(self):
        # Hybla: growth normalized by rho = RTT / 25 ms
        return jnp.maximum(self.rtt_s / HYBLA_RRTT, 1.0)

    @functools.cached_property
    def in_infer(self):
        return self.t_s < self.st["lp_until"]


# --- PktsAcked-analog side estimators (raw acks), shared by variants -------


def _est_min_rtt(c):
    st = c.st
    c.min_rtt = c.out["min_rtt"] = jnp.where(
        c.sampled, jnp.minimum(st["min_rtt"], c.rtt_s), st["min_rtt"]
    )


def _est_westwood(c):
    st, w, ar, rtt_s, sampled = c.st, c.w, c.ar, c.rtt_s, c.sampled
    # Westwood+: EWMA bandwidth once ~a cwnd's worth of acks arrived
    ww_acc = st["ww_acc"] + ar
    ww_done = sampled & (ww_acc >= w)
    ww_sample = ww_acc / jnp.maximum(rtt_s, 1e-6)
    bwe = jnp.where(
        ww_done,
        jnp.where(st["bwe"] == 0.0, ww_sample,
                  0.9 * st["bwe"] + 0.1 * ww_sample),
        st["bwe"],
    )
    ww_acc = jnp.where(ww_done, 0.0, ww_acc)
    c.out.update(ww_acc=ww_acc, bwe=bwe)


def _est_ill_max_rtt(c):
    st = c.st
    c.ill_max = c.out["ill_max_rtt"] = jnp.where(
        c.sampled, jnp.maximum(st["ill_max_rtt"], c.rtt_s),
        st["ill_max_rtt"],
    )


def _est_illinois(c):
    st, rtt_s, sampled = c.st, c.rtt_s, c.sampled
    ill_max, min_rtt = c.ill_max, c.min_rtt
    # Illinois: delay-modulated alpha/beta
    dm = ill_max - min_rtt
    da = jnp.maximum(rtt_s - min_rtt, 0.0)
    d1 = 0.01 * dm
    k_ill = (ILL_ALPHA_MAX - ILL_ALPHA_MIN) / jnp.maximum(dm - d1, 1e-9)
    alpha_raw = jnp.where(
        da <= d1, ILL_ALPHA_MAX,
        jnp.maximum(ILL_ALPHA_MAX - k_ill * (da - d1), ILL_ALPHA_MIN),
    )
    beta_raw = jnp.clip(
        ILL_BETA_MIN
        + (ILL_BETA_MAX - ILL_BETA_MIN) * da / jnp.maximum(dm, 1e-9),
        ILL_BETA_MIN, ILL_BETA_MAX,
    )
    c.ill_alpha = jnp.where(
        sampled, jnp.where(dm <= 0.0, ILL_ALPHA_MAX, alpha_raw),
        st["ill_alpha"],
    )
    ill_beta = jnp.where(
        sampled, jnp.where(dm <= 0.0, ILL_BETA_MIN, beta_raw),
        st["ill_beta"],
    )
    c.out.update(ill_alpha=c.ill_alpha, ill_beta=ill_beta)


def _est_bbr(c):
    st, w, ar, rtt_s, sampled = c.st, c.w, c.ar, c.rtt_s, c.sampled
    # BBR: per-round max-filtered delivery rate + state machine
    bbr_acc = st["bbr_acc"] + ar
    round_done = sampled & (bbr_acc >= w)
    bbr_sample = bbr_acc / jnp.maximum(rtt_s, 1e-6)
    bbr_bw = jnp.where(
        round_done,
        jnp.maximum(st["bbr_bw"] * BBR_BW_DECAY, bbr_sample),
        st["bbr_bw"],
    )
    bbr_acc = jnp.where(round_done, 0.0, bbr_acc)
    grew = bbr_sample > st["bbr_full_bw"] * 1.25
    bbr_full_bw = jnp.where(round_done & grew, bbr_sample, st["bbr_full_bw"])
    bbr_full_cnt = jnp.where(
        round_done,
        jnp.where(grew, 0, st["bbr_full_cnt"] + 1),
        st["bbr_full_cnt"],
    )
    state = st["bbr_state"]
    pipe_full = round_done & (state == BBR_STARTUP) & (bbr_full_cnt >= 3)
    state = jnp.where(pipe_full, BBR_DRAIN, state)
    # one round of DRAIN, then PROBE_BW cycling
    leave_drain = round_done & (st["bbr_state"] == BBR_DRAIN)
    state = jnp.where(leave_drain, BBR_PROBE_BW, state)
    bbr_cycle = jnp.where(
        round_done & (state == BBR_PROBE_BW),
        (st["bbr_cycle"] + 1) % len(BBR_CYCLE_GAINS),
        st["bbr_cycle"],
    )
    c.bbr_bw, c.bbr_state, c.bbr_cycle = bbr_bw, state, bbr_cycle
    c.out.update(bbr_acc=bbr_acc, bbr_bw=bbr_bw, bbr_full_bw=bbr_full_bw,
                 bbr_full_cnt=bbr_full_cnt, bbr_state=state,
                 bbr_cycle=bbr_cycle)


#: the shared estimators by name, with the side leaves each keeps.  The
#: first five run before the increase rules, in this order (a later one
#: reads an earlier one's result); ``diff`` is traced where the first
#: rule asks for it (:attr:`_Call.diff`; its ``last_diff`` is written
#: last) and ``dctcp`` is the marked-fraction EWMA in ``step_fn``
_ESTIMATORS = {
    "min_rtt": (_est_min_rtt, ("min_rtt",)),
    "westwood": (_est_westwood, ("ww_acc", "bwe")),
    "ill_max_rtt": (_est_ill_max_rtt, ("ill_max_rtt",)),
    "illinois": (_est_illinois, ("ill_alpha", "ill_beta")),
    "bbr": (_est_bbr, ("bbr_acc", "bbr_bw", "bbr_full_bw", "bbr_full_cnt",
                       "bbr_state", "bbr_cycle")),
    "diff": (None, ("base_rtt", "last_diff")),
    "dctcp": (None, ("dctcp_alpha",)),
}


# --- congestion avoidance rules (per ack batch) ----------------------------


def _grow_reno(c):
    return c.inc_reno


def _grow_scalable(c):
    return c.a / jnp.minimum(c.w, SCALABLE_AI)


def _grow_highspeed(c):
    w, a = c.w, c.a
    a_hs = jnp.where(
        w <= HS_LOW_WINDOW, 1.0, jnp.maximum(1.0, 0.156 * w**0.8 / 2.0)
    )
    return a_hs * a / w


def _grow_cubic(c):
    st, w, a, t_s, rtt_s = c.st, c.w, c.a, c.t_s, c.rtt_s
    # cubic: (re)open an epoch on first CA ack after loss
    fresh = (st["epoch_t"] < 0.0) & (a > 0) & ~c.in_ss
    k = jnp.where(
        st["w_max"] > w,
        jnp.cbrt(jnp.maximum(st["w_max"] - w, 0.0) / CUBIC_C),
        0.0,
    )
    origin = jnp.maximum(st["w_max"], w)
    epoch_t = jnp.where(fresh, t_s, st["epoch_t"])
    k = jnp.where(fresh, k, st["k"])
    origin = jnp.where(fresh, origin, st["origin"])
    w_est = jnp.where(fresh, w, st["w_est"])
    te = t_s - epoch_t + rtt_s
    target = origin + CUBIC_C * (te - k) ** 3
    w_est = w_est + 3.0 * (1 - CUBIC_BETA) / (1 + CUBIC_BETA) * a / w
    target = jnp.maximum(target, w_est)
    c.out.update(epoch_t=epoch_t, k=k, origin=origin, w_est=w_est)
    return jnp.clip((target - w) / w, 0.0, 0.5) * a


def _grow_vegas(c):
    w, a, diff = c.w, c.a, c.diff
    return jnp.where(
        diff < VEGAS_ALPHA, a / w, jnp.where(diff > VEGAS_BETA, -a / w, 0.0)
    )


def _grow_veno(c):
    return jnp.where(c.diff < VENO_BETA, c.inc_reno, 0.5 * c.inc_reno)


def _grow_linux_reno(c):
    st, w, a = c.st, c.w, c.a
    # Linux reno (and DCTCP, which inherits it): whole-cwnd ack counting
    c.is_lr = c.flows.mask(V_LINUXRENO, V_DCTCP)
    cnt = st["cwnd_cnt"] + a
    whole = jnp.floor(cnt / w)
    c.out["cwnd_cnt"] = jnp.where(
        _both(c.is_lr, ~c.in_ss) & (a > 0), cnt - whole * w, st["cwnd_cnt"]
    )
    return whole


def _grow_bic(c):
    st, w, a = c.st, c.w, c.a
    # BIC: binary search toward w_max, max-probe beyond it
    bic_mid = jnp.minimum((st["w_max"] - w) / 2.0, BIC_MAX_INCR)
    bic_probe = jnp.minimum(w - st["w_max"] + 1.0, BIC_MAX_INCR)
    bic_inc = jnp.maximum(
        jnp.where(w < st["w_max"], bic_mid, bic_probe), BIC_SMIN
    )
    return jnp.where(
        (w < BIC_LOW_WND) | (st["w_max"] == 0.0),
        c.inc_reno, a * bic_inc / w,
    )


def _grow_illinois(c):
    return c.ill_alpha * c.a / c.w


def _grow_hybla(c):
    rho = c.rho
    return c.a * rho * rho / c.w


def _grow_htcp(c):
    st = c.st
    # H-TCP: additive increase grows with time since the last congestion
    # event (quadratic past the 1 s low-speed boundary), scaled by the
    # adaptive backoff beta carried in st["htcp_beta"]
    h_delta = jnp.maximum(c.t_s - st["htcp_last_cong"] - HTCP_DELTA_B, 0.0)
    h_alpha = jnp.maximum(
        2.0 * (1.0 - st["htcp_beta"])
        * (1.0 + 10.0 * h_delta + 0.25 * h_delta * h_delta),
        1.0,
    )
    return h_alpha * c.a / c.w


def _grow_yeah(c):
    w, a, diff = c.w, c.a, c.diff
    # YeAH: STCP fast mode while the backlog estimate (the shared
    # Vegas-style `diff`) stays under Q_max; Reno slow mode past it with
    # the precautionary decongestion shed spread over one cwnd of acks
    return jnp.where(
        diff < YEAH_QMAX,
        a / jnp.minimum(w, YEAH_ALPHA),
        (1.0 - diff * (1.0 - YEAH_RHO)) * a / w,
    )


def _grow_ledbat(c):
    st, rtt_s = c.st, c.rtt_s
    # LEDBAT: window tracks the 100 ms queueing-delay target; negative
    # off-target shrinks the window (scavenger behavior)
    qdelay = jnp.maximum(rtt_s - jnp.minimum(st["min_rtt"], rtt_s), 0.0)
    return (
        LEDBAT_GAIN * (LEDBAT_TARGET_S - qdelay) / LEDBAT_TARGET_S
        * c.a / c.w
    )


def _grow_lp(c):
    # TCP-LP: Reno growth outside the inference phase (the early-
    # congestion collapse itself is applied after the select)
    return jnp.where(c.in_infer, 0.0, c.inc_reno)


# --- GetSsThresh rules (on a detected loss) --------------------------------


def _cut_reno(c):
    return c.w / 2.0


def _cut_cubic(c):
    st, w = c.st, c.w
    # cubic fast convergence: remember a reduced w_max when still climbing
    c.w_max[V_CUBIC] = jnp.where(
        w < st["w_max"], w * (1.0 + CUBIC_BETA) / 2.0, w
    )
    return w * CUBIC_BETA


def _cut_scalable(c):
    return c.w * (1.0 - SCALABLE_MD)


def _cut_highspeed(c):
    w = c.w
    b_hs = jnp.where(
        w <= HS_LOW_WINDOW,
        0.5,
        jnp.maximum(
            0.5
            - 0.4
            * (jnp.log(w) - math.log(HS_LOW_WINDOW))
            / (math.log(83000.0) - math.log(HS_LOW_WINDOW)),
            0.1,
        ),
    )
    return w * (1.0 - b_hs)


def _cut_veno(c):
    w = c.w
    return jnp.where(c.st["last_diff"] < VENO_BETA, w * 0.8, w * 0.5)


def _cut_bic(c):
    st, w = c.st, c.w
    # BIC fast convergence mirrors cubic's w_max bookkeeping at β=0.8
    c.w_max[V_BIC] = jnp.where(
        w < st["w_max"], w * (1.0 + BIC_BETA) / 2.0, w
    )
    return w * BIC_BETA


def _cut_westwood(c):
    st, w = c.st, c.w
    # Westwood+: BWE · RTTmin instead of blind halving
    return jnp.where(
        (st["bwe"] > 0.0) & jnp.isfinite(st["min_rtt"]),
        st["bwe"] * st["min_rtt"], w / 2.0,
    )


def _cut_illinois(c):
    return c.w * (1.0 - c.st["ill_beta"])


def _cut_bbr(c):
    st = c.st
    # BBR ignores loss beyond the BDP floor
    return jnp.maximum(st["bbr_bw"] * jnp.where(
        jnp.isfinite(st["min_rtt"]), st["min_rtt"], 0.0
    ), 4.0)


def _cut_dctcp(c):
    # DCTCP: reduction fraction follows the marked-byte EWMA
    return c.w * (1.0 - c.st["dctcp_alpha"] / 2.0)


def _cut_htcp(c):
    st = c.st
    # H-TCP adaptive backoff: beta = RTTmin/RTTmax clamped to [0.5, 0.8]
    # once an RTT spread exists, default 0.5 before
    h_valid = (st["ill_max_rtt"] > 0.0) & jnp.isfinite(st["min_rtt"])
    c.h_beta = jnp.where(
        h_valid,
        jnp.clip(
            st["min_rtt"] / jnp.maximum(st["ill_max_rtt"], 1e-9), 0.5, 0.8
        ),
        HTCP_DEFAULT_BACKOFF,
    )
    return c.w * c.h_beta


def _cut_yeah(c):
    w = c.w
    # YeAH: shed the larger of the measured backlog and cwnd/8
    return w - jnp.maximum(c.st["last_diff"], w / 8.0)


@dataclass(frozen=True)
class _Rule:
    """One variant's window rules."""

    #: congestion-avoidance increase per ack batch; None: BBR keeps no
    #: AIMD increase (its window tracks gain × BDP)
    grow: object
    #: ssthresh on a detected loss
    cut: object
    #: the shared estimators (``_ESTIMATORS``) its rules read
    uses: tuple = ()
    #: the side leaves its own rules keep (read and write)
    leaves: tuple = ()


_RULES = {
    V_NEWRENO: _Rule(_grow_reno, _cut_reno),
    V_CUBIC: _Rule(
        _grow_cubic, _cut_cubic,
        leaves=("w_max", "epoch_t", "k", "origin", "w_est"),
    ),
    V_SCALABLE: _Rule(_grow_scalable, _cut_scalable),
    V_HIGHSPEED: _Rule(_grow_highspeed, _cut_highspeed),
    V_VEGAS: _Rule(_grow_vegas, _cut_reno, uses=("diff",)),
    V_VENO: _Rule(_grow_veno, _cut_veno, uses=("diff",)),
    V_LINUXRENO: _Rule(_grow_linux_reno, _cut_reno, leaves=("cwnd_cnt",)),
    V_BIC: _Rule(_grow_bic, _cut_bic, leaves=("w_max",)),
    V_WESTWOOD: _Rule(_grow_reno, _cut_westwood, uses=("min_rtt", "westwood")),
    V_ILLINOIS: _Rule(
        _grow_illinois, _cut_illinois,
        uses=("min_rtt", "ill_max_rtt", "illinois"),
    ),
    V_HYBLA: _Rule(_grow_hybla, _cut_reno),
    V_BBR: _Rule(None, _cut_bbr, uses=("min_rtt", "bbr")),
    V_DCTCP: _Rule(
        _grow_linux_reno, _cut_dctcp, uses=("dctcp",), leaves=("cwnd_cnt",)
    ),
    V_HTCP: _Rule(
        _grow_htcp, _cut_htcp, uses=("min_rtt", "ill_max_rtt"),
        leaves=("htcp_beta", "htcp_last_cong"),
    ),
    V_YEAH: _Rule(_grow_yeah, _cut_yeah, uses=("diff",)),
    V_LEDBAT: _Rule(_grow_ledbat, _cut_reno, uses=("min_rtt",)),
    V_LP: _Rule(
        _grow_lp, _cut_reno, uses=("min_rtt", "ill_max_rtt"),
        leaves=("lp_until",),
    ),
}

#: the order the increase rules are traced in: the masked-dense step's
#: (HighSpeed before CUBIC), so that a program built for all seventeen
#: is that step's, equation for equation; the ssthresh rules and every
#: select go by variant id
_GROW_ORDER = (V_NEWRENO, V_SCALABLE, V_HIGHSPEED, V_CUBIC) + tuple(
    range(V_VEGAS, len(VARIANTS))
)

#: what a builder that is handed no set compiles: every rule
ALL_VARIANTS = tuple(range(len(VARIANTS)))


def variant_set(points) -> tuple:
    """The sorted ids of the variants a launch assigns, a sweep's
    points pooled: the static part of the runner's key that says which
    window rules its slot program holds."""
    return tuple(sorted({int(i) for p in points for i in p}))


def _used(present) -> set:
    """The shared estimators the rules of ``present`` read."""
    return {name for v in present for name in _RULES[v].uses}


def live_side_leaves(present) -> set:
    """The side leaves some rule of ``present`` reads or writes.  The
    slot passes every other one through untouched."""
    return {
        leaf for v in present for leaf in _RULES[v].leaves
    } | {leaf for name in _used(present) for leaf in _ESTIMATORS[name][1]}


def _trace_rules(c, order, kind) -> dict:
    """``{rule: its result}`` of the ``kind`` (``grow`` / ``cut``) rules
    of the present variants among ``order``, each rule traced once
    (variants share rules) and in that order."""
    done = {}
    for v in order:
        rule = getattr(_RULES[v], kind)
        if v in c.flows and rule is not None and rule not in done:
            done[rule] = rule(c)
    return done


def _cwnd_increase(flows, cwnd, ssthresh, acked, t_s, rtt_s, st,
                   acked_raw=None):
    """Vectorized per-ack cwnd growth (segments) for the variants
    ``flows`` was built for.

    ``st`` carries the variant side-state dict; returns (new_cwnd,
    ssthresh, st').  Each present variant's increase rule computes for
    every flow and the traced ids select among them; a rule, an
    estimator or a side leaf that no present variant names is not
    traced (its leaf passes through).  ``acked_raw`` (defaults to
    ``acked``) feeds the PktsAcked-analog estimators (min-RTT, Westwood
    BWE, Illinois delay, BBR rounds) — the host calls PktsAcked on
    every ack, recovery or not, while window growth sees only the
    recovery-masked count.
    """
    present = flows.present
    w = jnp.maximum(cwnd, 1.0)
    a = acked.astype(jnp.float32)
    c = _Call(
        flows, st, w=w, a=a,
        ar=a if acked_raw is None else acked_raw.astype(jnp.float32),
        in_ss=cwnd < ssthresh, t_s=t_s, rtt_s=rtt_s,
    )
    in_ss = c.in_ss
    used = _used(present)
    for name, (estimate, _) in _ESTIMATORS.items():
        if estimate is not None and name in used:
            estimate(c)

    incs = _trace_rules(c, _GROW_ORDER, "grow")
    new_cwnd = cwnd
    if incs:
        # one case a variant; LinuxReno and DCTCP share a rule and its
        # mask, so they share a case
        inc_ca = _select([
            (c.is_lr if _RULES[v].grow is _grow_linux_reno
             else flows.mask(v), incs[_RULES[v].grow])
            for v in present
            if _RULES[v].grow is not None
            and not (v == V_DCTCP and V_LINUXRENO in flows)
        ])
        # slow start: +1 per ack (Hybla: 2^rho − 1 per ack); Vegas leaves
        # SS once the backlog passes γ
        vegas_exit = None
        if V_VEGAS in flows:
            vegas_exit = (
                _both(flows.mask(V_VEGAS), in_ss) & (c.diff > VEGAS_GAMMA)
                & (a > 0)
            )
            ssthresh = jnp.where(
                vegas_exit, jnp.maximum(w - 1.0, 2.0), ssthresh
            )
        inc_ss = a
        if V_HYBLA in flows:
            inc_ss = _where(
                flows.mask(V_HYBLA), a * (2.0**c.rho - 1.0), a
            )
        inc = jnp.where(
            in_ss if vegas_exit is None else in_ss & ~vegas_exit,
            inc_ss, inc_ca,
        )
        floor = jnp.float32(2.0)
        if V_LP in flows:
            # TCP-LP yields completely while inferring congestion: the
            # collapsed 1-segment window must not slow-start straight
            # back up, or the scavenger stops yielding (the host's
            # ack-clocked hold is slower than this slot model's, so the
            # gate covers slow start too)
            inc = jnp.where(_both(flows.mask(V_LP), c.in_infer), 0.0, inc)
            # TCP-LP's inference collapse holds at ONE segment (host
            # behavior); every other variant keeps the usual 2-segment
            # floor
            floor = jnp.where(
                _both(flows.mask(V_LP), c.in_infer),
                jnp.float32(1.0), jnp.float32(2.0),
            )
        new_cwnd = jnp.maximum(cwnd + jnp.where(a > 0, inc, 0.0), floor)

    if V_BBR in flows:
        state, bbr_bw = c.bbr_state, c.bbr_bw
        # BBR replaces loss-driven AIMD entirely: cwnd tracks gain × BDP
        gain = jnp.select(
            [state == BBR_STARTUP, state == BBR_DRAIN],
            [BBR_HIGH_GAIN, 1.0 / BBR_HIGH_GAIN],
            # dtype pinned: an unpinned float table would ride f64
            # through the whole BBR lane under ambient x64 (JXL002)
            jnp.asarray(BBR_CYCLE_GAINS, jnp.float32)[c.bbr_cycle],
        )
        bdp = bbr_bw * c.min_rtt
        target = jnp.maximum(gain * bdp, 4.0)
        cwnd_bbr = jnp.where(
            bbr_bw == 0.0,
            cwnd + a,                                 # first RTTs
            jnp.where(
                cwnd < target,
                cwnd + jnp.minimum(a, target - cwnd + 1.0),
                jnp.maximum(target, 4.0),
            ),
        )
        new_cwnd = _where(
            flows.mask(V_BBR), jnp.where(a > 0, cwnd_bbr, cwnd), new_cwnd
        )

    if V_LP in flows:
        ill_max, min_rtt = c.ill_max, c.min_rtt
        # TCP-LP early-congestion inference: one-way delay past 15% of
        # the observed delay range collapses the window to one segment
        # and holds the inference phase for one RTT (host PktsAcked hook)
        lp_trigger = (
            _both(flows.mask(V_LP), c.sampled) & (ill_max > min_rtt)
            & (rtt_s > min_rtt + LP_INFERENCE_FRAC * (ill_max - min_rtt))
            & ~c.in_infer
        )
        new_cwnd = jnp.where(lp_trigger, 1.0, new_cwnd)
        ssthresh = jnp.where(
            lp_trigger, jnp.maximum(ssthresh / 2.0, 2.0), ssthresh
        )
        c.out["lp_until"] = jnp.where(
            lp_trigger, t_s + rtt_s, st["lp_until"]
        )
    if "diff" in used:
        c.out["last_diff"] = jnp.where(a > 0, c.diff, st["last_diff"])
    return new_cwnd, ssthresh, dict(st, **c.out)


def _loss_response(flows, cwnd, st, t_s):
    """Vectorized GetSsThresh on a detected loss (segments), of the
    variants ``flows`` was built for.

    ``t_s`` stamps H-TCP's last-congestion clock (its additive increase
    grows with the time elapsed since this moment)."""
    c = _Call(flows, st, w=jnp.maximum(cwnd, 1.0))
    c.w_max = {}   # CUBIC's and BIC's fast convergence, by variant
    ss = _trace_rules(c, flows.present, "cut")
    ssthresh = _select(
        [(flows.mask(v), ss[_RULES[v].cut]) for v in flows.present]
    )
    ssthresh = jnp.maximum(ssthresh, 2.0)
    out = {}
    if c.w_max:
        out["w_max"] = _select(
            [(flows.mask(v), x) for v, x in c.w_max.items()], st["w_max"]
        )
    if V_CUBIC in flows:
        out["epoch_t"] = jnp.full_like(st["epoch_t"], -1.0)
    if V_HTCP in flows:
        out["htcp_beta"] = _where(
            flows.mask(V_HTCP), c.h_beta, st["htcp_beta"]
        )
        out["htcp_last_cong"] = _where(
            flows.mask(V_HTCP), t_s, st["htcp_last_cong"]
        )
    return ssthresh, dict(st, **out)


#: queue-occupancy histogram bins for the on-device obs accumulators
OBS_QHIST_BINS = 16

#: ``jax.named_scope`` names inside one slot, in ``tf_op`` of the
#: operations they cover (names only: the arithmetic is what it was):
#: the slot's keys (``runtime.step_keys`` traces under the same
#: ``tpudes.dumbbell.rng``) and its draws; the window rules
#: (``_cwnd_increase`` + ``_loss_response``, of the variants the
#: program was built for, and the selects that apply them); departure
#: and admission at the bottleneck queue
RNG_SCOPE = "tpudes.dumbbell.rng"
CC_SCOPE = "tpudes.dumbbell.cc"
QUEUE_SCOPE = "tpudes.dumbbell.queue"


def build_dumbbell_step(prog: DumbbellProgram, replicas: int,
                        obs: bool = False, present: tuple = ALL_VARIANTS):
    """Return (init_state, step_fn) for the slot-stepped scan.

    ``step_fn(s, (t, key), var, ecn_cap)`` — ``t`` is the slot counter
    and ``key`` the LAUNCH key, the same at every slot: ``step_fn``
    derives the slot's per-replica keys itself, from the two, through
    :func:`runtime.step_keys` (no caller folds ``t`` into the key).
    The per-flow variant ids
    ``var`` (F,) and ECN-capability flags ``ecn_cap`` (F,) are RUNTIME
    operands, not trace-time constants: every assignment drawn from
    ``present`` rides one compiled executable, and the config-axis
    sweep vmaps them alongside the replica axis.

    ``present`` is the sorted set of variant ids the slot holds window
    rules for (:func:`variant_set` of what the launch assigns; handed
    none, every rule: the program that serves any ``var``).  ``var``
    must stay inside it: a flow of an absent variant would run no rule
    at all.  The carry keeps its shape whatever the set:
    ``init_state()`` returns the same leaves, and a side leaf that no
    present variant names (:func:`live_side_leaves`) passes through the
    body untouched, so XLA takes it out of the loop.  Such a leaf ends
    a run at its INITIAL value where the program built for all
    seventeen would have updated it (that program runs CUBIC's epoch
    clock, BBR's state machine, ... for every flow and discards them);
    nothing a run returns (``_tcp_unpack``) reads one.

    ``obs=True`` (the ``TpudesObs`` knob at run time) threads three
    extra accumulators through the carry — per-lane cwnd-cut events,
    retransmissions (losses consumed by the dupack-timed detector), and
    a bottleneck-occupancy histogram — fetched once at run end.  A
    disabled run compiles the exact pre-obs program.
    """
    from tpudes.parallel.runtime import step_keys

    R, F, L = replicas, prog.n_flows, prog.buf_len
    live_side = live_side_leaves(present)
    if obs:
        from tpudes.obs.flowmon import (
            FLOW_DELAY_BINS,
            VERDICT_DROP,
            VERDICT_RX,
            VERDICT_TX,
            flow_accumulate,
            flow_carry,
            flow_ring_write,
        )
    start = jnp.asarray(prog.start_slot)
    stop = jnp.asarray(prog.stop_slot)
    max_pkts = jnp.asarray(prog.max_pkts)
    # a strong f32 scalar: `t * slot_s` must stay f32 under ambient
    # x64 (an unpinned python float would promote the i32 clock to f64)
    slot_s = jnp.float32(prog.slot_s)
    base_rtt = jnp.float32(prog.base_rtt_s)
    rtt_slots = max(1, int(round(prog.base_rtt_s / prog.slot_s)))
    Q = prog.queue_cap
    burst = prog.burst_cap
    RED = prog.qdisc == "red"
    TRAFFIC = prog.traffic is not None
    if TRAFFIC:
        from tpudes.traffic.device import build_cum_fn

        tr_cum = build_cum_fn(prog.traffic)
        slot_us = max(1, int(round(prog.slot_s * 1e6)))

    def init_state():
        z = lambda *sh, dt=jnp.float32: jnp.zeros(sh, dt)  # noqa: E731
        extra = (
            dict(
                cwnd_cuts=z(R, F, dt=jnp.int32),
                retx_cnt=z(R, F, dt=jnp.int32),
                q_hist=z(R, OBS_QHIST_BINS, dt=jnp.int32),
                # per-flow FlowMonitor columns + the packet-event ring
                **flow_carry(F, lead=(R,)),
            )
            if obs
            else {}
        )
        # every fill dtype pinned f32: an unpinned python-float fill
        # would widen the whole carry under ambient x64 (JXL002)
        return dict(
            **extra,
            cwnd=jnp.full((R, F), INIT_CWND, jnp.float32),
            ssthresh=jnp.full((R, F), SSTHRESH0, jnp.float32),
            inflight=z(R, F, dt=jnp.int32),
            q=z(R, F, dt=jnp.int32),
            q_marked=z(R, F),            # CE-marked packets in the queue
            delivered=z(R, F, dt=jnp.int32),
            drops=z(R, F, dt=jnp.int32),
            recover_until=z(R, F, dt=jnp.int32),
            ack_buf=z(R, L, F, dt=jnp.int32),
            loss_buf=z(R, L, F, dt=jnp.int32),
            mark_buf=z(R, L, F),         # ECE echoes riding the acks
            rtt_buf=jnp.full((R, L), prog.base_rtt_s, jnp.float32),
            qsum=z(R),
            red_avg=z(R),                # RED EWMA average queue
            dctcp_acked=z(R, F),
            dctcp_marked=z(R, F),
            side=dict(
                w_max=z(R, F),
                epoch_t=jnp.full((R, F), -1.0, jnp.float32),
                k=z(R, F),
                origin=z(R, F), w_est=z(R, F),
                base_rtt=jnp.broadcast_to(base_rtt, (R, F)),
                last_diff=z(R, F),
                min_rtt=jnp.full((R, F), jnp.inf, jnp.float32),
                ww_acc=z(R, F), bwe=z(R, F),
                ill_max_rtt=z(R, F),
                ill_alpha=jnp.full((R, F), ILL_ALPHA_MAX, jnp.float32),
                ill_beta=jnp.full((R, F), ILL_BETA_MIN, jnp.float32),
                bbr_acc=z(R, F), bbr_bw=z(R, F), bbr_full_bw=z(R, F),
                bbr_full_cnt=z(R, F),
                bbr_state=z(R, F, dt=jnp.int32),
                bbr_cycle=z(R, F, dt=jnp.int32),
                cwnd_cnt=z(R, F),
                dctcp_alpha=jnp.ones((R, F), jnp.float32),
                htcp_beta=jnp.full(
                    (R, F), HTCP_DEFAULT_BACKOFF, jnp.float32
                ),
                htcp_last_cong=z(R, F),
                lp_until=z(R, F),
            ),
        )

    def step_fn(s, inp, var, ecn_cap, tr=None):
        t, key = inp
        idx = t % L

        # per-replica keying: replica r's draws at slot t are a pure
        # function of (key, t, r) — independent of R — so runtime
        # replica-bucketing (padding R to a power of two) leaves every
        # real replica's stream bit-identical
        rkeys = step_keys("dumbbell", key, t, R)
        with jax.named_scope(RNG_SCOPE):
            if RED:

                def draw(kk):
                    # fixed-arity split of a fold_in-derived key: pure in
                    # (key, t, r), so bucketing/chunking stay bit-exact;
                    # draw dtypes pinned f32 (ambient x64 must not widen
                    # the streams — JXL002)
                    k_dep, k_red, k_mark = jax.random.split(kk, 3)
                    return (
                        jax.random.uniform(k_dep, (), jnp.float32),
                        jax.random.uniform(k_red, (F,), jnp.float32),
                        jax.random.uniform(k_mark, (), jnp.float32),
                    )

                u_dep, u_red, u_mark = jax.vmap(draw)(rkeys)
            else:
                u_dep = jax.vmap(
                    lambda kk: jax.random.uniform(kk, (), jnp.float32)
                )(rkeys)

        # 1. consume this slot's ack / loss / ECN-echo arrivals
        acks = s["ack_buf"][:, idx, :]
        losses = s["loss_buf"][:, idx, :]
        marks = s["mark_buf"][:, idx, :]
        rtt = s["rtt_buf"][:, idx][:, None]
        ack_buf = s["ack_buf"].at[:, idx, :].set(0)
        loss_buf = s["loss_buf"].at[:, idx, :].set(0)
        mark_buf = s["mark_buf"].at[:, idx, :].set(0.0)
        inflight = s["inflight"] - acks - losses

        side = s["side"]
        d_acked, d_marked = s["dctcp_acked"], s["dctcp_marked"]
        if V_DCTCP in present:
            # DCTCP per-window marked-fraction EWMA (PktsAcked/EceReceived)
            d_acked = d_acked + acks.astype(jnp.float32)
            d_marked = d_marked + marks
            win_done = d_acked >= s["cwnd"]
            side = dict(
                side,
                dctcp_alpha=jnp.where(
                    win_done,
                    (1.0 - DCTCP_G) * side["dctcp_alpha"]
                    + DCTCP_G * d_marked / jnp.maximum(d_acked, 1.0),
                    side["dctcp_alpha"],
                ),
            )
            d_acked = jnp.where(win_done, 0.0, d_acked)
            d_marked = jnp.where(win_done, 0.0, d_marked)

        with jax.named_scope(CC_SCOPE):
            in_recovery = t < s["recover_until"]
            # (``var[None, :]`` once a call, as the masked-dense step
            # wrote it: the program of every rule keeps its equations)
            cwnd, ssthresh, side = _cwnd_increase(
                _Flows(var[None, :], present), s["cwnd"], s["ssthresh"],
                jnp.where(in_recovery, 0, acks), t * slot_s, rtt, side,
                acked_raw=acks,
            )
            # 2. one reduction per recovery window on loss or ECN echo
            # (RFC 3168: an ECE ack triggers the variant's loss response;
            # DCTCP's response is the alpha-scaled cut via ss_dctcp)
            reduce = (
                (losses > 0) | ((marks > 0) & ecn_cap[None, :])
            ) & ~in_recovery
            ss_loss, side_loss = _loss_response(
                _Flows(var[None, :], present), cwnd, side, t * slot_s
            )
            ssthresh = jnp.where(reduce, ss_loss, ssthresh)
            cwnd = jnp.where(reduce, ssthresh, cwnd)
            # a side leaf no present rule names stays the input leaf
            # itself, so XLA takes it out of the loop
            side = {
                k: jnp.where(reduce, side_loss[k], x) if k in live_side else x
                for k, x in side.items()
            }
            recover_until = jnp.where(
                reduce, t + rtt_slots, s["recover_until"]
            )

        with jax.named_scope(QUEUE_SCOPE):
            # 3. departure: serve one packet, flow ∝ queue occupancy
            q = s["q"]
            # int reductions pin dtype=jnp.int32: an unpinned .sum()
            # widens to i64 under ambient x64 (JXL002); bit-exact
            # no-op under the default config
            qtot = q.sum(axis=1, dtype=jnp.int32)
            backlogged = qtot > 0
            cum = jnp.cumsum(q, axis=1, dtype=jnp.int32)
            thresh = (u_dep * qtot.astype(jnp.float32)).astype(jnp.int32)
            dep = jnp.argmax(cum > thresh[:, None], axis=1)  # (R,)
            dep_oh = jax.nn.one_hot(dep, F, dtype=jnp.int32) * backlogged[
                :, None
            ].astype(jnp.int32)
            # the departing packet carries a CE mark with probability equal
            # to the flow's marked share — INTEGER marks only (a fractional
            # residue would keep the `marks > 0` loss response firing for
            # hundreds of RTTs after a marking episode)
            if RED:
                dep_marked = dep_oh.astype(jnp.float32) * (
                    u_mark[:, None]
                    < s["q_marked"] / jnp.maximum(q, 1).astype(jnp.float32)
                ).astype(jnp.float32)
            else:
                dep_marked = jnp.zeros((R, F), jnp.float32)
            q_marked = jnp.maximum(s["q_marked"] - dep_marked, 0.0)
            q = q - dep_oh
            delivered = s["delivered"] + dep_oh
            aidx = (t + prog.ack_lag) % L
            ack_buf = ack_buf.at[:, aidx, :].add(dep_oh)
            mark_buf = mark_buf.at[:, aidx, :].add(dep_marked)
            rtt_buf = s["rtt_buf"].at[:, aidx].set(
                prog.base_rtt_s + qtot.astype(jnp.float32) * slot_s
            )

            # 4. window-driven arrivals; AQM (RED mark/early-drop) then
            # tail-drop past capacity
            want = jnp.clip(
                cwnd.astype(jnp.int32) - inflight, 0, burst
            )
            live = (t >= start[None, :]) & (t < stop[None, :]) & (
                delivered + inflight < max_pkts[None, :]
            )
            want = jnp.where(live, want, 0)
            if TRAFFIC:
                # app-limited sending: the workload's cumulative offered
                # segments (closed-form, shared across replicas — the
                # realization IS the workload, like the mobility
                # trajectory) caps what may ever have left the
                # application — an EXACT clip, not a gate, so the send
                # burst cannot overshoot the offered count.  Arrivals
                # inside a slot are sendable in that slot (the slot-end
                # evaluation — sub-slot timing is below this model's
                # resolution either way)
                app_cum = jnp.floor(
                    tr_cum(tr, (t + 1) * jnp.int32(slot_us))
                ).astype(jnp.int32)                          # (F,)
                want = jnp.minimum(
                    want,
                    jnp.maximum(
                        app_cum[None, :] - delivered - inflight, 0
                    ),
                )
            red_avg = s["red_avg"]
            red_marks = jnp.zeros((R, F), jnp.float32)
            red_drops = jnp.zeros((R, F), jnp.int32)
            if RED:
                # EWMA over this slot's arrivals against the instantaneous
                # queue (per-arrival updates folded into one (1-qw)^n step;
                # idle-time decay not modeled — the bottleneck is backlogged
                # in every regime this engine targets)
                qnow = q.sum(axis=1, dtype=jnp.int32).astype(jnp.float32)
                n_arr = want.sum(axis=1, dtype=jnp.int32)
                red_avg = jnp.where(
                    n_arr > 0,
                    qnow
                    + (red_avg - qnow)
                    * jnp.float32(1.0 - prog.red_qw) ** n_arr,
                    red_avg,
                )
                p = jnp.where(
                    red_avg < prog.red_min_th,
                    0.0,
                    prog.red_max_p
                    * (red_avg - prog.red_min_th)
                    / max(prog.red_max_th - prog.red_min_th, 1e-9),
                )
                if prog.red_gentle:
                    p = jnp.where(
                        red_avg >= prog.red_max_th,
                        prog.red_max_p
                        + (1.0 - prog.red_max_p)
                        * (red_avg - prog.red_max_th) / prog.red_max_th,
                        p,
                    )
                    forced = red_avg >= 2.0 * prog.red_max_th
                else:
                    forced = red_avg >= prog.red_max_th
                p = jnp.clip(jnp.where(forced, 1.0, p), 0.0, 1.0)
                # ECT packets are marked unless the forced region hard-drops
                ect = ecn_cap[None, :] & prog.red_use_ecn
                n_act = jnp.minimum(
                    want,
                    jnp.floor(
                        want.astype(jnp.float32) * p[:, None] + u_red
                    ).astype(jnp.int32),
                )
                mark_sel = ect & ~(
                    forced[:, None] & bool(prog.red_use_hard_drop)
                )
                red_drops = jnp.where(mark_sel, 0, n_act)
                red_marks = jnp.where(mark_sel, n_act, 0).astype(jnp.float32)
                want_q = want - red_drops
            else:
                want_q = want
            wtot = want_q.sum(axis=1, dtype=jnp.int32)
            free = jnp.maximum(Q - q.sum(axis=1, dtype=jnp.int32), 0)
            # proportional admission with largest-remainder rounding
            scale = jnp.minimum(
                free.astype(jnp.float32)
                / jnp.maximum(wtot, 1).astype(jnp.float32),
                1.0,
            )
            exact = want_q.astype(jnp.float32) * scale[:, None]
            acc = jnp.floor(exact).astype(jnp.int32)
            rem = exact - acc
            leftover = jnp.minimum(
                free - acc.sum(axis=1, dtype=jnp.int32),
                wtot - acc.sum(axis=1, dtype=jnp.int32),
            )
            order = jnp.argsort(-rem, axis=1)
            rank = jnp.argsort(order, axis=1)
            acc = acc + (
                (rank < leftover[:, None]) & (acc < want_q)
            ).astype(jnp.int32)
            acc = jnp.minimum(acc, want_q)
            rej = want_q - acc
            q = q + acc
            # marked packets are among the admitted ones (integer count)
            q_marked = q_marked + jnp.minimum(
                red_marks, acc.astype(jnp.float32)
            )
            inflight = inflight + want
            drops = s["drops"] + rej + red_drops
            lidx = (t + prog.ack_lag) % L  # dupack-timed detection
            loss_buf = loss_buf.at[:, lidx, :].add(rej + red_drops)

        extra = {}
        if obs:
            # per-lane metric accumulators (no host sync: they ride the
            # carry and are fetched with the outcome arrays at run end)
            bucket = jnp.clip(
                qtot * OBS_QHIST_BINS // max(Q + 1, 1), 0, OBS_QHIST_BINS - 1
            )
            extra = dict(
                cwnd_cuts=s["cwnd_cuts"] + reduce.astype(jnp.int32),
                retx_cnt=s["retx_cnt"] + losses,
                q_hist=s["q_hist"]
                + jax.nn.one_hot(bucket, OBS_QHIST_BINS, dtype=jnp.int32),
            )
            # FlowMonitor columns: a packet is one segment + 40 header
            # bytes (the host monitor counts GetSize()+20 on packets
            # already carrying a 20-byte TCP header); one-way delay =
            # half the base RTT plus the bottleneck residence this
            # slot's departure saw — all dense adds, no sparse ops
            pkt_b = jnp.int32(prog.seg_bytes + 40)
            drop_f = rej + red_drops
            delay = (
                0.5 * base_rtt
                + qtot.astype(jnp.float32)[:, None] * slot_s
            )
            fm = flow_accumulate(
                {k: s[k] for k in s if k.startswith("fm_")},
                t_s=t * slot_s,
                tx=want,
                tx_bytes=want * pkt_b,
                rx=dep_oh,
                rx_bytes=dep_oh * pkt_b,
                delay_s=jnp.broadcast_to(delay, (R, F)),
                lost=drop_f,
                bin_width_s=(
                    0.5 * prog.base_rtt_s + Q * prog.slot_s
                ) / FLOW_DELAY_BINS,
            )
            # packet-event ring: ONE sampled event per (replica, slot)
            # — the delivery if one happened (at most one per replica
            # per slot: dep_oh is one-hot), else a drop, else a send;
            # step column -1 marks an idle slot
            has_drop = drop_f.sum(axis=1, dtype=jnp.int32) > 0
            has_tx = want.sum(axis=1, dtype=jnp.int32) > 0
            ev_flow = jnp.where(
                backlogged,
                dep,
                jnp.where(
                    has_drop,
                    jnp.argmax(drop_f, axis=1),
                    jnp.argmax(want, axis=1),
                ),
            ).astype(jnp.int32)
            ev_verdict = jnp.where(
                backlogged,
                VERDICT_RX,
                jnp.where(has_drop, VERDICT_DROP, VERDICT_TX),
            )
            any_ev = backlogged | has_drop | has_tx
            slot_us_c = jnp.int32(max(1, round(prog.slot_s * 1e6)))
            row = jnp.stack(
                [
                    jnp.where(any_ev, t, -1),
                    jnp.broadcast_to(t * slot_us_c, (R,)),
                    ev_flow,
                    jnp.broadcast_to(pkt_b, (R,)),
                    ev_verdict,
                ],
                axis=-1,
            )
            fm["fm_ring"] = flow_ring_write(s["fm_ring"], t, row)
            extra.update(fm)
        return dict(
            **extra,
            cwnd=cwnd, ssthresh=ssthresh, inflight=inflight, q=q,
            q_marked=q_marked,
            delivered=delivered, drops=drops, recover_until=recover_until,
            ack_buf=ack_buf, loss_buf=loss_buf, mark_buf=mark_buf,
            rtt_buf=rtt_buf,
            qsum=s["qsum"] + qtot.astype(jnp.float32),
            red_avg=red_avg,
            dctcp_acked=d_acked, dctcp_marked=d_marked,
            side=side,
        ), None

    return init_state, step_fn


#: the RED/AQM knobs — cache-key components only when the qdisc is
#: actually "red" (see dumbbell_prog_key)
_RED_FIELDS = (
    "red_min_th", "red_max_th", "red_max_p", "red_qw", "red_gentle",
    "red_use_ecn", "red_use_hard_drop",
)


def dumbbell_prog_key(prog: DumbbellProgram) -> tuple:
    """Hashable identity of the DumbbellProgram fields that shape the
    compiled program.  ``n_slots``, ``variant_idx`` and ``ecn`` are
    deliberately ABSENT: the horizon is a traced while_loop bound and
    the variant/ECN assignment a traced operand, so one executable
    serves every horizon AND every variant assignment drawn from one
    SET of variants.  The set itself (:func:`variant_set`: which window
    rules the slot holds) joins the runner's key beside this one, in
    :func:`run_tcp_dumbbell`, because a sweep's set is the union of
    its points and not this program's own.  In fifo mode
    the ``red_*`` parameters are absent too — they never reach the
    fifo program (keying on them was a dead cache-key component
    causing spurious recompiles across RED-parameter sweeps of
    non-RED studies; found by analysis rule JXL004).  ``traffic``
    contributes only its SHAPE key: the workload model id and every
    parameter are traced operands."""
    skip = {"n_slots", "variant_idx", "ecn", "traffic"}
    if prog.qdisc != "red":
        skip.update(_RED_FIELDS)
    return tuple(
        v.tobytes() if isinstance(v, np.ndarray) else v
        for k, v in prog.__dict__.items()
        if k not in skip
    ) + (None if prog.traffic is None else prog.traffic.shape_key(),)


def build_dumbbell_advance(prog: DumbbellProgram, r_pad: int,
                           obs: bool = False, n_cfg: int | None = None,
                           sweep: str = "variant",
                           present: tuple = ALL_VARIANTS):
    """``(init_state, fn)`` with ``fn(carry, key, var, ecn, t_end)``
    the UNJITTED advance exactly as :func:`run_tcp_dumbbell` jits it —
    factored out so the trace manifest (:func:`trace_manifest`)
    abstractly traces the same program the runner cache compiles.
    ``present`` is the set of variants whose window rules the slot
    holds (:func:`build_dumbbell_step`).
    ``key`` is the launch key; the loop's body hands it to ``step_fn``
    unchanged beside the carried slot counter, and ``step_fn`` derives
    the slot's keys (:func:`runtime.step_keys`).

    With a config axis (``n_cfg``), ``sweep`` picks which operand
    carries it: ``"variant"`` vmaps the per-flow variant/ECN
    assignment (the PR-5 sweep), ``"traffic"`` vmaps the workload
    operand tables instead (ISSUE-15: the BSS ``traffic_sweep`` seam
    mirrored — var/ecn are shared across points, the (C, …) traffic
    tables fan out)."""
    from tpudes.parallel.runtime import scoped_while_loop

    init_state, step_fn = build_dumbbell_step(
        prog, r_pad, obs=obs, present=present
    )

    def advance(carry, key, var, ecn, t_end, tr=None):
        # a slot's keys are pure in (key, t) (runtime.step_keys, in
        # step_fn), so the traced horizon needs no split-keys array
        # shape and a chunked run re-enters at t>0 on the same slot
        # streams
        def body(c):
            t, s = c
            s, _ = step_fn(s, (t, key), var, ecn, tr)
            return t + 1, s

        t, s = scoped_while_loop(
            "dumbbell", lambda c: c[0] < t_end, body, carry
        )
        # chunk summaries only under TpudesObs (obs is in the
        # cache key): a disabled run compiles the pre-obs program
        metrics = (
            dict(
                delivered=jnp.sum(
                    s["delivered"], axis=-1, dtype=jnp.int32
                ),
                drops=jnp.sum(s["drops"], axis=-1, dtype=jnp.int32),
                # the per-chunk packet-ring snapshot must be a FRESH
                # value (drive_chunks donates the carry before the
                # deferred fetch reads the metrics): lax.rev is a real
                # op XLA cannot fold back into an alias, and the
                # decoder orders rows by the step column, so the flip
                # needs no undo
                fm_ring=jnp.flip(s["fm_ring"], axis=-2),
            )
            if obs
            else {}
        )
        return (t, s), metrics

    fn = advance
    if n_cfg is not None:
        if sweep == "traffic":
            fn = jax.vmap(fn, in_axes=(0, None, None, None, None, 0))
        else:
            fn = jax.vmap(fn, in_axes=(0, None, 0, 0, None, None))
    return init_state, fn


def _variant_point(entry) -> np.ndarray:
    """One sweep point → (F,) int32 variant ids (names or ids in)."""
    return np.asarray(
        [VARIANTS.index(v) if isinstance(v, str) else int(v) for v in entry],
        np.int32,
    )


def _variant_ecn(variant_idx: np.ndarray) -> np.ndarray:
    """(F,) ECN capability implied by the variant alone (the
    ``REQUIRES_ECN`` class flag, e.g. DCTCP) — what a sweep point that
    reassigns variants can know without a live socket's UseEcn
    attribute."""
    from tpudes.models.internet.tcp_congestion import TCP_VARIANTS

    return np.asarray(
        [
            bool(getattr(TCP_VARIANTS[VARIANTS[int(i)]], "REQUIRES_ECN", False))
            for i in variant_idx
        ],
        bool,
    )


#: state keys fetched to the host at run end (plus the obs extras)
_TCP_FETCH = ("delivered", "drops", "qsum", "cwnd")


def _tcp_fetch_obs():
    from tpudes.obs.flowmon import FM_KEYS

    return ("cwnd_cuts", "retx_cnt", "q_hist") + FM_KEYS


def _planted_divergence(host, points):
    """``TPUDES_FUZZ_PLANTED_BUG=1``: deliberately corrupt CHUNKED-run
    results (replica 0, flow 0: ``delivered`` += 1) so the fuzz
    harness's planted-bug self-test (tests/test_fuzz.py + the CI step)
    can prove the scalar-vs-chunked oracle detects, shrinks and replays
    a real divergence end to end.  Never on outside that self-test —
    the flag is read per call and gates nothing else.  (A
    once-per-launch hook of the launch, run on the unpacked points.)"""
    for point in points:
        d = np.array(point["delivered"], copy=True)
        d[0, 0] += 1
        point["delivered"] = d


def _tcp_unpack(host: dict, prog: DumbbellProgram, replicas: int,
                obs: bool) -> dict:
    """Host-side result assembly for ONE config point."""
    sim_s = prog.n_slots * prog.slot_s
    R = replicas
    delivered = np.asarray(host["delivered"])[:R]
    result = dict(
        goodput_mbps=delivered.astype(np.float32) * prog.seg_bytes * 8.0
        / sim_s / 1e6,
        delivered=delivered,
        drops=np.asarray(host["drops"])[:R],
        mean_queue=np.asarray(host["qsum"])[:R] / prog.n_slots,
        cwnd_final=np.asarray(host["cwnd"])[:R],
    )
    if obs:
        from tpudes.obs.flowmon import FM_KEYS

        result.update(
            cwnd_cuts=np.asarray(host["cwnd_cuts"])[:R],
            retx=np.asarray(host["retx_cnt"])[:R],
            queue_hist=np.asarray(host["q_hist"])[:R],
            # per-flow FlowMonitor columns + the packet-event ring,
            # replica-sliced; reduce with tpudes.obs.flowmon
            flow={k: np.asarray(host[k])[:R] for k in FM_KEYS},
        )
    return result


def tcp_study(prog: DumbbellProgram, key, replicas, mesh=None):
    """Serving-layer study descriptor (see :mod:`tpudes.serving`): the
    per-flow variant/ECN assignment is the traced sweep operand, so two
    dumbbell studies coalesce onto one (C, R, F) launch whenever their
    static fields, slot horizon, key, replica count and mesh all match.
    The set of variants is no part of ``ck``: studies of different
    variants still coalesce, and the one launch compiles the window
    rules of their union.

    A program whose declared ``ecn`` disagrees with the variants'
    ``REQUIRES_ECN`` flags is marked ``solo``: sweep points derive ECN
    from the variant (the PR-5 equality contract), so such a study can
    only be served bit-faithfully by its own plain launch."""
    import dataclasses

    from tpudes.serving.descriptor import StudyDescriptor, mesh_fingerprint

    ids = np.asarray(prog.variant_idx, np.int32)
    declared = (
        np.asarray(prog.ecn, bool) if prog.ecn is not None
        else np.zeros(prog.n_flows, bool)
    )
    solo = not np.array_equal(declared, _variant_ecn(ids))
    statics = tuple(
        v.tobytes() if isinstance(v, np.ndarray) else v
        for k, v in prog.__dict__.items()
        if k not in ("variant_idx", "ecn", "traffic")
    ) + (
        # workload identity by VALUE: params are traced, but studies
        # with different workloads must not coalesce
        None if prog.traffic is None else prog.traffic.param_key(),
    )  # n_slots stays IN: the batch shares one traced slot bound
    ck = (
        statics, np.asarray(key).tobytes(), int(replicas),
        mesh_fingerprint(mesh),
    )
    point = tuple(int(i) for i in ids)

    def launch(points, block=False):
        if solo or len(points) == 1:
            pt = _variant_point(list(points[0]))
            p1 = prog if solo else dataclasses.replace(
                prog, variant_idx=pt, ecn=_variant_ecn(pt)
            )
            return run_tcp_dumbbell(
                p1, key, replicas=replicas, mesh=mesh, block=block
            )
        return run_tcp_dumbbell(
            prog, key, replicas=replicas, mesh=mesh,
            variants=[list(p) for p in points], block=block,
        )

    def warm(n_points):
        # the slot horizon is a traced operand: a 1-slot run compiles
        # the exact executable every real horizon reuses.  It warms
        # the study's OWN set of variants: a coalesced launch whose
        # points pool a wider set is another runner and compiles once
        # more, at its first launch (a runner miss, so
        # CompileTelemetry counts it)
        tiny = dataclasses.replace(prog, n_slots=1)
        if n_points == 1:
            run_tcp_dumbbell(tiny, key, replicas=replicas, mesh=mesh)
        else:
            run_tcp_dumbbell(
                tiny, key, replicas=replicas, mesh=mesh,
                variants=[list(point)] * n_points,
            )

    spec = None if (mesh is not None or solo) else dict(
        engine="dumbbell", prog=prog, key=np.asarray(key),
        replicas=replicas,
    )
    return StudyDescriptor(
        "dumbbell", ck, point, launch, warm, solo=solo, spec=spec
    )


def run_tcp_dumbbell(
    prog: DumbbellProgram,
    key,
    replicas: int,
    mesh=None,
    *,
    variants=None,
    traffic_sweep=None,
    chunk_slots: int | None = None,
    checkpoint=None,
    block: bool = True,
):
    """Execute R replicas of the dumbbell program; returns per-replica
    outcome arrays: goodput_mbps (R,F), delivered (R,F), drops (R,F),
    mean_queue (R,), cwnd_final (R,F) — plus, under ``TpudesObs=1``,
    the on-device metric accumulators ``cwnd_cuts`` (R,F), ``retx``
    (R,F) and ``queue_hist`` (R, OBS_QHIST_BINS).  The slot horizon AND
    the per-flow variant/ECN assignments are traced operands and the
    replica axis is runtime-bucketed, so horizon/variant/replica sweeps
    all reuse one executable per replica bucket and SET of variants
    assigned (the slot compiles the window rules of the variants the
    launch assigns, a sweep's points pooled, and no others).

    ``variants=[point, ...]`` (each point an (F,)-sequence of variant
    names or ids) runs a **config-axis sweep**: one launch of a
    (C, R, F) program, returning a list of per-point result dicts equal
    to what ``dataclasses.replace(prog, variant_idx=point,
    ecn=REQUIRES_ECN(point))`` per-point launches (same key) produce.

    ``traffic_sweep=[...]`` (TrafficPrograms sharing one
    ``shape_key``, with ``prog.traffic`` naming the shape class) runs
    a **config-axis workload sweep** instead (ISSUE-15, mirroring the
    BSS seam): the traffic operand tables gain the leading vmapped
    axis while the variant/ECN assignment is shared, so a C-point
    mixed cbr/mmpp/onoff/trace workload study is ONE launch of a
    (C, R, F) program — demuxed bit-equal to per-point launches with
    ``dataclasses.replace(prog, traffic=tp)`` and the same key.

    ``chunk_slots=N`` splits the horizon into N-slot segments with a
    donated carry handoff (bit-identical to single-shot; per-chunk
    metrics stream to ``tpudes.obs``).  ``checkpoint=`` (a path or
    :class:`~tpudes.parallel.checkpoint.CarryCheckpoint`) persists the
    carry after each chunk and resumes a matching run from its last
    completed chunk, bit-equal to uninterrupted.  ``block=False``
    returns an :class:`~tpudes.parallel.runtime.EngineFuture`.
    """
    from tpudes.parallel.runtime import Launch, chunk_bounds, stack_axis

    if variants is not None and traffic_sweep is not None:
        raise ValueError(
            "one config axis per launch: sweep either the variant "
            "assignment (variants=[...]) or the workload "
            "(traffic_sweep=[...])"
        )
    sweep = "traffic" if traffic_sweep is not None else "variant"
    n_cfg = (
        len(variants) if variants is not None
        else (len(traffic_sweep) if traffic_sweep is not None else None)
    )
    L = Launch("dumbbell", key, replicas, mesh, n_cfg)

    if variants is None:
        points = [np.asarray(prog.variant_idx, np.int32)]
    else:
        points = [_variant_point(p) for p in variants]
    from tpudes.obs import spans

    # the variants this launch assigns, a sweep's points pooled: the
    # slot program holds their window rules and no others
    present = variant_set(points)
    launch = spans.current()
    if launch is not None and launch.name == "launch":
        # what the slot's window rules were compiled FOR: the flow
        # count, the sorted names of the variants this launch assigns,
        # and how many rule sets that is (17: every rule, the program
        # before the set was part of the key; an all-CUBIC launch: 1)
        launch.args["n_flows"] = int(prog.n_flows)
        launch.args["variants"] = sorted(VARIANTS[i] for i in present)
        launch.args["cc_rules"] = len(present)

    def build():
        init_state, fn = build_dumbbell_advance(
            prog, L.r_pad, obs=L.obs, n_cfg=n_cfg, sweep=sweep,
            present=present,
        )
        return (
            lambda: (stack_axis((jnp.int32(0), init_state()), n_cfg),),
            (L.axis,), fn, None,
        )

    def operands(parts):
        if variants is None:
            ecns = [
                np.asarray(prog.ecn, bool)
                if prog.ecn is not None
                else np.zeros(prog.n_flows, bool)
            ]
        else:
            ecns = [_variant_ecn(p) for p in points]
            for p in points:
                if p.shape != (prog.n_flows,):
                    raise ValueError(
                        f"each sweep point assigns all {prog.n_flows} flows "
                        f"(got shape {p.shape})"
                    )
        one = n_cfg is None or sweep == "traffic"
        var = points[0] if one else np.stack(points)
        ecn = ecns[0] if one else np.stack(ecns)
        # workload params ride as TRACED operands (None = the bulk
        # path); the runner's key carries only the traffic shape key
        if traffic_sweep is not None:
            from tpudes.traffic.device import stack_traffic_operands

            if prog.traffic is None or any(
                tp.shape_key() != prog.traffic.shape_key()
                for tp in traffic_sweep
            ):
                raise ValueError(
                    "a workload sweep needs prog.traffic set and every "
                    "point sharing its traffic shape key (one executable "
                    "serves the sweep; pad tables to a common capacity)"
                )
            tr = stack_traffic_operands(traffic_sweep)
        else:
            tr = None if prog.traffic is None else prog.traffic.operands()
        return parts[0], (var, ecn, tr)

    # see dumbbell_prog_key for what is (deliberately) absent; the SET
    # of variants assigned is a cache-key component (the slot holds
    # their rules only) while the assignment itself is an operand; the
    # sweep KIND is one too (the two sweeps vmap different operands —
    # different executables)
    L.prepare(
        lambda: dumbbell_prog_key(prog) + (
            present, L.r_pad, L.obs, n_cfg, sweep
        ),
        build, operands,
    )

    def call(fn, carry, t_end, ops):
        var, ecn, tr = ops
        return fn(carry, key, var, ecn, t_end, tr)

    names = _TCP_FETCH + (_tcp_fetch_obs() if L.obs else ())
    return L.drive(
        call,
        chunk_bounds(prog.n_slots, chunk_slots or prog.n_slots),
        lambda carry: {k: carry[1][k] for k in names},
        lambda host: _tcp_unpack(host, prog, replicas, L.obs),
        once=(
            _planted_divergence
            if chunk_slots is not None
            and os.environ.get("TPUDES_FUZZ_PLANTED_BUG") == "1"
            else None
        ),
        checkpoint=checkpoint,
        identity=lambda: dumbbell_prog_key(prog) + (
            tuple(tuple(int(i) for i in p) for p in points),
            None if prog.traffic is None else prog.traffic.param_key(),
            None if traffic_sweep is None
            else tuple(tp.param_key() for tp in traffic_sweep),
        ),
        block=block,
    )


# --- trace manifest (tpudes.analysis.jaxpr) --------------------------------

#: canonical tiny replica count for the abstract traces
_TRACE_R = 2


def _trace_prog(**over):
    """Canonical tiny-shape program: 2 flows, short horizon."""
    import dataclasses

    from tpudes.parallel.programs import toy_dumbbell_program

    prog = toy_dumbbell_program(n_flows=2, n_slots=30)
    return dataclasses.replace(prog, **over) if over else prog


def _trace_entries(
    prog: DumbbellProgram, obs: bool = False, scale: bool = True
):
    """The cached-runner functions exactly as ``run_tcp_dumbbell`` jits
    them, with concrete tiny operands.  ``scale=False`` skips the
    JXL007 axis declarations (the axis builders re-enter here)."""
    from tpudes.analysis.jaxpr.spec import TraceEntry

    present = variant_set([prog.variant_idx])
    init_state, fn = build_dumbbell_advance(
        prog, _TRACE_R, obs=obs, present=present
    )
    key = jax.random.PRNGKey(0)
    var = jnp.asarray(prog.variant_idx, jnp.int32)
    ecn = jnp.asarray(_variant_ecn(np.asarray(prog.variant_idx)))
    carry = (jnp.int32(0), init_state())
    tr = None if prog.traffic is None else prog.traffic.operands()
    traced = {"ecn": 3, "t_end": 4}
    if len(present) > 1:
        # a one-variant program selects on nothing: its ``var`` is an
        # operand no equation reads, and the set is in the key
        traced["var"] = 2
    if tr is not None:
        traced["tr"] = 5
    return [
        TraceEntry("init", init_state, (), kernel=False),
        TraceEntry(
            "advance",
            fn,
            (carry, key, var, ecn, jnp.int32(8), tr),
            donate=(0,),
            carry=(0,),
            traced=traced,
            scale_axes=_scale_axes() if scale else (),
        ),
    ]


def _scale_axes():
    """JXL007 scale axis for the dumbbell advance kernel: per-flow
    cwnd/ring state is (R, F) — linear in the flow count, budget
    1.0 (an all-pairs fairness table would fire it)."""
    import dataclasses

    from tpudes.analysis.jaxpr.spec import ScaleAxis

    from tpudes.parallel.programs import toy_dumbbell_program

    def at(v):
        # the flow count scales, the set of window rules does not
        prog = toy_dumbbell_program(n_flows=int(v), n_slots=30)
        prog = dataclasses.replace(
            prog,
            variant_idx=np.resize(_trace_prog().variant_idx, int(v)),
        )
        return _trace_entries(prog, scale=False)[1]

    return (
        ScaleAxis(
            "n_flows", at, points=(2, 8), mem_budget=1.0
        ),
    )


def _trace_flips():
    import dataclasses

    from tpudes.analysis.jaxpr.spec import FlipSpec

    base = _trace_prog()

    def runner_key(prog):
        return dumbbell_prog_key(prog), variant_set([prog.variant_idx])

    def flip(**over):
        prog = dataclasses.replace(base, **over)
        return FlipSpec(
            build=lambda p=prog: _trace_entries(p),
            key_differs=runner_key(prog) != runner_key(base),
        )

    from tpudes.traffic import TrafficProgram

    return {
        # live components: each must change some traced program
        "queue_cap": flip(queue_cap=13),
        "ack_lag": flip(ack_lag=7),
        "qdisc": flip(qdisc="red"),
        # a workload program joins the trace (the app-limit gate) and
        # its SHAPE key joins the cache key
        "traffic": flip(
            traffic=TrafficProgram.onoff(2, 300.0, horizon_us=30_000)
        ),
        "obs": FlipSpec(
            build=lambda: _trace_entries(base, obs=True),
            key_differs=True,
        ),
        # another SET of variants is another slot program (other
        # window rules), and the set is in the runner's key
        "variant_set": flip(
            variant_idx=np.asarray([3, 5], np.int32)
        ),
        # excluded-by-design fields must leave every trace identical:
        # the horizon and the variant assignment INSIDE the program's
        # set are traced operands, and in fifo mode the RED knobs never
        # reach the program (the JXL004-found dead components)
        "n_slots": flip(n_slots=60),
        "variant_idx": flip(
            variant_idx=base.variant_idx[::-1].copy()
        ),
        "red_qw": flip(red_qw=0.5),
    }


def trace_manifest():
    """Per-engine trace manifest (see :mod:`tpudes.analysis.jaxpr`)."""
    from tpudes.analysis.jaxpr.spec import TraceManifest, TraceVariant

    from tpudes.parallel.programs import toy_dumbbell_program

    return TraceManifest(
        engine="dumbbell",
        path="tpudes/parallel/tcp_dumbbell.py",
        variants=lambda: [
            TraceVariant(
                "base", lambda: _trace_entries(_trace_prog())
            ),
            # the TpudesObs program (FlowMonitor columns + packet ring)
            # joins the lint surface: its ring dynamic_update_slice
            # must pass the registered SparseSite contract (JXL008)
            TraceVariant(
                "obs", lambda: _trace_entries(_trace_prog(), obs=True)
            ),
            # the two ends of what a launch's set of variants can
            # compile: one variant's rules (no select, ``var`` unread)
            # and all seventeen's, so that every rule stays on the
            # lint surface whatever the toy program assigns
            TraceVariant(
                "one_variant",
                lambda: _trace_entries(
                    _trace_prog(variant_idx=np.asarray(
                        [V_CUBIC, V_CUBIC], np.int32
                    )),
                    scale=False,
                ),
            ),
            TraceVariant(
                "all_variants",
                lambda: _trace_entries(
                    toy_dumbbell_program(
                        n_flows=len(VARIANTS), n_slots=30
                    ),
                    scale=False,
                ),
            ),
        ],
        flips=_trace_flips,
    )
