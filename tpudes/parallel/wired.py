"""Wired-graph per-link-queue device engine — the hybrid-PDES partition
unit (ROADMAP item 4).

The dumbbell engine (tcp_dumbbell.py) models ONE shared queue in slot
time; this module generalizes exactly its slot mechanics — integer slot
clock, one serialization per link per service period, FIFO queues — to
a **per-link-queue wired graph**: every link carries its own queue,
service time and propagation delay, and packets follow explicit
multi-hop paths.  Traffic is deterministic CBR (per-flow start/period/
budget, with an optional per-replica phase jitter drawn from the
``fold_in`` key discipline), which buys the property the space-parallel
story needs: **timestamps are exact**.  The device program computes the
same integer event times the sequential host DES computes, so a
partitioned run can be checked timestamp-exact, not statistically —
mirroring the upstream contract of ``tests/test_distributed.py``.

Why per-link queues are the partition unit: a partition boundary cuts
the graph at a link; the served packet's next-hop arrival time
``t + service + delay`` is known at serve time, so boundary traffic is
a (packet id, hop, arrival slot) triple and the boundary link's
``service + delay`` is the conservative **lookahead** — precisely the
granted-time-window contract of ``tpudes/parallel/distributed.py``,
with the per-rank event loop replaced by a lifted window kernel
(:mod:`tpudes.parallel.hybrid` drives it).

Device model (each choice shared with the host DES oracle below, so the
pair is exact):

- integer slot clock; link ``l`` serves one packet per ``service[l]``
  slots; a packet served at ``t`` arrives at its next hop's queue (or
  its destination) at ``t + service[l] + delay[l]``.
- FIFO by (arrival slot, packet id) — total order, no RNG in service.
- queues are unbounded (no drops): contention shows up as queueing
  delay, never as stochastic loss, keeping the model deterministic.
- services only START strictly below the horizon ``n_slots``; the
  delivery timestamp of a packet whose last service started in-horizon
  is recorded even when it lands past ``n_slots`` (the host oracle
  records delivery at service start for the same reason).

The kernel advances in ``advance(carry, ingress, t_grant)`` form — the
chunked-horizon carry-operand shape of PR 5 — stepping only the
*interesting* slots (the next pending arrival/free time), so a sparse
window costs its event count, not its slot count.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from tpudes.fuzz.envelope import FuzzEnvelope

__all__ = [
    "INF_SLOT",
    "WiredProgram",
    "UnliftableWiredError",
    "build_wired_advance",
    "build_wired_space_advance",
    "packet_table",
    "partition_flows",
    "partition_lookahead",
    "run_wired",
    "run_wired_host",
    "trace_manifest",
    "wired_cache_key",
    "wired_chain",
    "wired_weak_chain",
]

#: "no event" sentinel: far beyond any horizon, small enough that
#: ``INF_SLOT + service + delay`` never overflows int32
INF_SLOT = 1 << 30

#: nominal wire bytes per packet for the FlowMonitor byte counters —
#: the slot model has no packet sizes (service cost lives in
#: ``service_slots``), so the device FlowStats report this constant
#: per packet, like a fixed-MTU trace
WIRED_PKT_BYTES = 1000


class UnliftableWiredError(ValueError):
    """The wired program is malformed for the slot model (bad path,
    non-positive service period, negative delay)."""


@dataclass(frozen=True)
class WiredProgram:
    """Static description of one wired-graph scenario.

    ``link_owner`` maps each link to the PDES rank that serves it (all
    zeros = single-partition); it is metadata for the hybrid engine —
    the plain ``run_wired`` path always serves every link.
    """

    n_links: int
    service_slots: np.ndarray     # (L,) int32, >= 1
    delay_slots: np.ndarray       # (L,) int32, >= 1
    paths: np.ndarray             # (F, H) int32 link ids, -1 padded
    start_slot: np.ndarray        # (F,) int32 first packet's arrival
    period_slots: np.ndarray      # (F,) int32 CBR period, >= 1
    n_pkts: np.ndarray            # (F,) int32 per-flow packet budget
    n_slots: int                  # simulation horizon in slots
    slot_s: float = 1e-3          # one slot in seconds (reporting only)
    #: per-replica CBR phase jitter amplitude (slots); 0 keeps every
    #: replica on the deterministic host-DES trajectory
    jitter_slots: int = 0
    link_owner: np.ndarray = None  # (L,) int32 rank per link

    def __post_init__(self):
        owner = self.link_owner
        if owner is None:
            owner = np.zeros(self.n_links, np.int32)
            object.__setattr__(self, "link_owner", owner)
        svc = np.asarray(self.service_slots)
        if svc.shape != (self.n_links,) or (svc < 1).any():
            raise UnliftableWiredError(
                "service_slots must be (L,) with every period >= 1 "
                f"(got {svc!r}) — a zero-service link has no slot-model "
                "serialization time"
            )
        if (np.asarray(self.delay_slots) < 1).any():
            raise UnliftableWiredError(
                "delay_slots must be >= 1: a zero-delay hop would make "
                "same-slot arrival order depend on event insertion order "
                "(the device kernel's FIFO is the global (arrival, id) "
                "order over the whole slot)"
            )
        paths = np.asarray(self.paths)
        if ((paths >= self.n_links)).any():
            raise UnliftableWiredError("path names a link id >= n_links")
        if (np.asarray(self.period_slots) < 1).any():
            raise UnliftableWiredError("period_slots must be >= 1")

    @property
    def n_flows(self) -> int:
        return int(np.asarray(self.paths).shape[0])

    @property
    def n_ranks(self) -> int:
        return int(np.asarray(self.link_owner).max()) + 1


#: the documented-faithful fuzz region (see :mod:`tpudes.fuzz`): chain
#: topologies split at the midpoint into two partitions, deterministic
#: CBR flows crossing the boundary, windows cut at the boundary
#: lookahead — the hybrid_vs_host pair runs the 2-rank window protocol
#: on every scenario
FUZZ_ENVELOPE = FuzzEnvelope(
    engine="wired",
    axes={
        "n_links": ("int", 4, 8),
        "n_flows": ("int", 2, 5),
        "max_service": ("choice", (1, 2, 3)),
        "boundary_delay": ("choice", (4, 8, 16)),
        "period": ("int", 3, 17),
        "n_slots": ("int", 200, 1200),
        "replicas": ("int", 1, 4),
        "jitter": ("choice", (0, 2, 5)),
        "key_seed": ("int", 0, 2**16),
    },
    floors={"replicas": 1, "n_flows": 1, "n_links": 2, "n_slots": 32},
    doc="two-partition wired chain, deterministic CBR, exact timestamps",
)


def wired_chain(
    n_links: int = 6,
    n_flows: int = 3,
    *,
    service=None,
    delay=None,
    period: int = 5,
    n_pkts: int = 0,
    n_slots: int = 600,
    ranks: int = 1,
    boundary_delay: int = 8,
    jitter_slots: int = 0,
) -> WiredProgram:
    """Canonical chain builder: ``n_links`` in series, flow ``f``
    entering at link ``f % n_links`` and running to the end of the
    chain (every flow with hops on both sides crosses each partition
    boundary).  ``ranks`` splits the chain into equal contiguous
    partitions; each boundary link's delay is raised to
    ``boundary_delay`` so the window grants have room to batch slots.
    ``n_pkts=0`` fills the horizon (budget = horizon/period)."""
    L = int(n_links)
    # copies, not views: the boundary-delay raise below must never
    # write through a caller-provided array
    svc = np.array(
        service if service is not None else [1 + (i % 2) for i in range(L)],
        np.int32,
    )
    dly = np.array(
        delay if delay is not None else [2 + (i % 3) for i in range(L)],
        np.int32,
    )
    owner = np.minimum(np.arange(L) * ranks // L, ranks - 1).astype(np.int32)
    # a link whose successor lives on another rank is a boundary link;
    # give it the generous boundary delay so lookahead windows batch
    for i in range(L - 1):
        if owner[i] != owner[i + 1]:
            dly[i] = max(dly[i], boundary_delay)
    F = int(n_flows)
    paths = np.full((F, L), -1, np.int32)
    starts, periods, budgets = [], [], []
    for f in range(F):
        first = f % max(L - 1, 1)
        hops = list(range(first, L))
        paths[f, : len(hops)] = hops
        starts.append(1 + 3 * f)
        periods.append(int(period) + f)
        budgets.append(
            int(n_pkts) if n_pkts else max(1, int(n_slots) // (period + f))
        )
    return WiredProgram(
        n_links=L,
        service_slots=svc,
        delay_slots=dly,
        paths=paths,
        start_slot=np.asarray(starts, np.int32),
        period_slots=np.asarray(periods, np.int32),
        n_pkts=np.asarray(budgets, np.int32),
        n_slots=int(n_slots),
        jitter_slots=int(jitter_slots),
        link_owner=owner,
    )


def wired_weak_chain(
    ranks: int,
    links_per_rank: int = 4,
    flows_per_rank: int = 3,
    *,
    period: int = 41,
    cross_period: int = 257,
    n_slots: int = 3000,
    boundary_delay: int = 240,
    jitter_slots: int = 0,
) -> WiredProgram:
    """Weak-scaling scenario: each rank owns ``links_per_rank`` chain
    links carrying ``flows_per_rank`` rank-LOCAL flows (paths confined
    to the rank's block), plus ONE thin cross flow spanning the whole
    chain that keeps the partitions causally coupled.  Per-rank work is
    fixed as ``ranks`` grows — the flow-granular resident sets
    (:func:`partition_flows`) keep each rank's packet table at its
    local flows + the shared cross flow.

    Every rank's block is STRUCTURALLY IDENTICAL (service/delay
    patterns repeat per block; local flows start at the same offsets
    with the same periods in every block), so the local event slots of
    all ranks coincide — under the space-lane engine
    (``transport="batched"``) the union slot clock then steps one
    block's worth of interesting slots no matter how many ranks ride
    the kernel, which is what lets aggregate throughput scale.  The
    defaults keep traffic SPARSE (CBR periods ~``period``, one cross
    packet per ``cross_period``): at sparse partition shapes the
    while-loop step is dispatch-dominated, the regime where adding
    rank lanes is nearly free (the TPU-native pitch, and measurably so
    on XLA:CPU).  ``jitter_slots=0`` keeps replicas on the aligned
    deterministic trajectory; any positive jitter de-aligns lanes and
    the row degrades gracefully toward per-rank stepping."""
    K, lpr, fpr = int(ranks), int(links_per_rank), int(flows_per_rank)
    L = K * lpr
    svc = np.asarray([1 + ((i % lpr) % 2) for i in range(L)], np.int32)
    dly = np.asarray([2 + ((i % lpr) % 3) for i in range(L)], np.int32)
    owner = (np.arange(L) // lpr).astype(np.int32)
    for i in range(L - 1):
        if owner[i] != owner[i + 1]:
            dly[i] = max(dly[i], int(boundary_delay))
    F = K * fpr + 1
    paths = np.full((F, L), -1, np.int32)
    starts, periods, budgets = [], [], []
    f = 0
    for r in range(K):
        for i in range(fpr):
            first = r * lpr + (i % max(lpr - 1, 1))
            hops = list(range(first, (r + 1) * lpr))
            paths[f, : len(hops)] = hops
            # r-independent start/period: rank r's block replays rank
            # 0's local schedule exactly (slot alignment across lanes)
            starts.append(1 + 3 * i)
            periods.append(int(period) + 4 * i)
            budgets.append(max(1, int(n_slots) // (int(period) + 4 * i)))
            f += 1
    # the cross flow: end-to-end over every boundary
    paths[f, :L] = np.arange(L)
    starts.append(2)
    periods.append(int(cross_period))
    budgets.append(max(1, int(n_slots) // int(cross_period)))
    return WiredProgram(
        n_links=L,
        service_slots=svc,
        delay_slots=dly,
        paths=paths,
        start_slot=np.asarray(starts, np.int32),
        period_slots=np.asarray(periods, np.int32),
        n_pkts=np.asarray(budgets, np.int32),
        n_slots=int(n_slots),
        jitter_slots=int(jitter_slots),
        link_owner=owner,
    )


def partition_flows(prog: WiredProgram, rank: int):
    """Flow-granular resident set of ``rank``: the sub-program holding
    only flows whose path touches a link this rank owns, plus the
    global↔local id maps the boundary wire needs.  Returns
    ``(sub_prog, flow_ids, pkt_ids)`` — ``flow_ids`` (F_loc,) global
    flow ids, ``pkt_ids`` (P_loc,) global packet ids (the global
    packet table is flow-major, so both maps are strictly increasing
    and the kernel's (arrival, id) FIFO tiebreak is order-consistent
    across partitions)."""
    import dataclasses

    owner = np.asarray(prog.link_owner)
    paths = np.asarray(prog.paths)
    keep = [
        f for f in range(prog.n_flows)
        if (owner[paths[f][paths[f] >= 0]] == rank).any()
    ]
    if not keep:
        raise UnliftableWiredError(
            f"rank {rank} owns links touched by no flow — an idle "
            "partition has no resident traffic to simulate"
        )
    keep_np = np.asarray(keep, np.int32)
    counts = np.asarray(prog.n_pkts, np.int64)
    offs = np.concatenate(([0], np.cumsum(counts)))
    pkt_ids = np.concatenate(
        [np.arange(offs[f], offs[f + 1]) for f in keep]
    ).astype(np.int32)
    sub = dataclasses.replace(
        prog,
        paths=paths[keep_np],
        start_slot=np.asarray(prog.start_slot)[keep_np],
        period_slots=np.asarray(prog.period_slots)[keep_np],
        n_pkts=np.asarray(prog.n_pkts)[keep_np],
    )
    return sub, keep_np, pkt_ids


def packet_table(prog: WiredProgram):
    """Static per-packet arrays: (pkt_flow, pkt_birth, pkt_nhops), each
    (P,) with P = total packet budget.  Packet ids are flow-major, so
    FIFO's (arrival, id) tiebreak matches the host DES's insertion
    order for same-slot arrivals."""
    flows, births, nhops = [], [], []
    paths = np.asarray(prog.paths)
    for f in range(prog.n_flows):
        h = int((paths[f] >= 0).sum())
        for k in range(int(prog.n_pkts[f])):
            flows.append(f)
            births.append(int(prog.start_slot[f]) + k * int(prog.period_slots[f]))
            nhops.append(h)
    return (
        np.asarray(flows, np.int32),
        np.asarray(births, np.int32),
        np.asarray(nhops, np.int32),
    )


def partition_lookahead(prog: WiredProgram, rank: int) -> int:
    """Conservative lookahead of ``rank``'s partition: the minimum
    ``service + delay`` over its boundary links (links it owns whose
    successor on some flow path is owned elsewhere).  ``INF_SLOT`` when
    the rank never sends.  Raises :class:`UnliftableWiredError` naming
    the offending link when a boundary link's lookahead is not positive
    (the window grant would never advance past it)."""
    owner = np.asarray(prog.link_owner)
    svc = np.asarray(prog.service_slots)
    dly = np.asarray(prog.delay_slots)
    paths = np.asarray(prog.paths)
    look = INF_SLOT
    for f in range(prog.n_flows):
        hops = paths[f][paths[f] >= 0]
        for a, b in zip(hops[:-1], hops[1:]):
            if owner[a] == rank and owner[b] != rank:
                la = int(svc[a]) + int(dly[a])
                if la < 1:
                    raise UnliftableWiredError(
                        f"boundary link {int(a)} (flow {f}, toward rank "
                        f"{int(owner[b])}) has service+delay={la} <= 0: "
                        "zero lookahead degenerates the granted-time "
                        "window to no progress"
                    )
                look = min(look, la)
    return look


def _replica_jitter(prog: WiredProgram, key, replicas: int,
                    replica_offset: int = 0, flow_ids=None):
    """(R, F) per-replica CBR phase jitter in [0, jitter_slots].  Each
    entry is a pure function of ``(key, global replica index, global
    flow id)`` via two ``fold_in`` hops, so:

    - replica bucketing leaves every real replica's phases untouched;
    - every hybrid rank derives the identical jitter from the shared
      key — including ranks that carry only a flow SUBSET
      (``flow_ids`` names the global ids of the local rows);
    - a process computing the slice ``[replica_offset,
      replica_offset + replicas)`` of a larger study reproduces exactly
      the rows one big launch computes (the multi-process
      replica-sharding contract of :mod:`tpudes.parallel.procmesh`).
    """
    import jax
    import jax.numpy as jnp

    if prog.jitter_slots <= 0:
        return jnp.zeros((replicas, prog.n_flows), jnp.int32)
    ids = (
        jnp.arange(prog.n_flows)
        if flow_ids is None
        else jnp.asarray(flow_ids)
    )

    def one(r):
        def per_flow(f):
            return jax.random.randint(
                jax.random.fold_in(jax.random.fold_in(key, r), f), (),
                0, prog.jitter_slots + 1,
            )

        return jax.vmap(per_flow)(ids)

    return jax.vmap(one)(jnp.arange(replicas) + jnp.int32(replica_offset))


def _lane_tables(paths_np, pkt_flow_np, pkt_nhops_np, service_np,
                 delay_np, owned_np, g2l_np, pad_to: int | None = None
                 ) -> dict:
    """Per-(packet, hop) CONSTANT lookup tables for one partition lane.

    Every per-slot link lookup the step body needs (current link's
    owner/service/delay/local-row) is precomputed here as a (P, Hl)
    table indexed by the packet's hop counter, so the hot loop reads
    them through one-hot masked reductions with ZERO gather ops:
    XLA:CPU lowers dynamic gathers to serial per-element loops (they
    were the dominant per-step cost — ~10 us per (P,) gather at P~200),
    while the (P, Hl) elementwise forms fuse into vectorized loops
    whose cost stays far below the while-loop's fixed per-iteration
    dispatch.  That fixed dispatch is what the space-lane engine
    amortizes across ranks, so keeping the variable part tiny is what
    makes rank lanes nearly free.

    The hop axis is TRIMMED to the lane's own columns: only hop
    positions where some resident flow sits on an owned link survive
    (``colh`` holds their global hop values; a hop value outside the
    column set one-hot-matches nothing, which is exactly the "not my
    packet right now" semantics).  On a K-rank chain each lane owns
    ~L/K hop positions, so per-lane table width — and with it the
    per-step memory traffic — stays FIXED as ranks are added instead
    of growing with the global path length.  ``pad_to`` right-pads
    with never-matching ``colh=-1`` columns so ragged lanes stack."""
    valid = paths_np >= 0
    safe = np.clip(paths_np, 0, service_np.shape[0] - 1)
    svcdly = np.where(valid, service_np[safe] + delay_np[safe], 0)
    owned_h = valid & owned_np[safe]
    lseg_h = np.where(owned_h, g2l_np[safe], 0)
    keep = np.nonzero(owned_h.any(axis=0))[0].astype(np.int32)
    pad = 0 if pad_to is None else int(pad_to) - keep.size
    colh = np.concatenate([keep, np.full(pad, -1, np.int32)])

    def col(a, fill):
        out = a[:, keep]
        if pad:
            out = np.concatenate(
                [out, np.full((a.shape[0], pad), fill, a.dtype)], axis=1
            )
        return out

    return dict(
        colh=colh.astype(np.int32),
        pkt_nhops=pkt_nhops_np.astype(np.int32),
        # (P, Hl): service+delay / owned-ness / local link row at the
        # hop position colh[j]
        psvcdly=col(svcdly, 0)[pkt_flow_np].astype(np.int32),
        powned=col(owned_h, False)[pkt_flow_np],
        plseg=col(lseg_h, 0)[pkt_flow_np].astype(np.int32),
        service_local=service_np[np.nonzero(owned_np)[0]].astype(np.int32),
    )


def _make_lane_step(P: int, Lo: int):
    """Return ``(step, next_of)`` over one lane-replica's state.

    ``step(tbl, t, hop, ready, free, deliver, eg_hop, eg_ready,
    served)`` serves every owned, free link's FIFO head at slot ``t``
    and returns ``(new_state, next_interesting_slot)``; ``next_of(tbl,
    hop, ready, free)`` is the same next-event reduction standalone
    (the window driver's fresh metric).  The per-link FIFO argmin is a
    DENSE (Lo, P) masked reduction, not a segment/scatter op, and all
    link attributes come from the :func:`_lane_tables` one-hot forms —
    XLA:CPU serializes both scatters and gathers (each measured ~10x a
    fused masked reduction per step), and every other backend fuses
    the dense forms too."""
    import jax.numpy as jnp

    pid = jnp.arange(P, dtype=jnp.int32)
    lid = jnp.arange(Lo, dtype=jnp.int32)

    # every int reduction pins dtype=jnp.int32: jnp.sum follows numpy's
    # sub-default-int accumulator promotion, so an unpinned .sum() would
    # widen the whole carry to i64 under ambient x64 (JXL002) — under
    # the default config the pin is a bit-exact no-op
    def locate(tbl, hop):
        """(oh, on_owned, lseg, lane_oh) of each packet's CURRENT hop:
        whether it sits at a link this lane serves, and the (Lo, P)
        one-hot of which; all-false once delivered / parked at a peer
        (their hop value matches no ``colh`` column)."""
        oh = hop[:, None] == tbl["colh"][None, :]   # (P, Hl)
        on_owned = (tbl["powned"] & oh).any(1)
        # junk 0 unless owned
        lseg = (tbl["plseg"] * oh).sum(1, dtype=jnp.int32)
        lane_oh = (lseg[None, :] == lid[:, None]) & on_owned[None, :]
        return oh, on_owned, lseg, lane_oh

    def _next_min(on_owned, lseg, ready, free):
        lane_oh = (lseg[None, :] == lid[:, None]) & on_owned[None, :]
        free_p = (free[:, None] * lane_oh).sum(0, dtype=jnp.int32)
        return jnp.min(jnp.where(
            on_owned, jnp.maximum(ready, free_p), INF_SLOT
        ))

    def next_of(tbl, hop, ready, free):
        _, on_owned, lseg, _ = locate(tbl, hop)
        return _next_min(on_owned, lseg, ready, free)

    def step(tbl, t, hop, ready, free, deliver, eg_hop, eg_ready,
             served):
        oh, on_owned, lseg, lane_oh = locate(tbl, hop)
        waiting = on_owned & (ready <= t)
        at_link = lane_oh & waiting[None, :]      # (Lo, P)
        # FIFO head per link: lexicographic (arrival slot, packet id)
        # via two masked mins — int32-safe (no ready*P key to overflow)
        m_ready = jnp.where(at_link, ready[None, :], INF_SLOT).min(axis=1)
        m_ready_p = (m_ready[:, None] * lane_oh).sum(0, dtype=jnp.int32)
        cand = waiting & (ready == m_ready_p)
        m_pid = jnp.where(
            at_link & cand[None, :], pid[None, :], INF_SLOT
        ).min(axis=1)
        m_pid_p = (m_pid[:, None] * lane_oh).sum(0, dtype=jnp.int32)
        link_can = (free <= t) & (m_ready < INF_SLOT)   # (Lo,)
        link_can_p = (link_can[:, None] & lane_oh).any(0)
        serve = cand & (pid == m_pid_p) & link_can_p

        arr = t + (tbl["psvcdly"] * oh).sum(1, dtype=jnp.int32)  # (P,)
        new_hop = hop + 1
        oh2 = new_hop[:, None] == tbl["colh"][None, :]
        has_next = new_hop < tbl["pkt_nhops"]
        next_owned = (tbl["powned"] & oh2).any(1)
        done_now = serve & ~has_next
        deliver = jnp.where(done_now, arr, deliver)
        crossing = serve & has_next & ~next_owned
        eg_hop = jnp.where(crossing, new_hop, eg_hop)
        eg_ready = jnp.where(crossing, arr, eg_ready)
        hop = jnp.where(serve, new_hop, hop)
        ready = jnp.where(serve, arr, ready)
        link_served = (at_link & serve[None, :]).any(axis=1)  # <=1/slot
        free = jnp.where(link_served, t + tbl["service_local"], free)
        served = served + link_served.astype(jnp.int32)

        # next interesting slot: earliest (arrival, link-free) meet of
        # any still-active owned packet.  Post-step placement differs
        # from pre-step only for SERVED packets, whose new hop's
        # owned-ness/row were already computed above (``oh2``) — reuse
        # them instead of paying a second full locate()
        on_owned2 = jnp.where(serve, has_next & next_owned, on_owned)
        lseg2 = jnp.where(
            serve, (tbl["plseg"] * oh2).sum(1, dtype=jnp.int32), lseg
        )
        nxt = _next_min(on_owned2, lseg2, ready, free)
        return (hop, ready, free, deliver, eg_hop, eg_ready, served), nxt

    return step, next_of


def build_wired_advance(prog: WiredProgram, replicas: int, owned=None,
                        flow_ids=None, obs: bool = False):
    """Return ``(init_state, advance)`` for the windowed wired kernel.

    ``owned`` is an (L,) bool mask of the links THIS engine instance
    serves (None = all); packets currently at an unowned link are
    inert — they belong to a peer partition.  ``flow_ids`` names the
    GLOBAL flow id of each of ``prog``'s rows when ``prog`` is a
    resident-subset partition (see :func:`partition_flows`): the
    per-replica jitter is derived from global ids, so every rank draws
    identical phases for the flows it shares with peers.

    ``advance(carry, ing_hop, ing_ready, t_grant)`` applies the ingress
    operands (entries with ``ing_hop >= 0`` overwrite that packet's hop
    and arrival slot — the boundary traffic a peer demuxed at its last
    window edge), clears the egress buffers, then serves every owned
    link strictly below the traced grant.  Returns ``(carry, metrics)``
    with fresh-reduction metrics (``next_event``, ``n_steps``) — the
    window driver's grant inputs without fetching the full carry (the
    drivers demux boundary traffic straight from the egress buffers,
    so the metrics stay minimal: every extra field would be one more
    full-array reduction per window).
    """
    import jax
    import jax.numpy as jnp

    from tpudes.parallel.runtime import scoped_while_loop

    R = int(replicas)
    L = int(prog.n_links)
    pkt_flow_np, pkt_birth_np, pkt_nhops_np = packet_table(prog)
    P = int(pkt_flow_np.shape[0])
    H = int(np.asarray(prog.paths).shape[1])
    owned_np = (
        np.ones(L, bool) if owned is None else np.asarray(owned, bool)
    )
    # LOCAL link axis: the kernel's per-slot working set is (Lo, P) for
    # Lo = owned link count — ghost links exist only as (L,) lookup
    # tables, so per-rank work stays fixed as the global graph grows
    # (the weak-scaling property).  g2l maps global link id -> local
    # row; its value for unowned links is a junk 0 masked by on_owned.
    owned_idx_np = np.nonzero(owned_np)[0].astype(np.int32)
    Lo = int(owned_idx_np.size)
    g2l_np = np.zeros(L, np.int32)
    g2l_np[owned_idx_np] = np.arange(Lo, dtype=np.int32)

    pkt_flow = jnp.asarray(pkt_flow_np)          # (P,)
    pkt_birth = jnp.asarray(pkt_birth_np)
    tbl = {
        k: jnp.asarray(v)
        for k, v in _lane_tables(
            np.asarray(prog.paths), pkt_flow_np, pkt_nhops_np,
            np.asarray(prog.service_slots), np.asarray(prog.delay_slots),
            owned_np, g2l_np,
        ).items()
    }
    step, next_of = _make_lane_step(P, Lo)

    if obs:
        from tpudes.obs.flowmon import (
            FLOW_DELAY_BINS,
            VERDICT_RX,
            VERDICT_TX,
            flow_accumulate,
            flow_carry,
            flow_ring_write,
        )

        F = int(prog.n_flows)
        # (P, F) flow-membership CONSTANT: every per-flow reduction is
        # a matmul against it (counts/slot sums far below 2^24, exact
        # in f32) — the no-gather contract stays intact
        flow_oh = jnp.asarray(
            pkt_flow_np[:, None] == np.arange(F, dtype=pkt_flow_np.dtype),
            jnp.float32,
        )
        valid_h = np.asarray(prog.paths) >= 0
        safe_h = np.clip(np.asarray(prog.paths), 0, L - 1)
        path_slots = np.where(
            valid_h,
            np.asarray(prog.service_slots)[safe_h]
            + np.asarray(prog.delay_slots)[safe_h],
            0,
        ).sum(axis=1)
        # histogram bin width in SLOT units: slot_s is a reporting-only
        # scale that never reaches the compiled program (wired_cache_key
        # excludes it) — run_wired's unpack scales the fetched float
        # columns to seconds on the host
        bin_slots = max(1.0, 2.0 * float(path_slots.max()) / FLOW_DELAY_BINS)

        def per_flow(mask_f32):
            return jnp.matmul(mask_f32, flow_oh)        # (R, F)

    def init_state(key, replica_offset: int = 0):
        jit_rf = _replica_jitter(
            prog, key, R, replica_offset, flow_ids
        )  # (R, F)
        birth = pkt_birth[None, :] + jit_rf[:, pkt_flow]  # (R, P)
        state = dict(
            t=jnp.int32(0),
            hop=jnp.zeros((R, P), jnp.int32),
            ready=birth.astype(jnp.int32),
            free=jnp.zeros((R, Lo), jnp.int32),
            deliver=jnp.full((R, P), -1, jnp.int32),
            eg_hop=jnp.full((R, P), -1, jnp.int32),
            eg_ready=jnp.full((R, P), -1, jnp.int32),
            served=jnp.zeros((R, Lo), jnp.int32),
        )
        if obs:
            # fm_birth: the jittered send slot of every packet (delay =
            # deliver - birth, exact); fm_mark: the last slot whose
            # births were folded into fm_tx (exactly-once accounting
            # across event steps AND window boundaries)
            state.update(flow_carry(F, lead=(R,)))
            # a COPY: the carry is donated, and one buffer under two
            # carry leaves is "donate the same buffer twice" on a
            # backend that donates
            state["fm_birth"] = jnp.copy(state["ready"])
            state["fm_mark"] = jnp.int32(-1)
        return state

    vstep = jax.vmap(
        lambda t, *s: step(tbl, t, *s),
        in_axes=(None, 0, 0, 0, 0, 0, 0, 0),
    )
    vnext = jax.vmap(lambda h, rd, fr: next_of(tbl, h, rd, fr))

    def advance(carry, ing_hop, ing_ready, t_grant):
        inject = ing_hop >= 0
        hop = jnp.where(inject, ing_hop, carry["hop"])
        ready = jnp.where(inject, ing_ready, carry["ready"])
        state = (
            carry["t"],
            hop,
            ready,
            carry["free"],
            carry["deliver"],
            jnp.full((R, P), -1, jnp.int32),
            jnp.full((R, P), -1, jnp.int32),
            carry["served"],
        )

        def cond(c):
            return c[0] < t_grant

        def body(c):
            t, n_steps = c[0], c[1]
            if not obs:
                new, nxt = vstep(t, *c[2:-1])
                t_next = jnp.maximum(
                    t + 1, jnp.minimum(jnp.min(nxt), t_grant)
                )
                return (t_next, n_steps + 1, *new, nxt)
            # obs variant: the fm dict rides at the end of the loop
            # carry; deliveries are the deliver-column edge this event
            # step, sends the births that became visible since the
            # last accounted slot (fm_mark) — exactly-once per packet
            fm = c[-1]
            new, nxt = vstep(t, *c[2:-2])
            new_del = (new[3] >= 0) & (c[5] < 0)            # (R, P)
            born = (
                (fm["fm_birth"] > fm["fm_mark"])
                & (fm["fm_birth"] <= t)
            )
            rx_f = per_flow(new_del.astype(jnp.float32)).astype(jnp.int32)
            tx_f = per_flow(born.astype(jnp.float32)).astype(jnp.int32)
            dsum_f = per_flow(
                jnp.where(
                    new_del,
                    (new[3] - fm["fm_birth"]).astype(jnp.float32),
                    0.0,
                )
            )
            # per-(step, flow) delay observation = the step mean (the
            # documented multi-packet coarsening); dsum accumulates
            # mean*rx = the exact per-packet slot sum
            mean_d = dsum_f / jnp.maximum(rx_f, 1).astype(jnp.float32)
            fm2 = flow_accumulate(
                fm,
                t_s=t.astype(jnp.float32),                  # slot units
                tx=tx_f,
                tx_bytes=tx_f * jnp.int32(WIRED_PKT_BYTES),
                rx=rx_f,
                rx_bytes=rx_f * jnp.int32(WIRED_PKT_BYTES),
                delay_s=mean_d,                             # slot units
                lost=jnp.zeros_like(rx_f),
                bin_width_s=bin_slots,
            )
            any_rx = new_del.any(axis=1)
            any_tx = born.any(axis=1)
            ev_flow = jnp.where(
                any_rx,
                jnp.argmax(rx_f, axis=1),
                jnp.argmax(tx_f, axis=1),
            ).astype(jnp.int32)
            row = jnp.stack([
                jnp.where(any_rx | any_tx, t, jnp.int32(-1)),
                jnp.broadcast_to(t, (R,)),  # slot; host scales to µs
                ev_flow,
                jnp.full((R,), WIRED_PKT_BYTES, jnp.int32),
                jnp.where(
                    any_rx, jnp.int32(VERDICT_RX), jnp.int32(VERDICT_TX)
                ),
            ], axis=-1)
            fm2["fm_ring"] = flow_ring_write(fm["fm_ring"], t, row)
            fm2["fm_mark"] = t
            t_next = jnp.maximum(t + 1, jnp.minimum(jnp.min(nxt), t_grant))
            return (t_next, n_steps + 1, *new, nxt, fm2)

        nxt0 = jnp.full((R,), INF_SLOT, jnp.int32)
        loop0 = (state[0], jnp.int32(0), *state[1:], nxt0)
        if obs:
            loop0 = loop0 + (
                {k: v for k, v in carry.items() if k.startswith("fm_")},
            )
        out = scoped_while_loop("wired", cond, body, loop0)
        (t, n_steps, hop, ready, free, deliver, eg_hop, eg_ready,
         served, nxt) = out[:10]
        carry = dict(
            t=t, hop=hop, ready=ready, free=free, deliver=deliver,
            eg_hop=eg_hop, eg_ready=eg_ready, served=served,
        )
        if obs:
            fm = out[10]
            # window-edge flush: births the event loop never visited
            # (their first service met a busy link past the grant) are
            # still sends of THIS window — fold them in so fm_tx is
            # exact at every boundary; the next window resumes at
            # fm_mark = t_grant - 1
            born = (
                (fm["fm_birth"] > fm["fm_mark"])
                & (fm["fm_birth"] < t_grant)
            )
            tx_f = per_flow(born.astype(jnp.float32)).astype(jnp.int32)
            zf = jnp.zeros_like(tx_f)
            fm = flow_accumulate(
                fm,
                t_s=(t_grant - 1).astype(jnp.float32),
                tx=tx_f,
                tx_bytes=tx_f * jnp.int32(WIRED_PKT_BYTES),
                rx=zf,
                rx_bytes=zf,
                delay_s=jnp.zeros(tx_f.shape, jnp.float32),
                lost=zf,
                bin_width_s=bin_slots,
            )
            fm["fm_mark"] = t_grant - 1
            carry.update(fm)
        # the loop's LAST step already reduced the final state's next
        # interesting slot — recompute the full locate chain only for
        # the rare zero-step window (priming / an empty grant), where
        # the carried value is the INF sentinel, not the state's
        next_event = jax.lax.cond(
            n_steps == 0,
            lambda: jnp.min(vnext(hop, ready, free)),
            lambda: jnp.min(nxt),
        )
        metrics = dict(next_event=next_event, n_steps=n_steps)
        if obs:
            # lax.rev is a real op XLA cannot fold into an alias of the
            # donated carry (drive_chunks freshness invariant); the
            # decoder sorts by the step column, so order never matters
            metrics["fm_ring"] = jnp.flip(carry["fm_ring"], axis=-2)
        return carry, metrics

    return init_state, advance


def build_wired_space_advance(prog: WiredProgram, replicas: int):
    """All K partitions of ``prog`` as **vector lanes of one kernel**:
    ``(init_state, advance, parts)`` with every state array carrying a
    leading rank axis — hop/ready/deliver/egress ``(K, R, P)``,
    free/served ``(K, R, Lo)`` — and ONE shared slot clock stepping the
    union of the lanes' interesting slots.

    This is the single-host lowering of the hybrid PDES: the per-slot
    work of XLA's while loop is dispatch-dominated at partition shapes
    (measured ~0.3 ms/step on XLA:CPU whether the operands hold one
    partition or eight), so advancing all ranks as lanes of one
    program costs roughly ONE rank's wall — aggregate throughput then
    scales with the rank count, which is exactly the weak-scaling row's
    claim.  On a TPU mesh the same stacked program shards the rank axis
    across devices like any other batch axis; the spawned-process
    ``transport="mpi"`` path remains the multi-host form.

    Stepping a lane at another lane's interesting slot is a no-op (its
    FIFO has nothing ready, so the serve mask is empty), and the window
    protocol the driver runs on top is byte-for-byte the per-engine
    one, so results are bit-identical to ``transport="local"``/"mpi"
    and to the single-engine ``run_wired``.

    Requires uniform partitions (equal per-rank flow/packet/link
    counts — the weak-scaling chains are uniform by construction);
    raises :class:`UnliftableWiredError` otherwise.  ``parts`` is the
    per-rank ``(sub_prog, flow_ids, pkt_ids)`` list the driver needs
    for boundary demux.
    """
    import jax
    import jax.numpy as jnp

    from tpudes.parallel.runtime import scoped_while_loop

    R = int(replicas)
    L = int(prog.n_links)
    K = prog.n_ranks
    H = int(np.asarray(prog.paths).shape[1])
    parts = [partition_flows(prog, r) for r in range(K)]
    tabs = [packet_table(sub) for sub, _, _ in parts]
    owner = np.asarray(prog.link_owner)
    owned_ks = [owner == r for r in range(K)]
    if len({t[0].shape[0] for t in tabs}) != 1 or len(
        {int(m.sum()) for m in owned_ks}
    ) != 1 or len({p[0].n_flows for p in parts}) != 1:
        raise UnliftableWiredError(
            "space-batched lanes need uniform partitions (equal per-rank"
            " flow/packet/owned-link counts); partitions here are "
            f"flows={[p[0].n_flows for p in parts]} "
            f"pkts={[int(t[0].shape[0]) for t in tabs]} "
            f"links={[int(m.sum()) for m in owned_ks]} — use "
            "transport='local'/'mpi', which allow ragged partitions"
        )
    P = int(tabs[0][0].shape[0])
    Lo = int(owned_ks[0].sum())
    g2l_ks = []
    for m in owned_ks:
        idx = np.nonzero(m)[0].astype(np.int32)
        g2l = np.zeros(L, np.int32)
        g2l[idx] = np.arange(Lo, dtype=np.int32)
        g2l_ks.append(g2l)

    # per-lane constant tables (the no-gather one-hot forms of
    # :func:`_lane_tables`), stacked on the rank axis — axis 0 of every
    # leaf, the outer vmap's in_axes below
    service_np = np.asarray(prog.service_slots)
    delay_np = np.asarray(prog.delay_slots)

    def lane_tbl(k, pad_to=None):
        return _lane_tables(
            np.asarray(parts[k][0].paths), tabs[k][0], tabs[k][2],
            service_np, delay_np, owned_ks[k], g2l_ks[k], pad_to=pad_to,
        )

    width = max(lane_tbl(k)["colh"].size for k in range(K))
    lane_tbls = [lane_tbl(k, pad_to=width) for k in range(K)]
    tbl = {
        name: jnp.asarray(np.stack([lt[name] for lt in lane_tbls]))
        for name in lane_tbls[0]
    }
    step, next_of = _make_lane_step(P, Lo)

    # vmap replicas (shared tables, shared t), then lanes (per-lane
    # tables, shared t) — the union clock
    rstep = jax.vmap(step, in_axes=(None, None, 0, 0, 0, 0, 0, 0, 0))
    kstep = jax.vmap(rstep, in_axes=(0, None, 0, 0, 0, 0, 0, 0, 0))

    def lane_next_event(carry):
        rnext = jax.vmap(next_of, in_axes=(None, 0, 0, 0))
        knext = jax.vmap(rnext, in_axes=(0, 0, 0, 0))
        return jnp.min(
            knext(tbl, carry["hop"], carry["ready"], carry["free"]),
            axis=1,
        )

    def init_state(key):
        hops, readys = [], []
        for (sub, flow_ids, _), (pf, pb, _) in zip(parts, tabs):
            jit_rf = _replica_jitter(sub, key, R, 0, flow_ids)  # (R, F)
            readys.append(
                (jnp.asarray(pb)[None, :] + jit_rf[:, jnp.asarray(pf)])
                .astype(jnp.int32)
            )
            hops.append(jnp.zeros((R, P), jnp.int32))
        # lane-major layout BY DESIGN: the RANK axis leads (it is the
        # axis a device mesh shards), replicas ride second; the drivers
        # demux per lane, never through the runtime's axis-0 slice-back
        return dict(
            t=jnp.int32(0),
            hop=jnp.stack(hops),
            ready=jnp.stack(readys),
            free=jnp.zeros((K, R, Lo), jnp.int32),      # tpudes: ignore[SHP001]
            deliver=jnp.full((K, R, P), -1, jnp.int32),  # tpudes: ignore[SHP001]
            eg_hop=jnp.full((K, R, P), -1, jnp.int32),   # tpudes: ignore[SHP001]
            eg_ready=jnp.full((K, R, P), -1, jnp.int32),  # tpudes: ignore[SHP001]
            served=jnp.zeros((K, R, Lo), jnp.int32),     # tpudes: ignore[SHP001]
        )

    def advance(carry, ing_hop, ing_ready, t_grant):
        inject = ing_hop >= 0
        hop = jnp.where(inject, ing_hop, carry["hop"])
        ready = jnp.where(inject, ing_ready, carry["ready"])
        state = (
            hop, ready, carry["free"], carry["deliver"],
            jnp.full((K, R, P), -1, jnp.int32),  # tpudes: ignore[SHP001]
            jnp.full((K, R, P), -1, jnp.int32),  # tpudes: ignore[SHP001]
            carry["served"],
        )

        def cond(c):
            return c[0] < t_grant

        def body(c):
            t, n_steps = c[0], c[1]
            new, nxt = kstep(tbl, t, *c[2:-1])
            t_next = jnp.maximum(
                t + 1, jnp.minimum(jnp.min(nxt), t_grant)
            )
            return (t_next, n_steps + 1, *new, nxt)

        nxt0 = jnp.full((K, R), INF_SLOT, jnp.int32)  # tpudes: ignore[SHP001]
        (t, n_steps, hop, ready, free, deliver, eg_hop, eg_ready,
         served, nxt) = scoped_while_loop(
            "wired", cond, body,
            (carry["t"], jnp.int32(0), *state, nxt0),
        )
        carry = dict(
            t=t, hop=hop, ready=ready, free=free, deliver=deliver,
            eg_hop=eg_hop, eg_ready=eg_ready, served=served,
        )
        # per-lane next events ride out of the loop's LAST step; the
        # full locate chain only runs for a zero-step window (priming)
        next_event = jax.lax.cond(
            n_steps == 0,
            lambda: lane_next_event(carry),                     # (K,)
            lambda: jnp.min(nxt, axis=1),
        )
        metrics = dict(next_event=next_event, n_steps=n_steps)
        return carry, metrics

    return init_state, advance, parts


def wired_cache_key(prog: WiredProgram, keep_owner: bool = False) -> tuple:
    """Hashable identity of the WiredProgram fields that shape the
    compiled kernel (and its cached ``init_state`` closure).

    ``n_slots`` is absent — the grant is a traced while_loop bound, so
    one executable serves every horizon and window schedule.
    ``slot_s`` is absent — it is a reporting-only scale factor that
    never reaches the device (keying on it was a dead cache-key
    component causing spurious recompiles; found by analysis rule
    JXL004).  ``link_owner`` is absent unless ``keep_owner``: it is
    partition METADATA that plain ``run_wired`` and the per-rank
    hybrid engines never read (their served-link set arrives as the
    explicit ``owned`` mask, already keyed separately) — only the
    space-lanes kernel, which derives its whole lane structure from
    the ownership map, keys on it."""
    skip = {"n_slots", "slot_s"}
    if not keep_owner:
        skip.add("link_owner")
    return tuple(
        v.tobytes() if isinstance(v, np.ndarray) else v
        for k, v in prog.__dict__.items()
        if k not in skip
    )


def _wired_unpack(host: dict, prog: WiredProgram, replicas: int) -> dict:
    """Host-side result assembly (slice padded replicas back)."""
    R = int(replicas)
    pkt_flow, _, _ = packet_table(prog)
    deliver = np.asarray(host["deliver"])[:R]          # (R, P)
    F = prog.n_flows
    delivered = np.zeros((R, F), np.int32)
    np.add.at(
        delivered,
        (np.arange(R)[:, None].repeat(deliver.shape[1], 1), pkt_flow[None, :]),
        (deliver >= 0).astype(np.int32),
    )
    return dict(
        deliver_slot=deliver,
        delivered=delivered,
        served=np.asarray(host["served"])[:R],
    )


def run_wired(
    prog: WiredProgram,
    key,
    replicas: int = 1,
    mesh=None,
    *,
    window_slots: int | None = None,
    replica_offset: int = 0,
    block: bool = True,
):
    """Execute R replicas of the wired program on the device; returns
    ``deliver_slot`` (R, P) exact per-packet delivery slots (-1 =
    undelivered in-horizon), ``delivered`` (R, F) per-flow counts and
    ``served`` (R, L) per-link service counts.

    ``window_slots=N`` splits the horizon into N-slot ``advance``
    segments with a donated carry handoff — bit-identical to the
    single-shot run (the windowed form the hybrid ranks drive with
    grants instead of fixed bounds).  ``replica_offset`` shifts the
    per-replica jitter indices so a multi-process launch can shard the
    replica axis exactly: process ``p`` running
    ``run_wired(..., replicas=k, replica_offset=p*k)`` computes
    bit-identical rows to the corresponding slice of one big run.
    ``block=False`` returns an
    :class:`~tpudes.parallel.runtime.EngineFuture`.
    """
    import jax.numpy as jnp

    from tpudes.parallel.runtime import Launch, chunk_bounds

    L = Launch("wired", key, replicas, mesh, None)
    r_pad = L.r_pad

    def build():
        init_state, advance = build_wired_advance(prog, r_pad, obs=L.obs)

        def init(key, replica_offset):
            carry = init_state(key, replica_offset)
            # a run without peers: no window has ingress traffic
            none = jnp.full((r_pad, carry["hop"].shape[1]), -1, jnp.int32)
            return carry, (none, none)

        return init, (0, 0), advance, None

    # see wired_cache_key for what is (deliberately) absent;
    # replica_offset is a traced argument of the init program
    L.prepare(
        lambda: wired_cache_key(prog) + (r_pad, L.obs),
        build, lambda parts: parts,
        init_args=(key, np.int32(replica_offset)),
    )

    def fetch(carry):
        names = ("deliver", "served")
        if L.obs:
            from tpudes.obs.flowmon import FM_KEYS

            names += FM_KEYS
        return {k: carry[k] for k in names}

    def unpack_one(host):
        out = _wired_unpack(host, prog, replicas)
        fm = {
            k: np.asarray(v)[:replicas]
            for k, v in host.items()
            if k.startswith("fm_")
        }
        if fm:
            # the device accumulates in SLOT units (slot_s is a
            # reporting-only scale excluded from wired_cache_key, so it
            # must never reach the compiled program) — scale the float
            # columns to seconds and the ring timestamps to µs here;
            # the -1.0 sentinels stay negative under the positive scale
            slot_s = float(prog.slot_s)
            for k in ("fm_dsum", "fm_jsum", "fm_dlast", "fm_t0", "fm_t1"):
                fm[k] = np.asarray(fm[k], np.float64) * slot_s
            ring = np.asarray(fm["fm_ring"], np.int64).copy()
            ring[..., 1] = np.where(
                ring[..., 0] >= 0,
                np.round(ring[..., 1] * slot_s * 1e6).astype(np.int64),
                ring[..., 1],
            )
            fm["fm_ring"] = ring
            out["flow"] = fm
        return out

    return L.drive(
        lambda fn, carry, t_grant, no_ingress: fn(
            carry, *no_ingress, t_grant
        ),
        chunk_bounds(prog.n_slots, window_slots or prog.n_slots),
        fetch,
        unpack_one,
        block=block,
    )


def run_wired_host(prog: WiredProgram, jitter: np.ndarray | None = None) -> dict:
    """The sequential host DES oracle: the same wired model through the
    :class:`~tpudes.core.simulator.DefaultSimulatorImpl` event core
    (heap-ordered callbacks in tick time, 1 tick = 1 slot), mirroring
    how ``tests/test_distributed.py`` pins the space-parallel engines
    against the sequential run.  Timestamps are exact: returns
    ``deliver_slot`` (P,) identical to any ``run_wired`` replica with
    the same jitter row (``jitter`` is the (F,) phase offset; None = 0,
    the ``jitter_slots=0`` trajectory)."""
    from tpudes.core.simulator import DefaultSimulatorImpl

    pkt_flow, pkt_birth, pkt_nhops = packet_table(prog)
    P = int(pkt_flow.shape[0])
    paths = np.asarray(prog.paths)
    svc = np.asarray(prog.service_slots)
    dly = np.asarray(prog.delay_slots)
    if jitter is not None:
        pkt_birth = pkt_birth + np.asarray(jitter, np.int32)[pkt_flow]

    impl = DefaultSimulatorImpl()
    queues: list[list] = [[] for _ in range(prog.n_links)]  # (ready, pid)
    busy = [False] * prog.n_links
    hop_pos = np.zeros(P, np.int32)
    deliver = np.full(P, -1, np.int32)
    served = np.zeros(prog.n_links, np.int32)
    horizon = int(prog.n_slots)

    # event discipline matching the device kernel's slot-global FIFO:
    # every arrival at tick t is scheduled at a strictly earlier tick
    # (delay >= 1 is enforced by WiredProgram), so all tick-t arrivals
    # are in the heap before tick t begins; service attempts run as
    # ZERO-DELAY events inserted during tick t — after every arrival —
    # so the (arrival, id) FIFO choice sees the same candidate set the
    # device's whole-slot argmin sees
    def attempt(link: int):
        t = impl.Now()
        if busy[link] or not queues[link] or t >= horizon:
            return
        queues[link].sort()
        ready, p = queues[link].pop(0)
        busy[link] = True
        served[link] += 1
        hop_arr = t + int(svc[link]) + int(dly[link])
        pos = int(hop_pos[p])
        hop_pos[p] = pos + 1
        last = pos + 1 >= int(pkt_nhops[p])
        if last:
            # decided at SERVE time, like the device: a post-horizon
            # landing counts when its final service started in-horizon
            deliver[p] = hop_arr
        impl.Schedule(int(svc[link]), finish, (link, p, hop_arr, last))

    def finish(link: int, p: int, hop_arr: int, last: bool):
        busy[link] = False
        if not last:
            nxt = int(paths[pkt_flow[p]][int(hop_pos[p])])
            impl.Schedule(
                hop_arr - impl.Now(), arrive, (p, nxt, hop_arr)
            )
        impl.Schedule(0, attempt, (link,))

    def arrive(p: int, link: int, ready: int):
        queues[link].append((int(ready), int(p)))
        impl.Schedule(0, attempt, (link,))

    for p in range(P):
        first = int(paths[pkt_flow[p]][0])
        impl.Schedule(int(pkt_birth[p]), arrive, (p, first, int(pkt_birth[p])))
    # run to quiescence: the per-event horizon check in attempt() stops
    # all service starts at the horizon, so the heap drains on its own
    impl.Stop(horizon + int(svc.max()) + int(dly.max()) + 2)
    impl.Run()
    return dict(deliver_slot=deliver, served=served)


# --- trace manifest (tpudes.analysis.jaxpr) --------------------------------

#: canonical tiny replica count for the abstract traces
_TRACE_R = 2


def _trace_prog(**over):
    """Canonical tiny-shape program: 3-link chain, 2 flows, jittered so
    the per-replica ``fold_in`` draw path is part of the traced
    surface.  ``over`` applies single-field flips."""
    import dataclasses

    prog = wired_chain(
        n_links=3, n_flows=2, n_slots=40, jitter_slots=2
    )
    return dataclasses.replace(prog, **over) if over else prog


def _trace_entries(prog: WiredProgram, scale: bool = True,
                   obs: bool = False):
    """The two cached-runner functions exactly as ``run_wired`` jits
    them, with concrete tiny operands.  ``scale=False`` skips the
    JXL007 axis declarations (the axis builders re-enter here for
    their shape-scaled programs)."""
    import jax
    import jax.numpy as jnp

    from tpudes.analysis.jaxpr.spec import TraceEntry

    init_state, advance = build_wired_advance(prog, _TRACE_R, obs=obs)
    key = jax.random.PRNGKey(0)
    carry = init_state(key)
    P = int(carry["hop"].shape[1])
    no_ing = jnp.full((_TRACE_R, P), -1, jnp.int32)
    return [
        TraceEntry(
            "init", lambda k: init_state(k, 0), (key,), kernel=False
        ),
        TraceEntry(
            "advance",
            advance,
            (carry, no_ing, no_ing, jnp.int32(8)),
            donate=(0,),
            carry=(0,),
            traced={"ing_hop": 1, "ing_ready": 2, "t_grant": 3},
            scale_axes=_scale_axes() if scale else (),
        ),
    ]


def _scale_axes():
    """JXL007 scale axes for the advance kernel.  The dense
    per-(packet,hop) one-hot tables are O(links × packets): each axis
    alone is linear, but the joint ``n_nodes`` axis (links AND flows
    both grow with topology size in a chain) is quadratic and is
    declared at budget 1.0 so it FIRES by design — the documented,
    baselined ROADMAP item-2 worklist the sparse CSR rewrite must
    clear.  Axis builds pin ``n_pkts=4`` so the packet count scales
    exactly with the flow count (horizon-filled budgets would vary
    per-flow period and blur the fit)."""
    from tpudes.analysis.jaxpr.spec import ScaleAxis

    def at(**over):
        prog = wired_chain(
            n_slots=40, jitter_slots=2, n_pkts=4, **over
        )
        return _trace_entries(prog, scale=False)[1]

    return (
        ScaleAxis(
            "n_links",
            lambda v: at(n_links=int(v), n_flows=2),
            points=(3, 12),
            mem_budget=1.0,
        ),
        ScaleAxis(
            "n_flows",
            lambda v: at(n_links=3, n_flows=int(v)),
            points=(2, 8),
            mem_budget=1.0,
        ),
        ScaleAxis(
            "n_nodes",
            lambda v: at(n_links=int(v), n_flows=int(v)),
            points=(3, 6, 12),
            mem_budget=1.0,
            nodes_per_unit=1.0,
            note="joint links+flows axis: the dense one-hot step "
                 "tables are O(L*P) — fires until the CSR rewrite "
                 "(ROADMAP item 2) lands",
        ),
    )


def _trace_flips():
    """Single-field program variations for the JXL004 cache-key-hygiene
    check; ``key_differs`` comes from :func:`wired_cache_key` itself,
    so the manifest cannot drift from the real runner key."""
    import dataclasses

    from tpudes.analysis.jaxpr.spec import FlipSpec

    base = _trace_prog()

    def flip(**over):
        prog = dataclasses.replace(base, **over)
        return FlipSpec(
            build=lambda p=prog: _trace_entries(p),
            key_differs=wired_cache_key(prog) != wired_cache_key(base),
        )

    return {
        # live components: each must change some traced program
        "jitter_slots": flip(jitter_slots=0),
        # TpudesObs: the FlowMonitor columns/ring join the carry — a
        # different executable, keyed (run_wired appends obs to ck)
        "obs": FlipSpec(
            build=lambda: _trace_entries(base, obs=True),
            key_differs=True,
        ),
        "service_slots": flip(
            service_slots=np.asarray([2, 2, 1], np.int32)
        ),
        "period_slots": flip(
            period_slots=np.asarray([7, 9], np.int32)
        ),
        # excluded-by-design fields: each must leave every trace
        # identical (slot_s/link_owner were the JXL004-found dead
        # components; n_slots is the traced-horizon contract)
        "slot_s": flip(slot_s=0.5),
        "link_owner": flip(
            link_owner=np.asarray([0, 1, 1], np.int32)
        ),
        "n_slots": flip(n_slots=80),
    }


def trace_manifest():
    """Per-engine trace manifest (see :mod:`tpudes.analysis.jaxpr`):
    the no-gather contract is armed — the step kernel must stay one-hot
    masked-reduction forms only."""
    from tpudes.analysis.jaxpr.spec import TraceManifest, TraceVariant

    return TraceManifest(
        engine="wired",
        path="tpudes/parallel/wired.py",
        no_gather=True,
        variants=lambda: [
            TraceVariant(
                "base", lambda: _trace_entries(_trace_prog())
            ),
            # the TpudesObs program (FlowMonitor columns + packet ring)
            # joins the lint surface: its ring dynamic_update_slice
            # must pass the registered SparseSite contract — the
            # no-gather ban is relaxed ONLY for verified contracts
            TraceVariant(
                "obs", lambda: _trace_entries(_trace_prog(), obs=True)
            ),
        ],
        flips=_trace_flips,
    )
