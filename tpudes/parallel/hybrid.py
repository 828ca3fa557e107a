"""Hybrid space×replica parallelism: device-engine PDES ranks.

ROADMAP item 4(b): the conservative granted-time-window protocol of
:mod:`tpudes.parallel.distributed` (Pelkey & Riley's engine, after
Fujimoto) with the per-rank *host event loop* replaced by a **device
window kernel**.  Each rank owns a spatial partition of a
:class:`~tpudes.parallel.wired.WiredProgram` (a contiguous set of
links) and advances all R replicas of it with
``advance(carry, ingress, t_grant)`` — the chunked-horizon carry form —
up to each window grant.  At the window edge the rank demuxes boundary
traffic out of the device egress buffers, ships it to the owning peers,
and injects what it received into the next window's ingress operands.

The protocol is bitwise the host engine's:

1. **flush phase** — every rank lands all in-flight boundary traffic
   (``MpiInterface.Flush``; on the in-process fabric, a dict move);
2. **grant phase** — candidate = next-local-event slot (a fresh device
   reduction, adjusted for just-injected arrivals) + the partition's
   lookahead; the grant is the all-reduce **min** of the candidates —
   the same pmin-shaped reduction ``mesh.lbts_grant`` runs on-device
   for the replica axis;
3. every rank advances strictly below the grant.  A rank whose
   partition never feeds a remote link reports an infinite candidate
   (its events cannot affect peers); when the grant itself reaches
   infinity no rank will ever send again, so everyone drains to the
   horizon locally and stops — together, because the grant is global.

Transports:

- ``transport="local"`` — every rank's engine lives in THIS process and
  the rounds run in lockstep.  The sequence of ``advance`` calls and
  operands is identical to the multi-process run, so results are
  bit-identical; this is the fast path the fuzz oracle pair and the
  single-rank A/B use.
- ``transport="mpi"`` — one OS process per rank via
  :func:`~tpudes.parallel.mpi.LaunchDistributed`, boundary traffic and
  grants over the ``MpiInterface`` pipes (flush/grant wire protocol
  unchanged from the host engines).  This is the scale-out path the
  weak-scaling bench measures; on TPU pods each rank process binds its
  own device.

Every window records into
:class:`tpudes.obs.distributed.DistributedTelemetry` (windows/s, grant
sizes, boundary traffic, per-phase wall time).
"""

from __future__ import annotations

import numpy as np

from tpudes.parallel.wired import (
    INF_SLOT,
    WiredProgram,
    build_wired_advance,
    build_wired_space_advance,
    packet_table,
    partition_flows,
    partition_lookahead,
    wired_cache_key,
    _wired_unpack,
)

__all__ = [
    "HybridRank",
    "SpaceLanesHybrid",
    "run_hybrid",
    "trace_manifest",
]


def _key_to_np(key) -> np.ndarray:
    """Raw uint32 key bits — the picklable form the rank wire ships
    (typed PRNG keys cannot cross a process boundary as-is)."""
    import jax

    if hasattr(key, "dtype") and jax.dtypes.issubdtype(
        key.dtype, jax.dtypes.prng_key
    ):
        return np.asarray(jax.random.key_data(key))
    return np.asarray(key)


def _key_from_np(key_np):
    """Rebuild the typed key from its raw bits (default impl — the one
    every engine front-end uses), so each rank derives bit-identical
    ``fold_in`` streams to the single-engine run."""
    import jax
    import jax.numpy as jnp

    return jax.random.wrap_key_data(jnp.asarray(key_np))


def _demux_egress(eg_hop, eg_ready, paths, pkt_flow, pkt_ids, link_owner):
    """One lane's fetched egress buffers → ``outbox[dst_rank] =
    dict(r, p, hop, ready)`` numpy payloads.  The wire speaks GLOBAL
    packet ids (``pkt_ids`` maps local rows out; None = identity);
    peers map back to their own resident rows on injection.  Shared by
    the per-rank engine and the space-lane engine so the payload shape
    can never drift between transports."""
    rs, ps = np.nonzero(eg_hop >= 0)
    outbox: dict[int, dict] = {}
    if rs.size:
        hops = eg_hop[rs, ps]
        links = paths[pkt_flow[ps], hops]
        dsts = link_owner[links]
        gp = ps if pkt_ids is None else pkt_ids[ps]
        for dst in np.unique(dsts):
            m = dsts == dst
            outbox[int(dst)] = dict(
                r=rs[m].astype(np.int32),
                p=gp[m].astype(np.int32),
                hop=hops[m].astype(np.int32),
                ready=eg_ready[rs[m], ps[m]].astype(np.int32),
            )
    return outbox


def _inject_inbox(ing_hop, ing_ready, inbox, g2l, who: str) -> None:
    """Write the received boundary payloads into one lane's ingress
    operands in place (``g2l`` maps global packet id → resident row;
    None = identity).  A packet outside the resident flow set means the
    partition maps disagree — fail loudly."""
    for payload in inbox:
        lp = payload["p"] if g2l is None else g2l[payload["p"]]
        if (lp < 0).any():
            raise RuntimeError(
                f"peer injected a packet outside {who}'s resident "
                "flow set — partition maps disagree"
            )
        ing_hop[payload["r"], lp] = payload["hop"]
        ing_ready[payload["r"], lp] = payload["ready"]


def _scatter_results(deliver, served, pkt_ids, owned_mask, n_total_pkts,
                     n_links):
    """One lane's (R, P_loc) deliver / (R, Lo) served arrays scattered
    back to GLOBAL packet/link ids (-1 / 0 elsewhere) for the
    cross-rank merge."""
    if pkt_ids is not None:
        full = np.full((deliver.shape[0], n_total_pkts), -1, np.int32)
        full[:, pkt_ids] = deliver
        deliver = full
    g_served = np.zeros((served.shape[0], n_links), np.int32)
    g_served[:, np.nonzero(owned_mask)[0]] = served
    return deliver, g_served


class HybridRank:
    """One PDES rank: a device engine over its partition of the wired
    program, plus the host-side demux/inject glue.  The window drivers
    (local lockstep or MPI rank loop) call, per round:
    ``poll()`` → exchange → ``candidate()`` → grant → ``window()``."""

    def __init__(self, prog: WiredProgram, key, replicas: int, rank: int,
                 size: int):
        import jax
        import jax.numpy as jnp

        from tpudes.obs.device import CompileTelemetry
        from tpudes.parallel.runtime import RUNTIME, bucket_replicas, donate_argnums

        self.prog = prog
        self.rank = int(rank)
        self.size = int(size)
        owner = np.asarray(prog.link_owner)
        if self.size > 1 and owner.max() >= self.size:
            raise ValueError(
                f"link_owner names rank {int(owner.max())} but only "
                f"{self.size} ranks are launched"
            )
        self.owned = owner == self.rank if self.size > 1 else owner >= 0
        # validates every boundary link's service+delay > 0, naming the
        # offending link — a zero lookahead would freeze the grant
        self.lookahead = (
            partition_lookahead(prog, self.rank) if self.size > 1 else INF_SLOT
        )
        # flow-granular resident set: this rank's kernel carries only
        # the flows that ever touch its links, so per-rank state stays
        # fixed as more ranks (and more total traffic) are added — the
        # weak-scaling property the bench measures
        if self.size > 1:
            sub, self.flow_ids, self.pkt_ids = partition_flows(
                prog, self.rank
            )
        else:
            sub = prog
            self.flow_ids = np.arange(prog.n_flows, dtype=np.int32)
            self.pkt_ids = None  # identity
        self.sub = sub
        self.pkt_flow, _, _ = packet_table(sub)
        self.paths = np.asarray(sub.paths)
        self.link_owner = owner
        self.r_pad = bucket_replicas(replicas, None)
        self.replicas = int(replicas)
        self.t_now = 0
        self.windows = 0
        # global packet id -> local row (for ingress injection)
        n_total = int(np.asarray(prog.n_pkts).sum())
        if self.pkt_ids is not None:
            self._g2l = np.full(n_total, -1, np.int32)
            self._g2l[self.pkt_ids] = np.arange(
                self.pkt_ids.size, dtype=np.int32
            )
        else:
            self._g2l = None
        self.n_total_pkts = n_total

        # wired_cache_key drops n_slots/slot_s/link_owner (the latter
        # two were JXL004-found dead components — this rank's served
        # set is keyed by the explicit owned mask below, not by the
        # global ownership metadata)
        ck = wired_cache_key(sub) + (
            self.r_pad, self.owned.tobytes(), self.flow_ids.tobytes(),
        )

        def build():
            init_state, advance = build_wired_advance(
                sub, self.r_pad, owned=self.owned, flow_ids=self.flow_ids
            )
            return init_state, jax.jit(
                advance, donate_argnums=donate_argnums(0)
            )

        (init_state, fn), compiling = RUNTIME.runner("wired_hybrid", ck, build)
        self._fn = fn
        self._jnp = jnp
        carry = init_state(_key_from_np(_key_to_np(key)))
        P = carry["hop"].shape[1]
        self._no_ing = np.full((self.r_pad, P), -1, np.int32)
        # priming advance to t=0: computes the first next_event without
        # serving anything (and compiles the one window executable)
        with CompileTelemetry.timed("wired_hybrid", compiling):
            self.carry, self._metrics = fn(
                carry, jnp.asarray(self._no_ing), jnp.asarray(self._no_ing),
                jnp.int32(0),
            )
            RUNTIME.record_launch("wired_hybrid")
            if compiling:
                jax.block_until_ready(self.carry)

    # --- window-edge protocol --------------------------------------------

    def poll(self):
        """Fetch this window's boundary egress + next-event reduction
        from the device; returns ``(outbox, next_event)`` with
        ``outbox[dst_rank] = dict(r, p, hop, ready)`` numpy payloads."""
        import jax

        eg_hop, eg_ready, next_event = jax.device_get(
            (self.carry["eg_hop"], self.carry["eg_ready"],
             self._metrics["next_event"])
        )
        outbox = _demux_egress(
            eg_hop, eg_ready, self.paths, self.pkt_flow, self.pkt_ids,
            self.link_owner,
        )
        return outbox, int(next_event)

    def candidate(self, next_event: int, inbox: list) -> int:
        """Conservative grant candidate AFTER the flush landed: the
        earliest slot this rank might act (local next event or a
        just-received arrival) plus its sender-side lookahead."""
        c = next_event
        for payload in inbox:
            if payload["ready"].size:
                c = min(c, int(payload["ready"].min()))
        if c >= INF_SLOT or self.lookahead >= INF_SLOT:
            return INF_SLOT
        return min(c + self.lookahead, INF_SLOT)

    def window(self, inbox: list, t_grant: int) -> None:
        """Inject the received boundary traffic and advance the device
        partition to ``t_grant`` (clipped to the horizon)."""
        from tpudes.parallel.runtime import RUNTIME

        jnp = self._jnp
        ing_hop = self._no_ing
        ing_ready = self._no_ing
        if inbox and any(p["p"].size for p in inbox):
            ing_hop = self._no_ing.copy()
            ing_ready = self._no_ing.copy()
            _inject_inbox(
                ing_hop, ing_ready, inbox, self._g2l,
                f"rank {self.rank}",
            )
        g = min(int(t_grant), self.prog.n_slots)
        self.carry, self._metrics = self._fn(
            self.carry, jnp.asarray(ing_hop), jnp.asarray(ing_ready),
            jnp.int32(g),
        )
        RUNTIME.record_launch("wired_hybrid")
        self.t_now = g
        self.windows += 1

    def results(self) -> dict:
        """Fetch this rank's partition outcome, scattered back to
        GLOBAL packet ids (rows for packets whose delivering link it
        owns; -1 elsewhere)."""
        import jax

        host = jax.device_get(
            dict(deliver=self.carry["deliver"], served=self.carry["served"])
        )
        deliver, served = _scatter_results(
            host["deliver"], host["served"], self.pkt_ids, self.owned,
            self.n_total_pkts, self.prog.n_links,
        )
        return dict(deliver=deliver, served=served)


class SpaceLanesHybrid:
    """All K ranks as vector lanes of ONE device kernel
    (:func:`build_wired_space_advance`) driven by the same window
    protocol: one shared slot clock, per-lane egress demuxed at the
    window edge, the grant the min over per-lane candidates.  The
    single-host form of the hybrid PDES — per-window cost is one
    dispatch + one D2H regardless of K, so aggregate throughput scales
    with the rank count (the ``hybrid_weak_scaling`` bench row)."""

    def __init__(self, prog: WiredProgram, key, replicas: int):
        import jax

        from tpudes.obs.device import CompileTelemetry
        from tpudes.parallel.runtime import (
            RUNTIME,
            bucket_replicas,
            donate_argnums,
        )

        self.prog = prog
        self.size = prog.n_ranks
        self.replicas = int(replicas)
        self.r_pad = bucket_replicas(replicas, None)
        self.link_owner = np.asarray(prog.link_owner)
        self.t_now = 0
        self.windows = 0

        # keep_owner=True: unlike the per-rank engines, the space
        # kernel derives its whole lane structure from the ownership
        # map (n_slots/slot_s still excluded — traced bound /
        # reporting-only scale)
        ck = wired_cache_key(prog, keep_owner=True) + (self.r_pad, "space")
        r_pad, size = self.r_pad, self.size

        def build():
            # EVERYTHING derivable without the key lives in this cached
            # closure: repeat launches of the same program (the serving
            # / bench steady state) pay zero host-side rebuild cost
            init_state, advance, parts = build_wired_space_advance(
                prog, r_pad
            )
            n_total = int(np.asarray(prog.n_pkts).sum())
            tables = [packet_table(sub) for sub, _, _ in parts]
            pkt_flow = [t[0] for t in tables]
            paths = [np.asarray(sub.paths) for sub, _, _ in parts]
            pkt_ids = [p[2] for p in parts]
            g2l = []
            for ids in pkt_ids:
                m = np.full(n_total, -1, np.int32)
                m[ids] = np.arange(ids.size, dtype=np.int32)
                g2l.append(m)
            lookaheads = [
                partition_lookahead(prog, r) if size > 1 else INF_SLOT
                for r in range(size)
            ]
            owner = np.asarray(prog.link_owner)
            Lo = int((owner == 0).sum())
            P = int(pkt_flow[0].shape[0])
            # the jitter-free initial carry is key-independent: numpy
            # templates (+ the per-lane first-event mins) let engine
            # construction skip both the device init_state chain and
            # the priming advance dispatch entirely
            template = first_events = None
            if prog.jitter_slots == 0:
                births = np.stack(
                    [np.broadcast_to(t[1], (r_pad, P)) for t in tables]
                ).astype(np.int32)
                # lane-major BY DESIGN (rank axis leads, replicas
                # second) — matches build_wired_space_advance's layout
                template = dict(
                    t=np.int32(0),
                    hop=np.zeros((size, r_pad, P), np.int32),  # tpudes: ignore[SHP001]
                    ready=births,
                    free=np.zeros((size, r_pad, Lo), np.int32),  # tpudes: ignore[SHP001]
                    deliver=np.full((size, r_pad, P), -1, np.int32),  # tpudes: ignore[SHP001]
                    eg_hop=np.full((size, r_pad, P), -1, np.int32),  # tpudes: ignore[SHP001]
                    eg_ready=np.full((size, r_pad, P), -1, np.int32),  # tpudes: ignore[SHP001]
                    served=np.zeros((size, r_pad, Lo), np.int32),  # tpudes: ignore[SHP001]
                )
                first_events = []
                for k in range(size):
                    owned0 = owner[paths[k][pkt_flow[k], 0]] == k
                    first_events.append(
                        int(tables[k][1][owned0].min()) if owned0.any()
                        else INF_SLOT
                    )
            no_ing = np.full((size, r_pad, P), -1, np.int32)  # tpudes: ignore[SHP001]
            static = dict(
                n_total=n_total, pkt_flow=pkt_flow, paths=paths,
                pkt_ids=pkt_ids, g2l=g2l, lookaheads=lookaheads,
                template=template, first_events=first_events,
                no_ing=no_ing, no_ing_dev=None,
            )
            return (
                init_state,
                jax.jit(advance, donate_argnums=donate_argnums(0)),
                parts,
                static,
            )

        (init_state, fn, parts, static), compiling = RUNTIME.runner(
            "wired_space", ck, build
        )
        self._fn = fn
        self.parts = parts
        self.n_total_pkts = static["n_total"]
        self.lookaheads = static["lookaheads"]
        self._pkt_flow = static["pkt_flow"]
        self._paths = static["paths"]
        self._pkt_ids = static["pkt_ids"]
        self._g2l = static["g2l"]
        self._no_ing = static["no_ing"]
        if static["no_ing_dev"] is None:
            # one device-resident copy of the (usually reused) empty
            # ingress operands — windows without boundary arrivals skip
            # the per-call H2D upload
            static["no_ing_dev"] = self._jnp(self._no_ing)
        self._no_ing_dev = static["no_ing_dev"]

        if static["template"] is not None and not compiling:
            # fast path: key-independent start state — no device init
            # chain, no priming dispatch (the first next_event is the
            # host-computed per-lane first birth; egress starts empty)
            self.carry = {
                k: self._jnp(v) for k, v in static["template"].items()
            }
            self._metrics = dict(
                next_event=np.asarray(static["first_events"], np.int32)
            )
        else:
            if static["template"] is not None:
                carry = {
                    k: self._jnp(v) for k, v in static["template"].items()
                }
            else:
                carry = init_state(_key_from_np(_key_to_np(key)))
            with CompileTelemetry.timed("wired_space", compiling):
                # priming advance to t=0: computes the first next_event
                # without serving anything (and compiles the window
                # executable)
                self.carry, self._metrics = fn(
                    carry, self._no_ing_dev, self._no_ing_dev,
                    self._i32(0),
                )
                RUNTIME.record_launch("wired_space")
                if compiling:
                    jax.block_until_ready(self.carry)

    @staticmethod
    def _jnp(x):
        import jax.numpy as jnp

        return jnp.asarray(x)

    @staticmethod
    def _i32(x):
        import jax.numpy as jnp

        return jnp.int32(x)

    def poll(self):
        """One D2H for every lane: ``(outboxes, next_events)`` with
        ``outboxes[src_rank][dst_rank] = payload``."""
        import jax

        eg_hop, eg_ready, next_events = jax.device_get(
            (self.carry["eg_hop"], self.carry["eg_ready"],
             self._metrics["next_event"])
        )
        outboxes: list[dict[int, dict]] = []
        for k in range(self.size):
            outboxes.append(_demux_egress(
                eg_hop[k], eg_ready[k], self._paths[k],
                self._pkt_flow[k], self._pkt_ids[k], self.link_owner,
            ))
        return outboxes, [int(x) for x in next_events]

    def candidates(self, next_events: list, inboxes: list) -> list:
        out = []
        for k in range(self.size):
            c = next_events[k]
            for payload in inboxes[k]:
                if payload["ready"].size:
                    c = min(c, int(payload["ready"].min()))
            out.append(
                INF_SLOT
                if c >= INF_SLOT or self.lookaheads[k] >= INF_SLOT
                else min(c + self.lookaheads[k], INF_SLOT)
            )
        return out

    def window(self, inboxes: list, t_grant: int) -> None:
        """Inject every lane's received boundary traffic and advance
        ALL lanes to the grant in one device call."""
        from tpudes.parallel.runtime import RUNTIME

        # windows without boundary arrivals reuse the device-resident
        # empty ingress operands (no per-call H2D upload)
        ing_hop = self._no_ing_dev
        ing_ready = self._no_ing_dev
        if any(p["p"].size for inbox in inboxes for p in inbox):
            ing_hop_np = self._no_ing.copy()
            ing_ready_np = self._no_ing.copy()
            for k, inbox in enumerate(inboxes):
                _inject_inbox(
                    ing_hop_np[k], ing_ready_np[k], inbox, self._g2l[k],
                    f"lane {k}",
                )
            ing_hop = self._jnp(ing_hop_np)
            ing_ready = self._jnp(ing_ready_np)
        g = min(int(t_grant), self.prog.n_slots)
        self.carry, self._metrics = self._fn(
            self.carry, ing_hop, ing_ready, self._i32(g),
        )
        RUNTIME.record_launch("wired_space")
        self.t_now = g
        self.windows += 1

    def results(self) -> list:
        """Per-rank outputs in the ``_run_local`` shape (deliver/served
        scattered back to global ids) for the shared cross-rank merge."""
        import jax

        host = jax.device_get(
            dict(deliver=self.carry["deliver"], served=self.carry["served"])
        )
        outs = []
        for k in range(self.size):
            deliver, served = _scatter_results(
                host["deliver"][k], host["served"][k], self._pkt_ids[k],
                self.link_owner == k, self.n_total_pkts,
                self.prog.n_links,
            )
            outs.append(dict(
                deliver=deliver, served=served, windows=self.windows,
            ))
        return outs


def _run_batched(prog: WiredProgram, key, replicas: int, size: int,
                 window_slots: int | None = None) -> list:
    """Window driver for the space-lane engine — the same lockstep
    rounds as :func:`_run_local`, with all lanes advanced by one
    device call per window."""
    from tpudes.obs.distributed import DistributedTelemetry, wall_now

    if size != prog.n_ranks:
        raise ValueError(
            f"transport='batched' runs the program's own partitioning "
            f"({prog.n_ranks} ranks); got ranks={size}"
        )
    eng = SpaceLanesHybrid(prog, key, replicas)
    while True:
        t0 = wall_now()
        outboxes, next_events = eng.poll()
        t1 = wall_now()
        inboxes: list[list] = [[] for _ in range(size)]
        for outbox in outboxes:
            for dst, payload in outbox.items():
                inboxes[dst].append(payload)
        cands = eng.candidates(next_events, inboxes)
        grant = min(cands)
        t2 = wall_now()
        closing = grant >= INF_SLOT
        g = prog.n_slots if closing else min(grant, prog.n_slots)
        g = _bound_grant(g, eng.t_now, window_slots)
        t_prev = eng.t_now
        eng.window(inboxes, g)
        t3 = wall_now()
        for k in range(size):
            DistributedTelemetry.record_window(
                k,
                grant_slots=max(0, eng.t_now - t_prev),
                tx_pkts=sum(p["p"].size for p in outboxes[k].values()),
                rx_pkts=sum(p["p"].size for p in inboxes[k]),
                poll_wall_s=(t1 - t0) if k == 0 else 0.0,
                flush_wall_s=0.0,
                grant_wall_s=(t2 - t1) if k == 0 else 0.0,
                advance_wall_s=(t3 - t2) if k == 0 else 0.0,
            )
        if eng.t_now >= prog.n_slots:
            return eng.results()


def _bound_grant(g: int, t_now: int, window_slots: int | None) -> int:
    """Clamp a granted advance to ``window_slots`` past the current
    clock — the bounded-window knob of conservative PDES engines.  A
    bounded grant changes the window SCHEDULE, never the results (the
    windowed kernel is grant-schedule-indifferent, the run_wired
    ``window_slots`` contract); the weak-scaling bench uses it to run
    every rank count under the identical window cadence, so the rows
    isolate rank-lane cost from windowing cost.  Deterministic across
    ranks: every rank clamps the same global grant at the same clock."""
    if window_slots:
        return min(g, t_now + int(window_slots))
    return g


def _drive_rank(eng: HybridRank, flush, grant_reduce,
                window_slots: int | None = None) -> None:
    """The per-rank window loop shared by both transports.  ``flush``
    is phase 1 (outbox in, inbox out — all in-flight traffic lands);
    ``grant_reduce`` is phase 2 (the pmin-shaped candidate reduction)."""
    from tpudes.obs.distributed import DistributedTelemetry, wall_now

    prog = eng.prog
    while True:
        t0 = wall_now()
        outbox, next_event = eng.poll()
        tx = sum(p["p"].size for p in outbox.values())
        t1 = wall_now()
        inbox = flush(outbox)
        rx = sum(p["p"].size for p in inbox)
        t2 = wall_now()
        cand = eng.candidate(next_event, inbox)
        grant = grant_reduce(cand)
        t3 = wall_now()
        closing = grant >= INF_SLOT
        g = prog.n_slots if closing else min(grant, prog.n_slots)
        g = _bound_grant(g, eng.t_now, window_slots)
        t_prev = eng.t_now
        eng.window(inbox, g)
        t4 = wall_now()
        DistributedTelemetry.record_window(
            eng.rank,
            grant_slots=max(0, eng.t_now - t_prev),
            tx_pkts=int(tx),
            rx_pkts=int(rx),
            poll_wall_s=t1 - t0,
            flush_wall_s=t2 - t1,
            grant_wall_s=t3 - t2,
            advance_wall_s=t4 - t3,
        )
        if eng.t_now >= prog.n_slots:
            # the grant is a global reduction and the bound is a pure
            # function of the shared clock, so every rank observes the
            # same closing condition on the same round — nobody is
            # left blocking in a collective
            return


def _run_local(prog: WiredProgram, key, replicas: int, size: int,
               window_slots: int | None = None) -> list:
    """All ranks in THIS process, rounds in lockstep — the identical
    sequence of ``advance`` calls the multi-process fabric issues, so
    results are bit-identical to ``transport="mpi"``."""
    from tpudes.obs.distributed import DistributedTelemetry, wall_now

    engines = [HybridRank(prog, key, replicas, r, size) for r in range(size)]
    live = True
    while live:
        polled = [e.poll() for e in engines]
        inboxes: list[list] = [[] for _ in range(size)]
        for outbox, _ in polled:
            for dst, payload in outbox.items():
                inboxes[dst].append(payload)
        cands = [
            e.candidate(nx, inboxes[e.rank])
            for e, (_, nx) in zip(engines, polled)
        ]
        grant = min(cands)
        closing = grant >= INF_SLOT
        for e, (outbox, _) in zip(engines, polled):
            t0 = wall_now()
            t_prev = e.t_now
            g = prog.n_slots if closing else min(grant, prog.n_slots)
            g = _bound_grant(g, e.t_now, window_slots)
            e.window(inboxes[e.rank], g)
            DistributedTelemetry.record_window(
                e.rank,
                grant_slots=max(0, e.t_now - t_prev),
                tx_pkts=sum(p["p"].size for p in outbox.values()),
                rx_pkts=sum(p["p"].size for p in inboxes[e.rank]),
                poll_wall_s=0.0, flush_wall_s=0.0, grant_wall_s=0.0,
                advance_wall_s=wall_now() - t0,
            )
        if engines[0].t_now >= prog.n_slots:
            live = False
    return [e.results() | {"windows": e.windows} for e in engines]


def _pin_rank_cpu(rank: int) -> None:
    """Pin this rank process to one core (round-robin) BEFORE jax
    creates its CPU client: the window kernel's per-step work is far
    too small for intra-op threading to pay (measured slightly
    negative), while N unpinned rank processes each spawning a
    full-size XLA thread pool oversubscribe the box — the main
    contention source the weak-scaling bench would otherwise measure.
    ``TPUDES_HYBRID_PIN=0`` disables."""
    import os

    if os.environ.get("TPUDES_HYBRID_PIN", "1") == "0":
        return
    if not hasattr(os, "sched_setaffinity"):  # pragma: no cover - non-linux
        return
    ncpu = os.cpu_count() or 1
    try:
        os.sched_setaffinity(0, {rank % ncpu})
    except OSError:  # pragma: no cover - restricted container
        pass


def _hybrid_rank_main(rank: int, size: int, prog: WiredProgram, key_np,
                      replicas: int, window_slots: int | None = None):
    """Entry point of one spawned rank process (``transport="mpi"``)."""
    _pin_rank_cpu(rank)

    from tpudes.obs.distributed import DistributedTelemetry, wall_now
    from tpudes.parallel.mpi import MpiInterface

    DistributedTelemetry.reset()
    eng = HybridRank(prog, key_np, replicas, rank, size)
    if eng.lookahead < INF_SLOT:
        MpiInterface.RegisterLookahead(
            eng.lookahead, source=f"hybrid partition of rank {rank}"
        )

    def flush(outbox):
        inbox: list = []
        for dst, payload in outbox.items():
            # boundary traffic rides the unchanged MpiInterface data
            # plane; rx_ts is the earliest contained arrival slot
            MpiInterface.SendPacket(
                dst, int(payload["ready"].min()), 0, 0, payload
            )
        MpiInterface.Flush(
            lambda rx_ts, node_id, if_index, payload: inbox.append(payload)
        )
        return inbox

    import jax

    t0 = wall_now()
    _drive_rank(eng, flush, MpiInterface.AllReduceMin, window_slots)
    jax.block_until_ready(eng.carry)  # async dispatch must not leak
    wall = wall_now() - t0     # out of the measured loop wall
    DistributedTelemetry.record_transport(
        rank, MpiInterface._tx_count, MpiInterface._rx_count
    )
    out = eng.results()
    return dict(
        deliver=out["deliver"],
        served=out["served"],
        windows=eng.windows,
        loop_wall_s=wall,
        transport_tx=MpiInterface._tx_count,
        transport_rx=MpiInterface._rx_count,
        telemetry=DistributedTelemetry.snapshot(),
    )


def run_hybrid(
    prog: WiredProgram,
    key,
    replicas: int = 1,
    *,
    ranks: int | None = None,
    transport: str = "local",
    window_slots: int | None = None,
    timeout_s: float = 300.0,
):
    """Run the wired program space-partitioned over ``ranks`` PDES
    ranks (default: the partition count ``prog.link_owner`` declares),
    each rank a device engine advancing R replicas of its links by
    granted windows.  Results are merged across partitions and are
    **timestamp-exact**: equal to ``run_wired`` (single device engine)
    and to ``run_wired_host`` (sequential host DES) — the pinned
    contract of tests/test_hybrid.py.

    ``transport="local"`` drives every rank in-process (lockstep
    rounds, bit-identical operand sequence); ``transport="mpi"``
    spawns one process per rank over :func:`LaunchDistributed`.
    ``window_slots`` bounds every grant (see :func:`_bound_grant`):
    results are identical under any bound, only the window schedule —
    and the telemetry cadence — changes.
    """
    size = int(ranks) if ranks is not None else prog.n_ranks
    key_np = _key_to_np(key)
    if transport == "local":
        rank_outs = _run_local(prog, key_np, replicas, size, window_slots)
    elif transport == "batched":
        rank_outs = _run_batched(prog, key_np, replicas, size, window_slots)
    elif transport == "mpi":
        from tpudes.obs.distributed import DistributedTelemetry, wall_now
        from tpudes.parallel.mpi import LaunchDistributed
        from tpudes.parallel.procmesh import require_one_process_per_chip

        require_one_process_per_chip('run_hybrid(transport="mpi")', size)
        rank_outs = LaunchDistributed(
            _hybrid_rank_main, size,
            args=(prog, key_np, replicas, window_slots),
            timeout_s=timeout_s,
        )
        for out in rank_outs:
            DistributedTelemetry.absorb(out.pop("telemetry"))
    else:
        raise ValueError(f"unknown transport {transport!r}")

    deliver = rank_outs[0]["deliver"]
    served = rank_outs[0]["served"]
    for out in rank_outs[1:]:
        deliver = np.maximum(deliver, out["deliver"])
        served = served + out["served"]
    result = _wired_unpack(
        dict(deliver=deliver, served=served), prog, replicas
    )
    result["windows"] = int(rank_outs[0]["windows"])
    result["ranks"] = size
    if "loop_wall_s" in rank_outs[0]:
        result["loop_wall_s"] = max(o["loop_wall_s"] for o in rank_outs)
    return result


# --- trace manifest (tpudes.analysis.jaxpr) --------------------------------

#: canonical tiny replica count for the abstract traces
_TRACE_R = 2


def _trace_prog(**over):
    """Canonical tiny 2-rank chain — uniform partitions so the
    space-lanes kernel lifts it."""
    import dataclasses

    from tpudes.parallel.wired import wired_weak_chain

    prog = wired_weak_chain(
        2, links_per_rank=2, flows_per_rank=1, n_slots=60,
        boundary_delay=8,
    )
    return dataclasses.replace(prog, **over) if over else prog


def _trace_entries(prog):
    """The space-lanes window kernel exactly as :class:`SpaceLanesHybrid`
    jits it, with concrete tiny operands."""
    import jax
    import jax.numpy as jnp

    from tpudes.analysis.jaxpr.spec import TraceEntry
    from tpudes.parallel.wired import build_wired_space_advance

    init_state, advance, parts = build_wired_space_advance(
        prog, _TRACE_R
    )
    key = jax.random.PRNGKey(0)
    carry = init_state(key)
    K, R, P = carry["hop"].shape
    no_ing = jnp.full((K, R, P), -1, jnp.int32)  # tpudes: ignore[SHP001]
    return [
        TraceEntry("init", init_state, (key,), kernel=False),
        TraceEntry(
            "advance",
            advance,
            (carry, no_ing, no_ing, jnp.int32(8)),
            donate=(0,),
            carry=(0,),
            traced={"ing_hop": 1, "ing_ready": 2, "t_grant": 3},
            scale_axes=_scale_axes(),
        ),
    ]


def _scale_axes():
    """JXL007 scale axes: the lane step body shares the wired engine's
    dense one-hot tables, so the joint per-rank topology axis is
    quadratic and declared at budget 1.0 — it FIRES by design, the
    baselined hybrid half of the ROADMAP item-2 worklist."""
    from tpudes.analysis.jaxpr.spec import ScaleAxis
    from tpudes.parallel.wired import wired_weak_chain

    def at(v):
        prog = wired_weak_chain(
            2, links_per_rank=int(v), flows_per_rank=int(v),
            n_slots=60, boundary_delay=8,
        )
        entries = _trace_entries(prog)
        entry = entries[1]
        # strip the nested axis declarations the re-entrant build
        # added — axis traces must not recurse
        import dataclasses

        return dataclasses.replace(entry, scale_axes=())

    return (
        ScaleAxis(
            "n_nodes",
            at,
            points=(2, 4, 8),
            mem_budget=1.0,
            nodes_per_unit=2.0,  # two ranks: 2v links per axis unit
            note="joint links+flows per-rank axis: lane tables are "
                 "O(L*P) like the wired engine — fires until the CSR "
                 "rewrite (ROADMAP item 2) lands",
        ),
    )


def _trace_flips():
    import dataclasses

    from tpudes.analysis.jaxpr.spec import FlipSpec

    base = _trace_prog()

    def flip(**over):
        prog = dataclasses.replace(base, **over)
        return FlipSpec(
            build=lambda p=prog: _trace_entries(p),
            key_differs=(
                wired_cache_key(prog, keep_owner=True)
                != wired_cache_key(base, keep_owner=True)
            ),
        )

    L = int(base.n_links)
    return {
        # link_owner is LIVE here (it defines the lane structure) —
        # flip to one rank owning everything; key and trace must both
        # change
        "link_owner": flip(
            link_owner=np.zeros(L, np.int32)
        ),
        # excluded-by-design fields must leave every trace identical
        "slot_s": flip(slot_s=0.5),
        "n_slots": flip(n_slots=120),
    }


def trace_manifest():
    """Per-engine trace manifest for the hybrid space-lanes window
    kernel (see :mod:`tpudes.analysis.jaxpr`); the wired no-gather
    contract applies to the lane step body too."""
    from tpudes.analysis.jaxpr.spec import TraceManifest, TraceVariant

    return TraceManifest(
        engine="wired_space",
        path="tpudes/parallel/hybrid.py",
        no_gather=True,
        variants=lambda: [
            TraceVariant(
                "base", lambda: _trace_entries(_trace_prog())
            )
        ],
        flips=_trace_flips,
    )
