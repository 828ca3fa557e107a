"""Replica-axis execution of Internet-scale sparse traffic (config #5).

The BASELINE #5 workload — a BRITE-style 10k-node AS topology with
sparse CBR traffic × 1024 Monte-Carlo replicas — lowered TPU-first:

- **SPF on device**: delay-weighted Bellman–Ford as K rounds of
  edge-parallel scatter-min over a (D, N) distance table (D = distinct
  destinations).  This replaces the host GlobalRouteManager Dijkstra
  (tpudes/models/internet/global_routing.py), which stays the oracle.
- **Next hops** from one more scatter pass (argmin over incident
  edges), then each flow's path is unrolled with a bounded-hop walk —
  all (F, H) link indices static across replicas.
- **Replica axis = traffic uncertainty**: flow endpoints are fixed per
  run (RngRun-seeded, as upstream's RngRun sweeps); per-replica draws
  scale each flow's offered rate.  Link loads accumulate by
  max(hops) scatter-adds of the (R, F) rate matrix (the longest path,
  not the walk's bound H).
- **Flow-level (fluid) outcome model**, the documented deviation from
  the packet oracle: per-link delivery min(1, capacity/load) compounds
  along the path; queueing delay is M/M/1 ρ/(1-ρ) per transited link.
  Under the sparse-traffic regime (ρ ≪ 1) this coincides with the
  packet path — tests pin parity there and on overload direction.

Scalar oracle: the same scenario at reduced n with real UDP sockets +
Ipv4GlobalRouting (tests/test_as_flows.py).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from tpudes.fuzz.envelope import FuzzEnvelope

INF = jnp.float32(1e30)

#: the documented-faithful fuzz region (see :mod:`tpudes.fuzz`): BA
#: graphs are connected by construction (every new node attaches to m
#: existing ones), CBR loads stay in the sparse regime where the fluid
#: outcome model is documented to coincide with the packet oracle
FUZZ_ENVELOPE = FuzzEnvelope(
    engine="as_flows",
    axes={
        "n_nodes": ("int", 24, 72),
        "n_flows": ("int", 2, 6),
        "flow_kbps": ("choice", (200.0, 400.0, 800.0)),
        "pkt_bytes": ("choice", (256, 512)),
        "topo_seed": ("int", 1, 999),
        "sim_ms": ("int", 1000, 2500),
        "replicas": ("int", 2, 9),
        "chunk_divisor": ("choice", (2,)),
        "key_seed": ("int", 0, 2**16),
        # ISSUE-14 traffic draws (appended): per-flow offered rates
        # scale by the drawn workload's fluid multiplier; "off" keeps
        # the constant nominal rates
        "traffic": ("choice", ("off", "cbr", "mmpp", "onoff", "trace")),
        "tr_burst": ("float", 0.1, 0.6),
        "tr_phase": ("float", 0.0, 1.0),
        # ISSUE-15 surrogate draws (appended): "ste" compiles the
        # straight-through surrogate program, whose FORWARD is pinned
        # bit-equal to the legacy engine (the surrogate_off pair)
        "surrogate": ("choice", ("off", "ste")),
    },
    floors={"replicas": 1, "n_nodes": 8, "n_flows": 1},
    doc="BRITE BA AS topology, sparse CBR flows, fluid outcome model",
)


@dataclass(frozen=True)
class AsFlowsProgram:
    """Static device program for one AS-topology traffic study."""

    n: int                      # nodes
    edges: np.ndarray           # (E, 2) undirected
    delay_s: np.ndarray         # (E,)
    rate_bps: np.ndarray        # (E,)
    src: np.ndarray             # (F,) flow source node
    dst: np.ndarray             # (F,) flow destination node
    flow_bps: np.ndarray        # (F,) nominal offered rate
    pkt_bytes: int
    sim_s: float
    max_hops: int = 32          # path-walk bound (≫ BA diameter)
    spf_rounds: int = 48        # Bellman-Ford rounds (≥ weighted diameter)
    rate_jitter: float = 0.3    # per-replica lognormal-ish rate spread
    #: "hops" matches the host Ipv4GlobalRouting (interface Metric = 1);
    #: "delay" routes on propagation delay instead
    spf_metric: str = "hops"
    #: device-resident workload (tpudes.traffic.TrafficProgram over the
    #: F flows): None = constant nominal rates (bit-identical compile).
    #: The fluid engine consumes the workload's FLUID view — each
    #: flow's offered rate scales by the model's realized/nominal
    #: ratio over the horizon (exactly 1.0 for cbr, the traffic_off
    #: anchor), computed ON DEVICE from the traced tables so model/
    #: param flips never recompile.  Only ``traffic.shape_key()``
    #: enters the runner cache key; the horizon rides as a traced
    #: operand (``sim_s`` itself stays out of the key).
    traffic: object = None
    #: smooth-surrogate config (:class:`tpudes.diff.Surrogacy`): None =
    #: the identical legacy program (bit-equal trace, same runner —
    #: the ``surrogate_off`` contract).  With a config, the fluid
    #: delivery min-gate is temperature-smoothed (straight-through
    #: when ``ste``: hard bit-exact forward, soft backward) so
    #: ``jax.grad`` flows through the fixed point.  A CACHE-KEY
    #: component, never a traced operand — a temperature flip compiles
    #: a distinct executable, like a precision flip.
    surrogate: object = None


class UnliftableAsError(ValueError):
    """Graph/traffic shape the flow engine cannot faithfully represent."""


def lower_as_flows(sim_end_s: float) -> AsFlowsProgram:
    """Lower the live object graph: p2p links → edge arrays, UdpClient
    CBR apps → flows.  The scalar path stays authoritative for anything
    this rejects."""
    from tpudes.models.applications import UdpClient, UdpServer
    from tpudes.models.internet.ipv4 import Ipv4L3Protocol
    from tpudes.models.p2p import PointToPointNetDevice
    from tpudes.network.node import NodeList

    nodes = [NodeList.GetNode(i) for i in range(NodeList.GetNNodes())]
    addr_to_node: dict[int, int] = {}
    for i, node in enumerate(nodes):
        ipv4 = node.GetObject(Ipv4L3Protocol)
        if ipv4 is None:
            continue
        for iface in ipv4.interfaces[1:]:
            for a in iface.addresses:
                addr_to_node[a.GetLocal().addr] = i

    seen_ch: set[int] = set()
    edges, delays, rates = [], [], []
    for i, node in enumerate(nodes):
        for d in range(node.GetNDevices()):
            dev = node.GetDevice(d)
            if not isinstance(dev, PointToPointNetDevice):
                # another technology in the graph means routing may use
                # a path this engine does not model — even when the p2p
                # graph alone happens to connect the endpoints
                raise UnliftableAsError(
                    f"node {i} carries a {type(dev).__name__}; the flow "
                    "engine models pure point-to-point graphs"
                )
            ch = dev.GetChannel()
            if ch is None or id(ch) in seen_ch:
                continue
            seen_ch.add(id(ch))
            peer = ch.GetPeer(dev)
            edges.append((i, peer.GetNode().GetId()))
            delays.append(ch.GetDelay().GetSeconds())
            rates.append(float(dev.data_rate.GetBitRate()))
    if not edges:
        raise UnliftableAsError("no p2p links in the object graph")

    srcs, dsts, fbps, pkts = [], [], [], set()
    for i, node in enumerate(nodes):
        for a in range(node.GetNApplications()):
            app = node.GetApplication(a)
            if isinstance(app, UdpServer):
                continue
            if not isinstance(app, UdpClient):
                # unrecognized traffic would silently vanish from the
                # link loads — reject the graph instead
                raise UnliftableAsError(
                    f"unmodeled application {type(app).__name__} on node "
                    f"{i} (its traffic would be dropped)"
                )
            from tpudes.network.address import Ipv4Address

            dst_node = addr_to_node.get(Ipv4Address(app.remote_address).addr)
            if dst_node is None:
                raise UnliftableAsError(
                    f"UdpClient on node {i}: unknown destination"
                )
            interval = app.interval.GetSeconds()
            if interval <= 0:
                raise UnliftableAsError("UdpClient with zero interval")
            srcs.append(i)
            dsts.append(dst_node)
            fbps.append(8.0 * int(app.packet_size) / interval)
            pkts.add(int(app.packet_size))
    if not srcs:
        raise UnliftableAsError("no UdpClient CBR flows found")
    # flows must also be p2p-connected end to end (isolated islands of
    # an otherwise-pure p2p graph cannot carry the named traffic) —
    # this closed the hole that let an LTE+EPC scenario lift as its
    # p2p backhaul before the device-type rejection above existed
    from tpudes.helper.topology import component_labels

    labels = component_labels(len(nodes), edges)
    for s, d in zip(srcs, dsts):
        if labels[s] != labels[d]:
            raise UnliftableAsError(
                f"flow node{s}→node{d} is not connected by p2p links; "
                "the flow engine models the p2p graph only"
            )
    return AsFlowsProgram(
        n=len(nodes),
        edges=np.asarray(edges, np.int32),
        delay_s=np.asarray(delays),
        rate_bps=np.asarray(rates),
        src=np.asarray(srcs, np.int32),
        dst=np.asarray(dsts, np.int32),
        flow_bps=np.asarray(fbps),
        pkt_bytes=max(pkts) if pkts else 512,
        sim_s=sim_end_s,
    )


def device_spf(prog: AsFlowsProgram, mesh=None):
    """(dist, nh_edge, nh_node) for the distinct destination set.

    dist: (D, N) f32 shortest delay;  nh_edge/nh_node: (D, N) i32 —
    the directed-edge index / next node toward each destination.
    Returns (ddst, arrays): ddst maps flow → row in the tables.

    With ``mesh``, the TOPOLOGY tables themselves are sharded: the
    destination-row axis D spreads over the mesh devices (SURVEY.md
    §5.7 "shard-ready layouts"), so a 10k-node AS graph's (D, N)
    distance/next-hop state no longer replicates per device.  The
    Bellman-Ford relaxation is row-independent — zero collectives —
    and XLA inserts the gather where the flow walk reads rows.
    """
    e = np.concatenate([prog.edges, prog.edges[:, ::-1]])  # directed
    if prog.spf_metric == "hops":
        w_np = np.ones(e.shape[0], np.float32)
    else:
        w_np = np.concatenate([prog.delay_s, prog.delay_s]).astype(np.float32)
    u, v = jnp.asarray(e[:, 0]), jnp.asarray(e[:, 1])
    w = jnp.asarray(w_np)
    dsts_np, inv = np.unique(prog.dst, return_inverse=True)
    D, N = len(dsts_np), prog.n

    # pad the row axis to the mesh size so sharding never silently
    # degrades to replication (padded rows are all-INF and unread)
    D_pad = D
    if mesh is not None:
        n_dev = len(mesh.devices.flat)
        D_pad = ((D + n_dev - 1) // n_dev) * n_dev
    dist0 = jnp.full((D_pad, N), INF).at[
        jnp.arange(D), jnp.asarray(dsts_np)
    ].set(0.0)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        dist0 = jax.lax.with_sharding_constraint(
            dist0, NamedSharding(mesh, P("replica", None))
        )

    def bf_round(dist, _):
        cand = dist[:, v] + w[None, :]          # relax u→v backwards
        return dist.at[:, u].min(cand), None

    dist, _ = jax.lax.scan(bf_round, dist0, None, length=prog.spf_rounds)
    # next hop: the incident directed edge minimizing w(u,v) + dist[v]
    # (tables stay at the padded row count; callers index rows < D)
    score = w[None, :] + dist[:, v]             # (D_pad, 2E)
    best = jnp.full((D_pad, N), INF).at[:, u].min(score)
    eidx = jnp.arange(e.shape[0], dtype=jnp.int32)
    BIG = jnp.int32(2**30)
    cand_idx = jnp.where(score <= best[:, u] * (1 + 1e-6), eidx[None, :], BIG)
    nh_edge = jnp.full((D_pad, N), BIG).at[:, u].min(cand_idx)
    nh_node = jnp.where(nh_edge < BIG, v[jnp.minimum(nh_edge, e.shape[0] - 1)], -1)
    return jnp.asarray(inv, jnp.int32), dist, nh_edge, nh_node


def _walk_paths(prog: AsFlowsProgram, ddst, nh_edge, nh_node):
    """(F, H) directed-edge index per hop (2E = invalid/done), (F,) hop
    counts, and (F,) arrived flags; static across replicas."""
    F = len(prog.src)
    E2 = 2 * prog.edges.shape[0]
    BIG = jnp.int32(2**30)

    def step(cur, _):
        # cur: (F,) current node, or -1 once arrived
        arrived = cur == jnp.asarray(prog.dst)
        done = arrived | (cur < 0)
        row = ddst
        edge = jnp.where(done, BIG, nh_edge[row, jnp.maximum(cur, 0)])
        nxt = jnp.where(done, -1, nh_node[row, jnp.maximum(cur, 0)])
        return nxt, jnp.where(edge < BIG, edge, E2)

    cur0 = jnp.asarray(prog.src)
    cur_end, path = jax.lax.scan(step, cur0, None, length=prog.max_hops)
    path = path.T                                # (F, H)
    hops = jnp.sum(path < E2, axis=1)
    # arrival = the walk terminated (-1) or ended ON the destination
    # (a shortest path of exactly max_hops hops still arrives)
    arrived = (cur_end == -1) | (cur_end == jnp.asarray(prog.dst))
    return path, hops, arrived


#: fluid fixed-point relaxation rounds (feed-forward paths settle the
#: ≤k-th-hop links exactly in round k)
FP_ROUNDS = 4


def relax_links(prog: AsFlowsProgram) -> int:
    """Static width W of the fluid relaxation's link table.  The (F, H)
    path table holds at most F·H distinct ids (directed links and the
    done-hop sentinel 2E), and there are 2E + 1 ids in all."""
    return min(len(prog.src) * prog.max_hops, 2 * prog.edges.shape[0] + 1)


def _link_table(prog: AsFlowsProgram, path, cap2, dly2):
    """``(path, cap, dly)`` the fluid relaxation runs over, built once
    with the paths (replica-independent).  The table holds the distinct
    links the paths use, sorted, then the done-hop sentinel 2E and its
    fill, :func:`relax_links` columns: ``path`` becomes column ids into
    it and the per-link capacity and delay are gathered to its columns.
    The sentinel's capacity is inf, so its utilisation reads 0 and it
    adds no serialisation, no queueing and (sealed,
    :func:`_fluid_seal`) no loss."""
    E2 = cap2.shape[0]
    # the F·H path entries sorted, each repeat turned into the sentinel
    # and sorted again: the distinct links ascending, then 2E.  Where
    # F·H >= 2E + 1 some entry repeats or is 2E (there are 2E links),
    # so the first 2E + 1 hold every link in use and the sentinel
    flat = jnp.sort(path.reshape(-1))
    first = jnp.concatenate([jnp.ones((1,), bool), flat[1:] != flat[:-1]])
    links = jnp.sort(jnp.where(first, flat, E2))[: relax_links(prog)]
    cap = jnp.concatenate([cap2, jnp.full((1,), jnp.inf, jnp.float32)])
    dly = jnp.concatenate([dly2, jnp.zeros((1,), jnp.float32)])
    return jnp.searchsorted(links, path), cap[links], dly[links]


def _fluid_seal(x, cap):
    """A per-link quantity with the done-hop sentinel's column, and its
    fill, 0 where ``cap`` is inf (a done hop adds no loss and no
    delay)."""
    return jnp.where(jnp.isinf(cap)[None, :], 0.0, x)


def _as_carry(prog: AsFlowsProgram, r_pad: int):
    """The relaxation's carry ``(lfrac, lg, util)``: per-link log
    delivery over the link table, per-flow log delivery, per-link
    utilisation over the link table."""
    W, F = relax_links(prog), len(prog.src)
    return (
        jnp.zeros((r_pad, W), jnp.float32),
        jnp.zeros((r_pad, F), jnp.float32),
        jnp.zeros((r_pad, W), jnp.float32),
    )


def _fluid_round(prog: AsFlowsProgram, path, n_walk, rate, cap, lfrac_link):
    """ONE fluid fixed-point round — the walk/load/delivery core shared
    by the while-loop runner (:func:`build_as_run`) and the
    differentiable scan runner (:func:`build_as_diff`), so the two can
    never drift.  It runs over the link table of :func:`_link_table`
    (``path``, ``cap``).  A link's load is the SURVIVING rate of each
    transiting flow at that hop (loss upstream attenuates load
    downstream).  ``prog.surrogate`` (None = the exact legacy
    min-gate, bit-identical trace) smooths the per-link delivery clip
    ``min(1, cap/load)`` into a softplus gate in the log domain —
    straight-through (hard bit-exact forward) when ``surrogate.ste``.

    The walk runs hops ``[0, n_walk)``: see :func:`_walk_trip`.
    """
    R, F = rate.shape

    def walk(h, c):
        lg, load = c
        e_h = path[:, h]                       # (F,)
        load = load.at[:, e_h].add(rate * jnp.exp(lg))
        lg = lg + lfrac_link[:, e_h]
        return lg, load

    # the hop walk's scatter-add: the relaxation's bulk, named apart
    # from the utilisation, log and gate below (they read under the
    # loop body's own scope)
    with jax.named_scope("tpudes.as_flows.load"):
        lg, load = jax.lax.fori_loop(
            0, n_walk, walk,
            (jnp.zeros((R, F), jnp.float32),
             jnp.zeros((R, lfrac_link.shape[1]), jnp.float32)),
        )
    # a baked capacity divisor compiles to a multiply by its
    # reciprocal; the gathered one is written so, for the same bits
    util = load * (1.0 / cap)[None, :]
    hard = _fluid_seal(
        jnp.log(jnp.minimum(1.0, 1.0 / jnp.maximum(util, 1e-9))), cap
    )
    sur = prog.surrogate
    if sur is None:
        new_lfrac = hard
    else:
        # log-domain delivery: hard is -relu(log util); the soft gate
        # is the softplus smoothing at gate_temp (dtypes pinned f32 —
        # JXL002)
        t = jnp.float32(sur.gate_temp)
        soft = _fluid_seal(
            -jax.nn.softplus(
                jnp.log(jnp.maximum(util, jnp.float32(1e-9))) / t
            )
            * t,
            cap,
        )
        new_lfrac = sur.blend(hard, soft)
    return new_lfrac, lg, util


def _fluid_delay(prog: AsFlowsProgram, path, n_walk, util, cap, dly):
    """M/M/1 queue + serialization + propagation delay accumulated
    along each flow's path, hops ``[0, n_walk)``, from the settled
    utilizations (shared by both runners, like :func:`_fluid_round`)."""
    R = util.shape[0]
    F = path.shape[0]
    with jax.named_scope("tpudes.as_flows.delay"):
        rho = jnp.minimum(util, 0.99)
        q_delay = (
            rho / (1.0 - rho) * (8.0 * prog.pkt_bytes / cap)[None, :]
        )
        serial = (8.0 * prog.pkt_bytes / cap)[None, :]
        ldel = _fluid_seal(q_delay + serial + dly[None, :], cap)

        def acc_hop(h, dl):
            return dl + ldel[:, path[:, h]]

        dl = jax.lax.fori_loop(
            0, n_walk, acc_hop, jnp.zeros((R, F), jnp.float32)
        )
    return dl


def _walk_trip(hops):
    """The hop walks' trip count: the longest path, ``max(hops)``, an
    int32 scalar.  A row of the path table is its flow's links for its
    first ``hops`` entries and the done-hop sentinel after them (a walk
    that is done stays done), so hop ``max(hops)`` and every later one
    is the sentinel in every row: a hop that scatters into the
    sentinel's column (capacity inf, utilisation ``load * 0``) and adds
    its sealed +0.0 (:func:`_fluid_seal`) to ``lg`` and the delay sum,
    which are never -0.0.  Stopping there changes no bit of any output
    or carry leaf."""
    return jnp.max(hops).astype(jnp.int32)


#: carry layout of the relaxation, part of its checkpoint fingerprint:
#: the per-link leaves are as wide as :func:`relax_links`.  A file
#: written while they were always 2E + 1 wide (no tag in its
#: fingerprint) is refused as a different study, not loaded.
_AS_CARRY_LAYOUT = "link-table"

#: result keys carrying a leading replica axis (sliced back after
#: bucket padding); hops/unreachable are per-flow statics
_AS_R_LEAD = ("goodput_bps", "delay_s", "delivered_frac", "max_util")


def _as_unpack(host: dict, replicas: int) -> dict:
    from tpudes.obs import spans

    unpack = spans.current()
    if unpack is not None and unpack.name == "result.unpack":
        # the iterations each hop walk ran (_walk_trip), of max_hops
        unpack.args["walk_hops"] = int(np.max(host["hops"]))
    return {
        k: (np.asarray(v)[:replicas] if k in _AS_R_LEAD else np.asarray(v))
        for k, v in host.items()
    }


def as_prog_key(prog: AsFlowsProgram) -> tuple:
    """Hashable identity of the AsFlowsProgram fields that shape the
    compiled relaxation (shared by the runner cache key and the serving
    coalesce key so the two can never drift).  ``prog.sim_s`` is
    deliberately ABSENT: the fluid fixed point has no time horizon (its
    cost does not scale with simulated seconds)."""
    return (
        prog.edges.tobytes(), prog.delay_s.tobytes(),
        prog.rate_bps.tobytes(), prog.src.tobytes(), prog.dst.tobytes(),
        prog.flow_bps.tobytes(), prog.pkt_bytes, prog.max_hops,
        prog.spf_rounds, prog.rate_jitter, prog.spf_metric,
        # workload SHAPE only — the model id and params are traced
        None if prog.traffic is None else prog.traffic.shape_key(),
        # the surrogate config is a cache-key component, never traced:
        # a temperature/ste flip selects different arithmetic, i.e. a
        # different executable (the precision-flag pattern)
        None if prog.surrogate is None else prog.surrogate.key(),
    )


def as_study(prog: AsFlowsProgram, key, replicas, mesh=None,
             rate_scale: float = 1.0):
    """Serving-layer study descriptor (see :mod:`tpudes.serving`): the
    offered-load multiplier is the traced sweep operand, so two AS
    load studies coalesce onto one launch whenever their topology,
    flows, key, replica count and mesh all match.  A lone study still
    launches through ``rate_scale=[x]`` (the fluid engine has no plain
    scalar-scale entry), which the sweep equality tests pin equal to
    the unswept run at scale 1."""
    from tpudes.serving.descriptor import StudyDescriptor, mesh_fingerprint

    ck = as_prog_key(prog) + (
        np.asarray(key).tobytes(), int(replicas), mesh_fingerprint(mesh),
        # workload identity by VALUE, and the horizon it averages over
        # (with traffic the realized rates depend on sim_s even though
        # the executable does not)
        None if prog.traffic is None
        else prog.traffic.param_key() + (float(prog.sim_s),),
    )

    def launch(points, block=False):
        return run_as_flows(
            prog, key, replicas=replicas, mesh=mesh,
            rate_scale=[float(v) for v in points], block=block,
        )

    def warm(n_points):
        # no horizon to shrink: the fixed point's cost is topology-
        # bound, so warming runs the real relaxation once per bucket
        run_as_flows(
            prog, key, replicas=replicas, mesh=mesh,
            rate_scale=[1.0] * n_points,
        )

    spec = None if mesh is not None else dict(
        engine="as_flows", prog=prog, key=np.asarray(key),
        replicas=replicas,
    )
    return StudyDescriptor(
        "as_flows", ck, float(rate_scale), launch, warm, spec=spec
    )


def build_as_run(prog: AsFlowsProgram, r_pad: int, n_cfg: int | None = None,
                 obs: bool = False, mesh=None):
    """The UNJITTED runner function ``run(carry, z, scale, rounds_end)``
    exactly as :func:`run_as_flows` jits it — factored out so the trace
    manifest (:func:`trace_manifest`) abstractly traces the same
    program the runner cache compiles."""
    from tpudes.parallel.runtime import scoped_while_loop

    TRAFFIC = prog.traffic is not None
    if TRAFFIC:
        from tpudes.traffic.device import avg_mult

        mult_fn = avg_mult(prog.traffic)
    cap = jnp.concatenate(
        [jnp.asarray(prog.rate_bps), jnp.asarray(prog.rate_bps)]
    ).astype(jnp.float32)
    dly = jnp.concatenate(
        [jnp.asarray(prog.delay_s), jnp.asarray(prog.delay_s)]
    ).astype(jnp.float32)
    fbps = jnp.asarray(prog.flow_bps, jnp.float32)
    F = len(prog.src)

    def topo():
        # Bellman-Ford, next hops, the path walk, the link table and the
        # hop walks' trip count: replica-independent, outside the
        # relaxation loop, under one device name
        with jax.named_scope("tpudes.as_flows.spf"):
            ddst, dist, nh_edge, nh_node = device_spf(prog, mesh)
            path, hops, arrived = _walk_paths(prog, ddst, nh_edge, nh_node)
            reached = (
                dist[ddst, jnp.asarray(prog.src)] < INF
            ) & arrived
            links = _link_table(prog, path, cap, dly)
            n_walk = _walk_trip(hops)
        return links, n_walk, hops, reached

    def relax(carry, z, scale, rounds_end, links, n_walk, reached, mult):
        # per-replica offered rates: lognormal jitter around the
        # scale-multiplied nominal (z enters sharded over the
        # mesh's replica axis — every (R, ...) array downstream
        # inherits that sharding); the workload's fluid multiplier
        # rides per flow on top
        rate = fbps[None, :] * mult[None, :] * scale * jnp.exp(
            prog.rate_jitter * z - 0.5 * prog.rate_jitter**2
        )
        rate = jnp.where(reached[None, :], rate, 0.0)
        path, cap_l, dly_l = links

        # fluid fixed point: the round/delay cores are module-level
        # (shared with the differentiable runner, see _fluid_round)
        def body(c):
            i, lf, _, _ = c
            lf2, lg2, util2 = _fluid_round(
                prog, path, n_walk, rate, cap_l, lf
            )
            return i + 1, lf2, lg2, util2

        i, lfrac, lg, util = scoped_while_loop(
            "as_flows", lambda c: c[0] < rounds_end, body, carry
        )

        dl = _fluid_delay(prog, path, n_walk, util, cap_l, dly_l)
        frac = jnp.where(reached[None, :], jnp.exp(lg), 0.0)
        outputs = dict(
            goodput_bps=rate * frac,
            delay_s=jnp.where(reached[None, :], dl, jnp.inf),
            delivered_frac=frac,
            max_util=util.max(axis=1),
        )
        # chunk summary only under TpudesObs (obs is in the cache
        # key): a disabled run compiles the pre-obs program
        metrics = dict(max_util=jnp.max(util)) if obs else {}
        return (i, lfrac, lg, util), outputs, metrics

    def run(carry, z, scale, rounds_end, tr=None, horizon_us=None):
        links, n_walk, hops, reached = topo()
        # the workload's fluid multiplier: realized/nominal offered
        # ratio over the traced horizon — config- and replica-
        # independent, computed once like the SPF tables
        mult = (
            mult_fn(tr, horizon_us) if TRAFFIC
            else jnp.ones((F,), jnp.float32)
        )
        if n_cfg is None:
            carry, outputs, metrics = relax(
                carry, z, scale, rounds_end, links, n_walk, reached, mult
            )
        else:
            # SPF + path walk are config-independent: computed once,
            # closed over by the vmapped fixed point (an unbatched trip
            # count keeps the hop walks plain loops, not masked ones)
            carry, outputs, metrics = jax.vmap(
                lambda c, s: relax(
                    c, z, s, rounds_end, links, n_walk, reached, mult
                )
            )(carry, scale)
        outputs["hops"] = hops
        outputs["unreachable"] = ~reached
        return carry, outputs, metrics

    return run


def build_as_diff(prog: AsFlowsProgram, r_pad: int):
    """The DIFFERENTIABLE AS runner (``tpudes.diff.grad_as_flows``):
    the same fluid round/delay cores as :func:`build_as_run`
    (:func:`_fluid_round` / :func:`_fluid_delay`), restructured for
    ``jax.grad``:

    - the fixed-point ``while_loop`` becomes a fixed-length
      ``lax.scan`` over :data:`FP_ROUNDS` (reverse-mode autodiff
      cannot differentiate a ``while_loop``; the legacy runner runs
      exactly FP_ROUNDS rounds, so the forward values are BIT-EQUAL —
      pinned in tests/test_diff.py);
    - the hop walks run a static ``prog.max_hops`` hops (a
      ``fori_loop`` with static bounds is a ``scan``, which reverse
      mode differentiates), where the while-loop runner stops at the
      traced longest path (:func:`_walk_trip`; the hops past it add
      +0.0, so the values are the same bits);
    - per-flow nominal rates (``fbps``) and per-edge link capacities
      (``cap_bps``) are lifted from build-time closures to TRACED
      OPERANDS, the runtime operands KPI losses differentiate w.r.t.;
    - unreachable flows report ``delay_s`` 0 instead of inf (an inf
      would poison every gradient through the loss), with the
      ``reached`` mask returned so losses can weight it back in.

    Forward-equality contract (tests/test_diff.py):
    goodput/delivered_frac are BIT-equal to :func:`run_as_flows`;
    utilization/delay agree to ≤1 ULP — lifting the capacities from a
    baked constant to a traced operand changes how XLA
    strength-reduces the per-link division (constant divisors compile
    to reciprocal multiplies).

    ``diff_run(z, scale, fbps, cap_bps, tr, horizon_us) -> outputs``.
    """
    TRAFFIC = prog.traffic is not None
    if TRAFFIC:
        from tpudes.traffic.device import avg_mult

        mult_fn = avg_mult(prog.traffic)
    F = len(prog.src)
    dly = jnp.concatenate(
        [jnp.asarray(prog.delay_s), jnp.asarray(prog.delay_s)]
    ).astype(jnp.float32)

    def diff_run(z, scale, fbps, cap_bps, tr=None, horizon_us=None):
        ddst, dist, nh_edge, nh_node = device_spf(prog)
        path, hops, arrived = _walk_paths(prog, ddst, nh_edge, nh_node)
        reached = (
            dist[ddst, jnp.asarray(prog.src)] < INF
        ) & arrived
        mult = (
            mult_fn(tr, horizon_us) if TRAFFIC
            else jnp.ones((F,), jnp.float32)
        )
        cap2 = jnp.concatenate([cap_bps, cap_bps]).astype(jnp.float32)
        path, cap_l, dly_l = _link_table(prog, path, cap2, dly)
        rate = fbps[None, :] * mult[None, :] * scale * jnp.exp(
            prog.rate_jitter * z - 0.5 * prog.rate_jitter**2
        )
        rate = jnp.where(reached[None, :], rate, 0.0)

        # carry (lfrac, lg, util) exactly like the while-loop runner's
        # carry tail, so the final values are the same buffers (a
        # stacked-ys slice would cost a ULP on the max reduction)
        def body(c, _):
            lf, _, _ = c
            lf2, lg2, util2 = _fluid_round(
                prog, path, prog.max_hops, rate, cap_l, lf
            )
            return (lf2, lg2, util2), None

        (_, lg, util), _ = jax.lax.scan(
            body, _as_carry(prog, r_pad), None, length=FP_ROUNDS
        )
        dl = _fluid_delay(prog, path, prog.max_hops, util, cap_l, dly_l)
        frac = jnp.where(reached[None, :], jnp.exp(lg), 0.0)
        return dict(
            goodput_bps=rate * frac,
            delay_s=jnp.where(reached[None, :], dl, 0.0),
            delivered_frac=frac,
            max_util=util.max(axis=1),
            reached=reached.astype(jnp.float32),
        )

    return diff_run


def _as_replica_draws(prog: AsFlowsProgram, key, r_pad: int):
    """(R, F) per-replica rate-jitter z-draws keyed by
    ``fold_in(key, r)``: replica r's row is independent of the padded
    axis size, so bucketing is exact.  dtype pinned f32 — the draw must
    not widen under ambient x64 (analysis rule JXL002)."""
    from tpudes.parallel.runtime import replica_keys

    return jax.vmap(
        lambda kk: jax.random.normal(
            kk, (len(prog.src),), jnp.float32
        )
    )(replica_keys(key, r_pad))


def run_as_flows(
    prog: AsFlowsProgram,
    key,
    replicas: int,
    mesh=None,
    *,
    rate_scale=None,
    chunk_rounds: int | None = None,
    checkpoint=None,
    block: bool = True,
):
    """Execute R replicas; returns per-replica outcome arrays:
    ``goodput_bps`` (R,F), ``delay_s`` (R,F) fluid end-to-end delay,
    ``delivered_frac`` (R,F), ``max_util`` (R,), ``hops`` (F,),
    ``unreachable`` (F,) bool.  The replica axis is runtime-bucketed
    (padded to a power of two, results sliced back).

    ``rate_scale=[...]`` runs a **config-axis offered-load sweep**: the
    scale is a traced multiplier on every flow's nominal rate, vmapped
    over a leading config axis — a C-point load study is ONE launch in
    which the SPF/path tables are computed once and only the fluid
    fixed point fans out; returns a list of per-point result dicts.

    ``chunk_rounds=N`` splits the fixed-point relaxation into N-round
    while_loop segments with a donated carry handoff (bit-identical to
    the single-shot :data:`FP_ROUNDS` relaxation).  Chunking here is a
    streaming/debugging aid, not a throughput mode: the runner is one
    executable, so every segment re-runs the config-independent SPF +
    path walk and the output assembly — with :data:`FP_ROUNDS` = 4
    that is at most 4 repeats, but don't chunk a large-topology run
    you aren't inspecting.  ``checkpoint=`` (a path or
    :class:`~tpudes.parallel.checkpoint.CarryCheckpoint`) persists the
    relaxation carry after each segment and resumes a matching run,
    bit-equal to uninterrupted.  ``block=False`` returns an
    :class:`~tpudes.parallel.runtime.EngineFuture`.
    """
    from tpudes.obs import spans
    from tpudes.parallel.runtime import Launch, chunk_bounds, stack_axis

    n_cfg = None if rate_scale is None else len(rate_scale)
    L = Launch("as_flows", key, replicas, mesh, n_cfg)
    r_pad = L.r_pad
    launch = spans.current()
    if launch is not None and launch.name == "launch":
        # the topology the SPF was compiled for (its rows: the distinct
        # destinations) and the relaxation's round count
        launch.args["n_nodes"] = int(prog.n)
        launch.args["n_edges"] = int(prog.edges.shape[0])
        launch.args["n_flows"] = int(len(prog.src))
        launch.args["n_dests"] = int(len(np.unique(prog.dst)))
        launch.args["fp_rounds"] = FP_ROUNDS
        # the width of the relaxation's link table (2E + 1: every link)
        launch.args["relax_links"] = relax_links(prog)

    def build():
        def init(key):
            carry = (jnp.int32(0),) + _as_carry(prog, r_pad)
            return (
                _as_replica_draws(prog, key, r_pad),
                stack_axis(carry, n_cfg),
            )

        return (
            init, (0, L.axis),
            build_as_run(prog, r_pad, n_cfg=n_cfg, obs=L.obs, mesh=mesh),
            None,
        )

    def operands(parts):
        z, carry = parts
        scale = (
            np.float32(1.0) if n_cfg is None
            else np.asarray([float(v) for v in rate_scale], np.float32)
        )
        # workload operands (traced; None = the constant-rate path).
        # The horizon the fluid multiplier averages over is a traced
        # operand too — sim_s stays out of the cache key even with
        # traffic on
        tr = None if prog.traffic is None else prog.traffic.operands()
        horizon_us = (
            None if prog.traffic is None
            else np.int32(min(int(prog.sim_s * 1e6), 2**30 - 1))
        )
        return (carry, None), (z, scale, tr, horizon_us)

    # prog.sim_s is deliberately ABSENT (see as_prog_key).  mesh IS
    # present: device_spf shards its tables via the mesh closure,
    # unlike the engines whose sharding flows from inputs
    L.prepare(
        lambda: as_prog_key(prog) + (r_pad, mesh, n_cfg, L.obs),
        build, operands, init_args=(key,),
    )

    def call(run, c, rounds_end, ops):
        z, scale, tr, horizon_us = ops
        carry, out, metrics = run(
            c[0], z, scale, rounds_end, tr, horizon_us
        )
        return (carry, out), metrics

    return L.drive(
        call,
        chunk_bounds(FP_ROUNDS, chunk_rounds or FP_ROUNDS),
        lambda c: c[1],
        lambda host: _as_unpack(host, replicas),
        shared=("hops", "unreachable"),
        checkpoint=checkpoint,
        identity=lambda: as_prog_key(prog) + (
            None if rate_scale is None
            else tuple(float(v) for v in rate_scale),
            None if prog.traffic is None
            else prog.traffic.param_key() + (float(prog.sim_s),),
            _AS_CARRY_LAYOUT,
        ),
        block=block,
    )


# --- trace manifest (tpudes.analysis.jaxpr) --------------------------------

#: canonical tiny replica count for the abstract traces
_TRACE_R = 2


def _trace_prog(**over):
    """Canonical tiny-shape program: 12-node BA graph, 2 CBR flows."""
    import dataclasses

    from tpudes.parallel.programs import toy_as_program

    prog = toy_as_program(n_nodes=12, n_flows=2, spf_rounds=6)
    return dataclasses.replace(prog, **over) if over else prog


def _trace_entries(
    prog: AsFlowsProgram, obs: bool = False, scale: bool = True
):
    """The cached runner exactly as ``run_as_flows`` jits it, with
    concrete tiny operands (same construction as the entry point).
    ``scale=False`` skips the JXL007 axis declarations (the axis
    builders re-enter here)."""
    from tpudes.analysis.jaxpr.spec import TraceEntry

    run = build_as_run(prog, _TRACE_R, obs=obs)
    key = jax.random.PRNGKey(0)
    z = _as_replica_draws(prog, key, _TRACE_R)
    carry = (jnp.int32(0),) + _as_carry(prog, _TRACE_R)
    tr = None if prog.traffic is None else prog.traffic.operands()
    horizon = None if prog.traffic is None else jnp.int32(1_000_000)
    traced = {"scale": 2, "rounds_end": 3}
    if tr is not None:
        # the horizon is traced precisely so sim_s can stay out of the
        # runner cache key — the liveness check must guard it too
        traced["tr"] = 4
        traced["horizon_us"] = 5
    return [
        TraceEntry(
            "run",
            run,
            (carry, z, jnp.float32(1.0), jnp.int32(FP_ROUNDS), tr,
             horizon),
            donate=(0,),
            carry=(0,),
            traced=traced,
            scale_axes=_scale_axes() if scale else (),
        ),
    ]


def _scale_axes():
    """JXL007 scale axes for the SPF fixed-point runner: the SPF's
    edge tables are (D, 2E) with E linear in the node count of the BA
    topology, and the relaxation's link tables (R, min(F·H, 2E + 1)),
    linear in the flows and at most in the nodes.  Both axes budget
    1.0 — this is the linear-in-topology counterpoint to the wired
    engine's dense quadratic tables in the --cost report."""
    from tpudes.analysis.jaxpr.spec import ScaleAxis

    from tpudes.parallel.programs import toy_as_program

    def at(n_nodes, n_flows):
        prog = toy_as_program(
            n_nodes=int(n_nodes), n_flows=int(n_flows), spf_rounds=6
        )
        return _trace_entries(prog, scale=False)[0]

    return (
        ScaleAxis(
            "n_nodes",
            lambda v: at(v, 2),
            points=(8, 32),
            mem_budget=1.0,
            nodes_per_unit=1.0,
        ),
        ScaleAxis(
            "n_flows",
            lambda v: at(12, v),
            points=(2, 8),
            mem_budget=1.0,
        ),
    )


def _flip_traffic():
    from tpudes.traffic import TrafficProgram

    return TrafficProgram.onoff(2, 300.0, horizon_us=1_000_000)


def _flip_surrogacy():
    from tpudes.diff.surrogate import Surrogacy

    return Surrogacy(ste=False)


def _trace_flips():
    import dataclasses

    from tpudes.analysis.jaxpr.spec import FlipSpec

    base = _trace_prog()

    def flip(**over):
        prog = dataclasses.replace(base, **over)
        return FlipSpec(
            build=lambda p=prog: _trace_entries(p),
            key_differs=as_prog_key(prog) != as_prog_key(base),
        )

    return {
        # live components: each must change some traced program
        "spf_metric": flip(spf_metric="delay"),
        "rate_jitter": flip(rate_jitter=0.55),
        "pkt_bytes": flip(pkt_bytes=256),
        # obs is a cache-key component by construction (the metrics
        # tree compiles differently)
        "obs": FlipSpec(
            build=lambda: _trace_entries(base, obs=True),
            key_differs=True,
        ),
        # a workload program joins the trace (the fluid multiplier) and
        # its SHAPE key joins the cache key
        "traffic": flip(traffic=_flip_traffic()),
        # ISSUE-15: the surrogate config swaps the delivery min-gate
        # for the soft version — different arithmetic, different
        # executable, so it must be a cache-key component (and None
        # must compile the identical legacy trace, which JXL004 checks
        # by this flip being key_differs AND trace-differs)
        "surrogate": flip(surrogate=_flip_surrogacy()),
        # sim_s is excluded by design: the fluid fixed point has no
        # time horizon, so flipping it must leave the trace identical
        "sim_s": flip(sim_s=9.0),
    }


def trace_manifest():
    """Per-engine trace manifest (see :mod:`tpudes.analysis.jaxpr`)."""
    from tpudes.analysis.jaxpr.spec import TraceManifest, TraceVariant

    return TraceManifest(
        engine="as_flows",
        path="tpudes/parallel/as_flows.py",
        variants=lambda: [
            TraceVariant(
                "base", lambda: _trace_entries(_trace_prog())
            )
        ],
        flips=_trace_flips,
    )
