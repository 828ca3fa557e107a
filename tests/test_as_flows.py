"""Config-#5 tests: BRITE-style generator, device SPF, flow engine.

Strategy mirrors upstream's global-routing and BRITE integration tests:
generator structure, SPF-vs-oracle distance parity, end-to-end delivery
parity against the packet-level scalar DES, overload direction, and the
lift seam; then the engine against the benchmark's plain reference replica
by replica, the control and faults that reference has to catch, and the
device names and span arguments (which change no equation and no bit).
"""

import contextlib
import copy
import heapq
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import load_module
from tpudes.core import Seconds, Simulator
from tpudes.helper.topology import BriteTopologyHelper
from tpudes.parallel.as_flows import (
    AsFlowsProgram,
    UnliftableAsError,
    device_spf,
    lower_as_flows,
    run_as_flows,
)
from tpudes.scenarios import build_as_network


# ---------------------------------------------------------------- generator
def test_ba_generator_structure():
    g = BriteTopologyHelper(model="BA", n=500, m=2, seed=9).Generate()
    assert g.is_connected()
    assert g.m == 2 * (500 - 3) + 3  # m per new node + seed clique
    deg = np.bincount(g.edges.ravel(), minlength=g.n)
    # preferential attachment: heavy tail, hubs far above the mean
    assert deg.max() >= 8 * deg.mean()
    assert deg.min() >= 2


def test_waxman_generator_locality():
    h = BriteTopologyHelper(model="Waxman", n=400, alpha=0.3, beta=0.06, seed=9)
    g = h.Generate()
    assert g.is_connected()
    # locality: a Waxman edge is much shorter than a random node pair
    rng = np.random.default_rng(0)
    pairs = rng.integers(0, g.n, size=(2000, 2))
    rand_d = np.sqrt(
        ((g.pos[pairs[:, 0]] - g.pos[pairs[:, 1]]) ** 2).sum(-1)
    ).mean()
    edge_d = np.sqrt(
        ((g.pos[g.edges[:, 0]] - g.pos[g.edges[:, 1]]) ** 2).sum(-1)
    ).mean()
    assert edge_d < 0.5 * rand_d


def test_generator_is_seed_deterministic():
    a = BriteTopologyHelper(model="BA", n=300, m=2, seed=5).Generate()
    b = BriteTopologyHelper(model="BA", n=300, m=2, seed=5).Generate()
    c = BriteTopologyHelper(model="BA", n=300, m=2, seed=6).Generate()
    np.testing.assert_array_equal(a.edges, b.edges)
    assert not np.array_equal(a.edges, c.edges)


def test_generator_rides_the_seeded_stream_api():
    """Promoted RNG002 regression: the topology draws are keyed by the
    global (RngSeed, RngRun) pair — selecting a different RngRun
    re-randomizes the graph (a bare np.random.default_rng(seed) could
    never see it), while the same (seed, run) reproduces it exactly."""
    from tpudes.core.rng import RngSeedManager

    run0 = RngSeedManager.GetRun()
    try:
        a = BriteTopologyHelper(model="BA", n=200, m=2, seed=5).Generate()
        RngSeedManager.SetRun(run0 + 7)
        b = BriteTopologyHelper(model="BA", n=200, m=2, seed=5).Generate()
        RngSeedManager.SetRun(run0)
        c = BriteTopologyHelper(model="BA", n=200, m=2, seed=5).Generate()
    finally:
        RngSeedManager.SetRun(run0)
    assert not np.array_equal(a.edges, b.edges)
    np.testing.assert_array_equal(a.edges, c.edges)
    np.testing.assert_array_equal(a.pos, c.pos)


# ---------------------------------------------------------------- device SPF
def _dijkstra(n, edges, w, dst):
    """float64 host oracle (hop metric when w=1)."""
    adj = [[] for _ in range(n)]
    for (u, v), wt in zip(edges, w):
        adj[u].append((v, wt))
        adj[v].append((u, wt))
    dist = np.full(n, np.inf)
    dist[dst] = 0.0
    pq = [(0.0, dst)]
    while pq:
        d, u = heapq.heappop(pq)
        if d > dist[u]:
            continue
        for v, wt in adj[u]:
            nd = d + wt
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(pq, (nd, v))
    return dist


def test_device_spf_matches_dijkstra_oracle():
    g = BriteTopologyHelper(model="BA", n=200, m=2, seed=11).Generate()
    dsts = np.array([0, 17, 133], np.int32)
    prog = AsFlowsProgram(
        n=g.n, edges=g.edges, delay_s=g.delay_s, rate_bps=g.rate_bps,
        src=np.zeros(3, np.int32), dst=dsts,
        flow_bps=np.full(3, 1e5), pkt_bytes=512, sim_s=1.0,
    )
    ddst, dist, nh_edge, nh_node = device_spf(prog)
    dist = np.asarray(dist)
    for row, d in enumerate(np.unique(dsts)):
        oracle = _dijkstra(g.n, g.edges, np.ones(g.m), int(d))
        np.testing.assert_allclose(dist[row], oracle, rtol=1e-5)


def test_path_walk_reaches_destination_in_dist_hops():
    g = BriteTopologyHelper(model="BA", n=300, m=2, seed=2).Generate()
    rng = np.random.default_rng(1)
    F = 16
    src = rng.integers(0, g.n, F).astype(np.int32)
    dst = (src + rng.integers(1, g.n, F)).astype(np.int32) % g.n
    prog = AsFlowsProgram(
        n=g.n, edges=g.edges, delay_s=g.delay_s, rate_bps=g.rate_bps,
        src=src, dst=dst, flow_bps=np.full(F, 1e5), pkt_bytes=512,
        sim_s=1.0,
    )
    out = run_as_flows(prog, jax.random.PRNGKey(0), replicas=2)
    hops = np.asarray(out["hops"])
    assert not np.asarray(out["unreachable"]).any()
    for f in range(F):
        oracle = _dijkstra(g.n, g.edges, np.ones(g.m), int(dst[f]))
        assert hops[f] == int(oracle[src[f]]), f"flow {f} not shortest"


# ------------------------------------------------------------ flow outcomes
def test_sparse_traffic_parity_with_scalar_des():
    """Sparse regime: the fluid engine and the packet DES must agree on
    delivery (all packets arrive) and goodput within jitter."""
    build_as_network(80, 6, 2.0, seed=4)
    prog = lower_as_flows(2.0)
    _, servers = None, None  # objects live in the world already
    from tpudes.network.node import NodeList  # noqa: F401

    Simulator.Stop(Seconds(2.0))
    Simulator.Run()
    # host: every CBR packet delivered (no congestion on 10-100 Mbps links)
    from tpudes.models.applications import UdpServer

    host_rx = []
    for i in range(NodeList.GetNNodes()):
        node = NodeList.GetNode(i)
        for a in range(node.GetNApplications()):
            app = node.GetApplication(a)
            if isinstance(app, UdpServer):
                host_rx.append(app.received)
    expected = int((2.0 - 0.05) / (512 * 8 / 400e3))
    # a few packets are still in flight at Stop (multi-hop path delay)
    assert all(abs(rx - expected) <= 5 for rx in host_rx), host_rx

    out = run_as_flows(prog, jax.random.PRNGKey(0), replicas=16)
    frac = np.asarray(out["delivered_frac"])
    assert (frac > 0.999).all(), "sparse flows must be loss-free"
    g = np.asarray(out["goodput_bps"]).mean(axis=0)
    # replica jitter is zero-mean around the nominal 400 kbps
    assert g.mean() == pytest.approx(400e3, rel=0.15)


def test_overloaded_link_sheds_proportionally():
    """3-node line, two flows through the middle link at 2x capacity →
    fluid delivery ≈ 0.5 each."""
    edges = np.array([[0, 1], [1, 2]], np.int32)
    prog = AsFlowsProgram(
        n=3, edges=edges,
        delay_s=np.array([1e-3, 1e-3]),
        rate_bps=np.array([10e6, 10e6]),
        src=np.array([0, 0], np.int32), dst=np.array([2, 2], np.int32),
        flow_bps=np.array([10e6, 10e6]),
        pkt_bytes=512, sim_s=1.0, rate_jitter=0.0,
    )
    out = run_as_flows(prog, jax.random.PRNGKey(0), replicas=4)
    frac = np.asarray(out["delivered_frac"])
    np.testing.assert_allclose(frac, 0.5, rtol=0.01)
    assert np.asarray(out["max_util"]).max() == pytest.approx(2.0, rel=0.01)


def test_exact_max_hops_path_still_arrives():
    """A shortest path of exactly max_hops hops is reachable (r4 review:
    the arrival test off-by-one zeroed such flows)."""
    n = 6  # line graph: 5 hops end-to-end
    edges = np.stack(
        [np.arange(n - 1), np.arange(1, n)], axis=1
    ).astype(np.int32)
    prog = AsFlowsProgram(
        n=n, edges=edges, delay_s=np.full(n - 1, 1e-3),
        rate_bps=np.full(n - 1, 10e6),
        src=np.array([0], np.int32), dst=np.array([n - 1], np.int32),
        flow_bps=np.array([1e5]), pkt_bytes=512, sim_s=1.0,
        max_hops=5, spf_rounds=8, rate_jitter=0.0,
    )
    out = run_as_flows(prog, jax.random.PRNGKey(0), replicas=2)
    assert not np.asarray(out["unreachable"]).any()
    assert int(np.asarray(out["hops"])[0]) == 5
    np.testing.assert_allclose(
        np.asarray(out["delivered_frac"]), 1.0, rtol=1e-5
    )


def test_unmodeled_cross_traffic_is_rejected():
    """Apps the flow engine cannot represent must fail the lowering,
    not silently vanish from the link loads (r4 review)."""
    from tpudes.core import Seconds
    from tpudes.helper.applications import UdpEchoClientHelper
    from tpudes.network.address import Ipv4Address
    from tpudes.network.node import NodeList

    build_as_network(60, 4, 2.0, seed=8)
    echo = UdpEchoClientHelper(Ipv4Address("10.0.0.1"), 9)
    echo.Install(NodeList.GetNode(3)).Start(Seconds(0.1))
    with pytest.raises(UnliftableAsError, match="unmodeled"):
        lower_as_flows(2.0)


def test_flows_riding_other_technologies_are_rejected():
    """A UDP flow whose path crosses a non-p2p technology (here: LTE
    bearers behind the EPC) must NOT lift as the p2p backhaul graph
    (r4: the generic backstop silently swallowed an LTE scenario)."""
    from tpudes.helper.applications import UdpClientHelper, UdpServerHelper
    from tpudes.helper.containers import NodeContainer
    from tpudes.helper.internet import InternetStackHelper, Ipv4AddressHelper
    from tpudes.helper.point_to_point import PointToPointHelper
    from tpudes.core import Seconds

    # two p2p islands: remote--gw, and ue alone with an address the
    # client can name but no p2p path to reach it
    a = NodeContainer()
    a.Create(2)
    b = NodeContainer()
    b.Create(2)
    InternetStackHelper().Install(a)
    InternetStackHelper().Install(b)
    p2p = PointToPointHelper()
    p2p.SetDeviceAttribute("DataRate", "10Mbps")
    p2p.SetChannelAttribute("Delay", "1ms")
    Ipv4AddressHelper("10.1.0.0", "255.255.255.0").Assign(
        p2p.Install(a.Get(0), a.Get(1))
    )
    ifc_b = Ipv4AddressHelper("10.2.0.0", "255.255.255.0").Assign(
        p2p.Install(b.Get(0), b.Get(1))
    )
    server = UdpServerHelper(9)
    server.Install(b.Get(1)).Start(Seconds(0.0))
    client = UdpClientHelper(ifc_b.GetAddress(1), 9)
    client.SetAttribute("Interval", Seconds(0.01))
    client.Install(a.Get(0)).Start(Seconds(0.1))
    with pytest.raises(UnliftableAsError, match="not connected"):
        lower_as_flows(1.0)


def test_lowering_rejects_empty_and_lift_discovers():
    from tpudes.parallel.lift import lift

    with pytest.raises(UnliftableAsError):
        lower_as_flows(1.0)
    build_as_network(60, 4, 2.0, seed=8)
    kind, prog, commit = lift(2.0)
    assert kind == "as_flows"
    assert len(prog.src) == 4
    commit()


def test_mesh_sharded_run():
    from tpudes.parallel.mesh import replica_mesh

    g = BriteTopologyHelper(model="BA", n=100, m=2, seed=1).Generate()
    prog = AsFlowsProgram(
        n=g.n, edges=g.edges, delay_s=g.delay_s, rate_bps=g.rate_bps,
        src=np.array([1, 2], np.int32), dst=np.array([50, 60], np.int32),
        flow_bps=np.full(2, 1e5), pkt_bytes=512, sim_s=1.0,
    )
    out = run_as_flows(
        prog, jax.random.PRNGKey(0), replicas=16, mesh=replica_mesh(8)
    )
    assert np.asarray(out["goodput_bps"]).shape == (16, 2)
    assert not np.asarray(out["unreachable"]).any()


def test_topology_axis_sharding_matches_single_device():
    """SURVEY.md §5.7: the (D, N) SPF tables shard their destination
    rows over the mesh (with_sharding_constraint in device_spf) and the
    study result is identical to the replicated single-device run."""
    import jax as _jax

    if len(_jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    from tpudes.parallel.mesh import replica_mesh

    g = BriteTopologyHelper(model="BA", n=200, m=2, seed=3).Generate()
    n_dst = 16  # divisible by the 8-device mesh
    prog = AsFlowsProgram(
        n=g.n, edges=g.edges, delay_s=g.delay_s, rate_bps=g.rate_bps,
        src=np.arange(1, 1 + n_dst, dtype=np.int32),
        dst=np.arange(100, 100 + n_dst, dtype=np.int32),
        flow_bps=np.full(n_dst, 1e5), pkt_bytes=512, sim_s=1.0,
    )
    mesh = replica_mesh(8)
    sharded = run_as_flows(prog, jax.random.PRNGKey(2), replicas=16, mesh=mesh)
    single = run_as_flows(prog, jax.random.PRNGKey(2), replicas=16, mesh=None)
    np.testing.assert_allclose(
        np.asarray(sharded["goodput_bps"]), np.asarray(single["goodput_bps"]),
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(sharded["delay_s"]), np.asarray(single["delay_s"]),
        rtol=1e-5,
    )


def test_lift_warns_on_nondivisible_replica_count():
    """lift.py used to silently drop the mesh when replicas % devices
    != 0 (VERDICT r4 weak #5) — now it warns loudly."""
    import warnings

    import jax as _jax

    if len(_jax.devices()) < 2:
        pytest.skip("needs multiple devices")
    from tpudes.parallel.lift import run_lifted

    g = BriteTopologyHelper(model="BA", n=60, m=2, seed=1).Generate()
    prog = AsFlowsProgram(
        n=g.n, edges=g.edges, delay_s=g.delay_s, rate_bps=g.rate_bps,
        src=np.array([1], np.int32), dst=np.array([30], np.int32),
        flow_bps=np.full(1, 1e5), pkt_bytes=512, sim_s=1.0,
    )
    n_dev = len(_jax.devices())
    odd = n_dev + 1  # never divisible by (or sharing a factor > 1 with
                     # n_dev only when n_dev+1 ... gcd(n+1, n) == 1)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = run_lifted("as_flows", prog, replicas=odd)
    assert np.asarray(out["goodput_bps"]).shape[0] == odd
    assert any("not divisible" in str(w.message) for w in caught), [
        str(w.message) for w in caught
    ]


def test_flow_endpoints_ride_the_seeded_stream_api():
    """The endpoint draw uses the MRG32k3a stream API keyed by ``seed``
    (the promoted RNG002 baseline finding): the flow set is a pure
    function of the builder arguments, immune to stdlib random state."""
    import random as stdlib_random

    from tpudes.core.world import reset_world

    def endpoints(seed):
        reset_world()
        _, servers = build_as_network(40, 6, 1.0, seed=seed)
        out = [
            (srv.GetNode().GetId(), srv.port) for srv in servers
        ]
        reset_world()
        return out

    stdlib_random.seed(123)
    a = endpoints(seed=4)
    stdlib_random.seed(999)
    assert endpoints(seed=4) == a  # stdlib state is irrelevant
    assert endpoints(seed=5) != a  # but the seed argument is not


# ------------------------------------------------- the benchmark's plain reference
# `benchmark/references/as_flows.py` is the float64 numpy reference the cell `as.mc`
# decides `correct` with: it generates the stock script's graph and flows again,
# routes them on the hop metric and runs the fluid fixed point replica by replica
# on the program's own draws.  Here, small and on the CPU.

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = load_module(os.path.join(ROOT, "benchmark", "references", "as_flows.py"))
with open(os.path.join(ROOT, "benchmark", "configs", "brite-as-10k.json")) as f:
    CONFIG = json.load(f)

N_NODES, N_FLOWS, REPLICAS, SEED = 200, 16, 32, 3_000_000_019
#: sparse (no link near its rate) and overfilled (a tenth of the flows lose
#: packets on some link, so the gate, its compounding and the rounds matter)
SPARSE_KBPS, OVERLOAD_KBPS = 400.0, 24000.0

#: the largest relative error, engine against reference, replica by replica, and
#: why this much: float32 against float64 reads up to 1.0e-6 in goodput (the
#: program's float32 erfinv in its draw: 2e-5 in z where |z| nears 5, 0.3 of it
#: in the rate), 1.3e-7 to 1.8e-7 in the other fields sparse, and 5.2e-6 in the
#: delay of the overloaded deployment (a link at rho 0.99 scales its rounding by
#: a hundred); the control and the faults read above 3.8e-3 in the number that
#: tells them (FAULTS)
TOLERANCE = {"goodput_bps": 1e-5, "delivered_frac": 1e-5, "delay_s": 3e-5,
             "max_util": 1e-5}


def _deployment(flow_kbps: float) -> dict:
    cfg = copy.deepcopy(CONFIG)
    cfg["topology"].update(n_nodes=N_NODES, n_flows=N_FLOWS)
    cfg["physics"]["flow_kbps"] = flow_kbps
    return cfg


def _worst(got, want, mask=True) -> float:
    got, want = np.asarray(got, float), np.asarray(want, float)
    mask = np.broadcast_to(mask, got.shape)
    return float(np.max(np.abs(got[mask] - want[mask]) / np.abs(want[mask])))


@pytest.mark.parametrize("flow_kbps", [SPARSE_KBPS, OVERLOAD_KBPS],
                         ids=["sparse", "overload"])
def test_engine_matches_the_plain_reference_replica_by_replica(flow_kbps):
    cfg = _deployment(flow_kbps)
    build_as_network(N_NODES, N_FLOWS, 2.0, flow_kbps=flow_kbps)
    prog = lower_as_flows(2.0)
    topo = REF.topology(cfg)
    # the reference builds the stock script's graph and flows by itself
    for field in ("edges", "delay_s", "rate_bps", "src", "dst", "flow_bps"):
        np.testing.assert_array_equal(getattr(prog, field), topo[field])
    out = run_as_flows(prog, REF.launch_key(SEED, 0), replicas=REPLICAS)
    want = REF.simulate(cfg, 2.0, REPLICAS, SEED)
    np.testing.assert_array_equal(out["hops"], want["hops"])
    np.testing.assert_array_equal(out["unreachable"], want["unreachable"])
    assert not want["unreachable"].any()
    for field, tolerance in TOLERANCE.items():
        assert _worst(out[field], want[field]) < tolerance, field
    frac = np.asarray(out["delivered_frac"])
    assert (frac < 0.99).any() == (flow_kbps == OVERLOAD_KBPS)
    numbers = REF.compare(cfg, {"reference_replicas": REPLICAS}, [out], REPLICAS, SEED)
    assert numbers["rows_missing"] == numbers["hops_differ"] == 0
    assert max(numbers[k] for k in ("goodput_gap", "delay_gap", "max_util_gap")) < 3e-5


#: a fault of the reference, the deployment it shows in, the number that tells
#: it and what it read here
FAULTS = [
    # the control: link loads held in bfloat16 between hops (0.0038)
    (dict(precision="bfloat16"), SPARSE_KBPS, "max_util"),
    # routes on propagation delay, not hops: 6 of 16 flows' paths change
    (dict(metric="delay"), SPARSE_KBPS, "hops"),
    # the relaxation stopped a round early, where links overfill (0.17)
    (dict(rounds=3), OVERLOAD_KBPS, "goodput_bps"),
]


@pytest.mark.parametrize("fault,flow_kbps,field", FAULTS)
def test_the_control_and_the_faults_fail_the_tolerance(fault, flow_kbps, field):
    cfg = _deployment(flow_kbps)
    sound = REF.simulate(cfg, 2.0, REPLICAS, SEED)
    faulty = REF.simulate(cfg, 2.0, REPLICAS, SEED, **fault)
    if field == "hops":
        assert (faulty["hops"] != sound["hops"]).sum() >= 3
    else:
        assert _worst(faulty[field], sound[field]) > 3 * TOLERANCE[field]


# ------------------------------------------------- the link table the relaxation runs over
# The relaxation runs over min(F·H, 2E + 1) columns: fewer than the 2E + 1 ids where
# F·H < 2E + 1, every link in use and the sentinel elsewhere; both hold the reference
# at the cell's own limits.

with open(os.path.join(ROOT, "benchmark", "limits", "as.mc.json")) as f:
    LIMITS = json.load(f)["limits"]


@pytest.mark.parametrize("n_nodes,n_flows,compact", [(40, 4, True), (24, 8, False)],
                         ids=["links-in-use", "every-link"])
def test_the_link_table_holds_the_reference_at_the_cells_limits(n_nodes, n_flows, compact):
    from tpudes.obs import spans
    from tpudes.parallel.as_flows import relax_links
    from tpudes.parallel.lift import run_lifted

    cfg = copy.deepcopy(CONFIG)
    cfg["topology"].update(n_nodes=n_nodes, n_flows=n_flows)
    build_as_network(n_nodes, n_flows, 2.0)
    prog = lower_as_flows(2.0)
    E2 = 2 * prog.edges.shape[0]
    width = n_flows * prog.max_hops if compact else E2 + 1
    assert relax_links(prog) == width and (width < E2 + 1) == compact
    out = run_lifted("as_flows", prog, REPLICAS, REF.launch_key(SEED, 0))
    launch = [s for s in spans.snapshot() if s.name == "launch"][-1]
    assert launch.args["relax_links"] == width
    numbers = REF.compare(cfg, {"reference_replicas": REPLICAS}, [out], REPLICAS, SEED)
    assert {"rows_missing", "hops_differ", "goodput_gap", "max_util_gap"} <= set(numbers)
    for name, value in numbers.items():
        assert value <= LIMITS[name], name


@pytest.mark.parametrize("gate_temp", [None, 4.0], ids=["hard-gate", "soft-gate"])
def test_the_link_table_leaves_only_links_no_path_crosses(gate_temp):
    """Every link's utilisation by the formula over all 2E links (the pad column the
    done hops write into, the gate and FP_ROUNDS rounds, on the same draws) is the
    compact table's where the table holds the link and 0 where it does not, so the
    engine's max_util is the max over every link; and the done hops add nothing to a
    flow's delivery, under the hard gate and under a soft one whose value at 0 is not 0."""
    import dataclasses

    from tpudes.diff import Surrogacy
    from tpudes.parallel.as_flows import (
        FP_ROUNDS, _as_carry, _as_replica_draws, _link_table, _walk_paths, build_as_run,
        relax_links,
    )
    from tpudes.parallel.programs import toy_as_program

    prog = toy_as_program(n_nodes=40, n_flows=4, spf_rounds=12)
    if gate_temp is not None:
        prog = dataclasses.replace(prog, surrogate=Surrogacy(gate_temp=gate_temp))
    E2, R = 2 * prog.edges.shape[0], 4
    assert relax_links(prog) < E2 + 1
    z = _as_replica_draws(prog, jax.random.PRNGKey(9), R)
    carry, out, _ = jax.jit(build_as_run(prog, R))(
        (jnp.int32(0),) + _as_carry(prog, R), z, jnp.float32(400.0), jnp.int32(FP_ROUNDS))
    ddst, dist, nh_edge, nh_node = device_spf(prog)
    path, _, arrived = _walk_paths(prog, ddst, nh_edge, nh_node)
    cap2 = np.tile(prog.rate_bps, 2).astype(np.float32)
    _, cap, _ = _link_table(prog, path, jnp.asarray(cap2), jnp.zeros(E2, jnp.float32))
    links = np.asarray(jnp.unique(path, size=relax_links(prog), fill_value=E2))

    def gate(util):
        if gate_temp is None:
            return jnp.log(jnp.minimum(1.0, 1.0 / jnp.maximum(util, 1e-9)))
        t = jnp.float32(gate_temp)
        return -jax.nn.softplus(jnp.log(jnp.maximum(util, jnp.float32(1e-9))) / t) * t

    rate = jnp.asarray(prog.flow_bps, jnp.float32) * 400.0 * jnp.exp(
        prog.rate_jitter * z - 0.5 * prog.rate_jitter**2)
    rate = jnp.where(arrived[None, :], rate, 0.0)
    lfrac = jnp.zeros((R, E2 + 1), jnp.float32)
    for _ in range(FP_ROUNDS):
        load, lg = jnp.zeros((R, E2 + 1), jnp.float32), jnp.zeros_like(rate)
        for h in range(prog.max_hops):
            load = load.at[:, path[:, h]].add(rate * jnp.exp(lg))
            lg = lg + lfrac[:, path[:, h]]
        util = load[:, :E2] / cap2[None, :]
        lfrac = jnp.concatenate([gate(util), jnp.zeros((R, 1))], 1)
    util = np.asarray(util)
    compact = np.asarray(carry[3])
    live = np.isfinite(np.asarray(cap))
    np.testing.assert_allclose(compact[:, live], util[:, links[live]], rtol=1e-6)
    assert not compact[:, ~live].any()
    gone = np.setdiff1d(np.arange(E2), links)
    assert gone.size > E2 // 2 and not util[:, gone].any()
    np.testing.assert_allclose(np.asarray(out["max_util"]), util.max(axis=1), rtol=1e-6)
    frac = np.asarray(out["delivered_frac"])
    assert (frac < 0.999).any()  # the gate gated
    np.testing.assert_allclose(frac, np.exp(np.asarray(lg)), rtol=1e-6)


def _toy_run(n_replicas=2):
    from tpudes.parallel.as_flows import _as_carry, _as_replica_draws, build_as_run
    from tpudes.parallel.programs import toy_as_program

    prog = toy_as_program(n_nodes=40, n_flows=4, spf_rounds=12)
    args = (
        (jnp.int32(0),) + _as_carry(prog, n_replicas),
        _as_replica_draws(prog, jax.random.PRNGKey(5), n_replicas),
        jnp.float32(3.0), jnp.int32(4),
    )
    return jax.jit(build_as_run(prog, n_replicas)), args


def test_the_three_scopes_are_in_the_lowered_text():
    run, args = _toy_run()
    text = run.lower(*args).as_text(debug_info=True)
    step = "tpudes.as_flows.step"
    assert f"{step}/tpudes.as_flows.load" in text
    assert "tpudes.as_flows.spf" in text and "tpudes.as_flows.delay" in text
    # the shortest paths and the delay sum run outside the loop body
    assert f"{step}/tpudes.as_flows.spf" not in text
    assert f"{step}/tpudes.as_flows.delay" not in text


def test_the_launch_span_names_the_topology():
    from tpudes.obs import spans
    from tpudes.parallel.lift import run_lifted
    from tpudes.parallel.programs import toy_as_program

    prog = toy_as_program(n_nodes=40, n_flows=4, spf_rounds=12)
    run_lifted("as_flows", prog, 8, jax.random.PRNGKey(1))
    (launch,) = [s for s in spans.snapshot() if s.name == "launch"][-1:]
    assert {k: launch.args[k] for k in (
        "n_nodes", "n_edges", "n_flows", "n_dests", "fp_rounds")} == dict(
        n_nodes=40, n_edges=prog.edges.shape[0], n_flows=4, n_dests=4, fp_rounds=4)


def test_the_scopes_change_no_equation_and_no_bit(monkeypatch):
    """The runner's jaxpr holds the program's equations (131 at its top, 284
    walked, one `while` of 14: the trace toy's F·H = 32 < 2E + 1, so the
    link table is built with the paths), and a run traced with every scope
    taken out gives the same bits."""
    from tpudes.analysis.jaxpr.trace import walk_eqns
    from tpudes.parallel import as_flows as af

    (entry,) = af._trace_entries(af._trace_prog())
    jaxpr = jax.make_jaxpr(entry.fn)(*entry.args).jaxpr
    (loop,) = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    assert (len(jaxpr.eqns), len(list(walk_eqns(jaxpr))),
            len(loop.params["body_jaxpr"].jaxpr.eqns)) == (131, 284, 14)

    run, args = _toy_run()
    scoped = jax.tree_util.tree_map(np.asarray, run(*args))
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare, _ = _toy_run()
    assert "tpudes." not in bare.lower(*args).as_text(debug_info=True)
    unscoped = jax.tree_util.tree_map(np.asarray, bare(*args))
    for a, b in zip(jax.tree_util.tree_leaves(scoped),
                    jax.tree_util.tree_leaves(unscoped)):
        np.testing.assert_array_equal(a, b)
