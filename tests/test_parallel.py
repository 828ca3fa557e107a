"""Window engine + kernel + mesh tests.

SURVEY.md §4 analog of the MPI tests: the virtual 8-device CPU mesh is
the mpirun-on-localhost harness; JaxSimulatorImpl vs DefaultSimulatorImpl
trace equivalence is the determinism oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudes.core import GlobalValue, Seconds, Simulator
from tpudes.parallel import (
    make_replica_batch,
    replica_mesh,
    shard_leading_axis,
    sharded_window_step,
    wifi_phy_window,
)
from tpudes.parallel.kernels import replicated


def _first_slice_trace():
    """Run the first.cc topology, return the (time, event) trace."""
    from tpudes.helper.applications import UdpEchoClientHelper, UdpEchoServerHelper
    from tpudes.helper.containers import NodeContainer
    from tpudes.helper.internet import InternetStackHelper, Ipv4AddressHelper
    from tpudes.helper.point_to_point import PointToPointHelper

    trace = []
    nodes = NodeContainer()
    nodes.Create(2)
    p2p = PointToPointHelper()
    p2p.SetDeviceAttribute("DataRate", "5Mbps")
    p2p.SetChannelAttribute("Delay", "2ms")
    devices = p2p.Install(nodes)
    stack = InternetStackHelper()
    stack.Install(nodes)
    address = Ipv4AddressHelper()
    address.SetBase("10.1.1.0", "255.255.255.0")
    interfaces = address.Assign(devices)
    server = UdpEchoServerHelper(9)
    server_apps = server.Install(nodes.Get(1))
    server_apps.Start(Seconds(1.0))
    server_apps.Stop(Seconds(10.0))
    client = UdpEchoClientHelper(interfaces.GetAddress(1), 9)
    client.SetAttribute("MaxPackets", 3)
    client.SetAttribute("Interval", Seconds(1.0))
    client.SetAttribute("PacketSize", 1024)
    client_apps = client.Install(nodes.Get(0))
    client_apps.Start(Seconds(2.0))
    client_apps.Stop(Seconds(10.0))
    server_apps.Get(0).TraceConnectWithoutContext(
        "Rx", lambda pkt, *a: trace.append(("server", Simulator.NowTicks(), pkt.GetSize()))
    )
    client_apps.Get(0).TraceConnectWithoutContext(
        "Rx", lambda pkt, *a: trace.append(("client", Simulator.NowTicks(), pkt.GetSize()))
    )
    Simulator.Stop(Seconds(11))
    Simulator.Run()
    count = Simulator.GetEventCount()
    Simulator.Destroy()
    import tpudes.network.node as nn

    nn.NodeList.Reset()
    return trace, count


def test_degenerate_trace_parity_with_default_engine():
    """The step-4 oracle: with no batchable channels, JaxSimulatorImpl
    reproduces DefaultSimulatorImpl's trace EXACTLY (same ticks)."""
    from tpudes.core.rng import RngSeedManager

    RngSeedManager.Reset()
    GlobalValue.Bind("SimulatorImplementationType", "tpudes::DefaultSimulatorImpl")
    base_trace, base_count = _first_slice_trace()

    RngSeedManager.Reset()
    GlobalValue.Bind("SimulatorImplementationType", "tpudes::JaxSimulatorImpl")
    jax_trace, jax_count = _first_slice_trace()

    assert base_trace == jax_trace
    assert base_count == jax_count
    assert len(base_trace) == 6  # 3 at server + 3 echoed at client


def test_jax_engine_runs_wifi_with_cached_windows():
    """WiFi BSS under the window engine: same delivery outcome as the
    scalar engine for a strong-margin geometry, and the cache actually
    engaged (windows_run > 0)."""
    import tests.test_wifi as tw
    from tpudes.network.packet import Packet

    def run_engine(engine):
        from tpudes.core.rng import RngSeedManager

        RngSeedManager.Reset()
        GlobalValue.Bind("SimulatorImplementationType", engine)
        import tpudes.parallel  # registers JaxBatchMinPhys

        GlobalValue.Bind("JaxBatchMinPhys", 2)  # engage the cache at 4 phys
        nodes, devices = tw._wifi_nodes(
            4,
            [(0, 0, 0), (8, 0, 0), (0, 8, 0), (8, 8, 0)],
            lambda i, m: m.SetType("tpudes::AdhocWifiMac"),
        )
        got = []
        devices[1].SetReceiveCallback(lambda dev, pkt, proto, sender: got.append(pkt.GetSize()) or True)
        for k in range(5):
            Simulator.Schedule(
                Seconds(1.0 + 0.05 * k), devices[0].Send, Packet(300), devices[1].GetAddress(), 0x0800
            )
        Simulator.Stop(Seconds(2))
        Simulator.Run()
        impl = Simulator.GetImpl()
        windows = getattr(impl, "windows_run", None)
        Simulator.Destroy()
        import tpudes.network.node as nn
        from tpudes.parallel.engine import BatchableRegistry

        nn.NodeList.Reset()
        BatchableRegistry.reset()
        return got, windows

    got_default, _ = run_engine("tpudes::DefaultSimulatorImpl")
    got_jax, windows = run_engine("tpudes::JaxSimulatorImpl")
    assert got_default == [300] * 5
    assert got_jax == got_default
    assert windows and windows > 0


@pytest.mark.slow  # ISSUE-21 tier-1 budget: the multi-device CI step runs the full file
def test_wifi_phy_window_kernel_basics():
    # two close nodes, node 0 transmitting: node 1 decodes; a lone far
    # node below sensitivity does not
    positions = jnp.array([[0.0, 0, 0], [10.0, 0, 0], [30000.0, 0, 0]])
    tx = jnp.array([True, False, False])
    mode = jnp.zeros(3, jnp.int32)
    size = jnp.full(3, 500.0)
    ok, sinr, rx_dbm = wifi_phy_window(positions, tx, mode, size, jax.random.PRNGKey(0))
    assert bool(ok[0, 1])
    assert not bool(ok[0, 2])  # below sensitivity at 30 km
    assert not bool(ok[0, 0])  # no self-reception
    assert float(sinr[0, 1]) > 100  # strong link


@pytest.mark.slow  # ISSUE-21 tier-1 budget: the multi-device CI step runs the full file
def test_wifi_phy_window_interference_symmetry():
    # two simultaneous transmitters near one receiver: mutual interference
    # drives SINR to ~0 dB and both frames die at high order modulation
    positions = jnp.array([[0.0, 0, 0], [2.0, 0, 0], [1.0, 1.0, 0]])
    tx = jnp.array([True, True, False])
    mode = jnp.full(3, 7, jnp.int32)  # 54 Mbps
    size = jnp.full(3, 1000.0)
    ok, sinr, _ = wifi_phy_window(positions, tx, mode, size, jax.random.PRNGKey(1))
    assert float(sinr[0, 2]) < 3.0  # ~0 dB SIR
    assert not bool(ok[0, 2]) and not bool(ok[1, 2])
    # transmitters are half-duplex: they never receive
    assert not bool(ok[0, 1]) and not bool(ok[1, 0])


@pytest.mark.slow  # ISSUE-21 tier-1 budget: the multi-device CI step runs the full file
def test_replicated_vmap_axis():
    r, n = 8, 16
    positions, tx, mode, size, keys = make_replica_batch(r, n)
    run = replicated()
    ok, sinr, rx = run(positions, tx, mode, size, keys)
    assert ok.shape == (r, n, n)
    # same topology, same tx set, different keys: deterministic parts equal
    np.testing.assert_allclose(np.asarray(rx[0]), np.asarray(rx[1]), rtol=1e-6)


def test_sharded_window_step_on_virtual_mesh():
    """The 8-device CPU mesh exercise: shard_map + pmin grant + psum —
    the MPI-on-localhost analog (SURVEY.md §4)."""
    mesh = replica_mesh()
    n_dev = len(mesh.devices)
    assert n_dev == 8, "conftest must force 8 virtual devices"
    r, n = 2 * n_dev, 12
    positions, tx, mode, size, keys = make_replica_batch(r, n)
    positions, tx, mode, size, keys = shard_leading_axis(mesh, positions, tx, mode, size, keys)
    next_ts = jnp.arange(r, dtype=jnp.int32) + 100  # per-replica next event times
    (next_ts,) = shard_leading_axis(mesh, next_ts)
    lookahead = jnp.array([7], dtype=jnp.int32)

    step = sharded_window_step(mesh)
    ok, sinr, delivered, grant = jax.jit(step)(positions, tx, mode, size, keys, next_ts, lookahead)
    assert ok.shape == (r, n, n)
    assert int(grant) == 100 + 7  # global min across shards + lookahead
    # delivered is psum'd across shards: equals the global sum of ok
    assert int(delivered) == int(jnp.sum(ok))
    assert int(delivered) > 0


def test_multi_window_scan_jit():
    from tpudes.parallel import multi_window_scan

    positions = jax.random.uniform(jax.random.PRNGKey(3), (24, 3), maxval=40.0)
    mode = jnp.zeros(24, jnp.int32)
    size = jnp.full(24, 700.0)
    total = multi_window_scan(positions, 0.25, mode, size, jax.random.PRNGKey(4), n_windows=8)
    assert int(total) > 0


def test_lte_window_cache_beats_per_event_dispatch():
    """Cross-consumer check (VERDICT r5 weak #3): the LTE TTI controller
    registers as a second BatchableRegistry consumer beside
    YansWifiChannel, and on a mobile LTE graph the windowed engine's
    once-per-window geometry/SINR refresh replaces the per-TTI-event
    rebuild the scalar engine pays."""
    from tpudes.core.rng import RngSeedManager
    from tpudes.core.world import reset_world
    from tpudes.models.lte.controller import LteTtiController
    from tpudes.parallel.engine import BatchableRegistry

    sim_s = 0.05  # 50 TTIs

    def run(engine, window_ns=None):
        reset_world()
        RngSeedManager.Reset()
        GlobalValue.Bind("SimulatorImplementationType", engine)
        if window_ns is not None:
            GlobalValue.Bind("JaxWindowNs", window_ns)
        import tests.test_lte as tl
        from tpudes.models.mobility import MobilityHelper

        lte, _, ue_devs = tl._build_lena(1, 2)
        # make the geometry non-static: a (zero-velocity) walker model
        # on one UE — identical physics, but the controller can no
        # longer prove the gain matrix constant across TTIs
        walker = MobilityHelper()
        walker.SetMobilityModel("tpudes::ConstantVelocityMobilityModel")
        node = ue_devs.Get(0).GetNode()
        from tpudes.models.mobility import MobilityModel, Vector

        old = node.GetObject(MobilityModel)
        pos = old.GetPosition()
        walker.Install(node)
        new = [
            m for m in node._aggregates
            if isinstance(m, MobilityModel) and m is not old
        ]
        # the freshly-installed model must be the one GetObject resolves
        node._aggregates.remove(old)
        new[0].SetPosition(Vector(pos.x, pos.y, pos.z))

        c = lte.controller
        # ISSUE-10: the mobile refresh is now the geometry-only slice
        # of _rebuild (bit-equal, cheaper) — the per-window-vs-per-event
        # contract is about GEOMETRY REFRESHES, so count both kinds
        rebuilds = [0]
        orig = c._rebuild
        orig_geom = c._refresh_geometry

        def counting():
            rebuilds[0] += 1
            orig()

        def counting_geom():
            rebuilds[0] += 1
            orig_geom()

        c._rebuild = counting
        c._refresh_geometry = counting_geom
        members = BatchableRegistry.members()
        assert any(isinstance(m, LteTtiController) for m in members)

        Simulator.Stop(Seconds(sim_s))
        Simulator.Run()
        ttis = c.stats["ttis"]
        ok = c.stats["dl_ok"]
        reset_world()
        return rebuilds[0], ttis, ok

    per_event, ttis_a, ok_a = run("tpudes::DefaultSimulatorImpl")
    windowed, ttis_b, ok_b = run(
        "tpudes::JaxSimulatorImpl", window_ns=10_000_000
    )
    assert ttis_a == ttis_b == 50
    assert ok_a > 0 and ok_b > 0
    # per-event: ~one rebuild per TTI; windowed: ~one per 10 ms window
    assert per_event >= 45, per_event
    assert windowed <= per_event // 4, (windowed, per_event)

    # both consumer kinds coexist in the registry
    reset_world()
    from tpudes.models.lte import LteHelper
    from tpudes.models.wifi.channel import YansWifiChannel

    ch = YansWifiChannel()
    lte = LteHelper()
    kinds = {type(m).__name__ for m in BatchableRegistry.members()}
    assert {"YansWifiChannel", "LteTtiController"} <= kinds, kinds
    del ch, lte
    reset_world()
