"""Rank entry points for the distributed PDES tests.

Module-level functions (LaunchDistributed uses the spawn start method,
which pickles targets by reference) — deliberately jax-free so child
processes never touch the accelerator runtime.
"""

from __future__ import annotations


def run_chain(rank: int, size: int, n_packets: int = 5, interval_s: float = 0.1,
              engine: str = "tpudes::DistributedSimulatorImpl"):
    """4-node p2p chain n0-n1-n2-n3, echo client on n0 → server on n3.

    Partitioning (size=2): n0,n1 → rank 0; n2,n3 → rank 1 (the middle
    link crosses).  With size=1 (or MPI disabled) this is the sequential
    oracle.  Returns a dict with ``server_rx``/``client_rx`` lists of
    (sim_ticks, packet_size) in arrival order, plus ``events`` and
    ``windows`` counts.
    """
    from tpudes.core import Seconds, Simulator
    from tpudes.core.global_value import GlobalValue
    from tpudes.core.world import reset_world
    from tpudes.helper.applications import (
        UdpEchoClientHelper,
        UdpEchoServerHelper,
    )
    from tpudes.helper.containers import NodeContainer
    from tpudes.helper.internet import InternetStackHelper, Ipv4AddressHelper
    from tpudes.helper.point_to_point import PointToPointHelper
    from tpudes.models.internet.global_routing import Ipv4GlobalRoutingHelper
    from tpudes.parallel.mpi import MpiInterface

    reset_world()
    distributed = MpiInterface.IsEnabled() and MpiInterface.GetSize() > 1
    if distributed:
        GlobalValue.Bind("SimulatorImplementationType", engine)

    left = NodeContainer()
    left.Create(2, system_id=0)
    right = NodeContainer()
    right.Create(2, system_id=1 if distributed else 0)
    n = [left.Get(0), left.Get(1), right.Get(0), right.Get(1)]

    stack = InternetStackHelper()
    stack.SetRoutingHelper(Ipv4GlobalRoutingHelper())
    stack.Install(left)
    stack.Install(right)

    p2p = PointToPointHelper()
    p2p.SetDeviceAttribute("DataRate", "5Mbps")
    p2p.SetChannelAttribute("Delay", "2ms")
    addr = Ipv4AddressHelper("10.1.0.0", "255.255.255.0")
    last_ifc = None
    for i in range(3):
        devs = p2p.Install(n[i], n[i + 1])
        last_ifc = addr.Assign(devs)
        addr.NewNetwork()
    Ipv4GlobalRoutingHelper.PopulateRoutingTables()

    my_rank = MpiInterface.GetSystemId() if distributed else 0
    server_rx: list = []
    client_rx: list = []
    if n[3].GetSystemId() == my_rank or not distributed:
        server = UdpEchoServerHelper(9)
        sapps = server.Install(n[3])
        sapps.Start(Seconds(0.0))
        sapps.Get(0).TraceConnectWithoutContext(
            "Rx",
            lambda pkt, *a: server_rx.append(
                (Simulator.NowTicks(), pkt.GetSize())
            ),
        )
    if n[0].GetSystemId() == my_rank or not distributed:
        client = UdpEchoClientHelper(last_ifc.GetAddress(1), 9)
        client.SetAttribute("MaxPackets", n_packets)
        client.SetAttribute("Interval", Seconds(interval_s))
        client.SetAttribute("PacketSize", 333)
        capps = client.Install(n[0])
        capps.Start(Seconds(0.05))
        capps.Get(0).TraceConnectWithoutContext(
            "Rx",
            lambda pkt, *a: client_rx.append(
                (Simulator.NowTicks(), pkt.GetSize())
            ),
        )

    Simulator.Stop(Seconds(2.0))
    Simulator.Run()
    events = Simulator.GetEventCount()
    impl = Simulator.GetImpl()
    windows = getattr(impl, "windows_run", 0)
    nulls = getattr(impl, "null_messages_sent", 0)
    Simulator.Destroy()
    return dict(
        server_rx=server_rx, client_rx=client_rx,
        events=events, windows=windows, nulls=nulls,
    )


def run_asymmetric_stop(rank: int, size: int):
    """Rank 1's server calls Simulator.Stop() (no delay) after its 3rd
    packet while rank 0 would happily run to its 2 s stop — the window
    protocol must close out cleanly on both sides (r4 review)."""
    from tpudes.core import Seconds, Simulator
    from tpudes.core.global_value import GlobalValue
    from tpudes.core.world import reset_world
    from tpudes.helper.applications import (
        UdpEchoClientHelper,
        UdpEchoServerHelper,
    )
    from tpudes.helper.containers import NodeContainer
    from tpudes.helper.internet import InternetStackHelper, Ipv4AddressHelper
    from tpudes.helper.point_to_point import PointToPointHelper
    from tpudes.parallel.mpi import MpiInterface

    reset_world()
    GlobalValue.Bind(
        "SimulatorImplementationType", "tpudes::DistributedSimulatorImpl"
    )
    a = NodeContainer()
    a.Create(1, system_id=0)
    b = NodeContainer()
    b.Create(1, system_id=1)
    stack = InternetStackHelper()
    stack.Install(a)
    stack.Install(b)
    p2p = PointToPointHelper()
    p2p.SetDeviceAttribute("DataRate", "5Mbps")
    p2p.SetChannelAttribute("Delay", "2ms")
    ifc = Ipv4AddressHelper("10.9.0.0", "255.255.255.0").Assign(
        p2p.Install(a.Get(0), b.Get(0))
    )
    me = MpiInterface.GetSystemId()
    got = [0]
    if me == 1:
        server = UdpEchoServerHelper(9)
        sapps = server.Install(b.Get(0))
        sapps.Start(Seconds(0.0))

        def on_rx(pkt, *args):
            got[0] += 1
            if got[0] == 3:
                Simulator.Stop()  # immediate, rank-local

        sapps.Get(0).TraceConnectWithoutContext("Rx", on_rx)
    if me == 0:
        client = UdpEchoClientHelper(ifc.GetAddress(1), 9)
        client.SetAttribute("MaxPackets", 100)
        client.SetAttribute("Interval", Seconds(0.05))
        client.SetAttribute("PacketSize", 64)
        client.Install(a.Get(0)).Start(Seconds(0.1))
    Simulator.Stop(Seconds(2.0))
    Simulator.Run()
    out = dict(rank=me, server_rx=got[0], now=Simulator.NowTicks())
    Simulator.Destroy()
    return out


def run_bursty_window(rank: int, size: int, n_packets: int = 300):
    """One window carries ``n_packets`` cross-rank messages (far past
    the ~64 KiB OS pipe buffer) — the spooled threaded flush must not
    deadlock (r4 review)."""
    from tpudes.core import Seconds, Simulator
    from tpudes.core.global_value import GlobalValue
    from tpudes.core.world import reset_world
    from tpudes.helper.applications import UdpServerHelper
    from tpudes.helper.containers import NodeContainer
    from tpudes.helper.internet import InternetStackHelper, Ipv4AddressHelper
    from tpudes.helper.point_to_point import PointToPointHelper
    from tpudes.models.applications import UdpClient
    from tpudes.parallel.mpi import MpiInterface

    reset_world()
    GlobalValue.Bind(
        "SimulatorImplementationType", "tpudes::DistributedSimulatorImpl"
    )
    a = NodeContainer()
    a.Create(1, system_id=0)
    b = NodeContainer()
    b.Create(1, system_id=1)
    stack = InternetStackHelper()
    stack.Install(a)
    stack.Install(b)
    p2p = PointToPointHelper()
    p2p.SetDeviceAttribute("DataRate", "1Gbps")
    p2p.SetChannelAttribute("Delay", "5ms")
    ifc = Ipv4AddressHelper("10.8.0.0", "255.255.255.0").Assign(
        p2p.Install(a.Get(0), b.Get(0))
    )
    me = MpiInterface.GetSystemId()
    rx = [0]
    if me == 1:
        server = UdpServerHelper(9)
        sapps = server.Install(b.Get(0))
        sapps.Start(Seconds(0.0))
        sapps.Get(0).TraceConnectWithoutContext(
            "Rx", lambda *a_: rx.__setitem__(0, rx[0] + 1)
        )
    if me == 0:
        # all packets burst within one 5 ms lookahead window; 10 µs
        # spacing > the ~4.3 µs serialization so the tx queue never
        # overflows (the transport, not DropTail, is under test)
        client = UdpClient(
            RemoteAddress=str(ifc.GetAddress(1)),
            RemotePort=9,
            MaxPackets=n_packets,
            Interval=Seconds(0.00001),
            PacketSize=512,
        )
        a.Get(0).AddApplication(client)
        client.SetStartTime(Seconds(0.001))
    Simulator.Stop(Seconds(0.5))
    Simulator.Run()
    # the invariant: tpudes itself never pulls the jax-heavy engine
    # submodules into a distributed rank
    import sys as _sys

    out = dict(
        rank=me, rx=rx[0],
        heavy_loaded=any(
            m in _sys.modules
            for m in ("tpudes.parallel.kernels", "tpudes.parallel.mesh")
        ),
    )
    Simulator.Destroy()
    return out


def run_chain_three_ranks(rank: int, size: int):
    """6-node chain over 3 ranks (2 nodes each), echo end-to-end."""
    from tpudes.core import Seconds, Simulator
    from tpudes.core.global_value import GlobalValue
    from tpudes.core.world import reset_world
    from tpudes.helper.applications import (
        UdpEchoClientHelper,
        UdpEchoServerHelper,
    )
    from tpudes.helper.containers import NodeContainer
    from tpudes.helper.internet import InternetStackHelper, Ipv4AddressHelper
    from tpudes.helper.point_to_point import PointToPointHelper
    from tpudes.models.internet.global_routing import Ipv4GlobalRoutingHelper
    from tpudes.parallel.mpi import MpiInterface

    reset_world()
    GlobalValue.Bind(
        "SimulatorImplementationType", "tpudes::DistributedSimulatorImpl"
    )
    nodes = []
    for r in range(3):
        c = NodeContainer()
        c.Create(2, system_id=r)
        nodes += [c.Get(0), c.Get(1)]
    stack = InternetStackHelper()
    stack.SetRoutingHelper(Ipv4GlobalRoutingHelper())
    stack.Install(nodes)
    p2p = PointToPointHelper()
    p2p.SetDeviceAttribute("DataRate", "5Mbps")
    p2p.SetChannelAttribute("Delay", "1ms")
    addr = Ipv4AddressHelper("10.2.0.0", "255.255.255.0")
    last_ifc = None
    for i in range(5):
        devs = p2p.Install(nodes[i], nodes[i + 1])
        last_ifc = addr.Assign(devs)
        addr.NewNetwork()
    Ipv4GlobalRoutingHelper.PopulateRoutingTables()

    me = MpiInterface.GetSystemId()
    server_rx: list = []
    if nodes[5].GetSystemId() == me:
        server = UdpEchoServerHelper(9)
        sapps = server.Install(nodes[5])
        sapps.Start(Seconds(0.0))
        sapps.Get(0).TraceConnectWithoutContext(
            "Rx",
            lambda pkt, *a: server_rx.append(Simulator.NowTicks()),
        )
    if nodes[0].GetSystemId() == me:
        client = UdpEchoClientHelper(last_ifc.GetAddress(1), 9)
        client.SetAttribute("MaxPackets", 3)
        client.SetAttribute("Interval", Seconds(0.2))
        client.SetAttribute("PacketSize", 100)
        client.Install(nodes[0]).Start(Seconds(0.1))
    Simulator.Stop(Seconds(1.5))
    Simulator.Run()
    Simulator.Destroy()
    return dict(server_rx=server_rx)


# --- ISSUE-9: multi-process mesh workers (launch_process_mesh targets) ----


def procmesh_devices(pmesh):
    """Pin the jax.distributed invariant: the global device count sums
    every member's local devices while local stays local."""
    import jax

    return dict(
        process_id=pmesh.process_id,
        num_processes=pmesh.num_processes,
        global_devices=jax.device_count(),
        local_devices=jax.local_device_count(),
        backend=jax.default_backend(),
    )


def procmesh_replica_slice(pmesh, n_replicas: int):
    """Run this process's contiguous replica block of a jittered wired
    program at the GLOBAL offset (the fold_in purity contract)."""
    import jax

    from tpudes.parallel.wired import run_wired, wired_chain

    lo, hi = pmesh.slice_bounds(n_replicas)
    prog = wired_chain(n_links=4, n_flows=2, n_slots=300, jitter_slots=3)
    out = run_wired(prog, jax.random.key(11), replicas=hi - lo,
                    replica_offset=lo)
    return dict(lo=lo, hi=hi, deliver=out["deliver_slot"])


def procmesh_serving_router(pmesh, n_studies: int):
    """Rank 0 runs a StudyServer with a ProcessRouter over the member
    pipes; members run serve_studies.  Returns rank 0's routed results
    + solo references (computed in the SAME process so compile caches
    are warm), members' served counts."""
    import dataclasses

    import jax
    import numpy as np

    from tpudes.parallel.mpi import MpiInterface
    from tpudes.parallel.programs import toy_bss_program
    from tpudes.serving import ProcessRouter, StudyServer, serve_studies

    if pmesh.process_id != 0:
        return dict(served=serve_studies(MpiInterface._conns[0]))

    from tpudes.parallel.replicated import run_replicated_bss

    prog = toy_bss_program(n_sta=4, sim_end_us=40_000)
    key = jax.random.PRNGKey(3)
    horizons = [40_000 + 2_000 * i for i in range(n_studies)]
    router = ProcessRouter(MpiInterface._conns)
    server = StudyServer(max_batch=8, router=router, start=False)
    handles = [
        server.submit_study(
            "bss", dataclasses.replace(prog, sim_end_us=h), key, 2,
            tenant=f"t{i}",
        )
        for i, h in enumerate(horizons)
    ]
    server.pump(force=True)
    results = [h.result(timeout=240) for h in handles]
    server.close()
    equal = True
    for h, res in zip(horizons, results):
        solo = run_replicated_bss(
            dataclasses.replace(prog, sim_end_us=h), 2, key
        )
        for k in solo:
            if not np.array_equal(np.asarray(res[k]), np.asarray(solo[k])):
                equal = False
    return dict(
        routed_batches=router.routed_batches,
        routed_points=router.routed_points,
        equal=equal,
    )
