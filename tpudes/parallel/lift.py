"""Scenario lifting at the SimulatorImplementationType seam.

The north-star contract (BASELINE.json): a scenario script opts into the
TPU engine with ONE GlobalValue flip —

    python examples/wifi-bss.py \
        --SimulatorImplementationType=tpudes::JaxSimulatorImpl \
        --JaxReplicas=512

No per-example plumbing: when ``JaxSimulatorImpl.Run`` sees
``JaxReplicas > 0`` it walks the live object graph (NodeList), finds a
scenario shape a registered lowering can represent, lowers it to a
device program (replicated.py / lte_sm.py), and runs every replica on
the accelerator at once.  Graphs no lowering can faithfully represent
fall back to the windowed scalar engine with a loud warning — never a
silent mis-lowering (the round-2 rule).

Reference parity: upstream has no analog — this is the TPU-native
replacement for running 512 separate ns-3 processes; the seam itself is
simulator-impl.{h,cc}'s ObjectFactory (SURVEY.md §1, §7 step 7).
"""

from __future__ import annotations

from tpudes.obs.spans import OWN, span
from tpudes.parallel.replicated import UnliftableScenarioError
from tpudes.parallel.runtime import RUNTIME


def _iter_nodes():
    from tpudes.network.node import NodeList

    for i in range(NodeList.GetNNodes()):
        yield NodeList.GetNode(i)


def _discover_bss(sim_end_s: float):
    """Find a BSS shape in the global object graph and lower it: an
    infrastructure BSS (one AP, N STAs, echo clients), or an IBSS (ad
    hoc stations, one of them a PacketSink's node, OnOff senders to
    it on the others)."""
    from tpudes.models.applications import UdpEchoClient
    from tpudes.models.wifi.device import WifiNetDevice
    from tpudes.models.wifi.mac import AdhocWifiMac, ApWifiMac, StaWifiMac
    from tpudes.parallel.replicated import lower_bss

    aps, stas, adhoc, clients, stray_clients = [], [], [], [], 0
    bss_nodes = set()
    for node in _iter_nodes():
        for d in range(node.GetNDevices()):
            dev = node.GetDevice(d)
            if isinstance(dev, WifiNetDevice):
                mac = dev.GetMac()
                if isinstance(mac, ApWifiMac):
                    aps.append(dev)
                    bss_nodes.add(node)
                elif isinstance(mac, StaWifiMac):
                    stas.append(dev)
                    bss_nodes.add(node)
                elif isinstance(mac, AdhocWifiMac):
                    adhoc.append(dev)
    if adhoc and not aps and not stas:
        sink, senders, apps = _ibss_shape(adhoc)
        stas, aps, clients = senders, [sink], apps
    else:
        for node in _iter_nodes():
            for a in range(node.GetNApplications()):
                app = node.GetApplication(a)
                if isinstance(app, UdpEchoClient):
                    if node in bss_nodes:
                        clients.append(app)
                    else:
                        stray_clients += 1
    if len(aps) != 1 or not stas:
        raise UnliftableScenarioError(
            f"not an infrastructure BSS (found {len(aps)} APs, "
            f"{len(stas)} STAs) nor an IBSS with one PacketSink"
        )
    if stray_clients:
        # a client on a non-BSS node (mixed wired/wireless topology)
        # would be silently dropped by the lowering — refuse instead
        raise UnliftableScenarioError(
            f"{stray_clients} echo client(s) live on non-BSS nodes; the "
            "replica axis models only the BSS traffic"
        )
    from tpudes.core.global_value import GlobalValue

    prog = lower_bss(
        stas, aps[0], clients, sim_end_s,
        geom_stride=int(GlobalValue.GetValue("JaxGeomStride")),
    )
    prog = _attach_bss_traffic(prog)
    return "bss", prog, lambda: None


def _ibss_shape(adhoc):
    """``(sink device, other devices, OnOff apps)`` of an IBSS whose
    traffic is OnOff senders into one PacketSink; anything else on
    these nodes (another app, a sender to elsewhere, a second sink, a
    node outside the IBSS that sends) refuses.  A one-packet echo
    client that starts before every sender is ARP warm-up (upstream's
    wifi-hidden-terminal sends one to resolve ARP before its flows),
    skipped as the lowering skips the ARP it resolves."""
    from tpudes.models.applications import (
        OnOffApplication,
        PacketSink,
        UdpEchoClient,
    )
    from tpudes.models.internet.ipv4 import Ipv4L3Protocol

    ibss = {dev.GetNode(): dev for dev in adhoc}
    if len(ibss) != len(adhoc):
        raise UnliftableScenarioError("a node with two ad hoc devices")
    sinks, senders, warm_up = [], [], []
    for node in _iter_nodes():
        for a in range(node.GetNApplications()):
            app = node.GetApplication(a)
            if isinstance(app, PacketSink) and node in ibss:
                sinks.append(node)
            elif isinstance(app, OnOffApplication) and node in ibss:
                senders.append(app)
            elif (
                isinstance(app, UdpEchoClient) and node in ibss
                and int(app.max_packets) == 1
            ):
                warm_up.append(app)
            else:
                raise UnliftableScenarioError(
                    f"{type(app).__name__} on node {node.GetId()}: an IBSS "
                    "lifts with OnOff senders into one PacketSink only"
                )
    first = min((a.start_time.ticks for a in senders), default=0)
    if any(a.start_time.ticks >= first for a in warm_up):
        raise UnliftableScenarioError(
            "an echo client that starts with the senders is traffic, "
            "not ARP warm-up"
        )
    if len(set(sinks)) != 1:
        raise UnliftableScenarioError(
            f"an IBSS needs one PacketSink node (found {len(set(sinks))})"
        )
    sink = sinks[0]
    ipv4 = sink.GetObject(Ipv4L3Protocol)
    own = {
        a.GetLocal().addr
        for iface in (ipv4.interfaces[1:] if ipv4 is not None else [])
        for a in iface.addresses
    }
    for app in senders:
        remote = getattr(app.remote, "GetIpv4", None)
        if remote is None or remote().addr not in own:
            raise UnliftableScenarioError(
                f"OnOff sender on node {app.GetNode().GetId()} does not "
                "send to the PacketSink's node"
            )
    others = [dev for node, dev in ibss.items() if node is not sink]
    return ibss[sink], others, senders


def _attach_bss_traffic(prog):
    """The ISSUE-14 one-flip seam: ``--JaxTrafficModel=<model>`` swaps
    the lowered BSS program's STA arrivals onto the device traffic
    stage at the echo apps' mean rate (the AP's beacon process stays
    cbr); ``off`` returns the program untouched — the bit-identical
    legacy compile."""
    import dataclasses

    import numpy as np

    from tpudes.core.global_value import GlobalValue

    model = str(GlobalValue.GetValue("JaxTrafficModel"))
    if model == "off":
        return prog
    from tpudes.parallel.replicated import per_node_medium

    if per_node_medium(prog):
        raise UnliftableScenarioError(
            f"JaxTrafficModel={model}: the traffic stage rides the "
            "single-medium BSS step only"
        )
    from tpudes.traffic import TrafficProgram

    seed = int(GlobalValue.GetValue("JaxTrafficSeed"))
    n, horizon = prog.n, prog.sim_end_us
    sta_iv = prog.interval_us[1:].astype(np.int64)
    rate = float(
        np.mean(np.where(sta_iv >= 2**29, 0.0, 1e6 / np.maximum(sta_iv, 1)))
    )
    if model == "cbr":
        tp = TrafficProgram.cbr(prog.start_us, prog.interval_us)
    elif model == "mmpp":
        tp = TrafficProgram.mmpp(
            n, rate, horizon_us=horizon, epoch_s=0.05,
            start_us=prog.start_us, tr_seed=seed,
        )
    elif model == "onoff":
        tp = TrafficProgram.onoff(
            n, rate / 0.4, horizon_us=horizon, on=(1.5, 0.05, 0.5),
            off_mean_s=0.15, start_us=prog.start_us, tr_seed=seed,
        )
    elif model == "trace":
        # a deterministic synthetic trace at the apps' mean rate (the
        # stand-in until a pcap/CSV ingester lands — ROADMAP item 4
        # remainder).  The span clamps at 0: an app starting past the
        # horizon gets a constant (never-firing) row, not a descending
        # one trace_replay would reject
        k = max(4, int(rate * (horizon - int(prog.start_us[1:].min()))
                       / 1e6))
        span = np.maximum(
            horizon - prog.start_us[:, None].astype(np.int64), 0
        )
        grid = np.sort(
            (np.linspace(0.02, 0.98, k)[None, :] * span
             + prog.start_us[:, None]).astype(np.int64),
            axis=1,
        )
        tp = TrafficProgram.trace_replay(grid)
    else:
        raise ValueError(
            f"JaxTrafficModel={model!r}: pick off|cbr|mmpp|onoff|trace"
        )
    tp = tp.with_cbr_rows(
        np.arange(n) == 0, int(prog.interval_us[0]),
        int(prog.start_us[0]),
    )
    return dataclasses.replace(prog, traffic=tp)


def _discover_lte_sm(sim_end_s: float):
    """Find a full-buffer LTE shape (eNBs with a TTI controller) and
    lower it to the device-resident SM engine."""
    from types import SimpleNamespace

    from tpudes.models.lte.device import LteEnbNetDevice
    from tpudes.parallel.lte_sm import (
        UnliftableLteScenarioError,
        lower_lte_sm,
    )

    controller = None
    for node in _iter_nodes():
        for d in range(node.GetNDevices()):
            dev = node.GetDevice(d)
            if isinstance(dev, LteEnbNetDevice) and dev.controller is not None:
                controller = dev.controller
                break
        if controller is not None:
            break
    if controller is None:
        raise UnliftableScenarioError("no LTE eNB devices in the graph")
    try:
        from tpudes.core.global_value import GlobalValue

        prog = lower_lte_sm(
            SimpleNamespace(controller=controller), sim_end_s,
            geom_stride=int(GlobalValue.GetValue("JaxGeomStride")),
        )
    except UnliftableLteScenarioError as e:
        raise UnliftableScenarioError(str(e)) from e

    def commit():
        # the controller's own TTI events must not ALSO run the scenario;
        # armed only after the device run succeeds, so a failed run (OOM,
        # backend error) leaves the host path fully functional
        controller.lifted = True

    return "lte_sm", prog, commit


def _discover_dumbbell(sim_end_s: float):
    """Find a TCP dumbbell (bulk flows over one router-router
    bottleneck) and lower it to the packet-slot program."""
    from tpudes.parallel.tcp_dumbbell import (
        UnliftableDumbbellError,
        lower_dumbbell,
    )

    try:
        prog = lower_dumbbell(sim_end_s)
    except UnliftableDumbbellError as e:
        raise UnliftableScenarioError(str(e)) from e
    return "dumbbell", prog, lambda: None


def _discover_as_flows(sim_end_s: float):
    """Find a routed p2p topology carrying sparse CBR UDP flows (the
    config-#5 shape) and lower it to the flow-level device engine."""
    from tpudes.parallel.as_flows import UnliftableAsError, lower_as_flows

    try:
        prog = lower_as_flows(sim_end_s)
    except UnliftableAsError as e:
        raise UnliftableScenarioError(str(e)) from e
    return "as_flows", prog, lambda: None


#: discovery order: most specific first (as_flows last — it accepts the
#: most generic shape, any routed p2p graph with CBR UDP clients)
LOWERINGS = [_discover_lte_sm, _discover_dumbbell, _discover_bss, _discover_as_flows]


def _run_bss(prog, key, replicas, mesh, **engine_kwargs):
    from tpudes.parallel.replicated import run_replicated_bss

    return run_replicated_bss(
        prog, replicas, key, mesh=mesh, **engine_kwargs
    )


def _entry(module: str, name: str):
    """``run_*(prog, key, replicas, mesh, **kw)`` of an engine module,
    imported at the call (a lifted run loads only its own engine)."""

    def run(prog, key, replicas, mesh, **engine_kwargs):
        import importlib

        return getattr(importlib.import_module(module), name)(
            prog, key, replicas=replicas, mesh=mesh, **engine_kwargs
        )

    return run


#: kind (what a lowering of LOWERINGS returns) -> the engine's entry as
#: ``run(prog, key, replicas, mesh, **engine_kwargs)``
ENGINES = {
    "bss": _run_bss,        # run_replicated_bss takes (prog, replicas, key)
    "lte_sm": _entry("tpudes.parallel.lte_sm", "run_lte_sm"),
    "dumbbell": _entry("tpudes.parallel.tcp_dumbbell", "run_tcp_dumbbell"),
    "as_flows": _entry("tpudes.parallel.as_flows", "run_as_flows"),
}


def lift(sim_end_s: float):
    """Try every registered lowering; returns ``(kind, program, commit)``
    — ``commit()`` is called by the engine after the device run succeeds
    (it disarms any host-side duplicate of the scenario) — or raises
    UnliftableScenarioError with every reason collected."""
    reasons = []
    with span("lift"):
        for discover in LOWERINGS:
            try:
                return discover(sim_end_s)
            except UnliftableScenarioError as e:
                reasons.append(f"{discover.__name__}: {e}")
    raise UnliftableScenarioError("; ".join(reasons))


def lifted_key():
    """The PRNG key a lifted run draws from when none is passed: a pure
    function of the scenario's ``RngSeed`` / ``RngRun`` globals, so the
    same script arguments reproduce the same replicas."""
    import jax

    from tpudes.core.rng import RngSeedManager

    return jax.random.PRNGKey(
        (RngSeedManager.GetSeed() * 2654435761 + RngSeedManager.GetRun())
        & 0x7FFFFFFF
    )


def run_lifted(kind: str, prog, replicas: int, key=None, mesh=None,
               **engine_kwargs):
    """Execute a lifted program on the replica axis.

    ``mesh=None`` auto-selects: a 1-axis replica mesh over all local
    devices when more than one is visible and divides ``replicas``.
    ``engine_kwargs`` pass through to the engine's ``run_*`` entry
    (its chunk argument, ``block=False``, …).  Returns the program's
    per-replica outcome dict (see run_replicated_bss / run_lte_sm).
    """
    # `launch`: from here until the EngineFuture exists.  The future's
    # constructor closes it (so a blocking caller's wait and fetch are
    # never inside); the finally only matters when an engine raised
    # before it made one
    launch = span("launch", OWN, kind=kind, replicas=int(replicas)).open()
    try:
        import jax

        # for tpudes.obs.explain.replay(): references the caller holds anyway
        RUNTIME.last_lifted = (kind, prog, replicas, key, mesh, engine_kwargs)

        if key is None:
            key = lifted_key()
        if mesh is None:
            import math

            n_dev = len(jax.devices())
            n_use = math.gcd(replicas, n_dev)
            if n_use > 1:
                from tpudes.parallel.mesh import replica_mesh

                mesh = replica_mesh(n_use)
            if 1 < n_use < n_dev or (n_use == 1 < n_dev and replicas > 1):
                import warnings

                warnings.warn(
                    f"JaxReplicas={replicas} is not divisible by the "
                    f"{n_dev} visible devices; running on {n_use} — "
                    f"pick a multiple of {n_dev} to use the whole mesh",
                    RuntimeWarning,
                    stacklevel=2,
                )
        run = ENGINES.get(kind)
        if run is None:
            raise ValueError(f"unknown lifted program kind {kind!r}")
        return run(prog, key, replicas, mesh, **engine_kwargs)
    finally:
        launch.close()
