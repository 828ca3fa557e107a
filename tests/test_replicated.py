"""Replica-axis engine vs the sequential DES (SURVEY.md §4: statistical
— not bitwise — parity; §7 step 7 "prototype early").

The scalar engine is the per-event oracle: the same BSS config is run
(a) K times sequentially with distinct RngRun, (b) once with R replicas
through the vectorized event-stepped program lowered from the SAME
object graph.  Delivery-count distributions must agree.
"""

import math

import jax
import numpy as np
import pytest

from tpudes.core import Seconds, Simulator
from tpudes.core.rng import RngSeedManager
from tpudes.helper.applications import UdpEchoClientHelper, UdpEchoServerHelper
from tpudes.helper.containers import NetDeviceContainer, NodeContainer
from tpudes.helper.internet import InternetStackHelper, Ipv4AddressHelper
from tpudes.models.mobility import ListPositionAllocator, MobilityHelper, Vector
from tpudes.models.wifi import (
    WifiHelper,
    WifiMacHelper,
    YansWifiChannelHelper,
    YansWifiPhyHelper,
)
from tpudes.parallel.replicated import lower_bss, run_replicated_bss

N_STAS = 5
SIM_TIME = 1.8
RADIUS = 32.0  # lossy at 54 Mbps under the corrected NIST 64-QAM BER
               # (snr/21): per-attempt PSR well below 1, replicas diverge


def _positions():
    pos = [(0.0, 0.0, 0.0)]
    for i in range(N_STAS):
        a = 2 * math.pi * i / N_STAS
        pos.append((RADIUS * math.cos(a), RADIUS * math.sin(a), 0.0))
    return pos


def _reset_world():
    from tpudes.core.world import reset_world

    reset_world()


def _build_bss():
    """The wifi-bss.py topology with deterministic positions.  Returns
    (sta_devices, ap_device, clients, server_rx_counter)."""
    nodes = NodeContainer()
    nodes.Create(N_STAS + 1)

    mobility = MobilityHelper()
    alloc = ListPositionAllocator()
    for x, y, z in _positions():
        alloc.Add(Vector(x, y, z))
    mobility.SetPositionAllocator(alloc)
    mobility.SetMobilityModel("tpudes::ConstantPositionMobilityModel")
    mobility.Install(nodes)

    channel = YansWifiChannelHelper.Default().Create()
    phy = YansWifiPhyHelper()
    phy.SetChannel(channel)
    wifi = WifiHelper()
    wifi.SetRemoteStationManager(
        "tpudes::ConstantRateWifiManager", DataMode="OfdmRate54Mbps"
    )

    ap_mac = WifiMacHelper()
    ap_mac.SetType("tpudes::ApWifiMac")
    ap_devices = wifi.Install(phy, ap_mac, [nodes.Get(0)])
    sta_mac = WifiMacHelper()
    sta_mac.SetType("tpudes::StaWifiMac")
    sta_devices = wifi.Install(
        phy, sta_mac, [nodes.Get(i) for i in range(1, N_STAS + 1)]
    )

    stack = InternetStackHelper()
    stack.Install(nodes)
    address = Ipv4AddressHelper()
    address.SetBase("10.1.3.0", "255.255.255.0")
    devices = NetDeviceContainer()
    devices.Add(ap_devices.Get(0))
    for i in range(N_STAS):
        devices.Add(sta_devices.Get(i))
    interfaces = address.Assign(devices)

    server = UdpEchoServerHelper(9)
    server_apps = server.Install(nodes.Get(0))
    server_apps.Start(Seconds(0.4))
    server_apps.Stop(Seconds(SIM_TIME))
    rx = [0]
    server_apps.Get(0).TraceConnectWithoutContext(
        "Rx", lambda pkt, *a: rx.__setitem__(0, rx[0] + 1)
    )

    clients = []
    for i in range(N_STAS):
        helper = UdpEchoClientHelper(interfaces.GetAddress(0), 9)
        helper.SetAttribute("MaxPackets", 1_000_000)
        helper.SetAttribute("Interval", Seconds(0.1))
        helper.SetAttribute("PacketSize", 512)
        apps = helper.Install(nodes.Get(1 + i))
        apps.Start(Seconds(1.0 + 0.001 * i))
        apps.Stop(Seconds(SIM_TIME))
        clients.append(apps.Get(0))
    return sta_devices, ap_devices.Get(0), clients, rx


def _des_delivery_counts(runs):
    counts = []
    for run in range(1, runs + 1):
        _reset_world()
        RngSeedManager.SetRun(run)
        _, _, _, rx = _build_bss()
        Simulator.Stop(Seconds(SIM_TIME))
        Simulator.Run()
        counts.append(rx[0])
    _reset_world()
    return np.array(counts, dtype=np.float64)


def _lowered_program():
    _reset_world()
    sta_devices, ap_device, clients, _ = _build_bss()
    prog = lower_bss(
        [sta_devices.Get(i) for i in range(N_STAS)], ap_device, clients, SIM_TIME
    )
    _reset_world()
    return prog


def test_lowering_reads_object_graph():
    prog = _lowered_program()
    assert prog.n == N_STAS + 1
    np.testing.assert_allclose(prog.positions, np.array(_positions()), atol=1e-5)
    # 54 Mbps ConstantRate → mode 7; payload 512 → PSDU 512+64
    assert prog.data_mode_idx == 7
    assert prog.data_bytes == 512 + 64
    # clients: start 1.0 s + (i-1) ms, interval 100 ms, stop at SIM_TIME
    assert prog.start_us[1] == 1_000_000
    assert prog.start_us[2] == 1_001_000
    assert prog.interval_us[1] == 100_000
    assert prog.stop_us[1] == int(SIM_TIME * 1e6)
    # AP slot carries the beacon schedule
    assert prog.interval_us[0] == 102_400


def test_statistical_parity_with_sequential_engine():
    des = _des_delivery_counts(10)
    prog = _lowered_program()
    out = run_replicated_bss(prog, 256, jax.random.PRNGKey(42))
    assert out["all_done"]
    rep = np.asarray(out["srv_rx"], dtype=np.float64)

    # per-STA offered load: 8 sends each (1.0→1.8 s, 0.1 s interval)
    offered = N_STAS * 8
    assert 0 < rep.mean() <= offered
    assert 0 < des.mean() <= offered

    # distribution-level agreement: means within 3× the combined spread
    # of the two estimators (plus 1 frame of timing-model slack)
    sem = math.sqrt(
        des.var(ddof=1) / len(des) + rep.var(ddof=1) / len(rep)
    )
    assert abs(des.mean() - rep.mean()) <= 3.0 * sem + 1.5, (
        f"DES mean {des.mean():.2f} vs replicated mean {rep.mean():.2f} "
        f"(sem {sem:.2f}; des {des}, rep mean/std {rep.mean():.2f}/{rep.std():.2f})"
    )


def test_same_key_is_deterministic():
    prog = _lowered_program()
    a = run_replicated_bss(prog, 32, jax.random.PRNGKey(7))
    b = run_replicated_bss(prog, 32, jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(a["srv_rx"]), np.asarray(b["srv_rx"]))
    c = run_replicated_bss(prog, 32, jax.random.PRNGKey(8))
    assert not np.array_equal(np.asarray(a["srv_rx"]), np.asarray(c["srv_rx"]))


def test_mesh_sharded_matches_single_device():
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = Mesh(np.array(devs[:8]), ("replica",))
    prog = _lowered_program()
    plain = run_replicated_bss(prog, 64, jax.random.PRNGKey(3))
    sharded = run_replicated_bss(prog, 64, jax.random.PRNGKey(3), mesh=mesh)
    assert sharded["all_done"]
    np.testing.assert_array_equal(
        np.asarray(plain["srv_rx"]), np.asarray(sharded["srv_rx"])
    )
    np.testing.assert_array_equal(
        np.asarray(plain["cli_rx"]), np.asarray(sharded["cli_rx"])
    )


def test_echo_replies_bounded_by_requests():
    prog = _lowered_program()
    out = run_replicated_bss(prog, 64, jax.random.PRNGKey(5))
    cli = np.asarray(out["cli_rx"]).sum(axis=1)
    srv = np.asarray(out["srv_rx"])
    assert (cli <= srv).all()


class TestShortHorizonGuard:
    """lower_bss skips association/ARP/ADDBA warm-up; a horizon within
    ~5x of that budget must warn loudly (0.2 s), a comfortable one must
    stay silent (1.6 s)."""

    def _lower_at(self, sim_end_s):
        _reset_world()
        sta_devices, ap_device, clients, _ = _build_bss()
        prog = lower_bss(
            [sta_devices.Get(i) for i in range(N_STAS)],
            ap_device, clients, sim_end_s,
        )
        _reset_world()
        return prog

    def test_short_horizon_warns(self):
        with pytest.warns(UserWarning, match="warm-up"):
            self._lower_at(0.2)

    def test_comfortable_horizon_is_silent(self):
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("error")
            prog = self._lower_at(1.6)
        assert prog.sim_end_us == 1_600_000


# --------------------------------------------------------------------------
# the loop's predicate rides its carry (build_bss_advance): bit-identity
# against the loop whose condition searches the state again
# --------------------------------------------------------------------------


def _recomputing_advance(prog, r, obs=False, n_cfg=None, sweep="horizon"):
    """The BSS loop in the shape it had before its predicate rode the
    carry, assembled from ``build_bss_step``'s exported pieces: the
    condition runs ``pending`` on the state, the body is ``step_fn``
    alone."""
    import jax.numpy as jnp

    from tpudes.parallel.replicated import build_bss_step

    init, pending, step_fn = build_bss_step(prog, r, obs=obs)

    def advance(s, k, max_steps, sim_end, geom=None, tr=None):
        tr_keys = (
            step_fn.traffic_keys(k)
            if step_fn.traffic_keys is not None else None
        )
        out = jax.lax.while_loop(
            lambda st: jnp.logical_and(
                st["step"] < max_steps, jnp.any(pending(st, sim_end))
            ),
            lambda st: step_fn(st, k, sim_end, geom, tr, tr_keys),
            s,
        )
        return out, pending(out, sim_end)

    if n_cfg is not None:
        advance = jax.vmap(
            advance,
            in_axes=(
                (0, None, None, 0, None, None) if sweep == "horizon"
                else (0, None, None, None, None, 0)
            ),
        )
    return init, advance


def _carry_case(case):
    """``(prog, kwargs of build_bss_advance, n_cfg, sim_end, geom, tr,
    step budgets of the successive calls)`` of one program variant."""
    import dataclasses

    from tpudes.parallel.programs import toy_bss_program, toy_traffic_points
    from tpudes.traffic import TrafficProgram

    prog = toy_bss_program(n_sta=3, sim_end_us=120_000)
    kw, n_cfg, geom, tr, budgets = {}, None, None, None, (10_000,)
    sim_end = np.int32(prog.sim_end_us)
    if case == "ampdu":
        # every 2 ms a request: queues build and winners aggregate
        prog = dataclasses.replace(
            prog, max_mpdus=8, subframe_bytes=580,
            interval_us=np.where(
                np.arange(prog.n) == 0, prog.interval_us, 2_000
            ).astype(np.int32),
        )
    elif case == "mobile":
        from tpudes.ops.mobility import MobilityProgram

        prog = dataclasses.replace(
            prog,
            mobility=MobilityProgram.constant_velocity(
                np.asarray(prog.positions, np.float64),
                np.tile([3.0, -2.0, 0.0], (prog.n, 1)),
            ),
            geom_stride=4,
        )
        geom = dict(stride=np.int32(4), **prog.mobility.operands())
    elif case == "traffic":
        prog = dataclasses.replace(
            prog,
            traffic=TrafficProgram.mmpp(
                prog.n, 90.0, horizon_us=prog.sim_end_us, epoch_s=0.05,
                start_us=prog.start_us, tr_seed=1,
            ),
        )
        tr = prog.traffic.operands()
    elif case == "obs":
        kw = dict(obs=True)
    elif case == "horizon_sweep":
        n_cfg = 3
        kw = dict(n_cfg=n_cfg, sweep="horizon")
        sim_end = np.asarray([40_000, 120_000, 75_000], np.int32)
    elif case == "traffic_sweep":
        from tpudes.traffic.device import stack_traffic_operands

        pts = toy_traffic_points(
            prog.n, prog.sim_end_us, start_us=prog.start_us,
            beacon=(int(prog.interval_us[0]), int(prog.start_us[0])),
        )[:4]
        prog = dataclasses.replace(prog, traffic=pts[0])
        n_cfg = len(pts)
        kw = dict(n_cfg=n_cfg, sweep="traffic")
        tr = stack_traffic_operands(pts)
    elif case == "chunked":
        # three launches of the loop: the carry re-enters twice with
        # events still pending, then runs out
        budgets = (7, 19, 10_000)
    else:
        assert case == "legacy"
    return prog, kw, n_cfg, sim_end, geom, tr, budgets


@pytest.mark.parametrize(
    "case",
    ["legacy", "ampdu", "mobile", "traffic", "obs", "horizon_sweep",
     "traffic_sweep", "chunked"],
)
def test_carried_predicate_is_bit_identical_to_the_recomputing_loop(case):
    from tpudes.parallel.replicated import build_bss_advance
    from tpudes.parallel.runtime import stack_axis

    R = 4
    prog, kw, n_cfg, sim_end, geom, tr, budgets = _carry_case(case)
    init, _, fn = build_bss_advance(prog, R, **kw)
    ref_init, ref_fn = _recomputing_advance(prog, R, **kw)
    key = jax.random.PRNGKey(9)
    new, ref = jax.jit(fn), jax.jit(ref_fn)
    s_new = stack_axis(init(), n_cfg)
    s_ref = stack_axis(ref_init(), n_cfg)
    assert sorted(s_new) == sorted(s_ref)
    for budget in budgets:
        s_new, p_new, _ = new(s_new, key, np.int32(budget), sim_end, geom, tr)
        s_ref, p_ref = ref(s_ref, key, np.int32(budget), sim_end, geom, tr)
        assert sorted(s_new) == sorted(s_ref)
        for name in s_ref:
            np.testing.assert_array_equal(
                np.asarray(s_new[name]), np.asarray(s_ref[name]),
                err_msg=f"{case}: {name} after {budget}",
            )
        np.testing.assert_array_equal(np.asarray(p_new), np.asarray(p_ref))
    # the case ran to its horizon and did some work on the way
    assert not np.asarray(p_ref).any()
    assert int(np.sum(np.asarray(s_ref["tx_data"]))) > 0
    if case == "chunked":
        assert int(s_ref["step"]) > 19
    if case == "ampdu":
        assert int(np.sum(np.asarray(s_ref["tx_mpdus"]))) > int(
            np.sum(np.asarray(s_ref["tx_data"]))
        )


# --------------------------------------------------------------------------
# the step's keys come from runtime.step_keys (vector operands):
# bit-identity against the loop that folds the counter as a scalar
# --------------------------------------------------------------------------


CARRY_CASES = [
    "legacy", "ampdu", "mobile", "traffic", "obs", "horizon_sweep",
    "traffic_sweep", "chunked",
]


@pytest.mark.parametrize("case", CARRY_CASES)
def test_vector_step_keys_are_bit_identical_to_the_scalar_fold(
    case, scalar_step_keys
):
    """``build_bss_advance`` as it is against the same builder with
    ``runtime.step_keys`` replaced by the scalar fold the step did by
    hand: every carry leaf, the pending vector and the chunk metrics,
    after every call."""
    from tpudes.parallel.replicated import build_bss_advance
    from tpudes.parallel.runtime import stack_axis

    prog, kw, n_cfg, sim_end, geom, tr, budgets = _carry_case(case)
    key = jax.random.PRNGKey(9)

    def run():
        init, _, fn = build_bss_advance(prog, 4, **kw)
        fn, s, outs = jax.jit(fn), stack_axis(init(), n_cfg), []
        for budget in budgets:
            outs.append(fn(s, key, np.int32(budget), sim_end, geom, tr))
            s = outs[-1][0]
        return outs

    state, still_pending, _ = scalar_step_keys.same_bits(run, case)[-1]
    assert not still_pending.any() and state["tx_data"].sum() > 0
