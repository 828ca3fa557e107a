"""RTS/CTS + NAV tests (frame-exchange-manager NeedRts semantics)."""

from tpudes.core import Seconds, Simulator
from tpudes.core.world import reset_world
from tpudes.scenarios import build_bss


def _run_bss(threshold, n_stas=6, sim_time=2.0):
    reset_world()
    sta_devices, ap_device, clients, server_rx = build_bss(n_stas, sim_time)
    rts, cts = [0], [0]
    for i in range(n_stas):
        mac = sta_devices.Get(i).GetMac()
        mac.SetAttribute("RtsCtsThreshold", threshold)
        mac.TraceConnectWithoutContext(
            "RtsSent", lambda *a: rts.__setitem__(0, rts[0] + 1)
        )
    ap_device.GetMac().SetAttribute("RtsCtsThreshold", threshold)
    ap_device.GetMac().TraceConnectWithoutContext(
        "CtsSent", lambda *a: cts.__setitem__(0, cts[0] + 1)
    )
    Simulator.Stop(Seconds(sim_time))
    Simulator.Run()
    return server_rx[0], rts[0], cts[0]


def test_rts_cts_protects_without_losing_traffic():
    base_rx, base_rts, _ = _run_bss(threshold=65535)
    prot_rx, prot_rts, prot_cts = _run_bss(threshold=0)
    assert base_rts == 0
    assert prot_rts > 0 and prot_cts > 0
    # the AP answers (nearly) every received RTS
    assert prot_cts >= prot_rts * 0.8
    # protection must not change the delivered traffic on a clean channel
    assert prot_rx == base_rx


def test_threshold_gates_small_frames():
    # 512B payload → on-air ~576B: a 1000B threshold never triggers
    _, rts, _ = _run_bss(threshold=1000)
    assert rts == 0


def test_rts_protected_graph_lifts_and_runs():
    """The graph the replica lowering used to refuse (every frame above the
    threshold) lifts to the per-node arm of the BSS step and runs there: every
    exchange, the AP's echoes too, goes RTS -> CTS -> DATA -> ACK, and the
    counts are the unprotected legacy step's on this graph."""
    import jax
    import numpy as np

    from tpudes.parallel.replicated import (
        lower_bss,
        per_node_medium,
        run_replicated_bss,
    )

    reset_world()
    sta_devices, ap_device, clients, _ = build_bss(4, 2.0)
    for i in range(4):
        sta_devices.Get(i).GetMac().SetAttribute("RtsCtsThreshold", 0)
    ap_device.GetMac().SetAttribute("RtsCtsThreshold", 0)
    prog = lower_bss(
        [sta_devices.Get(i) for i in range(4)], ap_device, clients, 2.0
    )
    reset_world()
    assert per_node_medium(prog) and prog.echo and prog.rts_threshold == 0
    out = run_replicated_bss(prog, 16, jax.random.PRNGKey(5))
    assert out["all_done"]
    # 4 stations x 10 requests a second from 1 s; the one at 34 m sits 17 dB
    # over the noise, under what 54 Mbit/s data needs (its RTS at the 24 Mbit/s
    # control rate get through): its 10 are retried to the limit and dropped,
    # as the legacy step drops them without RTS
    srv = np.asarray(out["srv_rx"])
    assert ((srv >= 30) & (srv <= 32)).all()
    assert (np.asarray(out["cli_rx"]).sum(1) >= 27).all()
    assert (np.asarray(out["drops"]) >= 8).all()
    sent = np.asarray(out["tx_by"]).sum(1)
    assert (np.asarray(out["tx_rts"]) >= sent).all() and (sent >= 130).all()


def test_nav_defers_channel_access():
    """Virtual carrier sense must hold a grant past the reserved
    duration even with the PHY idle (r4 review: NAV was a no-op)."""
    from tpudes.core import MicroSeconds, Simulator
    from tpudes.models.wifi.mac import ChannelAccessManager

    reset_world()

    class IdlePhy:
        def IsStateIdle(self):
            return True

        def busy_until(self):
            return 0

        def idle_since(self):
            return -10_000_000_000

        def RegisterListener(self, listener):
            pass

    grants = []
    mgr = ChannelAccessManager(
        IdlePhy(), lambda: grants.append(Simulator.NowTicks())
    )
    nav_end = MicroSeconds(500).ticks
    mgr.NotifyNav(nav_end)
    mgr.request_access()
    Simulator.Stop(MicroSeconds(2000))
    Simulator.Run()
    assert len(grants) == 1
    assert grants[0] >= nav_end, "grant fired inside the NAV reservation"


def test_bbr_completes_dumbbell_transfer():
    from tpudes.scenarios import build_dumbbell

    reset_world()
    db, sinks = build_dumbbell(
        2, 4.0, variant="TcpBbr", bottleneck_rate="5Mbps"
    )
    Simulator.Stop(Seconds(4.0))
    Simulator.Run()
    tput = sum(s.GetTotalRx() for s in sinks) * 8 / 3.9 / 1e6
    assert tput > 3.0, f"BBR collapsed to {tput:.2f} Mbps"