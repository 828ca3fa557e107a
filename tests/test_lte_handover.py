"""RLC-AM, A3-RSRP handover, and EPC remote-host tests.

Upstream analogs: src/lte/test/lte-test-rlc-am-transmitter.cc /
lte-test-rlc-am-e2e.cc (AM delivers under loss), lte-test-handover-*
(X2 handover moves a UE between cells without losing bearers).
"""

import pytest

from tpudes.core import MilliSeconds, Seconds, Simulator
from tpudes.helper.containers import NodeContainer
from tpudes.models.lte import LteHelper
from tpudes.models.lte.rlc import LteRlcAm, LteRlcUm, make_rlc
from tpudes.models.mobility import (
    ConstantVelocityMobilityModel,
    ListPositionAllocator,
    MobilityHelper,
    Vector,
)
from tpudes.network.packet import Packet


# --- RLC-AM unit level ------------------------------------------------------
def _pump(tx, rx, n_rounds, opportunity=120, drop=lambda i: False):
    """Drive tx→rx for n_rounds opportunities, dropping PDUs on
    ``drop(i)``; Simulator carries the STATUS feedback."""
    sent = 0
    for i in range(n_rounds):
        pdu = tx.NotifyTxOpportunity(opportunity)
        if pdu is not None:
            sent += 1
            if not drop(i):
                rx.ReceivePdu(pdu)
        # let STATUS (2 ms) land between opportunities
        Simulator.Stop(MilliSeconds(5))
        Simulator.Run()
    return sent


def _am_pair():
    tx, rx = make_rlc("am"), make_rlc("am")
    rx.status_callback = tx.ReceiveStatus
    got = []
    rx.rx_sdu_callback = lambda p: got.append(p.GetSize())
    return tx, rx, got


def test_am_delivers_all_sdus_without_loss():
    tx, rx, got = _am_pair()
    for _ in range(10):
        tx.TransmitPdcpPdu(Packet(300))
    _pump(tx, rx, 40)
    assert got == [300] * 10


def test_am_recovers_lost_pdus_where_um_tears():
    drop = lambda i: i % 4 == 1  # noqa: E731 — lose every 4th PDU
    tx, rx, got = _am_pair()
    for _ in range(12):
        tx.TransmitPdcpPdu(Packet(500))
    _pump(tx, rx, 120, drop=drop)
    assert got == [500] * 12, "AM must retransmit across losses"
    assert tx.stats_retx_pdus > 0
    assert tx.stats_dropped_pdus == 0

    # UM under the identical loss pattern tears SDUs
    um_tx, um_rx = LteRlcUm(), LteRlcUm()
    um_got = []
    um_rx.rx_sdu_callback = lambda p: um_got.append(p.GetSize())
    for _ in range(12):
        um_tx.TransmitPdcpPdu(Packet(500))
    for i in range(120):
        pdu = um_tx.NotifyTxOpportunity(120)
        if pdu is not None and not drop(i):
            um_rx.ReceivePdu(pdu)
    assert len(um_got) < 12


def test_am_in_order_delivery_despite_reordering_gap():
    tx, rx, got = _am_pair()
    for size in (200, 300, 400):
        tx.TransmitPdcpPdu(Packet(size))
    p0 = tx.NotifyTxOpportunity(204 + 4)
    p1 = tx.NotifyTxOpportunity(304 + 4)
    p2 = tx.NotifyTxOpportunity(404 + 4)
    rx.ReceivePdu(p0)
    rx.ReceivePdu(p2)          # gap: p1 missing
    assert got == [200], "delivery must stall at the gap"
    rx.ReceivePdu(p1)          # late arrival fills it
    assert got == [200, 300, 400]


def test_am_gives_up_after_max_retx():
    tx, rx, got = _am_pair()
    tx.TransmitPdcpPdu(Packet(100))
    pdu = tx.NotifyTxOpportunity(200)
    assert pdu is not None
    # peer never gets it; NACK it repeatedly with real time between
    # (NACKs inside the suppression window are rightly ignored)
    for _ in range(LteRlcAm.MAX_RETX + 1):
        Simulator.Stop(MilliSeconds(LteRlcAm.NACK_IGNORE_WINDOW_MS + 1))
        Simulator.Run()
        tx.ReceiveStatus(pdu.sn + 1, [pdu.sn])
        tx.NotifyTxOpportunity(200)  # drains the retx queue each time
    assert tx.stats_dropped_pdus == 1
    assert not tx._retx and pdu.sn not in tx._unacked


def test_am_nack_flood_within_window_is_suppressed():
    """Per-PDU STATUS cadence must not burn the retx budget on one real
    loss (r4 review: duplicate NACKs reached MAX_RETX)."""
    tx, rx, got = _am_pair()
    tx.TransmitPdcpPdu(Packet(100))
    pdu = tx.NotifyTxOpportunity(200)
    for _ in range(10):  # flood of NACKs at the same instant
        tx.ReceiveStatus(pdu.sn + 1, [pdu.sn])
    assert tx._retx_count.get(pdu.sn, 0) <= 1
    assert tx.stats_dropped_pdus == 0


def test_am_poll_timer_recovers_lost_tail_pdu():
    """The LAST PDU of a burst is lost: no further data means no STATUS
    from the peer — t-PollRetransmit must resend it (r4 review)."""
    tx, rx, got = _am_pair()
    tx.TransmitPdcpPdu(Packet(300))
    tx.TransmitPdcpPdu(Packet(300))
    p0 = tx.NotifyTxOpportunity(310)
    p1 = tx.NotifyTxOpportunity(310)   # the tail — gets lost
    rx.ReceivePdu(p0)
    # run long enough for poll timeout + retx round trips
    for _ in range(6):
        Simulator.Stop(MilliSeconds(LteRlcAm.POLL_RETRANSMIT_MS + 5))
        Simulator.Run()
        retx = tx.NotifyTxOpportunity(310)
        if retx is not None:
            rx.ReceivePdu(retx)
    assert got == [300, 300], "poll-retransmit must recover the tail"


def test_am_resegments_retx_for_small_opportunities():
    """A big NACKed PDU must split across shrunken opportunities, not
    stall the bearer (r4 review)."""
    tx, rx, got = _am_pair()
    tx.TransmitPdcpPdu(Packet(1200))
    big = tx.NotifyTxOpportunity(1300)   # whole SDU in one PDU — lost
    assert big is not None
    Simulator.Stop(MilliSeconds(LteRlcAm.NACK_IGNORE_WINDOW_MS + 1))
    Simulator.Run()
    tx.ReceiveStatus(big.sn + 1, [big.sn])
    # only 400-byte opportunities from now on
    parts = []
    for _ in range(8):
        p = tx.NotifyTxOpportunity(400)
        if p is not None:
            parts.append(p)
            rx.ReceivePdu(p)
    assert len(parts) >= 3, "retx must re-segment to fit"
    assert got == [1200], "re-segmented SDU must reassemble"


def test_am_overlapping_retx_parts_do_not_corrupt():
    """An original whole PDU AND later re-segmented parts both arrive:
    coverage-based reassembly must deliver the SDU exactly once."""
    tx, rx, got = _am_pair()
    tx.TransmitPdcpPdu(Packet(1000))
    whole = tx.NotifyTxOpportunity(1100)
    Simulator.Stop(MilliSeconds(LteRlcAm.NACK_IGNORE_WINDOW_MS + 1))
    Simulator.Run()
    tx.ReceiveStatus(whole.sn + 1, [whole.sn])  # spurious NACK (raced)
    half = tx.NotifyTxOpportunity(600)          # re-segmented head
    rx.ReceivePdu(half)                         # part arrives first
    rx.ReceivePdu(whole)                        # then the stale whole
    assert got == [1000]
    assert rx.stats_rx_pdus == 2


def test_am_buffer_reports_retx_backlog():
    tx, rx, got = _am_pair()
    tx.TransmitPdcpPdu(Packet(100))
    pdu = tx.NotifyTxOpportunity(200)
    assert tx.BufferBytes() == 0
    Simulator.Stop(MilliSeconds(LteRlcAm.NACK_IGNORE_WINDOW_MS + 1))
    Simulator.Run()
    tx.ReceiveStatus(pdu.sn + 1, [pdu.sn])
    assert tx.BufferBytes() >= pdu.size_bytes


# --- A3 handover + X2-lite --------------------------------------------------
def _two_cell_moving_ue(rlc_mode="am", start_x=220.0, speed=100.0, ttt=160):
    lte = LteHelper()
    enbs = NodeContainer()
    enbs.Create(2)
    ues = NodeContainer()
    ues.Create(1)
    ea = ListPositionAllocator()
    ea.Add(Vector(0, 0, 30.0))
    ea.Add(Vector(500, 0, 30.0))
    me = MobilityHelper()
    me.SetPositionAllocator(ea)
    me.SetMobilityModel("tpudes::ConstantPositionMobilityModel")
    me.Install(enbs)
    ua = ListPositionAllocator()
    ua.Add(Vector(start_x, 0, 1.5))
    mu = MobilityHelper()
    mu.SetPositionAllocator(ua)
    mu.SetMobilityModel("tpudes::ConstantVelocityMobilityModel")
    mu.Install(ues)
    ues.Get(0).GetObject(ConstantVelocityMobilityModel).SetVelocity(
        Vector(speed, 0.0, 0.0)
    )
    enb_devs = lte.InstallEnbDevice(enbs)
    ue_devs = lte.InstallUeDevice(ues)
    lte.Attach([ue_devs.Get(0)])
    lte.ActivateDataRadioBearer([ue_devs.Get(0)], mode=rlc_mode)
    lte.SetHandoverAlgorithmType("tpudes::A3RsrpHandoverAlgorithm")
    lte.SetHandoverAlgorithmAttribute("TimeToTrigger", ttt)
    lte.AddX2Interface(enbs)
    return lte, enb_devs, ue_devs


@pytest.mark.slow  # ISSUE-21 tier-1 budget: runs in CI's slow-overflow step
def test_a3_handover_moves_ue_between_cells():
    lte, enb_devs, ue_devs = _two_cell_moving_ue(rlc_mode="sm")
    assert ue_devs.Get(0).rrc.serving_enb is enb_devs.Get(0)
    Simulator.Stop(Seconds(1.5))
    Simulator.Run()
    c = lte.controller
    assert c.stats["handovers"] == 1
    assert ue_devs.Get(0).rrc.serving_enb is enb_devs.Get(1)
    tti, imsi, src, dst = c.handover_log[0]
    assert (src, dst) == (enb_devs.Get(0).GetCellId(), enb_devs.Get(1).GetCellId())
    # A3 geometry: Friis + 3 dB hysteresis crosses at ~293 m, + TTT;
    # the UE (220 m + 100 m/s) must hand over in roughly [730, 1100] ms
    assert 700 <= tti <= 1200, tti
    # traffic continues at the target cell after the move
    assert c.stats["dl_ok"] > tti * 0.8


@pytest.mark.slow  # ISSUE-21 tier-1 budget: runs in CI's slow-overflow step
def test_handover_is_lossless_for_am_bearers():
    lte, enb_devs, ue_devs = _two_cell_moving_ue(rlc_mode="am")
    bearer = next(iter(ue_devs.Get(0).rrc.bearers.values()))
    got = []
    bearer.dl_rx.rx_sdu_callback = lambda p: got.append(p.GetSize())
    n_fed = [0]

    def feed():
        bearer.dl_pdcp.TransmitSdu(Packet(600))
        n_fed[0] += 1
        if n_fed[0] < 140:
            Simulator.Schedule(MilliSeconds(10), feed)

    feed()
    Simulator.Stop(Seconds(1.5))
    Simulator.Run()
    assert lte.controller.stats["handovers"] == 1
    assert len(got) == n_fed[0], "AM + X2-lite must lose no SDUs"


def test_no_x2_means_no_handover():
    lte, enb_devs, ue_devs = _two_cell_moving_ue(rlc_mode="sm")
    lte.controller.x2_enabled = False
    Simulator.Stop(Seconds(1.2))
    Simulator.Run()
    assert lte.controller.stats["handovers"] == 0
    assert ue_devs.Get(0).rrc.serving_enb is enb_devs.Get(0)


def test_hysteresis_blocks_marginal_neighbors():
    # UE sits just past midpoint (260 m): best cell differs from serving
    # but by < 3 dB, so A3 must never fire
    lte, enb_devs, ue_devs = _two_cell_moving_ue(
        rlc_mode="sm", start_x=260.0, speed=0.001
    )
    Simulator.Stop(Seconds(1.0))
    Simulator.Run()
    assert lte.controller.stats["handovers"] == 0


def test_a3_pending_entries_expire_when_measurements_stop():
    """Promoted EVT003 regression: a (ue, target) entry whose UE stops
    being measured (detach / controller teardown) must be swept by the
    algorithm's scheduled expiry instead of leaking forever.  A live A3
    condition is re-confirmed every measurement period, so only
    abandoned entries can age past the lapse window."""
    from tpudes.models.lte.handover import A3RsrpHandoverAlgorithm

    algo = A3RsrpHandoverAlgorithm(TimeToTrigger=256)
    # enter the pending dict at t=0: neighbour 5 dB above serving
    assert algo.evaluate(0, 0, 0, [10.0, 15.0]) is None
    assert (0, 1) in algo._entered
    # the UE vanishes (no further evaluate calls) — run past the lapse
    Simulator.Stop(MilliSeconds(4 * (256 + 80)))
    Simulator.Run()
    assert algo._entered == {}


def test_a3_sweep_keeps_live_entries():
    """The expiry sweep must NOT touch an entry that keeps being
    re-confirmed every measurement period (the sweep fires mid-run,
    between confirmations, and must leave the live entry alone)."""
    from tpudes.models.lte.handover import (
        MEASUREMENT_PERIOD_TTIS,
        A3RsrpHandoverAlgorithm,
    )

    algo = A3RsrpHandoverAlgorithm(TimeToTrigger=1000)
    row = [10.0, 15.0]
    for t in range(0, 2001, MEASUREMENT_PERIOD_TTIS):
        Simulator.Schedule(
            MilliSeconds(t), lambda t=t: algo.evaluate(t, 0, 0, row)
        )
    # the sweep (lapse = 2 periods + TTT = 1080 ms) fires at least once
    # inside this horizon while confirmations keep arriving
    Simulator.Stop(MilliSeconds(2001))
    Simulator.Run()
    assert (0, 1) in algo._entered
    assert algo._entered[(0, 1)][1] == 2000


# --- EPC with a true remote host -------------------------------------------
def test_remote_host_traffic_through_backhaul_and_pgw():
    """lena-simple-epc shape: remote host → p2p backhaul → PGW → DL
    bearer → UE, and the uplink back out to the remote host."""
    from tpudes.helper.applications import UdpClientHelper, UdpServerHelper
    from tpudes.helper.internet import InternetStackHelper, Ipv4AddressHelper
    from tpudes.helper.point_to_point import PointToPointHelper
    from tpudes.models.internet.ipv4 import Ipv4L3Protocol, Ipv4StaticRouting
    from tpudes.models.lte.epc import EpcHelper
    from tpudes.network.address import Ipv4Address, Ipv4Mask

    lte = LteHelper()
    epc = EpcHelper()
    remote = NodeContainer()
    remote.Create(1)
    InternetStackHelper().Install(remote)
    p2p = PointToPointHelper()
    p2p.SetDeviceAttribute("DataRate", "1Gbps")
    p2p.SetChannelAttribute("Delay", "5ms")
    backhaul = p2p.Install(remote.Get(0), epc.GetPgwNode())
    ifc = Ipv4AddressHelper("1.0.0.0", "255.0.0.0").Assign(backhaul)
    routing = remote.Get(0).GetObject(Ipv4L3Protocol).GetRoutingProtocol()
    assert isinstance(routing, Ipv4StaticRouting)
    routing.AddNetworkRouteTo(
        Ipv4Address(EpcHelper.UE_NETWORK), Ipv4Mask(EpcHelper.UE_MASK),
        remote.Get(0).GetObject(Ipv4L3Protocol).GetInterfaceForDevice(
            backhaul.Get(0)
        ),
        gateway=ifc.GetAddress(1),
    )

    enbs = NodeContainer()
    enbs.Create(1)
    ues = NodeContainer()
    ues.Create(1)
    ea = ListPositionAllocator()
    ea.Add(Vector(0, 0, 30.0))
    me = MobilityHelper()
    me.SetPositionAllocator(ea)
    me.SetMobilityModel("tpudes::ConstantPositionMobilityModel")
    me.Install(enbs)
    ua = ListPositionAllocator()
    ua.Add(Vector(70.0, 0, 1.5))
    mu = MobilityHelper()
    mu.SetPositionAllocator(ua)
    mu.SetMobilityModel("tpudes::ConstantPositionMobilityModel")
    mu.Install(ues)
    lte.InstallEnbDevice(enbs)
    ue_devs = lte.InstallUeDevice(ues)
    InternetStackHelper().Install(ues)
    lte.Attach([ue_devs.Get(0)])
    lte.ActivateDataRadioBearer([ue_devs.Get(0)], mode="um")
    (ue_addr,) = epc.AssignUeIpv4Address([ue_devs.Get(0)])

    dl_rx = [0]
    server = UdpServerHelper(1000)
    sapps = server.Install(ues.Get(0))
    sapps.Start(Seconds(0.0))
    sapps.Get(0).TraceConnectWithoutContext(
        "Rx", lambda pkt, *a: dl_rx.__setitem__(0, dl_rx[0] + 1)
    )
    dl = UdpClientHelper(ue_addr, 1000)
    dl.SetAttribute("MaxPackets", 8)
    dl.SetAttribute("Interval", Seconds(0.02))
    dl.SetAttribute("PacketSize", 300)
    dl.Install(remote.Get(0)).Start(Seconds(0.01))

    ul_server = UdpServerHelper(2000)
    ul_apps = ul_server.Install(remote.Get(0))
    ul_apps.Start(Seconds(0.0))
    ul = UdpClientHelper(ifc.GetAddress(0), 2000)
    ul.SetAttribute("MaxPackets", 6)
    ul.SetAttribute("Interval", Seconds(0.02))
    ul.SetAttribute("PacketSize", 150)
    ul.Install(ues.Get(0)).Start(Seconds(0.02))

    Simulator.Stop(Seconds(0.5))
    Simulator.Run()
    assert dl_rx[0] == 8, "all DL packets must reach the UE app"
    assert ul_apps.Get(0).received == 6, "all UL packets must reach the remote host"


# --- eNB RRC stranded-context sweep ----------------------------------------


def test_stranded_context_reclaimed_after_reattach_elsewhere():
    """Promoted EVT003 regression (LteEnbRrc.ues): a UE that re-attaches
    to another cell OUTSIDE the handover remove_ue path must have its
    old eNB-side UeContext reclaimed by the scheduled stranded-context
    sweep instead of leaking forever."""
    from tpudes.models.lte.device import (
        LteEnbNetDevice,
        LteEnbRrc,
        LteUeNetDevice,
    )

    src, dst = LteEnbNetDevice(), LteEnbNetDevice()
    ue = LteUeNetDevice()
    ctx = src.rrc.add_ue(ue)
    ue.rrc.connect(src, ctx.rnti)
    # raw re-attach: no remove_ue on the old cell
    ctx2 = dst.rrc.add_ue(ue)
    ue.rrc.connect(dst, ctx2.rnti)
    assert len(src.rrc.ues) == 1, "stranded until the sweep fires"
    Simulator.Stop(MilliSeconds(2 * LteEnbRrc.STRANDED_UE_LAPSE_MS))
    Simulator.Run()
    assert src.rrc.ues == {}
    assert list(dst.rrc.ues) == [ctx2.rnti]


def test_disconnect_releases_enb_context_after_lapse():
    """LteUeRrc.disconnect (RRC release) leaves the eNB context to the
    lapse sweep — reclaimed, but only after the grace window."""
    from tpudes.models.lte.device import (
        LteEnbNetDevice,
        LteEnbRrc,
        LteUeNetDevice,
        LteUeRrc,
    )

    enb = LteEnbNetDevice()
    ue = LteUeNetDevice()
    ctx = enb.rrc.add_ue(ue)
    ue.rrc.connect(enb, ctx.rnti)
    ue.rrc.disconnect()
    assert ue.rrc.state == LteUeRrc.IDLE
    assert len(enb.rrc.ues) == 1, "grace window: not reclaimed inline"
    Simulator.Stop(MilliSeconds(2 * LteEnbRrc.STRANDED_UE_LAPSE_MS))
    Simulator.Run()
    assert enb.rrc.ues == {}


def test_sweep_keeps_claimed_contexts():
    """The sweep armed by one UE's departure must not touch a context
    its UE still claims."""
    from tpudes.models.lte.device import (
        LteEnbNetDevice,
        LteEnbRrc,
        LteUeNetDevice,
    )

    enb = LteEnbNetDevice()
    stay, leave = LteUeNetDevice(), LteUeNetDevice()
    ctx_stay = enb.rrc.add_ue(stay)
    stay.rrc.connect(enb, ctx_stay.rnti)
    ctx_leave = enb.rrc.add_ue(leave)
    leave.rrc.connect(enb, ctx_leave.rnti)
    leave.rrc.disconnect()
    Simulator.Stop(MilliSeconds(2 * LteEnbRrc.STRANDED_UE_LAPSE_MS))
    Simulator.Run()
    assert list(enb.rrc.ues) == [ctx_stay.rnti]


def test_same_cell_reattach_reclaims_old_context():
    """Review fix: a UE re-establishing on the SAME cell under a fresh
    RNTI abandons its old context just like a re-attach elsewhere — the
    sweep must reclaim it (connect() notes the detach for any previous
    serving cell, not only a different one)."""
    from tpudes.models.lte.device import (
        LteEnbNetDevice,
        LteEnbRrc,
        LteUeNetDevice,
    )

    enb = LteEnbNetDevice()
    ue = LteUeNetDevice()
    ctx = enb.rrc.add_ue(ue)
    ue.rrc.connect(enb, ctx.rnti)
    ctx2 = enb.rrc.add_ue(ue)  # RRC re-establishment: fresh RNTI
    ue.rrc.connect(enb, ctx2.rnti)
    assert len(enb.rrc.ues) == 2, "old context stranded until the sweep"
    Simulator.Stop(MilliSeconds(2 * LteEnbRrc.STRANDED_UE_LAPSE_MS))
    Simulator.Run()
    assert list(enb.rrc.ues) == [ctx2.rnti]


def test_detach_during_pending_sweep_keeps_full_grace():
    """Review fix: a detach landing while a sweep is already pending
    keeps its OWN full lapse window (per-context timestamps) — a
    re-attach inside that window survives the earlier-armed sweep."""
    from tpudes.models.lte.device import (
        LteEnbNetDevice,
        LteEnbRrc,
        LteUeNetDevice,
    )

    lapse = LteEnbRrc.STRANDED_UE_LAPSE_MS
    enb = LteEnbNetDevice()
    ue1, ue2 = LteUeNetDevice(), LteUeNetDevice()
    ctx1 = enb.rrc.add_ue(ue1)
    ue1.rrc.connect(enb, ctx1.rnti)
    ctx2 = enb.rrc.add_ue(ue2)
    ue2.rrc.connect(enb, ctx2.rnti)
    ue1.rrc.disconnect()  # t=0: arms the sweep for t=lapse
    # t=lapse-1: ue2 detaches; t=lapse+1: it re-attaches (same RNTI) —
    # well inside ITS grace window even though the pending sweep fires
    # at t=lapse, 1 ms after its detach
    Simulator.Schedule(MilliSeconds(lapse - 1), ue2.rrc.disconnect)
    Simulator.Schedule(
        MilliSeconds(lapse + 1), lambda: ue2.rrc.connect(enb, ctx2.rnti)
    )
    Simulator.Stop(MilliSeconds(3 * lapse))
    Simulator.Run()
    assert ctx1.rnti not in enb.rrc.ues, "lapsed context reclaimed"
    assert ctx2.rnti in enb.rrc.ues, "re-attach inside its grace survives"
    assert enb.rrc._unclaimed_since == {}, "re-claimed context unmarked"
