"""802.11n (HT) slice: HT rates, A-MPDU aggregation under BlockAck,
MinstrelHt, table-based error model.

Mirrors upstream's wifi aggregation/block-ack test suites (SURVEY.md §4;
src/wifi/test/wifi-aggregation-test.cc, block-ack-test-suite.cc): count
PPDUs vs MPDUs to prove aggregation happened, force partial loss to
prove per-MPDU BlockAck retransmission, and pin the LUT error model
against its closed-form source.
"""

import math

import pytest

from tpudes.core import Seconds, Simulator
from tpudes.helper.containers import NodeContainer
from tpudes.models.mobility import ListPositionAllocator, MobilityHelper, Vector
from tpudes.models.wifi import (
    MinstrelHtWifiManager,
    NistErrorRateModel,
    TableBasedErrorRateModel,
    WifiHelper,
    WifiMacHelper,
    YansWifiChannelHelper,
    YansWifiPhyHelper,
    ppdu_duration_s,
)
from tpudes.models.wifi.mac import BLOCK_ACK_SIZE, WifiMacType, _ampdu_subframe_bytes
from tpudes.network.packet import Packet
from tpudes.ops.wifi_error import (
    HT_MODES,
    MODES_BY_NAME,
    chunk_success_rate_py,
    table_chunk_success_rate_py,
)


def _ht_pair(distance=10.0, manager=("tpudes::ConstantRateWifiManager", {"DataMode": "HtMcs7"}),
             max_ampdu=65535, phy_attrs=None):
    """Two-node adhoc HT link: returns (nodes, devices)."""
    nodes = NodeContainer()
    nodes.Create(2)
    mobility = MobilityHelper()
    alloc = ListPositionAllocator()
    alloc.Add(Vector(0, 0, 0))
    alloc.Add(Vector(distance, 0, 0))
    mobility.SetPositionAllocator(alloc)
    mobility.SetMobilityModel("tpudes::ConstantPositionMobilityModel")
    mobility.Install(nodes)

    channel = YansWifiChannelHelper.Default().Create()
    phy = YansWifiPhyHelper()
    phy.SetChannel(channel)
    for k, v in (phy_attrs or {}).items():
        phy.Set(k, v)
    wifi = WifiHelper()
    wifi.SetStandard("80211n")
    wifi.SetRemoteStationManager(manager[0], **manager[1])
    mac = WifiMacHelper()
    mac.SetType("tpudes::AdhocWifiMac", MaxAmpduSize=max_ampdu)
    devices = wifi.Install(phy, mac, nodes)
    return nodes, devices


def test_ht_ppdu_duration():
    # HT-mixed preamble is 36 µs (16 µs beyond legacy), 4 µs symbols
    mode = MODES_BY_NAME["HtMcs7"]  # 65 Mbps -> NDBPS = 260
    d = ppdu_duration_s(1000, mode)
    assert d == pytest.approx(36e-6 + math.ceil(8022 / 260) * 4e-6)
    # legacy modes are unchanged
    legacy = ppdu_duration_s(1000, MODES_BY_NAME["OfdmRate54Mbps"])
    assert legacy == pytest.approx(20e-6 + math.ceil(8022 / 216) * 4e-6)


def test_ht_ladder_monotone_rates():
    rates = [m.data_rate_bps for m in HT_MODES]
    assert rates == sorted(rates)
    assert MODES_BY_NAME["HtMcs0"].data_rate_bps == 6_500_000
    assert MODES_BY_NAME["VhtMcs9"].constellation == 256
    assert MODES_BY_NAME["HeMcs11"].constellation == 1024


def test_ampdu_aggregation_reduces_ppdu_count():
    """10 frames enqueued while the medium is busy must leave as a few
    A-MPDUs (after the ADDBA handshake), not 10 DATA/ACK exchanges."""
    nodes, devices = _ht_pair()
    got = []
    devices[1].SetReceiveCallback(lambda dev, pkt, proto, sender: got.append(pkt.GetSize()) or True)

    ppdus = []  # (size_bytes proxy: count tx begins at the sender PHY)
    devices[0].GetPhy().TraceConnectWithoutContext(
        "PhyTxBegin", lambda pkt, pw: ppdus.append(pkt)
    )
    # burst of 10 frames in one instant: first exchange runs the ADDBA
    # handshake; by the time data wins access, the queue is deep -> agg
    def burst():
        for _ in range(10):
            devices[0].Send(Packet(700), devices[1].GetAddress(), 0x0800)

    Simulator.Schedule(Seconds(1.0), burst)
    Simulator.Stop(Seconds(2))
    Simulator.Run()
    assert len(got) == 10
    from tpudes.models.wifi.phy import AmpduTag

    ampdus = [p for p in ppdus if p.PeekPacketTag(AmpduTag) is not None]
    assert ampdus, "no A-MPDU was ever transmitted"
    total_mpdus = sum(len(p.PeekPacketTag(AmpduTag).subframes) for p in ampdus)
    assert total_mpdus >= 10
    assert len(ampdus) <= 4, f"burst fragmented into {len(ampdus)} A-MPDUs"
    Simulator.Destroy()


def test_ampdu_respects_size_limit():
    """MaxAmpduSize bounds the aggregate: with a small cap the burst
    needs proportionally more PPDUs."""
    cap = 3 * _ampdu_subframe_bytes(700 + 8 + 24)  # ~3 MPDUs of 700B+LLC
    nodes, devices = _ht_pair(max_ampdu=cap)
    got = []
    devices[1].SetReceiveCallback(lambda dev, pkt, proto, sender: got.append(1) or True)
    from tpudes.models.wifi.phy import AmpduTag

    ampdus = []
    devices[0].GetPhy().TraceConnectWithoutContext(
        "PhyTxBegin",
        lambda pkt, pw: ampdus.append(pkt.PeekPacketTag(AmpduTag))
        if pkt.PeekPacketTag(AmpduTag) is not None
        else None,
    )

    def burst():
        for _ in range(9):
            devices[0].Send(Packet(700), devices[1].GetAddress(), 0x0800)

    Simulator.Schedule(Seconds(1.0), burst)
    Simulator.Stop(Seconds(2))
    Simulator.Run()
    assert len(got) == 9
    assert all(len(t.subframes) <= 3 for t in ampdus)
    assert any(len(t.subframes) == 3 for t in ampdus)
    Simulator.Destroy()


def test_block_ack_selective_retransmission():
    """At a marginal SNR some MPDUs of each A-MPDU fail; the BlockAck
    bitmap must retransmit exactly the losers until everything lands."""
    # 48 m at default power/loss -> per-MPDU PSR ≈ 0.66 for 700 B at
    # HtMcs3 (the NIST curve is steep: 45 m ≈ 0.97, 50 m ≈ 0.12) —
    # forces partial BlockAck bitmaps while BAs (32 B at 24 Mbps) survive
    nodes, devices = _ht_pair(
        distance=48.0,
        manager=("tpudes::ConstantRateWifiManager", {"DataMode": "HtMcs3"}),
    )
    got = []
    devices[1].SetReceiveCallback(lambda dev, pkt, proto, sender: got.append(1) or True)
    outcomes = []  # (n_ok, n_fail) per A-MPDU exchange
    devices[0].GetMac().TraceConnectWithoutContext(
        "AmpduTxOk", lambda to, ok, fail: outcomes.append((ok, fail))
    )

    def burst():
        for _ in range(16):
            devices[0].Send(Packet(700), devices[1].GetAddress(), 0x0800)

    Simulator.Schedule(Seconds(1.0), burst)
    Simulator.Stop(Seconds(4))
    Simulator.Run()
    # every frame eventually delivered exactly once (BA dedup) …
    assert len(got) == 16
    # … and at least one exchange had a partial bitmap (real selective
    # retransmission, not all-or-nothing)
    assert any(ok > 0 and fail > 0 for ok, fail in outcomes), outcomes
    assert sum(ok for ok, _ in outcomes) == 16
    Simulator.Destroy()


def test_minstrel_ht_converges_upward_on_clean_link():
    nodes, devices = _ht_pair(
        distance=5.0, manager=("tpudes::MinstrelHtWifiManager", {})
    )
    sm = devices[0].GetMac()._station_manager
    assert isinstance(sm, MinstrelHtWifiManager)
    got = []
    devices[1].SetReceiveCallback(lambda dev, pkt, proto, sender: got.append(1) or True)

    def feed(i=[0]):
        devices[0].Send(Packet(700), devices[1].GetAddress(), 0x0800)
        i[0] += 1
        if i[0] < 200:
            Simulator.Schedule(Seconds(0.004), feed)

    Simulator.Schedule(Seconds(1.0), feed)
    Simulator.Stop(Seconds(3))
    Simulator.Run()
    assert len(got) >= 190
    best = sm._best_rate(sm._st(devices[1].GetAddress()))
    # clean 5 m link: best throughput estimate should sit in the upper
    # half of the HT ladder
    assert best >= len(HT_MODES) // 2, f"best={best}"
    Simulator.Destroy()


def test_table_error_model_matches_nist_source():
    """LUT interpolation must track its closed-form source within the
    grid resolution, and preserve monotonicity in SNR."""
    for name in ("HtMcs0", "HtMcs4", "HtMcs7", "VhtMcs9"):
        mode = MODES_BY_NAME[name]
        prev = 0.0
        for snr_db in (2.0, 5.25, 8.4, 12.7, 18.0, 25.1):
            snr = 10 ** (snr_db / 10)
            lut = table_chunk_success_rate_py(snr, 8 * 1458, mode.index)
            exact = chunk_success_rate_py(snr, 8 * 1458, mode.constellation, mode.rate_class)
            assert lut == pytest.approx(exact, abs=0.05), (name, snr_db)
            assert lut >= prev - 1e-9
            prev = lut


def test_table_error_model_size_scaling():
    mode = MODES_BY_NAME["HtMcs3"]
    snr = 10 ** (1.15)  # mid-curve
    big = table_chunk_success_rate_py(snr, 8 * 1458, mode.index)
    small = table_chunk_success_rate_py(snr, 8 * 32, mode.index)
    # (1-PER)^(L/Lref): smaller frames succeed more often
    assert small > big
    assert small == pytest.approx(big ** (32 / 1458), rel=1e-6)


def test_phy_error_rate_model_attribute():
    nodes, devices = _ht_pair(phy_attrs={"ErrorRateModel": "tpudes::TableBasedErrorRateModel"})
    phy = devices[0].GetPhy()
    assert isinstance(phy.interference.error_model, TableBasedErrorRateModel)
    # default stays NIST
    _, dev2 = _ht_pair()
    assert isinstance(dev2[0].GetPhy().interference.error_model, NistErrorRateModel)
    Simulator.Destroy()


def test_block_ack_header_serialization_roundtrip():
    """The compressed-BA wire form must round-trip the bitmap (pcap and
    cross-rank transport see bytes, not header objects)."""
    from tpudes.models.wifi.mac import WifiMacHeader
    from tpudes.network.address import Mac48Address

    h = WifiMacHeader(
        WifiMacType.BLOCK_ACK,
        addr1=Mac48Address("00:00:00:00:00:01"),
        addr2=Mac48Address("00:00:00:00:00:02"),
    )
    h.ba_seqs = (100, 101, 103, 107, 130)
    data = h.Serialize()
    assert len(data) == h.GetSerializedSize() == BLOCK_ACK_SIZE - 4
    h2 = WifiMacHeader.Deserialize(data)
    assert h2.frame_type == WifiMacType.BLOCK_ACK
    assert set(h2.ba_seqs) == {100, 101, 103, 107, 130}
    assert h2.addr1 == h.addr1 and h2.addr2 == h.addr2


def test_block_ack_wide_set_acks_max_coverage_subset():
    """A pathological ack set spanning more than one 64-seq window must
    serialize the start that covers the MOST seqs — never a bitmap that
    silently acks almost nothing (r5 review fix; per-destination
    sequence counters make such sets unreachable in normal operation)."""
    from tpudes.models.wifi.mac import WifiMacHeader
    from tpudes.network.address import Mac48Address

    h = WifiMacHeader(
        WifiMacType.BLOCK_ACK,
        addr1=Mac48Address("00:00:00:00:00:01"),
        addr2=Mac48Address("00:00:00:00:00:02"),
    )
    h.ba_seqs = (10, 80, 150, 151, 152)
    h2 = WifiMacHeader.Deserialize(h.Serialize())
    # the 150-window covers three seqs; 10 and 80 cover one each
    assert set(h2.ba_seqs) == {150, 151, 152}


def test_sequence_counters_are_per_destination():
    """BA sessions are per-peer, so each destination must see a dense
    sequence stream even when traffic interleaves across peers."""
    from tpudes.models.wifi.mac import AdhocWifiMac
    from tpudes.network.address import Mac48Address

    mac = AdhocWifiMac()
    a = Mac48Address("00:00:00:00:00:0a")
    b = Mac48Address("00:00:00:00:00:0b")
    seqs_a = [mac._next_seq(a) for _ in range(3)]
    seqs_b = [mac._next_seq(b) for _ in range(3)]
    assert seqs_a == [1, 2, 3]
    assert seqs_b == [1, 2, 3]


@pytest.mark.slow  # ISSUE-21 tier-1 budget: runs in CI's slow-overflow step
def test_window_kernel_table_error_model():
    """The synthetic window kernel's LUT option must track the NIST form
    on the same batch within LUT resolution."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudes.parallel.kernels import WindowParams, wifi_phy_window

    pos = jnp.asarray(
        np.array([[0, 0, 0], [20, 0, 0], [0, 25, 0], [15, 15, 0]], np.float32)
    )
    tx = jnp.asarray([1, 0, 1, 0])
    mode = jnp.full((4,), MODES_BY_NAME["HtMcs4"].index, jnp.int32)
    size = jnp.full((4,), 700.0, jnp.float32)
    key = jax.random.PRNGKey(0)
    _, sinr_n, _ = wifi_phy_window(pos, tx, mode, size, key, WindowParams())
    _, sinr_t, _ = wifi_phy_window(
        pos, tx, mode, size, key, WindowParams(error_model="table")
    )
    # identical geometry -> identical SINR; PER differs only by LUT error
    assert np.allclose(np.asarray(sinr_n), np.asarray(sinr_t))


def test_ampdu_end_to_end_with_table_model():
    """Aggregation + LUT error model together on a clean link."""
    nodes, devices = _ht_pair(
        phy_attrs={"ErrorRateModel": "tpudes::TableBasedErrorRateModel"}
    )
    got = []
    devices[1].SetReceiveCallback(lambda dev, pkt, proto, sender: got.append(1) or True)

    def burst():
        for _ in range(8):
            devices[0].Send(Packet(400), devices[1].GetAddress(), 0x0800)

    Simulator.Schedule(Seconds(1.0), burst)
    Simulator.Stop(Seconds(2))
    Simulator.Run()
    assert len(got) == 8
    Simulator.Destroy()
