"""Hidden terminals with and without RTS/CTS: three ad hoc stations in a
row, the two outer ones out of each other's range, both sending a
saturating CBR stream to the middle one.

Upstream analog: examples/wireless/wifi-hidden-terminal.cc.  A
MatrixPropagationLossModel puts 50 dB between nodes 0 and 1 and between
nodes 2 and 1, and its default 200 dB between nodes 0 and 2, so 0 and
2 cannot hear each other; two UDP OnOff flows of 1400-byte packets
(3 000 000 bit/s from 1.0 s, 3 001 100 bit/s from 1.001 s) go to a
PacketSink on node 1; ``--rtsCts`` picks upstream's two experiments,
RtsCtsThreshold 100 (every data frame protected) or 2200 (none).

Departures from upstream, all in the PHY and rate manager: the 802.11a
OFDM PHY at a constant ``--dataMode`` (default OfdmRate6Mbps, which
keeps the two flows above the channel's capacity as upstream's 11 Mbps
DSSS does) in place of 802.11b with the ARF manager, whose DSSS PHY
and error model this simulator does not have.  Upstream's single echo
packet from each sender before the flows start (its workaround for
ARP) is kept: the lifted program skips it with the ARP it resolves.

Run: python examples/wifi-hidden-terminal.py --rtsCts=1 --simTime=3

The TPU engine is one GlobalValue flip away: the same object graph
lifts to the BSS engine's per-node medium (kind ``bss``) and runs R
Monte-Carlo replicas at once:

    python examples/wifi-hidden-terminal.py --rtsCts=1 \\
        --SimulatorImplementationType=tpudes::JaxSimulatorImpl \\
        --JaxReplicas=8192
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpudes.core import CommandLine, Seconds, Simulator
from tpudes.core.config import Config
from tpudes.core.rng import ConstantRandomVariable
from tpudes.helper.applications import (
    OnOffHelper,
    PacketSinkHelper,
    UdpEchoClientHelper,
)
from tpudes.helper.containers import NodeContainer
from tpudes.helper.internet import InternetStackHelper, Ipv4AddressHelper
from tpudes.models.flow_monitor import FlowMonitorHelper
from tpudes.models.mobility import ConstantPositionMobilityModel, MobilityModel
from tpudes.models.propagation import (
    ConstantSpeedPropagationDelayModel,
    MatrixPropagationLossModel,
)
from tpudes.models.wifi import WifiHelper, WifiMacHelper, YansWifiPhyHelper
from tpudes.models.wifi.channel import YansWifiChannel
from tpudes.network.address import InetSocketAddress, Ipv4Address

#: upstream's two experiments: every data frame protected, or none
RTS_THRESHOLD = {True: 100, False: 2200}
#: the flows: (sender, data rate bit/s, start s); node 1 receives both
FLOWS = ((0, 3_000_000, 1.0), (2, 3_001_100, 1.001))
PACKET_BYTES = 1400
SINK_PORT = 12345


def main(argv=None):
    cmd = CommandLine()
    cmd.AddValue("rtsCts", "1: RtsCtsThreshold 100 (RTS/CTS on); 0: 2200 (off)", 1)
    cmd.AddValue("dataMode", "ConstantRate data mode", "OfdmRate6Mbps")
    cmd.AddValue("simTime", "simulated seconds", 10.0)
    cmd.Parse(argv)
    rts_cts = bool(int(cmd.rtsCts))
    sim_time = float(cmd.simTime)

    # 0. enable or disable RTS/CTS
    Config.SetDefault("tpudes::WifiMac::RtsCtsThreshold", RTS_THRESHOLD[rts_cts])

    # 1-2. three nodes, placed (the loss matrix, not the positions, decides)
    nodes = NodeContainer()
    nodes.Create(3)
    for i in range(3):
        nodes.Get(i).AggregateObject(ConstantPositionMobilityModel())

    # 3. the loss matrix: 0 <-> 1 and 2 <-> 1 at 50 dB, the rest 200 dB
    loss = MatrixPropagationLossModel(DefaultLoss=200.0)
    mob = [nodes.Get(i).GetObject(MobilityModel) for i in range(3)]
    loss.SetLoss(mob[0], mob[1], 50.0)
    loss.SetLoss(mob[2], mob[1], 50.0)

    # 4. the channel
    channel = YansWifiChannel()
    channel.SetPropagationLossModel(loss)
    channel.SetPropagationDelayModel(ConstantSpeedPropagationDelayModel())

    # 5. ad hoc devices at a constant rate
    wifi = WifiHelper()
    wifi.SetStandard("80211a")
    wifi.SetRemoteStationManager(
        "tpudes::ConstantRateWifiManager", DataMode=str(cmd.dataMode)
    )
    phy = YansWifiPhyHelper()
    phy.SetChannel(channel)
    mac = WifiMacHelper()
    mac.SetType("tpudes::AdhocWifiMac")
    devices = wifi.Install(phy, mac, nodes)

    # 6. IP stack and addresses
    InternetStackHelper().Install(nodes)
    address = Ipv4AddressHelper()
    address.SetBase("10.0.0.0", "255.0.0.0")
    interfaces = address.Assign(devices)
    sink_ip = interfaces.GetAddress(1)

    # 7. two saturating CBR streams into node 1's sink
    sink = PacketSinkHelper(
        "tpudes::UdpSocketFactory", InetSocketAddress(Ipv4Address.GetAny(), SINK_PORT)
    )
    sink.Install(nodes.Get(1))
    for sender, rate, start in FLOWS:
        onoff = OnOffHelper(
            "tpudes::UdpSocketFactory", InetSocketAddress(sink_ip, SINK_PORT)
        )
        onoff.SetAttribute("PacketSize", PACKET_BYTES)
        onoff.SetAttribute("OnTime", ConstantRandomVariable(Constant=1.0))
        onoff.SetAttribute("OffTime", ConstantRandomVariable(Constant=0.0))
        onoff.SetAttribute("DataRate", f"{rate}bps")
        app = onoff.Install(nodes.Get(sender))
        app.Start(Seconds(start))

    # one echo packet from each sender before the flows start: upstream's
    # workaround for ARP (bug 187), which the replica axis does not model
    echo = UdpEchoClientHelper(sink_ip, 9)
    echo.SetAttribute("MaxPackets", 1)
    echo.SetAttribute("Interval", Seconds(0.1))
    echo.SetAttribute("PacketSize", 10)
    for sender, start in ((0, 0.001), (2, 0.006)):
        echo.Install(nodes.Get(sender)).Start(Seconds(start))

    # 8. per-flow statistics on the scalar path
    fmh = FlowMonitorHelper()
    monitor = fmh.InstallAll()

    # 9. run
    wall0 = time.monotonic()
    Simulator.Stop(Seconds(sim_time))
    Simulator.Run()
    wall = time.monotonic() - wall0

    res = getattr(Simulator.GetImpl(), "replicated_result", None)
    if res is not None:
        # JaxSimulatorImpl lifted the graph: node 1 is the program's node
        # 0, nodes 0 and 2 its nodes 1 and 2
        import numpy as np

        out = res["out"]
        flow_rx = np.asarray(out["flow_rx"], float)
        for k, (sender, _, start) in enumerate(FLOWS, start=1):
            mbps = flow_rx[:, k].mean() * PACKET_BYTES * 8 / (sim_time - start) / 1e6
            print(f"Flow {sender} -> 1: rx packets mean={flow_rx[:, k].mean():.1f} "
                  f"throughput={mbps:.4f} Mbps")
        print(
            f"replicas={res['replicas']} rtsCts={int(rts_cts)} "
            f"tx_rts={np.mean(out['tx_rts']):.1f} "
            f"cts_timeout={np.mean(out['cts_timeout']):.1f} "
            f"rx_lost_hidden={np.mean(out['rx_lost_hidden']):.1f} "
            f"drops={np.mean(out['drops']):.1f} steps={out['steps']} "
            f"all_done={out['all_done']} wall_incl_compile={wall:.2f}s"
        )
        Simulator.Destroy()
        return 0 if out["all_done"] and flow_rx[:, 1:].sum(1).mean() > 0 else 1

    monitor.CheckForLostPackets()
    total = 0
    for fid, st in sorted(monitor.GetFlowStats().items()):
        flow = fmh.GetClassifier().FindFlow(fid)
        if flow.destination_port != SINK_PORT:
            continue
        mbps = st.rx_bytes * 8.0 / (sim_time - 1.0) / 1e6
        total += st.rx_packets
        print(f"Flow {fid} ({flow.source} -> {flow.destination})")
        print(f"  Tx Packets: {st.tx_packets}")
        print(f"  Rx Packets: {st.rx_packets}")
        print(f"  Throughput: {mbps:.4f} Mbps")
    print(f"wall={wall:.2f}s")
    Simulator.Destroy()
    return 0 if total > 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
