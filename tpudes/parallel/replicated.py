"""Replica-axis execution of real simulations (SURVEY.md §7 step 7).

The north star's headline capability: run R Monte-Carlo replicas of an
*actual scenario* — not a synthetic kernel — on the TPU at once.

Design (the "union schedule" of SURVEY.md §7 hard-part 6, taken to its
TPU-native conclusion): replicas of one scenario share topology and the
*candidate* event structure but diverge in RNG-driven data (PHY coin
flips, backoff draws).  Because replicas are mutually independent, no
cross-replica event ordering exists — so instead of forcing one host
loop to drive R masked replicas, the scenario itself is **lowered to a
vectorized event-stepped program**: per-replica state lives in (R, N)
arrays, and one ``lax.while_loop`` iteration advances *every replica to
its own next event time* (arrival, backoff expiry, transmission).  Time
is a per-replica scalar, exactly as in a DES — just R of them at once.

This mirrors upstream's granted-time-window engine
(distributed-simulator-impl.cc, SURVEY.md §3.3) with the roles rotated:
the "ranks" are replicas, the LBTS grant is the loop's global
all-replicas-done reduction, and the per-rank event loop is the masked
vector update.

Scope: the infrastructure-BSS scenario (BASELINE.json config #3) — AP +
N STAs, DCF MAC, Yans PHY with log-distance loss, NIST error model, UDP
echo traffic, beacons — and the IBSS of upstream's wifi-hidden-terminal
study: ad hoc stations under a matrix loss table, OnOff CBR senders into
one PacketSink, RTS/CTS above the MAC's threshold.  HT (802.11n) graphs
lift too: QoS AC_BE AIFS, HT-mixed preamble timing, and A-MPDU
aggregation under an established
BlockAck session (every data exchange becomes backlog-sized A-MPDU +
compressed BA, per-MPDU decode at the subframe bit share — the
phy._end_rx_ampdu model vectorized — and a retry count per MPDU, as
mac._finish_ampdu keeps it: a backlog rides the carry as its histogram
over the count; ``tx_mpdus`` counts what the PPDUs carried).  The
ADDBA handshake, like association/ARP, is warm-up and not modeled.
``lower_bss`` builds the program's static inputs
from the *live object graph* a scenario script constructed (helpers,
attributes, station manager), so ``wifi-bss.py --replicas=R`` runs the
same config the sequential engine runs.  The scalar DES remains the
per-event oracle; tests check distribution-level parity of delivery
counts (SURVEY.md §4 — statistical, not bitwise, as f32 TPU replicas
cannot bit-match the host MRG32k3a path).

Timing model vs the scalar DES (all deviations are sub-slot or rare):
- 1 µs integer clock (DES: 1 ns); durations are ceil'd to µs.
- propagation delay (≤ ~83 ns at 25 m) is folded into the exchange
  duration rather than modeled per-link.
- on a failed exchange the medium frees after the data airtime (no ack
  is sent) while the sender personally waits out its ack timeout before
  recontending — as in the scalar DES.
- acks are assumed decodable (they ride a mandatory low rate over the
  same link that just decoded the data frame); association and ARP
  warm-up exchanges are not modeled — compare post-warm-up windows.
- when two senders tie on the same µs tx instant, each winner's frame
  is decoded independently at its destination (ok only requires the
  destination not to be transmitting), so one receiver can decode two
  overlapping frames in the same step; the scalar PHY locks onto the
  first preamble and drops the second as rx-busy.  Mutual interference
  keeps both psr values tiny, so the optimistic bias is small (ADVICE
  r2 low — documented deviation).
- carrier sense: a program whose every pair senses each other, with no
  RTS/CTS and an echoing AP, keeps the legacy step's single per-replica
  ``busy_until`` (every node senses every transmission; its jaxpr is what
  it was before the per-node arm).  A static program with hidden pairs,
  RTS/CTS, a PacketSink receiver or OnOff senders compiles the per-node
  arm (:func:`_build_medium_step`): busy time and NAV per (replica, node),
  backoff that freezes only on what the node itself senses, the RTS ->
  CTS -> DATA -> ACK exchange with CTS and ACK timeouts, and the
  interference each receiver's frame collects from every frame that
  overlaps it, through the NIST chunk success rate.  A MOBILE program
  keeps the single medium: ``lower_bss`` refuses a trajectory that leaves
  mutual sensing range at any point, and RTS/CTS, a sink or OnOff senders
  under motion.  RTS before an A-MPDU (the host MAC never sends one) and
  frame capture stay out of scope too.

Mobility (ISSUE-10): non-static node motion rides the scan as traced
operands (``tpudes.ops.mobility`` — closed-form const-velocity /
random-walk / waypoint trajectories, model id dispatched like the LTE
scheduler id) and the (R, N, N) loss/detectability tables live in the
carry, recomputed at each replica's own event time every
``geom_stride`` steps; the static path keeps its f64 host-precomputed
tables bit-for-bit.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from tpudes.fuzz.envelope import FuzzEnvelope
from tpudes.ops.interference import thermal_noise_w
from tpudes.ops.propagation import dbm_to_w, log_distance
from tpudes.ops.wifi_error import MODES_BY_NAME, mode_chunk_success_rate

# µs timing constants (models/wifi/mac.py; 802.11a OFDM 20 MHz)
SLOT = 9
SIFS = 16
DIFS = 34
CW_MIN = 15
CW_MAX = 1023
RETRY_LIMIT = 7
#: an MPDU's retry count runs 0..RETRY_LIMIT; one failure more drops it
RETRY_CLASSES = RETRY_LIMIT + 1
INF = np.int32(2**30)

#: the association + ARP (and, under aggregation, ADDBA) warm-up the
#: lowering skips, expressed as a time budget: on the scalar DES those
#: exchanges settle within a few hundred ms of the first app start.
#: Horizons within ~5× of this make the skipped transient a
#: first-order share of the outcome — lower_bss warns below the line.
MODELED_WARMUP_S = 0.25


#: the documented-faithful fuzz region (see :mod:`tpudes.fuzz`): radii
#: keep every STA pair inside mutual sensing range at the default 54
#: Mbps PHY (the single-medium step, and the mobile guard), horizons stay
#: past the ~1.25 s warm-up boundary so the skipped association/ARP transient is
#: second-order, and traffic is the UDP-echo shape the parity tests pin
FUZZ_ENVELOPE = FuzzEnvelope(
    engine="bss",
    axes={
        "n_stas": ("int", 2, 5),
        "radius": ("float", 10.0, 32.0),
        "interval_ms": ("choice", (60, 100, 150)),
        "packet_bytes": ("choice", (256, 512, 1024)),
        "sim_ms": ("int", 1300, 2000),
        "replicas": ("int", 2, 9),
        "chunk_divisor": ("choice", (2, 3)),
        "rng_run": ("int", 1, 8),
        "key_seed": ("int", 0, 2**16),
        # ISSUE-10 mobility draws (appended — axis order is part of
        # the seed→config contract): slow drifts keep the trajectory
        # inside the mutual-sensing guard at every in-envelope radius
        "mob_model": ("choice", ("static", "const_velocity",
                                 "random_walk")),
        "mob_speed": ("float", 0.3, 1.5),
        "geom_stride": ("choice", (1, 2, 4, 16)),
        # ISSUE-14 traffic draws (appended — axis order is part of the
        # seed→config contract): STA arrivals ride the drawn workload
        # model (beacons stay cbr); "off" keeps the legacy CBR advance
        "traffic": ("choice", ("off", "cbr", "mmpp", "onoff", "trace")),
        "tr_burst": ("float", 0.1, 0.6),
        "tr_phase": ("float", 0.0, 1.0),
    },
    floors={"replicas": 1, "n_stas": 1, "sim_ms": 1300},
    doc="AP + n STAs on one circle, UDP echo upstream, beacons on",
)


@dataclass(frozen=True)
class BssProgram:
    """Static description of one BSS scenario, ready to execute on the
    replica axis.  Produced by :func:`lower_bss` from a live object
    graph, or directly by tests/benchmarks."""

    positions: np.ndarray        # (N, 3) — node 0 is the AP
    data_mode_idx: int           # WifiMode index for data frames
    ack_mode_idx: int            # WifiMode index for the ack
    data_bytes: int              # on-air PSDU bytes of a data frame
    beacon_bytes: int            # on-air PSDU bytes of a beacon
    start_us: np.ndarray         # (N,) first app event per node (AP: beacon)
    interval_us: np.ndarray      # (N,) app period per node
    stop_us: np.ndarray          # (N,) no arrivals at/after this time
    sim_end_us: int
    tx_power_dbm: float = 16.0206
    path_loss_exponent: float = 3.0
    reference_loss_db: float = 46.6777
    noise_figure_db: float = 7.0
    bandwidth_hz: float = 20e6
    rx_sensitivity_dbm: float = -101.0
    #: contention AIFS for data (DIFS legacy; SIFS+3·SLOT for QoS AC_BE)
    aifs_us: int = DIFS
    #: A-MPDU cap: >1 turns every data exchange into an aggregated
    #: PPDU + compressed BlockAck under an (assumed-established) BA
    #: session, with retries counted per MPDU; 1 = legacy single-MPDU
    #: DATA/ACK (one retry count per node: it has one frame in flight)
    max_mpdus: int = 1
    #: on-air bytes of one A-MPDU subframe (delimiter + MPDU + FCS,
    #: padded to 4) — used instead of data_bytes when max_mpdus > 1
    subframe_bytes: int = 0
    #: device-resident motion (tpudes.ops.mobility.MobilityProgram):
    #: None = static geometry (the precomputed f64 pair tables).  The
    #: mobility PARAMS and model id are traced operands — only
    #: ``mobility.shape_key()`` enters the runner cache key, so a sweep
    #: across the model family reuses one executable.  Mobile geometry
    #: is computed in f32 on device (vs the static path's f64 host
    #: tables) — the documented precision of the moving regime.
    mobility: object = None
    #: recompute the pairwise loss matrix inside the kernel only every
    #: K event-loop steps (a traced operand — NOT a cache-key
    #: component).  stride=1 is bit-identical to per-step recompute;
    #: the trajectory itself is closed-form in time, so a strided run
    #: samples the same motion, just less often (the stride contract,
    #: pinned like TPUDES_BUCKETING's).
    geom_stride: int = 1
    #: device-resident workload (tpudes.traffic.TrafficProgram over the
    #: N nodes; entity 0 is the AP's beacon process): None = the legacy
    #: CBR advance (bit-identical compile).  The model id and every
    #: traffic parameter are traced operands — only
    #: ``traffic.shape_key()`` enters the runner cache key, so a sweep
    #: across the whole workload family reuses one executable.  The
    #: program's first arrivals still come from ``start_us``; the
    #: traffic stage supplies every subsequent inter-arrival gap,
    #: keyed ``fold_in(key, replica, entity, t)`` (bucketing/chunking/
    #: checkpoint stay bit-exact).  A matching cbr program is pinned
    #: bit-equal to ``traffic=None`` (the ``traffic_off`` fuzz pair).
    traffic: object = None
    #: (N, N) pair loss in dB, transmitter by row, receiver by column,
    #: read from a MatrixPropagationLossModel; None = the log-distance
    #: formula over ``positions``.  Its pairs below rx sensitivity are
    #: hidden pairs: they do not sense each other (the per-node arm)
    loss_db: np.ndarray | None = None
    #: a data frame whose on-air bytes exceed this is RTS/CTS-protected
    #: (the MAC's RtsCtsThreshold; the A-MPDU arm never protects)
    rts_threshold: int = 65535
    #: node 0's role: True = an AP whose echo server answers every
    #: request; False = a PacketSink that only acknowledges (an IBSS
    #: receiver: no echo, and no beacon unless ``start_us[0]`` says so)
    echo: bool = True
    #: OnOff clients: (N,) on and off durations in µs of each node's
    #: send cycle (a send at the start of every on period, then one a
    #: ``interval_us`` while on); None = plain CBR
    on_us: np.ndarray | None = None
    off_us: np.ndarray | None = None
    #: carrier sense of the per-node arm: "pairs" = a node senses the
    #: transmissions that reach it at rx sensitivity; "shared" = every
    #: node senses every transmission, the single medium of the legacy
    #: step (the control the hidden-terminal benchmark cell names)
    carrier_sense: str = "pairs"

    @property
    def n(self) -> int:
        return int(self.positions.shape[0])


def _preamble_us(mode) -> int:
    """20 µs legacy preamble+L-SIG; HT-family adds the 16 µs HT-mixed
    fields (phy.HT_PREAMBLE_EXTRA_S)."""
    return 36 if mode.standard == "ht" else 20


def _ppdu_us(size_bytes: int, mode) -> int:
    """PPDU airtime in whole µs (ceil), matching phy.ppdu_duration_s."""
    ndbps = mode.data_rate_bps * 4e-6
    nsym = math.ceil((16 + 8 * size_bytes + 6) / ndbps)
    return _preamble_us(mode) + nsym * 4


class UnliftableScenarioError(ValueError):
    """Raised when a scenario's object graph cannot be represented on the
    replica axis without silently changing its physics or traffic — the
    caller should fall back to the scalar DES (ADVICE r2: reject what the
    lowering can't represent rather than mis-lower)."""


def lower_bss(
    sta_devices, ap_device, echo_clients, sim_end_s: float,
    geom_stride: int = 1,
) -> BssProgram:
    """Lower a constructed BSS object graph to a replicated program.

    ``ap_device`` is the receiving node's device and becomes node 0: an
    AP (ApWifiMac: beacons, the echo server answering every request)
    or, in an IBSS (AdhocWifiMac), the PacketSink's node, which only
    acknowledges.  ``sta_devices`` are the other nodes' devices and
    ``echo_clients`` their sending applications: UdpEchoClients, or
    OnOffApplications with constant on and off times, all to node 0.

    Reads positions from each node's mobility model, PHY attributes
    (power, sensitivity, noise figure, bandwidth) from the AP's
    YansWifiPhy, the *configured* propagation model from the channel
    (one LogDistancePropagationLossModel, or one
    MatrixPropagationLossModel read pair by pair), the data mode from
    the devices' station manager (ConstantRate), the MAC's
    RtsCtsThreshold, and traffic from the client apps.  Pairs that
    cannot sense each other (hidden pairs), RTS/CTS, the sink role
    and OnOff senders compile the per-node arm of the step
    (:func:`per_node_medium`).  Anything the BssProgram cannot
    faithfully represent raises :class:`UnliftableScenarioError`.

    Non-static mobility models lift too (``tpudes.ops.mobility``):
    node motion becomes traced operands of the scan and the pairwise
    loss matrix is recomputed inside the kernel every ``geom_stride``
    event-loop steps.  ``TPUDES_DEVICE_GEOM=0`` restores the loud
    refusal (host-DES fallback for moving graphs).  A moving graph
    keeps the single medium: every pair must sense each other over
    the whole trajectory, with no RTS/CTS and an echoing AP.
    """
    from tpudes.models.applications import OnOffApplication, UdpEchoClient
    from tpudes.models.mobility import (
        MobilityModel,
        UnliftableMobilityError,
        device_mobility_program,
    )
    from tpudes.models.propagation import (
        LogDistancePropagationLossModel,
        MatrixPropagationLossModel,
    )
    from tpudes.models.wifi.mac import (
        FCS_SIZE,
        MAC_HEADER_SIZE,
        AdhocWifiMac,
        ApWifiMac,
        control_answer_mode,
    )
    from tpudes.models.wifi.rate_control import ConstantRateWifiManager
    from tpudes.ops.mobility import device_geom_enabled

    if sim_end_s < 5.0 * MODELED_WARMUP_S:
        import warnings

        warnings.warn(
            f"sim_end_s={sim_end_s} s is within ~5x of the association/"
            f"ARP/ADDBA warm-up (~{MODELED_WARMUP_S} s) this lowering "
            "skips; replica-axis outcomes over so short a horizon are "
            "dominated by the unmodeled transient — extend the horizon "
            "or compare post-warm-up windows on the scalar DES",
            stacklevel=2,
        )

    ap_node = ap_device.GetNode()
    nodes = [ap_node] + [d.GetNode() for d in sta_devices]
    mobs = [n.GetObject(MobilityModel) for n in nodes]
    positions = np.array(
        [(lambda p: (p.x, p.y, p.z))(m.GetPosition()) for m in mobs],
        dtype=np.float32,
    )
    sim_end_us = int(sim_end_s * 1e6)
    mobile = any(not m.is_static for m in mobs)
    mobility = None
    if mobile:
        if not device_geom_enabled():
            raise UnliftableScenarioError(
                "topology is mobile and device-resident geometry is "
                "disabled (TPUDES_DEVICE_GEOM=0) — run the host DES"
            )
        try:
            mobility = device_mobility_program(nodes, sim_end_us)
        except UnliftableMobilityError as e:
            raise UnliftableScenarioError(str(e)) from e

    phy = ap_device.GetPhy()
    mac = ap_device.GetMac()
    macs = [mac] + [d.GetMac() for d in sta_devices]
    if isinstance(mac, ApWifiMac):
        if any(isinstance(m, AdhocWifiMac) for m in macs):
            raise UnliftableScenarioError(
                "an AP among ad hoc stations: one BSS shape a program"
            )
    elif not (
        isinstance(mac, AdhocWifiMac)
        and all(isinstance(m, AdhocWifiMac) for m in macs)
    ):
        raise UnliftableScenarioError(
            "node 0 must be an AP, or every node an ad hoc (IBSS) station"
        )

    # --- configured physics (ADVICE r2 low: read, don't default) ---------
    channel = phy.GetChannel()
    loss = getattr(channel, "_loss", None)
    loss_db = None
    if isinstance(loss, MatrixPropagationLossModel) and loss.GetNext() is None:
        if mobility is not None:
            raise UnliftableScenarioError(
                "a matrix loss table under motion: the moving geometry "
                "is log-distance only"
            )
        loss_db = np.array(
            [[0.0 if a is b else -float(loss.DoCalcRxPower(0.0, a, b))
              for b in mobs] for a in mobs],
            dtype=np.float64,
        )
    elif not isinstance(loss, LogDistancePropagationLossModel) or loss.GetNext() is not None:
        raise UnliftableScenarioError(
            f"replica axis supports a single LogDistancePropagationLossModel "
            f"or MatrixPropagationLossModel; channel has {type(loss).__name__}"
            + (" with a chained next model" if loss is not None and loss.GetNext() else "")
        )
    elif abs(float(loss.reference_distance) - 1.0) > 1e-9:
        raise UnliftableScenarioError(
            f"replica axis assumes ReferenceDistance=1 m (got {loss.reference_distance})"
        )
    delay = getattr(channel, "_delay", None)
    if delay is not None and not hasattr(delay, "speed"):
        raise UnliftableScenarioError(
            "stochastic propagation delay models cannot be lifted"
        )

    sm = mac._station_manager
    if not isinstance(sm, ConstantRateWifiManager):
        raise UnliftableScenarioError(
            f"replica axis needs ConstantRateWifiManager (got {type(sm).__name__}); "
            "adaptive rate control diverges per replica"
        )
    data_mode = sm.get_data_mode(None)
    ampdu_sizes = {int(getattr(m, "max_ampdu_size", 0)) for m in macs}
    qos_flags = {bool(getattr(m, "qos_supported", False)) for m in macs}
    rts_thresholds = {int(getattr(m, "rts_cts_threshold", 65535)) for m in macs}
    if len(ampdu_sizes) > 1 or len(qos_flags) > 1 or len(rts_thresholds) > 1:
        raise UnliftableScenarioError(
            f"mixed per-device MAC configs (MaxAmpduSize {sorted(ampdu_sizes)}, "
            f"QosSupported {sorted(qos_flags)}, RtsCtsThreshold "
            f"{sorted(rts_thresholds)}) cannot ride one vector MAC model"
        )
    max_ampdu_size = ampdu_sizes.pop()
    qos = qos_flags.pop()
    rts_threshold = rts_thresholds.pop()

    n = len(nodes)
    start = np.full((n,), INF, dtype=np.int64)
    interval = np.full((n,), INF, dtype=np.int64)
    stop = np.full((n,), INF, dtype=np.int64)
    on_us = np.full((n,), INF, dtype=np.int64)
    off_us = np.zeros((n,), dtype=np.int64)
    payloads, kinds = set(), set()
    for app in echo_clients:
        idx = nodes.index(app.GetNode())
        if idx == 0:
            raise UnliftableScenarioError(
                "the receiving node sends: the replica axis models "
                "traffic into node 0 only"
            )
        start[idx] = int(app.start_time.ticks // 1000)
        stop[idx] = (
            int(app.stop_time.ticks // 1000) if app.stop_time.ticks > 0 else INF
        )
        kinds.add(type(app))
        if isinstance(app, UdpEchoClient):
            interval[idx] = max(1, int(app.interval.ticks // 1000))
        elif isinstance(app, OnOffApplication):
            on_s, off_s = (_constant_seconds(v, name) for v, name in (
                (app.on_time, "OnTime"), (app.off_time, "OffTime")))
            if int(app.max_bytes) or on_s <= 0.0:
                raise UnliftableScenarioError(
                    "OnOffApplication with MaxBytes or no on time: "
                    "not a periodic sender"
                )
            iv_ticks = int(app.data_rate.CalculateBytesTxTime(
                int(app.packet_size)).ticks)
            on_ticks = int(round(on_s * 1e9))
            interval[idx] = max(1, iv_ticks // 1000)
            # the host sends at every on period's start and one an
            # interval while on (strictly before its end): that many
            # sends a period, whatever the µs clock rounds
            sends = -(-on_ticks // iv_ticks)
            on_us[idx] = (sends - 1) * int(interval[idx]) + 1
            off_us[idx] = int(round((on_s + off_s) * 1e6)) - on_us[idx]
        else:
            raise UnliftableScenarioError(
                f"{type(app).__name__} is not a sender the replica axis models"
            )
        payloads.add(int(app.packet_size))
    if len(payloads) > 1:
        raise UnliftableScenarioError(
            f"replica axis models one on-air frame size; clients use {sorted(payloads)}"
        )
    if len(kinds) > 1:
        raise UnliftableScenarioError(
            "echo clients and OnOff senders in one graph: one traffic "
            "shape a program"
        )
    echo = isinstance(mac, ApWifiMac) and kinds != {OnOffApplication}
    payload = payloads.pop() if payloads else 0
    # AP slot: beacons
    if getattr(mac, "enable_beaconing", False) and int(mac.beacon_interval_us) > 0:
        start[0] = 0
        interval[0] = int(mac.beacon_interval_us)
        stop[0] = INF

    # on-air data PSDU: payload + UDP(8) + IPv4(20) + LLC/SNAP(8) + MAC(24) + FCS(4)
    data_bytes = payload + 8 + 20 + 8 + MAC_HEADER_SIZE + FCS_SIZE
    # aggregation (mpdu-aggregator analog): every data exchange to an
    # established-BA peer becomes an A-MPDU + compressed BlockAck; the
    # two-frame ADDBA handshake is warm-up, excluded like association/ARP
    max_mpdus, subframe_bytes = 1, 0
    if max_ampdu_size > 0:
        from tpudes.models.wifi.mac import MAX_AMPDU_FRAMES, _ampdu_subframe_bytes

        subframe_bytes = _ampdu_subframe_bytes(
            payload + 8 + 20 + 8 + MAC_HEADER_SIZE
        )
        max_mpdus = max(1, min(MAX_AMPDU_FRAMES, max_ampdu_size // subframe_bytes))
    beacon_bytes = 50 + MAC_HEADER_SIZE + FCS_SIZE
    ack_mode = control_answer_mode(data_mode)
    on_off = OnOffApplication in kinds

    tx_power_dbm = float(phy.tx_power_start + phy.tx_gain)
    log_distance = {} if loss_db is not None else dict(
        path_loss_exponent=float(loss.exponent),
        reference_loss_db=float(loss.reference_loss),
    )
    prog = BssProgram(
        positions=positions,
        data_mode_idx=data_mode.index,
        ack_mode_idx=ack_mode.index,
        data_bytes=data_bytes,
        beacon_bytes=beacon_bytes,
        start_us=np.minimum(start, INF).astype(np.int32),
        interval_us=np.minimum(interval, INF).astype(np.int32),
        stop_us=np.minimum(stop, INF).astype(np.int32),
        sim_end_us=sim_end_us,
        mobility=mobility,
        geom_stride=int(geom_stride),
        tx_power_dbm=tx_power_dbm,
        **log_distance,
        noise_figure_db=float(phy.noise_figure),
        bandwidth_hz=float(phy.channel_width) * 1e6,
        rx_sensitivity_dbm=float(phy.rx_sensitivity),
        # QoS data rides AC_BE (AIFSN 3); beacons' AC_VO AIFS (34 µs)
        # is approximated by the same value — ≤9 µs per beacon
        aifs_us=(SIFS + 3 * SLOT) if qos else DIFS,
        max_mpdus=max_mpdus,
        subframe_bytes=subframe_bytes,
        loss_db=loss_db,
        rts_threshold=rts_threshold,
        echo=echo,
        on_us=np.minimum(on_us, INF).astype(np.int32) if on_off else None,
        off_us=np.minimum(off_us, INF).astype(np.int32) if on_off else None,
    )
    if max_mpdus > 1 and hidden_pairs(prog):
        raise UnliftableScenarioError(
            "hidden pairs under A-MPDU aggregation: the per-node medium "
            "runs the single-MPDU exchange only"
        )

    # --- a moving graph keeps the single medium: every node must be
    # able to sense every other over the WHOLE trajectory, sampled on a
    # dense grid through the same closed-form kernel the scan traces.
    if mobility is not None:
        from tpudes.ops.mobility import (
            max_speed_mps,
            trajectory_positions,
            warn_geom_stride,
        )

        if rts_protected(prog) or not prog.echo or prog.on_us is not None:
            raise UnliftableScenarioError(
                "a moving graph with RTS/CTS, a non-echoing receiver or "
                "OnOff senders: the moving geometry rides the single-"
                "medium step only — run the scalar DES"
            )
        # sample density derived from the max speed so no excursion
        # can slip between samples by more than ~0.5 m of relative
        # displacement (bounded by 1025 samples); walks additionally
        # get the EXACT worst case below, since their reachable set is
        # the whole bounds rectangle regardless of sampled positions
        n_samp = int(
            np.clip(
                math.ceil(2.0 * max_speed_mps(mobility) * sim_end_s),
                65, 1025,
            )
        )
        grid = np.linspace(0, sim_end_us, n_samp).astype(np.int64)
        hidden = UnliftableScenarioError(
            "trajectory leaves mutual sensing range (hidden-node "
            "regime at some point of the run); the single-medium "
            "carrier-sense model cannot represent it — shrink "
            "the motion bounds or run the scalar DES"
        )
        for pos_t in trajectory_positions(mobility, grid):
            if not bool(
                (
                    _pairwise_rx_dbm(
                        dataclasses.replace(
                            prog, positions=pos_t.astype(np.float32)
                        )
                    )
                    >= prog.rx_sensitivity_dbm
                ).all()
            ):
                raise hidden
        if mobility.model == "random_walk" and not _walk_worst_case_ok(
            prog, mobility
        ):
            raise hidden
        warn_geom_stride(
            "lower_bss", mobility, int(geom_stride),
            _bss_nominal_step_s(prog),
        )
    return prog


def _constant_seconds(variable, name: str) -> float:
    """An OnOff app's on or off time, which the lowering needs constant."""
    from tpudes.core.rng import ConstantRandomVariable

    if not isinstance(variable, ConstantRandomVariable):
        raise UnliftableScenarioError(
            f"OnOffApplication {name} is {type(variable).__name__}: the "
            "replica axis models constant on and off times only"
        )
    return float(variable.GetValue())


def _walk_worst_case_ok(prog: BssProgram, mobility) -> bool:
    """EXACT mutual-sensing bound for random walks: a walker's
    reachable set is its whole bounds rectangle, so the worst pair
    separation is the rectangle diagonal (walker-walker) or the
    farthest corner from each pinned node (walker-static) — no sampled
    trajectory can prove these unreachable."""
    xmin, xmax, ymin, ymax = (float(v) for v in mobility.bounds)
    corners = np.array(
        [(xmin, ymin), (xmin, ymax), (xmax, ymin), (xmax, ymax)]
    )
    moving = mobility.speed[:, 1] > 0.0
    zs = mobility.base_pos[:, 2].astype(np.float64)
    dz_mm = (
        float(np.abs(zs[moving][:, None] - zs[moving][None, :]).max())
        if moving.sum() >= 2 else 0.0
    )
    worst = 0.0
    if moving.sum() >= 2:
        diag = math.hypot(xmax - xmin, ymax - ymin)
        worst = math.hypot(diag, dz_mm)
    for pos in mobility.base_pos[~moving].astype(np.float64):
        for z_m in zs[moving]:
            d_xy = np.sqrt(((corners - pos[None, :2]) ** 2).sum(-1)).max()
            worst = max(worst, math.hypot(float(d_xy), float(pos[2] - z_m)))
    loss = prog.reference_loss_db + 10.0 * prog.path_loss_exponent * (
        math.log10(max(worst, 1.0))
    )
    return prog.tx_power_dbm - loss >= prog.rx_sensitivity_dbm


def _bss_nominal_step_s(prog: BssProgram) -> float:
    """The nominal inter-step wall of the event loop — total offered
    events over the horizon — used ONLY to express ``geom_stride`` in
    seconds for the coherence advisory (arrival + tx + ack per frame,
    the same accounting _estimate_max_steps uses without its retry
    slack)."""
    return prog.sim_end_us * 1e-6 / max(
        3 * _total_offered_arrivals(prog), 1
    )


def _pairwise_rx_dbm(prog: BssProgram) -> np.ndarray:
    """(N, N) tx→rx power (dBm) under the program's log-distance physics,
    float64; diagonal entries are the (never-used) self-pairs at d=1 m.
    Single source of truth for both the build_bss_step physics tables and
    lower_bss's mutual-sensing guard.  A matrix loss table replaces the
    formula."""
    if prog.loss_db is not None:
        return prog.tx_power_dbm - np.asarray(prog.loss_db, np.float64)
    pos = prog.positions.astype(np.float64)
    d = np.sqrt(((pos[:, None, :] - pos[None, :, :]) ** 2).sum(-1))
    np.fill_diagonal(d, 1.0)
    loss = prog.reference_loss_db + 10.0 * prog.path_loss_exponent * np.log10(
        np.maximum(d, 1.0)
    )
    return prog.tx_power_dbm - loss


def _total_offered_arrivals(prog: BssProgram) -> int:
    """App arrivals offered over the horizon — shared by the step-bound
    estimate and the geom_stride coherence advisory so the arrival
    accounting cannot desynchronize between them."""
    total = 0
    for s1, iv, s2 in zip(prog.start_us, prog.interval_us, prog.stop_us):
        if s1 >= INF or iv >= INF:
            continue
        horizon = min(int(s2), prog.sim_end_us)
        if horizon > int(s1):
            total += (horizon - int(s1) + int(iv) - 1) // int(iv)
    return total


def _estimate_max_steps(prog: BssProgram, per_node: bool) -> int:
    # one arrival event + up to 1+RETRY_LIMIT tx events per frame, plus
    # same-instant arrival/tx splits; generous slack.  A traffic
    # program replaces the CBR count with the workload's own offered
    # total (the host mirror of the device cum kernel — bursty models
    # offer more than the nominal arrays say).
    total = _total_offered_arrivals(prog)
    if prog.traffic is not None:
        from tpudes.traffic.host import offered_packets

        horizon = np.minimum(
            prog.stop_us.astype(np.int64), prog.sim_end_us
        )
        total = max(
            total,
            int(np.ceil(offered_packets(prog.traffic, horizon).sum())),
        )
    if per_node:
        # (per_node_medium(prog), which the caller knows) an attempt is
        # at most RTS, CTS, DATA and ACK, a start and an end each, or a
        # frame, its end and a timeout
        return int(total * (1 + 8 * (1 + RETRY_LIMIT)) * 1.5) + 64
    return int(total * (3 + RETRY_LIMIT) * 1.5) + 64


def sense_mask(prog: BssProgram) -> np.ndarray:
    """(N, N) bool: whether node j (column) senses node i's (row)
    transmissions, i.e. receives them at rx sensitivity or above; the
    diagonal is False.  Under ``carrier_sense="shared"`` every node
    senses every other."""
    n = prog.n
    if prog.carrier_sense == "shared":
        mask = np.ones((n, n), bool)
    else:
        mask = _pairwise_rx_dbm(prog) >= prog.rx_sensitivity_dbm
    np.fill_diagonal(mask, False)
    return mask


def hidden_pairs(prog: BssProgram) -> int:
    """Node pairs of which at least one cannot sense the other (under
    the program's physics, whatever ``carrier_sense`` says)."""
    rx = _pairwise_rx_dbm(prog) >= prog.rx_sensitivity_dbm
    both = rx & rx.T
    return int((~both)[np.triu_indices(prog.n, 1)].sum())


def rts_protected(prog: BssProgram) -> bool:
    """Whether every data frame goes through RTS/CTS (the host MAC
    protects frames strictly larger than the threshold; the A-MPDU
    exchange never does)."""
    return prog.max_mpdus <= 1 and prog.data_bytes > int(prog.rts_threshold)


#: per_node_medium's answer by program object: a launch asks it twice,
#: and the pair table of a 65-node program costs some 0.3 ms of host
_PER_NODE: dict = {}


def per_node_medium(prog: BssProgram) -> bool:
    """Whether the program needs the per-node arm of the step: hidden
    pairs, RTS/CTS, a receiver that does not echo, OnOff senders, or
    the shared-medium control.  Every other program (the AP + echo
    shape in mutual sensing range) compiles the legacy step, equation
    for equation what it was."""
    import weakref

    held = _PER_NODE.get(id(prog))
    if held is not None and held[0]() is prog:
        return held[1]
    answer = prog.max_mpdus <= 1 and (
        rts_protected(prog)
        or not prog.echo
        or prog.on_us is not None
        or prog.carrier_sense != "pairs"
        or hidden_pairs(prog) > 0
    )
    if len(_PER_NODE) > 256:
        _PER_NODE.clear()
    _PER_NODE[id(prog)] = (weakref.ref(prog), answer)
    return answer


#: result fields of the per-node arm beside the legacy ones (``queue``
#: comes back as ``backlog``)
MEDIUM_FIELDS = (
    "flow_rx", "tx_by", "tx_rts", "cts_timeout", "rx_lost_hidden", "queue",
)

#: frame kinds on the air in the per-node arm (indices of its tables)
F_RTS, F_CTS, F_DATA, F_ACK, F_BEACON = range(1, 6)
#: a node's own exchange: contending; RTS sent, CTS awaited; CTS heard,
#: DATA due a SIFS after it; DATA sent, ACK awaited
P_IDLE, P_WAIT_CTS, P_DATA_DUE, P_WAIT_ACK = range(4)


def _medium_frames(prog: BssProgram) -> dict:
    """Airtimes, modes, bit rates and NAV fields of the per-node arm's
    frame kinds (index 0 is unused), and the response timeouts, as the
    host MAC times them (``mac._send_rts``, ``_send_cts``,
    ``_response_timeout_s``): RTS 20 B, CTS and ACK 14 B at the control
    answer rate, the beacon at 6 Mbps.  Only RTS and CTS carry a NAV
    (the host MAC leaves a data frame's duration field at 0)."""
    from tpudes.ops.wifi_error import ALL_MODES

    data_mode = ALL_MODES[prog.data_mode_idx]
    ctrl_mode = ALL_MODES[prog.ack_mode_idx]
    beacon_mode = MODES_BY_NAME["OfdmRate6Mbps"]
    dur = [
        0, _ppdu_us(20, ctrl_mode), _ppdu_us(14, ctrl_mode),
        _ppdu_us(prog.data_bytes, data_mode), _ppdu_us(14, ctrl_mode),
        _ppdu_us(prog.beacon_bytes, beacon_mode),
    ]
    mode = [
        ctrl_mode, ctrl_mode, ctrl_mode, data_mode, ctrl_mode, beacon_mode,
    ]
    nav_rts = 3 * SIFS + dur[F_CTS] + dur[F_DATA] + dur[F_ACK]
    return dict(
        dur=dur,
        mode_idx=[m.index for m in mode],
        rate_bps=[float(m.data_rate_bps) for m in mode],
        nav=[0, nav_rts, max(nav_rts - SIFS - dur[F_CTS], 0), 0, 0, 0],
        cts_timeout=dur[F_RTS] + SIFS + dur[F_CTS] + SLOT + 4,
        ack_timeout=dur[F_DATA] + SIFS + dur[F_ACK] + SLOT + 4,
    )


def _build_medium_step(prog: BssProgram, replicas: int):
    """``(init_state, pending, step_fn)`` of the per-node arm: every
    node has its own medium.

    Time advances event by event, as in the legacy step, but an event
    is any of: an application arrival; a contention grant (AIFS plus
    the backoff after the later of the node's physical busy time, its
    NAV and its own hold); a frame's end; a scheduled SIFS send (CTS,
    DATA after a CTS, ACK); a CTS or ACK timeout.  Arrivals take a
    step of their own when they come first, as in the legacy step;
    what is on the air is reckoned from ``t_air``, the last step that
    was not an arrival step, so a frame that ends in the µs of an
    arrival is still decoded in that µs.  Every other event at the
    instant is handled in one step, in this
    order: the interference chunk that just ended is charged to every
    frame being received; frames that end are decoded or lost (a
    coin against the product of their chunks' NIST success rates),
    and what they mean is applied (RTS -> CTS due, CTS -> DATA due,
    DATA -> delivered once and ACK due, ACK -> success; RTS and CTS
    overheard set the NAV); timeouts fail the exchange (retry count,
    CW doubling, drop past the retry limit); then the instant's
    transmissions start, a node that is not transmitting and not
    locked onto a frame locks onto the strongest that starts and
    reaches it, and every node that senses a start is busy until its
    end.  A contender whose idle start moves is credited with the
    slots it counted (a zero-backoff grant cut short draws anew).

    The MAC is the host's (``models/wifi/mac.py``): one retry count for
    RTS and DATA failures alike, 7 retries, no EIFS, a queue with no
    limit.  Departures from the host PHY, as in the legacy step: a 1 µs
    clock, a node senses exactly what reaches it at rx sensitivity (no energy
    threshold), two frames that start in the same µs at one receiver
    lock it onto the stronger, and a frame already on the air when a
    receiver frees up is interference only, as on the host."""
    from tpudes.parallel.runtime import step_keys

    n = prog.n
    R = replicas
    fr = _medium_frames(prog)
    AIFS = int(prog.aifs_us)
    RTS = rts_protected(prog)
    ON_OFF = prog.on_us is not None

    rx_dbm_np = _pairwise_rx_dbm(prog)
    rx_w_np = 10.0 ** ((rx_dbm_np - 30.0) / 10.0)
    np.fill_diagonal(rx_w_np, 0.0)
    sense_np = sense_mask(prog)
    rx_w = jnp.asarray(rx_w_np, jnp.float32)                 # (N tx, N rx)
    # a frame reaches a receiver (can be locked onto) at sensitivity;
    # physical carrier sense follows the program's sense mask
    reach = jnp.asarray(
        (rx_dbm_np >= prog.rx_sensitivity_dbm) & ~np.eye(n, dtype=bool)
    )
    sense = jnp.asarray(sense_np)
    # the sender of frame i cannot sense node k (row i, column k)
    unsensed = jnp.asarray(~sense_np.T & ~np.eye(n, dtype=bool))
    noise_w = float(thermal_noise_w(prog.bandwidth_hz, prog.noise_figure_db))
    modes = sorted(set(fr["mode_idx"][1:]))

    def by_kind(table, kind, dtype=jnp.int32):
        """``table[kind]`` for a per-kind table, as selects (no gather)"""
        out = jnp.zeros(kind.shape, dtype)
        for k in range(1, len(table)):
            out = jnp.where(kind == k, jnp.asarray(table[k], dtype), out)
        return out

    def chunk_success(sinr, span_us, kind):
        """NIST success of a frame's stretch of ``span_us`` at ``sinr``, at
        its kind's mode: each mode's closed form, selected by kind"""
        from tpudes.ops.wifi_error import ALL_MODES, chunk_success_rate

        out = jnp.ones(sinr.shape, jnp.float32)
        for idx in modes:
            m = ALL_MODES[idx]
            rate = jnp.float32(m.data_rate_bps * 1e-6)
            ok = chunk_success_rate(sinr, rate * span_us, m.constellation,
                                    m.rate_class)
            of_mode = np.array([i == idx for i in fr["mode_idx"]])
            out = jnp.where(by_kind(of_mode, kind, bool), ok, out)
        return out

    start0 = jnp.asarray(prog.start_us, jnp.int32)
    interval = jnp.asarray(prog.interval_us, jnp.int32)
    stop = jnp.asarray(prog.stop_us, jnp.int32)
    if ON_OFF:
        on = jnp.asarray(prog.on_us, jnp.int32)
        cycle = jnp.asarray(
            np.minimum(
                prog.on_us.astype(np.int64) + prog.off_us.astype(np.int64),
                INF,
            ),
            jnp.int32,
        )
    node = jnp.arange(n)
    is_ap = node == 0
    echo = bool(prog.echo)

    def init_state():
        zi = jnp.zeros((R, n), jnp.int32)
        return dict(
            t=jnp.zeros((R,), jnp.int32),
            t_air=jnp.zeros((R,), jnp.int32),   # the air reckoned up to
            next_arr=jnp.broadcast_to(start0, (R, n)).astype(jnp.int32),
            queue=zi,                 # frames waiting at each sender
            ap_pend=zi,               # echoes waiting at the AP, per STA
            bcn_pend=jnp.zeros((R,), jnp.int32),
            backoff=zi,
            hold=zi,                  # own tx end / timeout: no contending before
            immediate=jnp.zeros((R, n), bool),
            cw=jnp.full((R, n), CW_MIN, jnp.int32),
            retries=zi,
            cs=zi,                    # physical carrier sense: busy until
            nav=zi,                   # virtual carrier sense: busy until
            phase=zi,                 # own exchange (P_*)
            due=jnp.full((R, n), INF, jnp.int32),   # its timeout / DATA send
            xdst=zi,                  # its destination
            got=jnp.zeros((R, n), bool),            # its frame delivered once
            resp_t=jnp.full((R, n), INF, jnp.int32),  # SIFS response due
            resp_kind=zi,
            resp_dst=zi,
            tx_end=zi,                # own transmission: on the air until
            tx_kind=zi,
            tx_dst=zi,
            tx_hid=jnp.zeros((R, n), bool),  # overlapped by an unsensed node
            rx_src=jnp.full((R, n), -1, jnp.int32),  # frame locked onto
            rx_p=jnp.ones((R, n), jnp.float32),      # its success so far
            srv_rx=jnp.zeros((R,), jnp.int32),
            cli_rx=zi,
            flow_rx=zi,               # data frames of each sender delivered
            tx_by=zi,                 # data frames each node sent
            tx_data=jnp.zeros((R,), jnp.int32),
            tx_rts=jnp.zeros((R,), jnp.int32),
            cts_timeout=jnp.zeros((R,), jnp.int32),
            rx_lost_hidden=jnp.zeros((R,), jnp.int32),
            drops=jnp.zeros((R,), jnp.int32),
            step=jnp.int32(0),
        )

    def has_frame(s):
        sta = (s["queue"] > 0) & ~is_ap[None, :]
        ap = is_ap[None, :] & (
            (s["bcn_pend"] > 0)
            | (jnp.sum(s["ap_pend"], axis=1, dtype=jnp.int32) > 0)
        )[:, None]
        return sta | ap

    def idle_start(s):
        return jnp.maximum(jnp.maximum(s["cs"], s["nav"]), s["hold"])

    def event_times(s):
        """(contention grant times, earliest other event) per replica."""
        with jax.named_scope("tpudes.bss.sense"):
            base = idle_start(s)
            t = s["t"][:, None]
            grant = jnp.where(
                s["immediate"], jnp.maximum(t, base),
                base + AIFS + s["backoff"] * SLOT,
            )
            grant = jnp.where(
                has_frame(s) & (s["phase"] == P_IDLE),
                jnp.maximum(grant, t), INF,
            )
            due = jnp.where(s["phase"] != P_IDLE, s["due"], INF)
            ends = jnp.where(
                s["tx_end"] > s["t_air"][:, None], s["tx_end"], INF
            )
            other = jnp.minimum(
                jnp.minimum(grant, due), jnp.minimum(s["resp_t"], ends)
            )
            return grant, jnp.min(other, axis=1)

    def pick(onehot, table):
        """(R, N) rows of per-node ``table`` (R, N) selected by a (R, N,
        N) one-hot over its last axis."""
        return jnp.sum(jnp.where(onehot, table[:, None, :], 0), axis=2)

    def step_fn(s, key, sim_end, geom=None, tr=None, tr_keys=None):
        rkeys = step_keys("bss", key, s["step"], R)

        def draw(kk):
            k_back, k_coin = jax.random.split(kk)
            return (
                jax.random.uniform(k_back, (n,), jnp.float32),
                jax.random.uniform(k_coin, (n,), jnp.float32),
            )

        u_back, u_coin = jax.vmap(draw)(rkeys)
        t0 = s["t"]
        grant_t, t_other = event_times(s)
        ta = jnp.min(s["next_arr"], axis=1)
        live = t0 < sim_end
        now = jnp.where(live, jnp.minimum(ta, t_other), sim_end)
        past_end = now >= sim_end
        arrived = live & (ta <= t_other) & (ta < INF) & ~past_end
        act = live & (t_other < ta) & (t_other < INF) & ~past_end
        now_c = now[:, None]
        act_c = act[:, None]
        frame = has_frame(s)
        base0 = idle_start(s)

        # ---------- arrivals (a step of their own) ----------
        is_arr = arrived[:, None] & (s["next_arr"] == now_c)
        queue = s["queue"] + jnp.where(is_arr & ~is_ap[None, :], 1, 0)
        bcn = s["bcn_pend"] + jnp.sum(
            jnp.where(is_arr & is_ap[None, :], 1, 0), axis=1,
            dtype=jnp.int32,
        )
        adv = s["next_arr"] + interval[None, :]
        if ON_OFF:
            # a send at every on period's start, then one an interval
            # while on: past the on time the next send opens the next cycle
            since = s["next_arr"] - start0[None, :]
            opened = start0[None, :] + (since // cycle[None, :]) * cycle[None, :]
            adv = jnp.where(
                adv - opened >= on[None, :], opened + cycle[None, :], adv
            )
        adv = jnp.where(s["next_arr"] >= INF, INF, adv)
        adv = jnp.where(adv >= stop[None, :], INF, adv)
        next_arr = jnp.where(is_arr, adv, s["next_arr"])
        frame_after = jnp.where(is_arr & ~is_ap[None, :], queue > 0, frame)
        frame_after = jnp.where(
            is_arr & is_ap[None, :],
            ((bcn > 0) | (jnp.sum(s["ap_pend"], 1, dtype=jnp.int32) > 0))[
                :, None
            ],
            frame_after,
        )
        became_hol = is_arr & ~frame & frame_after
        imm = became_hol & (now_c >= base0 + AIFS)
        drawn = (u_back * (s["cw"] + 1).astype(jnp.float32)).astype(jnp.int32)
        backoff = jnp.where(became_hol & ~imm, drawn, s["backoff"])
        immediate = jnp.where(became_hol, imm, s["immediate"])

        # ---------- the instant's frames, responses and timeouts -------
        t_air = s["t_air"]
        on_air = s["tx_end"] > t_air[:, None]           # during (t_air, now]
        src = s["rx_src"]
        locked = src >= 0
        src_1h = node[None, None, :] == src[:, :, None]  # (R, rx, tx)
        with jax.named_scope("tpudes.bss.sense"):
            # the chunk (t_air, now] at the interference it had: every other
            # frame on the air that reaches nobody's decoder but adds
            # its power (exact f32 sums of a few terms, no matmul)
            pw = jnp.where(
                on_air[:, :, None], rx_w[None], 0.0
            )                                            # (R, tx, rx)
            sig = jnp.sum(
                jnp.where(src_1h, jnp.swapaxes(pw, 1, 2), 0.0), axis=2
            )
            interf = jnp.sum(pw, axis=1) - sig
            kind_rx = pick(src_1h, s["tx_kind"])
            span_us = (now - t_air).astype(jnp.float32)[:, None]
            chunk = chunk_success(sig / (noise_w + interf), span_us, kind_rx)
            rx_p = jnp.where(
                act_c & locked & (span_us > 0), s["rx_p"] * chunk, s["rx_p"]
            )
            # a sender overlapped by a node it cannot sense
            hid_now = jnp.any(
                unsensed[None] & on_air[:, None, :], axis=2
            )
            tx_hid = s["tx_hid"] | (act_c & on_air & hid_now)

        # frames that end now, decoded or lost at the node locked on them
        ending = act_c & on_air & (s["tx_end"] == now_c)
        resolve = locked & pick(src_1h, ending.astype(jnp.int32)).astype(bool)
        ok = resolve & (u_coin < rx_p)
        dst_rx = pick(src_1h, s["tx_dst"])
        mine = ok & (dst_rx == node[None, :])
        heard = ok & (dst_rx != node[None, :])
        rx_src = jnp.where(resolve, -1, src)
        # per sender: its frame ended and its destination decoded it
        decoded_by = jnp.any(
            (src_1h & mine[:, :, None]), axis=1
        )                                                # (R, tx)
        lost = ending & ~decoded_by & (
            (s["tx_kind"] == F_RTS) | (s["tx_kind"] == F_DATA)
        )
        rx_lost_hidden = s["rx_lost_hidden"] + jnp.sum(
            lost & tx_hid, axis=1, dtype=jnp.int32
        )
        tx_hid = jnp.where(ending, False, tx_hid)

        resp_t, resp_kind, resp_dst = s["resp_t"], s["resp_kind"], s["resp_dst"]
        phase, due, nav = s["phase"], s["due"], s["nav"]
        if RTS:
            with jax.named_scope("tpudes.bss.rts"):
                # RTS for me: CTS a SIFS later; CTS for me: DATA a SIFS
                # later; RTS or CTS overheard: the NAV
                cts_due = mine & (kind_rx == F_RTS)
                resp_t = jnp.where(cts_due, now_c + SIFS, resp_t)
                resp_kind = jnp.where(cts_due, F_CTS, resp_kind)
                resp_dst = jnp.where(cts_due, src, resp_dst)
                cts_in = mine & (kind_rx == F_CTS) & (phase == P_WAIT_CTS)
                phase = jnp.where(cts_in, P_DATA_DUE, phase)
                due = jnp.where(cts_in, now_c + SIFS, due)
                nav = jnp.maximum(
                    nav,
                    jnp.where(
                        heard & ((kind_rx == F_RTS) | (kind_rx == F_CTS)),
                        now_c + by_kind(fr["nav"], kind_rx), 0,
                    ),
                )
        # DATA for me: delivered (once a frame: the sender's flag), ACK due
        data_in = mine & (kind_rx == F_DATA)
        resp_t = jnp.where(data_in, now_c + SIFS, resp_t)
        resp_kind = jnp.where(data_in, F_ACK, resp_kind)
        resp_dst = jnp.where(data_in, src, resp_dst)
        delivered = jnp.any(src_1h & data_in[:, :, None], axis=1)  # (R, tx)
        first = delivered & ~s["got"]
        got = s["got"] | delivered
        first_i = first.astype(jnp.int32)
        srv_rx = s["srv_rx"] + jnp.sum(
            jnp.where(is_ap[None, :], 0, first_i), axis=1, dtype=jnp.int32
        )
        xdst_1h = node[None, :] == s["xdst"][:, 0:1]     # the AP's destination
        cli_rx = s["cli_rx"] + jnp.where(xdst_1h, first_i[:, 0:1], 0)
        flow_rx = s["flow_rx"] + first_i
        ap_pend = s["ap_pend"]
        if echo:
            ap_pend = ap_pend + jnp.where(is_ap[None, :], 0, first_i)

        # outcomes of the own exchange: ACK heard, or a timeout
        success = mine & (kind_rx == F_ACK) & (phase == P_WAIT_ACK)
        timeout = act_c & (
            (phase == P_WAIT_CTS) | (phase == P_WAIT_ACK)
        ) & (due == now_c)
        cts_timeout = s["cts_timeout"] + jnp.sum(
            timeout & (phase == P_WAIT_CTS), axis=1, dtype=jnp.int32
        )
        exceeded = timeout & (s["retries"] + 1 > RETRY_LIMIT)
        done = success | exceeded
        done_i = done.astype(jnp.int32)
        drops = s["drops"] + jnp.sum(exceeded, axis=1, dtype=jnp.int32)
        queue = queue - jnp.where(is_ap[None, :], 0, done_i)
        ap_pend = ap_pend - jnp.where(xdst_1h, done_i[:, 0:1], 0)
        retries = jnp.where(
            done, 0, s["retries"] + timeout.astype(jnp.int32)
        )
        cw = jnp.where(
            done, CW_MIN,
            jnp.where(timeout, jnp.minimum(2 * (s["cw"] + 1) - 1, CW_MAX),
                      s["cw"]),
        )
        got = jnp.where(done, False, got)
        ended = success | timeout
        phase = jnp.where(ended, P_IDLE, phase)
        redraw = (u_back * (cw + 1).astype(jnp.float32)).astype(jnp.int32)
        backoff = jnp.where(ended, redraw, backoff)
        immediate = jnp.where(ended, False, immediate)
        hold = jnp.where(timeout, now_c, s["hold"])

        # ---------- the instant's transmissions start ----------
        respond = act_c & (resp_t == now_c)
        data_go = act_c & (phase == P_DATA_DUE) & (due == now_c)
        granted = (
            act_c & frame & (s["phase"] == P_IDLE) & (grant_t == now_c)
            & ~respond
        )
        beacon = granted & is_ap[None, :] & (s["bcn_pend"] > 0)[:, None]
        echo_dst = jnp.argmax(s["ap_pend"] > 0, axis=1).astype(jnp.int32)
        # the AP retries the frame it sent; STAs send to node 0
        dst_new = jnp.where(
            is_ap[None, :],
            jnp.where(s["retries"][:, 0:1] > 0, s["xdst"][:, 0:1],
                      echo_dst[:, None]),
            0,
        )
        opener = F_RTS if RTS else F_DATA
        kind_go = jnp.where(
            respond, resp_kind,
            jnp.where(data_go, F_DATA, jnp.where(beacon, F_BEACON, opener)),
        )
        go = respond | data_go | granted
        dst_go = jnp.where(
            respond, resp_dst, jnp.where(data_go, s["xdst"], dst_new)
        )
        end_go = now_c + by_kind(fr["dur"], kind_go)
        tx_end = jnp.where(go, end_go, s["tx_end"])
        tx_kind = jnp.where(go, kind_go, s["tx_kind"])
        tx_dst = jnp.where(go, dst_go, s["tx_dst"])
        resp_t = jnp.where(respond, INF, resp_t)
        hold = jnp.where(go, jnp.maximum(hold, end_go), hold)
        sends_data = go & (kind_go == F_DATA)
        opens = granted & ~beacon
        xdst = jnp.where(opens, dst_new, s["xdst"])
        if RTS:
            with jax.named_scope("tpudes.bss.rts"):
                phase = jnp.where(opens, P_WAIT_CTS, phase)
                due = jnp.where(opens, now_c + fr["cts_timeout"], due)
        phase = jnp.where(sends_data, P_WAIT_ACK, phase)
        due = jnp.where(sends_data, now_c + fr["ack_timeout"], due)
        # a beacon needs no answer: done when it starts (the legacy step's
        # accounting), the AP contends again after its airtime
        bcn = bcn - jnp.sum(beacon, axis=1, dtype=jnp.int32)
        cw = jnp.where(beacon, CW_MIN, cw)
        backoff = jnp.where(beacon, redraw, backoff)
        immediate = jnp.where(go, False, immediate)
        tx_data = s["tx_data"] + jnp.sum(sends_data, axis=1, dtype=jnp.int32)
        tx_by = s["tx_by"] + sends_data.astype(jnp.int32)
        tx_rts = s["tx_rts"] + jnp.sum(
            go & (kind_go == F_RTS), axis=1, dtype=jnp.int32
        )
        with jax.named_scope("tpudes.bss.sense"):
            # a transmitter stops receiving; a free node locks onto the
            # strongest frame that starts and reaches it
            rx_src = jnp.where(go, -1, rx_src)
            cand = go[:, :, None] & reach[None]          # (R, tx, rx)
            power = jnp.where(cand, rx_w[None], -1.0)
            strongest = jnp.argmax(power, axis=1).astype(jnp.int32)
            lock = jnp.any(cand, axis=1) & (rx_src < 0) & ~go
            rx_src = jnp.where(lock, strongest, rx_src)
            rx_p = jnp.where(lock, 1.0, rx_p)
            # every node that senses a start is busy until its end
            cs = jnp.maximum(
                s["cs"],
                jnp.max(
                    jnp.where(go[:, :, None] & sense[None],
                              end_go[:, :, None], 0),
                    axis=1,
                ),
            )
            # a contender whose idle start moved is credited with the
            # slots it counted; a zero-backoff grant cut short draws anew
            base1 = jnp.maximum(jnp.maximum(cs, nav), hold)
            waiting = act_c & frame & (phase == P_IDLE) & ~ended & ~go
            moved = waiting & (base1 > base0)
            counted = jnp.maximum((now_c - base0 - AIFS) // SLOT, 0)
            backoff = jnp.where(
                moved & ~immediate, jnp.maximum(backoff - counted, 0),
                backoff,
            )
            backoff = jnp.where(moved & immediate, drawn, backoff)
            immediate = jnp.where(moved, False, immediate)

        return dict(
            t=jnp.maximum(now, t0),
            t_air=jnp.where(act, now, t_air),
            next_arr=next_arr,
            queue=jnp.maximum(queue, 0),
            ap_pend=jnp.maximum(ap_pend, 0),
            bcn_pend=jnp.maximum(bcn, 0),
            backoff=backoff,
            hold=hold,
            immediate=immediate,
            cw=cw,
            retries=retries,
            cs=cs,
            nav=nav,
            phase=phase,
            due=due,
            xdst=xdst,
            got=got,
            resp_t=resp_t,
            resp_kind=resp_kind,
            resp_dst=resp_dst,
            tx_end=tx_end,
            tx_kind=tx_kind,
            tx_dst=tx_dst,
            tx_hid=tx_hid,
            rx_src=rx_src,
            rx_p=rx_p,
            srv_rx=srv_rx,
            cli_rx=cli_rx,
            flow_rx=flow_rx,
            tx_by=tx_by,
            tx_data=tx_data,
            tx_rts=tx_rts,
            cts_timeout=cts_timeout,
            rx_lost_hidden=rx_lost_hidden,
            drops=drops,
            step=s["step"] + 1,
        )

    def pending(s, sim_end):
        _, t_other = event_times(s)
        ta = jnp.min(s["next_arr"], axis=1)
        return (s["t"] < sim_end) & (jnp.minimum(ta, t_other) < sim_end)

    step_fn.traffic_keys = None
    return init_state, pending, step_fn


def build_bss_step(
    prog: BssProgram, replicas: int, obs: bool = False,
    geom_per_step: bool = False,
):
    """Return ``(init_state, pending, step_fn)`` for the vectorized
    event loop — exposed separately so the driver dryrun and
    benchmarks can jit/shard the pieces themselves.

    ``step_fn(s, key, sim_end[, geom])`` / ``pending(s, sim_end)`` —
    the simulation horizon ``sim_end`` (µs) is a RUNTIME operand, so
    one compiled program serves every horizon and the config-axis
    sweep vmaps a batch of horizons alongside the replica axis.
    ``key`` is the LAUNCH key, the same at every step: ``step_fn``
    derives the step's per-replica keys itself, from it and the
    carried ``s["step"]``, through :func:`runtime.step_keys`.

    ``pending`` is the next-event search as a predicate: per replica,
    whether an arrival or a transmission still falls before its
    horizon.  It is exported because the loop needs it and does not
    own it: :func:`build_bss_advance` runs it on the state each step
    HAS PRODUCED, inside the loop's body, and a loop assembled from
    these pieces with ``pending`` in its condition is the same
    simulation, one search a step slower.

    With ``prog.mobility`` the step gains a geometry stage: ``geom``
    (the mobility operands + the traced ``stride``) drives a
    closed-form position read at each replica's own event time and the
    (R, N, N) loss/detectability tables ride the carry, recomputed
    under a ``lax.cond`` every ``stride`` steps.  ``geom_per_step=True``
    compiles the UNCONDITIONAL per-step recompute — the reference
    program the stride=1 bit-identity contract is pinned against.

    Known limitation (results unaffected): under a config-axis sweep
    the whole advance is vmapped, which batches the cond predicate and
    degrades it to compute-both-branches — a swept mobile BSS run pays
    the per-step geometry cost regardless of stride.  The LTE mobile
    runner keeps its geometry cond outside the vmaps (its trajectory
    is replica/config-shared); the BSS tables are per-replica-time by
    design, so hoisting would change the model.  Solo mobile launches
    (the bench path) stride for real.

    ``obs=True`` (the ``TpudesObs`` knob) adds a cumulative per-replica
    retransmission counter to the carry; a disabled run compiles the
    exact pre-obs program.

    A program with hidden pairs, RTS/CTS, a receiver that does not
    echo or OnOff senders (:func:`per_node_medium`) compiles the
    per-node arm instead (:func:`_build_medium_step`); every other
    program compiles what follows, unchanged."""
    if per_node_medium(prog):
        return _build_medium_step(prog, replicas)
    n = prog.n
    R = replicas
    from tpudes.ops.wifi_error import ALL_MODES
    from tpudes.parallel.runtime import step_keys

    if obs:
        from tpudes.obs.flowmon import (
            FLOW_DELAY_BINS,
            VERDICT_RX,
            VERDICT_TX,
            flow_accumulate,
            flow_carry,
            flow_ring_write,
        )

    data_mode = ALL_MODES[prog.data_mode_idx]
    ack_mode = ALL_MODES[prog.ack_mode_idx]
    AGG = prog.max_mpdus > 1
    K = prog.max_mpdus
    AIFS = int(prog.aifs_us)
    data_dur = _ppdu_us(prog.data_bytes, data_mode)
    # under a BA session the response is a compressed BlockAck (32 B),
    # else a normal ack (14 B) — both at the control answer rate
    resp_dur = _ppdu_us(32 if AGG else 14, ack_mode)
    exch_beacon = _ppdu_us(prog.beacon_bytes, MODES_BY_NAME["OfdmRate6Mbps"])
    preamble_data = _preamble_us(data_mode)
    # DES convention (InterferenceHelper.calculate_per): the PER integral
    # runs over the whole PPDU airtime at the payload rate, preamble
    # included — nbits = rate × airtime, not 8 × PSDU bytes
    ndbps = data_mode.data_rate_bps * 4e-6
    data_airtime_s = (
        preamble_data * 1e-6
        + math.ceil((16 + 8 * prog.data_bytes + 6) / ndbps) * 4e-6
    )
    nbits_data = float(data_mode.data_rate_bps * data_airtime_s)

    # --- static per-pair physics (f64 host tables; a mobile program
    # overrides them with the carried f32 device tables below)
    rx_dbm_np = _pairwise_rx_dbm(prog)
    rx_w_np = 10.0 ** ((rx_dbm_np - 30.0) / 10.0)
    np.fill_diagonal(rx_w_np, 0.0)
    noise_w = float(thermal_noise_w(prog.bandwidth_hz, prog.noise_figure_db))
    detectable_np = rx_dbm_np >= prog.rx_sensitivity_dbm

    rx_w = jnp.asarray(rx_w_np, dtype=jnp.float32)          # (N, N) tx→rx
    detectable = jnp.asarray(detectable_np)                 # (N, N)
    start0 = jnp.asarray(prog.start_us, dtype=jnp.int32)
    interval = jnp.asarray(prog.interval_us, dtype=jnp.int32)
    stop = jnp.asarray(prog.stop_us, dtype=jnp.int32)
    is_ap = jnp.arange(n) == 0

    # --- device-resident workload (tpudes.traffic) ------------------------
    TRAFFIC = prog.traffic is not None
    if TRAFFIC:
        from tpudes.traffic.device import TRAFFIC_KEY_TAG, build_gap_fn

        gap_fn = build_gap_fn(prog.traffic)

    # --- device-resident geometry (tpudes.ops.mobility) -------------------
    MOBILE = prog.mobility is not None
    if MOBILE:
        from tpudes.ops.mobility import build_position_fn

        pos_fn = build_position_fn(prog.mobility)
        eye_b = jnp.eye(n, dtype=bool)

        def geom_tables(mob_ops, t_vec):
            """(R,) per-replica event times → ((R, N, N) rx power W,
            (R, N, N) detectability) under the program's log-distance
            physics — the f32 device form of :func:`_pairwise_rx_dbm`
            (the static path keeps its f64 host tables; the moving
            regime is documented f32)."""
            pos = jax.vmap(lambda t: pos_fn(mob_ops, t))(t_vec)  # (R,N,3)
            diff = pos[:, :, None, :] - pos[:, None, :, :]
            d = jnp.sqrt(jnp.sum(diff * diff, axis=-1))          # (R,N,N)
            rx_dbm_m = log_distance(
                jnp.float32(prog.tx_power_dbm), d,
                exponent=prog.path_loss_exponent,
                reference_loss_db=prog.reference_loss_db,
            )
            rx_w_m = jnp.where(eye_b[None], 0.0, dbm_to_w(rx_dbm_m))
            return (
                rx_w_m.astype(jnp.float32),
                rx_dbm_m >= prog.rx_sensitivity_dbm,
            )

    def init_state():
        extra = (
            # flows = nodes (node 0 is the AP): per-flow FlowMonitor
            # columns + the packet-event ring ride the carry
            {"retx": jnp.zeros((R,), jnp.int32), **flow_carry(n, lead=(R,))}
            if obs
            else {}
        )
        if MOBILE:
            # placeholders only: step 0 refreshes (0 % stride == 0), so
            # no outcome ever reads these zeros
            extra.update(
                geom_rx_w=jnp.zeros((R, n, n), jnp.float32),
                geom_det=jnp.zeros((R, n, n), bool),
            )
        if AGG:
            # per-MPDU retry counts: a backlog is its histogram over the
            # count (the queue is oldest first, so the count never rises
            # along it and the histogram says all there is to say)
            extra.update(
                q_retry=jnp.zeros((R, n, RETRY_CLASSES), jnp.int32),
                ap_retry=jnp.zeros((R, n, RETRY_CLASSES), jnp.int32),
                tx_mpdus=jnp.zeros((R,), jnp.int32),
            )
        return dict(
            **extra,
            t=jnp.zeros((R,), jnp.int32),
            next_arr=jnp.broadcast_to(start0, (R, n)).astype(jnp.int32),
            queue=jnp.zeros((R, n), jnp.int32),      # STA→AP requests waiting
            ap_pend=jnp.zeros((R, n), jnp.int32),    # echoes waiting at AP per STA
            bcn_pend=jnp.zeros((R,), jnp.int32),
            backoff=jnp.zeros((R, n), jnp.int32),
            hold=jnp.zeros((R, n), jnp.int32),       # personal recontend time
            immediate=jnp.zeros((R, n), bool),       # zero-backoff grant armed
            cw=jnp.full((R, n), CW_MIN, jnp.int32),
            # the legacy exchange retries its one frame: a count per node
            **({} if AGG else {"retries": jnp.zeros((R, n), jnp.int32)}),
            busy_until=jnp.zeros((R,), jnp.int32),
            srv_rx=jnp.zeros((R,), jnp.int32),
            cli_rx=jnp.zeros((R, n), jnp.int32),
            tx_data=jnp.zeros((R,), jnp.int32),
            drops=jnp.zeros((R,), jnp.int32),
            step=jnp.int32(0),
        )

    def has_frame(s):
        sta_frame = (s["queue"] > 0) & ~is_ap[None, :]
        ap_frame = is_ap[None, :] & (
            (s["bcn_pend"] > 0)
            | (jnp.sum(s["ap_pend"], axis=1, dtype=jnp.int32) > 0)
        )[:, None]
        return sta_frame | ap_frame

    def tx_times(s):
        """(R, N) earliest allowed tx instant per contender; INF else."""
        frame = has_frame(s)
        base = jnp.maximum(s["busy_until"][:, None], s["hold"])
        countdown = base + AIFS + s["backoff"] * SLOT
        t_imm = jnp.maximum(s["t"][:, None], base)
        tx = jnp.where(s["immediate"], t_imm, countdown)
        tx = jnp.maximum(tx, s["t"][:, None])  # never in the past
        return jnp.where(frame, tx, INF)

    def traffic_keys(key):
        """(R, 2) per-replica traffic key rows — pure in the RUN key
        (not the step), so gap draws stay keyed (key, replica, entity,
        arrival time).  Loop-invariant: computed ONCE per advance and
        threaded into the while_loop body (recomputing R fold_ins per
        step would ride the hot path for nothing)."""
        tr_key = jax.random.fold_in(key, TRAFFIC_KEY_TAG)
        return jax.vmap(
            lambda i: jax.random.fold_in(tr_key, i)
        )(jnp.arange(R))

    def step_fn(s, key, sim_end, geom=None, tr=None, tr_keys=None):
        # per-replica keying: replica r's draws at step t are a pure
        # function of (key, t, r) — independent of R — so runtime
        # replica-bucketing (padding R to a power of two) leaves every
        # real replica's stream bit-identical.  A joint uniform(key,
        # (R, n)) draw would reshuffle all replicas whenever R changes.
        rkeys = step_keys("bss", key, s["step"], R)
        if AGG:

            def draw(kk):
                # fixed-arity split of a fold_in-derived key: pure in
                # (key, r, t), so bucketing/chunking stay bit-exact;
                # draw dtypes pinned f32 (ambient x64 must not widen
                # the streams — JXL002)
                k_back, k_mpdu = jax.random.split(kk)
                # two rows of per-MPDU coins: the AP's PPDU and the one
                # PPDU to the AP that can decode (see the A-MPDU block)
                return (
                    jax.random.uniform(k_back, (n,), jnp.float32),
                    jax.random.uniform(k_mpdu, (2, K), jnp.float32),
                )

            u_back, u_mpdu = jax.vmap(draw)(rkeys)
        else:

            def draw(kk):
                # see above: fixed-arity split, f32-pinned draws
                k_back, k_coin = jax.random.split(kk)
                return (
                    jax.random.uniform(k_back, (n,), jnp.float32),
                    jax.random.uniform(k_coin, (n,), jnp.float32),
                )

            u_back, u_coin = jax.vmap(draw)(rkeys)

        frame = has_frame(s)
        tx_t = tx_times(s)                               # (R, N)
        tc = jnp.min(tx_t, axis=1)                       # (R,)
        ta = jnp.min(s["next_arr"], axis=1)              # (R,)
        live = s["t"] < sim_end
        next_t = jnp.where(live, jnp.minimum(ta, tc), sim_end)
        past_end = next_t >= sim_end
        arrived = live & (ta <= tc) & (ta < INF) & ~past_end
        transmit = live & (tc < ta) & (tc < INF) & ~past_end

        # ---------- arrival processing ----------
        is_arr = arrived[:, None] & (s["next_arr"] == next_t[:, None])
        new_queue = s["queue"] + jnp.where(is_arr & ~is_ap[None, :], 1, 0)
        # int reductions pin dtype=jnp.int32: jnp.sum's numpy-style
        # accumulator promotion would widen the carry to i64 under
        # ambient x64 (JXL002); bit-exact no-op under the default config
        new_bcn = s["bcn_pend"] + jnp.sum(
            jnp.where(is_arr & is_ap[None, :], 1, 0), axis=1,
            dtype=jnp.int32,
        )
        if TRAFFIC:
            # traffic stage: the next inter-arrival gap per (replica,
            # node) comes from the traced workload program.  Gaps are
            # pure in (key, replica, entity, arrival time) — the
            # per-replica keys derive from the RUN key (not the
            # step-folded k; see traffic_keys), so chunk boundaries
            # and replica bucketing leave every stream bit-identical.
            # The legacy cbr advance is the model's cbr branch, bit
            # for bit.
            tr_rkeys = traffic_keys(key) if tr_keys is None else tr_keys
            gaps = jax.vmap(
                lambda kr, ta: gap_fn(tr, kr, ta)
            )(tr_rkeys, s["next_arr"])                   # (R, N) µs
            adv = jnp.where(
                s["next_arr"] >= INF, INF, s["next_arr"] + gaps
            )
        else:
            adv = jnp.where(
                s["next_arr"] >= INF, INF, s["next_arr"] + interval[None, :]
            )
        adv = jnp.where(adv >= stop[None, :], INF, adv)
        new_next_arr = jnp.where(is_arr, adv, s["next_arr"])

        # head-of-line transition: node had no frame, now has one
        frame_after = jnp.where(is_arr & ~is_ap[None, :], new_queue > 0, frame)
        frame_after = jnp.where(
            is_arr & is_ap[None, :],
            (
                (new_bcn > 0)
                | (jnp.sum(s["ap_pend"], 1, dtype=jnp.int32) > 0)
            )[:, None],
            frame_after,
        )
        became_hol = is_arr & ~frame & frame_after
        medium_idle = next_t >= s["busy_until"] + AIFS   # idle ≥ AIFS now
        imm_grant = became_hol & medium_idle[:, None]
        drawn = (u_back * (s["cw"] + 1).astype(jnp.float32)).astype(jnp.int32)
        new_backoff = jnp.where(became_hol & ~imm_grant, drawn, s["backoff"])
        new_immediate = jnp.where(became_hol, imm_grant, s["immediate"])

        # ---------- transmission processing ----------
        winners = transmit[:, None] & (tx_t == next_t[:, None]) & frame
        any_win = jnp.any(winners, axis=1)
        # countdown credit for non-winning contenders (freeze bookkeeping):
        # idle slots elapsed since busy-end+DIFS is what everyone consumed
        elapsed = jnp.maximum((next_t - s["busy_until"] - AIFS) // SLOT, 0)
        counting = frame & ~winners & ~s["immediate"] & transmit[:, None]
        new_backoff = jnp.where(
            counting,
            jnp.maximum(new_backoff - elapsed[:, None], 0),
            new_backoff,
        )
        # a zero-backoff grant interrupted by someone else's tx redraws
        interrupted = frame & ~winners & s["immediate"] & transmit[:, None]
        new_backoff = jnp.where(interrupted, drawn, new_backoff)
        new_immediate = jnp.where(interrupted, False, new_immediate)

        # AP frame choice: beacon outranks echo (FIFO approximation)
        ap_sends_beacon = winners[:, 0] & (s["bcn_pend"] > 0)
        echo_dst = jnp.argmax(s["ap_pend"] > 0, axis=1)   # lowest pending STA
        # one-hot of the AP's destination: every dst-indexed quantity
        # below is computed as dense one-hot algebra instead of a
        # gather/scatter — XLA lowers (512,65) gathers to ~300 µs serial
        # loops on TPU while the equivalent masked reductions fuse into
        # the elementwise step (the 4 gathers were 90% of step cost)
        ed_1h = jnp.arange(n)[None, :] == echo_dst[:, None]      # (R, N)
        ed_f = ed_1h.astype(jnp.float32)

        # PHY: signal/interference at each transmitter's destination.
        # STA destinations are all the AP (column 0); only the AP's
        # destination varies (echo_dst).
        w = winners.astype(jnp.float32)                  # (R, N)
        # the one-hot products SELECT received powers that are then
        # subtracted from each other (interf = total - sig): they must
        # be exact.  A TPU's default f32 matmul rounds its operands to
        # bf16, which leaves ~0.4% of the signal behind as phantom
        # interference — an SINR ceiling near 24 dB that starves the
        # high-rate modes (first chip run: 9% of a clean BSS's frames
        # lost).  HIGHEST is exact for a 0/1 operand on every backend.
        exact = jax.lax.Precision.HIGHEST
        if MOBILE:
            # geometry stage: recompute the carried (R, N, N) tables at
            # each replica's OWN event time every `stride` steps; the
            # cond predicate is the scalar shared step counter, so only
            # the refreshing steps pay the position/loss math
            def _recompute(_):
                return geom_tables(geom, next_t)

            if geom_per_step:
                rx_w_c, det_c = _recompute(None)
            else:
                rx_w_c, det_c = jax.lax.cond(
                    s["step"] % geom["stride"] == 0,
                    _recompute,
                    lambda _: (s["geom_rx_w"], s["geom_det"]),
                    None,
                )
            total_at = jnp.einsum(
                "rn,rnm->rm", w, rx_w_c, precision=exact
            )
            sig = jnp.where(
                is_ap[None, :],
                jnp.sum(ed_f * rx_w_c[:, 0, :], axis=1)[:, None],
                rx_w_c[:, :, 0],
            )
            det = jnp.where(
                is_ap[None, :],
                (ed_1h & det_c[:, 0, :]).any(axis=1)[:, None],
                det_c[:, :, 0],
            )
        else:
            # (R, N): power at rx j
            total_at = jnp.matmul(w, rx_w, precision=exact)
            sig = jnp.where(
                is_ap[None, :],
                # AP → echo_dst
                jnp.matmul(ed_f, rx_w[0], precision=exact)[:, None],
                rx_w[:, 0][None, :],                     # STA i → AP
            )
            det = jnp.where(
                is_ap[None, :],
                (ed_1h & detectable[0][None, :]).any(axis=1)[:, None],
                detectable[:, 0][None, :],
            )
        interf_at_dst = jnp.where(
            is_ap[None, :],
            jnp.sum(ed_f * total_at, axis=1)[:, None],
            total_at[:, 0][:, None],
        )
        interf = interf_at_dst - sig
        sinr = sig / (noise_w + interf)
        dst_idle = ~jnp.where(                           # half-duplex
            is_ap[None, :],
            (ed_1h & winners).any(axis=1)[:, None],
            winners[:, 0][:, None],
        )
        beacon_tx = winners & is_ap[None, :] & ap_sends_beacon[:, None]
        data_tx = winners & ~beacon_tx
        gate = data_tx & det & dst_idle
        if AGG:
            with jax.named_scope("tpudes.bss.ampdu"):
                # A-MPDU: the winner aggregates its whole backlog (up to
                # the BA-window/MaxAmpduSize cap) into one PPDU; per-MPDU
                # decode is the full-PPDU PSR at each subframe's bit
                # share (phy.mpdu_success_probs: equal shares, psr^(1/k))
                k_sta = jnp.minimum(s["queue"], K)
                k_ap = jnp.minimum(
                    jnp.sum(
                        jnp.where(ed_1h, s["ap_pend"], 0), axis=1,
                        dtype=jnp.int32,
                    ),
                    K,
                )[:, None]
                k_agg = jnp.maximum(
                    jnp.where(is_ap[None, :], k_ap, k_sta), 1
                ).astype(jnp.int32)
                nsym = jnp.ceil(
                    (22.0 + 8.0 * prog.subframe_bytes * k_agg) / ndbps
                )
                dur_k = preamble_data + (nsym * 4).astype(jnp.int32)
                nbits_k = (
                    jnp.float32(data_mode.data_rate_bps * 1e-6)
                    * dur_k.astype(jnp.float32)
                )
                psr = mode_chunk_success_rate(
                    sinr, nbits_k, jnp.asarray(prog.data_mode_idx)
                )
                p_mpdu = jnp.where(
                    gate, psr ** (1.0 / k_agg.astype(jnp.float32)), 0.0
                )
                # the backlog a winner sends from, by retry count: its own
                # queue, or the AP's echoes for echo_dst; oldest first is
                # highest count first, so positions [0, upto[c]) of the
                # PPDU hold the MPDUs that failed c times or more
                hist = jnp.where(
                    is_ap[None, :, None],
                    jnp.sum(
                        jnp.where(ed_1h[..., None], s["ap_retry"], 0),
                        axis=1, dtype=jnp.int32,
                    )[:, None, :],
                    s["q_retry"],
                )                                        # (R, N, C)
                at_least = jax.lax.cumsum(hist, axis=2, reverse=True)
                upto = jnp.where(
                    data_tx[..., None],
                    jnp.minimum(at_least, k_agg[..., None]), 0,
                )
                # the per-MPDU coins are tossed for two PPDUs a replica,
                # not for all N: the AP's, and the likeliest of those sent
                # to the AP.  No second one can decode there: two senders
                # cannot both sit above 0 dB at one receiver (the product
                # of their SINRs is below 1), and at or below 0 dB psr is
                # 0.0 exactly in float32 for every mode at these lengths.
                lead = jnp.argmax(
                    jnp.where(is_ap[None, :], -1.0, p_mpdu), axis=1
                )
                tossed = jnp.stack(
                    [
                        jnp.broadcast_to(is_ap[None, :], (R, n)),
                        jnp.arange(n)[None, :] == lead[:, None],
                    ],
                    axis=1,
                ) & data_tx[:, None, :]                  # (R, 2, N)
                p2 = jnp.sum(
                    jnp.where(tossed, p_mpdu[:, None, :], 0.0), axis=2
                )
                upto2 = jnp.sum(
                    jnp.where(tossed[..., None], upto[:, None], 0),
                    axis=2, dtype=jnp.int32,
                )                                        # (R, 2, C)
                pos = jnp.arange(K)
                lost_j = (pos < upto2[..., :1]) & ~(
                    u_mpdu < p2[..., None]
                )                                        # (R, 2, K)
                lost_upto2 = jnp.sum(
                    lost_j[:, :, None, :]
                    & (pos < upto2[..., None]),
                    axis=-1, dtype=jnp.int32,
                )                                        # (R, 2, C)
                # every other PPDU of the step loses all it carried
                lost_upto = jnp.where(
                    tossed.any(axis=1)[..., None],
                    jnp.sum(
                        jnp.where(
                            tossed[..., None], lost_upto2[:, :, None], 0
                        ),
                        axis=1, dtype=jnp.int32,
                    ),
                    upto,
                )                                        # (R, N, C)

                def per_count(cum):
                    # from "count c or more" to "count c"
                    return cum - jnp.pad(
                        cum[..., 1:], ((0, 0), (0, 0), (0, 1))
                    )

                sent, lost = per_count(upto), per_count(lost_upto)
                n_ok = jnp.sum(sent - lost, axis=-1, dtype=jnp.int32)
                # what the BlockAck did not acknowledge stays at the head
                # with its own count one higher; past the limit it drops
                # (block-ack-manager NotifyGotBlockAck / MissedBlockAck,
                # as the host MAC's _finish_ampdu)
                drop_n = lost[..., -1]
                hist_after = hist - sent + jnp.pad(
                    lost[..., :-1], ((0, 0), (0, 0), (1, 0))
                )
        else:
            k_agg = jnp.ones((R, n), jnp.int32)
            dur_k = jnp.full((R, n), data_dur, jnp.int32)
            psr = mode_chunk_success_rate(
                sinr, jnp.asarray(nbits_data, jnp.float32),
                jnp.asarray(prog.data_mode_idx),
            )
            n_ok = jnp.where(gate & (u_coin < psr), 1, 0).astype(jnp.int32)
        success = data_tx & (n_ok > 0)
        fail = data_tx & (n_ok == 0)

        # ---- outcome updates (counts generalize the single-MPDU 0/1)
        sta_ok = jnp.where(~is_ap[None, :], n_ok, 0)
        ap_ok = jnp.where(is_ap[None, :], n_ok, 0)
        new_srv = s["srv_rx"] + jnp.sum(sta_ok, axis=1, dtype=jnp.int32)
        got_echo = jnp.sum(ap_ok, axis=1, dtype=jnp.int32)
        ed_i = ed_1h.astype(jnp.int32)      # dense scatter-free updates
        new_cli = s["cli_rx"] + ed_i * got_echo[:, None]
        new_queue = new_queue - sta_ok
        new_ap_pend = s["ap_pend"] + sta_ok - ed_i * got_echo[:, None]
        new_bcn = new_bcn - jnp.where(ap_sends_beacon, 1, 0)

        if AGG:
            # retries are counted per MPDU (drop_n: the A-MPDU block);
            # the exchange is final, and the CW resets, when a PPDU that
            # decoded nothing leaves nothing of itself to send again
            retry_exceeded = fail & (drop_n >= k_agg)
        else:
            # the legacy exchange has one frame: a node-level counter
            # bumps on a failed exchange, resets on a success; at the
            # limit the frame drops
            retry_exceeded = fail & (s["retries"] + 1 > RETRY_LIMIT)
            drop_n = jnp.where(retry_exceeded, k_agg, 0)
        new_drops = s["drops"] + jnp.sum(
            drop_n, axis=1, dtype=jnp.int32
        )
        new_queue = new_queue - jnp.where(~is_ap[None, :], drop_n, 0)
        drop_echo = jnp.sum(
            jnp.where(is_ap[None, :], drop_n, 0), axis=1, dtype=jnp.int32
        )
        new_ap_pend = new_ap_pend - ed_i * drop_echo[:, None]
        if not AGG:
            new_retries = jnp.where(
                success | retry_exceeded | beacon_tx,
                0,
                s["retries"] + fail.astype(jnp.int32),
            )
        new_cw = jnp.where(
            success | retry_exceeded | beacon_tx,
            CW_MIN,
            jnp.where(fail, jnp.minimum(2 * (s["cw"] + 1) - 1, CW_MAX), s["cw"]),
        )
        # transmitters redraw backoff from the *post-outcome* CW (802.11:
        # reset on success/final-drop, doubled after a failure); the
        # medium was just busy with their own tx, so no immediate grant
        drawn_post = (u_back * (new_cw + 1).astype(jnp.float32)).astype(jnp.int32)
        new_backoff = jnp.where(winners, drawn_post, new_backoff)
        new_immediate = jnp.where(winners, False, new_immediate)

        # medium occupancy: full exchange when acked, bare data airtime on
        # a failure (no ack goes out), beacon airtime for beacons; the
        # failed sender personally waits its ack timeout before recontending
        exch = dur_k + SIFS + resp_dur       # acked/BA'd exchange airtime
        # failed sender's personal wait (mac response-timeout budget)
        resp_timeout = exch + SLOT + 4
        occ = jnp.where(success, exch, jnp.where(beacon_tx, exch_beacon, dur_k))
        new_busy = jnp.where(
            any_win,
            next_t + jnp.max(jnp.where(winners, occ, 0), axis=1),
            s["busy_until"],
        )
        new_hold = jnp.where(
            fail,
            next_t[:, None] + resp_timeout,
            jnp.where(winners, next_t[:, None] + occ, s["hold"]),
        )

        extra = (
            {"retx": s["retx"] + jnp.sum(fail, axis=1, dtype=jnp.int32)}
            if obs
            else {}
        )
        if obs:
            # FlowMonitor columns (flow = node): a data exchange sends
            # k_agg MPDUs and delivers n_ok of them; delay = the MAC
            # exchange airtime this PPDU occupied (dur_k µs); a failed
            # exchange is a retransmission, not a loss — only retry-
            # limit drops count as lost (the host monitor's Drop hook)
            pkt_b = jnp.int32(
                prog.subframe_bytes if AGG else prog.data_bytes
            )
            fm_tx = jnp.where(data_tx, k_agg, 0)
            delay_us = dur_k.astype(jnp.float32)
            fm = flow_accumulate(
                {k: s[k] for k in s if k.startswith("fm_")},
                t_s=next_t[:, None].astype(jnp.float32) * 1e-6,
                tx=fm_tx,
                tx_bytes=fm_tx * pkt_b,
                rx=n_ok,
                rx_bytes=n_ok * pkt_b,
                delay_s=delay_us * 1e-6,
                lost=drop_n,
                bin_width_s=max(1, 2 * data_dur)
                * 1e-6 / FLOW_DELAY_BINS,
            )
            # packet-event ring: one sampled event per (replica, step)
            # — the node whose MPDUs were delivered, else the (failed)
            # winner; idle steps stamp -1
            has_rx = jnp.sum(n_ok, axis=1, dtype=jnp.int32) > 0
            ev_flow = jnp.where(
                has_rx, jnp.argmax(n_ok, axis=1),
                jnp.argmax(winners.astype(jnp.int32), axis=1),
            ).astype(jnp.int32)
            ev_verdict = jnp.where(has_rx, VERDICT_RX, VERDICT_TX)
            row = jnp.stack(
                [
                    jnp.where(any_win, s["step"], -1),
                    next_t,
                    ev_flow,
                    jnp.broadcast_to(pkt_b, (R,)),
                    ev_verdict,
                ],
                axis=-1,
            )
            fm["fm_ring"] = flow_ring_write(s["fm_ring"], s["step"], row)
            extra.update(fm)
        if MOBILE:
            extra.update(geom_rx_w=rx_w_c, geom_det=det_c)
        if AGG:
            with jax.named_scope("tpudes.bss.ampdu"):
                fresh = jnp.arange(RETRY_CLASSES) == 0   # count 0
                extra.update(
                    # a station's queue: what it sent comes back one count
                    # higher or not at all; arrivals join at count 0
                    q_retry=jnp.where(
                        is_ap[None, :, None], 0, hist_after
                    ) + (is_arr & ~is_ap[None, :])[..., None] * fresh,
                    # the AP's echoes: echo_dst's row takes the AP's
                    # outcome, every request decoded adds a fresh echo
                    ap_retry=s["ap_retry"]
                    + ed_i[..., None] * (hist_after - hist)[:, :1]
                    + sta_ok[..., None] * fresh,
                    tx_mpdus=s["tx_mpdus"] + jnp.sum(
                        jnp.where(data_tx, k_agg, 0), axis=1,
                        dtype=jnp.int32,
                    ),
                )
        else:
            extra.update(retries=new_retries)
        return dict(
            **extra,
            t=jnp.maximum(next_t, s["t"]),
            next_arr=new_next_arr,
            queue=jnp.maximum(new_queue, 0),
            ap_pend=jnp.maximum(new_ap_pend, 0),
            bcn_pend=jnp.maximum(new_bcn, 0),
            backoff=new_backoff,
            hold=new_hold,
            immediate=new_immediate,
            cw=new_cw,
            busy_until=new_busy,
            srv_rx=new_srv,
            cli_rx=new_cli,
            tx_data=s["tx_data"]
            + jnp.sum(data_tx, axis=1, dtype=jnp.int32),
            drops=new_drops,
            step=s["step"] + 1,
        )

    def pending(s, sim_end):
        tx_t = jnp.min(tx_times(s), axis=1)
        ta = jnp.min(s["next_arr"], axis=1)
        return (s["t"] < sim_end) & (jnp.minimum(ta, tx_t) < sim_end)

    # loop-invariant key derivation, exposed so the advance builder
    # hoists it outside the while_loop (None when no traffic stage)
    step_fn.traffic_keys = traffic_keys if TRAFFIC else None
    return init_state, pending, step_fn


def _prog_cache_key(prog: BssProgram) -> tuple:
    """Hashable identity of a BssProgram (ndarray fields → bytes).
    ``sim_end_us`` AND ``geom_stride`` are deliberately ABSENT (both
    are traced operands — one executable serves every horizon and
    every stride), and ``mobility``/``traffic`` contribute only their
    SHAPE keys: the model ids and every mobility/workload parameter
    are traced too, so a sweep across either model family reuses one
    executable."""
    out = []
    for k, v in prog.__dict__.items():
        if k in ("sim_end_us", "geom_stride"):
            continue
        if k in ("mobility", "traffic"):
            out.append(None if v is None else v.shape_key())
        elif isinstance(v, np.ndarray):
            out.append(v.tobytes())
        else:
            out.append(v)
    return tuple(out)


def build_bss_advance(prog: "BssProgram", replicas: int, obs: bool = False,
                      n_cfg: int | None = None, geom_per_step: bool = False,
                      sweep: str = "horizon"):
    """``(init_state, pending, fn)`` with
    ``fn(s, k, max_steps, sim_end, geom, tr)`` the UNJITTED (but
    config-vmapped) advance exactly as :func:`run_replicated_bss`'s
    launch jits it — factored out so the trace manifest
    (:func:`trace_manifest`) abstractly traces the same program the
    runner cache compiles.

    **Who owns the loop's predicate: the body.**  The ``while`` inside
    ``fn`` carries ``(s, nxt)``.  ``s`` is the launch carry, leaf for
    leaf what ``init_state`` builds; ``nxt`` is what the next-event
    search found for that very ``s``: ``pending`` (R,) bool, the
    replicas with an event still before their horizon, and ``more``,
    a scalar, whether any has.  The body steps ``s`` and searches the
    state it has just produced, so the search runs once an event step;
    the condition is ``(step < max_steps) & more``: two scalars, no
    reduction, no read of the state.  XLA compiles condition and body
    as two computations that share nothing, so a condition that
    searches repeats the body's opening lines every step, and (read on
    the chip, ``PERF.md`` section 6, PR 33) a condition that reads the whole
    state keeps the carry out of the chip's fast memory.  ``nxt`` is
    seeded from the incoming ``s`` before the loop (once a launch or a
    chunk) and never leaves ``fn``: ``fn`` returns the stepped ``s``,
    ``nxt["pending"]`` and the metrics; what ``runtime.jit_init``
    builds, ``drive_chunks`` donates and re-enters, a checkpoint
    fingerprints and ``_bss_unpack`` reads is ``s`` alone.  Under the
    config-axis ``vmap`` ``more`` is one flag a config point; on a
    replica mesh its ``any`` is the step's one all-reduce.

    With ``n_cfg``, ``sweep`` picks the
    config-axis operand: ``"horizon"`` vmaps (state, sim_end) — the
    classic horizon sweep — while ``"traffic"`` vmaps (state, traffic
    operands): an 8-point WORKLOAD sweep (mixed cbr/mmpp/onoff/trace
    points sharing one traffic shape key) is one (C, R, …) launch."""
    from tpudes.parallel.runtime import scoped_while_loop

    init_state, pending, step_fn = build_bss_step(
        prog, replicas, obs=obs, geom_per_step=geom_per_step
    )

    def advance(s, k, max_steps, sim_end, geom=None, tr=None):
        tr_keys = (
            step_fn.traffic_keys(k)
            if step_fn.traffic_keys is not None else None
        )

        def search(st):
            # ``nxt`` of the docstring, for the state ``st``
            still = pending(st, sim_end)
            return dict(more=jnp.any(still), pending=still)

        def cond(c):
            st, nxt = c
            return jnp.logical_and(st["step"] < max_steps, nxt["more"])

        def body(c):
            new = step_fn(c[0], k, sim_end, geom, tr, tr_keys)
            return new, search(new)

        out, nxt = scoped_while_loop("bss", cond, body, (s, search(s)))
        # per-replica completion flags computed on-device so the
        # caller needs no second compiled program (no extra host
        # round trip); a vector so padded replicas can be sliced off
        # before the any().
        # chunk metrics only under TpudesObs (obs is in the runner
        # key) and as FRESH reductions only (drive_chunks's
        # invariant: a carry leaf here would be deleted when the
        # next chunk donates the carry)
        metrics = (
            dict(
                srv_rx=jnp.sum(out["srv_rx"], dtype=jnp.int32),
                drops=jnp.sum(out["drops"], dtype=jnp.int32),
                # lax.rev keeps the ring snapshot FRESH (not an alias
                # of the donated carry); the decoder orders rows by
                # the step column, so the flip needs no undo
                fm_ring=jnp.flip(out["fm_ring"], axis=-2),
            )
            if obs
            else {}
        )
        return out, nxt["pending"], metrics

    fn = advance
    if n_cfg is not None:
        fn = jax.vmap(
            fn,
            in_axes=(
                (0, None, None, 0, None, None) if sweep == "horizon"
                else (0, None, None, None, None, 0)
            ),
        )
    return init_state, pending, fn


def _bss_unpack(host: dict, replicas: int, obs: bool, prog=None) -> dict:
    """Host-side result assembly for ONE config point."""
    R = replicas
    result = dict(
        srv_rx=host["srv_rx"][:R],
        cli_rx=host["cli_rx"][:R],
        tx_data=host["tx_data"][:R],
        drops=host["drops"][:R],
        steps=int(host["step"]),
        all_done=not bool(host["pending"][:R].any()),
    )
    if "tx_mpdus" in host:
        # aggregated exchanges only: MPDUs carried by the tx_data PPDUs
        result["tx_mpdus"] = host["tx_mpdus"][:R]
    if "tx_rts" in host:
        # the per-node arm's counters; ``queue`` is the backlog each
        # sender still holds at the horizon
        result.update({
            ("backlog" if k == "queue" else k): host[k][:R]
            for k in MEDIUM_FIELDS
        })
    if obs:
        from tpudes.obs.flowmon import FM_KEYS

        result["retx"] = host["retx"][:R]
        # per-flow FlowMonitor columns + the packet-event ring (flow =
        # node), replica-sliced; reduce with tpudes.obs.flowmon
        result["flow"] = {
            k: np.asarray(host[k])[:R] for k in FM_KEYS
        }
    if prog is not None and prog.mobility is not None:
        # geometry-refresh accounting: the cond fires on steps where
        # step % stride == 0, i.e. ceil(steps / stride) times.
        # (Telemetry is recorded once per LAUNCH by the caller — a
        # config sweep shares one loop, so per-point recording here
        # would inflate the counters.)
        stride = max(1, int(prog.geom_stride))
        steps = int(host["step"])
        result["geom_refreshes"] = -(-steps // stride)
        result["geom_stride"] = stride
    return result


def bss_study(prog: BssProgram, key, replicas, mesh=None):
    """Serving-layer study descriptor (see :mod:`tpudes.serving`): the
    sim-end horizon is the traced sweep operand, so two BSS studies
    coalesce onto one (C, R, …) launch whenever their static program
    fields, key, replica count and mesh all match — only ``sim_end_us``
    may differ (the sweep shares one step budget; finished replicas are
    fixed points of the step, so outcomes stay bit-equal)."""
    import dataclasses

    from tpudes.serving.descriptor import StudyDescriptor, mesh_fingerprint

    # coalesce key: mobility params + stride are traced operands (not
    # in the runner cache key) but two studies with different
    # trajectories must NOT coalesce — the sweep operand is sim_end only
    ck = (
        _prog_cache_key(prog), np.asarray(key).tobytes(), int(replicas),
        mesh_fingerprint(mesh),
        None if prog.mobility is None else prog.mobility.param_key(),
        int(prog.geom_stride),
        # workload identity by VALUE: traffic params are traced (not in
        # the runner cache key) but two studies with different
        # workloads must not coalesce — the sweep operand is sim_end
        None if prog.traffic is None else prog.traffic.param_key(),
    )

    def launch(points, block=False):
        if len(points) == 1:
            return run_replicated_bss(
                dataclasses.replace(prog, sim_end_us=int(points[0])),
                replicas, key, mesh=mesh, block=block,
            )
        return run_replicated_bss(
            prog, replicas, key, mesh=mesh,
            sim_end_us=[int(v) for v in points], block=block,
        )

    def warm(n_points):
        # sim_end and max_steps are traced: a ~1 ms horizon compiles
        # the exact executable every real horizon reuses
        tiny = dataclasses.replace(prog, sim_end_us=1000)
        if n_points == 1:
            run_replicated_bss(tiny, replicas, key, mesh=mesh)
        else:
            run_replicated_bss(
                tiny, replicas, key, mesh=mesh,
                sim_end_us=[tiny.sim_end_us] * n_points,
            )

    spec = None if mesh is not None else dict(
        engine="bss", prog=prog, key=np.asarray(key), replicas=replicas,
    )
    return StudyDescriptor(
        "bss", ck, int(prog.sim_end_us), launch, warm, spec=spec
    )


def run_replicated_bss(
    prog: BssProgram,
    replicas: int,
    key: jax.Array,
    max_steps: int | None = None,
    mesh=None,
    *,
    sim_end_us=None,
    traffic_sweep=None,
    chunk_steps: int | None = None,
    checkpoint=None,
    block: bool = True,
    geom_per_step: bool = False,
):
    """Execute ``replicas`` Monte-Carlo replicas of the scenario.

    Returns a dict of per-replica outcome arrays:
      ``srv_rx``   (R,)   echo requests decoded at the AP
      ``cli_rx``   (R,N)  echo replies decoded per STA (col 0 unused)
      ``tx_data``  (R,)   data-frame transmission attempts
      ``drops``    (R,)   frames dropped at retry limit
      ``tx_mpdus`` (R,)   MPDUs those PPDUs carried (``max_mpdus > 1`` only)

    and, from a program of the per-node arm (:func:`per_node_medium`):
      ``flow_rx`` (R,N) data frames of each sender delivered (once each)
      ``tx_by``   (R,N) data frames each node sent
      ``tx_rts`` (R,)   RTS frames sent
      ``cts_timeout`` (R,) RTS that no CTS answered in time
      ``rx_lost_hidden`` (R,) RTS and DATA frames their destination did
                        not decode while a node their sender cannot
                        sense was on the air
      ``backlog`` (R,N) frames each sender still holds at the horizon
      ``steps``    int    vector event-loop iterations executed
      ``all_done`` bool   every replica reached sim_end (sanity flag)

    With ``mesh`` (a 1-axis ``jax.sharding.Mesh`` named "replica"), the
    replica axis of every state array is sharded over the mesh devices;
    the only cross-device traffic is the loop's any-replica-pending
    reduction (the LBTS-grant analog) and the final stats gather.

    ``sim_end_us=[...]`` runs a **config-axis horizon sweep**: the
    sim-end bound gains a leading vmapped axis, so a C-point horizon
    study is ONE launch of a (C, R, …) program; returns a list of
    per-point result dicts whose OUTCOME fields equal the per-point
    launch with ``dataclasses.replace(prog, sim_end_us=v)`` and the
    same key.  (``steps`` is the exception: the sweep shares one step
    budget and runs every point to the slowest point's bound — a
    finished replica is a fixed point of step_fn, so the extra
    iterations change nothing but the counter.)

    ``traffic_sweep=[...]`` (TrafficPrograms sharing one
    ``shape_key``, with ``prog.traffic`` naming the shape class) runs
    a **config-axis workload sweep** instead: the traffic operand
    tables gain the leading vmapped axis, so a C-point mixed
    cbr/mmpp/onoff/trace workload study is ONE launch of a (C, R, …)
    program — demuxed bit-equal to per-point launches with
    ``dataclasses.replace(prog, traffic=tp)`` and the same key (the
    sweep shares one step budget, exactly like the horizon sweep).

    ``chunk_steps=N`` splits the event loop into N-iteration segments
    with a donated carry handoff (bit-identical: the loop condition
    depends only on the carry).  ``checkpoint=`` (a path or
    :class:`~tpudes.parallel.checkpoint.CarryCheckpoint`) persists the
    carry after each segment and resumes a matching run from its last
    completed segment, bit-equal to uninterrupted.  ``block=False``
    returns an :class:`~tpudes.parallel.runtime.EngineFuture`.
    """
    import dataclasses

    from tpudes.parallel.runtime import Launch, chunk_bounds, stack_axis

    if sim_end_us is not None and traffic_sweep is not None:
        raise ValueError(
            "one config axis per launch: sweep either the horizon "
            "(sim_end_us=[...]) or the workload (traffic_sweep=[...])"
        )
    sweep = "traffic" if traffic_sweep is not None else "horizon"
    n_cfg = (
        len(sim_end_us) if sim_end_us is not None
        else (len(traffic_sweep) if traffic_sweep is not None else None)
    )
    ends = (
        [int(v) for v in sim_end_us] if sim_end_us is not None
        else [prog.sim_end_us]
    )
    sweep_progs = (
        [prog] if traffic_sweep is None
        else [
            dataclasses.replace(prog, traffic=tp) for tp in traffic_sweep
        ]
    )
    medium = per_node_medium(prog)
    if max_steps is None:
        max_steps = max(
            _estimate_max_steps(dataclasses.replace(p, sim_end_us=v), medium)
            for v in ends
            for p in sweep_progs
        )
    mobile = prog.mobility is not None
    # per-replica keying in step_fn makes the replica bucket exact, and
    # a finished replica's state is a fixed point of step_fn, so the
    # extra loop iterations the padding may cause cannot corrupt real
    # replicas
    L = Launch("bss", key, replicas, mesh, n_cfg)
    # the per-node arm keeps no FlowMonitor columns: TpudesObs leaves it
    # the program it is
    obs = L.obs and not medium
    if prog.max_mpdus > 1 or medium:
        from tpudes.obs import spans

        launch = spans.current()
        if launch is not None and launch.name == "launch":
            if medium:
                launch.args["hidden_pairs"] = hidden_pairs(prog)
                launch.args["rts_threshold"] = int(prog.rts_threshold)
            else:
                launch.args["max_mpdus"] = int(prog.max_mpdus)

    def build():
        init_state, _, fn = build_bss_advance(
            prog, L.r_pad, obs=obs, n_cfg=n_cfg,
            geom_per_step=geom_per_step, sweep=sweep,
        )
        return (
            lambda: (stack_axis(init_state(), n_cfg),),
            (L.axis,), fn, None,
        )

    def operands(parts):
        # mobility/traffic params ride as TRACED operands (None for the
        # legacy paths); the cache key carries only shapes
        geom = (
            None if not mobile
            else dict(
                stride=np.int32(max(1, int(prog.geom_stride))),
                **prog.mobility.operands(),
            )
        )
        if traffic_sweep is not None:
            from tpudes.traffic.device import stack_traffic_operands

            if prog.traffic is None or any(
                tp.shape_key() != prog.traffic.shape_key()
                for tp in traffic_sweep
            ):
                raise ValueError(
                    "a workload sweep needs prog.traffic set and every "
                    "point sharing its traffic shape key (one executable "
                    "serves the sweep; pad tables to a common capacity)"
                )
            tr = stack_traffic_operands(traffic_sweep)
        else:
            tr = None if prog.traffic is None else prog.traffic.operands()
        sim_end = (
            np.int32(ends[0]) if n_cfg is None or sweep == "traffic"
            else np.asarray(ends, np.int32)
        )
        return (parts[0], None), (sim_end, geom, tr)

    # max_steps AND sim_end are traced operands (a horizon sweep reuses
    # ONE executable); the runner is mesh-independent, sharding flows
    # from the init program's outputs
    L.prepare(
        lambda: (_prog_cache_key(prog), L.r_pad, obs, n_cfg, mobile,
                 geom_per_step, sweep if n_cfg is not None else None),
        build, operands,
    )

    def call(run, carry, max_steps, ops):
        # finished replicas are a fixed point of step_fn, so later
        # segments cost them one cond evaluation
        state, still_pending, metrics = run(carry[0], key, max_steps, *ops)
        return (state, still_pending), metrics

    def fetch(carry):
        # steps/all_done ride along instead of costing their own round
        # trips
        out, still_pending = carry
        names = ("srv_rx", "cli_rx", "tx_data", "drops", "step")
        if prog.max_mpdus > 1:
            names += ("tx_mpdus",)
        if medium:
            names += MEDIUM_FIELDS
        if obs:
            from tpudes.obs.flowmon import FM_KEYS

            names += ("retx",) + FM_KEYS
        return dict({k: out[k] for k in names}, pending=still_pending)

    def record_geometry(host, points):
        # a sweep's vmapped while_loop advances every point's step
        # counter in lockstep, so the lanes agree on the shared loop's
        # step count
        from tpudes.obs.geometry import GeomTelemetry

        stride = max(1, int(prog.geom_stride))
        steps = int(np.max(host["step"]))
        GeomTelemetry.record_device("bss", -(-steps // stride), steps)

    return L.drive(
        call,
        chunk_bounds(max_steps, chunk_steps or max_steps),
        fetch,
        lambda host: _bss_unpack(host, replicas, obs, prog),
        once=record_geometry if mobile else None,
        checkpoint=checkpoint,
        identity=lambda: _prog_cache_key(prog) + (
            tuple(ends), geom_per_step,
            # traffic identity by VALUE (shape key alone would let a
            # resumed run silently swap workloads mid-study)
            None if prog.traffic is None else prog.traffic.param_key(),
            None if traffic_sweep is None
            else tuple(tp.param_key() for tp in traffic_sweep),
        ),
        block=block,
    )


# --- trace manifest (tpudes.analysis.jaxpr) --------------------------------

#: canonical tiny replica count for the abstract traces
_TRACE_R = 2


def _trace_prog(**over):
    """Canonical tiny-shape program: AP + 2 STAs on the sensing circle."""
    import dataclasses

    from tpudes.parallel.programs import toy_bss_program

    prog = toy_bss_program(n_sta=2, sim_end_us=20_000)
    return dataclasses.replace(prog, **over) if over else prog


def _trace_hidden_prog():
    """The canonical program with its two stations hidden from each other
    behind a loss table and RTS/CTS on: the per-node arm."""
    import dataclasses

    loss = np.full((3, 3), 60.0)
    loss[1, 2] = loss[2, 1] = 200.0
    np.fill_diagonal(loss, 0.0)
    return dataclasses.replace(_trace_prog(), loss_db=loss, rts_threshold=100)


def _trace_entries(
    prog: "BssProgram", obs: bool = False, r: int = _TRACE_R,
    scale: bool = True,
):
    """The cached-runner functions exactly as ``run_replicated_bss``
    jits them, with concrete tiny operands.  ``r`` parameterizes the
    replica count for the JXL007 replicas axis; ``scale=False`` skips
    the axis declarations (the axis builders re-enter here)."""
    from tpudes.analysis.jaxpr.spec import TraceEntry

    init_state, pending, fn = build_bss_advance(prog, r, obs=obs)
    key = jax.random.PRNGKey(0)
    s0 = init_state()
    tr = None if prog.traffic is None else prog.traffic.operands()
    traced = {"max_steps": 2, "sim_end": 3}
    if tr is not None:
        traced["tr"] = 5
    return [
        TraceEntry("init", init_state, (), kernel=False),
        TraceEntry(
            "advance",
            fn,
            (s0, key, jnp.int32(64), jnp.int32(prog.sim_end_us), None,
             tr),
            donate=(0,),
            carry=(0,),
            traced=traced,
            scale_axes=_scale_axes() if scale else (),
        ),
    ]


def _scale_axes():
    """JXL007 scale axes for the BSS advance kernel: state and step
    tables are linear in the replica count, and the pairwise
    detectability geometry is O(n_sta^2) by physical contract — the
    station axis is declared at budget 2.0 (a dense pairwise table is
    the model, not an accident)."""
    from tpudes.analysis.jaxpr.spec import ScaleAxis

    def at(n_sta=None, r=_TRACE_R):
        if n_sta is None:
            prog = _trace_prog()
        else:
            from tpudes.parallel.programs import toy_bss_program

            prog = toy_bss_program(
                n_sta=int(n_sta), sim_end_us=20_000
            )
        return _trace_entries(prog, r=int(r), scale=False)[1]

    return (
        ScaleAxis(
            "replicas",
            lambda v: at(r=int(v)),
            points=(2, 8),
            mem_budget=1.0,
        ),
        ScaleAxis(
            "n_sta",
            lambda v: at(n_sta=int(v)),
            points=(2, 8),
            mem_budget=2.0,
            note="pairwise detect/interference geometry is O(n_sta^2) "
                 "by the channel model — budget 2.0 is the contract, "
                 "not a concession",
        ),
    )


def _flip_traffic():
    from tpudes.traffic import TrafficProgram

    return TrafficProgram.mmpp(3, 50.0, horizon_us=20_000)


def _trace_flips():
    import dataclasses

    from tpudes.analysis.jaxpr.spec import FlipSpec

    base = _trace_prog()

    def flip(**over):
        prog = dataclasses.replace(base, **over)
        return FlipSpec(
            build=lambda p=prog: _trace_entries(p),
            key_differs=_prog_cache_key(prog) != _prog_cache_key(base),
        )

    return {
        # live components: each must change some traced program
        "data_bytes": flip(data_bytes=600),
        "beacon_bytes": flip(beacon_bytes=100),
        "obs": FlipSpec(
            build=lambda: _trace_entries(base, obs=True),
            key_differs=True,
        ),
        # a workload program joins the trace (the traffic stage) and
        # its SHAPE key joins the cache key — while the traffic
        # manifest's own flips pin that model/param flips inside the
        # family stay compile-free
        "traffic": flip(traffic=_flip_traffic()),
        # RTS/CTS and the shared-medium control compile the per-node arm
        "rts_threshold": flip(rts_threshold=100),
        "carrier_sense": flip(carrier_sense="shared"),
        # excluded-by-design fields must leave every trace identical:
        # the horizon is a traced operand (one executable per program
        # across every sim_end / step budget)
        "sim_end_us": flip(sim_end_us=40_000),
        "geom_stride": flip(geom_stride=4),
    }


def trace_manifest():
    """Per-engine trace manifest (see :mod:`tpudes.analysis.jaxpr`)."""
    from tpudes.analysis.jaxpr.spec import TraceManifest, TraceVariant

    return TraceManifest(
        engine="bss",
        path="tpudes/parallel/replicated.py",
        variants=lambda: [
            TraceVariant(
                "base", lambda: _trace_entries(_trace_prog())
            ),
            # the TpudesObs program (FlowMonitor columns + packet ring)
            # joins the lint surface: its ring dynamic_update_slice
            # must pass the registered SparseSite contract (JXL008)
            TraceVariant(
                "obs", lambda: _trace_entries(_trace_prog(), obs=True)
            ),
            # the per-node arm: hidden pairs, RTS/CTS, NAV
            TraceVariant(
                "hidden", lambda: _trace_entries(_trace_hidden_prog(), scale=False)
            ),
        ],
        flips=_trace_flips,
    )
