"""tpudes.obs — unified observability across all three execution layers.

One GlobalValue knob, ``TpudesObs`` (bound like every engine knob:
``GlobalValue.Bind``, ``--TpudesObs=1`` on any CommandLine script, or
``NS_GLOBAL_VALUE``), turns on:

- the **host event-loop profiler** (:mod:`tpudes.obs.profiler`):
  per-event-type counts and wall time, queue depth, per-window stats
  and the propagation-cache hit rate on the windowed engine;
- the **flight recorder** (:mod:`tpudes.obs.flight_recorder`): the last
  ``TpudesObsRing`` events, dumped on an exception or invariant trip;
- **on-device metric accumulators** in the parallel engines, fetched
  once at run end (no host sync in the scan), plus process-wide XLA
  compile telemetry (:mod:`tpudes.obs.device` — always on, it costs one
  dict update per compile);
- the **Chrome-trace export** (:mod:`tpudes.obs.export`): set
  ``TpudesObsTrace=/path/trace.json`` and ``Simulator.Destroy`` writes
  a chrome://tracing / Perfetto loadable timeline.  Validate with
  ``python -m tpudes.obs trace.json``;
- the **device FlowMonitor** (:mod:`tpudes.obs.flowmon`): per-flow
  FlowStats columns and a packet-event ring riding each compiled
  engine's scan carry, reduced on the host into the same ``FlowStats``
  objects the host monitor produces.  Export through the shared
  ns-3-parity XML serializer, write delivered packets as pcap, merge
  flow spans into the Chrome trace, or round-trip a device run back
  into a trace-replay ``TrafficProgram``.  Validate the artifacts with
  ``python -m tpudes.obs --flowmon flowmon.xml`` / ``--pcap out.pcap``.

With the knob at 0 the engines run their pre-obs code paths unchanged
(pinned by the overhead test in tests/test_obs.py).

Independent of the knob and always on, the simulator's own launch path
is measured (:mod:`tpudes.obs.spans`, about 2 us a span, a bounded ring
in memory): host spans ``lifted_run`` > ``lift``, ``launch`` >
``launch.runner`` / ``.operands`` / ``.enqueue``, and ``result.wait`` /
``.fetch`` / ``.unpack`` tied to their launch by its id; and
:meth:`CompileTelemetry.xla_events` keeps every XLA trace, compile and
persistent-cache hit with its time.  They differ from ``TpudesObs=1``,
which adds carry buffers and so compiles a DIFFERENT device program
that measures the simulated network: these measure the simulator and
change no executable.  To read them: ``tpudes.obs.spans.snapshot()``
in process, or any ``jax.profiler`` trace, where the spans are the
host-plane events ``tpudes:*`` and every engine's loop carries its
scopes (``tpudes.<engine>.step`` / ``.cond`` and the engines' own
``.rng``, ``.ampdu``, ``.cc``, ``.queue``: PERF.md section 3 has the
list) on its device operations.  :mod:`tpudes.obs.explain` reads such a
trace by those names into one table (a loop step by scope, the
``while``'s own time, the idle gaps by span): ``explain.session()``
around launches, ``explain.replay()`` of the last one, or ``python -m
tpudes.obs --explain <trace>``; it is imported on use, not here.
"""

from tpudes.obs import spans
from tpudes.obs.device import (
    ChunkStream,
    CompileTelemetry,
    device_metrics_enabled,
)
from tpudes.obs.distributed import (
    DistributedTelemetry,
    validate_distributed_metrics,
)
from tpudes.obs.export import (
    assert_valid_chrome_trace,
    chrome_trace,
    export_chrome_trace,
    export_on_destroy,
    validate_chrome_trace,
)
from tpudes.obs.flight_recorder import FlightRecorder
from tpudes.obs.flowmon import (
    DeviceFlowMonitor,
    decode_packet_rings,
    host_reference_stats,
    reduce_flow_stats,
    serialize_flow_stats_xml,
    validate_flowmon_xml,
    validate_pcap,
    write_events_pcap,
)
from tpudes.obs.fuzz import FuzzTelemetry, validate_fuzz_metrics
from tpudes.obs.grad import GradTelemetry, validate_grad_metrics
from tpudes.obs.profiler import (
    HostProfiler,
    InstrumentedScheduler,
    RunStats,
    enabled,
)
from tpudes.obs.serving import ServingTelemetry, validate_serving_metrics
from tpudes.obs.traffic import TrafficTelemetry, validate_traffic_metrics

__all__ = [
    "TrafficTelemetry",
    "validate_traffic_metrics",
    "ChunkStream",
    "CompileTelemetry",
    "DeviceFlowMonitor",
    "DistributedTelemetry",
    "FlightRecorder",
    "FuzzTelemetry",
    "GradTelemetry",
    "validate_grad_metrics",
    "HostProfiler",
    "InstrumentedScheduler",
    "RunStats",
    "ServingTelemetry",
    "assert_valid_chrome_trace",
    "chrome_trace",
    "decode_packet_rings",
    "device_metrics_enabled",
    "enabled",
    "export_chrome_trace",
    "export_on_destroy",
    "host_reference_stats",
    "reduce_flow_stats",
    "serialize_flow_stats_xml",
    "spans",
    "validate_chrome_trace",
    "validate_distributed_metrics",
    "validate_flowmon_xml",
    "validate_fuzz_metrics",
    "validate_pcap",
    "validate_serving_metrics",
    "write_events_pcap",
]
