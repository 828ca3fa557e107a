"""Validate tpudes.obs export files against their schemas.

Usage::

    python -m tpudes.obs <trace.json> [more.json ...]
    python -m tpudes.obs --serving <metrics.json> [more.json ...]
    python -m tpudes.obs --fuzz <metrics.json> [more.json ...]
    python -m tpudes.obs --distributed <metrics.json> [more.json ...]
    python -m tpudes.obs --geometry <metrics.json> [more.json ...]
    python -m tpudes.obs --traffic <metrics.json> [more.json ...]
    python -m tpudes.obs --grad <metrics.json> [more.json ...]
    python -m tpudes.obs --flowmon <flowmon.xml> [more.xml ...]
    python -m tpudes.obs --pcap <capture.pcap> [more.pcap ...]
    python -m tpudes.obs --explain <trace dir or .xplane.pb> [more ...]

Default mode checks Chrome-trace exports against the Trace Event
format; ``--serving`` checks :class:`tpudes.obs.serving.ServingTelemetry`
snapshot dumps against the serving-metrics schema; ``--fuzz`` checks
:class:`tpudes.obs.fuzz.FuzzTelemetry` snapshot dumps against the
fuzz-metrics schema; ``--distributed`` checks
:class:`tpudes.obs.distributed.DistributedTelemetry` snapshot dumps
against the hybrid-PDES window-protocol schema; ``--geometry`` checks
:class:`tpudes.obs.geometry.GeomTelemetry` snapshot dumps against the
geometry-refresh schema (device recomputes vs host refreshes, stride
hit rate); ``--traffic`` checks
:class:`tpudes.obs.traffic.TrafficTelemetry` snapshot dumps against
the workload schema (offered vs delivered load, per-model launch
counts, burst duty cycle); ``--grad`` checks
:class:`tpudes.obs.grad.GradTelemetry` snapshot dumps against the
gradient schema (grad-norm/loss rings, descent step counters,
non-finite canaries); ``--flowmon`` checks FlowMonitor XML exports
(ours or upstream ns-3's ``SerializeToXmlFile``) for the standard
FlowStats attribute set; ``--pcap`` structurally validates classic
libpcap captures (both byte orders, µs and ns magic) record by record
— these two read XML / raw bytes, not JSON.  ``--explain`` validates
nothing: it prints :mod:`tpudes.obs.explain`'s table of a
``jax.profiler`` trace of lifted runs (a loop step by ``tpudes.*``
scope, the ``while``'s own time, the idle gaps by ``tpudes:`` span).
Exit 0 when every file is valid, 1 on
violations, 2 on usage / unreadable input.  These are the schema gates
the CI smoke steps run over the artifacts an example (``TpudesObs=1``),
the serving smoke, and the fuzz smoke produce.
"""

from __future__ import annotations

import json
import sys

from tpudes.obs.distributed import validate_distributed_metrics
from tpudes.obs.export import validate_chrome_trace
from tpudes.obs.fuzz import validate_fuzz_metrics
from tpudes.obs.geometry import validate_geometry_metrics
from tpudes.obs.grad import validate_grad_metrics
from tpudes.obs.serving import validate_serving_metrics
from tpudes.obs.traffic import validate_traffic_metrics


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    serving = "--serving" in argv
    fuzz = "--fuzz" in argv
    distributed = "--distributed" in argv
    geometry = "--geometry" in argv
    traffic = "--traffic" in argv
    grad = "--grad" in argv
    flowmon = "--flowmon" in argv
    pcap = "--pcap" in argv
    explain = "--explain" in argv
    argv = [
        a for a in argv
        if a not in ("--serving", "--fuzz", "--distributed",
                     "--geometry", "--traffic", "--grad",
                     "--flowmon", "--pcap", "--explain")
    ]
    if (
        not argv
        or serving + fuzz + distributed + geometry + traffic + grad
        + flowmon + pcap + explain > 1
        or any(a in ("-h", "--help") for a in argv)
    ):
        print(__doc__, file=sys.stderr)
        return 2
    if explain:
        from tpudes.obs.explain import format_table, load, reduce

        for path in argv:
            try:
                events = load(path)
            except (OSError, ImportError) as e:
                print(f"{path}: unreadable ({e})", file=sys.stderr)
                return 2
            print(f"{path}:\n{format_table(reduce(events))}")
        return 0
    if flowmon or pcap:
        # non-JSON modes: FlowMonitor XML / raw libpcap bytes
        from tpudes.obs.flowmon import validate_flowmon_xml, validate_pcap

        rc = 0
        for path in argv:
            try:
                if pcap:
                    with open(path, "rb") as f:
                        problems, n = validate_pcap(f.read())
                else:
                    with open(path, encoding="utf-8") as f:
                        problems, n = validate_flowmon_xml(f.read())
            except OSError as e:
                print(f"{path}: unreadable ({e})", file=sys.stderr)
                return 2
            if problems:
                rc = 1
                for p in problems:
                    print(f"{path}: {p}")
            else:
                kind = "pcap capture" if pcap else "FlowMonitor XML"
                print(f"{path}: valid {kind} ({n} records)")
        return rc
    if serving:
        validate, kind = validate_serving_metrics, "serving metrics"
    elif fuzz:
        validate, kind = validate_fuzz_metrics, "fuzz metrics"
    elif distributed:
        validate, kind = validate_distributed_metrics, "distributed metrics"
    elif geometry:
        validate, kind = validate_geometry_metrics, "geometry metrics"
    elif traffic:
        validate, kind = validate_traffic_metrics, "traffic metrics"
    elif grad:
        validate, kind = validate_grad_metrics, "gradient metrics"
    else:
        validate, kind = validate_chrome_trace, "Chrome trace"
    rc = 0
    for path in argv:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"{path}: unreadable ({e})", file=sys.stderr)
            return 2
        problems = validate(doc)
        if problems:
            rc = 1
            for p in problems:
                print(f"{path}: {p}")
        else:
            if serving:
                n = len(doc["engines"])
            elif fuzz:
                n = doc["counters"]["scenarios"]
            elif distributed:
                n = doc["counters"]["windows"]
            elif geometry or traffic or grad:
                n = len(doc["engines"])
            else:
                n = len(doc["traceEvents"])
            print(f"{path}: valid {kind} ({n} records)")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
