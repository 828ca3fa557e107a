#!/usr/bin/env python3
"""One cell of the benchmark, in one new process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's configuration, traffic mix, driver, plain reference, limits and
per-layer readers by the names in `BENCHMARK.json` (see `manifest.py`), refuses to
run without the TPU chips the cell asks for, warms up the cell's own shapes
(set-up), measures for `--seconds`, then checks what the window produced against
the plain reference.  The last stdout line is the result; the numbers `correct`
compared are its last key and the last lines on stderr.
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


@dataclasses.dataclass
class Cell:
    """What a driver gets: the cell's files, the configuration's reference
    module (loaded once: set-up asks it for the script's exit criterion, the
    check for the comparison), the seed, and the set-up split."""

    root: str
    name: str
    chips: int
    cfg: dict
    traffic: dict
    reference: object
    seed: int
    split: dict


def make_cell(manifest, workload: str, seed: int, program_root: str) -> Cell:
    entry = manifest.cell(workload)
    cfg = manifest.config(entry["config"])
    return Cell(
        root=program_root, name=workload, chips=int(entry["chips"]), cfg=cfg,
        traffic=manifest.traffic(entry["traffic"]),
        reference=manifest.reference(cfg["reference"]), seed=seed,
        split={"import_and_chip_init_s": time.monotonic() - PROCESS_START},
    )


def judge(numbers: dict, limits: dict):
    """Each number that has a limit beside it, and whether all hold; a number
    the comparison could not produce fails."""
    compared = {
        name: {"value": numbers.get(name), "limit": limit}
        for name, limit in limits.items()
    }
    return compared, all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values()
    )


def require_chips(chips: int):
    """The devices, or exit: a benchmark number comes from a TPU only."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != chips:
        print(
            f"benchmark: cell needs {chips} TPU chip(s); jax found "
            f"{len(devices)} x {devices[0].platform!r} "
            f"({devices[0].device_kind}): refusing to run",
            file=sys.stderr,
        )
        raise SystemExit(2)
    return devices


def device_block(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": peak,
    }


def trace_dir(root: str, name: str) -> str:
    return os.path.join(root, ".bench_trace", name)


def profiler(directory: str):
    """Context manager factory: a `jax.profiler` window without the Python
    tracer (it would slow the host that the spans time)."""
    import jax

    @contextlib.contextmanager
    def profile():
        shutil.rmtree(directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(directory, profiler_options=options)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    return profile


def run_cell(manifest, workload: str, seed: int, seconds: float, traced: bool,
             devices, program_root: str = ROOT) -> dict:
    """Set-up, window, check; returns the result line as a dict.
    `program_root` is the checkout that holds `examples/` and `tpudes/`."""
    from benchmark import trace as trace_mod
    from benchmark.spans import Spans
    from tpudes.obs.device import CompileTelemetry

    cell = make_cell(manifest, workload, seed, program_root)
    driver = manifest.driver(cell.traffic["driver"])
    limits = manifest.limits(workload)
    with open(os.path.join(manifest.bench_dir, "peaks.json")) as f:
        peaks = json.load(f)["peaks"]
    kind = devices[0].device_kind
    if devices[0].platform == "tpu" and kind not in peaks:
        # a device that is not in the table is an error, not a default
        raise SystemExit(f"benchmark: no peaks recorded for device {kind!r}")

    state = driver.setup(cell)
    setup_s = time.monotonic() - PROCESS_START
    print(json.dumps({"setup_split": cell.split, "setup_s": setup_s}), flush=True)

    def compiles_so_far():
        return sum(e["compiles"] for e in CompileTelemetry.snapshot().values())

    compiles_before = compiles_so_far()
    spans = Spans(annotate=True) if traced else None
    directory = trace_dir(program_root, workload)
    record = driver.window(
        state, cell, seconds, spans=spans,
        profile=profiler(directory) if traced else None,
    )
    compiles = compiles_so_far() - compiles_before
    device = device_block(devices)

    t_check = time.monotonic()
    numbers = driver.check(state, cell, record)
    print(json.dumps({"check_s": time.monotonic() - t_check}), flush=True)
    compared, correct = judge(numbers, limits)

    metrics = {}
    if traced:
        reduced = trace_mod.reduce_trace(trace_mod.load_xplane(directory))
        shutil.rmtree(directory, ignore_errors=True)
        context = dict(
            spans=spans.durations, starts=spans.starts, trace=reduced,
            record=record,
            device=device, compiles_in_window=compiles,
            counters=driver.counters(state, cell, record),
        )
        for m in manifest.metrics_of("per_layer", workload):
            value = manifest.layer_reader(m["name"])(context)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    else:
        values = dict(driver.end_to_end(state, cell, record), setup_s=setup_s)
        for m in manifest.metrics_of("end_to_end", workload):
            metrics[m["name"]] = {
                "value": float(values[m["name"]]), "unit": m["unit"],
            }
    result = {
        "correct": bool(correct),
        "attempted": int(driver.attempted(record)),
        "failed": int(record.get("failed", 0)),
        "metrics": metrics, "device": device,
    }
    if traced and reduced is not None:
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.manifest import Manifest

    manifest = Manifest(ROOT)
    entry = manifest.cell(args.workload)
    devices = require_chips(int(entry["chips"]))
    result = run_cell(
        manifest, args.workload, args.seed, args.seconds, bool(args.trace),
        devices,
    )
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"compared {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
