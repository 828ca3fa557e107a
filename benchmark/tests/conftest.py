"""Tests of the benchmark's own files: tiny, on the CPU, run by the builder
(`python -m pytest benchmark/tests -q`); tier-1 does not collect them."""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TOY_TRAFFIC = {
    "toy-lte": {"driver": "mc", "replicas": 8, "horizon_s": 0.3,
                "warm_launches": 1, "warm_horizon_s": 0.1, "trace_launches": 2,
                "trace_horizon_s": 0.1, "reference_replicas": 2},
    "toy-bss": {"driver": "mc", "replicas": 4, "horizon_s": 1.3,
                "warm_launches": 1, "trace_launches": 2,
                "trace_horizon_s": 1.2, "reference_replicas": 2},
    "toy-script": {"driver": "script", "replicas": 4, "horizon_s": 1.3,
                   "warm_studies": 2, "trace_studies": 2,
                   "reference_replicas": 2},
}
TOY_CELLS = {
    "toy.lte": ("lena-hex7x30", "toy-lte", "lte.mc"),
    "toy.bss": ("toy-config", "toy-bss", "wifi.mc"),
    "toy.script": ("wifi-bss-64sta", "toy-script", "wifi.mc"),
}
#: a per-layer metric that exists only as a new file and a manifest entry
TOY_READER = '''def read(ctx):
    return float(len(ctx["record"].get("outs") or ctx["record"]["studies"]))
'''
SCRIPT_LAYERS = ("graph_build_ms", "lower_ms", "run_host_ms", "launch_device_ms",
                 "study_p95_ms", "script_device_idle_share",
                 "script_compiles_in_window", "script_kpi_mean")


@pytest.fixture(scope="session")
def toy_root(tmp_path_factory):
    """A temp copy of `BENCHMARK.json` + `benchmark/` with toy cells ADDED as
    files and manifest entries: no file that exists is edited, which is what a
    later PR is held to."""
    root = tmp_path_factory.mktemp("toy")
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), root / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for name, mix in TOY_TRAFFIC.items():
        (root / "benchmark" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for cell, (config, traffic, limits_of) in TOY_CELLS.items():
        manifest["workloads"].append({
            "name": cell, "config": config, "traffic": traffic, "chips": 1,
            "why": "toy size for the CPU tests",
        })
        shutil.copy(
            root / "benchmark" / "limits" / f"{limits_of}.json",
            root / "benchmark" / "limits" / f"{cell}.json",
        )
    # a dummy configuration: one new file, one new manifest entry
    config = json.loads(
        (root / "benchmark" / "configs" / "wifi-bss-64sta.json").read_text()
    )
    config["name"] = "toy-config"
    (root / "benchmark" / "configs" / "toy-config.json").write_text(json.dumps(config))
    manifest["configs"].append({
        "name": "toy-config", "source": config["source"], "reduced": [],
        "file": "benchmark/configs/toy-config.json", "why": "dummy",
    })
    (root / "benchmark" / "layers" / "toy_launches.py").write_text(TOY_READER)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "lte.mc" in m.get("workloads", ()):
            m["workloads"] += ["toy.lte", "toy.bss"]
        if "wifi.script" in m.get("workloads", ()):
            m["workloads"].append("toy.script")
    names = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]}
    if "study_p50_s" not in names:      # the script cell is not shipped
        manifest["end_to_end"].append({
            "name": "study_p50_s", "unit": "s", "better": "lower", "bound": 0.05,
            "source": "host_clock", "workloads": ["toy.script"]})
        manifest["per_layer"] += [
            {"name": n, "unit": "x", "better": "lower", "source": "host_clock",
             "layer": "x", "moves": "study_p50_s", "workloads": ["toy.script"]}
            for n in SCRIPT_LAYERS
        ]
    manifest["per_layer"].append({
        "name": "toy_launches", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "toy", "moves": "sim_s_per_wall_s",
        "workloads": ["toy.lte", "toy.bss"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root)
