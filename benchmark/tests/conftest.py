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
    "toy-tcp": {"driver": "mc", "replicas": 4, "horizon_s": 1.0,
                "warm_launches": 1, "trace_launches": 2, "trace_horizon_s": 0.5},
    "toy-as": {"driver": "mc", "replicas": 4, "horizon_s": 1.0,
               "warm_launches": 1, "trace_launches": 2, "trace_horizon_s": 0.5},
}
TOY_CELLS = {
    "toy.lte": ("lena-hex7x30", "toy-lte", "lte.mc"),
    "toy.bss": ("toy-config", "toy-bss", "wifi.mc"),
    "toy.script": ("wifi-bss-64sta", "toy-script", "wifi.mc"),
    "toy.tcp": ("toy-dumbbell", "toy-tcp", None),
    "toy.as": ("toy-as", "toy-as", None),
}
#: the toy cells whose traffic's driver is `mc`
TOY_MC_CELLS = ["toy.lte", "toy.bss", "toy.tcp", "toy.as"]
#: deployments of two engine kinds the shipped cells do not have, each a new
#: configuration file and a new (stub) reference: what the next `model_config`
#: PR brings for `tcp.mc` and `as.mc`, at a size the CPU holds.  The dumbbell's
#: horizon is a slot count (1 / slot_s = 10 Mbit/s over 1040-byte packets), the
#: AS study's a float of seconds, and its loop does not scale with it
TOY_CONFIGS = {
    "toy-dumbbell": {
        "source": "https://gitlab.com/nsnam/ns-3-dev/-/blob/master/examples/tcp/tcp-variants-comparison.cc",
        "script": "tcp-variants.py", "args": {"nFlows": 2, "variant": "TcpCubic"},
        "horizon_arg": "simTime", "kind": "dumbbell",
        "horizon_field": {"name": "n_slots", "per_second": 1e7 / 8320},
        "step_iterations": {"per_sim_second": 1e7 / 8320},
        "reference": "toy_dumbbell", "reduced": [],
    },
    "toy-as": {
        "source": "https://www.nsnam.org/docs/models/html/brite.html",
        "script": "brite-as.py", "args": {"nNodes": 50, "nFlows": 4},
        "horizon_arg": "simTime", "kind": "as_flows",
        "horizon_field": {"name": "sim_s", "per_second": 1},
        "step_iterations": {"per_launch": 4},
        "reference": "toy_as", "reduced": [],
    },
}
#: a stub reference: the script's exit criterion, the rows that came, a kpi
TOY_REFERENCE = '''import numpy as np


def criterion(out):
    return None if {holds} else "{says}"


def compare(cfg, traffic, outs, expected_rows, seed):
    rows = [np.asarray(o["{rows}"]) for o in outs]
    return {{"rows_missing": float(
        expected_rows - sum(r.shape[0] for r in rows if r.ndim == 2))}}


def simulate(cfg, horizon_s, replicas, seed, **control):
    raise NotImplementedError("a stub: the toy cell has no control")


def kpi(out):
    return float(np.asarray(out["{rows}"], float).sum(axis=-1).mean())
'''
TOY_REFERENCES = {
    "toy_dumbbell": dict(holds='np.asarray(out["goodput_mbps"]).sum() > 0',
                         says="goodput > 0", rows="goodput_mbps"),
    "toy_as": dict(holds='not np.asarray(out["unreachable"]).any()',
                   says="no unreachable flow", rows="goodput_bps"),
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
    later PR is held to.  `toy.tcp` and `toy.as` are of engine kinds no shipped
    cell has (`dumbbell`, `as_flows`): on PR 34's parent they stop in
    `mc.setup` with `RuntimeError: tcp-variants.py: exit criterion failed: no
    exit criterion recorded for kind 'dumbbell'`, because the harness kept a
    table of exit criteria keyed on two kinds."""
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
        limits = root / "benchmark" / "limits"
        if limits_of:
            shutil.copy(limits / f"{limits_of}.json", limits / f"{cell}.json")
        else:
            (limits / f"{cell}.json").write_text(json.dumps(
                {"limits": {"rows_missing": 0, "rerun_differs": 0}}))
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
    for name, config in TOY_CONFIGS.items():
        path = f"benchmark/configs/{name}.json"
        (root / path).write_text(json.dumps(dict(config, name=name)))
        manifest["configs"].append({
            "name": name, "source": config["source"], "reduced": [],
            "file": path, "why": "toy size for the CPU tests",
        })
    for name, stub in TOY_REFERENCES.items():
        (root / "benchmark" / "references" / f"{name}.py").write_text(
            TOY_REFERENCE.format(**stub))
    (root / "benchmark" / "layers" / "toy_launches.py").write_text(TOY_READER)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "lte.mc" in m.get("workloads", ()):
            m["workloads"] += TOY_MC_CELLS
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
        "workloads": list(TOY_MC_CELLS)})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root)
