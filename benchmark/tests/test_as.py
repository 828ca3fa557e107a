"""The cell `as.mc`: its names resolve, its limits carry their reasons, its three
readers read a recorded window (and nothing from another engine's), the control and the
faults `correct` has to catch fail its limits on the cell's own topology, and a toy-sized
run of the cell goes through the harness on the CPU."""

import copy
import json
import os
import shutil

import jax
import numpy as np
import pytest

from conftest import ROOT

from benchmark import run
from benchmark.layers import _explain
from benchmark.manifest import Manifest

CELL = "as.mc"
CONFIG = "brite-as-10k"
COMPARED = {"rows_missing", "rerun_differs", "hops_differ", "unreachable_differ",
            "goodput_gap", "delay_gap", "max_util_gap"}
READERS = {"spf_ms": "device_trace", "scope_load_us": "device_trace",
           "max_util_mean": "program_counter"}
#: what the cell's traced line carries: the engine runtime's and the device's
#: readings, the loop's (not `step_us`, which sums every outermost `while` of the
#: program over the 4 rounds, and not `scope_rng_us`: this loop draws nothing), and
#: the three of its own
PER_LAYER = {
    "dispatch_ms", "fetch_unpack_ms", "device_idle_share", "peak_hbm_bytes",
    "compiles_in_window", "kpi_mean", "launch_runner_ms", "launch_operands_ms",
    "launch_enqueue_ms", "launch_self_ms", "result_fetch_ms", "result_unpack_ms",
    "xla_compiles_in_window", "loop_own_us", "loop_copies_us", "loop_events_per_step",
    "scope_step_us", "device_outside_loop_ms", "idle_in_launch_ms", "idle_in_result_ms",
} | set(READERS)


def test_the_cells_names_resolve():
    m = Manifest(ROOT)
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "mc-16384x10s", 1)
    cfg, mix = m.config(cell["config"]), m.traffic(cell["traffic"])
    assert cfg["args"] == {"nNodes": 10000, "nFlows": 128}
    assert (cfg["script"], cfg["kind"], cfg["reference"], cfg["reduced"]) == (
        "brite-as.py", "as_flows", "as_flows", [])
    assert cfg["step_iterations"] == {"per_launch": 4}
    assert (mix["driver"], mix["replicas"], mix["horizon_s"]) == ("mc", 16384, 10.0)
    assert (mix["warm_launches"], mix["trace_launches"], mix["trace_horizon_s"]) == (
        2, 2, 10.0)
    assert callable(m.driver(mix["driver"]).window)
    m.reference(cfg["reference"])          # raises where one of the four is missing
    assert set(m.limits(CELL)) == COMPARED
    for metric, source in READERS.items():
        assert callable(m.layer_reader(metric))
        (entry,) = [x for x in m.data["per_layer"] if x["name"] == metric]
        assert entry["workloads"] == [CELL] and entry["moves"] == "sim_s_per_wall_s"
        assert entry["source"] == source
    assert {x["name"] for x in m.metrics_of("per_layer", CELL)} == PER_LAYER
    assert {x["name"] for x in m.metrics_of("end_to_end", CELL)} == {
        "sim_s_per_wall_s", "setup_s"}
    (entry,) = [c for c in m.data["configs"] if c["name"] == cell["config"]]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "BASELINE.json config 5" in entry["source"] and "brite" in entry["source"]


def test_the_configuration_states_what_the_reference_needs():
    m = Manifest(ROOT)
    cfg = m.config(CONFIG)
    ref = m.reference("as_flows")
    topo = ref.topology(cfg)
    assert topo["n"] == 10000 and topo["edges"].shape == (19997, 2)
    assert len(topo["src"]) == 128 and (topo["src"] != topo["dst"]).all()
    assert topo["flow_bps"] == pytest.approx(np.full(128, 400e3))
    assert 10e6 <= topo["rate_bps"].min() and topo["rate_bps"].max() <= 100e6
    route = ref.routes_of(cfg)
    assert route["reached"].all() and 3 <= route["hops"].min()
    assert route["hops"].max() <= 8 < cfg["physics"]["max_hops"]
    assert (cfg["physics"]["fp_rounds"], cfg["physics"]["rate_jitter"]) == (4, 0.3)
    assert cfg["control"]["how"] == "reference" and cfg["control"]["why"]
    assert {"replicas", "topology", "flows", "routing", "replica_axis",
            "relaxation"} <= set(cfg["assumed"])
    assert len(cfg["guarantees"]) == 3


def test_every_limit_carries_its_reason_and_its_two_readings():
    with open(os.path.join(ROOT, "benchmark", "limits", CELL + ".json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == COMPARED
    assert "control" in limits["set_from"].lower() and "seeds" in limits["set_from"]
    for name in COMPARED - {"rows_missing", "rerun_differs"}:
        assert len(limits["why"][name]) > 40, name
    mix = Manifest(ROOT).traffic("mc-16384x10s")
    assert "replica by replica" in mix["reference_replicas_why"]


# --- the three readers -------------------------------------------------------------

#: a table as `tpudes.obs.explain` gives it for this engine's launch, and for another's
AS_TABLE = {
    "loop": {"scopes": {"tpudes.as_flows.step": {"us": 2.5, "ops": 4},
                        "tpudes.as_flows.load": {"us": 13000.0, "ops": 6}},
             "no_event": ["tpudes.as_flows.cond"]},
    "outside_scopes_ms": {"tpudes.as_flows.spf": 27.2, "tpudes.as_flows.delay": 0.9,
                          "_no_scope_": 1.7},
}
BSS_TABLE = {
    "loop": {"scopes": {"tpudes.bss.step": {"us": 8.1, "ops": 30},
                        "tpudes.bss.rng": {"us": 1.6, "ops": 6}},
             "no_event": ["tpudes.bss.cond"]},
    "outside_scopes_ms": {"_no_scope_": 0.02},
}


def _with_table(monkeypatch, got):
    monkeypatch.setattr(_explain, "table", lambda: got)
    m = Manifest(ROOT)
    return m.layer_reader("spf_ms")({}), m.layer_reader("scope_load_us")({})


def test_the_two_trace_readers_on_a_recorded_table(monkeypatch):
    assert _with_table(monkeypatch, AS_TABLE) == (27.2, 13000.0)
    # another engine's loop, a program without the split, no table at all: nothing
    assert _with_table(monkeypatch, BSS_TABLE) == (None, None)
    parent = {"loop": AS_TABLE["loop"]}
    assert _with_table(monkeypatch, parent) == (None, 13000.0)
    assert _with_table(monkeypatch, None) == (None, None)


def test_the_statistic_reader_on_a_recorded_window():
    read = Manifest(ROOT).layer_reader("max_util_mean")
    outs = [dict(max_util=np.full(4, 0.08), goodput_bps=np.ones((4, 2))),
            dict(max_util=np.full(4, 0.10), goodput_bps=np.ones((4, 2)))]
    assert read({"record": {"outs": outs}}) == pytest.approx(0.09)
    bss = {"record": {"outs": [dict(srv_rx=np.ones(4), drops=np.ones(4))]}}
    for other in (bss, {"record": {}}, {"record": {"outs": []}},
                  {"record": {"studies": [1]}}):
        assert read(other) is None


# --- correct fails where it should ---------------------------------------------

REPLICAS, SEED = 64, 2**31 + 40


@pytest.fixture(scope="module")
def cell():
    m = Manifest(ROOT)
    cfg = m.config(CONFIG)
    mix = dict(m.traffic("mc-16384x10s"), trace_launches=0)
    limits = {k: v for k, v in m.limits(CELL).items() if k != "rerun_differs"}
    return cfg, m.reference("as_flows"), mix, limits


def _overloaded(cfg):
    """The deployment at thirty times the offered load: 79 of 128 flows lose on
    some link, so the gate, its compounding and the rounds matter."""
    cfg = copy.deepcopy(cfg)
    cfg["physics"]["flow_kbps"] *= 30
    return cfg


@pytest.mark.parametrize("fault,overload,number,times", [
    # the control: link loads held in bfloat16 between hops (PERF.md section 4)
    (dict(precision="bfloat16"), False, "max_util_gap", 3),
    (dict(metric="delay"), False, "hops_differ", 1),     # routes on delay, not hops
    (dict(rounds=3), True, "goodput_gap", 3),           # a relaxation round short
])
def test_reference_faults_fail_the_cells_limits(cell, fault, overload, number, times):
    """Each reads `times` its limit at least (a count limited to 0: above it)."""
    cfg, ref, mix, limits = cell
    cfg = _overloaded(cfg) if overload else cfg
    faulty = ref.simulate(cfg, 10.0, REPLICAS, SEED, **fault)
    got = ref.compare(cfg, mix, [faulty], REPLICAS, SEED)
    assert not run.judge(got, limits)[1], got
    assert got[number] > times * limits[number], got


@pytest.mark.parametrize("fault", ["fewer_rows", "another_launchs_draws",
                                   "a_flow_halved", "a_hop_lost"])
def test_result_faults_come_out_not_correct(cell, fault):
    cfg, ref, mix, limits = cell
    sound = ref.simulate(cfg, 10.0, REPLICAS, SEED)
    out = {k: np.array(v) for k, v in sound.items() if k != "launch"}
    if fault == "fewer_rows":
        out = {k: (v[: REPLICAS // 2] if v.ndim and v.shape[0] == REPLICAS else v)
               for k, v in out.items()}
    elif fault == "another_launchs_draws":
        out = {k: np.array(v) for k, v in ref.simulate(
            cfg, 10.0, REPLICAS, SEED, launch=1).items() if k != "launch"}
    elif fault == "a_flow_halved":
        out["goodput_bps"][:, 7] /= 2
    else:
        out["hops"][3] -= 1
    got = ref.compare(cfg, mix, [out], REPLICAS, SEED)
    assert not run.judge(got, limits)[1], got


def test_the_sound_side_reads_zero_against_itself(cell):
    cfg, ref, mix, limits = cell
    got = ref.compare(cfg, mix, [ref.simulate(cfg, 10.0, REPLICAS, SEED)],
                      REPLICAS, SEED)
    assert run.judge(got, limits)[1] and max(got.values()) == 0.0


# --- the cell through the harness, toy-sized -------------------------------------

@pytest.fixture(scope="module")
def toy_as_root(tmp_path_factory):
    """`BENCHMARK.json` + `benchmark/` with the cell at 200 nodes, 16 flows and 32
    replicas ADDED as a configuration, a traffic file, a limits file and manifest
    entries (the cell's own limits)."""
    root = tmp_path_factory.mktemp("toy_as")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")))
    cfg["args"] = {"nNodes": 200, "nFlows": 16}
    cfg["topology"].update(n_nodes=200, n_flows=16)
    (root / "benchmark" / "configs" / "toy-as.json").write_text(json.dumps(cfg))
    manifest["configs"].append(dict(
        [c for c in manifest["configs"] if c["name"] == CONFIG][0],
        name="toy-as", file="benchmark/configs/toy-as.json"))
    mix = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "mc-16384x10s.json")))
    mix.update(replicas=32, horizon_s=2.0, reference_replicas=16, warm_launches=1,
               trace_launches=1, trace_horizon_s=1.0)
    (root / "benchmark" / "traffic" / "toy-as-shipped.json").write_text(
        json.dumps(mix))
    shutil.copy(root / "benchmark" / "limits" / f"{CELL}.json",
                root / "benchmark" / "limits" / "toy.as.mc.json")
    manifest["workloads"].append({
        "name": "toy.as.mc", "config": "toy-as", "traffic": "toy-as-shipped",
        "chips": 1, "why": "toy size for the CPU tests"})
    for x in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in x.get("workloads", ()):
            x["workloads"].append("toy.as.mc")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root)


def test_a_traced_toy_run_is_correct_and_reads_the_statistic(toy_as_root):
    result = run.run_cell(Manifest(toy_as_root), "toy.as.mc", 2**31 + 40, 0.5, True,
                          jax.devices(), program_root=ROOT)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["compared"]["hops_differ"]["value"] == 0
    metrics = result["metrics"]
    assert 0.02 < metrics["max_util_mean"]["value"] < 0.2
    assert metrics["kpi_mean"]["value"] == pytest.approx(16 * 0.4, rel=0.05)
    assert metrics["compiles_in_window"]["value"] == 0
    # the device readings need the chip: off it they are left out, not zero
    assert "spf_ms" not in metrics and "scope_load_us" not in metrics
