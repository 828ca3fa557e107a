"""The cell `tcp.mc` (PR 35): its names resolve, its limits carry their reasons, its
two readers read a recorded window (and nothing from another engine's), the control and
the faults `correct` has to catch fail its limits at the cell's own horizon, and a
toy-sized run of the cell goes through the harness on the CPU with both new metrics."""

import json
import os
import shutil

import jax
import numpy as np
import pytest

from conftest import ROOT

from benchmark import run
from benchmark.manifest import Manifest

CELL = "tcp.mc"
COMPARED = {"rows_missing", "rerun_differs", "agg_goodput_gap", "flow_goodput_gap",
            "drops_gap", "queue_gap", "jain_gap"}
READERS = ("bottleneck_utilisation", "drops_per_flow_s")


def test_the_cells_names_resolve():
    m = Manifest(ROOT)
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tcp-dumbbell-8flow-cubic", "mc-256x20s", 1)
    cfg, mix = m.config(cell["config"]), m.traffic(cell["traffic"])
    assert cfg["args"] == {"nFlows": 8, "variant": "TcpCubic"}
    assert (cfg["script"], cfg["kind"], cfg["reference"], cfg["reduced"]) == (
        "tcp-variants.py", "dumbbell", "dumbbell", [])
    assert (mix["driver"], mix["replicas"], mix["horizon_s"]) == ("mc", 256, 20.0)
    assert (mix["warm_launches"], mix["trace_launches"], mix["trace_horizon_s"]) == (
        2, 2, 2.0)
    assert callable(m.driver(mix["driver"]).window)
    m.reference(cfg["reference"])          # raises where one of the four is missing
    assert set(m.limits(CELL)) == COMPARED
    for metric in READERS:
        assert callable(m.layer_reader(metric))
        (entry,) = [x for x in m.data["per_layer"] if x["name"] == metric]
        assert entry["workloads"] == [CELL] and entry["moves"] == "sim_s_per_wall_s"
        assert entry["source"] == "program_counter"
    reported = {x["name"] for x in m.metrics_of("per_layer", CELL)}
    legacy = {x["name"] for x in m.metrics_of("per_layer", "wifi.mc")}
    assert reported == legacy | set(READERS)
    assert {x["name"] for x in m.metrics_of("end_to_end", CELL)} == {
        "sim_s_per_wall_s", "setup_s"}
    (entry,) = [c for c in m.data["configs"] if c["name"] == cell["config"]]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200


def test_the_configuration_states_what_the_reference_needs():
    m = Manifest(ROOT)
    cfg = m.config("tcp-dumbbell-8flow-cubic")
    geometry = m.reference("dumbbell").geometry(cfg, 20.0)
    assert geometry["slot_s"] == pytest.approx(832e-6)
    assert 1.0 / geometry["slot_s"] == pytest.approx(
        cfg["horizon_field"]["per_second"]) == pytest.approx(
        cfg["step_iterations"]["per_sim_second"])
    assert (geometry["n_flows"], geometry["queue"], geometry["burst"],
            geometry["ack_lag"], geometry["n_slots"]) == (8, 100, 10, 29, 24039)
    assert geometry["base_rtt_s"] == pytest.approx(0.024832)
    assert (cfg["physics"]["cubic_c"], cfg["physics"]["cubic_beta"]) == (0.4, 0.7)
    assert cfg["control"]["how"] == "reference" and cfg["control"]["why"]
    assert {"size", "upstream_defaults", "flows", "links", "queue", "segment",
            "horizon", "tcp"} <= set(cfg["assumed"])
    assert len(cfg["guarantees"]) == 3 and "tcp-variants-comparison.cc" in cfg["source"]


def test_every_limit_carries_its_reason_and_its_two_readings():
    with open(os.path.join(ROOT, "benchmark", "limits", CELL + ".json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == COMPARED
    assert "control" in limits["set_from"].lower() and "seeds" in limits["set_from"]
    for name in COMPARED - {"rows_missing", "rerun_differs"}:
        assert len(limits["why"][name]) > 40, name
    mix = Manifest(ROOT).traffic("mc-256x20s")
    assert "third" in mix["reference_replicas_why"]


def _outs(sim_s=20.0, replicas=4, flows=8, launches=3):
    rng = np.random.default_rng(0)
    outs = []
    for _ in range(launches):
        delivered = rng.integers(2900, 3100, (replicas, flows))
        outs.append(dict(
            delivered=delivered, drops=rng.integers(15, 30, (replicas, flows)),
            goodput_mbps=(delivered * 8000.0 / sim_s / 1e6).astype(np.float32)))
    return outs


def test_the_two_readers_on_a_recorded_window():
    m = Manifest(ROOT)
    outs = _outs()
    ctx = {"record": {"outs": outs}}
    delivered = sum(o["delivered"].sum() for o in outs)
    drops = sum(o["drops"].sum() for o in outs)
    slots = (20.0 - 0.1) / 832e-6
    assert m.layer_reader("bottleneck_utilisation")(ctx) == pytest.approx(
        delivered / (12 * slots), rel=1e-5)
    assert m.layer_reader("drops_per_flow_s")(ctx) == pytest.approx(
        drops / (12 * 8 * 20.0), rel=1e-5)
    # another engine's results, a script cell's record, an empty window: nothing
    # to read, and no raise
    bss = {"record": {"outs": [dict(srv_rx=np.ones(4), drops=np.ones(4))]}}
    for other in (bss, {"record": {}}, {"record": {"outs": []}},
                  {"record": {"studies": [1]}}):
        for reader in READERS:
            assert m.layer_reader(reader)(other) is None


# --- correct fails where it should ---------------------------------------------

REPLICAS = 48


@pytest.fixture(scope="module")
def tcp():
    m = Manifest(ROOT)
    cfg = m.config("tcp-dumbbell-8flow-cubic")
    ref = m.reference("dumbbell")
    mix = {"horizon_s": 20.0, "reference_replicas": REPLICAS}
    limits = {k: v for k, v in m.limits(CELL).items() if k != "rerun_differs"}
    return cfg, ref, mix, limits, ref.simulate(cfg, 20.0, REPLICAS, 1)


@pytest.mark.parametrize("fault,number,times", [
    # the control: 5 times the limits at the cell's size (PERF.md section 4); 48
    # replicas a side are held to three
    (dict(precision="bfloat16"), "queue_gap", 3),
    (dict(precision="bfloat16"), "drops_gap", 3),
    (dict(variant="newreno"), "queue_gap", 3),          # NewReno in CUBIC's place
    (dict(variant="newreno"), "drops_gap", 2),
    (dict(cut_per_loss=True), "queue_gap", 3),          # a cut at every loss notice
    (dict(service="fifo"), "drops_gap", 3),             # upstream's order of service
    (dict(service="fifo"), "queue_gap", 3),
])
def test_reference_faults_fail_the_cells_limits(tcp, fault, number, times):
    cfg, ref, mix, limits, sound = tcp
    faulty = ref.simulate(cfg, 20.0, REPLICAS, 3, **fault)
    got = ref.compare(cfg, mix, [faulty], REPLICAS, seed=2, ref=sound)
    assert not run.judge(got, limits)[1], got
    assert got[number] > times * limits[number], got


def test_no_fast_convergence_is_not_told_at_the_cells_size(tcp):
    """What the cell cannot see, stated: with eight flows that start within 70 ms
    of each other nobody has to yield to a late joiner, and CUBIC without fast
    convergence reads under every limit (drops move 1.6%).  Where flows join
    seconds apart it reads 3.5 times the tolerance:
    `tests/test_dumbbell_reference.py` holds it there."""
    cfg, ref, mix, limits, sound = tcp
    faulty = ref.simulate(cfg, 20.0, REPLICAS, 3, fast_convergence=False)
    got = ref.compare(cfg, mix, [faulty], REPLICAS, seed=2, ref=sound)
    # flow_goodput_gap is left out: with 48 replicas on BOTH sides it is noise
    for number in ("agg_goodput_gap", "drops_gap", "queue_gap", "jain_gap"):
        assert got[number] <= limits[number], got


@pytest.mark.parametrize("fault", ["half_left_out", "one_flow_starved",
                                   "fewer_rows", "idle_link"])
def test_result_faults_come_out_not_correct(tcp, fault):
    cfg, ref, mix, limits, sound = tcp
    out = {k: np.array(v) for k, v in sound.items()}
    if fault == "half_left_out":
        for k in ("goodput_mbps", "delivered", "drops", "mean_queue"):
            out[k][REPLICAS // 2:] = 0
    elif fault == "one_flow_starved":
        out["goodput_mbps"][:, 3] /= 2
    elif fault == "idle_link":
        out["goodput_mbps"] *= 0.99
    else:
        out = {k: v[: REPLICAS // 2] for k, v in out.items()}
    assert not run.judge(
        ref.compare(cfg, mix, [out], REPLICAS, seed=2, ref=sound), limits)[1]


def test_the_sound_side_reads_zero_against_itself(tcp):
    cfg, ref, mix, limits, sound = tcp
    got = ref.compare(cfg, mix, [sound], REPLICAS, seed=2, ref=sound)
    assert run.judge(got, limits)[1] and max(got.values()) == 0.0


# --- the cell through the harness, toy-sized -------------------------------------

@pytest.fixture(scope="module")
def toy_tcp_root(tmp_path_factory):
    """`BENCHMARK.json` + `benchmark/` with the cell at 8 replicas x 3 sim-s ADDED as a
    traffic file, a limits file and a manifest entry; at 8 reference replicas the
    random gaps are far wider than the cell's limits, so the toy's are ten times
    those."""
    root = tmp_path_factory.mktemp("toy_tcp")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mix = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "mc-256x20s.json")))
    mix.update(replicas=8, horizon_s=3.0, reference_replicas=8, warm_launches=1,
               trace_launches=1, trace_horizon_s=1.0)
    (root / "benchmark" / "traffic" / "toy-tcp-shipped.json").write_text(
        json.dumps(mix))
    limits = json.load(open(os.path.join(
        ROOT, "benchmark", "limits", CELL + ".json")))
    limits["limits"] = {k: 10 * v for k, v in limits["limits"].items()}
    (root / "benchmark" / "limits" / "toy.tcp.mc.json").write_text(json.dumps(limits))
    manifest["workloads"].append({
        "name": "toy.tcp.mc", "config": "tcp-dumbbell-8flow-cubic",
        "traffic": "toy-tcp-shipped", "chips": 1, "why": "toy size for the CPU tests"})
    for x in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in x.get("workloads", ()):
            x["workloads"].append("toy.tcp.mc")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root)


def test_a_traced_toy_run_reports_the_two_new_metrics(toy_tcp_root):
    result = run.run_cell(Manifest(toy_tcp_root), "toy.tcp.mc", 2**31 + 35, 0.5, True,
                          jax.devices(), program_root=ROOT)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    metrics = result["metrics"]
    assert 0.98 < metrics["bottleneck_utilisation"]["value"] <= 1.0
    assert 1.0 < metrics["drops_per_flow_s"]["value"] < 5.0
    assert metrics["compiles_in_window"]["value"] == 0
    assert 9.0 < metrics["kpi_mean"]["value"] < 9.7
