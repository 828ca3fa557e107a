"""The cell `wifi_ht.mc` (PR 32): its seven names resolve, its two readers read a
recorded window, the faults `correct` has to catch fail its limits, and a toy-sized
run of the cell goes through the harness on the CPU with both new metrics."""

import json
import os
import shutil

import jax
import numpy as np
import pytest

from conftest import ROOT

from benchmark import run
from benchmark.manifest import Manifest

CELL = "wifi_ht.mc"


def test_the_cells_seven_names_resolve():
    m = Manifest(ROOT)
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "wifi-bss-ht-64sta", "mc-512x2s-ht", 1)
    cfg, mix = m.config(cell["config"]), m.traffic(cell["traffic"])
    assert cfg["args"] == {"nStas": 64, "standard": "80211n",
                           "dataMode": "HtMcs7", "interval": 0.01}
    assert (cfg["kind"], cfg["reference"], cfg["reduced"]) == ("bss", "bss_ht", [])
    assert (mix["driver"], mix["replicas"], mix["horizon_s"]) == ("mc", 512, 2.0)
    assert callable(m.driver(mix["driver"]).window)
    reference = m.reference(cfg["reference"])
    for fn in ("simulate", "compare", "kpi"):
        assert callable(getattr(reference, fn))
    assert set(m.limits(CELL)) == {"rows_missing", "rerun_differs", "srv_rx_gap",
                                   "sta_echo_gap", "tx_data_gap", "drops_gap"}
    for metric in ("ampdu_mpdus_per_ppdu", "event_steps_per_launch"):
        assert callable(m.layer_reader(metric))
        (entry,) = [x for x in m.data["per_layer"] if x["name"] == metric]
        assert entry["workloads"] == [CELL] and entry["moves"] == "sim_s_per_wall_s"
    reported = {x["name"] for x in m.metrics_of("per_layer", CELL)}
    legacy = {x["name"] for x in m.metrics_of("per_layer", "wifi.mc")}
    assert reported == legacy | {"ampdu_mpdus_per_ppdu", "event_steps_per_launch"}
    assert {x["name"] for x in m.metrics_of("end_to_end", CELL)} == {
        "sim_s_per_wall_s", "setup_s"}


def test_the_configuration_states_what_the_reference_needs():
    cfg = Manifest(ROOT).config("wifi-bss-ht-64sta")
    ph = cfg["physics"]
    assert ph["data_rate_bps"] == ph["data_bits_per_symbol"] / ph["symbol_us"] * 1e6
    assert ph["subframe_bytes"] == 580 and ph["subframe_bytes"] % 4 == 0
    assert (ph["max_ampdu_bytes"], ph["block_ack_window"]) == (65535, 64)
    assert len(cfg["topology"]["positions"]) == 65
    assert {"block_ack_agreement", "access_category", "warm_up", "topology"} <= set(
        cfg["assumed"])
    assert len(cfg["source"]) <= 200 and "wifi-aggregation.cc" in cfg["source"]


RECORDED = [
    dict(tx_data=np.array([3, 5]), tx_mpdus=np.array([9, 15]), steps=3400),
    dict(tx_data=np.array([4, 4]), tx_mpdus=np.array([16, 8]), steps=3500),
    dict(tx_data=np.array([2, 6]), tx_mpdus=np.array([2, 22]), steps=3480),
]


def test_the_two_readers_on_a_recorded_window():
    m = Manifest(ROOT)
    ctx = {"record": {"outs": RECORDED}}
    assert m.layer_reader("ampdu_mpdus_per_ppdu")(ctx) == 72 / 24
    assert m.layer_reader("event_steps_per_launch")(ctx) == 3480.0
    # the parent's program returns no tx_mpdus: nothing to read, no raise
    parent = {"record": {"outs": [
        {k: v for k, v in o.items() if k != "tx_mpdus"} for o in RECORDED]}}
    assert m.layer_reader("ampdu_mpdus_per_ppdu")(parent) is None
    assert m.layer_reader("event_steps_per_launch")(parent) == 3480.0
    for empty in ({"record": {}}, {"record": {"outs": []}},
                  {"record": {"studies": [1]}}):
        assert m.layer_reader("ampdu_mpdus_per_ppdu")(empty) is None
        assert m.layer_reader("event_steps_per_launch")(empty) is None


# --- correct fails where it should ---------------------------------------------

REPLICAS = 24


@pytest.fixture(scope="module")
def ht():
    m = Manifest(ROOT)
    cfg = m.config("wifi-bss-ht-64sta")
    ref = m.reference("bss_ht")
    mix = {"horizon_s": 2.0, "reference_replicas": REPLICAS}
    limits = {k: v for k, v in m.limits(CELL).items() if k != "rerun_differs"}
    return cfg, ref, mix, limits, ref.simulate(cfg, 2.0, REPLICAS, 1)


@pytest.mark.parametrize("fault,number,times", [
    (dict(retry_limit=0), "drops_gap", 3),           # a dropped retry limit
    (dict(max_mpdus=1), "tx_data_gap", 3),           # a single-MPDU reference
    # a bfloat16 power sum: 3.7 times the limit at the cell's size (PERF.md
    # section 4); 24 replicas a side are held to twice
    (dict(precision="matmul_bfloat16"), "sta_echo_gap", 2),
])
def test_reference_faults_fail_the_new_limits(ht, fault, number, times):
    cfg, ref, mix, limits, sound = ht
    faulty = ref.simulate(cfg, 2.0, REPLICAS, 3, **fault)
    got = ref.compare(cfg, mix, [faulty], REPLICAS, seed=2, ref=sound)
    assert not run.judge(got, limits)[1], got
    assert got[number] > times * limits[number], got


@pytest.mark.parametrize("fault", ["half_left_out", "shards_left_out",
                                   "answer_altered", "fewer_rows"])
def test_result_faults_come_out_not_correct(ht, fault):
    cfg, ref, mix, limits, sound = ht
    out = {k: np.array(v) for k, v in sound.items()}
    if fault == "half_left_out":
        for k in ("srv_rx", "cli_rx", "tx_data"):
            out[k][REPLICAS // 2:] = 0
    elif fault == "shards_left_out":
        for k in ("srv_rx", "cli_rx", "tx_data"):
            out[k][REPLICAS // 4:] = 0
    elif fault == "answer_altered":
        out["cli_rx"][:, 4] //= 2       # the best-served station loses half
    else:
        out = {k: (v[: REPLICAS // 2] if np.ndim(v) else v) for k, v in out.items()}
    assert not run.judge(
        ref.compare(cfg, mix, [out], REPLICAS, seed=2, ref=sound), limits)[1]


def test_the_sound_side_reads_zero_against_itself(ht):
    cfg, ref, mix, limits, sound = ht
    got = ref.compare(cfg, mix, [sound], REPLICAS, seed=2, ref=sound)
    assert run.judge(got, limits)[1] and max(got.values()) == 0.0


# --- the cell through the harness, toy-sized -------------------------------------

@pytest.fixture(scope="module")
def toy_ht_root(tmp_path_factory):
    """`BENCHMARK.json` + `benchmark/` with the cell at 8 replicas ADDED as a traffic
    file, a limits file and a manifest entry; at 8 reference replicas the random
    gaps are far wider than the cell's limits, so the toy's are ten times those."""
    root = tmp_path_factory.mktemp("toy_ht")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mix = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "mc-512x2s-ht.json")))
    mix.update(replicas=8, reference_replicas=8, warm_launches=1, trace_launches=1)
    (root / "benchmark" / "traffic" / "toy-ht.json").write_text(json.dumps(mix))
    limits = json.load(open(os.path.join(
        ROOT, "benchmark", "limits", CELL + ".json")))
    limits["limits"] = {k: 10 * v for k, v in limits["limits"].items()}
    (root / "benchmark" / "limits" / "toy.ht.json").write_text(json.dumps(limits))
    manifest["workloads"].append({
        "name": "toy.ht", "config": "wifi-bss-ht-64sta", "traffic": "toy-ht",
        "chips": 1, "why": "toy size for the CPU tests"})
    for x in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in x.get("workloads", ()):
            x["workloads"].append("toy.ht")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root)


def test_a_traced_toy_run_reports_the_two_new_metrics(toy_ht_root):
    result = run.run_cell(Manifest(toy_ht_root), "toy.ht", 2**31 + 32, 0.5, True,
                          jax.devices(), program_root=ROOT)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    metrics = result["metrics"]
    assert 1.0 < metrics["ampdu_mpdus_per_ppdu"]["value"] <= 64.0
    assert metrics["event_steps_per_launch"]["value"] > 1000
    assert metrics["compiles_in_window"]["value"] == 0
    assert 3000 < metrics["kpi_mean"]["value"] < 5000
