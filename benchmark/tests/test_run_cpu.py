"""The harness end to end at toy sizes on the CPU: both traffic drivers, the
result line's keys, no device metric without a device, and dummies found by
name.  `run_cell` is `main` without the look for a chip."""

import json

import jax
import pytest

from conftest import ROOT

from benchmark import run
from benchmark.manifest import Manifest

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
DEVICE_ONLY = {"step_us", "device_idle_share",
               "peak_hbm_bytes", "launch_device_ms", "run_host_ms",
               "script_device_idle_share"}


def cell(toy_root, name, traced, seed=2**31 + 5):
    return run.run_cell(Manifest(toy_root), name, seed, 0.5, traced,
                        jax.devices(), program_root=ROOT)


@pytest.mark.parametrize("name,metric", [
    ("toy.lte", "sim_s_per_wall_s"), ("toy.bss", "sim_s_per_wall_s"),
    ("toy.script", "study_p50_s"),
])
def test_driver_runs_end_to_end_and_prints_the_contracts_keys(toy_root, name, metric):
    result = cell(toy_root, name, traced=False)
    assert list(result) == CONTRACT_KEYS + ["compared"]      # compared comes last
    json.dumps(result)
    assert set(result["metrics"]) == {metric, "setup_s"}
    assert result["metrics"][metric]["value"] > 0
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert all(set(c) == {"value", "limit"} for c in result["compared"].values())
    if name != "toy.lte":    # the toy LTE horizon is too short for the cell's limit
        assert result["correct"] is True


@pytest.mark.parametrize("name", ["toy.bss", "toy.script", "toy.tcp", "toy.as"])
def test_traced_run_reports_no_device_metric_without_a_device(toy_root, name):
    result = cell(toy_root, name, traced=True)
    assert not DEVICE_ONLY & set(result["metrics"])
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert list(result)[-1] == "compared" and result["correct"] is True
    if name == "toy.script":
        assert {"graph_build_ms", "lower_ms", "study_p95_ms"} <= set(result["metrics"])
        return
    # the dummy config, reference, traffic mix and reader were found by name
    assert result["metrics"]["toy_launches"]["value"] == result["attempted"]
    assert {"dispatch_ms", "fetch_unpack_ms", "kpi_mean",
            "compiles_in_window"} <= set(result["metrics"])
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert result["metrics"]["kpi_mean"]["value"] > 0


def test_main_refuses_without_the_chip(capsys):
    with pytest.raises(SystemExit) as refusal:
        run.main(["--workload", "lte.mc", "--seed", "1", "--seconds", "1"])
    assert refusal.value.code == 2
    out = capsys.readouterr()
    assert out.out == "" and "refusing to run" in out.err


def test_a_large_seed_makes_keys(toy_root):
    mc = Manifest(toy_root).driver("mc")
    a, b = mc._keys(2**31 + 12345), mc._keys(12345)
    assert a.shape == (mc.MAX_LAUNCHES, 2) and (a[0] != b[0]).any()
    assert (mc._keys(2**31 + 12345) == a).all()
