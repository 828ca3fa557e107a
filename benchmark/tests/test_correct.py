"""`correct` has to fail where it should: the configuration's control, and the
timed path broken underneath a run (the harness's look for a chip skipped)."""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from conftest import ROOT

from benchmark import run
from benchmark.manifest import Manifest


def _cfg(name):
    return json.load(open(os.path.join(ROOT, "benchmark", "configs", name + ".json")))


def _limits(cell):
    return Manifest(ROOT).limits(cell)


def _correct(numbers, limits):
    return run.judge(numbers, limits)[1]


@pytest.fixture(scope="module")
def bss():
    m = Manifest(ROOT)
    cfg = _cfg("wifi-bss-64sta")
    ref = m.reference("bss")
    mix = {"horizon_s": 2.0, "reference_replicas": 8}
    return cfg, ref, mix, ref.simulate(cfg, 2.0, 8, 1)


def test_bss_reference_agrees_with_itself_and_the_control_fails(bss):
    cfg, ref, mix, sound = bss
    limits = {k: v for k, v in _limits("wifi.mc").items() if k != "rerun_differs"}
    ok = ref.compare(cfg, mix, [sound], 8, seed=2)
    assert _correct(ok, limits), ok
    # the tempting step: the received-power sum as a default-precision matmul
    control = ref.simulate(cfg, 2.0, 8, 3, precision="matmul_bfloat16")
    bad = ref.compare(cfg, mix, [control], 8, seed=2)
    assert not _correct(bad, limits)
    assert bad["srv_rx_gap"] > 3 * max(limits["srv_rx_gap"], ok["srv_rx_gap"])
    # a guarantee broken: no retransmission after a collision
    no_retry = ref.simulate(cfg, 2.0, 8, 3, retry_limit=0)
    assert not _correct(ref.compare(cfg, mix, [no_retry], 8, seed=2), limits)


def test_bss_whole_chain_bfloat16_reads_like_float64_here(bss):
    # every station sits above the 54 Mbps cliff and a consistent rounding
    # cancels in total - signal: why this is not the control (PERF.md)
    cfg, ref, mix, sound = bss
    lowered = ref.simulate(cfg, 2.0, 2, 3, precision="bfloat16")
    assert ref.compare(cfg, mix, [lowered], 2, seed=2)["srv_rx_gap"] == 0.0


@pytest.mark.parametrize("fault", ["half_left_out", "shards_left_out",
                                   "answer_altered", "fewer_rows"])
def test_bss_faults_come_out_not_correct(bss, fault):
    cfg, ref, mix, sound = bss
    limits = {k: v for k, v in _limits("wifi.mc").items() if k != "rerun_differs"}
    out = {k: np.array(v) for k, v in sound.items()}
    if fault == "half_left_out":
        for k in ("srv_rx", "cli_rx", "tx_data"):
            out[k][4:] = 0
    elif fault == "shards_left_out":
        for k in ("srv_rx", "cli_rx", "tx_data"):
            out[k][2:] = 0
    elif fault == "answer_altered":
        out["cli_rx"][:, 7] -= 1
    else:
        out = {k: (v[:4] if np.ndim(v) else v) for k, v in out.items()}
    assert not _correct(ref.compare(cfg, mix, [out], 8, seed=2), limits)


@pytest.fixture(scope="module")
def lte(toy_root):
    """The program itself on the CPU at a size a test can hold: the real
    topology, 16 replicas x 1.5 sim-s (interpret-mode kernel)."""
    m = Manifest(toy_root)
    cfg = m.config("lena-hex7x30")
    mix = {"driver": "mc", "replicas": 16, "horizon_s": 1.5, "warm_launches": 0,
           "reference_replicas": 8}
    driver, ref = m.driver("mc"), m.reference("lte_sm")
    cell = run.Cell(root=ROOT, name="t", chips=1, cfg=cfg, traffic=mix,
                    reference=ref, seed=5, split={})
    state = driver.setup(cell)
    record = driver.window(state, cell, 0.0)        # one launch
    return cfg, mix, cell, driver, ref, state, record


#: at 16 x 1.5 sim-s a UE's rate is far noisier than at a cell's own size; the
#: test holds the run to three times the cell's limit, the control still fails
TEST_SIZE_SLACK = 3.0


def test_lte_program_agrees_and_its_bf16_path_fails(lte):
    cfg, mix, cell, driver, ref, state, record = lte
    limits = {k: v * TEST_SIZE_SLACK for k, v in _limits("lte.mc").items()}
    sound = driver.check(state, cell, record)
    assert _correct(sound, limits), sound
    lowered = dict(state, prog=dataclasses.replace(state["prog"], precision="bf16"))
    bad = driver.check(lowered, cell, driver.window(lowered, cell, 0.0))
    assert not _correct(bad, limits), bad
    assert bad["ue_rate_gap"] > 3 * sound["ue_rate_gap"]


@pytest.mark.parametrize("fault", ["half_left_out", "shards_left_out",
                                   "answer_altered", "fewer_rows"])
def test_lte_faults_under_a_run_come_out_not_correct(lte, toy_root, fault, monkeypatch):
    """Drive the rest of a run (`run_cell`, no look for a chip) with the timed
    path broken underneath: `run_lifted` answers wrongly."""
    from tpudes.parallel import lift

    real = lift.run_lifted

    def broken(kind, prog, replicas, key=None, mesh=None, **kw):
        out = {k: np.array(v) for k, v in real(kind, prog, replicas, key, mesh, **kw).items()}
        r = out["rx_bits"].shape[0]
        if fault == "half_left_out":
            out["rx_bits"][r // 2:] = 0
        elif fault == "shards_left_out":
            out["rx_bits"][r // 4:] = 0
        elif fault == "answer_altered":
            best = int(np.argmax(out["rx_bits"].sum(axis=0)))   # one UE's bits halved
            out["rx_bits"][:, best] //= 2
        else:
            out = {k: (v[: r // 2] if np.ndim(v) and v.shape[0] == r else v)
                   for k, v in out.items()}
        return out

    sound = run.run_cell(Manifest(toy_root), "toy.lte", 9, 0.3, False,
                         jax.devices(), program_root=ROOT)["compared"]
    monkeypatch.setattr(lift, "run_lifted", broken)
    result = run.run_cell(Manifest(toy_root), "toy.lte", 9, 0.3, False,
                          jax.devices(), program_root=ROOT)
    assert result["correct"] is False
    got = result["compared"]
    # not the toy horizon's noise: rows are missing, or a UE's rate is far off
    assert got["rows_missing"]["value"] > 0 or (
        got["ue_rate_gap"]["value"] > 3 * sound["ue_rate_gap"]["value"]
        and got["ue_rate_gap"]["value"] > 3 * got["ue_rate_gap"]["limit"]
    )
