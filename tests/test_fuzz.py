"""ISSUE 8 gates: the differential fuzzing subsystem.

- **Seed determinism**: a corpus entry is ONE integer — the same seed
  always derives the same in-envelope config, different seeds differ.
- **Corpus replay**: every ``tests/fuzz_corpus/`` entry (bucketing
  pads, chunk boundaries, sweep demux — 3 per engine) replays clean,
  deterministically, through the real oracle-pair machinery.
- **Planted bug end-to-end**: with ``TPUDES_FUZZ_PLANTED_BUG=1`` the
  scalar-vs-chunked oracle detects the deliberate dumbbell divergence,
  the shrinker reduces it to <= 2 replicas and <= 32 slots, the
  artifact round-trips, and ``replay`` reproduces the diff
  bit-identically.
- **Telemetry**: campaign counters pass the ``--fuzz`` schema gate.
"""

import json
from pathlib import Path

import pytest

CORPUS_DIR = Path(__file__).parent / "fuzz_corpus"


# --- seeded generation ----------------------------------------------------


def test_seed_derives_identical_configs():
    from tpudes.fuzz import scenario_config
    from tpudes.fuzz.engines import ENGINE_FUZZERS

    for eng in ENGINE_FUZZERS:
        a = scenario_config(eng, 11)
        b = scenario_config(eng, 11)
        c = scenario_config(eng, 12)
        assert a == b, eng
        assert a != c, eng


def test_draws_stay_in_envelope():
    from tpudes.fuzz import scenario_config
    from tpudes.fuzz.engines import ENGINE_FUZZERS

    for eng, fz in ENGINE_FUZZERS.items():
        for seed in range(6):
            cfg = scenario_config(eng, seed)
            assert fz.envelope.contains(cfg) == [], (eng, seed, cfg)


def test_envelope_contains_honors_shrink_floors():
    from tpudes.fuzz import ScenarioGen
    from tpudes.parallel.tcp_dumbbell import FUZZ_ENVELOPE

    cfg = FUZZ_ENVELOPE.draw(ScenarioGen(0))
    shrunk = dict(cfg, replicas=1, sim_ms=8)  # below envelope minima
    assert FUZZ_ENVELOPE.contains(shrunk) == []
    assert FUZZ_ENVELOPE.contains(dict(cfg, replicas=99)) == ["replicas"]
    assert FUZZ_ENVELOPE.contains(dict(cfg, variant="TcpBogus")) == [
        "variant"
    ]


def test_shrink_moves_are_strictly_smaller():
    from tpudes.fuzz import scenario_config
    from tpudes.fuzz.engines import ENGINE_FUZZERS

    for eng, fz in ENGINE_FUZZERS.items():
        cfg = scenario_config(eng, 3)
        axes = fz.envelope.axes
        for label, cand in fz.shrink_moves(cfg):
            changed = {k for k in cfg if cand[k] != cfg[k]}
            assert len(changed) == 1, (eng, label, changed)
            (k,) = changed
            if axes[k][0] == "int":
                assert cand[k] < cfg[k], (eng, label)
            else:
                # choice axes jump straight to the move's simplest
                # value (which may be numerically larger, e.g. the BSS
                # slowest-traffic interval): once applied, the same
                # move must no longer be offered
                assert label not in dict(fz.shrink_moves(cand)), (
                    eng, label,
                )


# --- first_diff ------------------------------------------------------------


def test_first_diff_reports_field_and_index():
    import numpy as np

    from tpudes.fuzz.engines import first_diff

    a = {"x": np.array([[1, 2], [3, 4]]), "y": np.array([1.0])}
    b = {"x": np.array([[1, 2], [3, 5]]), "y": np.array([1.0])}
    d = first_diff(a, b)
    assert d == {"field": "x", "index": [1, 1], "lhs": 4, "rhs": 5}
    assert first_diff(a, a) is None
    # tolerance mode passes near-equal floats, exact mode does not
    c = {"x": a["x"], "y": np.array([1.0 + 1e-7])}
    assert first_diff(a, c, rtol=1e-5) is None
    assert first_diff(a, c)["field"] == "y"
    # NaNs in the same position agree in both modes
    n1 = {"z": np.array([np.nan, 1.0])}
    n2 = {"z": np.array([np.nan, 1.0])}
    assert first_diff(n1, n2) is None and first_diff(n1, n2, rtol=1e-5) is None


def test_first_diff_catches_missing_fields_and_json_roundtrips():
    import json

    import numpy as np

    from tpudes.fuzz.artifact import _jsonable
    from tpudes.fuzz.engines import first_diff

    # a mode that drops (or invents) a result field is a divergence
    a = {"x": np.array([1]), "y": np.array([2])}
    b = {"x": np.array([1])}
    d = first_diff(a, b)
    assert d == {"field": "y", "index": [], "lhs": True, "rhs": False}
    # every branch's index survives the artifact JSON round-trip
    # unchanged (replay checks fresh == recorded)
    shape = first_diff({"x": np.zeros((2, 2))}, {"x": np.zeros((2, 3))})
    for diff in (d, shape):
        assert diff == json.loads(json.dumps(_jsonable(diff)))


def test_replay_rejects_unknown_engine():
    import pytest as _pytest

    from tpudes.fuzz import replay

    with _pytest.raises(ValueError, match="unknown engine"):
        replay({"engine": "bsss", "seed": 1})


# --- corpus replay (the tier-1 regression gate) ---------------------------


def _corpus_entries():
    return sorted(CORPUS_DIR.glob("*.json"))


def test_corpus_has_three_seeds_per_engine():
    by_engine: dict[str, int] = {}
    for path in _corpus_entries():
        doc = json.loads(path.read_text())
        assert doc["kind"] == "tpudes-fuzz-corpus", path
        by_engine[doc["engine"]] = by_engine.get(doc["engine"], 0) + 1
    # ISSUE-10 added 2 mobile stride-boundary seeds each for the two
    # radio engines (mobility + geom_stride draws); ISSUE-14 added 3
    # burst-boundary seeds (bss/lte_sm/dumbbell traffic draws)
    assert by_engine == {
        "bss": 6, "lte_sm": 6, "dumbbell": 4, "as_flows": 3, "wired": 3,
    }


@pytest.mark.parametrize(
    "path", _corpus_entries(), ids=lambda p: p.stem
)
def test_corpus_entry_replays_clean(path):
    from tpudes.fuzz import replay

    doc = json.loads(path.read_text())
    divs = replay(doc)
    assert divs == [], [d.render() for d in divs]


# --- planted bug: detect -> shrink -> artifact -> replay ------------------


def test_lte_pallas_pair_builds_both_lowerings(monkeypatch, sm_lowerings_built):
    """ISSUE 31: unset, ``TPUDES_PALLAS`` sends a batched draw to the
    XLA step, so the pair forces its kernel side: it compares Mosaic
    (interpret mode here) with XLA at every drawn replica count."""
    from tpudes.fuzz import scenario_config
    from tpudes.fuzz.engines import ENGINE_FUZZERS
    from tpudes.parallel.lte_sm import SM_KERNEL_MAX_LANES
    from tpudes.parallel.runtime import RUNTIME

    monkeypatch.delenv("TPUDES_PALLAS", raising=False)
    fz = ENGINE_FUZZERS["lte_sm"]
    cfg = dict(
        scenario_config("lte_sm", 3), replicas=SM_KERNEL_MAX_LANES + 1
    )
    prog = fz.build(cfg)
    RUNTIME.clear("lte_sm")
    canonical = fz.run_scalar(prog, cfg)
    assert sm_lowerings_built(prog) == {False}    # above N: XLA
    (pair,) = [f for n, f in fz.extra_pairs() if n == "pallas_vs_xla"]
    assert pair(prog, cfg, canonical) is None
    assert sm_lowerings_built(prog) == {True, False}


@pytest.mark.slow  # ISSUE-21 tier-1 budget: CI's fuzz step runs the planted-bug drill
def test_planted_bug_detected_shrunk_and_replayed(monkeypatch, tmp_path):
    from tpudes.fuzz import replay, run_scenario, shrink_divergence
    from tpudes.fuzz.artifact import (
        artifact_doc,
        load_artifact,
        write_artifact,
    )
    from tpudes.fuzz.engines import ENGINE_FUZZERS

    monkeypatch.setenv("TPUDES_FUZZ_PLANTED_BUG", "1")
    fz = ENGINE_FUZZERS["dumbbell"]
    # a small in-envelope config so the shrink loop stays cheap; the
    # planted divergence is horizon/replica-independent, so shrinking
    # must reach the floors
    cfg = dict(
        n_flows=2, variant="TcpNewReno", variant_mix="homogeneous",
        bottleneck_mbps=10, bottleneck_delay_ms=5, queue_pkts=25,
        seg_bytes=1000, sim_ms=900, replicas=3, chunk_divisor=2,
        key_seed=7, traffic="off", tr_burst=0.1, tr_phase=0.0,
    )
    assert fz.envelope.contains(cfg) == []
    divs = run_scenario(fz, cfg, pairs=["chunked_vs_single"], record=False)
    assert len(divs) == 1, "planted divergence must be detected"
    assert divs[0].pair == "chunked_vs_single"
    assert divs[0].diff["field"] == "delivered"

    scfg, sdiff, iters = shrink_divergence(fz, divs[0])
    assert iters > 0
    assert scfg["replicas"] <= 2, scfg
    prog = fz.build(scfg)
    assert prog.n_slots <= 32, (scfg, prog.n_slots)

    doc = artifact_doc(
        "dumbbell", 0, divs[0].pair, scfg, sdiff,
        original_config=cfg, shrink_iterations=iters,
    )
    path = write_artifact(tmp_path, doc)
    loaded = load_artifact(path)
    assert loaded["env"]["TPUDES_FUZZ_PLANTED_BUG"] == "1"
    # replay must reproduce the recorded first_diff bit-identically
    rep = replay(loaded)
    assert len(rep) == 1 and rep[0].diff == sdiff

    # ...and with the flag off, the same scenario is clean (the flag
    # gates nothing but the self-test corruption)
    monkeypatch.delenv("TPUDES_FUZZ_PLANTED_BUG")
    assert run_scenario(
        fz, cfg, pairs=["chunked_vs_single"], record=False
    ) == []


# --- telemetry -------------------------------------------------------------


def test_fuzz_telemetry_snapshot_passes_schema_gate():
    from tpudes.obs.fuzz import FuzzTelemetry, validate_fuzz_metrics

    FuzzTelemetry.reset()
    FuzzTelemetry.record_scenario("dumbbell", 1.5)
    FuzzTelemetry.record_pair("dumbbell", "chunked_vs_single", False)
    FuzzTelemetry.record_pair("dumbbell", "swept_vs_point", True)
    FuzzTelemetry.record_shrink("dumbbell", 7)
    snap = FuzzTelemetry.snapshot()
    assert validate_fuzz_metrics(snap) == []
    assert snap["counters"]["divergences"] == 1
    assert snap["counters"]["shrink_iterations"] == 7
    e = snap["engines"]["dumbbell"]
    assert e["scenarios"] == 1 and e["scenarios_per_s"] > 0
    FuzzTelemetry.reset()
    assert FuzzTelemetry.snapshot()["counters"]["scenarios"] == 0


def test_fuzz_metrics_schema_rejects_malformed_docs():
    from tpudes.obs.fuzz import validate_fuzz_metrics

    assert validate_fuzz_metrics([]) != []
    assert validate_fuzz_metrics({"version": 1}) != []
    bad = {
        "version": 1,
        "counters": {
            "scenarios": 1, "pair_runs": 1, "divergences": 2,
            "shrinks": 0, "shrink_iterations": 0,
        },
        "engines": {
            "bss": {
                "scenarios": 1, "wall_s": 1.0, "scenarios_per_s": 1.0,
                "pairs": {"x": {"runs": 1, "divergences": 2}},
            }
        },
    }
    assert any("divergences > runs" in p for p in validate_fuzz_metrics(bad))


def test_obs_cli_validates_fuzz_metrics(tmp_path, capsys):
    from tpudes.obs.__main__ import main
    from tpudes.obs.fuzz import FuzzTelemetry

    FuzzTelemetry.reset()
    FuzzTelemetry.record_scenario("bss", 0.5)
    p = tmp_path / "m.json"
    p.write_text(json.dumps(FuzzTelemetry.snapshot()))
    FuzzTelemetry.reset()
    assert main(["--fuzz", str(p)]) == 0
    p.write_text(json.dumps({"version": 1}))
    assert main(["--fuzz", str(p)]) == 1


# --- envelope declarations -------------------------------------------------


def test_every_engine_declares_an_envelope():
    from tpudes.fuzz.engines import ENGINE_FUZZERS

    for eng, fz in ENGINE_FUZZERS.items():
        env = fz.envelope
        assert env.engine == eng
        assert {"replicas", "key_seed"} <= set(env.axes), eng
        assert env.floors.get("replicas", 99) == 1, eng
