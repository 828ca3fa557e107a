"""Tier-1 CI gate: ``python -m tpudes.analysis`` over the repo must be
clean against tools/analysis_baseline.json, and the gate must actually
bite — a file with a true positive exits nonzero.

Runs inside the normal pytest tier-1 command, no extra CI wiring.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(*args, cwd=REPO):
    # PYTHONPATH keeps tpudes importable when cwd is not the repo root
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "tpudes.analysis", *args],
        cwd=cwd, capture_output=True, text=True, timeout=300, env=env,
    )


def test_repo_clean_against_baseline():
    proc = _run()
    assert proc.returncode == 0, (
        "new analysis findings (fix them or, for pre-existing debt, "
        "re-baseline with --write-baseline):\n"
        + proc.stdout + proc.stderr
    )


def test_true_positive_file_fails_the_gate(tmp_path):
    bad = tmp_path / "bad_model.py"
    bad.write_text(
        "from tpudes.core.simulator import Simulator\n"
        "\n"
        "def arm(devices):\n"
        "    backlog = set(devices)\n"
        "    for dev in backlog:\n"
        "        Simulator.Schedule(1, dev.poll)\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "DET001" in proc.stdout


def test_json_output_is_machine_readable(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("try:\n    pass\nexcept:\n    pass\n")
    proc = _run(str(bad), "--json")
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["findings"][0]["code"] == "LNT005"


def test_list_rules_covers_every_pass():
    proc = _run("--list-rules")
    assert proc.returncode == 0
    for code in ("JP001", "RNG001", "DET001", "EVT001", "REG001", "LNT001",
                 "TRC001", "KEY001", "JXL001", "JXL002", "JXL003", "JXL004",
                 "JXL005", "JXL006", "JXL007", "JXL008"):
        assert code in proc.stdout


def test_baseline_file_is_wellformed():
    data = json.loads((REPO / "tools" / "analysis_baseline.json").read_text())
    assert data["version"] == 1
    assert all(
        isinstance(v, int) and v > 0 for v in data["counts"].values()
    )


def test_lint_shim_still_gates():
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "lint.py")],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_subtree_scan_honors_the_baseline():
    # all 15 baselined findings live under tpudes/, and baseline keys
    # are root-relative — an explicit-path scan from the repo root must
    # not report frozen debt as new
    proc = _run("tpudes")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_misspelled_path_is_an_error_not_a_green_gate():
    proc = _run("tpudes/modles")
    assert proc.returncode == 2
    assert "no such file" in proc.stderr


def test_write_baseline_refuses_narrowed_runs(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("try:\n    pass\nexcept:\n    pass\n")
    before = (REPO / "tools" / "analysis_baseline.json").read_text()
    for narrowed in ([str(bad), "--write-baseline"],
                     ["--select", "LNT", "--write-baseline"]):
        proc = _run(*narrowed)
        assert proc.returncode == 2, proc.stdout + proc.stderr
    assert (REPO / "tools" / "analysis_baseline.json").read_text() == before


def test_missing_default_roots_is_an_error_not_a_green_gate(tmp_path):
    proc = _run(cwd=tmp_path)
    assert proc.returncode == 2
    assert "default roots" in proc.stderr


@pytest.mark.slow  # ISSUE-21 tier-1 budget: CI's analysis steps run this exact gate
def test_jaxpr_gate_is_clean_against_baseline():
    """ISSUE-12 acceptance: the trace manifests cover every device
    engine and the JXL pass family reports zero unbaselined findings
    (the four by-design wired egress-donation entries live in the
    baseline)."""
    proc = _run("--jaxpr")
    assert proc.returncode == 0, (
        "new jaxpr-analysis findings (fix them or, for structural "
        "debt, re-baseline with --jaxpr --write-baseline):\n"
        + proc.stdout + proc.stderr
    )


@pytest.mark.slow  # ISSUE-21 tier-1 budget: CI's analysis steps run this exact gate
def test_jaxpr_flag_composes_with_select_and_json():
    # --select JXL005 --no-baseline must surface exactly the known
    # egress-donation findings, machine-readably
    proc = _run("--jaxpr", "--select", "JXL005", "--no-baseline",
                "--json")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    codes = {f["code"] for f in payload["findings"]}
    paths = {f["path"] for f in payload["findings"]}
    assert codes == {"JXL005"}
    assert paths == {
        "tpudes/parallel/wired.py", "tpudes/parallel/hybrid.py",
    }


def test_sarif_output_is_schema_shaped(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("try:\n    pass\nexcept:\n    pass\n")
    proc = _run(str(bad), "--format", "sarif", "--no-baseline")
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    # the minimal SARIF 2.1.0 profile GitHub code scanning ingests
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-schema-2.1.0.json")
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "tpudes-analysis"
    rule_ids = [r["id"] for r in driver["rules"]]
    assert rule_ids == sorted(rule_ids) and len(set(rule_ids)) == len(rule_ids)
    for r in driver["rules"]:
        assert r["shortDescription"]["text"]
    # the driver advertises the full rule set, jaxpr family included
    assert {"LNT005", "KEY001", "JXL001", "JXL005"} <= set(rule_ids)
    assert run["results"], "the planted LNT005 must appear as a result"
    for res in run["results"]:
        assert res["ruleId"] in rule_ids
        assert rule_ids[res["ruleIndex"]] == res["ruleId"]
        assert res["level"] == "error"
        assert res["message"]["text"]
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"]
        assert loc["region"]["startLine"] >= 1
        assert loc["region"]["startColumn"] >= 1


@pytest.mark.slow  # ISSUE-21 tier-1 budget: runs in CI's slow-overflow step
def test_ast_cache_cold_then_warm(tmp_path):
    """The per-file content-hash cache: a warm run re-parses nothing,
    reports full hits, produces identical findings, and is measurably
    faster than the cold run that populated the cache."""
    cache = tmp_path / "cache.json"
    cold = _run("--json", "--cache", str(cache))
    assert cold.returncode == 0, cold.stdout + cold.stderr
    cold_payload = json.loads(cold.stdout)
    assert cold_payload["cache"]["hits"] == 0
    assert cold_payload["cache"]["misses"] > 100
    assert cache.exists()

    warm = _run("--json", "--cache", str(cache))
    assert warm.returncode == 0, warm.stdout + warm.stderr
    warm_payload = json.loads(warm.stdout)
    assert warm_payload["cache"]["misses"] == 0
    assert warm_payload["cache"]["hits"] == cold_payload["cache"]["misses"]
    assert warm_payload["findings"] == cold_payload["findings"]
    assert warm_payload["baselined"] == cold_payload["baselined"]
    # the whole point: the analysis phase collapses (cold runs every
    # pass over ~200 files, warm only hashes them)
    assert warm_payload["elapsed_s"] < cold_payload["elapsed_s"]


def test_ast_cache_invalidates_on_content_change(tmp_path):
    """A findings-relevant edit must not be masked by the cache."""
    import shutil

    proj = tmp_path / "proj"
    (proj / "tpudes").mkdir(parents=True)
    (proj / "tpudes" / "mod.py").write_text("x = 1\n")
    shutil.copytree(REPO / "tpudes" / "analysis",
                    proj / "tpudes" / "analysis")
    cache = tmp_path / "cache.json"
    first = _run("--json", "--cache", str(cache), "--no-baseline",
                 cwd=proj)
    assert first.returncode == 0, first.stdout + first.stderr
    (proj / "tpudes" / "mod.py").write_text(
        "try:\n    pass\nexcept:\n    pass\n"
    )
    second = _run("--json", "--cache", str(cache), "--no-baseline",
                  cwd=proj)
    payload = json.loads(second.stdout)
    assert second.returncode == 1
    assert any(f["code"] == "LNT005" for f in payload["findings"])


def _tiny_proj(tmp_path):
    proj = tmp_path / "proj"
    (proj / "tpudes").mkdir(parents=True)
    (proj / "tpudes" / "mod.py").write_text("x = 1\n")
    (proj / "tests").mkdir()
    (proj / "tests" / "t.py").write_text("y = 2\n")
    return proj


def _collect(proj):
    from tpudes.analysis.engine import collect_modules

    return collect_modules([proj / "tpudes", proj / "tests"], proj)


def test_jaxpr_cache_key_tracks_modules_rules_and_tracer(
    tmp_path, monkeypatch
):
    """ISSUE-16 satellite: the jaxpr section's key must move when a
    traced tpudes/ module, the JXL pass family, or the jax install
    changes — and must NOT move on test-file edits (retracing every
    manifest because a test changed would make the cache useless)."""
    from tpudes.analysis import cache as C

    proj = _tiny_proj(tmp_path)
    sha0 = C.AnalysisCache.jaxpr_sha(_collect(proj))

    (proj / "tests" / "t.py").write_text("y = 3\n")
    assert C.AnalysisCache.jaxpr_sha(_collect(proj)) == sha0

    (proj / "tpudes" / "mod.py").write_text("x = 2\n")
    sha1 = C.AnalysisCache.jaxpr_sha(_collect(proj))
    assert sha1 != sha0

    monkeypatch.setattr(C, "_jaxpr_rules_fp", "0" * 64)
    assert C.AnalysisCache.jaxpr_sha(_collect(proj)) != sha1
    monkeypatch.undo()

    monkeypatch.setattr(C, "_jax_version", lambda: "999.0")
    assert C.AnalysisCache.jaxpr_sha(_collect(proj)) != sha1


def test_jaxpr_cache_section_roundtrips_and_resets_with_store(tmp_path):
    from tpudes.analysis.base import Finding
    from tpudes.analysis.cache import CACHE_VERSION, AnalysisCache

    path = tmp_path / "cache.json"
    cache = AnalysisCache(path)
    f = Finding("tpudes/parallel/wired.py", 9, 1, "JXL007", "quadratic")
    cache.put_jaxpr("abc", [f])
    cache.save()

    again = AnalysisCache(path)
    served = again.get_jaxpr("abc")
    assert served is not None and served[0].to_json() == f.to_json()
    assert again.get_jaxpr("other-key") is None

    # a rules-fingerprint mismatch drops the jaxpr section with the
    # rest of the store
    data = json.loads(path.read_text())
    data["rules"] = "stale"
    assert data["version"] == CACHE_VERSION
    path.write_text(json.dumps(data))
    assert AnalysisCache(path).get_jaxpr("abc") is None


def test_engine_serves_and_invalidates_cached_jaxpr_findings(
    tmp_path, monkeypatch
):
    """Cold run executes the JXL family and stores the findings; warm
    run serves them without re-running; a tpudes/ edit re-runs; a
    narrowed (--select) cold run never writes."""
    import tpudes.analysis.jaxpr as jx
    from tpudes.analysis import engine
    from tpudes.analysis.base import Finding
    from tpudes.analysis.cache import AnalysisCache

    calls = []

    class StubJaxprPass:
        name = "stub-jaxpr"
        codes = {"JXL999": "stub rule"}
        project_wide = True

        def check_project(self, mods):
            calls.append(1)
            return [Finding("tpudes/mod.py", 1, 1, "JXL999", "stub")]

    monkeypatch.setattr(jx, "JAXPR_PASSES", (StubJaxprPass,))
    proj = _tiny_proj(tmp_path)

    def run(cache, **kw):
        out = engine.run_passes(_collect(proj), jaxpr=True, cache=cache,
                                **kw)
        return [f for f in out if f.code == "JXL999"]

    cache = AnalysisCache(tmp_path / "cache.json")
    assert len(run(cache)) == 1 and len(calls) == 1
    cache.save()

    warm = AnalysisCache(tmp_path / "cache.json")
    assert len(run(warm)) == 1
    assert len(calls) == 1, "warm run must serve, not re-trace"

    # selection narrows the output but still reads the cached set
    assert run(warm, select=["LNT"]) == []
    assert len(run(warm, select=["JXL"])) == 1
    assert len(calls) == 1

    (proj / "tpudes" / "mod.py").write_text("x = 2\n")
    assert len(run(warm)) == 1
    assert len(calls) == 2, "a tpudes/ edit must invalidate"

    # a narrowed COLD run re-traces but must not poison the store
    cold = AnalysisCache(tmp_path / "cache2.json")
    assert len(run(cold, select=["JXL"])) == 1
    assert len(calls) == 3
    cold.save()
    assert not (tmp_path / "cache2.json").exists()


@pytest.mark.slow  # ISSUE-21 tier-1 budget: CI's analysis steps run this exact gate
def test_jaxpr_warm_cache_analysis_is_subsecond():
    """ISSUE-16 satellite: CI reruns the --jaxpr gate between rounds;
    with the default cache warm it must answer in under a second (no
    jax import, no manifest tracing).  The first run warms the cache
    when a fresh checkout arrives cold."""
    _run("--jaxpr")
    warm = _run("--jaxpr", "--json")
    assert warm.returncode == 0, warm.stdout + warm.stderr
    payload = json.loads(warm.stdout)
    assert payload["elapsed_s"] < 1.0, payload["elapsed_s"]


def test_cost_requires_jaxpr():
    proc = _run("--cost")
    assert proc.returncode == 2
    assert "--jaxpr" in proc.stderr


@pytest.mark.slow
def test_cost_report_cli_end_to_end(tmp_path):
    """``--jaxpr --cost``: full-repo scale report with the wired
    worklist, plus the JSON artifact CI uploads."""
    out = tmp_path / "cost.json"
    proc = _run("--jaxpr", "--cost", "--cost-out", str(out))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OVER BUDGET" in proc.stdout
    assert "ROADMAP item 2" in proc.stdout
    report = json.loads(out.read_text())
    assert report["projection_nodes"] == [100000, 1000000]
    assert "wired/advance:n_nodes" in report["worklist"]
    assert "wired_space/advance:n_nodes" in report["worklist"]
    by_axis = {
        (r["engine"], r["axis"]): r for r in report["entries"]
    }
    wired_row = by_axis[("wired", "n_nodes")]
    assert wired_row["mem_exponent"] >= 1.99
    assert wired_row["projected"]["1e6_nodes"]["bytes"] > 0


def test_write_baseline_without_jaxpr_refuses_to_drop_jxl_entries():
    # the ratchet holds JXL trace findings; a plain --write-baseline
    # would silently delete them and break the --jaxpr gate later
    before = (REPO / "tools" / "analysis_baseline.json").read_text()
    proc = _run("--write-baseline")
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "--jaxpr" in proc.stderr
    assert (REPO / "tools" / "analysis_baseline.json").read_text() == before
