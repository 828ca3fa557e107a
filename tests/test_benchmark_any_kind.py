"""The benchmark harness takes a cell of any lifted engine kind by files alone
(PR 34), guarded in tier-1 (PR 35): the cases of
``benchmark/tests/test_any_kind.py``, which tier-1 does not collect, on a temp
copy of ``BENCHMARK.json`` + ``benchmark/`` built as
``benchmark/tests/conftest.py`` builds its own.  ``toy.tcp`` is the SHIPPED
``tcp.mc`` (its configuration, reference, readers and limits as they are) at a
toy traffic size; ``toy.as`` is a deployment of kind ``as_flows`` with a stub
reference, which no shipped cell has.  Both run untraced to ``correct: true``
on the CPU, through ``run_cell``, with no file of the harness edited."""

import json
import os
import pathlib
import re
import shutil

import jax
import pytest

from benchmark import run, stock
from benchmark.manifest import REFERENCE_API, Manifest, ManifestError, load_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = pathlib.Path(ROOT, "benchmark")
TOYS = load_module(str(BENCH / "tests" / "conftest.py"))
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
#: cell -> (configuration, kind); the traffic file is `toy-<kind>.json`
CELLS = {
    "toy.tcp": ("tcp-dumbbell-8flow-cubic", "dumbbell"),
    "toy.as": ("toy-as", "as_flows"),
}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("any_kind")
    bench = root / "benchmark"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    # the shipped cell at a size the CPU holds: 8 replicas x 3 sim-s against 8
    # reference replicas; at that size the random gaps are far wider than the
    # cell's limits, so the toy's are ten times those
    mix = json.loads((bench / "traffic" / "mc-256x20s.json").read_text())
    mix.update(replicas=8, horizon_s=3.0, reference_replicas=8, warm_launches=1)
    (bench / "traffic" / "toy-dumbbell.json").write_text(json.dumps(mix))
    limits = json.loads((bench / "limits" / "tcp.mc.json").read_text())
    limits["limits"] = {k: 10 * v for k, v in limits["limits"].items()}
    (bench / "limits" / "toy.tcp.json").write_text(json.dumps(limits))
    # a kind no shipped cell has, by a configuration and a stub reference
    (bench / "traffic" / "toy-as_flows.json").write_text(
        json.dumps(TOYS.TOY_TRAFFIC["toy-as"]))
    (bench / "limits" / "toy.as.json").write_text(json.dumps(
        {"limits": {"rows_missing": 0, "rerun_differs": 0}}))
    config = dict(TOYS.TOY_CONFIGS["toy-as"], name="toy-as")
    (bench / "configs" / "toy-as.json").write_text(json.dumps(config))
    manifest["configs"].append({
        "name": "toy-as", "source": config["source"], "reduced": [],
        "file": "benchmark/configs/toy-as.json", "why": "toy size"})
    (bench / "references" / "toy_as.py").write_text(
        TOYS.TOY_REFERENCE.format(**TOYS.TOY_REFERENCES["toy_as"]))
    for cell, (config_name, kind) in CELLS.items():
        manifest["workloads"].append({
            "name": cell, "config": config_name, "traffic": f"toy-{kind}",
            "chips": 1, "why": "toy size for the CPU tests"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "lte.mc" in m.get("workloads", ()):
            m["workloads"] += list(CELLS)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_cell_of_any_kind_runs_by_files_alone(toy_root, name):
    manifest = Manifest(toy_root)
    assert manifest.config(CELLS[name][0])["kind"] == CELLS[name][1]
    result = run.run_cell(manifest, name, 2**31 + 35, 0.3, False,
                          jax.devices(), program_root=ROOT)
    assert list(result) == CONTRACT_KEYS + ["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["compared"]["rows_missing"] == {"value": 0.0, "limit": 0}
    assert result["compared"]["rerun_differs"] == {"value": 0.0, "limit": 0}
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"sim_s_per_wall_s", "setup_s"}


def test_the_shipped_tcp_cell_is_compared_in_every_number(toy_root):
    manifest = Manifest(toy_root)
    assert set(manifest.limits("toy.tcp")) == set(manifest.limits("tcp.mc")) == {
        "rows_missing", "rerun_differs", "agg_goodput_gap", "flow_goodput_gap",
        "drops_gap", "queue_gap", "jain_gap"}
    cell = manifest.cell("tcp.mc")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tcp-dumbbell-8flow-cubic", "mc-256x20s", 1)


def test_no_file_of_the_harness_branches_on_an_engine_kind():
    kinds = ("bss", "lte_sm", "dumbbell", "as_flows", "wired")
    literal = re.compile(r"""["'](%s)["']""" % "|".join(kinds))
    for path in sorted(BENCH.rglob("*.py")):
        relative = path.relative_to(BENCH).as_posix()
        if relative.startswith("tests/"):
            continue
        text = path.read_text()
        assert not re.search(r"kind *(==|!=|in) ", text), relative
        if not relative.startswith("references"):   # one deployment's own
            assert not literal.search(text), relative


def test_every_reference_states_its_scripts_exit_criterion():
    manifest = Manifest(ROOT)
    for path in (BENCH / "references").glob("*.py"):
        module = manifest.reference(path.stem)
        assert all(callable(getattr(module, fn)) for fn in REFERENCE_API)
    assert not hasattr(stock, "criterion")


def test_a_failed_criterion_stops_set_up_with_what_failed(toy_root, monkeypatch):
    manifest = Manifest(toy_root)
    cell = run.make_cell(manifest, "toy.tcp", 1, ROOT)
    monkeypatch.setattr(cell.reference, "criterion", lambda out: "no goodput")
    with pytest.raises(RuntimeError, match="exit criterion failed: no goodput"):
        manifest.driver("mc").setup(cell)


def test_a_reference_without_criterion_is_refused_before_any_launch(toy_root):
    references = pathlib.Path(toy_root, "benchmark", "references")
    bare = references / "toy_bare.py"
    bare.write_text((references / "toy_as.py").read_text().replace(
        "def criterion(", "def _criterion("))
    try:
        with pytest.raises(ManifestError, match=r"toy_bare\.py lacks criterion"):
            Manifest(toy_root).reference("toy_bare")
    finally:
        bare.unlink()
