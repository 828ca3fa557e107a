"""A cell of any lifted engine kind by files alone (PR 34): nothing under
`benchmark/` knows an engine.  A deployment's exit criterion is `criterion(out)`
of its reference module, its horizon and its loop are described by its
configuration file; the toy cells of kinds `dumbbell` and `as_flows` that
`conftest.py` adds as files and manifest entries run through the whole harness."""

import pathlib
import re

import jax
import pytest

from conftest import ROOT, TOY_CELLS

from benchmark import run, stock
from benchmark.manifest import REFERENCE_API, Manifest, ManifestError

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
BENCH = pathlib.Path(ROOT, "benchmark")


def sources():
    """`(relative path, text)` of every Python file of the harness but its tests."""
    for path in sorted(BENCH.rglob("*.py")):
        relative = path.relative_to(BENCH).as_posix()
        if not relative.startswith("tests/"):
            yield relative, path.read_text()


def test_no_file_of_the_harness_branches_on_an_engine_kind():
    kinds = ("bss", "lte_sm", "dumbbell", "as_flows", "wired")
    literal = re.compile(r"""["'](%s)["']""" % "|".join(kinds))
    for path, text in sources():
        assert not re.search(r"kind *(==|!=|in) ", text), path
        if not path.startswith("references"):    # a reference is one deployment's
            assert not literal.search(text), path


@pytest.mark.parametrize("name,kind", [
    ("toy.tcp", "dumbbell"), ("toy.as", "as_flows"),
    ("toy.bss", "bss"), ("toy.lte", "lte_sm"),
])
def test_a_cell_of_any_kind_runs_by_files_alone(toy_root, name, kind):
    manifest = Manifest(toy_root)
    assert manifest.config(TOY_CELLS[name][0])["kind"] == kind
    result = run.run_cell(manifest, name, 2**31 + 34, 0.3, False,
                          jax.devices(), program_root=ROOT)
    assert list(result) == CONTRACT_KEYS + ["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["compared"]["rows_missing"] == {"value": 0.0, "limit": 0}
    assert result["compared"]["rerun_differs"] == {"value": 0.0, "limit": 0}
    if name != "toy.lte":    # the toy LTE horizon is too short for the cell's limit
        assert result["correct"] is True, result["compared"]


def test_every_reference_states_its_scripts_exit_criterion():
    manifest = Manifest(ROOT)
    for path in (BENCH / "references").glob("*.py"):
        module = manifest.reference(path.stem)
        assert all(callable(getattr(module, fn)) for fn in REFERENCE_API)
    assert not hasattr(stock, "criterion")


def test_a_failed_criterion_stops_set_up_with_what_failed(toy_root, monkeypatch):
    manifest = Manifest(toy_root)
    cell = run.make_cell(manifest, "toy.as", 1, ROOT)
    monkeypatch.setattr(cell.reference, "criterion", lambda out: "no flow at all")
    with pytest.raises(RuntimeError, match="exit criterion failed: no flow at all"):
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
