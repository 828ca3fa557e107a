import json
import os
import re

from conftest import ROOT

from benchmark.manifest import Manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_manifest_parses_and_every_file_resolves_by_name():
    m = Manifest(ROOT)
    data = m.data
    assert set(data) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    used = set()
    for cell in data["workloads"]:
        assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
        assert cell["chips"] in (1, 4)
        cfg = m.config(cell["config"])
        mix = m.traffic(cell["traffic"])
        used.add(cell["config"])
        driver = m.driver(mix["driver"])
        for fn in ("setup", "window", "end_to_end", "check", "attempted",
                   "counters", "reseed", "control"):
            assert callable(getattr(driver, fn))
        reference = m.reference(cfg["reference"])
        assert callable(reference.simulate) and callable(reference.compare)
        limits = m.limits(cell["name"])
        assert limits and all(v >= 0 for v in limits.values())
        assert os.path.isfile(os.path.join(ROOT, "examples", cfg["script"]))
        reported = [x["name"] for x in m.metrics_of("end_to_end", cell["name"])]
        assert "setup_s" in reported and len(reported) >= 2
        layers = m.metrics_of("per_layer", cell["name"])
        assert layers
        for metric in layers:
            assert callable(m.layer_reader(metric["name"]))
            assert metric["moves"] in reported
    assert used == {c["name"] for c in data["configs"]}
    four = sum(c["chips"] == 4 for c in data["workloads"])
    assert four <= max(1, len(data["workloads"]) // 2)


def test_configs_state_source_reference_and_guarantees():
    m = Manifest(ROOT)
    for c in m.data["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        cfg = m.config(c["name"])
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert cfg["guarantees"] and cfg["control"]["how"] in ("program", "reference")
        assert "tpudes" not in open(os.path.join(
            ROOT, "benchmark", "references", cfg["reference"] + ".py"
        )).read().replace("`tpudes`", "")


def test_references_import_nothing_of_the_program():
    for name in os.listdir(os.path.join(ROOT, "benchmark", "references")):
        if name.endswith(".py"):
            text = open(os.path.join(ROOT, "benchmark", "references", name)).read()
            assert not re.search(r"^\s*(from|import)\s+(tpudes|jax)", text, re.M)


def test_peaks_table_is_keyed_by_device_kind():
    peaks = json.load(open(os.path.join(ROOT, "benchmark", "peaks.json")))
    assert "TPU v5 lite" in peaks["peaks"] and peaks["source"]
