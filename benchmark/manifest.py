"""Everything the harness finds by name.

`BENCHMARK.json` names cells, configurations, traffic mixes and metrics; each of
them is one file under `benchmark/` that this module resolves from the name alone,
so a later PR adds a file and a manifest entry and edits nothing that exists:

    configs/<config>.json        the deployment as it is run (+ its `reference`)
    references/<reference>.py    the plain reference, the comparison and the
                                 stock script's exit criterion (`REFERENCE_API`)
    traffic/<traffic>.json       the mix's parameters (+ its `driver`)
    drivers/<driver>.py          the one general generator for such mixes
    layers/<metric>.py           one small reader per per-layer metric
    limits/<cell>.json           the limit of each number `correct` compares
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

#: what a reference module has to give: `criterion(out)` (None where the stock
#: script's own exit criterion holds on a lifted result, else what failed),
#: `compare(cfg, traffic, outs, expected_rows, seed)`, `simulate(cfg, horizon_s,
#: replicas, seed, **control)` and `kpi(out)`
REFERENCE_API = ("criterion", "compare", "simulate", "kpi")


class ManifestError(ValueError):
    """The manifest names something that no file under `paths` provides."""


def load_module(path: str):
    """Import one file by path (names with `-` and `.` are not importable by
    name, and nothing here is a package)."""
    if not os.path.isfile(path):
        raise ManifestError(f"no such file: {path}")
    name = "bench_" + "".join(
        c if c.isalnum() else "_"
        for c in os.path.relpath(path, BENCH_DIR).removesuffix(".py")
    )
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    """`BENCHMARK.json` of the checkout at `root`, with the files it names."""

    def __init__(self, root: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.data = json.load(f)
        self.bench_dir = os.path.join(root, self.data["paths"][0])

    def _json(self, *parts: str) -> dict:
        path = os.path.join(self.bench_dir, *parts)
        if not os.path.isfile(path):
            raise ManifestError(f"no such file: {path}")
        with open(path) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"unknown workload {name!r}; BENCHMARK.json has "
            f"{[w['name'] for w in self.data['workloads']]}"
        )

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise ManifestError(f"unknown config {name!r}")

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name + ".json")

    def limits(self, cell_name: str) -> dict:
        return self._json("limits", cell_name + ".json")["limits"]

    def driver(self, name: str):
        return load_module(os.path.join(self.bench_dir, "drivers", name + ".py"))

    def reference(self, name: str):
        path = os.path.join(self.bench_dir, "references", name + ".py")
        mod = load_module(path)
        lacks = [fn for fn in REFERENCE_API if not callable(getattr(mod, fn, None))]
        if lacks:
            raise ManifestError(f"{path} lacks {', '.join(lacks)}")
        return mod

    def layer_reader(self, metric: str):
        return load_module(
            os.path.join(self.bench_dir, "layers", metric + ".py")
        ).read

    def metrics_of(self, group: str, cell_name: str) -> list[dict]:
        """The `end_to_end` or `per_layer` metrics this cell reports: those
        that list it under `workloads`, and those that list nothing."""
        return [
            m for m in self.data[group]
            if "workloads" not in m or cell_name in m["workloads"]
        ]
