"""Traffic driver `script`: the front door itself, closed loop, one client.

Every study is the configuration's stock script's `main(argv)` in-process with
the one GlobalValue flip, as `python examples/<script>` runs it: object graph
built, lifted, run on the device, per-replica results on the host;
`reset_world()` between studies.  Every study of a run has the same `argv`: a
new `RngRun` would be a new geometry, a new runner and a new compile (the
lifted tables are closed-over constants of the jitted step), so `--seed` does
not enter the script; it seeds the plain reference only.

Set-up warms with `warm_studies` studies, then `gc.collect(); gc.freeze()` once
so that full collections stop walking the import graph.  A traced window wraps
`lift.lift` and `lift.run_lifted` (the engine looks both up at call time) in
spans; its first `trace_studies` run under the profiler.
"""

from __future__ import annotations

import gc
import statistics
import time
from unittest import mock

import numpy as np

from benchmark import stock


def _argv(cell) -> list[str]:
    cfg, mix = cell.cfg, cell.traffic
    args = dict(cfg["args"], **{cfg["horizon_arg"]: mix["horizon_s"]})
    return stock.script_argv(args, int(mix["replicas"]))


def _study(state, cell):
    rc, res, wall = stock.run_main(state["main"], state["argv"])
    ok = (
        rc == 0 and res is not None and res["kind"] == cell.cfg["kind"]
        and res["replicas"] == int(cell.traffic["replicas"])
        and cell.reference.criterion(res["out"]) is None
    )
    return ok, res, wall


def setup(cell) -> dict:
    t0 = time.monotonic()
    state = dict(
        main=stock.load_example(cell.root, cell.cfg["script"]).main,
        argv=_argv(cell),
    )
    ok, _, _ = _study(state, cell)
    if not ok:
        raise RuntimeError(
            f"{cell.cfg['script']} {state['argv']}: the stock script did not "
            "take the lifted path or failed its own exit criterion"
        )
    cell.split["script_first_run_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    for _ in range(int(cell.traffic.get("warm_studies", 12))):
        _study(state, cell)
    gc.collect()
    gc.freeze()
    cell.split["warm_up_s"] = time.monotonic() - t0
    return state


def _spanned(spans, name, fn):
    def wrapped(*args, **kwargs):
        with spans.span(name):
            return fn(*args, **kwargs)

    return wrapped


def window(state, cell, seconds: float, spans=None, profile=None) -> dict:
    from tpudes.parallel import lift as lift_mod

    studies, failed, record = [], 0, {}

    def one():
        nonlocal failed
        ok, res, wall = _study(state, cell)  # `state` as rebound below
        failed += not ok
        studies.append((wall, res["out"] if res else None))

    def loop(until_count=None):
        t0 = time.monotonic()
        while True:
            if spans is None:
                one()
            else:
                with spans.span("study"):
                    one()
            elapsed = time.monotonic() - t0
            if (len(studies) >= until_count if until_count
                    else elapsed >= seconds):
                return elapsed

    if spans is None:
        record["elapsed_s"] = loop()
    else:
        state = dict(state, main=_spanned(spans, "main", state["main"]))
        with mock.patch.object(
            lift_mod, "lift", _spanned(spans, "lift", lift_mod.lift)
        ), mock.patch.object(
            lift_mod, "run_lifted",
            _spanned(spans, "run_lifted", lift_mod.run_lifted),
        ):
            with profile():
                loop(until_count=int(cell.traffic["trace_studies"]))
            record["trace_studies"] = len(studies)
            spans.set_aside("traced_")
            traced = studies[:]
            del studies[:]
            record["elapsed_s"] = loop()
            record["trace_iterations"] = stock.iterations(
                cell.cfg, [out for _, out in traced if out],
                float(cell.traffic["horizon_s"]),
            )
    record.update(studies=studies, failed=failed)
    return record


def end_to_end(state, cell, record) -> dict:
    return {"study_p50_s": statistics.median(w for w, _ in record["studies"])}


def attempted(record) -> int:
    return len(record["studies"])


def check(state, cell, record) -> dict:
    """Every study's replicas against the plain reference; every study has the
    same arguments, hence the same key, hence bit-identical results."""
    outs = [out for _, out in record["studies"] if out is not None]
    numbers = cell.reference.compare(
        cell.cfg, cell.traffic, outs,
        len(record["studies"]) * int(cell.traffic["replicas"]), cell.seed,
    )
    numbers["rerun_differs"] = float(sum(
        any(not np.array_equal(np.asarray(outs[0][k]), np.asarray(o[k]))
            for k in outs[0] if not isinstance(outs[0][k], dict))
        for o in outs[1:]
    ))
    return numbers


def reseed(state, cell, seed: int) -> None:
    cell.seed = seed


def control(state, cell, seconds: float) -> dict:
    mix, how, reference = cell.traffic, cell.cfg["control"], cell.reference
    if how["how"] != "reference":
        raise ValueError("the script driver runs the reference's control only")
    stand_in = reference.simulate(
        cell.cfg, float(mix["horizon_s"]), int(mix["replicas"]),
        cell.seed + 1, **how["kwargs"]
    )
    return reference.compare(
        cell.cfg, mix, [stand_in], int(mix["replicas"]), cell.seed
    )


def counters(state, cell, record) -> dict:
    outs = [out for _, out in record["studies"] if out is not None]
    walls = sorted(w for w, _ in record["studies"])
    return {
        "kpi_mean": float(np.mean([cell.reference.kpi(o) for o in outs])),
        "study_walls_s": walls,
    }
