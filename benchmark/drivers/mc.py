"""Traffic driver `mc`: a Monte-Carlo campaign, closed loop, one client.

Set-up runs the configuration's stock script once through `JaxSimulatorImpl` at
the mix's replicas and horizon (graph build -> `lift()` -> compile or cache load
-> first launch) and keeps the lifted program.  The window then calls
`tpudes.parallel.lift.run_lifted(kind, prog, replicas, key)` back to back, each
launch with its own key made from `--seed` and the launch index, each result on
the host as numpy before the next call.  `run_lifted` picks the mesh itself, as
it does for a user.  The window ends at the first launch boundary after
`seconds`.

A traced window wraps the same launches in spans (`dispatch`: the call until
`run_lifted(block=False)` returns; `wait`: until the device is done;
`fetch_unpack`: until numpy is on the host), the first `trace_launches` of them
under the profiler at `trace_horizon_s`, the rest at the mix's own horizon.
"""

from __future__ import annotations

import dataclasses
import gc
import numbers
import time

import numpy as np

from benchmark import stock

#: keys made before the window (a launch takes one; the window never makes any)
MAX_LAUNCHES = 4096


def _keys(seed: int):
    """(MAX_LAUNCHES, 2) uint32 on the host: row i is
    fold_in(fold_in(PRNGKey(low 31 bits), high bits), i)."""
    import jax

    base = jax.random.fold_in(
        jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31
    )
    rows = jax.vmap(lambda i: jax.random.fold_in(base, i))(
        jax.numpy.arange(MAX_LAUNCHES)
    )
    return np.asarray(rows)


def _at_horizon(cfg: dict, prog, horizon_s: float):
    """The lifted program with its horizon field set (a traced operand of the
    engine's loop: the executable is the same at every horizon).  A field the
    program holds as a whole number (TTIs, microseconds, slots) gets one; a
    float of seconds stays a float."""
    field = cfg["horizon_field"]
    value = horizon_s * field["per_second"]
    if isinstance(getattr(prog, field["name"]), numbers.Integral):
        value = int(round(value))
    return dataclasses.replace(prog, **{field["name"]: value})


def setup(cell) -> dict:
    from tpudes.parallel.lift import run_lifted

    cfg, mix = cell.cfg, cell.traffic
    replicas, horizon = int(mix["replicas"]), float(mix["horizon_s"])
    t0 = time.monotonic()
    main = stock.load_example(cell.root, cfg["script"]).main
    args = dict(cfg["args"], **{cfg["horizon_arg"]: mix["horizon_s"]})
    rc, res, _ = stock.run_main(main, stock.script_argv(args, replicas))
    if rc != 0 or res is None:
        raise RuntimeError(
            f"{cfg['script']} exit code {rc}, lifted={res is not None}: the "
            "stock script did not take the lifted path"
        )
    if res["kind"] != cfg["kind"] or res["replicas"] != replicas:
        raise RuntimeError(
            f"lifted {res['kind']!r} x {res['replicas']}, the configuration "
            f"says {cfg['kind']!r} x {replicas}"
        )
    failed = cell.reference.criterion(res["out"])
    if failed:
        raise RuntimeError(f"{cfg['script']}: exit criterion failed: {failed}")
    cell.split["script_first_run_s"] = time.monotonic() - t0
    prog = res["program"]
    keys = _keys(cell.seed)
    t0 = time.monotonic()
    warm = _at_horizon(cfg, prog, float(mix.get("warm_horizon_s", horizon)))
    for i in range(int(mix.get("warm_launches", 1))):
        run_lifted(cfg["kind"], warm, replicas, keys[MAX_LAUNCHES - 1 - i])
    # what set-up allocated (jax's import graph, the object graph, the lifted
    # program) stays for the whole run: move it out of the collector's sight, so
    # that full collections inside the window stop walking it (PR 24 read the
    # window's rate spread over runs fall from 1.4% to 0.4% on wifi.mc)
    gc.collect()
    gc.freeze()
    cell.split["warm_up_s"] = time.monotonic() - t0
    return dict(prog=prog, keys=keys, run_lifted=run_lifted)


def _launch(state, cell, prog, index: int, spans=None) -> dict:
    kind, replicas = cell.cfg["kind"], int(cell.traffic["replicas"])
    key = state["keys"][index]
    if spans is None:
        return state["run_lifted"](kind, prog, replicas, key)
    with spans.span("dispatch"):
        fut = state["run_lifted"](kind, prog, replicas, key, block=False)
    with spans.span("wait"):
        fut.block()
    with spans.span("fetch_unpack"):
        return fut.result()


def window(state, cell, seconds: float, spans=None, profile=None) -> dict:
    """The measured window; `spans` and `profile` only in a traced run."""
    mix = cell.traffic
    horizon = float(mix["horizon_s"])
    prog, index, record = state["prog"], 0, {}
    if profile is not None:
        t_h = float(mix.get("trace_horizon_s", horizon))
        traced_prog = _at_horizon(cell.cfg, prog, t_h)
        with profile():
            traced = [
                _launch(state, cell, traced_prog, i, spans)
                for i in range(int(mix["trace_launches"]))
            ]
        index = len(traced)
        record["trace_iterations"] = stock.iterations(cell.cfg, traced, t_h)
        spans.set_aside("traced_")
    outs = []
    t0 = time.monotonic()
    while True:
        outs.append(_launch(state, cell, prog, index, spans))
        index += 1
        elapsed = time.monotonic() - t0
        if elapsed >= seconds or index >= MAX_LAUNCHES - 8:
            break
    record.update(outs=outs, elapsed_s=elapsed, first_index=index - len(outs))
    return record


def end_to_end(state, cell, record) -> dict:
    mix = cell.traffic
    done = len(record["outs"]) * int(mix["replicas"]) * float(mix["horizon_s"])
    return {"sim_s_per_wall_s": done / record["elapsed_s"]}


def attempted(record) -> int:
    return len(record["outs"])


def check(state, cell, record) -> dict:
    """Every launch of the window against the plain reference, and the first
    one run again with its own key: the same seed gives the same replicas."""
    mix = cell.traffic
    outs = record["outs"]
    numbers = cell.reference.compare(
        cell.cfg, mix, outs, len(outs) * int(mix["replicas"]), cell.seed
    )
    again = _launch(state, cell, state["prog"], record["first_index"])
    numbers["rerun_differs"] = float(sum(
        not np.array_equal(np.asarray(outs[0][k]), np.asarray(again[k]))
        for k in outs[0] if not isinstance(outs[0][k], dict)
    ))
    return numbers


def reseed(state, cell, seed: int) -> None:
    """Another `--seed` in the same process (readings.py): new launch keys."""
    cell.seed = seed
    state["keys"] = _keys(seed)


def control(state, cell, seconds: float) -> dict:
    """The configuration's control, compared as a run would be: either the
    program with its own lower-precision path switched on, or the reference at
    the lower precision put in the program's place, at the cell's own size."""
    how = cell.cfg["control"]
    if how["how"] == "program":
        lowered = dict(state, prog=dataclasses.replace(
            state["prog"], **how["replace"]
        ))
        _launch(lowered, cell, lowered["prog"], MAX_LAUNCHES - 1)  # compiles
        return check(lowered, cell, window(lowered, cell, seconds))
    mix, reference = cell.traffic, cell.reference
    stand_in = reference.simulate(
        cell.cfg, float(mix["horizon_s"]), int(mix["replicas"]),
        cell.seed + 1, **how["kwargs"]
    )
    return reference.compare(
        cell.cfg, mix, [stand_in], int(mix["replicas"]), cell.seed
    )


def counters(state, cell, record) -> dict:
    kpi = cell.reference.kpi
    return {"kpi_mean": float(np.mean([kpi(o) for o in record["outs"]]))}
