#!/usr/bin/env python3
"""lte_lane_sweep.py — time the two lowerings of the LTE TTI step at
each lane count, on one chip (ROADMAP A1(a); the table of PERF.md
section 6, PR 31, and the numbers behind ``lte_sm.SM_KERNEL_MAX_LANES``).

    python3 tools/lte_lane_sweep.py [--lanes 1,2,4,...] [--sim-s 10]
        [--profile 1,8,64] [--out chiprun_out/lte_lane_sweep.json]

The ``lte.mc`` program (``examples/lena-simple.py --nEnbs=7
--uesPerCell=30`` through ``JaxSimulatorImpl``) is launched with
``run_lte_sm`` at every lane count (1 = ``replicas=None``) under
``TPUDES_PALLAS=1`` and ``=0``: one warm-up launch (compile), then
``--reps`` timed launches of ``--sim-s`` simulated seconds, call to
numpy on the host; the per-TTI time is the best wall over the TTIs.
For the lane counts of ``--profile`` one more launch runs under
``jax.profiler`` and is reduced by the benchmark's own
``benchmark/trace.py`` (``step_us`` = the outermost ``while`` per TTI,
and the device operations by self time).  Refuses to run without a
TPU: a time from a CPU run says nothing about either lowering.  The
last stdout line is the JSON table.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

LOWERINGS = {"mosaic": "1", "xla": "0"}


def lifted_program():
    """The ``lena-hex7x30`` program as the benchmark's ``lte.mc`` lifts it."""
    from benchmark import stock

    main = stock.load_example(ROOT, "lena-simple.py").main
    args = dict(nEnbs=7, uesPerCell=30, simTime=0.01)
    rc, res, _ = stock.run_main(main, stock.script_argv(args, 2))
    if rc != 0 or res is None or res["kind"] != "lte_sm":
        raise SystemExit(f"lena-simple.py did not lift (exit code {rc})")
    return res["program"]


def profiled_launch(launch, directory: str, n_ttis: int) -> dict:
    import jax

    from benchmark import trace
    from benchmark.run import profiler

    with profiler(directory)():
        with jax.profiler.TraceAnnotation("bench:launch"):
            launch()
    reduced = trace.reduce_trace(trace.load_xplane(directory), top=12)
    shutil.rmtree(directory, ignore_errors=True)
    if reduced is None:
        return {}
    return dict(
        step_us=reduced["while_s"] / n_ttis * 1e6,
        busy_share=reduced["busy_s"] / reduced["window_s"],
        device_ops_us_per_tti=[
            [name, s / n_ttis * 1e6] for name, s in reduced["device_ops"]
        ],
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lanes", default="1,2,4,8,16,32,64,256")
    ap.add_argument("--profile", default="1,8,64")
    ap.add_argument("--sim-s", type=float, default=10.0)
    ap.add_argument("--profile-sim-s", type=float, default=2.0)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/lte_lane_sweep.json")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"lte_lane_sweep: needs a TPU, jax found {device.platform!r}: "
              "refusing to run", file=sys.stderr)
        return 2

    from tpudes.parallel.lte_sm import compiled_step_lowering, run_lte_sm

    base = lifted_program()
    n_ttis = int(round(args.sim_s * 1000))
    prog = dataclasses.replace(base, n_ttis=n_ttis)
    traced = dataclasses.replace(
        base, n_ttis=int(round(args.profile_sim_s * 1000))
    )
    key = jax.random.PRNGKey(31)
    to_profile = {int(x) for x in args.profile.split(",") if x}
    rows = []
    for lanes in (int(x) for x in args.lanes.split(",")):
        replicas = None if lanes == 1 else lanes
        row, bits = {"lanes": lanes}, {}
        for name, flag in LOWERINGS.items():
            with mock.patch.dict(os.environ, {"TPUDES_PALLAS": flag}):
                t0 = time.monotonic()
                run_lte_sm(dataclasses.replace(base, n_ttis=1), key, replicas)
                compile_s = time.monotonic() - t0
                walls = []
                for _ in range(args.reps):
                    t0 = time.monotonic()
                    out = run_lte_sm(prog, key, replicas)
                    walls.append(time.monotonic() - t0)
                bits[name] = np.asarray(out["rx_bits"])
                cell = dict(
                    compiled=compiled_step_lowering(base, key, replicas),
                    compile_s=compile_s, walls_s=walls,
                    us_per_tti=min(walls) / n_ttis * 1e6,
                )
                if lanes in to_profile:
                    cell["profile"] = profiled_launch(
                        lambda: run_lte_sm(traced, key, replicas),
                        os.path.join(ROOT, ".bench_trace", "lane_sweep"),
                        traced.n_ttis,
                    )
            row[name] = cell
            print(json.dumps({"lanes": lanes, name: cell}), flush=True)
        row["bit_equal"] = bool(np.array_equal(bits["mosaic"], bits["xla"]))
        rows.append(row)
    result = dict(
        device=device.device_kind, sim_s=args.sim_s, reps=args.reps,
        rows=rows,
    )
    os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
    with open(os.path.join(ROOT, args.out), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
