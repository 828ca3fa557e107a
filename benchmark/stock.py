"""The stock-script path: `python examples/<script>` in-process, with the one
GlobalValue flip, and what a configuration says of the program it lifts to.
`run_main` is copied from `chip_smoke.py` (`run_stock_script`) so that a later
change to that file cannot move the benchmark; the original is listed in PERF.md
for a later PR to fold in.  Nothing here knows an engine: a deployment's exit
criterion is `criterion(out)` of its reference module, its loop and horizon are
described by its configuration file.
"""

from __future__ import annotations

import importlib.util
import os
import time
from unittest import mock

JAX_ENGINE = "--SimulatorImplementationType=tpudes::JaxSimulatorImpl"


def load_example(root: str, script: str):
    """`examples/<script>` as a module (hyphenated names do not import)."""
    path = os.path.join(root, "examples", script)
    spec = importlib.util.spec_from_file_location(
        "bench_example_" + script.replace("-", "_").removesuffix(".py"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def script_argv(args: dict, replicas: int) -> list[str]:
    return [f"--{k}={v}" for k, v in args.items()] + [
        f"--JaxReplicas={replicas}", JAX_ENGINE,
    ]


def run_main(main, argv: list[str]):
    """One study: `main(argv)` between two `reset_world()`; returns `(exit
    code, replicated_result or None, wall seconds of main)`.

    The scripts print and then `Simulator.Destroy()`, which drops the engine's
    result: Destroy is wrapped for the call to keep a reference.  Nothing else
    of the script's path changes.
    """
    from tpudes.core.simulator import Simulator
    from tpudes.core.world import reset_world

    reset_world()  # Simulator and the GlobalValues are process-global
    kept = []
    real_destroy = Simulator.Destroy

    def destroy():
        kept.append(getattr(Simulator.GetImpl(), "replicated_result", None))
        real_destroy()

    with mock.patch.object(Simulator, "Destroy", destroy):
        t0 = time.monotonic()
        rc = main(list(argv))
        wall = time.monotonic() - t0
    reset_world()
    return rc, (kept[-1] if kept else None), wall


def iterations(cfg: dict, outs: list, horizon_s: float) -> float:
    """Iterations of the lifted program's outermost loop behind these results, as
    the configuration's `step_iterations` says to count them: a field of each
    result (`from_result`), so many a simulated second (`per_sim_second`), or so
    many a launch whatever the horizon (`per_launch`)."""
    how = cfg["step_iterations"]
    if "from_result" in how:
        return float(sum(int(o[how["from_result"]]) for o in outs))
    if "per_launch" in how:
        return float(len(outs) * how["per_launch"])
    return float(len(outs) * horizon_s * how["per_sim_second"])
