"""The stock-script path: `python examples/<script>` in-process, with the one
GlobalValue flip.  Copied from `chip_smoke.py` (`run_stock_script`,
`_script_criterion`) so that a later change to that file cannot move the
benchmark; the original is listed in PERF.md for a later PR to fold in.
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


def criterion(kind: str, out: dict) -> str | None:
    """The script's own exit criterion restated on the result: None where it
    holds, else what failed."""
    import numpy as np

    if kind == "bss":
        if not (out["all_done"] and np.asarray(out["srv_rx"]).mean() > 0):
            return "all_done and srv_rx.mean() > 0"
    elif kind == "lte_sm":
        if not np.asarray(out["rx_bits"]).sum() > 0:
            return "aggregate DL Mbps > 0"
    else:
        return f"no exit criterion recorded for kind {kind!r}"
    return None
