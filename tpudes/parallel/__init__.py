"""Parallel/TPU execution layer: the windowed engine, fused window
kernels, replica axis, device-mesh collectives, the shared engine
runtime (runner cache / shape bucketing / donation —
tpudes.parallel.runtime), and the host-side distributed (MPI-analog)
engine.

SURVEY.md §2.3, §5.8, §7 steps 4/7 — the reference's MPI machinery maps
here to XLA collectives over the device mesh; the Monte-Carlo RngRun
axis becomes vmap/shard_map over replicas; the space-parallel PDES
(mpi.py / distributed.py) runs over local process ranks.

Importing this package registers ``tpudes::JaxSimulatorImpl`` at the
SimulatorImplementationType seam (one-GlobalValue opt-in, as in
BASELINE.json's north star).

Attribute access is lazy (module ``__getattr__``): the jax-heavy
submodules (engine/kernels/mesh) only load when first touched, so the
jax-free distributed ranks — and any scalar-engine run that merely
imports ``tpudes.parallel.mpi`` — never pay the JAX import.
"""

_LAZY = {
    "BatchableRegistry": ("tpudes.parallel.engine", "BatchableRegistry"),
    "JaxSimulatorImpl": ("tpudes.parallel.engine", "JaxSimulatorImpl"),
    "WindowParams": ("tpudes.parallel.kernels", "WindowParams"),
    "lte_tti_sinr": ("tpudes.parallel.kernels", "lte_tti_sinr"),
    "multi_window_scan": ("tpudes.parallel.kernels", "multi_window_scan"),
    # NOTE: the kernels.replicated vmap factory is NOT aliased here —
    # the name would collide with the tpudes.parallel.replicated
    # submodule (first import wins, making resolution order-dependent);
    # import it from tpudes.parallel.kernels directly
    "wifi_phy_window": ("tpudes.parallel.kernels", "wifi_phy_window"),
    "pallas_enabled": ("tpudes.parallel.kernels_pallas", "pallas_enabled"),
    "RUNTIME": ("tpudes.parallel.runtime", "RUNTIME"),
    "EngineFuture": ("tpudes.parallel.runtime", "EngineFuture"),
    "EngineRuntime": ("tpudes.parallel.runtime", "EngineRuntime"),
    "lbts_grant": ("tpudes.parallel.mesh", "lbts_grant"),
    "make_replica_batch": ("tpudes.parallel.mesh", "make_replica_batch"),
    "replica_mesh": ("tpudes.parallel.mesh", "replica_mesh"),
    "shard_leading_axis": ("tpudes.parallel.mesh", "shard_leading_axis"),
    "sharded_window_step": ("tpudes.parallel.mesh", "sharded_window_step"),
}

# the engine must self-register at the seam when this package is named
# by SimulatorImplementationType — simulator.GetImpl imports us for
# exactly that; keep that path working without importing jax for
# everyone else by registering on first engine access instead
import tpudes.parallel.engine as _engine  # noqa: E402,F401


def __getattr__(name):
    entry = _LAZY.get(name)
    if entry is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    module = importlib.import_module(entry[0])
    value = getattr(module, entry[1])
    globals()[name] = value
    return value
