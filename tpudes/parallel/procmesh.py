"""Multi-process device meshes: ``jax.distributed``-backed scale-out.

ROADMAP item 4(a): the mesh rows used to stop at one host's visible
devices.  This module joins N **CPU processes** into one jax
distributed runtime so the replica and config axes can shard across
them.  It has not been run on more than one chip: the launcher hands
every rank the same environment, so it cannot pin one chip per rank
and :func:`require_one_process_per_chip` refuses instead.

- :func:`init_process_mesh` — ``jax.distributed.initialize`` against a
  local coordinator; afterwards ``jax.devices()`` enumerates EVERY
  process's devices.
- :func:`global_replica_mesh` — a 1-D mesh over the global device set.
  XLA:CPU does **not** implement cross-process computations, so
  :func:`supports_global_computation` gates that path and CI
  exercises the **process-sliced** contract below.
- **Process-sliced axes** (:func:`process_slice`): replica/config axes
  split into contiguous per-process blocks.  The engines' randomness is
  pure in the *global* replica index (``fold_in(key, r)`` — the PR-4
  bucketing contract), so a process running its block with the global
  offset (e.g. ``run_wired(..., replica_offset=lo)``) computes
  bit-identical rows to the corresponding slice of one big launch; the
  config axis needs no offset at all (points are explicit operands, and
  the PR-5 sweep contract makes any split bit-equal).  The serving
  layer routes coalesced batches across member processes exactly this
  way (:mod:`tpudes.serving.distributed`).
- :func:`launch_process_mesh` — spawn N local processes wired with the
  :mod:`tpudes.parallel.mpi` control fabric AND a shared
  ``jax.distributed`` coordinator; each runs
  ``worker(pmesh, *args)`` and results gather like
  :func:`LaunchDistributed`.
"""

from __future__ import annotations

import os
import socket
from dataclasses import dataclass

__all__ = [
    "ProcessMesh",
    "global_replica_mesh",
    "init_process_mesh",
    "launch_process_mesh",
    "process_slice",
    "require_one_process_per_chip",
    "supports_global_computation",
]


def held_accelerator() -> str | None:
    """Platform of the non-CPU jax backend THIS process has already
    initialised, else None.  Never initialises a backend itself — a
    launcher that asked ``jax.default_backend()`` would take the chip
    its children need."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return None
    # jax has no public "is a backend up yet" query; this is the one
    # jax.distributed.initialize itself checks
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    platform = jax.default_backend()
    return None if platform == "cpu" else platform


def require_one_process_per_chip(what: str, n_device_procs: int,
                                 env: dict | None = None) -> None:
    """Raise at once when spawning ``n_device_procs`` processes that
    each run a device engine would leave one of them without a chip —
    instead of letting the child fail or hang to the launch timeout.

    A chip belongs to one process: a parent that has initialised a
    non-CPU backend holds it, and ranks that all inherit the same
    environment all ask for the same chip(s).  Processes pinned to CPU
    (``JAX_PLATFORMS=cpu`` in ``env`` or inherited) share the host
    freely — the only configuration these launchers have been run in.
    """
    platforms = (env or {}).get(
        "JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", "")
    )
    if platforms.strip().lower() == "cpu":
        return
    held = held_accelerator()
    if held is not None:
        raise RuntimeError(
            f"{what}: this process has already initialised the {held!r} "
            "jax backend and holds its chip(s); a child process that "
            "needs the device would fail or hang.  Launch from a process "
            "that has not touched jax, or pin the children to CPU "
            "(JAX_PLATFORMS=cpu)."
        )
    if n_device_procs > 1:
        raise RuntimeError(
            f"{what}: {n_device_procs} processes would initialise the "
            "default jax backend from one shared environment; nothing "
            "pins one chip per rank, so on an accelerator host they "
            "contend for the same chip(s).  Only CPU member processes "
            "have been run: set JAX_PLATFORMS=cpu."
        )


@dataclass(frozen=True)
class ProcessMesh:
    """One process's view of the N-process device runtime."""

    process_id: int
    num_processes: int
    coordinator_address: str

    def slice_bounds(self, n: int) -> tuple[int, int]:
        """This process's contiguous block of an ``n``-long axis."""
        return process_slice(n, self.num_processes, self.process_id)


def process_slice(n: int, num_processes: int, process_id: int
                  ) -> tuple[int, int]:
    """Balanced contiguous split of an ``n``-long axis: the first
    ``n % num_processes`` blocks carry one extra element."""
    n, k, p = int(n), int(num_processes), int(process_id)
    base, extra = divmod(n, k)
    lo = p * base + min(p, extra)
    return lo, lo + base + (1 if p < extra else 0)


def supports_global_computation() -> bool:
    """True when the active backend is not XLA:CPU, which raises
    ``Multiprocess computations aren't implemented`` for ONE
    computation over a multi-process mesh — CI uses the process-sliced
    contract there instead.  (The accelerator side has not been run.)"""
    import jax

    return jax.default_backend() != "cpu"


def init_process_mesh(coordinator_address: str, num_processes: int,
                      process_id: int) -> ProcessMesh:
    """Join this process into the distributed jax runtime (idempotent
    per process).  After this call ``jax.device_count()`` counts every
    member process's devices while ``jax.local_device_count()`` stays
    local — the invariant the procmesh smoke test pins."""
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=int(num_processes),
        process_id=int(process_id),
    )
    # force backend construction NOW: the global topology exchange
    # blocks every member's first jax op until ALL members registered
    # their local devices — a member that defers its first jax touch
    # (e.g. straight into a blocking serve loop) would deadlock the
    # whole mesh for the key-value timeout
    jax.devices()
    return ProcessMesh(int(process_id), int(num_processes),
                       coordinator_address)


def global_replica_mesh(axis: str = "replica"):
    """1-D mesh over the GLOBAL device set (every member process).  On
    CPU it constructs (device enumeration works) but executing a
    computation over it raises — gate with
    :func:`supports_global_computation`."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), (axis,))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _procmesh_main(rank: int, size: int, port: int, env: dict, worker,
                   args: tuple):
    # the spawned child may inherit a parent's virtual-device XLA flag
    # overrides; apply the launcher's env pins before jax initializes
    for k, v in env.items():
        os.environ[k] = v
    pmesh = init_process_mesh(f"127.0.0.1:{port}", size, rank)
    return worker(pmesh, *args)


def launch_process_mesh(worker, num_processes: int, args: tuple = (),
                        timeout_s: float = 300.0, env: dict | None = None):
    """Run ``worker(pmesh, *args)`` in ``num_processes`` spawned local
    processes sharing one ``jax.distributed`` coordinator plus the
    all-to-all :class:`~tpudes.parallel.mpi.MpiInterface` control
    pipes; returns the per-process results in rank order.  Every rank
    gets the same ``env``; see :func:`require_one_process_per_chip`."""
    from tpudes.parallel.mpi import LaunchDistributed

    require_one_process_per_chip(
        "launch_process_mesh", num_processes, env
    )
    port = _free_port()
    return LaunchDistributed(
        _procmesh_main,
        num_processes,
        args=(port, dict(env or {}), worker, args),
        timeout_s=timeout_s,
    )
