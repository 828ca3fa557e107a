"""Device-mesh execution: replica sharding + LBTS window grants.

The distributed-communication layer of the framework (SURVEY.md §2.3,
§5.8): where the reference used MPI (allgather LBTS reduction, Isend
packet transport), the TPU build uses XLA collectives over ICI:

- replica (Monte-Carlo) axis sharded over the mesh with ``shard_map``
  — the DP analog; each device runs R/D replicas of the window kernel;
- the conservative window grant = ``jax.lax.pmin`` over per-shard
  next-event times + lookahead — the GrantedTimeWindow allgather
  (SURVEY.md §3.3) as one ICI collective;
- cross-shard statistics via ``jax.lax.psum``.

Multi-host (DCN) ranks reuse the same code: jax initializes a global
mesh across hosts and the collectives ride DCN automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudes.parallel.kernels import WindowParams


def replica_mesh(n_devices: int | None = None, axis: str = "replica") -> Mesh:
    """1-D mesh over all (or the first n) local devices."""
    devices = jax.devices() if n_devices is None else jax.devices()[:n_devices]
    import numpy as np

    return Mesh(np.array(devices), (axis,))


def lbts_grant(next_event_ts: jax.Array, lookahead_ticks) -> jax.Array:
    """Lower-bound-on-timestamp grant inside a shard_map region:
    pmin over every shard's earliest pending event + lookahead
    (DistributedSimulatorImpl's allgather reduction as one collective)."""
    return jax.lax.pmin(next_event_ts, "replica") + lookahead_ticks


def sharded_window_step(mesh: Mesh, params: WindowParams = WindowParams()):
    """Build the mesh-sharded multi-replica window step.

    Input arrays carry a leading replica axis sharded over the mesh;
    per-shard the kernel vmaps over its local replicas, then a psum
    aggregates delivered-frame counts — one ICI collective per window,
    exactly the reference's per-window MPI traffic pattern.

    Returns ``step(positions, tx_active, mode_idx, frame_bytes, keys,
    next_ts, lookahead) -> (ok, sinr, delivered_total, grant)``.
    """
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("replica"), P("replica"), P("replica"), P("replica"),
                  P("replica"), P("replica"), P()),
        out_specs=(P("replica"), P("replica"), P(), P()),
        check_vma=False,
    )
    def step(positions, tx_active, mode_idx, frame_bytes, keys, next_ts, lookahead):
        from tpudes.parallel.kernels import replicated

        ok, sinr, _ = replicated()(
            positions, tx_active, mode_idx, frame_bytes, keys, params
        )
        delivered = jax.lax.psum(jnp.sum(ok, dtype=jnp.int32), "replica")
        grant = lbts_grant(jnp.min(next_ts), lookahead[0])
        return ok, sinr, delivered, grant

    return step


def make_replica_batch(n_replicas: int, n_nodes: int, seed: int = 0, spread: float = 50.0):
    """Synthetic replica batch (shared topology, per-replica keys) for
    benches and dry runs."""
    key = jax.random.PRNGKey(seed)
    k_pos, k_keys = jax.random.split(key)
    positions = jax.random.uniform(
        k_pos, (n_nodes, 3), minval=0.0, maxval=spread
    ).at[:, 2].set(0.0)
    positions = jnp.broadcast_to(positions, (n_replicas, n_nodes, 3))
    # fold_in-derived rows (runtime.replica_keys): replica r's key is
    # independent of n_replicas, so growing the batch never reshuffles
    # existing replicas' draws (KEY001; split(k, n) rows depend on n)
    from tpudes.parallel.runtime import replica_keys

    keys = replica_keys(k_keys, n_replicas)
    tx_active = jnp.zeros((n_replicas, n_nodes), dtype=bool).at[:, 0].set(True)
    mode_idx = jnp.zeros((n_replicas, n_nodes), dtype=jnp.int32)
    frame_bytes = jnp.full((n_replicas, n_nodes), 1000.0, dtype=jnp.float32)
    return positions, tx_active, mode_idx, frame_bytes, keys


def shard_leading_axis(mesh: Mesh, *arrays, axis: str = "replica"):
    """Place arrays with their leading axis sharded over the mesh."""
    sharding = NamedSharding(mesh, P(axis))
    return tuple(jax.device_put(a, sharding) for a in arrays)
