"""Multi-process device meshes (ISSUE-9, ROADMAP item 4(a)).

``jax.distributed``-backed scale-out: N local CPU processes join one
coordinator, the replica axis splits into contiguous per-process
blocks that are BIT-equal to the single-launch rows, and the serving
layer routes coalesced batches across member processes.
"""

import numpy as np
import pytest

import jax

import _distributed_targets as targets

from tpudes.parallel.procmesh import (
    ProcessMesh,
    launch_process_mesh,
    process_slice,
    supports_global_computation,
)


# --- slicing math (pure host) ----------------------------------------------


def test_process_slice_balanced_cover():
    for n in (1, 5, 8, 13):
        for k in (1, 2, 3, 4):
            slices = [process_slice(n, k, p) for p in range(k)]
            # contiguous cover of [0, n)
            assert slices[0][0] == 0 and slices[-1][1] == n
            for (a, b), (c, d) in zip(slices, slices[1:]):
                assert b == c
            sizes = [hi - lo for lo, hi in slices]
            assert max(sizes) - min(sizes) <= 1


def test_process_mesh_slice_bounds():
    pm = ProcessMesh(1, 2, "127.0.0.1:1")
    assert pm.slice_bounds(5) == (3, 5)


def test_supports_global_computation_gates_cpu():
    # the test harness pins the CPU backend
    assert supports_global_computation() is False


# --- one process per chip (no processes spawned) ---------------------------


def test_device_launchers_refuse_at_once_without_a_chip_each(monkeypatch):
    """Every launcher whose children run device engines raises
    IMMEDIATELY (no spawn, no wait to the launch timeout) when told the
    parent already holds an accelerator backend — and, with no chip
    held, when several unpinned ranks would contend for one.  CPU-pinned
    members (the configuration CI runs) pass the check."""
    import time

    from tpudes.chaos.scenario import run_scenario
    from tpudes.parallel import procmesh
    from tpudes.parallel.hybrid import run_hybrid
    from tpudes.parallel.wired import wired_chain
    from tpudes.serving import ProcessRouter

    prog = wired_chain(n_links=4, n_flows=2, n_slots=100, ranks=2)
    launches = [
        lambda: launch_process_mesh(targets.procmesh_devices, 1),
        lambda: run_hybrid(prog, jax.random.key(0), transport="mpi"),
        lambda: run_scenario(seed=0, procs=2),
        lambda: ProcessRouter({1: object()}),
    ]
    # the children would inherit an environment that is not CPU-pinned
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(procmesh, "held_accelerator", lambda: "tpu")
    t0 = time.monotonic()
    for launch in launches:
        with pytest.raises(RuntimeError, match="holds its chip"):
            launch()
    monkeypatch.setattr(procmesh, "held_accelerator", lambda: None)
    for launch in launches[1:]:  # the multi-rank ones
        with pytest.raises(RuntimeError, match="one chip per rank"):
            launch()
    assert time.monotonic() - t0 < 5.0, "a refusal must not wait"
    procmesh.require_one_process_per_chip(
        "cpu members", 4, env={"JAX_PLATFORMS": "cpu"}
    )
    # this (CPU) test process holds no accelerator
    monkeypatch.undo()
    assert procmesh.held_accelerator() is None


# --- 2-process jax.distributed smoke ---------------------------------------


@pytest.mark.slow
def test_two_process_mesh_global_devices():
    outs = launch_process_mesh(targets.procmesh_devices, 2,
                               timeout_s=240.0)
    assert [o["process_id"] for o in outs] == [0, 1]
    for o in outs:
        assert o["num_processes"] == 2
        # the invariant: global devices = sum of members' local devices
        assert o["global_devices"] == 2 * o["local_devices"]
        assert o["backend"] == "cpu"


@pytest.mark.slow
def test_replica_blocks_bit_equal_to_single_launch():
    """Each member runs its block at the global offset; the stitched
    rows equal one big launch (fold_in purity in the global index)."""
    from tpudes.parallel.wired import run_wired, wired_chain

    R = 5
    outs = launch_process_mesh(
        targets.procmesh_replica_slice, 2, args=(R,), timeout_s=240.0
    )
    assert [(o["lo"], o["hi"]) for o in outs] == [(0, 3), (3, 5)]
    stitched = np.concatenate([o["deliver"] for o in outs], axis=0)
    prog = wired_chain(n_links=4, n_flows=2, n_slots=300, jitter_slots=3)
    ref = run_wired(prog, jax.random.key(11), replicas=R)
    assert (stitched == ref["deliver_slot"]).all()


# --- serving router --------------------------------------------------------


@pytest.mark.slow
def test_study_server_routes_batches_across_processes():
    """A coalesced batch's config points split across the mesh: block 0
    local, the rest over the framed pipes to serve_studies members —
    reassembled bit-equal to solo launches."""
    outs = launch_process_mesh(
        targets.procmesh_serving_router, 2, args=(4,), timeout_s=300.0
    )
    rank0, rank1 = outs
    assert rank0["equal"], "routed results diverged from solo launches"
    assert rank0["routed_batches"] >= 1
    assert rank0["routed_points"] >= 1
    assert rank1["served"] >= 1


# --- router unit behavior (no processes) -----------------------------------


def test_router_declines_unroutable_batches():
    from tpudes.serving import ProcessRouter

    router = ProcessRouter({})
    assert router.launch([], [1, 2]) is None  # no members

    class _Desc:
        spec = None

    class _Req:
        desc = _Desc()

    router2 = ProcessRouter({1: object()})
    # spec-less study stays host-local
    assert router2.launch([_Req()], [1, 2]) is None
    # single-point batches are not worth splitting
    assert router2.launch([_Req()], [1]) is None


def test_closed_router_never_routes():
    from tpudes.serving import ProcessRouter

    router = ProcessRouter({})
    router.close()
    assert router._closed
    assert router.launch([], [1, 2]) is None
