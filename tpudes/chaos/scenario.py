"""Canonical chaos scenarios: the replayable serving-fleet drills.

Two fixed scenarios, both pure functions of an integer seed (plus the
process count), so ``python -m tpudes.chaos --replay SEED`` can re-run
the exact injected failures and compare recovery telemetry:

- :func:`run_local_scenario` — in-process StudyServer (deterministic
  ``pump`` mode) under seed-planted launch-shaped errors: every study
  must complete via requeue/retry, bit-equal to solo launches.
- :func:`run_scenario` — a spawned serving fleet (rank 0 = StudyServer
  + ProcessRouter, ranks 1.. = ``serve_studies`` members) where the
  schedule SIGKILLs a seed-chosen member mid-coalesced-batch: the
  batch requeues onto the survivors (or the local engine) and every
  study still completes bit-equal.

Both return rank-0's report: ``equal`` (bit-equality vs solo runs),
``completed``, the failure/recovery counters, and the full serving
telemetry snapshot (schema-gated by ``python -m tpudes.obs
--serving``).
"""

from __future__ import annotations

__all__ = ["chaos_serving_rank", "run_local_scenario", "run_scenario"]

#: studies per scenario run (small enough for CI, large enough that a
#: kill lands mid-stream)
N_STUDIES = 6


def _bss_studies(n_studies: int):
    import jax

    from tpudes.parallel.programs import toy_bss_program

    prog = toy_bss_program(n_sta=4, sim_end_us=40_000)
    key = jax.random.PRNGKey(3)
    horizons = [40_000 + 2_000 * i for i in range(n_studies)]
    return prog, key, horizons


def _serve_and_check(server, prog, key, horizons, timeout_s: float,
                     pump_each: bool = False):
    """Submit one BSS study per horizon, pump to completion, and
    compare every result against a solo launch (computed in the same
    process, warm caches).  ``pump_each`` dispatches study-by-study
    (many launches — the local launch-error drill's shape) instead of
    one coalesced batch (the member-kill drill's shape)."""
    import dataclasses

    import numpy as np

    from tpudes.parallel.replicated import run_replicated_bss

    handles = []
    for i, h in enumerate(horizons):
        handles.append(server.submit_study(
            "bss", dataclasses.replace(prog, sim_end_us=h), key, 2,
            tenant=f"t{i}", slo="gold" if i == 0 else "standard",
        ))
        if pump_each:
            server.pump(force=True)
    server.pump(force=True)
    completed = equal = 0
    for h, handle in zip(horizons, handles):
        res = handle.result(timeout=timeout_s)
        completed += 1
        solo = run_replicated_bss(
            dataclasses.replace(prog, sim_end_us=h), 2, key
        )
        if all(
            np.array_equal(np.asarray(res[k]), np.asarray(solo[k]))
            for k in solo
        ):
            equal += 1
    return completed, equal


def run_local_scenario(seed: int, n_studies: int = N_STUDIES) -> dict:
    """In-process drill: seed-planted launch errors against a
    ``start=False`` (deterministic pump) StudyServer.  Same seed →
    same injected failures → same recovery counters."""
    import tpudes.chaos as chaos
    from tpudes.obs.serving import ServingTelemetry
    from tpudes.serving import StudyServer

    prog, key, horizons = _bss_studies(n_studies)
    ServingTelemetry.reset()
    chaos.arm(chaos.canonical_schedule(seed, members=0))
    try:
        with StudyServer(
            start=False, retry_backoff_s=0.005, retry_budget=3,
        ) as server:
            completed, equal = _serve_and_check(
                server, prog, key, horizons, timeout_s=120.0,
                pump_each=True,
            )
            snapshot = server.metrics()
    finally:
        chaos.disarm()
    return dict(
        completed=completed,
        equal=equal == n_studies,
        injected=dict(chaos=snapshot["failures"]["injected_failures"]),
        telemetry=snapshot,
    )


def chaos_serving_rank(rank: int, size: int, seed: int,
                       n_studies: int) -> dict:
    """``LaunchDistributed`` target for the member-kill drill (rank 0
    serves, the rest run :func:`tpudes.serving.serve_studies` under the
    same seed's schedule — the victim SIGKILLs itself mid-batch)."""
    import tpudes.chaos as chaos
    from tpudes.parallel.mpi import MpiInterface

    chaos.arm(chaos.canonical_schedule(seed, members=size - 1))
    if rank != 0:
        from tpudes.serving import serve_studies

        try:
            return dict(
                served=serve_studies(MpiInterface._conns[0],
                                     member_id=rank)
            )
        finally:
            chaos.disarm()
    from tpudes.obs.serving import ServingTelemetry
    from tpudes.serving import ProcessRouter, StudyServer

    prog, key, horizons = _bss_studies(n_studies)
    ServingTelemetry.reset()
    router = ProcessRouter(MpiInterface._conns, member_timeout_s=30.0)
    server = StudyServer(
        max_batch=8, router=router, start=False,
        retry_backoff_s=0.01, retry_budget=3,
    )
    try:
        completed, equal = _serve_and_check(
            server, prog, key, horizons, timeout_s=240.0
        )
        snapshot = server.metrics()
    finally:
        server.close()
        chaos.disarm()
    f = snapshot["failures"]
    return dict(
        completed=completed,
        equal=equal == n_studies,
        requeued=f["requeued_studies"],
        members_lost=f["members_lost"],
        routed_batches=router.routed_batches,
        excluded=sorted(router._dead),
        telemetry=snapshot,
    )


def run_scenario(seed: int, procs: int = 3,
                 n_studies: int = N_STUDIES) -> list:
    """Spawn the fleet drill (rank 0 + ``procs - 1`` members); member
    ranks are optional (the schedule SIGKILLs one).  Returns per-rank
    results (None for the killed member)."""
    from tpudes.parallel.mpi import LaunchDistributed
    from tpudes.parallel.procmesh import require_one_process_per_chip

    require_one_process_per_chip("chaos.run_scenario", procs)
    return LaunchDistributed(
        chaos_serving_rank,
        procs,
        args=(int(seed), int(n_studies)),
        timeout_s=420.0,
        optional_ranks=set(range(1, procs)),
    )
