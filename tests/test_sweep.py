"""ISSUE 5 gates: config-axis megabatching, async submission, chunked
horizons.

- **One launch, one compile**: an 8-point LTE scheduler sweep and an
  8-point TCP variant sweep each execute as ONE device launch (runtime
  launch counter) paying at most one fresh compile (CompileTelemetry).
- **Unstack exactness**: every config point of a sweep equals the
  per-point launch with the same key BIT for bit — all four engines,
  with bucketing disabled, and on the virtual 8-device mesh.
- **Pipelining**: RUNTIME.submit keeps >= 2 runs in flight (telemetry
  counters) and never exceeds the TPUDES_INFLIGHT window.
- **Chunked horizons**: fixed-size while_loop segments with donated
  carry handoff are bit-identical to single-shot runs for all four
  engines, and stream per-chunk metrics to tpudes.obs.
"""

import dataclasses

import jax
import numpy as np
import pytest

from tpudes.obs.device import ChunkStream, CompileTelemetry
from tpudes.parallel.runtime import RUNTIME

KEY = jax.random.PRNGKey(7)


@pytest.fixture(autouse=True)
def _fresh_runtime():
    RUNTIME.clear()
    CompileTelemetry.reset()
    ChunkStream.reset()
    yield
    RUNTIME.clear()
    ChunkStream.reset()


def _lte_prog(n_ttis=60):
    from tpudes.parallel.programs import toy_lte_program

    return toy_lte_program(n_enb=2, n_ue=4, n_ttis=n_ttis)


def _tcp_prog(n_slots=250):
    from tpudes.parallel.programs import toy_dumbbell_program

    return toy_dumbbell_program(n_flows=3, n_slots=n_slots)


def _as_prog():
    from tpudes.parallel.programs import toy_as_program

    return toy_as_program(n_nodes=64, n_flows=3)


def _bss_prog(sim_end_us=60_000):
    from tpudes.parallel.programs import toy_bss_program

    return toy_bss_program(n_sta=4, sim_end_us=sim_end_us)


# --- one launch / one compile: the acceptance-criteria sweeps -----------


def test_lte_8_point_scheduler_sweep_is_one_launch_one_compile():
    from tpudes.parallel.lte_sm import SM_SCHED_IDS, run_lte_sm

    prog = _lte_prog()
    scheds = list(SM_SCHED_IDS)[:8]
    results = run_lte_sm(prog, KEY, replicas=3, schedulers=scheds)
    assert RUNTIME.launches("lte_sm") == 1
    assert CompileTelemetry.compiles("lte_sm") <= 1
    assert len(results) == 8
    # a repeat sweep is zero fresh compiles, still one launch each
    run_lte_sm(prog, KEY, replicas=3, schedulers=scheds)
    assert RUNTIME.launches("lte_sm") == 2
    assert CompileTelemetry.compiles("lte_sm") <= 1


def test_tcp_8_point_variant_sweep_is_one_launch_one_compile():
    from tpudes.parallel.tcp_dumbbell import VARIANTS, run_tcp_dumbbell

    prog = _tcp_prog()
    points = [[v] * prog.n_flows for v in VARIANTS[:8]]
    results = run_tcp_dumbbell(prog, KEY, replicas=3, variants=points)
    assert RUNTIME.launches("dumbbell") == 1
    assert CompileTelemetry.compiles("dumbbell") <= 1
    assert len(results) == 8
    run_tcp_dumbbell(prog, KEY, replicas=3, variants=points)
    assert RUNTIME.launches("dumbbell") == 2
    assert CompileTelemetry.compiles("dumbbell") <= 1


# --- unstack exactness vs per-point launches ----------------------------


def _assert_point_equal(a: dict, b: dict):
    for k in a:
        if np.asarray(a[k]).dtype == object:  # pragma: no cover
            continue
        np.testing.assert_array_equal(
            np.asarray(a[k]), np.asarray(b[k]), err_msg=f"field {k!r}"
        )


def _sweep_vs_per_point(mesh=None):
    """Every engine: config-axis results == per-point launches, exact."""
    from tpudes.parallel.as_flows import run_as_flows
    from tpudes.parallel.lte_sm import run_lte_sm
    from tpudes.parallel.replicated import run_replicated_bss
    from tpudes.parallel.tcp_dumbbell import (
        _variant_ecn,
        _variant_point,
        run_tcp_dumbbell,
    )

    lte = _lte_prog()
    scheds = ["pf", "rr", "fdmt"]
    sweep = run_lte_sm(lte, KEY, replicas=5, mesh=mesh, schedulers=scheds)
    for i, s in enumerate(scheds):
        point = run_lte_sm(
            dataclasses.replace(lte, scheduler=s), KEY, replicas=5, mesh=mesh
        )
        _assert_point_equal(sweep[i], point)

    tcp = _tcp_prog()
    points = [["TcpNewReno"] * 3, ["TcpCubic"] * 3, ["TcpDctcp"] * 3]
    sweep = run_tcp_dumbbell(tcp, KEY, replicas=5, mesh=mesh, variants=points)
    for i, p in enumerate(points):
        ids = _variant_point(p)
        point = run_tcp_dumbbell(
            dataclasses.replace(tcp, variant_idx=ids, ecn=_variant_ecn(ids)),
            KEY, replicas=5, mesh=mesh,
        )
        _assert_point_equal(sweep[i], point)

    bss = _bss_prog()
    ends = [40_000, 60_000]
    sweep = run_replicated_bss(bss, 5, KEY, mesh=mesh, sim_end_us=ends)
    for i, v in enumerate(ends):
        point = run_replicated_bss(
            dataclasses.replace(bss, sim_end_us=v), 5, KEY, mesh=mesh
        )
        # steps may differ (the sweep runs every point to the slowest
        # point's bound; finished replicas are fixed points) — compare
        # outcomes, not loop iteration counts
        for k in ("srv_rx", "cli_rx", "tx_data", "drops", "all_done"):
            np.testing.assert_array_equal(
                np.asarray(sweep[i][k]), np.asarray(point[k]), err_msg=k
            )

    asp = _as_prog()
    scales = [0.5, 1.0, 2.0]
    sweep = run_as_flows(asp, KEY, replicas=5, mesh=mesh, rate_scale=scales)
    point = run_as_flows(asp, KEY, replicas=5, mesh=mesh)
    if mesh is None:
        _assert_point_equal(sweep[1], point)
    else:
        # the other engines' outcomes are integer counters and stay
        # bit-exact under SPMD; the fluid engine's outcome IS a float
        # chain, and GSPMD partitions the vmapped program differently
        # from the unbatched one (re-rounded fusions) — pin ULP-tight
        for k in point:
            np.testing.assert_allclose(
                np.asarray(sweep[1][k]), np.asarray(point[k]),
                rtol=2e-5, atol=0, err_msg=f"field {k!r}",
            )


def test_sweep_unstacking_matches_per_point_launches():
    _sweep_vs_per_point()


@pytest.mark.slow  # ISSUE-21 tier-1 budget: the multi-device CI step runs the full file
def test_sweep_unstacking_exact_with_bucketing_disabled(monkeypatch):
    monkeypatch.setenv("TPUDES_BUCKETING", "0")
    _sweep_vs_per_point()


@pytest.mark.slow  # ISSUE-21 tier-1 budget: the multi-device CI step runs the full file
def test_sweep_unstacking_exact_on_virtual_mesh():
    from tpudes.parallel.mesh import replica_mesh

    if len(jax.devices()) < 2:  # pragma: no cover - conftest forces 8
        pytest.skip("needs the virtual multi-device mesh")
    _sweep_vs_per_point(mesh=replica_mesh(len(jax.devices())))


# --- async submission ----------------------------------------------------


@pytest.mark.slow  # ISSUE-21 tier-1 budget: the multi-device CI step runs the full file
def test_submit_keeps_at_least_two_in_flight_and_bounds_the_window(
    monkeypatch,
):
    from tpudes.parallel.lte_sm import run_lte_sm

    monkeypatch.setenv("TPUDES_INFLIGHT", "3")
    prog = _lte_prog(n_ttis=40)
    # heterogeneous replica counts -> different buckets -> different
    # executables: the serialized-launch worst case the window pipelines
    futs = [
        RUNTIME.submit(run_lte_sm, prog, KEY, replicas=r)
        for r in (3, 5, 9, 2, 6)
    ]
    results = [f.result() for f in futs]
    stats = RUNTIME.stats()
    assert stats["submitted"] == 5 and stats["retired"] == 5
    assert stats["max_in_flight"] >= 2, (
        "async submission must keep >= 2 runs in flight"
    )
    assert stats["max_in_flight"] <= 3, "TPUDES_INFLIGHT window exceeded"
    assert stats["in_flight"] == 0
    # deferred results are the blocking results, bit for bit
    for fut_res, r in zip(results, (3, 5, 9, 2, 6)):
        blocking = run_lte_sm(prog, KEY, replicas=r)
        _assert_point_equal(fut_res, blocking)


@pytest.mark.slow  # ISSUE-21 tier-1 budget: the multi-device CI step runs the full file
def test_submit_overflow_retires_oldest_first(monkeypatch):
    from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

    monkeypatch.setenv("TPUDES_INFLIGHT", "2")
    prog = _tcp_prog(n_slots=120)
    f1 = RUNTIME.submit(run_tcp_dumbbell, prog, KEY, replicas=2)
    f2 = RUNTIME.submit(run_tcp_dumbbell, prog, KEY, replicas=3)
    f3 = RUNTIME.submit(run_tcp_dumbbell, prog, KEY, replicas=5)
    # the window is 2: submitting f3 must have retired f1 already
    assert f1.done() and f1.result() is f1.result()
    assert RUNTIME.stats()["in_flight"] == 2
    RUNTIME.drain()
    assert RUNTIME.stats()["in_flight"] == 0
    assert f2.result()["delivered"].shape[0] == 3
    assert f3.result()["delivered"].shape[0] == 5


def test_submit_rejects_non_engine_callables():
    with pytest.raises(TypeError):
        RUNTIME.submit(lambda block=True: {"not": "a future"})


def test_poisoned_future_is_retired_not_requeued(monkeypatch):
    """A future whose finalize raises must leave the in-flight window:
    the error surfaces ONCE (at the eviction or result() that hit it),
    not again on every later submit's window drain."""
    from tpudes.parallel.runtime import EngineFuture

    monkeypatch.setenv("TPUDES_INFLIGHT", "1")

    def bad_run(block=True):
        return EngineFuture("x", {}, lambda host: 1 / 0)

    def good_run(block=True):
        return EngineFuture("x", {}, lambda host: "ok")

    RUNTIME.submit(bad_run)
    with pytest.raises(ZeroDivisionError):
        RUNTIME.submit(good_run)  # evicting the poisoned future raises
    fut = RUNTIME.submit(good_run)  # ...but only once: window is clean
    assert fut.result() == "ok"
    RUNTIME.drain()
    assert RUNTIME.stats()["in_flight"] == 0


def test_future_result_is_memoized_and_releases_buffers():
    from tpudes.parallel.as_flows import run_as_flows

    fut = RUNTIME.submit(run_as_flows, _as_prog(), KEY, replicas=3)
    first = fut.result()
    assert fut.result() is first
    assert fut.done()


# --- chunked horizons -----------------------------------------------------


def test_chunked_runs_bit_identical_for_all_four_engines():
    from tpudes.parallel.as_flows import run_as_flows
    from tpudes.parallel.lte_sm import run_lte_sm
    from tpudes.parallel.replicated import run_replicated_bss
    from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

    lte = _lte_prog()
    _assert_point_equal(
        run_lte_sm(lte, KEY, replicas=3),
        run_lte_sm(lte, KEY, replicas=3, chunk_ttis=17),
    )
    # chunking reuses the single-shot executable: no fresh compile
    assert CompileTelemetry.compiles("lte_sm") == 1

    tcp = _tcp_prog()
    _assert_point_equal(
        run_tcp_dumbbell(tcp, KEY, replicas=3),
        run_tcp_dumbbell(tcp, KEY, replicas=3, chunk_slots=64),
    )
    assert CompileTelemetry.compiles("dumbbell") == 1

    bss = _bss_prog()
    a = run_replicated_bss(bss, 3, KEY)
    b = run_replicated_bss(bss, 3, KEY, chunk_steps=10_000)
    for k in ("srv_rx", "cli_rx", "tx_data", "drops", "steps", "all_done"):
        np.testing.assert_array_equal(
            np.asarray(a[k]), np.asarray(b[k]), err_msg=k
        )
    assert CompileTelemetry.compiles("bss") == 1

    asp = _as_prog()
    _assert_point_equal(
        run_as_flows(asp, KEY, replicas=3),
        run_as_flows(asp, KEY, replicas=3, chunk_rounds=1),
    )
    assert CompileTelemetry.compiles("as_flows") == 1


def test_chunk_metrics_stream_to_obs():
    from tpudes.core.global_value import GlobalValue
    from tpudes.parallel.lte_sm import run_lte_sm
    from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

    GlobalValue.Bind("TpudesObs", 1)
    try:
        run_lte_sm(_lte_prog(n_ttis=60), KEY, replicas=3, chunk_ttis=20)
        entries = ChunkStream.entries("lte_sm")
        assert [e["t_end"] for e in entries] == [20, 40, 60]
        # the streamed summaries are cumulative device counters
        oks = [int(np.asarray(e["metrics"]["ok"]).sum()) for e in entries]
        assert oks == sorted(oks)

        run_tcp_dumbbell(_tcp_prog(n_slots=100), KEY, replicas=3,
                         chunk_slots=50)
        t_ends = [e["t_end"] for e in ChunkStream.entries("dumbbell")]
        assert t_ends == [50, 100]
    finally:
        GlobalValue.Bind("TpudesObs", 0)


def test_unchunked_run_streams_nothing():
    """A single-shot run has no chunk stream — even with obs armed
    (the stream is the chunked-horizon progress feed, and a deferred
    fetch here would silently block async submission)."""
    from tpudes.core.global_value import GlobalValue
    from tpudes.parallel.lte_sm import run_lte_sm

    run_lte_sm(_lte_prog(), KEY, replicas=3)
    assert ChunkStream.entries() == []
    GlobalValue.Bind("TpudesObs", 1)
    try:
        run_lte_sm(_lte_prog(), KEY, replicas=3)
    finally:
        GlobalValue.Bind("TpudesObs", 0)
    assert ChunkStream.entries() == []


def test_chunked_async_defers_final_flush_until_result():
    """Under block=False the dispatch must return before the final
    chunk's metrics fetch — the flush rides the future's finalize."""
    from tpudes.core.global_value import GlobalValue
    from tpudes.parallel.lte_sm import run_lte_sm

    GlobalValue.Bind("TpudesObs", 1)
    try:
        fut = run_lte_sm(_lte_prog(n_ttis=60), KEY, replicas=3,
                         chunk_ttis=20, block=False)
        # chunks 1..n-1 streamed inline; the LAST entry arrives only
        # with result()
        assert [e["t_end"] for e in ChunkStream.entries("lte_sm")] == [20, 40]
        fut.result()
        assert [e["t_end"] for e in ChunkStream.entries("lte_sm")] == [
            20, 40, 60,
        ]
    finally:
        GlobalValue.Bind("TpudesObs", 0)
