"""The shared engine runtime (tpudes/parallel/runtime.py): LRU runner
cache, shape bucketing with exact mask correctness, warm-call compile
guarantees for every device engine, and the persistent-cache wiring.

The compile-count assertions are the PR-4 recompile-regression gates:
back-to-back identical calls compile exactly once per engine, and a
horizon×replica sweep compiles one program per replica *bucket* (the
horizon is a traced operand, so it never forces a recompile at all).
"""

import dataclasses

import jax
import numpy as np
import pytest

from tpudes.obs.device import CompileTelemetry
from tpudes.parallel.runtime import (
    RUNTIME,
    EngineRuntime,
    bucket_replicas,
    configure_persistent_cache,
    pow2_bucket,
    replica_keys,
    step_keys,
)


@pytest.fixture(autouse=True)
def _fresh_runtime():
    RUNTIME.clear()
    CompileTelemetry.reset()
    yield
    RUNTIME.clear()


# --- program fixtures: the shared synthetic builders (also what
# bench.bench_mesh runs, so bench and tests cannot drift apart) ----------


def _lte_prog(n_ttis=60):
    from tpudes.parallel.programs import toy_lte_program

    return toy_lte_program(n_enb=2, n_ue=4, n_ttis=n_ttis)


def _tcp_prog(n_slots=250):
    from tpudes.parallel.programs import toy_dumbbell_program

    return toy_dumbbell_program(n_flows=3, n_slots=n_slots)


def _as_prog():
    from tpudes.parallel.programs import toy_as_program

    return toy_as_program(n_nodes=64, n_flows=3)


def _bss_prog():
    from tpudes.parallel.programs import toy_bss_program

    return toy_bss_program(n_sta=4, sim_end_us=60_000)


# --- LRU cache semantics (the replicated.py eviction regression) --------


class TestEngineRuntimeLRU:
    def test_hit_refreshes_eviction_order(self):
        """The pre-runtime per-engine dicts popped the insertion-oldest
        entry, so a HOT entry could be evicted while a stale one
        survived.  True LRU: a hit moves the entry to the back."""
        rt = EngineRuntime(capacity=2)
        rt.runner("e", ("a",), lambda: "A")
        rt.runner("e", ("b",), lambda: "B")
        rt.runner("e", ("a",), lambda: "A2")       # hit: refresh "a"
        rt.runner("e", ("c",), lambda: "C")        # evicts "b", NOT "a"
        val, compiled = rt.runner("e", ("a",), lambda: "A3")
        assert val == "A" and not compiled         # hot entry survived
        _, compiled_b = rt.runner("e", ("b",), lambda: "B2")
        assert compiled_b                          # stale entry evicted

    def test_miss_reports_compiled_new_once(self):
        rt = EngineRuntime()
        _, first = rt.runner("e", (1,), lambda: object())
        _, second = rt.runner("e", (1,), lambda: object())
        assert first and not second
        assert rt.stats()["hits"] == 1 and rt.stats()["misses"] == 1

    def test_per_engine_size_and_clear(self):
        rt = EngineRuntime()
        rt.runner("x", (1,), lambda: 1)
        rt.runner("x", (2,), lambda: 2)
        rt.runner("y", (1,), lambda: 3)
        assert rt.size("x") == 2 and rt.size("y") == 1 and rt.size() == 3
        rt.clear("x")
        assert rt.size("x") == 0 and rt.size("y") == 1


# --- bucketing policy ---------------------------------------------------


def test_pow2_bucket_and_mesh_rounding():
    assert [pow2_bucket(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    assert bucket_replicas(None) is None
    assert bucket_replicas(5) == 8
    from tpudes.parallel.mesh import replica_mesh

    mesh3 = replica_mesh(3)
    # pow2 first, then rounded up to a multiple of the mesh size so the
    # sharded axis always divides evenly
    assert bucket_replicas(5, mesh3) == 9


def test_bucketing_env_kill_switch(monkeypatch):
    monkeypatch.setenv("TPUDES_BUCKETING", "0")
    assert bucket_replicas(5) == 5


def test_replica_keys_rows_independent_of_padding():
    """Row i of replica_keys(key, n) must not depend on n — the whole
    exactness argument for replica bucketing rests on this (and it is
    FALSE for jax.random.split, which is why the engines don't use it
    for the replica axis)."""
    k = jax.random.PRNGKey(3)
    a = np.asarray(replica_keys(k, 5))
    b = np.asarray(replica_keys(k, 8))
    np.testing.assert_array_equal(a, b[:5])


@pytest.mark.parametrize("how", ["jit", "while", "vmap"])
@pytest.mark.parametrize("n", [1, 3, 512, 1500])  # 1500: more than one block
@pytest.mark.parametrize("t", [0, 1, 1958, 2**31 - 1])
def test_step_keys_equal_the_scalar_fold(t, n, how, scalar_step_keys):
    """Row r of ``step_keys(engine, key, t, n)`` is
    ``fold_in(fold_in(key, t), r)`` bit for bit, whatever carries the
    counter: a jitted argument, the carry of a ``while_loop`` (every
    engine's case), or a config-axis ``vmap`` that batches the counter
    and not the key (``build_bss_advance``'s sweeps)."""
    import jax.numpy as jnp

    key = jax.random.PRNGKey(36)
    counters = np.asarray([t, t // 2, 7] if how == "vmap" else [t], np.int32)
    if how == "jit":
        got = jax.jit(lambda k, c: step_keys("toy", k, c, n))(
            key, counters[0]
        )[None]
    elif how == "while":
        # one iteration, its counter the loop's carry as in the engines
        got = jax.jit(
            lambda k, c0: jax.lax.while_loop(
                lambda c: c[0] == c0,
                lambda c: (c[0] + 1, step_keys("toy", k, c[0], n)),
                (c0, jnp.zeros((n, 2), jnp.uint32)),
            )
        )(key, counters[0])[1][None]
    else:
        got = jax.jit(
            jax.vmap(lambda k, c: step_keys("toy", k, c, n), (None, 0))
        )(key, counters)
    got = np.asarray(got)
    for rows, c in zip(got, counters):
        # the form the loops wrote by hand, and its definition row by
        # row at both ends of the replica axis
        np.testing.assert_array_equal(
            rows, np.asarray(scalar_step_keys("toy", key, c, n))
        )
        k = jax.random.fold_in(key, c)
        for r in {0, n - 1}:
            np.testing.assert_array_equal(
                rows[r], np.asarray(jax.random.fold_in(k, r))
            )


# --- warm-call guarantee: repeat-call compile count == 1 per engine -----


def test_lte_sm_warm_call_compiles_once():
    from tpudes.parallel.lte_sm import run_lte_sm

    prog = _lte_prog()
    a = run_lte_sm(prog, jax.random.PRNGKey(0), replicas=3)
    b = run_lte_sm(prog, jax.random.PRNGKey(0), replicas=3)
    assert CompileTelemetry.compiles("lte_sm") == 1
    np.testing.assert_array_equal(a["rx_bits"], b["rx_bits"])


def test_as_flows_warm_call_compiles_once():
    from tpudes.parallel.as_flows import run_as_flows

    prog = _as_prog()
    a = run_as_flows(prog, jax.random.PRNGKey(0), replicas=3)
    b = run_as_flows(prog, jax.random.PRNGKey(0), replicas=3)
    assert CompileTelemetry.compiles("as_flows") == 1
    np.testing.assert_array_equal(
        np.asarray(a["goodput_bps"]), np.asarray(b["goodput_bps"])
    )


def test_bss_warm_call_compiles_once():
    from tpudes.parallel.replicated import run_replicated_bss

    prog = _bss_prog()
    a = run_replicated_bss(prog, 3, jax.random.PRNGKey(0))
    b = run_replicated_bss(prog, 3, jax.random.PRNGKey(0))
    assert CompileTelemetry.compiles("bss") == 1
    assert a["all_done"]
    np.testing.assert_array_equal(a["srv_rx"], b["srv_rx"])


def test_dumbbell_warm_call_compiles_once():
    from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

    prog = _tcp_prog()
    a = run_tcp_dumbbell(prog, jax.random.PRNGKey(0), replicas=3)
    b = run_tcp_dumbbell(prog, jax.random.PRNGKey(0), replicas=3)
    assert CompileTelemetry.compiles("dumbbell") == 1
    np.testing.assert_array_equal(
        np.asarray(a["delivered"]), np.asarray(b["delivered"])
    )


# --- shape bucketing: sweeps hit the cache, results stay exact ----------


@pytest.mark.slow  # ISSUE-21 tier-1 budget: the multi-device CI step runs the full file
def test_horizon_replica_sweep_compiles_per_bucket():
    """5 nearby horizons × 3 replica counts = 15 points; horizons are a
    traced operand (zero programs) and the replica counts {3, 4, 6}
    land in buckets {4, 8} — so the sweep compiles exactly 2 programs,
    not 15."""
    from tpudes.parallel.lte_sm import run_lte_sm

    base = _lte_prog()
    replica_counts = (3, 4, 6)
    buckets = {bucket_replicas(r) for r in replica_counts}
    for n_ttis in (50, 55, 60, 61, 70):
        for r in replica_counts:
            run_lte_sm(
                dataclasses.replace(base, n_ttis=n_ttis),
                jax.random.PRNGKey(1),
                replicas=r,
            )
    assert CompileTelemetry.compiles("lte_sm") == len(buckets) == 2
    assert RUNTIME.size("lte_sm") == 2


@pytest.mark.slow  # ISSUE-21 tier-1 budget: the multi-device CI step runs the full file
def test_eight_point_sweep_compiles_at_most_four():
    """The PR-4 acceptance gate: an 8-point horizon×replica sweep used
    to compile 8 programs (every (n_slots, replicas) pair was a cache
    key); it must now compile ≤ 4."""
    from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

    base = _tcp_prog()
    points = [
        (200, 2), (220, 2), (240, 3), (260, 3),
        (280, 4), (300, 5), (320, 6), (340, 8),
    ]
    for n_slots, r in points:
        run_tcp_dumbbell(
            dataclasses.replace(base, n_slots=n_slots),
            jax.random.PRNGKey(0),
            replicas=r,
        )
    assert CompileTelemetry.compiles("dumbbell") <= 4
    assert RUNTIME.size("dumbbell") <= 4


def test_bucketed_results_equal_unbucketed_exactly(monkeypatch):
    """Mask correctness: padding the replica axis to a bucket must not
    change any real replica's outcome, bit for bit (per-replica fold_in
    keying makes each replica's stream independent of the padded axis
    size).  A/B via the TPUDES_BUCKETING kill switch."""
    from tpudes.parallel.lte_sm import run_lte_sm
    from tpudes.parallel.replicated import run_replicated_bss
    from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

    lte, tcp, bss = _lte_prog(), _tcp_prog(), _bss_prog()
    key = jax.random.PRNGKey(9)
    on = {
        "lte": run_lte_sm(lte, key, replicas=5),
        "tcp": run_tcp_dumbbell(tcp, key, replicas=5),
        "bss": run_replicated_bss(bss, 5, key),
    }
    monkeypatch.setenv("TPUDES_BUCKETING", "0")
    RUNTIME.clear()
    off = {
        "lte": run_lte_sm(lte, key, replicas=5),
        "tcp": run_tcp_dumbbell(tcp, key, replicas=5),
        "bss": run_replicated_bss(bss, 5, key),
    }
    np.testing.assert_array_equal(on["lte"]["rx_bits"], off["lte"]["rx_bits"])
    np.testing.assert_array_equal(on["lte"]["ok"], off["lte"]["ok"])
    np.testing.assert_array_equal(
        np.asarray(on["tcp"]["delivered"]), np.asarray(off["tcp"]["delivered"])
    )
    np.testing.assert_array_equal(
        np.asarray(on["tcp"]["cwnd_final"]), np.asarray(off["tcp"]["cwnd_final"])
    )
    for k in ("srv_rx", "cli_rx", "tx_data", "drops"):
        np.testing.assert_array_equal(on["bss"][k], off["bss"][k])
    # and the sliced shapes advertise the REQUESTED replica count
    assert on["lte"]["rx_bits"].shape[0] == 5
    assert np.asarray(on["tcp"]["delivered"]).shape[0] == 5
    assert on["bss"]["srv_rx"].shape[0] == 5


def test_bss_max_steps_is_traced_not_baked():
    """max_steps sweeps share one executable (it is a while_loop bound
    operand, not a compile-time constant)."""
    from tpudes.parallel.replicated import run_replicated_bss

    prog = _bss_prog()
    outs = [
        run_replicated_bss(prog, 3, jax.random.PRNGKey(0), max_steps=m)
        for m in (30_000, 40_000, 50_000)
    ]
    assert CompileTelemetry.compiles("bss") == 1
    # a bound the run never hits cannot change the outcome
    np.testing.assert_array_equal(outs[0]["srv_rx"], outs[2]["srv_rx"])


# --- persistent compilation cache wiring --------------------------------


def test_persistent_cache_config(tmp_path, monkeypatch):
    """The cache directory is placed from outside: with
    JAX_COMPILATION_CACHE_DIR set the code sets NO directory (jax reads
    the variable itself); unset, an accelerator backend gets the fixed
    in-checkout path and XLA:CPU (this suite) stays uncached, so
    tier-1 leaves nothing in the checkout."""
    import os

    old = jax.config.jax_compilation_cache_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert configure_persistent_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "sentinel"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert configure_persistent_cache() is None
        assert jax.config.jax_compilation_cache_dir == "sentinel"
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        fixed = os.path.join(repo, ".jax_cache")
        assert configure_persistent_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
