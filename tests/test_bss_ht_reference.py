"""The HT (802.11n) BSS engine held to the benchmark's plain reference.

``benchmark/references/bss_ht.py`` is the scalar EDCA + A-MPDU + BlockAck
event loop the cell ``wifi_ht.mc`` decides ``correct`` with (it imports
nothing of tpudes and counts retries per MPDU, as the host MAC and
upstream do).  Here, tiny and on the CPU: the device engine against it on
a small saturated HT BSS in every number ``correct`` compares; the
reference against the host DES at the size ``test_replicated_ht`` uses;
the ``tx_mpdus`` counter; and the legacy (``max_mpdus == 1``) program
pinned to what it was before the A-MPDU arm got its per-MPDU retry count.
"""

import copy
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import load_module
from tpudes.parallel.replicated import (
    build_bss_advance,
    lower_bss,
    run_replicated_bss,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIM_TIME = 1.6            # clients start at 1 s: 0.6 sim-s of traffic


REF = load_module(os.path.join(ROOT, "benchmark", "references", "bss_ht.py"))
with open(os.path.join(
        ROOT, "benchmark", "configs", "wifi-bss-ht-64sta.json")) as f:
    CONFIG = json.load(f)


def _lowered(n_stas, radii, interval_s):
    from tpudes.core.world import reset_world
    from tpudes.scenarios import build_bss

    reset_world()
    stas, ap, clients, _ = build_bss(
        n_stas, SIM_TIME, radii=radii, interval_s=interval_s,
        data_mode="HtMcs7", standard="80211n",
    )
    prog = lower_bss(
        [stas.Get(i) for i in range(n_stas)], ap, clients, SIM_TIME
    )
    reset_world()
    return prog


def _config_of(prog):
    """The deployment file's physics with this small program's topology
    and send interval: what the reference is given in the cell, at a
    size a test can hold."""
    cfg = copy.deepcopy(CONFIG)
    cfg["topology"]["positions"] = np.asarray(
        prog.positions, np.float64).tolist()
    cfg["physics"]["interval_us"] = int(prog.interval_us[1])
    return cfg


# --- the engine against the reference ----------------------------------------

N_STAS, REPLICAS = 8, 32
#: 8 stations x one request per 0.5 ms offer 74 Mbit/s to a 65 Mbit/s
#: PHY; half of them sit at 28.5 m, where a subframe decodes a third of
#: the time: BlockAcks come back with holes, MPDUs are retried singly,
#: and one in seventeen fails eight times and is dropped
RADII, INTERVAL_S = (12.0, 28.5), 0.0005

#: |engine - reference| as the cell scales it, and why this much: both
#: sides are 32-replica means of random counts.  Two sound sides (the
#: reference under eight seeds, at this size) read up to 0.031 apart in
#: srv_rx_gap, 0.070 in sta_echo_gap, 0.009 in tx_data_gap and 0.080 in
#: drops_gap (drops are 60% of the PPDU count here, and spread 15% a
#: replica); the tolerance is about twice the largest.  What the cell
#: guards against reads far above: one retry count per node (what the
#: engine had) never drops here, 0.56 in drops_gap; a single-MPDU
#: exchange 0.49 in srv_rx_gap; no retry 37 in drops_gap.
TOLERANCE = {
    "srv_rx_gap": 0.06, "sta_echo_gap": 0.15,
    "tx_data_gap": 0.03, "drops_gap": 0.15,
}


@pytest.fixture(scope="module")
def saturated():
    prog = _lowered(N_STAS, RADII, INTERVAL_S)
    assert prog.max_mpdus == 64 and prog.subframe_bytes == 580
    out = run_replicated_bss(prog, REPLICAS, jax.random.PRNGKey(32))
    assert out["all_done"]
    cfg = _config_of(prog)
    mix = {"horizon_s": SIM_TIME, "reference_replicas": REPLICAS}
    ref = REF.simulate(cfg, SIM_TIME, REPLICAS, seed=32)
    return prog, out, cfg, mix, ref


@pytest.mark.parametrize("number", sorted(TOLERANCE))
def test_engine_agrees_with_the_plain_reference(saturated, number):
    _, out, cfg, mix, ref = saturated
    numbers = REF.compare(cfg, mix, [out], REPLICAS, seed=32, ref=ref)
    assert numbers["rows_missing"] == 0
    assert numbers[number] <= TOLERANCE[number], numbers


def test_the_small_bss_is_saturated_and_retries_single_mpdus(saturated):
    """The test's own premise: queues build, BlockAcks have holes, some
    MPDUs reach the retry limit, on both sides."""
    _, out, cfg, _, ref = saturated
    offered = sum(i > 0 for _, i in REF._arrivals(cfg, int(SIM_TIME * 1e6)))
    for side in (out, ref):
        assert np.mean(side["srv_rx"]) < 0.9 * offered
        assert np.mean(side["drops"]) > 0
        assert np.mean(side["tx_mpdus"]) > 2 * np.mean(side["tx_data"])


def test_tx_mpdus_counts_what_the_ppdus_carried(saturated):
    prog, out, _, _, _ = saturated
    mpdus, ppdus = np.asarray(out["tx_mpdus"]), np.asarray(out["tx_data"])
    assert mpdus.shape == ppdus.shape == (REPLICAS,)
    assert (mpdus >= ppdus).all() and (ppdus > 0).all()
    assert (mpdus <= prog.max_mpdus * ppdus).all()
    # every MPDU decoded or dropped was carried at least once
    carried = (np.asarray(out["srv_rx"]) + np.asarray(out["drops"])
               + np.asarray(out["cli_rx"]).sum(axis=1))
    assert (mpdus >= carried).all()


@pytest.mark.parametrize("fault,number", [
    (dict(max_mpdus=1), "srv_rx_gap"),
    (dict(retry_limit=0), "drops_gap"),
    (dict(precision="matmul_bfloat16"), "drops_gap"),
])
def test_a_faulty_side_reads_three_times_the_tolerance(saturated, fault, number):
    """The tolerance means something: no aggregation, no retry, and the
    received-power sum with bfloat16 operands each fail one number by
    three times what the engine is allowed."""
    _, _, cfg, mix, ref = saturated
    faulty = REF.simulate(cfg, SIM_TIME, REPLICAS, seed=33, **fault)
    numbers = REF.compare(cfg, mix, [faulty], REPLICAS, seed=32, ref=ref)
    assert numbers[number] > 3 * TOLERANCE[number], numbers


# --- the reference against the host DES --------------------------------------

@pytest.mark.parametrize("interval_s", [0.002, 0.001])
def test_reference_agrees_with_the_host_des_at_four_stations(interval_s):
    """The scalar DES with the whole MAC (association, ADDBA, per-MPDU
    BlockAck bookkeeping) against the plain reference on the 4-station
    ring of ``test_replicated_ht``: the mean count of requests decoded
    at the server within 10% + 2 frames, that test's own pin (the host
    MAC spends its first exchanges on association and ADDBA)."""
    from tpudes.core import Seconds, Simulator
    from tpudes.core.rng import RngSeedManager
    from tpudes.core.world import reset_world
    from tpudes.scenarios import build_bss

    counts = []
    for run in (1, 2, 3):
        reset_world()
        RngSeedManager.SetRun(run)
        _, _, _, rx = build_bss(
            4, SIM_TIME, radii=(16.0,), interval_s=interval_s,
            data_mode="HtMcs7", standard="80211n",
        )
        Simulator.Stop(Seconds(SIM_TIME))
        Simulator.Run()
        counts.append(rx[0])
    reset_world()
    des = float(np.mean(counts))
    cfg = _config_of(_lowered(4, (16.0,), interval_s))
    ref = float(REF.simulate(cfg, SIM_TIME, 8, seed=4)["srv_rx"].mean())
    assert abs(des - ref) <= 0.10 * des + 2.0, (des, ref, counts)


# --- the legacy program is the program it was --------------------------------

#: ``jax.make_jaxpr`` of the legacy (max_mpdus == 1) init and advance at
#: the trace manifest's toy size (a stored string is brittle across jax
#: versions, the counts are not): carry leaves, equations of init, of the
#: advance, of the loop's body and of its condition.  Counted at HEAD
#: before PR 32 as advance=30, body=382, cond=32; since PR 33 the 30
#: equations of the next-event search (``pending`` and its ``any``) are
#: the body's last instead of the condition's first, the advance runs
#: them once before the loop to seed the carried flag where it ran the
#: 29 of ``pending`` after it, and the condition is a compare and an
#: ``and``.  The event step itself was still the 382 it was; since PR 36
#: it is 387: ``runtime.step_keys`` adds five equations (body 412 ->
#: 417): the counter's broadcast to a block of lanes, its
#: ``optimization_barrier``, and the reshape, the ``tile`` (``(1, 1)``
#: up to 1024 replicas) and the slice that make the folded block the
#: rows of the per-replica fold; the counter's ``random_fold_in`` has a
#: vector operand where it had a scalar one.  No other equation moved.
LEGACY_SHAPE = dict(leaves=16, init=15, advance=31, body=417, cond=2)


#: the launch carry's leaves: what ``runtime.jit_init`` builds,
#: ``drive_chunks`` donates, a checkpoint fingerprints, ``_bss_unpack`` reads
LEGACY_LEAVES = [
    "ap_pend", "backoff", "bcn_pend", "busy_until", "cli_rx", "cw",
    "drops", "hold", "immediate", "next_arr", "queue", "retries",
    "srv_rx", "step", "t", "tx_data",
]
HT_LEAVES = sorted(
    set(LEGACY_LEAVES) - {"retries"} | {"q_retry", "ap_retry", "tx_mpdus"}
)


def test_legacy_program_keeps_its_carry_and_its_equations():
    from tpudes.parallel.replicated import _trace_prog

    prog = _trace_prog()
    assert prog.max_mpdus == 1
    init, _, fn = build_bss_advance(prog, 4)
    s0 = init()
    advance = jax.make_jaxpr(fn)(
        s0, jax.random.PRNGKey(0), jnp.int32(64),
        jnp.int32(prog.sim_end_us), None, None,
    )
    assert sorted(s0) == LEGACY_LEAVES
    (loop,) = [e for e in advance.jaxpr.eqns if e.primitive.name == "while"]
    assert dict(
        leaves=len(jax.tree_util.tree_leaves(s0)),
        init=len(jax.make_jaxpr(init)().jaxpr.eqns),
        advance=len(advance.jaxpr.eqns),
        body=len(loop.params["body_jaxpr"].jaxpr.eqns),
        cond=len(loop.params["cond_jaxpr"].jaxpr.eqns),
    ) == LEGACY_SHAPE


@pytest.mark.parametrize(
    "over, leaves",
    [({}, LEGACY_LEAVES), (dict(max_mpdus=8, subframe_bytes=580), HT_LEAVES)],
    ids=["legacy", "ht"],
)
def test_loop_condition_reads_a_carried_scalar(over, leaves):
    """The event step owns the loop's predicate: it searches the state
    it produced for the next event, and the condition compares the step
    budget and reads the carried flag.  No reduction, no search, in the
    condition; and what rides beside the state never leaves the advance:
    the launch carry is the leaves it was."""
    from tpudes.analysis.jaxpr.trace import primitive_names, walk_eqns
    from tpudes.parallel.replicated import _trace_prog

    prog = _trace_prog(**over)
    init, _, fn = build_bss_advance(prog, 4)
    s0 = init()
    assert sorted(s0) == leaves
    advance, (state, still_pending, metrics) = jax.make_jaxpr(
        fn, return_shape=True
    )(
        s0, jax.random.PRNGKey(0), jnp.int32(64),
        jnp.int32(prog.sim_end_us), None, None,
    )
    (loop,) = [e for e in advance.jaxpr.eqns if e.primitive.name == "while"]
    cond = [
        e.primitive.name
        for e in walk_eqns(loop.params["cond_jaxpr"].jaxpr)
    ]
    assert len(cond) <= 4, cond
    assert not [
        p for p in cond if p.startswith(("reduce_", "arg", "cum", "dot"))
    ], cond
    # the search is in the body, for the state the step has produced
    assert {"reduce_or", "reduce_min"} <= primitive_names(
        loop.params["body_jaxpr"]
    )
    # out: the state with its own leaves, the pending vector, the metrics
    assert metrics == {}
    assert still_pending.shape == (4,) and still_pending.dtype == jnp.bool_
    assert {k: (v.shape, v.dtype) for k, v in state.items()} == {
        k: (v.shape, v.dtype) for k, v in s0.items()
    }


def _bss_loop_body(over):
    from tpudes.parallel.replicated import _trace_prog

    prog = _trace_prog(**over)
    init, _, fn = build_bss_advance(prog, 4)
    advance = jax.make_jaxpr(fn)(
        init(), jax.random.PRNGKey(0), jnp.int32(64),
        jnp.int32(prog.sim_end_us), None, None,
    )
    (loop,) = [e for e in advance.jaxpr.eqns if e.primitive.name == "while"]
    return loop.params["body_jaxpr"].jaxpr


def _dumbbell_loop_body(over):
    from tpudes.parallel.tcp_dumbbell import (
        _trace_prog,
        build_dumbbell_advance,
    )

    prog = _trace_prog(**over)
    init, fn = build_dumbbell_advance(prog, 4)
    advance = jax.make_jaxpr(fn)(
        (jnp.int32(0), init()), jax.random.PRNGKey(0),
        jnp.asarray(prog.variant_idx, jnp.int32),
        jnp.zeros(prog.n_flows, bool), jnp.int32(8), None,
    )
    (loop,) = [e for e in advance.jaxpr.eqns if e.primitive.name == "while"]
    return loop.params["body_jaxpr"].jaxpr


@pytest.mark.parametrize(
    "body, over",
    [
        (_bss_loop_body, {}),
        (_bss_loop_body, dict(max_mpdus=8, subframe_bytes=580)),
        (_dumbbell_loop_body, {}),
        (_dumbbell_loop_body, dict(qdisc="red")),
    ],
    ids=["bss-legacy", "bss-ht", "dumbbell-fifo", "dumbbell-red"],
)
def test_loop_body_folds_no_key_on_the_scalar_core(body, over):
    """The shape that must not come back: a ``fold_in`` (or a bare
    threefry) in a loop's body whose operands are all scalars.  XLA
    leaves its ~124 operations unfused on the TPU's scalar core, inside
    the ``while``, under no event of their own: 3.5 us of every BSS
    step until PR 36.  The step's keys come from ``runtime.step_keys``,
    whose folds have a replica-sized operand."""
    from tpudes.analysis.jaxpr.trace import walk_eqns

    folds = [
        e for e in walk_eqns(body(over))
        if e.primitive.name in ("random_fold_in", "threefry2x32")
    ]
    assert folds, "the step derives its keys in the loop's body"
    for e in folds:
        assert any(v.aval.ndim > 0 for v in e.invars), e


def test_legacy_result_has_no_tx_mpdus_and_the_ht_one_does():
    from tpudes.parallel.programs import toy_bss_program

    legacy = run_replicated_bss(toy_bss_program(), 4, jax.random.PRNGKey(1))
    assert "tx_mpdus" not in legacy
    ht = dataclasses.replace(
        toy_bss_program(), max_mpdus=8, subframe_bytes=580
    )
    init, _, _ = build_bss_advance(ht, 4)
    carry = init()
    assert "retries" not in carry
    assert carry["q_retry"].shape == carry["ap_retry"].shape == (4, 5, 8)
    out = run_replicated_bss(ht, 4, jax.random.PRNGKey(1))
    assert np.asarray(out["tx_mpdus"]).shape == (4,)
