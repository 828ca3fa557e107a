"""The TCP dumbbell engine held to the benchmark's plain reference.

``benchmark/references/dumbbell.py`` is the float64 numpy slot loop the cell
``tcp.mc`` decides ``correct`` with: it imports nothing of tpudes and writes
CUBIC from RFC 8312.  Here, small and on the CPU: the device engine against
it in every number ``correct`` compares, on a dumbbell where CUBIC's parts
can be told apart (three flows that join four seconds apart over a 50 ms
bottleneck: large windows, so the cubic region and fast convergence do the
work); the control and the faults the cell has to catch, at three times the
tolerance; the reference against the host DES; conservation of packets on
both sides; and the engine's advance pinned, equation for equation, to what
it was before it got its three scope names.
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
from tpudes.parallel.tcp_dumbbell import (
    build_dumbbell_advance,
    lower_dumbbell,
    run_tcp_dumbbell,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = load_module(os.path.join(ROOT, "benchmark", "references", "dumbbell.py"))
with open(os.path.join(
        ROOT, "benchmark", "configs", "tcp-dumbbell-8flow-cubic.json")) as f:
    CONFIG = json.load(f)

N_FLOWS, STAGGER_S, DELAY_MS, SIM_S, REPLICAS = 3, 4.0, 50, 12.0, 32

#: |engine - reference| as the cell scales it, and why this much: both
#: sides are 32-replica means.  Two sound sides (the reference under five
#: seeds, at this size) read up to 0.028 apart in flow_goodput_gap, 0.00014
#: in drops_gap, 0.026 in queue_gap and 0.0064 in jain_gap; the tolerance
#: is about twice the largest, and the engine reads 0.025, 0.00002, 0.015
#: and 0.0044.  What the cell guards against reads far above: see FAULTS.
TOLERANCE = {
    "agg_goodput_gap": 0.001, "flow_goodput_gap": 0.06,
    "drops_gap": 0.0003, "queue_gap": 0.05, "jain_gap": 0.015,
}

#: a fault of the reference, the number that tells it, what it read here
FAULTS = [
    # the control: a half-width carry of the window state (0.0064; 0.27)
    (dict(precision="bfloat16"), "drops_gap"),
    (dict(precision="bfloat16"), "flow_goodput_gap"),
    # NewReno's rules in CUBIC's place (0.20; 0.091)
    (dict(variant="newreno"), "queue_gap"),
    (dict(variant="newreno"), "jain_gap"),
    # CUBIC without fast convergence: the first flow does not yield to
    # the two that join late (0.21; 0.064)
    (dict(fast_convergence=False), "flow_goodput_gap"),
    (dict(fast_convergence=False), "jain_gap"),
    # a cut at every loss notice, not one per recovery window (0.66)
    (dict(cut_per_loss=True), "queue_gap"),
    # upstream's order of service against the engine's draw (0.0011)
    (dict(service="fifo"), "drops_gap"),
]


def _config(n_flows, stagger_s=None, delay_ms=None, rate_mbps=None):
    """The deployment file's physics on a smaller dumbbell: what the
    reference is given in the cell, at a size a test can hold."""
    cfg = copy.deepcopy(CONFIG)
    cfg["topology"]["n_flows"] = n_flows
    if stagger_s is not None:
        cfg["topology"]["flow_stagger_s"] = stagger_s
    if delay_ms is not None:
        cfg["physics"]["bottleneck_delay_s"] = delay_ms / 1000.0
    if rate_mbps is not None:
        cfg["physics"]["bottleneck_rate_bps"] = rate_mbps * 1e6
    return cfg


def _lowered(n_flows, sim_s, **kw):
    from tpudes.core.world import reset_world
    from tpudes.scenarios import build_dumbbell

    reset_world()
    build_dumbbell(n_flows, sim_s, variant="TcpCubic", **kw)
    prog = lower_dumbbell(sim_s)
    reset_world()
    return prog


# --- the engine against the reference ----------------------------------------

@pytest.fixture(scope="module")
def late_joiners():
    cfg = _config(N_FLOWS, STAGGER_S, DELAY_MS)
    geometry = REF.geometry(cfg, SIM_S)
    prog = _lowered(N_FLOWS, SIM_S, bottleneck_delay=f"{DELAY_MS}ms")
    # the stock graph staggers its flows by 10 ms; these join seconds apart
    prog = dataclasses.replace(
        prog, start_slot=geometry["start"].astype(np.int32)
    )
    assert (prog.n_slots, prog.ack_lag, prog.queue_cap, prog.burst_cap) == (
        geometry["n_slots"], geometry["ack_lag"], geometry["queue"],
        geometry["burst"],
    )
    assert prog.slot_s == pytest.approx(geometry["slot_s"])
    assert prog.base_rtt_s == pytest.approx(geometry["base_rtt_s"])
    out = run_tcp_dumbbell(prog, jax.random.PRNGKey(35), replicas=REPLICAS)
    mix = {"horizon_s": SIM_S, "reference_replicas": REPLICAS}
    ref = REF.simulate(cfg, SIM_S, REPLICAS, seed=0)
    return prog, out, cfg, mix, ref


@pytest.mark.parametrize("number", sorted(TOLERANCE))
def test_engine_agrees_with_the_plain_reference(late_joiners, number):
    _, out, cfg, mix, ref = late_joiners
    numbers = REF.compare(cfg, mix, [out], REPLICAS, seed=0, ref=ref)
    assert numbers["rows_missing"] == 0
    assert numbers[number] <= TOLERANCE[number], numbers


def test_the_small_dumbbell_is_backlogged_and_loses_packets(late_joiners):
    """The test's own premise: the queue never runs dry once the first
    flow is up, every flow loses packets, the late flows get less."""
    prog, out, cfg, _, ref = late_joiners
    slots = prog.n_slots - int(prog.start_slot[0])
    for side in (out, ref):
        delivered = np.asarray(side["delivered"])
        assert (delivered.sum(axis=1) > 0.97 * slots).all()
        assert (np.asarray(side["drops"]).mean(axis=0) > 0).all()
        goodput = np.asarray(side["goodput_mbps"]).mean(axis=0)
        assert goodput[0] > goodput[1] > goodput[2] > 0
    assert REF.criterion(out) is None and REF.kpi(out) > 9.0


_FAULTY = {}


def _faulty_numbers(fault, late_joiners):
    """One simulation per fault, however many numbers it is read in."""
    if fault not in _FAULTY:
        _, _, cfg, mix, ref = late_joiners
        faulty = REF.simulate(cfg, SIM_S, REPLICAS, seed=99, **dict(fault))
        _FAULTY[fault] = REF.compare(
            cfg, mix, [faulty], REPLICAS, seed=0, ref=ref
        )
    return _FAULTY[fault]


@pytest.mark.parametrize(
    "fault,number", FAULTS,
    ids=[f"{'-'.join(map(str, f.values()))}-{n}" for f, n in FAULTS],
)
def test_a_faulty_side_reads_three_times_the_tolerance(
    late_joiners, fault, number
):
    """The tolerance means something: the bfloat16 control, NewReno in
    CUBIC's place, no fast convergence, a cut per loss and upstream's
    order of service each fail a number by three times what the engine
    is allowed."""
    numbers = _faulty_numbers(tuple(fault.items()), late_joiners)
    assert numbers[number] > 3 * TOLERANCE[number], numbers


def test_the_reference_is_a_function_of_its_seed():
    first, again, other = (
        REF.simulate(_config(4), 3.0, 4, seed=s) for s in (0, 0, 1)
    )
    for field in ("delivered", "drops", "mean_queue", "cwnd_final"):
        assert np.array_equal(first[field], again[field]), field
    assert not np.array_equal(first["delivered"], other["delivered"])


# --- conservation of packets ------------------------------------------------

@pytest.mark.parametrize("service", ["draw", "fifo"])
def test_reference_conserves_packets_per_flow(service):
    cfg = _config(4)
    run = REF.simulate(cfg, 3.0, 8, seed=5, service=service)
    assert (run["sent"] > 0).all()
    # every packet sent was delivered, was dropped, or is still queued
    assert np.array_equal(
        run["sent"], run["delivered"] + run["drops"] + run["queued"]
    )
    # and what is in flight is queued or has its ACK or its notice due
    assert np.array_equal(
        run["inflight"], run["queued"] + run["unacked"] + run["unnoticed"]
    )


def test_engine_conserves_packets_per_flow():
    """The engine keeps no count of what it sent; what it does carry
    closes: a flow's packets in flight are those in the queue, those
    whose ACK is due and those whose loss notice is due."""
    prog = _lowered(4, 3.0)
    init, fn = build_dumbbell_advance(prog, 8)
    (_, s), _ = jax.jit(fn)(
        (jnp.int32(0), init()), jax.random.PRNGKey(5),
        jnp.asarray(prog.variant_idx), jnp.zeros(4, bool),
        jnp.int32(prog.n_slots), None,
    )
    s = jax.tree_util.tree_map(np.asarray, s)
    assert (s["delivered"] > 0).all() and s["drops"].sum() > 0
    assert np.array_equal(
        s["inflight"],
        s["q"] + s["ack_buf"].sum(axis=1) + s["loss_buf"].sum(axis=1),
    )
    assert (s["q"].sum(axis=1) <= prog.queue_cap).all()


# --- the reference against the host DES --------------------------------------

@pytest.mark.parametrize("n_flows,rate_mbps", [(2, 3), (3, 10), (4, 5)])
def test_reference_agrees_with_the_host_des(n_flows, rate_mbps):
    """The scalar DES (real TcpSocketBase with CUBIC over the
    point-to-point devices: SACK-less recovery, RTO, retransmissions)
    against the plain reference serving first in, first out as the host's
    queue does: aggregate goodput within 25%, the pin
    ``test_tcp_dumbbell`` holds the engine to."""
    from tpudes.core import Seconds, Simulator
    from tpudes.core.world import reset_world
    from tpudes.scenarios import build_dumbbell

    sim_s = 8.0
    reset_world()
    _, sinks = build_dumbbell(
        n_flows, sim_s, variant="TcpCubic",
        bottleneck_rate=f"{rate_mbps}Mbps",
    )
    Simulator.Stop(Seconds(sim_s))
    Simulator.Run()
    host = sum(s.GetTotalRx() * 8.0 / sim_s / 1e6 for s in sinks)
    reset_world()
    ref = REF.simulate(
        _config(n_flows, rate_mbps=rate_mbps), sim_s, 8, seed=4,
        service="fifo",
    )
    assert REF.kpi(ref) == pytest.approx(host, rel=0.25), (host, REF.kpi(ref))


# --- the engine's advance is the program it was ---------------------------

#: ``jax.make_jaxpr`` of the dumbbell init and advance at the trace
#: manifest's toy size, counted at HEAD before PR 35 gave the slot its
#: three scope names: carry leaves, equations of init, of the advance
#: (the ``while`` alone), of the loop's body and of its condition, and
#: of the whole advance walked through its sub-jaxprs.  A name is no
#: equation: the counts were the parent's.  Since PR 36 the slot's keys
#: come from ``runtime.step_keys``, which adds five equations (body 634
#: -> 639, walked 864 -> 869): the slot counter's broadcast to a block
#: of lanes, its ``optimization_barrier``, and the reshape, the ``tile``
#: and the slice that make the folded block the rows of the per-replica
#: fold; the counter's ``random_fold_in`` has a vector operand where it
#: had a scalar one.  No other equation moved.
DUMBBELL_SHAPE = dict(leaves=40, init=40, advance=1, body=639, cond=1,
                      walked=869)


def test_dumbbell_advance_keeps_its_carry_and_its_equations():
    from tpudes.analysis.jaxpr.trace import walk_eqns
    from tpudes.parallel.tcp_dumbbell import _trace_prog

    prog = _trace_prog()
    init, fn = build_dumbbell_advance(prog, 4)
    s0 = init()
    advance = jax.make_jaxpr(fn)(
        (jnp.int32(0), s0), jax.random.PRNGKey(0),
        jnp.asarray(prog.variant_idx, jnp.int32),
        jnp.zeros(prog.n_flows, bool), jnp.int32(8), None,
    )
    (loop,) = [e for e in advance.jaxpr.eqns if e.primitive.name == "while"]
    assert dict(
        leaves=len(jax.tree_util.tree_leaves(s0)),
        init=len(jax.make_jaxpr(init)().jaxpr.eqns),
        advance=len(advance.jaxpr.eqns),
        body=len(loop.params["body_jaxpr"].jaxpr.eqns),
        cond=len(loop.params["cond_jaxpr"].jaxpr.eqns),
        walked=len(list(walk_eqns(advance.jaxpr))),
    ) == DUMBBELL_SHAPE
