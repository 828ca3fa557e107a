"""TCP-dumbbell replica engine tests (BASELINE config #2).

Mirrors upstream's tcp-variants-comparison validation strategy: the
scalar DES (real sockets) is the oracle; the device packet-slot model
must match it statistically (aggregate goodput) and reproduce each
variant's qualitative signature (Vegas' empty queue, Scalable's
aggression), plus structural invariants (conservation, determinism,
mesh execution).
"""

import jax
import numpy as np
import pytest

from tpudes.core import Seconds, Simulator
from tpudes.parallel.tcp_dumbbell import (
    VARIANTS,
    UnliftableDumbbellError,
    lower_dumbbell,
    run_tcp_dumbbell,
)
from tpudes.scenarios import build_dumbbell

SIM_S = 4.0


def _lowered(n_flows=4, variant="TcpNewReno", rate="5Mbps", **kw):
    build_dumbbell(n_flows, SIM_S, variant=variant, bottleneck_rate=rate, **kw)
    return lower_dumbbell(SIM_S)


def test_lowering_reads_graph_parameters():
    prog = _lowered(3, rate="5Mbps", queue="50p", seg_bytes=500)
    assert prog.n_flows == 3
    assert prog.queue_cap == 50
    assert prog.seg_bytes == 500
    # τ = (500+40)·8 / 5e6
    assert prog.slot_s == pytest.approx(540 * 8 / 5e6)
    # access 100Mbps / bottleneck 5Mbps
    assert prog.burst_cap == 20
    assert prog.n_slots == pytest.approx(SIM_S / prog.slot_s, abs=1)


def test_lowering_rejects_non_dumbbell_graphs():
    from tpudes.core.world import reset_world
    from tpudes.helper.containers import NodeContainer

    nodes = NodeContainer()
    nodes.Create(2)
    with pytest.raises(UnliftableDumbbellError):
        lower_dumbbell(1.0)
    reset_world()
    # access slower than bottleneck → leaf-side queueing unrepresentable
    build_dumbbell(2, SIM_S, bottleneck_rate="5Mbps", access_rate="1Mbps")
    with pytest.raises(UnliftableDumbbellError):
        lower_dumbbell(SIM_S)


def test_conservation_and_utilization():
    prog = _lowered(4)
    out = run_tcp_dumbbell(prog, jax.random.PRNGKey(0), replicas=8)
    delivered = np.asarray(out["delivered"])
    assert (delivered > 0).all(), "every flow must make progress"
    # the bottleneck serves ≤ 1 packet per slot
    assert (delivered.sum(1) <= prog.n_slots).all()
    # backlogged loss-based flows fill the pipe: ≥ 85% utilization
    util = delivered.sum(1) / prog.n_slots
    assert (util > 0.85).all(), util


def test_same_key_is_deterministic():
    prog = _lowered(2)
    a = run_tcp_dumbbell(prog, jax.random.PRNGKey(7), replicas=4)
    b = run_tcp_dumbbell(prog, jax.random.PRNGKey(7), replicas=4)
    np.testing.assert_array_equal(
        np.asarray(a["delivered"]), np.asarray(b["delivered"])
    )


def test_variant_signatures():
    from tpudes.core.world import reset_world

    outs, progs = {}, {}
    for v in ("TcpNewReno", "TcpScalable", "TcpVegas"):
        reset_world()
        progs[v] = _lowered(4, variant=v)
        outs[v] = run_tcp_dumbbell(progs[v], jax.random.PRNGKey(1), replicas=8)
    q_reno = float(np.mean(np.asarray(outs["TcpNewReno"]["mean_queue"])))
    q_vegas = float(np.mean(np.asarray(outs["TcpVegas"]["mean_queue"])))
    drops_vegas = int(np.asarray(outs["TcpVegas"]["drops"]).sum())
    drops_reno = int(np.asarray(outs["TcpNewReno"]["drops"]).sum())
    drops_scal = int(np.asarray(outs["TcpScalable"]["drops"]).sum())
    # Vegas: delay-based — near-empty queue, no losses
    assert q_vegas < 0.4 * q_reno
    assert drops_vegas == 0
    # Scalable backs off least → more overflow events than Reno
    assert drops_scal > drops_reno
    # and all three still fill the pipe
    for v, o in outs.items():
        util = np.asarray(o["delivered"]).sum(1) / progs[v].n_slots
        assert (util > 0.85).all(), (v, util)


def test_statistical_parity_with_scalar_des():
    """Aggregate goodput of the slot model vs real TcpSocketBase over
    the identical graph — the replica engine's oracle contract."""
    from tpudes.core.world import reset_world

    host = {}
    for v in ("TcpNewReno", "TcpVegas"):
        reset_world()
        db, sinks = build_dumbbell(
            3, SIM_S, variant=v, bottleneck_rate="3Mbps"
        )
        Simulator.Stop(Seconds(SIM_S))
        Simulator.Run()
        host[v] = sum(
            s.GetTotalRx() * 8.0 / (SIM_S - 0.1) / 1e6 for s in sinks
        )
    for v in ("TcpNewReno", "TcpVegas"):
        reset_world()
        build_dumbbell(3, SIM_S, variant=v, bottleneck_rate="3Mbps")
        prog = lower_dumbbell(SIM_S)
        out = run_tcp_dumbbell(prog, jax.random.PRNGKey(3), replicas=8)
        dev = float(np.asarray(out["goodput_mbps"]).sum(1).mean())
        assert dev == pytest.approx(host[v], rel=0.25), (
            f"{v}: device {dev:.2f} vs host {host[v]:.2f} Mbps"
        )


def test_early_app_stop_halts_flow():
    """A flow stopped before sim end must stop occupying the bottleneck
    (code-review r4: stop_time was silently ignored)."""
    build_dumbbell(2, 2.0)  # apps Stop at 2.0 s
    prog = lower_dumbbell(4.0)  # but the simulation runs to 4.0 s
    assert (np.asarray(prog.stop_slot) < prog.n_slots).all()
    out = run_tcp_dumbbell(prog, jax.random.PRNGKey(0), replicas=4)
    util = np.asarray(out["delivered"]).sum(1) / prog.n_slots
    # ~half the horizon is post-stop (plus drain): utilization well below 0.75
    assert (util < 0.75).all() and (util > 0.3).all(), util


def test_rejects_mixed_segment_sizes():
    db, _ = build_dumbbell(2, SIM_S)
    db.GetLeft(0).GetApplication(0).send_size = 700
    with pytest.raises(UnliftableDumbbellError, match="SendSize"):
        lower_dumbbell(SIM_S)


def test_rejects_same_side_flow():
    """A left→left flow never crosses the bottleneck — must be rejected,
    not silently forced through the shared queue."""
    from tpudes.core import Seconds
    from tpudes.helper.applications import BulkSendHelper, PacketSinkHelper
    from tpudes.network.address import InetSocketAddress, Ipv4Address

    db, _ = build_dumbbell(3, SIM_S)
    sink = PacketSinkHelper(
        "tpudes::TcpSocketFactory",
        InetSocketAddress(Ipv4Address.GetAny(), 7000),
    )
    sink.Install(db.GetLeft(1)).Start(Seconds(0.0))
    bulk = BulkSendHelper(
        "tpudes::TcpSocketFactory",
        InetSocketAddress(
            Ipv4Address(str(db.GetLeftIpv4Address(1))), 7000
        ),
    )
    bulk.Install(db.GetLeft(0)).Start(Seconds(0.1))
    with pytest.raises(UnliftableDumbbellError, match="cross"):
        lower_dumbbell(SIM_S)


def test_lift_discovers_dumbbell():
    from tpudes.parallel.lift import lift

    build_dumbbell(2, SIM_S)
    kind, prog, commit = lift(SIM_S)
    assert kind == "dumbbell"
    assert prog.n_flows == 2
    commit()


def test_mesh_sharded_run():
    from tpudes.parallel.mesh import replica_mesh

    prog = _lowered(2)
    mesh = replica_mesh(8)
    out = run_tcp_dumbbell(prog, jax.random.PRNGKey(0), replicas=16, mesh=mesh)
    assert np.asarray(out["delivered"]).shape == (16, 2)
    assert int(np.asarray(out["delivered"]).sum()) > 0


# --------------------------------------------------------------------------
# the slot's keys come from runtime.step_keys (vector operands):
# bit-identity against the loop that folds the slot counter as a scalar
# --------------------------------------------------------------------------


def _slot_case(case):
    """``(prog, kwargs of build_dumbbell_advance, n_cfg, var, ecn, tr,
    slot bounds of the successive calls)`` of one program variant."""
    import dataclasses

    from tpudes.parallel.programs import (
        toy_dumbbell_program,
        toy_traffic_points,
    )
    from tpudes.parallel.tcp_dumbbell import V_CUBIC, V_DCTCP, V_VEGAS

    prog = toy_dumbbell_program(n_flows=3, n_slots=160)
    kw, n_cfg, tr, bounds = {}, None, None, (prog.n_slots,)
    var = np.asarray(prog.variant_idx, np.int32)
    ecn = np.zeros(prog.n_flows, bool)
    if case == "cubic":
        var = np.full(prog.n_flows, V_CUBIC, np.int32)
    elif case == "mixed":
        # a variant sweep: the config axis batches the slot counter
        n_cfg, kw = 2, dict(n_cfg=2, sweep="variant")
        var = np.asarray(
            [[V_CUBIC, V_VEGAS, V_DCTCP], var], np.int32
        )
        ecn = np.asarray([[False, False, True], [False] * 3])
    elif case == "red_ecn":
        prog = dataclasses.replace(prog, qdisc="red", red_use_ecn=True)
        var = np.asarray([V_DCTCP, V_CUBIC, V_DCTCP], np.int32)
        ecn = np.asarray([True, False, True])
    elif case == "traffic_sweep":
        from tpudes.traffic.device import stack_traffic_operands

        pts = toy_traffic_points(prog.n_flows, prog.n_slots * 1000)[:3]
        prog = dataclasses.replace(prog, traffic=pts[0])
        n_cfg, kw = len(pts), dict(n_cfg=len(pts), sweep="traffic")
        tr = stack_traffic_operands(pts)
    elif case == "chunked":
        # three calls: the carry re-enters twice at t > 0
        bounds = (37, 90, prog.n_slots)
    else:
        assert case == "obs"
        kw = dict(obs=True)
    return prog, kw, n_cfg, var, ecn, tr, bounds


@pytest.mark.parametrize(
    "case",
    ["cubic", "mixed", "red_ecn", "traffic_sweep", "chunked", "obs"],
)
def test_vector_step_keys_are_bit_identical_to_the_scalar_fold(
    case, scalar_step_keys
):
    """``build_dumbbell_advance`` as it is against the same builder with
    ``runtime.step_keys`` replaced by the scalar fold (the slot key the
    loop's body folded by hand, then one fold a replica): the slot
    counter, every carry leaf and the chunk metrics, after every call."""
    import jax.numpy as jnp

    from tpudes.parallel.runtime import stack_axis
    from tpudes.parallel.tcp_dumbbell import build_dumbbell_advance

    prog, kw, n_cfg, var, ecn, tr, bounds = _slot_case(case)

    def run():
        init, fn = build_dumbbell_advance(prog, 4, **kw)
        fn, outs = jax.jit(fn), []
        for key in (jax.random.PRNGKey(9), jax.random.PRNGKey(10)):
            carry = stack_axis((jnp.int32(0), init()), n_cfg)
            for t_end in bounds:
                carry, metrics = fn(
                    carry, key, var, ecn, np.int32(t_end), tr
                )
                outs.append((carry, metrics))
        return outs

    old = scalar_step_keys.same_bits(run, case)
    # the case ran to its horizon, did some work, and the draws
    # decided some of it: the second key's run is another run
    (t, s), _ = old[len(bounds) - 1]
    (_, s_other), _ = old[-1]
    assert (t == prog.n_slots).all() and s["delivered"].sum() > 0
    assert any(
        not np.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(s), jax.tree_util.tree_leaves(s_other)
        )
    )


# --------------------------------------------------------------------------
# the slot compiles the window rules of the variants a launch assigns:
# the SET is static (the runner's key), the per-flow ids stay traced
# --------------------------------------------------------------------------


def _set_case(case):
    """``(prog, var, ecn)`` of one assignment: a toy program of three
    flows, 120 slots (slow start, tail drops, the cut and the climb
    all happen: the queue holds 25 packets)."""
    import dataclasses

    from tpudes.parallel.programs import toy_dumbbell_program
    from tpudes.parallel.tcp_dumbbell import (
        V_CUBIC,
        V_DCTCP,
        V_NEWRENO,
        _variant_ecn,
    )

    prog = toy_dumbbell_program(n_flows=3, n_slots=120)
    if case == "three":
        var = np.asarray([V_NEWRENO, V_CUBIC, V_DCTCP], np.int32)
    elif case == "red_ecn":
        prog = dataclasses.replace(prog, qdisc="red", red_use_ecn=True)
        var = np.asarray([V_DCTCP, V_CUBIC, V_DCTCP], np.int32)
    else:
        var = np.full(3, VARIANTS.index(case), np.int32)
    return prog, var, _variant_ecn(var)


@pytest.fixture(scope="module")
def every_rule():
    """``f(prog, var, ecn)`` -> the final carry of the program that
    holds all seventeen variants' rules (the builder handed no set: the
    program before the set was part of the key), one compile a qdisc."""
    import jax.numpy as jnp

    from tpudes.parallel.tcp_dumbbell import build_dumbbell_advance

    built = {}

    def run(prog, var, ecn):
        if prog.qdisc not in built:
            init, fn = build_dumbbell_advance(prog, 4)
            built[prog.qdisc] = init, jax.jit(fn)
        init, fn = built[prog.qdisc]
        carry, _ = fn(
            (jnp.int32(0), init()), jax.random.PRNGKey(9), var, ecn,
            np.int32(prog.n_slots), None,
        )
        return jax.tree_util.tree_map(np.asarray, carry)

    return run


@pytest.mark.parametrize("case", list(VARIANTS) + ["three", "red_ecn"])
def test_set_built_program_equals_the_program_of_every_rule(
    case, every_rule
):
    """The program built for the set a launch assigns against the one
    built for all seventeen, same ``var`` operand, same key: the slot
    counter, every leaf outside the side state, and every side leaf a
    present rule names, bit for bit; a side leaf no present rule names
    stays what ``init_state()`` made it."""
    import jax.numpy as jnp

    from tpudes.parallel.tcp_dumbbell import (
        V_DCTCP,
        build_dumbbell_advance,
        live_side_leaves,
        variant_set,
    )

    prog, var, ecn = _set_case(case)
    present = variant_set([var])
    init, fn = build_dumbbell_advance(prog, 4, present=present)
    s0 = init()
    carry, _ = jax.jit(fn)(
        (jnp.int32(0), s0), jax.random.PRNGKey(9), var, ecn,
        np.int32(prog.n_slots), None,
    )
    (t, s) = jax.tree_util.tree_map(np.asarray, carry)
    (t_all, s_all) = every_rule(prog, var, ecn)
    assert t == t_all == prog.n_slots
    assert s["delivered"].sum() > 0 and s["drops"].sum() > 0
    # DCTCP's window counters sit outside the side dict
    dctcp_only = () if V_DCTCP in present else ("dctcp_acked", "dctcp_marked")
    for k in s:
        if k != "side" and k not in dctcp_only:
            np.testing.assert_array_equal(s[k], s_all[k], err_msg=k)
    live = live_side_leaves(present)
    assert len(live) < len(s["side"])
    for k, leaf in s["side"].items():
        np.testing.assert_array_equal(
            leaf, s_all["side"][k] if k in live else np.asarray(s0["side"][k]),
            err_msg=f"side.{k}",
        )
    for k in dctcp_only:
        assert not s[k].any()


def test_rule_table_names_every_side_leaf():
    """With all seventeen present every side leaf is some rule's, so
    that program passes none through: it is the masked-dense step."""
    from tpudes.parallel.programs import toy_dumbbell_program
    from tpudes.parallel.tcp_dumbbell import (
        ALL_VARIANTS,
        V_CUBIC,
        build_dumbbell_step,
        live_side_leaves,
        variant_set,
    )

    init, _ = build_dumbbell_step(toy_dumbbell_program(n_flows=2), 2)
    assert live_side_leaves(ALL_VARIANTS) == set(init()["side"])
    assert live_side_leaves((V_CUBIC,)) == {
        "w_max", "epoch_t", "k", "origin", "w_est"
    }
    assert variant_set([[3, 1], np.asarray([1, 16])]) == (1, 3, 16)


def _advance_jaxpr(prog, present):
    import jax.numpy as jnp

    from tpudes.parallel.tcp_dumbbell import build_dumbbell_advance

    kw = {} if present is None else dict(present=present)
    init, fn = build_dumbbell_advance(prog, 4, **kw)
    carry = (jnp.int32(0), init())
    return carry, jax.make_jaxpr(fn)(
        carry, jax.random.PRNGKey(0),
        jnp.asarray(prog.variant_idx, jnp.int32),
        jnp.zeros(prog.n_flows, bool), jnp.int32(8), None,
    )


def test_runner_keys_on_the_set_and_not_on_the_assignment(monkeypatch):
    """Another assignment drawn from the same set is a cache hit, a
    wider set one more runner; the set the runner is built for is the
    union of what the launch assigns, and a launch that assigns all
    seventeen traces, equation for equation, the program of the builder
    that is handed no set (``tests/test_dumbbell_reference.py`` holds
    that one to the counts it had before any of this)."""
    import dataclasses

    from tpudes.parallel import tcp_dumbbell
    from tpudes.parallel.programs import toy_dumbbell_program
    from tpudes.parallel.runtime import RUNTIME

    built = []
    real = tcp_dumbbell.build_dumbbell_advance

    def recording(*a, **kw):
        built.append(kw.get("present"))
        return real(*a, **kw)

    monkeypatch.setattr(tcp_dumbbell, "build_dumbbell_advance", recording)
    key = jax.random.PRNGKey(2)
    prog = toy_dumbbell_program(n_flows=3, n_slots=60)   # ids 0, 1, 2
    RUNTIME.clear("dumbbell")
    a = run_tcp_dumbbell(prog, key, replicas=2)
    misses = RUNTIME.misses
    swapped = dataclasses.replace(
        prog, variant_idx=np.asarray([2, 0, 1], np.int32)
    )
    b = run_tcp_dumbbell(swapped, key, replicas=2)
    assert RUNTIME.misses == misses and built == [(0, 1, 2)]
    # the same rules, other flows: flow 0 now runs what flow 2 ran
    assert not np.array_equal(a["cwnd_final"], b["cwnd_final"])
    wider = dataclasses.replace(
        prog, variant_idx=np.asarray([0, 1, 7], np.int32)
    )
    run_tcp_dumbbell(wider, key, replicas=2)
    assert RUNTIME.misses == misses + 1 and built[-1] == (0, 1, 7)
    # a sweep's set is the union of its points: one runner, built once
    run_tcp_dumbbell(
        prog, key, replicas=2,
        variants=[["TcpVegas"] * 3, ["TcpCubic", "TcpBbr", "TcpCubic"]],
    )
    assert RUNTIME.misses == misses + 2 and built[-1] == (1, 4, 11)

    family = toy_dumbbell_program(n_flows=17, n_slots=60)
    run_tcp_dumbbell(family, key, replicas=2)
    assert built[-1] == tcp_dumbbell.ALL_VARIANTS
    _, as_assigned = _advance_jaxpr(family, built[-1])
    _, handed_no_set = _advance_jaxpr(family, None)
    assert str(as_assigned) == str(handed_no_set)
    _, cubic = _advance_jaxpr(family, (tcp_dumbbell.V_CUBIC,))
    assert len(cubic.jaxpr.eqns[-1].params["body_jaxpr"].jaxpr.eqns) < 0.6 * len(
        handed_no_set.jaxpr.eqns[-1].params["body_jaxpr"].jaxpr.eqns
    )


def test_cubic_only_body_passes_every_other_side_leaf_through():
    """In the traced advance of a CUBIC-only program a side leaf outside
    CUBIC's own is the program's input leaf itself: the body handed it
    on untouched, so jax's ``while`` does not even carry it (and XLA's
    would drop it from the loop's tuple).  CUBIC's five are written, and
    no equation of the loop reads the variant ids."""
    from tpudes.parallel.programs import toy_dumbbell_program
    from tpudes.parallel.tcp_dumbbell import V_CUBIC, live_side_leaves

    prog = toy_dumbbell_program(n_flows=3, n_slots=60)
    carry, advance = _advance_jaxpr(prog, (V_CUBIC,))
    paths = [
        jax.tree_util.keystr(p)
        for p, _ in jax.tree_util.tree_flatten_with_path(carry)[0]
    ]
    n = len(paths)
    ins, outs = advance.jaxpr.invars[:n], advance.jaxpr.outvars[:n]
    passed = {p for p, i, o in zip(paths, ins, outs) if i is o}
    side = {p for p in paths if "'side'" in p}
    own = {f"[1]['side']['{k}']" for k in live_side_leaves((V_CUBIC,))}
    assert own <= side and len(side) == 24
    assert passed >= side - own and not passed & own
    # DCTCP's window counters too; the rings and the windows are written
    assert {"[1]['dctcp_acked']", "[1]['dctcp_marked']"} <= passed
    assert not {"[1]['cwnd']", "[1]['ack_buf']", "[0]"} & passed
    (loop,) = [e for e in advance.jaxpr.eqns if e.primitive.name == "while"]
    carried = len(loop.invars) - (
        loop.params["cond_nconsts"] + loop.params["body_nconsts"]
    )
    assert carried == n - len(passed)
    # nothing the body hands on depends on ``var``: one variant, no
    # compare (what reads it, the ``[None, :]``, feeds no equation)
    var = advance.jaxpr.invars[n + 1]
    assert var.aval.shape == (3,) and var.aval.dtype == np.int32
    body = loop.params["body_jaxpr"].jaxpr
    reached = {
        body.invars[loop.invars.index(var) - loop.params["cond_nconsts"]]
    }
    for eqn in body.eqns:
        if reached & {v for v in eqn.invars if hasattr(v, "count")}:
            reached |= set(eqn.outvars)
    assert len(reached) == 3 and not reached & set(body.outvars)
    # the program of every rule carries every side leaf (of the rest a
    # fifo program passes RED's average on)
    carry, advance = _advance_jaxpr(prog, None)
    assert {
        p for p, i, o in
        zip(paths, advance.jaxpr.invars[:n], advance.jaxpr.outvars[:n])
        if i is o
    } == {"[1]['red_avg']"}


def test_launch_span_counts_the_rule_sets_compiled():
    """``launch.args["cc_rules"]``: how many variants' window rules the
    launch's slot program holds: 1, 3 and 17 for an all-CUBIC launch, a
    three-variant sweep and a launch that assigns the whole family."""
    import dataclasses

    from tpudes.obs import spans
    from tpudes.parallel.lift import run_lifted
    from tpudes.parallel.programs import toy_dumbbell_program

    def cc_rules():
        return [
            s for s in spans.snapshot() if s.name == "launch"
        ][-1].args["cc_rules"]

    key = jax.random.PRNGKey(7)
    toy = toy_dumbbell_program(n_flows=2, n_slots=40)
    cubic = dataclasses.replace(toy, variant_idx=np.asarray([1, 1], np.int32))
    run_lifted("dumbbell", cubic, 2, key)
    assert cc_rules() == 1
    run_lifted("dumbbell", cubic, 2, key, variants=[
        ["TcpNewReno"] * 2, ["TcpCubic"] * 2, ["TcpDctcp"] * 2,
    ])
    assert cc_rules() == 3
    run_lifted("dumbbell", toy_dumbbell_program(n_flows=17, n_slots=40), 2, key)
    assert cc_rules() == 17
