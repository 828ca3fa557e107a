"""Launch-path spans (tpudes.obs.spans), XLA compile events and the
stable device names (ISSUE 25).

- the span ring: nesting, parent and request ids, bounded with the tail
  kept, one stack per thread;
- the nine span sites, on toy programs: a ``run_lifted`` leaves exactly
  one ``launch`` with its three children, ``result()`` leaves
  ``result.wait/fetch/unpack`` under that launch's id, blocking or not;
- ``CompileTelemetry.xla_events`` sees a new ``jit``'s first call and
  not its second;
- every engine's outermost loop lowers with ``tpudes.<engine>.step`` and
  ``.cond`` in its debug info (the manifests trace the program the
  runner cache compiles), the LTE one also with ``.rng`` and, through
  Pallas, the kernel's name.
"""

import importlib
import re
import threading

import jax
import jax.numpy as jnp
import pytest

from tpudes.obs import spans
from tpudes.obs.device import CompileTelemetry
from tpudes.parallel.lift import run_lifted
from tpudes.parallel.programs import (
    toy_as_program,
    toy_bss_program,
    toy_dumbbell_program,
    toy_lte_program,
)

LAUNCH_CHILDREN = ["launch.runner", "launch.operands", "launch.enqueue"]
RESULT_SPANS = ["result.wait", "result.fetch", "result.unpack"]


@pytest.fixture(autouse=True)
def empty_ring():
    spans.reset()
    yield
    assert spans.current() is None, "a span was left open on this thread"


def _toy(kind):
    if kind == "bss":
        return toy_bss_program()
    if kind == "dumbbell":
        return toy_dumbbell_program(n_flows=2, n_slots=30)
    if kind == "as_flows":
        return toy_as_program(n_nodes=12, n_flows=2, spf_rounds=6)
    if kind == "wired":
        from tpudes.parallel.wired import wired_chain

        return wired_chain(n_links=3, n_flows=2, n_slots=40, jitter_slots=2)
    return toy_lte_program(n_enb=2, n_ue=3, n_ttis=40)


# --- the ring ---------------------------------------------------------------


def test_nesting_gives_parent_and_request_ids():
    with spans.span("outer", tag=1) as outer:
        assert spans.current() is outer
        with spans.span("inner") as inner:
            with spans.span("leaf") as leaf:
                pass
        with spans.span("own", spans.OWN) as own:
            with spans.span("own.child") as own_child:
                pass
    with spans.span("joined", inner.id) as joined:
        pass
    assert outer.parent is None and outer.request == outer.id
    assert inner.parent == outer.id and leaf.parent == inner.id
    assert inner.request == leaf.request == outer.id
    # OWN starts a request under a parent; its children inherit it
    assert own.parent == outer.id and own.request == own.id
    assert own_child.request == own.id
    # an explicit id joins that request from a root
    assert joined.parent is None and joined.request == inner.id
    ring = spans.snapshot()
    assert [s.name for s in ring] == [
        "leaf", "inner", "own.child", "own", "outer", "joined",
    ]                                   # closed spans, in closing order
    assert all(s.end >= s.start for s in ring)
    assert outer.start <= inner.start and inner.end <= outer.end
    assert outer.args == {"tag": 1}
    assert len({s.id for s in ring}) == len(ring)


def test_ring_is_bounded_and_keeps_the_tail():
    for i in range(spans.RING + 10):
        with spans.span("s", i=i):
            pass
    ring = spans.snapshot()
    assert len(ring) == spans.RING
    assert ring[0].args["i"] == 10
    assert ring[-1].args["i"] == spans.RING + 9
    spans.reset()
    assert spans.snapshot() == []


def test_close_is_idempotent_and_out_of_order_close_keeps_the_stack():
    a = spans.span("a").open()
    b = spans.span("b").open()
    a.close()                            # not the innermost
    assert spans.current() is b
    a.close()
    b.close()
    assert spans.current() is None
    assert [s.name for s in spans.snapshot()] == ["a", "b"]


def test_threads_do_not_adopt_each_others_parents():
    inside = threading.Event()
    release = threading.Event()
    seen = {}

    def worker():
        with spans.span("worker.outer") as outer:
            inside.set()
            assert release.wait(10)
            with spans.span("worker.inner") as inner:
                pass
        seen.update(outer=outer, inner=inner)

    thread = threading.Thread(target=worker)
    thread.start()
    assert inside.wait(10)
    # the worker's span is open right now, on its own stack
    assert spans.current() is None
    with spans.span("main.outer") as main_outer:
        with spans.span("main.inner") as main_inner:
            release.set()
            thread.join(10)
            assert not thread.is_alive()
    assert main_outer.parent is None
    assert main_inner.parent == main_outer.id
    assert seen["outer"].parent is None
    assert seen["inner"].parent == seen["outer"].id
    assert seen["inner"].request == seen["outer"].id != main_outer.request


# --- the span sites -----------------------------------------------------------


@pytest.mark.parametrize("block", [False, True], ids=["future", "blocking"])
@pytest.mark.parametrize("kind", ["bss", "lte_sm"])
def test_run_lifted_leaves_one_launch_with_children_and_results(kind, block):
    prog, key = _toy(kind), jax.random.PRNGKey(7)
    out = run_lifted(kind, prog, 8, key, block=block)
    if not block:
        before = [s.name for s in spans.snapshot()]
        assert sorted(before) == sorted(LAUNCH_CHILDREN + ["launch"])
        launch_id = out.launch_id
        out = out.result()
    ring = spans.snapshot()
    by_name = {}
    for s in ring:
        by_name.setdefault(s.name, []).append(s)
    assert sorted(by_name) == sorted(
        ["launch"] + LAUNCH_CHILDREN + RESULT_SPANS
    )
    (launch,) = by_name["launch"]
    if not block:
        assert launch.id == launch_id
    assert launch.parent is None and launch.request == launch.id
    assert launch.args["kind"] == kind
    for name in LAUNCH_CHILDREN:
        (child,) = by_name[name]
        assert child.parent == launch.id and child.request == launch.id
        assert launch.start <= child.start and child.end <= launch.end
    assert by_name["launch.enqueue"][0].args["chunks"] == 1
    assert isinstance(by_name["launch.runner"][0].args["hit"], bool)
    # result.fetch is two spans: the copies started before the wait, and
    # what is left of the transfer after it
    assert [len(by_name[name]) for name in RESULT_SPANS] == [1, 2, 1]
    first_fetch, second_fetch = by_name["result.fetch"]
    assert first_fetch.end <= by_name["result.wait"][0].start
    assert by_name["result.wait"][0].end <= second_fetch.start
    for name in RESULT_SPANS:
        for res in by_name[name]:
            # after the launch, outside it, tied to it by the request id
            assert res.parent is None and res.request == launch.id
            assert res.start >= launch.end
    assert out is not None
    # a second launch is a runner-cache hit and a request of its own
    run_lifted(kind, prog, 8, key)
    launches = [s for s in spans.snapshot() if s.name == "launch"]
    runners = [s for s in spans.snapshot() if s.name == "launch.runner"]
    assert len(launches) == 2 and launches[1].request != launch.id
    assert runners[1].args["hit"] is True


@pytest.mark.parametrize("side", ["at_N", "above_N", "mesh"])
def test_lte_launch_span_names_the_step_lowering_and_lanes(monkeypatch, side):
    """ISSUE 31: how often the lane-count rule engages is read off the
    ``launch`` span: ``step_lowering`` is the rule's answer for this
    launch's ``lanes`` (``r_pad x n_cfg``), on both sides of
    ``SM_KERNEL_MAX_LANES`` and under ``run_lifted``'s own mesh."""
    import warnings

    from tpudes.parallel.lte_sm import SM_KERNEL_MAX_LANES as N

    monkeypatch.delenv("TPUDES_PALLAS", raising=False)
    # odd replica counts keep run_lifted off the 8 virtual devices
    # (it warns that they do not divide); 8 takes its mesh
    replicas, lanes, want = {
        "at_N": (1, 1, "mosaic" if N >= 1 else "xla"),
        "above_N": (N + 1 + N % 2, None, "xla"),
        "mesh": (8, 8, "xla"),
    }[side]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        run_lifted("lte_sm", _toy("lte_sm"), replicas, jax.random.PRNGKey(7))
    (launch,) = [s for s in spans.snapshot() if s.name == "launch"]
    assert launch.args["step_lowering"] == want
    if lanes is None:
        assert launch.args["lanes"] > N
    else:
        assert launch.args["lanes"] == lanes
    # the other engines' launches carry neither
    run_lifted("bss", _toy("bss"), 8, jax.random.PRNGKey(7))
    bss = [s for s in spans.snapshot() if s.name == "launch"][-1]
    assert "step_lowering" not in bss.args and "lanes" not in bss.args


@pytest.mark.parametrize("kind", ["bss", "lte_sm", "dumbbell", "as_flows"])
def test_warm_launches_reuse_the_init_program(kind):
    """ISSUE 29, and ISSUE 30 for every engine: the launch carry comes
    from the runner's cached ``jit_init`` program, so warm launches
    build nothing, trace nothing and compile nothing on their way to
    the enqueue."""
    import time

    from tpudes.parallel.runtime import RUNTIME

    CompileTelemetry.listen()
    prog, key = _toy(kind), jax.random.PRNGKey(7)
    first = run_lifted(kind, prog, 8, key, block=False)
    (cold,) = [s for s in spans.snapshot() if s.name == "launch.operands"]
    assert isinstance(cold.args["init_cached"], bool)
    first.result()
    spans.reset()
    before, t0 = RUNTIME.stats(), time.perf_counter()
    futs = [
        run_lifted(kind, prog, 8, jax.random.PRNGKey(i), block=False)
        for i in range(3)
    ]
    events = CompileTelemetry.xla_events(since=t0)
    after = RUNTIME.stats()
    assert after["init_programs"] == before["init_programs"]
    assert after["launches"][kind] == before["launches"][kind] + 3
    assert after["misses"] == before["misses"]
    assert [e for e in events if e[1].endswith("jaxpr_trace_duration")] == []
    assert [e for e in events
            if e[1].endswith("backend_compile_duration")] == []
    ring = spans.snapshot()
    for fut in futs:
        (operands,) = [
            s for s in ring
            if s.name == "launch.operands" and s.parent == fut.launch_id
        ]
        assert operands.args["init_cached"] is True
        assert fut.result() is not None


@pytest.mark.parametrize(
    "kind", ["bss", "lte_sm", "dumbbell", "as_flows", "wired"]
)
def test_direct_engine_call_records_children_without_a_launch(kind):
    from tpudes.parallel.as_flows import run_as_flows
    from tpudes.parallel.lte_sm import run_lte_sm
    from tpudes.parallel.replicated import run_replicated_bss
    from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell
    from tpudes.parallel.wired import run_wired

    prog, key = _toy(kind), jax.random.PRNGKey(3)
    if kind == "bss":
        fut = run_replicated_bss(prog, 2, key, block=False)
    else:
        run = dict(
            lte_sm=run_lte_sm, dumbbell=run_tcp_dumbbell,
            as_flows=run_as_flows, wired=run_wired,
        )[kind]
        fut = run(prog, key, 2, block=False)
    assert fut.engine == kind and fut.launch_id is None
    fut.block()
    fut.result()
    ring = spans.snapshot()
    assert "launch" not in [s.name for s in ring]
    for name in LAUNCH_CHILDREN + RESULT_SPANS:
        assert any(s.name == name and s.parent is None for s in ring), name
    # one of each launch child, in the order of the one launch path
    assert [s.name for s in ring if s.name in LAUNCH_CHILDREN] == (
        LAUNCH_CHILDREN
    )
    (operands,) = [s for s in ring if s.name == "launch.operands"]
    assert isinstance(operands.args["init_cached"], bool)
    # block() and result() each waited once
    assert sum(s.name == "result.wait" for s in ring) == 2


def test_launch_is_closed_when_the_engine_raises_first():
    with pytest.raises(ValueError, match="unknown lifted program kind"):
        run_lifted("no-such-kind", None, 8, jax.random.PRNGKey(0))
    assert spans.current() is None
    assert [s.name for s in spans.snapshot()] == ["launch"]


def test_script_study_nests_lift_and_launch_under_lifted_run():
    import chip_smoke

    rc, res, _ = chip_smoke.run_stock_script(
        "wifi-bss.py", dict(nStas=4, simTime=1.3), 4
    )
    assert rc == 0 and res is not None and res["kind"] == "bss"
    by_name = {s.name: s for s in spans.snapshot()}
    study = by_name["lifted_run"]
    assert study.parent is None and study.request == study.id
    assert study.args["replicas"] == 4
    lift, launch = by_name["lift"], by_name["launch"]
    assert lift.parent == study.id and lift.request == study.id
    # the launch hangs under the study and is a request of its own,
    # which its children and the blocking caller's result.* share
    assert launch.parent == study.id and launch.request == launch.id
    assert lift.end <= launch.start
    for name in LAUNCH_CHILDREN:
        assert by_name[name].parent == launch.id
    for name in RESULT_SPANS:
        res_span = by_name[name]       # (of two result.fetch, the last)
        assert res_span.parent == study.id
        assert res_span.request == launch.id
        assert study.start <= res_span.start and res_span.end <= study.end


def test_xla_events_see_a_new_jit_once():
    import time

    CompileTelemetry.listen()
    compile_event = "/jax/core/compile/backend_compile_duration"

    def compiles(since):
        return [e for e in CompileTelemetry.xla_events(since=since)
                if e[1] == compile_event]

    fresh = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(7, dtype=jnp.float32)
    t0 = time.perf_counter()     # the ring is bounded: count by time, not length
    fresh(x).block_until_ready()
    (first,) = compiles(t0)
    t, event, seconds, fun_name = first
    assert seconds > 0 and t >= t0 and "lambda" in fun_name
    # the trace of the new function was seen too
    assert any(e[1].endswith("jaxpr_trace_duration")
               for e in CompileTelemetry.xla_events(since=t0))
    n_after_first = len(CompileTelemetry.xla_events(since=t0))
    fresh(x).block_until_ready()
    assert len(CompileTelemetry.xla_events(since=t0)) == n_after_first
    # `since` cuts by the event's own perf_counter time
    assert first in CompileTelemetry.xla_events(since=t)
    assert CompileTelemetry.xla_events(since=time.perf_counter() + 1.0) == []
    assert len(CompileTelemetry.xla_events()) <= 1 << 12


def test_engine_programs_compile_under_their_own_name():
    from tpudes.parallel.runtime import RUNTIME

    CompileTelemetry.listen()
    RUNTIME.clear("bss")                 # a new jit object: it compiles
    t0 = max([e[0] for e in CompileTelemetry.xla_events()], default=0.0)
    run_lifted("bss", toy_bss_program(), 8, jax.random.PRNGKey(11))
    compiled = [
        e[3] for e in CompileTelemetry.xla_events()
        if e[0] > t0 and e[1].endswith("backend_compile_duration")
    ]
    # the module name is in jax's persistent-cache key (op metadata is
    # not), and is the name on a profile's `XLA Modules` line
    assert "jit(tpudes_bss_advance)" in compiled


# --- the device names ---------------------------------------------------------

#: manifest module -> the engine name in its scopes (the hybrid driver's
#: lanes-of-one-kernel program is the wired engine's loop)
ENGINE_SCOPES = {
    "tpudes.parallel.replicated": "bss",
    "tpudes.parallel.lte_sm": "lte_sm",
    "tpudes.parallel.tcp_dumbbell": "dumbbell",
    "tpudes.parallel.as_flows": "as_flows",
    "tpudes.parallel.wired": "wired",
    "tpudes.parallel.hybrid": "wired",
}


#: the engines whose loop folds a scalar counter into the launch key:
#: their step keys come from ``runtime.step_keys`` (the LTE loops fold
#: per lane, under ``lte_sm.RNG_SCOPE``)
STEP_KEY_ENGINES = {"bss", "dumbbell"}


def _lowered(entry) -> str:
    return jax.jit(entry.fn).lower(*entry.args).as_text(debug_info=True)


@pytest.mark.parametrize("module", sorted(ENGINE_SCOPES))
def test_engine_loop_lowers_with_stable_scope_names(module):
    engine = ENGINE_SCOPES[module]
    manifest = importlib.import_module(module).trace_manifest()
    base = manifest.variants()[0]
    texts = [_lowered(e) for e in base.build() if e.kernel]
    for part in ("step", "cond"):
        scope = f"tpudes.{engine}.{part}"
        assert any(scope in t for t in texts), scope
    if engine in STEP_KEY_ENGINES:
        # the step's keys: runtime.step_keys, under a name of its own
        # inside the step, down to the threefry of its folds
        scope = f"tpudes.{engine}.step/tpudes.{engine}.rng/"
        assert any(
            scope + "optimization_barrier" in t
            and re.search(re.escape(scope) + r"[^\"]*_threefry_fold_in", t)
            for t in texts
        ), scope


@pytest.mark.parametrize("variant", ["base", "traffic"])
def test_lte_variants_name_the_rng_draw(variant):
    from tpudes.parallel import lte_sm

    (build,) = [
        v.build for v in lte_sm.trace_manifest().variants()
        if v.name == variant
    ]
    text = "".join(_lowered(e) for e in build() if e.kernel)
    for scope in ("tpudes.lte_sm.step", "tpudes.lte_sm.cond",
                  lte_sm.RNG_SCOPE):
        assert scope in text, scope


def test_pallas_lowering_carries_the_kernel_name():
    from tpudes.parallel import lte_sm
    from tpudes.parallel.kernels_pallas import SM_KERNEL_NAME
    from tpudes.parallel.runtime import replica_keys, stack_axis

    prog = toy_lte_program(n_enb=2, n_ue=3, n_ttis=40)
    _, init_state, fn = lte_sm.build_sm_advance(
        prog, r_pad=2, use_pallas=True
    )
    carry = (jnp.int32(0), stack_axis(init_state(), 2))
    text = jax.jit(fn).lower(
        carry, replica_keys(jax.random.PRNGKey(0), 2), jnp.int32(0),
        jnp.int32(8),
    ).as_text(debug_info=True)
    assert SM_KERNEL_NAME == "tpudes_lte_sm_tti"
    assert SM_KERNEL_NAME in text
    assert lte_sm.RNG_SCOPE in text and "tpudes.lte_sm.step" in text
    # the replica vmap of the step wraps the lane's scope, not ours: a
    # wrapped kernel name reads `vmap_tpudes_lte_sm_tti_` on the device
    assert f"vmap({lte_sm.LANE_SCOPE})/{SM_KERNEL_NAME}" in text
    assert f"vmap({lte_sm.LANE_SCOPE})/{lte_sm.RNG_SCOPE}" in text


# --- the aggregated BSS exchange (ISSUE 32) -----------------------------------

def _toy_ht_bss():
    import dataclasses

    return dataclasses.replace(
        toy_bss_program(), max_mpdus=8, subframe_bytes=580
    )


def test_aggregated_bss_launch_span_names_max_mpdus():
    """A ``bss`` launch whose exchanges are A-MPDUs says so on its
    ``launch`` span (how much of a window the mechanism did is then a
    count of spans); the legacy launch carries no such argument."""
    run_lifted("bss", _toy_ht_bss(), 8, jax.random.PRNGKey(7))
    (launch,) = [s for s in spans.snapshot() if s.name == "launch"]
    assert launch.args["max_mpdus"] == 8
    run_lifted("bss", _toy("bss"), 8, jax.random.PRNGKey(7))
    legacy = [s for s in spans.snapshot() if s.name == "launch"][-1]
    assert "max_mpdus" not in legacy.args


@pytest.mark.parametrize("aggregated", [True, False], ids=["ht", "legacy"])
def test_ampdu_scope_names_the_aggregated_step_only(aggregated):
    """``tpudes.bss.ampdu`` is in the lowered program's debug info where
    the A-MPDU arm is compiled in, and nowhere in the legacy program."""
    from tpudes.parallel.replicated import _trace_entries

    prog = _toy_ht_bss() if aggregated else toy_bss_program()
    text = "".join(
        _lowered(e) for e in _trace_entries(prog, scale=False) if e.kernel
    )
    assert "tpudes.bss.step" in text
    assert ("tpudes.bss.ampdu" in text) is aggregated


# --- the TCP dumbbell's slot (ISSUE 35) ---------------------------------------

@pytest.mark.parametrize("scope", [
    "tpudes.dumbbell.rng", "tpudes.dumbbell.cc", "tpudes.dumbbell.queue",
])
def test_dumbbell_slot_names_its_three_parts(scope):
    """The per-replica draws, the seventeen window rules and the
    bottleneck queue are scopes of their own inside
    ``tpudes.dumbbell.step``, in the lowered program's debug info."""
    from tpudes.parallel import tcp_dumbbell

    (base,) = [
        v for v in tcp_dumbbell.trace_manifest().variants()
        if v.name == "base"
    ]
    text = "".join(_lowered(e) for e in base.build() if e.kernel)
    assert scope in (tcp_dumbbell.RNG_SCOPE, tcp_dumbbell.CC_SCOPE,
                     tcp_dumbbell.QUEUE_SCOPE)
    assert f"tpudes.dumbbell.step/{scope}" in text


def test_dumbbell_launch_span_names_flows_and_variants():
    """What the masked-dense rules were run for is read off the
    ``launch`` span: the flow count and the sorted names of the variants
    present, a sweep's points pooled; a BSS or LTE launch has neither."""
    import dataclasses

    import numpy as np

    toy, key = _toy("dumbbell"), jax.random.PRNGKey(7)
    prog = dataclasses.replace(toy, variant_idx=np.asarray([1, 1], np.int32))
    run_lifted("dumbbell", prog, 4, key)
    (launch,) = [s for s in spans.snapshot() if s.name == "launch"]
    assert launch.args["n_flows"] == 2
    assert launch.args["variants"] == ["TcpCubic"]
    run_lifted("dumbbell", toy, 4, key)
    toy_launch = [s for s in spans.snapshot() if s.name == "launch"][-1]
    assert toy_launch.args["variants"] == ["TcpCubic", "TcpNewReno"]
    run_lifted("dumbbell", prog, 4, key,
               variants=[["TcpVegas", "TcpCubic"], ["TcpCubic", "TcpBbr"]])
    swept = [s for s in spans.snapshot() if s.name == "launch"][-1]
    assert swept.args["n_flows"] == 2
    assert swept.args["variants"] == ["TcpBbr", "TcpCubic", "TcpVegas"]
    for kind in ("bss", "lte_sm"):
        run_lifted(kind, _toy(kind), 4, key)
        other = [s for s in spans.snapshot() if s.name == "launch"][-1]
        assert other.args["kind"] == kind
        assert "n_flows" not in other.args and "variants" not in other.args
