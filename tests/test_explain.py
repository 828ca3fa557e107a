"""tpudes.obs.explain (ISSUE 37): the program reads its own device trace.

- ``reduce`` on hand-made event lists: the innermost scope wins, a nested
  ``while`` counts once, the parts sum to ``step_us``, a gap is split over
  two spans by overlap and not by its middle, iterations are the modal
  count with an operation inside a conditional, a cut trace and stale
  names give ``withheld`` and no number, ``no_event`` lists ``.cond``;
- ``load`` on a small ``.xplane.pb`` recorded on the chip at toy sizes
  (``tests/data/explain_toy.xplane.pb``);
- ``session`` clips ``Launch.drive``'s bounds for every engine kind with
  no new compile and leaves an unclipped launch's result bit-equal, as it
  leaves another thread's launch whole (and then withholds its table);
  ``replay()`` is None before any launch and re-runs the last one after.
"""

import os
import threading

import jax
import numpy as np
import pytest

from tpudes.obs import explain, spans
from tpudes.obs.device import CompileTelemetry
from tpudes.parallel.lift import run_lifted
from tpudes.parallel.programs import (
    toy_as_program,
    toy_bss_program,
    toy_dumbbell_program,
    toy_lte_program,
)
from tpudes.parallel.runtime import RUNTIME

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "explain_toy.xplane.pb")
US = 1e3                    # the lists are in ns
STEP = "jit(tpudes_bss_advance)/jit(main)/while/body/tpudes.bss.step"
PLANE = "/device:TPU:0"
ITERATIONS = 5


# --- a hand-made trace -------------------------------------------------------

#: one iteration of the loop body: (name, tf_op, us); the rest of an
#: iteration's 6 us is the while's own
BODY = [
    ("fusion.10", STEP + "/add", 2.0),
    ("fusion.11", STEP + "/tpudes.bss.rng/vmap(xor)", 1.0),
    ("copy.3", "", 0.5),
    ("bitcast.2", "", 0.25),
]
#: an inner loop, in every iteration: its own 0.5 us reads under `.step`,
#: its three `fusion.20` of 0.25 us under `.ampdu`
INNER = ("while.7", STEP + "/while", 1.25)
INNER_OP = ("fusion.20", STEP + "/while/body/tpudes.bss.ampdu/mul", 0.25)
#: a conditional whose branch ran in 2 of the 5 iterations
BRANCH = ("conditional.1", STEP + "/cond", 0.5)
BRANCH_OP = ("fusion.30", STEP + "/cond/branch_1_fun/select_n", 0.25)
ITERATION_US = 6.0


def one_launch(t0: float, iterations: int = ITERATIONS, fetch_us: float = 10):
    """`(ops, modules, host)` of one launch that starts at `t0` ns: an init
    program of two operations, an advance program of a prologue operation
    and the loop, and the spans `run_lifted` leaves around them (the
    result's transfer takes `fetch_us`)."""
    ops, at = [], t0 + 90 * US
    ops += [("fusion.1", "jit(tpudes_bss_init)/broadcast", t0 + 40 * US, 10 * US),
            ("copy.1", "", t0 + 52 * US, 5 * US),
            ("fusion.9", "jit(tpudes_bss_advance)/convert", t0 + 82 * US, 4 * US)]
    loop_us = iterations * ITERATION_US
    ops.append(("while.4", "jit(tpudes_bss_advance)/while", at, loop_us * US))
    for i in range(iterations):
        t = at + i * ITERATION_US * US
        for name, tf_op, us in BODY:
            ops.append((name, tf_op, t, us * US))
            t += us * US
        ops.append((INNER[0], INNER[1], t, INNER[2] * US))
        for k in range(3):
            ops.append((INNER_OP[0], INNER_OP[1], t + k * 0.25 * US, 0.25 * US))
        t += INNER[2] * US
        if i in (1, 3):
            ops.append((BRANCH[0], BRANCH[1], t, BRANCH[2] * US))
            ops.append((BRANCH_OP[0], BRANCH_OP[1], t, BRANCH_OP[2] * US))
    end = at + loop_us * US
    modules = [("jit_tpudes_bss_init(11)", t0 + 39 * US, 20 * US),
               ("jit_tpudes_bss_advance(12)", t0 + 81 * US, end + US - t0 - 81 * US)]
    host = [
        ("launch", t0, 100 * US, {"kind": "bss", "replicas": 8}),
        ("launch.runner", t0 + 5 * US, 10 * US, {"engine": "bss"}),
        ("launch.operands", t0 + 20 * US, 30 * US, {}),
        ("launch.enqueue", t0 + 60 * US, 30 * US, {"engine": "bss", "chunks": 1}),
        ("result.fetch", t0 + 100 * US, 2 * US, {}),
        ("result.wait", t0 + 102 * US, end + 3 * US - t0 - 102 * US, {}),
        ("result.fetch", end + 3 * US, fetch_us * US, {}),
        ("result.unpack", end + (3 + fetch_us) * US, 5 * US, {}),
    ]
    return ops, modules, host


def hand_made(launches: int = 2, fetch_us=None, **extra) -> dict:
    ops, modules, host = [], [], []
    for k in range(launches):
        o, m, h = one_launch(
            1000 * US * (k + 1), fetch_us=fetch_us[k] if fetch_us else 10)
        ops, modules, host = ops + o, modules + m, host + h
    return dict(devices={PLANE: ops}, modules={PLANE: modules}, host=host,
                **extra)


def test_step_is_split_by_innermost_scope_and_the_parts_sum_to_it():
    table = explain.reduce(hand_made())
    assert table["withheld"] is None
    assert table["launches"] == 2 and table["devices"] == 1
    loop = table["loop"]
    assert loop["iterations"] == ITERATIONS
    assert loop["step_us"] == pytest.approx(ITERATION_US)
    scopes = loop["scopes"]
    # `.rng` inside `.step` reads under `.rng`; the inner loop's own time and
    # the conditional (2 of 5 iterations) read under `.step`, where they trace
    assert scopes["tpudes.bss.rng"] == {"us": pytest.approx(1.0), "ops": 1}
    assert scopes["tpudes.bss.ampdu"] == {"us": pytest.approx(0.75), "ops": 1}
    step = 2.0 + (1.25 - 0.75) + (0.5 - 0.25 + 0.25) * 2 / 5
    assert scopes["tpudes.bss.step"]["us"] == pytest.approx(step)
    assert scopes["tpudes.bss.step"]["ops"] == 4
    assert loop["copies_us"] == pytest.approx(0.5)
    assert loop["unscoped_us"] == pytest.approx(0.25)
    own = ITERATION_US - (2.0 + 1.0 + 0.5 + 0.25 + 1.25) - 0.5 * 2 / 5
    assert loop["own_us"] == pytest.approx(own)
    parts = (sum(s["us"] for s in scopes.values()) + loop["own_us"]
             + loop["copies_us"] + loop["unscoped_us"])
    assert parts == pytest.approx(loop["step_us"], rel=1e-12)
    assert loop["events_per_step"] == pytest.approx(4 + 1 + 3 + 2 * 2 / 5)
    assert loop["top"][0] == ["fusion.10", "tpudes.bss.step", pytest.approx(2.0)]
    assert len(loop["top"]) <= explain.TOP and loop["no_event"] is None


def test_a_nested_while_counts_once_by_the_outer_one():
    table = explain.reduce(hand_made(launches=1))
    # one loop a launch: `while.7` is an operation of `while.4`'s body
    assert table["loop"]["iterations"] == ITERATIONS
    assert table["loop"]["step_us"] == pytest.approx(ITERATION_US)
    assert "while.7" in [name for name, _, _ in table["loop"]["top"]]


def test_device_time_outside_the_loop_goes_to_its_program():
    table = explain.reduce(hand_made())
    assert table["outside_loop_ms"] == {
        "jit_tpudes_bss_advance": pytest.approx(4e-3),
        "jit_tpudes_bss_init": pytest.approx(15e-3),
    }
    assert table["busy_ms"] == pytest.approx((19 + 30) * 1e-3)


AS = "jit(tpudes_as_flows_advance)/jit(main)"
SPF, DELAY = AS + "/tpudes.as_flows.spf", AS + "/tpudes.as_flows.delay"
AS_STEP = AS + "/while/body/tpudes.as_flows.step"


def as_launch(t0: float) -> list:
    """The device operations of one launch of a program with three outermost
    whiles: a 20 us shortest-path scan of 4 rounds before the loop, the loop of
    4 rounds of 10 us (each a 6 us load walk under `.load`, 3 us under `.step`),
    a 5 us delay scan after it, and 2 us of output assembly under no scope."""
    us = lambda t: t0 + t * US
    ops = [("fusion.1", "jit(tpudes_as_flows_init)/normal", us(0), 4 * US),
           ("while.3", SPF + "/while", us(10), 20 * US)]
    ops += [("fusion.2", SPF + "/while/body/scatter", us(10 + 5 * i), 4 * US)
            for i in range(4)]
    ops.append(("while.8", AS + "/while", us(30), 40 * US))
    for i in range(4):
        t = 30 + 10 * i
        ops += [("while.9", AS_STEP + "/tpudes.as_flows.load/while", us(t), 6 * US),
                ("fusion.3", AS_STEP + "/tpudes.as_flows.load/while/body/scatter",
                 us(t), 5 * US),
                ("fusion.4", AS_STEP + "/log", us(t + 6), 3 * US)]
    ops += [("while.10", DELAY + "/while", us(70), 5 * US),
            ("fusion.5", DELAY + "/while/body/add", us(70), 4 * US),
            ("fusion.6", AS + "/concatenate", us(75), 2 * US)]
    return ops


def test_outside_the_loop_by_scope_adds_up_to_it_by_program():
    host, ops, modules = [], [], []
    for k in range(2):
        t0 = 1000 * US * (k + 1)
        ops += as_launch(t0)
        modules += [("jit_tpudes_as_flows_init(1)", t0, 5 * US),
                    ("jit_tpudes_as_flows_advance(2)", t0 + 9 * US, 70 * US)]
        host.append(("launch", t0 - 5 * US, 4 * US, {"kind": "as_flows"}))
    lowered = {"tpudes.as_flows.step", "tpudes.as_flows.cond", "tpudes.as_flows.load",
               "tpudes.as_flows.spf", "tpudes.as_flows.delay"}
    events = dict(devices={PLANE: ops}, modules={PLANE: modules}, host=host,
                  lowered={"tpudes_as_flows_advance": lowered})
    table = explain.reduce(events)
    assert table["withheld"] is None
    # the loop is the while under `.step`; the other two are outside it
    loop = table["loop"]
    assert loop["iterations"] == 4 and loop["step_us"] == pytest.approx(10.0)
    assert loop["scopes"]["tpudes.as_flows.load"]["us"] == pytest.approx(6.0)
    assert loop["scopes"]["tpudes.as_flows.step"]["us"] == pytest.approx(3.0)
    assert table["outside_loop_ms"] == {
        "jit_tpudes_as_flows_init": pytest.approx(4e-3),
        "jit_tpudes_as_flows_advance": pytest.approx(27e-3),
    }
    scopes = table["outside_scopes_ms"]
    assert scopes == {
        "tpudes.as_flows.spf": pytest.approx(20e-3),
        "tpudes.as_flows.delay": pytest.approx(5e-3),
        explain.NO_SCOPE: pytest.approx(6e-3),
    }
    assert sum(scopes.values()) == pytest.approx(sum(table["outside_loop_ms"].values()))
    assert "tpudes.as_flows.spf" in explain.format_table(table)
    # a scope outside the loop that the tree's lowered program does not name
    lowered.discard("tpudes.as_flows.spf")
    table = explain.reduce(events)
    assert "stale" in table["withheld"] and "tpudes.as_flows.spf" in table["withheld"]
    assert table["outside_scopes_ms"] is None and table["outside_loop_ms"] is None
    # the BSS launch has one loop and no scope outside it
    assert explain.reduce(hand_made())["outside_scopes_ms"] == {
        explain.NO_SCOPE: pytest.approx(19e-3)}


def test_a_gap_is_split_over_the_spans_by_overlap_not_by_its_middle():
    table = explain.reduce(hand_made())
    idle = table["idle_ms"]
    # the first gap of a launch runs from its start to the init program's
    # first operation at +40 us: its middle lies in `launch.operands`, which
    # covers 20 of the 40 us; runner 10, launch itself 5 + 5
    assert idle["launch.runner"] == pytest.approx(10e-3)
    assert idle["launch.operands"] == pytest.approx(20e-3)
    # `launch` itself: around the runner, between the init program's two
    # operations (operands closed at +50), and until the enqueue opens
    assert idle["launch"] == pytest.approx((5 + 5 + 2 + 3) * 1e-3)
    assert idle["launch.enqueue"] == pytest.approx((22 + 4) * 1e-3)
    assert idle["result.wait"] == pytest.approx(3e-3)
    assert idle["result.fetch"] == pytest.approx(10e-3)
    assert idle["result.unpack"] == pytest.approx(5e-3)
    assert sum(idle.values()) == pytest.approx(
        table["wall_ms"] - table["busy_ms"])
    # between the two launches nothing of ours is open
    assert idle[explain.OUTSIDE] == pytest.approx(862e-3 / 2)
    assert table["idle_each_ms"] == pytest.approx([(89 + 862) * 1e-3, 89e-3])


def test_a_launch_reads_as_the_median_launch_and_the_warm_up_is_left_out():
    # the first launch follows `start_trace` and fetches 400 us longer, the
    # third is held up for 300 us
    events = hand_made(launches=5, fetch_us=[410, 10, 310, 10, 10])
    base = explain.reduce(hand_made(launches=5))
    table = explain.reduce(dict(events, warm_up=1))
    assert table["withheld"] is None
    assert table["launches"] == 4 and table["launch"]["warm_up"] == 1
    # the first launch is not read, the third does not move the median of
    # four; the loop is read over the four
    spans_only = {k: v for k, v in base["idle_ms"].items() if k != explain.OUTSIDE}
    assert {k: v for k, v in table["idle_ms"].items()
            if k != explain.OUTSIDE} == pytest.approx(spans_only)
    assert table["idle_each_ms"] == pytest.approx(base["idle_each_ms"][1:])
    assert table["loop"] == base["loop"]
    assert table["outside_loop_ms"] == pytest.approx(base["outside_loop_ms"])
    whole = explain.reduce(events)
    assert whole["launches"] == 5 and "warm_up" not in whole["launch"]
    # a mean would have read 10 + (400 + 300) / 5 us
    assert whole["idle_ms"]["result.fetch"] == pytest.approx(10e-3)


def test_launch_carries_the_span_arguments_and_the_runtime_counts():
    table = explain.reduce(hand_made(
        launch_args=[{"kind": "bss", "replicas": 8, "max_mpdus": 64}],
        runtime={"init_programs": 1, "hits": 5, "misses": 1, "resident": 1},
        max_iterations=4096,
    ))
    assert table["launch"] == {
        "kind": "bss", "replicas": 8, "max_mpdus": 64, "init_programs": 1,
        "hits": 5, "misses": 1, "shortened_to": 4096,
    }


def numbers(table) -> list:
    return [table[k] for k in
            ("loop", "outside_loop_ms", "outside_scopes_ms", "idle_ms", "wall_ms",
             "busy_ms")]


def test_a_launch_of_another_thread_in_the_window_is_withheld():
    table = explain.reduce(hand_made(foreign=1))
    assert "other threads" in table["withheld"]
    assert numbers(table) == [None] * 6 and table["idle_each_ms"] is None


def test_a_cut_trace_is_withheld_the_loops_event_lost():
    events = hand_made()
    # the profiler's buffer ran over in the second launch: its `while` event
    # never closed, so the body's operations read as outermost
    whiles = [e for e in events["devices"][PLANE] if e[0] == "while.4"]
    events["devices"][PLANE].remove(whiles[-1])
    table = explain.reduce(events)
    assert "cut" in table["withheld"] and "fusion.10" in table["withheld"]
    assert numbers(table) == [None] * 6
    assert table["launch"]["kind"] == "bss" and table["launches"] == 2


def test_a_cut_trace_is_withheld_a_launch_without_a_loop():
    events = hand_made()
    second = 2000 * US
    events["devices"][PLANE] = [
        e for e in events["devices"][PLANE] if e[2] < second + 85 * US
    ]
    table = explain.reduce(events)
    assert "launch 2 of 2 has no loop" in table["withheld"]
    assert numbers(table) == [None] * 6


def test_stale_names_are_withheld_and_scopes_without_events_listed():
    lowered = {"tpudes.bss.step", "tpudes.bss.cond", "tpudes.bss.rng",
               "tpudes.bss.ampdu"}
    table = explain.reduce(hand_made(lowered={"tpudes_bss_advance": lowered}))
    assert table["withheld"] is None
    # a scalar condition leaves no device event: listed, not a fault
    assert table["loop"]["no_event"] == ["tpudes.bss.cond"]
    # the tree at hand names no `.ampdu`: the executable is another tree's
    lowered.discard("tpudes.bss.ampdu")
    table = explain.reduce(hand_made(lowered={"tpudes_bss_advance": lowered}))
    assert "stale" in table["withheld"] and "tpudes.bss.ampdu" in table["withheld"]
    assert numbers(table) == [None] * 6


def test_nothing_to_read_is_withheld_not_an_error():
    assert "no tpudes:launch" in explain.reduce(
        {"devices": {}, "modules": {}, "host": []})["withheld"]
    events = hand_made()
    events["devices"] = {}
    assert "no /device:TPU plane" in explain.reduce(events)["withheld"]


def test_two_devices_average_and_the_first_one_owns_the_gaps():
    events = hand_made()
    slow = [(n, t, s, d) for n, t, s, d in events["devices"][PLANE]]
    events["devices"]["/device:TPU:1"] = [
        (n, t, s, d * 2 if n == "fusion.9" else d) for n, t, s, d in slow
    ]
    events["modules"]["/device:TPU:1"] = events["modules"][PLANE]
    one, two = explain.reduce(hand_made()), explain.reduce(events)
    assert two["devices"] == 2
    assert two["loop"]["step_us"] == pytest.approx(one["loop"]["step_us"])
    assert two["outside_loop_ms"]["jit_tpudes_bss_advance"] == pytest.approx(6e-3)
    assert two["idle_ms"] == one["idle_ms"]


def test_scope_and_name_helpers():
    assert explain.scope_of(STEP + "/tpudes.bss.rng/vmap(xor)") == "tpudes.bss.rng"
    assert explain.scope_of(STEP + "/mul:") == "tpudes.bss.step"
    # jax's wrapping of the first scope under a vmap is not a scope of ours
    assert explain.scope_of(
        "jit(x)/tpudes.lte_sm.step/vmap(tpudes.lte_sm.lane)/add"
    ) == "tpudes.lte_sm.step"
    assert explain.scope_of("jit(x)/while/body/add") is None
    assert explain.program_name("jit_tpudes_bss_advance(123)") == (
        "jit_tpudes_bss_advance")
    assert explain.op_name("%while.4 = (s32[]) while(%tuple)") == "while.4"
    assert explain.scopes_in(
        'loc("jit(f)/tpudes.bss.step/tpudes.bss.rng/xor"("/r/tpudes/x.py":1)) '
        'loc("jit(f)/while/cond/tpudes.bss.cond/lt") vmap(tpudes.lte_sm.lane)'
    ) == {"tpudes.bss.step", "tpudes.bss.rng", "tpudes.bss.cond"}
    assert "WITHHELD" in explain.format_table(explain.reduce(
        {"devices": {}, "modules": {}, "host": []}))
    text = explain.format_table(explain.reduce(hand_made()))
    assert "tpudes.bss.rng" in text and "launch.operands" in text


# --- a trace recorded on the chip ---------------------------------------------


def test_load_reads_a_recorded_trace_with_both_forms_of_tf_op():
    """Two launches of the toy BSS program (8 replicas, 4 stations, loops
    stopped at 6 steps) on a TPU v5e, cut to a few hundred events."""
    xplane = explain._xplane_pb2()
    space = xplane.XSpace()
    with open(RECORDED, "rb") as f:
        space.ParseFromString(f.read())
    (device,) = [p for p in space.planes if p.name.startswith("/device:TPU:")]
    tf_op = {k for k, v in device.stat_metadata.items() if v.name == "tf_op"}
    forms = {
        stat.WhichOneof("value")
        for md in device.event_metadata.values()
        for stat in md.stats if stat.metadata_id in tf_op
    }
    assert forms == {"str_value", "ref_value"}
    (by_ref,) = [
        explain.op_name(md.name) for md in device.event_metadata.values()
        for stat in md.stats
        if stat.metadata_id in tf_op and stat.WhichOneof("value") == "ref_value"
    ]

    events = explain.load(RECORDED)
    assert list(events["devices"]) == [device.name]
    ops = events["devices"][device.name]
    assert 100 < len(ops) < 2000
    # the reference is followed into `stat_metadata`, a string read as it is
    assert {tf_op for name, tf_op, _, _ in ops if name == by_ref} == {
        "jit(tpudes_bss_advance)/while/body/tpudes.bss.step/tpudes.bss.rng/"
        "vmap(vmap())/vmap(vmap(jit(_threefry_fold_in)))/slice:"}
    assert sum("tpudes.bss.step" in tf_op for _, tf_op, _, _ in ops) > 300
    assert {explain.program_name(m[0]) for m in events["modules"][device.name]} >= {
        "jit_tpudes_bss_init", "jit_tpudes_bss_advance"}
    launches = [e for e in events["host"] if e[0] == "launch"]
    assert len(launches) == 2
    assert launches[0][3] == {"kind": "bss", "replicas": 8}
    assert {e[0] for e in events["host"]} >= {
        "launch.runner", "launch.operands", "launch.enqueue", "result.wait",
        "result.fetch", "result.unpack"}
    # a directory is searched for its newest trace
    assert explain.load(os.path.dirname(RECORDED))["host"] == events["host"]

    table = explain.reduce(events)
    assert table["withheld"] is None and table["launches"] == 2
    loop = table["loop"]
    assert loop["iterations"] == 6
    assert {"tpudes.bss.step", "tpudes.bss.rng"} <= set(loop["scopes"])
    parts = (sum(s["us"] for s in loop["scopes"].values()) + loop["own_us"]
             + loop["copies_us"] + loop["unscoped_us"])
    assert parts == pytest.approx(loop["step_us"], rel=1e-9)
    assert sum(table["idle_ms"].values()) == pytest.approx(
        table["wall_ms"] - table["busy_ms"])
    assert set(table["outside_loop_ms"]) >= {
        "jit_tpudes_bss_init", "jit_tpudes_bss_advance"}
    assert loop["step_us"] == pytest.approx(7.268, abs=1e-3)
    assert loop["scopes"]["tpudes.bss.rng"]["us"] == pytest.approx(1.568, abs=1e-3)
    assert loop["own_us"] == pytest.approx(1.911, abs=1e-3)


def test_the_obs_command_prints_a_traces_table(capsys):
    from tpudes.obs.__main__ import main

    assert main(["--explain", RECORDED]) == 0
    out = capsys.readouterr().out
    assert "tpudes.bss.step" in out and "jit_tpudes_bss_init" in out
    assert main(["--explain", os.path.join(HERE, "no-such-trace")]) == 2


# --- the session and the replay -------------------------------------------------


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


def _run(kind, prog, key, **kwargs):
    if kind == "wired":          # not a lifted kind: the engine's own entry
        from tpudes.parallel.wired import run_wired

        return run_wired(prog, key, 8, **kwargs)
    return run_lifted(kind, prog, 8, key, **kwargs)


def _same(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
        for k in a if not isinstance(a[k], dict)
    )


@pytest.fixture
def bounds_seen(monkeypatch):
    """The bounds the advance programs were handed, through `drive_chunks`."""
    from tpudes.parallel import runtime

    seen, real = [], runtime.drive_chunks

    def spy(engine, bounds, *args, **kwargs):
        seen.append(list(bounds))
        return real(engine, bounds, *args, **kwargs)

    monkeypatch.setattr(runtime, "drive_chunks", spy)
    return seen


@pytest.mark.parametrize(
    "kind", ["bss", "lte_sm", "dumbbell", "as_flows", "wired"]
)
def test_session_clips_the_bounds_of_every_engine_without_a_compile(
        kind, bounds_seen):
    import time

    CompileTelemetry.listen()
    prog, key = _toy(kind), jax.random.PRNGKey(37)
    whole = _run(kind, prog, key)
    (full,) = bounds_seen
    assert full[-1] > 3
    t0 = time.perf_counter()
    with explain.session(max_iterations=3) as s:
        assert RUNTIME.explain is s
        _run(kind, prog, key)
    compiles = [e for e in CompileTelemetry.xla_events(since=t0)
                if e[1].endswith("backend_compile_duration")]
    assert compiles == []
    assert bounds_seen[1] == [3]
    assert RUNTIME.explain is None
    # on the CPU there is no device plane: the table says so, and carries what
    # the launch span and the runtime recorded
    assert ("no tpudes:launch" if kind == "wired" else
            "no /device:TPU plane") in s.table["withheld"]
    assert s.table["launch"]["shortened_to"] == 3
    assert s.table["launch"]["misses"] == RUNTIME.stats()["misses"]
    if kind != "wired":
        assert s.table["launch"]["kind"] == kind
        assert s.table["launches"] == 1
    # and the launch after the session is whole again, bit for bit
    again = _run(kind, prog, key)
    assert bounds_seen[2] == full
    assert _same(whole, again)


def test_session_clips_each_segment_of_a_chunked_launch(bounds_seen):
    prog, key = toy_dumbbell_program(n_flows=2, n_slots=30), jax.random.PRNGKey(3)
    with explain.session(max_iterations=14):
        run_lifted("dumbbell", prog, 8, key, chunk_slots=8)
    assert bounds_seen == [[8, 14]]
    with explain.session(max_iterations=None) as s:
        run_lifted("dumbbell", prog, 8, key, chunk_slots=8)
    assert bounds_seen[1] == [8, 16, 24, 30]
    assert "shortened_to" not in s.table["launch"]


def test_session_leaves_another_threads_launch_whole_and_says_so(bounds_seen):
    """A server's launch that falls into someone's session is not cut to
    the session's bound: its caller would get a shortened study's answer
    with no mark on it."""
    prog, key = toy_dumbbell_program(n_flows=2, n_slots=30), jax.random.PRNGKey(3)
    whole = run_lifted("dumbbell", prog, 8, key)
    got = []
    with explain.session(max_iterations=5) as s:
        other = threading.Thread(
            target=lambda: got.append(run_lifted("dumbbell", prog, 8, key)))
        other.start()
        other.join()
        run_lifted("dumbbell", prog, 8, key)
    assert bounds_seen == [[30], [30], [5]]
    assert _same(whole, got[0])
    assert s.foreign == 1 and "1 launches of other threads" in s.table["withheld"]


def test_session_lowers_each_advance_program_once_for_its_scopes(monkeypatch):
    seen = []
    real = explain.reduce
    monkeypatch.setattr(explain, "reduce", lambda ev: seen.append(ev) or real(ev))
    prog, key = toy_dumbbell_program(n_flows=2, n_slots=30), jax.random.PRNGKey(3)
    with explain.session(max_iterations=5):
        for _ in range(2):
            run_lifted("dumbbell", prog, 8, key)
    (events,) = seen
    assert events["lowered"] == {"tpudes_dumbbell_advance": {
        "tpudes.dumbbell.step", "tpudes.dumbbell.cond", "tpudes.dumbbell.rng",
        "tpudes.dumbbell.cc", "tpudes.dumbbell.queue"}}
    assert [a["kind"] for a in events["launch_args"]] == ["dumbbell"] * 2
    assert events["launch_args"][0]["n_flows"] == 2


def test_a_trace_of_someone_elses_is_an_error_not_a_second_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with pytest.raises(RuntimeError, match="already running"):
            with explain.session():
                pass
        assert RUNTIME.explain is None
    finally:
        jax.profiler.stop_trace()
    with explain.session() as outer:
        with pytest.raises(RuntimeError, match="already open"):
            with explain.session():
                pass
        assert RUNTIME.explain is outer
    assert RUNTIME.explain is None


def test_session_leaves_no_table_and_no_hook_when_the_block_raises():
    with pytest.raises(ZeroDivisionError):
        with explain.session() as s:
            1 / 0
    assert s.table is None and RUNTIME.explain is None
    assert not os.path.exists(s._dir)


def test_replay_is_none_before_any_launch_and_reruns_the_last_after(
        monkeypatch, bounds_seen):
    monkeypatch.setattr(RUNTIME, "last_lifted", None)
    assert explain.replay() is None
    prog, key = toy_bss_program(), jax.random.PRNGKey(5)
    run_lifted("lte_sm", _toy("lte_sm"), 8, key)
    fut = run_lifted("bss", prog, 8, key, block=False)
    fut.result()
    last = RUNTIME.last_lifted
    assert last[0] == "bss" and last[1] is prog and last[5] == {"block": False}
    spans.reset()
    before = RUNTIME.launches("bss")
    table = explain.replay(max_iterations=4)
    assert RUNTIME.launches("bss") == before + explain.LAUNCHES
    assert bounds_seen[-explain.LAUNCHES:] == [[4]] * explain.LAUNCHES
    # the first launch follows `start_trace`: traced, not read
    assert table["launches"] == explain.LAUNCHES - explain.WARM_UP
    assert table["launch"]["kind"] == "bss"
    assert table["launch"]["shortened_to"] == 4
    assert table["launch"]["warm_up"] == explain.WARM_UP
    # blocking replays: each launch's result spans are in the ring
    assert sum(s.name == "result.unpack" for s in spans.snapshot()) == (
        explain.LAUNCHES)
    assert RUNTIME.last_lifted is last and RUNTIME.explain is None


def test_importing_tpudes_imports_neither_explain_nor_the_proto_module():
    import subprocess
    import sys

    code = (
        "import sys, tpudes, tpudes.obs, tpudes.parallel.runtime\n"
        "bad = [m for m in sys.modules if m == 'tpudes.obs.explain' "
        "or m.endswith('xplane_pb2') or m == 'tensorflow']\n"
        "assert not bad, bad\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "-c", code], env=env, timeout=120,
                          capture_output=True, text=True,
                          cwd=os.path.dirname(HERE))
    assert done.returncode == 0, done.stderr
