"""The readers of the program's own spans (`layers/_program_spans.py` and the
ten metrics that use it): a hand-made ring against hand-made harness spans,
what the window leaves out, a program without spans, the manifest entries, and
the harness end to end at toy size with the program's real ring."""

import collections

import jax
import pytest

from conftest import ROOT

from benchmark import run
from benchmark.layers import _program_spans as ps
from benchmark.manifest import Manifest

MC_METRICS = ["launch_runner_ms", "launch_operands_ms", "launch_enqueue_ms",
              "launch_self_ms", "result_fetch_ms", "result_unpack_ms",
              "xla_compiles_in_window"]
SCRIPT_METRICS = ["script_launch_ms", "script_result_ms",
                  "script_xla_compiles_in_window"]

Entry = collections.namedtuple("Entry", "name start end request")


def launch(request, t, runner, operands, enqueue, rest, wait, fetch, unpack):
    """One launch's spans as the program records them, starting at `t` (s);
    durations in ms.  Returns the entries and the time the launch ended."""
    ms = 1e-3
    out, at = [], t + rest * ms / 2          # half of the self time up front
    for name, d in (("launch.runner", runner), ("launch.operands", operands),
                    ("launch.enqueue", enqueue)):
        out.append(Entry(name, at, at + d * ms, request))
        at += d * ms
    at += rest * ms / 2
    out.append(Entry("launch", t, at, request))
    for name, d in (("result.wait", wait), ("result.fetch", fetch),
                    ("result.unpack", unpack)):
        out.append(Entry(name, at, at + d * ms, request))
        at += d * ms
    return out, at


def hand_made():
    """Two traced launches (set aside), three window launches, one launch after
    the window (the check's rerun); harness spans around each as `mc` makes
    them."""
    sizes = [                   # runner operands enqueue self wait fetch unpack
        (9.0, 9.0, 9.0, 9.0, 9.0, 9.0, 9.0),        # traced
        (9.0, 9.0, 9.0, 9.0, 9.0, 9.0, 9.0),        # traced
        (1.0, 6.0, 2.0, 0.4, 50.0, 0.5, 0.2),
        (3.0, 4.0, 2.5, 0.6, 50.0, 0.7, 0.4),
        (2.0, 5.0, 9.0, 0.5, 50.0, 0.6, 0.3),
        (7.0, 7.0, 7.0, 7.0, 7.0, 7.0, 7.0),        # after the window
    ]
    ring, starts, durations, t = [], {}, {}, 100.0
    for i, size in enumerate(sizes):
        entries, end = launch(i + 1, t, *size)
        ring += entries
        done = next(e.end for e in entries if e.name == "launch")
        waited = next(e.end for e in entries if e.name == "result.wait")
        prefix = "traced_" if i < 2 else ""
        if i < 5:
            for name, a, b in (("dispatch", t, done), ("wait", done, waited),
                               ("fetch_unpack", waited, end)):
                starts.setdefault(prefix + name, []).append(a)
                durations.setdefault(prefix + name, []).append(b - a)
        t = end + 0.001
    return ring, {"starts": starts, "spans": durations}


@pytest.fixture
def hand(monkeypatch):
    ring, ctx = hand_made()
    monkeypatch.setattr(ps, "ring", lambda: ring)
    return ctx


@pytest.mark.parametrize("metric,expected", [
    ("launch_runner_ms", 2.0), ("launch_operands_ms", 5.0),
    ("launch_enqueue_ms", 2.5), ("launch_self_ms", 0.5),
    ("result_fetch_ms", 0.6), ("result_unpack_ms", 0.3),
    ("script_launch_ms", 10.1),         # 9.4, 10.1, 16.5 whole launches
    ("script_result_ms", 0.9),          # 0.7, 1.1, 0.9
])
def test_readers_give_the_known_per_launch_medians(hand, metric, expected):
    value = Manifest(ROOT).layer_reader(metric)(hand)
    assert value == pytest.approx(expected, abs=1e-6)


def test_window_is_cut_by_the_untraced_harness_spans(hand):
    t0, t1 = ps.window(hand)
    assert t0 == pytest.approx(min(hand["starts"]["dispatch"]))
    assert t0 > max(hand["starts"]["traced_dispatch"])
    # the launch after the window (9 ms everywhere would move every median)
    # and the traced launches are outside
    assert ps.median_ms(hand, ("launch.runner",)) == pytest.approx(2.0)
    assert ps.median_ms(hand, ("no.such.span",)) is None
    assert ps.window({"starts": {}, "spans": {}}) is None
    only_traced = {
        key: {n: v for n, v in table.items() if n.startswith("traced_")}
        for key, table in hand.items()
    }
    assert ps.median_ms(only_traced, ("launch.runner",)) is None


def test_spans_of_one_launch_are_summed_before_the_median(monkeypatch):
    ring, ctx = hand_made()
    # `fut.block()` then `fut.result()`: two waits for one launch id
    extra = [Entry("result.wait", e.end, e.end + 0.002, e.request)
             for e in ring if e.name == "result.wait"]
    monkeypatch.setattr(ps, "ring", lambda: ring + extra)
    assert ps.median_ms(ctx, ("result.wait",)) == pytest.approx(52.0)


def test_a_program_without_spans_or_events_reads_as_nothing(hand, monkeypatch):
    from tpudes.obs.device import CompileTelemetry

    monkeypatch.setattr(ps, "ring", lambda: None)
    monkeypatch.delattr(CompileTelemetry, "xla_events")
    manifest = Manifest(ROOT)
    for metric in MC_METRICS + SCRIPT_METRICS:
        assert manifest.layer_reader(metric)(hand) is None


def test_xla_compiles_counts_backend_compiles_inside_the_window(hand, monkeypatch):
    from tpudes.obs.device import CompileTelemetry

    t0, t1 = ps.window(hand)
    events = [
        (t0 - 1.0, ps.XLA_COMPILE, 0.5, "jit(advance)"),         # set-up
        (t0 + 0.01, "/jax/core/compile/jaxpr_trace_duration", 0.001, "f"),
        (t0 + 0.02, ps.XLA_COMPILE, 0.03, "broadcast_in_dim"),
        (t1 - 0.01, ps.XLA_COMPILE, 0.03, "convert_element_type"),
        (t1 + 0.5, ps.XLA_COMPILE, 0.03, "after"),
    ]
    monkeypatch.setattr(
        CompileTelemetry, "xla_events",
        lambda since=None: [e for e in events if since is None or e[0] >= since],
    )
    manifest = Manifest(ROOT)
    assert manifest.layer_reader("xla_compiles_in_window")(hand) == 2.0
    assert manifest.layer_reader("script_xla_compiles_in_window")(hand) == 2.0


@pytest.mark.parametrize("which", ["shipped", "with_toy_cells"])
def test_each_new_manifest_entry_resolves_to_a_reader(which, toy_root):
    """PR 25's ten metrics are declared as it declared them, resolve to a reader,
    and list every cell whose traffic's driver is `mc` (`script` for the `script_`
    twins): read from the manifest, so a cell a later PR adds, as the temp copy's
    further `mc` and `script` cells are added, is held to it and does not fail it."""
    manifest = Manifest(ROOT if which == "shipped" else toy_root)
    by_name = {m["name"]: m for m in manifest.data["per_layer"]}
    cells_of = {"mc": [], "script": []}
    for cell in manifest.data["workloads"]:
        cells_of[manifest.traffic(cell["traffic"])["driver"]].append(cell["name"])
    assert len(cells_of["mc"]) >= (4 if which == "shipped" else 8)
    for name in MC_METRICS + SCRIPT_METRICS:
        entry = by_name[name]
        script = name.startswith("script_")
        assert sorted(entry["workloads"]) == sorted(
            cells_of["script" if script else "mc"])
        assert entry["moves"] == ("study_p50_s" if script else "sim_s_per_wall_s")
        assert entry["layer"] == "engine runtime" and entry["better"] == "lower"
        assert entry["source"] == (
            "program_counter" if "xla" in name else "host_clock")
        assert entry["unit"] == ("count" if "xla" in name else "ms")
        assert callable(manifest.layer_reader(name))


@pytest.mark.parametrize("name", ["toy.bss", "toy.script"])
def test_traced_toy_run_reads_the_programs_real_ring(toy_root, name):
    result = run.run_cell(Manifest(toy_root), name, 2**31 + 7, 0.5, True,
                          jax.devices(), program_root=ROOT)
    value = {k: v["value"] for k, v in result["metrics"].items()}
    if name == "toy.script":
        assert set(SCRIPT_METRICS) <= set(value)
        assert 0 < value["script_launch_ms"] and 0 < value["script_result_ms"]
        assert value["script_xla_compiles_in_window"] == 0
        return
    assert set(MC_METRICS) <= set(value)
    parts = sum(value[m] for m in MC_METRICS[:4])
    # the harness's `dispatch` is the same interval seen from outside, so the
    # parts cannot add up to much more than it (a reader that also counted the
    # traced launches or the check's rerun would); on a loaded CPU box the host
    # is preempted between the program's spans, inside `dispatch`, so from below
    # the medians agree only loosely (read 1.46 for 1.97 beside a 6-worker run)
    whole = value["dispatch_ms"]
    assert 0.4 * whole - 0.3 <= parts <= 1.25 * whole + 0.3
    assert value["launch_self_ms"] >= 0
    assert value["result_fetch_ms"] + value["result_unpack_ms"] <= (
        value["fetch_unpack_ms"] * 1.25 + 0.3)
    assert value["xla_compiles_in_window"] == 0
    assert value["compiles_in_window"] == 0
