"""The readers of the program's own trace reading (`layers/_explain.py` and the
eight metrics that use it, PR 37): each reads a hand-made table, and reads as
nothing, without raising, on the CPU, with `tpudes.obs.explain` absent, with a table
the program withheld, with a replay that fails and (the scope readers) in a loop that
names no such scope; the manifest entries."""

import sys

import jax
import pytest

from conftest import ROOT

from benchmark.layers import _explain
from benchmark.manifest import Manifest

MC = ["lte.mc", "wifi.mc", "lte.mc.x4", "wifi_ht.mc", "tcp.mc"]
#: metric -> (unit, layer, cells, what it reads of TABLE)
METRICS = {
    "loop_own_us": ("us", "engine step", MC, 3.0),
    "loop_copies_us": ("us", "engine step", MC, 2.5),
    "loop_events_per_step": ("count", "engine step", MC, 200.0),
    "scope_step_us": ("us", "engine step", MC, 3.5 + 0.5),
    "scope_rng_us": ("us", "engine step", MC, 1.5),
    "device_outside_loop_ms": ("ms", "device", MC, 0.25 + 0.125),
    "idle_in_launch_ms": ("ms", "engine runtime", MC, 0.5 + 1.0 + 0.25),
    "idle_in_result_ms": ("ms", "engine runtime", MC, 0.75 + 0.125),
}
#: a table as `tpudes.obs.explain.reduce` gives it; two engines' `.step`
#: scopes, so that a reader that chose by more than the last component shows
TABLE = {
    "withheld": None, "launches": 3, "devices": 1, "wall_ms": 110.0,
    "busy_ms": 107.3125,
    "loop": {
        "step_us": 25.0, "own_us": 3.0, "copies_us": 2.5, "unscoped_us": 0.5,
        "iterations": 4096.0, "events_per_step": 200.0, "top": [], "no_event": [],
        "scopes": {
            "tpudes.dumbbell.step": {"us": 3.5, "ops": 30},
            "tpudes.other.step": {"us": 0.5, "ops": 1},
            "tpudes.dumbbell.rng": {"us": 1.5, "ops": 4},
            "tpudes.dumbbell.cc": {"us": 8.0, "ops": 42},
            "tpudes.dumbbell.queue": {"us": 5.5, "ops": 23},
        },
    },
    "outside_loop_ms": {"jit_tpudes_dumbbell_init": 0.25,
                        "jit_tpudes_dumbbell_advance": 0.125},
    "idle_ms": {"launch": 0.5, "launch.operands": 1.0, "launch.enqueue": 0.25,
                "result.wait": 0.75, "result.fetch": 0.125,
                "_outside_every_span_": 0.0625},
    "launch": {"kind": "dumbbell"},
}


@pytest.fixture(autouse=True)
def fresh_table():
    _explain.table.cache_clear()
    yield
    _explain.table.cache_clear()


def read_all():
    manifest = Manifest(ROOT)
    return {name: manifest.layer_reader(name)({}) for name in METRICS}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_reader_gives_the_tables_number(metric, monkeypatch):
    monkeypatch.setattr(_explain, "table", lambda: TABLE)
    value = Manifest(ROOT).layer_reader(metric)({})
    assert value == pytest.approx(METRICS[metric][3])


def test_the_readings_with_the_rest_sum_to_the_step_and_to_the_idle_time(monkeypatch):
    monkeypatch.setattr(_explain, "table", lambda: TABLE)
    got = read_all()
    assert (got["idle_in_launch_ms"] + got["idle_in_result_ms"]
            + TABLE["idle_ms"]["_outside_every_span_"]) == pytest.approx(
        TABLE["wall_ms"] - TABLE["busy_ms"])
    parts = (got["scope_step_us"] + got["scope_rng_us"] + _explain.scope_us("cc")
             + _explain.scope_us("queue") + got["loop_own_us"] + got["loop_copies_us"]
             + TABLE["loop"]["unscoped_us"])
    assert parts == pytest.approx(TABLE["loop"]["step_us"])


def test_a_scope_reads_zero_only_where_the_program_names_it(monkeypatch):
    """A scope the lowered program names and no event carries (a draw fused into
    its consumer) took no time; one it does not name is another loop's, or was
    renamed, and a `better: lower` metric must not read that as a gain."""
    fused = dict(TABLE, loop=dict(TABLE["loop"], no_event=["tpudes.bss.ampdu"]))
    monkeypatch.setattr(_explain, "table", lambda: fused)
    assert _explain.scope_us("ampdu") == 0.0
    unread = dict(TABLE, loop=dict(TABLE["loop"], no_event=None))
    monkeypatch.setattr(_explain, "table", lambda: unread)
    assert _explain.scope_us("ampdu") is None


def test_every_reader_reads_nothing_on_the_cpu_and_says_so_once(capfd):
    assert jax.default_backend() == "cpu"
    assert read_all() == dict.fromkeys(METRICS)
    said = [line for line in capfd.readouterr().err.splitlines()
            if line.startswith("benchmark: explain:")]
    assert len(said) == 1 and "no device trace" in said[0]


def test_every_reader_reads_nothing_with_explain_absent(monkeypatch, capfd):
    import tpudes.obs

    monkeypatch.setitem(sys.modules, "tpudes.obs.explain", None)
    monkeypatch.delattr(tpudes.obs, "explain", raising=False)
    assert read_all() == dict.fromkeys(METRICS)
    assert "not in this program" in capfd.readouterr().err


@pytest.mark.parametrize("replay,says", [
    (lambda: dict(TABLE, withheld="the trace is cut: ...", loop=None), "withheld"),
    (lambda: None, "nothing was launched"),
    (lambda: 1 / 0, "replay failed: ZeroDivisionError"),
], ids=["withheld", "no_launch", "raises"])
def test_a_withheld_table_or_a_failed_replay_reads_as_nothing(
        replay, says, monkeypatch, capfd):
    from tpudes.obs import explain

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(explain, "replay", replay)
    assert read_all() == dict.fromkeys(METRICS)
    assert says in capfd.readouterr().err


def test_the_replay_runs_once_a_process(monkeypatch):
    from tpudes.obs import explain

    calls = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(explain, "replay", lambda: calls.append(1) or TABLE)
    got = read_all()
    assert len(calls) == 1 and got["loop_own_us"] == 3.0


def test_each_new_manifest_entry_resolves_to_a_reader():
    manifest = Manifest(ROOT)
    by_name = {m["name"]: m for m in manifest.data["per_layer"]}
    for name, (unit, layer, cells, _) in METRICS.items():
        entry = by_name[name]
        assert entry == dict(
            name=name, unit=unit, better="lower", source="device_trace",
            layer=layer, moves="sim_s_per_wall_s", workloads=cells)
        assert callable(manifest.layer_reader(name))
        for cell in cells:
            assert name in [m["name"] for m in manifest.metrics_of("per_layer", cell)]
    script = [m["name"] for m in manifest.metrics_of("per_layer", "wifi.script")]
    assert not set(script) & set(METRICS)
    # a replay runs no client code, and the outside twin is there already; a scope
    # of one engine's loop cannot be listed where it reads alone (`test_tcp.py`)
    assert not {"idle_outside_spans_ms", "loop_step_us", "scope_ampdu_us",
                "scope_cc_us", "scope_queue_us"} & set(by_name)
