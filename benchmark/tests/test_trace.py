import json
import os

import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    raw = json.load(open(os.path.join(HERE, "recorded_trace.json")))
    return {
        "devices": {k: [tuple(e) for e in v] for k, v in raw["devices"].items()},
        "host": [tuple(e) for e in raw["host"]],
    }


def test_known_busy_idle_and_step_numbers(recorded):
    got = trace.reduce_trace(recorded)
    # read off the recorded trace by hand: the spans run from 39.105366 ms to
    # 109.405661 ms; one while of 54.140028 ms; 8.143 us of operations before it
    assert got["window_s"] == pytest.approx(0.070300295, rel=1e-9)
    assert got["busy_s"] == pytest.approx(0.054148171, rel=1e-9)
    assert got["while_s"] == pytest.approx(0.054140028, rel=1e-9)
    assert got["collective_s"] == 0.0 and got["devices"] == 1
    idle = 1.0 - got["busy_s"] / got["window_s"]
    assert idle == pytest.approx(0.229759, abs=1e-6)
    gaps = dict(got["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(got["window_s"] - got["busy_s"])
    assert gaps["dispatch"] > gaps["fetch_unpack"] > 0 and "wait" not in gaps
    assert got["device_ops"][0][0] == "while.4" and len(got["device_ops"]) <= 10


def test_busy_matches_a_plain_interval_merge(recorded):
    t0 = min(s for _, s, _ in recorded["host"])
    t1 = max(s + d for _, s, d in recorded["host"])
    iv = sorted((max(s, t0), min(s + d, t1))
                for _, s, d in recorded["devices"]["/device:TPU:0"])
    busy, (cs, ce) = 0.0, iv[0]
    for s, e in iv[1:]:
        if s > ce:
            busy, cs, ce = busy + ce - cs, s, e
        else:
            ce = max(ce, e)
    busy += ce - cs
    assert trace.reduce_trace(recorded)["busy_s"] == pytest.approx(busy * 1e-9)


def test_two_devices_average_and_collectives_count(recorded):
    ops = recorded["devices"]["/device:TPU:0"]
    start = ops[-1][1] + ops[-1][2]
    second = [e for e in ops] + [("all-reduce.1", 60_000_000.0, 1_000_000.0)]
    both = dict(recorded, devices={"/device:TPU:0": ops, "/device:TPU:1": second})
    one, two = trace.reduce_trace(recorded), trace.reduce_trace(both)
    assert start > 0 and two["devices"] == 2
    # the collective sits inside the while: busy is unchanged, its share shows
    assert two["busy_s"] == pytest.approx(one["busy_s"])
    assert two["collective_s"] == pytest.approx(0.0005)


def test_a_gap_goes_to_the_innermost_span(recorded):
    t0 = min(s for _, s, _ in recorded["host"])
    t1 = max(s + d for _, s, d in recorded["host"])
    nested = dict(recorded, host=[("bench:study", t0, t1 - t0)] + recorded["host"])
    gaps = dict(trace.reduce_trace(nested)["idle_gaps"])
    assert "study" not in gaps and gaps["dispatch"] > 0


def test_nothing_to_read_gives_nothing():
    assert trace.reduce_trace({"devices": {}, "host": []}) is None
    assert trace.reduce_trace(
        {"devices": {}, "host": [("bench:wait", 0.0, 5.0)]}) is None
    assert trace.op_name("%while.139 = (s32[64]{0}) while(x)") == "while.139"
