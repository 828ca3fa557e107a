"""Hidden terminals and RTS/CTS on the BSS engine's per-node medium.

``examples/wifi-hidden-terminal.py`` (upstream's wifi-hidden-terminal study:
three ad hoc stations, a loss matrix that hides the outer two from each other,
two saturating CBR flows into the middle one) lifts through ``lift.py`` to a
``bss`` program with hidden pairs, which compiles the per-node arm of the
step.  Here, tiny and on the CPU: that lift, RTS on and off; the engine
against ``benchmark/references/bss_hidden.py`` (the plain event loop the cell
``wifi_hidden.mc`` decides ``correct`` with) in every number it compares; the
single-medium model reading far above every tolerance; the legacy programs
traced to the parent's jaxpr text; and what stays refused under motion.
"""

import dataclasses
import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import stock
from benchmark.manifest import load_module
from tpudes.parallel.replicated import (
    UnliftableScenarioError,
    build_bss_advance,
    hidden_pairs,
    lower_bss,
    per_node_medium,
    run_replicated_bss,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = load_module(os.path.join(ROOT, "benchmark", "references", "bss_hidden.py"))
with open(os.path.join(
        ROOT, "benchmark", "configs", "wifi-hidden-terminal-rts.json")) as f:
    CONFIG = json.load(f)

#: 0.8 s of traffic (the flows start at 1 s): some 215 frames offered a flow,
#: both senders backlogged from the first tenth of a second on
SIM_TIME = 1.8
REPLICAS, REF_REPLICAS = 256, 128
THRESHOLD = {1: 100, 0: 2200}


@functools.cache
def _lifted(rts_cts):
    """The stock script through the one GlobalValue flip, one replica (a
    launch of more would take the test run's eight virtual devices), and
    the arguments of its ``launch`` span."""
    from tpudes.obs import spans

    main = stock.load_example(ROOT, "wifi-hidden-terminal.py").main
    rc, res, _ = stock.run_main(main, stock.script_argv(
        {"rtsCts": rts_cts, "simTime": SIM_TIME}, 1))
    assert rc == 0 and res is not None
    launch = [sp for sp in spans.snapshot() if sp.name == "launch"][-1]
    return dict(res, launch_args=dict(launch.args))


def _config(rts_cts):
    cfg = json.loads(json.dumps(CONFIG))
    cfg["physics"]["rts_threshold"] = THRESHOLD[rts_cts]
    return cfg


@pytest.mark.parametrize("rts_cts", [1, 0], ids=["rts", "no_rts"])
def test_the_stock_script_lifts_to_the_per_node_medium(rts_cts):
    res = _lifted(rts_cts)
    prog, out = res["program"], res["out"]
    assert res["kind"] == "bss" and res["replicas"] == 1
    assert per_node_medium(prog) and hidden_pairs(prog) == 1
    # what tpudes.obs.explain's table["launch"] reads off the span
    assert res["launch_args"]["hidden_pairs"] == 1
    assert res["launch_args"]["rts_threshold"] == THRESHOLD[rts_cts]
    assert prog.rts_threshold == THRESHOLD[rts_cts] and not prog.echo
    assert prog.loss_db.tolist() == [[0, 50, 50], [50, 0, 200], [50, 200, 0]]
    assert out["all_done"] and (np.asarray(out["flow_rx"])[:, 1:] > 0).all()
    assert (np.asarray(out["tx_rts"]) > 0).all() == bool(rts_cts)
    # upstream's ARP warm-up echo packets are skipped, not lowered: only the
    # two senders send, 268 frames an on period each
    assert prog.start_us.tolist() == [2**30, 1_000_000, 1_001_000]
    assert prog.on_us.tolist()[1:] == [267 * 3733 + 1, 267 * 3731 + 1]


# --- the engine against the reference -----------------------------------------

#: |engine - reference| per number, as the cell scales it (a share of the
#: frames offered: 215 a flow here), and why this much: both sides are means of
#: random counts (256 engine and 128 reference replicas), so each tolerance is
#: about 4.5 standard errors of their difference, read from the per-replica
#: spread of both sides.  A flow can hold the medium for long stretches, so the
#: per-flow numbers spread most: their standard error is 0.0128 with RTS and
#: 0.0035-0.0041 without; RTS sent and CTS timeouts 0.0049 and 0.0051; drops
#: 0.00015 and 0.00045.  The single-medium model reads 0.17 and 0.21 in the RTS
#: counts with RTS (7.6 and 8.9 times the tolerance), and 0.22-0.64 in the
#: per-flow numbers without (13 times and more).
TOLERANCE = {
    1: {"flow_rx_gap": 0.06, "tx_data_gap": 0.06, "backlog_gap": 0.06,
        "tx_rts_gap": 0.023, "cts_timeout_gap": 0.023, "drops_gap": 0.001},
    0: {"flow_rx_gap": 0.017, "tx_data_gap": 0.019, "backlog_gap": 0.015,
        "tx_rts_gap": 0.0, "cts_timeout_gap": 0.0, "drops_gap": 0.002},
}


@pytest.fixture(scope="module", params=[1, 0], ids=["rts", "no_rts"])
def study(request):
    rts_cts = request.param
    prog = _lifted(rts_cts)["program"]
    out = run_replicated_bss(prog, REPLICAS, jax.random.PRNGKey(42))
    assert out["all_done"]
    cfg = _config(rts_cts)
    ref = REF.simulate(cfg, SIM_TIME, REF_REPLICAS, seed=42, workers=1)
    return rts_cts, prog, out, cfg, ref


def _numbers(study, out):
    _, _, _, cfg, ref = study
    mix = {"horizon_s": SIM_TIME}
    return REF.compare(cfg, mix, [out], REPLICAS, seed=42, ref=ref)


@pytest.mark.parametrize("number", sorted(TOLERANCE[1]))
def test_engine_agrees_with_the_plain_reference(study, number):
    rts_cts, _, out, _, _ = study
    numbers = _numbers(study, out)
    assert numbers["rows_missing"] == 0
    assert numbers[number] <= TOLERANCE[rts_cts][number], numbers


def test_the_single_medium_model_reads_far_above_the_tolerance(study):
    rts_cts, prog, _, _, _ = study
    shared = run_replicated_bss(
        dataclasses.replace(prog, carrier_sense="shared"), REPLICAS,
        jax.random.PRNGKey(43),
    )
    numbers = _numbers(study, shared)
    worst = max(numbers[k] / max(t, 1e-9)
                for k, t in TOLERANCE[rts_cts].items() if t > 0)
    assert worst >= 5.0, numbers
    assert int(np.sum(shared["rx_lost_hidden"])) == 0


def test_counters_say_what_the_exchange_did(study):
    rts_cts, _, out, _, _ = study
    rts, sent = np.asarray(out["tx_rts"]), np.asarray(out["tx_by"]).sum(1)
    if rts_cts:
        # every data frame was preceded by an RTS, and hidden RTS collide
        assert (rts >= sent).all()
        assert 0 < np.sum(out["cts_timeout"]) < np.sum(rts)
    else:
        assert not rts.any() and not np.asarray(out["cts_timeout"]).any()
    assert np.sum(out["rx_lost_hidden"]) > 0
    delivered = np.asarray(out["flow_rx"])
    assert (delivered[:, 0] == 0).all() and (delivered <= np.asarray(out["tx_by"])).all()
    assert (np.asarray(out["srv_rx"]) == delivered.sum(1)).all()


def test_where_every_pair_senses_the_per_node_arm_reads_the_legacy_step():
    """Six stations within mutual range, one 512-byte request each every 4 ms
    and the AP echoing it: the per-node arm (forced by the shared-medium
    switch, which changes nothing where every pair senses each other) counts
    what the legacy step counts.  The two differ only where frames start in
    the same microsecond: the legacy step decodes each at its destination,
    the per-node arm locks the receiver onto the stronger (0.2-0.3% fewer
    retransmissions here, 64 replicas a side)."""
    from tpudes.core.world import reset_world
    from tpudes.scenarios import build_bss

    reset_world()
    stas, ap, clients, _ = build_bss(6, 1.6, radii=(10.0, 18.0), interval_s=0.004)
    prog = lower_bss([stas.Get(i) for i in range(6)], ap, clients, 1.6)
    reset_world()
    assert not per_node_medium(prog)
    legacy = run_replicated_bss(prog, 64, jax.random.PRNGKey(1))
    per_node = run_replicated_bss(
        dataclasses.replace(prog, carrier_sense="shared"), 64,
        jax.random.PRNGKey(2),
    )
    for field, tolerance in (("srv_rx", 0.002), ("cli_rx", 0.002),
                             ("tx_data", 0.01)):
        a, b = (float(np.asarray(o[field]).sum(-1).mean()
                      if np.ndim(o[field]) == 2 else np.mean(o[field]))
                for o in (legacy, per_node))
        assert abs(a - b) <= tolerance * a, (field, a, b)


# --- the legacy programs are the programs they were ---------------------------

#: sha256 (first 16 hex digits) of ``str(jax.make_jaxpr(...))`` of the advance
#: and of the init of the BSS programs the per-node arm leaves alone (no hidden
#: pair, no RTS, an echoing AP): legacy and HT, static and mobile, with and
#: without the TpudesObs columns, traced on the tree before the per-node arm
#: existed.  Equal text is the parent's program equation for equation, so the
#: cells ``wifi.mc``, ``wifi_ht.mc`` and ``wifi.script`` cannot move.
PARENT_JAXPR = {
    "legacy_static": ("d62086f454ffd1cb", "a58e4d934dc1291f"),
    "legacy_static_obs": ("1eaee581a90cd4c0", "dd64dcb02347643a"),
    "ht_static": ("63af2c551a0c18e1", "68b10d14f5ae3cae"),
    "ht_static_obs": ("035baab6df172441", "0314976446e6846a"),
    "legacy_mobile": ("7bb21e9549382a4c", "465354390852a9fc"),
    "legacy_mobile_obs": ("a8e196c1c5a3c3cc", "fbf196e384607688"),
    "ht_mobile": ("ed5d6ddbcbbbbdb0", "3104f149b969a643"),
    "ht_mobile_obs": ("c9588d4c73348779", "6943010b367c1839"),
}
SHAPES = {
    "legacy": ("80211a", "OfdmRate54Mbps"), "ht": ("80211n", "HtMcs7"),
}


def _bss_program(standard, mode, mobility, **over):
    from tpudes.core.world import reset_world
    from tpudes.scenarios import build_bss

    reset_world()
    stas, ap, clients, _ = build_bss(
        3, 1.6, radii=(10.0, 14.0), interval_s=0.1, data_mode=mode,
        standard=standard, mobility=mobility, speed=0.5, **over,
    )
    try:
        return lower_bss([stas.Get(i) for i in range(3)], ap, clients, 1.6,
                         geom_stride=4)
    finally:
        reset_world()


def _digest(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PARENT_JAXPR))
def test_legacy_programs_trace_the_parents_jaxpr_text(name):
    shape, motion = name.split("_")[:2]
    prog = _bss_program(*SHAPES[shape],
                        "static" if motion == "static" else "const_velocity")
    assert not per_node_medium(prog)
    init, _, fn = build_bss_advance(prog, 4, obs=name.endswith("_obs"))
    geom = None if prog.mobility is None else dict(
        stride=np.int32(4), **prog.mobility.operands())
    advance = jax.make_jaxpr(fn)(
        init(), jax.random.PRNGKey(0), jnp.int32(64),
        jnp.int32(prog.sim_end_us), geom, None,
    )
    got = (_digest(str(advance)), _digest(str(jax.make_jaxpr(init)())))
    assert got == PARENT_JAXPR[name]


# --- what stays out of scope --------------------------------------------------

def test_a_hidden_pair_under_motion_still_refuses():
    from tpudes.core.world import reset_world
    from tpudes.scenarios import build_bss

    reset_world()
    # a station at 600 m hears the AP at -114 dBm, under the -101 dBm sensitivity
    stas, ap, clients, _ = build_bss(2, 2.0, radii=(10.0, 600.0),
                                     mobility="const_velocity", speed=0.5)
    try:
        with pytest.raises(UnliftableScenarioError, match="trajectory"):
            lower_bss([stas.Get(i) for i in range(2)], ap, clients, 2.0)
    finally:
        reset_world()


def test_rts_under_motion_refuses():
    from tpudes.core.world import reset_world
    from tpudes.scenarios import build_bss

    reset_world()
    stas, ap, clients, _ = build_bss(2, 2.0, radii=(10.0,),
                                     mobility="const_velocity", speed=0.5)
    for dev in [ap] + [stas.Get(i) for i in range(2)]:
        dev.GetMac().SetAttribute("RtsCtsThreshold", 0)
    try:
        with pytest.raises(UnliftableScenarioError, match="single-medium"):
            lower_bss([stas.Get(i) for i in range(2)], ap, clients, 2.0)
    finally:
        reset_world()
