"""Device-resident LTE SM engine tests.

Validates tpudes/parallel/lte_sm.py — lowering guards, determinism,
HARQ/drop conservation, the int32-overflow-free bit accounting, the
replica axis (vmap + mesh sharding), and statistical parity against the
host TTI controller on an identical scenario (the SURVEY.md §7 step-8
"same scenario, two engines" check).
"""

import hashlib
import json
import math
from pathlib import Path

import numpy as np
import pytest

from tpudes.parallel.lte_sm import (
    LteSmProgram,
    UnliftableLteScenarioError,
    lower_lte_sm,
    run_lte_sm,
)


def _toy_prog(n_ttis=300, scheduler="pf", n_ue=6, n_enb=2, n_rb=25):
    rng = np.random.default_rng(5)
    serving = (np.arange(n_ue) % n_enb).astype(np.int32)
    # serving path 20 dB above every interferer, ±6 dB per-UE spread:
    # every UE lands at a usable CQI
    gain = 10.0 ** rng.uniform(-11.6, -11.0, size=(n_enb, n_ue))
    gain[serving, np.arange(n_ue)] = 10.0 ** rng.uniform(
        -9.6, -9.0, size=(n_ue,)
    )
    return LteSmProgram(
        gain=gain,
        serving=serving,
        tx_power_dbm=np.full((n_enb,), 30.0),
        noise_psd=10.0 ** 0.9 * 1.380649e-23 * 290.0,
        n_rb=n_rb,
        n_ttis=n_ttis,
        scheduler=scheduler,
    )


def _build_helper_scenario(n_enbs=2, ues_per_cell=3, scheduler="pf"):
    from tpudes.helper.containers import NodeContainer
    from tpudes.models.lte import LteHelper
    from tpudes.models.lte.scheduler import resolve_scheduler
    from tpudes.models.mobility import (
        ListPositionAllocator,
        MobilityHelper,
        Vector,
    )

    lte = LteHelper()
    lte.SetSchedulerType(resolve_scheduler(scheduler))
    enbs = NodeContainer()
    enbs.Create(n_enbs)
    ues = NodeContainer()
    ues.Create(n_enbs * ues_per_cell)
    ea = ListPositionAllocator()
    for i in range(n_enbs):
        ea.Add(Vector(i * 500.0, 0.0, 30.0))
    me = MobilityHelper()
    me.SetPositionAllocator(ea)
    me.SetMobilityModel("tpudes::ConstantPositionMobilityModel")
    me.Install(enbs)
    ua = ListPositionAllocator()
    rng = np.random.default_rng(9)
    for c in range(n_enbs):
        for _ in range(ues_per_cell):
            r = 200.0 * math.sqrt(rng.uniform())
            a = 2 * math.pi * rng.uniform()
            ua.Add(Vector(c * 500.0 + r * math.cos(a), r * math.sin(a), 1.5))
    mu = MobilityHelper()
    mu.SetPositionAllocator(ua)
    mu.SetMobilityModel("tpudes::ConstantPositionMobilityModel")
    mu.Install(ues)
    lte.InstallEnbDevice(enbs)
    ue_devs = lte.InstallUeDevice(ues)
    ue_list = [ue_devs.Get(i) for i in range(ue_devs.GetN())]
    lte.Attach(ue_list)
    lte.ActivateDataRadioBearer(ue_list)
    return lte, ue_list


class TestLowering:
    def test_lower_from_helper(self):
        lte, _ = _build_helper_scenario()
        prog = lower_lte_sm(lte, 0.25)
        assert prog.n_enb == 2 and prog.n_ue == 6
        assert prog.n_ttis == 250
        assert prog.scheduler == "pf"
        assert (prog.serving == np.array([0, 1] * 3)).all() or set(
            prog.serving
        ) <= {0, 1}

    def test_rejects_non_sm_bearer(self):
        lte, ue_list = _build_helper_scenario()
        # re-activate one UE with a UM bearer on top
        enb = ue_list[0].rrc.serving_enb
        ctx = enb.rrc.ues[ue_list[0].rrc.rnti]
        enb.rrc.setup_bearer(ctx, "um")
        with pytest.raises(UnliftableLteScenarioError):
            lower_lte_sm(lte, 0.1)

    def test_rejects_unattached_ue(self):
        from tpudes.helper.containers import NodeContainer
        from tpudes.models.mobility import (
            ListPositionAllocator,
            MobilityHelper,
            Vector,
        )

        lte, _ = _build_helper_scenario()
        extra = NodeContainer()
        extra.Create(1)
        a = ListPositionAllocator()
        a.Add(Vector(50.0, 50.0, 1.5))
        m = MobilityHelper()
        m.SetPositionAllocator(a)
        m.SetMobilityModel("tpudes::ConstantPositionMobilityModel")
        m.Install(extra)
        lte.InstallUeDevice(extra)  # installed but never attached
        with pytest.raises(UnliftableLteScenarioError):
            lower_lte_sm(lte, 0.1)

    def _with_walker(self):
        from tpudes.helper.containers import NodeContainer
        from tpudes.models.mobility import MobilityHelper

        lte, _ = _build_helper_scenario()
        walker = NodeContainer()
        walker.Create(1)
        m = MobilityHelper()
        m.SetPositionAllocator(
            "tpudes::RandomDiscPositionAllocator", X=0.0, Y=0.0, Rho=10.0
        )
        m.SetMobilityModel(
            "tpudes::RandomWalk2dMobilityModel",
        )
        m.Install(walker)
        dev = lte.InstallUeDevice(walker)
        lte.Attach([dev.Get(0)])
        lte.ActivateDataRadioBearer([dev.Get(0)])
        return lte

    def test_mobile_geometry_lifts_by_default(self):
        # the ISSUE-10 flip: moving UEs ride the device geometry
        # pipeline instead of being refused
        lte = self._with_walker()
        prog = lower_lte_sm(lte, 0.3)
        assert prog.mobility is not None
        assert prog.mobility.model == "random_walk"
        assert prog.pathloss is not None and prog.enb_pos is not None

    def test_mobile_geometry_refused_under_kill_switch(self, monkeypatch):
        # TPUDES_DEVICE_GEOM=0 restores the loud refusal (the host
        # controller's per-window refresh is the fallback path)
        lte = self._with_walker()
        monkeypatch.setenv("TPUDES_DEVICE_GEOM", "0")
        with pytest.raises(UnliftableLteScenarioError):
            lower_lte_sm(lte, 0.3)

    def test_mobile_enb_still_refused(self):
        from tpudes.models.mobility import (
            ConstantVelocityMobilityModel,
            MobilityModel,
            Vector,
        )

        lte, _ = _build_helper_scenario()
        enb_node = lte.controller.enbs[0].GetNode()
        old = enb_node.GetObject(MobilityModel)
        cv = ConstantVelocityMobilityModel()
        cv.SetPosition(old.GetPosition())
        cv.SetVelocity(Vector(1.0, 0.0, 0.0))
        # replace the model in the aggregation ring (GetObject returns
        # the first match, so appending would not take effect)
        ring = enb_node._aggregates
        ring[ring.index(old)] = cv
        cv._aggregates = ring
        with pytest.raises(UnliftableLteScenarioError):
            lower_lte_sm(lte, 0.3)


class TestSmEngine:
    def test_deterministic_per_key(self):
        import jax

        prog = _toy_prog()
        a = run_lte_sm(prog, jax.random.PRNGKey(7))
        b = run_lte_sm(prog, jax.random.PRNGKey(7))
        c = run_lte_sm(prog, jax.random.PRNGKey(8))
        np.testing.assert_array_equal(a["rx_bits"], b["rx_bits"])
        assert (a["rx_bits"] != c["rx_bits"]).any()

    def test_conservation_new_tbs(self):
        import jax

        prog = _toy_prog(n_ttis=500)
        out = run_lte_sm(prog, jax.random.PRNGKey(0))
        # every first transmission ends decoded, dropped, or pending:
        # ok counts retransmission successes too, so compare TB-wise:
        # ok_tbs = new_tbs - drops - pending; pending is not exported but
        # bounded by E (one in-flight TB per UE at most)
        slack = prog.n_ue
        assert (out["ok"] + out["drops"] <= out["new_tbs"] + out["retx"]).all()
        assert (out["new_tbs"] >= out["ok"] + out["drops"] - slack).all()

    def test_every_ue_served_under_pf(self):
        import jax

        prog = _toy_prog(n_ttis=400)
        out = run_lte_sm(prog, jax.random.PRNGKey(1))
        assert (out["rx_bits"] > 0).all()

    def test_rr_time_shares_equal(self):
        import jax

        prog = _toy_prog(n_ttis=900, scheduler="rr")
        out = run_lte_sm(prog, jax.random.PRNGKey(2))
        # 3 UEs per cell, 900 TTIs: each UE wins ~300 TTIs
        tbs = out["new_tbs"] + out["retx"]
        assert tbs.min() > 250 and tbs.max() < 350

    def test_rx_bits_exact_past_int32(self):
        import jax

        # one UE hogging 100 RB at peak MCS: ~66k bits/TTI; 40k TTIs
        # crosses 2^31 bits — the lo/hi accounting must stay exact
        prog = LteSmProgram(
            gain=np.array([[1e-7]]),
            serving=np.zeros(1, np.int32),
            tx_power_dbm=np.array([46.0]),
            noise_psd=10.0 ** 0.9 * 1.380649e-23 * 290.0,
            n_rb=100,
            n_ttis=40_000,
            scheduler="pf",
        )
        out = run_lte_sm(prog, jax.random.PRNGKey(3))
        total = int(out["rx_bits"][0])
        assert total > 2**31
        # exact multiple of the (single, static) TB size
        from tpudes.ops.lte import tbs_bits_py

        consts_tb = None
        for mcs in range(29):
            tb = tbs_bits_py(mcs, 100)
            if total % max(tb, 1) == 0 and total // max(tb, 1) == int(
                out["ok"][0]
            ):
                consts_tb = tb
                break
        assert consts_tb is not None

    def test_replica_axis_vmap(self):
        import jax

        prog = _toy_prog(n_ttis=200)
        out = run_lte_sm(prog, jax.random.PRNGKey(4), replicas=4)
        assert out["rx_bits"].shape == (4, prog.n_ue)
        # replicas see different decode draws but identical physics:
        # totals agree within Monte-Carlo noise
        totals = out["rx_bits"].sum(axis=1).astype(float)
        assert totals.std() / totals.mean() < 0.1
        # not all byte-identical
        assert len({int(t) for t in totals}) > 1 or totals.std() == 0

    def test_replica_axis_mesh_sharded_matches_vmap(self):
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("needs the virtual multi-device mesh")
        from tpudes.parallel.mesh import replica_mesh

        prog = _toy_prog(n_ttis=150)
        mesh = replica_mesh(4)
        plain = run_lte_sm(prog, jax.random.PRNGKey(5), replicas=8)
        shard = run_lte_sm(prog, jax.random.PRNGKey(5), replicas=8, mesh=mesh)
        # sharding the replica axis must not change the computation
        np.testing.assert_array_equal(plain["rx_bits"], shard["rx_bits"])
        np.testing.assert_array_equal(plain["ok"], shard["ok"])


class TestSchedulerFamily:
    """All nine FF-MAC schedulers ride one jitted program (the traced
    scheduler-id dispatch) — behavior pins for each family plus the
    one-compile-serves-all property the perf story depends on."""

    def test_lowering_accepts_every_registered_scheduler(self):
        from tpudes.core.world import reset_world
        from tpudes.parallel.lte_sm import SM_SCHED_IDS

        for sched in SM_SCHED_IDS:
            reset_world()
            lte, _ = _build_helper_scenario(scheduler=sched)
            prog = lower_lte_sm(lte, 0.05)
            assert prog.scheduler == sched
        reset_world()

    def test_custom_scheduler_class_still_refused(self):
        """The refusal list names structural constraints only — but an
        unregistered user scheduler class has arbitrary host semantics
        and must never be silently approximated (the round-2 rule)."""
        from tpudes.models.lte.scheduler import FfMacScheduler

        class MyScheduler(FfMacScheduler):
            def schedule(self, tti, candidates, free_rbgs, rbg_size):
                return []

        lte, _ = _build_helper_scenario()
        for enb in lte.controller.enbs:
            enb.scheduler = MyScheduler()
        with pytest.raises(UnliftableLteScenarioError) as ei:
            lower_lte_sm(lte, 0.1)
        # no registered family name in the message: the device engine
        # no longer refuses any upstream scheduler
        for name in ("pf", "rr", "tdmt", "fdmt", "tta",
                     "tdbet", "fdbet", "cqa", "pss"):
            assert f" {name}" not in str(ei.value).lower()

    def test_one_compiled_program_serves_all_nine(self):
        """The scheduler id is a traced operand: sweeping the family
        reuses ONE cache entry (one XLA executable), not nine."""
        import dataclasses

        import jax

        from tpudes.parallel import lte_sm as mod
        from tpudes.parallel.runtime import RUNTIME

        RUNTIME.clear("lte_sm")
        base = _toy_prog(n_ttis=120)
        outs = {}
        for sched in mod.SM_SCHED_IDS:
            prog = dataclasses.replace(base, scheduler=sched)
            outs[sched] = run_lte_sm(prog, jax.random.PRNGKey(2))
        assert RUNTIME.size("lte_sm") == 1
        # and the dispatch actually differentiates the families
        assert (
            outs["tdmt"]["new_tbs"] != outs["pf"]["new_tbs"]
        ).any()

    def test_mt_winner_takes_all(self):
        import jax

        import dataclasses

        prog = dataclasses.replace(_toy_prog(n_ttis=400), scheduler="tdmt")
        out = run_lte_sm(prog, jax.random.PRNGKey(3))
        # per cell, exactly the best-rate UE is ever scheduled; the
        # others starve (max-throughput is maximally unfair)
        for c in range(prog.n_enb):
            members = np.where(prog.serving == c)[0]
            tbs = (out["new_tbs"] + out["retx"])[members]
            assert (tbs > 0).sum() == 1, tbs
            winner = members[np.argmax(tbs)]
            assert out["mcs"][winner] == out["mcs"][members].max()

    def test_bet_equalizes_bits_where_rr_equalizes_airtime(self):
        import dataclasses

        import jax

        base = _toy_prog(n_ttis=1200)
        bet = run_lte_sm(
            dataclasses.replace(base, scheduler="fdbet"), jax.random.PRNGKey(4)
        )
        rr = run_lte_sm(
            dataclasses.replace(base, scheduler="rr"), jax.random.PRNGKey(4)
        )
        def cv(x):
            x = x.astype(float)
            return x.std() / x.mean()

        # BET: served BITS converge to equal across unequal-CQI UEs;
        # RR gives equal airtime, so its bit spread tracks the MCS spread
        assert cv(bet["rx_bits"]) < 0.5 * cv(rr["rx_bits"])
        # while its airtime (TB count) spread is the wider one
        assert cv((bet["new_tbs"] + bet["retx"])) > cv(
            rr["new_tbs"] + rr["retx"]
        )

    def test_degenerate_families_coincide(self):
        """Full-buffer degeneracies pinned: TD≡FD within MT, TTA≡RR,
        CQA≡PSS≡PF — same decode draws, identical outcomes."""
        import dataclasses

        import jax

        base = _toy_prog(n_ttis=250)
        runs = {
            s: run_lte_sm(
                dataclasses.replace(base, scheduler=s), jax.random.PRNGKey(6)
            )
            for s in ("pf", "cqa", "pss", "rr", "tta", "tdmt", "fdmt",
                      "tdbet", "fdbet")
        }
        for a, b in (("cqa", "pf"), ("pss", "pf"), ("tta", "rr"),
                     ("fdmt", "tdmt"), ("fdbet", "tdbet")):
            np.testing.assert_array_equal(
                runs[a]["rx_bits"], runs[b]["rx_bits"], err_msg=f"{a} vs {b}"
            )


class TestHostDeviceParity:
    def test_sm_engine_matches_host_controller(self):
        """The device engine and the host TTI loop run the SAME lowered
        scenario; aggregate and per-cell DL throughput must agree within
        Monte-Carlo + timing-model tolerance (the deviations documented
        in the lte_sm module docstring, all bounded)."""
        import jax

        from tpudes.core.nstime import Seconds
        from tpudes.core.simulator import Simulator

        sim_time = 0.4
        lte, _ = _build_helper_scenario(n_enbs=2, ues_per_cell=3)
        prog = lower_lte_sm(lte, sim_time)

        # host engine
        Simulator.Stop(Seconds(sim_time))
        Simulator.Run()
        stats = lte.GetRlcStats()
        host_bits = sum(s["dl_rx_bytes"] for s in stats) * 8
        host_cell = {}
        for s in stats:
            host_cell[s["cell_id"]] = (
                host_cell.get(s["cell_id"], 0) + s["dl_rx_bytes"] * 8
            )

        # device engine, same program
        out = run_lte_sm(prog, jax.random.PRNGKey(11))
        dev_bits = int(out["rx_bits"].sum())
        dev_cell = {}
        cell_ids = [e.GetCellId() for e in lte.controller.enbs]
        for u in range(prog.n_ue):
            c = cell_ids[int(prog.serving[u])]
            dev_cell[c] = dev_cell.get(c, 0) + int(out["rx_bits"][u])

        assert dev_bits == pytest.approx(host_bits, rel=0.15)
        for c in host_cell:
            assert dev_cell[c] == pytest.approx(host_cell[c], rel=0.2)

    @pytest.mark.parametrize(
        "sched", ["pf", "rr", "tdmt", "fdmt", "tta", "tdbet", "fdbet",
                  "cqa", "pss"]
    )
    def test_scheduler_fairness_parity(self, sched):
        """Device vs host on the SAME lowered scenario, per scheduler:
        aggregate DL throughput within the documented timing-model
        tolerance AND per-UE fairness shares matching — the quantity
        each scheduler family actually differentiates.  MT gets a wider
        share tolerance: the device's single HARQ process redirects the
        winner's TTIs to the runner-up during the 8 ms HARQ RTT (module
        docstring deviation), which the host's overlapping processes
        don't."""
        import jax

        from tpudes.core.nstime import Seconds
        from tpudes.core.simulator import Simulator
        from tpudes.core.world import reset_world

        sim_time = 0.3
        reset_world()
        lte, _ = _build_helper_scenario(
            n_enbs=2, ues_per_cell=3, scheduler=sched
        )
        prog = lower_lte_sm(lte, sim_time)
        assert prog.scheduler == sched

        Simulator.Stop(Seconds(sim_time))
        Simulator.Run()
        host = np.array(
            [s["dl_rx_bytes"] * 8 for s in lte.GetRlcStats()], dtype=float
        )
        out = run_lte_sm(prog, jax.random.PRNGKey(11))
        dev = out["rx_bits"].astype(float)
        reset_world()

        assert dev.sum() == pytest.approx(host.sum(), rel=0.15), sched
        host_share = host / host.sum()
        dev_share = dev / dev.sum()
        tol = 0.15 if sched in ("tdmt", "fdmt") else 0.05
        np.testing.assert_allclose(
            dev_share, host_share, atol=tol,
            err_msg=f"{sched}: shares {dev_share} vs host {host_share}",
        )

    def test_sm_engine_matches_host_under_bf16(self):
        """ISSUE-6 budget pin: the bf16 mixed-precision mode must hold
        the SAME host-parity tolerances as f32 — the precision knob
        buys speed, not a different simulator."""
        import jax

        from tpudes.core.nstime import Seconds
        from tpudes.core.simulator import Simulator

        sim_time = 0.4
        lte, _ = _build_helper_scenario(n_enbs=2, ues_per_cell=3)
        prog = lower_lte_sm(lte, sim_time, precision="bf16")
        assert prog.precision == "bf16"

        Simulator.Stop(Seconds(sim_time))
        Simulator.Run()
        host_bits = sum(
            s["dl_rx_bytes"] for s in lte.GetRlcStats()
        ) * 8

        out = run_lte_sm(prog, jax.random.PRNGKey(11))
        assert int(out["rx_bits"].sum()) == pytest.approx(
            host_bits, rel=0.15
        )
        # the bf16-rounded CQI still matches the host's f32 steady
        # state away from efficiency boundaries: allow ±1 index
        host_cqi = np.asarray(lte.controller._cqi_dl)
        assert np.abs(out["cqi"].astype(int) - host_cqi).max() <= 1

    def test_sm_engine_cqi_matches_host(self):
        """Static full-buffer geometry: the device engine's precomputed
        CQI equals the host controller's steady-state applied CQI."""
        import jax

        from tpudes.core.nstime import Seconds
        from tpudes.core.simulator import Simulator

        lte, _ = _build_helper_scenario(n_enbs=2, ues_per_cell=3)
        prog = lower_lte_sm(lte, 0.02)
        out = run_lte_sm(prog, jax.random.PRNGKey(0))
        Simulator.Stop(Seconds(0.02))
        Simulator.Run()
        host_cqi = np.asarray(lte.controller._cqi_dl)
        np.testing.assert_array_equal(out["cqi"], host_cqi)


# --- ISSUE 26: the TTI loop is unbatched in all three advance builders ------


def _advance_and_args(variant, n_cfg):
    """One builder's unjitted advance with concrete tiny operands at
    ``r_pad=2`` (and ``n_cfg`` config points)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from tpudes.parallel import lte_sm
    from tpudes.parallel.runtime import replica_keys, stack_axis

    keys = replica_keys(jax.random.PRNGKey(0), 2)
    sid = jnp.int32(0) if n_cfg is None else jnp.zeros((n_cfg,), jnp.int32)
    stack = lambda s: stack_axis(stack_axis(s, 2), n_cfg)  # noqa: E731
    kw = dict(r_pad=2, n_cfg=n_cfg, use_pallas=False)
    if variant == "base":
        _, init_state, fn = lte_sm.build_sm_advance(
            lte_sm._trace_prog(), **kw
        )
        carry = (jnp.int32(0), stack(init_state()))
        return fn, (carry, keys, sid, jnp.int32(8))
    if variant == "traffic":
        prog = lte_sm._trace_traffic_prog()
        init_carry, fn = lte_sm.build_sm_traffic_advance(prog, **kw)
        t0, s0 = init_carry()
        return fn, (
            (t0, stack(s0)), keys, sid, jnp.int32(8),
            prog.traffic.operands(), jax.random.PRNGKey(1),
        )
    from tpudes.ops.mobility import MobilityProgram

    base = lte_sm._trace_prog()
    prog = dataclasses.replace(
        base,
        mobility=MobilityProgram.constant_velocity(
            np.full((base.n_ue, 3), 100.0), np.ones((base.n_ue, 3))
        ),
        enb_pos=np.array([[0.0, 0.0, 30.0], [500.0, 0.0, 30.0]]),
        pathloss=("friis", 2.12e9, 1.0, 0.0),
    )
    init_carry, fn = lte_sm.build_sm_mobile_advance(prog, **kw)
    t0, g0, s0 = init_carry()
    return fn, (
        (t0, g0, stack(s0)), keys, sid, jnp.int32(8),
        prog.mobility.operands(), jnp.int32(1), None,
    )


@pytest.mark.parametrize("n_cfg", [None, 2], ids=["r2", "r2-c2"])
@pytest.mark.parametrize("variant", ["base", "mobile", "traffic"])
def test_advance_runs_one_unbatched_tti_loop(variant, n_cfg):
    """The lanes are vmapped over the per-TTI step only: the advance
    holds ONE outermost ``while`` whose clock is a rank-0 int32 and
    whose condition is all scalars — a ``vmap`` of the loop would make
    the predicate per-lane, which lowers to a select of every carry
    leaf and an ``any`` (an all-reduce on a mesh) per TTI."""
    import jax
    import jax.numpy as jnp

    fn, args = _advance_and_args(variant, n_cfg)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    whiles = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    assert len(whiles) == 1
    (loop,) = whiles
    n_consts = loop.params["cond_nconsts"] + loop.params["body_nconsts"]
    clock = loop.invars[n_consts].aval
    assert clock.shape == () and clock.dtype == jnp.int32
    cond = loop.params["cond_jaxpr"].jaxpr
    assert cond.outvars[0].aval.shape == ()
    for eqn in cond.eqns:
        assert not eqn.primitive.name.startswith("reduce"), eqn
        assert eqn.primitive.name not in ("any", "argmax"), eqn
        for v in list(eqn.invars) + list(eqn.outvars):
            assert v.aval.shape == (), eqn


#: the digests below were written by the PARENT of ISSUE 26 (the base
#: advance still a vmap of the while_loop, per-lane clock): the
#: unbatched loop must reproduce every one of them exactly
_GOLDEN = Path(__file__).parent / "golden" / "lte_sm_base_advance.json"
GOLDEN_PROGS = ("toy", "spread")
GOLDEN_CASES = {
    "solo": dict(replicas=4),
    "sweep": dict(replicas=4, schedulers=["pf", "rr"]),
    "chunked": dict(replicas=4, chunk_ttis=16),
    "sweep_chunked": dict(
        replicas=4, schedulers=["pf", "rr"], chunk_ttis=16
    ),
}


def _sha(a):
    a = np.ascontiguousarray(np.asarray(a))
    h = hashlib.sha256(f"{a.dtype}{a.shape}".encode() + a.tobytes())
    return [list(a.shape), h.hexdigest()[:16]]


def golden_entry(prog_name, lowering, obs, case, built=None):
    """What one ``run_lte_sm`` call gives, as JSON: the integer result
    arrays verbatim, the FlowMonitor columns and the per-chunk metrics
    as ``[shape, sha256]``.  ``toy`` is the issue's program (every
    replica draws the same outcome at its 30 dB dominance); ``spread``
    has UEs near a BLER cliff, so replicas differ by their keys."""
    import os

    import jax

    from tpudes.core.global_value import GlobalValue
    from tpudes.obs.device import ChunkStream
    from tpudes.parallel.programs import toy_lte_program
    from tpudes.parallel.runtime import RUNTIME

    prog = (
        toy_lte_program(n_enb=2, n_ue=3, n_ttis=40)
        if prog_name == "toy" else _toy_prog(n_ttis=40)
    )
    saved = os.environ.get("TPUDES_PALLAS")
    os.environ["TPUDES_PALLAS"] = "1" if lowering == "pallas" else "0"
    GlobalValue.Bind("TpudesObs", int(obs))
    RUNTIME.clear("lte_sm")
    ChunkStream.reset()
    try:
        out = run_lte_sm(prog, jax.random.PRNGKey(26), **GOLDEN_CASES[case])
        if built is not None:
            # the side asked for is the side built (conftest's
            # sm_lowerings_built), at every lane count of the cases
            assert built(prog) == {lowering == "pallas"}
    finally:
        if saved is None:
            del os.environ["TPUDES_PALLAS"]
        else:
            os.environ["TPUDES_PALLAS"] = saved
        GlobalValue.Bind("TpudesObs", 0)
        RUNTIME.clear("lte_sm")
    points = []
    for p in out if isinstance(out, list) else [out]:
        e = {
            k: np.asarray(p[k]).tolist()
            for k in ("rx_bits", "new_tbs", "retx", "drops", "ok")
        }
        if "flow" in p:
            e["flow"] = {k: _sha(v) for k, v in sorted(p["flow"].items())}
        points.append(e)
    entry = {"result": points}
    if obs:
        entry["chunks"] = [
            [c["t_end"],
             {k: _sha(v) for k, v in sorted(c["metrics"].items())}]
            for c in ChunkStream.entries("lte_sm")
        ]
        ChunkStream.reset()
    return entry


@pytest.mark.parametrize("case", sorted(GOLDEN_CASES))
@pytest.mark.parametrize("obs", [0, 1], ids=["obs0", "obs1"])
@pytest.mark.parametrize("lowering", ["xla", "pallas"])
def test_base_advance_reproduces_the_vmapped_while(
    sm_lowerings_built, lowering, obs, case
):
    golden = json.loads(_GOLDEN.read_text())
    for prog_name in GOLDEN_PROGS:
        want = golden[f"{prog_name}.obs{obs}.{case}"]
        got = golden_entry(
            prog_name, lowering, obs, case, built=sm_lowerings_built
        )
        assert got == want, prog_name
        if obs and "chunk" in case:
            # one ok / drops / retx / flipped ring per lane and chunk,
            # not one sum over all lanes
            lanes = [2, 4] if "sweep" in case else [4]
            assert [c[0] for c in got["chunks"]] == [16, 32, 40]
            for _, m in got["chunks"]:
                assert m["ok"][0] == lanes and m["retx"][0] == lanes
                assert m["fm_ring"][0][:-3] == lanes
