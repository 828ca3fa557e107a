"""The traffic stage riding each device engine (ISSUE-14): the
traffic=None bit-identity anchors, the cross-mode bit-equality
contracts (chunking / bucketing / checkpoint / sweeps) WITH workloads
attached, the one-launch mixed workload sweep, and the serving-layer
coalesce-key separation."""

import dataclasses

import jax
import numpy as np
import pytest

from tpudes.parallel.programs import (
    toy_as_program,
    toy_bss_program,
    toy_dumbbell_program,
    toy_lte_program,
    toy_traffic_points,
)
from tpudes.traffic import TrafficProgram

KEY = jax.random.PRNGKey(11)


def _eq(a, b, fields):
    return all(
        np.array_equal(np.asarray(a[f]), np.asarray(b[f]))
        for f in fields
    )


BSS_FIELDS = ("srv_rx", "cli_rx", "tx_data", "drops")


def _bss_prog(sim_end_us=250_000, n_sta=3):
    return toy_bss_program(n_sta=n_sta, sim_end_us=sim_end_us)


def _bss_onoff(prog, seed=3):
    tp = TrafficProgram.onoff(
        prog.n, 120.0, horizon_us=prog.sim_end_us,
        on=(1.5, 0.05, 0.4), off_mean_s=0.1, start_us=prog.start_us,
        tr_seed=seed,
    )
    return tp.with_cbr_rows(
        np.arange(prog.n) == 0, int(prog.interval_us[0]),
        int(prog.start_us[0]),
    )


class TestBss:
    def test_cbr_program_bit_equal_to_traffic_none(self):
        from tpudes.parallel.replicated import run_replicated_bss

        prog = _bss_prog()
        base = run_replicated_bss(prog, 4, KEY)
        tp = TrafficProgram.cbr(prog.start_us, prog.interval_us)
        out = run_replicated_bss(
            dataclasses.replace(prog, traffic=tp), 4, KEY
        )
        assert _eq(base, out, BSS_FIELDS)

    def test_chunked_bucketed_checkpointed_bit_equal(self, tmp_path,
                                                     monkeypatch):
        from tpudes.parallel.replicated import run_replicated_bss

        prog = _bss_prog()
        p = dataclasses.replace(prog, traffic=_bss_onoff(prog))
        ref = run_replicated_bss(p, 5, KEY)
        chunk = max(1, int(ref["steps"]) // 3 - 1)
        chunked = run_replicated_bss(p, 5, KEY, chunk_steps=chunk)
        assert _eq(ref, chunked, BSS_FIELDS)
        monkeypatch.setenv("TPUDES_BUCKETING", "0")
        unbucketed = run_replicated_bss(p, 5, KEY)
        monkeypatch.delenv("TPUDES_BUCKETING")
        assert _eq(ref, unbucketed, BSS_FIELDS)
        # checkpoint/resume: first run persists segment carries, the
        # resumed run must be bit-equal to single-shot
        ck = tmp_path / "bss.ckpt"
        run_replicated_bss(p, 5, KEY, chunk_steps=chunk, checkpoint=ck)
        resumed = run_replicated_bss(
            p, 5, KEY, chunk_steps=chunk, checkpoint=ck
        )
        assert _eq(ref, resumed, BSS_FIELDS)

    def test_mixed_workload_sweep_one_launch_demux_bit_equal(self):
        from tpudes.obs.device import CompileTelemetry
        from tpudes.parallel.replicated import run_replicated_bss
        from tpudes.parallel.runtime import RUNTIME

        prog = _bss_prog()
        pts = toy_traffic_points(
            prog.n, prog.sim_end_us, start_us=prog.start_us,
            beacon=(int(prog.interval_us[0]), int(prog.start_us[0])),
        )
        assert len(pts) == 8
        per = [
            run_replicated_bss(
                dataclasses.replace(prog, traffic=tp), 3, KEY
            )
            for tp in pts
        ]
        base = dataclasses.replace(prog, traffic=pts[0])
        run_replicated_bss(base, 3, KEY, traffic_sweep=pts)  # warm
        l0 = RUNTIME.launches("bss")
        c0 = CompileTelemetry.compiles("bss")
        swept = run_replicated_bss(base, 3, KEY, traffic_sweep=pts)
        assert RUNTIME.launches("bss") - l0 == 1
        assert CompileTelemetry.compiles("bss") - c0 == 0
        for a, b in zip(per, swept):
            assert _eq(a, b, BSS_FIELDS)

    def test_workload_params_are_traced_not_cache_keyed(self):
        from tpudes.obs.device import CompileTelemetry
        from tpudes.parallel.replicated import run_replicated_bss

        prog = _bss_prog()
        p1 = dataclasses.replace(prog, traffic=_bss_onoff(prog, seed=1))
        p2 = dataclasses.replace(prog, traffic=_bss_onoff(prog, seed=2))
        run_replicated_bss(p1, 3, KEY)
        c0 = CompileTelemetry.compiles("bss")
        out = run_replicated_bss(p2, 3, KEY)
        assert CompileTelemetry.compiles("bss") - c0 == 0
        assert out["all_done"]

    def test_sweep_rejects_mismatched_shapes_and_double_axis(self):
        from tpudes.parallel.replicated import run_replicated_bss

        prog = _bss_prog()
        pts = toy_traffic_points(prog.n, prog.sim_end_us,
                                 start_us=prog.start_us)
        base = dataclasses.replace(prog, traffic=pts[0])
        bad = dataclasses.replace(pts[1], n_cycle=1)
        with pytest.raises(ValueError):
            run_replicated_bss(
                base, 3, KEY, traffic_sweep=[pts[0], bad]
            )
        with pytest.raises(ValueError):
            run_replicated_bss(
                base, 3, KEY, traffic_sweep=pts,
                sim_end_us=[prog.sim_end_us] * 8,
            )


LTE_FIELDS = ("rx_bits", "new_tbs", "retx", "drops", "ok")


def _lte_traffic(n_ue, n_ttis, seed=2):
    tp = TrafficProgram.onoff(
        n_ue, 50.0, horizon_us=n_ttis * 1000, on=(1.5, 0.01, 0.05),
        off_mean_s=0.02, tr_seed=seed,
    )
    return dataclasses.replace(
        tp, size_pareto=np.asarray([1.4, 800.0, 12000.0], np.float32)
    )


class TestLteSm:
    def test_saturating_fill_bit_equal_to_full_buffer(self):
        from tpudes.parallel.lte_sm import run_lte_sm

        prog = toy_lte_program(n_enb=2, n_ue=4, n_ttis=100)
        full = run_lte_sm(prog, KEY, replicas=2)
        sat = dataclasses.replace(
            TrafficProgram.cbr(
                np.zeros(4, np.int32), np.full(4, 1, np.int64)
            ),
            size_pareto=np.asarray([0.0, 20000.0, 20000.0], np.float32),
        )
        out = run_lte_sm(
            dataclasses.replace(prog, traffic=sat), KEY, replicas=2
        )
        assert _eq(full, out, LTE_FIELDS)

    @pytest.mark.slow  # ISSUE-21 tier-1 budget: runs in CI's slow-overflow step
    def test_finite_backlog_bounds_and_chunk_sweep_bit_equal(self):
        from tpudes.parallel.lte_sm import run_lte_sm

        prog = toy_lte_program(n_enb=2, n_ue=4, n_ttis=120)
        p = dataclasses.replace(
            prog, traffic=_lte_traffic(4, prog.n_ttis)
        )
        full = run_lte_sm(prog, KEY, replicas=2)
        ref = run_lte_sm(p, KEY, replicas=2)
        # an app-limited cell cannot beat the saturated one, and the
        # workload goodput accounting closes: drained + backlog stays
        # within the realized offered fill (size quanta are drawn per
        # TTI, so compare against a generous multiple of the mean)
        assert (
            np.asarray(ref["rx_bits"]).sum()
            <= np.asarray(full["rx_bits"]).sum()
        )
        assert (np.asarray(ref["goodput_bits"]) >= 0).all()
        assert (np.asarray(ref["backlog_bits"]) >= 0).all()
        assert ref["offered_bits"].shape == (4,)
        chunked = run_lte_sm(p, KEY, replicas=2, chunk_ttis=50)
        assert _eq(ref, chunked, LTE_FIELDS + (
            "backlog_bits", "goodput_bits"))
        sw = run_lte_sm(p, KEY, replicas=2, schedulers=["pf", "rr"])
        assert _eq(ref, sw[0], LTE_FIELDS + ("goodput_bits",))

    def test_size_params_are_traced_not_baked(self):
        # regression (ISSUE-14 review): size_pareto must reach the
        # compiled backlog fill as the tr_size OPERAND — a size flip
        # changes the offered load WITHOUT a recompile (the cache key
        # carries shapes only, so a baked constant would silently
        # serve stale sizes)
        from tpudes.obs.device import CompileTelemetry
        from tpudes.parallel.lte_sm import run_lte_sm

        prog = toy_lte_program(n_enb=2, n_ue=4, n_ttis=100)
        small = dataclasses.replace(
            TrafficProgram.onoff(
                4, 20.0, horizon_us=100_000, on=(1.5, 0.01, 0.05),
                off_mean_s=0.02, tr_seed=3,
            ),
            size_pareto=np.asarray([0.0, 400.0, 400.0], np.float32),
        )
        big = dataclasses.replace(
            small,
            size_pareto=np.asarray([0.0, 9000.0, 9000.0], np.float32),
        )
        r_small = run_lte_sm(
            dataclasses.replace(prog, traffic=small), KEY, replicas=2
        )
        c0 = CompileTelemetry.compiles("lte_sm")
        r_big = run_lte_sm(
            dataclasses.replace(prog, traffic=big), KEY, replicas=2
        )
        assert CompileTelemetry.compiles("lte_sm") - c0 == 0
        assert (
            np.asarray(r_big["goodput_bits"]).sum()
            > np.asarray(r_small["goodput_bits"]).sum()
        )

    def test_traffic_plus_mobility_rejected_loudly(self):
        from tpudes.ops.mobility import MobilityProgram
        from tpudes.parallel.lte_sm import (
            UnliftableLteScenarioError,
            run_lte_sm,
        )

        prog = toy_lte_program(n_enb=2, n_ue=3, n_ttis=40)
        mob = MobilityProgram.static(np.zeros((3, 3), np.float32))
        p = dataclasses.replace(
            prog,
            traffic=_lte_traffic(3, 40),
            mobility=mob,
            enb_pos=np.zeros((2, 3), np.float32),
            pathloss=("log_distance", 3.0, 1.0, 46.7),
        )
        with pytest.raises(UnliftableLteScenarioError):
            run_lte_sm(p, KEY, replicas=2)


TCP_FIELDS = ("delivered", "drops", "cwnd_final")


class TestDumbbell:
    def test_unlimited_offer_bit_equal_to_bulk(self):
        from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

        prog = toy_dumbbell_program(n_flows=2, n_slots=250)
        bulk = run_tcp_dumbbell(prog, KEY, replicas=2)
        tp = TrafficProgram.cbr(
            np.zeros(2, np.int32), np.full(2, 1, np.int64)
        )
        out = run_tcp_dumbbell(
            dataclasses.replace(prog, traffic=tp), KEY, replicas=2
        )
        assert _eq(bulk, out, TCP_FIELDS)

    @pytest.mark.slow  # ISSUE-21 tier-1 budget: runs in CI's slow-overflow step
    def test_app_limited_flows_and_chunk_variant_sweep(self):
        from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell
        from tpudes.traffic.host import offered_packets

        prog = toy_dumbbell_program(n_flows=2, n_slots=300)
        tp = TrafficProgram.onoff(
            2, 60.0, horizon_us=300_000, on=(1.5, 0.02, 0.08),
            off_mean_s=0.05, tr_seed=1,
        )
        p = dataclasses.replace(prog, traffic=tp)
        ref = run_tcp_dumbbell(p, KEY, replicas=2)
        # the app-limit gate: no flow delivers more than the workload
        # offered by the end of the horizon
        cap = np.floor(offered_packets(tp, prog.n_slots * 1000))
        assert (np.asarray(ref["delivered"]) <= cap[None, :]).all()
        chunked = run_tcp_dumbbell(
            p, KEY, replicas=2, chunk_slots=97
        )
        assert _eq(ref, chunked, TCP_FIELDS)
        sw = run_tcp_dumbbell(
            p, KEY, replicas=2,
            variants=[
                ["TcpNewReno", "TcpCubic"], ["TcpVegas", "TcpVegas"],
            ],
        )
        pt = run_tcp_dumbbell(
            dataclasses.replace(
                p,
                variant_idx=np.asarray([0, 1], np.int32),
                ecn=np.zeros(2, bool),
            ),
            KEY, replicas=2,
        )
        assert _eq(pt, sw[0], TCP_FIELDS)


class TestDumbbellTrafficSweep:
    """ISSUE-15: the dumbbell engine gains the BSS-style config-axis
    workload sweep (``traffic_sweep=``)."""

    def test_mixed_workload_sweep_one_launch_demux_bit_equal(self):
        from tpudes.obs.device import CompileTelemetry
        from tpudes.parallel.runtime import RUNTIME
        from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

        prog = toy_dumbbell_program(n_flows=3, n_slots=120)
        pts = toy_traffic_points(3, 120_000)
        assert len(pts) == 8
        per = [
            run_tcp_dumbbell(
                dataclasses.replace(prog, traffic=tp), KEY, replicas=3
            )
            for tp in pts
        ]
        base = dataclasses.replace(prog, traffic=pts[0])
        run_tcp_dumbbell(base, KEY, replicas=3, traffic_sweep=pts)  # warm
        l0 = RUNTIME.launches("dumbbell")
        c0 = CompileTelemetry.compiles("dumbbell")
        swept = run_tcp_dumbbell(
            base, KEY, replicas=3, traffic_sweep=pts
        )
        assert RUNTIME.launches("dumbbell") - l0 == 1
        assert CompileTelemetry.compiles("dumbbell") - c0 == 0
        for a, b in zip(per, swept):
            assert _eq(a, b, TCP_FIELDS)

    def test_sweep_rejects_mismatched_shapes_and_double_axis(self):
        from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

        prog = toy_dumbbell_program(n_flows=2, n_slots=60)
        pts = toy_traffic_points(2, 60_000)
        base = dataclasses.replace(prog, traffic=pts[0])
        bad = dataclasses.replace(pts[1], n_cycle=1)
        with pytest.raises(ValueError, match="shape key"):
            run_tcp_dumbbell(
                base, KEY, replicas=2, traffic_sweep=[pts[0], bad]
            )
        with pytest.raises(ValueError, match="one config axis"):
            run_tcp_dumbbell(
                base, KEY, replicas=2, traffic_sweep=pts,
                variants=[[0, 1]] * 8,
            )
        # prog.traffic unset: the sweep has no shape class to compile
        with pytest.raises(ValueError, match="prog.traffic"):
            run_tcp_dumbbell(
                prog, KEY, replicas=2, traffic_sweep=pts
            )


class TestAsFlows:
    def test_cbr_multiplier_is_exact_identity(self):
        from tpudes.parallel.as_flows import run_as_flows

        prog = toy_as_program(n_nodes=16, n_flows=2, spf_rounds=8)
        base = run_as_flows(prog, KEY, replicas=2)
        tp = TrafficProgram.cbr(
            np.zeros(2, np.int32), np.full(2, 1000, np.int64)
        )
        out = run_as_flows(
            dataclasses.replace(prog, traffic=tp), KEY, replicas=2
        )
        assert _eq(
            base, out,
            ("goodput_bps", "delay_s", "delivered_frac", "max_util"),
        )

    def test_workload_scales_offered_load_and_rate_sweep_rides(self):
        from tpudes.parallel.as_flows import run_as_flows
        from tpudes.traffic.host import offered_packets

        prog = toy_as_program(n_nodes=16, n_flows=2, spf_rounds=8)
        tp = TrafficProgram.onoff(
            2, 100.0, horizon_us=int(prog.sim_s * 1e6),
            on=(1.5, 0.05, 0.3), off_mean_s=0.1, tr_seed=4,
        )
        p = dataclasses.replace(prog, traffic=tp)
        base = run_as_flows(prog, KEY, replicas=2)
        out = run_as_flows(p, KEY, replicas=2)
        mult = offered_packets(tp, int(prog.sim_s * 1e6)) / (
            tp.rate_pps.astype(np.float64) * prog.sim_s
        )
        want = np.asarray(base["goodput_bps"], np.float64) * mult[None, :]
        np.testing.assert_allclose(
            np.asarray(out["goodput_bps"], np.float64), want, rtol=2e-3
        )
        sw = run_as_flows(p, KEY, replicas=2, rate_scale=[1.0, 0.5])
        assert _eq(
            out, sw[0],
            ("goodput_bps", "delay_s", "delivered_frac", "max_util"),
        )


class TestServingKeys:
    def test_workloads_separate_coalesce_groups(self):
        from tpudes.parallel.lte_sm import lte_sm_study
        from tpudes.parallel.replicated import bss_study
        from tpudes.parallel.tcp_dumbbell import tcp_study

        bss = _bss_prog()
        a = bss_study(
            dataclasses.replace(bss, traffic=_bss_onoff(bss, 1)),
            KEY, 4,
        )
        b = bss_study(
            dataclasses.replace(bss, traffic=_bss_onoff(bss, 2)),
            KEY, 4,
        )
        assert a.coalesce_key != b.coalesce_key
        lte = toy_lte_program(n_enb=2, n_ue=4, n_ttis=80)
        la = lte_sm_study(
            dataclasses.replace(lte, traffic=_lte_traffic(4, 80, 1)),
            KEY, replicas=2,
        )
        lb = lte_sm_study(
            dataclasses.replace(lte, traffic=_lte_traffic(4, 80, 2)),
            KEY, replicas=2,
        )
        assert la.coalesce_key != lb.coalesce_key
        tcp = toy_dumbbell_program(n_flows=2, n_slots=100)
        tp = TrafficProgram.cbr(
            np.zeros(2, np.int32), np.full(2, 5000, np.int64)
        )
        ta = tcp_study(dataclasses.replace(tcp, traffic=tp), KEY, 2)
        tb = tcp_study(tcp, KEY, 2)
        assert ta.coalesce_key != tb.coalesce_key
