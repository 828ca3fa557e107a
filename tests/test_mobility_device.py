"""Device-resident mobility (ISSUE-10): the geometry pipeline that
melts the mobility ❌ rows.

Pinned contracts:

- **Closed-form kernels** (``tpudes.ops.mobility``): const-velocity is
  exact kinematics, the walk is deterministic in its seed and bounded,
  the waypoint interpolation pauses at the final waypoint and treats
  zero-velocity segments as pauses.
- **Stride contract**: ``geom_stride=1`` is BIT-identical to the
  unconditional per-step recompute program, and the refresh count is
  ``ceil(steps/stride)`` — the geometry stage really skips work.
- **One executable**: mobility model id, every mobility parameter, and
  the stride are traced operands — flipping any of them must not
  recompile (CompileTelemetry pins it on both engines, including a
  model-family flip through the live-graph lowering).
- **Kill switch**: ``TPUDES_DEVICE_GEOM=0`` restores the loud refusal
  on both lowerings; on the LTE engine a mobile program still runs via
  the precomputed-positions per-window fallback, pinned bit-equal.
- **Host parity**: device mobile runs track the host DES with the same
  mobility trace at the documented fuzz bands (exact-trace models),
  including the waypoint edge cases.
- **Coherence advisory**: both lowerings warn when the stride lets the
  fastest node outrun the geometry coherence scale.
"""

import dataclasses
import math
import warnings

import jax
import numpy as np
import pytest

from tpudes.ops.mobility import (
    GEOM_COHERENCE_M,
    MobilityProgram,
    build_position_fn,
    fold_into_bounds,
    max_speed_mps,
    trajectory_positions,
)


def _pos(prog, t_us):
    fn = build_position_fn(prog)
    return np.asarray(fn(prog.operands(), jax.numpy.int32(t_us)))


def _reset_world():
    from tpudes.core.world import reset_world

    reset_world()


# --------------------------------------------------------------------------
# closed-form kernels
# --------------------------------------------------------------------------


class TestMobilityKernels:
    def test_const_velocity_closed_form(self):
        base = np.array([[0, 0, 0], [10, -5, 2]], np.float32)
        vel = np.array([[1, 2, 0], [-0.5, 0, 0]], np.float32)
        prog = MobilityProgram.constant_velocity(base, vel)
        np.testing.assert_allclose(
            _pos(prog, 3_000_000), base + 3.0 * vel, rtol=1e-6
        )

    def test_static_model_never_moves(self):
        base = np.array([[4, 5, 6]], np.float32)
        prog = MobilityProgram.static(base)
        for t in (0, 1, 999_999, 10_000_000):
            np.testing.assert_array_equal(_pos(prog, t), base)

    def test_walk_bounded_deterministic_and_seeded(self):
        base = np.array([[5, 5, 0], [15, 15, 0]], np.float32)
        speed = np.array([[1.0, 3.0], [1.0, 3.0]], np.float32)
        mk = lambda s: MobilityProgram.random_walk(  # noqa: E731
            base, (0.0, 20.0, 0.0, 20.0), speed, seg_s=0.25,
            horizon_us=4_000_000, mob_seed=s,
        )
        a = mk(7)
        for t in (0, 700_000, 1_900_000, 3_500_000):
            p = _pos(a, t)
            assert (p[:, 0] >= 0).all() and (p[:, 0] <= 20).all()
            assert (p[:, 1] >= 0).all() and (p[:, 1] <= 20).all()
            np.testing.assert_array_equal(p, _pos(mk(7), t))
        assert not np.array_equal(_pos(a, 2_000_000), _pos(mk(8), 2_000_000))

    def test_walk_zero_band_node_is_pinned_even_outside_bounds(self):
        # a static AP outside the walkers' rectangle must NOT be folded
        base = np.array([[50, 50, 0], [5, 5, 0]], np.float32)
        speed = np.array([[0.0, 0.0], [1.0, 2.0]], np.float32)
        prog = MobilityProgram.random_walk(
            base, (0.0, 10.0, 0.0, 10.0), speed, seg_s=0.5,
            horizon_us=2_000_000,
        )
        np.testing.assert_array_equal(_pos(prog, 1_500_000)[0], base[0])

    def test_walk_is_cadence_indifferent(self):
        # closed form in t: sampling the trajectory sparsely or densely
        # reads the SAME motion (what makes geom_stride a pure
        # staleness knob, not a different trajectory)
        base = np.array([[5, 5, 0]], np.float32)
        speed = np.array([[1.0, 2.0]], np.float32)
        prog = MobilityProgram.random_walk(
            base, (0.0, 12.0, 0.0, 12.0), speed, seg_s=0.3,
            horizon_us=3_000_000, mob_seed=3,
        )
        dense = trajectory_positions(
            prog, list(range(0, 3_000_001, 100_000))
        )
        np.testing.assert_array_equal(dense[10], _pos(prog, 1_000_000))

    def test_waypoint_interpolation_and_pause_at_final(self):
        wt = np.array([[100_000, 1_100_000, 2_100_000]])
        wp = np.array([[[0, 0, 0], [10, 0, 0], [10, 20, 0]]], np.float32)
        prog = MobilityProgram.waypoints(wt, wp)
        # holds the first waypoint before its time
        np.testing.assert_allclose(_pos(prog, 0), [[0, 0, 0]], atol=1e-6)
        # linear mid-leg
        np.testing.assert_allclose(
            _pos(prog, 600_000), [[5, 0, 0]], atol=1e-5
        )
        # pauses at the final waypoint forever after
        for t in (2_100_000, 5_000_000, 60_000_000):
            np.testing.assert_allclose(
                _pos(prog, t), [[10, 20, 0]], atol=1e-6
            )

    def test_waypoint_zero_velocity_segment_is_a_pause(self):
        # consecutive identical positions = a dwell; consecutive
        # identical TIMES (zero-duration leg) must not divide by zero
        wt = np.array([[0, 1_000_000, 2_000_000, 2_000_000]])
        wp = np.array(
            [[[0, 0, 0], [8, 0, 0], [8, 0, 0], [9, 9, 0]]], np.float32
        )
        prog = MobilityProgram.waypoints(wt, wp)
        np.testing.assert_allclose(
            _pos(prog, 1_500_000), [[8, 0, 0]], atol=1e-5
        )
        out = _pos(prog, 2_000_000)
        assert np.isfinite(out).all()

    def test_fold_into_bounds_identity_and_reflection(self):
        import jax.numpy as jnp

        x = jnp.asarray([2.0, 11.0, -3.0, 23.0])
        out = np.asarray(fold_into_bounds(x, 0.0, 10.0))
        np.testing.assert_allclose(out, [2.0, 9.0, 3.0, 3.0], atol=1e-6)

    def test_max_speed_per_model(self):
        base = np.zeros((2, 3), np.float32)
        assert max_speed_mps(MobilityProgram.static(base)) == 0.0
        cv = MobilityProgram.constant_velocity(
            base, np.array([[3, 4, 0], [0, 0, 0]], np.float32)
        )
        assert max_speed_mps(cv) == pytest.approx(5.0)
        wk = MobilityProgram.random_walk(
            base, (0, 1, 0, 1),
            np.array([[0.5, 2.5], [0, 0]], np.float32),
            horizon_us=1_000_000,
        )
        assert max_speed_mps(wk) == pytest.approx(2.5)
        wp = MobilityProgram.waypoints(
            np.array([[0, 1_000_000]]),
            np.array([[[0, 0, 0], [7, 0, 0]]], np.float32),
        )
        assert max_speed_mps(wp) == pytest.approx(7.0)


# --------------------------------------------------------------------------
# live-graph extraction
# --------------------------------------------------------------------------


class TestExtraction:
    def _nodes(self, models):
        from tpudes.helper.containers import NodeContainer

        nodes = NodeContainer()
        nodes.Create(len(models))
        for i, m in enumerate(models):
            nodes.Get(i).AggregateObject(m)
        return [nodes.Get(i) for i in range(len(models))]

    def test_all_static_returns_none(self):
        from tpudes.models.mobility import (
            ConstantPositionMobilityModel,
            Vector,
            device_mobility_program,
        )

        _reset_world()
        ms = [ConstantPositionMobilityModel() for _ in range(2)]
        for i, m in enumerate(ms):
            m.SetPosition(Vector(i, 0, 0))
        assert device_mobility_program(self._nodes(ms), 1_000_000) is None
        _reset_world()

    def test_mixed_moving_families_raise(self):
        from tpudes.models.mobility import (
            ConstantVelocityMobilityModel,
            UnliftableMobilityError,
            Vector,
            WaypointMobilityModel,
            device_mobility_program,
        )
        from tpudes.core.nstime import Seconds

        _reset_world()
        cv = ConstantVelocityMobilityModel()
        cv.SetPosition(Vector(0, 0, 0))
        cv.SetVelocity(Vector(1, 0, 0))
        wp = WaypointMobilityModel()
        wp.AddWaypoint(Seconds(0), Vector(1, 1, 0))
        wp.AddWaypoint(Seconds(1), Vector(2, 1, 0))
        with pytest.raises(UnliftableMobilityError):
            device_mobility_program(self._nodes([cv, wp]), 1_000_000)
        _reset_world()

    def test_gauss_markov_has_no_device_form(self):
        from tpudes.models.mobility import (
            GaussMarkovMobilityModel,
            UnliftableMobilityError,
            Vector,
            device_mobility_program,
        )

        _reset_world()
        gm = GaussMarkovMobilityModel()
        gm.SetPosition(Vector(0, 0, 0))
        with pytest.raises(UnliftableMobilityError):
            device_mobility_program(self._nodes([gm]), 1_000_000)
        _reset_world()

    def test_static_nodes_ride_a_waypoint_batch_as_pauses(self):
        from tpudes.core.nstime import Seconds
        from tpudes.models.mobility import (
            ConstantPositionMobilityModel,
            Vector,
            WaypointMobilityModel,
            device_mobility_program,
        )

        _reset_world()
        wp = WaypointMobilityModel()
        wp.AddWaypoint(Seconds(0.0), Vector(0, 0, 0))
        wp.AddWaypoint(Seconds(1.0), Vector(6, 0, 0))
        cp = ConstantPositionMobilityModel()
        cp.SetPosition(Vector(9, 9, 9))
        prog = device_mobility_program(
            self._nodes([wp, cp]), 2_000_000
        )
        assert prog.model == "waypoint"
        out = _pos(prog, 1_700_000)
        np.testing.assert_allclose(out[0], [6, 0, 0], atol=1e-5)
        np.testing.assert_allclose(out[1], [9, 9, 9], atol=1e-6)
        _reset_world()


# --------------------------------------------------------------------------
# BSS engine
# --------------------------------------------------------------------------


def _bss_mobile_prog(mobility="const_velocity", speed=1.0, stride=1,
                     n_stas=3, sim_s=1.5):
    from tpudes.parallel.replicated import lower_bss
    from tpudes.scenarios import build_bss

    _reset_world()
    stas, ap, clients, _ = build_bss(
        n_stas, sim_s, mobility=mobility, speed=speed
    )
    prog = lower_bss(
        [stas.Get(i) for i in range(n_stas)], ap, clients, sim_s,
        geom_stride=stride,
    )
    _reset_world()
    return prog


class TestBssMobile:
    @pytest.mark.slow  # ISSUE-21 tier-1 budget: the multi-device CI step runs the full file
    def test_stride1_bit_identical_to_per_step_recompute(self):
        from tpudes.parallel.replicated import run_replicated_bss

        prog = _bss_mobile_prog(stride=1)
        a = run_replicated_bss(prog, 8, jax.random.PRNGKey(0))
        b = run_replicated_bss(
            prog, 8, jax.random.PRNGKey(0), geom_per_step=True
        )
        for k in ("srv_rx", "cli_rx", "tx_data", "drops"):
            np.testing.assert_array_equal(
                np.asarray(a[k]), np.asarray(b[k]), err_msg=k
            )

    def test_stride_refresh_accounting(self):
        from tpudes.parallel.replicated import run_replicated_bss

        prog = _bss_mobile_prog(stride=4)
        out = run_replicated_bss(prog, 4, jax.random.PRNGKey(1))
        assert out["geom_stride"] == 4
        assert out["geom_refreshes"] == -(-out["steps"] // 4)
        one = run_replicated_bss(
            dataclasses.replace(prog, geom_stride=1), 4,
            jax.random.PRNGKey(1),
        )
        assert one["geom_refreshes"] == one["steps"]

    def test_params_model_and_stride_are_traced(self):
        # live-graph lowering of BOTH mobile families at the same shape
        # → ONE executable (the CompileTelemetry pin of the acceptance
        # criteria); stride and speed flips ride along free
        from tpudes.obs.device import CompileTelemetry
        from tpudes.parallel.replicated import run_replicated_bss
        from tpudes.parallel.runtime import RUNTIME

        cv = _bss_mobile_prog("const_velocity", speed=0.8, stride=1)
        walk = _bss_mobile_prog("random_walk", speed=0.8, stride=5)
        assert (
            cv.mobility.shape_key() == walk.mobility.shape_key()
        ), "family shapes must be normalized for the one-executable pin"
        RUNTIME.clear("bss")
        CompileTelemetry.reset()
        run_replicated_bss(cv, 4, jax.random.PRNGKey(0))
        assert CompileTelemetry.compiles("bss") == 1
        run_replicated_bss(walk, 4, jax.random.PRNGKey(0))
        run_replicated_bss(
            dataclasses.replace(cv, geom_stride=9), 4, jax.random.PRNGKey(2)
        )
        assert CompileTelemetry.compiles("bss") == 1, (
            "mobility model id / params / stride must be traced operands"
        )

    @pytest.mark.slow  # tier-1 covers this via corpus bss-seed202/244
    def test_chunked_and_swept_mobile_runs_bit_equal(self):
        from tpudes.parallel.replicated import run_replicated_bss

        prog = _bss_mobile_prog(stride=3)
        solo = run_replicated_bss(prog, 5, jax.random.PRNGKey(3))
        chunked = run_replicated_bss(
            prog, 5, jax.random.PRNGKey(3), chunk_steps=11
        )
        swept = run_replicated_bss(
            prog, 5, jax.random.PRNGKey(3),
            sim_end_us=[prog.sim_end_us, prog.sim_end_us * 3 // 4],
        )[0]
        for k in ("srv_rx", "cli_rx", "tx_data", "drops"):
            np.testing.assert_array_equal(
                np.asarray(solo[k]), np.asarray(chunked[k]), err_msg=k
            )
            np.testing.assert_array_equal(
                np.asarray(solo[k]), np.asarray(swept[k]), err_msg=k
            )

    def test_kill_switch_restores_refusal(self, monkeypatch):
        from tpudes.parallel.replicated import UnliftableScenarioError

        monkeypatch.setenv("TPUDES_DEVICE_GEOM", "0")
        with pytest.raises(UnliftableScenarioError, match="DEVICE_GEOM"):
            _bss_mobile_prog()

    def test_trajectory_leaving_sensing_range_is_refused(self):
        from tpudes.parallel.replicated import UnliftableScenarioError

        # 120 m/s tangential drift for 1.5 s sweeps the outer STAs
        # ~180 m out; opposite pairs end ~300 m apart — far beyond the
        # ~220 m log-distance sensing radius at some trajectory sample
        with pytest.raises(UnliftableScenarioError, match="trajectory"):
            _bss_mobile_prog(
                "const_velocity", speed=120.0, n_stas=3, sim_s=1.5,
            )

    @pytest.mark.slow  # multi-device CI runs the full file
    def test_host_parity_const_velocity_trace(self):
        """Device mobile runs vs the host DES with the SAME
        constant-velocity trace (exact-trace model): the documented
        distribution-level band."""
        from tpudes.core import Seconds, Simulator
        from tpudes.core.rng import RngSeedManager
        from tpudes.parallel.replicated import run_replicated_bss
        from tpudes.scenarios import build_bss

        des = []
        for run in range(1, 6):
            _reset_world()
            RngSeedManager.SetRun(run)
            _, _, _, rx = build_bss(
                3, 1.5, mobility="const_velocity", speed=1.0
            )
            Simulator.Stop(Seconds(1.5))
            Simulator.Run()
            des.append(rx[0])
        _reset_world()
        prog = _bss_mobile_prog("const_velocity", speed=1.0)
        out = run_replicated_bss(prog, 64, jax.random.PRNGKey(9))
        assert out["all_done"]
        rep = np.asarray(out["srv_rx"], np.float64)
        des = np.asarray(des, np.float64)
        sem = math.sqrt(
            des.var(ddof=1) / len(des) + rep.var(ddof=1) / len(rep)
        )
        assert abs(des.mean() - rep.mean()) <= 3.0 * sem + 1.5, (
            f"DES {des.mean():.2f} vs device {rep.mean():.2f} "
            f"(sem {sem:.2f})"
        )

    def test_host_parity_waypoint_edges(self):
        """Waypoint trace with a dwell (zero-velocity segment) and a
        final-waypoint pause: device vs host DES on the same table."""
        from tpudes.core import Seconds, Simulator
        from tpudes.core.nstime import Seconds as S
        from tpudes.core.rng import RngSeedManager
        from tpudes.models.mobility import (
            MobilityModel,
            Vector,
            WaypointMobilityModel,
        )
        from tpudes.parallel.replicated import lower_bss, run_replicated_bss
        from tpudes.scenarios import build_bss

        def _graph():
            stas, ap, clients, rx = build_bss(3, 1.5)
            # STA 0 walks 6 m outward, dwells, then pauses at the end
            node = stas.Get(0).GetNode()
            old = node.GetObject(MobilityModel)
            p0 = old.GetPosition()
            wp = WaypointMobilityModel()
            ring = node._aggregates
            ring[ring.index(old)] = wp
            wp._aggregates = ring
            wp.AddWaypoint(S(0.0), p0)
            wp.AddWaypoint(S(0.4), Vector(p0.x + 6.0, p0.y, p0.z))
            wp.AddWaypoint(S(0.8), Vector(p0.x + 6.0, p0.y, p0.z))
            wp.AddWaypoint(S(1.0), Vector(p0.x, p0.y + 4.0, p0.z))
            return stas, ap, clients, rx

        des = []
        for run in range(1, 5):
            _reset_world()
            RngSeedManager.SetRun(run)
            _, _, _, rx = _graph()
            Simulator.Stop(Seconds(1.5))
            Simulator.Run()
            des.append(rx[0])
        _reset_world()
        stas, ap, clients, _ = _graph()
        prog = lower_bss(
            [stas.Get(i) for i in range(3)], ap, clients, 1.5
        )
        _reset_world()
        assert prog.mobility is not None and prog.mobility.model == "waypoint"
        out = run_replicated_bss(prog, 64, jax.random.PRNGKey(4))
        rep = np.asarray(out["srv_rx"], np.float64)
        des = np.asarray(des, np.float64)
        sem = math.sqrt(
            des.var(ddof=1) / len(des) + rep.var(ddof=1) / len(rep)
        )
        assert abs(des.mean() - rep.mean()) <= 3.0 * sem + 1.5

    def test_stride_coherence_warning_boundary(self):
        # ~0.011 s/step estimate at this load; 1 m/s × stride 400 ≈ 4 m
        # drift > the 2 m coherence scale → warn; stride 1 is silent
        with pytest.warns(UserWarning, match="coherence"):
            _bss_mobile_prog(stride=400)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            _bss_mobile_prog(stride=1)


# --------------------------------------------------------------------------
# LTE engine
# --------------------------------------------------------------------------


def _lte_mobile_prog(mobility="const_velocity", speed=10.0, stride=1,
                     sim_s=0.08, n_enbs=2, upc=2, warn_ok=False):
    from tpudes.parallel.lte_sm import lower_lte_sm
    from tpudes.scenarios import build_lena

    _reset_world()
    lte, _ = build_lena(n_enbs, upc, mobility=mobility, speed=speed)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        prog = lower_lte_sm(lte, sim_s, geom_stride=stride)
    _reset_world()
    return prog


class TestLteMobile:
    @pytest.mark.slow  # tier-1 covers this via corpus lte_sm-seed219/227
    def test_device_geom_off_fallback_bit_equal(self, monkeypatch):
        from tpudes.parallel.lte_sm import run_lte_sm

        for model, stride in (("const_velocity", 1), ("random_walk", 8)):
            prog = _lte_mobile_prog(model, stride=stride)
            on = run_lte_sm(prog, jax.random.PRNGKey(0), replicas=3)
            monkeypatch.setenv("TPUDES_DEVICE_GEOM", "0")
            off = run_lte_sm(prog, jax.random.PRNGKey(0), replicas=3)
            monkeypatch.delenv("TPUDES_DEVICE_GEOM")
            for k in ("rx_bits", "ok", "retx", "drops", "cqi", "sinr"):
                np.testing.assert_array_equal(
                    np.asarray(on[k]), np.asarray(off[k]),
                    err_msg=f"{model}/{k}",
                )

    def test_model_params_and_stride_are_traced(self):
        from tpudes.obs.device import CompileTelemetry
        from tpudes.parallel.lte_sm import run_lte_sm
        from tpudes.parallel.runtime import RUNTIME

        cv = _lte_mobile_prog("const_velocity", stride=1)
        walk = _lte_mobile_prog("random_walk", stride=16)
        assert cv.mobility.shape_key() == walk.mobility.shape_key()
        RUNTIME.clear("lte_sm")
        CompileTelemetry.reset()
        a = run_lte_sm(cv, jax.random.PRNGKey(0), replicas=3)
        assert CompileTelemetry.compiles("lte_sm") == 1
        run_lte_sm(walk, jax.random.PRNGKey(0), replicas=3)
        run_lte_sm(
            dataclasses.replace(cv, geom_stride=5), jax.random.PRNGKey(1),
            replicas=3,
        )
        assert CompileTelemetry.compiles("lte_sm") == 1, (
            "model id / params / stride must be traced operands"
        )
        assert a["geom_refreshes"] == cv.n_ttis  # stride 1 = per TTI

    @pytest.mark.slow  # tier-1 covers chunking via corpus lte_sm-seed227
    def test_scheduler_sweep_and_chunking_bit_equal(self):
        from tpudes.parallel.lte_sm import run_lte_sm

        prog = _lte_mobile_prog(stride=4)
        solo = run_lte_sm(prog, jax.random.PRNGKey(2), replicas=3)
        chunked = run_lte_sm(
            prog, jax.random.PRNGKey(2), replicas=3, chunk_ttis=13
        )
        swept = run_lte_sm(
            prog, jax.random.PRNGKey(2), replicas=3,
            schedulers=[prog.scheduler, "rr"],
        )[0]
        for k in ("rx_bits", "ok", "retx", "drops"):
            np.testing.assert_array_equal(
                np.asarray(solo[k]), np.asarray(chunked[k]), err_msg=k
            )
            np.testing.assert_array_equal(
                np.asarray(solo[k]), np.asarray(swept[k]), err_msg=k
            )

    def test_pallas_and_xla_lowerings_agree_mobile(
        self, monkeypatch, sm_lowerings_built
    ):
        from tpudes.parallel.lte_sm import run_lte_sm
        from tpudes.parallel.runtime import RUNTIME

        prog = _lte_mobile_prog(stride=2)
        RUNTIME.clear("lte_sm")
        monkeypatch.setenv("TPUDES_PALLAS", "1")
        a = run_lte_sm(prog, jax.random.PRNGKey(5), replicas=2)
        monkeypatch.setenv("TPUDES_PALLAS", "0")
        b = run_lte_sm(prog, jax.random.PRNGKey(5), replicas=2)
        assert sm_lowerings_built(prog) == {True, False}
        np.testing.assert_array_equal(
            np.asarray(a["rx_bits"]), np.asarray(b["rx_bits"])
        )

    @pytest.mark.slow  # multi-device CI runs the full file
    def test_host_parity_const_velocity_trace(self):
        """Device mobile LTE vs the host TTI controller with the SAME
        constant-velocity trace, at the documented fuzz band."""
        from tpudes.core import Seconds, Simulator
        from tpudes.parallel.lte_sm import lower_lte_sm, run_lte_sm
        from tpudes.scenarios import build_lena

        _reset_world()
        lte, _ = build_lena(
            2, 3, mobility="const_velocity", speed=30.0, drop_seed=3
        )
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            prog = lower_lte_sm(lte, 0.3)
        Simulator.Stop(Seconds(0.3))
        Simulator.Run()
        host = sum(s["dl_rx_bytes"] for s in lte.GetRlcStats()) * 8
        _reset_world()
        out = run_lte_sm(prog, jax.random.PRNGKey(0), replicas=4)
        dev = float(np.asarray(out["rx_bits"]).sum(-1).mean())
        assert abs(host - dev) <= 0.35 * max(host, dev), (host, dev)

    def test_stride_coherence_warning_boundary(self):
        from tpudes.parallel.lte_sm import lower_lte_sm
        from tpudes.scenarios import build_lena

        # 30 m/s × 1 ms TTI: stride 100 drifts 3 m > 2 m → warn;
        # stride 10 drifts 0.3 m → silent
        _reset_world()
        lte, _ = build_lena(2, 2, mobility="const_velocity", speed=30.0)
        with pytest.warns(UserWarning, match="coherence"):
            lower_lte_sm(lte, 0.3, geom_stride=100)
        _reset_world()
        lte, _ = build_lena(2, 2, mobility="const_velocity", speed=30.0)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            lower_lte_sm(lte, 0.3, geom_stride=10)
        _reset_world()
        assert GEOM_COHERENCE_M == pytest.approx(2.0)

    def test_kill_switch_restores_refusal(self, monkeypatch):
        from tpudes.parallel.lte_sm import (
            UnliftableLteScenarioError,
            lower_lte_sm,
        )
        from tpudes.scenarios import build_lena

        _reset_world()
        lte, _ = build_lena(2, 2, mobility="const_velocity", speed=5.0)
        monkeypatch.setenv("TPUDES_DEVICE_GEOM", "0")
        with pytest.raises(UnliftableLteScenarioError, match="DEVICE_GEOM"):
            lower_lte_sm(lte, 0.3)
        _reset_world()


# --------------------------------------------------------------------------
# the host controller's per-window fallback path
# --------------------------------------------------------------------------


class TestControllerFallback:
    def _run(self):
        from tpudes.core import Seconds, Simulator
        from tpudes.scenarios import build_lena

        _reset_world()
        lte, _ = build_lena(
            2, 2, mobility="const_velocity", speed=5.0, drop_seed=5
        )
        Simulator.Stop(Seconds(0.05))
        Simulator.Run()
        stats = dict(lte.controller.stats)
        _reset_world()
        return stats

    def test_geometry_only_refresh_bit_equal_to_full_rebuild(
        self, monkeypatch
    ):
        # TPUDES_DEVICE_GEOM selects the geometry-only refresh vs the
        # legacy full per-window rebuild — same math, same inputs, so
        # the LTE per-window path must be bit-equal either way
        a = self._run()
        monkeypatch.setenv("TPUDES_DEVICE_GEOM", "0")
        b = self._run()
        assert a == b

    def test_host_refreshes_recorded(self):
        from tpudes.obs.geometry import GeomTelemetry

        GeomTelemetry.reset()
        from tpudes.core import Seconds, Simulator
        from tpudes.parallel.engine import BatchableRegistry
        from tpudes.scenarios import build_lena

        _reset_world()
        lte, _ = build_lena(2, 2, mobility="const_velocity", speed=5.0)
        # drive the per-window refresh the way a windowed engine does
        Simulator.Stop(Seconds(0.01))
        Simulator.Run()
        for member in BatchableRegistry.members():
            if hasattr(member, "refresh_window_cache"):
                member.refresh_window_cache()
        _reset_world()
        snap = GeomTelemetry.snapshot()
        assert snap["engines"]["lte_ctrl"]["host_refreshes"] >= 1


# --------------------------------------------------------------------------
# telemetry schema
# --------------------------------------------------------------------------


def test_geometry_metrics_schema_gate(tmp_path, capsys):
    import json

    from tpudes.obs.__main__ import main as obs_main
    from tpudes.obs.geometry import GeomTelemetry, validate_geometry_metrics

    GeomTelemetry.reset()
    GeomTelemetry.record_device("bss", 5, 20)
    GeomTelemetry.record_host("lte_ctrl", 3)
    snap = GeomTelemetry.snapshot()
    assert validate_geometry_metrics(snap) == []
    assert snap["engines"]["bss"]["stride_hit_rate"] == pytest.approx(0.75)
    p = tmp_path / "geom.json"
    p.write_text(json.dumps(snap))
    assert obs_main(["--geometry", str(p)]) == 0
    bad = {"version": 1, "engines": {"bss": {
        "device_refreshes": 30, "host_refreshes": 0, "steps": 20,
        "stride_hit_rate": 2.0,
    }}}
    assert validate_geometry_metrics(bad) != []
    GeomTelemetry.reset()
    capsys.readouterr()
