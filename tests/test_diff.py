"""Differentiable simulation (tpudes.diff, ISSUE-15): surrogate
exactness pins, finite-difference checks on every exposed operand,
vmap-of-grad batching, and the one-executable grad-sweep contract.

f32 tolerance notes (documented per the ISSUE): the engines are pinned
float32, so central differences carry ~|loss|·2⁻²³/h cancellation
noise on top of O(h²) truncation — each check sizes its step h so both
terms sit well under the asserted rtol (0.02 for the steep LTE chain,
5e-3 for the near-linear AS chain).
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpudes.diff import Surrogacy, grad_as_flows, grad_lte_sm  # noqa: E402
from tpudes.parallel.lte_sm import LteSmProgram  # noqa: E402
from tpudes.parallel.programs import (  # noqa: E402
    toy_as_program,
    toy_lte_program,
)

KEY = jax.random.PRNGKey(42)


def _as_prog(**over):
    prog = toy_as_program(n_nodes=24, n_flows=3)
    return dataclasses.replace(prog, **over) if over else prog


def _congested_as_prog(**over):
    """The toy graph pushed near link saturation, where the delivery
    gate actually gates (capacity gradients are zero in the deep
    sparse regime — delivery is pinned at 1)."""
    prog = toy_as_program(n_nodes=24, n_flows=3)
    return dataclasses.replace(
        prog,
        flow_bps=np.full(3, 4e7),
        surrogate=Surrogacy(ste=False),
        **over,
    )


def _lte_pos_prog(n_ue: int = 6, **over):
    """Tiny positional (pathloss-bearing) LTE program + UE positions."""
    E = 2
    serving = (np.arange(n_ue) % E).astype(np.int32)
    rng = np.random.default_rng(7)
    enb_pos = np.array([[0.0, 0.0, 30.0], [600.0, 0.0, 30.0]], np.float32)
    ue_pos = (
        enb_pos[serving]
        + np.c_[rng.uniform(-200, 200, n_ue),
                rng.uniform(-200, 200, n_ue),
                np.full(n_ue, -28.5)]
    ).astype(np.float32)
    prog = LteSmProgram(
        gain=np.full((E, n_ue), 1e-12),
        serving=serving,
        tx_power_dbm=np.full((E,), 43.0),
        noise_psd=10.0**0.9 * 1.380649e-23 * 290.0,
        n_rb=25,
        n_ttis=400,
        scheduler="pf",
        enb_pos=enb_pos,
        pathloss=("log_distance", 3.2, 1.0, 46.67),
        **over,
    )
    return prog, ue_pos


# --- surrogate exactness ----------------------------------------------------


class TestSurrogateExactness:
    def test_surrogate_none_is_same_runner_and_bit_equal(self):
        """The default program IS the legacy program: re-running hits
        the cached runner (no fresh compile) and an explicit
        surrogate=None replace changes nothing."""
        from tpudes.obs.device import CompileTelemetry
        from tpudes.parallel.as_flows import run_as_flows

        prog = _as_prog()
        base = run_as_flows(prog, KEY, replicas=4)
        c0 = CompileTelemetry.compiles("as_flows")
        again = run_as_flows(
            dataclasses.replace(prog, surrogate=None), KEY, replicas=4
        )
        assert CompileTelemetry.compiles("as_flows") - c0 == 0
        for k in base:
            assert np.array_equal(base[k], again[k]), k

    def test_ste_forward_bit_equal_to_legacy(self):
        """Straight-through surrogate: hard forward is BIT-equal to
        surrogate=None (the ste() correction is an exact float zero) —
        the surrogate_off fuzz-pair contract, pinned here."""
        from tpudes.parallel.as_flows import run_as_flows

        prog = _as_prog()
        base = run_as_flows(prog, KEY, replicas=4)
        ste = run_as_flows(
            dataclasses.replace(prog, surrogate=Surrogacy(ste=True)),
            KEY, replicas=4,
        )
        for k in base:
            assert np.array_equal(base[k], ste[k]), k

    def test_soft_surrogate_changes_the_forward(self):
        """ste=False really swaps the delivery gate (the flag is not
        decorative): near saturation the soft program's delivered
        fractions differ (deep in the sparse regime the soft gate's
        correction is below f32 resolution by design)."""
        from tpudes.parallel.as_flows import run_as_flows

        prog = _congested_as_prog()
        base = run_as_flows(
            dataclasses.replace(prog, surrogate=None), KEY, replicas=4
        )
        soft = run_as_flows(prog, KEY, replicas=4)
        assert not np.array_equal(
            base["delivered_frac"], soft["delivered_frac"]
        )

    def test_diff_runner_forward_bit_equal_to_engine(self):
        """The scan-based differentiable runner reproduces the
        production while-loop engine bit for bit (same fluid cores,
        fixed FP_ROUNDS), here over the links the paths use
        (F·H < 2E + 1)."""
        self._forward_equal(_as_prog(), compact=True)

    def test_diff_runner_forward_bit_equal_to_engine_over_every_link(self):
        """The same where F·H ≥ 2E + 1 (few nodes, many flows): the
        link table is 2E + 1 wide, every link in use and the sentinel."""
        self._forward_equal(
            toy_as_program(n_nodes=12, n_flows=8), compact=False
        )

    @staticmethod
    def _forward_equal(prog, compact):
        from tpudes.parallel.as_flows import (
            _as_replica_draws,
            build_as_diff,
            relax_links,
            run_as_flows,
        )
        from tpudes.parallel.runtime import bucket_replicas

        assert (relax_links(prog) < 2 * len(prog.edges) + 1) == compact
        out = run_as_flows(prog, KEY, replicas=5)
        r_pad = bucket_replicas(5, None)
        diff_run = jax.jit(build_as_diff(prog, r_pad))
        d = diff_run(
            _as_replica_draws(prog, KEY, r_pad), jnp.float32(1.0),
            jnp.asarray(prog.flow_bps, jnp.float32),
            jnp.asarray(prog.rate_bps, jnp.float32),
        )
        assert np.array_equal(
            np.asarray(d["goodput_bps"])[:5], out["goodput_bps"]
        )
        assert np.array_equal(
            np.asarray(d["delivered_frac"])[:5], out["delivered_frac"]
        )
        # utilization/delay: ≤1 ULP — lifting the capacities from a
        # baked constant to a traced operand changes how XLA strength-
        # reduces the division (documented in build_as_diff)
        np.testing.assert_allclose(
            np.asarray(d["max_util"])[:5], out["max_util"], rtol=2e-7
        )
        reach = ~out["unreachable"]
        np.testing.assert_allclose(
            np.asarray(d["delay_s"])[:5][:, reach],
            out["delay_s"][:, reach],
            rtol=2e-7,
        )

    def test_ops_level_hard_paths_unchanged(self):
        """ops/lte.py surrogate seams: surrogate=None is the identical
        legacy math, eff_from_sinr's hard staircase equals the CQI
        table gather, and the ste identity is bit-exact."""
        from tpudes.diff.surrogate import ste
        from tpudes.ops.lte import (
            _CQI_EFF,
            cqi_from_sinr,
            decode_ok,
            eff_from_sinr,
            qm_from_eff,
        )

        sinr = jnp.asarray(
            np.logspace(-2, 4, 41, dtype=np.float32)
        )
        legacy = cqi_from_sinr(sinr)
        assert np.array_equal(
            np.asarray(legacy),
            np.asarray(cqi_from_sinr(sinr, surrogate=None)),
        )
        eff_hard = np.asarray(eff_from_sinr(sinr))
        assert np.allclose(
            eff_hard, _CQI_EFF[np.asarray(legacy)], atol=1e-6
        )
        qm_hard = np.asarray(qm_from_eff(jnp.asarray(eff_hard)))
        assert set(np.unique(qm_hard)) <= {2.0, 4.0, 6.0}
        coin = jnp.asarray([0.05, 0.5, 0.95], jnp.float32)
        bler = jnp.asarray([0.1, 0.5, 0.9], jnp.float32)
        assert np.array_equal(
            np.asarray(decode_ok(coin, bler)),
            np.asarray(coin >= bler),
        )
        # ste: forward bit-exact, backward takes the soft branch
        hard = jnp.float32(3.0)
        soft = jnp.float32(2.5)
        assert float(ste(hard, soft)) == 3.0
        g = jax.grad(lambda x: ste(jnp.round(x), x * x))(jnp.float32(1.4))
        assert float(g) == pytest.approx(2.8)

    def test_surrogate_flips_compile_separate_runners(self):
        """The Surrogacy config is a cache-key component: a temperature
        flip compiles a fresh executable instead of hitting a stale
        runner."""
        from tpudes.obs.device import CompileTelemetry
        from tpudes.parallel.as_flows import run_as_flows

        prog = _as_prog(surrogate=Surrogacy(gate_temp=0.25))
        run_as_flows(prog, KEY, replicas=2)
        c0 = CompileTelemetry.compiles("as_flows")
        run_as_flows(
            dataclasses.replace(prog, surrogate=Surrogacy(gate_temp=0.5)),
            KEY, replicas=2,
        )
        assert CompileTelemetry.compiles("as_flows") - c0 == 1


# --- finite-difference checks ----------------------------------------------


def _fd_check(loss_at, v0, h, ad, rtol, atol=0.0):
    """Central-difference check of AD gradient ``ad`` at ``v0``."""
    fd = np.zeros_like(np.asarray(v0, np.float64))
    flat0 = np.asarray(v0, np.float64).ravel()
    for i in range(flat0.size):
        p, m = flat0.copy(), flat0.copy()
        p[i] += h
        m[i] -= h
        fd.ravel()[i] = (
            loss_at(p.reshape(np.shape(v0)))
            - loss_at(m.reshape(np.shape(v0)))
        ) / (2 * h)
    np.testing.assert_allclose(np.asarray(ad), fd, rtol=rtol, atol=atol)
    return fd


class TestFiniteDifference:
    def test_as_every_exposed_operand(self):
        """FD vs AD on flow_bps / cap_bps / rate_scale (the AS operand
        surface), soft surrogate so FD sees the differentiated
        forward.  flow/scale probe the sparse regime (near-linear,
        rtol 5e-3); capacity gradients only exist near saturation, so
        cap_bps probes the congested program (rtol 2e-2 — the gate is
        steeper there)."""
        sparse = _as_prog(surrogate=Surrogacy(ste=False))
        congested = _congested_as_prog()

        checks = [
            # (program, operand, h, rtol): steps sized to the
            # operand's scale
            (sparse, "flow_bps", 200.0, 5e-3),
            (congested, "cap_bps", 20000.0, 2e-2),
            (sparse, "rate_scale", 1e-3, 5e-3),
        ]
        for prog, name, h, rtol in checks:
            base = grad_as_flows(prog, KEY, 4, loss="neg_goodput")
            v0 = np.asarray(
                {
                    "flow_bps": prog.flow_bps,
                    "cap_bps": prog.rate_bps,
                    "rate_scale": 1.0,
                }[name],
                np.float64,
            )

            def loss_at(v, prog=prog, name=name):
                return grad_as_flows(
                    prog, KEY, 4, loss="neg_goodput", at={name: v}
                )["loss"]

            fd = _fd_check(
                loss_at, v0, h, base["grads"][name], rtol=rtol,
                atol=1e-10,
            )
            assert np.abs(fd).max() > 0, f"{name}: degenerate FD probe"

    def test_as_delay_and_kpi_losses_differentiate(self):
        prog = _as_prog(surrogate=Surrogacy(ste=False))
        tgt = np.full(3, 5e4, np.float32)
        for loss, kw in [("kpi_mse", {"target": tgt}), ("delay", {})]:
            r = grad_as_flows(prog, KEY, 4, loss=loss, **kw)
            g = r["grads"]["flow_bps"]
            assert np.isfinite(g).all() and np.abs(g).max() > 0, loss

    def test_lte_every_exposed_operand(self):
        """FD vs AD on tx powers, UE/eNB positions, propagation
        params, scheduler weights (the LTE operand surface).  rtol
        0.02 at per-operand steps (f32, steep staircase chain)."""
        prog, ue_pos = _lte_pos_prog()
        at = {"ue_pos": ue_pos}
        base = grad_lte_sm(
            prog, loss="neg_goodput", at=at,
            surrogate=Surrogacy(ste=False),
        )
        defaults = {
            "tx_power_dbm": np.full(2, 43.0),
            "ue_pos": ue_pos.astype(np.float64),
            "enb_pos": np.asarray(prog.enb_pos, np.float64),
            "ploss": np.array([3.2, 1.0, 46.67]),
            "sched_w": np.ones(6),
        }
        steps = {
            # (h, rtol): position probes tolerate more curvature —
            # metre-scale central differences over a chain whose soft
            # staircase bends within metres (see module note)
            "tx_power_dbm": (0.02, 0.02),
            "ue_pos": (0.5, 0.06),
            "enb_pos": (0.5, 0.06),
            "ploss": (0.002, 0.02),
            "sched_w": (0.01, 0.02),
        }
        for name, (h, rtol) in steps.items():
            def loss_at(v, name=name):
                return grad_lte_sm(
                    prog, loss="neg_goodput", at={**at, name: v},
                    surrogate=Surrogacy(ste=False),
                )["loss"]

            fd = _fd_check(
                loss_at, defaults[name], h, base["grads"][name],
                rtol=rtol, atol=3e-4,
            )
            assert np.abs(fd).max() > 0, f"{name}: degenerate FD probe"

    def test_lte_cqi_loss_differentiates_propagation(self):
        prog, ue_pos = _lte_pos_prog()
        tgt = np.linspace(4.0, 14.0, 6).astype(np.float32)
        r = grad_lte_sm(
            prog, loss="cqi_mse", target=tgt, at={"ue_pos": ue_pos},
            surrogate=Surrogacy(ste=False),
        )
        assert np.isfinite(r["grads"]["ploss"]).all()
        assert np.abs(r["grads"]["ploss"][0]) > 0


# --- batching: vmap-of-grad + the one-executable sweep ----------------------


class TestGradBatching:
    def test_as_vmap_of_grad_equals_stacked_solo(self):
        prog = _as_prog(surrogate=Surrogacy())
        cands = np.array(
            [[1e5, 1e5, 1e5], [2e5, 5e4, 1e5], [8e4, 3e5, 6e4]],
            np.float32,
        )
        batched = grad_as_flows(
            prog, KEY, 4, loss="neg_goodput",
            batch={"flow_bps": cands},
        )
        for i in range(3):
            solo = grad_as_flows(
                prog, KEY, 4, loss="neg_goodput",
                at={"flow_bps": cands[i]},
            )
            assert np.float32(solo["loss"]) == np.float32(
                batched["loss"][i]
            )
            for k in solo["grads"]:
                assert np.array_equal(
                    solo["grads"][k], batched["grads"][k][i]
                ), k

    def test_lte_vmap_of_grad_equals_stacked_solo(self):
        prog, ue_pos = _lte_pos_prog()
        at = {"ue_pos": ue_pos}
        cands = np.stack(
            [np.full(2, 40.0), np.full(2, 43.0), np.array([46.0, 38.0])]
        ).astype(np.float32)
        batched = grad_lte_sm(
            prog, loss="neg_goodput", at=at,
            batch={"tx_power_dbm": cands},
        )
        for i in range(3):
            solo = grad_lte_sm(
                prog, loss="neg_goodput",
                at={**at, "tx_power_dbm": cands[i]},
            )
            assert np.float32(solo["loss"]) == np.float32(
                batched["loss"][i]
            )
            assert np.array_equal(
                solo["grads"]["tx_power_dbm"],
                batched["grads"]["tx_power_dbm"][i],
            )

    def test_grad_sweep_is_one_launch_one_executable(self):
        """A grad-of-sweep batch: 1 device launch, 0 fresh compiles
        once warm (CompileTelemetry-pinned, the ISSUE acceptance
        row)."""
        from tpudes.obs.device import CompileTelemetry
        from tpudes.parallel.runtime import RUNTIME

        prog = _as_prog(surrogate=Surrogacy())
        scales = [0.5, 1.0, 2.0, 4.0]
        grad_as_flows(
            prog, KEY, 4, loss="neg_goodput", rate_scale=scales
        )  # warm
        l0 = RUNTIME.launches("diff_as")
        c0 = CompileTelemetry.compiles("diff_as")
        r = grad_as_flows(
            prog, KEY, 4, loss="neg_goodput", rate_scale=scales
        )
        assert RUNTIME.launches("diff_as") - l0 == 1
        assert CompileTelemetry.compiles("diff_as") - c0 == 0
        assert np.shape(r["loss"]) == (4,)
        assert r["grads"]["rate_scale"].shape == (4,)

    def test_loss_averages_requested_replicas_not_the_bucket(self):
        """Regression (review): the objective must average exactly the
        requested replicas — a 5-replica grad loss equals the engine's
        5-replica mean KPI, not the pow2 bucket's 8-row mean."""
        from tpudes.parallel.as_flows import run_as_flows

        prog = _as_prog(surrogate=Surrogacy(ste=True))
        out5 = run_as_flows(prog, KEY, replicas=5)
        want = -float(
            np.asarray(out5["goodput_bps"], np.float64)
            .mean(axis=0).sum() * 1e-6
        )
        got5 = grad_as_flows(prog, KEY, 5, loss="neg_goodput")["loss"]
        got8 = grad_as_flows(prog, KEY, 8, loss="neg_goodput")["loss"]
        assert got5 == pytest.approx(want, rel=1e-5)
        assert got5 != got8

    def test_operand_value_flips_never_recompile(self):
        """Every operand is traced: FD probes / optimizer steps reuse
        the executable (the cache key carries only program identity +
        loss + batching shape)."""
        from tpudes.obs.device import CompileTelemetry

        prog = _as_prog(surrogate=Surrogacy())
        grad_as_flows(prog, KEY, 4, loss="neg_goodput")  # warm
        c0 = CompileTelemetry.compiles("diff_as")
        for scale in (0.7, 1.3, 2.9):
            grad_as_flows(
                prog, KEY, 4, loss="neg_goodput",
                at={"flow_bps": np.asarray(prog.flow_bps) * scale},
            )
        assert CompileTelemetry.compiles("diff_as") - c0 == 0


# --- LTE expected-KPI chain vs the Monte-Carlo engine -----------------------


class TestLteForwardParity:
    def test_expected_goodput_tracks_the_engine(self):
        """The diff chain's expected per-UE goodput sits within a
        ±30 % band of the real SM engine's Monte-Carlo goodput on the
        dominant-gain toy grid (documented deviations: HARQ-IR
        retransmission gain, integer RBG quantization, the CQI-ladder
        vs MCS-ladder efficiency gap)."""
        from tpudes.diff.lte_grad import build_lte_diff, lte_default_params
        from tpudes.parallel.lte_sm import run_lte_sm

        prog = toy_lte_program(n_enb=2, n_ue=4, n_ttis=1000)
        sim_s = prog.n_ttis * 1e-3
        eng = run_lte_sm(prog, KEY)
        eng_bps = np.asarray(eng["rx_bits"], np.float64) / sim_s
        kpi = jax.jit(build_lte_diff(prog, Surrogacy(ste=True)))
        exp_bps = np.asarray(
            kpi(lte_default_params(prog))["tput_bps"], np.float64
        )
        assert eng_bps.shape == exp_bps.shape
        ratio = exp_bps / np.maximum(eng_bps, 1.0)
        assert (0.7 < ratio).all() and (ratio < 1.3).all(), ratio

    def test_gain_based_program_rejects_positional_wrt(self):
        prog = toy_lte_program(n_enb=2, n_ue=4)
        with pytest.raises(ValueError, match="positional"):
            grad_lte_sm(prog, wrt=("ue_pos",))
        # tx-power grads still work on the gain-based program
        r = grad_lte_sm(prog, loss="neg_goodput")
        assert np.isfinite(r["grads"]["tx_power_dbm"]).all()
