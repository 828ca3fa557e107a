"""ISSUE-6 gates: the fused Pallas LTE TTI kernel chain.

- **One math core, two lowerings**: ``TPUDES_PALLAS=1`` (the Pallas
  kernel, interpret-mode on CPU — the exact body Mosaic compiles on
  TPU) and ``=0`` (the plain XLA lowering) are BIT-identical for every
  scheduler id, under bucketing on and off, and across the 8-point
  config-axis scheduler sweep.
- **Flags are cache-key components**: flipping the kill switch or the
  precision mode compiles a distinct runner — never reuses a stale
  executable for different arithmetic.
- **The lowering is picked by lane count** (ISSUE 31): with
  ``TPUDES_PALLAS`` unset an unsharded launch of at most
  ``SM_KERNEL_MAX_LANES`` lanes builds the kernel step, more lanes and
  every mesh build the XLA step, for the three variants and the config
  axis; ``=0`` / ``=1`` force.  Every A/B here sets ``=1`` on its
  kernel side and asserts that both lowerings were built.
- **Mixed precision**: the bf16 mode sweeps with ≤1 compile and one
  launch (the CI multi-device smoke rides this), stays within the
  engine-level throughput budget of the f32 mode, and holds the same
  HARQ conservation laws.
- **lower_lte_sm horizon warning**: the compile-amortization boundary
  (COMPILE_AMORTIZE_TTIS) warns below the line, not at it.
"""

import dataclasses
import warnings

import jax
import numpy as np
import pytest

from tpudes.obs.device import CompileTelemetry
from tpudes.parallel.kernels_pallas import (
    build_sm_consts,
    build_sm_step_fn,
    pallas_enabled,
    pallas_switch,
    sm_init_state,
)
from tpudes.parallel import lte_sm
from tpudes.parallel.lte_sm import SM_SCHED_IDS, run_lte_sm
from tpudes.parallel.programs import toy_lte_program
from tpudes.parallel.runtime import RUNTIME

KEY = jax.random.PRNGKey(11)

OUT_KEYS = ("rx_bits", "ok", "new_tbs", "retx", "drops")


@pytest.fixture(autouse=True)
def _fresh_runtime():
    RUNTIME.clear()
    yield
    RUNTIME.clear()


def _prog(**kw):
    kw.setdefault("n_enb", 2)
    kw.setdefault("n_ue", 6)
    kw.setdefault("n_ttis", 150)
    return toy_lte_program(**kw)


def _assert_same(a, b, msg=""):
    for k in OUT_KEYS:
        np.testing.assert_array_equal(a[k], b[k], err_msg=f"{msg}: {k}")


def test_pallas_knob_default_on_and_kill_switch(monkeypatch):
    monkeypatch.delenv("TPUDES_PALLAS", raising=False)
    assert pallas_enabled() and pallas_switch() is None
    monkeypatch.setenv("TPUDES_PALLAS", " ")
    assert pallas_enabled() and pallas_switch() is None
    for off in ("0", "false", "no", "OFF"):
        monkeypatch.setenv("TPUDES_PALLAS", off)
        assert not pallas_enabled() and pallas_switch() is False
    monkeypatch.setenv("TPUDES_PALLAS", "1")
    assert pallas_enabled() and pallas_switch() is True


# --- which lowering a launch builds (ISSUE 31) --------------------------

N = lte_sm.SM_KERNEL_MAX_LANES


def test_the_kernel_keeps_a_lane_count():
    """The shapes below are written for a power of two >= 1; were the
    kernel to lose at every lane count (N = 0) it would go, with these
    tests, in a change of its own (ISSUE 31, tentpole step 5)."""
    assert N >= 1 and N & (N - 1) == 0


def _variant_prog(variant):
    prog = _prog(n_ttis=8)
    if variant == "traffic":
        from tpudes.traffic import TrafficProgram

        return dataclasses.replace(prog, traffic=TrafficProgram.cbr(
            np.zeros(prog.n_ue, np.int32), np.full(prog.n_ue, 1, np.int64)
        ))
    if variant == "mobile":
        from tpudes.scenarios import build_lena

        lte, _ = build_lena(2, 3, mobility="const_velocity", speed=10.0)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            return lte_sm.lower_lte_sm(lte, 0.008, geom_stride=2)
    return prog


def _built_step(prog, sm_lowerings_built, **launch):
    """What ``run_lte_sm(prog, KEY, **launch)`` builds, read twice:
    from the runner's cache key, and from the advance program's jaxpr
    (a ``pallas_call`` is there or not)."""
    RUNTIME.clear()
    L, _, _ = lte_sm._sm_prepare(
        prog, KEY, launch.get("replicas"), launch.get("mesh"),
        launch.get("schedulers"),
    )
    jaxpr = lte_sm._sm_call(
        L.fn.trace, L.carry, np.int32(prog.n_ttis), L.ops
    ).jaxpr
    (keyed,) = sm_lowerings_built(prog)
    assert keyed == ("pallas_call" in str(jaxpr))
    return "mosaic" if keyed else "xla"


#: launch shape -> lanes, on both sides of N along the replica axis
#: and along the config axis alone
_SHAPES = {
    "solo": (dict(), 1),
    "at_N": (dict(replicas=N), N),
    "above_N": (dict(replicas=N + 1), 2 * N),
    "cfg_at_N": (dict(schedulers=["pf"] * N), N),
    "cfg_above_N": (dict(schedulers=["pf", "rr"] * N), 2 * N),
    "cfg_x_replicas": (dict(replicas=N, schedulers=["pf", "rr"]), 2 * N),
}


@pytest.mark.parametrize("shape", sorted(_SHAPES))
@pytest.mark.parametrize("variant", ["plain", "traffic", "mobile"])
def test_unset_switch_picks_the_lowering_by_lane_count(
    monkeypatch, sm_lowerings_built, variant, shape
):
    monkeypatch.delenv("TPUDES_PALLAS", raising=False)
    launch, lanes = _SHAPES[shape]
    got = _built_step(_variant_prog(variant), sm_lowerings_built, **launch)
    assert got == ("mosaic" if lanes <= N else "xla"), (lanes, N)


@pytest.mark.parametrize("variant", ["plain", "traffic", "mobile"])
def test_every_mesh_builds_the_xla_step(
    monkeypatch, sm_lowerings_built, variant
):
    from tpudes.parallel.mesh import replica_mesh

    prog = _variant_prog(variant)
    for flag in (None, "1"):
        if flag is None:
            monkeypatch.delenv("TPUDES_PALLAS", raising=False)
        else:
            monkeypatch.setenv("TPUDES_PALLAS", flag)
        for n_dev in (1, 2):
            got = _built_step(
                prog, sm_lowerings_built, replicas=2,
                mesh=replica_mesh(n_dev),
            )
            assert got == "xla", (flag, n_dev)


@pytest.mark.parametrize("shape", ["solo", "above_N", "cfg_x_replicas"])
@pytest.mark.parametrize("variant", ["plain", "traffic", "mobile"])
def test_set_switch_forces_the_lowering_at_every_lane_count(
    monkeypatch, sm_lowerings_built, variant, shape
):
    prog = _variant_prog(variant)
    launch, _ = _SHAPES[shape]
    monkeypatch.setenv("TPUDES_PALLAS", "0")
    assert _built_step(prog, sm_lowerings_built, **launch) == "xla"
    monkeypatch.setenv("TPUDES_PALLAS", "1")
    assert _built_step(prog, sm_lowerings_built, **launch) == "mosaic"


@pytest.mark.parametrize("sched", list(SM_SCHED_IDS))
def test_interpret_mode_bit_parity_every_scheduler(
    monkeypatch, sm_lowerings_built, sched
):
    """The Pallas kernel (interpret on CPU) and the XLA fallback run the
    SAME math core: bit equality per scheduler id."""
    prog = _prog(scheduler=sched)
    monkeypatch.setenv("TPUDES_PALLAS", "1")
    on = run_lte_sm(prog, KEY)
    monkeypatch.setenv("TPUDES_PALLAS", "0")
    off = run_lte_sm(prog, KEY)
    assert sm_lowerings_built(prog) == {True, False}
    _assert_same(on, off, sched)


@pytest.mark.slow  # ISSUE-21 tier-1 budget: the multi-device CI step runs the full file
def test_step_fn_bit_parity_at_kernel_level():
    """Below the engine: one fused step, both lowerings, same state in,
    bit-identical state out (including the f32 accumulators)."""
    prog = _prog()
    consts = build_sm_consts(prog)
    s = sm_init_state(prog.n_enb, prog.n_ue)
    coin = jax.random.uniform(KEY, (prog.n_ue,))[None, :]
    sid = jax.numpy.int32(0)
    for t in range(3):
        t_j = jax.numpy.int32(t)
        s_p = build_sm_step_fn(consts, True)(s, coin, t_j, sid)
        s_x = build_sm_step_fn(consts, False)(s, coin, t_j, sid)
        for k in s_p:
            np.testing.assert_array_equal(
                np.asarray(s_p[k]), np.asarray(s_x[k]), err_msg=k
            )
        s = s_p


@pytest.mark.slow  # loads libtpu into the process; the multi-device CI step runs it
def test_kernel_compiles_for_v5e_through_mosaic(monkeypatch):
    """The non-interpret ``pallas_call`` — the branch only a TPU runs —
    lowered and compiled for a v5e by libtpu's compile-only client (no
    chip needed): the 7 x 210 program's fused step, unbatched and
    replica-vmapped, plus the dynamic-row form the mobile and traffic
    runners feed.  A primitive Mosaic cannot lower (jax 0.9.0 has no
    erf/erfc rule — the BLER tail used to call ``lax.erfc``) fails
    here, on CPU, instead of on the first chip run."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from tpudes.parallel.lte_sm import SM_DYNAMIC_ROWS

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # noqa: BLE001 - no libtpu on this box
        pytest.skip(f"no TPU compile-only client: {e}")
    sharding = SingleDeviceSharding(topo.devices[0])

    def abstract(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)

    prog = _prog(n_enb=7, n_ue=210)
    consts = build_sm_consts(prog)
    jnp = jax.numpy
    state = sm_init_state(prog.n_enb, prog.n_ue)
    coin = jnp.zeros((1, prog.n_ue), jnp.float32)
    t = sid = jnp.int32(0)
    dyn = {k: jnp.asarray(consts[k]) for k in SM_DYNAMIC_ROWS}
    # the builder picks interpret mode from the backend it runs on
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    static = build_sm_step_fn(consts, True)
    dynamic = build_sm_step_fn(consts, True, dynamic=SM_DYNAMIC_ROWS)
    batched = jax.vmap(static, in_axes=(0, 0, None, None))
    stack = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda v: jnp.broadcast_to(v, (4,) + v.shape), tree
    )
    for fn, args in (
        (static, (state, coin, t, sid)),
        (dynamic, (state, coin, t, sid, dyn)),
        (batched, (stack(state), stack(coin), t, sid)),
    ):
        compiled = (
            jax.jit(fn)
            .trace(*jax.tree_util.tree_map(abstract, args))
            .lower(lowering_platforms=("tpu",))
            .compile()
        )
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bucketing", ["1", "0"])
def test_ab_equality_under_bucketing(
    monkeypatch, sm_lowerings_built, bucketing
):
    """TPUDES_PALLAS=0 A/B equality composed with the replica-axis
    bucketing knob: 3 replicas pad to 4 (or not at all) identically in
    both kernel modes."""
    monkeypatch.setenv("TPUDES_BUCKETING", bucketing)
    prog = _prog()
    monkeypatch.setenv("TPUDES_PALLAS", "1")
    on = run_lte_sm(prog, KEY, replicas=3)
    monkeypatch.setenv("TPUDES_PALLAS", "0")
    off = run_lte_sm(prog, KEY, replicas=3)
    assert sm_lowerings_built(prog) == {True, False}
    assert on["rx_bits"].shape == (3, prog.n_ue)
    _assert_same(on, off, f"bucketing={bucketing}")


def test_ab_equality_8_point_scheduler_sweep(monkeypatch, sm_lowerings_built):
    """The config-axis megabatch (16 lanes: a batched shape the unset
    switch would send to XLA on both sides) sweeps identically through
    both lowerings — point by point, bit for bit."""
    prog = _prog()
    scheds = list(SM_SCHED_IDS)[:8]
    monkeypatch.setenv("TPUDES_PALLAS", "1")
    on = run_lte_sm(prog, KEY, replicas=2, schedulers=scheds)
    monkeypatch.setenv("TPUDES_PALLAS", "0")
    off = run_lte_sm(prog, KEY, replicas=2, schedulers=scheds)
    assert sm_lowerings_built(prog) == {True, False}
    assert len(on) == len(off) == 8
    for s, a, b in zip(scheds, on, off):
        _assert_same(a, b, s)


def test_pallas_flag_is_a_cache_key_component(monkeypatch):
    """Flipping the kill switch compiles a SECOND runner instead of
    reusing the other mode's executable (stale-arithmetic hazard)."""
    prog = _prog(n_ttis=40)
    monkeypatch.setenv("TPUDES_PALLAS", "1")
    run_lte_sm(prog, KEY)
    assert RUNTIME.size("lte_sm") == 1
    monkeypatch.setenv("TPUDES_PALLAS", "0")
    run_lte_sm(prog, KEY)
    assert RUNTIME.size("lte_sm") == 2
    # and back: a cache HIT, not a third entry
    monkeypatch.setenv("TPUDES_PALLAS", "1")
    run_lte_sm(prog, KEY)
    assert RUNTIME.size("lte_sm") == 2


def test_precision_is_a_cache_key_component():
    prog = _prog(n_ttis=40)
    run_lte_sm(prog, KEY)
    run_lte_sm(dataclasses.replace(prog, precision="bf16"), KEY)
    assert RUNTIME.size("lte_sm") == 2


def test_invalid_precision_refused():
    with pytest.raises(ValueError, match="precision"):
        run_lte_sm(dataclasses.replace(_prog(), precision="f16"), KEY)


# --- mixed precision ---------------------------------------------------


def test_bf16_sweep_one_launch_one_compile():
    """The CI mixed-precision smoke as a test: an 8-point scheduler
    sweep at bf16 is ONE launch paying at most ONE fresh compile."""
    prog = dataclasses.replace(_prog(), precision="bf16")
    c0 = CompileTelemetry.compiles("lte_sm")
    results = run_lte_sm(
        prog, KEY, replicas=2, schedulers=list(SM_SCHED_IDS)[:8]
    )
    assert RUNTIME.launches("lte_sm") == 1
    assert CompileTelemetry.compiles("lte_sm") - c0 <= 1
    assert len(results) == 8


def test_bf16_engine_outcome_within_budget():
    """Engine-level budget: bf16 rounds the SINR/metric/BLER chain but
    the aggregate served traffic stays within a few percent of f32, and
    the HARQ conservation law holds unchanged."""
    prog = _prog(n_ue=8, n_ttis=400)
    f32 = run_lte_sm(prog, KEY, replicas=4)
    bf16 = run_lte_sm(
        dataclasses.replace(prog, precision="bf16"), KEY, replicas=4
    )
    a = float(f32["rx_bits"].sum())
    b = float(bf16["rx_bits"].sum())
    assert b == pytest.approx(a, rel=0.10), (a, b)
    # conservation: decoded + dropped never exceeds transmissions
    assert (
        bf16["ok"] + bf16["drops"] <= bf16["new_tbs"] + bf16["retx"]
    ).all()


def test_bf16_and_f32_share_no_executable(monkeypatch):
    """bf16 arithmetic must be a different program in BOTH kernel
    modes (precision × pallas = 4 distinct runners)."""
    prog = _prog(n_ttis=40)
    for pallas in ("1", "0"):
        monkeypatch.setenv("TPUDES_PALLAS", pallas)
        for precision in ("f32", "bf16"):
            run_lte_sm(
                dataclasses.replace(prog, precision=precision), KEY
            )
    assert RUNTIME.size("lte_sm") == 4


# --- the lower_lte_sm compile-amortization warning ---------------------


def _helper_scenario():
    import math as _math

    from tpudes.helper.containers import NodeContainer
    from tpudes.models.lte import LteHelper
    from tpudes.models.mobility import (
        ListPositionAllocator,
        MobilityHelper,
        Vector,
    )

    lte = LteHelper()
    enbs = NodeContainer()
    enbs.Create(1)
    ues = NodeContainer()
    ues.Create(2)
    ea = ListPositionAllocator()
    ea.Add(Vector(0.0, 0.0, 30.0))
    me = MobilityHelper()
    me.SetPositionAllocator(ea)
    me.SetMobilityModel("tpudes::ConstantPositionMobilityModel")
    me.Install(enbs)
    ua = ListPositionAllocator()
    for i in range(2):
        ua.Add(Vector(50.0 * _math.cos(i), 50.0 * _math.sin(i), 1.5))
    mu = MobilityHelper()
    mu.SetPositionAllocator(ua)
    mu.SetMobilityModel("tpudes::ConstantPositionMobilityModel")
    mu.Install(ues)
    lte.InstallEnbDevice(enbs)
    devs = lte.InstallUeDevice(ues)
    ue_list = [devs.Get(i) for i in range(devs.GetN())]
    lte.Attach(ue_list)
    lte.ActivateDataRadioBearer(ue_list)
    return lte


def test_lower_warns_below_compile_amortization_horizon():
    from tpudes.parallel.lte_sm import COMPILE_AMORTIZE_TTIS, lower_lte_sm

    lte = _helper_scenario()
    with pytest.warns(UserWarning, match="one-time XLA compile"):
        lower_lte_sm(lte, (COMPILE_AMORTIZE_TTIS - 1) / 1000.0)


def test_lower_silent_at_the_boundary():
    from tpudes.parallel.lte_sm import COMPILE_AMORTIZE_TTIS, lower_lte_sm

    lte = _helper_scenario()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        prog = lower_lte_sm(lte, COMPILE_AMORTIZE_TTIS / 1000.0)
    assert prog.n_ttis == COMPILE_AMORTIZE_TTIS
