"""Device-resident LTE engine for full-buffer (RLC-SM) scenarios.

The LTE counterpart of :mod:`tpudes.parallel.replicated` (SURVEY.md §7
step 8 + hard-part 6): instead of one simulator event per TTI making a
host↔device round trip, the WHOLE multi-TTI simulation — FF-MAC
scheduling, HARQ-IR, decode draws, PF averaging, for every cell at once
— runs as one ``lax.while_loop`` on the accelerator, its horizon a
traced operand.  The loop itself is unbatched (one scalar TTI clock);
the replica axis (and a scheduler sweep's config axis) is a ``vmap``
of the per-TTI step over per-replica PRNG keys (:func:`_vmap_lanes`).

This is sound because under RLC saturation mode every buffer is always
full, so the only evolving state is scheduler/HARQ bookkeeping — pure
(U,)/(E,U) array math.  With full-buffer traffic every cell occupies its
entire RB grid every TTI, which makes the interference pattern (and
hence SINR, CQI, MCS, per-RB MI) static for a static topology: they are
precomputed once at lowering time.

The per-TTI math itself lives in
:mod:`tpudes.parallel.kernels_pallas`: one fused kernel chain (retx
admission → scheduler dispatch → MI/BLER decode → HARQ update) with a
hand-written Pallas lowering on TPU (interpret mode everywhere else)
and a plain-XLA lowering of the same math core.  A launch takes the
kernel only unsharded and at no more than :data:`SM_KERNEL_MAX_LANES`
lanes, the vectorised XLA step otherwise (:func:`_sm_use_pallas`;
``TPUDES_PALLAS=0`` / ``=1`` force either on unsharded launches).  The
optional bf16/f32 mixed-precision mode (``LteSmProgram.precision``)
and the resolved lowering are cache-key components, never traced
operands.

All NINE FF-MAC schedulers (models/lte/scheduler.py) lower: each is a
per-UE metric whose per-cell argmax drives the same one-hot allocation
algebra, so a SINGLE jitted program serves the whole family — the
scheduler id is a traced operand selecting the metric
(:data:`SM_SCHED_IDS`).  Full-buffer degeneracies, identical on the
host on the same scenario, are relied on and pinned by tests:
- TD and FD variants coincide: the greedy fill gives the first
  (best-metric) flow every RBG its infinite buffer wants, which is the
  whole grid — winner-takes-the-rest IS the frequency-domain cascade;
- TTA reduces to RR: with wideband CQI the subband/wideband rate ratio
  is identically 1 (the host class literally inherits RR);
- CQA and PSS reduce to PF: the saturation-mode controller has no
  HOL-delay or target-bit-rate state to feed them (SchedCandidate
  defaults 0), so the delay group / priority set is degenerate.

Timing-model deviations vs the host TTI loop (controller.py), all
bounded fixed offsets — tests/test_lte_sm.py pins host-vs-device
throughput parity (aggregate and per-cell) and CQI equality on an
identical lowered scenario:
- one HARQ process per UE: a UE awaiting retransmission is not
  scheduled new data during the 8 ms HARQ RTT (the host loop, like
  upstream's 8 processes, can overlap);
- CQI is applied from TTI 0 (host: 3-TTI feedback transient);
- TB sizes are kept in bits (host rounds to whole bytes);
- the last (partial) RBG counts as rbg_size RBs in the TB-size math.
"""

from __future__ import annotations

from collections.abc import Callable
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tpudes.fuzz.envelope import FuzzEnvelope
from tpudes.models.lte.scheduler import SCHEDULERS
from tpudes.obs import spans
from tpudes.parallel.kernels_pallas import (
    SM_PRECISIONS,
    SM_SCHED_IDS,
    build_sm_consts,
    build_sm_step_fn,
    pallas_switch,
    sm_init_state,
)
from tpudes.parallel.runtime import scoped_while_loop


class UnliftableLteScenarioError(ValueError):
    """The object graph cannot run on the device-resident SM engine
    (non-SM bearers, mobile nodes, unattached UEs, …)."""


#: SM_SCHED_IDS (scheduler short name → traced dispatch id) is defined
#: next to the kernel whose family boundaries derive from it
#: (tpudes/parallel/kernels_pallas.py) and re-exported here, the
#: engine's public surface.

#: host FfMacScheduler class → short name, derived from the host
#: registry so SM_SCHED_IDS stays the single device-support list (a
#: host class rename cannot silently demote a scheduler to "custom")
_SCHED_CLASS_TO_NAME = {
    cls.__name__: cls.name
    for cls in set(SCHEDULERS.values())
    if cls.name in SM_SCHED_IDS
}


#: the documented-faithful fuzz region (see :mod:`tpudes.fuzz`): lena
#: macro drops the host controller also runs (strongest-cell attach,
#: RLC-SM full buffer; static, drifting, or walking UEs over the
#: device geometry pipeline), every registered FF-MAC scheduler,
#: horizons short enough for the host TTI loop to be an affordable
#: oracle — all inside the lower_lte_sm guards
FUZZ_ENVELOPE = FuzzEnvelope(
    engine="lte_sm",
    axes={
        "n_enbs": ("int", 1, 3),
        "ues_per_cell": ("int", 2, 4),
        "scheduler": ("choice", tuple(SM_SCHED_IDS)),
        "inter_site": ("choice", (400.0, 500.0, 800.0)),
        "layout": ("choice", ("hex", "line")),
        "drop_seed": ("int", 1, 999),
        "sim_ms": ("int", 80, 320),
        "replicas": ("int", 1, 6),
        "chunk_divisor": ("choice", (2, 3)),
        "key_seed": ("int", 0, 2**16),
        # ISSUE-10 mobility draws (appended — axis order is part of
        # the seed→config contract); pedestrian..vehicular UE speeds
        "mob_model": ("choice", ("static", "const_velocity",
                                 "random_walk")),
        "mob_speed": ("float", 1.0, 30.0),
        "geom_stride": ("choice", (1, 2, 8, 32)),
        # ISSUE-14 traffic draws (appended): finite per-UE backlogs
        # from the drawn workload model; "off" keeps RLC-SM full
        # buffer.  Joint region note: a mobile draw forces "off" (the
        # engine rejects traffic+mobility on one program).
        "traffic": ("choice", ("off", "cbr", "mmpp", "onoff", "trace")),
        "tr_burst": ("float", 0.1, 0.6),
        "tr_phase": ("float", 0.0, 1.0),
    },
    floors={"replicas": 1, "n_enbs": 1, "ues_per_cell": 1, "sim_ms": 16},
    doc="lena macro grid, full-buffer RLC-SM downlink, all 9 schedulers",
)


@dataclass(frozen=True)
class LteSmProgram:
    """Static description of a full-buffer LTE downlink scenario."""

    gain: np.ndarray          # (E, U) linear DL path gain
    serving: np.ndarray       # (U,) int32
    tx_power_dbm: np.ndarray  # (E,)
    noise_psd: float
    n_rb: int
    n_ttis: int
    scheduler: str            # any key of SM_SCHED_IDS
    pf_alpha: float = 0.05
    #: arithmetic mode of the SINR/CQI/metric/BLER chain — "f32"
    #: (exact legacy math) or "bf16" (mixed precision with f32
    #: accumulators; see tpudes/parallel/kernels_pallas.py).  A cache-
    #: key component, never a traced operand: flipping it compiles a
    #: distinct executable.
    precision: str = "f32"
    #: UE motion (tpudes.ops.mobility.MobilityProgram): None = static
    #: geometry (the build-time SINR constants).  Model id + params are
    #: traced operands — only ``mobility.shape_key()`` enters the
    #: runner cache key, so a sweep across the model family reuses one
    #: executable.  With mobility the per-TTI kernel consumes DYNAMIC
    #: SINR-derived rows recomputed on device every ``geom_stride``
    #: TTIs (f32 geometry, vs the static path's f64 build-time chain —
    #: the documented precision of the moving regime).
    mobility: object = None
    #: geometry refresh stride in TTIs (traced — NOT a cache-key
    #: component); stride=1 is bit-identical to per-TTI recompute and
    #: the closed-form trajectory makes a strided run sample the SAME
    #: motion, just less often
    geom_stride: int = 1
    #: static eNB sites (E, 3) f32 — mobile programs only
    enb_pos: np.ndarray = None
    #: pure-kernel pathloss descriptor for the device geometry stage:
    #: ("friis", frequency_hz, system_loss, min_loss_db) or
    #: ("log_distance", exponent, reference_distance, reference_loss_db)
    pathloss: tuple = None
    #: device-resident workload (tpudes.traffic.TrafficProgram over the
    #: U UEs): None = RLC-SM full buffer (bit-identical compile).  With
    #: a program the engine runs FINITE per-UE backlogs: each TTI adds
    #: the workload's offered bits (trace replay: exact bytes;
    #: generative: arrivals × a bounded-Pareto size quantum, fold_in-
    #: keyed and shared across replicas like the realization itself),
    #: a UE is scheduling-eligible only while its backlog is non-empty
    #: (the kernel's dynamic ``eligible`` row — the mobility seam), and
    #: DELIVERED bits drain it.  Model id + params are traced operands;
    #: only ``traffic.shape_key()`` enters the runner cache key.  A
    #: saturating program (offered ≫ servable) is pinned bit-equal to
    #: the full-buffer path (the ``traffic_off`` fuzz pair).
    traffic: object = None

    # ISSUE-15 note — the DIFFERENTIABLE seam of this engine lives in
    # :mod:`tpudes.diff.lte_grad`: ``grad_lte_sm(prog, ...)`` consumes
    # the same program fields (gain/serving/powers, and for positional
    # gradients ``enb_pos``/``pathloss`` + the PR-10 mobility
    # operands) through the closed-form per-TTI expectation built from
    # the identical ``tpudes.ops.lte`` kernels, with a
    # :class:`tpudes.diff.Surrogacy` smoothing the staircase points.
    # The run path here stays integer-exact by construction — it IS
    # the straight-through forward — so no surrogate flag rides this
    # dataclass (nothing in the compiled program would change).

    @property
    def n_enb(self) -> int:
        return int(self.gain.shape[0])

    @property
    def n_ue(self) -> int:
        return int(self.gain.shape[1])


#: below this horizon the fused TTI scan's one-time XLA compile
#: (seconds), not the per-TTI math (tens of µs), dominates a cold run's
#: wall time — the LTE analog of lower_bss's MODELED_WARMUP_S boundary
COMPILE_AMORTIZE_TTIS = 250


def lower_lte_sm(
    helper, sim_time_s: float, precision: str = "f32",
    geom_stride: int = 1,
) -> LteSmProgram:
    """Lower a constructed LteHelper object graph (controller state) to
    a device program; raises UnliftableLteScenarioError for anything the
    full-buffer engine cannot faithfully represent.

    ``precision`` selects the arithmetic mode of the SINR/CQI/BLER
    chain ("f32" exact, "bf16" mixed precision — see
    :class:`LteSmProgram`).

    Mobile UEs lift too (``tpudes.ops.mobility``): their motion rides
    the scan as traced operands and the SINR→CQI→MCS→MI chain is
    recomputed ON DEVICE every ``geom_stride`` TTIs.  Requires a
    pure-kernel pathloss model (Friis / LogDistance), no buildings or
    directional antennas, static eNBs, and ``TPUDES_DEVICE_GEOM`` on —
    anything else keeps the loud refusal (the host controller's
    per-window refresh is the fallback path)."""
    from tpudes.models.mobility import MobilityModel

    if precision not in SM_PRECISIONS:
        raise ValueError(
            f"precision {precision!r} not in {SM_PRECISIONS}"
        )

    ctrl = helper.controller
    if not ctrl.enbs or not ctrl.ues:
        raise UnliftableLteScenarioError("no eNBs or UEs installed")
    if getattr(ctrl, "ffr_algorithm", None) is not None:
        raise UnliftableLteScenarioError(
            "an FFR algorithm restricts per-cell RBG masks; the device "
            "SM engine models full-band reuse-1 only — run the scalar "
            "engine for frequency-reuse studies"
        )
    if ctrl.handover_algorithm is not None and ctrl.x2_enabled:
        raise UnliftableLteScenarioError(
            "handover is armed (X2 + algorithm); the SM engine models a "
            "fixed serving map — a mid-run handover (possible even with "
            "static UEs attached off-best) would silently diverge"
        )
    for enb in ctrl.enbs:
        for ctx in enb.rrc.ues.values():
            if not ctx.bearers:
                raise UnliftableLteScenarioError(
                    f"UE imsi={ctx.ue_device.GetImsi()} has no bearer"
                )
            for b in ctx.bearers.values():
                if b.mode != "sm":
                    raise UnliftableLteScenarioError(
                        f"bearer lcid={b.lcid} is {b.mode!r}, not RLC-SM"
                    )
    sched_types = {type(enb.scheduler).__name__ for enb in ctrl.enbs}
    if len(sched_types) > 1:
        raise UnliftableLteScenarioError(f"mixed schedulers {sched_types}")
    sched_name = sched_types.pop()
    sched = _SCHED_CLASS_TO_NAME.get(sched_name)
    if sched is None:
        # a custom user scheduler class has arbitrary host semantics —
        # never lower it to an approximation silently (the round-2 rule)
        raise UnliftableLteScenarioError(
            f"unrecognized custom FF-MAC scheduler class {sched_name}; "
            "the device engine lowers the registered upstream family "
            "only — run the host controller for custom algorithms"
        )

    for dev in ctrl.enbs:
        mob = dev.GetNode().GetObject(MobilityModel)
        if mob is None or not mob.is_static:
            raise UnliftableLteScenarioError(
                "SM engine needs static eNB sites (mobile eNBs have no "
                "device representation)"
            )
    ue_static = all(
        (m := dev.GetNode().GetObject(MobilityModel)) is not None
        and m.is_static
        for dev in ctrl.ues
    )
    n_ttis = int(round(sim_time_s * 1000.0))
    mobility, pathloss_desc = None, None
    if not ue_static:
        mobility, pathloss_desc = _lift_lte_mobility(
            ctrl, n_ttis, geom_stride
        )
    ctrl._rebuild()
    if (ctrl._serving < 0).any():
        raise UnliftableLteScenarioError("unattached UEs present")
    if n_ttis < COMPILE_AMORTIZE_TTIS:
        import warnings

        warnings.warn(
            f"sim_time_s={sim_time_s} s ({n_ttis} TTIs) is below the "
            f"~{COMPILE_AMORTIZE_TTIS}-TTI horizon at which the fused "
            "TTI scan's one-time XLA compile stops dominating wall "
            "time; a cold run this short measures the compiler, not "
            "the engine — extend the horizon, sweep replicas/"
            "schedulers to amortize, or pre-warm the persistent compile "
            "cache",
            stacklevel=2,
        )
    alphas = {
        getattr(enb.scheduler, "alpha", None) for enb in ctrl.enbs
    } - {None}
    return LteSmProgram(
        gain=np.asarray(ctrl._gain_dl, dtype=np.float64),
        serving=np.asarray(ctrl._serving, dtype=np.int32),
        tx_power_dbm=np.array(
            [e.phy.tx_power_dbm for e in ctrl.enbs], dtype=np.float64
        ),
        noise_psd=float(ctrl._noise_dl),
        n_rb=ctrl.n_rb,
        n_ttis=n_ttis,
        scheduler=sched,
        pf_alpha=float(alphas.pop()) if alphas else 0.05,
        precision=precision,
        mobility=mobility,
        geom_stride=int(geom_stride),
        enb_pos=(
            None if mobility is None
            else ctrl._positions(ctrl.enbs).astype(np.float32)
        ),
        pathloss=pathloss_desc,
    )


def _lift_lte_mobility(ctrl, n_ttis: int, geom_stride: int):
    """The mobile half of :func:`lower_lte_sm`: guards + extraction.
    Returns ``(MobilityProgram, pathloss_descriptor)`` or raises."""
    import sys

    from tpudes.models.mobility import (
        UnliftableMobilityError,
        device_mobility_program,
    )
    from tpudes.models.propagation import (
        FriisPropagationLossModel,
        LogDistancePropagationLossModel,
    )
    from tpudes.ops.mobility import device_geom_enabled, warn_geom_stride

    if not device_geom_enabled():
        raise UnliftableLteScenarioError(
            "UEs are mobile and device-resident geometry is disabled "
            "(TPUDES_DEVICE_GEOM=0) — the host controller's per-window "
            "refresh is the fallback path"
        )
    loss = ctrl.pathloss
    if isinstance(loss, FriisPropagationLossModel):
        pathloss_desc = (
            "friis", float(loss.frequency), float(loss.system_loss),
            float(loss.min_loss),
        )
    elif isinstance(loss, LogDistancePropagationLossModel):
        pathloss_desc = (
            "log_distance", float(loss.exponent),
            float(loss.reference_distance), float(loss.reference_loss),
        )
    else:
        raise UnliftableLteScenarioError(
            f"mobile geometry needs a pure-kernel pathloss model "
            f"(Friis/LogDistance), not {type(loss).__name__}"
        )
    if getattr(loss, "GetNext", lambda: None)() is not None:
        raise UnliftableLteScenarioError(
            "chained pathloss models cannot ride the device geometry "
            "stage"
        )
    bmod = sys.modules.get("tpudes.models.buildings")
    if bmod is not None and bmod.BuildingList.GetNBuildings():
        raise UnliftableLteScenarioError(
            "buildings make the scene loss position-dependent in a way "
            "the device geometry stage does not model — run the host "
            "controller"
        )
    if any(e.phy.antenna is not None for e in ctrl.enbs):
        raise UnliftableLteScenarioError(
            "directional eNB antennas are not modeled by the device "
            "geometry stage — run the host controller"
        )
    try:
        mobility = device_mobility_program(
            [d.GetNode() for d in ctrl.ues], horizon_us=n_ttis * 1000
        )
    except UnliftableMobilityError as e:
        raise UnliftableLteScenarioError(str(e)) from e
    # the TTI clock is exactly 1 ms — the stride advisory is exact here
    warn_geom_stride("lower_lte_sm", mobility, int(geom_stride), 1e-3)
    return mobility, pathloss_desc


#: device name of the per-TTI ``fold_in`` + uniform HARQ coin draw, the
#: one part of the TTI outside the fused kernel (inside
#: ``tpudes.lte_sm.step``, see ``runtime.scoped_while_loop``)
RNG_SCOPE = "tpudes.lte_sm.rng"

#: device name of one lane's (replica's, config point's) share of a TTI
#: — what :func:`_vmap_lanes` maps; it keeps the names inside it whole
LANE_SCOPE = "tpudes.lte_sm.lane"


def build_sm_step(prog: LteSmProgram, use_pallas: bool):
    """Returns ``(consts, init_state, step_fn)`` for the per-TTI loop
    body (single replica; :func:`build_sm_advance` vmaps it over the
    lanes inside its unbatched ``while_loop``).

    The TTI math itself lives in :mod:`tpudes.parallel.kernels_pallas`
    (one math core, two lowerings — the fused Pallas kernel and the
    plain-XLA fallback); this builder only owns the loop plumbing: the
    per-TTI coin draw and the state layout.

    ``step_fn(state, (t, key), sid)`` — ``sid`` is the traced scheduler
    id (:data:`SM_SCHED_IDS`), so the compiled program is
    scheduler-agnostic: ``prog.scheduler`` only picks the value fed in.
    """
    consts_np = build_sm_consts(prog)
    fused = build_sm_step_fn(consts_np, use_pallas)
    E, U = prog.n_enb, prog.n_ue

    def init_state():
        return sm_init_state(E, U)

    def step_fn(s, xs, sid):
        t, key = xs
        # coin dtype pinned f32: ambient x64 must not widen the HARQ
        # stream (JXL002)
        with jax.named_scope(RNG_SCOPE):
            coin = jax.random.uniform(key, (U,), jnp.float32)[None, :]
        return fused(s, coin, t, sid)

    consts = dict(
        sinr=consts_np["sinr"][0], cqi=consts_np["cqi"][0],
        mcs=consts_np["mcs"][0],
    )
    return consts, init_state, step_fn


#: the const rows the geometry stage recomputes per refresh (the
#: SINR-derived per-UE tables; everything else — cell structure, RR
#: bookkeeping, the prefix operator — is attachment topology, which
#: the fixed serving map keeps static)
SM_DYNAMIC_ROWS = ("mi0", "rate0", "eff0", "ecr0", "eligible")


def _build_geom_fn(prog: LteSmProgram, consts: dict):
    """Device geometry stage for a mobile program: returns
    ``(pos_at(mob_ops, t_tti) -> (U, 3),
    rows_from_pos(pos_u) -> dict)`` — positions split out so the
    ``TPUDES_DEVICE_GEOM=0`` fallback can gather HOST-precomputed
    positions while running the identical rows math (the bit-equality
    contract of the per-window fallback path).

    The rows mirror :func:`~tpudes.parallel.kernels_pallas.build_sm_consts`
    (same CQI/MCS/MI chain, same bf16 storage-rounding policy) but in
    f32 device arithmetic — the documented precision of the moving
    regime."""
    import jax.numpy as jnp

    from tpudes.ops import propagation as P
    from tpudes.ops.lte import RB_BANDWIDTH_HZ, RE_PER_RB_DATA
    from tpudes.ops.lte import (
        _MCS_ECR,
        _MCS_EFF,
        _MCS_QM,
        cqi_from_sinr,
        mcs_from_cqi,
        mi_per_rb,
    )
    from tpudes.ops.mobility import build_position_fn
    from tpudes.parallel.kernels_pallas import _compute_dtype

    U = prog.n_ue
    dtype = _compute_dtype(prog.precision)
    enb_pos = jnp.asarray(prog.enb_pos, jnp.float32)        # (E, 3)
    cell_onehot = jnp.asarray(consts["cell_onehot"])        # (E, U)
    psd = jnp.asarray(
        10.0 ** ((prog.tx_power_dbm - 30.0) / 10.0)
        / (prog.n_rb * RB_BANDWIDTH_HZ),
        jnp.float32,
    )                                                       # (E,)
    kind, *params = prog.pathloss
    rbg_size = consts["rbg_size"]
    pos_fn = build_position_fn(prog.mobility)

    def pos_at(mob_ops, t_tti):
        return pos_fn(mob_ops, t_tti * 1000)                # TTI → µs

    def rows_from_pos(pos_u):
        d = jnp.sqrt(
            jnp.sum((enb_pos[:, None, :] - pos_u[None, :, :]) ** 2, -1)
        )                                                   # (E, U)
        if kind == "friis":
            rx_dbm = P.friis(jnp.float32(0.0), d, params[0], params[1],
                             params[2])
        else:
            rx_dbm = P.log_distance(
                jnp.float32(0.0), d, exponent=params[0],
                reference_distance=params[1], reference_loss_db=params[2],
            )
        gain = P.db_to_ratio(rx_dbm)                        # (E, U)
        seen = psd[:, None] * gain
        total = jnp.sum(seen, axis=0)                       # (U,)
        sig = jnp.sum(cell_onehot * seen, axis=0)           # (U,)
        sinr = sig / (total - sig + jnp.float32(prog.noise_psd))
        # storage rounding: same policy as build_sm_consts
        sinr = sinr.astype(dtype).astype(jnp.float32)
        cqi = cqi_from_sinr(sinr, dtype=dtype)
        mcs = mcs_from_cqi(cqi)
        qm = jnp.asarray(_MCS_QM)[mcs]
        mi0 = mi_per_rb(sinr, qm, dtype=dtype)
        eff0 = jnp.asarray(_MCS_EFF)[mcs]
        ecr0 = jnp.asarray(_MCS_ECR)[mcs]
        rate0 = jnp.floor(eff0 * rbg_size * RE_PER_RB_DATA) * 1000.0
        row = lambda a: jnp.reshape(a, (1, U))  # noqa: E731
        return dict(
            mi0=row(mi0.astype(jnp.float32)),
            rate0=row(rate0.astype(jnp.float32)),
            eff0=row(eff0.astype(jnp.float32)),
            ecr0=row(ecr0.astype(jnp.float32)),
            eligible=row((cqi >= 1).astype(jnp.int32)),
            sinr=row(sinr), cqi=row(cqi.astype(jnp.int32)),
            mcs=row(mcs.astype(jnp.int32)),
        )

    def init_rows():
        z = lambda dt: jnp.zeros((1, U), dt)  # noqa: E731
        return dict(
            mi0=z(jnp.float32), rate0=z(jnp.float32), eff0=z(jnp.float32),
            ecr0=z(jnp.float32), eligible=z(jnp.int32), sinr=z(jnp.float32),
            cqi=z(jnp.int32), mcs=z(jnp.int32),
            refreshes=jnp.int32(0),
        )

    return pos_at, rows_from_pos, init_rows


def _sm_cache_key(prog: LteSmProgram, replicas, n_cfg, obs, use_pallas) -> tuple:
    # prog.scheduler AND prog.n_ttis are deliberately ABSENT: the
    # scheduler id and the TTI horizon are both traced operands, so one
    # compiled program serves all nine schedulers at every horizon — a
    # scheduler×horizon sweep pays one compile, not one per point.
    # Likewise prog.geom_stride and every mobility PARAMETER (only the
    # mobility shape key + the pathloss branch are trace-time).
    # prog.precision and the resolved lowering (_sm_use_pallas) ARE
    # present: they select different executables — flipping
    # TPUDES_PALLAS mid-process must not hit a stale runner.
    return (
        prog.gain.tobytes(), prog.serving.tobytes(),
        prog.tx_power_dbm.tobytes(), prog.noise_psd, prog.n_rb,
        prog.pf_alpha, prog.precision, use_pallas, replicas, n_cfg, obs,
        None if prog.mobility is None else prog.mobility.shape_key(),
        None if prog.enb_pos is None else prog.enb_pos.tobytes(),
        prog.pathloss,
        # workload SHAPE only — model id + params are traced operands
        None if prog.traffic is None else prog.traffic.shape_key(),
    )


#: carry layout of the base advance, part of its checkpoint fingerprint:
#: ``(t, s)`` with ONE scalar clock for all lanes.  A file written
#: while the clock was stacked per lane (no tag in its fingerprint) is
#: refused as a different study, not loaded into this carry.
_SM_CARRY_LAYOUT = "scalar-clock"

#: the state-dict keys fetched back to the host at run end
_SM_FETCH = ("rx_lo", "rx_hi", "new_tbs", "retx", "drops", "ok_cnt")


def _sm_fetch_obs() -> tuple:
    from tpudes.obs.flowmon import FM_KEYS

    return FM_KEYS


def _sm_unpack(host: dict, consts_np: dict, replicas) -> dict:
    """Host-side result assembly for ONE config point (already
    device_get; drops the kernel's (1, U) row axis, slices the replica
    padding, rebuilds the 52-bit rx counter).  FlowMonitor columns
    (``fm_*``, present under TpudesObs) land in a ``flow`` sub-dict."""
    result = {}
    for k, v in host.items():
        v = np.asarray(v)
        if k in ("fm_hist", "fm_ring"):
            # (…, 1, U, BINS) / (…, 1, CAP, 5): only the kernel row
            # axis drops — the trailing two axes are payload
            result[k] = np.squeeze(v, axis=-3)
        else:
            result[k] = v.reshape(v.shape[:-2] + v.shape[-1:])
    if replicas is not None and result["rx_lo"].shape[0] != replicas:
        result = {k: v[:replicas] for k, v in result.items()}
    fm = {k: result.pop(k) for k in list(result) if k.startswith("fm_")}
    if fm:
        result["flow"] = fm
    result["rx_bits"] = (
        result.pop("rx_hi").astype(np.int64) << 20
    ) + result.pop("rx_lo").astype(np.int64)
    result["ok"] = result.pop("ok_cnt")
    result.update(consts_np)
    return result


def lte_sm_study(prog: LteSmProgram, key, replicas=None, mesh=None):
    """Serving-layer study descriptor (see :mod:`tpudes.serving`): the
    scheduler is the traced sweep operand, so two full-buffer studies
    coalesce onto one (C, R, …) launch whenever their static program
    fields, horizon, key, replica count and mesh all match — only the
    FF-MAC scheduler may differ."""
    import dataclasses

    from tpudes.serving.descriptor import StudyDescriptor, mesh_fingerprint

    ck = (
        prog.gain.tobytes(), prog.serving.tobytes(),
        prog.tx_power_dbm.tobytes(), prog.noise_psd, prog.n_rb,
        prog.pf_alpha, prog.precision, prog.n_ttis,
        np.asarray(key).tobytes(), replicas, mesh_fingerprint(mesh),
        # mobility/traffic params are traced but must still separate
        # coalesce groups (only the scheduler id may differ per point)
        None if prog.mobility is None else prog.mobility.param_key(),
        int(prog.geom_stride),
        None if prog.traffic is None else prog.traffic.param_key(),
    )

    def launch(points, block=False):
        # a single point rides the PLAIN entry so it shares the common
        # non-sweep executable with every non-serving caller
        if len(points) == 1:
            return run_lte_sm(
                dataclasses.replace(prog, scheduler=points[0]), key,
                replicas=replicas, mesh=mesh, block=block,
            )
        return run_lte_sm(
            prog, key, replicas=replicas, mesh=mesh,
            schedulers=list(points), block=block,
        )

    def warm(n_points):
        # the horizon is a traced operand: a 1-TTI run compiles the
        # exact executable every real horizon reuses
        tiny = dataclasses.replace(prog, n_ttis=1)
        if n_points == 1:
            run_lte_sm(tiny, key, replicas=replicas, mesh=mesh)
        else:
            run_lte_sm(
                tiny, key, replicas=replicas, mesh=mesh,
                schedulers=[prog.scheduler] * n_points,
            )

    spec = None if mesh is not None else dict(
        engine="lte_sm", prog=prog, key=np.asarray(key), replicas=replicas,
    )
    return StudyDescriptor(
        "lte_sm", ck, prog.scheduler, launch, warm, spec=spec
    )


def _vmap_lanes(one, r_pad: int | None, n_cfg: int | None):
    """``one(s_r, k_r, sid_s)``, one lane's work for one TTI, lifted
    to the stacked ``(n_cfg,) (r_pad,)`` state: the replica axis maps
    state and key (one scheduler id for all), the config axis maps
    state and scheduler id (one key array for all); ``None`` leaves an
    axis out.  The three advance builders vmap ONLY this — the TTI
    ``while_loop`` around it stays unbatched (scalar clock, scalar
    predicate): ``vmap`` of a ``while_loop`` whose predicate is
    per-replica selects every carry leaf by it and reduces it with
    ``any`` each iteration (on a mesh, an all-reduce per TTI), for
    replicas that all share one ``t_end``.

    The lane traces under :data:`LANE_SCOPE`: jax's name stack wraps
    the first scope after a ``vmap`` (``vmap(<scope>)``), and that
    scope is the sacrifice — without it the kernel's event name reads
    ``vmap_tpudes_lte_sm_tti_`` and the coin draw's scope
    ``vmap(tpudes.lte_sm.rng)``."""
    def lane(s_r, k_r, sid_s):
        with jax.named_scope(LANE_SCOPE):
            return one(s_r, k_r, sid_s)

    step = lane if r_pad is None else jax.vmap(lane, in_axes=(0, 0, None))
    if n_cfg is not None:
        step = jax.vmap(step, in_axes=(0, None, 0))
    return step


def build_sm_advance(prog: LteSmProgram, r_pad: int | None = None,
                     n_cfg: int | None = None, obs: bool = False,
                     use_pallas: bool = False):
    """``(consts, init_state, fn)`` with ``fn(carry, keys, sid, t_end)``
    the UNJITTED advance exactly as :func:`run_lte_sm` jits it —
    factored out so the trace manifest (:func:`trace_manifest`)
    abstractly traces the same program the runner cache compiles.

    ``carry = (t, s)``: ``t`` the scalar ``int32`` TTI clock every lane
    shares, ``s`` the state dict stacked on ``(n_cfg,) (r_pad,)``;
    ``keys`` the whole ``(r_pad, 2)`` replica key array.  The TTI
    ``while_loop`` runs unbatched and :func:`_vmap_lanes` maps the
    per-TTI step (and, under ``obs``, the per-chunk summary) over the
    lanes — the same shape as :func:`build_sm_mobile_advance` and
    :func:`build_sm_traffic_advance`."""
    consts, init_state, step_fn = build_sm_step(prog, use_pallas)
    if obs:
        from tpudes.obs.flowmon import (
            VERDICT_RX,
            VERDICT_TX,
            flow_accumulate,
            flow_carry,
            flow_ring_write,
        )

        U = prog.n_ue
        base_init = init_state

        def init_state():  # noqa: F811 — obs variant shadows on purpose
            return dict(base_init(), **flow_carry(U, lead=(1,)))

    def advance(carry, keys, sid, t_end):
        def body(c):
            t, s = c

            # per-TTI key = fold_in(k, t): a pure function of (k, t),
            # so the traced horizon needs no key-array shape at all —
            # one executable serves every n_ttis (split(k, n_ttis)
            # would bake the horizon into the program), and a chunked
            # run re-entering at t>0 draws the same per-TTI streams
            def one(s_r, k_r, sid_s):
                with jax.named_scope(RNG_SCOPE):
                    kt = jax.random.fold_in(k_r, t)
                if not obs:
                    return step_fn(s_r, (t, kt), sid_s)
                # the fused TTI core builds exact-key state dicts, so
                # the FlowMonitor columns ride AROUND it: split them
                # off the carry, diff the cumulative counters across
                # the TTI, and merge them back (flow = UE; one
                # observation per TTI)
                fm = {kk: v for kk, v in s_r.items()
                      if kk.startswith("fm_")}
                core = {kk: v for kk, v in s_r.items()
                        if not kk.startswith("fm_")}
                s2 = step_fn(core, (t, kt), sid_s)
                d_ok = s2["ok_cnt"] - core["ok_cnt"]            # (1, U)
                d_tx = (
                    (s2["new_tbs"] - core["new_tbs"])
                    + (s2["retx"] - core["retx"])
                )
                d_drop = s2["drops"] - core["drops"]
                # acked bits this TTI, split-counter diff (bits far
                # below 2^31 per TTI, so plain i32 arithmetic is exact)
                d_bytes = (
                    ((s2["rx_hi"] - core["rx_hi"]) << jnp.int32(20))
                    + (s2["rx_lo"] - core["rx_lo"])
                ) // jnp.int32(8)
                tti_s = jnp.float32(1e-3)
                fm = flow_accumulate(
                    fm,
                    t_s=t.astype(jnp.float32) * tti_s,
                    tx=d_tx,
                    # bytes are metered at ACK (the rx counters are the
                    # only byte stream the TTI core keeps) — documented
                    # coarsening: tx_bytes counts acknowledged bytes
                    tx_bytes=d_bytes,
                    rx=d_ok,
                    rx_bytes=d_bytes,
                    # MAC-to-ACK latency is one TTI by construction in
                    # the sub-band model — delay is exact, jitter zero
                    delay_s=jnp.full((1, U), tti_s, jnp.float32),
                    lost=d_drop,
                    bin_width_s=1e-3,
                )
                got = jnp.sum(d_ok) > 0
                sent = jnp.sum(d_tx) > 0
                ev_flow = jnp.where(
                    got, jnp.argmax(d_ok[0]), jnp.argmax(d_tx[0])
                ).astype(jnp.int32)
                oh = (jnp.arange(U, dtype=jnp.int32) == ev_flow)
                ev_bytes = jnp.sum(
                    d_bytes[0] * oh.astype(jnp.int32), dtype=jnp.int32
                )
                row = jnp.stack([
                    jnp.where(got | sent, t, jnp.int32(-1)),
                    t * jnp.int32(1000),
                    ev_flow,
                    ev_bytes,
                    jnp.where(
                        got, jnp.int32(VERDICT_RX), jnp.int32(VERDICT_TX)
                    ),
                ])
                fm["fm_ring"] = flow_ring_write(
                    fm["fm_ring"], t, row[None, :]
                )
                return dict(s2, **fm)

            return t + 1, _vmap_lanes(one, r_pad, n_cfg)(s, keys, sid)

        t, s = scoped_while_loop(
            "lte_sm", lambda c: c[0] < t_end, body, carry
        )
        if not obs:
            return (t, s), {}

        # small per-chunk summaries, one per lane (fresh buffers, NOT
        # aliased to the carry — the next chunk donates the carry
        # away); only under TpudesObs, so a disabled run compiles the
        # exact pre-obs program
        def summary(s_r, _k_r, _sid_s):
            return dict(
                ok=jnp.sum(s_r["ok_cnt"]), drops=jnp.sum(s_r["drops"]),
                retx=jnp.sum(s_r["retx"]),
                # lax.rev is a real op XLA cannot fold into an alias of
                # the donated carry; the decoder sorts by step, so the
                # flipped order never needs undoing
                fm_ring=jnp.flip(s_r["fm_ring"], axis=-2),
            )

        return (t, s), _vmap_lanes(summary, r_pad, n_cfg)(s, keys, sid)

    return consts, init_state, advance


def build_sm_mobile_advance(prog: LteSmProgram, r_pad: int | None = None,
                            n_cfg: int | None = None, obs: bool = False,
                            use_pallas: bool = False):
    """``(init_carry, fn)`` with
    ``fn(carry, keys, sid, t_end, mob_ops, stride_, pos_table)`` the
    UNJITTED mobile-geometry advance exactly as :func:`run_lte_sm`
    jits it for a program with ``prog.mobility``.

    Structure as in all three builders (:func:`_vmap_lanes`): the TTI
    ``while_loop`` runs UNBATCHED (scalar clock + the geometry row dict
    in the carry) and only the per-lane step is vmapped over the
    replica / config axes inside the body — the trajectory is shared
    by every replica and config point, so the
    geometry ``lax.cond`` keeps a SCALAR predicate and the refresh
    really is skipped on non-stride TTIs (a batched predicate would
    degrade to select-both-branches under vmap and the stride would
    save nothing)."""
    consts_np = build_sm_consts(prog)
    fused = build_sm_step_fn(
        consts_np, use_pallas, dynamic=SM_DYNAMIC_ROWS
    )
    pos_at, rows_from_pos, init_rows = _build_geom_fn(prog, consts_np)
    E, U = prog.n_enb, prog.n_ue

    def advance(carry, keys, sid, t_end, mob_ops, stride_, pos_table):
        def body(c):
            t, g, s = c

            def refresh(_):
                pos = (
                    pos_at(mob_ops, t) if pos_table is None
                    else pos_table[t // stride_]
                )
                return dict(
                    rows_from_pos(pos),
                    refreshes=g["refreshes"] + 1,
                )

            g2 = jax.lax.cond(
                t % stride_ == 0, refresh, lambda _: g, None
            )
            dyn = {k: g2[k] for k in SM_DYNAMIC_ROWS}

            def one(s_r, k_r, sid_s):
                with jax.named_scope(RNG_SCOPE):
                    coin = jax.random.uniform(
                        jax.random.fold_in(k_r, t), (U,), jnp.float32
                    )[None, :]
                return fused(s_r, coin, t, sid_s, dyn)

            s2 = _vmap_lanes(one, r_pad, n_cfg)(s, keys, sid)
            return t + 1, g2, s2

        t, g, s = scoped_while_loop(
            "lte_sm", lambda c: c[0] < t_end, body, carry
        )
        metrics = (
            dict(
                ok=jnp.sum(s["ok_cnt"]), drops=jnp.sum(s["drops"]),
                retx=jnp.sum(s["retx"]),
            )
            if obs
            else {}
        )
        return (t, g, s), metrics

    def init_carry():
        return (jnp.int32(0), init_rows(), sm_init_state(E, U))

    return init_carry, advance


def build_sm_traffic_advance(prog: LteSmProgram, r_pad: int | None = None,
                             n_cfg: int | None = None, obs: bool = False,
                             use_pallas: bool = False):
    """``(init_carry, fn)`` with ``fn(carry, keys, sid, t_end, tr)``
    the UNJITTED finite-backlog advance exactly as :func:`run_lte_sm`
    jits it for a program with ``prog.traffic``.

    Structure as in all three builders (:func:`_vmap_lanes`): the TTI
    ``while_loop`` runs UNBATCHED and only the per-lane step is vmapped
    over the replica/config axes — the workload realization (like the
    mobility trajectory) is shared by every replica and config point,
    so the per-TTI offered-bits fill is computed ONCE per TTI.  The
    per-UE backlog rides the state dict (``_tr_backlog``, bits, f32)
    inside the vmapped unit: it drains by each replica's own DELIVERED
    bits (the rx counter delta — RLC-UM-style accounting: a TB leaves
    the buffer when it decodes, and a TB grant larger than the backlog
    still decodes whole, the documented TB-quantization deviation),
    and gates the kernel's dynamic ``eligible`` row."""
    import jax.numpy as jnp

    from tpudes.traffic.device import build_bits_fn

    consts_np = build_sm_consts(prog)
    fused = build_sm_step_fn(consts_np, use_pallas, dynamic=("eligible",))
    bits_fn = build_bits_fn(prog.traffic)
    E, U = prog.n_enb, prog.n_ue
    elig0 = jnp.asarray(consts_np["eligible"])            # (1, U) i32

    def advance(carry, keys, sid, t_end, tr, tr_key):
        def body(c):
            t, s = c
            # this TTI's offered bits — pure in (tr_key, entity, t),
            # shared by every replica/config lane (ONE evaluation)
            arr = jnp.reshape(
                bits_fn(tr, tr_key, t * 1000, (t + 1) * 1000), (1, U)
            )

            def one(s_r, k_r, sid_s):
                bl = jnp.minimum(
                    s_r["_tr_backlog"] + arr, jnp.float32(2**30)
                )
                core = {
                    k: v for k, v in s_r.items()
                    if not k.startswith("_tr_")
                }
                dyn = {
                    "eligible": elig0
                    * (bl > 0.0).astype(elig0.dtype)
                }
                prev_lo, prev_hi = core["rx_lo"], core["rx_hi"]
                with jax.named_scope(RNG_SCOPE):
                    coin = jax.random.uniform(
                        jax.random.fold_in(k_r, t), (U,), jnp.float32
                    )[None, :]
                s2 = fused(core, coin, t, sid_s, dyn)
                served = (
                    (s2["rx_hi"] - prev_hi).astype(jnp.float32)
                    * jnp.float32(2**20)
                    + (s2["rx_lo"] - prev_lo).astype(jnp.float32)
                )
                # a delivered TB larger than the backlog is padding
                # (the TB-quantization deviation): only real SDU bits
                # drain, and only they count as workload goodput.  The
                # goodput counter uses the engine's rx_lo/rx_hi split
                # (20-bit carry) so it stays EXACT past the ~2^24-bit
                # f32 integer ceiling on long horizons.
                drain = jnp.minimum(served, bl)
                lo = s_r["_tr_drained_lo"] + jnp.round(drain).astype(
                    jnp.int32
                )
                return dict(
                    s2,
                    _tr_backlog=bl - drain,
                    _tr_drained_lo=lo % jnp.int32(2**20),
                    _tr_drained_hi=s_r["_tr_drained_hi"]
                    + lo // jnp.int32(2**20),
                )

            s2 = _vmap_lanes(one, r_pad, n_cfg)(s, keys, sid)
            return t + 1, s2

        t, s = scoped_while_loop(
            "lte_sm", lambda c: c[0] < t_end, body, carry
        )
        metrics = (
            dict(
                ok=jnp.sum(s["ok_cnt"]), drops=jnp.sum(s["drops"]),
                retx=jnp.sum(s["retx"]),
            )
            if obs
            else {}
        )
        return (t, s), metrics

    def init_carry():
        s = sm_init_state(E, U)
        s["_tr_backlog"] = jnp.zeros((1, U), jnp.float32)
        s["_tr_drained_lo"] = jnp.zeros((1, U), jnp.int32)
        s["_tr_drained_hi"] = jnp.zeros((1, U), jnp.int32)
        return (jnp.int32(0), s)

    return init_carry, advance


#: the largest lane count (``(r_pad or 1) * (n_cfg or 1)``: what
#: :func:`_vmap_lanes` maps the step over) at which the Mosaic kernel
#: is still no slower than the vectorised XLA step: only the unbatched
#: launch.  Warm 7 x 210 launches of 10 000 TTIs on one TPU v5e, us a
#: TTI, kernel / XLA (``tools/lte_lane_sweep.py``, PERF.md section 6,
#: PR 31): 1 lane 7.20 / 10.11, 2 lanes 6.95 / 5.55, 4: 9.27 / 5.78,
#: 8: 12.99 / 5.03, 16: 20.35 / 4.82, 32: 34.21 / 5.22, 64: 62.75 /
#: 6.19, 256: 219.17 / 10.11 (the kernel adds 0.85 us a lane).
SM_KERNEL_MAX_LANES = 1


def _sm_use_pallas(mesh, lanes: int) -> bool:
    """Which TTI-step lowering a launch takes: True the Mosaic kernel,
    False the plain-XLA lowering of the same math core.

    Under a mesh always XLA, on every backend: GSPMD cannot partition
    a Mosaic call ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map" — the TPU
    compiler's words for the replica-sharded program), and interpret-
    mode pallas runs the kernel interpreter per shard.  Unsharded,
    ``TPUDES_PALLAS`` decides where it is set (``0``: XLA, anything
    else: the kernel) and the lane count where it is not: ``vmap``
    turns the lanes into a SEQUENTIAL grid of the kernel (one
    ``(1, U)`` row a step), while the XLA step puts them on the vector
    unit, so the kernel only wins up to
    :data:`SM_KERNEL_MAX_LANES`."""
    if mesh is not None:
        return False
    wish = pallas_switch()
    return lanes <= SM_KERNEL_MAX_LANES if wish is None else wish


class _SmVariant(NamedTuple):
    """What differs between the three forms of one LTE launch (plain,
    finite-backlog, mobile geometry); :func:`_sm_prepare` and
    :func:`run_lte_sm` hold what they share.  The state dict is the
    LAST part of every form's carry."""

    #: joins the runner's cache key
    tag: tuple
    #: ``(r_pad=, n_cfg=, obs=, use_pallas=)`` -> ``(init_carry,
    #: advance, aux)``: the builder's un-jitted ``(t0, *shared, s)``
    #: and advance, and the host constants the unpack wants
    build: Callable
    #: the advance program's arguments after ``t_end``, given what
    #: ``init_extra(key)`` made inside the init program
    operands: Callable = lambda extra: ()
    init_extra: Callable = lambda key: ()
    #: ``(carry, obs)`` -> the leaves that go to the host
    fetch: Callable = lambda carry, obs: {
        k: carry[-1][k]
        for k in _SM_FETCH + (_sm_fetch_obs() if obs else ())
    }
    #: fetched names without a config axis
    shared: tuple = ()
    #: ``(aux, want)`` -> one config point's unpack
    unpack: Callable = lambda aux, want: (
        lambda host: _sm_unpack(host, aux, want)
    )
    #: once-per-launch telemetry, ``(host, points)``
    once: Callable | None = None
    #: ``(sids)`` -> what a checkpoint's fingerprint holds beside
    #: :func:`_sm_cache_key`
    identity: Callable = lambda sids: (tuple(sids), _SM_CARRY_LAYOUT)


def _sm_host_consts(consts: dict) -> dict:
    return {k: np.asarray(consts[k]) for k in ("cqi", "mcs", "sinr")}


def _sm_plain(prog: LteSmProgram) -> _SmVariant:
    def build(**kw):
        consts, init_state, fn = build_sm_advance(prog, **kw)
        # the clock is one scalar for every lane (replicated on a
        # mesh); only the state is stacked and sharded
        return (
            lambda: (jnp.int32(0), init_state()), fn,
            _sm_host_consts(consts),
        )

    return _SmVariant((), build)


def _sm_traffic(prog: LteSmProgram) -> _SmVariant:
    """The finite-backlog form: results gain per-UE ``backlog_bits`` /
    ``goodput_bits`` / ``offered_bits``.  One executable serves the
    whole workload family AND all nine schedulers at every horizon —
    model id, traffic params, scheduler id and TTI bound are all
    traced operands."""
    from tpudes.obs.traffic import TrafficTelemetry
    from tpudes.traffic.device import TRAFFIC_KEY_TAG
    from tpudes.traffic.host import offered_bits_mean

    traffic = prog.traffic
    # the workload's mean offered bits per UE over the horizon — the
    # host mirror of the device fill (size quantization differs per
    # TTI draw; this is its expectation), for telemetry + results
    offered = offered_bits_mean(traffic, prog.n_ttis * 1000)
    tr_leaves = ("_tr_backlog", "_tr_drained_lo", "_tr_drained_hi")

    def build(**kw):
        init_carry, fn = build_sm_traffic_advance(prog, **kw)
        consts = build_sm_consts(prog)
        return init_carry, fn, _sm_host_consts(
            {k: consts[k][0] for k in ("cqi", "mcs", "sinr")}
        )

    def unpack(aux, want):
        def row(v):
            a = np.asarray(v)
            a = a.reshape(a.shape[:-2] + a.shape[-1:])
            return a[:want] if want is not None and a.shape[0] != want \
                else a

        def unpack_one(host):
            host = dict(host)
            backlog = row(host.pop("_tr_backlog"))
            drained = (
                row(host.pop("_tr_drained_hi")).astype(np.int64) << 20
            ) + row(host.pop("_tr_drained_lo")).astype(np.int64)
            out = _sm_unpack(host, aux, want)
            out["backlog_bits"] = backlog
            out["goodput_bits"] = drained
            out["offered_bits"] = offered
            return out

        return unpack_one

    def record_traffic(host, points):
        # burst duty (mean ON share) only means anything for onoff
        duty = (
            float(
                np.clip(
                    traffic.rate_pps.sum()
                    / max(float(traffic.peak_pps.sum()), 1e-9),
                    0.0, 1.0,
                )
            )
            if traffic.model == "onoff"
            else None
        )
        goodput = [np.asarray(p["goodput_bits"], np.float64) for p in points]
        lanes = sum(g.size for g in goodput) // prog.n_ue
        TrafficTelemetry.record(
            "lte_sm", traffic.model,
            offered=float(offered.sum()) * lanes,
            delivered=float(sum(g.sum() for g in goodput)), duty=duty,
        )

    return _SmVariant(
        ("traffic",), build,
        operands=lambda extra: (traffic.operands(), *extra),
        init_extra=lambda key: (jax.random.fold_in(key, TRAFFIC_KEY_TAG),),
        fetch=lambda carry, obs: {
            k: carry[-1][k] for k in _SM_FETCH + tr_leaves
        },
        unpack=unpack,
        once=record_traffic,
        identity=lambda sids: (
            "traffic", traffic.param_key(), tuple(sids)
        ),
    )


def _sm_mobile(prog: LteSmProgram) -> _SmVariant:
    """The mobile-geometry form (:func:`build_sm_mobile_advance`):
    results gain ``geom_refreshes`` / ``geom_stride``.

    ``TPUDES_DEVICE_GEOM=0`` takes the per-window fallback: refresh
    POSITIONS are precomputed on the host (one tiny device call per
    refresh time through the same closed-form kernel) and shipped as a
    ``(K_ref, U, 3)`` operand the loop gathers — the per-window
    fresh-operands shape of the host controller path — while the rows
    math stays the identical in-step code, so the two modes are pinned
    bit-equal."""
    from tpudes.obs.geometry import GeomTelemetry
    from tpudes.ops.mobility import device_geom_enabled

    stride = max(1, int(prog.geom_stride))
    dg_on = device_geom_enabled()
    # fallback mode: the refresh-time grid is a SHAPE (K_ref rows)
    k_ref = None if dg_on else -(-prog.n_ttis // stride)
    shared = ("_geom_sinr", "_geom_cqi", "_geom_mcs", "_geom_refreshes")

    def operands(extra):
        pos_table = None
        if k_ref is not None:
            from tpudes.ops.mobility import trajectory_positions

            pos_table = jnp.asarray(
                trajectory_positions(
                    prog.mobility,
                    [t * 1000 for t in range(0, prog.n_ttis, stride)],
                ),
                jnp.float32,
            )
        return prog.mobility.operands(), np.int32(stride), pos_table

    def fetch(carry, obs):
        _, g_fin, s_fin = carry
        return dict(
            {k: s_fin[k] for k in _SM_FETCH},
            **{f"_geom_{k}": g_fin[k]
               for k in ("sinr", "cqi", "mcs", "refreshes")},
        )

    def unpack(aux, want):
        def unpack_one(host):
            host = dict(host)
            consts_np = {
                k: np.asarray(host.pop(f"_geom_{k}"))[0]
                for k in ("sinr", "cqi", "mcs")
            }
            refreshes = int(host.pop("_geom_refreshes"))
            out = _sm_unpack(host, consts_np, want)
            out["geom_refreshes"] = refreshes
            out["geom_stride"] = stride
            return out

        return unpack_one

    return _SmVariant(
        ("mobile", dg_on, k_ref),
        lambda **kw: (*build_sm_mobile_advance(prog, **kw), None),
        operands=operands,
        fetch=fetch,
        shared=shared,
        unpack=unpack,
        # the geometry loop is shared by every config point (its rows
        # ride `shared`)
        once=lambda host, points: GeomTelemetry.record_device(
            "lte_sm", int(host["_geom_refreshes"]), prog.n_ttis
        ),
        identity=lambda sids: (
            "mobile", dg_on, k_ref, stride, tuple(sids)
        ),
    )


def _sm_prepare(prog: LteSmProgram, key, replicas, mesh, schedulers):
    """The prepared :class:`~tpudes.parallel.runtime.Launch` of
    ``run_lte_sm(prog, key, replicas, mesh, schedulers=...)``, its
    variant and the scheduler ids: the cached runner plus the exact
    carry and operands it is called with, shared by :func:`run_lte_sm`
    and :func:`compiled_step_lowering` so the inspector reads the very
    executable a run dispatches."""
    from tpudes.parallel.runtime import Launch, replica_keys, stack_axis

    if prog.traffic is not None:
        if prog.mobility is not None:
            raise UnliftableLteScenarioError(
                "traffic + mobility cannot yet ride one LTE program; "
                "run one axis on device and the other on the host "
                "controller"
            )
        v = _sm_traffic(prog)
    else:
        v = _sm_plain(prog) if prog.mobility is None else _sm_mobile(prog)
    n_cfg = None if schedulers is None else len(schedulers)
    L = Launch("lte_sm", key, replicas, mesh, n_cfg)
    r_pad = L.r_pad
    lanes = (r_pad or 1) * (n_cfg or 1)
    use_pallas = _sm_use_pallas(mesh, lanes)
    launch = spans.current()
    if launch is not None and launch.name == "launch":
        # the wish; :func:`compiled_step_lowering` is the read-back
        launch.args.update(
            step_lowering="mosaic" if use_pallas else "xla", lanes=lanes
        )

    def build():
        init_carry, fn, aux = v.build(
            r_pad=r_pad, n_cfg=n_cfg, obs=L.obs, use_pallas=use_pallas
        )

        def parts(key):
            # ``keys`` the (r_pad, 2) ``fold_in(key, i)`` rows (``key``
            # itself without a replica axis); what every lane shares
            # (the clock, the mobile form's geometry rows) stays
            # unstacked, replicated on a mesh; the state is stacked on
            # ``(n_cfg,) (r_pad,)``
            *shared, s = init_carry()
            keys = key if r_pad is None else replica_keys(key, r_pad)
            return (
                keys, tuple(shared),
                stack_axis(stack_axis(s, r_pad), n_cfg),
                v.init_extra(key),
            )

        return parts, (0, None, L.axis, None), fn, aux

    sids = [
        SM_SCHED_IDS[s]
        for s in ([prog.scheduler] if schedulers is None else schedulers)
    ]

    def operands(parts):
        keys, shared, s0, extra = parts
        # the traced scheduler id(s)
        sid = (
            np.int32(sids[0]) if n_cfg is None
            else np.asarray(sids, np.int32)
        )
        return (*shared, s0), (keys, sid, v.operands(extra))

    L.prepare(
        lambda: _sm_cache_key(prog, r_pad, n_cfg, L.obs, use_pallas)
        + v.tag,
        build, operands, init_args=(key,),
    )
    return L, v, sids


def _sm_call(fn, carry, t_end, ops):
    keys, sid, extra = ops
    return fn(carry, keys, sid, t_end, *extra)


def compiled_step_lowering(prog: LteSmProgram, key, replicas=None,
                           mesh=None, schedulers=None) -> str:
    """Which TTI step the executable behind
    ``run_lte_sm(prog, key, replicas, mesh, schedulers=...)`` actually
    holds — read from the COMPILED program, not from the rule or
    ``TPUDES_PALLAS`` (the wish, on the ``launch`` span as
    ``step_lowering``): ``"mosaic"`` when its HLO carries a
    ``tpu_custom_call`` (the Pallas kernel, compiled by Mosaic),
    ``"xla"`` otherwise.
    Lowers the cached runner with a run's own operands, so after a run
    this is a compile-cache hit, not a second compile."""
    L, _, _ = _sm_prepare(prog, key, replicas, mesh, schedulers)
    text = _sm_call(
        L.fn.lower, L.carry, np.int32(prog.n_ttis), L.ops
    ).compile().as_text()
    return "mosaic" if "tpu_custom_call" in text else "xla"


def run_lte_sm(
    prog: LteSmProgram,
    key,
    replicas: int | None = None,
    mesh=None,
    *,
    schedulers=None,
    chunk_ttis: int | None = None,
    checkpoint=None,
    block: bool = True,
):
    """Run the full-buffer downlink simulation on-device.

    Without ``replicas``: one run, returns per-UE arrays
    ``{rx_bits, new_tbs, retx, drops, ok, cqi, mcs, sinr}``.
    With ``replicas=R``: R Monte-Carlo replicas advance in lock step
    inside ONE TTI loop (scalar clock; the per-TTI step is vmapped over
    per-replica keys), leading axis R on the outcome arrays; with
    ``mesh`` (1-axis "replica") the replica axis is sharded over the
    mesh devices and the loop needs no collective.  The replica axis is
    runtime-bucketed (padded to a power of two, results sliced back) so
    replica sweeps reuse one executable per bucket.

    ``schedulers=[...]`` (names from :data:`SM_SCHED_IDS`) turns the
    call into a **config-axis sweep**: the scheduler id gains a leading
    axis, vmapped over the step alongside the replica axis, so a
    C-point scheduler study is ONE device launch of a (C, R, …)
    program; the return value is a list of per-point result dicts, each
    exactly what the per-point launch (same key) would have produced.
    Scheduler id and horizon are traced, so a 9-scheduler sweep keeps
    the recorded compile count at ONE.

    ``chunk_ttis=N`` splits the horizon into N-TTI while_loop segments
    with the carry handed (donated) from segment to segment — results
    are bit-identical to a single-shot run (per-TTI keys are
    ``fold_in(key, t)``, indifferent to segment boundaries) while each
    segment's summary metrics stream to ``tpudes.obs`` as the next
    segment runs.

    ``block=False`` returns an :class:`~tpudes.parallel.runtime.EngineFuture`
    (the launch is dispatched; D2H + unpack happen at ``result()``) —
    the :meth:`RUNTIME.submit` payload.

    A program with ``prog.mobility`` runs the mobile-geometry form
    (same contract; results gain ``geom_refreshes``/``geom_stride``) —
    see :func:`_sm_mobile`.  A program with ``prog.traffic`` runs the
    finite-backlog form (results gain ``backlog_bits``/
    ``offered_bits``) — see :func:`_sm_traffic`; combining both axes on
    one LTE program is rejected loudly (run one axis on device and the
    other through the host controller) — the ROADMAP remainder.
    """
    from tpudes.parallel.runtime import chunk_bounds

    L, v, sids = _sm_prepare(prog, key, replicas, mesh, schedulers)
    return L.drive(
        _sm_call,
        chunk_bounds(prog.n_ttis, chunk_ttis or prog.n_ttis),
        lambda carry: v.fetch(carry, L.obs),
        v.unpack(L.aux, replicas),
        shared=v.shared,
        once=v.once,
        checkpoint=checkpoint,
        identity=lambda: _sm_cache_key(prog, None, L.n_cfg, L.obs, False)
        + v.identity(sids),
        block=block,
    )


# --- trace manifest (tpudes.analysis.jaxpr) --------------------------------

#: canonical tiny replica count for the abstract traces
_TRACE_R = 2


def _trace_prog(**over):
    """Canonical tiny-shape program: 2 cells, 3 UEs, PF scheduler."""
    import dataclasses

    from tpudes.parallel.programs import toy_lte_program

    prog = toy_lte_program(n_enb=2, n_ue=3, n_ttis=40)
    return dataclasses.replace(prog, **over) if over else prog


def _trace_entries(
    prog: LteSmProgram, obs: bool = False, scale: bool = True
):
    """The cached-runner functions exactly as ``run_lte_sm`` jits them
    (plain-XLA lowering), with concrete tiny operands.  ``scale=False``
    skips the JXL007 axis declarations (the axis builders re-enter
    here)."""
    from tpudes.analysis.jaxpr.spec import TraceEntry
    from tpudes.parallel.runtime import replica_keys, stack_axis

    consts, init_state, fn = build_sm_advance(
        prog, r_pad=_TRACE_R, obs=obs, use_pallas=False
    )
    keys = replica_keys(jax.random.PRNGKey(0), _TRACE_R)
    carry = (jnp.int32(0), stack_axis(init_state(), _TRACE_R))
    return [
        TraceEntry("init", init_state, (), kernel=False),
        TraceEntry(
            "advance",
            fn,
            (carry, keys, jnp.int32(SM_SCHED_IDS[prog.scheduler]),
             jnp.int32(8)),
            donate=(0,),
            carry=(0,),
            traced={"sid": 2, "t_end": 3},
            scale_axes=_scale_axes() if scale else (),
        ),
    ]


def _scale_axes():
    """JXL007 scale axes for the SINR/scheduler advance kernel: the
    gain/SINR tables are (U, E) — linear in the UE count at fixed
    cells and linear in the cell count at fixed UEs.  Both axes budget
    1.0; a dense (U, U) interference rewrite would fire them."""
    from tpudes.analysis.jaxpr.spec import ScaleAxis
    from tpudes.parallel.programs import toy_lte_program

    def at(n_enb, n_ue):
        prog = toy_lte_program(
            n_enb=int(n_enb), n_ue=int(n_ue), n_ttis=40
        )
        return _trace_entries(prog, scale=False)[1]

    return (
        ScaleAxis(
            "n_ue",
            lambda v: at(2, v),
            points=(3, 12),
            mem_budget=1.0,
        ),
        ScaleAxis(
            "n_enb",
            lambda v: at(v, 3),
            points=(2, 8),
            mem_budget=1.0,
        ),
    )


def _trace_traffic_prog():
    """Tiny finite-backlog program for the traffic TraceVariant."""
    import dataclasses

    from tpudes.traffic import TrafficProgram

    base = _trace_prog()
    return dataclasses.replace(
        base,
        traffic=TrafficProgram.onoff(
            base.n_ue, 100.0, horizon_us=base.n_ttis * 1000,
            on=(1.5, 0.01, 0.05), off_mean_s=0.02,
        ),
    )


def _trace_entries_traffic(prog: LteSmProgram):
    """The finite-backlog advance exactly as ``run_lte_sm``
    jits it (plain-XLA lowering), with concrete tiny operands — the
    new jitted program joins the JXL lint surface like the base one."""
    from tpudes.analysis.jaxpr.spec import TraceEntry
    from tpudes.parallel.runtime import replica_keys, stack_axis
    from tpudes.traffic.device import TRAFFIC_KEY_TAG

    init_carry, fn = build_sm_traffic_advance(
        prog, r_pad=_TRACE_R, use_pallas=False
    )
    keys = replica_keys(jax.random.PRNGKey(0), _TRACE_R)
    t0, s0 = init_carry()
    carry = (t0, stack_axis(s0, _TRACE_R))
    tr = prog.traffic.operands()
    tr_key = jax.random.fold_in(jax.random.PRNGKey(0), TRAFFIC_KEY_TAG)
    return [
        TraceEntry(
            "traffic_advance",
            fn,
            (carry, keys, jnp.int32(SM_SCHED_IDS[prog.scheduler]),
             jnp.int32(8), tr, tr_key),
            donate=(0,),
            carry=(0,),
            traced={"sid": 2, "t_end": 3, "tr": 4},
        ),
    ]


def _trace_flips():
    import dataclasses

    from tpudes.analysis.jaxpr.spec import FlipSpec

    base = _trace_prog()

    def key_of(p):
        return _sm_cache_key(p, _TRACE_R, None, False, False)

    def flip(**over):
        prog = dataclasses.replace(base, **over)
        return FlipSpec(
            build=lambda p=prog: _trace_entries(p),
            key_differs=key_of(prog) != key_of(base),
        )

    return {
        # live components: each must change some traced program
        "n_rb": flip(n_rb=50),
        "pf_alpha": flip(pf_alpha=0.25),
        # the flip value must leave the degenerate regime: at the toy
        # program's 30 dB dominance a thermal-scale noise change
        # vanishes into the saturated MCS rows, so flip to an
        # interference-scale value that moves the baked CQI/MI tables
        "noise_psd": flip(noise_psd=1e-13),
        "obs": FlipSpec(
            build=lambda: _trace_entries(base, obs=True),
            key_differs=True,
        ),
        # excluded-by-design fields must leave every trace identical:
        # the scheduler id and the TTI horizon are traced operands
        # (one executable serves all nine schedulers at every horizon)
        "scheduler": flip(scheduler="rr"),
        "n_ttis": flip(n_ttis=80),
        "geom_stride": flip(geom_stride=8),
    }


def trace_manifest():
    """Per-engine trace manifest (see :mod:`tpudes.analysis.jaxpr`).
    The ``bf16`` variant arms the JXL002 accumulator check: every
    reduction in the mixed-precision program must accumulate in f32
    (the PR 6 precision policy)."""
    import dataclasses

    from tpudes.analysis.jaxpr.spec import TraceManifest, TraceVariant

    return TraceManifest(
        engine="lte_sm",
        path="tpudes/parallel/lte_sm.py",
        variants=lambda: [
            TraceVariant(
                "base", lambda: _trace_entries(_trace_prog())
            ),
            TraceVariant(
                "bf16",
                lambda: _trace_entries(
                    dataclasses.replace(_trace_prog(), precision="bf16")
                ),
                bf16=True,
            ),
            # ISSUE-14: the finite-backlog traffic advance is its own
            # jitted program — it must ride the lint surface too
            TraceVariant(
                "traffic",
                lambda: _trace_entries_traffic(_trace_traffic_prog()),
            ),
            # the TpudesObs program (FlowMonitor columns + packet ring)
            # joins the lint surface: its ring write — a scatter here,
            # the replica vmap of the step batches the ring — must
            # pass the registered SparseSite contract (JXL008)
            TraceVariant(
                "obs", lambda: _trace_entries(_trace_prog(), obs=True)
            ),
        ],
        flips=_trace_flips,
    )
