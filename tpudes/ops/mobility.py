"""Device-resident mobility kernels — node motion as traced operands.

The last structural ❌ family of the engine capability matrix was
mobility: any moving topology either fell back to the host DES or paid
the LTE TTI controller's per-window host geometry refresh (host
recompute → H2D → fresh operands every window).  This module lifts the
motion itself onto the device: every supported model is a CLOSED-FORM
pure function ``positions_at(params, t_us) -> (N, 3)`` of simulation
time, so the engines' scan bodies can evaluate geometry at any step
without integrating state — and therefore without any dependence on
the step cadence (a ``geom_stride=K`` run samples the *same*
trajectory a stride-1 run samples, just less often).

Model family (``MOB_MODEL_IDS``), dispatched by a TRACED model id the
same way the LTE engine dispatches its FF-MAC scheduler id — one
compiled executable serves every model:

- ``static`` / ``const_velocity`` — ``p(t) = p0 + v·t`` (static is the
  ``v = 0`` point of the same branch; ConstantVelocityMobilityModel
  semantics).
- ``random_walk`` — per-(node, segment) speed/direction draws from a
  ``fold_in``-keyed stream (pure in ``(mob_seed, segment, node)``, so
  the trajectory is one integer), displacement summed over the static
  segment grid and folded back into the bounds rectangle by the
  triangle-wave reflection (the closed form of elastic rebound).  The
  DEVICE walk is a re-keyed walk: it matches the host
  RandomWalk2dMobilityModel in distribution (speed band, segment
  cadence, bounds), not step for step — host parity for walks is
  statistical, like the PHY coin flips.
- ``waypoint`` — per-node ``(time, position)`` tables with linear
  interpolation, clamped at both ends (a node PAUSES at its final
  waypoint; a zero-duration or zero-displacement segment is a pause —
  WaypointMobilityModel semantics, bit-matching the host interpolation
  up to f32).

Every per-node parameter (bases, velocities, speed bands, waypoint
tables, the model id, the walk seed) is a RUNTIME operand of the
compiled engines; only the SHAPES (node count, waypoint-table width,
walk-segment count) and the segment length are trace-time constants
(:meth:`MobilityProgram.shape_key`).

``TPUDES_DEVICE_GEOM=0`` is the family kill switch: the engine
lowerings refuse mobile graphs again (restoring the host-DES /
per-window-host-refresh fallback), and the LTE engine's mobile runner
takes the precomputed-positions per-window path (see
``tpudes/parallel/lte_sm.py``) — pinned bit-equal to the carried
geometry.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass

import numpy as np

__all__ = [
    "GEOM_COHERENCE_M",
    "MOB_MODEL_IDS",
    "MobilityProgram",
    "build_position_fn",
    "device_geom_enabled",
    "fold_into_bounds",
    "max_speed_mps",
    "trajectory_positions",
    "walk_segment_velocities",
    "warn_geom_stride",
]

#: mobility model short name → traced dispatch id (the scheduler-id
#: pattern: the id is a runtime operand selecting the position branch,
#: so the whole family rides one compiled executable)
MOB_MODEL_IDS = {
    "static": 0,
    "const_velocity": 1,
    "random_walk": 2,
    "waypoint": 3,
}

#: the geometry-coherence length scale (meters) behind the
#: ``geom_stride`` advisory in ``lower_bss``/``lower_lte_sm``: once the
#: fastest node can move further than this between two geometry
#: refreshes, the strided loss matrix is a materially stale snapshot
#: (log-distance loss moves ~1 dB over ~2 m at short range), so the
#: lowering warns — the stride still RUNS (the contract is accuracy
#: advice, not a refusal), mirroring the COMPILE_AMORTIZE_TTIS warning.
GEOM_COHERENCE_M = 2.0

#: root key of every device walk stream (the FUZZ_ROOT_SEED pattern):
#: segment draws are fold_in(fold_in(PRNGKey(root), mob_seed), segment)
_MOB_ROOT_SEED = 0x6E0B17


def device_geom_enabled() -> bool:
    """Device-resident mobility is on unless ``TPUDES_DEVICE_GEOM``
    says otherwise (read per call so tests can A/B without
    re-importing — the TPUDES_BUCKETING/TPUDES_PALLAS contract)."""
    raw = os.environ.get("TPUDES_DEVICE_GEOM")
    if raw is None:
        return True
    return raw.strip().lower() not in {"0", "false", "no", "off"}


@dataclass(frozen=True)
class MobilityProgram:
    """One node batch's motion, ready to ride a device engine.

    All array fields are RUNTIME operands of the compiled program;
    :meth:`shape_key` is the only part that belongs in an engine cache
    key.  Build via the factory classmethods or
    ``tpudes.models.mobility.device_mobility_program`` (the live-graph
    extractor)."""

    model: str                    # key of MOB_MODEL_IDS
    base_pos: np.ndarray          # (N, 3) f32 position at t = 0
    velocity: np.ndarray          # (N, 3) f32 (const_velocity)
    speed: np.ndarray             # (N, 2) f32 per-node [min, max] m/s (walk)
    bounds: np.ndarray            # (4,) f32 (xmin, xmax, ymin, ymax) (walk)
    wp_t: np.ndarray              # (N, W) i32 waypoint times (µs), sorted
    wp_p: np.ndarray              # (N, W, 3) f32 waypoint positions
    seg_us: int = 1_000_000       # walk segment length (trace-time constant)
    n_seg: int = 1                # walk segment-grid length (shape)
    mob_seed: int = 0             # walk stream seed (runtime operand)

    @property
    def n(self) -> int:
        return int(self.base_pos.shape[0])

    def shape_key(self) -> tuple:
        """The trace-time identity: everything that changes the
        compiled program's shape.  Model id and every array are
        deliberately ABSENT — they are traced operands, so a sweep
        across the model family reuses one executable."""
        return (
            self.n, int(self.wp_t.shape[1]), int(self.n_seg),
            int(self.seg_us),
        )

    def param_key(self) -> tuple:
        """Hashable identity of the FULL parameter set (serving-layer
        coalesce keys: studies with different trajectories must not
        coalesce even though the params are traced)."""
        return (
            self.model, self.base_pos.tobytes(), self.velocity.tobytes(),
            self.speed.tobytes(), self.bounds.tobytes(),
            self.wp_t.tobytes(), self.wp_p.tobytes(),
            int(self.seg_us), int(self.n_seg), int(self.mob_seed),
        )

    def operands(self) -> dict:
        """The traced-operand dict ``build_position_fn`` consumes.

        The walk's per-(node, segment) velocity table is materialized
        HERE (eagerly — jax PRNG draws are spec'd identical eager vs
        traced), not inside the position kernel: it is loop-invariant,
        and as an operand a refresh pays one (S,) einsum instead of
        O(S·N) draws + trig per cond firing.  Different seeds are just
        different operand values — the one-executable property holds.

        Memoized on the (immutable) program so repeat launches — bench
        iterations, fuzz oracle-pair reruns — skip the re-materialize
        + H2D; the cache is dropped on pickling (procmesh study specs
        cross process boundaries)."""
        import jax.numpy as jnp

        cached = self.__dict__.get("_operands_cache")
        if cached is None:
            cached = dict(
                mob_id=jnp.int32(MOB_MODEL_IDS[self.model]),
                mob_base=jnp.asarray(self.base_pos, jnp.float32),
                mob_vel=jnp.asarray(self.velocity, jnp.float32),
                mob_speed=jnp.asarray(self.speed, jnp.float32),
                mob_bounds=jnp.asarray(self.bounds, jnp.float32),
                mob_wp_t=jnp.asarray(self.wp_t, jnp.int32),
                mob_wp_p=jnp.asarray(self.wp_p, jnp.float32),
                mob_walk_vels=walk_segment_velocities(self),
            )
            object.__setattr__(self, "_operands_cache", cached)
        return dict(cached)

    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_operands_cache", None)  # device arrays stay local
        return state

    # --- factories --------------------------------------------------------

    @classmethod
    def _fill(cls, model: str, base: np.ndarray, **kw) -> "MobilityProgram":
        base = np.asarray(base, np.float32)
        n = base.shape[0]
        defaults = dict(
            velocity=np.zeros((n, 3), np.float32),
            speed=np.zeros((n, 2), np.float32),
            bounds=np.zeros((4,), np.float32),
            wp_t=np.zeros((n, 2), np.int32),
            wp_p=np.broadcast_to(base[:, None, :], (n, 2, 3)).copy(),
        )
        defaults.update(kw)
        return cls(model=model, base_pos=base, **defaults)

    @classmethod
    def static(cls, base) -> "MobilityProgram":
        return cls._fill("static", base)

    @classmethod
    def constant_velocity(cls, base, velocity) -> "MobilityProgram":
        return cls._fill(
            "const_velocity", base,
            velocity=np.asarray(velocity, np.float32),
        )

    @classmethod
    def random_walk(
        cls, base, bounds, speed, *, seg_s: float = 1.0,
        horizon_us: int, mob_seed: int = 0,
    ) -> "MobilityProgram":
        """``speed`` is (N, 2) per-node [min, max] m/s — a [0, 0] row
        pins that node in place (how mixed static+walking batches ride
        one model id).  ``horizon_us`` sizes the static segment grid."""
        base = np.asarray(base, np.float32)
        seg_us = max(1, int(round(seg_s * 1e6)))
        n_seg = int(horizon_us) // seg_us + 1
        return cls._fill(
            "random_walk", base,
            speed=np.asarray(speed, np.float32).reshape(base.shape[0], 2),
            bounds=np.asarray(bounds, np.float32).reshape(4),
            seg_us=seg_us, n_seg=n_seg, mob_seed=int(mob_seed),
        )

    @classmethod
    def waypoints(cls, wp_t, wp_p) -> "MobilityProgram":
        """``wp_t`` (N, W) µs ascending per row, ``wp_p`` (N, W, 3);
        nodes hold the first entry before its time and PAUSE at the
        last entry forever after (the upstream clamp)."""
        wp_t = np.asarray(wp_t, np.int64)
        wp_p = np.asarray(wp_p, np.float32)
        if wp_t.shape[1] < 2:  # interp needs two columns; repeat the last
            wp_t = np.concatenate([wp_t, wp_t], axis=1)
            wp_p = np.concatenate([wp_p, wp_p], axis=1)
        if (np.diff(wp_t, axis=1) < 0).any():
            raise ValueError("waypoint times must ascend per node")
        # the device clock is int32 µs: a waypoint past ~35.8 simulated
        # minutes would WRAP negative under a silent astype and snap
        # the node to the wrong leg at t=0 — clamp instead (ordering
        # survives, and the pause-at-final interp makes a clamped
        # far-future waypoint behave as 'still en route' for every
        # representable t)
        wp_t = np.minimum(wp_t, np.int64(2**31 - 1))
        return cls._fill(
            "waypoint", wp_p[:, 0, :],
            wp_t=wp_t.astype(np.int32), wp_p=wp_p,
        )


def walk_segment_velocities(prog: MobilityProgram):
    """(n_seg, N, 2) per-(segment, node) walk velocities — pure in
    ``(mob_seed, segment, node)`` via two ``fold_in`` hops, so the
    whole trajectory is the one integer seed.  Zero-band nodes get
    zero vectors (speed interpolation from a [0, 0] band)."""
    import jax
    import jax.numpy as jnp

    n = prog.n
    speed = jnp.asarray(prog.speed, jnp.float32)
    key = jax.random.fold_in(
        jax.random.PRNGKey(_MOB_ROOT_SEED), int(prog.mob_seed)
    )

    def seg_vel(s):
        u = jax.random.uniform(jax.random.fold_in(key, s), (n, 2))
        spd = speed[:, 0] + u[:, 0] * (speed[:, 1] - speed[:, 0])
        ang = jnp.float32(2.0 * math.pi) * u[:, 1]
        return jnp.stack(
            [spd * jnp.cos(ang), spd * jnp.sin(ang)], axis=-1
        )                                                  # (N, 2)

    return jax.vmap(seg_vel)(jnp.arange(int(prog.n_seg)))


def fold_into_bounds(x, lo, hi):
    """Triangle-wave reflection of ``x`` into ``[lo, hi]`` — the closed
    form of elastic wall rebound (a straight-line path with reflections
    unrolled is a straight line in the unfolded plane).  Degenerate
    bounds (``hi <= lo``) clamp to ``lo``."""
    import jax.numpy as jnp

    span = hi - lo
    y = jnp.mod(x - lo, 2.0 * span)
    folded = lo + span - jnp.abs(span - y)
    return jnp.where(span > 0.0, folded, jnp.broadcast_to(lo, x.shape))


def build_position_fn(prog: MobilityProgram):
    """Closed-form position kernel for ``prog``'s SHAPE class: returns
    ``pos_fn(ops, t_us) -> (N, 3)`` where ``ops`` is
    :meth:`MobilityProgram.operands` (all traced) and ``t_us`` a traced
    scalar.  Every model branch is evaluated and the traced
    ``mob_id`` selects — the dispatch shape of the LTE scheduler id,
    which is what keeps the family on one executable."""
    import jax.numpy as jnp

    n_seg = int(prog.n_seg)
    seg_us = float(prog.seg_us)
    W = int(prog.wp_t.shape[1])

    def pos_fn(ops, t_us):
        t_s = t_us.astype(jnp.float32) * jnp.float32(1e-6)
        base = ops["mob_base"]

        # static / const_velocity (static rides v = 0)
        p_cv = base + ops["mob_vel"] * t_s

        # random walk: the per-(node, segment) velocity table rides as
        # a loop-invariant OPERAND (walk_segment_velocities); a refresh
        # only sums displacement and triangle-folds into bounds (z
        # inherits the base plane)
        vels = ops["mob_walk_vels"]                        # (S, N, 2)
        dt = jnp.clip(
            t_us.astype(jnp.float32)
            - jnp.arange(n_seg, dtype=jnp.float32) * seg_us,
            0.0, seg_us,
        ) * jnp.float32(1e-6)                              # (S,)
        disp = jnp.einsum("snk,s->nk", vels, dt)           # (N, 2)
        bx = fold_into_bounds(
            base[:, 0] + disp[:, 0], ops["mob_bounds"][0],
            ops["mob_bounds"][1],
        )
        by = fold_into_bounds(
            base[:, 1] + disp[:, 1], ops["mob_bounds"][2],
            ops["mob_bounds"][3],
        )
        # a zero-speed-band node is pinned: it must NOT be folded into
        # the walkers' rectangle (a static AP may sit outside it)
        moving = ops["mob_speed"][:, 1] > 0.0
        p_walk = jnp.stack(
            [
                jnp.where(moving, bx, base[:, 0]),
                jnp.where(moving, by, base[:, 1]),
                base[:, 2],
            ],
            axis=-1,
        )

        # waypoint table: clamp-interpolate each node's row
        wt = ops["mob_wp_t"]                               # (N, W)
        wp = ops["mob_wp_p"]                               # (N, W, 3)
        idx = jnp.clip(
            jnp.sum(wt <= t_us, axis=1) - 1, 0, W - 2
        )                                                  # (N,)
        t0 = jnp.take_along_axis(wt, idx[:, None], axis=1)[:, 0]
        t1 = jnp.take_along_axis(wt, idx[:, None] + 1, axis=1)[:, 0]
        p0 = jnp.take_along_axis(wp, idx[:, None, None], axis=1)[:, 0]
        p1 = jnp.take_along_axis(wp, idx[:, None, None] + 1, axis=1)[:, 0]
        frac = jnp.clip(
            (t_us - t0).astype(jnp.float32)
            / jnp.maximum((t1 - t0).astype(jnp.float32), 1.0),
            0.0, 1.0,
        )                                                  # (N,)
        p_wp = p0 + (p1 - p0) * frac[:, None]

        mid = ops["mob_id"]
        return jnp.where(
            mid == MOB_MODEL_IDS["random_walk"], p_walk,
            jnp.where(mid == MOB_MODEL_IDS["waypoint"], p_wp, p_cv),
        )

    return pos_fn


def max_speed_mps(prog: MobilityProgram) -> float:
    """Upper bound on any node's speed over the whole run — the input
    of the geometry-coherence stride advisory."""
    if prog.model in ("static",):
        return 0.0
    if prog.model == "const_velocity":
        return float(
            np.sqrt((prog.velocity.astype(np.float64) ** 2).sum(-1)).max()
        ) if prog.velocity.size else 0.0
    if prog.model == "random_walk":
        return float(prog.speed[:, 1].max()) if prog.speed.size else 0.0
    # waypoint: fastest leg over the table (zero-duration legs are
    # pauses by the interp clamp, not infinite speeds)
    t = prog.wp_t.astype(np.float64)
    p = prog.wp_p.astype(np.float64)
    dt = np.diff(t, axis=1) * 1e-6                        # (N, W-1)
    dp = np.sqrt((np.diff(p, axis=1) ** 2).sum(-1))       # (N, W-1)
    with np.errstate(divide="ignore", invalid="ignore"):
        v = np.where(dt > 0.0, dp / np.maximum(dt, 1e-30), 0.0)
    return float(v.max()) if v.size else 0.0


def warn_geom_stride(
    who: str, mobility: MobilityProgram, geom_stride: int, step_s: float
) -> None:
    """Advise when a stride outruns the geometry coherence the max
    node speed implies (the COMPILE_AMORTIZE_TTIS warning shape: the
    run still executes, the accuracy regime is just named loudly).
    ``step_s`` is the engine's nominal inter-step spacing — exactly
    1 ms for the LTE TTI clock, the offered-event estimate for the
    event-stepped BSS loop."""
    speed = max_speed_mps(mobility)
    drift_m = speed * geom_stride * step_s
    if drift_m > GEOM_COHERENCE_M:
        import warnings

        warnings.warn(
            f"{who}: geom_stride={geom_stride} lets the fastest node "
            f"({speed:.1f} m/s) drift ~{drift_m:.1f} m between "
            f"geometry refreshes (> the ~{GEOM_COHERENCE_M:.0f} m "
            "coherence scale of the loss models) — the strided loss "
            "matrix is a materially stale snapshot; lower the stride "
            "or accept the documented staleness",
            stacklevel=3,
        )


#: one jitted sampler per SHAPE class (build_position_fn closes over
#: shapes only, operands ride as arguments) — a fresh jit per call
#: would recompile the kernel for every lowering guard / fuzz build
_TRAJ_SAMPLERS: dict = {}


def trajectory_positions(prog: MobilityProgram, t_grid_us) -> np.ndarray:
    """Host-side trajectory samples ``(T, N, 3)`` through the SAME
    compiled position kernel the engines trace — the single source of
    truth for lowering guards (mutual-sensing over the whole run) and
    the ``TPUDES_DEVICE_GEOM=0`` precomputed-positions fallback, whose
    bit-equality contract depends on both paths sharing this kernel."""
    import jax
    import jax.numpy as jnp

    fn = _TRAJ_SAMPLERS.get(prog.shape_key())
    if fn is None:
        pos_fn = build_position_fn(prog)
        # ONE vmapped dispatch for the whole grid (a per-t loop would
        # pay T dispatches + D2H round trips); pinned bit-equal to the
        # scan's in-loop evaluation by the device_geom_off tests
        fn = jax.jit(jax.vmap(pos_fn, in_axes=(None, 0)))
        _TRAJ_SAMPLERS[prog.shape_key()] = fn
    return np.asarray(
        fn(
            prog.operands(),
            jnp.asarray([int(t) for t in t_grid_us], jnp.int32),
        )
    )
