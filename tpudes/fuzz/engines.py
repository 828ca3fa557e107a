"""Per-engine fuzz hooks: config → object graph → lowered program,
oracle-pair runners, host-DES oracles, and shrink moves.

One :class:`EngineFuzzer` per device engine.  Every scenario config is
a plain JSON dict drawn from the engine's ``FUZZ_ENVELOPE`` (declared
next to the engine it describes); the fuzzer builds the *live object
graph* through the canonical :mod:`tpudes.scenarios` builders and
lowers it — so the device program under test is exactly the scenario
the host DES runs, and the lowering guards (``Unliftable*Error``)
enforce the envelope by construction.

Oracle pairs come in two strengths:

- **exact** cross-mode pairs — chunked horizon, config-axis sweep
  point, bucketing off, virtual-mesh sharding, serving coalescing,
  and (LTE) Pallas-vs-XLA: the documented bit-equality contracts of
  the runtime (tests/test_sweep.py pins them at hand-picked configs;
  the fuzzer generalizes them to the whole envelope);
- **tolerance** pairs — host DES vs device at *fuzz* tolerances
  (wider than the pinned parity tests: random in-envelope configs sit
  away from the hand-tuned regimes, and this oracle exists to catch
  gross semantic divergence, not to re-pin the documented bounds), and
  the LTE bf16 precision budget.
"""

from __future__ import annotations

import contextlib
import os
import warnings
from dataclasses import dataclass, field

import numpy as np

__all__ = [
    "Divergence",
    "ENGINE_FUZZERS",
    "EngineFuzzer",
    "first_diff",
    "scenario_key",
]


@dataclass
class Divergence:
    """One oracle-pair disagreement, ready for artifact emission."""

    engine: str
    pair: str
    #: first differing field/index: {"field", "index", "lhs", "rhs"}
    diff: dict
    message: str = ""
    config: dict = field(default_factory=dict)

    def render(self) -> str:
        d = self.diff
        at = f"[{', '.join(str(i) for i in d.get('index', ()))}]"
        return (
            f"{self.engine}/{self.pair}: {d.get('field')}{at} "
            f"{d.get('lhs')} != {d.get('rhs')}"
            + (f" ({self.message})" if self.message else "")
        )


def _as_comparable(v):
    a = np.asarray(v)
    return a if a.dtype != object else None


def first_diff(a: dict, b: dict, fields=None, rtol=0.0, atol=0.0):
    """First field (sorted order) and index at which the two result
    trees differ — ``None`` when they agree.  ``rtol/atol == 0`` is the
    bit-equality mode (integer counters compare exactly; float fields
    compare by equality including NaN position).  ``fields=None``
    compares the key UNION: a mode that silently drops (or invents) a
    result field is a divergence, not an agreement."""
    keys = sorted(fields if fields is not None else set(a) | set(b))
    for k in keys:
        if k not in a or k not in b:
            # index is ALWAYS a list (every branch): artifacts JSON
            # round-trip, and replay checks fresh == recorded equality
            return {"field": k, "index": [], "lhs": k in a, "rhs": k in b}
        x, y = _as_comparable(a[k]), _as_comparable(b[k])
        if x is None or y is None:
            continue
        if x.shape != y.shape:
            return {
                "field": k, "index": [],
                "lhs": list(x.shape), "rhs": list(y.shape),
            }
        if rtol == 0.0 and atol == 0.0:
            neq = ~(
                (x == y)
                | (np.isnan(x) & np.isnan(y))
                if np.issubdtype(x.dtype, np.floating)
                else (x == y)
            )
        else:
            xf = x.astype(np.float64)
            yf = y.astype(np.float64)
            neq = ~(
                np.isclose(xf, yf, rtol=rtol, atol=atol)
                | (np.isnan(xf) & np.isnan(yf))
            )
        neq = np.asarray(neq)
        if neq.any():
            idx = tuple(int(i) for i in np.argwhere(neq)[0])
            lhs = x[idx] if idx else x[()]
            rhs = y[idx] if idx else y[()]
            return {
                "field": k,
                "index": list(idx),
                "lhs": lhs.item() if hasattr(lhs, "item") else lhs,
                "rhs": rhs.item() if hasattr(rhs, "item") else rhs,
            }
    return None


def scenario_key(cfg: dict):
    """The scenario's device PRNG key (the ``key_seed`` axis)."""
    import jax

    return jax.random.PRNGKey(int(cfg.get("key_seed", 0)))


def _reset_world():
    from tpudes.core.world import reset_world

    reset_world()


def _recorder_entries():
    """Flight-recorder tail of the just-finished host run — present
    only under ``TpudesObs=1`` (the recorder exists only then); rides
    the host oracle summary into divergence artifacts."""
    from tpudes.core.simulator import Simulator

    rec = getattr(
        getattr(Simulator._impl, "_obs", None), "recorder", None
    )
    return rec.to_dicts() if rec is not None else None


@contextlib.contextmanager
def _env(name: str, value: str | None):
    """Temporarily set/unset one env knob (the per-call-read toggles:
    TPUDES_BUCKETING, TPUDES_PALLAS)."""
    old = os.environ.get(name)
    try:
        if value is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = value
        yield
    finally:
        if old is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = old


@contextlib.contextmanager
def _quiet_lowering():
    """The fuzz envelopes intentionally include short horizons; the
    engines' compile-amortization / warm-up advisories are for humans
    picking one config, not a generator sweeping thousands."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        yield


def _mesh_or_none(n_devices: int = 2):
    """A small replica mesh when >1 device is visible (the fuzz mesh
    pair deliberately uses 2 devices: the pow2 replica bucket is then
    already a multiple of the device count, so the sharded run reuses
    the unsharded executables for the input-sharded engines)."""
    import jax

    if len(jax.devices()) < n_devices:
        return None
    from tpudes.parallel.mesh import replica_mesh

    return replica_mesh(n_devices)


def _shrink_int(cfg, name, floor):
    """Halve one integer axis toward ``floor`` (None when already
    there) — the generic shrink move."""
    v = int(cfg[name])
    if v <= floor:
        return None
    nv = max(floor, v // 2)
    out = dict(cfg)
    out[name] = nv
    return out


def _shrink_choice(cfg, name, simplest):
    if cfg[name] == simplest:
        return None
    out = dict(cfg)
    out[name] = simplest
    return out


def _fuzz_traffic(cfg, n, horizon_us, rate_pps, start_us=0):
    """The drawn workload program (ISSUE-14 axes) for ``n`` entities,
    or None for the "off" draw.  ``tr_burst`` is the burstiness knob
    (ON-OFF off-time mean / mmpp state spread), ``tr_phase`` the
    diurnal-envelope phase (amp fixed at 0.35, one period per
    horizon); the realization seed is the scenario's ``key_seed`` so
    the workload is part of the one-integer reproduction story."""
    from tpudes.traffic import TrafficProgram

    model = cfg.get("traffic", "off")
    if model == "off":
        return None
    burst = float(cfg.get("tr_burst", 0.3))
    env = (0.35, horizon_us / 1e6, float(cfg.get("tr_phase", 0.0)))
    seed = int(cfg.get("key_seed", 0))
    if model == "cbr":
        iv = max(1, int(round(1e6 / max(rate_pps, 1e-6))))
        return TrafficProgram.cbr(
            np.broadcast_to(
                np.asarray(start_us, np.int32), (n,)
            ).copy(),
            iv,
        )
    if model == "mmpp":
        return TrafficProgram.mmpp(
            n, rate_pps, horizon_us=horizon_us, epoch_s=0.05,
            mult=(1.0 - burst, 1.0 + 2.0 * burst),
            switch_p=(0.4, 0.4), start_us=start_us, envelope=env,
            tr_seed=seed,
        )
    if model == "onoff":
        duty = 1.0 / (1.0 + burst / 0.2)  # on-mean 0.2 s vs off-mean
        return TrafficProgram.onoff(
            n, rate_pps / max(duty, 0.05), horizon_us=horizon_us,
            on=(1.5, 0.05, 1.0), off_mean_s=burst, start_us=start_us,
            envelope=env, tr_seed=seed,
        )
    # trace: a deterministic synthetic "empirical" table derived from
    # the scenario draws (no host RNG — the seed IS the trace)
    k = max(4, min(64, int(rate_pps * horizon_us / 1e6)))
    phase = float(cfg.get("tr_phase", 0.0))
    grid = (
        np.linspace(0.05 + 0.4 * phase / max(k, 1), 0.95, k)[None, :]
        * (horizon_us - int(np.max(start_us)))
        + np.asarray(start_us).reshape(-1, 1)
        + np.arange(n)[:, None] * 997
    ).astype(np.int64)
    sizes = (256 + 61 * ((seed + np.arange(n * k)) % 23)).reshape(n, k)
    return TrafficProgram.trace_replay(np.sort(grid, axis=1), sizes)


class EngineFuzzer:
    """Template for one engine's fuzz surface; subclasses fill in the
    build/run/host hooks.  ``outcome_fields`` is the sweep/serving
    comparison set (fields documented identical across launch modes);
    ``None`` means "every field".  ``cross_mode_pairs`` restricts the
    default exact-pair set for engines that do not implement every
    execution mode (None = the full ``harness.CROSS_MODE_PAIRS``)."""

    name: str = ""
    outcome_fields: tuple | None = None
    cross_mode_pairs: tuple | None = None

    @property
    def envelope(self):
        raise NotImplementedError

    # --- scenario construction -------------------------------------------

    def build(self, cfg: dict):
        """Fresh world → object graph → lowered program → fresh world."""
        raise NotImplementedError

    # --- device runs ------------------------------------------------------

    def run_scalar(self, prog, cfg, mesh=None):
        raise NotImplementedError

    def run_chunked(self, prog, cfg, canonical):
        raise NotImplementedError

    def run_sweep0(self, prog, cfg):
        """2-point config-axis sweep whose point 0 is the scenario
        itself; returns point 0's result."""
        raise NotImplementedError

    def serving_studies(self, prog, cfg):
        """(engine_name, [(prog_i, engine_kwargs_i)]) — two compatible
        studies whose FIRST is the scenario itself."""
        raise NotImplementedError

    # --- host oracle ------------------------------------------------------

    def host_run(self, cfg: dict) -> dict:
        raise NotImplementedError

    def host_compare(self, host: dict, dev: dict, cfg: dict):
        """Divergence diff dict (see :func:`first_diff`) or None."""
        raise NotImplementedError

    # --- engine-specific exact pairs -------------------------------------

    #: fields the ``traffic_off`` pair compares (None = key union) —
    #: engines whose traffic runs add result fields (LTE backlog/
    #: goodput) restrict to the common outcome set
    traffic_off_fields: tuple | None = None

    def neutral_traffic(self, prog):
        """A workload program pinned BIT-EQUAL to ``traffic=None`` on
        this engine (the cbr branch / a saturating fill), or None when
        the engine has no traffic seam.  Powers the ``traffic_off``
        exact oracle pair."""
        return None

    def _traffic_off_pair(self, prog, cfg, canonical):
        """ISSUE-14 exactness anchor: the engine with its traffic
        stage COMPILED IN but fed the neutral workload must match the
        legacy (traffic=None) path bit for bit — generalized over the
        whole envelope, whatever workload the scenario drew."""
        import dataclasses

        del canonical  # both sides are fresh runs
        neutral = self.neutral_traffic(prog)
        if neutral is None:
            return None
        off = self.run_scalar(
            dataclasses.replace(prog, traffic=None), cfg
        )
        neu = self.run_scalar(
            dataclasses.replace(prog, traffic=neutral), cfg
        )
        return first_diff(off, neu, fields=self.traffic_off_fields)

    def extra_pairs(self):
        """[(pair_name, fn(prog, cfg, canonical) -> diff|None), ...]
        Every engine carries the ``traffic_off`` pair; one without a
        traffic seam (``neutral_traffic`` → None) passes it
        trivially."""
        return [("traffic_off", self._traffic_off_pair)]

    # --- shrinking --------------------------------------------------------

    def shrink_moves(self, cfg: dict):
        """Ordered candidate shrinks: [(label, smaller_cfg), ...] —
        each strictly smaller along its axis; the greedy shrinker keeps
        any candidate that still reproduces the divergence."""
        floors = self.envelope.floors
        out = []
        for name in ("replicas",):
            c = _shrink_int(cfg, name, floors.get(name, 1))
            if c:
                out.append((f"halve {name}", c))
        if "sim_ms" in cfg:
            c = _shrink_int(cfg, "sim_ms", floors.get("sim_ms", 8))
            if c:
                out.append(("halve sim_ms", c))
        if "traffic" in cfg:
            # dropping the workload model is the single biggest
            # simplification a traffic-era divergence can take
            c = _shrink_choice(cfg, "traffic", "off")
            if c:
                out.append(("traffic -> off", c))
        return out


# ---------------------------------------------------------------------------
# BSS (replicated Wi-Fi)
# ---------------------------------------------------------------------------


class BssFuzzer(EngineFuzzer):
    name = "bss"
    #: ``steps`` is documented to differ under the sweep's shared step
    #: budget — outcomes are the cross-mode contract
    outcome_fields = ("srv_rx", "cli_rx", "tx_data", "drops", "all_done")

    @property
    def envelope(self):
        from tpudes.parallel.replicated import FUZZ_ENVELOPE

        return FUZZ_ENVELOPE

    def _graph(self, cfg):
        from tpudes.scenarios import build_bss

        return build_bss(
            n_stas=int(cfg["n_stas"]),
            sim_time=cfg["sim_ms"] / 1e3,
            radii=(float(cfg["radius"]),),
            interval_s=cfg["interval_ms"] / 1e3,
            packet_bytes=int(cfg["packet_bytes"]),
            mobility=str(cfg.get("mob_model", "static")),
            speed=float(cfg.get("mob_speed", 1.0)),
        )

    def build(self, cfg):
        import dataclasses

        from tpudes.parallel.replicated import lower_bss

        _reset_world()
        try:
            stas, ap, clients, _ = self._graph(cfg)
            with _quiet_lowering():
                prog = lower_bss(
                    [stas.Get(i) for i in range(int(cfg["n_stas"]))],
                    ap, clients, cfg["sim_ms"] / 1e3,
                    geom_stride=int(cfg.get("geom_stride", 1)),
                )
            # ISSUE-14: STA arrivals ride the drawn workload (the AP
            # row stays cbr at the beacon period); mean rate pinned to
            # the envelope's CBR load so offered stays in-region
            tp = _fuzz_traffic(
                cfg, prog.n, prog.sim_end_us,
                rate_pps=1000.0 / float(cfg["interval_ms"]),
                start_us=prog.start_us,
            )
            if tp is not None:
                tp = tp.with_cbr_rows(
                    np.arange(prog.n) == 0, prog.interval_us[0],
                    prog.start_us[0],
                )
                prog = dataclasses.replace(prog, traffic=tp)
            return prog
        finally:
            _reset_world()

    def run_scalar(self, prog, cfg, mesh=None):
        from tpudes.parallel.replicated import run_replicated_bss

        return run_replicated_bss(
            prog, int(cfg["replicas"]), scenario_key(cfg), mesh=mesh
        )

    def run_chunked(self, prog, cfg, canonical):
        from tpudes.parallel.replicated import run_replicated_bss

        # the BSS horizon is event steps: derive an off-boundary chunk
        # from the steps the scalar run actually took
        chunk = max(1, int(canonical["steps"]) // int(cfg["chunk_divisor"]) - 1)
        return run_replicated_bss(
            prog, int(cfg["replicas"]), scenario_key(cfg), chunk_steps=chunk
        )

    def run_sweep0(self, prog, cfg):
        from tpudes.parallel.replicated import run_replicated_bss

        ends = [int(prog.sim_end_us), max(1_300_000, prog.sim_end_us * 3 // 4)]
        return run_replicated_bss(
            prog, int(cfg["replicas"]), scenario_key(cfg), sim_end_us=ends
        )[0]

    def serving_studies(self, prog, cfg):
        import dataclasses

        return "bss", [
            (prog, {}),
            (dataclasses.replace(
                prog, sim_end_us=max(1_300_000, prog.sim_end_us * 3 // 4)
            ), {}),
        ]

    def host_run(self, cfg):
        from tpudes.core import Seconds, Simulator
        from tpudes.core.rng import RngSeedManager

        _reset_world()
        try:
            RngSeedManager.SetRun(int(cfg["rng_run"]))
            _, _, _, rx = self._graph(cfg)
            Simulator.Stop(Seconds(cfg["sim_ms"] / 1e3))
            Simulator.Run()
            out = {"srv_rx": int(rx[0])}
            fr = _recorder_entries()
            if fr:
                out["_flight_recorder"] = fr
            return out
        finally:
            _reset_world()

    def neutral_traffic(self, prog):
        from tpudes.traffic import TrafficProgram

        return TrafficProgram.cbr(prog.start_us, prog.interval_us)

    def host_compare(self, host, dev, cfg):
        # the host graph runs CBR echo apps: with a generative device
        # workload the two sides simulate DIFFERENT arrival processes
        # — host parity for those lives in the dedicated host-mirror
        # parity tests (and the traffic_off exact pair covers the
        # seam); the band below is the cbr-workload contract
        if cfg.get("traffic", "off") not in ("off", "cbr"):
            return None
        # one host RngRun draw against the device replica spread: the
        # fuzz band is the replica min/max widened by a timing-model +
        # Monte-Carlo slack proportional to the offered load (BSS host
        # parity is *statistical* — tests/test_replicated.py pins the
        # distribution-level contract; this band catches gross drift)
        rep = np.asarray(dev["srv_rx"], dtype=np.float64)
        offered = float(_bss_offered(cfg))
        slack = max(6.0, 0.35 * offered)
        lo, hi = rep.min() - slack, rep.max() + slack
        h = float(host["srv_rx"])
        if lo <= h <= hi:
            return None
        return {
            "field": "srv_rx", "index": [],
            "lhs": h, "rhs": [float(rep.min()), float(rep.max())],
        }

    def shrink_moves(self, cfg):
        out = super().shrink_moves(cfg)
        floors = self.envelope.floors
        c = _shrink_int(cfg, "n_stas", floors.get("n_stas", 1))
        if c:
            out.append(("halve n_stas", c))
        c = _shrink_choice(cfg, "interval_ms", 150)
        if c:
            out.append(("slowest traffic", c))
        return out


def _bss_offered(cfg) -> int:
    """Echo requests offered over the horizon (from the config alone)."""
    sim_ms = int(cfg["sim_ms"])
    iv = int(cfg["interval_ms"])
    n = 0
    for i in range(int(cfg["n_stas"])):
        start_ms = 1000 + i  # scenarios.build_bss: 1.0 s + 1 ms stagger
        if sim_ms > start_ms:
            n += (sim_ms - start_ms + iv - 1) // iv
    return n


# ---------------------------------------------------------------------------
# LTE (full-buffer RLC-SM)
# ---------------------------------------------------------------------------


class LteSmFuzzer(EngineFuzzer):
    name = "lte_sm"
    outcome_fields = None  # every field is bit-exact across modes

    @property
    def envelope(self):
        from tpudes.parallel.lte_sm import FUZZ_ENVELOPE

        return FUZZ_ENVELOPE

    def _graph(self, cfg):
        from tpudes.scenarios import build_lena

        return build_lena(
            n_enbs=int(cfg["n_enbs"]),
            ues_per_cell=int(cfg["ues_per_cell"]),
            scheduler=str(cfg["scheduler"]),
            inter_site=float(cfg["inter_site"]),
            layout=str(cfg["layout"]),
            drop_seed=int(cfg["drop_seed"]),
            mobility=str(cfg.get("mob_model", "static")),
            speed=float(cfg.get("mob_speed", 5.0)),
        )

    def build(self, cfg):
        import dataclasses

        from tpudes.parallel.lte_sm import lower_lte_sm

        _reset_world()
        try:
            lte, _ = self._graph(cfg)
            with _quiet_lowering():
                prog = lower_lte_sm(
                    lte, cfg["sim_ms"] / 1e3,
                    geom_stride=int(cfg.get("geom_stride", 1)),
                )
            # ISSUE-14: finite per-UE backlogs from the drawn workload
            # — only on STATIC drops (the engine rejects traffic +
            # mobility on one program; a mobile draw keeps full buffer)
            if prog.mobility is None:
                tp = _fuzz_traffic(
                    cfg, prog.n_ue, prog.n_ttis * 1000, rate_pps=120.0
                )
                if tp is not None:
                    tp = dataclasses.replace(
                        tp,
                        size_pareto=np.asarray(
                            [1.4, 800.0, 12000.0], np.float32
                        ),
                    )
                    prog = dataclasses.replace(prog, traffic=tp)
            return prog
        finally:
            _reset_world()

    def run_scalar(self, prog, cfg, mesh=None):
        from tpudes.parallel.lte_sm import run_lte_sm

        return run_lte_sm(
            prog, scenario_key(cfg), replicas=int(cfg["replicas"]), mesh=mesh
        )

    def run_chunked(self, prog, cfg, canonical):
        from tpudes.parallel.lte_sm import run_lte_sm

        chunk = max(1, prog.n_ttis // int(cfg["chunk_divisor"]) - 1)
        return run_lte_sm(
            prog, scenario_key(cfg), replicas=int(cfg["replicas"]),
            chunk_ttis=chunk,
        )

    def run_sweep0(self, prog, cfg):
        from tpudes.parallel.lte_sm import run_lte_sm

        other = "rr" if prog.scheduler != "rr" else "pf"
        return run_lte_sm(
            prog, scenario_key(cfg), replicas=int(cfg["replicas"]),
            schedulers=[prog.scheduler, other],
        )[0]

    def serving_studies(self, prog, cfg):
        import dataclasses

        other = "rr" if prog.scheduler != "rr" else "pf"
        return "lte_sm", [
            (prog, {}),
            (dataclasses.replace(prog, scheduler=other), {}),
        ]

    #: the common outcome set: a traffic run legitimately ADDS
    #: backlog_bits/goodput_bits/offered_bits, which the traffic=None
    #: side does not have
    traffic_off_fields = (
        "rx_bits", "new_tbs", "retx", "drops", "ok", "cqi", "mcs",
        "sinr",
    )

    def neutral_traffic(self, prog):
        """A saturating cbr fill (1 packet/µs at jumbo sizes): every
        backlog is non-empty from TTI 0, so the dynamic-eligible
        kernel must reproduce the full-buffer program bit for bit.
        None on mobile draws — the engine rejects traffic + mobility
        on one program, so there is no seam to pin there."""
        import dataclasses

        from tpudes.traffic import TrafficProgram

        if prog.mobility is not None:
            return None

        tp = TrafficProgram.cbr(
            np.zeros(prog.n_ue, np.int32),
            np.full(prog.n_ue, 1, np.int64),
        )
        return dataclasses.replace(
            tp,
            size_pareto=np.asarray([0.0, 20000.0, 20000.0], np.float32),
        )

    def extra_pairs(self):
        return super().extra_pairs() + [
            ("pallas_vs_xla", self._pallas_pair),
            ("bf16_budget", self._bf16_pair),
            ("device_geom_off", self._device_geom_pair),
        ]

    def _device_geom_pair(self, prog, cfg, canonical):
        # ISSUE-10: the TPUDES_DEVICE_GEOM=0 fallback runs the mobile
        # scan against HOST-precomputed refresh positions (the
        # per-window fresh-operands shape of the legacy controller
        # path) — pinned bit-equal to the carried geometry.  A static
        # draw has no geometry stage; the pair degenerates to a rerun
        # and still must agree bit for bit.
        with _env("TPUDES_DEVICE_GEOM", "0"):
            off = self.run_scalar(prog, cfg)
        return first_diff(canonical, off)

    def _pallas_pair(self, prog, cfg, canonical):
        # the two lowerings of the fused TTI chain are pinned
        # bit-identical per backend (tests/test_lte_pallas.py) — the
        # fuzzer extends the pin to every in-envelope geometry.  Both
        # sides are forced: unset, the engine picks the lowering by
        # lane count (lte_sm._sm_use_pallas), so the canonical run of
        # a batched draw is already the XLA step and a lone =0 run
        # would compare XLA with XLA
        with _env("TPUDES_PALLAS", "1"):
            kernel = self.run_scalar(prog, cfg)
        with _env("TPUDES_PALLAS", "0"):
            xla = self.run_scalar(prog, cfg)
        return first_diff(canonical, kernel) or first_diff(kernel, xla)

    def _bf16_pair(self, prog, cfg, canonical):
        import dataclasses

        from tpudes.parallel.lte_sm import run_lte_sm

        out = run_lte_sm(
            dataclasses.replace(prog, precision="bf16"),
            scenario_key(cfg), replicas=int(cfg["replicas"]),
        )
        f32_bits = float(np.asarray(canonical["rx_bits"]).sum())
        b16_bits = float(np.asarray(out["rx_bits"]).sum())
        if not np.isfinite(b16_bits):
            return {"field": "rx_bits", "index": [], "lhs": f32_bits,
                    "rhs": b16_bits}
        # fuzz budget: the pinned engine-level bf16 budget (≤10% at the
        # test geometry) widened for arbitrary in-envelope geometries
        if abs(b16_bits - f32_bits) > 0.15 * max(f32_bits, b16_bits, 1.0):
            return {"field": "rx_bits", "index": [], "lhs": f32_bits,
                    "rhs": b16_bits}
        dcqi = np.abs(
            np.asarray(out["cqi"], np.int64)
            - np.asarray(canonical["cqi"], np.int64)
        )
        if dcqi.max() > 1:
            idx = tuple(int(i) for i in np.argwhere(dcqi > 1)[0])
            return {
                "field": "cqi", "index": list(idx),
                "lhs": int(np.asarray(canonical["cqi"])[idx]),
                "rhs": int(np.asarray(out["cqi"])[idx]),
            }
        return None

    def host_run(self, cfg):
        from tpudes.core import Seconds, Simulator

        _reset_world()
        try:
            lte, _ = self._graph(cfg)
            Simulator.Stop(Seconds(cfg["sim_ms"] / 1e3))
            Simulator.Run()
            bits = sum(s["dl_rx_bytes"] for s in lte.GetRlcStats()) * 8
            out = {"rx_bits": int(bits)}
            fr = _recorder_entries()
            if fr:
                out["_flight_recorder"] = fr
            return out
        finally:
            _reset_world()

    def host_compare(self, host, dev, cfg):
        # the host controller runs RLC-SM full buffer: any finite-
        # backlog device workload simulates a different offered load —
        # the traffic_off exact pair covers the seam instead
        if cfg.get("traffic", "off") != "off" and cfg.get(
            "mob_model", "static"
        ) == "static":
            return None
        h = float(host["rx_bits"])
        d = float(np.asarray(dev["rx_bits"]).sum(axis=-1).mean())
        # pinned parity is rel 0.15 at the hand-tuned geometry; random
        # drops can park UEs at CQI boundaries where the documented
        # timing-model deviations bite harder — fuzz band 0.35
        if abs(h - d) <= 0.35 * max(h, d, 1.0) + 1e5:
            return None
        return {"field": "rx_bits", "index": [], "lhs": h, "rhs": d}

    def shrink_moves(self, cfg):
        out = super().shrink_moves(cfg)
        floors = self.envelope.floors
        for name in ("ues_per_cell", "n_enbs"):
            c = _shrink_int(cfg, name, floors.get(name, 1))
            if c:
                out.append((f"halve {name}", c))
        c = _shrink_choice(cfg, "scheduler", "pf")
        if c:
            out.append(("scheduler -> pf", c))
        c = _shrink_choice(cfg, "layout", "line")
        if c:
            out.append(("layout -> line", c))
        return out


# ---------------------------------------------------------------------------
# TCP dumbbell
# ---------------------------------------------------------------------------


class DumbbellFuzzer(EngineFuzzer):
    name = "dumbbell"
    outcome_fields = None

    @property
    def envelope(self):
        from tpudes.parallel.tcp_dumbbell import FUZZ_ENVELOPE

        return FUZZ_ENVELOPE

    def _variants(self, cfg) -> list[str]:
        from tpudes.parallel.tcp_dumbbell import VARIANTS

        n = int(cfg["n_flows"])
        if cfg["variant_mix"] == "homogeneous":
            return [cfg["variant"]] * n
        i0 = VARIANTS.index(cfg["variant"])
        return [VARIANTS[(i0 + i) % len(VARIANTS)] for i in range(n)]

    def _graph(self, cfg):
        from tpudes.scenarios import build_dumbbell

        return build_dumbbell(
            n_flows=int(cfg["n_flows"]),
            sim_time=cfg["sim_ms"] / 1e3,
            variants=self._variants(cfg),
            bottleneck_rate=f"{int(cfg['bottleneck_mbps'])}Mbps",
            bottleneck_delay=f"{int(cfg['bottleneck_delay_ms'])}ms",
            queue=f"{int(cfg['queue_pkts'])}p",
            seg_bytes=int(cfg["seg_bytes"]),
        )

    def build(self, cfg):
        import dataclasses

        from tpudes.parallel.tcp_dumbbell import lower_dumbbell

        _reset_world()
        try:
            self._graph(cfg)
            with _quiet_lowering():
                prog = lower_dumbbell(cfg["sim_ms"] / 1e3)
            # ISSUE-14: app-limited flows — mean offered ~70% of the
            # bottleneck's fair share, so the workload (not just the
            # window) shapes the dynamics without starving the queue
            fair_pps = (
                float(cfg["bottleneck_mbps"]) * 1e6
                / (8.0 * float(cfg["seg_bytes"]))
                / max(int(cfg["n_flows"]), 1)
            )
            tp = _fuzz_traffic(
                cfg, prog.n_flows, int(cfg["sim_ms"]) * 1000,
                rate_pps=0.7 * fair_pps,
            )
            if tp is not None:
                prog = dataclasses.replace(prog, traffic=tp)
            return prog
        finally:
            _reset_world()

    def run_scalar(self, prog, cfg, mesh=None):
        from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

        return run_tcp_dumbbell(
            prog, scenario_key(cfg), replicas=int(cfg["replicas"]), mesh=mesh
        )

    def run_chunked(self, prog, cfg, canonical):
        from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

        chunk = max(1, prog.n_slots // int(cfg["chunk_divisor"]) - 1)
        return run_tcp_dumbbell(
            prog, scenario_key(cfg), replicas=int(cfg["replicas"]),
            chunk_slots=chunk,
        )

    def run_sweep0(self, prog, cfg):
        from tpudes.parallel.tcp_dumbbell import VARIANTS, run_tcp_dumbbell

        p0 = [VARIANTS[i] for i in np.asarray(prog.variant_idx)]
        p1 = ["TcpNewReno"] * prog.n_flows
        return run_tcp_dumbbell(
            prog, scenario_key(cfg), replicas=int(cfg["replicas"]),
            variants=[p0, p1],
        )[0]

    def serving_studies(self, prog, cfg):
        import dataclasses

        from tpudes.parallel.tcp_dumbbell import (
            _variant_ecn,
            _variant_point,
        )

        pt = _variant_point(["TcpNewReno"] * prog.n_flows)
        return "dumbbell", [
            (prog, {}),
            (dataclasses.replace(
                prog, variant_idx=pt, ecn=_variant_ecn(pt)
            ), {}),
        ]

    def host_run(self, cfg):
        from tpudes.core import Seconds, Simulator

        _reset_world()
        try:
            _, sinks = self._graph(cfg)
            sim_s = cfg["sim_ms"] / 1e3
            Simulator.Stop(Seconds(sim_s))
            Simulator.Run()
            span = max(sim_s - 0.1, 1e-3)  # bulk apps start at 0.1 s
            mbps = sum(s.GetTotalRx() * 8.0 / span / 1e6 for s in sinks)
            out = {"goodput_mbps": float(mbps)}
            fr = _recorder_entries()
            if fr:
                out["_flight_recorder"] = fr
            return out
        finally:
            _reset_world()

    def neutral_traffic(self, prog):
        from tpudes.traffic import TrafficProgram

        # 1 segment/µs offered: the app never limits the window, so
        # the app-limit gate must reproduce the bulk program bit for
        # bit
        return TrafficProgram.cbr(
            np.zeros(prog.n_flows, np.int32),
            np.full(prog.n_flows, 1, np.int64),
        )

    def host_compare(self, host, dev, cfg):
        # the host graph runs bulk senders: an app-limited device
        # workload is a different offered load — the traffic_off exact
        # pair covers the seam instead
        if cfg.get("traffic", "off") != "off":
            return None
        h = float(host["goodput_mbps"])
        d = float(np.asarray(dev["goodput_mbps"]).sum(axis=-1).mean())
        cap = float(cfg["bottleneck_mbps"])
        # The pinned rel-0.25 parity (tests/test_tcp_dumbbell.py) holds
        # at the long-horizon low-BDP reference config.  In-envelope
        # high-BDP short-horizon shapes are transient-dominated — the
        # host's loss-recovery convergence takes whole seconds while
        # the slot model fills the pipe from slot 0 (measured rel up to
        # ~0.7 for NewReno at 5 Mbps / 20 ms / 0.9 s) — so the fuzz
        # band is a gross-divergence detector: shared-capacity bound,
        # progress, and a wide relative band.
        diff = {"field": "goodput_mbps", "index": [], "lhs": h, "rhs": d}
        if h > 1.05 * cap or d > 1.05 * cap:
            return diff  # exceeding the shared bottleneck is never right
        if int(cfg["sim_ms"]) > 400 and (h <= 0.0) != (d <= 0.0):
            return diff  # one engine moves traffic, the other is dead
        if abs(h - d) <= 0.75 * max(h, d) + 0.3:
            return None
        return diff

    def shrink_moves(self, cfg):
        out = super().shrink_moves(cfg)
        floors = self.envelope.floors
        c = _shrink_int(cfg, "n_flows", floors.get("n_flows", 1))
        if c:
            out.append(("halve n_flows", c))
        c = _shrink_choice(cfg, "variant_mix", "homogeneous")
        if c:
            out.append(("homogeneous variants", c))
        c = _shrink_choice(cfg, "variant", "TcpNewReno")
        if c:
            out.append(("variant -> NewReno", c))
        return out


# ---------------------------------------------------------------------------
# AS flows (fluid)
# ---------------------------------------------------------------------------


class AsFlowsFuzzer(EngineFuzzer):
    name = "as_flows"
    outcome_fields = None
    #: the fluid outcome chain is float; GSPMD re-rounds fusions under
    #: sharding — the documented mesh tolerance (tests/test_sweep.py)
    mesh_rtol = 2e-5

    @property
    def envelope(self):
        from tpudes.parallel.as_flows import FUZZ_ENVELOPE

        return FUZZ_ENVELOPE

    def _graph(self, cfg):
        from tpudes.scenarios import build_as_network

        return build_as_network(
            n_nodes=int(cfg["n_nodes"]),
            n_flows=int(cfg["n_flows"]),
            sim_time=cfg["sim_ms"] / 1e3,
            flow_kbps=float(cfg["flow_kbps"]),
            pkt_bytes=int(cfg["pkt_bytes"]),
            seed=int(cfg["topo_seed"]),
        )

    def build(self, cfg):
        import dataclasses

        from tpudes.parallel.as_flows import lower_as_flows

        _reset_world()
        try:
            self._graph(cfg)
            with _quiet_lowering():
                prog = lower_as_flows(cfg["sim_ms"] / 1e3)
            # ISSUE-14: the fluid engine consumes the workload's
            # realized/nominal rate multiplier per flow
            tp = _fuzz_traffic(
                cfg, len(prog.src), int(cfg["sim_ms"]) * 1000,
                rate_pps=float(cfg["flow_kbps"]) * 1e3
                / (8.0 * float(cfg["pkt_bytes"])),
            )
            if tp is not None:
                prog = dataclasses.replace(prog, traffic=tp)
            # ISSUE-15: "ste" compiles the straight-through surrogate
            # program — forward pinned bit-equal to the legacy engine
            # (pre-ISSUE-15 corpus configs lack the axis: off)
            if cfg.get("surrogate", "off") == "ste":
                from tpudes.diff.surrogate import Surrogacy

                prog = dataclasses.replace(
                    prog, surrogate=Surrogacy(ste=True)
                )
            return prog
        finally:
            _reset_world()

    def run_scalar(self, prog, cfg, mesh=None):
        from tpudes.parallel.as_flows import run_as_flows

        return run_as_flows(
            prog, scenario_key(cfg), replicas=int(cfg["replicas"]), mesh=mesh
        )

    def run_chunked(self, prog, cfg, canonical):
        from tpudes.parallel.as_flows import run_as_flows

        return run_as_flows(
            prog, scenario_key(cfg), replicas=int(cfg["replicas"]),
            chunk_rounds=int(cfg["chunk_divisor"]),
        )

    def run_sweep0(self, prog, cfg):
        from tpudes.parallel.as_flows import run_as_flows

        return run_as_flows(
            prog, scenario_key(cfg), replicas=int(cfg["replicas"]),
            rate_scale=[1.0, 0.5],
        )[0]

    def serving_studies(self, prog, cfg):
        return "as_flows", [
            (prog, {"rate_scale": 1.0}),
            (prog, {"rate_scale": 0.5}),
        ]

    def host_run(self, cfg):
        from tpudes.core import Seconds, Simulator

        _reset_world()
        try:
            _, servers = self._graph(cfg)
            sim_s = cfg["sim_ms"] / 1e3
            Simulator.Stop(Seconds(sim_s))
            Simulator.Run()
            out = {"rx": [int(s.received) for s in servers]}
            fr = _recorder_entries()
            if fr:
                out["_flight_recorder"] = fr
            return out
        finally:
            _reset_world()

    def neutral_traffic(self, prog):
        from tpudes.traffic import TrafficProgram

        # any cbr program: the fluid multiplier is exactly 1.0 for the
        # cbr branch by construction
        return TrafficProgram.cbr(
            np.zeros(len(prog.src), np.int32),
            np.full(len(prog.src), 1000, np.int64),
        )

    def host_compare(self, host, dev, cfg):
        # the host graph runs constant-rate UdpClients: a generative
        # device workload offers a different load — the traffic_off
        # exact pair covers the seam (cbr's multiplier is exactly 1,
        # so the cbr draw keeps the band meaningful)
        if cfg.get("traffic", "off") not in ("off", "cbr"):
            return None
        sim_s = cfg["sim_ms"] / 1e3
        interval_s = int(cfg["pkt_bytes"]) * 8.0 / (cfg["flow_kbps"] * 1e3)
        expected = (sim_s - 0.05) / interval_s  # clients start at 0.05 s
        frac = np.asarray(dev["delivered_frac"]).mean(axis=0)  # (F,)
        rx = np.asarray(host["rx"], dtype=np.float64)
        # sparse-regime contract: where the fluid engine says a flow
        # delivers (frac ~ 1) the packet DES must deliver most of its
        # offered packets, and vice versa (multi-hop in-flight slack)
        for f in range(len(rx)):
            host_frac = rx[f] / max(expected, 1.0)
            if frac[f] > 0.95 and host_frac < 0.7:
                return {"field": "delivered_frac", "index": [f],
                        "lhs": host_frac, "rhs": float(frac[f])}
            if frac[f] < 0.5 and host_frac > 0.9:
                return {"field": "delivered_frac", "index": [f],
                        "lhs": host_frac, "rhs": float(frac[f])}
        return None

    def _surrogate_off_pair(self, prog, cfg, canonical):
        """ISSUE-15 exactness anchor: the straight-through surrogate
        program (hard forward, soft backward) must match the legacy
        (surrogate=None) engine bit for bit — generalized over the
        whole envelope, whatever surrogate the config drew.  The
        surrogate=None side IS the canonical run when the scenario
        drew 'off' (reused, not recomputed)."""
        import dataclasses

        from tpudes.diff.surrogate import Surrogacy

        off = canonical if prog.surrogate is None else self.run_scalar(
            dataclasses.replace(prog, surrogate=None), cfg
        )
        ste = self.run_scalar(
            dataclasses.replace(prog, surrogate=Surrogacy(ste=True)),
            cfg,
        )
        return first_diff(off, ste)

    def extra_pairs(self):
        return super().extra_pairs() + [
            ("surrogate_off", self._surrogate_off_pair)
        ]

    def shrink_moves(self, cfg):
        out = super().shrink_moves(cfg)
        floors = self.envelope.floors
        for name in ("n_flows", "n_nodes"):
            c = _shrink_int(cfg, name, floors.get(name, 1))
            if c:
                out.append((f"halve {name}", c))
        if cfg.get("surrogate", "off") != "off":
            c = _shrink_choice(cfg, "surrogate", "off")
            if c:
                out.append(("surrogate -> off", c))
        return out


# ---------------------------------------------------------------------------
# Wired graph (per-link queues — the hybrid-PDES partition unit)
# ---------------------------------------------------------------------------


class WiredFuzzer(EngineFuzzer):
    """The hybrid-capable wired engine: deterministic CBR over
    per-link queues, so every oracle pair here is EXACT (bit-equal
    timestamps) — including the ``hybrid_vs_host`` pair, which runs the
    full 2-rank granted-time-window protocol (in-process fabric: the
    identical advance/operand sequence the spawned-rank transport
    issues) against both the single-engine device run and the
    sequential host DES."""

    name = "wired"
    outcome_fields = ("deliver_slot", "delivered", "served")
    # no config-sweep axis on the wired engine (yet), so the swept /
    # serving pairs cannot run; chunking, bucketing and mesh sharding
    # all apply
    cross_mode_pairs = ("chunked_vs_single", "bucketing_off",
                        "mesh_vs_single")

    @property
    def envelope(self):
        from tpudes.parallel.wired import FUZZ_ENVELOPE

        return FUZZ_ENVELOPE

    def build(self, cfg):
        from tpudes.parallel.wired import wired_chain

        L = int(cfg["n_links"])
        return wired_chain(
            n_links=L,
            n_flows=int(cfg["n_flows"]),
            service=[1 + (i % int(cfg["max_service"])) for i in range(L)],
            period=int(cfg["period"]),
            n_slots=int(cfg["n_slots"]),
            ranks=2,
            boundary_delay=int(cfg["boundary_delay"]),
            jitter_slots=int(cfg["jitter"]),
        )

    def run_scalar(self, prog, cfg, mesh=None):
        from tpudes.parallel.wired import run_wired

        return run_wired(
            prog, scenario_key(cfg), int(cfg["replicas"]), mesh=mesh
        )

    def run_chunked(self, prog, cfg, canonical):
        from tpudes.parallel.wired import run_wired

        # an off-boundary window size, mimicking a mid-stream grant cut
        window = max(1, int(cfg["n_slots"]) // 3 - 1)
        return run_wired(
            prog, scenario_key(cfg), int(cfg["replicas"]),
            window_slots=window,
        )

    def _jitter_rows(self, prog, cfg):
        from tpudes.parallel.wired import _replica_jitter

        return np.asarray(_replica_jitter(
            prog, scenario_key(cfg), int(cfg["replicas"])
        ))

    def host_run(self, cfg):
        from tpudes.parallel.wired import run_wired_host

        prog = self.build(cfg)
        jit = self._jitter_rows(prog, cfg)
        # the host DES is cheap: run EVERY replica's jitter trajectory
        rows = [
            run_wired_host(prog, jitter=jit[r])
            for r in range(int(cfg["replicas"]))
        ]
        return dict(
            deliver_slot=np.stack([r["deliver_slot"] for r in rows]),
            served=np.stack([r["served"] for r in rows]),
        )

    def host_compare(self, host, dev, cfg):
        # deterministic model: the host DES and the device engine must
        # agree on every timestamp — exact, not a fuzz band
        return first_diff(
            {k: host[k] for k in ("deliver_slot", "served")},
            {k: np.asarray(dev[k]) for k in ("deliver_slot", "served")},
        )

    def extra_pairs(self):
        def hybrid_vs_host(prog, cfg, canonical):
            from tpudes.parallel.hybrid import run_hybrid

            hybrid = run_hybrid(
                prog, scenario_key(cfg), int(cfg["replicas"]),
                ranks=2, transport="local",
            )
            diff = first_diff(
                canonical, hybrid, fields=self.outcome_fields
            )
            if diff is not None:
                return diff
            host = self.host_run(cfg)
            return first_diff(
                {k: host[k] for k in ("deliver_slot", "served")},
                {k: np.asarray(hybrid[k]) for k in ("deliver_slot", "served")},
            )

        # super() keeps the base traffic_off pair on the roster (it
        # passes trivially until the wired engine grows a traffic
        # seam, at which point the oracle arms itself)
        return super().extra_pairs() + [
            ("hybrid_vs_host", hybrid_vs_host)
        ]

    def shrink_moves(self, cfg):
        out = super().shrink_moves(cfg)
        floors = self.envelope.floors
        for name in ("n_slots", "n_flows", "n_links"):
            c = _shrink_int(cfg, name, floors.get(name, 1))
            if c:
                out.append((f"halve {name}", c))
        c = _shrink_choice(cfg, "jitter", 0)
        if c:
            out.append(("no jitter", c))
        return out


#: engine name -> fuzzer (the registry the harness and CLI iterate)
ENGINE_FUZZERS: dict[str, EngineFuzzer] = {
    f.name: f
    for f in (BssFuzzer(), LteSmFuzzer(), DumbbellFuzzer(), AsFlowsFuzzer(),
              WiredFuzzer())
}
