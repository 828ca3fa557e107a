"""Benchmark: engine vs engine on the BASELINE scenarios.

Numerator: the SAME constructed object graph lowered onto the replica
axis (tpudes/parallel/lift.py) and run on the accelerator —
``JaxSimulatorImpl``'s lifted path.  Denominator: ``DefaultSimulatorImpl``
executing the identical scenario's scalar event loop on the host.
Both sides are *scenario-level* sim-seconds per wall-second; the ratio
is the engine speedup the north star asks for (BASELINE.json: "one
GlobalValue flag flips a stock scenario onto the TPU").

Four scenarios:
  - BSS (BASELINE config #3): 64-STA infrastructure WiFi, UDP echo,
    512 Monte-Carlo replicas at once (the headline metric).
  - LTE (BASELINE config #4): 7 eNB x 210 UE full-buffer hex grid,
    64 replicas of 10 simulated seconds on the device SM engine vs the
    host per-TTI controller loop.
  - TCP dumbbell (BASELINE config #2): 8 bulk flows over a 10 Mbps
    bottleneck, 256 replicas of 20 simulated seconds on the packet-slot
    engine vs the host socket stack.
  - AS topology (BASELINE config #5): BRITE-style BA graph, 10k nodes,
    128 sparse CBR flows, 1024 replicas on the flow engine vs one host
    packet-level run of the same scenario.  The flow engine computes the
    converged steady-state outcome directly (its cost does not scale
    with simulated seconds), so this line reports **studies/s** — one
    study = one replica's complete traffic outcome — not sim-s/wall-s;
    the host side's study is its AS_HOST_S packet-level integration.

Two sweep rows ride on top of the LTE and TCP scenarios (r6):
  - lte_sched_sweep: the SAME lowered hex grid through all NINE FF-MAC
    schedulers.  The scheduler id is a traced operand of the compiled
    program, so the sweep pays ONE compile; the row reports the whole-
    family wall time and asserts the single-executable property.
  - tcp_variant_sweep: one dumbbell with 17 flows, one per
    TcpCongestionOps variant — the full family in one fused program.

Timing protocol: the device side compiles once, then runs N_TIMED=5
timed repetitions with distinct PRNG keys; the reported value is the
MEDIAN with min/max spread (rounds 1-3 reported single-shot numbers,
whose ±20% drift was indistinguishable from real regressions — the
spread now makes the noise visible).  The host side runs once (its
wall time is deterministic within a few percent) after a warm-up
segment so JIT compilation of the TTI kernel is excluded on both sides.

Strong-scaling rows (PR 4): ``bench_mesh()`` runs each engine's SAME
program on a 1-device mesh vs the full mesh and reports rate, wall
medians, speedup and per-configuration compile counts.  The section
rides the default output whenever more than one device is visible, and
``--mesh [--smoke]`` emits it standalone (the CI virtual-device job and
the MULTICHIP harness both use that path).  The timed repetitions ride
``RUNTIME.submit`` — the async in-flight window — so the rows measure
pipelined steady-state throughput, not launch+sync round trips.

ISSUE-6 rows:
  - the `lte` row carries `ttis_per_wall_s` + the pallas/precision
    flags.

ISSUE-5 rows:
  - sweep_vectorized: the 8-point LTE scheduler sweep and 8-point TCP
    variant sweep as ONE config-axis (C, R, …) launch vs 8 per-point
    launches of the same executable — the one-launch rate must be >=
    the per-point rate on every platform, and the row carries the
    launch/compile counters that pin the single-launch property.
  - pipeline_overlap: a heterogeneous 6-horizon LTE sweep dispatched
    blocking vs through RUNTIME.submit; reports both walls and the
    max_in_flight telemetry.
  - mesh_config_sweep (with --mesh): a 2-point scheduler sweep on the
    full mesh — megabatching composed with replica sharding.

ISSUE-7 row:
  - serving_closed_loop: a closed-loop multi-tenant client pool driving
    the StudyServer (tpudes/serving) vs serialized RUNTIME.submit of
    the same study stream — requests/s at bounded p99 study latency,
    the first metric that models many concurrent users rather than one
    batch job.  Coalesced serving must be >= 2x serialized throughput
    at equal (bit-pinned) results.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N_STAS = 64
WIFI_REPLICAS = 512
WIFI_SIM_S = 2.0
WIFI_HT_SIM_S = 2.0
WIFI_HT_INTERVAL_S = 0.01
LTE_ENBS = 7
LTE_UES_PER_CELL = 30
LTE_REPLICAS = 64
LTE_SIM_S = 10.0
LTE_HOST_WARM_S = 0.01
LTE_HOST_MEAS_S = 0.04
TCP_FLOWS = 8
TCP_REPLICAS = 256
TCP_SIM_S = 20.0
TCP_HOST_S = 5.0
AS_NODES = 10_000
AS_FLOWS = 128
AS_REPLICAS = 1024
AS_SIM_S = 10.0
AS_HOST_S = 2.0
N_TIMED = 5


def _bench_bss(sim_s, **build_kwargs):
    """Shared BSS harness: scalar denominator + replica-engine numerator
    on the SAME object graph, so the legacy and HT WiFi lines are
    measured identically."""
    import jax

    from tpudes.core import Seconds, Simulator
    from tpudes.core.world import reset_world
    from tpudes.parallel.replicated import lower_bss, run_replicated_bss
    from tpudes.scenarios import build_bss

    reset_world()
    sta_devices, ap_device, clients, _ = build_bss(N_STAS, sim_s, **build_kwargs)
    n = sta_devices.GetN()
    prog = lower_bss(
        [sta_devices.Get(i) for i in range(n)], ap_device, clients, sim_s
    )

    # --- denominator: DefaultSimulatorImpl on the same graph ------------
    t0 = time.monotonic()
    Simulator.Stop(Seconds(sim_s))
    Simulator.Run()
    scalar_wall = time.monotonic() - t0
    scalar_events = Simulator.GetEventCount()
    reset_world()
    scalar_rate = sim_s / scalar_wall

    # --- numerator: replica engine, median of N_TIMED ---------------------
    run_replicated_bss(prog, WIFI_REPLICAS, jax.random.PRNGKey(0))  # compile
    walls, delivered = [], 0
    for i in range(N_TIMED):
        t0 = time.monotonic()
        out = run_replicated_bss(prog, WIFI_REPLICAS, jax.random.PRNGKey(1 + i))
        walls.append(time.monotonic() - t0)
        delivered += int(out["srv_rx"].sum())
        assert out["all_done"]
    med = statistics.median(walls)
    rate = WIFI_REPLICAS * sim_s / med
    return prog, dict(
        sim_s_per_wall_s=rate,
        vs_scalar=rate / scalar_rate,
        wall_median_s=med,
        wall_min_s=min(walls),
        wall_max_s=max(walls),
        scalar_sim_s_per_wall_s=scalar_rate,
        scalar_events_per_s=scalar_events / scalar_wall,
        srv_rx_mean=delivered / (N_TIMED * WIFI_REPLICAS),
    )


def bench_wifi():
    _, out = _bench_bss(WIFI_SIM_S)
    return out


def bench_wifi_ht():
    """The 802.11n line: same BSS shape, HT rates + QoS + A-MPDU under
    BlockAck, at an offered load (512 B / 10 ms per STA, doubled by
    echoes) that saturates single-MPDU exchanges so aggregation is
    actually exercised on both engines."""
    prog, out = _bench_bss(
        WIFI_HT_SIM_S, interval_s=WIFI_HT_INTERVAL_S,
        data_mode="HtMcs7", standard="80211n",
    )
    assert prog.max_mpdus > 1, "HT bench must exercise aggregation"
    out["max_mpdus"] = prog.max_mpdus
    return out


def bench_lte():
    import jax

    from tpudes.core import Seconds, Simulator
    from tpudes.core.world import reset_world
    from tpudes.parallel.lte_sm import lower_lte_sm, run_lte_sm
    from tpudes.scenarios import build_lena

    reset_world()
    lte, _ = build_lena(LTE_ENBS, LTE_UES_PER_CELL)
    prog = lower_lte_sm(lte, LTE_SIM_S)

    # --- denominator: the host per-TTI controller loop -------------------
    # warm-up segment first so the TTI kernel's jit compile is excluded,
    # then a measured segment (the host path is linear in TTIs)
    Simulator.Stop(Seconds(LTE_HOST_WARM_S))
    Simulator.Run()
    t0 = time.monotonic()
    Simulator.Stop(Seconds(LTE_HOST_MEAS_S))
    Simulator.Run()
    host_wall = time.monotonic() - t0
    reset_world()
    host_rate = LTE_HOST_MEAS_S / host_wall

    # --- numerator: device SM engine, median of N_TIMED -------------------
    run_lte_sm(prog, jax.random.PRNGKey(0), replicas=LTE_REPLICAS)  # compile
    walls, bits = [], 0
    for i in range(N_TIMED):
        t0 = time.monotonic()
        out = run_lte_sm(
            prog, jax.random.PRNGKey(1 + i), replicas=LTE_REPLICAS
        )
        walls.append(time.monotonic() - t0)
        bits += int(out["rx_bits"].sum())
    med = statistics.median(walls)
    rate = LTE_REPLICAS * LTE_SIM_S / med
    ues = LTE_REPLICAS * LTE_ENBS * LTE_UES_PER_CELL
    from tpudes.parallel.kernels_pallas import pallas_enabled

    return dict(
        sim_s_per_wall_s=rate,
        vs_scalar=rate / host_rate,
        wall_median_s=med,
        wall_min_s=min(walls),
        wall_max_s=max(walls),
        scalar_sim_s_per_wall_s=host_rate,
        # ISSUE-6: where the TTI budget goes, not just that it is spent
        ttis_per_wall_s=LTE_REPLICAS * prog.n_ttis / med,
        pallas=pallas_enabled(),
        precision=prog.precision,
        agg_dl_mbps=bits / N_TIMED / LTE_REPLICAS / LTE_SIM_S / 1e6,
        # tpudes.obs device accumulators (last timed run, per-UE means)
        obs_grants_per_ue=float(out["new_tbs"].sum()) / ues,
        obs_harq_retx_per_ue=float(out["retx"].sum()) / ues,
        obs_harq_drops_per_ue=float(out["drops"].sum()) / ues,
    )


def bench_mobile_bss(smoke: bool = False):
    """ISSUE-10 row: a MOVING BSS topology on the device engine.

    Three measurements on the same scenario shape:
    - ``host``: the scalar host DES on the mobile graph — the rate any
      mobile topology ran at while the engines refused mobility (the
      host-geometry-refresh baseline);
    - ``static``: the device engine on the frozen (t=0) geometry — the
      ceiling the mobile engine is compared against;
    - ``mobile``: the device engine with the geometry stage in the scan
      carry at ``geom_stride``.

    Acceptance: mobile >= 5x the host baseline at <= 1.5x the static
    wall (CPU reference shape); the row carries the geometry-refresh
    counters so the artifact PROVES which regime ran."""
    import jax

    from tpudes.core import Seconds, Simulator
    from tpudes.core.world import reset_world
    from tpudes.obs.geometry import GeomTelemetry
    from tpudes.parallel.replicated import lower_bss, run_replicated_bss
    from tpudes.scenarios import build_bss

    n_stas = 8 if smoke else N_STAS
    sim_s = 1.4 if smoke else WIFI_SIM_S
    replicas = 32 if smoke else WIFI_REPLICAS
    stride = 8
    speed = 1.0

    def _lowered(mobility):
        reset_world()
        stas, ap, clients, _ = build_bss(
            n_stas, sim_s, mobility=mobility, speed=speed
        )
        prog = lower_bss(
            [stas.Get(i) for i in range(n_stas)], ap, clients, sim_s,
            geom_stride=stride,
        )
        return prog

    # --- host baseline: the mobile graph on the scalar DES ---------------
    prog_m = _lowered("const_velocity")
    t0 = time.monotonic()
    Simulator.Stop(Seconds(sim_s))
    Simulator.Run()
    host_rate = sim_s / (time.monotonic() - t0)
    prog_s = _lowered("static")
    reset_world()

    def _timed(prog):
        run_replicated_bss(prog, replicas, jax.random.PRNGKey(0))  # compile
        walls = []
        for i in range(N_TIMED):
            t0 = time.monotonic()
            out = run_replicated_bss(prog, replicas, jax.random.PRNGKey(1 + i))
            walls.append(time.monotonic() - t0)
            assert out["all_done"]
        return statistics.median(walls), out

    GeomTelemetry.reset()
    static_wall, _ = _timed(prog_s)
    mobile_wall, mout = _timed(prog_m)
    mobile_rate = replicas * sim_s / mobile_wall
    return dict(
        sim_s_per_wall_s=mobile_rate,
        static_sim_s_per_wall_s=replicas * sim_s / static_wall,
        host_sim_s_per_wall_s=host_rate,
        # the two acceptance ratios
        vs_host_refresh=mobile_rate / host_rate,
        wall_vs_static=mobile_wall / static_wall,
        wall_median_s=mobile_wall,
        geom_stride=stride,
        mob_model=prog_m.mobility.model,
        speed_mps=speed,
        # per-run geometry accounting (last timed mobile run) + the
        # process-cumulative telemetry the obs schema gate validates
        geom_refreshes=mout["geom_refreshes"],
        steps=mout["steps"],
        geom_telemetry=GeomTelemetry.engine("bss"),
        replicas=replicas,
        n_stas=n_stas,
    )


def bench_lte_mobility(smoke: bool = False):
    """ISSUE-10 row, LTE side: moving UEs through the SM engine's
    device geometry stage vs (a) the host TTI controller on the same
    mobile graph — whose every TTI pays the host geometry refresh that
    used to be the ONLY way to run mobile LTE — and (b) the device
    engine on the frozen drop (the static-geometry ceiling)."""
    import jax

    from tpudes.core import Seconds, Simulator
    from tpudes.core.world import reset_world
    from tpudes.obs.geometry import GeomTelemetry
    from tpudes.parallel.lte_sm import lower_lte_sm, run_lte_sm
    from tpudes.scenarios import build_lena

    n_enbs, upc = (2, 4) if smoke else (LTE_ENBS, LTE_UES_PER_CELL)
    sim_s = 0.3 if smoke else LTE_SIM_S
    replicas = 8 if smoke else LTE_REPLICAS
    stride = 8
    speed = 10.0

    reset_world()
    lte, _ = build_lena(
        n_enbs, upc, mobility="const_velocity", speed=speed
    )
    prog_m = lower_lte_sm(lte, sim_s, geom_stride=stride)
    # host baseline: the controller's TTI loop on the SAME mobile graph
    # (per-TTI host geometry refresh); warm segment excludes the jit
    Simulator.Stop(Seconds(LTE_HOST_WARM_S))
    Simulator.Run()
    t0 = time.monotonic()
    Simulator.Stop(Seconds(LTE_HOST_WARM_S + LTE_HOST_MEAS_S))
    Simulator.Run()
    host_rate = LTE_HOST_MEAS_S / (time.monotonic() - t0)
    reset_world()
    lte, _ = build_lena(n_enbs, upc)  # same drop, frozen
    prog_s = lower_lte_sm(lte, sim_s)
    reset_world()

    def _timed(prog):
        run_lte_sm(prog, jax.random.PRNGKey(0), replicas=replicas)
        walls = []
        for i in range(N_TIMED):
            t0 = time.monotonic()
            out = run_lte_sm(
                prog, jax.random.PRNGKey(1 + i), replicas=replicas
            )
            walls.append(time.monotonic() - t0)
        return statistics.median(walls), out

    GeomTelemetry.reset()
    static_wall, _ = _timed(prog_s)
    mobile_wall, mout = _timed(prog_m)
    mobile_rate = replicas * sim_s / mobile_wall
    return dict(
        sim_s_per_wall_s=mobile_rate,
        static_sim_s_per_wall_s=replicas * sim_s / static_wall,
        host_sim_s_per_wall_s=host_rate,
        vs_host_refresh=mobile_rate / host_rate,
        wall_vs_static=mobile_wall / static_wall,
        wall_median_s=mobile_wall,
        ttis_per_wall_s=replicas * prog_m.n_ttis / mobile_wall,
        geom_stride=stride,
        mob_model=prog_m.mobility.model,
        speed_mps=speed,
        geom_refreshes=mout["geom_refreshes"],
        geom_telemetry=GeomTelemetry.engine("lte_sm"),
        replicas=replicas,
        n_enbs=n_enbs,
        ues_per_cell=upc,
    )


def bench_traffic_burst(smoke: bool = False):
    """ISSUE-14 row: the device-resident traffic stage as a metric.

    Three measurements on one BSS program:

    - ``stage_overhead``: the neutral cbr WORKLOAD program (identical
      arrivals through the traffic stage's traced dispatch) vs
      ``traffic=None`` (the legacy advance) — the pure cost of
      compiling the model-family dispatch in;
    - ``burst_overhead``: a bursty ON-OFF workload vs the cbr
      workload at MATCHED mean load, normalized per retired event
      step — the acceptance bar is <= 1.5x.  (Clustered arrivals
      legitimately serialize more steps — same-instant contention —
      so the raw ``burst_wall_ratio`` rides the row unguarded and
      the gate bounds what the stage costs per step.);
    - the one-launch WORKLOAD sweep: 8 mixed cbr/mmpp/onoff/trace
      points (shape-unified, `toy_traffic_points`) as ONE (C, R, …)
      launch — launches must be 1, fresh compiles during the timed
      call 0, and the demux bit-equal to per-point launches.

    The row embeds the :class:`TrafficTelemetry` snapshot so the
    artifact PROVES which models ran.
    """
    import dataclasses

    import jax
    import numpy as np

    from tpudes.obs.device import CompileTelemetry
    from tpudes.obs.traffic import TrafficTelemetry
    from tpudes.parallel.programs import toy_bss_program, toy_traffic_points
    from tpudes.parallel.replicated import run_replicated_bss
    from tpudes.parallel.runtime import RUNTIME
    from tpudes.traffic.host import offered_packets

    # smoke shapes stay big enough that the wall ratio measures the
    # engine, not dispatch jitter (the CI gate pins ratio <= 1.5)
    n_stas = 4 if smoke else 8
    sim_s = 1.2 if smoke else 1.5
    replicas = 32 if smoke else 64
    reps = N_TIMED
    from tpudes.traffic import TrafficProgram, bounded_pareto_mean

    prog = toy_bss_program(n_sta=n_stas, sim_end_us=int(sim_s * 1e6))
    pts = toy_traffic_points(
        prog.n, prog.sim_end_us, start_us=prog.start_us,
        beacon=(int(prog.interval_us[0]), int(prog.start_us[0])),
    )
    cbr_prog = dataclasses.replace(prog, traffic=pts[0])
    # the burst program offers the SAME mean load as the cbr one
    # (peak = rate / duty), so the wall ratio measures burstiness —
    # gap dispatch + arrival clustering — not extra workload volume
    on, off_s = (1.5, 0.05, 0.3), 0.1
    duty = bounded_pareto_mean(*on) / (bounded_pareto_mean(*on) + off_s)
    sta_rate = 1e6 / float(prog.interval_us[1])
    burst_tp = TrafficProgram.onoff(
        prog.n, sta_rate / duty, horizon_us=prog.sim_end_us, on=on,
        off_mean_s=off_s, start_us=prog.start_us, tr_seed=1,
    ).with_cbr_rows(
        np.arange(prog.n) == 0, int(prog.interval_us[0]),
        int(prog.start_us[0]),
    )
    burst_prog = dataclasses.replace(prog, traffic=burst_tp)

    def timed(fn):
        # MIN of the repetitions, not the bench's usual median: this
        # row's deliverable is a RATIO gated in CI, and at CPU-smoke
        # walls (tens of ms) one scheduler hiccup on the numerator or
        # denominator alone flakes the gate — the minimum is the
        # noise-floor estimator for a single-process ratio
        fn(jax.random.PRNGKey(0))  # compile + warm
        walls = []
        for i in range(reps):
            t0 = time.monotonic()
            fn(jax.random.PRNGKey(1 + i))
            walls.append(time.monotonic() - t0)
        return min(walls)

    outs = {}

    def runner(name, p):
        def fn(k):
            outs[name] = run_replicated_bss(p, replicas, k)

        return fn

    wall_none = timed(runner("none", prog))
    wall_cbr = timed(runner("cbr", cbr_prog))
    wall_burst = timed(runner("burst", burst_prog))
    # clustered arrivals legitimately serialize MORE event steps at the
    # same mean load (same-instant contention → extra backoff/retry
    # events) — that is workload physics, not stage cost.  The gated
    # overhead is therefore PER RETIRED STEP: wall ratio divided by
    # step-count ratio, the cost the traffic stage adds to each event
    # the vector loop executes.  The raw wall ratio rides the row too.
    step_ratio = max(
        int(outs["burst"]["steps"]) / max(int(outs["cbr"]["steps"]), 1),
        1e-9,
    )

    # --- one-launch workload sweep (the acceptance criterion) ------------
    key = jax.random.PRNGKey(99)
    per = [
        run_replicated_bss(
            dataclasses.replace(prog, traffic=tp), replicas, key
        )
        for tp in pts
    ]
    run_replicated_bss(cbr_prog, replicas, key, traffic_sweep=pts)  # warm
    l0 = RUNTIME.launches("bss")
    c0 = CompileTelemetry.compiles("bss")
    t0 = time.monotonic()
    swept = run_replicated_bss(
        cbr_prog, replicas, key, traffic_sweep=pts
    )
    sweep_wall = time.monotonic() - t0
    demux_equal = all(
        np.array_equal(np.asarray(a[f]), np.asarray(b[f]))
        for a, b in zip(per, swept)
        for f in ("srv_rx", "cli_rx", "tx_data", "drops")
    )

    # workload telemetry: offered from the host mirror of the device
    # cum kernel, delivered from the burst run's outcome counters
    res = outs["burst"]
    offered = float(
        np.floor(
            offered_packets(burst_prog.traffic, prog.sim_end_us)[1:]
        ).sum()
    ) * replicas
    TrafficTelemetry.record(
        "bss", "onoff",
        offered=offered,
        delivered=float(np.asarray(res["srv_rx"], np.int64).sum()),
        unit="packets",
        duty=float(
            np.clip(
                burst_prog.traffic.rate_pps[1:].sum()
                / max(float(burst_prog.traffic.peak_pps[1:].sum()), 1e-9),
                0.0, 1.0,
            )
        ),
    )

    return dict(
        replicas=replicas,
        sim_s=sim_s,
        wall_none_s=round(wall_none, 4),
        wall_cbr_s=round(wall_cbr, 4),
        wall_burst_s=round(wall_burst, 4),
        stage_overhead=round(wall_cbr / wall_none, 3),
        burst_steps=int(outs["burst"]["steps"]),
        cbr_steps=int(outs["cbr"]["steps"]),
        burst_wall_ratio=round(wall_burst / wall_cbr, 3),
        # the CI-gated bound (<= 1.5): per-step wall overhead of the
        # bursty workload vs cbr at matched mean load
        burst_overhead=round(wall_burst / wall_cbr / step_ratio, 3),
        sweep_points=len(pts),
        sweep_wall_s=round(sweep_wall, 4),
        sweep_launches=RUNTIME.launches("bss") - l0,       # must be 1
        sweep_compiles_timed=CompileTelemetry.compiles("bss") - c0,  # 0
        sweep_demux_bit_equal=bool(demux_equal),
        smoke=smoke,
        traffic_telemetry=TrafficTelemetry.snapshot()["engines"].get(
            "bss", {}
        ),
    )


def bench_grad_calibration(smoke: bool = False):
    """ISSUE-15 row: optimization-as-a-service as a metric.

    Two measurements:

    - the LTE calibration demo — plant a propagation exponent,
      observe per-UE CQIs through the differentiable expected-KPI
      chain, recover it by L-BFGS-lite descent.  The WHOLE descent is
      one compiled ``lax.scan``: ``descent_launches`` must be 1 and
      ``descent_compiles_timed`` 0 on the timed (warm) run; the row
      carries the loss-vs-iteration curve (subsampled) and the
      recovered-parameter relative error (acceptance <= 2 %);
    - a C-point grad-of-sweep batch on the AS engine (vmap-of-grad
      over the offered-load axis) — ``grad_sweep_launches`` must be 1
      with 0 timed compiles (the one-executable contract).

    The row embeds the :class:`GradTelemetry` snapshot so the
    artifact PROVES the descent ran (step counts, grad-norm rings,
    the non-finite canary at zero).
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudes.diff import Surrogacy, calibrate_lte, grad_as_flows
    from tpudes.diff.lte_grad import build_lte_diff, lte_default_params
    from tpudes.obs.device import CompileTelemetry
    from tpudes.obs.grad import GradTelemetry
    from tpudes.parallel.lte_sm import LteSmProgram
    from tpudes.parallel.programs import toy_as_program
    from tpudes.parallel.runtime import RUNTIME

    key = jax.random.PRNGKey(15)
    n_ue = 6 if smoke else 12
    E = 2 if smoke else 3
    steps = 60 if smoke else 120
    serving = (np.arange(n_ue) % E).astype(np.int32)
    rng = np.random.default_rng(3)
    enb_pos = np.asarray(
        [[600.0 * i, 0.0, 30.0] for i in range(E)], np.float32
    )
    ue_pos = (
        enb_pos[serving]
        + np.c_[rng.uniform(-220, 220, n_ue),
                rng.uniform(-220, 220, n_ue),
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
        pathloss=("log_distance", 3.0, 1.0, 46.67),
    )
    planted = 3.45
    kpi = jax.jit(build_lte_diff(prog, Surrogacy()))
    p = lte_default_params(prog, {"ue_pos": ue_pos})
    p["ploss"] = jnp.asarray([planted, 1.0, 46.67], jnp.float32)
    observed = np.asarray(kpi(p)["cqi"])

    def run_calibration():
        return calibrate_lte(
            prog, key, observed, wrt=("ploss",), at={"ue_pos": ue_pos},
            steps=steps, lr=0.5, loss="cqi_mse", opt="lbfgs",
        )

    run_calibration()  # compile + warm the descent program
    l0 = RUNTIME.launches("diff_lte")
    c0 = CompileTelemetry.compiles("diff_lte")
    t0 = time.monotonic()
    res = run_calibration()
    wall = time.monotonic() - t0
    descent_launches = RUNTIME.launches("diff_lte") - l0
    descent_compiles = CompileTelemetry.compiles("diff_lte") - c0
    rel_err = abs(float(res.params["ploss"][0]) - planted) / planted

    # C-point grad-of-sweep on the AS engine: one launch, one grad per
    # sweep point
    as_prog = dataclasses.replace(
        toy_as_program(n_nodes=24 if smoke else 48, n_flows=3),
        surrogate=Surrogacy(),
    )
    scales = [0.5, 1.0, 2.0, 4.0]
    grad_as_flows(
        as_prog, key, 8, loss="neg_goodput", rate_scale=scales
    )  # warm
    l0 = RUNTIME.launches("diff_as")
    c0 = CompileTelemetry.compiles("diff_as")
    sweep = grad_as_flows(
        as_prog, key, 8, loss="neg_goodput", rate_scale=scales
    )
    sweep_launches = RUNTIME.launches("diff_as") - l0
    sweep_compiles = CompileTelemetry.compiles("diff_as") - c0

    curve = res.loss[:: max(1, steps // 12)].tolist() + [
        float(res.loss[-1])
    ]
    return {
        "engine": "diff_lte",
        "opt": res.opt,
        "steps": res.steps,
        "wall_s": round(wall, 4),
        "steps_per_s": round(res.steps / wall, 1),
        "loss_first": float(res.loss[0]),
        "loss_final": float(res.loss[-1]),
        "loss_curve": [round(v, 8) for v in curve],
        "planted_exponent": planted,
        "recovered_exponent": round(float(res.params["ploss"][0]), 5),
        "recovered_rel_err": round(rel_err, 6),
        "descent_launches": descent_launches,       # must be 1
        "descent_compiles_timed": descent_compiles, # must be 0 warm
        "grad_sweep_points": len(scales),
        "grad_sweep_launches": sweep_launches,      # must be 1
        "grad_sweep_compiles_timed": sweep_compiles,
        "grad_sweep_losses": [round(float(v), 6) for v in sweep["loss"]],
        "grad_telemetry": GradTelemetry.snapshot(),
    }


def bench_lte_sched_sweep():
    """All nine FF-MAC schedulers over the SAME lowered scenario: the
    whole family rides one XLA executable (the traced scheduler-id
    dispatch), so a 9-point scheduler study costs one compile plus nine
    device runs — the row the r6 tentpole adds must not regress the
    plain `lte` row above."""
    import dataclasses

    import jax

    from tpudes.core.world import reset_world
    from tpudes.parallel.lte_sm import SM_SCHED_IDS, lower_lte_sm, run_lte_sm
    from tpudes.parallel.runtime import RUNTIME
    from tpudes.scenarios import build_lena

    reset_world()
    lte, _ = build_lena(LTE_ENBS, LTE_UES_PER_CELL)
    prog = lower_lte_sm(lte, LTE_SIM_S)
    reset_world()

    from tpudes.obs.device import CompileTelemetry

    RUNTIME.clear("lte_sm")
    compiles_before = CompileTelemetry.compiles("lte_sm")
    run_lte_sm(prog, jax.random.PRNGKey(0), replicas=LTE_REPLICAS)  # compile
    t0 = time.monotonic()
    per_sched = {}
    for i, sched in enumerate(SM_SCHED_IDS):
        out = run_lte_sm(
            dataclasses.replace(prog, scheduler=sched),
            jax.random.PRNGKey(1 + i), replicas=LTE_REPLICAS,
        )
        per_sched[sched] = round(
            float(out["rx_bits"].sum() / LTE_REPLICAS / LTE_SIM_S / 1e6), 3
        )
    wall = time.monotonic() - t0
    n_compiled = RUNTIME.size("lte_sm")
    rate = len(SM_SCHED_IDS) * LTE_REPLICAS * LTE_SIM_S / wall
    return dict(
        sim_s_per_wall_s=rate,
        wall_sweep_s=wall,
        schedulers=len(SM_SCHED_IDS),
        compiled_programs=n_compiled,   # must stay 1
        # same single-executable property from the obs telemetry side
        obs_compiles=CompileTelemetry.compiles("lte_sm") - compiles_before,
        agg_dl_mbps=per_sched,
    )


def bench_tcp_variant_sweep():
    """The 17-variant comparison itself: one dumbbell, one flow per
    TcpCongestionOps variant, every variant's cwnd rule evaluated as a
    masked vector lane of the same fused step."""
    import jax

    from tpudes.core.world import reset_world
    from tpudes.parallel.tcp_dumbbell import (
        VARIANTS,
        lower_dumbbell,
        run_tcp_dumbbell,
    )
    from tpudes.scenarios import build_dumbbell

    reset_world()
    build_dumbbell(
        len(VARIANTS), TCP_SIM_S, variants=list(VARIANTS),
        bottleneck_rate="13Mbps",
    )
    prog = lower_dumbbell(TCP_SIM_S)
    reset_world()

    run_tcp_dumbbell(prog, jax.random.PRNGKey(0), replicas=TCP_REPLICAS)
    walls = []
    goodput = None
    for i in range(N_TIMED):
        t0 = time.monotonic()
        out = run_tcp_dumbbell(
            prog, jax.random.PRNGKey(1 + i), replicas=TCP_REPLICAS
        )
        walls.append(time.monotonic() - t0)
        import numpy as np

        g = np.asarray(out["goodput_mbps"]).mean(0)
        goodput = g if goodput is None else goodput + g
    med = statistics.median(walls)
    rate = TCP_REPLICAS * TCP_SIM_S / med
    return dict(
        sim_s_per_wall_s=rate,
        wall_median_s=med,
        wall_min_s=min(walls),
        wall_max_s=max(walls),
        variants=len(VARIANTS),
        per_variant_mbps={
            v: round(float(goodput[i] / N_TIMED), 3)
            for i, v in enumerate(VARIANTS)
        },
    )


def bench_sweep_vectorized():
    """The ISSUE-5 tentpole as a metric: the SAME 8-point scheduler /
    variant sweeps executed one-point-per-launch (the PR-4 shape —
    already one executable, but serialized dispatch + D2H per point)
    vs ONE config-axis launch of a (C, R, …) program.  Reports both
    walls, the one-launch speedup, and the launch/compile counters
    that pin the single-launch property."""
    import dataclasses

    import jax

    from tpudes.core.world import reset_world
    from tpudes.obs.device import CompileTelemetry
    from tpudes.parallel.lte_sm import SM_SCHED_IDS, lower_lte_sm, run_lte_sm
    from tpudes.parallel.runtime import RUNTIME
    from tpudes.parallel.tcp_dumbbell import (
        VARIANTS,
        _variant_ecn,
        _variant_point,
        lower_dumbbell,
        run_tcp_dumbbell,
    )
    from tpudes.scenarios import build_dumbbell, build_lena

    reset_world()
    lte, _ = build_lena(LTE_ENBS, LTE_UES_PER_CELL)
    lte_prog = lower_lte_sm(lte, LTE_SIM_S)
    reset_world()
    build_dumbbell(TCP_FLOWS, TCP_SIM_S, variant="TcpCubic")
    tcp_prog = lower_dumbbell(TCP_SIM_S)
    reset_world()

    rows = {}
    scheds = list(SM_SCHED_IDS)[:8]
    points = [[v] * TCP_FLOWS for v in VARIANTS[:8]]

    def lte_per_point(key):
        for i, s in enumerate(scheds):
            run_lte_sm(
                dataclasses.replace(lte_prog, scheduler=s),
                jax.random.fold_in(key, i), replicas=LTE_REPLICAS,
            )

    def lte_one_launch(key):
        run_lte_sm(lte_prog, key, replicas=LTE_REPLICAS, schedulers=scheds)

    def tcp_per_point(key):
        for i, p in enumerate(points):
            ids = _variant_point(p)
            run_tcp_dumbbell(
                dataclasses.replace(
                    tcp_prog, variant_idx=ids, ecn=_variant_ecn(ids)
                ),
                jax.random.fold_in(key, i), replicas=TCP_REPLICAS,
            )

    def tcp_one_launch(key):
        run_tcp_dumbbell(
            tcp_prog, key, replicas=TCP_REPLICAS, variants=points
        )

    for name, per_point, one_launch, sim_s, replicas in (
        ("lte_sm", lte_per_point, lte_one_launch, LTE_SIM_S, LTE_REPLICAS),
        ("dumbbell", tcp_per_point, tcp_one_launch, TCP_SIM_S, TCP_REPLICAS),
    ):
        RUNTIME.clear(name)
        per_point(jax.random.PRNGKey(0))   # warm (compile both modes)
        l0 = RUNTIME.launches(name)
        one_launch(jax.random.PRNGKey(0))
        launches_one = RUNTIME.launches(name) - l0  # the 1-launch pin
        c0 = CompileTelemetry.compiles(name)
        pp_walls, ol_walls = [], []
        for i in range(N_TIMED):
            t0 = time.monotonic()
            per_point(jax.random.PRNGKey(1 + i))
            pp_walls.append(time.monotonic() - t0)
            t0 = time.monotonic()
            one_launch(jax.random.PRNGKey(1 + i))
            ol_walls.append(time.monotonic() - t0)
        pp, ol = statistics.median(pp_walls), statistics.median(ol_walls)
        sweep_sim = 8 * replicas * sim_s
        rows[name] = dict(
            points=8,
            wall_per_point_s=round(pp, 4),
            wall_one_launch_s=round(ol, 4),
            rate_per_point=round(sweep_sim / pp, 1),
            rate_one_launch=round(sweep_sim / ol, 1),
            one_launch_speedup=round(pp / ol, 3),
            launches_one_launch=launches_one,                     # must be 1
            compiles_timed=CompileTelemetry.compiles(name) - c0,  # must be 0
        )
    return rows


def bench_pipeline_overlap():
    """Async submission vs blocking per-point dispatch on a
    heterogeneous sweep (distinct horizons of the lowered LTE grid —
    one executable, the traced-horizon property, but N serialized
    launch+sync round trips when blocking).  Reports both walls and
    the in-flight telemetry that pins >= 2 runs overlapped."""
    import dataclasses

    import jax

    from tpudes.core.world import reset_world
    from tpudes.parallel.lte_sm import lower_lte_sm, run_lte_sm
    from tpudes.parallel.runtime import RUNTIME
    from tpudes.scenarios import build_lena

    reset_world()
    lte, _ = build_lena(LTE_ENBS, LTE_UES_PER_CELL)
    prog = lower_lte_sm(lte, LTE_SIM_S)
    reset_world()

    horizons = [int(LTE_SIM_S * 1000 * f) for f in
                (0.6, 0.8, 1.0, 1.2, 0.7, 0.9)]
    progs = [dataclasses.replace(prog, n_ttis=h) for h in horizons]
    run_lte_sm(progs[0], jax.random.PRNGKey(0), replicas=LTE_REPLICAS)  # warm

    block_walls, submit_walls = [], []
    for i in range(N_TIMED):
        key = jax.random.PRNGKey(1 + i)
        t0 = time.monotonic()
        for j, p in enumerate(progs):
            run_lte_sm(p, jax.random.fold_in(key, j), replicas=LTE_REPLICAS)
        block_walls.append(time.monotonic() - t0)
        t0 = time.monotonic()
        futs = [
            RUNTIME.submit(
                run_lte_sm, p, jax.random.fold_in(key, j),
                replicas=LTE_REPLICAS,
            )
            for j, p in enumerate(progs)
        ]
        for f in futs:
            f.result()
        submit_walls.append(time.monotonic() - t0)
    blk = statistics.median(block_walls)
    sub = statistics.median(submit_walls)
    stats = RUNTIME.stats()
    return dict(
        points=len(horizons),
        wall_blocking_s=round(blk, 4),
        wall_submitted_s=round(sub, 4),
        overlap_speedup=round(blk / sub, 3),
        max_in_flight=stats["max_in_flight"],
        submitted=stats["submitted"],
    )


SERVING_CLIENTS = 16
SERVING_STUDIES_PER_CLIENT = 6
SERVING_SLOTS = 50
SERVING_REPLICAS = 1
SERVING_MAX_WAIT_S = 0.004
SERVING_MAX_BATCH = 8


def bench_serving_closed_loop(smoke: bool = False):
    """ISSUE-7 tentpole row: simulation-as-a-service under closed-loop
    multi-tenant load.  A pool of client threads drives a StudyServer —
    each client submits a study (one dumbbell program per TCP variant,
    same static program / key / replica count, so every study is
    coalescible), waits for its demuxed result, and submits the next.
    The baseline is the SAME study stream through serialized
    ``RUNTIME.submit`` — the best a caller could do before the serving
    layer (pipelined async dispatch, but one device launch per study).

    The row reports requests/s on both paths and the serving p50/p99
    study latency: the acceptance bar is coalesced >= 2x serialized at
    equal results (equality is pinned by tests/test_serving.py, which
    compares coalesced results bit-for-bit against solo launches).
    The study shape is deliberately SMALL: this row measures the
    serving layer's per-launch amortization, not engine compute — on
    accelerators the fixed launch+transfer overhead it amortizes is
    larger still.

    ISSUE-13 columns: a THIRD phase re-runs the closed loop under a
    seed-keyed chaos schedule (launch-shaped errors recovered by the
    requeue/retry path) with clients split across SLO classes (gold /
    standard).  ``degraded_speedup`` is that run against the same
    serialized baseline — the acceptance target is >= 1.5x (the fleet
    absorbs injected failures without falling back to serialized
    throughput) with bounded gold p99; the failure counters and
    per-class SLO attainment ride the row."""
    import dataclasses
    import threading

    import jax

    import tpudes.chaos as chaos
    from tpudes.obs.serving import ServingTelemetry
    from tpudes.parallel.programs import toy_dumbbell_program
    from tpudes.parallel.runtime import RUNTIME
    from tpudes.parallel.tcp_dumbbell import (
        VARIANTS,
        _variant_ecn,
        _variant_point,
        run_tcp_dumbbell,
    )
    from tpudes.serving import StudyServer

    n_clients = 8 if smoke else SERVING_CLIENTS
    per_client = 4 if smoke else SERVING_STUDIES_PER_CLIENT
    prog = toy_dumbbell_program(n_flows=3, n_slots=SERVING_SLOTS)
    key = jax.random.PRNGKey(0)

    def study_prog(i):
        ids = _variant_point([VARIANTS[i % len(VARIANTS)]] * prog.n_flows)
        return dataclasses.replace(
            prog, variant_idx=ids, ecn=_variant_ecn(ids)
        )

    total = n_clients * per_client
    stream = [study_prog(i) for i in range(total)]
    RUNTIME.clear("dumbbell")
    run_tcp_dumbbell(stream[0], key, replicas=SERVING_REPLICAS)  # warm

    # --- baseline: serialized (but async-pipelined) submission -----------
    t0 = time.monotonic()
    futs = [
        RUNTIME.submit(run_tcp_dumbbell, p, key, SERVING_REPLICAS)
        for p in stream
    ]
    for f in futs:
        f.result()
    wall_serial = time.monotonic() - t0

    def closed_loop(slo_of=None):
        """One closed-loop pool run; returns (wall_s, metrics)."""
        ServingTelemetry.reset()
        server = StudyServer(
            max_wait_s=SERVING_MAX_WAIT_S,
            max_batch=SERVING_MAX_BATCH,
            retry_backoff_s=0.002,
            warm=[dict(engine="dumbbell", prog=stream[0], key=key,
                       replicas=SERVING_REPLICAS)],
        )

        def client(c):
            for j in range(per_client):
                h = server.submit_study(
                    "dumbbell", stream[c * per_client + j], key,
                    SERVING_REPLICAS, tenant=f"tenant{c}",
                    slo=slo_of(c) if slo_of else "standard",
                )
                h.result(timeout=300)

        t0 = time.monotonic()
        threads = [
            threading.Thread(target=client, args=(c,))
            for c in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
        metrics = server.metrics()
        server.close()
        return wall, metrics

    # --- coalesced serving: closed-loop client pool ----------------------
    wall_served, metrics = closed_loop()

    # --- degraded: same pool under injected failures + SLO classes -------
    # (ISSUE-13) a seed-keyed schedule plants launch-shaped errors on
    # early dispatches; every affected batch recovers via requeue/retry
    chaos.arm(chaos.ChaosSchedule([
        chaos.ChaosEvent("launch_error", "local_launch", nth=n)
        for n in (2, 5, 9)
    ]))
    try:
        wall_degraded, m_deg = closed_loop(
            slo_of=lambda c: "gold" if c < max(1, n_clients // 4)
            else "standard"
        )
    finally:
        chaos.disarm()
    fail = m_deg["failures"]
    slo = m_deg["slo"]

    eng = metrics["engines"]["dumbbell"]
    return dict(
        requests=total,
        clients=n_clients,
        smoke=smoke,
        rps_serialized=round(total / wall_serial, 1),
        rps_coalesced=round(total / wall_served, 1),
        coalesced_speedup=round(wall_serial / wall_served, 3),  # >= 2 target
        launches=eng["launches"],
        coalesced_launches=eng["coalesced_launches"],
        coalesce_rate=metrics["coalesce_rate"],
        batch_occupancy=eng["batch_occupancy"],
        latency_p50_ms=round(eng["study_latency_s"]["p50"] * 1e3, 2),
        latency_p99_ms=round(eng["study_latency_s"]["p99"] * 1e3, 2),
        launch_p99_ms=round(eng["launch_wall_s"]["p99"] * 1e3, 2),
        # --- ISSUE-13: failure-injection + SLO-attainment columns -------
        injected_failures=fail["injected_failures"],
        requeued_studies=fail["requeued_studies"],
        retry_budget_exhausted=fail["retry_budget_exhausted"],
        rps_degraded=round(total / wall_degraded, 1),
        degraded_speedup=round(wall_serial / wall_degraded, 3),  # >= 1.5
        slo_attainment={
            name: s["attainment"] for name, s in slo.items()
        },
        gold_p99_ms=round(
            slo.get("gold", {}).get("latency_s", {}).get("p99", 0.0)
            * 1e3, 2,
        ),
    )


def bench_fuzz_throughput(smoke: bool = False):
    """ISSUE-8 row: differential-fuzz harness cost.  Runs a small
    fixed-seed campaign (2 scenarios per engine, every cross-mode
    oracle pair, the host-DES pair on the first scenario of each
    engine; --smoke halves it and skips the host pair) and reports
    scenarios/s per engine from the FuzzTelemetry snapshot — so the
    safety net's price is tracked alongside the engine rates it
    protects.  A non-zero divergence count here is a red flag worth
    more than any rate."""
    from tpudes.fuzz.harness import run_campaign
    from tpudes.obs.fuzz import FuzzTelemetry

    per_engine = 1 if smoke else 2
    host_every = 0 if smoke else 2
    from tpudes.fuzz.engines import ENGINE_FUZZERS

    result = run_campaign(
        budget=len(ENGINE_FUZZERS) * per_engine,
        host_every=host_every,
        artifacts_dir="fuzz_artifacts",
    )
    snap = FuzzTelemetry.snapshot()
    return dict(
        scenarios=result.scenarios,
        host_every=host_every,
        smoke=smoke,
        wall_s=round(result.wall_s, 3),
        scenarios_per_s={
            eng: e["scenarios_per_s"] for eng, e in snap["engines"].items()
        },
        pair_runs=snap["counters"]["pair_runs"],
        divergences=snap["counters"]["divergences"],
    )


def bench_hybrid_weak_scaling(max_ranks: int = 2, smoke: bool = False):
    """ISSUE-9 row: hybrid PDES weak scaling — fixed work per rank.

    Every rank count runs the SAME engine (the space-lane hybrid,
    ``transport="batched"``) on a structurally identical per-rank block
    (:func:`tpudes.parallel.wired.wired_weak_chain`) under the SAME
    bounded window cadence (``window_slots`` = the boundary lookahead),
    so the rows isolate what adding rank lanes costs from what the
    window protocol costs.  Aggregate throughput = ranks x horizon
    sim-s / wall-s; the acceptance bar is the 2-rank aggregate >= 1.6x
    the 1-rank row on the CPU reference shape (lanes amortize the
    per-window dispatch + D2H + demux that dominate at sparse shapes).

    Measurement is PAIRED: the rank counts are interleaved round-robin
    and each pair contributes one ratio, so hypervisor throttling
    phases hit all rows alike; the row reports the MEDIAN ratio with
    min/max spread (this box's unpaired walls drift ±40%)."""
    import statistics

    import jax

    from tpudes.obs.distributed import DistributedTelemetry
    from tpudes.parallel.hybrid import run_hybrid
    from tpudes.parallel.wired import wired_weak_chain

    n_slots = 18_000 if smoke else 108_000
    period = 601 if smoke else 3573
    # the window cadence (= the boundary lookahead) picks the regime:
    # finer windows raise the K-shared protocol share of the wall, so
    # rank lanes amortize more — the reference shape runs 180 windows
    boundary = 1200 if smoke else 600
    cross = 1481 if smoke else 8793
    pairs = 3 if smoke else 9
    rank_counts = [k for k in (1, 2, 4) if k <= max(2, int(max_ranks))]
    key = jax.random.key(7)

    progs = {
        k: wired_weak_chain(
            k, links_per_rank=2, period=period, n_slots=n_slots,
            boundary_delay=boundary, cross_period=cross,
        )
        for k in rank_counts
    }

    def once(k):
        t0 = time.monotonic()
        out = run_hybrid(
            progs[k], key, replicas=1, transport="batched",
            window_slots=boundary,
        )
        return time.monotonic() - t0, out

    DistributedTelemetry.reset()
    windows = {k: once(k)[1]["windows"] for k in rank_counts}  # warm
    walls: dict[int, list] = {k: [] for k in rank_counts}
    for _ in range(pairs):
        for k in rank_counts:
            walls[k].append(once(k)[0])

    med = {k: statistics.median(walls[k]) for k in rank_counts}
    rows = {}
    for k in rank_counts:
        ratios = [
            (k * w1) / wk for w1, wk in zip(walls[1], walls[k])
        ]
        rows[str(k)] = dict(
            wall_med_s=round(med[k], 4),
            windows=windows[k],
            agg_sim_s_per_wall_s=round(
                k * n_slots * progs[k].slot_s / med[k], 1
            ),
            ratio_vs_1rank=round(statistics.median(ratios), 3),
            ratio_min=round(min(ratios), 3),
            ratio_max=round(max(ratios), 3),
        )
    return dict(
        transport="batched",
        window_slots=boundary,
        lookahead_slots=boundary,
        per_rank=dict(
            links=2, flows=3, n_slots=n_slots, period=period,
        ),
        pairs=pairs,
        smoke=smoke,
        telemetry=DistributedTelemetry.snapshot()["counters"],
        ranks=rows,
    )


def _distributed_mesh_worker(pmesh, n_replicas, n_slots):
    """One member process of the ``distributed_mesh`` row: run this
    process's contiguous replica block with the GLOBAL offset — the
    ``fold_in(key, r)`` purity contract makes the block bit-identical
    to the same rows of one big launch (module-level so the spawn
    start method can pickle it by reference)."""
    import jax

    from tpudes.parallel.wired import run_wired, wired_chain

    lo, hi = pmesh.slice_bounds(n_replicas)
    prog = wired_chain(n_links=4, n_flows=2, n_slots=n_slots,
                       jitter_slots=3)
    key = jax.random.key(11)
    run_wired(prog, key, replicas=hi - lo, replica_offset=lo)  # warm
    t0 = time.monotonic()
    out = run_wired(prog, key, replicas=hi - lo, replica_offset=lo)
    wall = time.monotonic() - t0
    return dict(
        lo=lo,
        hi=hi,
        wall_s=wall,
        global_devices=jax.device_count(),
        local_devices=jax.local_device_count(),
        deliver=out["deliver_slot"],
    )


def bench_distributed_mesh(n_procs: int = 2, smoke: bool = False):
    """ISSUE-9 row: the replica axis over N ``jax.distributed``
    processes (the multi-process mesh path of
    :mod:`tpudes.parallel.procmesh`).  CPU CI exercises the
    process-sliced contract — each member runs its contiguous replica
    block at the global offset and the stitched result must be
    BIT-equal to the single-process launch (asserted here, not just
    reported); on TPU/GPU the same worker takes the global-mesh path.
    The row reports per-process walls, the stitched aggregate
    replicas/s, and the global/local device counts the procmesh smoke
    pins (global = members x local)."""
    import jax
    import numpy as np

    from tpudes.parallel.procmesh import launch_process_mesh
    from tpudes.parallel.wired import run_wired, wired_chain

    n_replicas = 4 if smoke else 8
    n_slots = 300 if smoke else 1200
    outs = launch_process_mesh(
        _distributed_mesh_worker, n_procs, args=(n_replicas, n_slots),
        timeout_s=300.0,
    )
    stitched = np.concatenate([o["deliver"] for o in outs], axis=0)
    prog = wired_chain(n_links=4, n_flows=2, n_slots=n_slots,
                       jitter_slots=3)
    ref = run_wired(prog, jax.random.key(11), replicas=n_replicas)
    bit_equal = bool((stitched == ref["deliver_slot"]).all())
    if not bit_equal:
        raise AssertionError(
            "distributed_mesh: stitched member blocks diverged from the "
            "single-process launch — the replica_offset purity contract "
            "is broken"
        )
    wall = max(o["wall_s"] for o in outs)
    return dict(
        processes=n_procs,
        replicas=n_replicas,
        slices=[[o["lo"], o["hi"]] for o in outs],
        global_devices=outs[0]["global_devices"],
        local_devices=outs[0]["local_devices"],
        wall_max_s=round(wall, 4),
        replicas_per_s=round(n_replicas / wall, 2),
        bit_equal=bit_equal,
        smoke=smoke,
    )


def bench_tcp():
    import jax

    from tpudes.core import Seconds, Simulator
    from tpudes.core.world import reset_world
    from tpudes.parallel.tcp_dumbbell import lower_dumbbell, run_tcp_dumbbell
    from tpudes.scenarios import build_dumbbell

    reset_world()
    _, sinks = build_dumbbell(TCP_FLOWS, TCP_HOST_S, variant="TcpCubic")
    # --- denominator: real TcpSocketBase over the scalar engine ----------
    t0 = time.monotonic()
    Simulator.Stop(Seconds(TCP_HOST_S))
    Simulator.Run()
    host_wall = time.monotonic() - t0
    host_rx = sum(s.GetTotalRx() for s in sinks)
    reset_world()
    host_rate = TCP_HOST_S / host_wall

    # --- numerator: packet-slot engine, median of N_TIMED -----------------
    build_dumbbell(TCP_FLOWS, TCP_SIM_S, variant="TcpCubic")
    prog = lower_dumbbell(TCP_SIM_S)
    run_tcp_dumbbell(prog, jax.random.PRNGKey(0), replicas=TCP_REPLICAS)
    walls, mbps = [], 0.0
    for i in range(N_TIMED):
        t0 = time.monotonic()
        out = run_tcp_dumbbell(
            prog, jax.random.PRNGKey(1 + i), replicas=TCP_REPLICAS
        )
        walls.append(time.monotonic() - t0)
        mbps += float(out["goodput_mbps"].sum(1).mean())
    med = statistics.median(walls)
    rate = TCP_REPLICAS * TCP_SIM_S / med
    import numpy as np

    return dict(
        sim_s_per_wall_s=rate,
        vs_scalar=rate / host_rate,
        wall_median_s=med,
        wall_min_s=min(walls),
        wall_max_s=max(walls),
        scalar_sim_s_per_wall_s=host_rate,
        scalar_goodput_mbps=host_rx * 8 / TCP_HOST_S / 1e6,
        agg_goodput_mbps=mbps / N_TIMED,
        # tpudes.obs device accumulators (last timed run, per-replica)
        obs_drops_per_replica=float(
            np.asarray(out["drops"]).sum(axis=1).mean()
        ),
        obs_mean_queue_pkts=float(np.asarray(out["mean_queue"]).mean()),
    )


def bench_as():
    import jax

    from tpudes.core import Seconds, Simulator
    from tpudes.core.world import reset_world
    from tpudes.parallel.as_flows import lower_as_flows, run_as_flows
    from tpudes.scenarios import build_as_network

    reset_world()
    _, servers = build_as_network(AS_NODES, AS_FLOWS, AS_HOST_S, seed=3)
    prog = lower_as_flows(AS_SIM_S)
    # --- denominator: one host packet-level run of the same graph --------
    t0 = time.monotonic()
    Simulator.Stop(Seconds(AS_HOST_S))
    Simulator.Run()
    host_wall = time.monotonic() - t0
    host_rx = sum(s.received for s in servers)
    reset_world()
    host_studies_per_s = 1.0 / host_wall

    # --- numerator: flow engine, median of N_TIMED ------------------------
    run_as_flows(prog, jax.random.PRNGKey(0), replicas=AS_REPLICAS)
    walls, frac = [], 0.0
    for i in range(N_TIMED):
        t0 = time.monotonic()
        out = run_as_flows(
            prog, jax.random.PRNGKey(1 + i), replicas=AS_REPLICAS
        )
        walls.append(time.monotonic() - t0)
        frac += float(out["delivered_frac"].mean())
    med = statistics.median(walls)
    rate = AS_REPLICAS / med
    return dict(
        studies_per_s=rate,
        vs_scalar=rate / host_studies_per_s,
        wall_median_s=med,
        wall_min_s=min(walls),
        wall_max_s=max(walls),
        scalar_studies_per_s=host_studies_per_s,
        scalar_rx_pkts=host_rx,
        delivered_frac=frac / N_TIMED,
    )


# --- per-engine mesh strong scaling (the MULTICHIP rows) ----------------

MESH_TIMED = 3


def _mesh_programs(smoke: bool):
    """Per-engine device programs for the strong-scaling rows — the
    shared synthetic builders (tpudes/parallel/programs.py, also the
    test_runtime fixtures), no host object graph, so the multichip
    driver can emit the rows cheaply on any backend.  ``smoke`` shrinks
    every shape for the CI virtual-device job."""
    from tpudes.parallel.programs import (
        toy_as_program,
        toy_bss_program,
        toy_dumbbell_program,
        toy_lte_program,
    )

    bss = toy_bss_program(
        n_sta=8 if smoke else 32,
        sim_end_us=100_000 if smoke else 1_000_000,
    )
    lte = toy_lte_program(
        *((2, 8) if smoke else (7, 70)),
        n_ttis=200 if smoke else 2000,
    )
    tcp = toy_dumbbell_program(
        n_flows=4 if smoke else 8, n_slots=400 if smoke else 10_000
    )
    asp = toy_as_program(
        n_nodes=128 if smoke else 2000,
        n_flows=8 if smoke else 64,
        spf_rounds=16 if smoke else 32,
    )
    return bss, lte, tcp, asp


def bench_mesh(smoke: bool = False, n_devices: int | None = None):
    """Per-engine strong scaling: the SAME device program at the same
    replica count on a 1-device mesh vs the full mesh.  Emits, per
    engine, sim-s/wall-s (studies/s for the AS flow engine) on both
    configurations, the speedup, and the XLA compile count each
    configuration paid (CompileTelemetry delta) — the rows the
    MULTICHIP harness records."""
    import jax

    from tpudes.obs.device import CompileTelemetry
    from tpudes.parallel.as_flows import run_as_flows
    from tpudes.parallel.lte_sm import run_lte_sm
    from tpudes.parallel.mesh import replica_mesh
    from tpudes.parallel.replicated import run_replicated_bss
    from tpudes.parallel.runtime import RUNTIME
    from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

    n_dev = len(jax.devices()) if n_devices is None else n_devices
    bss, lte, tcp, asp = _mesh_programs(smoke)
    r_scale = 2 * n_dev if smoke else None  # full: the BENCH replica counts

    engines = [
        (
            "bss",
            lambda key, mesh, r, **kw: run_replicated_bss(
                bss, r, key, mesh=mesh, **kw
            ),
            r_scale or WIFI_REPLICAS,
            (bss.sim_end_us / 1e6, "sim-s/wall-s"),
        ),
        (
            "lte_sm",
            lambda key, mesh, r, **kw: run_lte_sm(
                lte, key, replicas=r, mesh=mesh, **kw
            ),
            r_scale or LTE_REPLICAS,
            (lte.n_ttis / 1000.0, "sim-s/wall-s"),
        ),
        (
            "dumbbell",
            lambda key, mesh, r, **kw: run_tcp_dumbbell(
                tcp, key, replicas=r, mesh=mesh, **kw
            ),
            r_scale or TCP_REPLICAS,
            (tcp.n_slots * tcp.slot_s, "sim-s/wall-s"),
        ),
        (
            "as_flows",
            lambda key, mesh, r, **kw: run_as_flows(
                asp, key, replicas=r, mesh=mesh, **kw
            ),
            r_scale or AS_REPLICAS,
            (1.0, "studies/s"),  # one study = one replica outcome
        ),
    ]

    rows = {}
    for name, runner, replicas, (per_replica, unit) in engines:
        row = {"replicas": replicas, "unit": unit}
        for label, mesh in (("1dev", replica_mesh(1)), ("ndev", replica_mesh(n_dev))):
            # each mesh configuration pays (and records) its own
            # compiles: jit re-specializes per input sharding even on a
            # runner-cache hit, so the honest count needs a cold cache
            RUNTIME.clear(name)
            c0 = CompileTelemetry.compiles(name)
            runner(jax.random.PRNGKey(0), mesh, replicas)  # compile + warm
            # the timed repetitions ride the async submission window:
            # launch i+1 is dispatched while i's D2H/unpack drains, so
            # the row measures pipelined steady-state throughput (the
            # wall below is the per-run mean of the pipelined batch)
            t0 = time.monotonic()
            futs = [
                RUNTIME.submit(runner, jax.random.PRNGKey(1 + i), mesh,
                               replicas)
                for i in range(MESH_TIMED)
            ]
            for f in futs:
                f.result()
            # renamed from wall_median_s_*: this is the per-run MEAN of
            # a pipelined batch, not a median of blocking walls — the
            # new key keeps old MULTICHIP rows from being compared
            # against it as like-for-like
            mean = (time.monotonic() - t0) / MESH_TIMED
            row[f"wall_mean_s_{label}"] = round(mean, 4)
            row[f"rate_{label}"] = round(replicas * per_replica / mean, 3)
            row[f"compiles_{label}"] = CompileTelemetry.compiles(name) - c0
        row["speedup"] = round(row["rate_ndev"] / row["rate_1dev"], 3)
        row["pipelined"] = True
        rows[name] = row
    return {"n_devices": n_dev, "smoke": smoke, "rows": rows}


def bench_mesh_sweep(smoke: bool = True, n_devices: int | None = None):
    """CI row: a 2-point config-axis scheduler sweep executed as ONE
    launch on the full virtual mesh — the megabatch and the replica
    sharding composed (the `--mesh --smoke` job asserts this emits)."""
    import jax

    from tpudes.parallel.lte_sm import run_lte_sm
    from tpudes.parallel.mesh import replica_mesh
    from tpudes.parallel.runtime import RUNTIME

    n_dev = len(jax.devices()) if n_devices is None else n_devices
    _, lte, _, _ = _mesh_programs(smoke)
    mesh = replica_mesh(n_dev)
    replicas = 2 * n_dev if smoke else LTE_REPLICAS
    scheds = ["pf", "rr"]
    RUNTIME.clear("lte_sm")
    l0 = RUNTIME.launches("lte_sm")
    run_lte_sm(lte, jax.random.PRNGKey(0), replicas=replicas, mesh=mesh,
               schedulers=scheds)  # compile + warm
    t0 = time.monotonic()
    out = run_lte_sm(lte, jax.random.PRNGKey(1), replicas=replicas,
                     mesh=mesh, schedulers=scheds)
    wall = time.monotonic() - t0
    return dict(
        points=len(scheds),
        replicas=replicas,
        n_devices=n_dev,
        launches=RUNTIME.launches("lte_sm") - l0,  # 2 (warm + timed)
        wall_s=round(wall, 4),
        rate=round(len(scheds) * replicas * lte.n_ttis / 1000.0 / wall, 3),
        agg_rx_bits=[int(p["rx_bits"].sum()) for p in out],
    )


def main():
    import jax

    wifi = bench_wifi()
    wifi_ht = bench_wifi_ht()
    mobile_bss = bench_mobile_bss()
    traffic_burst = bench_traffic_burst()
    lte = bench_lte()
    lte_mobility = bench_lte_mobility()
    lte_sweep = bench_lte_sched_sweep()
    tcp = bench_tcp()
    tcp_sweep = bench_tcp_variant_sweep()
    asn = bench_as()
    sweep_vec = bench_sweep_vectorized()
    pipeline = bench_pipeline_overlap()
    serving = bench_serving_closed_loop()
    fuzz = bench_fuzz_throughput()
    grad_cal = bench_grad_calibration()
    # honest-metric caveat (VERDICT r4 weak #6): the AS ratio compares a
    # host packet-level integration to a converged fluid fixed point —
    # different study definitions; the comparable number is studies/s
    asn["metric_note"] = (
        "studies/s; host study = packet-level integration of "
        f"{AS_HOST_S} sim-s, device study = converged fluid fixed point "
        "— vs_scalar compares different study definitions"
    )
    r3 = lambda d: {  # noqa: E731
        k: (round(v, 3) if isinstance(v, float) else v) for k, v in d.items()
    }
    from tpudes.obs.device import CompileTelemetry

    out = {
        "metric": (
            "scenario sim-seconds per wall-second, replica engine "
            f"(BSS {N_STAS} STA x {WIFI_REPLICAS} replicas)"
        ),
        "value": round(wifi["sim_s_per_wall_s"], 1),
        "unit": "sim-s/wall-s",
        # engine-vs-engine: same scenario through DefaultSimulatorImpl
        "vs_baseline": round(wifi["vs_scalar"], 1),
        "wifi": r3(wifi),
        "wifi_ht": r3(wifi_ht),
        "lte": r3(lte),
        # ISSUE-10 rows: moving topologies on the device engines —
        # mobile rate vs the host-geometry-refresh baseline (>= 5x)
        # and vs the static-geometry wall (<= 1.5x), with the
        # geometry-refresh counters that prove which regime ran
        "mobile_bss": r3(mobile_bss),
        "lte_mobility": r3(lte_mobility),
        # ISSUE-14 row: the device-resident traffic stage — bursty vs
        # CBR wall overhead (<= 1.5x), the one-launch 8-point mixed
        # workload sweep with its launch/compile/demux pins, and the
        # workload telemetry naming which models ran
        "traffic_burst": r3(traffic_burst),
        "lte_sched_sweep": r3(lte_sweep),
        "tcp": r3(tcp),
        "tcp_variant_sweep": r3(tcp_sweep),
        "as": r3(asn),
        # ISSUE-5 rows: one-launch (C,R,…) megabatch vs per-point
        # dispatch, and async-submission overlap on a heterogeneous
        # sweep (one-launch must be >= per-point on every platform)
        "sweep_vectorized": sweep_vec,
        "pipeline_overlap": pipeline,
        # ISSUE-7 row: closed-loop multi-tenant serving — requests/s at
        # bounded p99, coalesced StudyServer vs serialized submission
        # of the same study stream (>= 2x is the acceptance bar)
        "serving_closed_loop": serving,
        # ISSUE-8 row: scenarios/s per engine through the differential
        # fuzz harness (every oracle pair) — the cost of the safety net
        "fuzz_throughput": fuzz,
        # ISSUE-15 row: gradient-based calibration — loss-vs-iteration
        # of the one-compile descent loop (planted propagation
        # exponent recovered by L-BFGS-lite) plus the one-launch
        # grad-of-sweep pin and the GradTelemetry snapshot
        "grad_calibration": grad_cal,
        # ISSUE-9 row: hybrid space-parallel weak scaling (fixed work
        # per PDES rank, paired measurement).  The N-process
        # distributed_mesh row stays under --ranks: its children need
        # a device this process already holds
        "hybrid_weak_scaling": bench_hybrid_weak_scaling(max_ranks=4),
        # tpudes.obs compile telemetry: per-engine XLA compile count +
        # wall time over the whole bench process (sweeps must not add
        # compiles — the single-executable property as a metric)
        "obs_compile": CompileTelemetry.snapshot(),
        "devices": len(jax.devices()),
        "platform": jax.devices()[0].platform,
    }
    # strong-scaling rows whenever more than one device is visible (the
    # single-device rows above are measured first, so this section
    # cannot perturb them)
    if len(jax.devices()) > 1:
        out["mesh_scaling"] = bench_mesh()
    print(json.dumps(out))


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--mesh",
        action="store_true",
        help="emit ONLY the per-engine 1-vs-N-device strong-scaling rows",
    )
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny shapes for the CI virtual-device job (with --mesh)",
    )
    ap.add_argument(
        "--ranks",
        type=int,
        default=0,
        help=(
            "emit ONLY the hybrid weak-scaling row up to N PDES ranks "
            "plus the N-process distributed mesh row (ISSUE-9)"
        ),
    )
    args = ap.parse_args()
    if args.ranks:
        print(json.dumps({
            "hybrid_weak_scaling": bench_hybrid_weak_scaling(
                max_ranks=args.ranks, smoke=args.smoke
            ),
            "distributed_mesh": bench_distributed_mesh(
                n_procs=max(2, min(args.ranks, 4)), smoke=args.smoke
            ),
        }))
    elif args.mesh:
        print(json.dumps({
            "mesh_scaling": bench_mesh(smoke=args.smoke),
            "mesh_config_sweep": bench_mesh_sweep(smoke=args.smoke),
            # the serving row rides the CI artifact too, so the
            # closed-loop metric is asserted present on every run
            "serving_closed_loop": bench_serving_closed_loop(
                smoke=args.smoke
            ),
            # ISSUE-8: harness cost rides the CI artifact (and any
            # divergence found by even this tiny budget fails loudly
            # in the asserted row)
            "fuzz_throughput": bench_fuzz_throughput(smoke=args.smoke),
            # ISSUE-9: the hybrid weak-scaling row rides the mesh
            # artifact so rank-lane scaling is asserted on every run
            "hybrid_weak_scaling": bench_hybrid_weak_scaling(
                max_ranks=2, smoke=args.smoke
            ),
            # ISSUE-10: the mobile-BSS row (with geometry counters)
            # rides the CI artifact so device-resident mobility is
            # asserted on every run
            "mobile_bss": bench_mobile_bss(smoke=args.smoke),
            # ISSUE-14: the traffic-stage row (burst overhead, the
            # one-launch workload sweep, workload telemetry) rides the
            # CI artifact so the traffic subsystem is asserted on
            # every run
            "traffic_burst": bench_traffic_burst(smoke=args.smoke),
            # ISSUE-15: the calibration row (one-compile descent,
            # planted-parameter recovery, one-launch grad sweep) rides
            # the CI artifact so differentiable simulation is asserted
            # on every run
            "grad_calibration": bench_grad_calibration(smoke=args.smoke),
        }))
    else:
        main()
