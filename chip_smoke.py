#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpudes still starts on the chip.

Runs the system's main path once, on ONE process, at the sizes users
run (BASELINE.json configs 2-5 = the constants at the top of bench.py),
through the entry points a user calls:

1. the five stock scenario scripts under ``examples/`` with the one
   GlobalValue flip (``--SimulatorImplementationType=
   tpudes::JaxSimulatorImpl --JaxReplicas=R``): script →
   ``JaxSimulatorImpl._try_lift`` → ``lift()`` / ``run_lifted()`` →
   ``run_*`` → ``EngineRuntime``.  Each must take the LIFTED path
   (``replicated_result`` set — the scripts exit 0 from the scalar
   fallback too, which here would hide the device), pass the script's
   own exit criterion, and reproduce bit-identically on a same-key
   rerun;
2. the LTE TTI step under ``TPUDES_PALLAS=1`` and ``=0``, naming the
   lowering each run COMPILED (read from the executable, not from the
   environment variable) and the agreement found, and what the unset
   variable compiles at the batched and the unbatched lane count;
3. every engine once more chunked under ``TpudesObs=1`` at a short
   horizon, so carry donation and the chunk-metric snapshots execute
   on a backend that donates;
4. the serving front door: an in-process ``StudyServer`` answers four
   LTE studies, each equal to its solo ``run_lte_sm``;
5. the replica BSS engine against the sequential host DES on the same
   object graph (echo count within 2%), and the wired engine against
   its exact host DES oracle, timestamp for timestamp;
6. with more than one chip: every program on a one-device mesh and on
   the whole mesh — equal results, outputs sharded over every device.

``main()`` always demands a TPU and the full sizes; there is no flag or
environment variable that lets it pass without one.  The phase
functions take their sizes as arguments so tests/test_chip_smoke.py
can run them tiny on CPU.  Walls printed here are smoke walls (cold
includes lowering + compile) — information only, never a benchmark
metric.  The last stdout line is the JSON verdict.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time
import warnings
from unittest import mock

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

JAX_ENGINE = "--SimulatorImplementationType=tpudes::JaxSimulatorImpl"

#: BASELINE.json configs 2-5 as stock-script invocations: name →
#: (script, lifted kind, script arguments, replicas)
PROGRAMS = {
    "wifi": ("wifi-bss.py", "bss", dict(nStas=64, simTime=2), 512),
    "wifi_ht": (
        "wifi-bss.py", "bss",
        dict(nStas=64, simTime=2, standard="80211n", dataMode="HtMcs7",
             interval=0.01),
        512,
    ),
    "lte": (
        "lena-simple.py", "lte_sm",
        dict(nEnbs=7, uesPerCell=30, simTime=10), 64,
    ),
    "tcp": (
        "tcp-variants.py", "dumbbell",
        dict(nFlows=8, variant="TcpCubic", simTime=20), 256,
    ),
    "as": (
        "brite-as.py", "as_flows",
        dict(nNodes=10000, nFlows=128, simTime=10), 1024,
    ),
}

#: the short chunked + TpudesObs=1 launch of each engine: name →
#: (horizon field cut short or None, its value, chunk size)
CHUNKED = {
    "wifi": ("sim_end_us", 1_150_000, 100),
    "wifi_ht": ("sim_end_us", 1_150_000, 100),
    "lte": ("n_ttis", 400, 100),
    "tcp": ("n_slots", 4000, 1000),
    "as": (None, None, 2),
}

#: each lifted kind's chunk argument (run_* keyword)
CHUNK_ARG = {
    "bss": "chunk_steps", "lte_sm": "chunk_ttis",
    "dumbbell": "chunk_slots", "as_flows": "chunk_rounds",
}

SERVING_SCHEDULERS = ("pf", "rr", "tdmt", "fdmt")

#: the host-DES parity phase: BASELINE config #3 as bench.py builds it
BSS_PARITY = dict(n_stas=64, sim_s=2.0, replicas=64, rtol=0.02)

#: wired_chain(...) arguments, replicas and window of the oracle phase
WIRED = dict(
    chain=dict(n_links=16, n_flows=8, n_slots=2000, jitter_slots=6),
    replicas=64, window_slots=250,
)

#: TPUDES_PALLAS=1 vs =0 on the chip: Mosaic's and XLA's exp / divide
#: differ in the last ulps, so a decode coin within ~1e-7 of its BLER
#: can flip and that replica's HARQ history then diverges (statistically
#: the same run).  What must hold is the aggregate: mean delivered bits
#: per replica within this relative tolerance.
LOWERING_RTOL = 1e-2


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def differing_fields(a: dict, b: dict) -> list[str]:
    """Names of the fields on which two engine result dicts are not
    bit-identical (nested dicts — the FlowMonitor block — included)."""
    import numpy as np

    bad = []
    for k in sorted(set(a) | set(b)):
        if k not in a or k not in b:
            bad.append(k)
        elif isinstance(a[k], dict):
            bad += [f"{k}.{s}" for s in differing_fields(a[k], b[k])]
        elif not np.array_equal(np.asarray(a[k]), np.asarray(b[k])):
            bad.append(k)
    return bad


# --- phase 1: the stock scripts through JaxSimulatorImpl --------------------


def _load_example(script: str):
    """Import ``examples/<script>`` as a module (hyphenated file names
    are not importable by name)."""
    path = os.path.join(ROOT, "examples", script)
    spec = importlib.util.spec_from_file_location(
        "tpudes_example_" + script.replace("-", "_").removesuffix(".py"),
        path,
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_stock_script(script: str, args: dict, replicas: int):
    """One stock-script run with the GlobalValue flip; returns ``(exit
    code, replicated_result or None, wall seconds)``.

    The scripts print and then ``Simulator.Destroy()``, so the engine's
    result object is gone when ``main`` returns: Destroy is wrapped for
    the duration of the call to keep a reference.  Nothing else about
    the script's path changes — this IS ``python examples/<script>``.
    """
    from tpudes.core.simulator import Simulator
    from tpudes.core.world import reset_world

    reset_world()  # Simulator and the GlobalValues are process-global
    argv = [f"--{k}={v}" for k, v in args.items()]
    argv += [f"--JaxReplicas={replicas}", JAX_ENGINE]
    kept = []
    real_destroy = Simulator.Destroy

    def destroy():
        kept.append(
            getattr(Simulator.GetImpl(), "replicated_result", None)
        )
        real_destroy()

    main = _load_example(script).main
    with mock.patch.object(Simulator, "Destroy", destroy):
        t0 = time.monotonic()
        rc = main(argv)
        wall = time.monotonic() - t0
    reset_world()
    return rc, (kept[-1] if kept else None), wall


def _script_criterion(kind: str, out: dict) -> str | None:
    """The script's own exit criterion, restated on the result (None =
    holds, else what failed)."""
    import numpy as np

    if kind == "bss":
        if not (out["all_done"] and np.asarray(out["srv_rx"]).mean() > 0):
            return "all_done and srv_rx.mean() > 0"
    elif kind == "lte_sm":
        if not np.asarray(out["rx_bits"]).sum() > 0:
            return "aggregate DL Mbps > 0"
    elif kind == "dumbbell":
        if not np.asarray(out["goodput_mbps"]).sum() > 0:
            return "goodput > 0"
    elif kind == "as_flows":
        if np.asarray(out["unreachable"]).any():
            return "no unreachable flow"
    return None


def phase_script(name: str, script: str, kind: str, args: dict,
                 replicas: int) -> dict:
    """Run one scripted program twice with the same arguments (hence
    the same key): lifted path taken, exit criterion met, second run
    bit-identical to the first.  Returns the first run's result dict
    (``kind``/``replicas``/``out``/``program``/…) plus the two walls."""
    rc, res, cold = run_stock_script(script, args, replicas)
    check(
        res is not None,
        f"{name}: replicated_result is None — the script fell back to "
        f"the scalar engine (exit code {rc}), the lifted path did not run",
    )
    check(
        res["kind"] == kind and res["replicas"] == replicas,
        f"{name}: lifted kind/replicas {res['kind']!r}/{res['replicas']} "
        f"!= expected {kind!r}/{replicas}",
    )
    check(rc == 0, f"{name}: script exit code {rc}")
    failed = _script_criterion(kind, res["out"])
    check(failed is None, f"{name}: exit criterion failed: {failed}")
    rc2, res2, warm = run_stock_script(script, args, replicas)
    check(rc2 == 0 and res2 is not None, f"{name}: rerun failed (rc={rc2})")
    bad = differing_fields(res["out"], res2["out"])
    check(not bad, f"{name}: same-key rerun differs on {bad}")
    return dict(res, cold_s=cold, warm_s=warm)


# --- phase 2: which LTE TTI step compiled, and how the two agree ------------


def phase_lte_lowerings(prog, key, replicas: int,
                        expect_pallas: str) -> dict:
    """Run the LTE program under ``TPUDES_PALLAS=1`` and ``=0``; name
    the lowering each executable holds (``expect_pallas`` is what =1
    must compile to on this backend: "mosaic" on a TPU, "xla" where
    pallas runs discharged) and state the agreement found.  With the
    variable unset the engine picks by lane count
    (``lte_sm._sm_use_pallas``): the batched launch and an unbatched
    one are read back too (``lowered["unset"]``, ``["unset_solo"]``)."""
    import numpy as np

    from tpudes.parallel.lte_sm import (
        SM_KERNEL_MAX_LANES,
        compiled_step_lowering,
        run_lte_sm,
    )
    from tpudes.parallel.runtime import bucket_replicas

    outs, lowered, walls = {}, {}, {}
    for flag in ("1", "0"):
        with mock.patch.dict(os.environ, {"TPUDES_PALLAS": flag}):
            t0 = time.monotonic()
            outs[flag] = run_lte_sm(prog, key, replicas=replicas)
            walls[flag] = time.monotonic() - t0
            lowered[flag] = compiled_step_lowering(
                prog, key, replicas=replicas
            )
    with mock.patch.dict(os.environ):
        os.environ.pop("TPUDES_PALLAS", None)
        for name, r in (("unset", replicas), ("unset_solo", None)):
            lowered[name] = compiled_step_lowering(prog, key, replicas=r)
            kernel = (bucket_replicas(r) or 1) <= SM_KERNEL_MAX_LANES
            check(
                lowered[name] == (expect_pallas if kernel else "xla"),
                f"TPUDES_PALLAS unset, replicas={r}: compiled the "
                f"{lowered[name]!r} step; the rule keeps the kernel up "
                f"to {SM_KERNEL_MAX_LANES} lanes",
            )
    check(
        lowered["1"] == expect_pallas,
        f"TPUDES_PALLAS=1 compiled the {lowered['1']!r} step, expected "
        f"{expect_pallas!r}",
    )
    check(
        lowered["0"] == "xla",
        f"TPUDES_PALLAS=0 compiled the {lowered['0']!r} step",
    )
    a = np.asarray(outs["1"]["rx_bits"], np.float64)
    b = np.asarray(outs["0"]["rx_bits"], np.float64)
    same_rows = float(np.mean(np.all(a == b, axis=-1)))
    rel = abs(a.sum() - b.sum()) / max(b.sum(), 1.0)
    check(
        rel <= LOWERING_RTOL,
        f"TPUDES_PALLAS=1 vs =0 delivered bits differ by {rel:.3e} "
        f"(tolerance {LOWERING_RTOL})",
    )
    return dict(
        lowered=lowered, bit_equal=not differing_fields(outs["1"], outs["0"]),
        replicas_bit_equal=same_rows, rel_diff_bits=rel, walls=walls,
    )


# --- phase 3: chunked launches under TpudesObs=1 (donation) -----------------


def _count_donation_warnings(caught) -> int:
    return sum(
        "donated buffers were not usable" in str(w.message) for w in caught
    )


@contextlib.contextmanager
def _obs_on():
    """``TpudesObs=1`` in a fresh world for the duration (reset again on
    the way out); yields the list the run's warnings are recorded in."""
    from tpudes.core.global_value import GlobalValue
    from tpudes.core.world import reset_world

    reset_world()
    GlobalValue.Bind("TpudesObs", 1)
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            yield caught
    finally:
        reset_world()


def phase_chunked_obs(name: str, kind: str, prog, key, replicas: int,
                      field: str | None, short, chunk: int) -> dict:
    """One single-shot and one chunked launch of ``prog`` (horizon cut
    to ``field=short``) under ``TpudesObs=1``: the chunked run hands a
    DONATED carry from segment to segment and snapshots chunk metrics
    after the next segment is dispatched — a metrics leaf aliasing the
    carry would surface as "Array has been deleted" (which propagates).
    The two must be bit-identical (the chunking contract)."""
    from tpudes.obs.device import ChunkStream
    from tpudes.parallel.lift import run_lifted

    if field is not None:
        prog = dataclasses.replace(prog, **{field: short})
    ChunkStream.reset()
    with _obs_on() as caught:
        t0 = time.monotonic()
        single = run_lifted(kind, prog, replicas, key)
        cold = time.monotonic() - t0
        t0 = time.monotonic()
        chunked = run_lifted(
            kind, prog, replicas, key, **{CHUNK_ARG[kind]: chunk}
        )
        warm = time.monotonic() - t0
        snapshots = len(ChunkStream.entries())
    check(
        snapshots > 0,
        f"{name}: chunked {kind} run streamed no chunk metrics "
        f"({CHUNK_ARG[kind]}={chunk} made a single segment?)",
    )
    bad = differing_fields(single, chunked)
    check(not bad, f"{name}: chunked run differs from single-shot on {bad}")
    return dict(
        snapshots=snapshots, cold_s=cold, warm_s=warm,
        donation_warnings=_count_donation_warnings(caught),
    )


# --- phase 4: the serving front door ----------------------------------------


def phase_serving(prog, key, replicas: int,
                  schedulers=SERVING_SCHEDULERS) -> dict:
    """An in-process StudyServer answers one LTE study per scheduler on
    ``prog``; every answer equals the solo ``run_lte_sm`` for the same
    key."""
    from tpudes.parallel.lte_sm import run_lte_sm
    from tpudes.serving import StudyServer

    progs = [dataclasses.replace(prog, scheduler=s) for s in schedulers]
    t0 = time.monotonic()
    with StudyServer(max_wait_s=0.2) as server:
        handles = [
            server.submit_study(
                "lte_sm", p, key, replicas=replicas, tenant=f"user{i}"
            )
            for i, p in enumerate(progs)
        ]
        served = [h.result(timeout=600) for h in handles]
    wall = time.monotonic() - t0
    for sched, p, got in zip(schedulers, progs, served):
        solo = run_lte_sm(p, key, replicas=replicas)
        bad = differing_fields(got, solo)
        check(not bad, f"serving[{sched}] differs from solo on {bad}")
    return dict(
        studies=len(served), batch_sizes=[h.batch_size for h in handles],
        wall_s=wall,
    )


# --- phase 5: the replica engine against the host DES, same graph -----------


def phase_bss_host_parity(n_stas: int, sim_s: float, replicas: int,
                          rtol: float) -> dict:
    """BASELINE config #3 as ``tpudes.scenarios.build_bss`` drops it
    (bench.py's graph): the sequential host DES and the replica engine
    run the SAME object graph and must deliver the same echo count to
    the server, within ``rtol`` of the host's.  This drop's outer ring
    sits on the 54 Mbps SINR cliff, so it is where a backend's
    arithmetic shows first — the first chip run lost 9% of a clean
    BSS's frames to bf16-rounded f32 matmuls here while every other
    check passed."""
    import jax
    import numpy as np

    from tpudes.core import Seconds, Simulator
    from tpudes.core.world import reset_world
    from tpudes.parallel.replicated import lower_bss, run_replicated_bss
    from tpudes.scenarios import build_bss

    reset_world()
    stas, ap, clients, server_rx = build_bss(n_stas, sim_s)
    prog = lower_bss(
        [stas.Get(i) for i in range(stas.GetN())], ap, clients, sim_s
    )
    t0 = time.monotonic()
    Simulator.Stop(Seconds(sim_s))
    Simulator.Run()  # DefaultSimulatorImpl: the plain reference
    host_wall = time.monotonic() - t0
    host = int(server_rx[0])
    reset_world()
    out = run_replicated_bss(prog, replicas, jax.random.PRNGKey(0))
    dev = float(np.asarray(out["srv_rx"]).mean())
    check(bool(out["all_done"]), "bss parity: a replica did not finish")
    check(host > 0, "bss parity: the host DES delivered nothing")
    check(
        abs(dev - host) <= rtol * host,
        f"bss parity: replica engine delivered {dev:.2f} echoes per "
        f"replica, host DES {host} on the same graph (rtol {rtol})",
    )
    return dict(host=host, device_mean=dev, host_wall_s=host_wall)


# --- phase 6: the wired engine against its exact host oracle ----------------


def phase_wired(chain: dict, replicas: int, window_slots: int,
                oracle_rows=(0, 1, -1)) -> dict:
    """``run_wired`` on a ``wired_chain`` against ``run_wired_host``,
    timestamp for timestamp (the one exact host oracle the repo has),
    then once more windowed under ``TpudesObs=1`` — the wired engine's
    donated-carry path — bit-identical to the single shot."""
    import jax
    import numpy as np

    from tpudes.parallel.wired import (
        _replica_jitter,
        run_wired,
        run_wired_host,
        wired_chain,
    )

    prog = wired_chain(**chain)
    key = jax.random.PRNGKey(7)
    t0 = time.monotonic()
    dev = run_wired(prog, key, replicas=replicas)
    cold = time.monotonic() - t0
    t0 = time.monotonic()
    again = run_wired(prog, key, replicas=replicas)
    warm = time.monotonic() - t0
    check(not differing_fields(dev, again), "wired: same-key rerun differs")
    check(int(dev["delivered"].sum()) > 0, "wired: nothing delivered")
    jitter = np.asarray(_replica_jitter(prog, key, replicas))
    for r in sorted({r % replicas for r in oracle_rows}):
        host = run_wired_host(prog, jitter=jitter[r])
        check(
            bool((dev["deliver_slot"][r] == host["deliver_slot"]).all()),
            f"wired: replica {r} delivery slots differ from the host DES",
        )
        check(
            bool((dev["served"][r] == host["served"]).all()),
            f"wired: replica {r} per-link service counts differ",
        )
    with _obs_on() as caught:
        windowed = run_wired(
            prog, key, replicas=replicas, window_slots=window_slots
        )
    bad = [
        k for k in ("deliver_slot", "delivered", "served")
        if not np.array_equal(dev[k], windowed[k])
    ]
    check(not bad, f"wired: windowed obs run differs on {bad}")
    return dict(
        packets=int(dev["deliver_slot"].shape[1]), cold_s=cold,
        warm_s=warm, donation_warnings=_count_donation_warnings(caught),
    )


# --- phase 7: more than one chip --------------------------------------------


def phase_mesh(name: str, kind: str, prog, key, replicas: int) -> dict:
    """The same program on a one-device mesh and on the whole mesh:
    equal results (bit-equal; ``rtol=1e-5`` on the AS engine's float
    fields, whose sharded SPF reduces in another order) and device
    outputs that span every device."""
    import jax
    import numpy as np

    from tpudes.parallel.lift import run_lifted
    from tpudes.parallel.mesh import replica_mesh

    n_dev = len(jax.devices())
    one = run_lifted(kind, prog, replicas, key, mesh=replica_mesh(1))
    fut = run_lifted(
        kind, prog, replicas, key, mesh=replica_mesh(n_dev), block=False
    )
    spans = {
        len(leaf.sharding.device_set)
        for leaf in jax.tree_util.tree_leaves(fut.device_out)
        if getattr(leaf, "ndim", 0) > 0
    }
    full = fut.result()
    check(
        n_dev in spans,
        f"{name}: no device output spans all {n_dev} devices "
        f"(device-set sizes {sorted(spans)})",
    )
    if kind == "as_flows":
        for k in one:
            np.testing.assert_allclose(
                np.asarray(full[k], np.float64),
                np.asarray(one[k], np.float64),
                rtol=1e-5, err_msg=f"{name}: {k}",
            )
    else:
        bad = differing_fields(one, full)
        check(not bad, f"{name}: 1-device vs {n_dev}-device differ on {bad}")
    return dict(devices=n_dev, output_spans=sorted(spans))


# --- the run ---------------------------------------------------------------


def _versions() -> dict:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = "not installed"
    return out


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; jax found platform "
            f"{dev.platform!r} ({dev.device_kind}, {len(devices)} "
            "device(s)) — refusing to run",
            file=sys.stderr,
        )
        return 2

    from tpudes.core.native import get_native
    from tpudes.parallel.lift import lifted_key
    from tpudes.parallel.runtime import configure_persistent_cache

    print(
        f"chip_smoke: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} versions={_versions()}"
    )
    print(f"chip_smoke: compile cache at {configure_persistent_cache()}")
    print(
        "chip_smoke: native event core "
        + ("loaded" if get_native() is not None else "NOT loaded (pure python)")
    )

    t_start = time.monotonic()
    donation_warnings = 0
    results = {}
    for name, (script, kind, args, replicas) in PROGRAMS.items():
        res = phase_script(name, script, kind, args, replicas)
        results[name] = res
        print(
            f"chip_smoke: script {name}: kind={res['kind']} "
            f"replicas={replicas} lifted=yes criterion=ok "
            f"rerun=bit-identical cold={res['cold_s']:.2f}s "
            f"warm={res['warm_s']:.2f}s"
        )

    key = lifted_key()  # the key the scripted runs drew from
    lte = results["lte"]
    low = phase_lte_lowerings(
        lte["program"], key, lte["replicas"], expect_pallas="mosaic"
    )
    print(
        f"chip_smoke: lte lowering TPUDES_PALLAS=1 -> {low['lowered']['1']}, "
        f"=0 -> {low['lowered']['0']}, unset -> {low['lowered']['unset']} "
        f"({lte['replicas']} lanes) / {low['lowered']['unset_solo']} "
        f"(1 lane); bit_equal={low['bit_equal']} "
        f"replicas_bit_equal={low['replicas_bit_equal']:.3f} "
        f"rel_diff_bits={low['rel_diff_bits']:.3e} "
        f"walls(1/0)={low['walls']['1']:.2f}s/{low['walls']['0']:.2f}s"
    )

    for name, (field, short, chunk) in CHUNKED.items():
        res = results[name]
        got = phase_chunked_obs(
            name, res["kind"], res["program"], key, res["replicas"],
            field, short, chunk,
        )
        donation_warnings += got["donation_warnings"]
        print(
            f"chip_smoke: chunked+obs {name}: snapshots={got['snapshots']} "
            f"equal-to-single-shot donation_warnings="
            f"{got['donation_warnings']} cold={got['cold_s']:.2f}s "
            f"warm={got['warm_s']:.2f}s"
        )

    got = phase_serving(lte["program"], key, lte["replicas"])
    print(
        f"chip_smoke: serving: {got['studies']} LTE studies == solo "
        f"(batch sizes {got['batch_sizes']}) wall={got['wall_s']:.2f}s"
    )

    got = phase_bss_host_parity(**BSS_PARITY)
    print(
        f"chip_smoke: bss vs host DES (same graph): device mean "
        f"{got['device_mean']:.2f} vs host {got['host']} echoes "
        f"(host DES wall {got['host_wall_s']:.2f}s)"
    )

    got = phase_wired(**WIRED)
    donation_warnings += got["donation_warnings"]
    print(
        f"chip_smoke: wired: {got['packets']} packets timestamp-exact vs "
        f"host DES; windowed+obs equal; donation_warnings="
        f"{got['donation_warnings']} cold={got['cold_s']:.2f}s "
        f"warm={got['warm_s']:.2f}s"
    )

    if len(devices) > 1:
        for name, res in results.items():
            got = phase_mesh(
                name, res["kind"], res["program"], key, res["replicas"]
            )
            print(
                f"chip_smoke: mesh {name}: 1-device == "
                f"{got['devices']}-device; output spans "
                f"{got['output_spans']}"
            )
        from tpudes.parallel.lte_sm import compiled_step_lowering
        from tpudes.parallel.mesh import replica_mesh

        print(
            "chip_smoke: lte lowering on the mesh -> "
            + compiled_step_lowering(
                lte["program"], key, lte["replicas"],
                mesh=replica_mesh(len(devices)),
            )
        )

    print(
        f"chip_smoke: all phases passed in "
        f"{time.monotonic() - t_start:.1f}s; donated-buffer warnings: "
        f"{donation_warnings}"
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
