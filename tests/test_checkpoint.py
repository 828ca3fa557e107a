"""ISSUE 13 gates: checkpoint/resume of chunked-horizon runs.

A run killed between chunks (the chaos ``checkpoint_kill`` site fires
AFTER the save, i.e. the crash window the format guarantees against)
must resume from its last completed chunk and finish BIT-equal to the
uninterrupted run — for all four engines' chunked paths, at chunk
boundary 0 (nothing completed), after the final chunk (no-op resume,
zero launches), and across ``TPUDES_INFLIGHT`` / ``TPUDES_BUCKETING``
setting changes.  A checkpoint that does not belong to the run
(different key, different chunk schedule) is refused loudly.
"""

import jax
import numpy as np
import pytest

import tpudes.chaos as chaos
from tpudes.chaos import ChaosEvent, ChaosInjected, ChaosSchedule
from tpudes.obs.device import ChunkStream, CompileTelemetry
from tpudes.parallel.checkpoint import CarryCheckpoint, CheckpointError
from tpudes.parallel.runtime import RUNTIME

KEY = jax.random.PRNGKey(17)


@pytest.fixture(autouse=True)
def _fresh():
    RUNTIME.clear()
    CompileTelemetry.reset()
    ChunkStream.reset()
    chaos.reset()
    yield
    chaos.reset()
    RUNTIME.clear()


def _dumbbell(**kw):
    from tpudes.parallel.programs import toy_dumbbell_program
    from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

    prog = toy_dumbbell_program(n_flows=3, n_slots=120)
    return run_tcp_dumbbell(
        prog, KEY, replicas=5, chunk_slots=40, **kw
    )


def _lte(**kw):
    from tpudes.parallel.lte_sm import run_lte_sm
    from tpudes.parallel.programs import toy_lte_program

    prog = toy_lte_program(n_enb=2, n_ue=4, n_ttis=60)
    return run_lte_sm(prog, KEY, replicas=3, chunk_ttis=20, **kw)


def _bss(**kw):
    from tpudes.parallel.programs import toy_bss_program
    from tpudes.parallel.replicated import run_replicated_bss

    prog = toy_bss_program(n_sta=4, sim_end_us=40_000)
    return run_replicated_bss(prog, 2, KEY, chunk_steps=150, **kw)


def _as(**kw):
    from tpudes.parallel.as_flows import run_as_flows
    from tpudes.parallel.programs import toy_as_program

    prog = toy_as_program(n_nodes=64, n_flows=3)
    return run_as_flows(prog, KEY, replicas=4, chunk_rounds=2, **kw)


ENGINES = {
    "dumbbell": _dumbbell,
    "lte_sm": _lte,
    "bss": _bss,
    "as_flows": _as,
}


def _assert_equal(a, b):
    a_list = a if isinstance(a, list) else [a]
    b_list = b if isinstance(b, list) else [b]
    assert len(a_list) == len(b_list)
    for pa, pb in zip(a_list, b_list):
        for k in pb:
            np.testing.assert_array_equal(
                np.asarray(pa[k]), np.asarray(pb[k]), err_msg=f"field {k!r}"
            )


# --- killed between chunks -> resume bit-equal (all four engines) ---------


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_kill_between_chunks_resumes_bit_equal(engine, tmp_path):
    run = ENGINES[engine]
    ref = run()  # uninterrupted reference (same chunk schedule)
    ckpt = CarryCheckpoint(tmp_path / f"{engine}.ckpt")
    chaos.arm(ChaosSchedule([
        ChaosEvent("checkpoint_kill", "checkpoint_save", nth=1,
                   param=engine),
    ]))
    with pytest.raises(ChaosInjected):
        run(checkpoint=ckpt)
    chaos.disarm()
    assert ckpt.exists(), "the kill fires only after a durable save"
    before = RUNTIME.launches(engine)
    out = run(checkpoint=ckpt)
    resumed_launches = RUNTIME.launches(engine) - before
    _assert_equal(out, ref)
    # the resume really skipped the completed chunk
    full = {"dumbbell": 3, "lte_sm": 3, "bss": None, "as_flows": 2}[engine]
    if full is not None:
        assert resumed_launches == full - 1, (
            f"resume relaunched {resumed_launches} chunks"
        )


# --- edge cases ------------------------------------------------------------


def test_fresh_checkpoint_path_is_boundary_zero(tmp_path):
    """No checkpoint on disk = resume at chunk boundary 0: the run
    executes in full, result bit-equal, and leaves a final-state
    checkpoint behind."""
    ref = _dumbbell()
    ckpt = CarryCheckpoint(tmp_path / "fresh.ckpt")
    assert not ckpt.exists()
    out = _dumbbell(checkpoint=ckpt)
    _assert_equal(out, ref)
    assert ckpt.exists()


def test_resume_after_final_chunk_is_noop(tmp_path):
    ref = _dumbbell()
    ckpt = CarryCheckpoint(tmp_path / "done.ckpt")
    _dumbbell(checkpoint=ckpt)  # runs to completion, saves final carry
    before = RUNTIME.launches("dumbbell")
    out = _dumbbell(checkpoint=ckpt)
    assert RUNTIME.launches("dumbbell") == before, (
        "a completed checkpoint must relaunch nothing"
    )
    _assert_equal(out, ref)


def test_resume_under_different_inflight_window(tmp_path, monkeypatch):
    ref = _dumbbell()
    ckpt = CarryCheckpoint(tmp_path / "win.ckpt")
    chaos.arm(ChaosSchedule([
        ChaosEvent("checkpoint_kill", "checkpoint_save", nth=2),
    ]))
    with pytest.raises(ChaosInjected):
        _dumbbell(checkpoint=ckpt)
    chaos.disarm()
    monkeypatch.setenv("TPUDES_INFLIGHT", "1")
    _assert_equal(_dumbbell(checkpoint=ckpt), ref)


def test_resume_across_bucketing_flip(tmp_path, monkeypatch):
    """Saved under pow2 bucketing (5 replicas -> pad 8), resumed with
    TPUDES_BUCKETING=0 (exact 5): the checkpoint stores only real
    replica rows, so both directions resume bit-equal."""
    ckpt = CarryCheckpoint(tmp_path / "buck.ckpt")
    chaos.arm(ChaosSchedule([
        ChaosEvent("checkpoint_kill", "checkpoint_save", nth=1),
    ]))
    with pytest.raises(ChaosInjected):
        _dumbbell(checkpoint=ckpt)  # bucketing ON at save
    chaos.disarm()
    monkeypatch.setenv("TPUDES_BUCKETING", "0")
    ref_off = _dumbbell()  # uninterrupted, bucketing off
    out = _dumbbell(checkpoint=ckpt)
    _assert_equal(out, ref_off)
    monkeypatch.delenv("TPUDES_BUCKETING")
    # and the reverse flip: save unbucketed, resume bucketed
    ckpt2 = CarryCheckpoint(tmp_path / "buck2.ckpt")
    monkeypatch.setenv("TPUDES_BUCKETING", "0")
    chaos.arm(ChaosSchedule([
        ChaosEvent("checkpoint_kill", "checkpoint_save", nth=1),
    ]))
    with pytest.raises(ChaosInjected):
        _dumbbell(checkpoint=ckpt2)
    chaos.disarm()
    monkeypatch.delenv("TPUDES_BUCKETING")
    ref_on = _dumbbell()
    _assert_equal(_dumbbell(checkpoint=ckpt2), ref_on)


# --- refusal: a checkpoint that is not this run's --------------------------


def test_wrong_key_is_refused(tmp_path):
    from tpudes.parallel.programs import toy_dumbbell_program
    from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

    prog = toy_dumbbell_program(n_flows=3, n_slots=120)
    ckpt = CarryCheckpoint(tmp_path / "key.ckpt")
    run_tcp_dumbbell(prog, KEY, replicas=5, chunk_slots=40,
                     checkpoint=ckpt)
    other = jax.random.PRNGKey(99)
    with pytest.raises(CheckpointError, match="fingerprint"):
        run_tcp_dumbbell(prog, other, replicas=5, chunk_slots=40,
                         checkpoint=ckpt)


def test_changed_chunk_schedule_is_refused(tmp_path):
    from tpudes.parallel.programs import toy_dumbbell_program
    from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell

    prog = toy_dumbbell_program(n_flows=3, n_slots=120)
    ckpt = CarryCheckpoint(tmp_path / "sched.ckpt")
    run_tcp_dumbbell(prog, KEY, replicas=5, chunk_slots=40,
                     checkpoint=ckpt)
    with pytest.raises(CheckpointError, match="chunk schedule"):
        run_tcp_dumbbell(prog, KEY, replicas=5, chunk_slots=60,
                         checkpoint=ckpt)


def test_corrupt_checkpoint_is_refused(tmp_path):
    ckpt = CarryCheckpoint(tmp_path / "bad.ckpt")
    (tmp_path / "bad.ckpt").write_bytes(b"not a pickle")
    with pytest.raises(CheckpointError, match="unreadable"):
        _dumbbell(checkpoint=ckpt)


def test_checkpoint_telemetry_counters(tmp_path):
    from tpudes.obs.serving import ServingTelemetry

    ServingTelemetry.reset()
    ckpt = CarryCheckpoint(tmp_path / "tel.ckpt")
    chaos.arm(ChaosSchedule([
        ChaosEvent("checkpoint_kill", "checkpoint_save", nth=2),
    ]))
    with pytest.raises(ChaosInjected):
        _dumbbell(checkpoint=ckpt)
    chaos.disarm()
    _dumbbell(checkpoint=ckpt)
    f = ServingTelemetry.snapshot()["failures"]
    assert f["checkpoint_saves"] >= 3  # 2 before the kill + resume saves
    assert f["checkpoint_restores"] == 1
    assert f["injected_checkpoint_kill"] == 1
    ServingTelemetry.reset()


# --- ISSUE 26: the base LTE carry holds ONE scalar clock -------------------

#: what the parent of ISSUE 26 wrote into a checkpoint of ``_lte()``
#: (read from a file it saved): no carry-layout tag in the identity
_LTE_STACKED_CLOCK_FINGERPRINT = (
    "c2d0416623946ba98235e3684e7aadd1c3e052f143c0bb55d0341624c0b23065"
)


@pytest.mark.parametrize("layout", ["scalar-clock", "stacked-clock"])
def test_lte_base_carry_layout(layout, tmp_path):
    """A file of this layout resumes bit-equal with the scalar clock
    restored as a scalar; a file written while the clock was stacked
    per replica is refused by the fingerprint, not loaded into the
    scalar-clock carry."""
    import pickle

    from tpudes.parallel.checkpoint import checkpoint_ctx
    from tpudes.parallel.kernels_pallas import SM_SCHED_IDS
    from tpudes.parallel.lte_sm import _sm_cache_key
    from tpudes.parallel.programs import toy_lte_program

    ckpt = CarryCheckpoint(tmp_path / "layout.ckpt")
    chaos.arm(ChaosSchedule([
        ChaosEvent("checkpoint_kill", "checkpoint_save", nth=2),
    ]))
    with pytest.raises(ChaosInjected):
        _lte(checkpoint=ckpt)
    chaos.disarm()
    with open(ckpt.path, "rb") as f:
        doc = pickle.load(f)
    t, s = doc["carry"]
    assert np.asarray(t).shape == () and int(t) == doc["bound"] == 40
    assert doc["replica_leaf"][0] is False
    if layout == "scalar-clock":
        _assert_equal(_lte(checkpoint=ckpt), _lte())
        return
    prog = toy_lte_program(n_enb=2, n_ue=4, n_ttis=60)
    old = checkpoint_ctx(
        ckpt, engine="lte_sm", key=KEY, replicas=3, r_pad=4, n_cfg=None,
        obs=False, axis=0,
        extra=_sm_cache_key(prog, None, None, False, False)
        + ((SM_SCHED_IDS[prog.scheduler],),),
    )
    assert old.fingerprint == _LTE_STACKED_CLOCK_FINGERPRINT
    ckpt.save(old, 40, [20, 40, 60], (np.full((4,), t, np.int32), s))
    with pytest.raises(CheckpointError, match="fingerprint"):
        _lte(checkpoint=ckpt)


#: the fingerprint ``_as``'s checkpoint carried while the relaxation's
#: per-link carry leaves were always 2E + 1 wide (no layout tag)
_AS_WIDE_CARRY_FINGERPRINT = (
    "b1ddd3b9cbdaece3831be7cc15ad5aedc397dc8a9198dd52b40ffeaa2c7c04e6"
)


@pytest.mark.parametrize("layout", ["link-table", "wide"])
def test_as_flows_carry_layout(layout, tmp_path):
    """A file of the link-table layout (per-link leaves as wide as the
    links the paths use) resumes bit-equal; a file written while they
    were 2E + 1 wide is refused by the fingerprint, not loaded."""
    import pickle

    from tpudes.parallel.as_flows import as_prog_key, relax_links
    from tpudes.parallel.checkpoint import checkpoint_ctx
    from tpudes.parallel.programs import toy_as_program

    ckpt = CarryCheckpoint(tmp_path / "layout.ckpt")
    chaos.arm(ChaosSchedule([
        ChaosEvent("checkpoint_kill", "checkpoint_save", nth=1),
    ]))
    with pytest.raises(ChaosInjected):
        _as(checkpoint=ckpt)
    chaos.disarm()
    with open(ckpt.path, "rb") as f:
        doc = pickle.load(f)
    (i, lfrac, lg, util), out = doc["carry"]
    prog = toy_as_program(n_nodes=64, n_flows=3)
    E2 = 2 * prog.edges.shape[0]
    assert int(i) == doc["bound"] == 2 and relax_links(prog) < E2 + 1
    assert np.shape(lfrac) == np.shape(util) == (4, relax_links(prog))
    if layout == "link-table":
        _assert_equal(_as(checkpoint=ckpt), _as())
        return
    old = checkpoint_ctx(
        ckpt, engine="as_flows", key=KEY, replicas=4, r_pad=4, n_cfg=None,
        obs=False, axis=0, extra=as_prog_key(prog) + (None, None),
    )
    assert old.fingerprint == _AS_WIDE_CARRY_FINGERPRINT
    wide = (i, np.zeros((4, E2 + 1), np.float32), lg,
            np.zeros((4, E2), np.float32))
    ckpt.save(old, 2, doc["bounds"], (wide, out))
    with pytest.raises(CheckpointError, match="fingerprint"):
        _as(checkpoint=ckpt)
