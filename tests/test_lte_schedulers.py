"""The FF-MAC scheduler family beyond PF/RR (SURVEY.md §2.6 lists
PF, RR, FD/TD-MT, TTA, TD/FD-BET, CQA, PSS) — each algorithm pinned on
the behavioral signature upstream's lte test suites check: MT starves,
BET equalizes bits, CQA honors urgency, PSS honors targets."""

from tpudes.models.lte.scheduler import (
    CqaFfMacScheduler,
    FdBetFfMacScheduler,
    FdMtFfMacScheduler,
    PssFfMacScheduler,
    SCHEDULERS,
    SchedCandidate,
    TdBetFfMacScheduler,
    TdMtFfMacScheduler,
    TtaFfMacScheduler,
)

RBGS = list(range(13))
RBG = 2


def _full_buffer(cqis, **extra):
    return [
        SchedCandidate(rnti=i + 1, cqi=c, queue_bytes=1 << 30, **extra)
        for i, c in enumerate(cqis)
    ]


def _run(sched, cqis, ttis, cands_fn=None):
    """Drive full-buffer TTIs; returns served bits per rnti."""
    served = {i + 1: 0 for i in range(len(cqis))}
    for tti in range(ttis):
        cands = cands_fn(tti) if cands_fn else _full_buffer(cqis)
        allocs = sched.schedule(tti, cands, list(RBGS), RBG)
        bits = {a.rnti: a.tb_bytes * 8 for a in allocs}
        for r, b in bits.items():
            served[r] += b
        if hasattr(sched, "end_tti"):
            sched.end_tti(
                {a.rnti: a.tb_bytes * 8 for a in allocs}, list(served)
            )
    return served


def test_registry_has_the_nine_upstream_algorithms():
    names = {c.name for c in set(SCHEDULERS.values())}
    assert names == {
        "pf", "rr", "tdmt", "fdmt", "tta", "tdbet", "fdbet", "cqa", "pss"
    }


def test_tdmt_gives_the_whole_tti_to_the_best_channel():
    sched = TdMtFfMacScheduler()
    for tti in range(20):
        allocs = sched.schedule(tti, _full_buffer([15, 8, 4]), list(RBGS), RBG)
        assert len(allocs) == 1 and allocs[0].rnti == 1
    # the starved UEs never appear — MT's defining (anti-)fairness
    served = _run(TdMtFfMacScheduler(), [15, 8, 4], 50)
    assert served[2] == 0 and served[3] == 0


def test_fdmt_serves_by_rate_order():
    sched = FdMtFfMacScheduler()
    cands = [
        SchedCandidate(rnti=1, cqi=4, queue_bytes=300),
        SchedCandidate(rnti=2, cqi=15, queue_bytes=300),
    ]
    allocs = sched.schedule(0, cands, list(RBGS), RBG)
    # the high-rate UE is filled first (light load: both fit)
    assert allocs[0].rnti == 2
    assert sorted(a.rnti for a in allocs) == [1, 2]


def test_bet_equalizes_bits_across_unequal_channels():
    """BET's defining property: UEs at CQI 15 and CQI 6 end up with
    ~equal BITS (RR would give them equal AIRTIME, hence unequal bits)."""
    for cls in (TdBetFfMacScheduler, FdBetFfMacScheduler):
        served = _run(cls(alpha=0.1), [15, 6], 3000)
        ratio = served[1] / max(served[2], 1)
        assert 0.8 < ratio < 1.25, (cls.__name__, served)


def test_tta_multiplexes_and_skips_dead_channels():
    sched = TtaFfMacScheduler()
    served = _run(sched, [12, 12, 12], 30)
    assert all(v > 0 for v in served.values())
    allocs = sched.schedule(99, _full_buffer([0, 12, 12]), list(RBGS), RBG)
    assert all(a.rnti != 1 for a in allocs)  # CQI 0 never scheduled


def test_cqa_urgency_beats_channel():
    sched = CqaFfMacScheduler()
    cands = [
        SchedCandidate(rnti=1, cqi=15, queue_bytes=1 << 30, hol_delay_ms=0.0),
        SchedCandidate(rnti=2, cqi=6, queue_bytes=1 << 30, hol_delay_ms=45.0),
    ]
    allocs = sched.schedule(0, cands, list(RBGS), RBG)
    assert allocs[0].rnti == 2, "stale HOL must outrank the better channel"
    # with equal delay groups the channel term decides again
    cands[1].hol_delay_ms = 0.0
    allocs = sched.schedule(1, cands, list(RBGS), RBG)
    assert allocs[0].rnti == 1


def test_pss_priority_set_meets_target_then_yields():
    sched = PssFfMacScheduler(alpha=0.1)
    # rnti 1: great channel, no target; rnti 2: poor channel, 1 Mbps TBR
    def cands(_tti):
        return [
            SchedCandidate(rnti=1, cqi=15, queue_bytes=1 << 30),
            SchedCandidate(rnti=2, cqi=5, queue_bytes=1 << 30,
                           tbr_bps=1_000_000.0),
        ]

    served = _run(sched, [15, 5], 2000, cands_fn=cands)
    # the targeted flow is protected: it reaches (around) its TBR even
    # though pure PF/MT would starve its poor channel
    got_bps = served[2] / 2.0 * 1000 / 1000  # bits over 2000 ms -> bps
    assert served[2] > 0
    assert got_bps > 500_000, got_bps
    # and the best-effort flow still gets the (larger) remainder
    assert served[1] > served[2]


def test_all_schedulers_run_in_the_full_lena_loop():
    """End-to-end: each registered algorithm drives a small lena grid
    for 30 TTIs without error and serves every UE's buffer."""
    from tests.test_lte import _build_lena
    from tpudes.core import Seconds, Simulator
    from tpudes.core.world import reset_world

    for name in ("tdmt", "fdmt", "tta", "tdbet", "fdbet", "cqa", "pss"):
        reset_world()
        lte, enbs, ues = _build_lena(1, 3, scheduler=name)
        Simulator.Stop(Seconds(0.03))
        Simulator.Run()
        assert lte.controller.stats["ttis"] >= 30, name
        assert lte.controller.stats["dl_tbs"] > 0, name
    reset_world()


def test_sm_engine_lowers_every_registered_scheduler():
    """r5 review forbade silently mis-lowering non-pf/rr algorithms;
    r6 closes the gap the right way: every registered FF-MAC scheduler
    now lowers to the traced-id dispatch (tests/test_lte_sm.py pins the
    per-family behavior) while a custom class still refuses loudly."""
    from tests.test_lte import _build_lena
    from tpudes.core.world import reset_world
    from tpudes.parallel.lte_sm import lower_lte_sm

    reset_world()
    lte, enbs, ues = _build_lena(1, 2, scheduler="tdmt")
    prog = lower_lte_sm(lte, 1.0)
    assert prog.scheduler == "tdmt"
    reset_world()
