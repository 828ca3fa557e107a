"""Wired-graph per-link-queue device engine (ISSUE-9).

The partition unit of the hybrid PDES: deterministic CBR over explicit
multi-hop paths, timestamps EXACT against the sequential host DES —
the property that lets the space-parallel runs be checked
timestamp-for-timestamp rather than statistically.
"""

import numpy as np
import pytest

import jax

from tpudes.parallel.wired import (
    INF_SLOT,
    UnliftableWiredError,
    WiredProgram,
    packet_table,
    partition_flows,
    partition_lookahead,
    run_wired,
    run_wired_host,
    wired_chain,
    wired_weak_chain,
)

KEY = jax.random.key(7)


# --- program validation ----------------------------------------------------


def test_zero_service_rejected():
    with pytest.raises(UnliftableWiredError, match="service"):
        wired_chain(n_links=3, service=[1, 0, 1])


def test_zero_delay_rejected():
    """delay >= 1 is the FIFO contract: a zero-delay hop would make
    same-slot arrival order depend on event insertion order."""
    with pytest.raises(UnliftableWiredError, match="delay"):
        wired_chain(n_links=3, delay=[2, 0, 2])


def test_bad_link_id_rejected():
    prog = wired_chain(n_links=4)
    with pytest.raises(UnliftableWiredError, match="link id"):
        WiredProgram(
            n_links=4,
            service_slots=np.asarray(prog.service_slots),
            delay_slots=np.asarray(prog.delay_slots),
            paths=np.asarray([[0, 9, -1, -1]], np.int32),
            start_slot=np.asarray([1], np.int32),
            period_slots=np.asarray([5], np.int32),
            n_pkts=np.asarray([3], np.int32),
            n_slots=100,
        )


# --- device vs host oracle (exact timestamps) ------------------------------


def test_device_matches_host_des_exactly():
    prog = wired_chain(n_links=6, n_flows=3, n_slots=500)
    host = run_wired_host(prog)
    dev = run_wired(prog, KEY, replicas=2)
    assert (dev["deliver_slot"][0] == host["deliver_slot"]).all()
    assert (dev["deliver_slot"][1] == host["deliver_slot"]).all()
    assert (dev["served"][0] == host["served"]).all()
    assert dev["delivered"].sum() > 0


def test_obs_carry_never_holds_one_buffer_under_two_leaves():
    """The carry is donated on accelerators: one device buffer reachable
    from two carry leaves is "Attempt to donate the same buffer twice"
    there (the first chip run hit it — ``fm_birth`` WAS ``ready``).
    XLA:CPU does not donate, so pin the structure instead."""
    import jax

    from tpudes.parallel.wired import build_wired_advance

    prog = wired_chain(n_links=4, n_flows=2, n_slots=100, jitter_slots=2)
    init_state, _ = build_wired_advance(prog, 2, obs=True)
    leaves = [
        leaf for leaf in jax.tree_util.tree_leaves(init_state(KEY))
        if leaf.ndim > 0
    ]
    pointers = {leaf.unsafe_buffer_pointer() for leaf in leaves}
    assert len(pointers) == len(leaves)


def test_windowed_run_bit_identical_to_single_shot():
    """window_slots cuts the horizon into advance() segments — the
    grant-schedule-indifference the hybrid window protocol relies on."""
    prog = wired_chain(n_links=6, n_flows=3, n_slots=500)
    one = run_wired(prog, KEY, replicas=2)
    for window in (7, 63, 500):
        win = run_wired(prog, KEY, replicas=2, window_slots=window)
        for k in ("deliver_slot", "delivered", "served"):
            assert (one[k] == win[k]).all(), (k, window)


def test_jitter_replicas_differ_and_match_host_per_row():
    from tpudes.parallel.wired import _replica_jitter

    prog = wired_chain(n_links=5, n_flows=3, n_slots=400, jitter_slots=6)
    dev = run_wired(prog, KEY, replicas=3)
    jit = np.asarray(_replica_jitter(prog, KEY, 3))
    assert (jit >= 0).all() and (jit <= 6).all()
    # each replica's trajectory is the host DES run at its jitter row
    for r in range(3):
        host = run_wired_host(prog, jitter=jit[r])
        assert (dev["deliver_slot"][r] == host["deliver_slot"]).all(), r
    # some phase actually moved (seed-dependent but jit covers 3x3 rows)
    assert jit.any()


@pytest.mark.slow  # ISSUE-21 tier-1 budget: the multi-device CI step runs the full file
def test_replica_offset_slices_bit_equal():
    """Process p computing [lo, hi) with the global offset reproduces
    the same rows of one big launch — the multi-process replica
    sharding contract of procmesh."""
    prog = wired_chain(n_links=5, n_flows=3, n_slots=300, jitter_slots=4)
    full = run_wired(prog, KEY, replicas=5)
    lo = run_wired(prog, KEY, replicas=3, replica_offset=0)
    hi = run_wired(prog, KEY, replicas=2, replica_offset=3)
    stitched = np.concatenate([lo["deliver_slot"], hi["deliver_slot"]])
    assert (stitched == full["deliver_slot"]).all()


# --- partitioning ----------------------------------------------------------


def test_partition_flows_resident_sets():
    prog = wired_chain(n_links=6, n_flows=3, n_slots=300, ranks=2)
    sub0, flows0, pkts0 = partition_flows(prog, 0)
    sub1, flows1, pkts1 = partition_flows(prog, 1)
    # every flow reaches the chain tail, so rank 1 sees all flows;
    # rank 0 only those entering on its half
    assert set(flows1) == {0, 1, 2}
    pf, _, _ = packet_table(prog)
    assert pkts1.size == pf.size
    # id maps are strictly increasing (FIFO tiebreak order-consistent)
    assert (np.diff(pkts0) > 0).all() and (np.diff(pkts1) > 0).all()


def test_partition_flows_idle_rank_rejected():
    prog = wired_chain(n_links=4, n_flows=2, n_slots=200)
    with pytest.raises(UnliftableWiredError, match="idle"):
        partition_flows(prog, 3)


def test_partition_lookahead_boundary_minimum():
    prog = wired_chain(n_links=6, n_flows=3, n_slots=300, ranks=2,
                       boundary_delay=9)
    owner = np.asarray(prog.link_owner)
    cut = int(np.nonzero(np.diff(owner))[0][0])
    svc = int(prog.service_slots[cut])
    dly = int(prog.delay_slots[cut])
    assert partition_lookahead(prog, 0) == svc + dly
    # the tail rank never sends back on a chain
    assert partition_lookahead(prog, 1) == INF_SLOT


def test_weak_chain_is_uniform_and_aligned():
    wp = wired_weak_chain(4, links_per_rank=3, flows_per_rank=2,
                          n_slots=2000)
    assert wp.n_ranks == 4
    subs = [partition_flows(wp, r) for r in range(4)]
    # uniform partitions: equal per-rank flow/packet counts
    assert len({s[0].n_flows for s in subs}) == 1
    assert len({packet_table(s[0])[0].size for s in subs}) == 1
    # local schedules replay rank 0's block (slot alignment)
    for r in (1, 2, 3):
        assert (np.asarray(subs[r][0].start_slot)
                == np.asarray(subs[0][0].start_slot)).all()
        assert (np.asarray(subs[r][0].period_slots)
                == np.asarray(subs[0][0].period_slots)).all()


def test_weak_chain_device_matches_host():
    wp = wired_weak_chain(2, n_slots=1500)
    host = run_wired_host(wp)
    dev = run_wired(wp, KEY, replicas=1)
    assert (dev["deliver_slot"][0] == host["deliver_slot"]).all()
    # the cross flow delivered something (causal coupling is real)
    assert dev["delivered"][0, -1] >= 1
