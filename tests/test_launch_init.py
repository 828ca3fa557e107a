"""The launch carry comes from one jitted init program per runner
(``runtime.jit_init``, ISSUE 29), on the one launch path of every
engine (``runtime.Launch``, ISSUE 30).

- leaf for leaf, in value, dtype, shape and placement, the init
  program gives the prepared launch what the eager chain it replaced
  gave (``replica_keys``, per-replica draws; ``init_state()`` ->
  ``stack_axis`` -> ``shard_replica_axis``): BSS, the three LTE forms,
  the dumbbell, AS flows and wired, with and without a config axis, on
  one device and on a 1-axis replica mesh;
- a run through the one path reproduces, bit for bit, the result
  arrays that the PARENT of the issue that moved the engine wrote into
  ``golden/launch_init_parent.json`` (ISSUE 29: eager carry) and
  ``golden/launch_path_parent.json`` (ISSUE 30: eager carry, own copy
  of the run contract);
- the program is made once per runner and mesh and counted
  (``RUNTIME.stats()["init_programs"]``).
"""

import dataclasses
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudes.parallel.runtime import (
    RUNTIME,
    Launch,
    bucket_replicas,
    replica_keys,
    shard_replica_axis,
    stack_axis,
)

KEY = jax.random.PRNGKey(29)
REPLICAS = 4
VARIANTS_29 = ("bss", "lte_base", "lte_traffic", "lte_mobile")
VARIANTS_30 = ("dumbbell", "as_flows", "wired")
VARIANTS = VARIANTS_29 + VARIANTS_30
# `wired` has no config axis: its cases are solo only
CASES = pytest.mark.parametrize(
    "variant,n_cfg,on_mesh",
    [
        pytest.param(
            v, c, m,
            id=f"{v}-{'solo' if c is None else 'cfg2'}"
               f"-{'mesh' if m else 'one'}",
        )
        for m in (False, True)
        for c in (None, 2)
        for v in VARIANTS
        if not (v == "wired" and c is not None)
    ],
)
_GOLDEN_DIR = Path(__file__).parent / "golden"
# written by ``__main__`` below on the PARENT tree of the issue named:
# 29 put the first four variants on the init program, 30 the rest
_GOLDEN = {
    **dict.fromkeys(VARIANTS_29, _GOLDEN_DIR / "launch_init_parent.json"),
    **dict.fromkeys(VARIANTS_30, _GOLDEN_DIR / "launch_path_parent.json"),
}
#: a result array a launch path may round at most a ULP off the
#: parent's, as ``{(golden entry, field): the parent's own array}`` (its
#: digest is the golden one, checked in the test): `as_flows.solo.mesh`'s
#: `delay_s`, where the parent's mesh program rounded one entry a ULP
#: above its one-device program, and the relaxation over the link table
#: its paths use rounds as the one-device program does
_ULP_OFF_PARENT = {
    ("as_flows.solo.mesh", "delay_s"): np.array(
        [[1009856700, 1022060195], [1009856783, 1022059970],
         [1009856710, 1022059982], [1009856697, 1022060073]],
        np.uint32,
    ).view(np.float32),
}
#: `wired` runs windowed and from a replica offset, so that the
#: offset's way into the init program is part of what is pinned
WIRED_KW = dict(window_slots=16, replica_offset=3)


def _mesh(on_mesh):
    if not on_mesh:
        return None
    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual multi-device mesh")
    from tpudes.parallel.mesh import replica_mesh

    return replica_mesh(4)


def _prog(variant):
    from tpudes.parallel import lte_sm
    from tpudes.parallel.programs import toy_bss_program

    if variant == "bss":
        return toy_bss_program(n_sta=4, sim_end_us=60_000)
    if variant == "lte_base":
        return lte_sm._trace_prog()
    if variant == "lte_traffic":
        return lte_sm._trace_traffic_prog()
    if variant == "dumbbell":
        from tpudes.parallel.programs import toy_dumbbell_program

        return toy_dumbbell_program(n_flows=3, n_slots=120)
    if variant == "as_flows":
        from tpudes.parallel import as_flows

        return as_flows._trace_prog()
    if variant == "wired":
        from tpudes.parallel import wired

        return wired._trace_prog()
    from tpudes.ops.mobility import MobilityProgram

    base = lte_sm._trace_prog()
    return dataclasses.replace(
        base,
        mobility=MobilityProgram.constant_velocity(
            np.full((base.n_ue, 3), 100.0), np.ones((base.n_ue, 3))
        ),
        enb_pos=np.array([[0.0, 0.0, 30.0], [500.0, 0.0, 30.0]]),
        pathloss=("friis", 2.12e9, 1.0, 0.0),
    )


def _run(variant, n_cfg, mesh):
    """The variant's launch through its public entry."""
    from tpudes.parallel.as_flows import run_as_flows
    from tpudes.parallel.lte_sm import run_lte_sm
    from tpudes.parallel.replicated import run_replicated_bss
    from tpudes.parallel.tcp_dumbbell import run_tcp_dumbbell
    from tpudes.parallel.wired import run_wired

    prog = _prog(variant)
    if variant == "bss":
        ends = None if n_cfg is None else [40_000, 60_000]
        return run_replicated_bss(
            prog, REPLICAS, KEY, mesh=mesh, sim_end_us=ends
        )
    if variant == "dumbbell":
        points = None if n_cfg is None else [[0, 1, 2], [5, 9, 16]]
        return run_tcp_dumbbell(
            prog, KEY, REPLICAS, mesh=mesh, variants=points
        )
    if variant == "as_flows":
        scales = None if n_cfg is None else [0.5, 2.0]
        return run_as_flows(
            prog, KEY, REPLICAS, mesh=mesh, rate_scale=scales
        )
    if variant == "wired":
        assert n_cfg is None
        return run_wired(prog, KEY, REPLICAS, mesh=mesh, **WIRED_KW)
    scheds = None if n_cfg is None else ["pf", "rr"]
    return run_lte_sm(
        prog, KEY, replicas=REPLICAS, mesh=mesh, schedulers=scheds
    )


def _prepared(variant, n_cfg, mesh, monkeypatch):
    """The launch of ``_run`` stopped after the runtime's prepare step:
    the :class:`Launch` with the runner, the carry and the operands the
    run would be driven with."""
    monkeypatch.setattr(Launch, "drive", lambda self, *a, **kw: self)
    return _run(variant, n_cfg, mesh)


def _init_and_eager(variant, n_cfg, mesh, monkeypatch):
    """``(got, want)``: what the runner's init program gave the
    prepared launch, and the eager chain (``init_state()`` ->
    ``stack_axis`` -> ``shard_replica_axis``, ``replica_keys``) on the
    same builder's un-jitted ``init_state``."""
    from tpudes.parallel import as_flows, lte_sm, replicated
    from tpudes.parallel import tcp_dumbbell, wired

    prog = _prog(variant)
    r_pad = bucket_replicas(REPLICAS, mesh)
    axis = 0 if n_cfg is None else 1
    L = _prepared(variant, n_cfg, mesh, monkeypatch)

    def shard(tree, ax):
        return shard_replica_axis(tree, mesh, r_pad, ax)

    if variant == "bss":
        init_state, _, _ = replicated.build_bss_advance(
            prog, r_pad, n_cfg=n_cfg
        )
        return L.carry, (shard(stack_axis(init_state(), n_cfg), axis), None)
    if variant == "dumbbell":
        init_state, _ = tcp_dumbbell.build_dumbbell_advance(
            prog, r_pad, n_cfg=n_cfg
        )
        carry = stack_axis((jnp.int32(0), init_state()), n_cfg)
        return L.carry, shard(carry, axis)
    if variant == "as_flows":
        carry = (jnp.int32(0),) + as_flows._as_carry(prog, r_pad)
        z = as_flows._as_replica_draws(prog, KEY, r_pad)
        return (L.ops[0], L.carry[0]), (
            shard(z, 0), shard(stack_axis(carry, n_cfg), axis)
        )
    if variant == "wired":
        init_state, _ = wired.build_wired_advance(prog, r_pad)
        carry = init_state(KEY, WIRED_KW["replica_offset"])
        no_ingress = jnp.full(
            (r_pad, carry["hop"].shape[1]), -1, jnp.int32
        )
        return (L.carry, L.ops), (
            shard(carry, 0), (shard(no_ingress, 0), shard(no_ingress, 0))
        )
    kw = dict(r_pad=r_pad, n_cfg=n_cfg, use_pallas=False)
    if variant == "lte_base":
        _, init_state, _ = lte_sm.build_sm_advance(prog, **kw)

        def init_carry():
            return (jnp.int32(0), init_state())
    else:
        build = (
            lte_sm.build_sm_traffic_advance if variant == "lte_traffic"
            else lte_sm.build_sm_mobile_advance
        )
        init_carry, _ = build(prog, **kw)
    *shared, s = init_carry()
    s0 = shard(stack_axis(stack_axis(s, r_pad), n_cfg), axis)
    return (L.ops[0], L.carry), (
        shard(replica_keys(KEY, r_pad), 0), (*shared, s0)
    )


@CASES
def test_init_program_gives_the_eager_carry(
    variant, n_cfg, on_mesh, monkeypatch
):
    mesh = _mesh(on_mesh)
    got, want = _init_and_eager(variant, n_cfg, mesh, monkeypatch)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    got_leaves = jax.tree_util.tree_leaves_with_path(got)
    want_leaves = jax.tree_util.tree_leaves(want)
    sharded = 0
    for (path, g), w in zip(got_leaves, want_leaves, strict=True):
        name = jax.tree_util.keystr(path)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert g.weak_type == w.weak_type, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
        if mesh is None:
            # uncommitted one-device arrays, as the eager jnp calls
            # gave: the advance program is lowered for exactly these
            assert not g.committed and not w.committed, name
            assert g.sharding.is_equivalent_to(w.sharding, g.ndim), name
        elif w.committed:
            # a replica leaf: the same NamedSharding spec
            assert g.sharding.spec == w.sharding.spec, name
            assert g.sharding.mesh == mesh, name
            assert "replica" in g.sharding.spec, name
            sharded += 1
        else:
            # everything else the program replicates over the mesh
            assert g.sharding.is_fully_replicated, name
            assert g.sharding.mesh == mesh, name
    if mesh is not None:
        assert 0 < sharded <= len(want_leaves)


# --- bit identity with the parent's eager carry ------------------------------


def _sha(a):
    a = np.ascontiguousarray(np.asarray(a))
    h = hashlib.sha256(f"{a.dtype}{a.shape}".encode() + a.tobytes())
    return [list(a.shape), h.hexdigest()[:16]]


def _points(variant, n_cfg, mesh):
    """One run's result arrays, ``{name: array}`` per config point."""
    RUNTIME.clear()
    out = _run(variant, n_cfg, mesh)
    return [
        {k: v for k, v in sorted(p.items()) if isinstance(v, np.ndarray)}
        for p in (out if isinstance(out, list) else [out])
    ]


def golden_entry(variant, n_cfg, mesh):
    """One run's result arrays as ``{name: [shape, sha256]}`` per config
    point.  Uses public entry points only: the digests in the golden
    file were written by running this function on the parent tree."""
    return [
        {k: _sha(v) for k, v in p.items()}
        for p in _points(variant, n_cfg, mesh)
    ]


def _golden_name(variant, n_cfg, on_mesh):
    return (f"{variant}.{'solo' if n_cfg is None else 'cfg2'}"
            f".{'mesh' if on_mesh else 'one'}")


@CASES
def test_run_reproduces_the_parents_result_arrays(variant, n_cfg, on_mesh):
    name = _golden_name(variant, n_cfg, on_mesh)
    want = json.loads(_GOLDEN[variant].read_text())[name]
    points = _points(variant, n_cfg, _mesh(on_mesh))
    got = [{k: _sha(v) for k, v in p.items()} for p in points]
    assert len(got) == (1 if n_cfg is None else n_cfg)
    for (entry, field), parent in _ULP_OFF_PARENT.items():
        if entry == name:
            assert _sha(parent) == want[0][field]
            np.testing.assert_array_max_ulp(points[0][field], parent, 1)
            got[0][field] = want[0][field]
    # `wired` returns three arrays, every other engine at least four
    assert all(len(point) >= 3 for point in got)
    assert got == want


# --- one program per runner and mesh -----------------------------------------


def test_init_program_is_made_once_per_runner_and_mesh():
    from tpudes.parallel.replicated import run_replicated_bss

    mesh = _mesh(True)
    prog = _prog("bss")
    RUNTIME.clear()
    n0 = RUNTIME.stats()["init_programs"]
    run_replicated_bss(prog, REPLICAS, KEY)
    assert RUNTIME.stats()["init_programs"] == n0 + 1
    run_replicated_bss(prog, REPLICAS, jax.random.PRNGKey(1))
    assert RUNTIME.stats()["init_programs"] == n0 + 1
    # the runner (and its advance program) is mesh-independent: the
    # same entry serves the mesh launch, only the init program is new
    misses = RUNTIME.stats()["misses"]
    run_replicated_bss(prog, REPLICAS, KEY, mesh=mesh)
    run_replicated_bss(prog, REPLICAS, KEY, mesh=mesh)
    assert RUNTIME.stats()["init_programs"] == n0 + 2
    assert RUNTIME.stats()["misses"] == misses
    # no replica axis, nothing to shard: a mesh makes no second program
    from tpudes.parallel.runtime import jit_init

    init = jit_init("toy", lambda k: (k, jnp.zeros((3,))), None, (0, None))
    n1 = RUNTIME.stats()["init_programs"]
    a, _ = init(None, KEY)
    b, _ = init(mesh, KEY)
    assert RUNTIME.stats()["init_programs"] == n1 + 1
    assert a.sharding.is_equivalent_to(b.sharding, 1)


def test_init_program_compiles_under_its_own_name():
    from tpudes.obs.device import CompileTelemetry
    from tpudes.parallel.lift import run_lifted

    CompileTelemetry.listen()
    RUNTIME.clear()
    t0 = max([e[0] for e in CompileTelemetry.xla_events()], default=0.0)
    run_lifted("bss", _prog("bss"), REPLICAS, KEY)
    run_lifted("lte_sm", _prog("lte_base"), REPLICAS, KEY)
    compiled = [
        e[3] for e in CompileTelemetry.xla_events()
        if e[0] > t0 and e[1].endswith("backend_compile_duration")
    ]
    assert "jit(tpudes_bss_init)" in compiled
    assert "jit(tpudes_lte_sm_init)" in compiled


if __name__ == "__main__":
    # python tests/test_launch_init.py 30 > tests/golden/launch_path_parent.json
    # (run from the PARENT tree's root with this file's path; the
    # argument names the issue whose variants are written)
    import itertools
    import sys

    entries = {}
    for variant, n_cfg, on_mesh in itertools.product(
        VARIANTS_30 if sys.argv[1:] == ["30"] else VARIANTS_29,
        [None, 2], [False, True],
    ):
        if variant == "wired" and n_cfg is not None:
            continue
        entries[_golden_name(variant, n_cfg, on_mesh)] = golden_entry(
            variant, n_cfg, _mesh(on_mesh)
        )
    print(json.dumps(entries, indent=1, sort_keys=True))
