"""Fused LTE per-TTI kernel chain — Pallas inner loops + precision policy.

The LTE SM engine was the outlier in every bench round (~410
sim-s/wall-s vs 3k-13k for the other engines) and PR 5's async
pipelining barely moved it, so the cost lives INSIDE the compiled
per-TTI scan.  This module rebuilds that hot path as one fused kernel
over the ``(U, RB)``-derived inner arrays:

    retx admission ─► scheduler metric + per-cell winner ─► allocation
    ─► MI/BLER ─► HARQ decode ─► state update

as a single hand-written Pallas kernel (:func:`build_sm_step_fn`),
with three structural properties the tests pin:

- **One math core, two lowerings.**  :func:`sm_step_math` is the only
  definition of the TTI math; the Pallas kernel body and the plain-XLA
  lowering both execute it, so ``TPUDES_PALLAS=1`` and ``=0`` produce
  BIT-identical results on the same backend.  On non-TPU backends the
  ``pallas_call`` runs in interpret mode (discharged to ordinary XLA
  ops at trace time — zero runtime overhead), so the CPU tier-1 suite
  exercises the exact kernel body that Mosaic compiles on TPU.
- **TPU-shaped data layout.**  Per-UE state is carried as ``(1, U)``
  lane rows and per-cell state as ``(E, 1)`` sublane columns; every
  cross-axis quantity is a broadcast-and-reduce over the ``(E, U)``
  grid or a small ``(U, U)`` masked-prefix matmul (the per-cell
  retransmission cumsum), never a dynamic gather — Mosaic-friendly by
  construction.  Integer quantities that ride f32 matmuls are bounded
  far below 2^24, so the float path is exact.
- **Mixed precision with an explicit budget.**  ``precision="bf16"``
  (an :class:`~tpudes.parallel.lte_sm.LteSmProgram` field, a cache-key
  component, never a traced operand) computes the SINR→CQI→MI prelude
  and the per-TTI scheduler-metric / BLER-argument arithmetic in
  bfloat16 while keeping every ACCUMULATOR (PF average EMA, HARQ-IR
  MI accumulation, bit counters) and every transcendental (log2, erfc,
  sqrt) in f32 — the f32-accumulating-reduction policy.  The error
  budget is pinned by tests/test_ops_lte_kernels.py (ULP envelope on
  the SINR chain, MI/BLER budget) and tests/test_lte_sm.py (host
  parity holds under bf16 at the same tolerances).

Which lowering a launch takes is the engine's choice
(``lte_sm._sm_use_pallas``), made from what it sees: under a mesh the
XLA lowering on every backend (GSPMD cannot partition a Mosaic call);
unsharded, by the number of lanes the step is ``vmap``ped over — the
batching rule turns them into a sequential grid of this kernel, one
``(1, U)`` row a step, so on a v5e the kernel wins unbatched (7.2 us a
7 x 210 TTI against 10.1) and loses from two lanes on (62.8 against
6.2 at 64; PERF.md section 6, PR 31).  ``TPUDES_PALLAS``
(:func:`pallas_switch`) overrides the lane rule: ``0`` is the kill
switch (XLA everywhere), an explicit ``1`` the kernel on every
unsharded launch (the A/B tests' kernel side).  The runtime cache keys
the resolved lowering, so flips never collide on a stale executable;
``lte_sm.compiled_step_lowering`` reads which step an executable
actually holds.
"""

from __future__ import annotations

import os

import numpy as np

from tpudes.models.lte.scheduler import (
    HARQ_MAX_TX,
    HARQ_RTT_TTIS,
    rbg_size_for,
)
from tpudes.ops.lte import (
    RB_BANDWIDTH_HZ,
    RE_PER_RB_DATA,
    _MCS_ECR,
    _MCS_EFF,
    _MCS_QM,
    cqi_from_sinr,
    mcs_from_cqi,
    mi_per_rb,
    tb_bler_ecr,
)

#: precision modes the engine accepts; "bf16" is the mixed-precision
#: mode documented above, "f32" the exact legacy arithmetic
SM_PRECISIONS = ("f32", "bf16")

#: scheduler short name → traced dispatch id.  Families sharing a
#: full-buffer-degenerate metric share an id group in the kernel's
#: dispatch (see tpudes/parallel/lte_sm.py module docstring); the id
#: itself is a RUNTIME operand of the compiled program, so all nine
#: ride one XLA executable.  Lives here (not in lte_sm) because the
#: kernel's family-boundary constants below MUST derive from it — a
#: reordered table with hand-kept thresholds would silently dispatch
#: the wrong metric.
SM_SCHED_IDS = {
    "pf": 0, "cqa": 1, "pss": 2,
    "rr": 3, "tta": 4,
    "tdmt": 5, "fdmt": 6,
    "tdbet": 7, "fdbet": 8,
}

#: family boundaries of the traced dispatch: ids ≤ _PF_MAX take the PF
#: metric, ≤ _RR_MAX round-robin, ≤ _MT_MAX max-throughput, else BET
_PF_MAX = SM_SCHED_IDS["pss"]
_RR_MAX = SM_SCHED_IDS["tta"]
_MT_MAX = SM_SCHED_IDS["fdmt"]

NEG = -1e30  # the "no candidate" metric fill (finite in bf16 too)


def pallas_switch() -> bool | None:
    """``TPUDES_PALLAS`` as set: ``None`` when unset or empty (the
    engine picks the lowering by lane count,
    ``lte_sm._sm_use_pallas``), ``False`` for ``0`` / ``false`` /
    ``no`` / ``off`` (XLA everywhere), ``True`` for anything else (the
    kernel on every unsharded launch).  Read per call so tests can A/B
    without re-importing — the same contract as ``TPUDES_BUCKETING``."""
    raw = (os.environ.get("TPUDES_PALLAS") or "").strip().lower()
    if not raw:
        return None
    return raw not in {"0", "false", "no", "off"}


def pallas_enabled() -> bool:
    """The fused Pallas TTI kernel is not switched off."""
    return pallas_switch() is not False


def _compute_dtype(precision: str):
    import jax.numpy as jnp

    if precision not in SM_PRECISIONS:
        raise ValueError(
            f"precision {precision!r} not in {SM_PRECISIONS}"
        )
    return jnp.bfloat16 if precision == "bf16" else jnp.float32


# --------------------------------------------------------------------------
# build-time constants (the SINR → CQI half of the chain)
# --------------------------------------------------------------------------


def build_sm_consts(prog) -> dict:
    """Static per-program constants of the fused step, all numpy.

    Full-buffer ⇒ full grid ⇒ the interference pattern is static, so
    the SINR → CQI → MCS → MI chain collapses to per-UE constants
    computed ONCE at build time.  Under ``precision="bf16"`` the SINR
    is rounded to bfloat16 storage and the CQI/MI arithmetic runs at
    the mixed-precision policy (products in bf16, log2/reductions in
    f32) — the rounded values are then carried as f32 constants, so
    the kernel boundary stays f32 either way.
    """
    import jax.numpy as jnp

    E, U = prog.n_enb, prog.n_ue
    rbg_size = rbg_size_for(prog.n_rb)
    n_rbg = (prog.n_rb + rbg_size - 1) // rbg_size
    dtype = _compute_dtype(prog.precision)

    psd = 10.0 ** ((prog.tx_power_dbm - 30.0) / 10.0) / (
        prog.n_rb * RB_BANDWIDTH_HZ
    )  # (E,) W/Hz
    seen = psd[:, None] * prog.gain                       # (E, U)
    total = seen.sum(axis=0)                              # (U,)
    sig = seen[prog.serving, np.arange(U)]
    sinr_np = sig / (total - sig + prog.noise_psd)        # (U,) flat over RBs

    # storage rounding: bf16 mode quantizes the SINR the whole chain
    # sees; f32 mode reproduces the legacy arithmetic bit for bit
    sinr = np.asarray(
        jnp.asarray(sinr_np, jnp.float32).astype(dtype).astype(jnp.float32)
    )
    cqi = np.asarray(cqi_from_sinr(jnp.asarray(sinr), dtype=dtype))
    mcs0 = np.asarray(mcs_from_cqi(jnp.asarray(cqi)))
    qm0 = _MCS_QM[mcs0]
    mi0 = np.asarray(
        mi_per_rb(jnp.asarray(sinr), jnp.asarray(qm0), dtype=dtype)
    )
    eligible = cqi >= 1
    eff0 = _MCS_EFF[mcs0]                                 # (U,) bits/RE
    ecr0 = _MCS_ECR[mcs0]                                 # (U,) code rate
    # bits/s if served the whole grid (the PF/MT rate metric)
    rate0 = np.floor(eff0 * rbg_size * RE_PER_RB_DATA) * 1000.0

    cell_onehot = prog.serving[None, :] == np.arange(E)[:, None]  # (E, U)
    # RR rotation bookkeeping: position of each UE within its cell
    pos = np.zeros((U,), dtype=np.int32)
    count_c = np.zeros((E,), dtype=np.int32)
    for u in range(U):
        c = int(prog.serving[u])
        pos[u] = count_c[c]
        count_c[c] += 1
    count_u = np.maximum(count_c, 1)[prog.serving]
    # per-cell prefix-sum operator: cum_u = nrbg_req(1,U) @ prefix
    # where prefix[u', u] = same-cell AND u' <= u (UE-index order, the
    # host rnti admission order).  Values are bounded by U * n_rbg
    # (≈ thousands) — exact in the f32 matmul.
    same_cell = prog.serving[:, None] == prog.serving[None, :]    # (U, U)
    prefix = (
        same_cell & (np.arange(U)[:, None] <= np.arange(U)[None, :])
    ).astype(np.float32)

    row_f32 = lambda a: np.asarray(a, np.float32).reshape(1, U)  # noqa: E731
    row_i32 = lambda a: np.asarray(a, np.int32).reshape(1, U)    # noqa: E731
    return dict(
        E=E, U=U, n_rbg=n_rbg, rbg_size=rbg_size, n_rb=prog.n_rb,
        pf_alpha=float(prog.pf_alpha), precision=prog.precision,
        sinr=row_f32(sinr), cqi=row_i32(cqi), mcs=row_i32(mcs0),
        mi0=row_f32(mi0), rate0=row_f32(rate0),
        eff0=row_f32(eff0), ecr0=row_f32(ecr0),
        eligible=row_i32(eligible),
        cell_onehot=cell_onehot.astype(np.float32),       # (E, U)
        pos=row_i32(pos), count_u=row_i32(count_u),
        count_c=np.asarray(count_c, np.int32).reshape(E, 1),
        prefix=prefix,                                    # (U, U)
    )


#: the Pallas TTI kernel's stable name (``pl.pallas_call(name=...)``)
SM_KERNEL_NAME = "tpudes_lte_sm_tti"

#: carry layout of the fused step: (key, shape-suffix, dtype).  Per-UE
#: state rides (1, U) lane rows, the RR pointer (E, 1) sublane columns.
SM_STATE = (
    ("avg", "u", "f32"), ("pend", "u", "i32"),
    ("p_mi", "u", "f32"), ("p_tbb", "u", "f32"),
    ("p_nrbg", "u", "i32"), ("p_txc", "u", "i32"), ("p_due", "u", "i32"),
    ("rr_ptr", "e", "i32"),
    ("rx_lo", "u", "i32"), ("rx_hi", "u", "i32"),
    ("new_tbs", "u", "i32"), ("retx", "u", "i32"),
    ("drops", "u", "i32"), ("ok_cnt", "u", "i32"),
)


def sm_init_state(E: int, U: int) -> dict:
    import jax.numpy as jnp

    shapes = {"u": (1, U), "e": (E, 1)}
    dts = {"f32": jnp.float32, "i32": jnp.int32}
    out = {k: jnp.zeros(shapes[sx], dts[dt]) for k, sx, dt in SM_STATE}
    out["avg"] = jnp.ones((1, U), jnp.float32)
    return out


# --------------------------------------------------------------------------
# the TTI math core — one definition, shared by both lowerings
# --------------------------------------------------------------------------


def sm_admit_retx(cj: dict, s: dict, t):
    """Stage 1 — HARQ retransmission admission: which due TBs fit the
    per-cell RBG budget (UE-index order, the host rnti tie-break), and
    how many RBGs each cell has left for new data."""
    import jax.numpy as jnp

    pend = s["pend"] != 0
    due = pend & (s["p_due"] <= t) & (cj["eligible"] != 0)
    nrbg_req = jnp.where(due, s["p_nrbg"], 0)
    # per-cell capped admission via the masked prefix matmul (exact:
    # integer values far below 2^24)
    cum_u = jnp.dot(
        nrbg_req.astype(jnp.float32), cj["prefix"],
        preferred_element_type=jnp.float32,
    )                                                           # (1, U)
    retx_fit = due & (cum_u <= cj["n_rbg"])
    used_c = jnp.sum(
        cj["cell_onehot"] * jnp.where(retx_fit, nrbg_req, 0),
        axis=1, keepdims=True,
    ).astype(jnp.int32)                                         # (E, 1)
    rem_c = cj["n_rbg"] - used_c
    return pend, retx_fit, rem_c


def sm_dispatch(cj: dict, s: dict, pend, rem_c, sid):
    """Stage 2 — scheduler dispatch: one metric per FF-MAC family
    (selected by the traced scheduler id), per-cell winner at the
    lowest-UE-index tie-break, winner-takes-the-rest allocation."""
    import jax
    import jax.numpy as jnp

    dtype = _compute_dtype(cj["precision"])
    E, U = cj["E"], cj["U"]
    cand = (cj["eligible"] != 0) & ~pend
    # metric arithmetic at the compute precision (ONE bf16 division on
    # the hot path); the EMA accumulator itself stays f32
    rate0 = cj["rate0"].astype(dtype).astype(jnp.float32)
    avg = s["avg"].astype(dtype)
    pf_metric = (
        cj["rate0"].astype(dtype) / jnp.maximum(avg, 1.0)
    ).astype(jnp.float32)
    rr_ptr_u = jnp.sum(
        cj["cell_onehot"] * s["rr_ptr"], axis=0, keepdims=True
    ).astype(jnp.int32)                                         # (1, U)
    ahead = jnp.mod(cj["pos"] - rr_ptr_u, cj["count_u"])
    # `ahead` is an exact ORDINAL (queue position), not approximate
    # arithmetic: it stays f32 in every precision mode (bf16 would
    # collapse positions ≥ 256 into ties and desync the rotation)
    rr_metric = -ahead.astype(jnp.float32)
    # pf/cqa/pss → PF; rr/tta → RR; td/fd-mt → rate; td/fd-bet → -avg
    metric = jnp.where(
        sid <= _PF_MAX, pf_metric,
        jnp.where(
            sid <= _RR_MAX, rr_metric,
            jnp.where(sid <= _MT_MAX, rate0, -avg.astype(jnp.float32)),
        ),
    )
    neg = jnp.float32(NEG)
    m_eu = jnp.where(
        (cj["cell_onehot"] > 0) & cand, metric, neg
    )                                                           # (E, U)
    mx_e = jnp.max(m_eu, axis=1, keepdims=True)                 # (E, 1)
    iota_u = jax.lax.broadcasted_iota(jnp.int32, (E, U), 1)
    win_idx = jnp.min(
        jnp.where(m_eu == mx_e, iota_u, U), axis=1, keepdims=True
    )
    has_win = (mx_e > neg) & (rem_c > 0)                        # (E, 1)
    winner_oh = (iota_u == win_idx) & has_win                   # (E, U)
    is_winner = jnp.sum(winner_oh, axis=0, keepdims=True) > 0   # (1, U)
    new_nrbg = jnp.sum(
        winner_oh * rem_c, axis=0, keepdims=True
    ).astype(jnp.int32)                                         # (1, U)
    ptr_winner = jnp.sum(
        winner_oh * cj["pos"], axis=1, keepdims=True
    ).astype(jnp.int32)                                         # (E, 1)
    new_ptr = jnp.where(
        has_win, jnp.mod(ptr_winner + 1, cj["count_c"]), s["rr_ptr"]
    )
    return dict(is_winner=is_winner, new_nrbg=new_nrbg, new_ptr=new_ptr)


def sm_decode(cj: dict, s: dict, retx_fit, new_nrbg, is_winner, coin):
    """Stage 3 — transport blocks + MI-based HARQ-IR decode: TB sizes
    from the static MCS, accumulated MI (f32 accumulator), BLER at the
    compute precision with the erfc tail in f32, decode coin compare."""
    import jax.numpy as jnp

    new_nrb = jnp.minimum(new_nrbg * cj["rbg_size"], cj["n_rb"])
    tb_new = jnp.floor(
        cj["eff0"] * new_nrb.astype(jnp.float32) * RE_PER_RB_DATA
    )
    tx = retx_fit | is_winner
    tbb_tx = jnp.where(retx_fit, s["p_tbb"], tb_new)
    # HARQ-IR MI accumulation in f32 (the accumulator policy)
    mi_tx = jnp.where(
        retx_fit, jnp.minimum(s["p_mi"] + cj["mi0"], 1.0), cj["mi0"]
    )
    bler = tb_bler_ecr(
        mi_tx, cj["ecr0"], tbb_tx, dtype=_compute_dtype(cj["precision"])
    )
    ok = tx & (coin >= bler)
    return tx, tbb_tx, mi_tx, ok


def sm_update(cj: dict, s: dict, retx_fit, disp, tx, tbb_tx, mi_tx, ok, t):
    """Stage 4 — HARQ bookkeeping + accumulators (all f32/int32): the
    pend/retx/drop ladder, the PF EMA, the 52-bit exact rx counter."""
    import jax.numpy as jnp

    fail = tx & ~ok
    txc_after = jnp.where(retx_fit, s["p_txc"] + 1, 1)
    dropped = fail & (txc_after >= HARQ_MAX_TX)
    repend = fail & ~dropped
    # a due TB that didn't fit the RBG budget stays pending (its p_due
    # is already <= t, so it retries next TTI) — clearing on `due`
    # alone would silently erase it
    keep = (s["pend"] != 0) & ~retx_fit
    served_bits = jnp.where(ok, tbb_tx, 0.0)
    lo = s["rx_lo"] + served_bits.astype(jnp.int32)
    return dict(
        avg=(1.0 - cj["pf_alpha"]) * s["avg"]
        + cj["pf_alpha"] * served_bits * 1000.0,
        pend=(keep | repend).astype(jnp.int32),
        p_mi=jnp.where(repend, mi_tx, s["p_mi"]),
        p_tbb=jnp.where(repend, tbb_tx, s["p_tbb"]),
        p_nrbg=jnp.where(
            repend,
            jnp.where(retx_fit, s["p_nrbg"], disp["new_nrbg"]),
            s["p_nrbg"],
        ),
        p_txc=jnp.where(repend, txc_after, s["p_txc"]),
        p_due=jnp.where(repend, t + HARQ_RTT_TTIS, s["p_due"]),
        rr_ptr=disp["new_ptr"],
        # exact bit accounting without int32 overflow on long runs:
        # rx_lo rolls over into rx_hi at 2^20 (≤1e5 bits/TTI, so rx_lo
        # never exceeds 2^21 before the carry)
        rx_lo=lo & 0xFFFFF,
        rx_hi=s["rx_hi"] + (lo >> 20),
        new_tbs=s["new_tbs"] + disp["is_winner"].astype(jnp.int32),
        retx=s["retx"] + retx_fit.astype(jnp.int32),
        drops=s["drops"] + dropped.astype(jnp.int32),
        ok_cnt=s["ok_cnt"] + ok.astype(jnp.int32),
    )


def sm_step_math(cj: dict, s: dict, coin, t, sid) -> dict:
    """One TTI of the whole chain — the single definition both the
    Pallas kernel body and the plain-XLA fallback execute."""
    pend, retx_fit, rem_c = sm_admit_retx(cj, s, t)
    disp = sm_dispatch(cj, s, pend, rem_c, sid)
    tx, tbb_tx, mi_tx, ok = sm_decode(
        cj, s, retx_fit, disp["new_nrbg"], disp["is_winner"], coin
    )
    return sm_update(cj, s, retx_fit, disp, tx, tbb_tx, mi_tx, ok, t)


# --------------------------------------------------------------------------
# the two lowerings
# --------------------------------------------------------------------------


def _as_jnp_consts(consts: dict) -> dict:
    import jax.numpy as jnp

    return {
        k: (jnp.asarray(v) if isinstance(v, np.ndarray) else v)
        for k, v in consts.items()
    }


def build_sm_step_fn(consts: dict, use_pallas: bool, dynamic: tuple = ()):
    """Returns ``step(state_dict, coin, t, sid[, dyn]) -> state_dict``.

    ``use_pallas=True`` lowers the math core through ONE
    ``pl.pallas_call`` — compiled by Mosaic on TPU (VMEM-resident
    state, SMEM scalars), interpret-mode (= discharged to ordinary XLA
    ops at trace time) everywhere else so the CPU tier-1 suite runs the
    very same kernel body.  ``False`` is the plain XLA lowering of the
    same core — what every batched or sharded launch takes
    (``lte_sm._sm_use_pallas``).

    ``dynamic`` names const entries that arrive PER CALL as the ``dyn``
    dict instead of closing over the build-time tables — the
    device-resident mobility seam: a geometry stage recomputes the
    SINR-derived per-UE rows (mi0/rate0/eff0/ecr0/eligible) every
    ``geom_stride`` TTIs and feeds them through here, with the kernel
    body (and the Pallas lowering's input list) unchanged.
    """
    import jax
    import jax.numpy as jnp

    cj = _as_jnp_consts(consts)
    keys = [k for k, _, _ in SM_STATE]
    dynamic = tuple(dynamic)

    if not use_pallas:
        def step(s, coin, t, sid, dyn=None):
            ck = cj if not dynamic else {**cj, **dyn}
            return sm_step_math(ck, s, coin, t, sid)

        return step

    from jax.experimental import pallas as pl

    E, U = consts["E"], consts["U"]
    shapes = {"u": (1, U), "e": (E, 1)}
    dts = {"f32": jnp.float32, "i32": jnp.int32}
    out_shape = tuple(
        jax.ShapeDtypeStruct(shapes[sx], dts[dt]) for _, sx, dt in SM_STATE
    )
    # pallas kernels may not capture array constants — the static
    # per-program tables ride as explicit inputs (under vmap they stay
    # unbatched: the batching rule maps them to the same block for
    # every replica/config lane, no R-fold copy)
    const_names = [
        k for k, v in consts.items()
        if isinstance(v, np.ndarray) and k not in ("sinr", "cqi", "mcs")
    ]
    scalars = {
        k: v for k, v in consts.items() if not isinstance(v, np.ndarray)
    }

    def kernel(t_ref, sid_ref, coin_ref, *refs):
        nc, ns = len(const_names), len(keys)
        ck = dict(scalars)
        ck.update(
            {k: r[...] for k, r in zip(const_names, refs[:nc])}
        )
        s = {k: r[...] for k, r in zip(keys, refs[nc:nc + ns])}
        new = sm_step_math(
            ck, s, coin_ref[...], t_ref[0, 0], sid_ref[0, 0]
        )
        for k, r in zip(keys, refs[nc + ns:]):
            r[...] = new[k]

    # never interpret on a TPU: there the kernel compiles through
    # Mosaic or the launch fails with the compiler's own error (this
    # branch is AOT-compiled for a v5e by tests/test_lte_pallas.py)
    interpret = jax.default_backend() != "tpu"
    kwargs = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu

        smem = pl.BlockSpec((1, 1), memory_space=pltpu.SMEM)
        vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
        kwargs = dict(
            in_specs=[smem, smem]
            + [vmem] * (1 + len(const_names) + len(keys)),
            out_specs=tuple(vmem for _ in keys),
        )

    # the name is the kernel's in every profile (a device-trace reader
    # finds the TTI by it, not by what the compiler calls the call site)
    call = pl.pallas_call(
        kernel, out_shape=out_shape, interpret=interpret,
        name=SM_KERNEL_NAME, **kwargs
    )

    def step(s, coin, t, sid, dyn=None):
        out = call(
            jnp.reshape(t, (1, 1)), jnp.reshape(sid, (1, 1)), coin,
            *[
                (dyn[k] if k in dynamic else cj[k])
                for k in const_names
            ],
            *[s[k] for k in keys],
        )
        return dict(zip(keys, out))

    return step
