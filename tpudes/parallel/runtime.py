"""Shared engine runtime: the one launch path, the runner cache, shape
bucketing, donation and the persistent compile cache of the device
engines (replicated BSS, LTE SM, TCP dumbbell, AS flows, wired).

- :class:`Launch` — what a launch IS, written once.  An engine's
  ``run_*`` describes what only it knows (its cache key, its builder,
  its operand tables, how its advance program takes them, what it
  fetches and how it unpacks); the launch does the rest in one order
  for all of them: bucket the replica axis, find or build the runner,
  make the carry with the entry's init program, fingerprint a
  checkpoint, time a compile, dispatch one advance program per
  segment, block when that call compiled, chain the metrics flush in
  front of the unpack, return the result or its future.  A warm launch
  of any engine dispatches two programs: ``tpudes_<engine>_init`` and
  ``tpudes_<engine>_advance``.

- :class:`EngineRuntime` / :data:`RUNTIME` — one process-wide runner
  registry with **true LRU eviction** (a cache hit moves the entry to
  the back of the eviction order).  Misses call the launch's ``build``
  and report ``compiled_new``, so
  :class:`~tpudes.obs.device.CompileTelemetry` is triggered from
  exactly one place.

- **Shape bucketing** (:func:`bucket_replicas`): the replica axis is
  padded up to the next power of two (and to a multiple of the mesh
  device count when sharding), so a replica-count sweep compiles one
  program per *bucket* instead of one per point; callers slice results
  back to the requested count.  Horizons (``max_steps`` / TTIs / slots)
  need no bucket at all: the engines take the horizon as a **traced
  operand** of a ``lax.while_loop`` bound, so one executable serves
  every horizon with zero masked-iteration cost.

  Bucketing is *exact*, not statistical: padding must not change any
  real replica's outcome, which is why the engines derive per-replica
  randomness via :func:`replica_keys` / per-step ``fold_in`` — replica
  ``r``'s stream is a pure function of ``(key, r)`` and step ``t``'s of
  ``(key, t)``, independent of the padded axis sizes.  (A joint
  ``jax.random.uniform(key, (R, n))`` draw or ``split(key, R)`` does
  NOT have this property: threefry lays counters out per-shape, so
  growing R would silently reshuffle every replica's draws.)
  ``TPUDES_BUCKETING=0`` disables padding for A/B debugging.

- **Donation**: the state carry crossing the jit boundary is donated on
  accelerators (it is made fresh per launch, so XLA may alias it into
  the loop buffers instead of copying); XLA:CPU does not implement
  donation and warns per call, so :func:`donate_argnums` gives the CPU
  backend an empty list.

- :func:`configure_persistent_cache` — arms jax's persistent
  compilation cache, so a *second process* running the same engines
  skips the XLA compiles entirely (the in-memory runner cache only ever
  amortized within one process).  The directory comes from
  ``JAX_COMPILATION_CACHE_DIR`` when set, else (accelerator backends
  only) the fixed ``<checkout>/.jax_cache``.  Wired lazily on the
  first runner build.

- **Async submission** (:meth:`EngineRuntime.submit` /
  :class:`EngineFuture`): every ``run_*`` entry point takes
  ``block=False`` and returns an :class:`EngineFuture` instead of
  blocking — the device work is dispatched (jax's async dispatch) but
  the D2H fetch and host-side unpack are deferred to ``result()``.
  ``RUNTIME.submit(run_fn, *args, **kw)`` adds a **bounded in-flight
  window** on top (``TPUDES_INFLIGHT``, default 4): submitting past the
  window retires the oldest future first, so a heterogeneous sweep
  (different buckets → different executables) keeps the device busy
  while the host builds/unpacks other points instead of serializing on
  a ``block_until_ready`` per point.  Telemetry (``submitted``,
  ``retired``, ``max_in_flight``, per-engine ``launches``) rides
  :meth:`EngineRuntime.stats` so pipelining is pinned by tests, not
  assumed.

- **Chunked horizons** (:func:`chunk_bounds`): a long horizon splits
  into fixed-size ``while_loop`` segments; the launch hands the carry
  from segment to segment (donated, so the state never copies) and
  each segment returns a small metrics tree that streams to
  :class:`tpudes.obs.device.ChunkStream` while the *next* one runs.
  Results are bit-identical to a single-shot run because every step's
  randomness is ``fold_in(key, t)`` — pure in t, indifferent to where
  the segment boundaries fall.

- **Launch-path spans and device names**, always on
  (:mod:`tpudes.obs.spans`): ``launch.runner`` in
  :meth:`EngineRuntime.runner`, ``launch.operands`` in
  :meth:`Launch.prepare`, ``launch.enqueue`` in :func:`drive_chunks`,
  ``result.wait`` / ``.fetch`` / ``.unpack`` in :class:`EngineFuture`,
  whose constructor also ends the ``launch`` span ``run_lifted``
  opened.  :func:`scoped_while_loop` gives every engine's outermost
  loop the stable device names ``tpudes.<engine>.step`` / ``.cond``;
  :func:`step_keys`, which derives a step's per-replica keys for the
  loops that fold a scalar counter into the launch key (BSS,
  dumbbell), traces under ``tpudes.<engine>.rng``.
"""

from __future__ import annotations

import os
from collections import OrderedDict

from tpudes.obs import spans

__all__ = [
    "RUNTIME",
    "EngineFuture",
    "EngineRuntime",
    "Launch",
    "bucket_replicas",
    "chunk_bounds",
    "configure_persistent_cache",
    "donate_argnums",
    "pow2_bucket",
    "replica_keys",
    "scoped_while_loop",
    "shard_replica_axis",
    "step_keys",
    "stack_axis",
]


def bucketing_enabled() -> bool:
    """Shape bucketing is on unless ``TPUDES_BUCKETING`` says otherwise
    (read per call so tests can A/B without re-importing)."""
    raw = os.environ.get("TPUDES_BUCKETING")
    if raw is None:
        return True
    return raw.strip().lower() not in {"0", "false", "no", "off"}


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    n = int(n)
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def bucket_replicas(replicas: int | None, mesh=None) -> int | None:
    """Padded replica-axis size: next power of two, then rounded up to a
    multiple of the mesh device count so the sharded axis always divides
    evenly.  ``None`` (no replica axis) passes through."""
    if replicas is None:
        return None
    r = int(replicas)
    if bucketing_enabled():
        r = pow2_bucket(r)
    if mesh is not None:
        n_dev = len(mesh.devices.flat)
        r = ((r + n_dev - 1) // n_dev) * n_dev
    return r


def replica_keys(key, n: int):
    """(n, …) batch of per-replica PRNG keys; row ``i`` is
    ``fold_in(key, i)`` — a pure function of ``(key, i)`` independent of
    ``n``, so padding the replica axis to a bucket leaves every real
    replica's stream untouched.  ``jax.random.split(key, n)`` must NOT
    be used for this: its rows depend on n."""
    import jax
    import jax.numpy as jnp

    return jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))


def inflight_window() -> int:
    """Bound on concurrently in-flight submitted runs
    (``TPUDES_INFLIGHT``, default 4, floor 1; read per call so tests
    can resize without re-importing)."""
    raw = os.environ.get("TPUDES_INFLIGHT")
    if not raw:
        return 4
    try:
        return max(1, int(raw))
    except ValueError:
        return 4


def chunk_bounds(total: int, chunk: int) -> list[int]:
    """Segment end-bounds covering ``[0, total)`` in ``chunk``-sized
    pieces: ``chunk_bounds(10, 4) == [4, 8, 10]``.  A non-positive or
    oversized chunk degenerates to one segment."""
    total = int(total)
    chunk = int(chunk)
    if chunk <= 0 or chunk >= total:
        return [total]
    return list(range(chunk, total, chunk)) + [total]


def drive_chunks(engine: str, bounds, carry, launch, obs: bool,
                 checkpoint=None):
    """The chunk-dispatch protocol of :meth:`Launch.drive`: one device
    launch per bound; when observability is up AND the run is actually
    chunked (>1 bound — a single-shot run has no chunk stream), chunk
    k's metrics are fetched only after chunk k+1 is dispatched, so the
    D2H overlaps the next segment's compute.  Returns ``(carry,
    flush)``: the final carry plus a deferred thunk (or None) that
    records the LAST chunk's metrics — it runs inside the
    EngineFuture's finalize, so a ``block=False`` caller's dispatch
    never blocks on a metrics fetch.

    ``launch(carry, bound) -> (carry', metrics)`` — INVARIANT: every
    leaf of ``metrics`` must be a FRESH device value (a reduction or
    other computed output), never a leaf of the returned carry: the
    next launch donates the carry on accelerators, and a metrics tree
    aliasing it would be deleted before the deferred fetch reads it.

    ``checkpoint`` (a :func:`tpudes.parallel.checkpoint.checkpoint_ctx`
    result) persists the carry after every completed chunk and, when a
    matching checkpoint already exists, SKIPS the completed chunks and
    resumes from the restored carry — bit-equal to an uninterrupted
    run, since per-step randomness is ``fold_in``-keyed and segment-
    boundary-indifferent.  Checkpointing trades the chunk-pipelining
    overlap for durability: each save blocks on that chunk's D2H.
    """
    import jax

    from tpudes.obs.device import ChunkStream

    bounds = list(bounds)
    start = 0
    if checkpoint is not None:
        restored = checkpoint.ckpt.restore(checkpoint, bounds)
        if restored is not None:
            done_bound, carry = restored
            start = bounds.index(done_bound) + 1
    stream = obs and len(bounds) > 1
    prev = None
    with spans.span(
        "launch.enqueue", engine=engine, chunks=len(bounds) - start
    ):
        for bound in bounds[start:]:
            carry, metrics = launch(carry, bound)
            RUNTIME.record_launch(engine)
            if checkpoint is not None:
                checkpoint.ckpt.save(checkpoint, bound, bounds, carry)
            if stream:
                if prev is not None:
                    ChunkStream.record(
                        engine, prev[0], jax.device_get(prev[1])
                    )
                prev = (bound, metrics)
    if not (stream and prev is not None):
        return carry, None

    def flush(last=prev):
        ChunkStream.record(engine, last[0], jax.device_get(last[1]))

    return carry, flush


def stack_axis(tree, n: int | None):
    """Broadcast every leaf of ``tree`` to a new leading axis of size
    ``n`` (None passes through) — how the engines stack the initial
    carry over the replica and config axes.  One ``broadcast_to`` per
    leaf, so it belongs inside a carry builder that :func:`jit_init`
    traces, where the whole carry is one program."""
    if n is None:
        return tree
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (int(n),) + jnp.shape(x)), tree
    )


def _replica_spec(v, r_pad: int, axis: int):
    """The one rule for which leaves carry the replica axis: dimension
    ``axis`` exists and equals ``r_pad``.  Returns the leaf's
    PartitionSpec with that dimension on "replica", else None."""
    from jax.sharding import PartitionSpec as P

    if getattr(v, "ndim", 0) > axis and v.shape[axis] == r_pad:
        return P(*([None] * axis), "replica",
                 *([None] * (v.ndim - axis - 1)))
    return None


def shard_replica_axis(tree, mesh, r_pad: int | None, axis: int):
    """device_put every leaf whose ``axis`` dimension equals ``r_pad``
    with that dimension sharded over the mesh's "replica" axis (other
    leaves pass through).  ``axis`` is 0 for plain runs, 1 when a
    config axis leads.  One transfer per leaf: a :func:`jit_init`
    program places its outputs by the same rule with no transfer, so
    this is for arrays that exist already — a restored checkpoint's
    carry (``checkpoint.py``)."""
    if mesh is None or r_pad is None:
        return tree
    import jax
    from jax.sharding import NamedSharding

    def put(v):
        spec = _replica_spec(v, r_pad, axis)
        return v if spec is None else jax.device_put(
            v, NamedSharding(mesh, spec)
        )

    return jax.tree_util.tree_map(put, tree)


def donate_argnums(*argnums: int) -> tuple[int, ...]:
    """``argnums`` on accelerators, ``()`` on CPU (XLA:CPU does not
    implement buffer donation and logs a warning per donated call)."""
    import jax

    return argnums if jax.default_backend() != "cpu" else ()


def scoped_while_loop(engine: str, cond, body, init):
    """``lax.while_loop`` whose ``body`` and ``cond`` trace under the
    stable device names ``tpudes.<engine>.step`` / ``.cond``
    (``jax.named_scope``): every operation of the loop carries them in
    its metadata, so a profile reads the engine step by OUR name
    whatever the compiler calls the fusions.  Every engine's outermost
    loop, the one a step time divides, goes through here.  Scopes
    change metadata only; results are bit-identical."""
    import jax

    def scoped(part, fn):
        def inner(c):
            with jax.named_scope(f"tpudes.{engine}.{part}"):
                return fn(c)

        return inner

    return jax.lax.while_loop(scoped("cond", cond), scoped("step", body), init)


#: the block :func:`step_keys` folds the step counter over: one TPU
#: vector register of 32-bit lanes.  Read on the chip against the whole
#: replica axis ``(R,)`` and against ``(128,)`` (PERF.md section 6, PR 36)
_STEP_KEY_LANES = (8, 128)


def step_keys(engine: str, key, counter, n: int):
    """(n, …) per-replica keys of ONE step of an engine's loop: row
    ``r`` is ``fold_in(fold_in(key, counter), r)`` — pure in ``(key,
    counter, r)`` and independent of ``n``, so bucketing and chunked
    re-entry (``counter`` > 0 on entry) keep every replica's stream.
    ``counter`` is the loop's traced scalar step count.

    Both folds run with VECTOR operands.  ``fold_in(key, counter)`` on
    the scalar itself is ~124 unfused threefry operations on the TPU's
    scalar core, inside the ``while``, with no event of their own (3.5
    of the BSS loop's 5.2 µs a step, PERF.md section 6, PRs 33 and 36).
    So the counter is broadcast to one vector register of lanes behind
    an ``optimization_barrier`` (without it XLA folds the broadcast
    back into the scalar computation), folded there as redundant lanes
    — one fusion — and the rows of that block seed the per-replica
    fold.  Same bits either way.  The keys trace under the device name
    ``tpudes.<engine>.rng``."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope(f"tpudes.{engine}.rng"):
        lanes = jax.lax.optimization_barrier(
            jnp.broadcast_to(counter, _STEP_KEY_LANES)
        )
        step_key = jax.vmap(jax.vmap(lambda c: jax.random.fold_in(key, c)))(
            lanes
        ).reshape((-1,) + jnp.shape(key))
        reps = (-(-n // len(step_key)),) + (1,) * jnp.ndim(key)
        rows = jnp.tile(step_key, reps)[:n]
        return jax.vmap(jax.random.fold_in)(rows, jnp.arange(n))


def _named(fn, name: str):
    """``fn`` under the program name ``name`` (what ``jax.jit`` calls
    the executable, see :func:`jit_advance`)."""
    import functools

    @functools.wraps(fn)
    def named(*args):
        return fn(*args)

    named.__name__ = named.__qualname__ = name
    return named


def jit_advance(engine: str, fn):
    """How :meth:`Launch.prepare` jits an engine's advance function:
    the carry (argument 0) donated on accelerators, and the program
    NAMED ``tpudes_<engine>_advance``.  The name is what a profile's
    ``XLA Modules`` line shows, and it is part of jax's
    persistent-cache key, which ignores operation metadata: two
    programs that differ only in their :func:`scoped_while_loop` names
    would otherwise share one cached executable (seen on the chip,
    PERF.md PR 25)."""
    import jax

    return jax.jit(
        _named(fn, f"tpudes_{engine}_advance"),
        donate_argnums=donate_argnums(0),
    )


class InitProgram:
    """What :func:`jit_init` returns: ``init(mesh, *args)`` runs the
    engine's carry builder as ONE executable and returns its parts.
    The jitted program is made on the first call for each mesh (None
    included) and kept here, in the runner's cache entry; the advance
    program beside it stays mesh-independent."""

    __slots__ = ("_fn", "_r_pad", "_axes", "_by_mesh")

    def __init__(self, fn, r_pad, axes):
        self._fn = fn
        self._r_pad = r_pad
        self._axes = tuple(axes)
        self._by_mesh: dict = {}

    def _jit(self, mesh, args):
        import jax

        if mesh is None:
            return jax.jit(self._fn)
        import functools

        from jax.sharding import NamedSharding, PartitionSpec as P

        def place(axis, v):
            spec = (
                None if axis is None
                else _replica_spec(v, self._r_pad, axis)
            )
            return NamedSharding(mesh, P() if spec is None else spec)

        parts = jax.eval_shape(self._fn, *args)
        return jax.jit(
            self._fn,
            out_shardings=tuple(
                jax.tree_util.tree_map(functools.partial(place, axis), part)
                for axis, part in zip(self._axes, parts, strict=True)
            ),
        )

    def __call__(self, mesh, *args):
        if self._r_pad is None:
            mesh = None        # no replica axis: nothing to shard
        prog = self._by_mesh.get(mesh)
        cached = prog is not None
        if not cached:
            prog = self._by_mesh[mesh] = self._jit(mesh, args)
            RUNTIME.init_programs += 1
        operands = spans.current()
        if operands is not None and operands.name == "launch.operands":
            operands.args["init_cached"] = cached
        return prog(*args)


def jit_init(engine: str, fn, r_pad: int | None, axes) -> InitProgram:
    """How :meth:`Launch.prepare` makes a launch's initial carry:
    ``fn``, the engine's carry builder (``init_state()``,
    :func:`stack_axis` over the replica and config axes,
    :func:`replica_keys`, per-replica draws), jitted under the program
    name ``tpudes_<engine>_init`` and kept in the runner's cache entry
    beside the advance program, so that a warm launch dispatches two
    executables.  (Built eagerly the same carry is a dispatched
    program per leaf per operation: 33 a BSS launch, 50 an LTE launch,
    68 on a four-chip mesh, 10 to 37 ms of host time with the chip
    idle, PERF.md PR 25.)

    ``fn(*args)`` returns a TUPLE of parts; its runtime arguments are
    traced (the run key, ``wired``'s replica offset), shapes are static
    and already in the runner's cache key.  ``axes`` names, per part,
    the dimension that carries the replica axis in that part's leaves
    (None: the part has none).  On a mesh the program places its
    outputs itself, by :func:`shard_replica_axis`'s rule: a leaf of a
    part with an axis whose dimension there equals ``r_pad`` is
    sharded over "replica", every other leaf is replicated, so each
    chip makes its own shard and no ``device_put`` follows.  Without a
    mesh the outputs are uncommitted single-device arrays, exactly
    what eager ``jnp`` calls give, so the advance program sees the
    avals and shardings it was compiled for.

    ``RUNTIME.stats()["init_programs"]`` counts the executables made
    (one per runner and mesh); the ``launch.operands`` span open
    around the call gets ``args.init_cached``."""
    return InitProgram(_named(fn, f"tpudes_{engine}_init"), r_pad, axes)


def configure_persistent_cache() -> str | None:
    """Arm jax's persistent compilation cache so a fresh process reuses
    the previous process's XLA compiles; returns its directory (None
    when not armed).

    The directory is placed from OUTSIDE: when
    ``JAX_COMPILATION_CACHE_DIR`` is set jax already reads it and this
    function sets no directory.  Otherwise an accelerator backend
    caches at the fixed ``<checkout>/.jax_cache`` (the path is part of
    jax's cache key, so it never carries a temp name, pid or time),
    and XLA:CPU stays uncached — its AOT loader logs a machine-feature
    mismatch on every hit, and CPU is the test backend, which must
    leave the checkout clean."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if jax.default_backend() == "cpu":
            return None
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)
            ))),
            ".jax_cache",
        )
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every engine program: the default thresholds skip
    # fast-compiling entries, which is exactly the sweep traffic
    # the engines generate
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class EngineFuture:
    """Handle to one dispatched engine run (``run_* (..., block=False)``).

    Holds the on-device output tree plus the engine's host-side
    ``finalize`` (slice padded replicas, unstack config points, rebuild
    wide counters).  The device work is already in flight when the
    future is created; ``result()`` performs the deferred D2H transfer
    and unpack exactly once."""

    __slots__ = ("engine", "launch_id", "_device_out", "_finalize",
                 "_result", "_done", "_runtime")

    def __init__(self, engine: str, device_out, finalize):
        self.engine = engine
        self._device_out = device_out
        self._finalize = finalize
        self._result = None
        self._done = False
        self._runtime: "EngineRuntime | None" = None
        # the future's existence ends the `launch` span that run_lifted
        # opened, so a blocking caller's wait and fetch stay outside
        # it; the id ties result.* to their launch from any thread
        launch = spans.current()
        self.launch_id = None
        if launch is not None and launch.name == "launch":
            launch.close()
            self.launch_id = launch.id

    @property
    def device_out(self):
        """The on-device output tree (None once ``result()`` fetched
        it) — where a caller reads the outputs' shardings."""
        return self._device_out

    def done(self) -> bool:
        """True once the device work has finished (never blocks)."""
        if self._done:
            return True
        import jax

        return all(
            leaf.is_ready()
            for leaf in jax.tree_util.tree_leaves(self._device_out)
            if hasattr(leaf, "is_ready")
        )

    def block(self) -> "EngineFuture":
        """Wait for the device work without fetching/unpacking."""
        if not self._done:
            import jax

            with spans.span("result.wait", self.launch_id):
                jax.block_until_ready(self._device_out)
        return self

    def result(self):
        """Fetch (one batched D2H) + unpack; memoized.  Retires from
        the runtime's in-flight window even when the fetch/unpack
        raises — a poisoned future must not jam every later submit's
        window-eviction loop (the caller may retry result(); the
        device buffers are still held)."""
        if not self._done:
            import jax

            try:
                # start the D2H copies BEFORE the wait, as device_get
                # alone would: the transfer then follows the compute
                # with no host round trip between them (waiting first
                # cost 0.3-0.5 ms a launch on a v5e).  result.fetch is
                # therefore two spans a launch: starting the copies,
                # and what is left of the transfer after the wait
                with spans.span("result.fetch", self.launch_id):
                    for leaf in jax.tree_util.tree_leaves(self._device_out):
                        if hasattr(leaf, "copy_to_host_async"):
                            leaf.copy_to_host_async()
                self.block()
                with spans.span("result.fetch", self.launch_id):
                    host = jax.device_get(self._device_out)
                with spans.span("result.unpack", self.launch_id):
                    self._result = self._finalize(host)
            finally:
                if self._runtime is not None:
                    self._runtime._retire(self)
            self._device_out = None  # release the device buffers
            self._done = True
        return self._result


class Launch:
    """One launch of a device engine: the ONE place that knows what a
    launch is.  The engine's ``run_*`` says what only it knows, in two
    steps; everything else (the order of the steps, what is timed,
    what is counted, when the call blocks, how the carry is made and
    placed) happens here, once, for every engine.

    ``Launch(engine, key, replicas, mesh, n_cfg)`` buckets the replica
    axis (``r_pad``) and reads the observability switch (``obs``);
    ``n_cfg`` is the size of the leading config axis of a sweep (None:
    no such axis), ``axis`` the dimension the replica axis then has.

    :meth:`prepare` finds or builds the runner (``launch.runner``) and
    makes this call's carry and operands (``launch.operands``); it
    dispatches the init program and nothing else, so an inspector
    (``lte_sm.compiled_step_lowering``) stops after it and lowers
    ``fn`` with the run's own ``carry`` and ``ops``.  :meth:`drive`
    dispatches the advance program once per bound
    (``launch.enqueue``) and returns the result or, with
    ``block=False``, its :class:`EngineFuture`."""

    __slots__ = ("engine", "key", "replicas", "mesh", "n_cfg", "axis",
                 "r_pad", "obs", "fn", "aux", "compiling", "carry", "ops")

    def __init__(self, engine: str, key, replicas, mesh, n_cfg):
        from tpudes.obs.device import device_metrics_enabled

        self.engine = engine
        self.key = key
        self.replicas = replicas
        self.mesh = mesh
        self.n_cfg = n_cfg
        # where the replica axis sits in a leaf that carries the
        # config axis too (which leads)
        self.axis = 0 if n_cfg is None else 1
        # padded replicas are real independent simulations whose rows
        # the engine's unpack slices off again
        self.r_pad = bucket_replicas(replicas, mesh)
        self.obs = device_metrics_enabled()

    def prepare(self, static_key, build, operands, init_args=()):
        """``static_key()`` is the runner's cache key: the engine's
        cache-key helper plus what else shapes the executable
        (``r_pad``, ``obs``, ``n_cfg``, ...); it runs once, inside
        ``launch.runner``.  ``build()`` runs at a miss and returns
        ``(init_fn, axes, advance, aux)``, both functions UN-jitted:
        they become the entry's :func:`jit_init` and
        :func:`jit_advance` programs here (``axes`` as in
        :func:`jit_init`); ``aux`` is whatever host value the engine
        wants kept with them.  ``operands(parts)`` gets what
        ``init_fn(*init_args)`` returned, from the init program and
        already placed on the mesh, and returns ``(carry, ops)``: the
        first carry and the operand tables of this call, as numpy
        (the aval ``jnp`` would give, without an eager transfer)."""
        engine, r_pad = self.engine, self.r_pad

        def build_entry():
            init_fn, axes, advance, aux = build()
            return (jit_init(engine, init_fn, r_pad, axes),
                    jit_advance(engine, advance), aux)

        (init, self.fn, self.aux), self.compiling = RUNTIME.runner(
            engine, static_key, build_entry
        )
        with spans.span("launch.operands"):
            self.carry, self.ops = operands(init(self.mesh, *init_args))
        return self

    def drive(self, call, bounds, fetch, unpack_one, *, shared=(),
              once=None, checkpoint=None, identity=None, block=True):
        """``call(fn, carry, bound, ops) -> (carry', metrics)`` is how
        this engine's advance program takes its arguments (``bound``
        arrives as ``np.int32``; ``metrics`` under :func:`drive_chunks`'s
        invariant); ``bounds`` the segment ends
        (:func:`chunk_bounds`).  ``fetch(carry)`` picks the leaves of
        the FINAL carry that go to the host, as one dict and so one
        batched transfer; ``unpack_one(host)`` assembles one config
        point's result from it (with a config axis each point gets its
        slice of every fetched array but those named in ``shared``,
        per-flow statics identical across points).  ``once(host,
        points)`` runs once per launch after the unpack, ``points`` the
        list of per-point results: a sweep shares one loop, so
        per-launch telemetry recorded per point would count it
        ``n_cfg``-fold.  ``identity()`` is what a ``checkpoint`` adds
        to the run's fingerprint: every value that, if changed, would
        make a saved carry mean another study."""
        import jax
        import numpy as np

        from tpudes.obs.device import CompileTelemetry

        engine, fn, ops = self.engine, self.fn, self.ops
        explain = RUNTIME.explain
        if explain is not None:
            # an open tpudes.obs.explain session stops the loop early
            bounds = explain.shorten(self, call, bounds)
        ckpt = None
        if checkpoint is not None:
            from tpudes.parallel.checkpoint import checkpoint_ctx

            ckpt = checkpoint_ctx(
                checkpoint, engine=engine, key=self.key,
                replicas=self.replicas, r_pad=self.r_pad,
                n_cfg=self.n_cfg, obs=self.obs, axis=self.axis,
                mesh=self.mesh,
                extra=identity(),
            )
        # the first segment takes (on accelerators: donates) the carry,
        # so the launch, which the unpack closures keep alive as long
        # as the future, lets go of it
        carry, self.carry = self.carry, None
        with CompileTelemetry.timed(engine, self.compiling):
            # chunking reuses the SAME executable: each segment only
            # raises the traced bound
            carry, flush = drive_chunks(
                engine, bounds, carry,
                lambda c, bound: call(fn, c, np.int32(bound), ops),
                self.obs, checkpoint=ckpt,
            )
            if self.compiling:
                # the recorded compile time must include the compile
                jax.block_until_ready(carry)
        n_cfg = self.n_cfg

        def finalize(host):
            if flush is not None:
                flush()        # the last segment's deferred metrics
            if n_cfg is None:
                out = unpack_one(host)
                points = [out]
            else:
                # each point's slice of the leading config axis
                out = points = [
                    unpack_one({
                        k: (v if k in shared else v[i])
                        for k, v in host.items()
                    })
                    for i in range(n_cfg)
                ]
            if once is not None:
                once(host, points)
            return out

        fut = EngineFuture(engine, fetch(carry), finalize)
        return fut.result() if block else fut


class EngineRuntime:
    """Process-wide runner registry shared by all device engines.

    Entries are keyed ``(engine, *engine_key)`` and evicted true-LRU:
    a hit refreshes the entry's position, so sweep working sets stay
    resident while one-shot programs age out.
    """

    def __init__(self, capacity: int = 64):
        self.capacity = int(capacity)
        self._runners: OrderedDict[tuple, object] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.init_programs = 0
        self._cache_wired = False
        self._inflight: list[EngineFuture] = []
        self.submitted = 0
        self.retired = 0
        self.max_in_flight = 0
        self._launches: dict[str, int] = {}
        # tpudes.obs.explain: the open session (None: off), and what
        # run_lifted was last called with, for explain.replay()
        self.explain = None
        self.last_lifted = None

    def runner(self, engine: str, key, build):
        """Return ``(value, compiled_new)``: the cached runner for
        ``(engine, *key)``, building (and recording a miss) when absent.
        ``compiled_new`` is the engines' CompileTelemetry trigger.
        ``key`` is the tuple or a thunk that makes it: the engines pass
        a thunk, so that the ``launch.runner`` span times the key's
        construction (``tobytes()`` of every table) with the lookup."""
        if not self._cache_wired:
            from tpudes.obs.device import CompileTelemetry

            configure_persistent_cache()
            CompileTelemetry.listen()
            self._cache_wired = True
        with spans.span("launch.runner", engine=engine) as sp:
            if callable(key):
                key = key()
            full = (engine, *key)
            hit = self._runners.get(full)
            sp.args["hit"] = hit is not None
            if hit is not None:
                # true LRU: hot entries survive
                self._runners.move_to_end(full)
                self.hits += 1
                return hit, False
            self.misses += 1
            value = build()
            self._runners[full] = value
            while len(self._runners) > self.capacity:
                self._runners.popitem(last=False)
            return value, True

    def size(self, engine: str | None = None) -> int:
        """Resident runner count, optionally for one engine."""
        if engine is None:
            return len(self._runners)
        return sum(1 for k in self._runners if k[0] == engine)

    def clear(self, engine: str | None = None) -> None:
        """Drop cached runners (all, or one engine's).  A full clear
        also zeroes the submit/launch telemetry — the test-isolation
        reset (in-flight futures stay valid; they hold their own
        buffers)."""
        if engine is None:
            self._runners.clear()
            self.submitted = self.retired = self.max_in_flight = 0
            self._inflight = []
            self._launches = {}
            return
        for k in [k for k in self._runners if k[0] == engine]:
            # not a sim-time buffer: entries age out via the capacity
            # LRU in runner(), so no expiry event is ever needed
            del self._runners[k]  # tpudes: ignore[EVT003]

    # --- async submission -------------------------------------------------

    def submit(self, run_fn, *args, **kwargs) -> EngineFuture:
        """Dispatch ``run_fn(*args, block=False, **kwargs)`` and track it
        in the bounded in-flight window: at the window, the OLDEST
        future is retired (D2H + unpack) BEFORE the new run is
        dispatched — the window's other runs keep the device busy
        through that wait, and an eviction error surfaces before this
        submit has dispatched anything, so it can never orphan a
        just-launched run's future.  Returns the new run's
        :class:`EngineFuture`."""
        window = inflight_window()
        while len(self._inflight) >= window:
            self._inflight[0].result()  # retires itself via _retire
        fut = run_fn(*args, block=False, **kwargs)
        if not isinstance(fut, EngineFuture):
            raise TypeError(
                f"{getattr(run_fn, '__name__', run_fn)!r} did not return "
                "an EngineFuture under block=False — only the device "
                "engines' run_* entry points are submittable"
            )
        fut._runtime = self
        self._inflight.append(fut)
        self.submitted += 1
        self.max_in_flight = max(self.max_in_flight, len(self._inflight))
        return fut

    def _retire(self, fut: EngineFuture) -> None:
        try:
            self._inflight.remove(fut)
        except ValueError:
            return  # already retired (result() is memoized)
        self.retired += 1

    def drain(self) -> None:
        """Retire every outstanding future (in submission order)."""
        while self._inflight:
            self._inflight[0].result()

    def poll(self) -> int:
        """Retire (fetch + unpack) every in-flight future whose device
        work has already FINISHED — never blocks.  The serving layer's
        window sweep: between dispatches the StudyServer polls so
        completed launches leave the in-flight window (and free their
        device buffers) without a blocking ``result()`` serializing the
        scheduler on still-running work.  Returns the number retired."""
        n = 0
        for fut in list(self._inflight):
            if fut.done():
                fut.result()
                n += 1
        return n

    def record_launch(self, engine: str, n: int = 1) -> None:
        """Count one device dispatch — the sweep tests pin that an
        8-point config-axis sweep is exactly ONE of these."""
        self._launches[engine] = self._launches.get(engine, 0) + int(n)

    def launches(self, engine: str) -> int:
        return self._launches.get(engine, 0)

    def stats(self) -> dict:
        """Hit/miss counters plus per-engine residency — bench fodder."""
        per_engine: dict[str, int] = {}
        for k in self._runners:
            per_engine[k[0]] = per_engine.get(k[0], 0) + 1
        return {
            "hits": self.hits,
            "misses": self.misses,
            # jit_init executables made: one per runner and mesh, so a
            # warm window leaves it alone while `launches` grows
            "init_programs": self.init_programs,
            "resident": len(self._runners),
            "per_engine": per_engine,
            "submitted": self.submitted,
            "retired": self.retired,
            "in_flight": len(self._inflight),
            "max_in_flight": self.max_in_flight,
            "launches": dict(self._launches),
        }


#: the one shared registry every engine routes through
RUNTIME = EngineRuntime()
