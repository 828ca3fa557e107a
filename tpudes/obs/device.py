"""Device-side observability: XLA compile telemetry + metric plumbing.

The device engines (tpudes/parallel) accumulate their metrics *inside*
the scan carry — drops, retransmits, scheduler grants, cwnd-cut events,
queue histograms — and fetch them once at run end with the outcome
arrays, so the hot loop never syncs with the host.  What lives here is
the part that must be process-global:

- :class:`CompileTelemetry` — every engine records one entry per
  jit-cache miss (compile count + wall time of the compiling call).
  This pins the "one executable serves the family" property as a
  *metric*: a 9-scheduler LTE sweep must show ``compiles == 1``.
  Recording is always on (a dict update per compile is free); the
  registry deliberately survives ``reset_world`` because XLA's compile
  caches do too.  Beside the per-engine counts it keeps every XLA
  trace/compile/cache-hit of the process with its time
  (:meth:`CompileTelemetry.xla_events`, fed by ``jax.monitoring``), so
  "which function recompiled, and when" has an answer.
- :func:`device_metrics_enabled` — the engines consult this at
  lowering/build time; the extra carry buffers exist only when the
  ``TpudesObs`` knob is up, so a disabled run compiles the exact
  pre-obs program.
- :class:`ChunkStream` — the landing strip for chunked-horizon runs:
  each fixed-size while_loop segment returns a small device metrics
  tree alongside the carry, and the engine records it here *after
  dispatching the next segment*, so the D2H fetch overlaps the next
  chunk's compute instead of serializing the pipeline.
"""

from __future__ import annotations

import collections
import time
from contextlib import contextmanager


def device_metrics_enabled() -> bool:
    """The engines consult this when building a device program: the
    same ``TpudesObs`` knob that arms the host profiler."""
    from tpudes.obs.profiler import enabled

    return enabled()


#: the jax.monitoring duration events kept by `CompileTelemetry.listen`:
#: a trace of a jitted function, a backend compile (it also fires when
#: the persistent cache answers), and that cache's hit
XLA_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class CompileTelemetry:
    """Process-wide per-engine compile counters, and every XLA compile
    of the process with its time."""

    _entries: dict[str, dict] = {}
    _xla: collections.deque = collections.deque(maxlen=1 << 12)
    _listening = False

    @classmethod
    def listen(cls) -> None:
        """Register (once) the ``jax.monitoring`` listener behind
        :meth:`xla_events`; the runtime calls it at its first runner
        lookup, next to ``configure_persistent_cache``."""
        if cls._listening:
            return
        import jax.monitoring

        def on_duration(event: str, seconds: float, **kwargs) -> None:
            if event in XLA_EVENTS:
                cls._xla.append((
                    time.perf_counter(), event, float(seconds),
                    kwargs.get("fun_name"),
                ))

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        cls._listening = True

    @classmethod
    def xla_events(cls, since: float | None = None) -> list[tuple]:
        """``(perf_counter at the event's end, event, seconds,
        fun_name)`` of the last 4096 :data:`XLA_EVENTS`, oldest first;
        with ``since``, those at or after that ``perf_counter`` time.
        Unlike :meth:`record` this sees EVERY program jax builds, the
        eager ``jnp`` calls of the launch path included (each is a
        ``jit`` of its own), and says which function it was."""
        return [
            e for e in list(cls._xla) if since is None or e[0] >= since
        ]

    @classmethod
    def record(cls, engine: str, wall_s: float) -> None:
        entry = cls._entries.setdefault(
            engine, {"compiles": 0, "wall_s": 0.0}
        )
        entry["compiles"] += 1
        entry["wall_s"] += float(wall_s)

    @classmethod
    def snapshot(cls) -> dict[str, dict]:
        return {
            engine: {"compiles": e["compiles"], "wall_s": round(e["wall_s"], 3)}
            for engine, e in sorted(cls._entries.items())
        }

    @classmethod
    def compiles(cls, engine: str) -> int:
        return cls._entries.get(engine, {}).get("compiles", 0)

    @classmethod
    def reset(cls) -> None:
        cls._entries.clear()

    @classmethod
    @contextmanager
    def timed(cls, engine: str, compiling: bool):
        """Record one compile entry for the wrapped block when
        ``compiling`` (a jit-cache miss) — the single plumbing shape
        every parallel engine uses.  The caller must block on the
        result inside the block (``jax.block_until_ready``) or the
        recorded wall time under-counts the async compile."""
        if not compiling:
            yield
            return
        t0 = time.monotonic()
        yield
        cls.record(engine, time.monotonic() - t0)


class ChunkStream:
    """Per-chunk metrics streamed by chunked-horizon engine runs.

    Bounded (oldest entries drop past :data:`CAP`) because a long
    streaming run would otherwise grow host memory without limit; the
    stream is a progress feed, not an archive."""

    CAP = 4096
    _entries: list[dict] = []
    _dropped = 0

    @classmethod
    def record(cls, engine: str, t_end: int, metrics: dict) -> None:
        cls._entries.append(
            {"engine": engine, "t_end": int(t_end), "metrics": metrics}
        )
        if len(cls._entries) > cls.CAP:
            del cls._entries[: len(cls._entries) - cls.CAP]
            cls._dropped += 1

    @classmethod
    def entries(cls, engine: str | None = None) -> list[dict]:
        if engine is None:
            return list(cls._entries)
        return [e for e in cls._entries if e["engine"] == engine]

    @classmethod
    def reset(cls) -> None:
        cls._entries.clear()
        cls._dropped = 0


