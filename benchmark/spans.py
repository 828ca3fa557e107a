"""Spans the harness records around its own calls into each layer."""

from __future__ import annotations

import contextlib
import time


class Spans:
    """Named host-clock intervals, kept in memory; with `annotate` each is also
    a `bench:<name>` event in the profiler's trace, on the device's clock."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.durations: dict[str, list[float]] = {}
        self.starts: dict[str, list[float]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        marker = contextlib.nullcontext()
        if self.annotate:
            import jax

            marker = jax.profiler.TraceAnnotation("bench:" + name)
        with marker:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.starts.setdefault(name, []).append(t0)
                self.durations.setdefault(name, []).append(
                    time.perf_counter() - t0
                )

    def set_aside(self, prefix: str) -> None:
        """Rename everything recorded so far (the traced launches), so that
        what follows is read apart."""
        for table in (self.durations, self.starts):
            for name in list(table):
                table[prefix + name] = table.pop(name)
