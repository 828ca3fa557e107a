"""Host spans of the launch path: always on, bounded, read in process.

``span(name, **args)`` is a context manager that records ``(name, start,
end, id, parent, request)`` on ``time.perf_counter()`` into one ring of
the last :data:`RING` closed spans, and wraps the block in
``jax.profiler.TraceAnnotation("tpudes:" + name)``: whenever any
``jax.profiler`` trace is running, the same span is an event on the
host plane of that trace, on the device trace's clock.

- **parent**: the innermost span open on the calling thread when this
  one opened (``None`` at a root).  Threads never adopt each other's
  spans; ``tpudes.serving`` dispatches from more than one.
- **request**: the id shared by every span of one piece of work.  A
  root starts one (its own id) and children inherit it; ``request=OWN``
  starts a new one under a parent (each ``launch`` of a study);
  ``request=<id>`` joins the request that caused this span, from any
  thread and at any later time (``result.*`` carry their ``launch``'s
  id, which the :class:`~tpudes.parallel.runtime.EngineFuture` keeps).

Same policy as :class:`tpudes.obs.device.CompileTelemetry`: no knob, no
environment variable, nothing written to a file.  A span is two clock
reads, one small object and one ``deque.append``; it belongs in host
code only, never inside a function that ``jit`` traces (there the
stable names are ``jax.named_scope``'s, see
:func:`tpudes.parallel.runtime.scoped_while_loop`).  The names in use
and what each covers are listed in PERF.md section 3.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time

__all__ = ["OWN", "RING", "Span", "current", "reset", "snapshot", "span"]

#: closed spans kept (a 20 s window of 70 ms launches is ~3000)
RING = 1 << 15
#: ``request=OWN``: start a new request even under a parent
OWN = 0

_ring: collections.deque = collections.deque(maxlen=RING)
_ids = itertools.count(1)       # next() is atomic under the GIL
_open = threading.local()       # .stack: this thread's open spans
_annotation = None              # jax.profiler.TraceAnnotation, on first use


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


class Span:
    """One interval.  ``with span(...)`` is the usual form; ``open()`` /
    ``close()`` serve the one span that ends in another function
    (``launch``: opened by ``run_lifted``, closed where the
    ``EngineFuture`` is made).  ``close()`` is idempotent.  ``args`` may
    be filled while the span is open (``launch.runner``'s ``hit``)."""

    __slots__ = ("name", "start", "end", "id", "parent", "request", "args",
                 "_mark")

    def __init__(self, name: str, request: int | None = None, **args):
        self.name = name
        self.request = request
        self.args = args
        self.start = self.end = self.id = self.parent = self._mark = None

    def open(self) -> "Span":
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        stack = _stack()
        parent = stack[-1] if stack else None
        self.id = next(_ids)
        self.parent = parent.id if parent is not None else None
        if self.request is None:
            self.request = parent.request if parent is not None else self.id
        elif self.request == OWN:
            self.request = self.id
        stack.append(self)
        self._mark = _annotation("tpudes:" + self.name, **self.args)
        self._mark.__enter__()
        self.start = time.perf_counter()
        return self

    def close(self) -> None:
        if self.end is not None or self.start is None:
            return
        self.end = time.perf_counter()
        self._mark.__exit__(None, None, None)
        self._mark = None
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:      # closed out of order (or from a handler)
            stack.remove(self)
        _ring.append(self)

    __enter__ = open

    def __exit__(self, *exc) -> None:
        self.close()

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"request={self.request}, start={self.start}, "
                f"end={self.end}, args={self.args})")


#: ``span(name, request=None, **args)``: the spelling at the span sites
span = Span


def current() -> Span | None:
    """The innermost span open on the calling thread."""
    stack = _stack()
    return stack[-1] if stack else None


def snapshot() -> list[Span]:
    """The ring's closed spans, oldest first (ordered by their end)."""
    return list(_ring)


def reset() -> None:
    """Empty the ring (open spans stay open and are recorded at close)."""
    _ring.clear()
