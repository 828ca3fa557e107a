"""The program's own reading of its device trace: `tpudes.obs.explain.replay()`.

The harness reduces its profiler window from outside (`benchmark/trace.py`: the
outermost `while`, whole idle gaps by `bench:` span).  The program can read a trace
by its own names: a loop step split by `tpudes.*` scope, the `while`'s own time, the
copies, the device time outside the loop, each idle gap split over the `tpudes:`
span the host was in (PERF.md section 3).  `table()` asks it to run its last launch
again under a profiler window of its own, once a process: the readers of a
`--trace 1` run call it after the window and the check, and share the one table
because they import this module.

So these readings are NOT of the measured window: they are of `explain.LAUNCHES`
blocking replays of the cell's last launch, back to back, each loop stopped at
`explain.MAX_ITERATIONS`, the first replay left out, a launch's numbers the median over
the others.  A loop step's split does not depend on where the loop stops (PERF.md
section 6, PR 37); the idle readings name what one launch of the PROGRAM leaves the
device waiting for, and nothing of the harness's or a client's own code between two
launches, which a replay does not run.

A program without `explain` (the parent of the PR that added it), a host without a
TPU, a missing proto module, a table the program withheld (a cut trace, stale names)
or any error inside the replay reads as None, with one line on stderr that says why:
the metric is left out of the line, and the run never fails here.  Scopes are chosen
by their LAST component, so no reader names an engine; a loop that names no such scope
reads as None too, never as 0 (a renamed scope must not read as a gain).  Only the two
scopes every loop has (`step`, `rng`) are metrics: a scope of one engine's loop can be
listed in no cell until `tests/test_tcp.py` and `test_wifi_ht.py` beside this directory
let a cell's per-layer set differ from `wifi.mc`'s (PERF.md section 7, row 8).  The
stderr line gives every scope's reading all the same.
"""

import functools
import sys
import time

LAUNCH_SPANS = ("launch", "launch.runner", "launch.operands", "launch.enqueue")
RESULT_SPANS = ("result.wait", "result.fetch", "result.unpack")


def _say(what: str) -> None:
    print(f"benchmark: explain: {what}", file=sys.stderr, flush=True)


@functools.cache
def table():
    """The replay's table, or None (see above)."""
    try:
        import jax

        from tpudes.obs import explain
    except ImportError as e:
        return _say(f"not in this program ({e})")
    if jax.default_backend() != "tpu":
        return _say(f"no device trace to read on {jax.default_backend()!r}")
    t0 = time.perf_counter()
    try:
        got = explain.replay()
    except Exception as e:      # the boundary: a reader never fails a run
        return _say(f"replay failed: {type(e).__name__}: {e}")
    took = time.perf_counter() - t0
    if got is None:
        return _say("nothing was launched")
    if got["withheld"]:
        return _say(f"withheld after {took:.2f} s: {got['withheld']}")
    parts = "  ".join(f"{k} {v:.2f}" for k, v in (got.get("took_s") or {}).items())
    each = " ".join(f"{v:.3f}" for v in got.get("idle_each_ms") or ())
    scopes = "  ".join(f"{k} {v['us']:.3f}" for k, v in got["loop"]["scopes"].items())
    _say(f"replay of {got['launches']} launches read in {took:.2f} s ({parts}); "
         f"idle ms of each: {each}; us an iteration by scope: {scopes}")
    return got


def loop(name: str):
    """`loop[name]` of the table, per iteration."""
    got = table()
    return None if got is None else got["loop"][name]


def scope_us(last: str):
    """Self time an iteration under the scopes whose last component is `last`.  None
    where the loop's lowered program names no such scope (another engine's loop, or
    a renamed scope); 0 only where it names one that left no device event."""
    got = table()
    if got is None:
        return None

    def chosen(scopes):
        return [scope for scope in scopes if scope.rsplit(".", 1)[-1] == last]

    rows = got["loop"]["scopes"]
    if chosen(rows):
        return sum(rows[scope]["us"] for scope in chosen(rows))
    return 0.0 if chosen(got["loop"]["no_event"] or ()) else None


def idle_ms(spans):
    """Idle time of the first device a launch, under the named `tpudes:` spans."""
    got = table()
    if got is None:
        return None
    return sum(got["idle_ms"].get(name, 0.0) for name in spans)


def outside_loop_ms():
    """Device busy time a launch outside the loop, all programs."""
    got = table()
    return None if got is None else sum(got["outside_loop_ms"].values())
