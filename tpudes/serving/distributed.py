"""Cross-process study routing: the StudyServer on a process mesh.

ROADMAP item 4/6: "the serving layer routes studies to member
processes" — and survives those members dying.  A :class:`ProcessRouter`
plugs into ``StudyServer(router=...)``: when a coalesced batch's
studies carry a picklable ``spec`` (see :class:`~tpudes.serving.
descriptor.StudyDescriptor`), the router splits the batch's config
points into contiguous per-process blocks (:func:`~tpudes.parallel.
procmesh.process_slice`), keeps block 0 on the serving process (through
the descriptor's own launch, inside ``RUNTIME``'s in-flight window) and
ships the other blocks to member processes over the
:class:`~tpudes.parallel.mpi.MpiInterface` framed pipes.  Each member
rebuilds the descriptor from the spec through the SAME ``*_study``
extractor and launches its block — so every split result is covered by
the PR-5 sweep bit-equality contract, and the reassembled batch is
bit-equal to the unrouted launch (tests/test_procmesh.py pins it).

Fault model (ISSUE 13): a member is **lost** when its pipe hits EOF
(the process died — e.g. SIGKILL mid-batch), a frame fails
:class:`~tpudes.parallel.mpi.WireFormatError` validation (the stream
cannot be resynchronized), or its reply misses ``member_timeout_s`` (a
hung member's late reply would desync the next batch on that pipe).
All three surface as a typed :class:`~tpudes.serving.errors.
MemberLostError` carrying the member ids — never a raw pickle/pipe
exception — and the StudyServer requeues the whole batch onto the
survivors (or the local engine) after :meth:`ProcessRouter.exclude`
retires the member.  Requeued batches are re-coalesced and relaunched
through the same descriptors, so recovered results stay bit-equal to a
failure-free run.

Members run :func:`serve_studies` — a poll-with-timeout loop on the
pipe to the serving rank (a dead serving rank is an EOF exit, not a
hang) — until the router closes.  Chaos injection sites
(``member_study``, ``router_send``, ``router_recv`` — see
:mod:`tpudes.chaos`) make every failure mode above a replayable
integer seed.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from tpudes.serving.errors import MemberLostError

__all__ = ["MemberLostError", "ProcessRouter", "serve_studies"]


class _RoutedFuture:
    """Future over one routed batch: the local block's EngineFuture
    plus the member replies still in flight.  Duck-types the
    ``done()/result()`` surface StudyServer's demux loop uses, plus
    ``deadline`` (monotonic seconds) past which the scheduler force-
    demuxes so a hung member cannot pin the batch forever."""

    def __init__(self, local_fut, local_n, remote, local_error=None,
                 timeout_s: float = 60.0, lost_at_send=()):
        self._local_fut = local_fut
        self._local_n = local_n
        self._remote = remote          # [(member, conn, n_points), ...]
        self._local_error = local_error
        #: members whose study frame never went out (they died at send
        #: time): their blocks are simply missing, so the batch must
        #: requeue — but the SENT members' replies still get drained
        #: here first, keeping their pipes frame-synced
        self._lost_at_send = tuple(lost_at_send)
        self._timeout_s = timeout_s
        self.deadline = time.monotonic() + timeout_s
        self._result = None
        self._done = False

    def done(self) -> bool:
        if self._done:
            return True
        if self._local_fut is not None and not self._local_fut.done():
            return False
        # a dead member's pipe polls ready (EOF is readable), so a
        # killed member never wedges this sweep
        return all(conn.poll() for _, conn, _ in self._remote)

    def result(self):
        from tpudes.parallel.mpi import WireFormatError, recv_frame

        if self._done:
            if isinstance(self._result, Exception):
                raise self._result
            return self._result
        # gate the member reply budget on the LOCAL block first: it is
        # a same-sized slice of the same computation on this host, so
        # members get member_timeout_s measured from when comparable
        # work finished here — a long-horizon routed batch must not see
        # its healthy members declared lost just because the compute
        # wall exceeded the dispatch-relative deadline
        if self._local_fut is not None and self._local_error is None:
            try:
                self._local_fut.result()  # memoized; reused below
            except Exception as e:  # noqa: BLE001 - surfaced after drain
                self._local_error = e
            self.deadline = max(
                self.deadline, time.monotonic() + self._timeout_s
            )
        # drain EVERY member reply, even when something already
        # failed: a frame left on a shared pipe would be read by the
        # NEXT routed batch's future, silently desyncing every routed
        # launch after one poisoned batch.  Per-member failures are
        # collected (not raised mid-drain) for the same reason.
        replies: list = []
        lost: list = [(m, EOFError("died at send")) for m in
                      self._lost_at_send]
        for member, conn, n in self._remote:
            budget = max(0.05, self.deadline - time.monotonic())
            try:
                replies.append((member, n, recv_frame(
                    conn, timeout_s=budget,
                    chaos_site="router_recv", member=member,
                )))
            except (EOFError, OSError, TimeoutError, WireFormatError) as e:
                lost.append((member, e))
        self._done = True
        try:
            out: list = []
            if lost:
                detail = "; ".join(
                    f"member {m}: {type(e).__name__}: {e}"
                    for m, e in lost
                )
                raise MemberLostError([m for m, _ in lost], detail)
            if self._local_error is not None:
                raise self._local_error
            if self._local_fut is not None:
                res = self._local_fut.result()
                local = res if isinstance(res, list) else [res]
                if len(local) != self._local_n:
                    raise RuntimeError(
                        f"local block returned {len(local)} results for "
                        f"{self._local_n} points"
                    )
                out.extend(local)
            for member, n, (kind, payload) in replies:
                if kind == "error":
                    raise RuntimeError(
                        f"routed member {member} launch failed:\n{payload}"
                    )
                if len(payload) != n:
                    raise RuntimeError(
                        f"routed member {member} returned {len(payload)} "
                        f"results for {n} points"
                    )
                out.extend(payload)
        except Exception as e:
            self._result = e
            raise
        self._result = out
        return out


class ProcessRouter:
    """Splits coalesced batches across the member processes reachable
    over ``conns`` (peer rank -> Connection, e.g.
    ``MpiInterface._conns`` inside a :func:`launch_process_mesh`
    worker).  Members declared lost via :meth:`exclude` never receive
    another frame — their pipe state is untrusted once a batch failed
    on them."""

    def __init__(self, conns: dict, member_timeout_s: float = 60.0):
        if conns:
            # the serving process launches block 0 itself and every
            # member launches its own block: all of them need a device
            from tpudes.parallel.procmesh import require_one_process_per_chip

            require_one_process_per_chip("ProcessRouter", 1 + len(conns))
        self._members = [(m, c) for m, c in sorted(conns.items())]
        self.member_timeout_s = float(member_timeout_s)
        self.routed_batches = 0
        self.routed_points = 0
        self._dead: set = set()
        self._closed = False

    def exclude(self, member) -> None:
        """Retire a member (dead, corrupt stream, or timed out): no
        future launch routes to it and close() skips it."""
        self._dead.add(member)

    @property
    def live_members(self) -> list:
        return [m for m, _ in self._members if m not in self._dead]

    def launch(self, batch, points):
        """Dispatch one batch, split across the serving process + live
        members; returns a :class:`_RoutedFuture`, or None when the
        batch cannot be routed (single point, no live members, or a
        spec-less study) — the caller falls back to the plain local
        launch."""
        from tpudes.parallel.mpi import send_frame
        from tpudes.parallel.procmesh import process_slice
        from tpudes.parallel.runtime import RUNTIME

        live = [(m, c) for m, c in self._members if m not in self._dead]
        n_procs = len(live) + 1
        if self._closed or n_procs < 2 or len(points) < 2:
            return None
        if any(r.desc.spec is None for r in batch):
            return None
        spec = batch[0].desc.spec
        bounds = [
            process_slice(len(points), n_procs, p) for p in range(n_procs)
        ]
        remote = []
        lost_at_send = []
        for p, (member, conn) in enumerate(live, start=1):
            lo, hi = bounds[p]
            if hi <= lo:
                continue
            try:
                send_frame(conn, (
                    "study",
                    dict(
                        engine=spec["engine"],
                        prog=spec["prog"],
                        key=np.asarray(spec["key"]),
                        replicas=spec["replicas"],
                        points=list(points[lo:hi]),
                    ),
                ), chaos_site="router_send", member=member)
            except (OSError, ValueError, BrokenPipeError):
                # the member died at send time.  Do NOT re-split and
                # resend: earlier members already hold frames for THIS
                # split, and a second frame would desync their reply
                # pipes for every later batch.  Mark the block lost —
                # the future drains the sent members' replies (pipes
                # stay synced), then raises MemberLostError and the
                # whole batch requeues without the dead member.
                self.exclude(member)
                lost_at_send.append(member)
                continue
            remote.append((member, conn, hi - lo))
        lo, hi = bounds[0]
        local_fut = local_error = None
        if hi > lo:
            try:
                local_fut = RUNTIME.submit(
                    batch[0].desc.launch, list(points[lo:hi])
                )
            except Exception as e:  # noqa: BLE001 - member frames are
                # already in flight; the future must still drain their
                # replies before surfacing this, or the pipes desync
                local_error = e
        self.routed_batches += 1
        self.routed_points += sum(n for _, _, n in remote)
        return _RoutedFuture(
            local_fut, hi - lo, remote, local_error,
            timeout_s=self.member_timeout_s,
            lost_at_send=lost_at_send,
        )

    def close(self) -> None:
        """Tell every member's :func:`serve_studies` loop to exit —
        best-effort even toward excluded members (an excluded member
        may be alive with a merely-untrusted stream, and the close
        frame is the only thing that releases its loop; a truly dead
        member's pipe just raises and is ignored)."""
        from tpudes.parallel.mpi import pack_frame

        if self._closed:
            return
        self._closed = True
        for _member, conn in self._members:
            try:
                conn.send_bytes(pack_frame(("close", None)))
            except (OSError, ValueError):
                pass


def serve_studies(conn, member_id=None, poll_s: float = 1.0) -> int:
    """Member-process loop: execute routed launch specs arriving on
    ``conn`` (the pipe to the serving rank) until a close frame;
    returns the number of launches served.  The spec rebuilds the
    study through the engine's own ``*_study`` extractor, so a member
    launch takes exactly the code path a local launch takes.

    The wait is a poll-with-timeout loop (never a bare blocking recv —
    analysis rule SRV001): a dead serving rank surfaces as EOF and the
    loop returns instead of hanging forever.  A frame that fails wire
    validation ends the loop too — the stream cannot be resynchronized,
    so the member retires and the router's MemberLostError path takes
    over.  Chaos site ``member_study`` fires before each study: a
    ``kill_member`` event SIGKILLs this process (or raises, in
    thread-member test mode), ``slow_member`` sleeps through the
    router's timeout.
    """
    import traceback

    from tpudes.parallel.mpi import WireFormatError, pack_frame, recv_frame

    if member_id is None:
        from tpudes.parallel.mpi import MpiInterface

        member_id = (
            MpiInterface.GetSystemId() if MpiInterface.IsEnabled() else None
        )
    served = 0
    while True:
        if not conn.poll(poll_s):
            continue
        try:
            kind, payload = recv_frame(conn)
        except (EOFError, OSError):
            return served  # serving rank is gone: clean exit
        except WireFormatError:
            try:
                conn.close()
            except OSError:
                pass
            return served  # poisoned stream: retire this member
        if kind == "close":
            return served
        if kind != "study":
            raise RuntimeError(f"unexpected routed frame kind {kind!r}")
        _maybe_die(member_id)
        try:
            mod_name, fn_name = _engine_study(payload["engine"])
            desc = _engine_extract(mod_name, fn_name)(
                payload["prog"], payload["key"], payload["replicas"]
            )
            res = desc.launch(payload["points"])
            if hasattr(res, "result"):  # EngineFuture: resolve to host
                res = res.result()      # numpy before the wire
            results = res if isinstance(res, list) else [res]
            conn.send_bytes(pack_frame(("result", results)))
            served += 1
        except Exception:  # noqa: BLE001 - poison the batch, not the loop
            conn.send_bytes(pack_frame(("error", traceback.format_exc())))


def _maybe_die(member_id) -> None:
    """The ``member_study`` chaos site: SIGKILL (process members) or
    raise (thread members, ``param=="raise"``) when the armed schedule
    plants a death here; sleep on ``slow_member``."""
    from tpudes.chaos import ChaosInjected, fire

    ev = fire("member_study", member=member_id)
    if ev is None:
        return
    if ev.kind == "kill_member":
        if ev.param == "raise":
            raise ChaosInjected(
                f"chaos-injected member death (member {member_id})"
            )
        import os
        import signal

        os.kill(os.getpid(), signal.SIGKILL)
    elif ev.kind == "slow_member":
        time.sleep(float(ev.param or 0.1))


def _engine_study(engine: str):
    from tpudes.serving.server import _ENGINE_STUDY

    return _ENGINE_STUDY[engine]


def _engine_extract(mod_name: str, fn_name: str):
    return getattr(importlib.import_module(mod_name), fn_name)
