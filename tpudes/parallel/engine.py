"""JaxSimulatorImpl — the windowed engine at the SimulatorImplementationType seam.

Reference parity: the engine seam itself is simulator-impl.{h,cc} +
the ``SimulatorImplementationType`` GlobalValue (SURVEY.md §1 "key
architectural seam"); the window structure reuses the granted-time-
window math of distributed-simulator-impl.cc (SURVEY.md §3.3) with the
batch boundary playing the role of the MPI grant.

Behavior (SURVEY.md §7 step 4): the host event queue stays authoritative
for ordering.  Per window the engine snapshots channel geometry and
pushes the full (tx × rx) propagation table through the jitted batch
kernels ONCE; the in-window scalar event path then reads cached rows
instead of recomputing per-pair host math.  With no registered batchable
channels the engine degenerates to DefaultSimulatorImpl and reproduces
its event traces exactly (the step-3 oracle contract).
"""

from __future__ import annotations

from time import monotonic

from tpudes.core.global_value import GlobalValue
from tpudes.core.simulator import DefaultSimulatorImpl, register_simulator_impl

# the engine's GlobalValue knobs (JaxWindowNs, JaxBatchMinPhys,
# JaxReplicas) are registered in tpudes.core.global_value so that
# CommandLine can bind them before this module is imported


class BatchableRegistry:
    """Channels (and later: PHY evaluation pools) that want a per-window
    batched refresh register here.

    Weak references: channels from destroyed simulations vanish once
    their object graph is collected, so back-to-back runs in one process
    don't accumulate dead members.
    """

    _members: list = []  # list[weakref.ref]

    @classmethod
    def register(cls, member) -> None:
        import weakref

        cls._members.append(weakref.ref(member))

    @classmethod
    def members(cls) -> list:
        alive = []
        live_refs = []
        for ref in cls._members:
            obj = ref()
            if obj is not None:
                alive.append(obj)
                live_refs.append(ref)
        cls._members = live_refs
        return alive

    @classmethod
    def reset(cls) -> None:
        cls._members = []


class JaxSimulatorImpl(DefaultSimulatorImpl):
    def __init__(self):
        super().__init__()
        self.window_ticks = int(GlobalValue.GetValue("JaxWindowNs"))
        self.windows_run = 0
        #: set by the lifted replica-axis path: {"kind", "replicas",
        #: "out", "sim_end_s"} — scenario scripts read per-replica
        #: outcomes from here after Run()
        self.replicated_result = None

    def _try_lift(self) -> bool:
        """JaxReplicas > 0: lower the live object graph to a device
        program and run every replica on the accelerator at once.
        Returns True when the lifted path ran (the scalar queue is then
        bypassed); False → loud warning, windowed scalar fallback."""
        replicas = int(GlobalValue.GetValue("JaxReplicas"))
        if replicas <= 0 or self.replicated_result is not None:
            return False
        if self._scheduled_stop_ts is None:
            import warnings

            warnings.warn(
                "JaxReplicas set but Simulator.Stop(t) was never called; "
                "the replica-axis path needs a bounded horizon — falling "
                "back to the windowed scalar engine",
                stacklevel=2,
            )
            return False
        sim_end_s = self._scheduled_stop_ts / 1e9
        from tpudes.obs.spans import span
        from tpudes.parallel.lift import (
            UnliftableScenarioError,
            lift,
            run_lifted,
        )

        # one script study on the device, lift() call to results set:
        # the root span of `lift`, `launch`, `launch.*` and `result.*`
        with span("lifted_run", replicas=replicas):
            try:
                kind, prog, commit = lift(sim_end_s)
            except UnliftableScenarioError as e:
                import warnings

                warnings.warn(
                    f"JaxReplicas={replicas} requested but no lowering "
                    f"can represent this object graph ({e}); falling "
                    f"back to the windowed scalar engine",
                    stacklevel=2,
                )
                return False
            out = run_lifted(kind, prog, replicas)
            commit()  # only a *successful* device run disarms the host path
            self.replicated_result = dict(
                kind=kind, replicas=replicas, out=out, sim_end_s=sim_end_s,
                program=prog,
            )
        self.current_ts = self._scheduled_stop_ts
        return True

    def IsFinished(self) -> bool:
        # a completed lifted run IS the whole simulation, even though the
        # scalar queue was never drained
        return self.replicated_result is not None or super().IsFinished()

    def Run(self) -> None:
        if self.replicated_result is not None:
            # the lifted run already covered the scenario; a second Run()
            # must not replay the stale scalar queue with time moving
            # backwards
            return
        if self._try_lift():
            return
        self._stop = False
        events = self._events
        obs = self._obs
        while not self._stop:
            self._process_events_with_context()
            if events.IsEmpty():
                break
            # conservative window: [next event, next event + W)
            window_end = events.PeekNext().ts + self.window_ticks
            members = BatchableRegistry.members()
            for member in members:
                member.refresh_window_cache()
            self.windows_run += 1
            if obs is not None:
                # host window loop, never traced
                w0, e0 = monotonic(), self._event_count  # tpudes: ignore[JP001]
            while not self._stop:
                self._process_events_with_context()
                if events.IsEmpty() or events.PeekNext().ts > window_end:
                    break
                self._invoke(events.RemoveNext())
            if obs is not None:
                obs.on_window(
                    w0, monotonic() - w0,  # tpudes: ignore[JP001]
                    self._event_count - e0, len(members),
                )


register_simulator_impl("tpudes::JaxSimulatorImpl", JaxSimulatorImpl)
register_simulator_impl("ns3::JaxSimulatorImpl", JaxSimulatorImpl)
