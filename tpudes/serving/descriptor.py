"""Study descriptors: what the coalescing scheduler needs to know.

A *study* is one client-requested simulation: a lowered device program
plus its PRNG key and replica count.  The serving layer's whole trick
is that two studies whose programs differ only in a **traced operand**
(scheduler id, TCP variant assignment, BSS horizon, AS load scale)
compile to the SAME executable and can ride ONE megabatched config-axis
launch — the PR-5 sweep arguments — with results demultiplexed back
per study, bit-equal to solo launches.

Each engine front-end owns a ``*_study`` extraction function (the
engine knows which of its fields are traced) returning a
:class:`StudyDescriptor`:

- ``coalesce_key`` — hashable identity of everything that must MATCH
  for two studies to share a launch: the program's static (cache-key)
  fields, the shared launch bound where the engine has one (LTE
  ``n_ttis``, dumbbell ``n_slots`` — the BSS horizon is itself the
  sweep operand, so it is absent from the BSS key), the PRNG key bytes
  (a (C, R, …) launch feeds ONE key to every point; the PR-5 equality
  guarantee is "equals the per-point launch *with the same key*"),
  the replica count, and the mesh.
- ``sweep_point`` — this study's value of the traced sweep operand.
- ``launch(points, block=False)`` — dispatch a batch: one point goes
  through the engine's PLAIN entry (so singles share the common
  non-sweep executable with every other caller); several points go
  through the config-axis sweep argument as one device launch.
- ``warm(n_points)`` — compile the executable a batch of ``n_points``
  would use, against a minimal-horizon copy of the program (horizons
  are traced operands, so the minimal-horizon compile IS the real
  one); the server's warm pool calls this at start, where a warm
  persistent compile cache turns it into a disk hit.
- ``solo`` — True marks a study the sweep equality guarantee cannot
  cover (e.g. a dumbbell program whose ``ecn`` disagrees with the
  variants' ``REQUIRES_ECN`` flags — sweep points derive ECN from the
  variant); the server never batches it with anything.
- ``spec`` — a **picklable** launch description
  (``{"engine", "prog", "key", "replicas"}``) for studies that can be
  routed to a member process of a multi-process mesh
  (:mod:`tpudes.serving.distributed`): the member rebuilds the
  descriptor from the spec through the same ``*_study`` extractor and
  launches its slice of the batch's points.  ``None`` (e.g. a study
  pinned to a live device mesh) keeps the study host-local.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

__all__ = ["StudyDescriptor", "mesh_fingerprint"]


def mesh_fingerprint(mesh) -> tuple | None:
    """Hashable identity of a mesh for the coalesce key (two studies
    must target the same device set to share a launch)."""
    if mesh is None:
        return None
    return (
        tuple(mesh.axis_names),
        tuple(d.id for d in mesh.devices.flat),
    )


@dataclass(frozen=True)
class StudyDescriptor:
    """One submitted study, as the coalescing scheduler sees it."""

    engine: str
    coalesce_key: tuple
    sweep_point: Any
    launch: Callable  # (points, block=False) -> result | EngineFuture
    warm: Callable = None  # (n_points) -> None, blocking mini-compile
    solo: bool = field(default=False)
    #: picklable launch spec for cross-process routing (None = local)
    spec: dict | None = field(default=None, compare=False)

    def compatible(self, other: "StudyDescriptor") -> bool:
        """True when ``self`` and ``other`` may share one launch."""
        return (
            not self.solo
            and not other.solo
            and self.engine == other.engine
            and self.coalesce_key == other.coalesce_key
        )
