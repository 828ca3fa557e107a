"""Test configuration.

JAX runs on a virtual 8-device CPU mesh (SURVEY.md 4: the analog of ns-3's
mpirun-on-localhost distributed test harness) — set before any jax import.
Every test gets a fresh simulator world.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_world():
    """Reset all process-global simulator state between tests."""
    from tpudes.core.world import reset_world

    yield
    reset_world()


@pytest.fixture
def sm_lowerings_built():
    """``f(prog)`` -> the step lowerings among the cached ``lte_sm``
    runners, read from their cache keys: ``{True}`` the Mosaic kernel
    only, ``{False}`` the XLA step only, ``{True, False}`` both.  What
    an A/B of the two lowerings asserts, so that it cannot silently
    compare XLA with XLA (ISSUE 31: unset, ``TPUDES_PALLAS`` picks by
    lane count)."""
    from tpudes.parallel import lte_sm
    from tpudes.parallel.runtime import RUNTIME

    def built(prog) -> set:
        flag = object()
        # +1: the runtime prefixes the engine's name
        at = 1 + lte_sm._sm_cache_key(prog, None, None, False, flag).index(flag)
        return {k[at] for k in RUNTIME._runners if k[0] == "lte_sm"}

    return built
