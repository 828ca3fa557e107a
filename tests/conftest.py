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


@pytest.fixture
def scalar_step_keys(monkeypatch):
    """``runtime.step_keys`` in the form every loop wrote by hand before
    it (``replicated.py`` and ``tcp_dumbbell.py`` up to PR 35): the
    counter folded into the key as a SCALAR, then one fold a replica.
    What the helper has to equal bit for bit.

    ``scalar_step_keys.same_bits(run, label)`` calls ``run()`` (which
    builds an engine's loop and runs it) as the tree stands, then again
    with this form over ``runtime.step_keys`` (the loop as it was),
    requires the two results equal leaf for leaf, and returns them as
    numpy."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudes.parallel import runtime

    def scalar(engine, key, counter, n):
        scalar.calls += 1
        k = jax.random.fold_in(key, counter)
        return jax.vmap(lambda r: jax.random.fold_in(k, r))(jnp.arange(n))

    def same_bits(run, label):
        new = jax.tree_util.tree_map(np.asarray, run())
        scalar.calls = 0
        with monkeypatch.context() as m:
            m.setattr(runtime, "step_keys", scalar)
            old = jax.tree_util.tree_map(np.asarray, run())
        assert scalar.calls > 0, "the builder did not take the helper"
        leaves, structure = jax.tree_util.tree_flatten_with_path(new)
        assert structure == jax.tree_util.tree_structure(old)
        for (path, a), b in zip(leaves, jax.tree_util.tree_leaves(old)):
            np.testing.assert_array_equal(
                a, b, err_msg=f"{label}: {jax.tree_util.keystr(path)}"
            )
        return old

    scalar.calls = 0
    scalar.same_bits = same_bits
    return scalar
