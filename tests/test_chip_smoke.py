"""chip_smoke.py's phases at toy sizes on CPU.

``chip_smoke.main()`` demands a TPU and BASELINE sizes; its phase
functions take their sizes as arguments, so the checks the chip run
makes — lifted path taken (never the scalar fallback), same-key
determinism, chunked == single-shot under TpudesObs, serving == solo,
wired == host DES — are exercised here on every tier-1 run.
"""

import pytest

import chip_smoke as cs
from tpudes.parallel.runtime import RUNTIME

#: name -> (script, kind, script arguments, replicas), cut to toy size
TINY = {
    "wifi": ("wifi-bss.py", "bss", dict(nStas=4, simTime=1.3), 4),
    "lte": (
        "lena-simple.py", "lte_sm",
        dict(nEnbs=2, uesPerCell=3, simTime=0.25), 4,
    ),
    "tcp": (
        "tcp-variants.py", "dumbbell",
        dict(nFlows=2, variant="TcpCubic", simTime=2), 4,
    ),
    "as": ("brite-as.py", "as_flows", dict(nNodes=64, nFlows=4, simTime=1), 4),
}


@pytest.fixture(autouse=True)
def _fresh_runtime():
    RUNTIME.clear()
    yield
    RUNTIME.clear()


@pytest.mark.parametrize("name", list(TINY))
def test_stock_script_lifts_and_reproduces(name):
    script, kind, args, replicas = TINY[name]
    res = cs.phase_script(name, script, kind, args, replicas)
    assert res["kind"] == kind and res["replicas"] == replicas
    assert res["program"] is not None


def test_scalar_fallback_is_a_failure_not_a_pass():
    """JaxReplicas=0 refuses the lift: the script still exits 0 from
    the scalar engine — exactly the fallback the smoke must not accept
    as a device run."""
    with pytest.raises(cs.SmokeFailure, match="replicated_result is None"):
        cs.phase_script(
            "wifi", "wifi-bss.py", "bss", dict(nStas=2, simTime=1.2), 0
        )


def test_lte_lowerings_chunked_obs_and_serving(sm_lowerings_built):
    from tpudes.parallel.lift import lifted_key

    script, kind, args, replicas = TINY["lte"]
    res = cs.phase_script("lte", script, kind, args, replicas)
    key = lifted_key()
    # on CPU pallas runs discharged to XLA ops: the COMPILED program
    # holds no Mosaic call whatever TPUDES_PALLAS wishes, and the two
    # lowerings of the one math core are bit-identical
    low = cs.phase_lte_lowerings(
        res["program"], key, replicas, expect_pallas="xla"
    )
    assert low["bit_equal"] and set(low["lowered"].values()) == {"xla"}
    assert sorted(low["lowered"]) == ["0", "1", "unset", "unset_solo"]
    # the executables read alike here, the runners differ: =1 and the
    # unset solo launch built the kernel step (interpret mode), =0 and
    # the unset batched launch the XLA step
    assert sm_lowerings_built(res["program"]) == {True, False}
    got = cs.phase_chunked_obs(
        "lte", kind, res["program"], key, replicas, "n_ttis", 100, 30
    )
    assert got["snapshots"] == 4 and got["donation_warnings"] == 0
    with pytest.raises(cs.SmokeFailure, match="no chunk metrics"):
        cs.phase_chunked_obs(
            "lte", kind, res["program"], key, replicas, "n_ttis", 100, 100
        )
    served = cs.phase_serving(res["program"], key, replicas)
    assert served["studies"] == 4


def test_bss_replica_engine_matches_host_des_on_the_same_graph():
    got = cs.phase_bss_host_parity(n_stas=6, sim_s=1.5, replicas=4, rtol=0.02)
    assert got["host"] > 0
    with pytest.raises(cs.SmokeFailure, match="bss parity"):
        # an impossible tolerance on a lossy ring: the check can fail
        cs.phase_bss_host_parity(n_stas=6, sim_s=1.5, replicas=4, rtol=-1.0)


def test_wired_matches_host_oracle():
    got = cs.phase_wired(
        dict(n_links=6, n_flows=3, n_slots=400, jitter_slots=6),
        replicas=4, window_slots=50,
    )
    assert got["packets"] > 0


def test_mesh_phase_on_the_virtual_devices():
    """The four-chip check (1-device mesh == whole mesh, outputs span
    every device) on conftest's 8 virtual CPU devices."""
    from tpudes.parallel.lift import lifted_key

    script, kind, args, _ = TINY["tcp"]
    res = cs.phase_script("tcp", script, kind, args, 8)
    got = cs.phase_mesh("tcp", kind, res["program"], lifted_key(), 8)
    assert got["devices"] == 8 and 8 in got["output_spans"]


def test_main_refuses_without_a_tpu(capsys):
    """Under JAX_PLATFORMS=cpu (this suite) main() exits non-zero,
    names what it found, and prints no verdict."""
    assert cs.main() != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "'cpu'" in captured.err
