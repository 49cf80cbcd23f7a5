"""Multi-device SPMD tests (subprocess with 8 forced host devices).

Each check runs in its own process because jax locks the device count at
first init; see tests/spmd_check.py for the actual assertions.
"""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
SRC = os.path.join(os.path.dirname(HERE), "src")


def run_check(name, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "spmd_check.py"), name],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, f"{name} failed:\n{proc.stdout}\n{proc.stderr}"
    assert f"{name} OK" in proc.stdout


@pytest.mark.slow
def test_gossip_equals_dense_transition():
    """Structured ppermute aggregation == the paper's dense Lemma-1 einsum."""
    run_check("gossip_equivalence")


@pytest.mark.slow
def test_tiny_dryrun_lowers_and_compiles():
    run_check("tiny_dryrun")


@pytest.mark.slow
def test_sequence_sharded_decode_matches_local():
    run_check("decode_sharded")


@pytest.mark.slow
def test_lm_collective_mesh_matches_emulation():
    """Federated-LM round under shard_map on a client mesh == vmap emulation."""
    run_check("lm_collective_mesh")


@pytest.mark.slow
def test_continuous_serving_mesh_matches_fallback():
    """Slot-pool decode on mesh-sharded cluster replicas == vmap fallback."""
    run_check("continuous_mesh_serving")


@pytest.mark.slow
def test_round_scheduler_mesh_compiles_once():
    """mesh="auto": client state starts one client per device; one compile."""
    run_check("round_scheduler_mesh")
