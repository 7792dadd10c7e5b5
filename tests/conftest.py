"""Test harness: 8 fake CPU devices, per SURVEY.md §4.

The reference had no test suite at all (its only "integration test" was a
CloudFormation stack reaching CREATE_COMPLETE); we test every parallelism
path on a virtual 8-device CPU mesh so multi-chip behavior is exercised in
CI without TPU hardware.

Env must be adjusted before the first JAX backend initialization: every
accelerator-selection variable is scrubbed and the process pinned to 8
fake CPU devices, whatever the host environment carries.
"""

import importlib.util
import os
from pathlib import Path

# One shared scrub rule (tpucfn/utils/env.py), loaded by file path so no
# package (and no jax) import happens before the environment is fixed.
_spec = importlib.util.spec_from_file_location(
    "_tpucfn_env",
    Path(__file__).resolve().parent.parent / "tpucfn" / "utils" / "env.py")
_envmod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_envmod)
_clean = _envmod.scrub_accelerator_env(os.environ, n_devices=8)
os.environ.clear()
os.environ.update(_clean)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _assert_fake_devices():
    assert jax.devices()[0].platform == "cpu"
    assert len(jax.devices()) == 8, (
        "tests need 8 fake CPU devices; got "
        f"{len(jax.devices())} — check XLA_FLAGS handling in conftest"
    )
    yield


@pytest.fixture()
def mesh8():
    """A full 6-axis mesh over the 8 fake devices: 2 data × 2 fsdp × 2 tensor."""
    from tpucfn.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=2, fsdp=2, tensor=2))


@pytest.fixture()
def mesh_dp8():
    """Pure-DP mesh (data=8) — the reference-equivalent topology."""
    from tpucfn.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=8))
