"""Test configuration: force JAX onto a virtual 8-device CPU mesh so all
sharding/parallel tests run without TPU hardware (the driver dry-runs the
real multi-chip path separately via __graft_entry__.dryrun_multichip)."""

import os

# dependency-free (and jax-free), so it is safe to consult before the
# XLA backend configuration below
from racon_tpu import flags as racon_flags

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

if not racon_flags.get_bool("RACON_TPU_TEST_REAL"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")

import pathlib

import pytest

REFERENCE_DATA = pathlib.Path("/root/reference/test/data")


@pytest.fixture(scope="session")
def data_dir():
    if not REFERENCE_DATA.exists():
        pytest.skip("reference test data not available")
    return REFERENCE_DATA
