"""Test configuration: run the collective tests on a virtual 8-device
CPU mesh (the TPU analogue of the reference running its parallel tests
under `horovodrun -np 2 -H localhost:2 --gloo`,
.buildkite/gen-pipeline.sh:278 — multi-device is simulated on one host
via XLA's host-platform device partitioning)."""

import os
import sys

# Must be set before jax initializes its backends.  The environment is
# what child processes of the tests inherit: the CPU platform and eight
# virtual devices.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
# The engine picks its mesh from this platform, so a test run on a host
# with a chip stays off it.
os.environ["HOROVOD_TPU_PLATFORM"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# jax 0.9.0 honours both the XLA flag above and this option; the option
# wins where an inherited XLA_FLAGS names another count.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# The reference supports 64-bit dtypes (message.h:30-41); enable them.
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "integration: end-to-end multi-process launches (slower)")
    config.addinivalue_line(
        "markers",
        "slow: the worker process takes the host's REAL default "
        "backend (the chip where the host has one — the pytest parent "
        "is pinned to the CPU, so the worker is the chip's one "
        "process), or compiles a whole model step; excluded from the "
        "fast tier, run explicitly with -m slow")


@pytest.fixture()
def hvd_shutdown():
    """Ensure a clean runtime between tests that call init()."""
    yield
    if hvd.is_initialized():
        hvd.shutdown()
