"""Test configuration: run the suite on a virtual 8-device CPU mesh.

Mirrors the reference CI trick of testing distributed semantics on one
machine (`ci/docker/runtime_functions.sh:551`): multi-chip sharding tests
use --xla_force_host_platform_device_count=8 host devices.

``JAX_PLATFORMS=cpu`` and ``XLA_FLAGS`` are set here, before anything
imports jax: jax reads both when its first backend initializes.

On-chip lane (the reference's GPU re-run pattern,
tests/python/gpu/test_operator_gpu.py): set ``MXNET_TEST_TPU=1`` to leave
the platform alone and run the ``tpu``-marked tests of
tests/test_tpu_smoke.py on the attached chip:

    MXNET_TEST_TPU=1 python -m pytest tests/ -m tpu -q

Without the env var, ``tpu``-marked tests are skipped and everything else
runs on the virtual CPU mesh. The chip belongs to one process at a time:
run the lane with one worker, and after anything else that held the chip
has exited.
"""
import os

TPU_LANE = os.environ.get("MXNET_TEST_TPU", "") == "1"

if not TPU_LANE:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: smoke tests that run on the attached TPU chip "
        "(enabled with MXNET_TEST_TPU=1, select with -m tpu)")
    config.addinivalue_line(
        "markers", "launched: spawns multi-process worker subprocesses "
        "(coordinator/PS/elastic tests); all subprocess waits go through "
        "tests/launchutil.py with explicit timeouts so a hung coordinator "
        "can never wedge the tier-1 lane; deselect with -m 'not launched'")
    config.addinivalue_line(
        "markers", "timeout(seconds): documented wall-clock budget of a "
        "launched test; enforcement is the subprocess timeouts inside "
        "(tests/launchutil.py), not a runner plugin")


def pytest_collection_modifyitems(config, items):
    if TPU_LANE:
        return
    skip_tpu = pytest.mark.skip(
        reason="on-chip lane disabled (set MXNET_TEST_TPU=1 and run -m tpu)")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip_tpu)


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    import mxnet_tpu as mx
    mx.random.seed(0)
    yield


@pytest.fixture(autouse=True)
def _chaos_disarm():
    """No chaos trigger armed in one test may leak into the next."""
    yield
    from mxnet_tpu import chaos
    chaos.clear()
