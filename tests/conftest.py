"""Test bootstrap: force an 8-device virtual CPU mesh.

The reference tests multi-device semantics on multiple *cpu* contexts in one
process (tests/python/unittest/test_model_parallel.py:12-30); we do the same
with an 8-device virtual CPU platform so sharding/collective paths are
exercised without TPU hardware.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: full-fit contract tests excluded from the tier-1 budget "
        "(-m 'not slow'); ci.sh's unfiltered suite runs them")


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _clear_process_warn_dedupe():
    """BaseModule._warn_once dedupes advisories once per PROCESS —
    correct for bench/serving workloads, but cross-test leakage would
    make caplog warning asserts order-dependent.  Clear the process set
    around every test."""
    try:
        from mxnet_tpu.module import base_module
    except Exception:
        yield
        return
    base_module._WARNED_PROCESS.clear()
    yield
    base_module._WARNED_PROCESS.clear()
