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


_JAX_CACHE_KNOBS = ("jax_compilation_cache_dir",
                    "jax_persistent_cache_min_compile_time_secs",
                    "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def _jax_compile_cache_stays_in_its_test():
    """``benchmark.harness.run()``, which tests/benchmark_harness calls
    in this process, points jax's persistent compilation cache at
    ``<checkout>/.jax_cache`` and keeps every program, for the whole
    process. Left on, every later test of the same xdist worker
    compiles through a directory it shares with the other workers and
    with earlier runs — and XLA:CPU cannot serialize again an
    executable that came out of that cache: ``Predictor.warmup(
    cache_dir=)`` then commits entries that load and fail at launch
    (``NOT_FOUND: Function add_sqrt_fusion not found``). Put the knobs
    back after the test that turned them."""
    import jax
    before = {k: getattr(jax.config, k) for k in _JAX_CACHE_KNOBS}
    yield
    turned = [k for k in _JAX_CACHE_KNOBS
              if getattr(jax.config, k) != before[k]]
    for k in turned:
        jax.config.update(k, before[k])
    if turned:
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
