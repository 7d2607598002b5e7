"""Test bootstrap: force a hermetic 8-virtual-device CPU platform.

``chip_smoke.py`` is what runs on the real TPU chip; tests run anywhere.
The virtual device count lets sharding/collective tests exercise a real
``jax.sharding.Mesh`` without hardware (SURVEY.md §4 implication: ~95% of
the system verifiable on a single host).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# persistent compile cache, placed through jax's own variable: one fixed
# directory OUTSIDE the checkout, so the thousands of XLA:CPU entries a
# suite run writes never land in the tree the chip tool copies.  It
# cuts repeat suite runs from minutes of recompiles to cache reads.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.expanduser("~/.cache/spark_rapids_tpu/xla-cpu-tests"))

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _bounded_compile_state():
    """Clear jit/kernel caches between test modules: a full-suite run
    compiles thousands of XLA:CPU executables, and unbounded accumulation
    has produced compiler segfaults late in the run."""
    yield
    from spark_rapids_tpu.exec import kernel_cache
    kernel_cache.clear_compile_state()


@pytest.fixture(autouse=True)
def _bounded_memory_maps():
    """Executor-longevity guard INSIDE big modules (TPC-DS is ~120
    tests in one module) — the shared engine guard, forced every test
    with a tighter line."""
    yield
    from spark_rapids_tpu.exec import kernel_cache
    kernel_cache.maybe_clear_for_map_pressure(threshold=25000,
                                              force_check=True)


@pytest.fixture()
def session():
    from spark_rapids_tpu import TpuSparkSession
    return TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    })


def pytest_configure(config):
    # expected under sql.fusion.donateInputs: jax warns once per compile
    # when a donated input shape has no same-shaped output to reuse
    # (string max_len re-bucketing, filtered column drops) — partial
    # reuse is the point, the warning is noise
    config.addinivalue_line(
        "filterwarnings",
        "ignore:Some donated buffers were not usable")
    config.addinivalue_line(
        "markers",
        "faults: deterministic fault-injection tests exercising the "
        "shuffle retry/recovery/fallback machinery (tier-1 safe)")
    config.addinivalue_line(
        "markers",
        "perf: performance-oriented tests (e.g. the scan-plan cache "
        "byte-budget eviction drill) — runnable standalone via "
        "`pytest -m perf`")
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 budgeted run (ROADMAP.md runs "
        "-m 'not slow'); the heaviest distributed-plan parity drills "
        "live here — run them via `pytest -m slow`")
