"""spark-rapids-tpu: a TPU-native columnar SQL/ETL engine.

From-scratch rebuild of the capability set of NVIDIA's RAPIDS Accelerator
for Apache Spark (spark-rapids v0.3.0) with TPU-first architecture:
plan override/tag/fallback/explain, HBM-resident Arrow-layout columnar
batches, expressions compiled to XLA, sort-based segmented-reduce
aggregation, total-order key-encoded sorts, ICI-collective shuffle, and a
device->host->disk spill framework.  See SURVEY.md at the repo root for the
full blueprint and reference mapping.
"""

import os as _os

import jax as _jax

# SQL engines need exact int64/float64; enable before anything traces.
_jax.config.update("jax_enable_x64", True)

# where the persistent compile cache lives when nothing outside places
# it: one fixed path inside the checkout (git-ignored).  The path is
# part of the cache key, so it must not move between runs.
_DEFAULT_COMPILE_CACHE = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")


def _enable_compile_cache(cache_dir=None) -> None:
    """Persistent XLA compilation cache, on every platform.

    The engine plans fresh exec trees per query and fresh processes per
    run; re-loading compiled executables beats recompiling.

    Placement is JAX's own: where ``JAX_COMPILATION_CACHE_DIR`` is set,
    jax already reads it and no directory is set in code (the fleet
    store's shared directory included — the environment wins).
    Otherwise ``cache_dir`` (the fleet's shared compile-cache
    directory) or the fixed in-checkout default applies.  Switch the
    cache off with jax's ``jax_enable_compilation_cache``.

    Called at session init; every program is cached, however quick its
    compile (a query dispatches many small programs).
    """
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        target = cache_dir or _DEFAULT_COMPILE_CACHE
        if _jax.config.jax_compilation_cache_dir != target:
            _os.makedirs(target, exist_ok=True)
            _jax.config.update("jax_compilation_cache_dir", target)
            # jax decides at the first compile of the process whether
            # a cache exists; a jit that ran before this session would
            # otherwise pin it off
            from jax.experimental.compilation_cache import (
                compilation_cache as _cc)
            _cc.reset_cache()
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from spark_rapids_tpu.exec import kernel_cache as _kc
    _kc.share_executables_across_chips()


from spark_rapids_tpu.api.session import TpuSparkSession  # noqa: E402,F401
from spark_rapids_tpu.api.column import Column, col, lit  # noqa: E402,F401
from spark_rapids_tpu.api import functions  # noqa: E402,F401

__version__ = "0.1.0"
