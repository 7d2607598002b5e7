"""Compile for the chip, without the chip.

The TPU's compiler is installed here and compiles for a v5e that is
described, not attached (``JAX_PLATFORMS=cpu`` stays set): the programs
``chip_smoke.py``'s statements dispatch, at the smoke's own batch shapes
(files of ``ROWS_PER_FILE`` rows), captured from one CPU run of those
statements — every first (kernel, shapes) call through the kernel cache
hands over its traceable, jit kwargs and abstract arguments — and
lowered for the described device.  All of them must compile.

Nothing runs on a device here: a compile that passes is not a chip run.
All of it lives in this one file and describes the topology inside a
fixture — the worker that is handed this file is the only process that
loads the TPU library.
"""

import os

import numpy as np
import pytest

import jax


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def smoke_programs(tmp_path_factory):
    """{family: [(traceable, jit kwargs, args, kwargs)]} of every
    program the smoke's statements dispatch on the default path: q6 over
    two files (two scan batches, so partial aggregates merge) and q3
    over one.  Arguments are abstract (shape + dtype) where they were
    arrays."""
    import chip_smoke
    from spark_rapids_tpu.exec import kernel_cache as kc

    programs = {}
    observe = kc._observe_compiles

    def abstract(x):
        if isinstance(x, (jax.Array, np.ndarray)):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    def spy(key, fn, replay_src=None):
        inner, jit_kwargs = replay_src
        seen = set()

        def first_calls(*args, **kwargs):
            spec = jax.tree_util.tree_map(abstract, (args, kwargs))
            leaves, treedef = jax.tree_util.tree_flatten(spec)
            sig = (treedef, tuple(
                (x.shape, str(x.dtype))
                if isinstance(x, jax.ShapeDtypeStruct) else repr(x)
                for x in leaves))
            if sig not in seen:
                seen.add(sig)
                programs.setdefault(key[0], []).append(
                    (inner, jit_kwargs) + spec)
            return fn(*args, **kwargs)
        return observe(key, first_calls, replay_src)

    root = str(tmp_path_factory.mktemp("smoke_data"))
    chip_smoke.make_data(root, 2 * chip_smoke.ROWS_PER_FILE, seed=22)
    one_file = os.path.join(root, "store_sales", "part-0000.parquet")
    kc._observe_compiles = spy
    try:
        spark = chip_smoke.start_session(
            root, {"spark.rapids.tpu.serve.enabled": False})
        spark.sql(chip_smoke.Q6_SQL.replace(
            ":lo", str(chip_smoke.Q6_BINDINGS[0]))).collect()
        spark.register_view("store_sales", spark.read.parquet(one_file))
        spark.sql(chip_smoke.Q3_SQL).collect()
    finally:
        kc._observe_compiles = observe
    return programs


@pytest.fixture(scope="module")
def for_chip(one_chip, smoke_programs):
    """compile(fn, jit_kwargs, args, kwargs) -> executable for the
    described v5e.  While it is in force: the persistent cache is off
    (such an executable cannot be read back without a chip), and code
    that asks ``jax.default_backend()`` is told "tpu" — expr/eval_tpu's
    ``f64_bits`` bitcasts on the CPU and rebuilds the bits
    arithmetically on a TPU, whose compiler refuses the 64-bit bitcast;
    the CPU capture above ran first and its traces are dropped."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def place(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=one_chip)
        return x

    def compile(fn, jit_kwargs, args, kwargs):
        args, kwargs = jax.tree_util.tree_map(place, (args, kwargs))
        return jax.jit(fn, **(jit_kwargs or {})).lower(
            *args, **kwargs).compile()

    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    try:
        yield compile
    finally:
        mp.undo()
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()
        jax.clear_caches()


# -- every program of the smoke's statements --------------------------------

_XLA_CASES = {
    "decode": ("pq_fused6",),
    "agg_update": ("agg_update",),
    "agg_merge": ("agg_merge", "concat"),
    "agg_finalize": ("agg_final",),
    "sort_keys": ("sort_keys", "shared_digit_sort", "sort_apply"),
    "join_probe": ("probe_count", "probe_emit_u"),
}


@pytest.mark.parametrize("case", list(_XLA_CASES) + ["everything_else"])
def test_smoke_xla_programs_compile_for_v5e(case, smoke_programs,
                                            for_chip):
    named = {f for fams in _XLA_CASES.values() for f in fams}
    families = _XLA_CASES.get(case) or \
        tuple(f for f in smoke_programs if f not in named)
    for fam in families:
        assert smoke_programs.get(fam), \
            f"the smoke's statements dispatched no {fam} program: " \
            f"{sorted(smoke_programs)}"
        for fn, jit_kwargs, args, kwargs in smoke_programs[fam]:
            for_chip(fn, jit_kwargs, args, kwargs)
