"""ICI shuffle: device-resident partition exchange over a jax Mesh.

This is the TPU-native replacement for the reference's accelerated shuffle
data plane (reference: shuffle-plugin UCX transport, UCX.scala:53-533;
RapidsCachingWriter keeping map-output batches in the device store,
RapidsShuffleInternalManager.scala:90-155).  Where the reference moves
device buffers peer-to-peer over RDMA with bounce-buffer windowing, here
partitions never leave HBM at all: a ``shard_map`` region hash-partitions
rows on-device and swaps the buckets with one ``lax.all_to_all`` over the
ICI mesh axis — the collective formulation SURVEY.md §2g/§5 prescribes.

The flagship composite op is the distributed hash aggregate:

  local update-agg  ->  murmur3 pmod bucketize  ->  all_to_all  ->
  compact  ->  merge-agg  ->  final projection

which is exactly the reference's partial-agg / shuffle / final-agg stage
pair (aggregate.scala + GpuShuffleExchangeExec) fused into one SPMD step
XLA can schedule end-to-end.  Static shapes: each device sends exactly
``capacity`` candidate slots per peer; true counts travel as a tiny int
vector alongside (the scalar-prefetch idiom).
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu import dtypes as dt
from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn
from spark_rapids_tpu.exec.kernel_cache import jit_named
from spark_rapids_tpu.exec.tpu_aggregate import (finalize_aggregate,
                                                 make_spec, merge_aggregate,
                                                 update_aggregate)
from spark_rapids_tpu.exec.tpu_basic import compact
from spark_rapids_tpu.expr import ir
from spark_rapids_tpu.expr.eval_tpu import ColVal, hash_colval
from spark_rapids_tpu.plan.logical import Schema


def partition_targets(key_vals: Sequence[ColVal], n_parts: int,
                      seed: int = 42) -> jnp.ndarray:
    """Spark-compatible murmur3 pmod partition ids
    (GpuHashPartitioning analog, reference: GpuHashPartitioning.scala:29)."""
    cap = key_vals[0].data.shape[0]
    h = jnp.full((cap,), np.int32(seed), dtype=jnp.int32)
    for v in key_vals:
        h = hash_colval(v, h)
    m = h % np.int32(n_parts)
    return jnp.where(m < 0, m + n_parts, m)


def bucketize(batch: DeviceBatch, target: jnp.ndarray, n_parts: int
              ) -> Tuple[List[DeviceColumn], jnp.ndarray]:
    """Slice a batch into n_parts contiguous buckets (stacked on a new
    leading axis).  The XLA analog of cudf contiguous_split used by
    GpuPartitioning.sliceInternalOnGpu (reference: GpuPartitioning.scala:45).

    Returns columns whose arrays have shape [n_parts, cap, ...] plus a
    per-bucket row count [n_parts].
    """
    cap = batch.capacity
    exists = batch.row_mask()
    t = jnp.where(exists, target, n_parts)  # park padding out of range
    counts = jnp.zeros((n_parts,), dtype=jnp.int32).at[t].add(
        exists.astype(jnp.int32), mode="drop")
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    order = jnp.argsort(t, stable=True)  # groups rows by target, padding last
    sorted_t = jnp.take(t, order)
    rank = jnp.arange(cap, dtype=jnp.int32) - jnp.take(
        offsets, jnp.clip(sorted_t, 0, n_parts - 1))
    flat_pos = jnp.where(sorted_t < n_parts,
                         sorted_t * cap + jnp.clip(rank, 0, cap - 1),
                         n_parts * cap)  # padding -> dropped
    gather_idx = jnp.zeros((n_parts * cap,), dtype=jnp.int32).at[
        flat_pos].set(order.astype(jnp.int32), mode="drop")
    slot = jnp.arange(n_parts * cap) % cap
    valid = slot < jnp.repeat(counts, cap)
    out_cols = []
    for c in batch.columns:
        g = c.gather(gather_idx, valid)
        data = g.data.reshape((n_parts, cap) + g.data.shape[1:])
        validity = g.validity.reshape((n_parts, cap))
        lengths = g.lengths.reshape((n_parts, cap)) \
            if g.lengths is not None else None
        ev = g.elem_validity.reshape((n_parts, cap) +
                                     g.elem_validity.shape[1:]) \
            if g.elem_validity is not None else None
        out_cols.append(DeviceColumn(c.dtype, data, validity, lengths, ev))
    return out_cols, counts


def exchange(stacked_cols: List[DeviceColumn], counts: jnp.ndarray,
             axis: str) -> Tuple[List[DeviceColumn], jnp.ndarray]:
    """One tiled all_to_all per buffer: bucket d of device s lands on
    device d as block s.  (The whole UCX client/server/bounce-buffer
    machinery of the reference collapses into this collective.)  The
    collectives sit in the named scope ``ici.exchange``, which their
    HLO metadata carries into a device trace."""
    def a2a(x):
        with jax.named_scope("ici.exchange"):
            return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
    out_cols = []
    for c in stacked_cols:
        out_cols.append(DeviceColumn(
            c.dtype, a2a(c.data), a2a(c.validity),
            a2a(c.lengths) if c.lengths is not None else None,
            a2a(c.elem_validity) if c.elem_validity is not None else None))
    return out_cols, a2a(counts)


def reassemble(names: Sequence[str], stacked_cols: List[DeviceColumn],
               counts_recv: jnp.ndarray) -> DeviceBatch:
    """Flatten received blocks and compact valid rows to the front."""
    n_parts = counts_recv.shape[0]
    cap = stacked_cols[0].validity.shape[1]
    slot = jnp.arange(n_parts * cap) % cap
    valid = slot < jnp.repeat(counts_recv, cap)
    flat_cols = []
    for c in stacked_cols:
        data = c.data.reshape((n_parts * cap,) + c.data.shape[2:])
        validity = c.validity.reshape((n_parts * cap,))
        lengths = c.lengths.reshape((n_parts * cap,)) \
            if c.lengths is not None else None
        ev = c.elem_validity.reshape((n_parts * cap,) +
                                     c.elem_validity.shape[2:]) \
            if c.elem_validity is not None else None
        flat_cols.append(DeviceColumn(c.dtype, data, validity, lengths, ev))
    # rows arrive block-strided; compact the `valid` rows to the front so
    # the result satisfies the DeviceBatch row_mask contract (scatter by
    # cumsum rank — no sort; XLA sort compiles are minutes-scale)
    tcap = n_parts * cap
    count = jnp.sum(valid.astype(jnp.int32))
    dest = jnp.where(valid, jnp.cumsum(valid.astype(jnp.int32)) - 1,
                     tcap)
    from spark_rapids_tpu.columnar.batch import compact_arrays
    cols = [DeviceColumn(c.dtype, *compact_arrays(
        valid, dest, c.data, c.validity, c.lengths, c.elem_validity))
        for c in flat_cols]
    return DeviceBatch(names, cols, count)


def make_distributed_agg_step(mesh: Mesh, axis: str,
                              schema: Schema,
                              groupings: Sequence[ir.Expression],
                              aggregates: Sequence[ir.AggregateExpression],
                              out_names: Sequence[str]):
    """Build the jitted SPMD step: sharded input columns -> per-device
    aggregated output shard.

    Inputs are global arrays sharded on the leading (row) axis over
    ``axis``; ``local_rows`` is an [n_devices] vector of true per-shard row
    counts.  Output shards hold disjoint group subsets (hash-partitioned),
    exactly like the reference's final-aggregate stage after a hash
    exchange.
    """
    specs = [make_spec(a) for a in aggregates]
    nk = len(groupings)
    n_dev = mesh.shape[axis]
    names = schema.names
    dtypes = schema.dtypes

    def local_step(cols_leaves, local_rows):
        cols = _leaves_to_cols(cols_leaves, dtypes)
        batch = DeviceBatch(names, cols, local_rows[0])
        partial = update_aggregate(batch, groupings, aggregates, specs)
        key_vals = [ColVal(c.dtype, c.data, c.validity, c.lengths)
                    for c in partial.columns[:nk]]
        target = partition_targets(key_vals, n_dev) if nk else \
            jnp.zeros((partial.capacity,), dtype=jnp.int32)
        stacked, counts = bucketize(partial, target, n_dev)
        stacked, counts_recv = exchange(stacked, counts, axis)
        received = reassemble(partial.names, stacked, counts_recv)
        merged = merge_aggregate(received, nk, specs)
        final = finalize_aggregate(merged, nk, specs, out_names)
        out_leaves = _cols_to_leaves(final.columns)
        return out_leaves, jnp.reshape(
            jnp.asarray(final.num_rows, dtype=jnp.int32), (1,))

    in_specs = (_col_specs(dtypes, P(axis)), P(axis))
    out_dtypes = _probe_out_dtypes(schema, groupings, aggregates, out_names)
    out_specs = (_col_specs(out_dtypes, P(axis)), P(axis))

    step = jax.shard_map(local_step, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
    return jit_named(step, "ici_agg"), out_dtypes


def _probe_out_dtypes(schema, groupings, aggregates, out_names):
    for g in groupings:
        g.resolve() if g.dtype is None else None
    key_dts = [g.dtype for g in groupings]
    agg_dts = [a.dtype for a in aggregates]
    return key_dts + agg_dts


def _col_specs(dtypes, spec):
    out = []
    for d in dtypes:
        if d.is_string:
            out.append((spec, spec, spec))
        else:
            out.append((spec, spec))
    return tuple(out)


def _cols_to_leaves(cols: Sequence[DeviceColumn]):
    leaves = []
    for c in cols:
        if c.elem_validity is not None:
            leaves.append((c.data, c.validity, c.lengths, c.elem_validity))
        elif c.lengths is not None:
            leaves.append((c.data, c.validity, c.lengths))
        else:
            leaves.append((c.data, c.validity))
    return tuple(leaves)


def _leaves_to_cols(leaves, dtypes):
    cols = []
    for leaf, d in zip(leaves, dtypes):
        if len(leaf) == 4:
            cols.append(DeviceColumn(d, leaf[0], leaf[1], leaf[2], leaf[3]))
        elif len(leaf) == 3:
            cols.append(DeviceColumn(d, leaf[0], leaf[1], leaf[2]))
        else:
            cols.append(DeviceColumn(d, leaf[0], leaf[1], None))
    return cols


def shard_batch(batch: DeviceBatch, mesh: Mesh, axis: str
                ) -> Tuple[Tuple, jnp.ndarray]:
    """Distribute a host-built DeviceBatch's rows round-robin-contiguously
    across the mesh: returns (sharded column leaves, per-shard row counts).

    The capacity must divide evenly by the device count; rows are laid out
    so shard i holds rows [i*local_cap, (i+1)*local_cap).
    """
    n_dev = mesh.shape[axis]
    cap = batch.capacity
    assert cap % n_dev == 0, f"capacity {cap} not divisible by {n_dev}"
    local_cap = cap // n_dev
    total = int(batch.num_rows)
    # per-shard true row counts for the contiguous layout
    counts = np.clip(total - np.arange(n_dev) * local_cap, 0, local_cap)
    counts = jnp.asarray(counts, dtype=jnp.int32)
    sharding = NamedSharding(mesh, P(axis))
    leaves = []
    for c in batch.columns:
        # leaf arity must match _cols_to_leaves: 4-tuple implies lengths
        assert c.elem_validity is None or c.lengths is not None
        leaf = [jax.device_put(c.data, sharding),
                jax.device_put(c.validity, sharding)]
        if c.lengths is not None:
            leaf.append(jax.device_put(c.lengths, sharding))
        if c.elem_validity is not None:
            leaf.append(jax.device_put(c.elem_validity, sharding))
        leaves.append(tuple(leaf))
    counts = jax.device_put(counts, sharding)
    return tuple(leaves), counts


# ---------------------------------------------------------------------------
# Generic partition exchange: the ICI data plane behind
# TpuShuffleExchangeExec(transport='ici').  Reference analog: the UCX
# transport implementation behind the shuffle SPI
# (shuffle-plugin/.../UCX.scala:53-533) — here the entire peer-to-peer
# client/server machinery collapses into one lax.all_to_all over the mesh.
# ---------------------------------------------------------------------------

_DEFAULT_MESH: Optional[Mesh] = None
_STEP_CACHE = {}


def get_default_mesh() -> Mesh:
    """Process-wide 1-D mesh over every visible device (the 'shuffle'
    axis).  On the 8-virtual-CPU test platform this is an 8-way mesh; on a
    single real TPU chip it degenerates to 1 device (all_to_all becomes an
    identity, keeping one code path)."""
    global _DEFAULT_MESH
    if _DEFAULT_MESH is None:
        _DEFAULT_MESH = Mesh(np.array(jax.devices()), ("shuffle",))
    return _DEFAULT_MESH


def with_capacity(batch: DeviceBatch, cap: int) -> DeviceBatch:
    """Re-capacity a front-compacted batch (grow or shrink padding)."""
    if batch.capacity == cap:
        return batch
    assert int(batch.num_rows) <= cap
    from spark_rapids_tpu.shuffle.exchange import slice_span
    return slice_span(batch, jnp.int32(0),
                      jnp.asarray(batch.num_rows, jnp.int32), cap)


def make_exchange_step(mesh: Mesh, axis: str, names, dtypes, aux_key):
    """Jitted shard_map step routing rows to the device owning their
    target partition.  The batch's LAST column is the int32 target
    partition id; device d owns partitions {p : p % n_dev == d}.

    Returns out leaves of per-device capacity n_dev*local_cap (worst case:
    every row lands on one device) plus per-device received row counts.
    """
    key = (mesh, axis, tuple(names), aux_key)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]
    n_dev = mesh.shape[axis]

    def local_step(leaves, local_rows):
        cols = _leaves_to_cols(leaves, dtypes)
        batch = DeviceBatch(names, cols, local_rows[0])
        part = batch.columns[-1].data.astype(jnp.int32)
        owner = part % np.int32(n_dev)
        stacked, counts = bucketize(batch, owner, n_dev)
        stacked, counts_recv = exchange(stacked, counts, axis)
        received = reassemble(names, stacked, counts_recv)
        return _cols_to_leaves(received.columns), jnp.reshape(
            jnp.asarray(received.num_rows, dtype=jnp.int32), (1,))

    step = jit_named(jax.shard_map(
        local_step, mesh=mesh, in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis)), check_vma=False), "ici_exchange")
    _STEP_CACHE[key] = step
    return step


def split_shards(arr: jnp.ndarray, n_dev: int) -> List[jnp.ndarray]:
    """Per-device local views of a leading-axis-sharded global array,
    without any collective (each view stays committed to its device)."""
    per = arr.shape[0] // n_dev
    shards = {s.index[0].start or 0: s.data for s in arr.addressable_shards}
    if len(shards) == n_dev and all(d * per in shards
                                    for d in range(n_dev)):
        return [shards[d * per] for d in range(n_dev)]
    return [arr[d * per:(d + 1) * per] for d in range(n_dev)]


def exchange_batch(batch: DeviceBatch, targets: jnp.ndarray,
                   min_bucket: int = 16
                   ) -> Tuple[List[Optional[DeviceBatch]], Mesh]:
    """Run the full ICI exchange for one global batch.

    ``targets`` is a per-slot int32 target-partition vector (padding slots
    ignored).  Returns one local DeviceBatch per mesh device — each batch
    carries a trailing '__part__' column so the reader can sub-split the
    device's rows into its owned partitions — plus the mesh used.
    """
    from spark_rapids_tpu.columnar.batch import bucket_rows

    mesh = get_default_mesh()
    n_dev = mesh.shape["shuffle"]
    total = int(batch.num_rows)
    part_col = DeviceColumn(dt.INT32, targets.astype(jnp.int32),
                            batch.row_mask(), None)
    aug = DeviceBatch(list(batch.names) + ["__part__"],
                      list(batch.columns) + [part_col], total)
    local_cap = bucket_rows((total + n_dev - 1) // n_dev, min_bucket)
    aug = with_capacity(aug, local_cap * n_dev)
    leaves, counts = shard_batch(aug, mesh, "shuffle")
    aux_key = tuple((c.dtype.name, c.data.shape[1:],
                     c.lengths is not None, c.elem_validity is not None)
                    for c in aug.columns) + (local_cap,)
    step = make_exchange_step(mesh, "shuffle", aug.names, aug.dtypes,
                              aux_key)
    out_leaves, out_rows = step(leaves, counts)
    rows = np.asarray(out_rows)
    dev_batches: List[Optional[DeviceBatch]] = []
    for d in range(n_dev):
        if int(rows[d]) == 0:
            dev_batches.append(None)
            continue
        cols = []
        for leaf, c in zip(out_leaves, aug.columns):
            parts = [split_shards(a, n_dev)[d] for a in leaf]
            lengths = parts[2] if c.lengths is not None else None
            ev = parts[-1] if c.elem_validity is not None else None
            cols.append(DeviceColumn(c.dtype, parts[0], parts[1],
                                     lengths, ev))
        dev_batches.append(DeviceBatch(aug.names, cols, int(rows[d])))
    return dev_batches, mesh


def ring_broadcast_batch(batch: DeviceBatch) -> dict:
    """Build replication over the POINT-TO-POINT plane: the batch is
    sharded across the mesh and each shard travels around the ICI ring
    with ``lax.ppermute`` (collective_permute) until every device holds
    every shard — n_dev-1 neighbor hops instead of one all-to-all, the
    memory-traffic shape of a ring all-gather.

    This is the engine's collective formulation of the reference's
    tag-matched per-peer pulls (UCXConnection.scala:385: each reducer
    fetches specific blocks from specific peers); BASELINE.json's north
    star names ICI all_to_all AND collective_permute as the two data
    planes.  Same {device: DeviceBatch} contract as broadcast_batch."""
    from spark_rapids_tpu.columnar.batch import bucket_rows

    mesh = get_default_mesh()
    n_dev = mesh.shape["shuffle"]
    if n_dev == 1:
        return broadcast_batch(batch)
    total = int(batch.num_rows)
    local_cap = bucket_rows(max((total + n_dev - 1) // n_dev, 1), 16)
    aug = with_capacity(batch, local_cap * n_dev)
    leaves, counts = shard_batch(aug, mesh, "shuffle")
    names = aug.names
    # each device sends its current block to its LEFT neighbor, so after
    # k hops a device holds the block of (its index + k) % n_dev
    perm = [(i, (i - 1) % n_dev) for i in range(n_dev)]

    def local_step(cols_leaves, local_rows):
        me = lax.axis_index("shuffle")
        flat, treedef = jax.tree_util.tree_flatten(
            (cols_leaves, local_rows))
        accs = [jnp.zeros((n_dev,) + a.shape, a.dtype) for a in flat]
        cur = list(flat)
        for k in range(n_dev):
            pos = (me + k) % np.int32(n_dev)
            accs = [jax.lax.dynamic_update_slice(
                acc, c[None], (pos,) + (jnp.int32(0),) * c.ndim)
                for acc, c in zip(accs, cur)]
            if k < n_dev - 1:
                cur = [lax.ppermute(c, "shuffle", perm) for c in cur]
        # accs are IDENTICAL on every device now: [n_dev, ...] blocks in
        # global shard order — rebuild stacked columns and compact
        g_cols_leaves, g_rows = jax.tree_util.tree_unflatten(
            treedef, accs)
        stacked: List[DeviceColumn] = []
        for c, leaf in zip(aug.columns, g_cols_leaves):
            parts = list(leaf)
            lengths = parts[2] if c.lengths is not None else None
            ev = parts[-1] if c.elem_validity is not None else None
            stacked.append(DeviceColumn(c.dtype, parts[0], parts[1],
                                        lengths, ev))
        counts_recv = jnp.reshape(g_rows, (n_dev,))
        out = reassemble(names, stacked, counts_recv)
        return _cols_to_leaves(out.columns), jnp.reshape(
            jnp.asarray(out.num_rows, jnp.int32), (1,))

    step = jit_named(jax.shard_map(
        local_step, mesh=mesh, in_specs=(P("shuffle"), P("shuffle")),
        out_specs=(P(), P()), check_vma=False), "ici_join")
    out_leaves, out_rows = step(leaves, counts)
    n_out = int(np.asarray(out_rows)[0])

    out = {}
    for d in mesh.devices.flat:
        def local(a, d=d):
            if a is None or not hasattr(a, "addressable_shards"):
                return a
            for s in a.addressable_shards:
                if s.device == d:
                    return s.data
            return a
        cols = []
        for leaf, c in zip(out_leaves, aug.columns):
            parts = [local(a) for a in leaf]
            lengths = parts[2] if c.lengths is not None else None
            ev = parts[-1] if c.elem_validity is not None else None
            cols.append(DeviceColumn(c.dtype, parts[0], parts[1],
                                     lengths, ev))
        out[d] = DeviceBatch(names, cols, n_out)
    return out


def broadcast_batch(batch: DeviceBatch) -> dict:
    """One-to-all replication of a batch over the mesh: ONE
    fully-replicated ``jax.device_put`` lets XLA broadcast every column
    over ICI, then each device gets a zero-copy local view.

    The mesh sibling of ``exchange_batch`` (all-to-all) — the
    ``GpuBroadcastExchangeExec`` analog (reference:
    GpuBroadcastExchangeExec.scala:238-398, which serializes the build
    side once and ships it to every executor).  Returns
    {device: DeviceBatch} with one entry per mesh device."""
    mesh = get_default_mesh()
    rep = NamedSharding(mesh, P())
    rep_batch = jax.device_put(batch, rep)
    out = {}
    for d in mesh.devices.flat:
        def local(a, d=d):
            if a is None or not hasattr(a, "addressable_shards"):
                return a
            for s in a.addressable_shards:
                if s.device == d:
                    return s.data
            return a
        cols = [DeviceColumn(c.dtype, local(c.data), local(c.validity),
                             local(c.lengths), local(c.elem_validity))
                for c in rep_batch.columns]
        out[d] = DeviceBatch(batch.names, cols,
                             local(rep_batch.num_rows))
    return out
