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
XLA can schedule end-to-end.  Static shapes: each device sends one bucket
of slots per peer, the sender's capacity where nothing was counted and
the power of two of the fullest count where the host has read the
per-peer counts first (``exchange_placed``); true counts travel as a
tiny int vector alongside (the scalar-prefetch idiom).
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
from spark_rapids_tpu.columnar.batch import (DeviceBatch, DeviceColumn,
                                             read_host)
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


def bucketize(batch: DeviceBatch, target: jnp.ndarray, n_parts: int,
              bucket_cap: Optional[int] = None
              ) -> Tuple[List[DeviceColumn], jnp.ndarray]:
    """Slice a batch into n_parts contiguous buckets (stacked on a new
    leading axis).  The XLA analog of cudf contiguous_split used by
    GpuPartitioning.sliceInternalOnGpu (reference: GpuPartitioning.scala:45).

    Returns columns whose arrays have shape [n_parts, bucket_cap, ...]
    plus a per-bucket row count [n_parts].  ``bucket_cap`` is the slots
    a bucket has (the batch's capacity where none is given: any count
    fits); the caller that passes a smaller one has counted the rows and
    knows that no bucket holds more.

    A counting placement, no sort: a row's slot is its target's bucket
    base plus its rank among the earlier rows of that target (an
    exclusive prefix sum of one mask a target), so a bucket keeps the
    batch's row order.  One int32 scatter of the row index builds the
    gather that fills the buckets.
    """
    cap = batch.capacity
    bcap = cap if bucket_cap is None else int(bucket_cap)
    exists = batch.row_mask()
    t = jnp.where(exists, target, n_parts)  # padding matches no target
    rank = jnp.zeros((cap,), dtype=jnp.int32)
    counts = []
    for k in range(n_parts):
        hit = t == k
        seen = jnp.cumsum(hit.astype(jnp.int32))
        rank = jnp.where(hit, seen - 1, rank)
        counts.append(seen[-1])
    counts = jnp.stack(counts)
    flat_pos = jnp.where(t < n_parts, t * bcap + rank,
                         n_parts * bcap)  # padding -> dropped
    gather_idx = jnp.zeros((n_parts * bcap,), dtype=jnp.int32).at[
        flat_pos].set(jnp.arange(cap, dtype=jnp.int32), mode="drop")
    slot = jnp.arange(n_parts * bcap) % bcap
    valid = slot < jnp.repeat(counts, bcap)
    out_cols = []
    for c in batch.columns:
        g = c.gather(gather_idx, valid)
        data = g.data.reshape((n_parts, bcap) + g.data.shape[1:])
        validity = g.validity.reshape((n_parts, bcap))
        lengths = g.lengths.reshape((n_parts, bcap)) \
            if g.lengths is not None else None
        ev = g.elem_validity.reshape((n_parts, bcap) +
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
               counts_recv: jnp.ndarray,
               out_cap: Optional[int] = None) -> DeviceBatch:
    """The received blocks' rows, block after block, at the front of a
    batch of ``out_cap`` slots (the blocks' total where none is given;
    the caller that passes one knows that no chip receives more rows).

    Block ``b``'s rows are its first ``counts_recv[b]`` slots and belong
    at the sum of the earlier counts, so each block is copied there
    whole, in block order, each over the unused tail of the one before;
    the slots past the rows are then cleared.  Copies of contiguous
    runs: no scatter and no gather."""
    n_parts = counts_recv.shape[0]
    cap = stacked_cols[0].validity.shape[1]
    ocap = n_parts * cap if out_cap is None else int(out_cap)
    counts_recv = counts_recv.astype(jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts_recv)])
    count = starts[-1]
    # every copy lands inside the buffer: start[b] + cap <= n_parts * cap
    room = max(ocap, n_parts * cap)
    valid = jnp.arange(ocap) < count

    def runs(a, fill):
        out = jnp.full((room,) + a.shape[2:], fill, a.dtype)
        for b in range(n_parts):
            out = lax.dynamic_update_slice_in_dim(out, a[b], starts[b],
                                                  axis=0)
        out = out[:ocap]
        mask = valid.reshape(valid.shape + (1,) * (out.ndim - 1))
        return jnp.where(mask, out, jnp.asarray(fill, a.dtype))

    cols = [DeviceColumn(
        c.dtype, runs(c.data, 0), runs(c.validity, False),
        None if c.lengths is None else runs(c.lengths, 0),
        None if c.elem_validity is None else runs(c.elem_validity, False))
        for c in stacked_cols]
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
    total = int(read_host(batch.num_rows, "exchange.shardRowsWait"))
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
    """Re-capacity a front-compacted batch (grow or shrink padding).
    A shrink cuts padding off the end and is a prefix slice; only a
    grow gathers."""
    if batch.capacity == cap:
        return batch
    assert int(batch.num_rows) <= cap
    from spark_rapids_tpu.exec import kernel_cache as kc
    from spark_rapids_tpu.shuffle.exchange import prefix_span, slice_span
    shrink = cap < batch.capacity     # the key holds both capacities
    fn = kc.get_kernel(
        ("exch_slice", cap, batch.schema_key()),
        lambda: (lambda b, o, c: prefix_span(b, c, cap)) if shrink
        else (lambda b, o, c: slice_span(b, o, c, cap)))
    return fn(batch, jnp.int32(0), jnp.asarray(batch.num_rows, jnp.int32))


def make_exchange_step(mesh: Mesh, axis: str, names, dtypes, aux_key,
                       bucket_cap: Optional[int] = None,
                       recv_cap: Optional[int] = None):
    """Jitted shard_map step routing rows to the device owning their
    target partition.  The batch's LAST column is the int32 target
    partition id; device d owns partitions {p : p % n_dev == d}.

    Every device sends ``bucket_cap`` slots to every peer and compacts
    what it received into ``recv_cap`` slots a device (``n_dev *
    bucket_cap`` where none is given); with the out leaves come the
    per-device received row counts.  Without a ``bucket_cap`` a bucket
    has the sender's whole capacity (worst case: every row lands on one
    device); ``exchange_placed`` counts the rows first and passes the
    power of two of the fullest bucket and the tier of the most rows a
    device receives.
    """
    key = (mesh, axis, tuple(names), aux_key, bucket_cap, recv_cap)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]
    n_dev = mesh.shape[axis]

    def local_step(leaves, local_rows):
        cols = _leaves_to_cols(leaves, dtypes)
        batch = DeviceBatch(names, cols, local_rows[0])
        part = batch.columns[-1].data.astype(jnp.int32)
        owner = part % np.int32(n_dev)
        stacked, counts = bucketize(batch, owner, n_dev, bucket_cap)
        stacked, counts_recv = exchange(stacked, counts, axis)
        received = reassemble(names, stacked, counts_recv, recv_cap)
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


def _with_part(batch: DeviceBatch, targets: jnp.ndarray) -> DeviceBatch:
    part_col = DeviceColumn(dt.INT32, targets.astype(jnp.int32),
                            batch.row_mask(), None)
    return DeviceBatch(list(batch.names) + ["__part__"],
                       list(batch.columns) + [part_col], batch.num_rows)


def _peer_counts(aug: DeviceBatch, n_dev: int) -> jnp.ndarray:
    """How many of a chip's rows go to each peer: int32[n_dev], computed
    where the rows lie (kernel family ``exch_counts``)."""
    from spark_rapids_tpu.exec import kernel_cache as kc

    def impl(part, num_rows):
        live = jnp.arange(part.shape[0]) < num_rows
        owner = jnp.where(live, part % np.int32(n_dev), n_dev)
        return jnp.zeros((n_dev,), jnp.int32).at[owner].add(
            live.astype(jnp.int32), mode="drop")
    fn = kc.get_kernel(("exch_counts", n_dev, aug.capacity), lambda: impl)
    return fn(aug.columns[-1].data,
              jnp.asarray(aug.num_rows, dtype=jnp.int32))


def _like_on(template: DeviceBatch, cap: int, device) -> DeviceBatch:
    """A batch of no rows with ``template``'s columns, on ``device``: a
    chip that holds nothing still takes part in the collective."""
    cols = []
    for c in template.columns:
        def zeros(a):
            return None if a is None else jax.device_put(
                np.zeros((cap,) + a.shape[1:], a.dtype), device)
        cols.append(DeviceColumn(c.dtype, zeros(c.data), zeros(c.validity),
                                 zeros(c.lengths), zeros(c.elem_validity)))
    return DeviceBatch(template.names, cols, 0)


def _same_shapes(augs: List[DeviceBatch]) -> List[DeviceBatch]:
    """Every chip's batch at one capacity, one width a string column and
    one set of buffers a column, so that the per-device arrays are the
    shards of one global array.  Usually nothing to do."""
    cap = max(b.capacity for b in augs)
    augs = [with_capacity(b, cap) for b in augs]
    n_cols = len(augs[0].columns)
    widths = [max(b.columns[i].max_len for b in augs)
              if augs[0].columns[i].dtype.has_lengths else 0
              for i in range(n_cols)]
    with_ev = [any(b.columns[i].elem_validity is not None for b in augs)
               for i in range(n_cols)]
    out = []
    for b in augs:
        cols = []
        for c, w, ev in zip(b.columns, widths, with_ev):
            if w and c.max_len < w:
                pad = ((0, 0), (0, w - c.max_len))
                c = DeviceColumn(
                    c.dtype, jnp.pad(c.data, pad), c.validity, c.lengths,
                    None if c.elem_validity is None
                    else jnp.pad(c.elem_validity, pad))
            if ev and c.elem_validity is None:
                c = DeviceColumn(c.dtype, c.data, c.validity, c.lengths,
                                 jnp.ones_like(c.data, dtype=jnp.bool_))
            cols.append(c)
        out.append(DeviceBatch(b.names, cols, b.num_rows))
    return out


def exchange_placed(batches: List[Optional[DeviceBatch]],
                    targets: List[Optional[jnp.ndarray]],
                    min_bucket: int = 16
                    ) -> Tuple[List[Optional[DeviceBatch]], dict]:
    """The ICI exchange over rows that already lie on the mesh:
    ``batches[d]`` is what mesh device ``d`` holds (committed there; None
    where it holds nothing) and ``targets[d]`` its per-slot target
    partitions.  Nothing passes through one chip: each chip counts its
    rows a peer where they lie, the ``n_dev x n_dev`` counts are read in
    one transfer (the scalar-prefetch idiom of the module docstring: the
    true counts decide the static shape), the buckets get the power of
    two of the fullest one, the global arrays are assembled from the
    per-device ones and one ``all_to_all`` step runs; it compacts what
    each device received at the tier of the most rows any receives.  A
    smaller receiver's batch is then cut to the tier of its own rows.
    Each exchange adds its send slots, ``n_dev * n_dev * bucket``, to
    the counter ``exchange.ici.sendSlots``.

    The one read is ``exchange.countWait``.  Returns one local
    DeviceBatch per mesh device (None where a device
    received nothing; each carries a trailing ``__part__`` column so the
    reader can sub-split the device's rows into its owned partitions)
    and what was counted: ``rows`` (sender x receiver), ``bucket_rows``,
    ``capacities``."""
    from spark_rapids_tpu.columnar.batch import bucket_rows
    from spark_rapids_tpu.obs import registry as obsreg

    mesh = get_default_mesh()
    n_dev = mesh.shape["shuffle"]
    devices = list(mesh.devices.flat)
    assert len(batches) == len(targets) == n_dev
    template = next(_with_part(b, t) for b, t in zip(batches, targets)
                    if b is not None)
    augs = [_with_part(b, t) if b is not None
            else _like_on(template, template.capacity, devices[d])
            for d, (b, t) in enumerate(zip(batches, targets))]
    per_chip = [_peer_counts(a, n_dev) for a in augs]
    counts = np.stack(read_host(per_chip,
                                "exchange.countWait"))   # sender x receiver
    bucket = 1 << (max(int(counts.max()), min_bucket) - 1).bit_length()
    received = counts.sum(axis=0)
    recv_cap = bucket_rows(max(int(received.max()), 1), min_bucket)
    augs = _same_shapes(augs)
    local_cap = augs[0].capacity
    bucket = min(bucket, local_cap)
    obsreg.get_registry().inc("exchange.ici.sendSlots",
                              n_dev * n_dev * bucket)
    sharding = NamedSharding(mesh, P("shuffle"))

    def glob(arrays):
        shape = (n_dev * local_cap,) + arrays[0].shape[1:]
        # a buffer made outside any kernel may be uncommitted
        arrays = [a if a.devices() == {dev} else jax.device_put(a, dev)
                  for a, dev in zip(arrays, devices)]
        return jax.make_array_from_single_device_arrays(
            shape, sharding, arrays)
    leaves = tuple(
        tuple(glob([leaf[k] for leaf in per_dev])
              for k in range(len(per_dev[0])))
        for per_dev in zip(*(_cols_to_leaves(a.columns) for a in augs)))
    rows = jax.make_array_from_single_device_arrays(
        (n_dev,), sharding,
        [jax.device_put(np.asarray([counts[d].sum()], np.int32), devices[d])
         for d in range(n_dev)])
    first = augs[0]
    aux_key = tuple((c.dtype.name, c.data.shape[1:],
                     c.lengths is not None, c.elem_validity is not None)
                    for c in first.columns) + (local_cap,)
    step = make_exchange_step(mesh, "shuffle", first.names, first.dtypes,
                              aux_key, bucket, recv_cap)
    out_leaves, _ = step(leaves, rows)
    dev_batches: List[Optional[DeviceBatch]] = []
    for d in range(n_dev):
        if int(received[d]) == 0:
            dev_batches.append(None)
            continue
        cols = []
        for leaf, c in zip(out_leaves, first.columns):
            parts = [split_shards(a, n_dev)[d] for a in leaf]
            lengths = parts[2] if c.lengths is not None else None
            ev = parts[-1] if c.elem_validity is not None else None
            cols.append(DeviceColumn(c.dtype, parts[0], parts[1],
                                     lengths, ev))
        got = DeviceBatch(first.names, cols, int(received[d]))
        dev_batches.append(with_capacity(
            got, min(got.capacity,
                     bucket_rows(int(received[d]), min_bucket))))
    return dev_batches, {
        "rows": counts, "bucket_rows": bucket,
        "capacities": [0 if b is None else b.capacity
                       for b in dev_batches]}


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
    total = int(read_host(batch.num_rows, "exchange.shardRowsWait"))
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
    n_out = int(read_host(out_rows, "exchange.ringRowsWait")[0])

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

    The mesh sibling of ``exchange_placed`` (all-to-all) — the
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
