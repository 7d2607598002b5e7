"""HBM-resident columnar batches: the device data currency of the engine.

TPU-native analog of the reference's ``GpuColumnVector``/``ColumnarBatch``
(reference: sql-plugin/src/main/java/.../GpuColumnVector.java:40-576 wrapping a
cudf device column, and Table<->ColumnarBatch conversions at
GpuColumnVector.java:261,293).

Design differences forced by TPU/XLA (see SURVEY.md §7 hard part #1):
cudf tolerates dynamic row counts; XLA compiles per static shape.  So a
``DeviceBatch`` carries

  * ``capacity`` — the padded, power-of-two-bucketed physical row count that
    XLA sees (bounds recompiles to O(log max_rows) shapes per schema), and
  * ``num_rows`` — the true logical row count, held host-side.

Rows in ``[num_rows, capacity)`` are padding: validity False, data zeroed.
Kernels must treat ``row_mask()`` as the ground truth for "row exists".

Strings are Arrow-var-len on host but fixed-width on device: a
``uint8 [capacity, max_len]`` byte matrix plus an ``int32 [capacity]`` length
vector (max_len itself is bucketed).  This is the TPU-friendly layout for the
byte-tensor string kernels (SURVEY.md §7 hard part #3).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

import jax
import jax.numpy as jnp

from spark_rapids_tpu import dtypes as dt


def compact_arrays(keep: "jnp.ndarray", dest: "jnp.ndarray", data,
                   validity, lengths=None, elem_validity=None):
    """Stable-compaction scatter shared by every compact path (filter
    compact, fused-filter value compact, ICI reassemble): row i moves
    to dest[i] when keep[i], rows with dest >= len drop.  Returns
    (data, validity, lengths, elem_validity)."""
    d = jnp.zeros_like(data).at[dest].set(data, mode="drop")
    v = jnp.zeros_like(validity).at[dest].set(validity & keep,
                                              mode="drop")
    ln = None if lengths is None else \
        jnp.zeros_like(lengths).at[dest].set(
            jnp.where(keep, lengths, 0), mode="drop")
    ev = None if elem_validity is None else \
        jnp.zeros_like(elem_validity).at[dest].set(
            elem_validity & keep[:, None], mode="drop")
    return d, v, ln, ev


def bucket_rows(n: int, min_bucket: int = 16) -> int:
    """Smallest capacity tier >= n (>= min_bucket).

    Delegates to the shape-erased ABI's capacity ladder
    (exec/kernel_abi.py): every 2^tierStride-th power-of-two rung
    below 1,048,576 and every power of two from there up under the
    default ABI, the legacy every-pow2 ladder when the ABI is
    disabled.  Batches BORN at tier capacities make the dispatch-time
    pad of kernel_abi.erase a no-op on the hot path."""
    from spark_rapids_tpu.exec import kernel_abi
    return kernel_abi.tier_rows(n, min_bucket)


def _bucket_strlen(n: int) -> int:
    from spark_rapids_tpu.exec import kernel_abi
    return kernel_abi.tier_strlen(n)


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class DeviceColumn:
    """One column: device buffers + validity. Analog of GpuColumnVector.

    STRING and LIST share the var-len layout: a padded 2-D payload
    ``[capacity, max_len]`` + per-row ``lengths``; LIST additionally
    carries ``elem_validity`` (null elements inside a list)."""

    dtype: dt.DType
    data: jnp.ndarray              # [capacity] or [capacity, max_len]
    validity: jnp.ndarray          # bool [capacity]
    lengths: Optional[jnp.ndarray] = None  # int32 [capacity], string/list
    elem_validity: Optional[jnp.ndarray] = None  # bool [cap, max_len], list
    # static value-range hint for integer-backed columns: every VALID
    # value v satisfies -2^(vbits-1) <= v < 2^(vbits-1).  Set by scans
    # from host-known facts (dictionary pages, parquet chunk statistics),
    # bucketed to {8,16,...,56} so jit cache keys stay stable across
    # files; None = unknown.  Lets the aggregate/sort layers encode
    # narrow radix keys or direct-bin group ids (the analog of cudf's
    # hash-vs-sort groupby choice, which this engine makes per compile).
    vbits: Optional[int] = None
    # static no-nulls hint: validity is True at every live row (i < the
    # batch row count).  Set by scans when every page's def levels were
    # all-valid; lets reductions skip validity gathers entirely.
    nonnull: bool = False

    # -- pytree protocol so columns/batches can cross jit boundaries --------
    def tree_flatten(self):
        leaves = [self.data, self.validity]
        if self.lengths is not None:
            leaves.append(self.lengths)
        if self.elem_validity is not None:
            leaves.append(self.elem_validity)
        return tuple(leaves), (self.dtype, self.lengths is not None,
                               self.elem_validity is not None, self.vbits,
                               self.nonnull)

    @classmethod
    def tree_unflatten(cls, aux, children):
        dtype, has_len, has_ev = aux[0], aux[1], aux[2]
        vbits = aux[3] if len(aux) > 3 else None
        nonnull = aux[4] if len(aux) > 4 else False
        it = iter(children)
        data, validity = next(it), next(it)
        lengths = next(it) if has_len else None
        ev = next(it) if has_ev else None
        return cls(dtype, data, validity, lengths, ev, vbits, nonnull)

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    @property
    def max_len(self) -> int:
        assert self.dtype.has_lengths
        return int(self.data.shape[1])

    def nbytes(self) -> int:
        n = self.data.size * self.data.dtype.itemsize + self.validity.size
        if self.lengths is not None:
            n += self.lengths.size * 4
        if self.elem_validity is not None:
            n += self.elem_validity.size
        return int(n)

    def gather(self, indices: jnp.ndarray, valid: jnp.ndarray) -> "DeviceColumn":
        """Row gather; `valid` masks rows whose source index is meaningful.

        vbits<=32 integer-backed 8-byte columns gather through an i32
        view and widen after — an emulated-i64 gather costs 3x an i32
        one on TPU (PERF.md) and the hint guarantees losslessness."""
        if (self.vbits is not None and self.vbits <= 32 and
                self.data.ndim == 1 and
                self.data.dtype.itemsize == 8 and
                jnp.issubdtype(self.data.dtype, jnp.integer)):
            data = jnp.take(self.data.astype(jnp.int32), indices
                            ).astype(self.data.dtype)
        else:
            data = jnp.take(self.data, indices, axis=0)
        validity = jnp.take(self.validity, indices, axis=0) & valid
        lengths = None
        ev = None
        if self.lengths is not None:
            lengths = jnp.where(valid, jnp.take(self.lengths, indices), 0)
            data = jnp.where(valid[:, None], data,
                             jnp.zeros((), data.dtype))
        else:
            # zeros typed like data: a bare 0 would PROMOTE bool columns
            # to int under numpy rules and change the output schema
            data = jnp.where(_bcast(valid, data), data,
                             jnp.zeros((), data.dtype))
        if self.elem_validity is not None:
            ev = jnp.take(self.elem_validity, indices, axis=0) & \
                valid[:, None]
        return DeviceColumn(self.dtype, data, validity, lengths, ev,
                            self.vbits)


def _bcast(mask: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    if like.ndim == 2:
        return mask[:, None]
    return mask


@jax.tree_util.register_pytree_node_class
class DeviceBatch:
    """A batch of device columns with a host-side logical row count."""

    def __init__(self, names: Sequence[str], columns: Sequence[DeviceColumn],
                 num_rows):
        self.names: List[str] = list(names)
        self.columns: List[DeviceColumn] = list(columns)
        # num_rows may be a host int OR a traced jnp scalar (inside jit);
        # host-side code that needs a concrete count calls int(batch.num_rows)
        self.num_rows = int(num_rows) if isinstance(
            num_rows, (int, np.integer)) else num_rows
        if self.columns:
            caps = {c.capacity for c in self.columns}
            assert len(caps) == 1, f"ragged capacities {caps}"
            self._capacity = caps.pop()
        else:
            self._capacity = bucket_rows(int(num_rows))

    # num_rows travels as a leaf so jit does NOT specialize on it — only on
    # capacity/schema (the XLA static-shape bucketing contract)
    def tree_flatten(self):
        # flatten must be purely structural: transforms (lax.cond, vmap)
        # round-trip pytrees through abstract values, and coercing here
        # would call jnp.asarray on an aval.  Coerce only host ints.
        nr = self.num_rows
        if isinstance(nr, (int, np.integer)):
            nr = jnp.asarray(nr, dtype=jnp.int32)
        leaves = tuple(self.columns) + (nr,)
        return leaves, (tuple(self.names), self._capacity)

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, capacity = aux
        *cols, num_rows = children
        b = cls.__new__(cls)
        b.names = list(names)
        b.columns = list(cols)
        b.num_rows = num_rows
        b._capacity = capacity
        return b

    # ----------------------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    @property
    def dtypes(self) -> List[dt.DType]:
        return [c.dtype for c in self.columns]

    def schema_key(self) -> Tuple:
        """Hashable (schema, shape-bucket) key — the XLA compile-cache key."""
        return (tuple(self.names),
                tuple(c.dtype.name for c in self.columns),
                self._capacity,
                tuple(c.max_len if c.dtype.has_lengths else 0
                      for c in self.columns))

    def column(self, name: str) -> DeviceColumn:
        return self.columns[self.names.index(name)]

    def row_mask(self) -> jnp.ndarray:
        return jnp.arange(self._capacity) < self.num_rows

    def nbytes(self) -> int:
        return sum(c.nbytes() for c in self.columns)

    def with_columns(self, names: Sequence[str],
                     columns: Sequence[DeviceColumn]) -> "DeviceBatch":
        return DeviceBatch(names, columns, self.num_rows)

    def select(self, names: Sequence[str]) -> "DeviceBatch":
        return DeviceBatch(names, [self.column(n) for n in names],
                           self.num_rows)

    def __repr__(self) -> str:
        cols = ", ".join(f"{n}:{c.dtype.name}" for n, c in
                         zip(self.names, self.columns))
        return (f"DeviceBatch(rows={int(self.num_rows)}/{self._capacity}, "
                f"[{cols}])")


# ---------------------------------------------------------------------------
# Host (Arrow) <-> device conversion.  Analog of HostColumnarToGpu /
# GpuColumnarToRowExec device<->host copies (reference:
# HostColumnarToGpu.scala:30-291, GpuColumnarToRowExec.scala:38-306).
# ---------------------------------------------------------------------------

def _np_column_from_arrow(arr: pa.ChunkedArray | pa.Array,
                          dtype: dt.DType, capacity: int
                          ) -> Tuple[np.ndarray, np.ndarray,
                                     Optional[np.ndarray],
                                     Optional[np.ndarray]]:
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    n = len(arr)
    validity = np.zeros(capacity, dtype=np.bool_)
    validity[:n] = ~np.asarray(arr.is_null())

    if dtype.is_list:
        # padded [capacity, max_len] element payload + lengths + element
        # validity (the device mirror of Arrow's offsets+values+nulls)
        py = arr.to_pylist()
        lens = [len(v) if v is not None else 0 for v in py]
        max_len = _bucket_strlen(max(lens, default=0))
        el_np = dtype.element.to_np()
        data = np.zeros((capacity, max_len), dtype=el_np)
        ev = np.zeros((capacity, max_len), dtype=np.bool_)
        lengths = np.zeros(capacity, dtype=np.int32)
        for i, v in enumerate(py):
            if v is None:
                continue
            lengths[i] = len(v)
            for j, x in enumerate(v):
                if x is None:
                    continue  # null element: ev stays False, data stays 0
                ev[i, j] = True
                data[i, j] = x
        return data, validity, lengths, ev

    if dtype.is_string:
        py = arr.to_pylist()
        blens = [len(s.encode("utf-8")) if s is not None else 0 for s in py]
        max_len = _bucket_strlen(max(blens, default=0))
        data = np.zeros((capacity, max_len), dtype=np.uint8)
        lengths = np.zeros(capacity, dtype=np.int32)
        for i, s in enumerate(py):
            if s is None:
                continue
            b = s.encode("utf-8")
            lengths[i] = len(b)
            if b:
                data[i, :len(b)] = np.frombuffer(b, dtype=np.uint8)
        return data, validity, lengths, None

    np_dtype = dtype.to_np()
    data = np.zeros(capacity, dtype=np_dtype)
    if pa.types.is_timestamp(arr.type):
        arr = arr.cast(pa.timestamp("us"))
        vals = arr.to_numpy(zero_copy_only=False)
        ints = vals.astype("datetime64[us]").astype(np.int64)
        ints = np.where(validity[:n], ints, 0)
        data[:n] = ints
    elif pa.types.is_date32(arr.type):
        vals = arr.to_numpy(zero_copy_only=False)
        ints = vals.astype("datetime64[D]").astype(np.int64).astype(np.int32)
        ints = np.where(validity[:n], ints, 0)
        data[:n] = ints
    else:
        vals = arr.fill_null(_zero_value(dtype)).to_numpy(zero_copy_only=False)
        data[:n] = vals.astype(np_dtype, copy=False)
    return data, validity, None, None


def _zero_value(dtype: dt.DType):
    if dtype.is_bool:
        return False
    if dtype.is_floating:
        return 0.0
    return 0


def from_arrow(table: pa.Table, min_bucket: int = 16,
               capacity: Optional[int] = None) -> DeviceBatch:
    """Upload an Arrow table into a padded DeviceBatch."""
    n = table.num_rows
    cap = capacity or bucket_rows(n, min_bucket)
    names, cols = [], []
    for field_, col in zip(table.schema, table.columns):
        dtype = dt.from_arrow(field_.type)
        if dtype is None:
            raise TypeError(f"unsupported Arrow type {field_.type} "
                            f"for column {field_.name}")
        if dtype == dt.NULL:
            dtype = dt.BOOL  # void columns materialize as all-null bool
        data, validity, lengths, ev = _np_column_from_arrow(col, dtype, cap)
        names.append(field_.name)
        vb, nn = _upload_hints(dtype, data, validity, n)
        cols.append(DeviceColumn(
            dtype,
            jnp.asarray(data),
            jnp.asarray(validity),
            jnp.asarray(lengths) if lengths is not None else None,
            jnp.asarray(ev) if ev is not None else None,
            vbits=vb, nonnull=nn))
    return DeviceBatch(names, cols, n)


_VBIT_BUCKETS = (8, 16, 24, 32, 40, 48, 56)


def bits_for_range(lo: int, hi: int):
    """Smallest vbits bucket whose signed range covers [lo, hi]
    (None when none does); the shared bucket table keeps jit cache
    keys stable across files/uploads with nearby ranges."""
    for b in _VBIT_BUCKETS:
        if -(1 << (b - 1)) <= lo and hi < (1 << (b - 1)):
            return b
    return None


def _upload_hints(dtype: dt.DType, data: np.ndarray,
                  validity: np.ndarray, n: int):
    """Static hints for an uploaded column: one O(n) host pass over the
    numpy buffers bounds the valid values (see DeviceColumn.vbits) —
    negligible next to the upload itself, and it unlocks the narrow
    sort/aggregate/gather fast paths for in-memory DataFrames the same
    way parquet statistics do for scans."""
    if n == 0:
        return None, True
    live_valid = validity[:n]
    nn = bool(live_valid.all())
    if (dtype.is_string or dtype.is_bool or dtype.is_list or
            not np.issubdtype(np.asarray(data).dtype, np.integer)):
        return None, nn
    vals = data[:n][live_valid] if not nn else data[:n]
    from spark_rapids_tpu.exec import kernel_abi
    if vals.size == 0:
        return kernel_abi.bucket_vbits(_VBIT_BUCKETS[0]), nn
    # the ABI re-buckets upload-derived hints to its coarse table so
    # data-dependent value ranges stop minting per-range programs
    return kernel_abi.bucket_vbits(
        bits_for_range(int(vals.min()), int(vals.max()))), nn


def _pack_wire_key(d: jnp.ndarray) -> str:
    if d.dtype == jnp.bool_:
        return "uint8"
    return str(d.dtype)


def _pack_batch_impl(batch: DeviceBatch):
    """Serialize a whole DeviceBatch (num_rows + every column's
    data/validity/lengths/elem_validity at FULL capacity) into ONE
    device buffer per wire dtype — no cross-width bitcasts (the TPU X64
    rewriter rejects 64-bit bitcast-convert in larger graphs)."""
    bufs: Dict[str, List[jnp.ndarray]] = {}

    def put(key: str, arr: jnp.ndarray) -> None:
        bufs.setdefault(key, []).append(arr.reshape(-1))

    put("int32", jnp.asarray(batch.num_rows,
                             dtype=jnp.int32).reshape(1))
    for c in batch.columns:
        d = c.data
        put(_pack_wire_key(d),
            d.astype(jnp.uint8) if d.dtype == jnp.bool_ else d)
        put("uint8", c.validity.astype(jnp.uint8))
        if c.lengths is not None:
            put("int32", c.lengths.astype(jnp.int32))
        if c.elem_validity is not None:
            put("uint8", c.elem_validity.astype(jnp.uint8))
    return {k: (v[0] if len(v) == 1 else jnp.concatenate(v))
            for k, v in bufs.items()}


def _dispatch_pack(batch: DeviceBatch) -> jnp.ndarray:
    """Dispatch (async) the pack kernel for one batch; no host read.

    Pack is a pure column-container kernel (names never reach the
    emitted HLO), so it keys on the ABI's positional layout and runs
    over the name/hint-erased batch — any two batches with one
    physical layout share one program.  pad=False: the host download
    epilogue reads the ORIGINAL buffer shapes back out of the packed
    buffer, so dispatch-time capacity padding must not apply here."""
    from spark_rapids_tpu.exec import kernel_abi, kernel_cache as kc
    key = ("pack_batch", kernel_abi.erased_key(batch))
    fn = kc.get_kernel(key, lambda: _pack_batch_impl)
    # strip_hints: pack never reads vbits/nonnull, so even bucketed
    # hints on the treedef would re-trace an identical program
    return fn(kernel_abi.erase(batch, pad=False, strip_hints=True))


def _download_batch(batch: DeviceBatch, packed: Optional[jnp.ndarray]
                    = None):
    """ONE device->host transfer for the whole batch.

    The terminal collect packs everything device-side and reads one
    buffer, so a query pays one read-back instead of one per column
    (what a read-back costs on the attached chip is not measured).

    Returns (num_rows, [(data, validity, lengths, ev), ...]) as numpy
    arrays at full capacity."""
    if packed is None:
        packed = _dispatch_pack(batch)
    # one read of the (few) buffers, their transfers overlapped
    host = dict(zip(packed, read_host(list(packed.values()),
                                      "collect.downloadWait")))
    pos = {k: 0 for k in host}

    def take(key: str, count: int):
        off = pos[key]
        pos[key] = off + count
        return host[key][off:off + count]

    n = int(take("int32", 1)[0])
    cap = batch.capacity
    cols = []
    for c in batch.columns:
        count = int(np.prod(c.data.shape))
        data = take(_pack_wire_key(c.data), count).reshape(c.data.shape)
        if c.data.dtype == jnp.bool_:
            data = data.astype(bool)
        validity = take("uint8", cap).astype(bool)
        lengths = ev = None
        if c.lengths is not None:
            lengths = take("int32", cap)
        if c.elem_validity is not None:
            cnt = int(np.prod(c.elem_validity.shape))
            ev = take("uint8", cnt).reshape(
                c.elem_validity.shape).astype(bool)
        cols.append((data, validity, lengths, ev))
    return n, cols


def _strings_to_arrow(data: np.ndarray, lengths: np.ndarray,
                      validity: np.ndarray, n: int) -> pa.Array:
    """Vectorized padded-byte-matrix -> Arrow utf8 (no per-row Python)."""
    data, lengths, validity = data[:n], lengths[:n], validity[:n]
    lens = np.where(validity, lengths, 0).astype(np.int64)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    if offsets[-1] > np.iinfo(np.int32).max:
        # >2 GiB of string payload overflows utf8's int32 offsets;
        # build row-by-row into a (chunked-friendly) python list
        py = [bytes(data[i, :lens[i]]).decode("utf-8", errors="replace")
              if validity[i] else None for i in range(n)]
        return pa.array(py, type=pa.string())
    mask = np.arange(data.shape[1])[None, :] < lens[:, None]
    flat = np.ascontiguousarray(data)[mask]
    offsets = offsets.astype(np.int32)
    null_bitmap = pa.py_buffer(
        np.packbits(validity, bitorder="little").tobytes())
    return pa.Array.from_buffers(
        pa.utf8(), n,
        [None if validity.all() else null_bitmap,
         pa.py_buffer(offsets.tobytes()), pa.py_buffer(flat.tobytes())])


# Fixed compaction tiers: a batch with a huge capacity but few rows
# compacts to the smallest tier >= its row count.  Tiers (not exact
# buckets) keep the candidate kernel set tiny so every compact/pack
# program can be dispatched BEFORE the first device->host download
# (what loading an executable after it costs on the attached chip is
# not measured).
_DL_TIERS = (4096, 65536, 1048576)
_WARMED_TIERS: set = set()


def _dl_tier(n: int, capacity: int):
    for t in _DL_TIERS:
        if n <= t and capacity > 4 * t:
            return t
    return None


def _compact_kernels(b: DeviceBatch):
    """(tier -> (slice kernel, pack kernel)) for one batch, loading every
    candidate executable now (pre-download).  Keys and dispatch are
    schema-erased like pack (the slice gathers by position only); the
    caller restamps real names on the compacted batch."""
    from spark_rapids_tpu.exec import kernel_abi, kernel_cache as kc
    out = {}
    for t in _DL_TIERS:
        if b.capacity > 4 * t:
            key = ("dl_compact", kernel_abi.erased_key(b), t)
            out[t] = kc.get_kernel(key, lambda: _slice_head,
                                   static_argnames=("cap",))
    return out


def _run_compact(b: DeviceBatch, fn, t: int) -> DeviceBatch:
    """One erased dl_compact dispatch + host-side name restamp."""
    from spark_rapids_tpu.exec import kernel_abi
    nb = fn(kernel_abi.erase(b, pad=False), cap=t)
    return DeviceBatch(b.names, nb.columns, nb.num_rows)


def _fetched(value) -> bool:
    """Whether jax has already copied ``value`` to the host (it keeps
    the copy, so reading it again moves nothing)."""
    return getattr(value, "_npy_value", None) is not None


def read_host(values, site: str):
    """The host's blocking read of device values.  ``values`` is what
    one site reads at once: one value, or a list of them; the host
    values come back in the same form (numpy for a device array).

    The engine's device-to-host reads all pass here, so each is counted
    where it happens.  A call that copies counts once in
    ``device.reads`` and once in ``device.reads.<site>`` and, with
    tracing on, copies inside a span named ``site`` (cat
    ``device.read``, args ``chips``: the device ids of the values, and
    ``values``: how many were copied).  A value the host already knows
    (a host number, or a device array jax has copied before) is no
    read.  A site is named ``<layer>.<what>Wait``.  The copy is
    ``jax.device_get``, an explicit transfer, so a run under
    ``jax.transfer_guard_device_to_host("log")`` logs only the copies
    that bypass this helper."""
    one = not isinstance(values, (list, tuple))
    vals = [values] if one else list(values)
    at = [i for i, v in enumerate(vals)
          if isinstance(v, jax.Array) and not _fetched(v)]
    if at:
        from spark_rapids_tpu.obs import registry as obsreg
        from spark_rapids_tpu.obs import trace as obstrace
        obsreg.get_registry().inc_many(("device.reads", 1),
                                       (f"device.reads.{site}", 1))
        dev = [vals[i] for i in at]
        if obstrace.is_enabled():
            chips = sorted({d.id for v in dev for d in v.devices()})
            with obstrace.span(site, cat="device.read",
                               args={"chips": chips, "values": len(dev)}):
                got = jax.device_get(dev)
        else:
            got = jax.device_get(dev)
        for i, g in zip(at, got):
            vals[i] = g
    vals = [np.asarray(v) if isinstance(v, jax.Array) else v for v in vals]
    return vals[0] if one else vals


def read_row_counts(batches: Sequence[DeviceBatch],
                    site: str) -> List[int]:
    """Row counts of ``batches`` on the host.  Counts that are still
    device scalars come back in ONE transfer (stacked, copied once),
    however many batches there are; where every count is host-known
    nothing is read.  The batches are left as they are.

    The copy blocks until every program that feeds a count has run, so
    it belongs where the host has nothing left to enqueue (the terminal
    collect, a pipeline breaker's last input).  ``site`` names that
    read (:func:`read_host`)."""
    counts = [b.num_rows for b in batches]
    traced = [i for i, n in enumerate(counts)
              if not isinstance(n, (int, np.integer))]
    if traced:
        # distributed (ICI) readers hand out batches committed to
        # different mesh devices; colocate the count scalars before the
        # fused stack+read
        scalars = [jnp.asarray(counts[i], dtype=jnp.int32)
                   for i in traced]
        devs = {d for s in scalars for d in s.devices()}
        if len(devs) > 1:
            tgt = sorted(devs, key=lambda d: d.id)[0]
            scalars = [jax.device_put(s, tgt) for s in scalars]
        got = read_host(jnp.stack(scalars), site)
        for i, n in zip(traced, got):
            counts[i] = n
    return [int(n) for n in counts]


def _compact_for_download(batches: Sequence[DeviceBatch]):
    """Re-bucket batches whose capacity vastly exceeds their row count
    (e.g. an aggregate output that inherited a multi-million-row concat
    capacity) so the terminal download moves rows, not padding.

    Returns (batches, packed_or_None per batch).  EVERY pack/compact
    kernel — including the plain full-capacity pack of batches that end
    up uncompacted — is built and dispatched BEFORE the single fused
    row-count read, so nothing compiles or loads after the first
    (dispatch-degrading) download.

    That read is the first point at which the host blocks on the
    device: everything dispatched so far has to finish before the
    counts arrive: the read ``collect.deviceWait`` (:func:`read_host`),
    a name round a wait that is there anyway, never a sync of its own."""
    candidates = {}
    full_packed = []
    for b in batches:
        if any(b.capacity > 4 * t for t in _DL_TIERS):
            candidates[id(b)] = _compact_kernels(b)
            # warm the slice+pack kernels for each possible compacted
            # schema ONCE per (schema, tier) per process — mid-query
            # to_arrow callers (shuffle slices) must not re-pay the
            # discarded warm-up compute on every call
            from spark_rapids_tpu.exec import kernel_abi
            for t, fn in candidates[id(b)].items():
                wkey = (kernel_abi.erased_key(b), t)
                if wkey not in _WARMED_TIERS:
                    _WARMED_TIERS.add(wkey)
                    _dispatch_pack(_run_compact(b, fn, t))
        # full-capacity pack, reused if this batch stays uncompacted
        full_packed.append(_dispatch_pack(b))
    out, out_packed = [], []
    for b, fp, n in zip(batches, full_packed,
                        read_row_counts(batches, "collect.deviceWait")):
        b.num_rows = n
        tier = _dl_tier(n, b.capacity)
        if tier is not None and id(b) in candidates and \
                tier in candidates[id(b)]:
            nb = _run_compact(b, candidates[id(b)][tier], tier)
            nb.num_rows = n
            out.append(nb)
            out_packed.append(_dispatch_pack(nb))
        else:
            out.append(b)
            out_packed.append(fp)
    return out, out_packed


def _slice_head(batch: DeviceBatch, cap: int) -> DeviceBatch:
    idx = jnp.arange(cap)
    valid = idx < jnp.asarray(batch.num_rows, dtype=jnp.int32)
    idx = jnp.clip(idx, 0, batch.capacity - 1)
    cols = [c.gather(idx, valid) for c in batch.columns]
    return DeviceBatch(batch.names, cols, batch.num_rows)


def to_arrow_all(batches: Sequence[DeviceBatch]) -> List[pa.Table]:
    """Convert many batches: ALL pack kernels dispatch before the first
    download, so every device op runs on the fast pre-download path.

    Two spans split the terminal collect: ``collect.deviceWait`` (the
    first blocking read-back, which ends when the device has done the
    query's work) and ``collect.download`` (the compacted batches'
    pack, the host copies and the Arrow build)."""
    from spark_rapids_tpu.obs import trace as obstrace
    batches, packed = _compact_for_download(batches)
    with obstrace.span("collect.download", cat="query"):
        return [to_arrow(b, p) for b, p in zip(batches, packed)]


def to_arrow(batch: DeviceBatch,
             packed: Optional[jnp.ndarray] = None) -> pa.Table:
    """Download a DeviceBatch back to an Arrow table (strips padding),
    via a single packed device->host transfer."""
    if packed is None:
        (batch,), (packed,) = _compact_for_download([batch])
    n, host_cols = _download_batch(batch, packed)
    arrays, fields = [], []
    for name, col, (data, validity, lengths, ev) in zip(
            batch.names, batch.columns, host_cols):
        validity = validity[:n]
        mask = ~validity
        if col.dtype.is_string:
            arr = _strings_to_arrow(data, lengths, validity, n)
        elif col.dtype.is_list:
            data = data[:n]
            lengths = lengths[:n]
            if ev is None:
                ev = np.ones(data.shape, dtype=bool)
            else:
                ev = ev[:n]
            py = []
            for i in range(n):
                if not validity[i]:
                    py.append(None)
                else:
                    py.append([data[i, j].item() if ev[i, j] else None
                               for j in range(lengths[i])])
            arr = pa.array(py, type=col.dtype.to_arrow())
        elif col.dtype.id == dt.TypeId.TIMESTAMP_US:
            ints = data[:n].astype("datetime64[us]")
            arr = pa.array(ints, type=pa.timestamp("us", tz="UTC"),
                           mask=mask)
        elif col.dtype.id == dt.TypeId.DATE32:
            days = data[:n].astype("datetime64[D]")
            arr = pa.array(days, type=pa.date32(), mask=mask)
        else:
            arr = pa.array(data[:n], mask=mask)
        arrays.append(arr)
        fields.append(pa.field(name, arr.type))
    return pa.Table.from_arrays(arrays, schema=pa.schema(fields))


def _combined_hints(cols: Sequence[DeviceColumn]):
    """Hint union for concatenated columns: the widest vbits if every
    input carries one, nonnull only if every input is."""
    vbs = [c.vbits for c in cols]
    vb = max(vbs) if all(v is not None for v in vbs) else None
    return vb, all(c.nonnull for c in cols)


def concat_batches(batches: Sequence[DeviceBatch],
                   min_bucket: int = 16) -> DeviceBatch:
    """Device-side concatenation (analog of Table.concatenate used by
    GpuCoalesceBatches, reference: GpuCoalesceBatches.scala:40-711).

    Batches whose ``num_rows`` is a device scalar (output of a jitted
    kernel that hasn't been read back) concatenate WITHOUT any
    device->host sync — an ``int(num_rows)`` here would serialize the
    whole async pipeline per batch (the r2 bench's 8.4 s hot spot)."""
    if any(not isinstance(b.num_rows, (int, np.integer))
           for b in batches):
        return _concat_batches_nosync(batches, min_bucket)
    batches = [b for b in batches if int(b.num_rows) > 0] or list(batches[:1])
    if len(batches) == 1:
        return batches[0]
    # distributed readers (shuffle/ici.py) hand out batches committed to
    # their owning mesh device; concatenating across partitions must first
    # colocate them or XLA rejects the mixed-device concat
    devs = set()
    for b in batches:
        if b.columns:
            devs |= set(b.columns[0].data.devices())
    if len(devs) > 1:
        target = sorted(devs, key=lambda d: d.id)[0]
        batches = [jax.device_put(b, target) for b in batches]
    total = sum(int(b.num_rows) for b in batches)
    cap = bucket_rows(total, min_bucket)
    names = batches[0].names
    out_cols: List[DeviceColumn] = []
    for ci, name in enumerate(names):
        dtype = batches[0].columns[ci].dtype
        if dtype.has_lengths:
            max_len = max(b.columns[ci].max_len for b in batches)
            has_ev = any(b.columns[ci].elem_validity is not None
                         for b in batches)
            datas, vals, lens, evs = [], [], [], []
            for b in batches:
                c = b.columns[ci]
                nb = int(b.num_rows)
                d = c.data[:nb]
                if c.max_len < max_len:
                    d = jnp.pad(d, ((0, 0), (0, max_len - c.max_len)))
                datas.append(d)
                vals.append(c.validity[:nb])
                lens.append(c.lengths[:nb])
                if has_ev:
                    e = c.elem_validity if c.elem_validity is not None \
                        else jnp.ones((c.capacity, c.max_len),
                                      dtype=jnp.bool_)
                    e = e[:nb]
                    if c.max_len < max_len:
                        e = jnp.pad(e, ((0, 0), (0, max_len - c.max_len)))
                    evs.append(e)
            data = jnp.concatenate(datas, axis=0)
            data = jnp.pad(data, ((0, cap - total), (0, 0)))
            validity = jnp.pad(jnp.concatenate(vals), (0, cap - total))
            lengths = jnp.pad(jnp.concatenate(lens), (0, cap - total))
            ev = None
            if has_ev:
                ev = jnp.pad(jnp.concatenate(evs, axis=0),
                             ((0, cap - total), (0, 0)))
            out_cols.append(DeviceColumn(dtype, data, validity, lengths,
                                         ev))
        else:
            vb, nn = _combined_hints([b.columns[ci] for b in batches])
            data = jnp.concatenate([b.columns[ci].data[:int(b.num_rows)]
                                    for b in batches])
            data = jnp.pad(data, (0, cap - total))
            validity = jnp.pad(
                jnp.concatenate([b.columns[ci].validity[:int(b.num_rows)]
                                 for b in batches]), (0, cap - total))
            out_cols.append(DeviceColumn(dtype, data, validity, None,
                                         vbits=vb, nonnull=nn))
    return DeviceBatch(names, out_cols, total)


def _concat_batches_nosync(batches: Sequence[DeviceBatch],
                           min_bucket: int = 16) -> DeviceBatch:
    """Concatenate without reading any device value: output capacity is
    the (static) bucketed sum of input capacities, valid rows compact to
    the front with one stable argsort, and the result's num_rows is the
    traced sum — so the async dispatch stream never blocks."""
    # host-known empties can still be dropped for free
    kept = [b for b in batches
            if not (isinstance(b.num_rows, (int, np.integer))
                    and int(b.num_rows) == 0)]
    batches = kept or list(batches[:1])
    if len(batches) == 1:
        return batches[0]
    devs = set()
    for b in batches:
        if b.columns:
            devs |= set(b.columns[0].data.devices())
    if len(devs) > 1:
        target = sorted(devs, key=lambda d: d.id)[0]
        batches = [jax.device_put(b, target) for b in batches]

    from spark_rapids_tpu.exec import kernel_abi, kernel_cache as kc
    cap = bucket_rows(sum(b.capacity for b in batches), min_bucket)
    key = ("concat", cap,
           tuple(kernel_abi.erased_key(b) for b in batches))
    fn = kc.get_kernel(key, lambda: _concat_nosync_impl,
                       static_argnames=("cap",))
    # schema-erased dispatch (concat is positional); restamp the real
    # names host-side — callers read the output's names
    out = fn(tuple(kernel_abi.erase(b, pad=False) for b in batches),
             cap=cap)
    return DeviceBatch(batches[0].names, out.columns, out.num_rows)


def _concat_nosync_impl(batches, cap: int) -> DeviceBatch:
    exists = jnp.concatenate([b.row_mask() for b in batches])
    exists = jnp.pad(exists, (0, cap - exists.shape[0]))
    # valid rows to the front WITHOUT a sort (XLA sort compiles are
    # minutes-scale): scatter an identity map at cumsum ranks, then
    # gather through it
    dest = jnp.where(exists, jnp.cumsum(exists.astype(jnp.int32)) - 1,
                     cap)
    src = jnp.arange(cap, dtype=jnp.int32)
    order = jnp.zeros((cap,), dtype=jnp.int32).at[dest].set(
        src, mode="drop")
    sorted_exists = jnp.take(exists, order) & \
        (jnp.arange(cap) < jnp.sum(exists.astype(jnp.int32)))
    names = batches[0].names
    out_cols: List[DeviceColumn] = []
    for ci in range(len(names)):
        dtype = batches[0].columns[ci].dtype
        if dtype.has_lengths:
            max_len = max(b.columns[ci].max_len for b in batches)
            has_ev = any(b.columns[ci].elem_validity is not None
                         for b in batches)
            datas, vals, lens, evs = [], [], [], []
            for b in batches:
                c = b.columns[ci]
                d = c.data
                if c.max_len < max_len:
                    d = jnp.pad(d, ((0, 0), (0, max_len - c.max_len)))
                datas.append(d)
                vals.append(c.validity)
                lens.append(c.lengths)
                if has_ev:
                    e = c.elem_validity if c.elem_validity is not None \
                        else jnp.ones((c.capacity, c.max_len),
                                      dtype=jnp.bool_)
                    if c.max_len < max_len:
                        e = jnp.pad(e, ((0, 0), (0, max_len - c.max_len)))
                    evs.append(e)
            col = DeviceColumn(
                dtype,
                jnp.pad(jnp.concatenate(datas, axis=0),
                        ((0, cap - sum(d.shape[0] for d in datas)),
                         (0, 0))),
                jnp.pad(jnp.concatenate(vals),
                        (0, cap - sum(v.shape[0] for v in vals))),
                jnp.pad(jnp.concatenate(lens),
                        (0, cap - sum(x.shape[0] for x in lens))),
                jnp.pad(jnp.concatenate(evs, axis=0),
                        ((0, cap - sum(e.shape[0] for e in evs)),
                         (0, 0))) if has_ev else None)
        else:
            data = jnp.concatenate([b.columns[ci].data for b in batches])
            col = DeviceColumn(
                dtype,
                jnp.pad(data, (0, cap - data.shape[0])),
                jnp.pad(jnp.concatenate([b.columns[ci].validity
                                         for b in batches]),
                        (0, cap - data.shape[0])),
                None)
        # gather() zeroes data/lengths/ev where the mask is False, so
        # the padding-rows-are-zeroed batch contract holds as-is
        gcol = col.gather(order, sorted_exists)
        if not dtype.has_lengths:
            # the compaction maps live outputs to live inputs, so the
            # inputs' hints survive (gather() alone can't know that)
            vb, nn = _combined_hints([b.columns[ci] for b in batches])
            gcol = replace(gcol, vbits=vb, nonnull=nn)
        out_cols.append(gcol)
    total = sum(jnp.asarray(b.num_rows, dtype=jnp.int32)
                for b in batches)
    return DeviceBatch(names, out_cols, total)
