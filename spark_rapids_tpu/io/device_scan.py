"""TPU Parquet scan exec: the file scan whose decode runs on device.

Analog of ``GpuFileSourceScanExec`` + ``Table.readParquet`` (reference:
GpuFileSourceScanExec.scala:372, GpuParquetScan.scala:1022): the reader
uploads packed page bytes and decodes in HBM (io/device_parquet.py) instead
of decoding on host and uploading decoded columns.  One plan partition per
file (PERFILE); batches are emitted per row group — downstream
TpuCoalesceBatchesExec re-sizes them to the CoalesceGoal exactly as the
reference inserts GpuCoalesceBatches after scans.

Hive partition-value columns are appended as device constant columns
(ColumnarPartitionReaderWithPartitionValues analog)."""

from __future__ import annotations

from typing import Iterator, List, Optional

import numpy as np

import jax.numpy as jnp

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu import dtypes as dt
from spark_rapids_tpu.columnar.batch import (DeviceBatch, DeviceColumn,
                                             _bucket_strlen)
from spark_rapids_tpu.exec.base import TpuExec, timed
from spark_rapids_tpu.io import device_parquet as devpq
from spark_rapids_tpu.mem.device import tpu_semaphore
from spark_rapids_tpu.obs import registry as _obsreg
from spark_rapids_tpu.plan.logical import FileScan, Schema


def _const_column(dtype: dt.DType, raw: Optional[str], cap: int,
                  n_rows: int) -> DeviceColumn:
    """Device constant column for one partition value."""
    row_valid = jnp.arange(cap) < n_rows
    if raw is None:
        if dtype.is_string:
            return DeviceColumn(dtype, jnp.zeros((cap, 1), dtype=jnp.uint8),
                                jnp.zeros((cap,), dtype=bool),
                                jnp.zeros((cap,), dtype=jnp.int32))
        return DeviceColumn(dtype,
                            jnp.zeros((cap,), dtype=dtype.to_np()),
                            jnp.zeros((cap,), dtype=bool))
    if dtype.is_string:
        b = raw.encode("utf-8")
        ml = _bucket_strlen(len(b))
        row = np.zeros((ml,), dtype=np.uint8)
        row[:len(b)] = np.frombuffer(b, dtype=np.uint8)
        data = jnp.broadcast_to(jnp.asarray(row), (cap, ml))
        lens = jnp.where(row_valid, np.int32(len(b)), 0)
        return DeviceColumn(dtype, data, row_valid, lens)
    val = np.asarray(raw, dtype=dtype.to_np()) if dtype.to_np().kind != "i" \
        else np.asarray(int(raw), dtype=dtype.to_np())
    data = jnp.where(row_valid, jnp.asarray(val),
                     jnp.zeros((), dtype=dtype.to_np()))
    return DeviceColumn(dtype, data, row_valid)


def _group_label(srcs) -> str:
    """Short source id for one coalesced scan group — the prefetcher
    stamps it into prefetch/stall span args so a trace names WHICH
    file/row-group the consumer starved on."""
    import os as _os
    if not srcs:
        return ""
    path, rg = srcs[0]
    label = f"{_os.path.basename(str(path))}#rg{rg}"
    if len(srcs) > 1:
        label += f"+{len(srcs) - 1}"
    return label


class TpuParquetScanExec(TpuExec):
    """Device-decoding parquet scan (is_tpu — yields DeviceBatch)."""

    fmt = "parquet"

    def __init__(self, scan: FileScan, conf):
        super().__init__()
        self.scan = scan
        self.conf = conf
        self.columns = scan.options.get("columns")
        self._schema = scan.schema if not self.columns else Schema(
            [scan.schema.field(c) for c in self.columns])
        self.part_fields = dict(scan.options.get("part_fields") or [])
        # cleared by the planner when the plan reads input_file_name()
        # (the reference's coalescing reader bails out the same way:
        # GpuParquetScan.scala canUseCoalesceFilesReader)
        self.allow_fused = True
        self.metrics.extra["fallbackColumns"] = 0

    @property
    def schema(self) -> Schema:
        return self._schema

    def _file_part(self, file_index: int) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.exec.context import set_input_file
        path = self.scan.paths[file_index]
        try:
            for b in self._file_part_inner(file_index):
                # set right before the yield so the consumer evaluates
                # input_file_name() against THIS batch's file even when
                # two scans are drained interleaved
                set_input_file(path)
                yield b
        finally:
            set_input_file("")

    def _file_part_inner(self, file_index: int) -> Iterator[DeviceBatch]:
        path = self.scan.paths[file_index]
        pv_list = self.scan.options.get("part_values") or []
        pv = pv_list[file_index] if file_index < len(pv_list) else {}
        wanted = [f.name for f in self._schema.fields]
        part_cols = [c for c in wanted if c in self.part_fields]
        file_cols = [c for c in wanted if c not in self.part_fields]
        file_schema = Schema([self._schema.field(c) for c in file_cols])
        fctx = self._open(path)  # one open/footer parse per file
        for rg in range(self._num_chunks(fctx)):
            with tpu_semaphore(self.metrics):
                with timed(self.metrics, "scan.decode"):
                    batch, fallbacks = self._decode_chunk(
                        fctx, rg, file_schema, file_cols)
                self.metrics.add_extra("fallbackColumns",
                                       len(fallbacks))
                cap = batch.capacity
                names = list(batch.names)
                cols = list(batch.columns)
                for c in part_cols:
                    d = self.part_fields[c]
                    names.append(c)
                    cols.append(_const_column(d, pv.get(c), cap,
                                              int(batch.num_rows)))
                # restore requested column order
                order = [names.index(c) for c in wanted]
                out = DeviceBatch([names[i] for i in order],
                                  [cols[i] for i in order],
                                  batch.num_rows)
                self.metrics.num_output_rows += int(out.num_rows)
                self.metrics.add_batches()
                yield out

    def _open(self, path: str):
        from spark_rapids_tpu.io import scan_cache as sc
        return path, sc.open_source(path, metrics=self.metrics)

    def _num_chunks(self, fctx) -> int:
        return fctx[1].metadata.num_row_groups

    def _decode_chunk(self, fctx, idx: int, file_schema: Schema,
                      file_cols):
        from spark_rapids_tpu.io import scan_cache as sc
        path, pf = fctx
        return devpq.decode_row_group(
            path, idx, file_schema, columns=file_cols,
            parquet_file=pf, source_key=sc.handle_key(pf, path),
            metrics=self.metrics)

    def execute(self) -> List[Iterator[DeviceBatch]]:
        if (self.fmt == "parquet" and self.allow_fused and
                self.conf.get(cfg.PARQUET_FUSED_DECODE)):
            return self._execute_fused()
        from spark_rapids_tpu.io.readers import scan_file_indices
        return [self._file_part(i) for i in scan_file_indices(self.scan)]

    # -- fused coalescing reader (one XLA program per batch) ---------------
    def _fused_groups(self):
        """Greedy grouping of (file, row-group) pairs: same partition
        values, bounded by reader batchSizeRows/Bytes (the coalescing
        goal; reference: MultiFileParquetPartitionReader's
        maxReadBatchSizeRows/Bytes).

        Files open only transiently here (footer metadata) and lazily
        again inside each group's iterator — a scan over thousands of
        files must not hold thousands of descriptors for the query."""
        from spark_rapids_tpu.io import scan_cache as sc
        from spark_rapids_tpu.io.readers import scan_file_indices
        max_rows = int(self.conf.get(cfg.MAX_READER_BATCH_SIZE_ROWS))
        max_bytes = int(self.conf.get(cfg.MAX_READER_BATCH_SIZE_BYTES))
        pv_list = self.scan.options.get("part_values") or []
        groups = []
        cur, cur_rows, cur_bytes, cur_pv = [], 0, 0, None
        # a file_subset restriction (incremental delta scans) excludes
        # files HERE, before any footer opens: a restricted scan never
        # stats, walks, or uploads a byte of an excluded file
        for fi in scan_file_indices(self.scan):
            path = self.scan.paths[fi]
            pf = sc.open_source(path, metrics=self.metrics)
            pv = pv_list[fi] if fi < len(pv_list) else {}
            pv_key = tuple(sorted(pv.items()))
            md = pf.metadata
            n_rgs = md.num_row_groups
            sizes = [(md.row_group(rg).num_rows,
                      md.row_group(rg).total_byte_size)
                     for rg in range(n_rgs)]
            pf.close()
            for rg in range(n_rgs):
                rows, nbytes = sizes[rg]
                if cur and (pv_key != cur_pv or
                            cur_rows + rows > max_rows or
                            cur_bytes + nbytes > max_bytes):
                    groups.append((cur, dict(cur_pv)))
                    cur, cur_rows, cur_bytes = [], 0, 0
                cur_pv = pv_key
                cur.append((path, rg))
                cur_rows += rows
                cur_bytes += nbytes
        if cur:
            groups.append((cur, dict(cur_pv)))
        return groups

    def _execute_fused(self) -> List[Iterator[DeviceBatch]]:
        from spark_rapids_tpu.exec.scans import ScanPrefetcher
        from spark_rapids_tpu.io import parquet_fused as pqf
        from spark_rapids_tpu.io import scan_cache as sc

        wanted = [f.name for f in self._schema.fields]
        part_cols = [c for c in wanted if c in self.part_fields]
        file_cols = [c for c in wanted if c not in self.part_fields]
        file_schema = Schema([self._schema.field(c) for c in file_cols])
        host_threads = max(1, int(self.conf.get(
            cfg.SCAN_HOST_PREP_THREADS)))
        depth = max(0, int(self.conf.get(cfg.SCAN_PREFETCH_DEPTH)))
        groups = self._fused_groups()
        # on a mesh of several chips partition p is chip p % n_dev's
        # (exec/placement): its pages are uploaded there, its upload set
        # is kept there and its decode runs there
        from spark_rapids_tpu.exec import placement
        chips = placement.mesh_devices(self.conf)
        if chips:
            _obsreg.get_registry().inc_many(
                ("scan.placed.batches", len(groups)),
                ("scan.placed.chips",
                 min(len(groups), len(chips)) if len(groups) > 1 else 0))

        def chip_of(idx):
            return chips[idx % len(chips)] if chips else None

        # shared-scan multicast (io/scan_share): concurrent queries
        # decoding the same (stamps, row-groups, columns) group share
        # ONE host prep + device decode
        share = None
        share_keys: List = []
        if bool(self.conf.get(cfg.SCAN_SHARED_ENABLED)):
            from spark_rapids_tpu.io import scan_share
            share = scan_share.get_share(
                int(self.conf.get(cfg.SCAN_SHARED_WINDOW_BYTES)))
            schema_sig = tuple((f.name, f.dtype.name)
                               for f in self._schema.fields)
            # a shared decode is shared where it lies
            share_keys = [scan_share.share_key(
                srcs, pv, schema_sig if not chips else schema_sig + (
                    ("device", str(chip_of(i).id)),))
                for i, (srcs, pv) in enumerate(groups)]

        def prepare(idx, path_rgs):
            """Host prep + packed-page upload for one batch (NO device
            read — safe on the prefetch thread)."""
            handles = {p: sc.open_source(p, metrics=self.metrics)
                       for p in {p for p, _ in path_rgs}}
            sources = [(handles[p], p, rg) for p, rg in path_rgs]
            try:
                return pqf.prepare_fused(
                    sources, file_schema, columns=file_cols,
                    host_threads=host_threads,
                    metrics=self.metrics, device=chip_of(idx)), handles
            except BaseException:
                for h in handles.values():
                    h.close()
                raise

        def finish(prepared, pv) -> DeviceBatch:
            """Dispatch the prepared batch (caller holds the TPU
            semaphore)."""
            prep, handles = prepared
            try:
                with timed(self.metrics, "scan.dispatch"):
                    batch, fallbacks = pqf.finish_fused(prep)
                self.metrics.add_extra("fallbackColumns",
                                       len(fallbacks))
                cap = batch.capacity
                names = list(batch.names)
                cols = list(batch.columns)
                for c in part_cols:
                    d = self.part_fields[c]
                    names.append(c)
                    cols.append(_const_column(
                        d, pv.get(c), cap, int(batch.num_rows)))
                order = [names.index(c) for c in wanted]
                out = DeviceBatch([names[i] for i in order],
                                  [cols[i] for i in order],
                                  batch.num_rows)
                if prep.device is not None and (part_cols
                                                or prep.extra_cols):
                    # columns made on the host join the decoded ones
                    # on the batch's chip
                    import jax
                    out = jax.device_put(out, prep.device)
                rows = int(out.num_rows)
                self.metrics.num_output_rows += rows
                self.metrics.add_batches()
                # the fill of the capacity tier the batch was born at:
                # every program above the scan runs over the slots
                _obsreg.get_registry().inc_many(
                    ("scan.batch.rows", rows), ("scan.batch.slots", cap))
                return out
            finally:
                for h in handles.values():
                    h.close()

        def _lead(kind, entry, idx, path_rgs):
            try:
                return (kind, entry, prepare(idx, path_rgs))
            except BaseException as e:
                share.fail(entry, e)
                share.release(entry)
                raise

        def _prep(idx, path_rgs, ahead=True):
            """Prepare with a sharing claim: markers are ("solo"/"lead"/
            "ahead"/"join", entry, prepared).  A joined claim skips the
            host prep (and so the page walks) entirely.  A leader that
            decodes at once takes the right to with its claim
            ("lead"); one that prepares ahead of its consumer (the
            look-ahead threads) leaves it open ("ahead") until
            ``_resolve``."""
            if share is None or share_keys[idx] is None:
                return ("solo", None, prepare(idx, path_rgs))
            role, entry = share.claim(share_keys[idx], not ahead)
            if role == "join":
                return ("join", entry, None)
            return _lead("ahead" if ahead else "lead", entry, idx,
                         path_rgs)

        def _finish_marker(marker, pv) -> DeviceBatch:
            """Dispatch one non-join marker's decode (caller holds the
            TPU semaphore); a lead marker settles its flight."""
            kind, entry, prepared = marker
            if kind == "solo":
                return finish(prepared, pv)
            try:
                out = finish(prepared, pv)
            except BaseException as e:
                share.fail(entry, e)
                share.release(entry)
                raise
            share.publish(entry, out)
            share.release(entry)
            # host-side share stamp (tree_flatten drops it): downstream
            # donation checks the entry's live refcount at dispatch
            # time (fused_stage dispatch -> ScanShare.try_steal)
            out._scan_share_entry = entry
            return out

        def _resolve(marker, idx, path_rgs, pv) -> DeviceBatch:
            """Marker -> decoded batch.  Takes the semaphore only for
            real decode work — never while waiting on another query's
            flight (the leader's decode needs a slot)."""
            while True:
                kind, entry, prepared = marker
                if kind == "join" and share.begin(entry):
                    # the leader prepared ahead and its consumer has
                    # not come; it may be waiting on this very scan
                    # (two scans of one table in one query: the build
                    # side's join the flights the stream side's
                    # look-ahead leads).  Decode in its place.
                    marker = _lead("lead", entry, idx, path_rgs)
                elif kind == "ahead" and share.begin(entry):
                    marker = ("lead", entry, prepared)
                elif kind == "ahead":
                    # a subscriber decoded in this leader's place
                    for h in prepared[1].values():
                        h.close()
                    marker = ("join", entry, None)
                if marker[0] != "join":
                    with tpu_semaphore(self.metrics):
                        return _finish_marker(marker, pv)
                try:
                    out = share.wait(entry)
                finally:
                    share.release(entry)
                if out is not None:
                    # decode skipped: account this exec's output so the
                    # query profile still shows the rows it consumed
                    self.metrics.num_output_rows += int(out.num_rows)
                    self.metrics.add_batches()
                    # a joined claim's batch is multicast by definition
                    # (entry.joined > 0 bars the donation steal)
                    out._scan_share_entry = entry
                    return out
                # the leader failed or abandoned its flight: decode
                # locally under a FRESH claim, so a later subscriber
                # can still share this decode
                marker = _prep(idx, path_rgs, ahead=False)

        def _cleanup(marker) -> None:
            kind, entry, prepared = marker
            if prepared is not None:
                for h in prepared[1].values():
                    h.close()
            if kind == "ahead":
                if share.begin(entry):     # else a subscriber decodes it
                    share.fail(entry,
                               RuntimeError("scan flight abandoned"))
                share.release(entry)
            elif kind == "join":
                share.release(entry)

        prefetcher = None
        if depth > 0 and len(groups) > 1:
            # bounded look-ahead: host prep + upload of batch k+1
            # overlaps the dispatch-only decode of batch k
            prefetcher = ScanPrefetcher(
                [(lambda i=i, prgs=srcs: _prep(i, prgs))
                 for i, (srcs, _pv) in enumerate(groups)],
                depth=depth, metrics=self.metrics,
                cleanup=_cleanup,
                labels=[_group_label(srcs) for srcs, _pv in groups])

        def group_part(idx, path_rgs, pv) -> Iterator[DeviceBatch]:
            from spark_rapids_tpu.exec.context import set_input_file
            from spark_rapids_tpu.mem.device import current_chip, task_chip
            # the decode takes a slot of the chip it runs on, whichever
            # thread pulls the partition
            chip = idx % len(chips) if chips else current_chip()
            try:
                with task_chip(chip):
                    if prefetcher is not None:
                        marker = prefetcher.get(idx)
                        out = _resolve(marker, idx, path_rgs, pv)
                    else:
                        # no pipelining: the whole prep+upload+dispatch
                        # runs under the semaphore, preserving the
                        # pre-prefetch concurrent-device-work bound (a
                        # joined claim waits OUTSIDE the semaphore
                        # instead)
                        out = None
                        with tpu_semaphore(self.metrics):
                            marker = _prep(idx, path_rgs, ahead=False)
                            if marker[0] != "join":
                                out = _finish_marker(marker, pv)
                        if out is None:
                            out = _resolve(marker, idx, path_rgs, pv)
                paths = {p for p, _ in path_rgs}
                # set right before the yield so the consumer evaluates
                # input_file_name() against THIS batch's file
                set_input_file(paths.pop() if len(paths) == 1 else "")
                yield out
            finally:
                set_input_file("")
                if prefetcher is not None:
                    # once every partition has finished (or failed),
                    # unconsumed prepared batches release immediately
                    prefetcher.part_done()

        return [group_part(i, srcs, pv)
                for i, (srcs, pv) in enumerate(groups)]

    def simple_string(self) -> str:
        return (f"{type(self).__name__}"
                f"(files={len(self.scan.paths)}, deviceDecode)")


class TpuOrcScanExec(TpuParquetScanExec):
    """Device-decoding ORC scan: stripe streams expand in HBM
    (GpuOrcScan analog, reference: GpuOrcScan.scala:206+).  One batch
    per stripe; shares the partition-column and fallback machinery."""

    fmt = "orc"

    def _open(self, path: str):
        from spark_rapids_tpu.io import device_orc as dorc
        with open(path, "rb") as f:
            raw = f.read()
        return path, raw, dorc.read_meta(raw)

    def _num_chunks(self, fctx) -> int:
        return len(fctx[2].stripes)

    def _decode_chunk(self, fctx, idx: int, file_schema: Schema,
                      file_cols):
        from spark_rapids_tpu.io import device_orc as dorc
        path, raw, meta = fctx
        return dorc.decode_stripe(path, idx, file_schema,
                                  columns=file_cols, raw=raw, meta=meta)


class TpuCsvScanExec(TpuExec):
    """Device-decoding CSV scan: ONE byte-tensor kernel per file scans
    delimiters and parses fields in HBM (GpuBatchScanExec Table.readCSV
    analog, reference: GpuBatchScanExec.scala:465).  Unsupported
    dialects (quotes, ragged rows, exotic numerics) fall back to the
    Arrow reader per file/column."""

    def __init__(self, scan: FileScan, conf):
        super().__init__()
        self.scan = scan
        self.conf = conf
        self.columns = scan.options.get("columns")
        self._schema = scan.schema if not self.columns else Schema(
            [scan.schema.field(c) for c in self.columns])
        self.metrics.extra["fallbackColumns"] = 0
        self.metrics.extra["fallbackFiles"] = 0

    @property
    def schema(self) -> Schema:
        return self._schema

    def _file_part(self, path: str):
        from spark_rapids_tpu.exec.context import set_input_file
        from spark_rapids_tpu.io import device_csv as dcsv
        from spark_rapids_tpu.io.readers import _read_csv, _normalize
        from spark_rapids_tpu.columnar.batch import from_arrow
        wanted = [f.name for f in self._schema.fields]
        opts = self.scan.options
        try:
            with tpu_semaphore(self.metrics):
                with timed(self.metrics, "scan.csvDecode"):
                    try:
                        batch, fallbacks = dcsv.decode_csv(
                            path, self.scan.schema, columns=wanted,
                            sep=opts.get("sep", ","),
                            header=bool(opts.get("header", True)))
                        self.metrics.add_extra("fallbackColumns",
                                               len(fallbacks))
                    except dcsv.UnsupportedCsv:
                        # whole-file host fallback
                        self.metrics.add_extra("fallbackFiles", 1)
                        t = _normalize(_read_csv(path, opts),
                                       self.scan.schema,
                                       permissive=True)
                        batch = from_arrow(t.select(wanted))
                    self.metrics.num_output_rows += int(batch.num_rows)
                    self.metrics.add_batches()
                    set_input_file(path)
                    yield batch
        finally:
            set_input_file("")

    def execute(self):
        from spark_rapids_tpu.io.readers import scan_file_indices
        return [self._file_part(self.scan.paths[i])
                for i in scan_file_indices(self.scan)]

    def simple_string(self) -> str:
        return (f"{type(self).__name__}"
                f"(files={len(self.scan.paths)}, deviceDecode)")
