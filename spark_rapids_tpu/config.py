"""Typed, self-documenting configuration registry.

TPU-native analog of the reference's ``RapidsConf`` system
(reference: sql-plugin/.../RapidsConf.scala:269-281 — ``ConfEntry`` registry with
typed builders, defaults, and doc generation via ``RapidsConf.main`` emitting
docs/configs.md).

Keys live under ``spark.rapids.tpu.*``.  Per-operator enable keys are derived
automatically from exec/expression class names (reference:
GpuOverrides.scala:131-139) — see :mod:`spark_rapids_tpu.plan.overrides`.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

_REGISTRY: Dict[str, "ConfEntry"] = {}
_REGISTRY_LOCK = threading.Lock()


@dataclass(frozen=True)
class ConfEntry:
    """One typed configuration key with default + documentation.

    Mirrors reference ``ConfEntry``/``ConfBuilder`` (RapidsConf.scala:180-281).
    """

    key: str
    default: Any
    doc: str
    value_type: type
    internal: bool = False
    # converter applied to raw (string or typed) values at lookup time
    converter: Optional[Callable[[Any], Any]] = None

    def get(self, conf: "RapidsTpuConf") -> Any:
        raw = conf._settings.get(self.key, self.default)
        if raw is None:
            return None
        if self.converter is not None:
            return self.converter(raw)
        if self.value_type is bool and isinstance(raw, str):
            return raw.strip().lower() in ("true", "1", "yes")
        if self.value_type in (int, float) and isinstance(raw, str):
            return self.value_type(raw)
        return raw


def _register(entry: ConfEntry) -> ConfEntry:
    with _REGISTRY_LOCK:
        if entry.key in _REGISTRY:
            raise ValueError(f"duplicate conf key {entry.key}")
        _REGISTRY[entry.key] = entry
    return entry


def conf(key: str, default: Any, doc: str, value_type: type = str,
         internal: bool = False,
         converter: Optional[Callable[[Any], Any]] = None) -> ConfEntry:
    return _register(ConfEntry(key=key, default=default, doc=doc,
                               value_type=value_type, internal=internal,
                               converter=converter))


# ---------------------------------------------------------------------------
# Core keys (subset mirrors reference RapidsConf.scala; grows with features)
# ---------------------------------------------------------------------------

SQL_ENABLED = conf(
    "spark.rapids.tpu.sql.enabled", True,
    "Enable or disable TPU acceleration of SQL operators entirely.", bool)

EXPLAIN = conf(
    "spark.rapids.tpu.sql.explain", "NONE",
    "Explain why parts of a query were or were not placed on the TPU: "
    "NONE, NOT_ON_TPU, ALL. (reference: RapidsConf.scala:747, "
    "GpuOverrides.scala:2054-2060)")

INCOMPATIBLE_OPS = conf(
    "spark.rapids.tpu.sql.incompatibleOps.enabled", False,
    "Enable operators that produce results that differ from Spark in corner "
    "cases (e.g. float aggregation ordering). (reference: RapidsConf.scala:424)",
    bool)

HAS_NANS = conf(
    "spark.rapids.tpu.sql.hasNans", True,
    "Assume floating point data may contain NaNs; disables some ops unless "
    "false. (reference: RapidsConf.scala:431)", bool)

VARIABLE_FLOAT_AGG = conf(
    "spark.rapids.tpu.sql.variableFloatAgg.enabled", False,
    "Allow float/double aggregations whose result may vary run-to-run due to "
    "reduction ordering. (reference: RapidsConf.scala:437)", bool)

IMPROVED_FLOAT_OPS = conf(
    "spark.rapids.tpu.sql.improvedFloatOps.enabled", False,
    "Enable float ops that are more accurate than Spark's but differ bit-wise.",
    bool)

BATCH_SIZE_BYTES = conf(
    "spark.rapids.tpu.sql.batchSizeBytes", 2 << 30,
    "Target size in bytes for coalesced columnar batches handed to one XLA "
    "program invocation. (reference: RapidsConf.scala:364)", int)

BATCH_SIZE_ROWS = conf(
    "spark.rapids.tpu.sql.batchSizeRows", 1 << 21,
    "Soft cap on rows per coalesced batch.", int)

MIN_BUCKET_ROWS = conf(
    "spark.rapids.tpu.sql.shape.minBucketRows", 16,
    "Smallest padded row-capacity bucket. Batches are padded up to "
    "power-of-two buckets so XLA recompiles are bounded (TPU static-shape "
    "requirement; no reference analog — cudf tolerates dynamic shapes).", int)

CONCURRENT_TPU_TASKS = conf(
    "spark.rapids.tpu.sql.concurrentTpuTasks", 2,
    "Number of tasks that may hold the TPU semaphore concurrently. "
    "(reference: GpuSemaphore.scala:101, RapidsConf.scala)", int)

TEST_ENABLED = conf(
    "spark.rapids.tpu.sql.test.enabled", False,
    "Test mode: assert that every supported operator actually ran on the TPU. "
    "(reference: RapidsConf.scala:607-621, assertIsOnTheGpu)", bool)

TEST_ALLOWED_NON_TPU = conf(
    "spark.rapids.tpu.sql.test.allowedNonTpu", "",
    "Comma-separated exec/expr class names allowed to stay on CPU in test "
    "mode.")

CAST_STRING_TO_FLOAT = conf(
    "spark.rapids.tpu.sql.castStringToFloat.enabled", False,
    "Enable string-to-float casts on TPU. The device parse "
    "(mantissa x 10^exp in float64) can differ from strtod in the last "
    "ulp for full-precision decimal strings (reference flags GPU "
    "castStringToFloat incompatible for the same reason).", bool)

CAST_FLOAT_TO_STRING = conf(
    "spark.rapids.tpu.sql.castFloatToString.enabled", True,
    "Enable float-to-string casts on TPU. The device Ryu kernel "
    "(expr/ryu.py) produces the engine's exact shortest-round-trip "
    "repr formatting, bit-identical to the CPU path; disable only to "
    "force the CPU fallback (reference gates GPU castFloatToString "
    "behind the same kind of flag because Java formatting differs).",
    bool)

ALLOW_INCOMPAT_UTC_ONLY = conf(
    "spark.rapids.tpu.sql.castStringToTimestamp.enabled", False,
    "Enable string-to-timestamp casts (UTC only).", bool)

MAX_READER_BATCH_SIZE_ROWS = conf(
    "spark.rapids.tpu.sql.reader.batchSizeRows", 1 << 21,
    "Max rows a file reader emits per batch. (reference: RapidsConf.scala:378)",
    int)

MAX_READER_BATCH_SIZE_BYTES = conf(
    "spark.rapids.tpu.sql.reader.batchSizeBytes", 2 << 30,
    "Max bytes a file reader emits per batch.", int)

PARQUET_DEVICE_DECODE = conf(
    "spark.rapids.tpu.sql.format.parquet.deviceDecode.enabled", True,
    "Decode parquet pages in HBM (RLE/dictionary/def-level expansion on "
    "device; reference: GpuParquetScan.scala:1022 Table.readParquet).",
    bool)

CSV_DEVICE_DECODE = conf(
    "spark.rapids.tpu.sql.format.csv.deviceDecode.enabled", True,
    "Decode CSV files in HBM: one byte-tensor kernel scans delimiters "
    "and parses fields per file (reference: GpuBatchScanExec.scala:465 "
    "Table.readCSV). Quoted/ragged/exotic files fall back to the host "
    "Arrow reader.", bool)

PARQUET_DEVICE_ENCODE = conf(
    "spark.rapids.tpu.sql.format.parquet.deviceEncode.enabled", True,
    "Encode parquet writes from device batches: per-column null "
    "compaction on device, one packed download, host page/footer "
    "assembly (reference: GpuParquetFileFormat.scala:281 "
    "Table.writeParquetChunked). Unsupported types or partitioned "
    "writes fall back to the host Arrow writer.", bool)

ORC_DEVICE_ENCODE = conf(
    "spark.rapids.tpu.sql.format.orc.deviceEncode.enabled", True,
    "Encode ORC writes from device batches: per-column null compaction "
    "on device, one packed download, host RLEv1/protobuf stripe "
    "assembly (reference: GpuOrcFileFormat.scala:103 "
    "Table.writeORCChunked). Unsupported types or partitioned writes "
    "fall back to the host Arrow writer.", bool)

CACHE_DEVICE_ENCODE = conf(
    "spark.rapids.tpu.sql.cache.deviceEncode.enabled", True,
    "Compress df.cache() batches to parquet blobs with the DEVICE "
    "encoder instead of host Arrow (reference: "
    "ParquetCachedBatchSerializer.scala:333 "
    "compressColumnarBatchWithParquet encodes cached batches on GPU).",
    bool)

PARQUET_FUSED_DECODE = conf(
    "spark.rapids.tpu.sql.format.parquet.fusedDecode.enabled", True,
    "Decode ALL columns of ALL coalesced row groups in one XLA program "
    "(the multi-file coalescing reader; reference: "
    "GpuParquetScan.scala:489 MultiFileParquetPartitionReader packs "
    "many files into one Table.readParquet call). Falls back to "
    "per-column decode per row group when off or when "
    "input_file_name() is used.", bool)

SCAN_METADATA_CACHE_ENABLED = conf(
    "spark.rapids.tpu.sql.scan.metadataCache.enabled", True,
    "Cache scan host-prep artifacts (parsed parquet footers, Thrift "
    "page descriptors, RLE run tables) process-wide, keyed on (path, "
    "mtime, size, column, options) so repeat scans of unchanged files "
    "skip the page-header walks entirely (the footer-cache analog of "
    "the reference's multi-file reader; host-side sibling of the "
    "compiled-kernel cache).", bool)

SCAN_METADATA_CACHE_MAX_BYTES = conf(
    "spark.rapids.tpu.sql.scan.metadataCache.maxBytes", 4 << 30,
    "Byte budget for the scan metadata/plan cache; least-recently-used "
    "files evict (whole-file granularity) when cached run tables and "
    "packed page buffers exceed it.  What the plans leave of it holds "
    "the assembled upload sets of whole scan batches in device memory "
    "(dropped first, and all of them under memory pressure): a fact "
    "table whose sets do not fit is walked, packed and uploaded again "
    "every query (1.3 GB of pages a scan at TPC-DS SF10).", int)

SCAN_HOST_PREP_THREADS = conf(
    "spark.rapids.tpu.sql.scan.hostPrep.threads", 4,
    "Thread-pool size for parallel scan host prep: page-header and RLE "
    "run-boundary walks across (column, row-group) pairs run "
    "concurrently instead of sequentially (page reads and codec "
    "decompression release the GIL). 1 disables the pool.", int)

SCAN_PREFETCH_DEPTH = conf(
    "spark.rapids.tpu.sql.scan.prefetch.depth", 2,
    "Bounded look-ahead for the fused parquet scan: up to this many "
    "batches' host prep + packed-page upload run ahead of the "
    "dispatch-only device decode of the current batch (prep of batch "
    "k+1 overlaps decode of batch k; no device->host read happens "
    "before the terminal barrier). 0 disables pipelining.", int)

SCAN_SHARED_ENABLED = conf(
    "spark.rapids.tpu.sql.scan.shared.enabled", True,
    "Multicast decoded scan batches across concurrent queries: when "
    "two plans decode the same (file, row-group, column-set, stamp) "
    "key at the same time, one decodes and every subscriber receives "
    "the decoded batch (refcounted retention window; eviction is "
    "always correctness-safe — a miss just re-decodes). Off reverts "
    "to per-query decoding.", bool)

SCAN_SHARED_WINDOW_BYTES = conf(
    "spark.rapids.tpu.sql.scan.shared.windowBytes", 64 << 20,
    "Byte budget for the shared-scan multicast retention window "
    "(decoded batches kept briefly so a slightly-behind subscriber "
    "still shares the decode). LRU eviction; the window also registers "
    "as a pressure spiller so HBM pressure drops retained batches "
    "first.", int)

ORC_DEVICE_DECODE = conf(
    "spark.rapids.tpu.sql.format.orc.deviceDecode.enabled", True,
    "Decode ORC stripes on the TPU: CPU parses stripe footers and RLEv2 "
    "run boundaries, device kernels expand runs/PRESENT streams and "
    "gather string dictionaries in HBM. Columns with unsupported "
    "encodings fall back to host Arrow decode individually. (reference: "
    "GpuOrcScan.scala:206 device decode via libcudf)", bool)

PARQUET_READER_TYPE = conf(
    "spark.rapids.tpu.sql.format.parquet.reader.type", "AUTO",
    "Parquet reader strategy: AUTO, PERFILE, COALESCING, MULTITHREADED. "
    "(reference: RapidsConf.scala:513)")

PARQUET_MULTITHREAD_READ_NUM_THREADS = conf(
    "spark.rapids.tpu.sql.format.parquet.multiThreadedRead.numThreads", 20,
    "Thread pool size for the MULTITHREADED cloud reader. "
    "(reference: RapidsConf.scala:540)", int)

CLOUD_SCHEMES = conf(
    "spark.rapids.tpu.cloudSchemes", "gs,s3,s3a,s3n,wasbs,abfs",
    "URI schemes treated as high-latency cloud stores (selects the "
    "MULTITHREADED reader under AUTO).")

MEM_POOL_FRACTION = conf(
    "spark.rapids.tpu.memory.pool.fraction", 0.9,
    "Fraction of free HBM the arena manages for columnar batches. "
    "(reference: GpuDeviceManager.scala:196-262 RMM pool init)", float)

MEM_DEVICE_LIMIT = conf(
    "spark.rapids.tpu.memory.device.batchStorageSize", 4 << 30,
    "Bytes of HBM budget for registered spillable batches; exceeding it "
    "triggers synchronous device->host spill (RMM pool + event-handler "
    "analog).", int)

MEM_SPILL_ENABLED = conf(
    "spark.rapids.tpu.memory.spill.enabled", True,
    "Enable device->host->disk spill of registered batches under memory "
    "pressure. (reference: RapidsBufferCatalog.scala:128-142)", bool)

MEM_HOST_SPILL_LIMIT = conf(
    "spark.rapids.tpu.memory.host.spillStorageSize", 8 << 30,
    "Bytes of host memory used to cache spilled device batches before "
    "falling through to disk.", int)

MEM_SPILL_DIR = conf(
    "spark.rapids.tpu.memory.spill.dir", "",
    "Directory for the disk spill tier (defaults to a temp dir).")

SHUFFLE_TRANSPORT = conf(
    "spark.rapids.tpu.shuffle.transport", "local",
    "Shuffle transport implementation: 'local' (in-process Arrow IPC store, "
    "the default-path analog), 'device' (HBM-resident slices, one process), "
    "'manager' (accelerated TpuShuffleManager: device-resident catalog + "
    "tag-matched client/server transport), 'ici' (device-resident "
    "all_to_all over a jax Mesh; reference: shuffle-plugin UCX "
    "transport), or 'ici_ring' (like 'ici' but broadcast builds "
    "replicate via collective_permute ring hops — the point-to-point "
    "plane; reference: tag-matched per-peer pulls, "
    "UCXConnection.scala:385), or 'process' (map stages execute in "
    "spawned executor OS processes that serve their catalogs over the "
    "TCP transport; the cross-process executor-fleet data plane, "
    "RapidsShuffleInternalManager.scala:90-186).")

COLUMN_PRUNING = conf(
    "spark.rapids.tpu.sql.columnPruning.enabled", True,
    "Prune unreferenced columns out of file and in-memory scans before "
    "physical planning (Catalyst ColumnPruning analog; on TPU this "
    "skips whole device parquet column-chunk decodes and HBM uploads).",
    bool)

SHUFFLE_PROCESS_EXECUTORS = conf(
    "spark.rapids.tpu.shuffle.transport.processExecutors", 2,
    "Number of executor processes the 'process' shuffle transport "
    "spawns (the executor fleet the RapidsShuffleManager spans).", int)

SHUFFLE_PROCESS_NESTED_TRANSPORT = conf(
    "spark.rapids.tpu.shuffle.transport.processNestedTransport", "local",
    "Data plane for exchanges NESTED inside a shipped map stage when "
    "shuffle.transport=process: 'local' (in-process store) or 'ici' / "
    "'ici_ring' (each executor runs the nested exchange as collectives "
    "over its own device mesh — the DCN-over-ICI composition: "
    "intra-slice collectives per executor, TCP between executors).")

SHUFFLE_FETCH_MAX_RETRIES = conf(
    "spark.rapids.tpu.shuffle.fetch.maxRetries", 3,
    "Max per-peer fetch retries in the shuffle iterator before the "
    "failure escalates (to the CPU fallback when enabled, else to a "
    "fetch-failed exception that re-runs the map stage). 0 disables "
    "retries: any transport fault fails the fetch immediately with the "
    "typed shuffle exceptions.", int)

SHUFFLE_FETCH_RETRY_BACKOFF_MS = conf(
    "spark.rapids.tpu.shuffle.fetch.retryBackoffMs", 50,
    "Base backoff between shuffle fetch retries; doubles per attempt "
    "with deterministic jitter (exponential backoff).", int)

SHUFFLE_CONNECT_TIMEOUT_MS = conf(
    "spark.rapids.tpu.shuffle.connectTimeoutMs", 5000,
    "TCP shuffle transport connect timeout per attempt. A failed "
    "connect is redialed once with backoff within a fetch attempt; the "
    "overall retry budget is governed by fetch.maxRetries at the fetch "
    "layer.", int)

SHUFFLE_READ_TIMEOUT_MS = conf(
    "spark.rapids.tpu.shuffle.readTimeoutMs", 10000,
    "TCP shuffle transport read-watchdog window: a connection with "
    "in-flight requests or posted receives that stays silent for two "
    "consecutive windows fails them all (surfacing as a retryable "
    "fetch failure); the double window guarantees an operation posted "
    "mid-window a full window of budget. 0 disables.", int)

SHUFFLE_CPU_FALLBACK = conf(
    "spark.rapids.tpu.shuffle.fetch.cpuFallbackEnabled", True,
    "After shuffle fetch retries and map-stage re-runs are exhausted, "
    "re-read the affected partitions through the CPU shuffle block "
    "store (recomputing the map side in-process) instead of failing "
    "the query — the fall-back-to-Spark-shuffle contract.", bool)

SHUFFLE_FAULT_PLAN = conf(
    "spark.rapids.tpu.shuffle.test.faultPlan", "",
    "Deterministic fault-injection plan for chaos testing, e.g. "
    "'seed=7;tcp.server.data:drop@2;procpool.map_stage:kill@1:i0'. "
    "See spark_rapids_tpu/shuffle/faults.py for the grammar and the "
    "named injection points. Empty disables injection.")

PYWORKER_HANDSHAKE_TIMEOUT_MS = conf(
    "spark.rapids.tpu.python.worker.handshakeTimeoutMs", 20000,
    "How long to wait for a spawned python worker to connect back and "
    "authenticate before the spawn fails with PythonWorkerError.", int)

PYWORKER_CLOSE_TIMEOUT_MS = conf(
    "spark.rapids.tpu.python.worker.closeTimeoutMs", 5000,
    "How long to wait for a python worker to exit cleanly on close "
    "before it is hard-killed.", int)

PYWORKER_MAX_RESPAWNS = conf(
    "spark.rapids.tpu.python.worker.maxRespawns", 1,
    "How many times a python-worker batch is transparently replayed on "
    "a fresh worker after the worker process crashes mid-batch. 0 "
    "disables replay (a crash surfaces as PythonWorkerError).", int)

SHUFFLE_COMPRESSION_CODEC = conf(
    "spark.rapids.tpu.shuffle.compression.codec", "none",
    "Codec for shuffle data: none, lz4, zstd, zlib (zlib compresses "
    "the wire leg only — Arrow IPC has no zlib buffer compression, so "
    "block stores hold those blocks uncompressed). "
    "Applies to serialized shuffle partitions (pyarrow IPC buffer "
    "compression in the block stores) AND, on the TCP/DCN process "
    "transport, to the per-frame DATA wire leg — the driver's clients "
    "negotiate the codec in their HELLO handshake and executor servers "
    "wrap every DATA payload back to them (flag + uncompressed-size + "
    "body; incompressible or empty frames ride uncompressed inside the "
    "wrapper). See docs/shuffle_wire_format.md. (reference: "
    "TableCompressionCodec.scala:41)")

SHUFFLE_PIPELINE_DEPTH = conf(
    "spark.rapids.tpu.shuffle.pipeline.depth", 2,
    "Bounded look-ahead of the pipelined process-transport exchange: "
    "up to this many reduce partitions are fetched + decoded + "
    "uploaded ahead of the consumer (the ScanPrefetcher shape), with "
    "per-map completion notifications letting reducers fetch a map "
    "task's output the moment that map id finishes instead of "
    "barriering on the whole map stage. Prepared partitions register "
    "with the spill catalog at shuffle-input priority, so memory "
    "pressure spills them to host/disk instead of stalling admission. "
    "0 disables the pipeline (the sequential map->fetch->decode "
    "exchange, bit-identical results — the CI parity gate diffs the "
    "two).", int)

SHUFFLE_PIPELINE_TIMEOUT_MS = conf(
    "spark.rapids.tpu.shuffle.pipeline.timeoutMs", 120000,
    "No-progress bound on the pipelined exchange's wait for the next "
    "map-task completion: if no new map id lands within this window "
    "the read escalates through the standard recovery ladder "
    "(map-stage re-run of dead executors, then the CPU fallback when "
    "enabled). Raise for map stages whose single tasks legitimately "
    "run longer, or set 0 to wait indefinitely (the sequential "
    "barrier's semantics: a dead executor still surfaces promptly "
    "through its submit thread; only a wedged-but-alive one blocks, "
    "exactly as it blocks the depth=0 pipe read).", int)

AUTO_BROADCAST_THRESHOLD = conf(
    "spark.rapids.tpu.sql.autoBroadcastJoinThreshold", 10 << 20,
    "Max estimated byte size of a join side to broadcast it "
    "(spark.sql.autoBroadcastJoinThreshold analog; -1 disables).", int)

CACHE_COMPRESSION = conf(
    "spark.rapids.tpu.sql.cache.compression", "snappy",
    "Parquet compression codec for df.cache() blobs "
    "(ParquetCachedBatchSerializer analog; none|snappy|zstd|gzip|lz4).")

CACHE_DEVICE_DECODE = conf(
    "spark.rapids.tpu.sql.cache.deviceDecode.enabled", True,
    "Decode cached parquet blobs on device (HBM RLE/dictionary "
    "expansion), falling back per column like file scans.", bool)

ADAPTIVE_ENABLED = conf(
    "spark.rapids.tpu.sql.adaptive.enabled", True,
    "Adaptive shuffle reads: after an exchange materializes, coalesce "
    "undersized reduce partitions and split skewed ones using the "
    "measured per-partition sizes (AQE CustomShuffleReaderExec analog; "
    "reference: GpuCustomShuffleReaderExec.scala:38).", bool)

ADAPTIVE_ADVISORY_PARTITION_SIZE = conf(
    "spark.rapids.tpu.sql.adaptive.advisoryPartitionSizeInBytes",
    64 << 20,
    "Target output partition size for adaptive coalescing and skew "
    "splitting.", int)

ADAPTIVE_MIN_PARTITION_NUM = conf(
    "spark.rapids.tpu.sql.adaptive.coalescePartitions.minPartitionNum", 1,
    "Lower bound on the post-coalesce partition count.", int)

ADAPTIVE_SKEW_FACTOR = conf(
    "spark.rapids.tpu.sql.adaptive.skewJoin.skewedPartitionFactor", 5,
    "A partition is skewed if its bytes exceed this multiple of the "
    "median partition size (and the absolute threshold).", int)

ADAPTIVE_SKEW_THRESHOLD = conf(
    "spark.rapids.tpu.sql.adaptive.skewJoin."
    "skewedPartitionThresholdInBytes", 256 << 20,
    "Absolute minimum bytes for a partition to be considered skewed.",
    int)

SHUFFLE_PARTITIONS = conf(
    "spark.rapids.tpu.sql.shuffle.partitions", 8,
    "Default number of shuffle partitions (spark.sql.shuffle.partitions "
    "analog).", int)

JOIN_OOCORE_ENABLED = conf(
    "spark.rapids.tpu.sql.join.oocore.enabled", True,
    "Out-of-core grace hash join (exec/join_partition.py): when a "
    "join's per-partition build side exceeds join.buildSideBudgetBytes "
    "it is hash-partitioned (a different murmur seed per recursion "
    "level, decorrelated from the exchange's bucketing) into 2^k grace "
    "partitions together with its probe side; build partitions spill "
    "through the device->host->disk tiers and each grace partition is "
    "re-streamed and joined alone, recursing on a still-oversized "
    "partition. Under-budget joins take the unpartitioned path "
    "byte-for-byte; off reverts entirely (the one-knob revert).", bool)

JOIN_BUILD_BUDGET = conf(
    "spark.rapids.tpu.sql.join.buildSideBudgetBytes", 0,
    "Per-partition build-side byte budget that activates the "
    "out-of-core grace join. 0 (default) derives it from the admission "
    "machinery: the scheduler memory budget (sched.memoryBudget or its "
    "HBM-pool derivation) divided by sched.maxConcurrent — one "
    "admitted query's fair share. -1 disables the budget check "
    "entirely (build sides gather unconditionally, today's behavior).",
    int)

JOIN_OOCORE_PARTITIONS_LOG2 = conf(
    "spark.rapids.tpu.sql.join.oocore.partitionsLog2", 0,
    "Explicit grace fan-out exponent: partition both sides into 2^k "
    "pieces when the build side exceeds the budget. 0 (default) picks "
    "the smallest k whose expected per-partition build size fits the "
    "budget, capped at 5 (32-way).", int)

JOIN_OOCORE_MAX_RECURSION = conf(
    "spark.rapids.tpu.sql.join.oocore.maxRecursion", 3,
    "Recursion-depth bound for grace partitions that stay over budget "
    "after a split (duplicate-heavy keys). At the bound — or as soon "
    "as a level fails to shrink the partition (a single hot key cannot "
    "hash-split) — the join falls back to streaming the probe side in "
    "chunks against the oversized build partition, which is always "
    "correct and always terminates.", int)

JOIN_SKEW_ENABLED = conf(
    "spark.rapids.tpu.sql.join.skew.enabled", False,
    "Runtime hot-bucket splitting at the shuffle boundary: the "
    "map-output tracker aggregates per-(map, reduce-bucket) sizes as "
    "map tasks complete; a probe-side bucket projected over "
    "join.skew.bucketFactor x the median splits into sub-readers over "
    "disjoint map-output ranges BEFORE the reduce fetch, each joined "
    "against a replica (or broadcast, when small) of the matching "
    "build bucket — one hot key no longer serializes the reduce stage "
    "on a single reducer. Takes over the skew half of the adaptive "
    "reader for eligible joins; off (default) keeps today's plan "
    "shape exactly.", bool)

JOIN_SKEW_FACTOR = conf(
    "spark.rapids.tpu.sql.join.skew.bucketFactor", 4.0,
    "A reduce bucket is hot when its projected probe-side bytes exceed "
    "this multiple of the median nonzero bucket size (and the "
    "join.skew.minBucketBytes floor).", float)

JOIN_SKEW_MIN_BUCKET_BYTES = conf(
    "spark.rapids.tpu.sql.join.skew.minBucketBytes", 4 << 20,
    "Absolute floor for hot-bucket detection: buckets under this many "
    "bytes are never split regardless of the factor (splitting tiny "
    "buckets buys scheduling overhead, not wall time).", int)

JOIN_SKEW_MAX_SPLITS = conf(
    "spark.rapids.tpu.sql.join.skew.maxSplits", 8,
    "Upper bound on the sub-readers one hot bucket splits into (the "
    "split count otherwise targets the median bucket size).", int)

JOIN_SKEW_BROADCAST_THRESHOLD = conf(
    "spark.rapids.tpu.sql.join.skew.broadcastThresholdBytes", 8 << 20,
    "When the hot bucket's matching build-side bucket is under this "
    "many bytes it is broadcast (one shared device batch reused by "
    "every sub-join, zero copies); over it the bucket is still "
    "replicated by reference but counted as a replication so the "
    "memory cost is observable.", int)

KERNEL_ABI_ENABLED = conf(
    "spark.rapids.tpu.kernel.abi.enabled", True,
    "Shape-erased kernel ABI (exec/kernel_abi.py): batches are renamed "
    "to canonical positional column names, value-range hints re-bucket "
    "to the coarse ABI table, and row-capacity / var-len-width ladders "
    "quantize to capacity tiers (with host-side pad at dispatch for "
    "batches not born at a tier) before every kernel dispatch, so "
    "queries that differ only in schema names, value ranges, or "
    "near-miss batch sizes share one compiled program. Every erased "
    "shape is a subset of the legacy power-of-two ladder, so disabling "
    "this only multiplies compiles — it never changes results (the "
    "ci.sh ABI-collapse gate diffs the two).", bool)

KERNEL_ABI_TIER_STRIDE = conf(
    "spark.rapids.tpu.kernel.abi.tierStride", 2,
    "Row-capacity tier ladder stride: below 1,048,576 rows capacities "
    "quantize to every 2^stride-th power-of-two rung (stride 1 = the "
    "legacy every-pow2 ladder; the default 2 gives tiers 16, 64, 256, "
    "1024, ..., 1048576 — at most 4x padding for at most half the "
    "distinct capacity programs per family). From 1,048,576 up every "
    "power of two is a tier at any stride (2097152, 4194304, ...): at "
    "that scale a half-empty tier costs every program over it tenths "
    "of a second a call, and one more executable its build once.", int)

KERNEL_ABI_WIDTH_STRIDE = conf(
    "spark.rapids.tpu.kernel.abi.widthStride", 2,
    "String/list max-width tier ladder stride (same scheme as "
    "tierStride; default tiers 1, 4, 16, 64, ...). Wide-string padding "
    "costs capacity x width bytes, so raise with care on string-heavy "
    "workloads.", int)

KERNEL_ABI_BUCKET_HINTS = conf(
    "spark.rapids.tpu.kernel.abi.bucketHints", True,
    "Re-bucket DeviceColumn.vbits value-range hints to the coarse ABI "
    "table {16, 32, 56} at the dispatch boundary (and at scan/upload "
    "hint derivation). The narrow fast paths only branch on coarse "
    "thresholds (<=16 single-digit sorts, <=32 i32 gathers, <64 packed "
    "radix fields), so the precise buckets buy program churn, not "
    "speed. A weaker vbits bound is always sound.", bool)

AGG_FUSED_FILTER = conf(
    "spark.rapids.tpu.sql.agg.fusedFilter.enabled", True,
    "Fuse a Filter directly under a hash aggregate into the "
    "aggregate's update kernel as a row mask instead of a compact "
    "(the sort-based grouping is capacity-proportional either way; "
    "compaction costs one full-capacity gather per column — measured "
    "~315 ms of the 738 ms round-4 q6 pipeline).", bool)

FUSION_ENABLED = conf(
    "spark.rapids.tpu.sql.fusion.enabled", True,
    "Whole-stage kernel fusion: collapse maximal chains of dispatch-only "
    "execs (Project/Filter) into a single TpuFusedStageExec whose one "
    "cached kernel evaluates the composed expression DAG with at most "
    "one stream compaction, and inline projection prologues directly "
    "under a hash aggregate into the aggregate's own update kernel. "
    "An N-exec chain pays N-1 fewer dispatches per batch (the cost of "
    "one dispatch is not measured on the attached chip). "
    "Disable for parity testing against the unfused per-node path "
    "(Spark's whole-stage codegen / the reference's tiered project, "
    "basicPhysicalOperators.scala).", bool)

FUSION_MAX_EXPRS = conf(
    "spark.rapids.tpu.sql.fusion.maxExprs", 256,
    "Ceiling on the total expression-node count of one fused stage's "
    "composed output+condition DAG.  Substituting a projection into "
    "its consumers duplicates shared subtrees, so unguarded fusion "
    "could blow up trace time and compile breadth (the TPC-DS compile "
    "bill is pure breadth, PERF.md round 5); past the ceiling the "
    "chain stays unfused.", int)

FUSION_DONATE = conf(
    "spark.rapids.tpu.sql.fusion.donateInputs", True,
    "Donate the input batch's device buffers to fused-stage / project / "
    "filter dispatches (jax donate_argnums) when the producing exec is "
    "known not to retain them, letting XLA reuse the input HBM for the "
    "output and cutting peak memory for deep chains.  Donated "
    "dispatches skip the HBM-OOM retry path (the retry would replay "
    "consumed buffers).", bool)

AGG_EXCHANGE = conf(
    "spark.rapids.tpu.sql.agg.exchange.enabled", False,
    "Plan grouped aggregates as a hash exchange on the grouping keys "
    "followed by a per-partition aggregate (Spark's partial/final "
    "aggregate split restructured so the exchange can ride a distributed "
    "data plane; auto-enabled when shuffle.transport=ici).", bool)

SORT_EXCHANGE = conf(
    "spark.rapids.tpu.sql.sort.exchange.enabled", False,
    "Plan global ORDER BY as a range exchange on the sort keys followed "
    "by per-partition sorts (partition p holds range-bucket p, so "
    "partition-ordered concatenation IS the total order; auto-enabled "
    "when shuffle.transport=ici/ici_ring so the exchange rides the "
    "mesh; reference: GpuRangePartitioning + GpuSortExec per shard).",
    bool)

WINDOW_EXCHANGE = conf(
    "spark.rapids.tpu.sql.window.exchange.enabled", False,
    "Plan window functions over PARTITION BY keys as a hash exchange on "
    "those keys followed by per-partition window evaluation "
    "(auto-enabled when shuffle.transport=ici/ici_ring; reference: "
    "Spark requires ClusteredDistribution(partitionSpec) under "
    "GpuWindowExec).", bool)

ENABLE_FLOAT_SORT = conf(
    "spark.rapids.tpu.sql.sort.float.enabled", True,
    "Enable sorting on float columns (NaN ordering matches Spark: NaN sorts "
    "greatest).", bool)

UDF_COMPILER_ENABLED = conf(
    "spark.rapids.tpu.sql.udfCompiler.enabled", True,
    "Compile Python UDF bytecode into the expression IR so UDFs run on TPU. "
    "(reference: udf-compiler Plugin.scala:29-34)", bool)

METRICS_ENABLED = conf(
    "spark.rapids.tpu.metrics.enabled", True,
    "Collect per-operator metrics (totalTime, numOutputRows/Batches, "
    "peakDevMemory). (reference: GpuExec.scala:27-56)", bool)

OBS_TRACE_ENABLED = conf(
    "spark.rapids.tpu.obs.trace.enabled", False,
    "Record execution spans (scan prep/upload/dispatch, exchange "
    "phases, semaphore waits, pyworker batches) into the bounded "
    "in-process ring buffer. Disabled, the instrumented paths take a "
    "single-bool-check no-op. Spans surface through the per-query "
    "profile and the Chrome trace exporter "
    "(obs/trace.py; open in Perfetto or chrome://tracing).", bool)

OBS_TRACE_BUFFER_SPANS = conf(
    "spark.rapids.tpu.obs.trace.bufferSpans", 65536,
    "Capacity of the span ring buffer; when a query outruns it the "
    "oldest spans drop (bounded memory, never the process).", int)

OBS_TRACE_CHROME_PATH = conf(
    "spark.rapids.tpu.obs.trace.chromePath", "",
    "When set (and tracing is enabled), every query's span window is "
    "also written to this path as Chrome trace-event JSON, overwriting "
    "the previous query's file.")

SCHED_MEMORY_BUDGET = conf(
    "spark.rapids.tpu.sched.memoryBudget", 0,
    "HBM byte budget the admission controller packs query estimates "
    "into: queries are admitted while the sum of their declared "
    "working-set estimates stays under it (sched.maxConcurrent is the "
    "hard count cap); excess queries queue instead of OOMing. 0 "
    "derives the budget from the device manager's HBM pool "
    "(bytes_limit x memory.pool.fraction; 8 GiB when the backend "
    "reports no limit).", int)

SCHED_MAX_CONCURRENT = conf(
    "spark.rapids.tpu.sched.maxConcurrent", 4,
    "Hard cap on concurrently RUNNING queries in the per-session "
    "QueryService, regardless of memory estimates (the inter-query "
    "layer above sql.concurrentTpuTasks, which still bounds "
    "device-task concurrency inside admitted queries).", int)

SCHED_DEFAULT_TIMEOUT_MS = conf(
    "spark.rapids.tpu.sched.defaultTimeoutMs", 0,
    "Default per-query deadline in milliseconds, covering queue wait "
    "AND execution; on expiry the query's CancelToken fires with "
    "timed_out=true and the query unwinds (admission slot released, "
    "prefetcher drained, shuffle fetches cancelled, spill entries "
    "freed), raising QueryTimeoutError from result(). 0 disables; "
    "submit(timeout_ms=...) overrides per query.", int)

SCHED_MAX_QUEUED = conf(
    "spark.rapids.tpu.sched.maxQueued", 1024,
    "Bound on the admission wait queue; submissions past it are "
    "rejected with QueryRejectedError (back-pressure instead of an "
    "unbounded thread pile-up).", int)

SCHED_QUERY_ESTIMATE_BYTES = conf(
    "spark.rapids.tpu.sched.queryEstimateBytes", 0,
    "Fixed HBM working-set estimate per query for admission control. "
    "0 (default) derives batchSizeBytes x (concurrentTpuTasks + "
    "scan.prefetch.depth), then refines per plan shape from the spill "
    "catalog's device-bytes high-water mark of prior runs; "
    "submit(estimate_bytes=...) overrides per query.", int)

SCHED_DEDUP_ENABLED = conf(
    "spark.rapids.tpu.sched.dedup.enabled", True,
    "Single-flight execution: concurrent submissions of the same "
    "deterministic plan (same canonical digest + output names) join "
    "one in-flight execution instead of running N copies — followers' "
    "futures resolve from the leader's result, leader cancellation "
    "promotes a follower instead of killing the flight. "
    "Non-deterministic / uncacheable plans always bypass "
    "(PlanFingerprint.cacheable gate). Off reverts to "
    "one-execution-per-submission.", bool)

SCHED_PROFILE_RING = conf(
    "spark.rapids.tpu.sched.profileRing", 64,
    "How many completed QueryProfiles the session retains, keyed by "
    "query id (concurrent collects no longer race one last-profile "
    "slot; last_query_profile() returns the most recently COMPLETED "
    "query's profile).", int)

OBS_HTTP_ENABLED = conf(
    "spark.rapids.tpu.obs.http.enabled", False,
    "Serve the live operational telemetry endpoint from a background "
    "daemon thread: /metrics (Prometheus text exposition of the "
    "MetricsRegistry plus live scheduler gauges), /queries (the "
    "QueryService's queued/running/recently-completed table), and "
    "/profiles/<qid> (QueryProfile JSON from the profile ring). Off by "
    "default: nothing binds a socket and the serving path costs "
    "nothing.", bool)

OBS_HTTP_PORT = conf(
    "spark.rapids.tpu.obs.http.port", 0,
    "TCP port for the telemetry endpoint when obs.http.enabled=true. "
    "0 binds an ephemeral port (discover it via "
    "session.obs_server.port — the CI scrape idiom).", int)

OBS_HTTP_HOST = conf(
    "spark.rapids.tpu.obs.http.host", "127.0.0.1",
    "Bind address for the telemetry endpoint (loopback by default; "
    "widen deliberately, the endpoint is unauthenticated).")

OBS_RECORDER_DIR = conf(
    "spark.rapids.tpu.obs.recorder.dir", "",
    "Directory for flight-recorder diagnostic bundles. Non-empty "
    "enables the recorder: a bounded in-memory ring of recent engine "
    "events (admission decisions, spill/arena traffic, OOM retries, "
    "query lifecycle) is kept, and on query failure, timeout, "
    "cancellation, or an OOM-retried success a self-contained bundle "
    "(profile.json + trace.json + events.jsonl + config.json + "
    "registry.json) is written here. Empty (default) disables the "
    "recorder entirely; event hooks cost one bool check. Bundles ride "
    "the QueryProfile assembly path, so obs.profile.enabled must stay "
    "true (its default) for them to fire.")

OBS_RECORDER_MAX_EVENTS = conf(
    "spark.rapids.tpu.obs.recorder.maxEvents", 4096,
    "Capacity of the flight recorder's in-memory event ring; the "
    "oldest events drop when a busy engine outruns it (bounded memory, "
    "never the process).", int)

OBS_SLOW_QUERY_MS = conf(
    "spark.rapids.tpu.obs.slowQueryMs", 0,
    "Wall-clock threshold in milliseconds for the structured "
    "slow-query log: a completed (or failed) query at or over it emits "
    "ONE JSONL record (ts, query_id, status, error, wall_s, "
    "queue_wait_s, result_rows, phases, wall_breakdown) to "
    "obs.slowQueryPath, or through the "
    "'spark_rapids_tpu.obs.slowquery' python logger when no path is "
    "set. 0 (default) disables. Rides the QueryProfile assembly path, "
    "so obs.profile.enabled must stay true (its default).", int)

OBS_SLOW_QUERY_PATH = conf(
    "spark.rapids.tpu.obs.slowQueryPath", "",
    "Append-mode file for slow-query JSONL records (one JSON object "
    "per line). Empty routes records to the python logger instead.")

OBS_SLOW_QUERY_MAX_BYTES = conf(
    "spark.rapids.tpu.obs.slowQueryMaxBytes", 16 * 1024 * 1024,
    "Size-based rotation for the slow-query JSONL file (and the drift "
    "sentinel's breach log): when an append would push the file past "
    "this many bytes, it is atomically renamed to <path>.1 (replacing "
    "the previous .1) and a fresh file starts — the keep-1 logrotate "
    "shape, at most 2x this size on disk per log. 0 disables rotation "
    "(unbounded append, the pre-rotation behaviour).", int)

OBS_ACCOUNTING_ENABLED = conf(
    "spark.rapids.tpu.obs.accounting.enabled", True,
    "Per-tenant resource metering (obs/accounting.py): attributes "
    "kernel dispatches, compile wall, scan bytes walked/uploaded, "
    "shuffle wire bytes, result-cache hits/misses, HBM byte-seconds "
    "and queue wait to the owning (session, statement template | plan "
    "digest) tenant, served on the obs endpoint's /tenants route. "
    "Single-flight followers and batched-statement members are billed "
    "their fair share of the execution they joined. Off: every "
    "charging hook is one bool check (the obs.compile pattern).", bool)

OBS_SENTINEL_ENABLED = conf(
    "spark.rapids.tpu.obs.sentinel.enabled", False,
    "Drift sentinel (obs/sentinel.py): a background watcher sampling "
    "the metrics registry every obs.sentinel.intervalMs, comparing "
    "windowed rates against a trailing EWMA baseline, and on a "
    "sustained breach (p95 latency regression, slow-query spike, "
    "result-cache hit-rate collapse, compile storm, spill surge) "
    "emitting ONE flight-recorder bundle per episode (reason 'slo') "
    "with per-tenant top-talkers attached, obs.sentinel.breaches[.rule]"
    " counters, and a structured JSONL line. Off by default: no "
    "thread runs.", bool)

OBS_SENTINEL_INTERVAL_MS = conf(
    "spark.rapids.tpu.obs.sentinel.intervalMs", 1000,
    "Sampling window of the drift sentinel in milliseconds; each tick "
    "evaluates the rule set against the delta since the previous "
    "tick.", int)

OBS_SENTINEL_RULES = conf(
    "spark.rapids.tpu.obs.sentinel.rules", "",
    "Rule spec for the drift sentinel: semicolon-separated "
    "rule:key=val,key=val entries — e.g. "
    "'latency:factor=2,sustain=2;slow:min=5' enables ONLY those rules "
    "with the given overrides. Empty (default) enables every rule "
    "(latency, slow, cacheHit, compile, spill) at its defaults; a "
    "typo'd rule or parameter raises at session init rather than "
    "silently disarming the watcher.")

OBS_SENTINEL_PATH = conf(
    "spark.rapids.tpu.obs.sentinel.path", "",
    "JSONL file for the sentinel's structured breach records (rotated "
    "by obs.slowQueryMaxBytes, the slow-query log's writer). Empty "
    "disables the breach log; flight-recorder bundles and counters "
    "still fire.")

SERVE_ENABLED = conf(
    "spark.rapids.tpu.serve.enabled", False,
    "Start the multi-tenant SQL serving front-end (serve/server.py): a "
    "background TCP server multiplexing remote client sessions onto "
    "this session's QueryService — length-prefixed wire protocol, "
    "per-session conf overlays and fair-share caps, prepared "
    "statements, a stamped result-set cache, and chunked streaming "
    "result delivery with client-credit backpressure. Off by default: "
    "nothing binds a socket.", bool)

SERVE_PORT = conf(
    "spark.rapids.tpu.serve.port", 0,
    "TCP port for the serving front-end when serve.enabled=true. 0 "
    "binds an ephemeral port (discover it via "
    "session.serve_server.port — the CI smoke idiom).", int)

SERVE_HOST = conf(
    "spark.rapids.tpu.serve.host", "127.0.0.1",
    "Bind address for the serving front-end (loopback by default; the "
    "protocol is unauthenticated, widen deliberately).")

SERVE_SESSION_IDLE_TIMEOUT_MS = conf(
    "spark.rapids.tpu.serve.session.idleTimeoutMs", 600_000,
    "Evict a client session after this much inactivity with no query "
    "in flight (prepared statements and the session conf overlay go "
    "with it; the next request on an evicted session gets a typed "
    "SessionExpired error and must re-hello).", int)

SERVE_SESSION_MAX_INFLIGHT = conf(
    "spark.rapids.tpu.serve.session.maxInFlight", 4,
    "Fair-share cap on concurrently in-flight queries per client "
    "session; past it a request is refused with FairShareExceeded "
    "(back-pressure to that client) so one greedy client cannot "
    "monopolize sched.memoryBudget or the admission queue.", int)

SERVE_RESULT_CACHE_ENABLED = conf(
    "spark.rapids.tpu.serve.resultCache.enabled", True,
    "Cache materialized query results keyed on (canonical plan digest, "
    "output names, source file stamps): a repeated deterministic query "
    "over unchanged files is served straight from host memory — zero "
    "device dispatches — and invalidates automatically when a source "
    "file's (mtime, size) stamp moves (the scan-cache contract applied "
    "to whole results). Non-deterministic plans (rand, UDFs) and "
    "unstampable sources never enter.", bool)

SERVE_RESULT_CACHE_MAX_BYTES = conf(
    "spark.rapids.tpu.serve.resultCache.maxBytes", 256 << 20,
    "Byte budget for the serving result-set cache; least-recently-used "
    "results evict past it. A single result larger than the whole "
    "budget is never cached.", int)

SERVE_INCREMENTAL_ENABLED = conf(
    "spark.rapids.tpu.serve.incremental.enabled", True,
    "Incremental maintenance of the serving result cache "
    "(exec/incremental.py): for deterministic cacheable plans whose "
    "root chain is a TPU hash aggregate over stampable parquet "
    "sources, the pre-final MERGED aggregate partial state is retained "
    "alongside the result (both under serve.resultCache.maxBytes). "
    "When a later lookup finds the sources drifted by pure APPEND "
    "(every old file's (path, mtime_ns, size) stamp unchanged, new "
    "files added), the SAME plan re-runs its update phase over only "
    "the delta files, merges with the retained partials, and "
    "finalizes — recompute cost proportional to the delta, not the "
    "dataset. Any other drift (rewrite / shrink / delete / mtime-only "
    "touch) falls back to the full recompute, which stays the "
    "bit-identical correctness oracle (flip this off to revert to "
    "all-or-nothing caching in one knob, the sql.fusion.enabled "
    "pattern).", bool)

SERVE_INCREMENTAL_REFRESH_MS = conf(
    "spark.rapids.tpu.serve.incremental.refreshMs", 0,
    "Poll interval for the background incremental refresher: every "
    "refreshMs it re-stamps the sources of retained cache entries and "
    "delta-refreshes any that drifted by pure append, at low priority "
    "and only while the scheduler has no live queries (the "
    "sched.precompile idle-wait contract) — so interactive hits stay "
    "warm instead of paying the delta on first touch. 0 (default) "
    "disables the thread; lookups still delta-refresh on demand.", int)

SERVE_INCREMENTAL_MAX_TRACKED = conf(
    "spark.rapids.tpu.serve.incremental.maxTracked", 64,
    "How many distinct (plan digest, output names) entries the "
    "incremental maintainer tracks for delta refresh (LRU past it). "
    "Each tracked entry pins its logical plan template; the retained "
    "partial-state tables themselves live in the result cache under "
    "serve.resultCache.maxBytes.", int)

SERVE_STREAM_CHUNK_ROWS = conf(
    "spark.rapids.tpu.serve.stream.chunkRows", 65536,
    "Rows per streamed Arrow result chunk. Each chunk costs one CHUNK "
    "frame and one client credit, so this knob trades per-frame "
    "overhead against backpressure granularity (a slow consumer bounds "
    "the server's read-ahead to its credit window times this).", int)

SERVE_WIRE_MAX_FRAME_BYTES = conf(
    "spark.rapids.tpu.serve.wire.maxFrameBytes", 256 << 20,
    "Upper bound on a single serving wire frame's declared payload "
    "length. A frame header claiming more is a protocol violation "
    "(a hostile or desynced length prefix): the connection is answered "
    "with a typed ServeWireError ERR (reason 'oversized') and torn "
    "down BEFORE any payload allocation happens — body bytes only "
    "ever allocate after the declared length validates under this "
    "bound.", int)

SERVE_WIRE_READ_TIMEOUT_MS = conf(
    "spark.rapids.tpu.serve.wire.readTimeoutMs", 30_000,
    "Per-connection frame-progress deadline on the serving reader: "
    "once the first byte of a frame has arrived, the rest of the "
    "frame must arrive within this bound or the connection is "
    "answered with a typed ERR (reason 'timeout') and closed — the "
    "slowloris defense (a client holding a half-sent frame open "
    "cannot pin a reader thread forever). A connection IDLE at a "
    "frame boundary is never timed out by this knob; idle sessions "
    "are serve.session.idleTimeoutMs territory.", int)

SERVE_WIRE_WRITE_STALL_MS = conf(
    "spark.rapids.tpu.serve.wire.writeStallMs", 60_000,
    "Write-stall deadline on serving-side frame sends (result "
    "streamers and control responses): a send that makes zero "
    "progress for this long — a client that stopped draining its "
    "socket — aborts the connection with a typed ServeWireError "
    "instead of pinning a streamer thread (and its retained result) "
    "in sendall forever. Progress resets the deadline, so a slow but "
    "live consumer is never killed.", int)

SERVE_WIRE_STORM_THRESHOLD = conf(
    "spark.rapids.tpu.serve.wire.stormThreshold", 16,
    "Malformed-frame storm threshold: once this server instance has "
    "counted this many malformed wire frames "
    "(serve.wire.malformedFrames), ONE flight-recorder bundle with "
    "reason 'protocol' is dumped (when obs.recorder.dir is set) so a "
    "hostile or desynced client storm is diagnosable post-hoc. 0 "
    "disables the bundle (counters still move).", int)

SERVE_DRAIN_DEADLINE_MS = conf(
    "spark.rapids.tpu.serve.drain.deadlineMs", 10_000,
    "Default deadline for ServeServer.drain(): the server stops "
    "accepting connections, refuses new queries with a typed "
    "'Draining' error, and gives in-flight result streams this long "
    "to finish; past it they are cancelled with the same typed error "
    "and every connection is torn down leak-audited (streamer threads "
    "joined, admission slots released, credit state dropped). Clients "
    "resume interrupted streams after reconnecting (resume tokens + "
    "chunk sequence numbers).", int)

SERVE_STREAM_RETAIN_BYTES = conf(
    "spark.rapids.tpu.serve.stream.retainBytes", 128 << 20,
    "Byte budget for the retained-stream window: materialized result "
    "tables of in-flight and recently finished streams are retained "
    "(LRU, process-wide — they survive a drain/restart cycle) so a "
    "client that reconnects can resume a stream from its last "
    "received chunk sequence number instead of re-running the query. "
    "An entry is dropped when the client acknowledges the completed "
    "stream, on LRU pressure, or when its session's resume token "
    "ages out.", int)

SERVE_BATCH_ENABLED = conf(
    "spark.rapids.tpu.serve.batch.enabled", True,
    "Coalesce prepared-statement executions: when the same statement "
    "template is bound with different parameters within the batching "
    "window, eligible plan shapes (projection over a parameterized "
    "filter) merge into ONE vectorized execution — each binding's "
    "predicate rides along as a marker column and results split per "
    "client host-side. Literal erasure in the kernel ABI means the "
    "coalesced run is compile-free across binding values. Off reverts "
    "to one execution per bind.", bool)

SERVE_BATCH_WINDOW_MS = conf(
    "spark.rapids.tpu.serve.batch.windowMs", 2,
    "How long an execute of a batch-eligible prepared statement waits "
    "for siblings before flushing (the micro-batching window). A full "
    "batch (batch.maxStatements) flushes immediately.", int)

SERVE_BATCH_MAX_STATEMENTS = conf(
    "spark.rapids.tpu.serve.batch.maxStatements", 16,
    "Upper bound on bindings coalesced into one vectorized execution; "
    "arrivals past it start the next batch.", int)

SERVE_FAULT_PLAN = conf(
    "spark.rapids.tpu.serve.test.faultPlan", "",
    "Deterministic fault-injection plan for serving-plane chaos "
    "testing, e.g. 'seed=7;stream.chunk:drop@3;accept:close@2;"
    "frame.body:corrupt@1'. Same grammar as shuffle.test.faultPlan; "
    "see spark_rapids_tpu/serve/faults.py for the serving injection "
    "points (accept, frame.header, frame.body, stream.chunk, "
    "client.read, session.lookup) and actions (drop, delay, close, "
    "corrupt, truncate, oversize, unknown, slow, fail). Empty "
    "disables injection.")

SERVE_AUTH_TOKENS = conf(
    "spark.rapids.tpu.serve.auth.tokens", "",
    "Comma-separated bearer-token allowlist for the serving wire. "
    "Non-empty: every hello must carry an 'auth_token' field matching "
    "one entry or the connection is refused with a typed AuthFailed "
    "ERR (counted in serve.authFailures) before a session exists. "
    "Empty (default) disables auth — the pre-fleet loopback posture. "
    "The token doubles as the tenant identity the fleet router keys "
    "its per-tenant in-flight quotas on.")

SERVE_TLS_CERT_FILE = conf(
    "spark.rapids.tpu.serve.tls.certFile", "",
    "PEM certificate chain for TLS on the serving listener. Set "
    "together with serve.tls.keyFile to ssl-wrap every accepted "
    "serving connection (clients connect with tls=True); empty "
    "(default) serves plaintext. The obs HTTP endpoint is unaffected.")

SERVE_TLS_KEY_FILE = conf(
    "spark.rapids.tpu.serve.tls.keyFile", "",
    "PEM private key matching serve.tls.certFile. Both must be set "
    "for TLS to engage; setting exactly one raises at server start "
    "rather than silently serving plaintext.")

FLEET_ENABLED = conf(
    "spark.rapids.tpu.fleet.enabled", False,
    "Join this session to a serve fleet: attach the shared cache "
    "plane at fleet.store.url — statement-template registry, "
    "plan-digest result cache (stamp-validated at lookup, so "
    "catalog/file drift invalidates fleet-wide), retained aggregate "
    "partials, and the persistent XLA compile cache directory — so N "
    "replicas behind fleet/router.py serve as one tier. Off "
    "(default): no store is attached and the single-process serve "
    "path is byte-for-byte unchanged.", bool)

FLEET_STORE_URL = conf(
    "spark.rapids.tpu.fleet.store.url", "",
    "Shared-store endpoint for the fleet cache plane: "
    "'file:///path/to/dir' (file-backed, the default deployment "
    "shape — atomic temp+rename puts, safe for same-host and "
    "shared-filesystem fleets) or 'tcp://host:port' (the in-memory "
    "fleet.store.StoreServer, for tests). Required when "
    "fleet.enabled=true.")

FLEET_STORE_MAX_ENTRY_BYTES = conf(
    "spark.rapids.tpu.fleet.store.maxEntryBytes", 64 << 20,
    "Largest single result-cache entry published to the shared "
    "store; bigger results stay local-only (they still serve local "
    "hits). Bounds both the store's disk/memory footprint and the "
    "deserialization cost a sibling replica pays on a shared hit.",
    int)

FLEET_ROUTER_HEALTH_POLL_MS = conf(
    "spark.rapids.tpu.fleet.router.healthPollMs", 500,
    "How often the fleet router polls each replica's /healthz and "
    "/metrics: drain state takes a replica out of placement rotation "
    "(satellite: /healthz now reports "
    "{state: serving|draining|drained, inflight}), and the sched "
    "queued/running gauges feed least-loaded placement for new "
    "sessions.", int)

FLEET_TENANT_MAX_INFLIGHT = conf(
    "spark.rapids.tpu.fleet.tenant.maxInFlight", 0,
    "Router-level cap on concurrently in-flight queries per tenant "
    "identity (the auth token, or the client address when auth is "
    "off) ACROSS the whole fleet — a layer above the per-session "
    "serve.session.maxInFlight each replica enforces. Past it the "
    "router answers the request with a typed TenantQuotaExceeded ERR "
    "without forwarding. 0 (default) disables the fleet-level "
    "quota.", int)

OBS_COMPILE_ENABLED = conf(
    "spark.rapids.tpu.obs.compile.enabled", True,
    "Record a CompileEvent for every first (kernel, arg-shape) call "
    "through the process kernel cache — the compile observatory "
    "(obs/compile.py): kernel family, canonical shape/dtype signature, "
    "compile wall, cache tier (in-memory hit / persistent-"
    "XLA-cache reload / fresh compile), and the triggering query id + "
    "plan digest. Events land in a bounded ring with process-lifetime "
    "per-family aggregates, surface as kernel.compile spans in the "
    "Chrome trace, a 'compile' QueryProfile section, kernel.compile.* "
    "registry counters, and the /compiles endpoint route. Disabled, "
    "the kernel dispatch path pays one bool check.", bool)

OBS_COMPILE_RING_EVENTS = conf(
    "spark.rapids.tpu.obs.compile.ringEvents", 4096,
    "Capacity of the compile observatory's event ring; the oldest "
    "events drop past it (process-lifetime aggregates — per-family "
    "program/signature counts, compile wall — are unaffected).", int)

OBS_COMPILE_STORM_THRESHOLD = conf(
    "spark.rapids.tpu.obs.compile.stormThreshold", 64,
    "Programs one query may compile before the observatory flags a "
    "'compile storm': a flight-recorder compile.storm event (once per "
    "query) plus the kernel.compile.storms counter. The TPC-DS-99 "
    "suite averages ~27 programs/query cold (PERF.md compile bill), "
    "so a query past this threshold is hitting pathological shape "
    "churn.", int)

OBS_COMPILE_CORPUS_PATH = conf(
    "spark.rapids.tpu.obs.compile.corpusPath", "",
    "Append-mode JSONL file for the precompile corpus: on the first "
    "completion of each distinct plan digest that compiled at least "
    "one program, one record {plan_digest, query_id, programs: "
    "[{family, key, signature}]} is appended — exactly the "
    "replay artifact an AOT precompile service needs to warm the "
    "persistent XLA cache off the serving path (ROADMAP item 2). "
    "Empty (default) disables corpus emission.")

OBS_COMPILE_CORPUS_REPLAY = conf(
    "spark.rapids.tpu.obs.compile.corpusReplay", True,
    "Attach a replay payload (pickled traceable + abstract argument "
    "shapes, base64) to each corpus program record so the AOT "
    "precompile service (sched/precompile.py) can re-lower and "
    "re-compile the exact program in a fresh process without data or "
    "plans. Costs one pickle per first (kernel, shape) call while a "
    "corpusPath is configured; programs whose traceable cannot pickle "
    "are recorded without a payload and counted as skipped at replay. "
    "Donation-built kernels never carry a payload — they are barred "
    "from the persistent cache (see sql.fusion.donateInputs).", bool)

SCHED_PRECOMPILE_ENABLED = conf(
    "spark.rapids.tpu.sched.precompile.enabled", False,
    "Start the background AOT precompile service at session init "
    "(sched/precompile.py): replays the precompile corpus "
    "(sched.precompile.corpusPath, falling back to "
    "obs.compile.corpusPath) through jax lower+compile at low priority "
    "— pausing whenever the scheduler has live queries — so a replica "
    "restart warms the persistent XLA cache off the serving path and "
    "serves warm from query one.", bool)

SCHED_PRECOMPILE_CORPUS_PATH = conf(
    "spark.rapids.tpu.sched.precompile.corpusPath", "",
    "Corpus JSONL the precompile service replays (a file written by a "
    "previous process via obs.compile.corpusPath). A DIRECTORY "
    "replays every *.jsonl inside it — the fleet warm-join shape, "
    "where each replica appends its own corpus file under the shared "
    "store's corpus/ directory and a joining replica replays them "
    "all. Empty: falls back to this session's obs.compile.corpusPath.")

SCHED_PRECOMPILE_IDLE_WAIT_MS = conf(
    "spark.rapids.tpu.sched.precompile.idleWaitMs", 25,
    "How long the precompile service sleeps between corpus programs, "
    "and while waiting for the scheduler to drain live queries before "
    "compiling the next one — the low-priority contract keeping "
    "replay off the serving path.", int)

OBS_PROFILE_ENABLED = conf(
    "spark.rapids.tpu.obs.profile.enabled", True,
    "Assemble a QueryProfile after every action (annotated plan tree, "
    "wall breakdown, per-query registry delta, explain report) — "
    "surfaced via session.last_query_profile(), "
    "DataFrame.explain('profile'), and query listeners.", bool)


class RapidsTpuConf:
    """Accessor over a settings map; analog of ``new RapidsConf(conf)``."""

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._settings: Dict[str, Any] = dict(settings or {})

    def get(self, entry: ConfEntry) -> Any:
        return entry.get(self)

    def get_raw(self, key: str, default: Any = None) -> Any:
        return self._settings.get(key, default)

    def set(self, key: str, value: Any) -> "RapidsTpuConf":
        self._settings[key] = value
        return self

    def is_operator_enabled(self, key: str, incompat: bool,
                            disabled_by_default: bool) -> bool:
        """Per-operator kill-switch lookup (reference: GpuOverrides.scala:131)."""
        raw = self._settings.get(key)
        if raw is not None:
            if isinstance(raw, str):
                return raw.strip().lower() in ("true", "1", "yes")
            return bool(raw)
        if disabled_by_default:
            return False
        if incompat:
            return self.get(INCOMPATIBLE_OPS)
        return True

    # -- convenience properties used widely ---------------------------------
    @property
    def sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED)

    @property
    def explain(self) -> str:
        return str(self.get(EXPLAIN)).upper()

    @property
    def batch_size_bytes(self) -> int:
        return self.get(BATCH_SIZE_BYTES)

    @property
    def shuffle_partitions(self) -> int:
        return self.get(SHUFFLE_PARTITIONS)

    @property
    def test_enabled(self) -> bool:
        return self.get(TEST_ENABLED)

    @property
    def test_allowed_non_tpu(self) -> List[str]:
        raw = self.get(TEST_ALLOWED_NON_TPU) or ""
        return [s.strip() for s in raw.split(",") if s.strip()]


def registered_entries() -> List[ConfEntry]:
    with _REGISTRY_LOCK:
        return sorted(_REGISTRY.values(), key=lambda e: e.key)


def generate_docs() -> str:
    """Emit markdown docs for all keys.

    Analog of ``RapidsConf.main`` -> docs/configs.md ("Generated by
    RapidsConf.help. DO NOT EDIT!", reference RapidsConf.scala:885).
    """
    lines = [
        "# spark-rapids-tpu Configuration",
        "",
        "<!-- Generated by spark_rapids_tpu.config.generate_docs. DO NOT EDIT! -->",
        "",
        "| Name | Default | Description |",
        "|---|---|---|",
    ]
    for e in registered_entries():
        if e.internal:
            continue
        doc = e.doc.replace("|", "\\|")
        lines.append(f"| `{e.key}` | {e.default!r} | {doc} |")
    return "\n".join(lines) + "\n"


if __name__ == "__main__":  # python -m spark_rapids_tpu.config > docs/configs.md
    print(generate_docs(), end="")
