"""Typed config registry — the ``spark.rapids.*`` namespace.

Mirrors the reference's single-file typed ConfEntry builder DSL
[REF: sql-plugin/../RapidsConf.scala :: RapidsConf, ConfEntry, ConfBuilder]:
entries are declared once with type/doc/default, validated at startup, and
``docs/configs.md`` is generated from the registry so docs never drift.

The config namespace is kept byte-compatible with the reference
(``spark.rapids.sql.enabled`` etc.) so existing spark-rapids job configs
carry over; TPU-specific knobs live under ``spark.rapids.tpu.*``.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, List, Optional

_SIZE_RE = re.compile(r"^(\d+)([kKmMgGtT]?)[bB]?$")
_SIZE_MULT = {"": 1, "k": 1 << 10, "m": 1 << 20, "g": 1 << 30, "t": 1 << 40}


def parse_bytes(v) -> int:
    if isinstance(v, int):
        return v
    m = _SIZE_RE.match(str(v).strip())
    if not m:
        raise ValueError(f"cannot parse byte size {v!r}")
    return int(m.group(1)) * _SIZE_MULT[m.group(2).lower()]


def _parse_bool(v) -> bool:
    if isinstance(v, bool):
        return v
    s = str(v).strip().lower()
    if s in ("true", "1", "yes"):
        return True
    if s in ("false", "0", "no"):
        return False
    raise ValueError(f"cannot parse boolean {v!r}")


@dataclasses.dataclass
class ConfEntry:
    key: str
    doc: str
    default: Any
    converter: Callable[[Any], Any]
    category: str = "sql"
    internal: bool = False
    startup_only: bool = False
    checker: Optional[Callable[[Any], bool]] = None
    check_msg: str = ""

    def convert(self, raw):
        v = self.converter(raw)
        if self.checker is not None and not self.checker(v):
            hint = f" ({self.check_msg})" if self.check_msg else ""
            raise ValueError(f"invalid value {v!r} for {self.key}{hint}")
        return v


class _Registry:
    def __init__(self):
        self.entries: Dict[str, ConfEntry] = {}

    def register(self, e: ConfEntry):
        if e.key in self.entries:
            raise ValueError(f"duplicate conf key {e.key}")
        self.entries[e.key] = e
        return e


REGISTRY = _Registry()


class ConfBuilder:
    """``conf(key).doc(...).boolean().create_with_default(x)`` builder DSL."""

    def __init__(self, key: str):
        self._key = key
        self._doc = ""
        self._category = "sql"
        self._internal = False
        self._startup = False
        self._converter: Callable = str
        self._checker = None
        self._check_msg = ""

    def doc(self, d: str) -> "ConfBuilder":
        self._doc = d
        return self

    def category(self, c: str) -> "ConfBuilder":
        self._category = c
        return self

    def internal(self) -> "ConfBuilder":
        self._internal = True
        return self

    def startup_only(self) -> "ConfBuilder":
        self._startup = True
        return self

    def boolean(self) -> "ConfBuilder":
        self._converter = _parse_bool
        return self

    def integer(self) -> "ConfBuilder":
        self._converter = int
        return self

    def double(self) -> "ConfBuilder":
        self._converter = float
        return self

    def string(self) -> "ConfBuilder":
        self._converter = str
        return self

    def bytes(self) -> "ConfBuilder":
        self._converter = parse_bytes
        return self

    def check(self, fn, msg="") -> "ConfBuilder":
        self._checker = fn
        self._check_msg = msg
        return self

    def create_with_default(self, default) -> ConfEntry:
        return REGISTRY.register(
            ConfEntry(
                key=self._key,
                doc=self._doc,
                default=default,
                converter=self._converter,
                category=self._category,
                internal=self._internal,
                startup_only=self._startup,
                checker=self._checker,
                check_msg=self._check_msg,
            )
        )


def conf(key: str) -> ConfBuilder:
    return ConfBuilder(key)


# ---------------------------------------------------------------------------
# Core entries (the reference's most load-bearing knobs, same keys)
# ---------------------------------------------------------------------------

SQL_ENABLED = (
    conf("spark.rapids.sql.enabled")
    .doc("Enable columnar acceleration on TPU. When false every operator "
         "runs on the CPU fallback path (the correctness oracle).")
    .boolean()
    .create_with_default(True)
)

EXPLAIN = (
    conf("spark.rapids.sql.explain")
    .doc("Explain mode for plan conversion: NONE, ALL, or NOT_ON_GPU "
         "(log every operator that could not be accelerated and why).")
    .string()
    .check(lambda v: v.upper() in ("NONE", "ALL", "NOT_ON_GPU",
                                   "NOT_ON_TPU"),
           "one of NONE, ALL, NOT_ON_GPU, NOT_ON_TPU")
    .create_with_default("NONE")
)

TEST_ENABLED = (
    conf("spark.rapids.sql.test.enabled")
    .doc("Test mode: raise instead of silently falling back to CPU for any "
         "operator not in the allow-list (see test.allowedNonGpu).")
    .category("test")
    .boolean()
    .create_with_default(False)
)

TEST_ALLOWED_NON_GPU = (
    conf("spark.rapids.sql.test.allowedNonGpu")
    .doc("Comma-separated operator class names permitted to fall back to "
         "CPU when test.enabled is on.")
    .category("test")
    .string()
    .create_with_default("")
)

BATCH_SIZE_BYTES = (
    conf("spark.rapids.sql.batchSizeBytes")
    .doc("Target device batch size; coalescing concatenates small batches "
         "up to this size. TPU default is smaller than the reference's 1g "
         "because padded static-shape buckets amplify footprint.")
    .bytes()
    .create_with_default(512 << 20)
)

BATCH_ROWS = (
    conf("spark.rapids.tpu.batchRows")
    .doc("Target device batch row count. Row counts are padded up to "
         "power-of-two buckets so XLA executables cache per (op, schema, "
         "bucket).")
    .integer()
    .create_with_default(1 << 20)
)

MIN_BUCKET_ROWS = (
    conf("spark.rapids.tpu.minBucketRows")
    .doc("Smallest static-shape row bucket.")
    .internal()
    .integer()
    .create_with_default(1 << 10)
)

AGG_BUCKET_ROWS = (
    conf("spark.rapids.tpu.agg.bucketRows")
    .doc("Grouped aggregates coalesce input batches up to this many live "
         "rows before each partial-pass kernel. 0 (default) disables "
         "coalescing: each concat costs a count round trip plus a "
         "gather, which can exceed the saved per-chain dispatches (no "
         "measurement on an attached chip yet). With many tiny partial "
         "batches, try 128k-512k.")
    .integer()
    .create_with_default(0)
)

AGG_SKIP_RATIO = (
    conf("spark.rapids.sql.agg.skipAggPassReductionRatio")
    .doc("When a grouped aggregate's first partial pass keeps more than "
         "this fraction of its input rows (grouping keys are nearly "
         "unique), later batches skip the per-batch sort+reduce and "
         "emit raw update buffers; the merge pass does the single real "
         "reduction [REF: GpuHashAggregateExec "
         "skipAggPassReductionRatio]. 1.0 disables skipping.")
    .double()
    .check(lambda v: 0.0 < v <= 1.0, "in (0, 1]")
    .create_with_default(0.9)
)

CONCURRENT_TASKS = (
    conf("spark.rapids.sql.concurrentGpuTasks")
    .doc("Number of tasks that may hold the device semaphore concurrently "
         "[REF: GpuSemaphore.scala].")
    .category("memory")
    .integer()
    .create_with_default(2)
)

MEMORY_FRACTION = (
    conf("spark.rapids.memory.gpu.allocFraction")
    .doc("Fraction of device HBM the budget arbiter may hand out before "
         "synchronous spill kicks in.")
    .category("memory")
    .double()
    .check(lambda v: 0.0 < v <= 1.0, "in (0, 1]")
    .create_with_default(0.85)
)

POOL_SIZE = (
    conf("spark.rapids.tpu.memory.poolSize")
    .doc("Explicit device memory budget in bytes; 0 means derive from "
         "allocFraction of detected HBM.")
    .category("memory")
    .bytes()
    .create_with_default(0)
)

HOST_SPILL_STORAGE = (
    conf("spark.rapids.memory.host.spillStorageSize")
    .doc("Host memory limit for spilled device buffers before they go to "
         "disk.")
    .category("memory")
    .bytes()
    .create_with_default(4 << 30)
)

SPILL_PATH = (
    conf("spark.rapids.tpu.spillPath")
    .doc("Directory for disk-tier spill files.")
    .category("memory")
    .string()
    .create_with_default("/tmp/tpuq-spill")
)

RETRY_MAX = (
    conf("spark.rapids.tpu.retry.maxAttempts")
    .doc("Max retry attempts per device/IO step before the engine gives "
         "up (OOM retries in the memory arbiter and every resilience "
         "failure domain share this one policy) "
         "[REF: RmmRapidsRetryIterator.scala :: withRetry].")
    .category("memory")
    .integer()
    .check(lambda v: v >= 1, "at least 1")
    .create_with_default(8)
)

RETRY_BACKOFF_BASE_MS = (
    conf("spark.rapids.tpu.retry.backoffBaseMs")
    .doc("Base delay for the retry policy's exponential backoff: attempt "
         "n sleeps ~base*2^(n-1) ms (capped by retry.backoffMaxMs, "
         "scaled by deterministic seeded jitter). 0 disables sleeping.")
    .category("memory")
    .double()
    .check(lambda v: v >= 0.0, "non-negative")
    .create_with_default(5.0)
)

RETRY_BACKOFF_MAX_MS = (
    conf("spark.rapids.tpu.retry.backoffMaxMs")
    .doc("Upper bound on a single retry backoff sleep in milliseconds.")
    .category("memory")
    .double()
    .check(lambda v: v >= 0.0, "non-negative")
    .create_with_default(1000.0)
)

RETRY_BUDGET_PER_QUERY = (
    conf("spark.rapids.tpu.retry.budgetPerQuery")
    .doc("Total retries one query may spend across every failure domain "
         "before further faults are treated as exhausted (degrade or "
         "fail instead of retry-storming). 0 disables the budget.")
    .category("memory")
    .integer()
    .check(lambda v: v >= 0, "non-negative")
    .create_with_default(64)
)

RETRY_HOST_DEGRADE = (
    conf("spark.rapids.tpu.retry.hostDegrade.enabled")
    .doc("On retry exhaustion in a degradable failure domain (execute, "
         "transfer, compile, spill_write, collective), trip the per-op "
         "circuit breaker and re-run the step on the host path instead "
         "of failing the query. Disable to surface a domain-tagged "
         "terminal error instead.")
    .category("memory")
    .boolean()
    .create_with_default(True)
)

SHUFFLE_MODE = (
    conf("spark.rapids.shuffle.mode")
    .doc("Shuffle transport: MULTITHREADED (host-path serialization, works "
         "everywhere), ICI (collective all_to_all across the slice — the "
         "UCX analog), or CACHE_ONLY.")
    .category("shuffle")
    .string()
    .check(lambda v: v.upper() in ("MULTITHREADED", "ICI", "CACHE_ONLY"),
           "one of MULTITHREADED, ICI, CACHE_ONLY")
    .create_with_default("MULTITHREADED")
)

EXCHANGE_MODE = (
    conf("spark.rapids.tpu.exchange.mode")
    .doc("ICI exchange transport: compiled (device-resident "
         "prepare/boundary SPMD programs — shuffle is one collective "
         "launch per stage seam), host (pin every exchange to the "
         "host-shuffle transport, the collective domain's degrade "
         "target), or auto (compiled when the mesh supports it). Only "
         "consulted when spark.rapids.shuffle.mode=ICI.")
    .category("shuffle")
    .string()
    .check(lambda v: v.lower() in ("compiled", "host", "auto"),
           "one of compiled, host, auto")
    .create_with_default("auto")
)

SHUFFLE_THREADS = (
    conf("spark.rapids.shuffle.multiThreaded.writer.threads")
    .doc("Serializer thread pool size for MULTITHREADED shuffle.")
    .category("shuffle")
    .integer()
    .create_with_default(4)
)

MULTITHREADED_READ_THREADS = (
    conf("spark.rapids.sql.multiThreadedRead.numThreads")
    .doc("Thread pool size for the MULTITHREADED parquet reader "
         "(concurrent host decode + H2D per scan partition).")
    .category("io")
    .integer()
    .check(lambda v: v >= 1, ">= 1")
    .create_with_default(4)
)

SHUFFLE_PARTITIONS = (
    conf("spark.sql.shuffle.partitions")
    .doc("Default shuffle partition count (Spark core key, honored here).")
    .category("shuffle")
    .integer()
    .create_with_default(16)
)

METRICS_LEVEL = (
    conf("spark.rapids.sql.metrics.level")
    .doc("Metric verbosity: ESSENTIAL, MODERATE, DEBUG.")
    .string()
    .check(lambda v: v.upper() in ("ESSENTIAL", "MODERATE", "DEBUG"),
           "one of ESSENTIAL, MODERATE, DEBUG")
    .create_with_default("MODERATE")
)

INCOMPATIBLE_OPS = (
    conf("spark.rapids.sql.incompatibleOps.enabled")
    .doc("Enable operators whose results differ from Spark CPU in corner "
         "cases (documented per op).")
    .boolean()
    .create_with_default(False)
)

HAS_NANS = (
    conf("spark.rapids.sql.hasNans")
    .doc("Assume float data may contain NaNs (affects agg/join/sort "
         "eligibility for some ops).")
    .boolean()
    .create_with_default(True)
)

CAST_STRING_TO_FLOAT = (
    conf("spark.rapids.sql.castStringToFloat.enabled")
    .doc("Allow device string→float/double casts. Results can differ "
         "from Java's parseDouble by 1 ulp beyond 15 significant digits "
         "(same caveat as the reference's flag of this name).")
    .boolean()
    .create_with_default(False)
)

BROADCAST_THRESHOLD = (
    conf("spark.sql.autoBroadcastJoinThreshold")
    .doc("Max estimated size of a join side to broadcast it (gathered "
         "once, reused per stream partition — no exchange). -1 or 0 "
         "disables broadcast joins. Spark core key, honored here.")
    .bytes()
    .create_with_default(10 << 20)
)

PARQUET_DEVICE_DICT = (
    conf("spark.rapids.tpu.parquet.deviceDictDecode")
    .doc("Read parquet string columns dictionary-encoded and expand "
         "them ON DEVICE (indices + a small dictionary ride the "
         "host→device transfer instead of full byte matrices; the "
         "expansion is a device gather). The decode-on-device half of "
         "the reference's GPU parquet path that makes sense on TPU — "
         "decompression stays on host (no TPU decompress engine). "
         "[REF: GpuParquetScan.scala; SURVEY N6 phase-2]")
    .category("io")
    .boolean()
    .create_with_default(True)
)

JOIN_TARGET_ROWS = (
    conf("spark.rapids.tpu.join.targetRows")
    .doc("Row-capacity cap for one in-core sort-merge join. When either "
         "gathered side exceeds this many rows the join proactively "
         "hash-sub-partitions both sides ([REF: GpuSubPartitionHashJoin] "
         "— but size-driven, not OOM-reactive), recursing with fresh "
         "hash seeds on still-oversized sub-partitions, so sort/search "
         "kernels stay at or below the cap (exception: a single hot key "
         "cannot be spread by any key hash; after bounded recursion "
         "such a pair joins in-core, and the build side of a broadcast "
         "join is bounded by the broadcast byte threshold rather than "
         "this row cap — its streamed side honors the cap by its live "
         "rows: batches shrunk to their live buckets and joined in-core "
         "once when the live rows fit, bounded groups only when they "
         "exceed it). XLA compile cost grows "
         "superlinearly with bucket size, so this bounds cold-compile "
         "time as well as memory. Join outputs are also re-batched to "
         "spark.rapids.tpu.batchRows chunks so downstream kernels never "
         "inherit an oversized bucket.")
    .integer()
    .create_with_default(1 << 18)
)

UDF_COMPILER_ENABLED = (
    conf("spark.rapids.sql.udfCompiler.enabled")
    .doc("Compile simple python UDFs (arithmetic, comparisons, "
         "conditionals, basic string methods) into device expressions "
         "via AST lowering — the compiled UDF fuses into the XLA "
         "program instead of crossing the arrow bridge. UDFs outside "
         "the subset silently fall back to the bridge.")
    .boolean()
    .create_with_default(False)
)

EXECUTOR_ID = (
    conf("spark.rapids.executor.id")
    .doc("This process's executor index in a multi-executor run "
         "(0-based). With executor.count > 1 the session joins the "
         "global device mesh via jax.distributed and scans serve only "
         "this executor's slice of source partitions.")
    .category("distributed")
    .startup_only()
    .integer()
    .create_with_default(0)
)

EXECUTOR_COUNT = (
    conf("spark.rapids.executor.count")
    .doc("Number of executor processes in the slice. >1 activates "
         "multi-executor mode: requires shuffle.mode=ICI, the "
         "jax.distributed coordinator address and the shuffle "
         "rendezvous address.")
    .category("distributed")
    .startup_only()
    .integer()
    .create_with_default(1)
)

COORDINATOR_ADDRESS = (
    conf("spark.rapids.executor.coordinator.address")
    .doc("host:port of the jax.distributed coordinator (process 0 "
         "binds it). Required when executor.count > 1.")
    .category("distributed")
    .startup_only()
    .string()
    .create_with_default("")
)

RENDEZVOUS_ADDRESS = (
    conf("spark.rapids.shuffle.rendezvous.address")
    .doc("host:port of the shuffle RendezvousCoordinator (driver-side "
         "barrier service). ICI exchanges use it for cross-process "
         "shape agreement and collective entry; required when "
         "executor.count > 1. [REF: RapidsShuffleInternalManagerBase "
         "— the MapOutputTracker-coordination analog]")
    .category("distributed")
    .startup_only()
    .string()
    .create_with_default("")
)

RENDEZVOUS_TIMEOUT = (
    conf("spark.rapids.shuffle.rendezvous.timeoutSec")
    .doc("Legacy alias for spark.rapids.tpu.rendezvous.timeoutMs "
         "(seconds). When set explicitly it wins over the timeoutMs "
         "key; prefer the millisecond key for new deployments.")
    .category("distributed")
    .double()
    .create_with_default(120.0)
)

RENDEZVOUS_TIMEOUT_MS = (
    conf("spark.rapids.tpu.rendezvous.timeoutMs")
    .doc("Deadline in milliseconds for every rendezvous barrier "
         "(allgather/enter). On expiry the coordinator fails ALL "
         "waiters of the stage (fail-together: nobody enters a "
         "collective that cannot complete — a hung ICI collective "
         "would wedge the whole slice); survivors then retry the "
         "stage at the next epoch under the shared retry policy.")
    .category("distributed")
    .integer()
    .check(lambda v: v > 0, "positive")
    .create_with_default(60000)
)

RENDEZVOUS_HEARTBEAT_MS = (
    conf("spark.rapids.tpu.rendezvous.heartbeatMs")
    .doc("Executor liveness heartbeat period. Each executor process "
         "registers with the rendezvous coordinator and renews its "
         "lease at this period; see rendezvous.leaseMs for the "
         "expiry. 0 disables the heartbeat (no liveness tracking).")
    .category("distributed")
    .integer()
    .check(lambda v: v >= 0, "non-negative")
    .create_with_default(1500)
)

RENDEZVOUS_LEASE_MS = (
    conf("spark.rapids.tpu.rendezvous.leaseMs")
    .doc("Heartbeat lease: an executor that has not heartbeated for "
         "this long is declared dead, and the coordinator immediately "
         "poisons every in-flight and future rendezvous stage with a "
         "peer-tagged abort so survivors unwind in ~one lease instead "
         "of independent full stage deadlines. Must comfortably "
         "exceed heartbeatMs (default gives 10 beats per lease).")
    .category("distributed")
    .integer()
    .check(lambda v: v > 0, "positive")
    .create_with_default(15000)
)

RENDEZVOUS_SOCKET_TIMEOUT_MS = (
    conf("spark.rapids.tpu.rendezvous.socketTimeoutMs")
    .doc("Socket receive timeout for coordinator handler threads. A "
         "half-open client connection that never sends its request is "
         "dropped after this long instead of pinning a handler thread "
         "forever.")
    .category("distributed")
    .integer()
    .check(lambda v: v > 0, "positive")
    .create_with_default(10000)
)

ADAPTIVE_ENABLED = (
    conf("spark.sql.adaptive.enabled")
    .doc("Adaptive query execution: shuffle-read coalescing of small "
         "partitions and splitting of skewed ones, planned from measured "
         "partition sizes (Spark core key, honored here).")
    .category("aqe")
    .boolean()
    .create_with_default(True)
)

ADVISORY_PARTITION_SIZE = (
    conf("spark.sql.adaptive.advisoryPartitionSizeInBytes")
    .doc("Target bytes per shuffle-read partition after AQE coalescing/"
         "skew-splitting (Spark core key, honored here).")
    .category("aqe")
    .bytes()
    .create_with_default(64 << 20)
)

ADAPTIVE_PLANE_ENABLED = (
    conf("spark.rapids.tpu.adaptive.enabled")
    .doc("Master switch for the adaptive execution plane "
         "(spark_rapids_tpu/adaptive/): a cost model + replanner that "
         "spends the stats plane's recorded rows/bytes/partition sizes "
         "to rewrite the physical plan at stage boundaries — broadcast "
         "vs shuffled join strategy, skewed-partition splitting, and "
         "dynamic batch retargeting.  Each decision has its own "
         "sub-gate below; every decision taken is counted in "
         "tpuq_adaptive_decisions_total{kind} and rendered in EXPLAIN "
         "ANALYZE as adaptive=...")
    .category("aqe")
    .boolean()
    .create_with_default(False)
)

ADAPTIVE_SKEW_SPLIT = (
    conf("spark.rapids.tpu.adaptive.skewSplit.enabled")
    .doc("Adaptive skew splitting: when a shuffle exchange's recorded "
         "partition sizes show a skew factor above "
         "adaptive.skewThreshold, split the hot stream-side "
         "partition(s) into rank-interleaved sub-partitions and "
         "replicate the build side's matching partition, so one "
         "straggler stops serializing the stage.  Unlike hash "
         "sub-partitioning this spreads a SINGLE hot key.  Requires "
         "adaptive.enabled; inner/left/left_semi/left_anti joins only.")
    .category("aqe")
    .boolean()
    .create_with_default(True)
)

ADAPTIVE_SKEW_THRESHOLD = (
    conf("spark.rapids.tpu.adaptive.skewThreshold")
    .doc("Skew factor (hottest partition / mean) above which adaptive "
         "skew splitting triggers.  0 inherits "
         "spark.rapids.tpu.stats.skewThreshold so the replanner splits "
         "exactly the partitions the stats plane flags as SKEWED.")
    .category("aqe")
    .double()
    .check(lambda v: v >= 0, "non-negative")
    .create_with_default(0.0)
)

ADAPTIVE_MAX_SPLITS = (
    conf("spark.rapids.tpu.adaptive.maxSplitsPerPartition")
    .doc("Upper bound on the number of rank-interleaved sub-partitions "
         "one hot partition may be split into — caps task fan-out (and "
         "build-side replication cost) no matter how hot the key is.")
    .category("aqe")
    .integer()
    .check(lambda v: v >= 2, "at least 2")
    .create_with_default(8)
)

ADAPTIVE_HISTORY_PATH = (
    conf("spark.rapids.tpu.adaptive.historyPath")
    .doc("JSONL profile store consulted for warm-query join decisions: "
         "the most recent recorded build-side bytes for a join's stable "
         "plan signature decides broadcast vs shuffled WITHOUT "
         "re-measuring.  Empty inherits spark.rapids.tpu.stats.storePath "
         "(decisions recorded there feed the next run automatically).")
    .category("aqe")
    .string()
    .create_with_default("")
)

DPP_ENABLED = (
    conf("spark.sql.optimizer.dynamicPartitionPruning.enabled")
    .doc("Dynamic partition pruning: joins on a hive-partition column "
         "evaluate the build side's distinct keys first and skip "
         "non-matching files of the probe-side scan (Spark core key, "
         "honored here).")
    .category("aqe")
    .boolean()
    .create_with_default(True)
)

ANSI_ENABLED = (
    conf("spark.sql.ansi.enabled")
    .doc("ANSI mode: arithmetic overflow and invalid casts raise instead "
         "of returning null (Spark core key, honored here).")
    .boolean()
    .create_with_default(False)
)

LORE_TAG = (
    conf("spark.rapids.sql.lore.tag")
    .doc("Exec class name (e.g. TpuSortMergeJoinExec) whose INPUT batches "
         "are dumped for offline replay [REF: GpuLore]. Empty disables.")
    .category("test")
    .string()
    .create_with_default("")
)

LORE_DUMP_PATH = (
    conf("spark.rapids.sql.lore.dumpPath")
    .doc("Directory for LORE dumps (parquet batches + meta).")
    .category("test")
    .string()
    .create_with_default("/tmp/tpuq-lore")
)

MEMORY_DEBUG = (
    conf("spark.rapids.memory.gpu.debug")
    .doc("NONE or STDOUT: track every spillable registration with its "
         "creation stack and report LEAK DETECTED for batches never "
         "closed [REF: cudf MemoryCleaner refcount debugging].")
    .category("memory")
    .string()
    .check(lambda v: v.upper() in ("NONE", "STDOUT"), "NONE or STDOUT")
    .create_with_default("NONE")
)

PROFILE_ENABLED = (
    conf("spark.rapids.profile.enabled")
    .doc("Capture a per-query device profile (jax/xplane trace, viewable "
         "in TensorBoard/XProf) [REF: spark-rapids-jni profiler].  With "
         "the tracer on (attribution, the default, or trace.enabled) the "
         "query's spans are host events tpuq.<op>:<stage> in the same "
         "xplane, on the device ops' clock.")
    .boolean()
    .create_with_default(False)
)

PROFILE_PATH = (
    conf("spark.rapids.profile.path")
    .doc("Directory for profile captures.")
    .string()
    .create_with_default("/tmp/tpuq-profile")
)

TRACE_ENABLED = (
    conf("spark.rapids.sql.trace.enabled")
    .doc("Per-query span tracing (the NVTX-range analog): every exec's "
         "partition pump and internal stages (compile, transfer, compute, "
         "collective) record spans, reduced to a per-operator self-time "
         "vs total-time rollup in the query log.  The spans themselves "
         "are on the profiler's timeline wherever a jax profiler session "
         "records (spark.rapids.profile.enabled).")
    .boolean()
    .create_with_default(False)
)

QUERY_LOG_PATH = (
    conf("spark.rapids.sql.queryLog.path")
    .doc("JSONL file appended with one entry per executed query: plan "
         "tree, device/fallback report, all metrics at their levels, span "
         "rollup, and cross-links to trace/profile/LORE artifacts. Empty "
         "disables the file (session.query_history() still records).")
    .string()
    .create_with_default("")
)

STATS_ENABLED = (
    conf("spark.rapids.tpu.stats.enabled")
    .doc("Per-operator runtime statistics (the stats plane): every exec "
         "pump boundary records observed rows/batches/bytes and batch-"
         "shape histograms, exchanges record per-partition sizes with a "
         "skew factor, and df.explain('analyze') / "
         "session.last_query_profile() surface the result. Off by "
         "default — each pumped device batch pays one device sync for "
         "its live-row count; df.explain('analyze') enables it for its "
         "own execution regardless.")
    .category("observability")
    .boolean()
    .create_with_default(False)
)

STATS_LEVEL = (
    conf("spark.rapids.tpu.stats.level")
    .doc("BASIC records rows/batches/bytes and batch-shape histograms; "
         "FULL adds per-column observed null ratios (one extra device "
         "sync per nullable column per batch).")
    .category("observability")
    .string()
    .check(lambda v: v.upper() in ("BASIC", "FULL"), "BASIC or FULL")
    .create_with_default("BASIC")
)

STATS_STORE_PATH = (
    conf("spark.rapids.tpu.stats.storePath")
    .doc("JSONL profile store appended with one record per executed "
         "query: per-operator observed stats keyed by a stable plan-"
         "node signature, plus exchange skew summaries. Read by "
         "python -m spark_rapids_tpu.utils.profile (top/skew/diff) and "
         "consultable by future planners across runs. Empty disables.")
    .category("observability")
    .string()
    .create_with_default("")
)

STATS_SKEW_THRESHOLD = (
    conf("spark.rapids.tpu.stats.skewThreshold")
    .doc("An exchange partition-size skew factor (max/mean) above this "
         "is reported as skewed in profiles, explain('analyze') and "
         "the profiler CLI skew report.")
    .category("observability")
    .double()
    .check(lambda v: v > 1.0, "> 1.0")
    .create_with_default(2.0)
)

ATTRIBUTION_ENABLED = (
    conf("spark.rapids.tpu.attribution.enabled")
    .doc("Per-query wall-clock attribution (the time books): fold trace "
         "spans, telemetry counter deltas and op/exchange stats into "
         "exclusive buckets (queue wait, semaphore wait, compile, kernel "
         "dispatch, exchange collectives, host shuffle, spill/restore "
         "I/O, cache, pump idle, host fallback) that sum to the query's "
         "end-to-end wall time within 10 %, with any gap "
         "reported explicitly as unaccounted. Also arms the flight "
         "recorder: a bounded ring of recent spans/health/retry/cancel "
         "events dumped atomically as query-<id>.blackbox.json when a "
         "query dies (timeout, cancel, error) or health degrades. On by "
         "default — reuses the existing span/counter instrumentation, "
         "no new timers on the pump hot path.")
    .category("observability")
    .boolean()
    .create_with_default(True)
)

ATTRIBUTION_BLACKBOX_PATH = (
    conf("spark.rapids.tpu.attribution.blackboxPath")
    .doc("Directory for flight-recorder dumps "
         "(query-<id>.blackbox.json, written atomically via "
         "tmp+rename). Empty disables dumping.")
    .category("observability")
    .string()
    .create_with_default("/tmp/tpuq-blackbox")
)

QUERY_TIMEOUT_MS = (
    conf("spark.rapids.tpu.query.timeoutMs")
    .doc("Per-query deadline in milliseconds, enforced in-process by "
         "the cooperative cancellation layer (runtime/cancel.py): when "
         "a query exceeds it, every blocking boundary raises "
         "QueryCancelled(reason='deadline') and the engine reclaims "
         "all of the query's resources (semaphore permits, HBM "
         "reservations, spill files). An explicit "
         "collect(timeout_ms=...) overrides this. <= 0 disables.")
    .category("lifecycle")
    .integer()
    .create_with_default(0)
)

CANCEL_POLL_MS = (
    conf("spark.rapids.tpu.query.cancelPollMs")
    .doc("Upper bound on how long any blocking wait (semaphore, retry "
         "backoff, spill IO, shuffle, rendezvous) may park before "
         "re-polling the query's CancelToken. Cancels and deadline "
         "expiries surface within ~2x this interval; registered "
         "waiters (the device semaphore) wake immediately.")
    .category("lifecycle")
    .integer()
    .check(lambda v: v > 0, "positive")
    .create_with_default(50)
)

FAULT_INJECT = (
    conf("spark.rapids.tpu.test.injectOomAtAlloc")
    .doc("Force an OOM at the Nth device allocation (test hook, mirrors "
         "RmmSpark.forceRetryOOM). -1 disables.")
    .category("test")
    .internal()
    .integer()
    .create_with_default(-1)
)

INJECT_EXECUTE_AT = (
    conf("spark.rapids.tpu.test.injectExecuteErrorAt")
    .doc("Raise an injected device error at the Nth kernel execution "
         "(resilience test hook, the faultinj analog). -1 disables.")
    .category("test")
    .internal()
    .integer()
    .create_with_default(-1)
)

INJECT_TRANSFER_AT = (
    conf("spark.rapids.tpu.test.injectTransferErrorAt")
    .doc("Raise an injected device error at the Nth device→host "
         "transfer. -1 disables.")
    .category("test")
    .internal()
    .integer()
    .create_with_default(-1)
)

INJECT_TRANSIENT_COUNT = (
    conf("spark.rapids.tpu.test.injectTransientCount")
    .doc("How many injected device errors are transient (recoverable by "
         "the retry policy) before they turn terminal. Legacy alias for "
         "the execute/transfer domains' inject.<domain>.transientCount.")
    .category("test")
    .internal()
    .integer()
    .create_with_default(0)
)

# Engine failure domains — every device/IO boundary the resilience layer
# guards.  Each domain gets an independently armable injection pair:
# ``spark.rapids.tpu.test.inject.<domain>.at`` (fire from the Nth call
# on; -1 disables) and ``.transientCount`` (transient fires before the
# fault turns terminal / the domain disarms).
FAILURE_DOMAINS = ("execute", "transfer", "alloc", "spill_write",
                   "spill_read", "shuffle_ser", "shuffle_exchange",
                   "collective", "compile", "rendezvous", "peer_loss",
                   "tenancy")

INJECT_DOMAIN_AT: Dict[str, ConfEntry] = {}
INJECT_DOMAIN_TRANSIENT: Dict[str, ConfEntry] = {}
for _dom in FAILURE_DOMAINS:
    INJECT_DOMAIN_AT[_dom] = (
        conf(f"spark.rapids.tpu.test.inject.{_dom}.at")
        .doc(f"Arm the '{_dom}' failure domain: raise an injected fault "
             "from its Nth call on (resilience test hook, the faultinj "
             "analog). -1 disables.")
        .category("test")
        .internal()
        .integer()
        .create_with_default(-1)
    )
    INJECT_DOMAIN_TRANSIENT[_dom] = (
        conf(f"spark.rapids.tpu.test.inject.{_dom}.transientCount")
        .doc(f"How many '{_dom}' injected faults are transient before "
             "they turn terminal (0 = the first fire is terminal).")
        .category("test")
        .internal()
        .integer()
        .create_with_default(0)
    )
del _dom

TELEMETRY_ENABLED = (
    conf("spark.rapids.tpu.telemetry.enabled")
    .doc("Continuous process telemetry: a background sampler snapshots "
         "the metrics registry (HBM arbiter, spill tiers, device "
         "semaphore, kernel cache, shuffle, pump pool) into a JSONL "
         "time series and a Prometheus text-format dump. The registry "
         "itself always updates; this only gates the sampler/sinks.")
    .category("telemetry")
    .boolean()
    .create_with_default(False)
)

LOCKDEP_ENABLED = (
    conf("spark.rapids.tpu.lockdep.enabled")
    .doc("Lockdep-style runtime watchdog: wraps the engine's "
         "threading.Lock/RLock/Condition instances, records the "
         "process-wide lock acquisition-order graph, and reports any "
         "edge that closes a cycle (a latent deadlock) from a single "
         "observation of both orders. Diagnostic; adds per-acquisition "
         "bookkeeping overhead.")
    .category("telemetry")
    .boolean()
    .create_with_default(False)
)

LOCKDEP_RAISE_ON_CYCLE = (
    conf("spark.rapids.tpu.lockdep.raiseOnCycle")
    .doc("With lockdep enabled, raise LockOrderViolation at the "
         "acquisition that closes a cycle instead of only recording it "
         "for the violations report.")
    .category("telemetry")
    .boolean()
    .create_with_default(False)
)

TELEMETRY_PERIOD_MS = (
    conf("spark.rapids.tpu.telemetry.samplePeriodMs")
    .doc("Sampler period in milliseconds.")
    .category("telemetry")
    .integer()
    .check(lambda v: v > 0, "positive")
    .create_with_default(1000)
)

TELEMETRY_SINK_PATH = (
    conf("spark.rapids.tpu.telemetry.sinkPath")
    .doc("JSONL time-series sink: one line per sample with every "
         "counter/gauge value and histogram summary. Empty disables "
         "the JSONL sink.")
    .category("telemetry")
    .string()
    .create_with_default("/tmp/tpuq-telemetry/metrics.jsonl")
)

TELEMETRY_PROM_PATH = (
    conf("spark.rapids.tpu.telemetry.promPath")
    .doc("Prometheus text exposition dump, atomically rewritten every "
         "sample — scrape it with node_exporter's textfile collector "
         "or serve the file. Empty disables the dump.")
    .category("telemetry")
    .string()
    .create_with_default("/tmp/tpuq-telemetry/metrics.prom")
)

HEALTH_SPILL_RATIO = (
    conf("spark.rapids.tpu.telemetry.health.spillRatio")
    .doc("WARN when one query's spilled bytes exceed this fraction of "
         "the bytes it reserved (the working set does not fit the HBM "
         "budget).")
    .category("telemetry")
    .double()
    .check(lambda v: v >= 0.0, "non-negative")
    .create_with_default(0.5)
)

HEALTH_SEM_WAIT_RATIO = (
    conf("spark.rapids.tpu.telemetry.health.semaphoreWaitRatio")
    .doc("WARN when one query's cumulative device-admission wait "
         "exceeds this fraction of its wall time (semaphore "
         "saturation: concurrentGpuTasks is the bottleneck).")
    .category("telemetry")
    .double()
    .check(lambda v: v >= 0.0, "non-negative")
    .create_with_default(0.5)
)

HEALTH_COMPILE_STORM = (
    conf("spark.rapids.tpu.telemetry.health.compileStorm")
    .doc("WARN when one query triggers more than this many XLA "
         "compiles (shape buckets / kernel fingerprints are not being "
         "reused).")
    .category("telemetry")
    .integer()
    .check(lambda v: v >= 0, "non-negative")
    .create_with_default(64)
)

# -- shape plane + persistent kernel cache (runtime/shapes.py +
#    runtime/kernel_cache.py) ------------------------------------------------


def _valid_ladder(v) -> bool:
    """CSV of strictly-increasing positive row counts ('' = unset)."""
    s = str(v).strip()
    if not s:
        return True
    try:
        rungs = [int(x.strip()) for x in s.split(",")]
    except ValueError:
        return False
    return (all(r > 0 for r in rungs)
            and all(a < b for a, b in zip(rungs, rungs[1:])))


KERNEL_CACHE_DIR = (
    conf("spark.rapids.tpu.kernel.cacheDir")
    .doc("Directory for the persistent (on-disk) XLA compilation cache. "
         "Compiled executables survive process restarts, so a warm "
         "QueryServer restart pays zero hot-path compiles. The directory "
         "carries a manifest versioned on (jax, jaxlib, engine); a "
         "version mismatch invalidates the cache wholesale. Empty "
         "(default) caches under .jax_cache in the checkout. The "
         "JAX_COMPILATION_CACHE_DIR environment variable outranks this "
         "key and is used as it is (no manifest). Ignored on the "
         "XLA:CPU backend, whose AOT cache entries are unsafe to "
         "reload.")
    .category("kernel")
    .string()
    .create_with_default("")
)

KERNEL_BUCKETING = (
    conf("spark.rapids.tpu.kernel.bucketing")
    .doc("Batch-shape bucketing policy of the shape plane: 'pow2' pads "
         "device batch capacities up to power-of-two row buckets, "
         "'ladder' pads up to the explicit rung list in "
         "kernel.bucketLadder (pow2 above the top rung), 'off' disables "
         "re-bucketing at the exec pump boundary. Fewer distinct shapes "
         "means fewer (op, schema, bucket) XLA compiles.")
    .category("kernel")
    .string()
    .check(lambda v: str(v).lower() in ("off", "pow2", "ladder"),
           "one of off, pow2, ladder")
    .create_with_default("pow2")
)

KERNEL_BUCKET_LADDER = (
    conf("spark.rapids.tpu.kernel.bucketLadder")
    .doc("Comma-separated strictly-increasing row-count rungs for "
         "kernel.bucketing=ladder, e.g. '1024,8192,65536,1048576'. "
         "Capacities above the top rung fall back to pow2 rounding. "
         "Empty means ladder mode behaves like pow2.")
    .category("kernel")
    .string()
    .check(_valid_ladder, "comma-separated strictly-increasing "
                          "positive integers")
    .create_with_default("")
)

KERNEL_MAX_PAD_FRACTION = (
    conf("spark.rapids.tpu.kernel.maxPadFraction")
    .doc("Upper bound on the padding a bucket may introduce, as "
         "(bucket - capacity) / bucket. A rung that would exceed it is "
         "rejected in favor of the batch's pow2 bucket, trading a "
         "possible extra compile for bounded pad-waste bytes.")
    .category("kernel")
    .double()
    .check(lambda v: 0.0 <= v < 1.0, "in [0, 1)")
    .create_with_default(0.75)
)

KERNEL_BACKEND = (
    conf("spark.rapids.tpu.kernel.backend")
    .doc("Kernel-plane backend for the fused hash-join / segmented-sort "
         "/ hash-agg kernels: 'jnp' is the pure jax.numpy reference, "
         "'fused' the single-program XLA hash/tiled-rank kernels, "
         "'pallas' adds the Mosaic VPU hash kernel (TPU only), 'auto' "
         "picks pallas on TPU and fused elsewhere (except sort, whose "
         "tiled form only pays on TPU). Non-jnp backends degrade down "
         "the pallas>fused>jnp ladder on detected 64-bit hash "
         "collisions or unhashable keys, so results are always exact. "
         "See docs/kernels.md.")
    .category("kernel")
    .string()
    .check(lambda v: str(v).lower() in ("auto", "pallas", "fused", "jnp"),
           "one of auto, pallas, fused, jnp")
    .create_with_default("auto")
)

EXEC_PUMP_DEPTH = (
    conf("spark.rapids.tpu.exec.pumpDepth")
    .doc("Batches kept in flight by the double-buffered exec pump: each "
         "operator's output iterator is pre-pulled up to this depth so "
         "JAX async dispatch overlaps the producer's H2D/compute with "
         "the consumer's compute/D2H. 1 disables prefetch. Bounded "
         "small on purpose — holding all outputs alive costs ~60% "
         "exchange bandwidth (utils.exchange_bench).")
    .category("kernel")
    .integer()
    .check(lambda v: 1 <= int(v) <= 8, "in [1, 8]")
    .create_with_default(2)
)

KERNEL_WARMUP_ON_START = (
    conf("spark.rapids.tpu.kernel.warmupOnStart")
    .doc("QueryServer construction pre-executes the warmup plans handed "
         "to it (session.warmup), compiling the op x bucket matrix "
         "outside any query window — so the first tenant query never "
         "pays XLA compile and never trips the compile-storm health "
         "WARN. Disable to defer compilation to first use.")
    .category("kernel")
    .boolean()
    .create_with_default(True)
)


# -- whole-stage fusion plane (spark_rapids_tpu/fusion/) --------------------

FUSION_ENABLED = (
    conf("spark.rapids.tpu.fusion.enabled")
    .doc("Master switch for the whole-stage fusion plane "
         "(spark_rapids_tpu/fusion/): after plan conversion, maximal "
         "chains of fusable per-batch map operators (project / filter / "
         "cast chains) are stitched into FusedStageExec regions, each "
         "lowered to ONE jitted XLA program — intermediate batches stay "
         "device-resident SSA values inside the program, and the pump / "
         "pad-mask / shape-bucket boundary is paid once per region "
         "instead of once per operator.  Region boundaries are "
         "exchanges, stateful or non-jitable operators (limits, UDF "
         "fallbacks, collect aggregates) and anything whose fusion hook "
         "the fusion-purity analysis cannot prove host-pull-free.  A "
         "region that fails to compile falls open to the unfused pump "
         "chain (counted in tpuq_fusion_fallback_total); answers are "
         "bit-identical either way (tests/test_fusion.py).")
    .category("fusion")
    .boolean()
    .create_with_default(False)
)

FUSION_MAX_OPS = (
    conf("spark.rapids.tpu.fusion.maxOpsPerRegion")
    .doc("Upper bound on the member operators stitched into one fused "
         "region.  A chain longer than this splits into consecutive "
         "regions, bounding single-program XLA compile time; raising it "
         "trades compile latency for fewer dispatch boundaries.")
    .category("fusion")
    .integer()
    .check(lambda v: 2 <= int(v) <= 64, "in [2, 64]")
    .create_with_default(16)
)

FUSION_MODE = (
    conf("spark.rapids.tpu.fusion.mode")
    .doc("Region-selection policy: 'auto' fuses only chains of 2+ "
         "fusable operators (a singleton region saves nothing over the "
         "op's own cached kernel), 'aggressive' also wraps singleton "
         "fusable ops so every map rides region bookkeeping (useful to "
         "exercise the plane), 'off' disables region selection even "
         "when fusion.enabled is true.")
    .category("fusion")
    .string()
    .check(lambda v: str(v).lower() in ("auto", "off", "aggressive"),
           "one of auto, off, aggressive")
    .create_with_default("auto")
)


# -- multi-tenant query service (runtime/scheduler.py + sql/server.py) ------
#
# Per-tenant overrides ride a dynamic key family the scheduler reads at
# tenant creation:
#   spark.rapids.tpu.scheduler.tenant.<name>.weight        (double)
#   spark.rapids.tpu.scheduler.tenant.<name>.maxInFlight   (int)
#   spark.rapids.tpu.scheduler.tenant.<name>.maxQueued     (int)
#   spark.rapids.tpu.scheduler.tenant.<name>.hbmShare      (double)
#   spark.rapids.tpu.scheduler.tenant.<name>.sloP99Ms      (int)
# Unlisted tenants get the tenantWeight/tenantMaxInFlight/tenantMaxQueued/
# tenantHbmShare/tenantSloP99Ms defaults below.

SCHED_MAX_CONCURRENT = (
    conf("spark.rapids.tpu.scheduler.maxConcurrentQueries")
    .doc("How many admitted queries may execute concurrently across "
         "ALL tenants. Queries beyond this wait in their tenant's "
         "queue until the fairness scheduler (weighted deficit "
         "round-robin across tenants, priority lanes within a tenant) "
         "grants them a run slot. This caps whole queries; "
         "spark.rapids.sql.concurrentGpuTasks still caps the "
         "per-partition device admission inside each running query.")
    .category("scheduler")
    .integer()
    .check(lambda v: v > 0, "positive")
    .create_with_default(4)
)

SCHED_MAX_QUEUED = (
    conf("spark.rapids.tpu.scheduler.maxQueuedQueries")
    .doc("Global cap on queries waiting for a run slot, across all "
         "tenants. A submission beyond it is rejected with "
         "QueryRejected(reason='queue_full').")
    .category("scheduler")
    .integer()
    .check(lambda v: v >= 0, "non-negative")
    .create_with_default(256)
)

SCHED_TENANT_WEIGHT = (
    conf("spark.rapids.tpu.scheduler.tenantWeight")
    .doc("Default fair-share weight of a tenant in the deficit "
         "round-robin dispatcher: a tenant with weight 2 is granted "
         "run slots twice as often as a weight-1 tenant under "
         "contention. Per-tenant override: "
         "spark.rapids.tpu.scheduler.tenant.<name>.weight.")
    .category("scheduler")
    .double()
    .check(lambda v: v >= 0.01, ">= 0.01")
    .create_with_default(1.0)
)

SCHED_TENANT_MAX_IN_FLIGHT = (
    conf("spark.rapids.tpu.scheduler.tenantMaxInFlight")
    .doc("Default per-tenant cap on concurrently RUNNING queries. "
         "Submissions beyond it queue (they are not rejected). "
         "Per-tenant override: "
         "spark.rapids.tpu.scheduler.tenant.<name>.maxInFlight.")
    .category("scheduler")
    .integer()
    .check(lambda v: v > 0, "positive")
    .create_with_default(4)
)

SCHED_TENANT_MAX_QUEUED = (
    conf("spark.rapids.tpu.scheduler.tenantMaxQueued")
    .doc("Default per-tenant cap on QUEUED queries. A submission "
         "beyond it is rejected with "
         "QueryRejected(reason='tenant_queue_full'). Per-tenant "
         "override: spark.rapids.tpu.scheduler.tenant.<name>.maxQueued.")
    .category("scheduler")
    .integer()
    .check(lambda v: v >= 0, "non-negative")
    .create_with_default(64)
)

SCHED_TENANT_HBM_SHARE = (
    conf("spark.rapids.tpu.scheduler.tenantHbmShare")
    .doc("Default per-tenant HBM-reservation share, enforced as the "
         "fraction of maxConcurrentQueries run slots the tenant may "
         "hold at once (each running query may reserve up to the full "
         "HBM pool, so bounding a tenant's share of run slots bounds "
         "its share of device memory pressure). Per-tenant override: "
         "spark.rapids.tpu.scheduler.tenant.<name>.hbmShare.")
    .category("scheduler")
    .double()
    .check(lambda v: 0.0 < v <= 1.0, "in (0, 1]")
    .create_with_default(1.0)
)

SCHED_SHED_QUEUE_DEPTH = (
    conf("spark.rapids.tpu.scheduler.shed.queueDepth")
    .doc("Load-shed watermark on total service depth (queued + running "
         "queries): a submission arriving at or above it is shed with "
         "QueryRejected(reason='shed_queue_depth'), counted in "
         "tpuq_admission_shed_total and WARNed by the health "
         "evaluator, instead of joining a queue that can no longer "
         "drain within any useful deadline.")
    .category("scheduler")
    .integer()
    .check(lambda v: v > 0, "positive")
    .create_with_default(128)
)

SCHED_SHED_SPILL_RATIO = (
    conf("spark.rapids.tpu.scheduler.shed.spillRatio")
    .doc("Load-shed watermark on spill pressure: when the host spill "
         "tier's occupancy fraction (DeviceMemoryManager.spill_pressure) "
         "is at or above this, new submissions are shed with "
         "QueryRejected(reason='shed_spill_pressure') BEFORE the "
         "arbiter starts thrashing the disk tier.")
    .category("scheduler")
    .double()
    .check(lambda v: v > 0.0, "positive")
    .create_with_default(0.85)
)

SCHED_SHED_SEM_SATURATION = (
    conf("spark.rapids.tpu.scheduler.shed.semaphoreSaturation")
    .doc("Load-shed watermark on device-admission saturation: "
         "(semaphore holders + blocked waiters) / permits at or above "
         "this sheds new submissions with "
         "QueryRejected(reason='shed_semaphore_saturation'). The "
         "default 4.0 means: shed when 4x more tasks want the device "
         "than it admits.")
    .category("scheduler")
    .double()
    .check(lambda v: v > 0.0, "positive")
    .create_with_default(4.0)
)

SCHED_PREEMPT_ENABLED = (
    conf("spark.rapids.tpu.scheduler.preempt.enabled")
    .doc("Let the scheduler cooperatively preempt running queries: "
         "when a waiter has starved past preempt.graceMs the arbiter "
         "suspends a victim (largest-runtime query of the most "
         "over-share tenant) at its next pump boundary — permits "
         "released, resident batches spilled through the HBM tiers — "
         "admits the waiter, and resumes the victim bit-identically "
         "once capacity frees. Off by default: preemption trades "
         "victim latency for waiter fairness and should be an "
         "operator's explicit choice.")
    .category("scheduler")
    .boolean()
    .create_with_default(False)
)

SCHED_PREEMPT_GRACE_MS = (
    conf("spark.rapids.tpu.scheduler.preempt.graceMs")
    .doc("How long a queued query must wait before the preemption "
         "arbiter considers suspending a running victim on its "
         "behalf. Small values make the scheduler aggressive "
         "(hot-potato slots); large values approach "
         "fairness-by-politeness.")
    .category("scheduler")
    .integer()
    .check(lambda v: v > 0, "positive")
    .create_with_default(500)
)

SCHED_PREEMPT_MIN_RUN_MS = (
    conf("spark.rapids.tpu.scheduler.preempt.minRunMs")
    .doc("A running query younger than this (measured from its grant, "
         "and re-armed at each resume) is never picked as a "
         "preemption victim — the anti-thrash floor that guarantees "
         "forward progress under sustained overload.")
    .category("scheduler")
    .integer()
    .check(lambda v: v >= 0, "non-negative")
    .create_with_default(250)
)

SCHED_TENANT_SLO_P99_MS = (
    conf("spark.rapids.tpu.scheduler.tenantSloP99Ms")
    .doc("Default per-tenant p99 submit-to-completion latency SLO in "
         "milliseconds, tracked by a sliding-window estimator over the "
         "tenant's recent completions. 0 disables SLO tracking. While "
         "a tenant's observed p99 breaches its target the scheduler "
         "halves that tenant's effective queue cap and sheds the "
         "overflow with QueryRejected(reason='shed_slo') (counted in "
         "tpuq_slo_breach_total, black-box dumped with the dominant "
         "attribution bucket). Per-tenant override: "
         "spark.rapids.tpu.scheduler.tenant.<name>.sloP99Ms.")
    .category("scheduler")
    .integer()
    .check(lambda v: v >= 0, "non-negative")
    .create_with_default(0)
)

SCHED_SLO_WINDOW = (
    conf("spark.rapids.tpu.scheduler.sloWindow")
    .doc("Sliding-window size (completions per tenant) for the SLO "
         "p99 estimator. Breach detection needs at least 8 samples in "
         "the window, so small windows react faster but gate on fewer "
         "observations.")
    .category("scheduler")
    .integer()
    .check(lambda v: v >= 8, ">= 8")
    .create_with_default(64)
)


# ---------------------------------------------------------------------------
# Cluster-wide tenancy protocol (runtime/tenancy.py + parallel/rendezvous.py)
# ---------------------------------------------------------------------------

TENANCY_ENABLED = (
    conf("spark.rapids.tpu.tenancy.enabled")
    .doc("Cluster-wide tenancy enforcement: each executor's "
         "TenancyAgent piggybacks per-tenant state (in-flight, queued "
         "depth, HBM bytes, largest-runtime query) on its rendezvous "
         "heartbeat, and the coordinator's arbiter fans epoch-tagged "
         "suspend/resume/shed directives back on the heartbeat "
         "response, so a tenant breaching its cluster share on one "
         "executor is preempted even when the starved waiter sits on "
         "another. Requires a rendezvous address and heartbeats "
         "enabled; without them enforcement stays process-local.")
    .category("scheduler")
    .boolean()
    .create_with_default(False)
)


# ---------------------------------------------------------------------------
# Result-cache plane (spark_rapids_tpu/cache/, docs/result_cache.md)
# ---------------------------------------------------------------------------

CACHE_ENABLED = (
    conf("spark.rapids.tpu.cache.enabled")
    .doc("Serve repeated queries from the host-resident result cache. "
         "A hit is keyed by sha1(physical-plan fingerprint + "
         "result-affecting confs + input fingerprints) and bypasses "
         "the scheduler and device semaphore entirely; the query log "
         "still records the query with entry['cache'].status='hit'.")
    .category("cache")
    .boolean()
    .create_with_default(False)
)

CACHE_MAX_BYTES = (
    conf("spark.rapids.tpu.cache.maxBytes")
    .doc("Byte budget for resident cached results (Arrow bytes). "
         "Least-recently-used entries are evicted to stay under it; a "
         "single result larger than the budget is never cached.")
    .category("cache")
    .bytes()
    .check(lambda v: v > 0, "positive")
    .create_with_default(256 * 1024 * 1024)
)

CACHE_TTL_MS = (
    conf("spark.rapids.tpu.cache.ttlMs")
    .doc("Time-to-live for cached results in milliseconds; an entry "
         "older than this counts as an eviction at lookup. 0 disables "
         "TTL (entries live until evicted or invalidated).")
    .category("cache")
    .integer()
    .check(lambda v: v >= 0, "non-negative")
    .create_with_default(600_000)
)

CACHE_MIN_RUNTIME_MS = (
    conf("spark.rapids.tpu.cache.minRuntimeMs")
    .doc("Only cache results whose cold execution took at least this "
         "many milliseconds — sub-millisecond queries churn the byte "
         "budget for no device savings.")
    .category("cache")
    .integer()
    .check(lambda v: v >= 0, "non-negative")
    .create_with_default(0)
)

CACHE_SUBPLAN_ENABLED = (
    conf("spark.rapids.tpu.cache.subplan.enabled")
    .doc("Also cache materialized shuffle-exchange outputs under "
         "subtree signatures, so partially-overlapping queries reuse "
         "shared stages even when their full result keys differ. "
         "Entries share the cache.maxBytes budget.")
    .category("cache")
    .boolean()
    .create_with_default(False)
)


class RapidsConf:
    """Immutable-ish view over a raw key->value dict, validated at init.

    [REF: RapidsConf.scala :: RapidsConf]
    """

    def __init__(self, raw: Optional[Dict[str, Any]] = None):
        self._raw = dict(raw or {})
        self._values: Dict[str, Any] = {}
        unknown = []
        for k, v in self._raw.items():
            e = REGISTRY.entries.get(k)
            if e is None:
                if k.startswith("spark.rapids.sql.expression.") or k.startswith(
                    "spark.rapids.sql.exec."
                ):
                    # per-op kill switches are registered dynamically by the
                    # overrides rule table; store raw
                    self._values[k] = _parse_bool(v)
                elif k.startswith("spark.rapids.tpu.scheduler.tenant."):
                    # per-tenant scheduler overrides (weight/maxInFlight/
                    # maxQueued/hbmShare) keyed by tenant name; the scheduler
                    # parses and validates at tenant creation
                    self._values[k] = v
                elif k.startswith("spark.rapids."):
                    unknown.append(k)
                else:
                    self._values[k] = v
            else:
                self._values[k] = e.convert(v)
        if unknown:
            raise ValueError(f"unknown spark.rapids.* conf keys: {unknown}")

    def get(self, entry: ConfEntry):
        return self._values.get(entry.key, entry.default)

    def get_raw(self, key: str, default=None):
        return self._values.get(key, default)

    def raw_prefix(self, prefix: str) -> Dict[str, Any]:
        """All dynamically-registered raw keys under a prefix (e.g. the
        per-tenant scheduler overrides) — result-key derivation folds
        these in so tenant conf differences key separately."""
        return {k: v for k, v in self._values.items()
                if k.startswith(prefix)}

    def is_op_enabled(self, kind: str, name: str, default: bool = True) -> bool:
        """Per-op kill switch, e.g. spark.rapids.sql.expression.Substring."""
        return self._values.get(f"spark.rapids.sql.{kind}.{name}", default)

    def with_overrides(self, extra: Dict[str, Any]) -> "RapidsConf":
        raw = dict(self._raw)
        raw.update(extra)
        return RapidsConf(raw)

    # convenience properties -------------------------------------------------
    @property
    def sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED)

    @property
    def explain(self) -> str:
        return str(self.get(EXPLAIN)).upper()

    @property
    def test_enabled(self) -> bool:
        return self.get(TEST_ENABLED)

    @property
    def allowed_non_gpu(self) -> List[str]:
        s = str(self.get(TEST_ALLOWED_NON_GPU)).strip()
        return [x.strip() for x in s.split(",") if x.strip()]

    @property
    def batch_rows(self) -> int:
        return self.get(BATCH_ROWS)

    @property
    def min_bucket_rows(self) -> int:
        return self.get(MIN_BUCKET_ROWS)

    @property
    def shuffle_partitions(self) -> int:
        return self.get(SHUFFLE_PARTITIONS)

    @property
    def shuffle_mode(self) -> str:
        return str(self.get(SHUFFLE_MODE)).upper()

    @property
    def exchange_mode(self) -> str:
        return str(self.get(EXCHANGE_MODE)).lower()

    @property
    def ansi_enabled(self) -> bool:
        return self.get(ANSI_ENABLED)


def generate_configs_md() -> str:
    """Auto-generate docs/configs.md from the registry.

    [REF: RapidsConf.scala :: doc-gen main]
    """
    lines = [
        "# Configuration",
        "",
        "Generated from `spark_rapids_tpu/conf.py` — do not edit by hand.",
        "",
        "| Key | Default | Category | Description |",
        "|---|---|---|---|",
    ]
    for e in sorted(REGISTRY.entries.values(), key=lambda e: e.key):
        if e.internal:
            continue
        lines.append(f"| `{e.key}` | `{e.default}` | {e.category} | {e.doc} |")
    lines.append("")
    return "\n".join(lines)
