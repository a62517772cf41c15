"""Config keys, defaults, and naming constants.

Parity: com/microsoft/hyperspace/index/IndexConstants.scala:21-107 and
actions/Constants.scala:20-33 in the reference. Keys keep the reference's
dotted-name style but live under a ``hyperspace.`` prefix (no Spark).
"""

# --- system layout -----------------------------------------------------------
INDEX_SYSTEM_PATH = "hyperspace.system.path"
INDEX_SYSTEM_PATH_DEFAULT = "indexes"  # resolved relative to workspace root

# Operation-log directory name inside every index directory
# (reference: IndexConstants.scala:61, HYPERSPACE_LOG)
HYPERSPACE_LOG = "_hyperspace_log"
# Versioned index-data directory prefix (reference: IndexConstants.scala:62)
INDEX_VERSION_DIRECTORY_PREFIX = "v__"

# --- index build -------------------------------------------------------------
# (reference: IndexConstants.scala:29-32; default = spark.sql.shuffle.partitions
# = 200 there. On TPU the natural default is a multiple of the mesh size; 200
# is kept as the parity default and the engine rounds up to the mesh when
# executing.)
INDEX_NUM_BUCKETS = "hyperspace.index.numBuckets"
INDEX_NUM_BUCKETS_DEFAULT = 200
INDEX_NUM_BUCKETS_LEGACY = "hyperspace.num.buckets"  # legacy fallback key

# Out-of-core streaming build (no direct reference analog: Spark streams
# splits through executors for free — CreateActionBase.scala:122-140 delegates
# to a distributed scan+shuffle+write. Here the streamed pipeline is explicit:
# fixed-capacity chunks through one compiled bucketize+sort executable, spill
# runs grouped by bucket, per-bucket merge at write time. Bounded host RAM and
# HBM regardless of dataset size.)
BUILD_MODE = "hyperspace.index.build.mode"
BUILD_MODE_AUTO = "auto"
BUILD_MODE_INMEMORY = "inmemory"
BUILD_MODE_STREAMING = "streaming"
BUILD_MODES = (BUILD_MODE_AUTO, BUILD_MODE_INMEMORY, BUILD_MODE_STREAMING)
BUILD_MODE_DEFAULT = BUILD_MODE_AUTO
BUILD_CHUNK_ROWS = "hyperspace.index.build.chunkRows"
BUILD_CHUNK_ROWS_DEFAULT = 1 << 21  # 2M rows per streamed chunk
# What the streamed build does with its spilled sorted runs:
#   merge — k-way-merge runs into one file per bucket at finalize (every
#           row is written twice: spill + final — the round-3 write wall);
#   runs  — promote the runs themselves to final multi-bucket data files
#           (footer bucketCounts give per-bucket row ranges); queries read
#           bucket segments and merge at execution time, and optimize()
#           later compacts runs into per-bucket files — the reference's
#           small-file→optimize lifecycle (OptimizeAction.scala:85-99)
#           applied to build latency: rows are written ONCE at build time.
BUILD_FINALIZE_MODE = "hyperspace.index.build.finalizeMode"
BUILD_FINALIZE_MERGE = "merge"
BUILD_FINALIZE_RUNS = "runs"
BUILD_FINALIZE_MODES = (BUILD_FINALIZE_MERGE, BUILD_FINALIZE_RUNS)
BUILD_FINALIZE_MODE_DEFAULT = BUILD_FINALIZE_MERGE
# auto mode streams when the source files exceed this many bytes on disk
BUILD_STREAMING_THRESHOLD_BYTES = "hyperspace.index.build.streamingThresholdBytes"
BUILD_STREAMING_THRESHOLD_BYTES_DEFAULT = 256 * 1024 * 1024
# Build engine: device (fused XLA bucketize+sort), host (numpy lexsort
# twin), or auto — resolved once per build by one static rule
# (index.builder.resolve_build_engine): host on a CPU platform, for an
# in-memory build below INMEMORY_HOST_MAX_ROWS, or when the first device
# touch failed; device otherwise. The chosen engine is observable as
# build.engine.* counters.
BUILD_ENGINE = "hyperspace.index.build.engine"
BUILD_ENGINE_AUTO = "auto"
BUILD_ENGINE_DEVICE = "device"
BUILD_ENGINE_HOST = "host"
BUILD_ENGINES = (BUILD_ENGINE_AUTO, BUILD_ENGINE_DEVICE, BUILD_ENGINE_HOST)
BUILD_ENGINE_DEFAULT = BUILD_ENGINE_AUTO
# Pipelined build (docs/14-build-pipeline.md): worker counts and queue
# depths of the staged ingest→dispatch→spill-compute→spill-write→merge
# pipeline. pipeline=off runs every stage inline on the caller thread
# (zero background threads — the deterministic reference of the parity
# tests and the debugging escape hatch). Worker counts accept an int
# or "auto" (derived from the host core count).
BUILD_PIPELINE = "hyperspace.index.build.pipeline"
BUILD_PIPELINE_ON = "on"
BUILD_PIPELINE_OFF = "off"
BUILD_PIPELINE_MODES = (BUILD_PIPELINE_ON, BUILD_PIPELINE_OFF)
BUILD_PIPELINE_DEFAULT = BUILD_PIPELINE_ON
BUILD_INGEST_WORKERS = "hyperspace.index.build.ingestWorkers"
BUILD_SPILL_COMPUTE_WORKERS = "hyperspace.index.build.spillComputeWorkers"
BUILD_SPILL_WRITE_WORKERS = "hyperspace.index.build.spillWriteWorkers"
BUILD_MERGE_WORKERS = "hyperspace.index.build.mergeWorkers"
BUILD_QUEUE_DEPTH = "hyperspace.index.build.queueDepth"
BUILD_WORKERS_AUTO = "auto"

# Lineage (reference: IndexConstants.scala:74-76)
INDEX_LINEAGE_ENABLED = "hyperspace.index.lineage.enabled"
INDEX_LINEAGE_ENABLED_DEFAULT = False
DATA_FILE_NAME_ID = "_data_file_id"
UNKNOWN_FILE_ID = -1  # (reference: IndexConstants.scala:95)

# --- hybrid scan -------------------------------------------------------------
# (reference: IndexConstants.scala:34-48)
INDEX_HYBRID_SCAN_ENABLED = "hyperspace.index.hybridscan.enabled"
INDEX_HYBRID_SCAN_ENABLED_DEFAULT = False
INDEX_HYBRID_SCAN_APPENDED_RATIO_THRESHOLD = (
    "hyperspace.index.hybridscan.maxAppendedRatio"
)
INDEX_HYBRID_SCAN_APPENDED_RATIO_THRESHOLD_DEFAULT = 0.3
INDEX_HYBRID_SCAN_DELETED_RATIO_THRESHOLD = (
    "hyperspace.index.hybridscan.maxDeletedRatio"
)
INDEX_HYBRID_SCAN_DELETED_RATIO_THRESHOLD_DEFAULT = 0.2

# --- cache -------------------------------------------------------------------
# (reference: IndexConstants.scala:57-59)
INDEX_CACHE_EXPIRY_DURATION_SECONDS = "hyperspace.index.cache.expiryDurationInSeconds"
INDEX_CACHE_EXPIRY_DURATION_SECONDS_DEFAULT = 300

# --- optimize ----------------------------------------------------------------
# (reference: IndexConstants.scala:86-88; OptimizeAction.scala:115-133)
OPTIMIZE_FILE_SIZE_THRESHOLD = "hyperspace.index.optimize.fileSizeThreshold"
OPTIMIZE_FILE_SIZE_THRESHOLD_DEFAULT = 256 * 1024 * 1024  # 256 MB
OPTIMIZE_MODE_QUICK = "quick"
OPTIMIZE_MODE_FULL = "full"
OPTIMIZE_MODES = (OPTIMIZE_MODE_QUICK, OPTIMIZE_MODE_FULL)

# --- incremental background compaction (index/compactor.py) ------------------
# The runs layout defers compaction to optimize(); the background
# compactor closes the gap by compacting run files into per-bucket files
# bucket-by-bucket LONG before optimize(), prioritized by observed bucket
# heat, each step committed through the normal operation-log protocol
# (lease-fenced; snapshot-pinned readers keep serving the old version
# wholesale). "auto" lets the QueryServer host sweeps the way it hosts
# the recovery sweep; "off" (the default) keeps compaction an explicit
# verb (optimize() / Hyperspace.compact_index).
INDEX_COMPACTION = "hyperspace.index.compaction.enabled"
INDEX_COMPACTION_AUTO = "auto"
INDEX_COMPACTION_OFF = "off"
INDEX_COMPACTION_MODES = (INDEX_COMPACTION_AUTO, INDEX_COMPACTION_OFF)
INDEX_COMPACTION_DEFAULT = INDEX_COMPACTION_OFF
# Buckets compacted per committed step. Each step also rewrites the
# remaining run files minus the compacted buckets (immutable files — the
# only way rows leave a run), so smaller steps mean earlier per-bucket
# files for hot buckets but more remainder-rewrite bytes over the whole
# convergence; bucketsPerStep >= numBuckets degenerates to one
# optimize()-shaped step. A step materializes its buckets' run rows on
# the host at once (the group's coalesced segment map), so this knob is
# also the step's peak-memory bound — size it to rows-per-bucket.
# optimize() does NOT use this knob: it groups by a read-bytes budget
# over the logged run sizes (actions/optimize.py).
INDEX_COMPACTION_BUCKETS_PER_STEP = "hyperspace.index.compaction.bucketsPerStep"
INDEX_COMPACTION_BUCKETS_PER_STEP_DEFAULT = 64
# How often a hosting QueryServer's submit path may kick a background
# compaction sweep (the recovery-sweep throttle pattern). <= 0 disables
# server-hosted sweeps even when compaction is "auto".
INDEX_COMPACTION_INTERVAL_SECONDS = "hyperspace.index.compaction.intervalSeconds"
INDEX_COMPACTION_INTERVAL_SECONDS_DEFAULT = 30.0
# Steps one hosted sweep may commit per index before yielding (bounded
# background work per sweep; the next interval continues convergence).
INDEX_COMPACTION_MAX_STEPS_PER_SWEEP = (
    "hyperspace.index.compaction.maxStepsPerSweep"
)
INDEX_COMPACTION_MAX_STEPS_PER_SWEEP_DEFAULT = 1

# --- segment IO (storage/layout.py planner) ----------------------------------
# How (run file, bucket) segment reads execute: "planned" (default)
# merges adjacent/near-adjacent ranges into one ordered sweep per run
# file fanned across the worker pool; "naive" issues one ranged read per
# segment — the pre-planner behavior, kept as an A/B lever
# (HYPERSPACE_TPU_SEGMENT_IO overrides both).
STORAGE_SEGMENT_IO = "hyperspace.storage.segmentIo"
STORAGE_SEGMENT_IO_PLANNED = "planned"
STORAGE_SEGMENT_IO_NAIVE = "naive"
STORAGE_SEGMENT_IO_MODES = (STORAGE_SEGMENT_IO_PLANNED, STORAGE_SEGMENT_IO_NAIVE)
STORAGE_SEGMENT_IO_DEFAULT = STORAGE_SEGMENT_IO_PLANNED

# --- refresh -----------------------------------------------------------------
# (reference: IndexConstants.scala:78-92)
REFRESH_MODE_INCREMENTAL = "incremental"
REFRESH_MODE_FULL = "full"
REFRESH_MODE_QUICK = "quick"
REFRESH_MODES = (REFRESH_MODE_INCREMENTAL, REFRESH_MODE_FULL, REFRESH_MODE_QUICK)

# --- query rewrite -----------------------------------------------------------
# Marker injected into relation options so a rewritten plan is never rewritten
# twice (reference: IndexConstants.scala:54, INDEX_RELATION_IDENTIFIER)
INDEX_RELATION_IDENTIFIER = ("indexhyperspace", "true")

# --- explain display ---------------------------------------------------------
# (reference: IndexConstants.scala:65-72, DisplayMode.scala:24-88)
DISPLAY_MODE = "hyperspace.explain.displayMode"
HIGHLIGHT_BEGIN_TAG = "hyperspace.explain.displayMode.highlight.beginTag"
HIGHLIGHT_END_TAG = "hyperspace.explain.displayMode.highlight.endTag"
DISPLAY_MODE_PLAIN_TEXT = "plaintext"
DISPLAY_MODE_HTML = "html"
DISPLAY_MODE_CONSOLE = "console"
DISPLAY_MODE_DEFAULT = DISPLAY_MODE_PLAIN_TEXT

# --- sources -----------------------------------------------------------------
# (reference: HyperspaceConf.scala:78-90 — the full six-format list;
# avro is served by the self-contained OCF reader in storage/avro_io.py
# since the environment ships no avro library)
FILE_BASED_SOURCE_BUILDERS = "hyperspace.index.sources.fileBasedBuilders"
DEFAULT_SUPPORTED_FORMATS = ("avro", "csv", "json", "orc", "parquet", "text")
# Globbing patterns for index sources (reference: IndexConstants.scala:101-106)
GLOBBING_PATTERN_KEY = "hyperspace.source.globbingPattern"
# Hive-style partition discovery toggle (source option, default on — the
# analog of Spark's PartitioningAwareFileIndex, which the reference's
# partitioned-source support rides on; DefaultFileBasedSource.scala:235-250)
PARTITION_INFERENCE_KEY = "hyperspace.source.partitionInference"
# Internal relation option recording the discovered partition column names
# (a JSON list, in directory order). Logged with the relation so refresh
# reconstructs the SAME spec instead of re-guessing the layout — a later
# re-layout that would shadow a data column with a same-named partition
# directory is thereby inert rather than silently corrupting reads.
PARTITION_COLUMNS_META = "hyperspace.source.partitionColumns"

# --- reliability -------------------------------------------------------------
# Crash-consistent lifecycle knobs (reliability/; no reference analog —
# Spark Hyperspace leans on HDFS semantics and human cancel()).
# Writer-lease directory name inside every index directory (next to the
# operation log)
HYPERSPACE_LEASE = "_hyperspace_lease"
# How long a writer's lease lives between heartbeats before an expired,
# unreleased lease counts as a dead writer and triggers auto-rollback
RELIABILITY_LEASE_DURATION_SECONDS = "hyperspace.reliability.lease.durationSeconds"
RELIABILITY_LEASE_DURATION_SECONDS_DEFAULT = 60.0
# Master toggle for automatic rollback of abandoned transient states
RELIABILITY_AUTO_RECOVERY = "hyperspace.reliability.autoRecovery"
RELIABILITY_AUTO_RECOVERY_DEFAULT = True
# Storage retry policy on the FileSystem seam (bounded exponential
# backoff with deterministic jitter; transient errors only)
RELIABILITY_RETRY_MAX_ATTEMPTS = "hyperspace.reliability.retry.maxAttempts"
RELIABILITY_RETRY_MAX_ATTEMPTS_DEFAULT = 4
RELIABILITY_RETRY_BASE_DELAY_SECONDS = "hyperspace.reliability.retry.baseDelaySeconds"
RELIABILITY_RETRY_BASE_DELAY_SECONDS_DEFAULT = 0.05
RELIABILITY_RETRY_MAX_DELAY_SECONDS = "hyperspace.reliability.retry.maxDelaySeconds"
RELIABILITY_RETRY_MAX_DELAY_SECONDS_DEFAULT = 2.0

# --- multi-tenant serving ----------------------------------------------------
# Per-tenant admission quotas, weighted-fair scheduling, and overload
# degradation for the serve tier (docs/16-multitenant-serving.md; no
# reference analog — Spark Hyperspace serves through Spark's own
# scheduler). Per-tenant overrides are a RUNTIME-BUILT key family under
# SERVE_TENANT_PREFIX (f"{prefix}.<tenant>.weight" etc.) — the prefix
# constant is the HS013 registration act for the family.
SERVE_TENANT_PREFIX = "hyperspace.serve.tenant"
# Relative scheduling weight of a tenant with no per-tenant override:
# the weighted deficit dispatcher grants device/worker turns in
# proportion to weight, so a weight-4 tenant drains ~4x as fast as a
# weight-1 tenant under contention.
SERVE_TENANT_DEFAULT_WEIGHT = "hyperspace.serve.tenant.defaultWeight"
SERVE_TENANT_DEFAULT_WEIGHT_DEFAULT = 1.0
# Per-tenant queue-depth cap: a tenant whose own backlog reaches this
# is rejected even when the global queue has room — one tenant's burst
# cannot consume the whole admission budget.
SERVE_TENANT_DEFAULT_MAX_QUEUE = "hyperspace.serve.tenant.defaultMaxQueue"
SERVE_TENANT_DEFAULT_MAX_QUEUE_DEFAULT = 32
# Per-tenant in-flight cap: how many of a tenant's queries may occupy
# workers at once (0 or negative = no cap).
SERVE_TENANT_DEFAULT_MAX_INFLIGHT = "hyperspace.serve.tenant.defaultMaxInflight"
SERVE_TENANT_DEFAULT_MAX_INFLIGHT_DEFAULT = 0
# Circuit breaker: this many CONSECUTIVE deadline misses open a
# tenant's circuit; while open, submissions are rejected immediately
# (retry-after = the remaining cooldown). After openSeconds the breaker
# goes HALF-OPEN: exactly one probe query is admitted — a clean finish
# closes the circuit, another miss re-opens it.
SERVE_BREAKER_MISS_THRESHOLD = "hyperspace.serve.tenant.breaker.missThreshold"
SERVE_BREAKER_MISS_THRESHOLD_DEFAULT = 5
SERVE_BREAKER_OPEN_SECONDS = "hyperspace.serve.tenant.breaker.openSeconds"
SERVE_BREAKER_OPEN_SECONDS_DEFAULT = 5.0
# Load-shed ladder (least- to most-drastic as global occupancy climbs):
#   depth >= highWaterFraction * global queue cap -> submissions from
#     the LOWEST-weight tenant class are rejected first;
#   depth >= batchOffFraction * cap -> micro-batch widening is disabled
#     (each dispatch serves one query: no drain scan, lower per-dispatch
#     latency variance under pressure);
#   the third rung — host-latch degraded mode — is triggered by device
#     failure, not load (the PR-2 latch).
SERVE_SHED_HIGHWATER_FRACTION = "hyperspace.serve.shed.highWaterFraction"
SERVE_SHED_HIGHWATER_FRACTION_DEFAULT = 0.75
SERVE_SHED_BATCH_OFF_FRACTION = "hyperspace.serve.shed.batchOffFraction"
SERVE_SHED_BATCH_OFF_FRACTION_DEFAULT = 0.9
# Sliding window over which per-tenant completion (drain) rate is
# measured; AdmissionRejected.retry_after_s = queued/(drain rate), so
# backoff reflects the tenant's OBSERVED throughput, not a constant.
SERVE_DRAIN_RATE_WINDOW_SECONDS = "hyperspace.serve.retryAfter.windowSeconds"
SERVE_DRAIN_RATE_WINDOW_SECONDS_DEFAULT = 10.0

# --- residency tier ladder ---------------------------------------------------
# Oversubscribed residency (docs/15-streaming-residency.md; no reference
# analog — Spark leans on the OS page cache). The exec caches are
# process-global, so these session knobs set process defaults via
# HyperspaceSession (the residency.knobs module); the matching
# HYPERSPACE_TPU_RESIDENCY_* env vars override both (hbm_cache style).
# Compression: "auto" bit-packs code planes when the raw table exceeds
# the HBM budget; "force" always packs packable columns (tests, and
# deployments that prefer capacity over decode cost); "off" never packs.
RESIDENCY_COMPRESSION = "hyperspace.residency.compression"
RESIDENCY_COMPRESSION_AUTO = "auto"
RESIDENCY_COMPRESSION_FORCE = "force"
RESIDENCY_COMPRESSION_OFF = "off"
RESIDENCY_COMPRESSION_MODES = (
    RESIDENCY_COMPRESSION_AUTO,
    RESIDENCY_COMPRESSION_FORCE,
    RESIDENCY_COMPRESSION_OFF,
)
RESIDENCY_COMPRESSION_DEFAULT = RESIDENCY_COMPRESSION_AUTO
# Streaming block-window tier: "auto" stages oversubscribed tables
# through the double-buffered HBM slab pair; "off" refuses them (host
# path) — the pre-PR-8 behavior.
RESIDENCY_STREAMING = "hyperspace.residency.streaming"
RESIDENCY_STREAMING_AUTO = "auto"
RESIDENCY_STREAMING_OFF = "off"
RESIDENCY_STREAMING_MODES = (RESIDENCY_STREAMING_AUTO, RESIDENCY_STREAMING_OFF)
RESIDENCY_STREAMING_DEFAULT = RESIDENCY_STREAMING_AUTO
# Rows per streamed window (padded up to the mask tile). Two windows'
# device bytes are charged against the HBM budget — the fixed slab pair.
RESIDENCY_STREAMING_WINDOW_ROWS = "hyperspace.residency.streaming.windowRows"
RESIDENCY_STREAMING_WINDOW_ROWS_DEFAULT = 1 << 20
# Frame-of-reference delta packing of the join regions' pre-sorted right
# codes ("on"/"off").
RESIDENCY_FOR_DELTA = "hyperspace.residency.forDelta"
RESIDENCY_FOR_DELTA_DEFAULT = "on"

# --- telemetry ---------------------------------------------------------------
# (reference: telemetry/Constants.scala:20)
EVENT_LOGGER_CLASS = "hyperspace.eventLoggerClass"

# Per-query span tracing (telemetry/trace.py; docs/18-observability.md).
# "on" opens a trace per collect()/served ticket (span sites then record;
# the flight recorder rings completed traces); "off" restores the
# pre-tracing entry points — the A/B lever for tracing overhead. No
# reference analog: Spark delegates this to its UI.
TELEMETRY_TRACING = "hyperspace.telemetry.tracing"
TELEMETRY_TRACING_ON = "on"
TELEMETRY_TRACING_OFF = "off"
TELEMETRY_TRACING_MODES = (TELEMETRY_TRACING_ON, TELEMETRY_TRACING_OFF)
TELEMETRY_TRACING_DEFAULT = TELEMETRY_TRACING_ON
# Flight recorder bounds (telemetry/recorder.py): how many completed
# traces the ring keeps, and how many failure snapshots (device-loss /
# breaker-open / shed) are retained. Process-global; adopted at session
# construction like the residency knobs.
TELEMETRY_RECORDER_ENTRIES = "hyperspace.telemetry.recorder.entries"
TELEMETRY_RECORDER_ENTRIES_DEFAULT = 64
TELEMETRY_RECORDER_SNAPSHOTS = "hyperspace.telemetry.recorder.snapshots"
TELEMETRY_RECORDER_SNAPSHOTS_DEFAULT = 8
# Opt-in on-disk metrics rotation (telemetry/export.py): unset = off;
# "auto" resolves to <system path>/_hyperspace_metrics (next to the
# operation log); any other value is the directory itself. stats()
# appends one JSON-lines snapshot per call, size-rotated.
TELEMETRY_EXPORT_DIR = "hyperspace.telemetry.export.dir"
TELEMETRY_EXPORT_DIR_AUTO = "auto"
TELEMETRY_METRICS_DIRNAME = "_hyperspace_metrics"
TELEMETRY_EXPORT_ROTATE_BYTES = "hyperspace.telemetry.export.rotateBytes"
TELEMETRY_EXPORT_ROTATE_BYTES_DEFAULT = 4 * 1024 * 1024
TELEMETRY_EXPORT_KEEP = "hyperspace.telemetry.export.keep"
TELEMETRY_EXPORT_KEEP_DEFAULT = 4

# --- signature provider ------------------------------------------------------
SIGNATURE_PROVIDER = "hyperspace.index.signatureProvider"

# --- TPU execution -----------------------------------------------------------
# TPU-specific knobs with no reference analog: mesh axis used for bucket
# (data) parallelism, and the on-disk row-block alignment for HBM streaming.
TPU_MESH_BUCKET_AXIS = "hyperspace.tpu.mesh.bucketAxis"
TPU_MESH_BUCKET_AXIS_DEFAULT = "buckets"
# Devices of the session's bucket mesh when HyperspaceSession is given no
# ``mesh``: above 1 the session builds a 1-D mesh over the first n local
# devices (builds shard through the ICI all_to_all; queries run the
# shard_map engine); 1 keeps the single-device paths. More devices than
# are visible raises at session construction, never falls back.
TPU_MESH_DEVICES = "hyperspace.tpu.mesh.devices"
TPU_MESH_DEVICES_DEFAULT = 1
STORAGE_BLOCK_ALIGN = 128  # bytes; lane-friendly alignment for column buffers
# Below this many total rows a mesh query executes host-side: the fixed
# dispatch+transfer latency of a shard_map call cannot win on small data
# (same gate philosophy as the single-device scan's MIN_DEVICE_ROWS).
TPU_DISTRIBUTED_MIN_ROWS = "hyperspace.tpu.distributedQuery.minRows"
TPU_DISTRIBUTED_MIN_ROWS_DEFAULT = 1_000_000
# When set to a directory, query execution runs under jax.profiler.trace —
# the XLA-level view (per-op device timing, HLO) complementing the
# engine-level metrics registry (SURVEY §5.1: "JAX profiler + per-kernel
# timing"). The reference delegates the equivalent to the Spark UI.
TPU_PROFILE_DIR = "hyperspace.tpu.profile.dir"

# --- whole-plan compilation (hyperspace_tpu/compile) -------------------------
# Lower an optimized plan subtree to ONE fused pipeline (docs/17): "auto"
# compiles every executed plan (interpreter stays the fallback leg),
# "off" restores pure per-operator interpretation (the A/B lever).
COMPILE_MODE = "hyperspace.compile.mode"
COMPILE_MODE_AUTO = "auto"
COMPILE_MODE_OFF = "off"
COMPILE_MODES = (COMPILE_MODE_AUTO, COMPILE_MODE_OFF)
COMPILE_MODE_DEFAULT = COMPILE_MODE_AUTO
# Compiled-pipeline cache bound (entries are routing state, not data —
# a few hundred bytes each; the jitted executables they reach live in
# their own bounded caches).
COMPILE_CACHE_ENTRIES = "hyperspace.compile.cacheEntries"
COMPILE_CACHE_ENTRIES_DEFAULT = 256
# RESULT cache stub riding the pipeline fingerprint (ROADMAP PR-9
# follow-up): memoize finished result tables keyed on (value-level plan
# signature, index-log version token). Off by default — result reuse is
# only sound for workloads that tolerate snapshot-stale reads within one
# log version, which is exactly what the version-token key guarantees,
# but the memory trade is the operator's call.
COMPILE_RESULT_CACHE = "hyperspace.compile.resultCache"
COMPILE_RESULT_CACHE_ON = "on"
COMPILE_RESULT_CACHE_OFF = "off"
COMPILE_RESULT_CACHE_MODES = (COMPILE_RESULT_CACHE_ON, COMPILE_RESULT_CACHE_OFF)
COMPILE_RESULT_CACHE_DEFAULT = COMPILE_RESULT_CACHE_OFF
COMPILE_RESULT_CACHE_ENTRIES = "hyperspace.compile.resultCache.entries"
COMPILE_RESULT_CACHE_ENTRIES_DEFAULT = 64
# Per-entry byte ceiling: a memoized result larger than this never
# enters the cache (point lookups and small aggregates are the target;
# memoizing scans-of-everything would just mirror the page cache).
COMPILE_RESULT_CACHE_MAX_BYTES = "hyperspace.compile.resultCache.maxResultBytes"
COMPILE_RESULT_CACHE_MAX_BYTES_DEFAULT = 8 * 1024 * 1024
# Telemetry-driven admission (docs/17): a result is admitted only when
# its observed recompute cost times its fingerprint's repeat rate (a
# sliding window of batch_fingerprints seen at admission) beats its byte
# cost.  windowSize bounds the repeat-rate window; byteRatePerSec is the
# exchange rate turning seconds-saved into bytes-worth-caching (a cached
# byte "pays for itself" when cost_s * repeats * rate >= nbytes).
COMPILE_RESULT_CACHE_WINDOW = "hyperspace.compile.resultCache.windowSize"
COMPILE_RESULT_CACHE_WINDOW_DEFAULT = 512
COMPILE_RESULT_CACHE_BYTE_RATE = "hyperspace.compile.resultCache.byteRatePerSec"
COMPILE_RESULT_CACHE_BYTE_RATE_DEFAULT = 64 * 1024 * 1024
# Fraction of the HBM budget ladder the result cache may claim (its
# bytes charge against the SAME budget residency uses, and shed FIRST —
# cached results are the cheapest thing on the ladder to drop).
COMPILE_RESULT_CACHE_BUDGET_SHARE = (
    "hyperspace.compile.resultCache.budgetShare"
)
COMPILE_RESULT_CACHE_BUDGET_SHARE_DEFAULT = 0.05
