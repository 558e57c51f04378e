"""Typed configuration tree.

The reference spreads configuration over three tiers — Dockerfile/compose env
vars, per-service ``constants.py`` modules, and hard-coded tuning in source
(reference: microservices/binary_executor_image/constants.py,
docker-compose.yml:20-24, builder_image/server.py:57-62).  Here there is one
typed tree covering the store backend, volume roots, API server, mesh shape
and job-engine sizing, overridable from the environment.
"""

from __future__ import annotations

import dataclasses
import os
from pathlib import Path

from learningorchestra_tpu.concurrency_rt import make_lock

#: jax's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: one fixed, git-ignored path in the checkout.  The
#: directory is part of the cache key, so a path that moved between
#: runs would never hit; deployments place it from outside with the
#: jax variable (services/context.py ``_init_backend``).
DEFAULT_XLA_CACHE_DIR = (
    Path(__file__).resolve().parent.parent / ".jax_cache"
)


@dataclasses.dataclass
class StoreConfig:
    """Where artifacts live."""

    # Root directory for the document store (collections + WAL files).
    root: str = "~/.learningorchestra_tpu/store"
    # Root for volume-backed binaries.  The reference keys binary paths by
    # service type onto six named Docker volumes
    # (reference: microservices/binary_executor_image/Dockerfile:10-13).
    volume_root: str = "~/.learningorchestra_tpu/volumes"
    # fsync appends on every write (durable) vs. rely on OS flush (fast).
    durable_writes: bool = False
    # Document-store engine: "auto" | "native" (C++ liblodstore) | "python".
    backend: str = "auto"

    def store_path(self) -> Path:
        return Path(os.path.expanduser(self.root))

    def volume_path(self) -> Path:
        return Path(os.path.expanduser(self.volume_root))


@dataclasses.dataclass
class APIConfig:
    """REST front server (single entry point, replacing the KrakenD gateway +
    9 Flask containers; reference: microservices/krakend/krakend.json)."""

    host: str = "0.0.0.0"
    port: int = 80
    # Reference gateway budget: 10s timeout, 300s cache (krakend.json tail).
    request_timeout_s: float = 10.0
    cache_ttl_s: float = 300.0
    # Concurrency caps: the reference gateway bounds work with its
    # worker pool; here every ADMITTED handler holds a semaphore slot
    # and saturation answers 503 immediately (backpressure instead of
    # unbounded per-request threads), while ``max_connections`` caps
    # raw connection threads underneath (a slow-loris trickling bodies
    # never reaches the handler cap).  <=0 disables either cap.
    max_inflight: int = 64
    max_connections: int = 256
    # GET pagination cap (reference: database_api_image/constants.py:42-44).
    page_limit_max: int = 100
    page_limit_default: int = 20
    api_prefix: str = "/api/learningOrchestra/v1"
    # Host advertised in monitoring (TensorBoard) URLs.  The reference
    # builds these from the box's EXTERNAL IP so a remote client can
    # open them (binary_executor_image/utils.py:358-361); unset means
    # bind+advertise 127.0.0.1 (local dev).  The k8s deploy sets this
    # to the service/node address.
    monitoring_external_host: str | None = None


@dataclasses.dataclass
class JobConfig:
    """Async job engine sizing."""

    max_workers: int = 8
    # Reference Ray placement-group timeout
    # (binary_executor_image/server.py:16).
    start_timeout_s: float = 120.0
    # Weighted-fair dispatch weights per job class (service type) —
    # the reference's fairscheduler pool weights (fairscheduler.xml).
    # Unlisted classes weigh 1; weights are consecutive dispatches per
    # round-robin turn, so {"train": 2} gives training twice the share
    # under contention.  Env: LO_TPU_JOB_WEIGHTS='{"train": 2}'.
    class_weights: dict = dataclasses.field(default_factory=dict)
    # Preemption-retry budget per job (a body raising ``Preempted``
    # re-executes up to this many times).  Env: LO_TPU_JOB_RETRIES.
    max_preemption_retries: int = 3
    # Retry backoff: attempt N sleeps min(max, base * 2**(N-1)) with
    # U[0.5, 1.5) jitter before re-executing — preempted jobs must not
    # re-slam a recovering device pool in lockstep.
    # Env: LO_TPU_JOB_BACKOFF_S / LO_TPU_JOB_BACKOFF_MAX_S.
    retry_backoff_s: float = 0.05
    retry_backoff_max_s: float = 5.0
    # Default wall-clock deadline per dispatched job run (preemption
    # retries included); past it the engine watchdog fails the job
    # and reclaims its worker and chip leases.  <= 0 disables;
    # per-submit ``deadlineS`` overrides.  Env: LO_TPU_JOB_DEADLINE_S.
    deadline_s: float = 0.0
    # Graceful-shutdown drain budget: shutdown(wait=True) waits at
    # most this long for accepted work, then flips every outstanding
    # body's cancel token (jobs/cancel.py), cancels still-queued
    # futures and abandons non-cooperating threads after a short
    # grace — a deadline-failed zombie can no longer hang shutdown.
    # <= 0 keeps the legacy unbounded drain.  Env: LO_TPU_JOB_DRAIN_S.
    shutdown_drain_s: float = 0.0
    # Crash-durable job journal (jobs/journal.py): every job state
    # transition is group-committed to the _job_journal collection's
    # WAL (enqueued on the hot path, drained in FIFO batches by the
    # journal flusher within ~one batch-write time), each boot mints
    # an engine epoch (.engine_epoch) and stale-epoch stragglers are
    # refused at commit time.  Off: legacy in-memory-only engine
    # (interrupted jobs are re-flagged failed at boot, nothing is
    # re-dispatched).  Env: LO_TPU_JOB_JOURNAL.
    journal: bool = True
    # Boot-time recovery: replay the journal and RE-DISPATCH
    # recoverable jobs (train fits resume from their newest managed
    # checkpoint via the PATCH path; queued jobs re-enqueue in order).
    # Off: recovered jobs are terminally failed `orphaned-by-restart`
    # instead (operator re-runs with a bare PATCH).
    # Env: LO_TPU_JOB_JOURNAL_RECOVER.
    journal_recover: bool = True
    # Journal compaction threshold: past this many records, boot-time
    # pruning keeps only the last record of each terminal job (full
    # history is kept for live jobs).  <= 0 disables pruning.
    # Env: LO_TPU_JOB_JOURNAL_MAX.
    journal_max_records: int = 4096


@dataclasses.dataclass
class CompileCacheConfig:
    """Process-wide compiled-program cache (train/compile_cache.py):
    jitted epoch/eval callables survive across jobs so a repeated train
    spec or a same-architecture tune sweep traces once.  Complements
    jax's persistent compilation cache (which dedups only the XLA
    compile, not Python tracing or closure rebuilds)."""

    # Entry cap; <= 0 disables the cache (every job re-traces).
    # Env: LO_TPU_COMPILE_CACHE_ENTRIES.
    max_entries: int = 64
    # Estimated-resident-bytes cap (jax exposes no exact executable
    # size; each entry charges ``entry_bytes``).
    # Env: LO_TPU_COMPILE_CACHE_BYTES.
    max_bytes: int = 2 << 30
    # Per-entry byte estimate. Env: LO_TPU_COMPILE_CACHE_ENTRY_BYTES.
    entry_bytes: int = 32 << 20


@dataclasses.dataclass
class AOTConfig:
    """Durable warm start (train/aot_store.py): hot compiled programs
    are AOT-serialized to disk next to the XLA cache and restored into
    the compile cache at boot, so a restart/deploy serves its first
    dispatches without re-tracing.  Env knobs: LO_TPU_AOT_*."""

    # Master switch — OFF by default: restored executables pin exact
    # shapes/dtypes and device signatures, so durability is an
    # explicit deployment opt-in (both deploy manifests set it).
    # Env: LO_TPU_AOT_ENABLED.
    enabled: bool = False
    # On-disk executable store (blobs + hot-set manifest).
    # Env: LO_TPU_AOT_DIR.
    dir: str = "~/.learningorchestra_tpu/aot_cache"
    # Persisted-entry cap; <= 0 disables the store.
    # Env: LO_TPU_AOT_MAX_ENTRIES.
    max_entries: int = 64
    # Persisted-bytes cap (real serialized sizes from the manifest).
    # Env: LO_TPU_AOT_MAX_BYTES.
    max_bytes: int = 1 << 30
    # Boot pre-warm: restore the manifest's hot set into the compile
    # cache on a background thread at ServiceContext boot.
    # Env: LO_TPU_AOT_PREWARM.
    prewarm: bool = True
    # Fleet: warm a fresh replica against its model's recorded hot
    # bucket set BEFORE the P2C router may pick it.
    # Env: LO_TPU_AOT_REPLICA_PREWARM.
    replica_prewarm: bool = False


@dataclasses.dataclass
class ServeConfig:
    """Resident model serving (serve/): request-coalescing batched
    inference over device-pinned params (POST /serve/<model>/predict).
    Env knobs: LO_TPU_SERVE_*."""

    # Largest coalesced dispatch (rows); also the largest shape bucket,
    # so the deployment compiles <= log2(max_batch)+1 executables per
    # model.  Env: LO_TPU_SERVE_MAX_BATCH.
    max_batch: int = 64
    # Bounded request queue (rows) per served model — beyond it,
    # submit sheds load (HTTP 429 + Retry-After).
    # Env: LO_TPU_SERVE_MAX_QUEUE.
    max_queue: int = 256
    # Flush deadline: a dispatch fires at most this many ms after the
    # OLDEST waiting request arrived — the latency bound a lone request
    # pays for coalescing.  Env: LO_TPU_SERVE_FLUSH_MS.
    flush_ms: float = 5.0
    # Registry caps: resident model count and total parameter bytes
    # (real bytes, summed over param leaves).
    # Env: LO_TPU_SERVE_MAX_MODELS / LO_TPU_SERVE_MAX_BYTES.
    max_models: int = 4
    max_bytes: int = 1 << 30
    # Retry-After seconds advertised with a 429.
    # Env: LO_TPU_SERVE_RETRY_AFTER.
    retry_after_s: float = 1.0


@dataclasses.dataclass
class DecodeConfig:
    """Streaming LM decode engine (serve/decode/): resident KV page
    pools + continuous batching + SSE token streaming behind
    ``POST /serve/<model>/generate``.  Env knobs: LO_TPU_DECODE_*."""

    # Master switch: off, /generate still answers non-stream requests
    # through the solo jitted scan; stream=true is refused (406).
    # Env: LO_TPU_DECODE_ENABLED.
    enabled: bool = True
    # Ceiling of the slot buckets of a KV page pool: a pool grows
    # through the powers of two up to this (1, 2, 4, ... max_slots; the
    # ceiling itself is a bucket even where it is no power of two), so
    # it bounds the sequences in flight per (model, kv bucket) AND the
    # slot dimension of every step executable, and a KV bucket costs
    # floor(log2(max_slots - 1)) + 2 step programs to compile and keep:
    # 4 at the default 8, 7 at 64 (``bucketing.bucket_sizes``).
    # Env: LO_TPU_DECODE_MAX_SLOTS.
    max_slots: int = 8
    # Largest KV-length bucket (pages per slot); also caps prompt+
    # generation length served by the engine.  The effective cap is
    # min(model max_len, this).  Env: LO_TPU_DECODE_MAX_KV.
    max_kv: int = 2048
    # Active + pending stream cap per model — beyond it, submission
    # sheds load (HTTP 429 + Retry-After).
    # Env: LO_TPU_DECODE_MAX_STREAMS.
    max_streams: int = 64
    # Server-side ceiling on a request's maxNewTokens.
    # Env: LO_TPU_DECODE_MAX_NEW.
    max_new_tokens: int = 128
    # Idle decode workers park and free their resident KV pools after
    # this long with no streams.  Env: LO_TPU_DECODE_IDLE_S.
    idle_timeout_s: float = 60.0


@dataclasses.dataclass
class FleetConfig:
    """Fleet serving (serve/fleet/): multi-replica data plane over
    leased chips with metrics-driven autoscaling.  Env knobs:
    LO_TPU_FLEET_*.  Defaults keep the fleet OFF (max 1 replica —
    classic single-batcher serving) until a deployment raises the
    bounds globally or per model (POST /serve/<model>/replicas)."""

    # Autoscaler control loop master switch (replica sets and manual
    # scaling still work when off).  Env: LO_TPU_FLEET_ENABLED.
    enabled: bool = True
    # Deployment-wide default replica bounds per served model;
    # max > 1 puts every served model on the fleet routing path.
    # Env: LO_TPU_FLEET_MIN / LO_TPU_FLEET_MAX.
    min_replicas: int = 1
    max_replicas: int = 1
    # Autoscaler tick interval; <= 0 disables the loop thread.
    # Env: LO_TPU_FLEET_INTERVAL_S.
    interval_s: float = 2.0
    # Scale-up triggers: fleet queue depth as a fraction of total
    # queue capacity sustained for up_ticks consecutive ticks, any
    # shed (429) requests, or p99 latency above up_p99_ms (0 = off).
    # Env: LO_TPU_FLEET_UP_QUEUE_FRAC / LO_TPU_FLEET_UP_TICKS /
    # LO_TPU_FLEET_UP_P99_MS.
    up_queue_frac: float = 0.25
    up_ticks: int = 2
    up_p99_ms: float = 0.0
    # Scale-down after this many consecutive empty-queue ticks.
    # Env: LO_TPU_FLEET_DOWN_TICKS.
    down_ticks: int = 5
    # Queue-depth GROWTH-SLOPE scale-up trigger (rows/second), fitted
    # by least squares over the shared rollup series
    # (lo_serving_model_queue_depth, obs/rollup.py) — reacts to a ramp
    # before the level crosses up_queue_frac.  0 = off; needs the
    # rollup engine enabled and ticking.  Env: LO_TPU_FLEET_UP_SLOPE /
    # LO_TPU_FLEET_SLOPE_WINDOW_S.
    up_slope: float = 0.0
    slope_window_s: float = 30.0
    # Cost-aware scale-up: attributed device-time fraction (per-model
    # device seconds per wall second, obs/costs.py serving ledger)
    # above this triggers scale-up — a model saturating its chip
    # scales BEFORE queues back up.  0 = off.
    # Env: LO_TPU_FLEET_UP_DEVICE_FRAC.
    up_device_frac: float = 0.0
    # Chip-lease budget when placing a new replica; on timeout the
    # scale-up is skipped and retried next tick.
    # Env: LO_TPU_FLEET_LEASE_TIMEOUT_S.
    lease_timeout_s: float = 5.0
    # Router RNG seed (P2C is seeded-deterministic, like the fault
    # plane's schedules).
    router_seed: int = 0
    # Chips leased per replica (deployment default; override per model
    # via POST /serve/<model>/replicas devicesPerReplica).  > 1 makes
    # every replica a multi-chip SHARD GROUP: params place across its
    # devices (serve/fleet/replicaset.py) — models bigger than one
    # chip serve through the same P2C/autoscaler path.
    # Env: LO_TPU_FLEET_DEVICES_PER_REPLICA.
    devices_per_replica: int = 1


@dataclasses.dataclass
class MPMDConfig:
    """MPMD pipeline-parallel training (parallel/mpmd.py): per-stage
    compiled programs driven by a host-side 1F1B dispatcher.  Env
    knobs: LO_TPU_MPMD_*."""

    # Deployment-default pipeline schedule for PipelinedTransformer
    # when the job doesn't pass one: "" keeps the estimator default
    # (gpipe); "gpipe" | "1f1b" | "mpmd" force it fleet-wide.
    # Env: LO_TPU_MPMD_SCHEDULE.
    schedule: str = ""
    # Default microbatch count when the job doesn't pass
    # n_microbatches; 0 = auto (2 × pipeline stages).
    # Env: LO_TPU_MPMD_MICRO.
    n_micro: int = 0


@dataclasses.dataclass
class ObsConfig:
    """Unified observability layer (obs/): metrics registry +
    Prometheus exposition at GET /metrics.prom + end-to-end job trace
    spans.  Env knobs: LO_TPU_OBS_*."""

    # Master switch: off makes every metric/span primitive a no-op.
    # Env: LO_TPU_OBS_ENABLED.
    enabled: bool = True
    # Job tracing (request-id propagation + spans persisted into the
    # execution ledger); metrics stay on when only this is off.
    # Env: LO_TPU_OBS_TRACE.
    trace: bool = True
    # Label-cardinality cap per metric: past it, new label
    # combinations collapse into one ``_overflow`` series.
    # Env: LO_TPU_OBS_MAX_SERIES.
    max_series: int = 1024
    # Span cap per job trace (an epoch-per-span 10k-epoch fit must
    # not grow the ledger record without bound).
    # Env: LO_TPU_OBS_MAX_SPANS.
    max_spans: int = 512
    # Span-ledger sampling (0.0-1.0): the fraction of jobs whose span
    # trees persist, decided deterministically per requestId (a
    # retried request samples the same way).  Sampled-out jobs keep
    # every metric; only the span tree is skipped.
    # Env: LO_TPU_OBS_TRACE_SAMPLE.
    trace_sample: float = 1.0
    # Latency histogram bucket edges, milliseconds, ascending.
    # Env: LO_TPU_OBS_BUCKETS_MS (comma-separated).
    latency_buckets_ms: tuple = (
        1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
        250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0, 30000.0,
    )


@dataclasses.dataclass
class RollupConfig:
    """Windowed time-series rollups (obs/rollup.py): a daemon that
    snapshots selected registry families on a fixed tick into bounded
    ring buffers and derives windowed views — counter rates, gauge
    min/avg/max, histogram-delta quantiles — served at
    ``GET /observability/timeseries``.  Env knobs: LO_TPU_ROLLUP_*."""

    # Master switch: off, no snapshots are taken, the timeseries
    # endpoint answers empty, and SLO evaluation (which reads rollup
    # windows) is implicitly off too.  Env: LO_TPU_ROLLUP_ENABLED.
    enabled: bool = True
    # Snapshot cadence; <= 0 disables the daemon thread (tick() stays
    # callable — tests drive the schedule deterministically).
    # Env: LO_TPU_ROLLUP_TICK_S.
    tick_s: float = 10.0
    # Ring length per series: points * tick_s is the retention window
    # (defaults: 360 x 10 s = 1 h, covering the SLO slow window).
    # Env: LO_TPU_ROLLUP_POINTS.
    points: int = 360
    # Total tracked series across families; past it, NEW series are
    # dropped (counted, surfaced) instead of growing memory unbounded.
    # Env: LO_TPU_ROLLUP_MAX_SERIES.
    max_series: int = 2048
    # Extra family names to track on top of the built-in core set
    # (HTTP counters/latency, job states, queue depths, predict
    # latency).  Env: LO_TPU_ROLLUP_FAMILIES (comma-separated).
    families: tuple = ()


@dataclasses.dataclass
class SLOConfig:
    """Declarative SLO objectives + multi-window burn-rate alerting
    over the rollup series (obs/slo.py): route availability, per-model
    predict latency, job success rate — each with an error budget, a
    pending → firing → resolved alert state machine
    (``GET /observability/alerts``), ``lo_alert_active`` /
    ``lo_slo_burn_rate`` Prometheus families, and a pluggable sink
    (structured log line always; webhook POST when ``webhook`` is
    set).  Env knobs: LO_TPU_SLO_*."""

    # Master switch for evaluation; the rollup engine keeps ticking
    # when off (timeseries remain queryable).  Env: LO_TPU_SLO_ENABLED.
    enabled: bool = True
    # Route availability objective: 1 - target is the 5xx error
    # budget over the slow window.  Env: LO_TPU_SLO_AVAILABILITY.
    availability_target: float = 0.999
    # Per-model predict latency objective: at least predict_target of
    # predicts complete under predict_p99_ms.  0 ms disables the
    # objective.  Env: LO_TPU_SLO_PREDICT_P99_MS /
    # LO_TPU_SLO_PREDICT_TARGET.
    predict_p99_ms: float = 250.0
    predict_target: float = 0.99
    # Streamed-decode time-to-first-token objective: at least
    # decode_ttft_target of streams see their first token under
    # decode_ttft_ms.  0 ms disables the objective (the default — a
    # deployment opts in when it serves LMs).
    # Env: LO_TPU_SLO_DECODE_TTFT_MS / LO_TPU_SLO_DECODE_TTFT_TARGET.
    decode_ttft_ms: float = 0.0
    decode_ttft_target: float = 0.99
    # Job success objective: finished / (finished + failed + deadline)
    # over the window.  Env: LO_TPU_SLO_JOB_SUCCESS.
    job_success_target: float = 0.99
    # Multi-window burn-rate evaluation: an alert needs the burn rate
    # over BOTH windows above ``burn_threshold`` (fast catches the
    # page-now spike, slow stops a brief blip from paging).  Scaled
    # down by tests so drills run in seconds.
    # Env: LO_TPU_SLO_FAST_S / LO_TPU_SLO_SLOW_S / LO_TPU_SLO_BURN.
    fast_window_s: float = 300.0
    slow_window_s: float = 3600.0
    burn_threshold: float = 14.4
    # Alert state machine dwell times: a breach is ``pending`` until
    # it holds for ``for_s``, then ``firing``; a firing alert resolves
    # after ``resolve_s`` breach-free seconds.
    # Env: LO_TPU_SLO_FOR_S / LO_TPU_SLO_RESOLVE_S.
    for_s: float = 60.0
    resolve_s: float = 300.0
    # Webhook sink URL (POSTed JSON on firing/resolved transitions).
    # Empty = webhook delivery off (the default — the structured log
    # sink still records every transition).  Env: LO_TPU_SLO_WEBHOOK.
    webhook: str = ""


@dataclasses.dataclass
class CostsConfig:
    """Cost-accounting plane (obs/costs.py): per-program FLOPs/HBM
    ledgers from XLA cost/memory analysis at compile-cache build time,
    plus sampled per-dispatch device-time attribution (per job, per
    served model, per serving bucket).  Env knobs: LO_TPU_COSTS_*."""

    # Master switch: off, builders skip analysis and the per-dispatch
    # hook is one config check.  Env: LO_TPU_COSTS_ENABLED.
    enabled: bool = True
    # Deep analysis: AOT-compile each analyzed program once at build
    # time for Compiled.memory_analysis() (HBM footprint) and the
    # serialized executable size the compile cache's byte cap charges.
    # The extra XLA compile is per cache ENTRY (amortized over every
    # job that hits it) and dedups against the persistent XLA disk
    # cache; off, analysis stops at Lowered.cost_analysis() (flops /
    # bytes, no backend compile) and the byte cap falls back to the
    # flat estimate.  Env: LO_TPU_COSTS_DEEP.
    deep: bool = True
    # Per-dispatch attribution sampling (0.0-1.0): every k-th dispatch
    # records, contributions scaled by k — deterministic and unbiased.
    # QUANTIZED to 1/round(1/sample): only 1, 1/2, 1/3, ... thin —
    # 0.7 still records every dispatch; use 0.5, 0.1, 0.01 etc.
    # Env: LO_TPU_COSTS_SAMPLE.
    sample: float = 1.0
    # Ledger bounds: distinct program fingerprints / freshest jobs.
    # Env: LO_TPU_COSTS_MAX_PROGRAMS / LO_TPU_COSTS_MAX_JOBS.
    max_programs: int = 256
    max_jobs: int = 64
    # Per-chip peak FLOP/s for model-FLOPs-utilization gauges (e.g.
    # 2.75e14 for TPU v4 bf16).  0 = unknown: MFU is omitted rather
    # than fabricated.  Env: LO_TPU_COSTS_PEAK_FLOPS.
    peak_flops: float = 0.0


@dataclasses.dataclass
class ProfilingConfig:
    """On-demand profiler capture (obs/profiling.py): jax.profiler
    behind POST /observability/profile/start|stop.  Env knobs:
    LO_TPU_PROF_*."""

    # Capture root; "" derives <volume_root>/_profiles at server
    # construction.  Env: LO_TPU_PROF_DIR.
    dir: str = ""
    # Auto-stop deadline per capture (also the cap on a request's
    # maxSeconds): a forgotten capture must not trace forever.
    # Env: LO_TPU_PROF_MAX_S.
    max_seconds: float = 60.0
    # Retained captures; older ones are deleted on the next start.
    # Env: LO_TPU_PROF_MAX_CAPTURES.
    max_captures: int = 8


@dataclasses.dataclass
class FlightConfig:
    """Always-on flight recorder (obs/flight.py): bounded per-domain
    event rings holding the last N runtime events — HTTP requests,
    decode stream steps, job dispatch decisions, compile-cache builds,
    fault triggers, lock contention — each stamped with monotonic time
    and the request id.  Env knobs: LO_TPU_FLIGHT_*."""

    # Master switch.  Disabled, every record() is one global check.
    # Env: LO_TPU_FLIGHT_ENABLED.
    enabled: bool = True
    # Ring capacity per domain; the newest events win.  Retention in
    # seconds = events / event rate, so size for the fast domains
    # (decode steps) — 512 covers ~30 s of a busy decoder.
    # Env: LO_TPU_FLIGHT_EVENTS.
    events: int = 512


@dataclasses.dataclass
class BundleConfig:
    """Debug-bundle assembler (obs/bundle.py): on an SLO alert firing,
    a watchdog stall, a retries-exhausted job failure or a manual
    POST, snapshot the flight rings + metrics + rollup tails + SLO
    state + fleet ledger + journal tail into a versioned on-disk
    bundle.  Env knobs: LO_TPU_BUNDLE_*."""

    # Master switch for trigger-driven capture (the REST list/fetch
    # surface stays probeable either way).  Env: LO_TPU_BUNDLE_ENABLED.
    enabled: bool = True
    # Bundle root; "" derives <volume_root>/_bundles at server
    # construction.  Env: LO_TPU_BUNDLE_DIR.
    dir: str = ""
    # Retained bundles; oldest pruned after each build.
    # Env: LO_TPU_BUNDLE_MAX.
    max_bundles: int = 8
    # Minimum seconds between AUTO-triggered bundles: an alert storm
    # lands one bundle, not fifty (manual POSTs bypass this).
    # Env: LO_TPU_BUNDLE_DEBOUNCE_S.
    debounce_s: float = 300.0
    # Auto-start a short jax.profiler capture with each bundle (off by
    # default: a device trace is not free at incident time).
    # Env: LO_TPU_BUNDLE_PROFILE / LO_TPU_BUNDLE_PROFILE_S.
    profile: bool = False
    profile_s: float = 2.0
    # Journal records included in the bundle's tail (newest-last).
    # Env: LO_TPU_BUNDLE_JOURNAL_TAIL.
    journal_tail: int = 200


@dataclasses.dataclass
class MeshConfig:
    """Logical device-mesh shape for distributed execution.

    Axis names are fixed framework-wide:
      - ``dp``: data parallelism (batch sharding)
      - ``fsdp``: parameter sharding within the data axis (zero-style)
      - ``tp``: tensor parallelism (feature/head sharding)
      - ``sp``: sequence/context parallelism (ring attention)
      - ``pp``: pipeline stages
      - ``ep``: expert parallelism (MoE expert sharding)
    A dimension of 0 means "auto": fill with remaining devices on dp.
    """

    dp: int = 0
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1

    axis_names: tuple = ("dp", "fsdp", "pp", "ep", "tp", "sp")

    def shape(self, n_devices: int) -> dict:
        fixed = self.fsdp * self.tp * self.sp * self.pp * self.ep
        dp = self.dp
        if dp == 0:
            if n_devices % max(fixed, 1) != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {fixed}"
                )
            dp = n_devices // max(fixed, 1)
        return {
            "dp": dp,
            "fsdp": self.fsdp,
            "pp": self.pp,
            "ep": self.ep,
            "tp": self.tp,
            "sp": self.sp,
        }


@dataclasses.dataclass
class DistributedConfig:
    """Multi-host (DCN) bootstrap — replaces Ray GCS + client
    (reference: binary_executor_image/start.sh:7, server.py:13-17)."""

    coordinator_address: str | None = None  # "host:port" of process 0
    num_processes: int = 1
    process_id: int = 0
    agent_port: int = 7077  # per-host agent control port
    # Cluster mode: when set, POST /train/horovod dispatches the fit to
    # HostAgents through the task Coordinator (parallel/coordinator.py)
    # instead of fitting in-process — the reference's RayExecutor.run
    # fan-out (binary_execution.py:237-292), SPMD-style.
    task_coordinator: str | None = None  # Coordinator HTTP "host:port"
    jax_coordinator: str | None = None  # jax.distributed rendezvous
    # Cluster fit wall-clock budget; on expiry the job is cancelled at
    # the coordinator and this side records failure.  Generous default:
    # real fine-tunes run for hours.
    job_timeout_s: float = 86400.0


@dataclasses.dataclass
class HAConfig:
    """Store failover pairing (store/ha.py — the reference's mongo
    replica set, reference: docker-compose.yml:42-90)."""

    # "host:port" of the HA partner node: the standby before promotion,
    # the old primary after.  When set, serve() refuses to start — and
    # a running primary self-demotes — if the peer answers
    # /replication/status with a HIGHER election epoch (it promoted
    # over this store during a partition).  Needs no shared disk.
    peer: str = ""
    # Seconds between fence/peer-epoch checks while serving.  Bounds
    # the dual-writable window when a primary revives during its
    # standby's promotion (no shared disk = no fence file to see).
    # <= 0 keeps the server default (APIServer.FENCE_CHECK_INTERVAL_S).
    fence_interval_s: float = 0.0
    # A fenced primary automatically rejoins as the NEW primary's
    # standby (network WAL shipping into <store>.rejoined) instead of
    # exiting — mongo's stepped-down-primary-rejoins-as-secondary,
    # restoring pair redundancy with no operator action.  Off by
    # default: rejoining re-syncs the full store over the wire.
    auto_rejoin: bool = False
    # Takeover tuning for the auto-rejoined standby.  Defaults match
    # the deployed standby role's deliberately conservative window
    # (2 s x 15 = 30 s dead): an ordinary restart of the partner —
    # process boot alone exceeds a naive threshold — must never get
    # fenced out by the rejoined node.
    rejoin_interval_s: float = 2.0
    rejoin_misses: int = 15


@dataclasses.dataclass
class FaultsConfig:
    """Fault-injection plane (faults/plane.py): seeded chaos schedules
    armed at boot from ``LO_TPU_FAULT_<POINT>=<mode>[:k=v,...]`` env
    vars (e.g. ``LO_TPU_FAULT_ENGINE_DISPATCH=preempt:rate=0.5,seed=7``)
    — the API server passes ``specs`` to ``faults.load_env`` at
    construction.  Disabled (no vars) the plane costs one dict-empty
    check per probe."""

    # point-name suffix (env spelling) -> raw spec string.
    specs: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class ClusterConfig:
    """Scale-out control plane (jobs/cluster.py): N engine processes
    over ONE store root share dispatch through a store-backed claim
    table with heartbeat-renewed leases.  Requires the python store
    backend (the claim table needs its WAL-refresh coherence
    primitive); single-engine deployments leave it off and pay only a
    None-check per dispatch."""

    # Join the cluster at boot.  Env: LO_TPU_CLUSTER_ENABLED.
    enabled: bool = False
    # Stable engine identity in the claim table ("" derives
    # engine-<pid>).  Two engines sharing an id would see each other's
    # claims as their own — give each process a distinct one.
    # Env: LO_TPU_CLUSTER_ENGINE_ID.
    engine_id: str = ""
    # Lease renewal cadence.  Env: LO_TPU_CLUSTER_HEARTBEAT_S.
    heartbeat_s: float = 1.0
    # A claim (or engine) whose heartbeat is older than this is dead
    # and stealable.  Must comfortably exceed heartbeat_s; the two
    # engines' clocks must agree to within it.
    # Env: LO_TPU_CLUSTER_TTL_S.
    ttl_s: float = 5.0
    # Expired-claim sweep cadence.  Env: LO_TPU_CLUSTER_SWEEP_S.
    sweep_s: float = 2.0


@dataclasses.dataclass
class TenantConfig:
    """Per-tenant fair-share admission (jobs/cluster.py
    TenantAdmission): quotas on the X-Tenant request header, enforced
    at the API tier with 429 + Retry-After.  Under clustering the
    counters live in the claim collection so every engine rejects
    identically.  0 disables a quota."""

    # Max queued-but-undispatched jobs per tenant.
    # Env: LO_TPU_TENANT_MAX_QUEUED.
    max_queued: int = 0
    # Max concurrently RUNNING fits (executor/distributed classes)
    # per tenant.  Env: LO_TPU_TENANT_MAX_RUNNING.
    max_running: int = 0
    # Retry-After seconds on a quota rejection.
    # Env: LO_TPU_TENANT_RETRY_AFTER_S.
    retry_after_s: float = 1.0


@dataclasses.dataclass
class Config:
    store: StoreConfig = dataclasses.field(default_factory=StoreConfig)
    api: APIConfig = dataclasses.field(default_factory=APIConfig)
    jobs: JobConfig = dataclasses.field(default_factory=JobConfig)
    compile_cache: CompileCacheConfig = dataclasses.field(
        default_factory=CompileCacheConfig
    )
    aot: AOTConfig = dataclasses.field(default_factory=AOTConfig)
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)
    decode: DecodeConfig = dataclasses.field(
        default_factory=DecodeConfig
    )
    fleet: FleetConfig = dataclasses.field(default_factory=FleetConfig)
    mpmd: MPMDConfig = dataclasses.field(default_factory=MPMDConfig)
    obs: ObsConfig = dataclasses.field(default_factory=ObsConfig)
    rollup: RollupConfig = dataclasses.field(
        default_factory=RollupConfig
    )
    slo: SLOConfig = dataclasses.field(default_factory=SLOConfig)
    costs: CostsConfig = dataclasses.field(default_factory=CostsConfig)
    profiling: ProfilingConfig = dataclasses.field(
        default_factory=ProfilingConfig
    )
    flight: FlightConfig = dataclasses.field(
        default_factory=FlightConfig
    )
    bundle: BundleConfig = dataclasses.field(
        default_factory=BundleConfig
    )
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    dist: DistributedConfig = dataclasses.field(
        default_factory=DistributedConfig
    )
    ha: HAConfig = dataclasses.field(default_factory=HAConfig)
    faults: FaultsConfig = dataclasses.field(
        default_factory=FaultsConfig
    )
    cluster: ClusterConfig = dataclasses.field(
        default_factory=ClusterConfig
    )
    tenant: TenantConfig = dataclasses.field(
        default_factory=TenantConfig
    )

    @staticmethod
    def from_env() -> "Config":
        """Build a config from LO_TPU_* environment variables."""
        cfg = Config()
        env = os.environ
        if "LO_TPU_STORE_ROOT" in env:
            cfg.store.root = env["LO_TPU_STORE_ROOT"]
        if "LO_TPU_VOLUME_ROOT" in env:
            cfg.store.volume_root = env["LO_TPU_VOLUME_ROOT"]
        if "LO_TPU_STORE_BACKEND" in env:
            cfg.store.backend = env["LO_TPU_STORE_BACKEND"]
        if "LO_TPU_API_PORT" in env:
            cfg.api.port = int(env["LO_TPU_API_PORT"])
        if "LO_TPU_MONITORING_EXTERNAL_HOST" in env:
            cfg.api.monitoring_external_host = (
                env["LO_TPU_MONITORING_EXTERNAL_HOST"] or None
            )
        if "LO_TPU_MAX_WORKERS" in env:
            cfg.jobs.max_workers = int(env["LO_TPU_MAX_WORKERS"])
        if "LO_TPU_JOB_WEIGHTS" in env:
            import json as _json

            cfg.jobs.class_weights = {
                str(k): int(v)
                for k, v in _json.loads(env["LO_TPU_JOB_WEIGHTS"]).items()
            }
        if "LO_TPU_JOB_RETRIES" in env:
            cfg.jobs.max_preemption_retries = int(
                env["LO_TPU_JOB_RETRIES"]
            )
        if "LO_TPU_JOB_BACKOFF_S" in env:
            cfg.jobs.retry_backoff_s = float(env["LO_TPU_JOB_BACKOFF_S"])
        if "LO_TPU_JOB_BACKOFF_MAX_S" in env:
            cfg.jobs.retry_backoff_max_s = float(
                env["LO_TPU_JOB_BACKOFF_MAX_S"]
            )
        if "LO_TPU_JOB_DEADLINE_S" in env:
            cfg.jobs.deadline_s = float(env["LO_TPU_JOB_DEADLINE_S"])
        if "LO_TPU_JOB_DRAIN_S" in env:
            cfg.jobs.shutdown_drain_s = float(
                env["LO_TPU_JOB_DRAIN_S"]
            )
        # Fault-injection schedules: every LO_TPU_FAULT_<POINT> var is
        # carried verbatim; the API server arms them via faults.load_env
        # (bad specs are rejected LOUDLY there — a typo'd chaos knob
        # silently doing nothing would fake a green drill).
        for key, raw in env.items():
            if key.startswith("LO_TPU_FAULT_") and raw.strip():
                cfg.faults.specs[key[len("LO_TPU_FAULT_"):]] = raw
        if "LO_TPU_COMPILE_CACHE_ENTRIES" in env:
            cfg.compile_cache.max_entries = int(
                env["LO_TPU_COMPILE_CACHE_ENTRIES"]
            )
        if "LO_TPU_COMPILE_CACHE_BYTES" in env:
            cfg.compile_cache.max_bytes = int(
                env["LO_TPU_COMPILE_CACHE_BYTES"]
            )
        if "LO_TPU_COMPILE_CACHE_ENTRY_BYTES" in env:
            cfg.compile_cache.entry_bytes = int(
                env["LO_TPU_COMPILE_CACHE_ENTRY_BYTES"]
            )
        if "LO_TPU_SERVE_MAX_BATCH" in env:
            cfg.serve.max_batch = int(env["LO_TPU_SERVE_MAX_BATCH"])
        if "LO_TPU_SERVE_MAX_QUEUE" in env:
            cfg.serve.max_queue = int(env["LO_TPU_SERVE_MAX_QUEUE"])
        if "LO_TPU_SERVE_FLUSH_MS" in env:
            cfg.serve.flush_ms = float(env["LO_TPU_SERVE_FLUSH_MS"])
        if "LO_TPU_SERVE_MAX_MODELS" in env:
            cfg.serve.max_models = int(env["LO_TPU_SERVE_MAX_MODELS"])
        if "LO_TPU_SERVE_MAX_BYTES" in env:
            cfg.serve.max_bytes = int(env["LO_TPU_SERVE_MAX_BYTES"])
        if "LO_TPU_SERVE_RETRY_AFTER" in env:
            cfg.serve.retry_after_s = float(
                env["LO_TPU_SERVE_RETRY_AFTER"]
            )
        def _bool_env(key: str) -> bool:
            # Same loud-rejection contract as LO_HA_AUTO_REJOIN: a
            # silently-misparsed "true" would run production blind.
            raw = env[key].strip().lower()
            if raw in ("1", "true", "yes", "on"):
                return True
            if raw in ("0", "false", "no", "off", ""):
                return False
            raise ValueError(
                f"{key}={env[key]!r} is not a recognized boolean "
                "(use 1/0, true/false, yes/no, on/off)"
            )

        if "LO_TPU_JOB_JOURNAL" in env:
            cfg.jobs.journal = _bool_env("LO_TPU_JOB_JOURNAL")
        if "LO_TPU_JOB_JOURNAL_RECOVER" in env:
            cfg.jobs.journal_recover = _bool_env(
                "LO_TPU_JOB_JOURNAL_RECOVER"
            )
        if "LO_TPU_JOB_JOURNAL_MAX" in env:
            cfg.jobs.journal_max_records = int(
                env["LO_TPU_JOB_JOURNAL_MAX"]
            )
        if "LO_TPU_CLUSTER_ENABLED" in env:
            cfg.cluster.enabled = _bool_env("LO_TPU_CLUSTER_ENABLED")
        if "LO_TPU_CLUSTER_ENGINE_ID" in env:
            cfg.cluster.engine_id = env["LO_TPU_CLUSTER_ENGINE_ID"]
        if "LO_TPU_CLUSTER_HEARTBEAT_S" in env:
            cfg.cluster.heartbeat_s = float(
                env["LO_TPU_CLUSTER_HEARTBEAT_S"]
            )
        if "LO_TPU_CLUSTER_TTL_S" in env:
            cfg.cluster.ttl_s = float(env["LO_TPU_CLUSTER_TTL_S"])
        if "LO_TPU_CLUSTER_SWEEP_S" in env:
            cfg.cluster.sweep_s = float(env["LO_TPU_CLUSTER_SWEEP_S"])
        if "LO_TPU_TENANT_MAX_QUEUED" in env:
            cfg.tenant.max_queued = int(
                env["LO_TPU_TENANT_MAX_QUEUED"]
            )
        if "LO_TPU_TENANT_MAX_RUNNING" in env:
            cfg.tenant.max_running = int(
                env["LO_TPU_TENANT_MAX_RUNNING"]
            )
        if "LO_TPU_TENANT_RETRY_AFTER_S" in env:
            cfg.tenant.retry_after_s = float(
                env["LO_TPU_TENANT_RETRY_AFTER_S"]
            )
        if "LO_TPU_AOT_ENABLED" in env:
            cfg.aot.enabled = _bool_env("LO_TPU_AOT_ENABLED")
        if "LO_TPU_AOT_DIR" in env:
            cfg.aot.dir = env["LO_TPU_AOT_DIR"]
        if "LO_TPU_AOT_MAX_ENTRIES" in env:
            cfg.aot.max_entries = int(env["LO_TPU_AOT_MAX_ENTRIES"])
        if "LO_TPU_AOT_MAX_BYTES" in env:
            cfg.aot.max_bytes = int(env["LO_TPU_AOT_MAX_BYTES"])
        if "LO_TPU_AOT_PREWARM" in env:
            cfg.aot.prewarm = _bool_env("LO_TPU_AOT_PREWARM")
        if "LO_TPU_AOT_REPLICA_PREWARM" in env:
            cfg.aot.replica_prewarm = _bool_env(
                "LO_TPU_AOT_REPLICA_PREWARM"
            )
        if "LO_TPU_DECODE_ENABLED" in env:
            cfg.decode.enabled = _bool_env("LO_TPU_DECODE_ENABLED")
        if "LO_TPU_DECODE_MAX_SLOTS" in env:
            cfg.decode.max_slots = int(env["LO_TPU_DECODE_MAX_SLOTS"])
        if "LO_TPU_DECODE_MAX_KV" in env:
            cfg.decode.max_kv = int(env["LO_TPU_DECODE_MAX_KV"])
        if "LO_TPU_DECODE_MAX_STREAMS" in env:
            cfg.decode.max_streams = int(
                env["LO_TPU_DECODE_MAX_STREAMS"]
            )
        if "LO_TPU_DECODE_MAX_NEW" in env:
            cfg.decode.max_new_tokens = int(
                env["LO_TPU_DECODE_MAX_NEW"]
            )
        if "LO_TPU_DECODE_IDLE_S" in env:
            cfg.decode.idle_timeout_s = float(
                env["LO_TPU_DECODE_IDLE_S"]
            )
        if "LO_TPU_FLEET_ENABLED" in env:
            cfg.fleet.enabled = _bool_env("LO_TPU_FLEET_ENABLED")
        if "LO_TPU_FLEET_MIN" in env:
            cfg.fleet.min_replicas = int(env["LO_TPU_FLEET_MIN"])
        if "LO_TPU_FLEET_MAX" in env:
            cfg.fleet.max_replicas = int(env["LO_TPU_FLEET_MAX"])
        if "LO_TPU_FLEET_INTERVAL_S" in env:
            cfg.fleet.interval_s = float(env["LO_TPU_FLEET_INTERVAL_S"])
        if "LO_TPU_FLEET_UP_QUEUE_FRAC" in env:
            cfg.fleet.up_queue_frac = float(
                env["LO_TPU_FLEET_UP_QUEUE_FRAC"]
            )
        if "LO_TPU_FLEET_UP_TICKS" in env:
            cfg.fleet.up_ticks = int(env["LO_TPU_FLEET_UP_TICKS"])
        if "LO_TPU_FLEET_DOWN_TICKS" in env:
            cfg.fleet.down_ticks = int(env["LO_TPU_FLEET_DOWN_TICKS"])
        if "LO_TPU_FLEET_UP_P99_MS" in env:
            cfg.fleet.up_p99_ms = float(env["LO_TPU_FLEET_UP_P99_MS"])
        if "LO_TPU_FLEET_UP_SLOPE" in env:
            cfg.fleet.up_slope = float(env["LO_TPU_FLEET_UP_SLOPE"])
        if "LO_TPU_FLEET_SLOPE_WINDOW_S" in env:
            cfg.fleet.slope_window_s = float(
                env["LO_TPU_FLEET_SLOPE_WINDOW_S"]
            )
        if "LO_TPU_FLEET_UP_DEVICE_FRAC" in env:
            cfg.fleet.up_device_frac = float(
                env["LO_TPU_FLEET_UP_DEVICE_FRAC"]
            )
        if "LO_TPU_FLEET_LEASE_TIMEOUT_S" in env:
            cfg.fleet.lease_timeout_s = float(
                env["LO_TPU_FLEET_LEASE_TIMEOUT_S"]
            )
        if "LO_TPU_FLEET_DEVICES_PER_REPLICA" in env:
            cfg.fleet.devices_per_replica = int(
                env["LO_TPU_FLEET_DEVICES_PER_REPLICA"]
            )
        if cfg.fleet.devices_per_replica < 1:
            raise ValueError(
                "LO_TPU_FLEET_DEVICES_PER_REPLICA must be >= 1, got "
                f"{cfg.fleet.devices_per_replica}"
            )
        if "LO_TPU_MPMD_SCHEDULE" in env:
            cfg.mpmd.schedule = env["LO_TPU_MPMD_SCHEDULE"].strip()
        if cfg.mpmd.schedule not in ("", "gpipe", "1f1b", "mpmd"):
            # Loud at boot, not deep inside the first pipeline fit.
            raise ValueError(
                "LO_TPU_MPMD_SCHEDULE must be one of gpipe|1f1b|mpmd "
                f"(or empty for the estimator default), got "
                f"{cfg.mpmd.schedule!r}"
            )
        if "LO_TPU_MPMD_MICRO" in env:
            cfg.mpmd.n_micro = int(env["LO_TPU_MPMD_MICRO"])
        if not 1 <= cfg.fleet.min_replicas <= cfg.fleet.max_replicas:
            # Loud at BOOT, like the boolean knobs: deferred, these
            # bounds first fail inside a predict's lazy ReplicaSet
            # construction — an env typo becoming per-request 500s.
            raise ValueError(
                "fleet replica bounds need 1 <= LO_TPU_FLEET_MIN "
                f"({cfg.fleet.min_replicas}) <= LO_TPU_FLEET_MAX "
                f"({cfg.fleet.max_replicas})"
            )
        if "LO_TPU_OBS_ENABLED" in env:
            cfg.obs.enabled = _bool_env("LO_TPU_OBS_ENABLED")
        if "LO_TPU_OBS_TRACE" in env:
            cfg.obs.trace = _bool_env("LO_TPU_OBS_TRACE")
        if "LO_TPU_OBS_MAX_SERIES" in env:
            cfg.obs.max_series = int(env["LO_TPU_OBS_MAX_SERIES"])
        if "LO_TPU_OBS_MAX_SPANS" in env:
            cfg.obs.max_spans = int(env["LO_TPU_OBS_MAX_SPANS"])
        def _fraction_env(key: str) -> float:
            # Sampling knobs: a typo'd rate silently clamping would
            # either drop every trace or record everything — reject
            # out-of-range values LOUDLY at boot.
            value = float(env[key])
            if not 0.0 <= value <= 1.0:
                raise ValueError(
                    f"{key}={env[key]!r} must be a fraction in "
                    "[0.0, 1.0]"
                )
            return value

        if "LO_TPU_OBS_TRACE_SAMPLE" in env:
            cfg.obs.trace_sample = _fraction_env(
                "LO_TPU_OBS_TRACE_SAMPLE"
            )
        if "LO_TPU_ROLLUP_ENABLED" in env:
            cfg.rollup.enabled = _bool_env("LO_TPU_ROLLUP_ENABLED")
        if "LO_TPU_ROLLUP_TICK_S" in env:
            cfg.rollup.tick_s = float(env["LO_TPU_ROLLUP_TICK_S"])
        if "LO_TPU_ROLLUP_POINTS" in env:
            cfg.rollup.points = int(env["LO_TPU_ROLLUP_POINTS"])
        if "LO_TPU_ROLLUP_MAX_SERIES" in env:
            cfg.rollup.max_series = int(
                env["LO_TPU_ROLLUP_MAX_SERIES"]
            )
        if "LO_TPU_ROLLUP_FAMILIES" in env:
            cfg.rollup.families = tuple(
                tok.strip()
                for tok in env["LO_TPU_ROLLUP_FAMILIES"].split(",")
                if tok.strip()
            )
        if "LO_TPU_SLO_ENABLED" in env:
            cfg.slo.enabled = _bool_env("LO_TPU_SLO_ENABLED")
        if "LO_TPU_SLO_AVAILABILITY" in env:
            cfg.slo.availability_target = _fraction_env(
                "LO_TPU_SLO_AVAILABILITY"
            )
        if "LO_TPU_SLO_PREDICT_P99_MS" in env:
            cfg.slo.predict_p99_ms = float(
                env["LO_TPU_SLO_PREDICT_P99_MS"]
            )
        if "LO_TPU_SLO_PREDICT_TARGET" in env:
            cfg.slo.predict_target = _fraction_env(
                "LO_TPU_SLO_PREDICT_TARGET"
            )
        if "LO_TPU_SLO_JOB_SUCCESS" in env:
            cfg.slo.job_success_target = _fraction_env(
                "LO_TPU_SLO_JOB_SUCCESS"
            )
        if "LO_TPU_SLO_DECODE_TTFT_MS" in env:
            cfg.slo.decode_ttft_ms = float(
                env["LO_TPU_SLO_DECODE_TTFT_MS"]
            )
        if "LO_TPU_SLO_DECODE_TTFT_TARGET" in env:
            cfg.slo.decode_ttft_target = _fraction_env(
                "LO_TPU_SLO_DECODE_TTFT_TARGET"
            )
        if "LO_TPU_SLO_FAST_S" in env:
            cfg.slo.fast_window_s = float(env["LO_TPU_SLO_FAST_S"])
        if "LO_TPU_SLO_SLOW_S" in env:
            cfg.slo.slow_window_s = float(env["LO_TPU_SLO_SLOW_S"])
        if "LO_TPU_SLO_BURN" in env:
            cfg.slo.burn_threshold = float(env["LO_TPU_SLO_BURN"])
        if "LO_TPU_SLO_FOR_S" in env:
            cfg.slo.for_s = float(env["LO_TPU_SLO_FOR_S"])
        if "LO_TPU_SLO_RESOLVE_S" in env:
            cfg.slo.resolve_s = float(env["LO_TPU_SLO_RESOLVE_S"])
        if "LO_TPU_SLO_WEBHOOK" in env:
            cfg.slo.webhook = env["LO_TPU_SLO_WEBHOOK"].strip()
        # A target of 1.0 has a ZERO error budget — burn rate would
        # divide by zero on the first bad event.  Reject loudly at
        # boot, like the fleet bounds.
        for knob, value in (
            ("LO_TPU_SLO_AVAILABILITY", cfg.slo.availability_target),
            ("LO_TPU_SLO_PREDICT_TARGET", cfg.slo.predict_target),
            ("LO_TPU_SLO_JOB_SUCCESS", cfg.slo.job_success_target),
            ("LO_TPU_SLO_DECODE_TTFT_TARGET",
             cfg.slo.decode_ttft_target),
        ):
            if value >= 1.0:
                raise ValueError(
                    f"{knob}={value!r} leaves no error budget — SLO "
                    "targets must be < 1.0"
                )
        if "LO_TPU_COSTS_ENABLED" in env:
            cfg.costs.enabled = _bool_env("LO_TPU_COSTS_ENABLED")
        if "LO_TPU_COSTS_DEEP" in env:
            cfg.costs.deep = _bool_env("LO_TPU_COSTS_DEEP")
        if "LO_TPU_COSTS_SAMPLE" in env:
            cfg.costs.sample = _fraction_env("LO_TPU_COSTS_SAMPLE")
        if "LO_TPU_COSTS_MAX_PROGRAMS" in env:
            cfg.costs.max_programs = int(
                env["LO_TPU_COSTS_MAX_PROGRAMS"]
            )
        if "LO_TPU_COSTS_MAX_JOBS" in env:
            cfg.costs.max_jobs = int(env["LO_TPU_COSTS_MAX_JOBS"])
        if "LO_TPU_COSTS_PEAK_FLOPS" in env:
            cfg.costs.peak_flops = float(
                env["LO_TPU_COSTS_PEAK_FLOPS"]
            )
        if "LO_TPU_PROF_DIR" in env:
            cfg.profiling.dir = env["LO_TPU_PROF_DIR"]
        if "LO_TPU_PROF_MAX_S" in env:
            cfg.profiling.max_seconds = float(env["LO_TPU_PROF_MAX_S"])
        if "LO_TPU_PROF_MAX_CAPTURES" in env:
            cfg.profiling.max_captures = int(
                env["LO_TPU_PROF_MAX_CAPTURES"]
            )
        if "LO_TPU_FLIGHT_ENABLED" in env:
            cfg.flight.enabled = _bool_env("LO_TPU_FLIGHT_ENABLED")
        if "LO_TPU_FLIGHT_EVENTS" in env:
            cfg.flight.events = int(env["LO_TPU_FLIGHT_EVENTS"])
        if "LO_TPU_BUNDLE_ENABLED" in env:
            cfg.bundle.enabled = _bool_env("LO_TPU_BUNDLE_ENABLED")
        if "LO_TPU_BUNDLE_DIR" in env:
            cfg.bundle.dir = env["LO_TPU_BUNDLE_DIR"]
        if "LO_TPU_BUNDLE_MAX" in env:
            cfg.bundle.max_bundles = int(env["LO_TPU_BUNDLE_MAX"])
        if "LO_TPU_BUNDLE_DEBOUNCE_S" in env:
            cfg.bundle.debounce_s = float(
                env["LO_TPU_BUNDLE_DEBOUNCE_S"]
            )
        if "LO_TPU_BUNDLE_PROFILE" in env:
            cfg.bundle.profile = _bool_env("LO_TPU_BUNDLE_PROFILE")
        if "LO_TPU_BUNDLE_PROFILE_S" in env:
            cfg.bundle.profile_s = float(
                env["LO_TPU_BUNDLE_PROFILE_S"]
            )
        if "LO_TPU_BUNDLE_JOURNAL_TAIL" in env:
            cfg.bundle.journal_tail = int(
                env["LO_TPU_BUNDLE_JOURNAL_TAIL"]
            )
        if "LO_TPU_OBS_BUCKETS_MS" in env:
            edges = tuple(
                float(tok)
                for tok in env["LO_TPU_OBS_BUCKETS_MS"].split(",")
                if tok.strip()
            )
            if not edges or list(edges) != sorted(edges):
                raise ValueError(
                    "LO_TPU_OBS_BUCKETS_MS must be a non-empty "
                    "ascending comma-separated list of milliseconds"
                )
            cfg.obs.latency_buckets_ms = edges
        if "LO_TPU_TASK_COORDINATOR" in env:
            cfg.dist.task_coordinator = env["LO_TPU_TASK_COORDINATOR"]
        if "LO_TPU_JAX_COORDINATOR" in env:
            cfg.dist.jax_coordinator = env["LO_TPU_JAX_COORDINATOR"]
        if "LO_TPU_WORLD_SIZE" in env:
            cfg.dist.num_processes = int(env["LO_TPU_WORLD_SIZE"])
        if "LO_HA_PEER" in env:
            cfg.ha.peer = env["LO_HA_PEER"]
        if "LO_HA_FENCE_INTERVAL" in env:
            cfg.ha.fence_interval_s = float(env["LO_HA_FENCE_INTERVAL"])
        if "LO_HA_AUTO_REJOIN" in env:
            # Accept the usual truthy/falsy spellings and reject the
            # rest LOUDLY: "true" silently parsing as False would leave
            # a pair without the redundancy the flag was set to provide.
            raw = env["LO_HA_AUTO_REJOIN"].strip().lower()
            if raw in ("1", "true", "yes", "on"):
                cfg.ha.auto_rejoin = True
            elif raw in ("0", "false", "no", "off", ""):
                cfg.ha.auto_rejoin = False
            else:
                raise ValueError(
                    f"LO_HA_AUTO_REJOIN={env['LO_HA_AUTO_REJOIN']!r} is "
                    "not a recognized boolean (use 1/0, true/false, "
                    "yes/no, on/off)"
                )
        if "LO_HA_REJOIN_INTERVAL" in env:
            cfg.ha.rejoin_interval_s = float(
                env["LO_HA_REJOIN_INTERVAL"]
            )
        if "LO_HA_REJOIN_MISSES" in env:
            cfg.ha.rejoin_misses = int(env["LO_HA_REJOIN_MISSES"])
        return cfg


#: Knobs read straight from the environment at their use site instead
#: of through :meth:`Config.from_env` — each for a reason: the log
#: level must apply before any config is built (config errors
#: themselves need a logger), and the flash-attention interpret
#: override is re-read per call so tests can flip it mid-process.
#: They are registered HERE because config.py is the canonical knob
#: index: the static drift gate (analysis/drift.py) fails any
#: ``LO_TPU_*`` reference that this file doesn't know about.
DIRECT_ENV_KNOBS = (
    "LO_TPU_LOG_LEVEL",        # log.py: root level, default INFO
    "LO_TPU_FLASH_INTERPRET",  # ops/attention.py: "1" forces the
                               # Pallas interpreter, "0" forces
                               # compiled kernels
    # Runtime lock witness (concurrency_rt.py) — read at lock-
    # construction time, which happens while THIS module is still
    # importing (config's own singleton lock), so they cannot ride
    # Config.from_env.
    "LO_TPU_WITNESS",          # "1" instruments make_lock/make_rlock
    "LO_TPU_WITNESS_STALL_S",  # stall-watchdog threshold (default 30)
    "LO_TPU_WITNESS_DUMP",     # path: dump the witnessed-order graph
                               # JSON at exit for lo_check --witness
)

_lock = make_lock("config._lock")
_config: Config | None = None


def get_config() -> Config:
    global _config
    with _lock:
        if _config is None:
            _config = Config.from_env()
        return _config


def set_config(cfg: Config) -> None:
    global _config
    with _lock:
        _config = cfg
