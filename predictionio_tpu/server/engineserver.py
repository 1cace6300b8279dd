"""Engine server: deployed-model query serving.

Capability parity with the reference engine server
(``workflow/CreateServer.scala:109-705``): ``POST /queries.json`` runs
supplement → per-algorithm predict → serve (:484-633, serving called with
the *original* query by design :506-513), the feedback loop posts
``predict`` events with a generated ``prId`` back to the event store
(:527-589), ``/reload`` rebinds to the latest COMPLETED engine instance
(``MasterActor`` :342-371), ``/stop`` shuts down, ``GET /`` renders a
status page with per-request bookkeeping (:415-417,597-604), and output
plugins transform/observe every prediction (:591-595).

The TPU-minded difference: models stay resident in HBM and ``predict`` is
expected to be a thin host wrapper over jitted device code, so the serving
hot path never recompiles.
"""

from __future__ import annotations

import html
import itertools
import json
import logging
import secrets
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, List, NamedTuple, Optional

from ..concurrency import (
    instrument_locks,
    locks_instrumented,
    new_lock,
    new_rlock,
    register_lock_metrics,
)
from ..controller.context import Context
from ..controller.engine import Engine
from ..controller.params import EngineParams
from ..data.event import Event, utcnow
from ..data.storage.base import STATUS_COMPLETED, EngineInstance
from ..faults import declare, fire
from ..faults import registry as fault_registry
from ..utils.retrying import RetryPolicy, backoff_delays
from ..obs import (
    DEFAULT_LATENCY_BOUNDS,
    POW2_COUNT_BOUNDS,
    MetricsRegistry,
    OverlapTracker,
    hbm_stats,
)
from ..obs import numerics as numerics_sentinel
from ..obs.overlap import STATES
from ..obs.runtime import RoleThread, name_os_thread
from ..obs.trace import (
    activate_traces,
    add_stage_spans,
    mark_active_traces,
    stage_span,
)
from ..rollout.registry import ReleaseRegistry
from ..rollout.splitter import ARM_CANDIDATE, ARM_STABLE
from ..utils.jsonutil import from_jsonable, to_jsonable
from ..workflow.batch_predict import (
    SUPPLEMENT_WAYS,
    PendingBatch,
    dispatch_batch,
    supplement_batch,
)
from .http import (
    AppServer,
    HTTPApp,
    HTTPError,
    Request,
    Response,
    json_response,
    make_key_auth,
    mount_metrics,
)
from .plugins import EngineServerPlugins

log = logging.getLogger(__name__)

F_LANE = declare("serving.lane",
                 "one micro-batch dispatch on a replicated serving "
                 "lane (lane= labels the device ordinal) — injecting "
                 "here simulates a dead device/lane")
F_LANE_RESTART = declare("serving.lane_restart",
                         "a lane-restart probe (lane=): injecting here "
                         "keeps a dead lane down")
F_DISPATCH = declare("serving.dispatch",
                     "one batched device dispatch (any serving mode)")


def pick_live_lane(lane: int, n_lanes: int, dead) -> int:
    """Route traffic for ``lane`` to a surviving lane: identity while
    healthy; a dead lane's batches redistribute deterministically
    across the survivors (round-robin by ordinal). With every lane
    dead there is nothing better than the original."""
    if n_lanes <= 0 or lane not in dead:
        return lane
    alive = [i for i in range(n_lanes) if i not in dead]
    if not alive:
        return lane
    return alive[lane % len(alive)]


def _gen_pr_id() -> str:
    """64-char alphanumeric prediction id (``CreateServer.scala:535``)."""
    return secrets.token_hex(32)


@dataclass
class ServerConfig:
    """Knobs of the reference's ``ServerConfig``
    (``CreateServer.scala:78-96``)."""

    feedback: bool = False
    #: App receiving feedback events (required when ``feedback``).
    feedback_app_name: Optional[str] = None
    accesskey: Optional[str] = None  # require ?accessKey= on control routes
    #: Coalesce concurrent queries into one ``batch_predict`` device
    #: dispatch (SURVEY hard part 3 — the reference served strictly
    #: per-request, ``CreateServer.scala:507-510`` "TODO: Parallelize").
    batching: bool = False
    batch_window_ms: float = 2.0   # max wait for a batch to fill
    #: largest coalesced batch (`ptpu deploy --max-batch` shares this
    #: default). Not re-decided on the current chip: that needs a
    #: benchmark cell.
    max_batch: int = 128
    #: Dispatch threads of a single-binding ``StagedPipeline`` (enqueue
    #: concurrency; a lane binding runs one thread per lane instead).
    #: In-flight batches are bounded by ``pipeline_depth``, not this.
    #: Not re-decided on the current chip: that needs a benchmark cell.
    batch_pipeline: int = 4
    #: Per-query deadline (ms) covering queue wait through readback: a
    #: submit unanswered by then returns 503 and its queue entry is
    #: shed (``pio_query_deadline_exceeded_total`` counts them), so a
    #: wedged dispatch degrades into fast 503s instead of hanging every
    #: HTTP worker forever. 0 disables (the pre-ISSUE-9 behavior).
    queue_deadline_ms: float = 30_000.0
    #: staged pipeline: host threads forming/parsing/supplementing
    #: batches (the assemble stage). One is plenty for fast
    #: supplements (forming a batch costs ~0.3ms); raise it for
    #: templates whose supplement does event-store reads — more
    #: workers split the arrival stream into SMALLER batches, which
    #: costs device efficiency (measured: 2 workers dropped mean
    #: occupancy 16 → 9 at 24-thread burst).
    assemble_workers: int = 1
    #: staged pipeline: host threads blocking on device results and
    #: serializing/feedback (the readback stage). Sized to the
    #: in-flight depth: each worker parks on one batch's readback
    #: while the device runs later batches.
    readback_workers: int = 4
    #: staged pipeline: bounded in-flight (picked-up-but-unserved)
    #: batches per lane — the knob that trades every query's wait
    #: behind earlier dispatches against latency hiding. 0 = auto: 2
    #: on every backend, one batch on the device and one behind it.
    #: On the v5e 2 against the former 4 took 10 ms off the median at
    #: 0.8 × the knee and held the saturated rate, with fuller, fewer
    #: dispatches (PR 26, PERF.md section 6). While the
    #: pipeline is full, arrivals pool in the submit queue (where the
    #: deadline sheds them) and the next pickup coalesces the backlog
    #: into one fat batch.
    pipeline_depth: int = 0
    #: POST query errors to this URL (``remoteLog``,
    #: ``CreateServer.scala:435-446``); never fails the query.
    log_url: Optional[str] = None
    log_prefix: str = ""
    #: Compile the serving device kernels for every batch size the
    #: micro-batcher can produce (the pow2 ladder) BEFORE traffic hits
    #: them. Each novel shape is a fresh XLA compile, and a compile
    #: under traffic lands in the p90/p99. Runs in a background thread;
    #: ``/status.json`` exposes ``servingWarm``. A warm-up that fails
    #: fails the deploy.
    warm_start: bool = True
    #: ``jax.transfer_guard`` level wrapped around the post-warmup query
    #: path — the runtime complement of ``ptpu check``'s
    #: host-sync-in-hot-path lint. "log" surfaces every implicit
    #: device↔host transfer a query triggers; "disallow" turns them into
    #: errors (canary deployments); "allow"/"off"/None disables. Applied
    #: only once warmup is done: warmup itself legitimately transfers.
    transfer_guard: Optional[str] = "log"
    #: Serving cache hierarchy (ISSUE 4): an exact-key query-result
    #: cache consulted BEFORE the micro-batcher (hot queries skip
    #: supplement/dispatch entirely, singleflight dedups concurrent
    #: identical misses), a feature cache for serving-time event-store
    #: reads, and a device-resident hot-entity tier — all invalidated
    #: by the event server's ingest bus and flushed on every rebind.
    #: Off by default: turning result caching on is a staleness
    #: decision the operator must make (see docs/serving-cache.md).
    serving_cache: bool = False
    cache_entries: int = 8192          # query-tier LRU capacity
    #: query-result staleness BOUND: the bus usually invalidates far
    #: sooner; this TTL is the ceiling when ingest happens in another
    #: process (no in-process bus delivery)
    cache_ttl_sec: float = 30.0
    feature_cache_entries: int = 8192
    feature_ttl_sec: float = 5.0       # event-store read staleness bound
    #: hottest entities whose factor rows stay pinned on device
    #: (0 disables the tier)
    hot_entities: int = 512
    hot_refresh_every: int = 256       # re-rank/re-pin cadence (serves)
    #: Instrument every lock in the serving stack with the
    #: concurrency package's DebugLock: live lock-order-inversion and
    #: re-entry detection, pio_lock_* wait/hold/contention series, and
    #: a deadlock watchdog that dumps all thread stacks to the access
    #: log when a wait exceeds PTPU_LOCK_WATCHDOG_SEC. Off by default:
    #: disabled means plain threading locks — zero overhead. The
    #: PTPU_DEBUG_LOCKS=1 env var enables it without a config change
    #: (the staging runbook path, docs/operations.md).
    debug_locks: bool = False
    #: Runtime NaN/Inf sentinels on the numeric serving stack
    #: (docs/observability.md): streaming fold-in solves run
    #: checkify-wrapped (device-side nonfinite detection before a
    #: hot-swap can poison the serving table) and serving top-k
    #: scores get a host NaN probe, feeding the
    #: pio_numerics_checks_total / pio_numerics_nonfinite_total
    #: counters and the ``nonfinite`` flag of /status.json's degraded
    #: block. Off by default: the instrumented sites are one bool
    #: check — zero overhead (the fault-registry pattern). The
    #: PTPU_DEBUG_NUMERICS=1 env var enables it without a config
    #: change.
    debug_numerics: bool = False
    #: Row-quantized serving factor tables (ISSUE 13,
    #: docs/kernels.md): "int8" stores per-row-scaled int8 factors
    #: (~4x more users per HBM, ~4x less bandwidth per scored batch),
    #: "bf16" halves both — dequantized on the fly with f32
    #: accumulation (Tensor-Casting precision co-design). Guarded by a
    #: deploy-time NDCG@10 parity probe that auto-falls-back to f32
    #: when the model's rank/scale cannot take the quantization, so
    #: the knob can never silently degrade ranking. "off" serves f32.
    serving_quant: str = "off"
    #: Mesh-wide serving (ISSUE 6, docs/sharded-serving.md):
    #: "single" — today's one-device path; "replicated" — a full model
    #: copy per device, the micro-batcher fans micro-batches out
    #: round-robin across per-device lanes (~N× qps on N chips, no
    #: cross-device sync on the serve path); "sharded" — factor tables
    #: row-sharded over the (batch, model) mesh via NamedSharding
    #: (models bigger than one HBM; GSPMD resolves the gathers);
    #: "auto" — sharded when the model's resident bytes exceed the
    #: per-device HBM headroom, else replicated on >1 device.
    serving_mode: str = "single"
    #: Streaming incremental training (ISSUE 10, docs/streaming.md):
    #: start a :class:`~predictionio_tpu.streaming.StreamTrainer` with
    #: the deploy — it tails ``stream_app_name``'s event log behind a
    #: durable cursor, folds fresh events into the bound ALS model via
    #: per-entity least-squares solves, canaries each delta, and
    #: hot-swaps the updated rows into this serving binding. Off by
    #: default; ``ptpu stream start`` attaches one to a live server.
    streaming: bool = False
    #: App whose event log the trainer tails (required when
    #: ``streaming``; falls back to ``feedback_app_name``).
    stream_app_name: Optional[str] = None
    #: Poll fallback between fold-in passes; in-process ingest wakes
    #: the trainer immediately through the invalidation bus.
    stream_interval_ms: float = 500.0
    stream_max_events: int = 2048      # events per fold-in micro-batch
    #: durable cursor identity (two trainers sharing a consumer name
    #: fight over one cursor)
    stream_consumer: str = "stream-trainer"
    stream_drift_threshold: float = 1.0  # DriftMonitor retrain trigger
    #: touched-entity probes per fold-in canary check (0 disables)
    stream_canary_probes: int = 8
    #: Fault injection (ISSUE 11, docs/reliability.md): a
    #: ``PTPU_FAULTS``-grammar spec string armed into the process-wide
    #: fault registry at server construction, so failure drills script
    #: real storage/lane/dispatch faults against a deployed server
    #: (``ptpu deploy --faults``). None = nothing armed (the env var
    #: still works).
    faults: Optional[str] = None
    #: End-to-end request tracing (ISSUE 12, docs/tracing.md): every
    #: request is traced into the tail-sampled flight recorder — only
    #: slow (adaptive p99) / errored / deadline-503'd / fault-injected
    #: traces are retained, served as Perfetto JSON on
    #: ``GET /trace.json``. On by default: the per-request cost is a
    #: handful of allocations (measured ≤5% on the host fast path);
    #: off for A/B benches of that overhead.
    tracing: bool = True
    #: retained traces the flight-recorder ring holds (oldest evicted)
    trace_ring: int = 512
    #: fixed slow-retention threshold in ms; 0 = adaptive (the live
    #: p99 of traced request durations)
    trace_slow_ms: float = 0.0
    #: probabilistic sampling of the structured JSON access log: 1.0
    #: logs every request (the historical behavior), 0.01 logs ~1% —
    #: errors and 503s ALWAYS log regardless. High-qps serving should
    #: not pay a json.dumps per healthy request (ISSUE 12 satellite).
    access_log_sample: float = 1.0
    #: artifact directory for on-demand ``POST /profile`` device
    #: captures (None: $PTPU_PROFILE_DIR, else <tmp>/ptpu-profiles)
    profile_dir: Optional[str] = None
    #: Hot-key telemetry (ISSUE 17, docs/fleet.md): capacity of the
    #: Space-Saving heavy-hitter sketch fed by the query path's entity
    #: ids — every key hotter than 1/k of traffic is guaranteed
    #: monitored. Exported as ``pio_hot_keys{rank,key}`` and the
    #: ``hotKeys`` block of /status.json (which the fleet aggregator
    #: merges); the signal entity-affinity routing will consume.
    #: 0 disables the sketch entirely.
    hot_keys_k: int = 128
    #: SLO engine (ISSUE 15, docs/slo.md): declarative service
    #: objectives evaluated continuously against this server's live
    #: metric registry via multi-window error-budget burn rates
    #: (pio_slo_* series, /slo.json, an slo block on /status.json).
    #: None = the built-in default specs (availability + latency on
    #: /queries.json, freshness while streaming); a path loads a
    #: committed spec file (slo/specs/*.json). Breach transitions
    #: force-retain flight-recorder traces for the duration of the
    #: burn, so every violation arrives with exemplar evidence.
    slo_specs: Optional[str] = None
    #: evaluation tick; 0 disables the SLO engine entirely
    slo_interval_ms: float = 1000.0
    #: consecutive failed dispatches on one replicated lane before the
    #: lane is declared dead and its traffic redistributed across the
    #: surviving lanes (degraded mode — pio_serving_degraded)
    lane_fail_threshold: int = 3
    #: lane-restart probe schedule: bounded exponential backoff from
    #: this base, capped at 32x — a dead lane is probed (restart =
    #: fault-point probe + per-device model re-replication) until it
    #: comes back or the attempt budget is spent
    lane_restart_backoff_ms: float = 100.0
    lane_restart_max_attempts: int = 8
    #: Warm-from-artifact deploy (ISSUE 19, docs/cold-start.md): root
    #: of the AOT artifact store ``ptpu build --aot`` wrote. When set,
    #: ``_warm_serving`` becomes artifact-load-then-verify — serving
    #: executables deserialize in milliseconds instead of compiling —
    #: with automatic fallback to compiling on any key mismatch,
    #: missing build, or corrupt entry. None keeps the compile warm.
    artifact_dir: Optional[str] = None


@dataclass
class CandidateBinding:
    """A candidate release bound ALONGSIDE the stable one: its own
    algorithms/models/serving so the two arms never share mutable
    state. ``raw_models`` keep the as-loaded blobs — promotion rebinds
    through the normal ``_bind`` path so the stable batch budget (and
    its device placement) is re-derived, not inherited from the
    candidate's batch-1 serving."""

    engine_params: EngineParams
    algorithms: List[Any]
    models: List[Any]
    raw_models: List[Any]
    serving: Any
    instance: EngineInstance
    warm_done: threading.Event


class QueryServer:
    """One deployed engine: algorithms + live models + serving logic."""

    def __init__(self, ctx: Context, engine: Engine,
                 engine_params: EngineParams, models: List[Any],
                 instance: EngineInstance,
                 config: Optional[ServerConfig] = None,
                 plugins: Optional[EngineServerPlugins] = None):
        self.ctx = ctx
        self.engine = engine
        self.config = config or ServerConfig()
        if self.config.feedback:
            # fail fast at deploy rather than logging per query
            app_name = self.config.feedback_app_name
            if not app_name:
                raise ValueError(
                    "feedback=True requires feedback_app_name")
            if ctx.storage.apps().get_by_name(app_name) is None:
                raise ValueError(
                    f"feedback app {app_name!r} does not exist")
        self.plugins = plugins or EngineServerPlugins()
        if self.config.faults:
            # failure drills (ISSUE 11): arm the requested injections
            # BEFORE anything that might be their target exists
            from ..faults import inject_spec

            inject_spec(self.config.faults)
        if self.config.debug_locks and not locks_instrumented():
            # flip the factories BEFORE any serving-stack lock exists
            # so the cache/rollout/batcher locks built below are all
            # DebugLocks feeding one process order graph
            instrument_locks(True)
        if self.config.debug_numerics or numerics_sentinel.debug_env():
            # arm the NaN/Inf sentinels BEFORE the bind so warmup
            # fold-ins and probe serves are covered too
            numerics_sentinel.enable()
        self._lock = new_rlock("QueryServer._lock")
        # serving cache hierarchy (ISSUE 4): built BEFORE the first
        # _bind so the bind can wire the feature tier into algorithms
        self.cache = self._make_cache()
        self._bind(engine_params, models, instance)
        # bookkeeping (CreateServer.scala:415-417)
        self.start_time = utcnow()
        self.request_count = 0
        self.avg_serving_sec = 0.0
        self.last_serving_sec = 0.0
        # telemetry (ISSUE 2): the engine server's metric registry —
        # per-phase query-path histograms plus the batcher's occupancy
        # and queue-depth series. QueryServer owns it so direct query()
        # callers (tests, batch jobs) record the same series HTTP
        # traffic does; build_app mounts it on /metrics.
        self.metrics = MetricsRegistry()
        self._phase_hist = self.metrics.histogram(
            "pio_query_phase_seconds",
            "Per-phase query-path wall time. A staged query's "
            "residence in pipeline order: admit, queue_wait, assemble, "
            "supplement, dispatch_q, dispatch, readback_q, device_wait, "
            "serve, finish (of which readback and feedback are parts), "
            "wake, respond",
            bounds=DEFAULT_LATENCY_BOUNDS)
        #: bound children by phase: ``labels()`` validates and sorts
        #: its keywords under a lock on every call
        self._phase_children: dict = {}
        # the three below have one, unlabeled child each: bound here,
        # for a family's own observe() looks its child up under a lock
        self._latency_hist = self.metrics.histogram(
            "pio_query_latency_seconds",
            "End-to-end serving wall time per query",
            bounds=DEFAULT_LATENCY_BOUNDS).labels()
        self._batch_occupancy = self.metrics.histogram(
            "pio_batch_occupancy",
            "Queries coalesced per micro-batch dispatch",
            bounds=POW2_COUNT_BOUNDS).labels()
        self._queue_depth = self.metrics.histogram(
            "pio_queue_depth",
            "Batcher queue depth observed at each batch pickup",
            bounds=POW2_COUNT_BOUNDS).labels()
        self._query_errors = self.metrics.counter(
            "pio_query_errors_total", "Failed queries by status class")
        # staged serving pipeline series (ISSUE 9,
        # docs/serving-pipeline.md): per-stage wall time, inter-stage
        # queue depths, deadline sheds, and the overlap accounting that
        # PROVES the device computes while host stages run
        self._pipeline_stage_hist = self.metrics.histogram(
            "pio_pipeline_stage_seconds",
            "Per-batch wall time of each staged-pipeline stage "
            "(assemble = parse+supplement, dispatch = device enqueue, "
            "readback = device wait + serve + serialize + feedback)",
            bounds=DEFAULT_LATENCY_BOUNDS)
        self._pipeline_qdepth = self.metrics.histogram(
            "pio_pipeline_queue_depth",
            "Queue depth observed at each pipeline stage pickup "
            "(queue=submit|dispatch|readback)",
            bounds=POW2_COUNT_BOUNDS)
        self._deadline_exceeded = self.metrics.counter(
            "pio_query_deadline_exceeded_total",
            "Queries shed with 503 after exceeding "
            "ServerConfig.queue_deadline_ms — load shedding under a "
            "wedged or saturated dispatch, never silent hangs")
        self._pipeline_overlapped = self.metrics.counter(
            "pio_pipeline_overlapped_dispatches_total",
            "Batch launches that found an earlier batch still in "
            "flight on the device — direct evidence of stage overlap")
        self._pipeline_supplemented = self.metrics.counter(
            "pio_pipeline_supplement_batches_total",
            "Assembled batches by the way their queries were "
            "supplemented: identity (the serving inherits "
            "Serving.supplement: nothing is called), serial (an "
            "overriding supplement, one query, on the assemble "
            "thread), pool (the same, a call a query on the shared "
            "thread pool)")
        self.overlap = OverlapTracker()
        state_seconds = self.metrics.counter(
            "pio_pipeline_state_seconds_total",
            "The starvation clock: wall seconds since the first batch, "
            "each in exactly ONE state, the first that holds a batch: "
            "enqueued (queued on the device, results not back) > "
            "launching (a dispatch call in progress) > staged (waits "
            "for the dispatch thread) > assembling (parse + supplement) "
            "> empty (no batch anywhere in the pipeline)")
        for state in STATES:
            state_seconds.labels(state=state).set_fn(
                lambda state=state: self.overlap.state_seconds(state))
        self.metrics.gauge(
            "pio_pipeline_device_idle_fraction",
            "Fraction of wall time (since first batch) with NO batch "
            "in flight on the device; the staged pipeline under load "
            "should drive this toward 0",
            fn=self.overlap.device_idle_fraction)
        self.metrics.gauge(
            "pio_pipeline_overlap_fraction",
            "Fraction of wall time where the device was busy WHILE an "
            "assemble/readback host stage ran — the overlap the staged "
            "pipeline exists to create",
            fn=self.overlap.overlap_fraction)
        # mesh-wide serving series (ISSUE 6): per-device lane depth /
        # latency / dispatch counts while replicated fan-out is active,
        # plus the resolved mode as a render-time gauge
        self._lane_latency = self.metrics.histogram(
            "pio_lane_batch_seconds",
            "Per-lane micro-batch wall time (replicated fan-out; lane "
            "label = device ordinal)",
            bounds=DEFAULT_LATENCY_BOUNDS)
        self._lane_depth = self.metrics.histogram(
            "pio_lane_queue_depth",
            "Batcher queue depth observed at each lane's batch pickup",
            bounds=POW2_COUNT_BOUNDS)
        self._lane_dispatches = self.metrics.counter(
            "pio_lane_dispatches_total",
            "Micro-batches dispatched per serving lane")
        self.metrics.gauge(
            "pio_serving_lanes",
            "Per-device serving lanes active (0 = single/sharded "
            "binding)",
            fn=lambda: float(len(self.lane_models)))
        # graceful degradation (ISSUE 11, docs/reliability.md): lane
        # supervision state + the telemetry that makes a dead lane an
        # alert instead of a mystery latency cliff. _lane_health guards
        # the dead-set and failure streaks; the binding lock is NOT
        # reused here because lane death is detected on the dispatch
        # hot path.
        self._lane_health = new_lock("QueryServer._lane_health")
        self._dead_lanes: dict = {}        # lane → {"since", "reason"}
        self._lane_streaks: dict = {}      # lane → consecutive failures
        self._lane_restarts = self.metrics.counter(
            "pio_lane_restarts_total",
            "Successful restarts of a dead serving lane, by lane")
        self._lane_failures = self.metrics.counter(
            "pio_lane_failures_total",
            "Failed micro-batch dispatches per serving lane (the "
            "streak that crosses lane_fail_threshold kills the lane)")
        self.metrics.gauge(
            "pio_serving_degraded",
            "1 while one or more replicated serving lanes are dead "
            "and their traffic is redistributed across survivors",
            fn=lambda: 1.0 if self._dead_lanes else 0.0)
        # end-to-end tracing (ISSUE 12, docs/tracing.md): the server
        # owns the tracer (like the registry) so direct query() callers
        # trace the same way HTTP traffic does; build_app mounts it on
        # the request path + /trace.json. The profiler backs
        # POST /profile (bounded-window jax.profiler captures).
        from ..obs.trace import DeviceProfiler, Tracer
        self.tracer = (Tracer(ring=self.config.trace_ring,
                              slow_ms=self.config.trace_slow_ms)
                       if self.config.tracing else None)
        self.profiler = DeviceProfiler(self.config.profile_dir)
        # hot-key telemetry (ISSUE 17): a Space-Saving sketch over the
        # query path's entity ids — exported per replica as
        # pio_hot_keys{rank,key} and merged fleet-wide by the
        # aggregator. O(k) per record, k bounded by config.
        from ..obs.hotkeys import SpaceSaving, mount_hot_key_metrics
        self.hotkeys: Optional[SpaceSaving] = None
        if self.config.hot_keys_k > 0:
            self.hotkeys = SpaceSaving(capacity=self.config.hot_keys_k)
            mount_hot_key_metrics(self.metrics, self.hotkeys)
        # fault-injection observability: injections delivered anywhere
        # in this process, attributed by point and mode — and flagged
        # onto whatever traces the injected thread was working on, so
        # a fault-injected request is retained by the flight recorder
        self._fault_injections = self.metrics.counter(
            "pio_fault_injections_total",
            "Fault-registry injections delivered, by point and mode "
            "(drills only; 0 in production)")

        def _on_fault(point: str, mode: str) -> None:
            self._fault_injections.labels(point=point, mode=mode).inc()
            mark_active_traces("fault", faultPoint=point,
                               faultMode=mode)

        fault_registry().add_listener(_on_fault)
        self.metrics.gauge(
            "pio_fault_enabled",
            "1 while any fault-injection spec is armed in this process",
            fn=lambda: 1.0 if fault_registry().enabled() else 0.0)
        # numeric-sentinel observability (debug_numerics /
        # PTPU_DEBUG_NUMERICS=1): checks delivered anywhere in this
        # process, attributed by entry point; any nonfinite sample
        # also raises the `nonfinite` flag in /status.json's degraded
        # block
        self._numerics_checks = self.metrics.counter(
            "pio_numerics_checks_total",
            "Numeric-sentinel NaN/Inf checks delivered, by entry "
            "point (debug_numerics only; absent in production)")
        self._numerics_nonfinite = self.metrics.counter(
            "pio_numerics_nonfinite_total",
            "Numeric-sentinel checks that observed NaN/Inf, by entry "
            "point — nonzero flags nonfinite in /status.json")

        def _on_numerics(entry: str, bad: bool) -> None:
            self._numerics_checks.labels(entry=entry).inc()
            if bad:
                self._numerics_nonfinite.labels(entry=entry).inc()

        if numerics_sentinel.active():
            self._numerics_listener = _on_numerics
            numerics_sentinel.add_listener(_on_numerics)
        else:
            self._numerics_listener = None
        # progressive delivery (ISSUE 3): per-release-arm series the
        # rollout health gate windows over, the release registry this
        # server's deploy/reload/promote/rollback actions are recorded
        # in, and the (at most one) live candidate binding + controller
        self._release_queries = self.metrics.counter(
            "pio_release_queries_total",
            "Queries served per release arm while a rollout is live")
        self._release_errors = self.metrics.counter(
            "pio_release_query_errors_total",
            "Server-side (5xx) query failures per release arm while a "
            "rollout is live")
        self._release_latency = self.metrics.histogram(
            "pio_release_latency_seconds",
            "End-to-end serving wall time per release arm while a "
            "rollout is live",
            bounds=DEFAULT_LATENCY_BOUNDS)
        self._shadow_mirrors = self.metrics.counter(
            "pio_release_shadow_mirrors_total",
            "Queries mirrored to a shadow candidate")
        self.releases = ReleaseRegistry(
            ctx.storage, instance.engine_id, instance.engine_version,
            instance.engine_variant)
        self.rollout = None  # the live RolloutController, if any
        self._candidate: Optional[CandidateBinding] = None
        self._algo_pool = None    # parallel per-algorithm dispatch
        self._mirror_pool = None  # shadow mirrors (separate pool: a
        # mirror runs query_candidate, which dispatches into the algo
        # pool — sharing one pool could deadlock at saturation
        # recompile sentinel: armed when warmup finishes, so every
        # compile after that is a query paying a trace it shouldn't
        # (the runtime half of ptpu check's recompile-hazard lint)
        from .stats import RecompileSentinel
        self.recompile_sentinel = RecompileSentinel()
        self.warm_done = threading.Event()
        #: set instead of ``warm_done`` when a warm-up fails; the
        #: deploy is then over (``on_warm_failure`` stops the listener)
        self.warm_error: Optional[str] = None
        self.on_warm_failure: Optional[Callable[[], None]] = None
        # lifecycle advertisement (ISSUE 18): the router's lifecycle
        # manager flips this via POST /drain; the fleet aggregator
        # reads the resulting /status.json "lifecycle" field so a
        # draining replica leaves rollups + the headroom denominator
        # without an up-flap when its scrapes finally stop
        self.drain_started = threading.Event()
        self.metrics.gauge(
            "pio_compiles_since_warm",
            "XLA compiles after serving warmup finished — every one is "
            "traffic paying a trace it should not",
            fn=lambda: self.recompile_sentinel.since_armed)
        self.metrics.gauge(
            "pio_serving_warm",
            "1 once the serving shapes are pre-compiled",
            fn=lambda: 1.0 if self.warm_done.is_set() else 0.0)
        # warm-time telemetry (ISSUE 19): where warm time actually went
        # — artifact-store open + executable deserialize ("load"), the
        # lane-0 warm ladder net of loads ("compile"), lanes 1..N-1
        # ("replicate"), and the post-warm verify pass ("probe")
        self._warmup_seconds = self.metrics.histogram(
            "pio_warmup_seconds",
            "Serving warm-up wall time by phase "
            "(phase=load|compile|replicate|probe); an artifact warm "
            "puts its mass in load, a cold warm in compile",
            bounds=[0.01, 0.05, 0.25, 1.0, 2.0, 5.0, 15.0, 30.0, 60.0])
        #: warm provenance for /status.json: set by _warm_serving once
        #: per generation ({"artifact": bool, "seconds": {...}, ...})
        self._warm_report: dict = {}
        # the initial _bind ran before this registry existed; record
        # the serving-kernel mode now (rebinds re-record inside _bind)
        self._record_serving_kernel()
        self._record_sharding_findings()
        for algo in self.algorithms:
            self._bind_algorithm_metrics(algo)
        if self.cache is not None:
            self.cache.register_metrics(self.metrics)
        if locks_instrumented():
            register_lock_metrics(self.metrics)
        # the batcher lives on the server (not build_app) so the cached
        # serve() path and direct embedders share one batcher.
        # Replicated mode implies it: the dispatch threads ARE the
        # per-device lanes (fan-out), so a replicated binding without
        # --batching still gets its N lanes.
        lanes = len(self.lane_models) or 1
        if self.config.batching or lanes > 1:
            self.batcher = StagedPipeline(
                self, self.config.batch_window_ms,
                self.config.max_batch, lanes=lanes,
                assemble_workers=self.config.assemble_workers,
                readback_workers=self.config.readback_workers,
                depth=self.config.pipeline_depth,
                deadline_ms=self.config.queue_deadline_ms,
                dispatch_workers=self.config.batch_pipeline)
        else:
            self.batcher = None
        self._warm_gen = 0  # stale warm threads must not set the event
        if self.config.warm_start:
            threading.Thread(target=self._warm_serving, args=(0,),
                             daemon=True, name="serving-warmup").start()
        else:
            self.warm_done.set()
            self.recompile_sentinel.arm()
        # streaming incremental training (ISSUE 10): the deploy-time
        # trainer. Fail fast on a bad config — a deploy that silently
        # drops its freshness contract is worse than one that errors.
        self.stream = None
        if self.config.streaming:
            self.start_stream()
        # SLO engine (ISSUE 15, docs/slo.md): every objective is
        # accounted against the registry built above, on a background
        # tick. The server owns it (like the tracer/registry) so
        # direct query() embedders burn the same budgets HTTP traffic
        # does; build_app serves /slo.json off it. Breach transitions
        # flip the tracer into force-retention — the flight recorder
        # carries the evidence for every violation counted.
        self.slo = None
        if self.config.slo_interval_ms > 0:
            from ..slo import SLOEngine, default_specs, load_specs

            if self.config.slo_specs:
                # fail fast at deploy: a server that silently dropped
                # its objectives is worse than one that errors
                slo_specs, _ = load_specs(self.config.slo_specs)
            else:
                slo_specs = default_specs(
                    streaming=self.config.streaming)
            self.slo = SLOEngine(self.metrics, slo_specs,
                                 on_transition=self._on_slo_transition)
            self.slo.register_metrics(self.metrics)
            self.slo.start(self.config.slo_interval_ms / 1000.0)

    def _on_slo_transition(self, spec, breached: bool, info) -> None:
        """ok↔breach edge hook: while ANY spec burns, the tail sampler
        retains every trace (reason ``slo``) — an SLO violation must
        never arrive without flight-recorder exemplars riding along."""
        tracer = self.tracer
        if tracer is None or self.slo is None:
            return
        tracer.force_retention("slo" if self.slo.burning() else None)

    def slo_status(self) -> dict:
        """The ``slo`` block of ``/status.json`` (and ``/slo.json``)."""
        if self.slo is None:
            return {"enabled": False,
                    "hint": "deploy with --slo-specs FILE (or leave "
                            "slo_interval_ms at its default) to "
                            "evaluate service objectives"}
        return self.slo.status()

    def stop_slo(self) -> None:
        if self.slo is not None:
            self.slo.stop()

    def close(self, timeout: float = 5.0) -> None:
        """Release every background worker this server owns — rollout
        gate, stream trainer, SLO evaluator, batcher drainers /
        pipeline stages, sniffer pump — so a deploy→shutdown cycle
        leaks no threads (``ptpu audit-lifecycle`` gates this).
        Idempotent. Direct ``query()`` calls still work after close;
        batched submits do not — close after the listener is down."""
        if self.rollout is not None:
            self.rollout.stop()
        self.stop_stream()
        self.stop_slo()
        if self.batcher is not None:
            self.batcher.close(timeout=timeout)
        self.plugins.close()

    # -- lifecycle advertisement (ISSUE 18) ----------------------------------
    @property
    def lifecycle(self) -> str:
        """``warming`` | ``ready`` | ``draining`` — the state this
        replica advertises on ``/status.json``. Draining means "finish
        what's in flight, send me nothing new": the router has already
        pulled this replica from its ring; the aggregator keeps it out
        of rollups and treats its eventual silence as an expected
        departure."""
        if self.drain_started.is_set():
            return "draining"
        return "ready" if self.warm_done.is_set() else "warming"

    def enter_drain(self) -> None:
        """Irreversible: announce drain (``POST /drain``). The server
        keeps answering queries — in-flight and in-deadline work must
        complete — but every surface now reports lifecycle=draining."""
        self.drain_started.set()

    def artifact_key(self) -> dict:
        """The AOT artifact store key for THIS binding — every field
        that changes which executables serve: toolchain identity (jax
        version/backend/device count, added by ``aot.store_key``), the
        resolved serving placement (mode, mesh shape, lane count), the
        bound tables' rank + ACTUAL quantization (the parity probe may
        have fallen back to f32 — the requested knob is not the truth),
        and the batching envelope. ``ptpu build`` and deploy both
        derive the key through here, so any drift resolves to a
        different artifact directory and deploy falls back to
        compiling (docs/cold-start.md)."""
        from .. import aot

        with self._lock:
            models = list(self.models)
            lanes = len(self.lane_models)
        ranks, quants = [], []
        for m in models:
            itf = getattr(m, "item_factors", None)
            if itf is None:
                continue
            data = getattr(itf, "data", itf)
            shape = getattr(data, "shape", None)
            if shape is not None and len(shape) == 2:
                ranks.append(int(shape[-1]))
            quants.append(str(getattr(itf, "quant", "off")))
        mesh = getattr(self, "serving_mesh", None)
        return aot.store_key(
            serving_mode=str(getattr(self, "serving_mode_resolved",
                                     self.config.serving_mode)),
            mesh_shape=(tuple(int(s) for s in mesh.devices.shape)
                        if mesh is not None else None),
            lanes=lanes,
            rank=tuple(ranks),
            quant=tuple(quants),
            max_batch=int(self.config.max_batch),
            batching=bool(self.config.batching or lanes),
        )

    def _warm_serving(self, gen: int) -> None:
        """Warm the serving path's device shapes (single query + the
        batcher's pow2 ladder) so first traffic never pays a compile.
        Algorithms opt in by implementing
        ``warm_serving(model, max_batch)``. A failed warm-up FAILS the
        deploy: the error lands in ``warm_error`` and on
        ``/status.json`` (``warmReport.error``), ``servingWarm`` stays
        false, and ``on_warm_failure`` (the HTTP listener's shutdown,
        ``create_engine_server``) runs — a shape that does not compile
        now would not compile under traffic either. ``gen`` guards
        against a stale deploy-time thread flipping ``warm_done`` while
        a post-reload re-warm (newer generation) is still compiling new
        shapes.

        With ``config.artifact_dir`` set this is artifact-load-then-
        verify (ISSUE 19): the AOT store built by ``ptpu build`` is
        opened and activated, the same ladder then ANSWERS from
        deserialized executables (milliseconds) instead of compiling,
        and executing every entry on real zeros is the verification.
        Any mismatch — stale key, missing build, corrupt entry — falls
        back to compiling that entry exactly as before."""
        from .. import aot

        with self._lock:
            # snapshot: a concurrent reload/promote must not swap the
            # lists out from under the zip mid-warm
            algorithms, models = self.algorithms, self.models
            lane_models = list(self.lane_models)
        max_b = self.config.max_batch \
            if (self.config.batching or lane_models) else 1
        for algo in algorithms:  # a lane's depth: what one device holds
            algo.batches_in_flight = \
                self.batcher.depth if self.batcher is not None else 1
        aot.reset_stats()
        t0 = time.perf_counter()
        store = None
        if self.config.artifact_dir:
            try:
                store = aot.ArtifactStore.open(self.config.artifact_dir,
                                               self.artifact_key())
            except Exception as e:  # noqa: BLE001 — artifacts optional
                log.warning("artifact store open failed: %s — "
                            "compiling", e)
            aot.activate(store)
            if store is not None:
                log.info("serving artifacts: %d entries under %s",
                         len(store), store.path)
            else:
                log.warning(
                    "no matching serving artifacts under %s (stale key "
                    "or missing build) — falling back to compile",
                    self.config.artifact_dir)
        t_open = time.perf_counter() - t0

        errors: List[str] = []

        def _walk(models_i) -> None:
            for algo, model in zip(algorithms, models_i):
                warm = getattr(algo, "warm_serving", None)
                if warm is None:
                    continue
                try:
                    warm(model, max_b)
                except Exception as e:  # noqa: BLE001 — recorded below:
                    # the first failure decides the deploy; the rest of
                    # the ladder still runs so the log names every
                    # shape that fails
                    log.error("serving warmup failed for %s",
                              type(algo).__name__, exc_info=True)
                    errors.append(f"{type(algo).__name__}: "
                                  f"{type(e).__name__}: {e}")

        # every lane warms its own copy: executables compile (or load)
        # PER DEVICE, so warming lane 0 alone leaves lanes 1..N-1
        # paying cold compiles on first fan-out. Lane 0 accounts to
        # the "compile" phase, the rest to "replicate"; artifact
        # deserialize time is subtracted into "load" where it belongs.
        all_lanes = lane_models or [models]
        t1 = time.perf_counter()
        _walk(all_lanes[0])
        first_walk = time.perf_counter() - t1
        first_load = aot.stats()["load_seconds"]
        t2 = time.perf_counter()
        for models_i in all_lanes[1:]:
            _walk(models_i)
        repl_walk = time.perf_counter() - t2
        # probe: re-run the lane-0 ladder against the now-warm caches —
        # every shape must answer without a compile; this is the
        # "verify" half of artifact-load-then-verify. Compile warms
        # skip it: the compile itself proved every shape, and algo
        # ``warm_serving`` hooks keep their one-run-per-warm contract
        # (the reload-race tests count on it)
        t3 = time.perf_counter()
        if store is not None:
            _walk(all_lanes[0])
        t_probe = time.perf_counter() - t3
        s = aot.stats()
        phases = {
            "load": t_open + s["load_seconds"],
            "compile": max(first_walk - first_load, 0.0),
            "replicate": max(repl_walk
                             - (s["load_seconds"] - first_load), 0.0),
            "probe": t_probe,
        }
        for phase, sec in phases.items():
            self._warmup_seconds.labels(phase=phase).observe(sec)
        report = {
            # an ARTIFACT warm: a store was bound and every ladder
            # entry answered from it (zero compile fallbacks)
            "artifact": bool(store is not None and s["loaded_entries"]
                             and not s["compiled_calls"]),
            "store": store.path if store is not None else None,
            "storeEntries": len(store) if store is not None else 0,
            "loadedEntries": int(s["loaded_entries"]),
            "compiledFallbacks": int(s["compiled_calls"]),
            "corruptEntries": int(s["corrupt_entries"]),
            "staleStores": int(s["stale"]),
            "seconds": {k: round(v, 4) for k, v in phases.items()},
            "totalSeconds": round(sum(phases.values()), 4),
        }
        if report["artifact"]:
            log.info("serving warm from artifact in %.2fs (%d entries)",
                     report["totalSeconds"], report["loadedEntries"])
        if errors:
            report["error"] = "; ".join(errors)
        # check+set under the lock: unsynchronized, a stale thread could
        # pass the gen check, lose the CPU to reload()'s clear+increment,
        # then set() — reporting warm while the re-warm still compiles
        with self._lock:
            if gen != self._warm_gen:
                return
            self._warm_report = report
            if errors:
                self.warm_error = report["error"]
            else:
                self.warm_done.set()
                self.recompile_sentinel.arm()
        if errors and self.on_warm_failure is not None:
            self.on_warm_failure()

    def _bind(self, engine_params: EngineParams, models: List[Any],
              instance: EngineInstance) -> None:
        with self._lock:
            if self.cache is not None:
                # FULL flush on every rebind (deploy/reload/promote):
                # a new model must never serve results — or pinned
                # factor rows — computed by the old one (ISSUE 4)
                self.cache.flush_all()
            self.engine_params = engine_params
            self.instance = instance
            # stream lineage (ISSUE 10): a rebind installs a fresh
            # full-retrain base — the incremental generation restarts
            # from it (the StreamTrainer notices the new instance id
            # and re-folds pending events against the new base)
            self._stream_generation = 0
            self._stream_rows = 0
            self._stream_last_apply: Optional[float] = None
            self._stream_base_bound_at = time.time()
            self.algorithms = self.engine.make_algorithms(engine_params)
            for algo in self.algorithms:
                algo.bind_serving(self.ctx)
                self._bind_feature_cache(algo)
                self._bind_algorithm_metrics(algo)
            # serving fast path (ISSUE 13): validate the knob — a bad
            # config fails the deploy, not the first query — and
            # row-quantize the serving tables BEFORE device placement,
            # so the host→HBM transfer already moves the small tables.
            # The quantize hook runs its NDCG parity probe and returns
            # the f32 model unchanged where quantization loses ranking
            # (auto-off).
            if self.config.serving_quant not in ("off", "bf16", "int8"):
                raise ValueError(
                    f"serving_quant must be 'off', 'bf16' or 'int8', "
                    f"got {self.config.serving_quant!r}")
            if self.config.serving_quant != "off":
                quantized = []
                for a, m in zip(self.algorithms, models):
                    q = getattr(a, "quantize_serving_model", None)
                    if q is None:
                        quantized.append(m)
                        continue
                    # bind-time only (deploy/reload/promote, never a
                    # query): the quantize hook is a pure table
                    # rewrite with the same atomic-swap contract as
                    # the prepare_serving_model calls below; it
                    # cannot re-enter the binding lock.
                    # ptpu: allow[callback-under-lock]
                    quantized.append(q(m, self.config.serving_quant))
                models = quantized
            # fix device placement ONCE at bind (deploy/reload), not
            # per query — a re-materialized model holds numpy factors
            bind_batch = self.config.max_batch if self.config.batching \
                else 1
            self.models = [a.prepare_serving_model(m, bind_batch)
                           for a, m in zip(self.algorithms, models)]
            self.serving = self.engine.make_serving(engine_params)
            self._record_serving_kernel()
            # mesh-wide placement (ISSUE 6): resolve the serving mode
            # against the live devices and the model's resident bytes,
            # then either fan the binding out as per-device lane copies
            # (replicated) or re-place it row-sharded over the serving
            # mesh (sharded). Inside the same lock as the binding swap:
            # a promote/reload swaps mode, mesh, lanes and models as
            # one unit — queries never see a half-placed binding.
            # ptpu: allow[blocking-under-lock] — that atomic-swap
            # contract is exactly why the device placement happens
            # with the lock held (bind-time, never per query)
            self._place_binding()

    # ptpu: guarded-by[_lock] — only ever called from _bind under the
    # binding lock (the gauge family itself is thread-safe)
    def _record_serving_kernel(self) -> None:
        """Note the quantization the bound ALS tables ended up with
        (the parity probe may have refused the configured one) and
        refresh the ``pio_serving_kernel`` info gauge (ISSUE 13) from
        it: that label reads 1, a label from a prior bind drops to 0.
        The very first _bind runs before __init__ creates the registry
        — __init__ re-records right after; rebinds find it in place."""
        from ..models.als import ALSModel, serving_quant_of

        self._serving_quant = next(
            (serving_quant_of(m) for m in self.models
             if isinstance(m, ALSModel)), None)
        if getattr(self, "metrics", None) is None:
            return  # constructor's initial _bind; __init__ re-records
        if self._serving_quant is None:
            return
        fam = self.metrics.gauge(
            "pio_serving_kernel",
            "Quant dtype of the bound engine's serving tables (info "
            "gauge: 1 at the active label)")
        for _, child in fam.children():
            child.set(0.0)
        fam.labels(quant=self._serving_quant).set(1.0)

    def _record_sharding_findings(self) -> None:
        """Record the ``pio_sharding_findings`` info gauge (ISSUE 14):
        per-rule count of ``# ptpu: allow[...]`` pragmas naming a
        sharding-family rule baked into THIS deployed build — the
        accepted-and-justified sharding debt the static pass would
        otherwise flag. A deploy that ships new suppressed sharding
        findings moves this gauge, so the debt is visible on /metrics
        next to ``pio_serving_kernel``, not only in
        code review. Source-text census (no jax, no AST), run once at
        server construction — the installed sources don't change under
        a live process."""
        if getattr(self, "metrics", None) is None:
            return
        try:
            from ..analysis.sharding import count_sharding_pragmas

            counts = count_sharding_pragmas()
            fam = self.metrics.gauge(
                "pio_sharding_findings",
                "Pragma-suppressed sharding findings baked into the "
                "deployed build (info gauge: count per rule)")
            for rule, n in sorted(counts.items()):
                fam.labels(rule=rule).set(float(n))
            self._sharding_findings = dict(counts)
        except Exception:  # noqa: BLE001 — telemetry must not block
            pass           # server construction

    def sharding_findings_status(self) -> dict:
        """The suppressed-sharding-debt block for /status.json."""
        counts = getattr(self, "_sharding_findings", None) or {}
        return {"suppressed": sum(counts.values()),
                "byRule": dict(sorted(counts.items()))}

    def serving_kernel_status(self) -> dict:
        """The serving-kernel block for /status.json: the configured
        quant dtype and the one the bound ALS tables ended up with
        (they differ after the auto-off parity fallback; None with no
        ALS model bound)."""
        with self._lock:
            quant = self._serving_quant
        return {"configuredQuant": self.config.serving_quant,
                "quant": quant}

    @staticmethod
    def _models_nbytes(models: List[Any]) -> Optional[int]:
        """Resident bytes of the bound models' array leaves — the
        numerator of the auto-mode HBM sizing math. None when nothing
        reports nbytes (sizing unknown ≠ sizing zero)."""
        try:
            import jax

            total = 0
            seen = False
            for m in models:
                for leaf in jax.tree_util.tree_leaves(m):
                    nb = getattr(leaf, "nbytes", None)
                    if nb is not None:
                        total += int(nb)
                        seen = True
            return total if seen else None
        except Exception:  # noqa: BLE001 — sizing is advisory
            return None

    # ptpu: guarded-by[_lock] — only ever called from _bind, which
    # holds the (reentrant) binding lock around the whole placement
    def _place_binding(self) -> None:
        """Resolve ``ServerConfig.serving_mode`` and place the stable
        binding accordingly. Called under ``self._lock`` from
        :meth:`_bind`. Sets ``serving_mode_resolved``, ``serving_mesh``
        (sharded), and ``lane_devices``/``lane_models`` (replicated:
        one full model list per device, each committed to its own
        chip)."""
        self.serving_mesh = None
        self.lane_devices: List[Any] = []
        self.lane_models: List[List[Any]] = []
        # a rebind replicates every lane fresh: prior lane deaths are
        # about models/devices that no longer serve (the constructor's
        # first _bind runs before the health state exists)
        if getattr(self, "_lane_health", None) is not None:
            with self._lane_health:
                self._dead_lanes.clear()
                self._lane_streaks.clear()
        mode = self.config.serving_mode
        if mode == "single":
            self.serving_mode_resolved = "single"
            return
        import jax

        from ..parallel.mesh import (
            make_serving_mesh,
            resolve_serving_mode,
        )

        devices = jax.devices()
        resolved = resolve_serving_mode(
            mode, self._models_nbytes(self.models), len(devices))
        if resolved != "sharded" and len(devices) <= 1:
            resolved = "single"
        self.serving_mode_resolved = resolved
        if resolved == "replicated":
            self.lane_devices = list(devices)
            for dev in devices:
                lane = []
                for a, m in zip(self.algorithms, self.models):
                    rep = getattr(a, "replicate_serving_model", None)
                    lane.append(rep(m, dev) if rep is not None else m)
                self.lane_models.append(lane)
        elif resolved == "sharded":
            mesh = make_serving_mesh(devices=devices)
            self.serving_mesh = mesh
            self.models = self._shard_models(self.algorithms,
                                             self.models, mesh)

    @staticmethod
    def _shard_models(algorithms: List[Any], models: List[Any],
                      mesh) -> List[Any]:
        """Row-shard every model whose algorithm supports it; models
        without the hook keep their single-device placement (they
        still serve — just not mesh-wide)."""
        out = []
        for a, m in zip(algorithms, models):
            hook = getattr(a, "shard_serving_model", None)
            out.append(hook(m, mesh) if hook is not None else m)
        return out

    def _bind_feature_cache(self, algo: Any) -> None:
        """Hand the feature tier to algorithms that cache serving-time
        event-store reads (e.g. the e-commerce template's seen/
        unavailable/weighted/recent lookups)."""
        if self.cache is None:
            return
        bind = getattr(algo, "bind_feature_cache", None)
        if bind is not None:
            bind(self.cache.features)

    def _bind_algorithm_metrics(self, algo: Any) -> None:
        """Hand the registry to algorithms that keep per-batch series
        of their own (``register_metrics``; e.g. the generative
        template's token and expert-load counts). The constructor's
        initial ``_bind`` runs before the registry exists; ``__init__``
        binds again right after."""
        if getattr(self, "metrics", None) is None:
            return
        register = getattr(algo, "register_metrics", None)
        if register is not None:
            register(self.metrics)

    def _make_cache(self):
        cfg = self.config
        if not cfg.serving_cache:
            return None
        from ..cache import ServingCache

        return ServingCache(
            query_entries=cfg.cache_entries,
            query_ttl_sec=cfg.cache_ttl_sec,
            feature_entries=cfg.feature_cache_entries,
            feature_ttl_sec=cfg.feature_ttl_sec,
            hot_capacity=cfg.hot_entities,
            hot_refresh_every=cfg.hot_refresh_every,
            pin_fn=self._pin_hot)

    def _pin_hot(self, entity_keys: List[str]):
        """Hot-tier pin callback: delegate to the (single) algorithm's
        ``pin_hot_entities`` against the CURRENT stable binding. Under
        replicated fan-out the pin lands on EVERY lane device
        (per-device pinned shards), so hot serves stay lane-local."""
        with self._lock:
            algorithms, models = self.algorithms, self.models
            devices = list(self.lane_devices)
        if len(algorithms) != 1:
            return {}, 0  # multi-algo serving blends predictions;
        pin = getattr(algorithms[0], "pin_hot_entities", None)  # a
        if pin is None:                  # single-algo pin would skew
            return {}, 0
        if devices:
            try:
                return pin(models[0], entity_keys, devices=devices)
            except TypeError:
                pass  # algorithm predates per-lane pinning
        return pin(models[0], entity_keys)

    def _transfer_guard(self):
        """Post-warmup queries run under ``jax.transfer_guard`` so any
        implicit device↔host transfer on the hot path is logged (or
        rejected, per config) instead of silently stalling dispatch.
        Warmup-phase traffic and guard levels of "allow"/"off" get a
        no-op context; so does a jax too old to have the API."""
        from contextlib import nullcontext

        level = self.config.transfer_guard
        if not level or level in ("off", "allow") \
                or not self.warm_done.is_set():
            return nullcontext()
        try:
            import jax

            return jax.transfer_guard(level)
        except Exception:  # noqa: BLE001 — observability, never a dep
            return nullcontext()

    def _ensure_algo_pool(self):
        with self._lock:
            if self._algo_pool is None:
                from concurrent.futures import ThreadPoolExecutor
                self._algo_pool = ThreadPoolExecutor(
                    max_workers=8, thread_name_prefix="algo-dispatch",
                    initializer=name_os_thread)
            return self._algo_pool

    def _predict_all(self, algorithms: List[Any], models: List[Any],
                     supplemented: Any) -> List[Any]:
        """Per-algorithm predictions, dispatched CONCURRENTLY when the
        engine has more than one algorithm (the reference served them
        serially — ``CreateServer.scala:507-510`` "TODO: Parallelize";
        predictions are independent by the DASE contract, serving sees
        them in params order). The single-algorithm common case stays
        pool-free."""
        if len(algorithms) == 1:
            return [algorithms[0].predict(models[0], supplemented)]
        pool = self._ensure_algo_pool()
        futures = [pool.submit(a.predict, m, supplemented)
                   for a, m in zip(algorithms, models)]
        return [f.result() for f in futures]

    def _dispatch_predictions(self, algorithms: List[Any],
                              models: List[Any],
                              supplemented: Any) -> List[Any]:
        """Per-query dispatch with the hot-entity fast path (ISSUE 4):
        a known-hot user's prediction runs off the pinned device-
        resident row table (``predict_pinned``), skipping the full
        factor-table gather; anything unusual falls back to the normal
        path — the tier is an accelerator, never a correctness
        dependency."""
        cache = self.cache
        if (cache is not None and cache.hot is not None
                and len(algorithms) == 1):
            entity = getattr(supplemented, "user", None)
            handle = (cache.hot.lookup(str(entity))
                      if entity is not None else None)
            pinned = getattr(algorithms[0], "predict_pinned", None)
            if handle is not None and pinned is not None:
                try:
                    return [pinned(models[0], supplemented, handle)]
                except Exception as e:  # noqa: BLE001 — e.g. a pin
                    log.warning(        # raced a rebind; serve normally
                        "pinned hot-path serve failed, falling "
                        "back: %s", e)
        return self._predict_all(algorithms, models, supplemented)

    def _phase(self, phase: str):
        """The bound ``pio_query_phase_seconds`` child of ``phase``."""
        child = self._phase_children.get(phase)
        if child is None:
            child = self._phase_children[phase] = \
                self._phase_hist.labels(phase=phase)
        return child

    def _record_phases(self, phases: dict) -> None:
        for phase, sec in phases.items():
            self._phase(phase).observe(sec)

    def _observe_release(self, arm: str, seconds: float,
                         error: bool) -> None:
        """Per-arm health series, recorded only while a rollout is
        live (the controller windows these; client 4xx never counts
        against an arm's health)."""
        rollout = self.rollout
        if rollout is None or not rollout.active:
            return
        self._release_queries.labels(arm=arm).inc()
        if error:
            self._release_errors.labels(arm=arm).inc()
        self._release_latency.labels(arm=arm).observe(seconds)

    def release_arm_snapshot(self, arm: str):
        """Cumulative ``(queries, errors, latency buckets)`` for one
        release arm — the rollout controller diffs successive snapshots
        into sliding windows."""
        return (self._release_queries.labels(arm=arm).value,
                self._release_errors.labels(arm=arm).value,
                self._release_latency.labels(arm=arm).bucket_counts())

    def release_arms(self) -> dict:
        """Live per-arm stats for ``/release.json`` and the bench."""
        out = {}
        for arm in (ARM_STABLE, ARM_CANDIDATE):
            queries, errors, _ = self.release_arm_snapshot(arm)
            out[arm] = {
                "queries": int(queries), "errors": int(errors),
                "latency": self._release_latency.labels(
                    arm=arm).snapshot()}
        return out

    def mesh_status(self) -> dict:
        """Mesh-wide serving state for ``/status.json`` and the status
        page (ISSUE 6): resolved mode, mesh shape, and — under
        replicated fan-out — per-lane device / dispatch-count / batch
        latency / queue-depth rows (the per-device occupancy view; the
        per-device HBM gauges live in the sibling ``hbm`` block)."""
        with self._lock:
            mode = self.serving_mode_resolved
            lane_devices = list(self.lane_devices)
            mesh = self.serving_mesh
        out: dict = {"mode": mode}
        if mesh is not None:
            out["meshShape"] = {str(ax): int(sz) for ax, sz
                                in zip(mesh.axis_names,
                                       mesh.devices.shape)}
            out["devices"] = int(mesh.devices.size)
        if lane_devices:
            out["devices"] = len(lane_devices)
            lanes = []
            for i, dev in enumerate(lane_devices):
                lat = self._lane_latency.labels(lane=str(i)).snapshot()
                depth = self._lane_depth.labels(lane=str(i)).snapshot()
                lanes.append({
                    "lane": i,
                    "device": str(dev),
                    "deviceId": int(getattr(dev, "id", i)),
                    "dispatches": int(self._lane_dispatches.labels(
                        lane=str(i)).value),
                    "batchP50Ms": (round(lat["p50"] * 1000, 3)
                                   if lat.get("count") else None),
                    "batchP99Ms": (round(lat["p99"] * 1000, 3)
                                   if lat.get("count") else None),
                    "queueDepthP50": (depth["p50"]
                                      if depth.get("count") else None),
                })
            out["lanes"] = lanes
        return out

    # -- lane supervision / graceful degradation (ISSUE 11) -----------------
    def live_lane(self, lane: int) -> int:
        """Where a batch assigned to ``lane`` should actually run:
        identity while the lane is healthy, a surviving lane while it
        is dead (docs/reliability.md)."""
        with self._lock:
            n = len(self.lane_models)
        with self._lane_health:
            return pick_live_lane(lane, n, self._dead_lanes)

    def lane_attempt_order(self, lane: int) -> List[int]:
        """Dispatch-failover order for a batch assigned to ``lane``:
        its live mapping first, then every other lane (healthy ones
        before dead ones as a last resort) — each tried at most once,
        so one batch can never loop."""
        with self._lock:
            n = len(self.lane_models)
        if n <= 0:
            return [lane]
        with self._lane_health:
            dead = set(self._dead_lanes)
        first = pick_live_lane(lane % n, n, dead)
        rest = [i for i in range(n) if i != first]
        rest.sort(key=lambda i: (i in dead, i))
        return [first] + rest

    def _lane_ok(self, lane: int) -> None:
        with self._lane_health:
            self._lane_streaks.pop(lane, None)

    def _lane_error(self, lane: int, exc: Exception) -> None:
        """A dispatch on ``lane`` failed: count the streak and declare
        the lane dead at ``lane_fail_threshold`` consecutive failures
        (then start its restarter)."""
        self._lane_failures.labels(lane=str(lane)).inc()
        threshold = max(self.config.lane_fail_threshold, 1)
        with self._lane_health:
            if lane in self._dead_lanes:
                return
            streak = self._lane_streaks.get(lane, 0) + 1
            self._lane_streaks[lane] = streak
            if streak < threshold:
                return
            self._dead_lanes[lane] = {
                "since": time.time(),
                "reason": f"{type(exc).__name__}: {exc}"[:300],
                "failures": streak,
            }
        log.error("serving lane %d declared dead after %d consecutive "
                  "dispatch failures (%s); redistributing its traffic "
                  "and starting the restarter", lane, streak, exc)
        threading.Thread(target=self._lane_restarter, args=(lane,),
                         daemon=True,
                         name=f"lane-restarter-{lane}").start()

    def _lane_restarter(self, lane: int) -> None:
        """Probe a dead lane back to life: bounded-exponential-backoff
        attempts, each probing the lane's fault point (a still-armed
        injection keeps it down) and re-replicating the serving models
        onto the lane's device. Success rejoins the lane and counts
        ``pio_lane_restarts_total``; an exhausted budget leaves it dead
        (degraded mode persists — the operator sees it on
        /status.json)."""
        cfg = self.config
        policy = RetryPolicy(
            max_attempts=max(cfg.lane_restart_max_attempts, 1),
            base_ms=max(cfg.lane_restart_backoff_ms, 1.0),
            cap_ms=max(cfg.lane_restart_backoff_ms, 1.0) * 32)
        delays = list(backoff_delays(policy)) + [0.0]
        for delay in delays:
            time.sleep(delay)
            with self._lock:
                if lane >= len(self.lane_devices):
                    return  # a rebind changed the lane layout
                dev = self.lane_devices[lane]
                algorithms = self.algorithms
                models = self.models
                instance_id = self.instance.id
            try:
                # the probe: if the injected (or real) fault is still
                # there, this raises and we back off
                fire(F_LANE_RESTART, lane=str(lane))
                fire(F_LANE, lane=str(lane))
                fresh = []
                for a, m in zip(algorithms, models):
                    rep = getattr(a, "replicate_serving_model", None)
                    fresh.append(rep(m, dev) if rep is not None else m)
            except Exception as e:  # noqa: BLE001 — still down
                log.warning("lane %d restart probe failed: %s", lane, e)
                continue
            with self._lock:
                if self.instance.id != instance_id \
                        or lane >= len(self.lane_models):
                    return  # binding swapped mid-restart: the rebind
                    # already rebuilt every lane and reset health
                self.lane_models[lane] = fresh
            with self._lane_health:
                self._dead_lanes.pop(lane, None)
                self._lane_streaks.pop(lane, None)
            self._lane_restarts.labels(lane=str(lane)).inc()
            log.info("serving lane %d restarted and rejoined", lane)
            return
        log.error("serving lane %d restart budget exhausted (%d "
                  "attempts); staying degraded", lane,
                  policy.max_attempts)

    def degraded_status(self) -> dict:
        """The degraded block of ``/status.json``: dead lanes, restart
        and failure totals, and whether fault injection is armed."""
        with self._lane_health:
            dead = [{"lane": int(k), "since": v["since"],
                     "reason": v["reason"]}
                    for k, v in sorted(self._dead_lanes.items())]

        def _total(fam) -> int:
            return int(sum(child.value for _, child in fam.children()))

        nonfinite = numerics_sentinel.active() \
            and numerics_sentinel.nonfinite_seen()
        return {
            "active": bool(dead) or nonfinite,
            "deadLanes": dead,
            "laneRestarts": _total(self._lane_restarts),
            "laneFailures": _total(self._lane_failures),
            "faultInjection": fault_registry().enabled(),
            "nonfinite": nonfinite,
        }

    def spans_summary(self) -> dict:
        """Percentile rows for the status page: each query phase plus
        end-to-end latency, from the live bounded histograms."""
        out: dict = {}

        def row(hist) -> Optional[dict]:
            s = hist.snapshot()
            if not s.get("count"):
                return None
            return {"count": s["count"], "p50": s["p50"],
                    "p90": s["p90"], "p99": s["p99"],
                    "max_sec": s["max"]}

        for items, child in self._phase_hist.children():
            r = row(child)
            if r is not None:
                out["phase:" + dict(items).get("phase", "?")] = r
        r = row(self._latency_hist)
        if r is not None:
            out["query (end-to-end)"] = r
        return out

    # -- cached serving entrypoints (ISSUE 4) --------------------------------
    @staticmethod
    def _entity_of(query_json: Any) -> Optional[str]:
        """The query's primary entity (the cache-tag / hot-tier key).
        Every bundled template keys queries by ``user``; entity-less
        queries cache fine but can't be invalidated per-entity (the
        TTL bound covers them)."""
        if isinstance(query_json, dict):
            entity = query_json.get("user")
            if entity is not None:
                return str(entity)
        return None

    def _record_cache_hit(self, arm: str, t0: float,
                          obs: Optional[dict]) -> None:
        dt = time.monotonic() - t0
        self._latency_hist.observe(dt)
        self._observe_release(arm, dt, error=False)
        if obs is not None:
            obs["cache"] = "hit"
            tr = self._trace_of(obs)
            if tr is not None:
                # a hit never touches the device: one span tells the
                # whole story, and the tier rides as an attribute
                tr.set_attr("arm", arm)
                tr.set_attr("cacheTier", "query")
                tr.add_span("cache_hit", t0, t0 + dt, tier="query")
                tr.exemplar(self._latency_hist, dt)
        with self._lock:
            self.last_serving_sec = dt
            self.avg_serving_sec = (
                (self.avg_serving_sec * self.request_count + dt)
                / (self.request_count + 1))
            self.request_count += 1

    def _compute_stable(self, query_json: Any,
                        obs: Optional[dict]) -> Any:
        """The uncached stable pipeline: micro-batcher when configured,
        else the per-query path. Returns the jsonable result or an
        ``HTTPError`` instance (the batcher's slot contract); the
        per-query path raises instead — callers handle both."""
        if self.batcher is not None:
            return self.batcher.submit(query_json, obs=obs)
        return self.query(query_json, obs=obs)

    def serve(self, query_json: Any, obs: Optional[dict] = None) -> Any:
        """The stable-arm serving entry ``/queries.json`` uses: query
        cache → singleflight → batcher/per-query compute → cache fill.
        A cache hit skips supplement and device dispatch entirely;
        concurrent identical misses compute ONCE. Returns the result
        or an ``HTTPError`` instance; may also raise ``HTTPError``."""
        if self.hotkeys is not None:
            # recorded BEFORE the cache: a hot key that is hot because
            # it keeps hitting the cache is still a hot key (the
            # router signal counts demand, not device work)
            self.hotkeys.record(self._entity_of(query_json))
        cache = self.cache
        if cache is None:
            return self._compute_stable(query_json, obs)
        from ..cache import canonical_key, entity_tag

        t0 = time.monotonic()
        with self._lock:
            instance_id = self.instance.id
        key = (instance_id, canonical_key(query_json))
        entity = self._entity_of(query_json)
        if entity is not None and cache.hot is not None:
            cache.hot.record(entity)
        found, value = cache.query.lookup(key)
        if found:
            self._record_cache_hit(ARM_STABLE, t0, obs)
            return value
        tag = entity_tag("user", entity) if entity is not None else None

        def compute() -> Any:
            # epoch BEFORE the pipeline runs: an ingest that lands
            # mid-compute moves it, and the fill is dropped instead of
            # caching a result the invalidation already condemned
            token = cache.epoch_token(tag)
            result = self._compute_stable(query_json, obs)
            if not isinstance(result, HTTPError):
                cache.put_query_fresh(
                    key, result, (tag,) if tag else (), token)
            return result

        result, leader = cache.flight.do(key, compute)
        if obs is not None and not leader:
            obs["cache"] = "coalesced"
        return result

    def serve_candidate(self, query_json: Any,
                        obs: Optional[dict] = None) -> Any:
        """The candidate-arm serving entry: same cache discipline as
        :meth:`serve` under the CANDIDATE instance's namespace — the
        two arms can never serve each other's cached results. Raises
        like :meth:`query_candidate`."""
        if self.hotkeys is not None:
            self.hotkeys.record(self._entity_of(query_json))
        cache = self.cache
        with self._lock:
            cand = self._candidate
        if cache is None or cand is None:
            return self.query_candidate(query_json, obs=obs)
        from ..cache import canonical_key, entity_tag

        t0 = time.monotonic()
        key = (cand.instance.id, canonical_key(query_json))
        found, value = cache.query.lookup(key)
        if found:
            self._record_cache_hit(ARM_CANDIDATE, t0, obs)
            return value
        entity = self._entity_of(query_json)
        tag = entity_tag("user", entity) if entity is not None else None

        def compute() -> Any:
            token = cache.epoch_token(tag)
            result = self.query_candidate(query_json, obs=obs)
            cache.put_query_fresh(key, result, (tag,) if tag else (),
                                  token)
            return result

        result, leader = cache.flight.do(key, compute)
        if obs is not None and not leader:
            obs["cache"] = "coalesced"
        return result

    # -- batched hot path ---------------------------------------------------
    def _finish_pipeline_batch(self, ab: "_AssembledBatch",
                               results: List[Any]) -> None:
        """Readback-stage tail of the staged pipeline (ISSUE 9): the
        per-query host work after the device results resolve —
        serialization (``to_jsonable``), feedback, output plugins,
        metric recording, caller wake. Per-query errors come back as
        ``HTTPError``s in the result slots so one bad query never fails
        its batch-mates. ``results`` is the resolved
        :class:`PendingBatch` output, aligned with ``ab.entries``.
        Stamps ``ab.t_done`` and derives everything it records from the
        batch's stamps."""
        cfg = self.config
        readback = feedback = None
        final: List[Any] = [None] * len(ab.entries)
        for i, (entry, result) in enumerate(zip(ab.entries, results)):
            if isinstance(result, HTTPError):
                final[i] = result
                continue
            if isinstance(result, Exception):
                final[i] = HTTPError(500, str(result))
                continue
            try:
                tr0 = time.monotonic()
                jsonable = to_jsonable(result)
                tr1 = tf = time.monotonic()
                # max-not-sum: the batch phase reports the worst
                # query's serialization; the sum overstated the phase
                # ~B× at large batches in the status page's percentile
                # table (entry.own keeps each query's own split)
                readback = max(readback or 0.0, tr1 - tr0)
                if cfg.feedback:
                    jsonable = self._feedback(
                        ab.queries[i], entry.query_json, jsonable,
                        ab.instance_id)
                    tf = time.monotonic()
                    feedback = (feedback or 0.0) + (tf - tr1)
                entry.own = (tr0, tr1, tf)
                final[i] = self.plugins.process_output(entry.query_json,
                                                       jsonable)
            except Exception as e:  # noqa: BLE001 — per-query slot
                final[i] = HTTPError(500, str(e))
        ab.t_done = now = time.monotonic()
        timeline = ab.timeline()
        phases = {name: t1 - t0 for name, t0, t1 in timeline.spans}
        # parts of ``finish``, as documented
        if readback is not None:
            phases["readback"] = readback
        if feedback is not None:
            phases["feedback"] = feedback
        self._record_phases(phases)
        self._batch_occupancy.observe(len(ab.entries))
        if ab.lane is not None:
            self._lane_latency.labels(lane=str(ab.lane)).observe(
                now - ab.t_dispatch_pick)
            self._lane_dispatches.labels(lane=str(ab.lane)).inc()
        batch_obs = {"batchSize": len(ab.entries), "pipeline": "staged"}
        if ab.lane is not None:
            batch_obs["lane"] = ab.lane
        batch_obs.update({f"{k}Ms": round(v * 1000, 3)
                          for k, v in phases.items()})
        total_dt = 0.0
        for i, (entry, result) in enumerate(zip(ab.entries, final)):
            # end-to-end per query INCLUDING its queue wait — the
            # latency the caller actually experienced
            dt = now - entry.t_enq
            total_dt += dt
            self._latency_hist.observe(dt)
            is_err = isinstance(result, HTTPError)
            self._observe_release(
                ARM_STABLE, dt, error=is_err and result.status >= 500)
            if is_err:
                self._query_errors.labels(
                    status=str(result.status)).inc()
            if entry.obs is not None:
                entry.obs.update(batch_obs)
                if entry.own is not None:
                    tr0, tr1, tf = entry.own
                    entry.obs["readbackMs"] = round(
                        (tr1 - tr0) * 1000, 3)
                    if cfg.feedback:
                        entry.obs["feedbackMs"] = round(
                            (tf - tr1) * 1000, 3)
            entry.t_done = now
            entry.batch = timeline
            entry.slot[0] = result
            entry.done.set()
        n_q = len(ab.entries)
        if n_q:
            with self._lock:
                n = self.request_count
                self.last_serving_sec = total_dt / n_q
                self.avg_serving_sec = ((self.avg_serving_sec * n
                                         + total_dt) / (n + n_q))
                self.request_count += n_q

    def _stamp_wake(self, e: "_Submit", obs: Optional[dict]) -> None:
        """The handler thread runs again after its staged query: stamp
        ``t_wake``, observe ``wake`` (and ``admit``, which needs the
        request's ``t_enter``), copy the query's stamps onto the
        request's record for the HTTP layer's closing stamps, and hand
        the stamps to the query's trace. A shed query's entry has no
        ``t_done``: nothing to derive."""
        if e.t_done is None:
            return
        t_wake = time.monotonic()
        self._phase("wake").observe(t_wake - e.t_done)
        st = obs.get("_stamps") if obs is not None else None
        t_enter = None
        if st is not None and st.t_enter is not None:
            t_enter = st.t_enter
            st.t_enq, st.t_done, st.t_wake = e.t_enq, e.t_done, t_wake
            st.batch = e.batch
            self._phase("admit").observe(e.t_enq - t_enter)
        tr = self._trace_of(obs)
        if tr is not None and e.batch is not None:
            tr.defer(_staged_spans, e.batch, t_enter, e.t_enq, e.own,
                     t_wake)
            tr.exemplar(self._latency_hist, e.t_done - e.t_enq)

    def pipeline_status(self) -> dict:
        """Serving batch-path state for ``/status.json`` and the status
        page (ISSUE 9): architecture, deadline accounting, and the
        overlap snapshot that proves (or disproves) the device stays
        busy while host stages run."""
        b = self.batcher
        out: dict = {
            "mode": "staged" if b is not None else "off",
            "deadlineMs": self.config.queue_deadline_ms,
            "deadlineExceeded": int(self._deadline_exceeded
                                    .labels().value),
        }
        if b is not None:
            out["assembleWorkers"] = self.config.assemble_workers
            out["readbackWorkers"] = self.config.readback_workers
            out["depth"] = b.depth  # resolved (0 = auto in config)
            out["inFlight"] = self.overlap.active("device")
        snap = self.overlap.snapshot()
        if snap["wall_sec"] > 0:
            out["overlap"] = {
                "wallSec": round(snap["wall_sec"], 3),
                "deviceBusySec": round(snap["device_busy_sec"], 3),
                "deviceIdleFraction": round(
                    snap["device_idle_fraction"], 4),
                "overlapFraction": round(snap["overlap_fraction"], 4),
                "overlappedDispatches": int(
                    self._pipeline_overlapped.labels().value),
            }
        return out

    def _trace_of(self, obs: Optional[dict]):
        """The live request trace riding the obs dict (None when the
        caller is untraced or tracing is off)."""
        if obs is None or self.tracer is None:
            return None
        return obs.get("_trace")

    # -- the per-query hot path (CreateServer.scala:484-633) ---------------
    def query(self, query_json: Any, obs: Optional[dict] = None) -> Any:
        t0 = time.monotonic()
        phases: dict = {}
        trace = self._trace_of(obs)
        with self._lock:
            algorithms, models, serving = \
                self.algorithms, self.models, self.serving
            instance_id = self.instance.id
        if trace is not None:
            trace.set_attr("engineInstanceId", instance_id)
            trace.set_attr("arm", ARM_STABLE)
        query_cls = algorithms[0].query_class
        try:
            query = from_jsonable(query_cls, query_json)
        except (TypeError, ValueError) as e:
            self._query_errors.labels(status="400").inc()
            raise HTTPError(400, str(e))
        t1 = time.monotonic()
        phases["assemble"] = t1 - t0
        try:
            with activate_traces([trace]), self._transfer_guard():
                supplemented = serving.supplement(query)
                t2 = time.monotonic()
                phases["supplement"] = t2 - t1
                predictions = self._dispatch_predictions(
                    algorithms, models, supplemented)
                t3 = time.monotonic()
                phases["dispatch"] = t3 - t2
                # by design: serve sees the original query
                # (CreateServer.scala:511)
                prediction = serving.serve(query, predictions)
                t4 = time.monotonic()
                phases["serve"] = t4 - t3
            result = to_jsonable(prediction)
            t5 = time.monotonic()
            phases["readback"] = t5 - t4

            if self.config.feedback:
                result = self._feedback(query, query_json, result,
                                        instance_id)
                phases["feedback"] = time.monotonic() - t5
            result = self.plugins.process_output(query_json, result)
        except Exception:
            self._query_errors.labels(status="500").inc()
            self._observe_release(ARM_STABLE, time.monotonic() - t0,
                                  error=True)
            self._record_phases(phases)
            add_stage_spans(trace, t0, phases)
            raise

        dt = time.monotonic() - t0
        self._record_phases(phases)
        self._latency_hist.observe(dt)
        self._observe_release(ARM_STABLE, dt, error=False)
        if trace is not None:
            # per-query child spans (ISSUE 12): the phases run
            # back-to-back on this thread, so the sequential layout
            # from t0 IS the real timeline
            add_stage_spans(trace, t0, phases)
            trace.exemplar(self._latency_hist, dt)
        if obs is not None:
            obs.update({f"{k}Ms": round(v * 1000, 3)
                        for k, v in phases.items()})
        with self._lock:
            self.last_serving_sec = dt
            self.avg_serving_sec = (
                (self.avg_serving_sec * self.request_count + dt)
                / (self.request_count + 1))
            self.request_count += 1
        return result

    def _feedback(self, query: Any, query_json: Any, result: Any,
                  instance_id: str) -> Any:
        """Record the prediction as a ``predict`` event on entity type
        ``pio_pr`` (``CreateServer.scala:527-589``); injects ``prId`` into
        the response when the prediction carries one."""
        pr_id = _gen_pr_id()
        if isinstance(result, dict) and result.get("prId"):
            pr_id = result["prId"]
        properties = {"engineInstanceId": instance_id,
                      "query": to_jsonable(query_json),
                      "prediction": result}
        event = Event(event="predict", entity_type="pio_pr", entity_id=pr_id,
                      properties=properties,
                      pr_id=(query_json or {}).get("prId")
                      if isinstance(query_json, dict) else None)
        app_name = self.config.feedback_app_name
        try:
            app = self.ctx.storage.apps().get_by_name(app_name or "")
            if app is None:
                raise RuntimeError(
                    f"feedback app {app_name!r} not found")
            self.ctx.storage.events().insert(event, app.id)
        except Exception as e:  # feedback must never fail the query
            log.error("feedback event failed: %s", e)
        if isinstance(result, dict):
            result = dict(result, prId=pr_id)
        return result

    # -- progressive delivery (ISSUE 3) -------------------------------------
    def bind_candidate(self, instance: EngineInstance,
                       engine_params: Optional[EngineParams] = None,
                       models: Optional[List[Any]] = None) -> None:
        """Bind a candidate release ALONGSIDE the stable one (stable
        serving is untouched). The candidate serves per-query (batch 1)
        — at canary fractions there is nothing to coalesce — and warms
        its serving shapes in the background."""
        from ..workflow import core as wf

        with self._lock:
            stable_params = self.engine_params
        ep = engine_params or stable_params
        if models is None:
            models = wf.load_models_for_deploy(self.ctx, self.engine,
                                               instance, ep)
        algorithms = self.engine.make_algorithms(ep)
        for algo in algorithms:
            algo.bind_serving(self.ctx)
            self._bind_feature_cache(algo)
            self._bind_algorithm_metrics(algo)
        # the candidate serves under the same quant policy as stable
        # (an A/B across precision is a config change, not a canary);
        # raw_models stay unquantized so promote re-derives through
        # the normal _bind
        to_prepare = models
        if self.config.serving_quant != "off":
            to_prepare = []
            for a, m in zip(algorithms, models):
                q = getattr(a, "quantize_serving_model", None)
                to_prepare.append(
                    q(m, self.config.serving_quant)
                    if q is not None else m)
        prepared = [a.prepare_serving_model(m, 1)
                    for a, m in zip(algorithms, to_prepare)]
        with self._lock:
            mode, mesh = self.serving_mode_resolved, self.serving_mesh
        if mode == "sharded" and mesh is not None:
            # sharded warm-swap (ISSUE 6): a candidate for a >1-HBM
            # stable must bind row-sharded too — a single-device copy
            # of it may not physically fit. Promote later re-places
            # through the normal _bind, so the stable arm re-derives
            # its own sharding rather than inheriting this one.
            prepared = self._shard_models(algorithms, prepared, mesh)
        binding = CandidateBinding(
            engine_params=ep, algorithms=algorithms, models=prepared,
            raw_models=list(models),
            serving=self.engine.make_serving(ep),
            instance=instance, warm_done=threading.Event())

        def _warm_candidate():
            for algo, model in zip(algorithms, prepared):
                warm = getattr(algo, "warm_serving", None)
                if warm is None:
                    continue
                try:
                    warm(model, 1)
                except Exception as e:  # noqa: BLE001 — cold is slow,
                    log.warning(        # not broken
                        "candidate warmup failed for %s: %s",
                        type(algo).__name__, e)
            binding.warm_done.set()

        threading.Thread(target=_warm_candidate, daemon=True,
                         name="candidate-warmup").start()
        with self._lock:
            self._candidate = binding
            stable_id = self.instance.id
        log.info("candidate release %s bound alongside stable %s",
                 instance.id, stable_id)

    def drop_candidate(self) -> None:
        with self._lock:
            cand = self._candidate
            self._candidate = None
        if cand is not None and self.cache is not None:
            # rollback: the dead arm's cached results must die with it
            # (stable's namespace — still serving — is left intact)
            self.cache.flush_namespace(cand.instance.id)

    @property
    def candidate_instance_id(self) -> Optional[str]:
        with self._lock:
            cand = self._candidate
        return cand.instance.id if cand is not None else None

    def promote_candidate(self) -> str:
        """Swap the candidate in as the stable release. The swap is the
        same single-lock ``_bind`` every deploy/reload takes —
        concurrent queries see either the old or the new binding in
        full, never a mix — and the batch ladder re-warms so
        post-promote traffic pays no cold compiles."""
        with self._lock:
            cand = self._candidate
            self._candidate = None
        if cand is None:
            raise HTTPError(409, "no candidate release bound")
        self._bind(cand.engine_params, cand.raw_models, cand.instance)
        self._rewarm()
        log.info("candidate %s promoted to serving stable",
                 cand.instance.id)
        return cand.instance.id

    def start_canary(self, instance_id: str,
                     fraction: Optional[float] = None,
                     shadow: bool = False, actor: str = "",
                     reason: str = "", policy=None,
                     models: Optional[List[Any]] = None):
        """Bind ``instance_id`` as the candidate and start the
        health-gated rollout loop (canary split or shadow mirror).
        Returns the live :class:`~..rollout.RolloutController`."""
        from ..rollout import HealthPolicy, RolloutController

        if self.rollout is not None and self.rollout.active:
            raise HTTPError(409, "a rollout is already in progress "
                            f"(candidate {self.rollout.instance_id})")
        inst = self.ctx.storage.engine_instances().get(instance_id)
        if inst is None:
            raise HTTPError(
                404, f"engine instance {instance_id!r} not found")
        if inst.status != STATUS_COMPLETED:
            raise HTTPError(
                400, f"instance {instance_id!r} is {inst.status}, "
                     f"not {STATUS_COMPLETED}")
        with self._lock:
            stable_id = self.instance.id
        if inst.id == stable_id:
            raise HTTPError(
                400, f"instance {instance_id!r} is already the "
                     f"serving stable")
        self.bind_candidate(inst, models=models)
        pol = policy or HealthPolicy()
        mode = "shadow" if shadow else "canary"
        start_fraction = (fraction if fraction is not None
                          else (1.0 if shadow else pol.ramp[0]))
        try:
            self.releases.start_candidate(
                inst.id, start_fraction, mode=mode, actor=actor,
                reason=reason)
        except Exception as e:  # noqa: BLE001 — history is best-effort
            log.error("release history write failed on %s: %s", mode, e)
        controller = RolloutController(
            self, self.releases, inst.id, policy=pol,
            fraction=start_fraction, shadow=shadow,
            actor=actor or "engine-server")
        self.rollout = controller
        controller.start()
        return controller

    def query_candidate(self, query_json: Any,
                        obs: Optional[dict] = None) -> Any:
        """Serve one query off the CANDIDATE binding (canary route or
        shadow mirror). Leaner than the stable path by design: no
        feedback events (the ``prId`` lineage belongs to the stable
        release — a rolled-back candidate must leave no trace in the
        event store) and no micro-batching."""
        t0 = time.monotonic()
        with self._lock:
            cand = self._candidate
        if cand is None:
            raise HTTPError(503, "no candidate release bound")
        try:
            query = from_jsonable(cand.algorithms[0].query_class,
                                  query_json)
        except (TypeError, ValueError) as e:
            # malformed input is the client's fault: it must not count
            # against the candidate's health
            self._query_errors.labels(status="400").inc()
            raise HTTPError(400, str(e))
        try:
            with self._transfer_guard():
                supplemented = cand.serving.supplement(query)
                predictions = self._predict_all(
                    cand.algorithms, cand.models, supplemented)
                prediction = cand.serving.serve(query, predictions)
            result = to_jsonable(prediction)
            result = self.plugins.process_output(query_json, result)
        except Exception:
            self._query_errors.labels(status="500").inc()
            self._observe_release(ARM_CANDIDATE,
                                  time.monotonic() - t0, error=True)
            raise
        dt = time.monotonic() - t0
        self._observe_release(ARM_CANDIDATE, dt, error=False)
        if obs is not None:
            obs["releaseArm"] = ARM_CANDIDATE
            tr = self._trace_of(obs)
            if tr is not None:
                tr.set_attr("arm", ARM_CANDIDATE)
                tr.set_attr("engineInstanceId", cand.instance.id)
                tr.add_span("candidate_serve", t0, t0 + dt)
        return result

    def mirror_to_candidate(self, query_json: Any) -> None:
        """Shadow mode: replay the query against the candidate from a
        pool thread. The answer is discarded (the arm metrics keep the
        outcome); errors are counted and swallowed — mirroring must
        never slow or fail stable traffic."""
        with self._lock:
            if self._mirror_pool is None:
                from concurrent.futures import ThreadPoolExecutor
                self._mirror_pool = ThreadPoolExecutor(
                    max_workers=4, thread_name_prefix="shadow-mirror")
            pool = self._mirror_pool

        def _mirror():
            try:
                self.query_candidate(query_json)
            except Exception:  # noqa: BLE001 — counted in arm metrics
                pass

        self._shadow_mirrors.inc()
        pool.submit(_mirror)

    # -- streaming incremental training (ISSUE 10) --------------------------
    def stream_snapshot(self, algo_index: int = 0):
        """The streaming trainer's read side: ``(instance_id, model)``
        of the CURRENT stable binding, or None when the indexed
        algorithm's model is not foldable (no id maps — not an ALS
        factor model). The pair is snapshotted under the binding lock
        so the fold-in solves against a model that actually served
        together with that instance id; the apply re-checks the id."""
        with self._lock:
            if not (0 <= algo_index < len(self.models)):
                return None
            model = self.models[algo_index]
            instance_id = self.instance.id
        if getattr(model, "user_ids", None) is None \
                or getattr(model, "item_ids", None) is None:
            return None
        return instance_id, model

    def apply_stream_delta(self, algo_index: int, new_model: Any,
                           touched_entities: List[str],
                           base_instance_id: str,
                           rows_updated: int = 0,
                           rows_inserted: int = 0) -> bool:
        """Hot-swap a fold-in delta into the serving binding: the
        streaming twin of promote's ``_bind``, scoped to one
        algorithm's model. Under the binding lock the base instance id
        is re-checked — a reload/promote that raced the fold-in wins
        and the apply returns False (the trainer's unadvanced cursor
        re-folds against the new base). Replicated lanes re-derive
        their per-device copies from the folded model so every lane
        serves the new rows. After the swap, cached results and pinned
        hot-tier rows for exactly the touched entities are
        invalidated (docs/streaming.md)."""
        with self._lock:
            if self.instance.id != base_instance_id:
                return False
            if not (0 <= algo_index < len(self.algorithms)):
                return False
            has_lanes = bool(self.lane_models)
            rep = (getattr(self.algorithms[algo_index],
                           "replicate_serving_model", None)
                   if has_lanes else None)
            devices = list(self.lane_devices) if has_lanes else []
        # per-device replication OUTSIDE the lock: device_put of a
        # whole factor table must not stall queries, and the algorithm
        # hook is dynamically bound. The id re-check below voids the
        # copies if a rebind raced us.
        lane_copies = ([rep(new_model, dev) for dev in devices]
                       if rep is not None
                       else [new_model] * len(devices))
        with self._lock:
            if self.instance.id != base_instance_id:
                return False
            self.models[algo_index] = new_model
            if self.lane_models:
                for lane, copy in enumerate(lane_copies):
                    self.lane_models[lane][algo_index] = copy
            self._stream_generation += 1
            self._stream_rows += int(rows_updated) + int(rows_inserted)
            self._stream_last_apply = time.time()
            cache = self.cache
        if cache is not None and touched_entities:
            # per-entity, not a flush: untouched entities' cached
            # results are still exactly right — that precision is the
            # point of folding rows instead of rebinding
            cache.invalidate_entities("user", touched_entities)
            if cache.hot is not None:
                # refresh ONLY when the swap actually dropped a pinned
                # entry: an unconditional refresh re-gathered the full
                # pinned table and re-warmed its k-ladder on every
                # fold-in even when no pinned entity was touched
                # (ISSUE 13 satellite) — pure wasted device work at
                # streaming cadence
                if cache.hot.invalidate(touched_entities):
                    cache.hot.refresh(wait=False)  # re-pin new rows
        return True

    def start_stream(self, config=None):
        """Attach (and start) the streaming trainer. ``config`` is a
        :class:`~predictionio_tpu.streaming.StreamConfig`; None builds
        one from the ``ServerConfig.stream_*`` knobs. Raises
        ``ValueError`` on a bad app/channel (deploy fails fast) and
        ``HTTPError`` 409 when one is already running."""
        from ..streaming import StreamConfig, StreamTrainer

        with self._lock:
            if self.stream is not None and self.stream.running:
                raise HTTPError(
                    409, "streaming trainer already running (consumer "
                         f"{self.stream.config.consumer!r}); stop it "
                         f"first")
        cfg = config or StreamConfig(
            interval_ms=self.config.stream_interval_ms,
            max_events=self.config.stream_max_events,
            consumer=self.config.stream_consumer,
            drift_threshold=self.config.stream_drift_threshold,
            canary_probes=self.config.stream_canary_probes)
        if not cfg.app_name:
            cfg.app_name = (self.config.stream_app_name
                            or self.config.feedback_app_name or "")
        if not cfg.app_name:
            raise ValueError(
                "streaming requires an app name (ServerConfig."
                "stream_app_name, --stream-app, or the request's "
                "appName) — the app whose event log the trainer tails")
        trainer = StreamTrainer(self, cfg)
        with self._lock:
            self.stream = trainer
            instance_id = self.instance.id
        trainer.start()
        try:
            self.releases.record(
                "stream-start", instance_id=instance_id,
                actor=f"stream-trainer:{cfg.consumer}",
                reason=f"tailing app {cfg.app_name!r} every "
                       f"{cfg.interval_ms:g}ms")
        except Exception as e:  # noqa: BLE001 — history is best-effort
            log.error("release history write failed on stream-start: "
                      "%s", e)
        log.info("streaming trainer started (app %s, consumer %s)",
                 cfg.app_name, cfg.consumer)
        return trainer

    def stop_stream(self, timeout: float = 10.0) -> bool:
        """Stop and detach the streaming trainer; False when none is
        attached. The durable cursor stays in EVENTDATA — a later
        start with the same consumer resumes exactly where this one
        stopped."""
        with self._lock:
            trainer = self.stream
            self.stream = None
            instance_id = self.instance.id
        if trainer is None:
            return False
        trainer.stop(timeout=timeout)
        try:
            self.releases.record(
                "stream-stop", instance_id=instance_id,
                actor=f"stream-trainer:{trainer.config.consumer}",
                reason=f"{trainer.applies} deltas applied, "
                       f"{trainer.events_consumed} events consumed")
        except Exception as e:  # noqa: BLE001 — history is best-effort
            log.error("release history write failed on stream-stop: "
                      "%s", e)
        return True

    def stream_lineage(self) -> dict:
        """What blend of batch + stream is actually serving (ISSUE 10
        satellite): the base full-retrain instance, how many fold-in
        generations sit on top of it, and how stale the serving model
        is — seconds since it last absorbed data (the last fold-in,
        else the base retrain's completion)."""
        with self._lock:
            base = self.instance
            gen = self._stream_generation
            rows = self._stream_rows
            last = self._stream_last_apply
            bound = self._stream_base_bound_at
            trainer = self.stream
        now = time.time()
        trained = getattr(base, "end_time", None)
        if last is not None:
            staleness = now - last
        elif trained is not None:
            try:
                staleness = max(0.0, now - trained.timestamp())
            except (OSError, OverflowError, ValueError):
                staleness = now - bound
        else:
            staleness = now - bound
        return {
            "baseInstanceId": base.id,
            "incrementalGeneration": gen,
            "incrementalRows": rows,
            "lastFoldInSecAgo": (round(now - last, 3)
                                 if last is not None else None),
            "stalenessSec": round(staleness, 3),
            "streaming": trainer is not None and trainer.running,
        }

    def remote_log(self, message: str, wait: bool = False) -> None:
        """Ship an error to the configured log collector
        (``remoteLog``, ``CreateServer.scala:435-446``); failures to ship
        are logged and swallowed. Ships from a daemon thread so a slow or
        dead collector never delays the error response (pass ``wait=True``
        to block, e.g. in tests)."""
        if not self.config.log_url:
            return
        import urllib.request

        with self._lock:
            instance_id = self.instance.id
        payload = (self.config.log_prefix + json.dumps({
            "engineInstance": instance_id,
            "message": message})).encode("utf-8")

        def ship():
            try:
                req = urllib.request.Request(self.config.log_url,
                                             data=payload, method="POST")
                with urllib.request.urlopen(req, timeout=5) as resp:
                    resp.read()
            except Exception as e:  # noqa: BLE001 — must not fail us
                log.error("Unable to send remote log: %s", e)

        if wait:
            ship()
        else:
            threading.Thread(target=ship, daemon=True,
                             name="remote-log").start()

    def _rewarm(self) -> None:
        """Re-warm after a rebind (reload/promote): the swapped-in
        models may have new device shapes (catalog growth changes the
        compiled [B, n_items] kernels) — re-warm so post-rebind traffic
        doesn't pay cold compiles while /status.json still says warm."""
        if not self.config.warm_start:
            return
        with self._lock:  # pairs with _warm_serving's check+set
            self._warm_gen += 1
            gen = self._warm_gen
            self.warm_done.clear()
        threading.Thread(target=self._warm_serving,
                         args=(gen,), daemon=True,
                         name="serving-rewarm").start()

    def reload(self) -> str:
        """Rebind through the release registry: the PINNED release when
        one is set, else the latest COMPLETED instance (the reference's
        ``MasterActor.receive`` :342-371 semantics). Every reload is a
        recorded release action."""
        from ..workflow import core as wf

        instances = self.ctx.storage.engine_instances()
        pinned = None
        try:
            pinned = self.releases.pinned_instance()
        except Exception as e:  # noqa: BLE001 — registry must never
            log.error(          # make a model unreloadable
                "release registry read failed; reloading latest: %s", e)
        with self._lock:
            serving_instance = self.instance
            engine_params = self.engine_params
        if pinned:
            latest = instances.get(pinned)
            if latest is None or latest.status != STATUS_COMPLETED:
                raise HTTPError(
                    409, f"pinned release {pinned!r} is not a "
                         f"COMPLETED engine instance (unpin or re-pin)")
        else:
            latest = instances.get_latest_completed(
                serving_instance.engine_id,
                serving_instance.engine_version,
                serving_instance.engine_variant)
            if latest is None:
                raise HTTPError(
                    404, "no COMPLETED engine instance to reload")
        models = wf.load_models_for_deploy(self.ctx, self.engine, latest,
                                           engine_params)
        self._bind(engine_params, models, latest)
        self._rewarm()
        try:
            self.releases.record_deploy(
                latest.id, actor="/reload",
                reason=("pinned release" if pinned
                        else "latest COMPLETED instance"))
        except Exception as e:  # noqa: BLE001 — history is best-effort
            log.error("release history write failed on reload: %s", e)
        log.info("reloaded engine instance %s%s", latest.id,
                 " (pinned)" if pinned else "")
        return latest.id


def build_app(server: QueryServer) -> HTTPApp:
    app = HTTPApp("engineserver")
    cfg = server.config

    _auth = make_key_auth(cfg.accesskey)

    def _phase_table() -> dict:
        """p50/p90/p99 per phase + end-to-end, from the live registry."""
        snap = server.metrics.snapshot()
        out = {}
        for key, label in (("pio_query_phase_seconds", "phases"),
                           ("pio_query_latency_seconds", "latency"),
                           ("pio_batch_occupancy", "batchOccupancy"),
                           ("pio_queue_depth", "queueDepth")):
            v = snap.get(key)
            if v:
                out[label] = v
        return out

    def _release_summary() -> dict:
        """Compact release state for /status.json and the status page."""
        rollout = server.rollout
        active = rollout is not None and rollout.active
        state: dict = {}
        try:
            state = server.releases.state()
        except Exception:  # noqa: BLE001 — status must always render
            pass
        return {
            "stable": server.instance.id,
            "pinned": state.get("pinned", ""),
            "candidate": server.candidate_instance_id or "",
            "mode": (("shadow" if rollout.shadow else "canary")
                     if active else ""),
            "fraction": rollout.splitter.fraction if active else 0.0,
        }

    def _pipeline_line() -> str:
        """One status-page line proving (or disproving) pipeline
        overlap: mode, in-flight, device idle fraction, sheds."""
        p = server.pipeline_status()
        if p["mode"] == "off":
            return ""
        parts = [f"serving pipeline: {p['mode']}"]
        ov = p.get("overlap")
        if ov:
            parts.append(f"device idle {ov['deviceIdleFraction'] * 100:.0f}%")
            parts.append(f"overlap {ov['overlapFraction'] * 100:.0f}%")
        if p.get("deadlineExceeded"):
            parts.append(f"deadline sheds {p['deadlineExceeded']}")
        return "<li>" + html.escape(" · ".join(parts)) + "</li>"

    def _stream_line() -> str:
        """One status-page line on the batch+stream blend serving
        right now (ISSUE 10): base instance, fold-in generations,
        staleness."""
        lin = server.stream_lineage()
        parts = [f"model lineage: base {lin['baseInstanceId']}"]
        if lin["incrementalGeneration"]:
            parts.append(f"+{lin['incrementalGeneration']} fold-ins "
                         f"({lin['incrementalRows']} rows)")
        parts.append(f"staleness {lin['stalenessSec']:.1f}s")
        if lin["streaming"]:
            parts.append("stream live")
        return ("<li>" + html.escape(" · ".join(parts))
                + " (<a href='/stream.json'>stream.json</a>)</li>")

    def _slo_line() -> str:
        """One status-page line on the SLO engine: specs watched,
        anything burning, the thinnest remaining budget (ISSUE 15)."""
        s = server.slo_status()
        if not s.get("enabled", False) or not s.get("specs"):
            return ""
        parts = [f"SLOs: {len(s['specs'])} watched"]
        burning = s.get("burning") or []
        if burning:
            parts.append("BURNING: " + ", ".join(burning))
        budgets = [(sp["budgetRemaining"], sp["name"])
                   for sp in s["specs"]
                   if sp.get("budgetRemaining") is not None]
        if budgets:
            worst, name = min(budgets)
            parts.append(f"thinnest budget {worst * 100:.1f}% "
                         f"({name})")
        return ("<li>" + html.escape(" · ".join(parts))
                + " (<a href='/slo.json'>slo.json</a>)</li>")

    def _trace_line() -> str:
        """One status-page line on the flight recorder: retained
        count/ring, live slow threshold, profiler state."""
        if server.tracer is None:
            return ""
        t = server.tracer.status()
        parts = [f"flight recorder: {t['retained']}/"
                 f"{t['ringCapacity']} retained"]
        if t.get("slowThresholdMs") is not None:
            parts.append(f"slow ≥ {t['slowThresholdMs']:.1f}ms")
        if server.profiler.active:
            parts.append("device profile capturing")
        return ("<li>" + html.escape(" · ".join(parts))
                + " (<a href='/trace.json'>trace.json</a>)</li>")

    def _cache_line() -> str:
        if server.cache is None:
            return ""
        tiers = server.cache.stats()["tiers"]
        parts = [f"{name} {t['hitRatio'] * 100:.0f}% of "
                 f"{t['hits'] + t['misses']}"
                 for name, t in tiers.items()]
        return ("<li>cache hit ratio: " + html.escape(", ".join(parts))
                + " (<a href='/cache.json'>cache.json</a>)</li>")

    def _sharding_line() -> str:
        """Suppressed sharding-debt census (ISSUE 14): how many
        pragma-justified sharding findings this build carries, per
        rule — the static pass's audit trail surfaced where an
        operator looks first."""
        sf = server.sharding_findings_status()
        if not sf["suppressed"]:
            return ""
        parts = ", ".join(f"{rule} {n}"
                          for rule, n in sf["byRule"].items())
        return (f"<li>sharding findings suppressed: "
                f"{sf['suppressed']} ({html.escape(parts)})</li>")

    def _mesh_panel() -> str:
        """Per-device lane/HBM occupancy while a mesh is active
        (ISSUE 6); empty in single mode — the page stays what it was."""
        mesh = server.mesh_status()
        if mesh.get("mode", "single") == "single":
            return ""
        hbm_by_dev = {str(e.get("device")): e for e in hbm_stats()}
        parts = [f"<h2>Mesh serving</h2><ul><li>mode: "
                 f"{html.escape(mesh['mode'])}</li>"]
        if mesh.get("meshShape"):
            shape = " × ".join(f"{k}={v}" for k, v
                               in mesh["meshShape"].items())
            parts.append(f"<li>mesh: {html.escape(shape)}</li>")
        if mesh.get("devices"):
            parts.append(f"<li>devices: {mesh['devices']}</li>")
        parts.append("</ul>")
        rows = []
        for lane in mesh.get("lanes", ()):  # replicated fan-out only
            hbm = hbm_by_dev.get(str(lane["deviceId"]), {})
            used = hbm.get("bytesInUse")
            rows.append(
                f"<tr><td>{lane['lane']}</td>"
                f"<td>{html.escape(str(lane['device']))}</td>"
                f"<td>{lane['dispatches']}</td>"
                f"<td>{lane['batchP50Ms'] if lane['batchP50Ms'] is not None else '-'}</td>"
                f"<td>{lane['batchP99Ms'] if lane['batchP99Ms'] is not None else '-'}</td>"
                f"<td>{used // (1 << 20) if used else '-'}</td></tr>")
        if rows:
            parts.append(
                "<table border='1'><tr><th>lane</th><th>device</th>"
                "<th>dispatches</th><th>batch p50 (ms)</th>"
                "<th>batch p99 (ms)</th><th>HBM used (MiB)</th></tr>"
                + "".join(rows) + "</table>")
        return "".join(parts)

    @app.route("GET", "/")
    def index(req: Request) -> Response:
        inst = server.instance
        # percentile latency table (ISSUE 2): the status page shows
        # tails, not just means
        rows = []
        for name, s in sorted(
                server.spans_summary().items()):
            rows.append(
                f"<tr><td>{html.escape(name)}</td><td>{s['count']}</td>"
                f"<td>{s['p50'] * 1000:.3f}</td>"
                f"<td>{s['p90'] * 1000:.3f}</td>"
                f"<td>{s['p99'] * 1000:.3f}</td>"
                f"<td>{s['max_sec'] * 1000:.3f}</td></tr>")
        table = (
            "<h2>Latency percentiles</h2>"
            "<table border='1'><tr><th>series</th><th>count</th>"
            "<th>p50 (ms)</th><th>p90 (ms)</th><th>p99 (ms)</th>"
            "<th>max (ms)</th></tr>" + "".join(rows) + "</table>"
            if rows else "")
        # release panel (ISSUE 3): which release serves, what is
        # canarying/shadowing at what fraction, recent history
        rel = _release_summary()
        rel_rows = [
            f"<li>stable release: {html.escape(rel['stable'])}</li>"]
        if rel["pinned"]:
            rel_rows.append(
                f"<li>pinned: {html.escape(rel['pinned'])}</li>")
        if rel["candidate"]:
            rel_rows.append(
                f"<li>candidate: {html.escape(rel['candidate'])} "
                f"({html.escape(rel['mode'])} at "
                f"{rel['fraction'] * 100:.0f}%)</li>")
        hist_rows = []
        try:
            for ev in server.releases.history(limit=5):
                hist_rows.append(
                    f"<tr><td>{html.escape(ev.time[:19])}</td>"
                    f"<td>{html.escape(ev.action)}</td>"
                    f"<td>{html.escape(ev.instance_id)}</td>"
                    f"<td>{html.escape(ev.actor)}</td>"
                    f"<td>{html.escape(ev.reason)}</td></tr>")
        except Exception:  # noqa: BLE001 — status must always render
            pass
        release_panel = (
            "<h2>Release</h2><ul>" + "".join(rel_rows) + "</ul>"
            + ("<table border='1'><tr><th>time</th><th>action</th>"
               "<th>instance</th><th>actor</th><th>reason</th></tr>"
               + "".join(hist_rows) + "</table>" if hist_rows else "")
            + "<p><a href='/release.json'>release.json</a></p>")
        body = f"""<html><head><title>{html.escape(inst.engine_id)} \
- predictionio_tpu engine server</title></head><body>
<h1>Engine: {html.escape(inst.engine_id)} v{html.escape(inst.engine_version)}</h1>
<ul>
<li>engine instance: {html.escape(inst.id)}</li>
<li>variant: {html.escape(inst.engine_variant)}</li>
<li>started: {server.start_time.isoformat()}</li>
<li>requests served: {server.request_count}</li>
<li>average serving: {server.avg_serving_sec * 1000:.3f} ms</li>
<li>last serving: {server.last_serving_sec * 1000:.3f} ms</li>
<li>compiles since warm: {server.recompile_sentinel.since_armed}</li>
{_sharding_line()}{_pipeline_line()}{_stream_line()}{_cache_line()}{_slo_line()}{_trace_line()}
</ul>{_mesh_panel()}{release_panel}{table}
<p><a href="/metrics">Prometheus metrics</a> ·
<a href="/status.json">status.json</a></p></body></html>"""
        return Response(body=body, content_type="text/html")

    @app.route("GET", "/status.json")
    def status(req: Request) -> Response:
        from ..obs import TransferGuardCounter

        return json_response({
            "engineId": server.instance.engine_id,
            "engineVersion": server.instance.engine_version,
            "engineVariant": server.instance.engine_variant,
            "engineInstanceId": server.instance.id,
            "release": _release_summary(),
            "requestCount": server.request_count,
            "avgServingSec": server.avg_serving_sec,
            "lastServingSec": server.last_serving_sec,
            "servingWarm": server.warm_done.is_set(),
            # True when THIS warm answered every ladder entry from the
            # AOT artifact store (ISSUE 19) — the lifecycle warm gate
            # logs artifact-vs-compile spin-ups off this flag
            "artifactWarm": bool(server._warm_report.get("artifact")),
            "warmReport": server._warm_report,
            "lifecycle": server.lifecycle,
            "transferGuard": cfg.transfer_guard or "off",
            "transferGuardViolations": TransferGuardCounter.total(),
            "recompile": server.recompile_sentinel.snapshot(),
            "pipeline": server.pipeline_status(),
            "slo": server.slo_status(),
            "trace": (server.tracer.status()
                      if server.tracer is not None
                      else {"enabled": False}),
            "lineage": server.stream_lineage(),
            "stream": (server.stream.status()
                       if server.stream is not None
                       else {"running": False}),
            "mesh": server.mesh_status(),
            "degraded": server.degraded_status(),
            # the serving-quant sizing claim is read off these two
            # blocks together: servingKernel says the wire dtype, hbm
            # says the resident bytes it produced (docs/kernels.md)
            "servingKernel": server.serving_kernel_status(),
            "shardingFindings": server.sharding_findings_status(),
            "hbm": hbm_stats(),
            "cache": (server.cache.stats() if server.cache is not None
                      else {"enabled": False}),
            # hot-key telemetry (ISSUE 17): the fleet aggregator
            # merges these per-replica sketches into the fleet top-K
            "hotKeys": (server.hotkeys.snapshot()
                        if server.hotkeys is not None
                        else {"enabled": False}),
            **_phase_table(),
        })

    # -- service-level objectives (ISSUE 15, docs/slo.md) --------------------
    @app.route("GET", "/slo.json")
    def slo_json(req: Request) -> Response:
        """Live SLO state: per-spec burn rates (fast/slow window),
        error-budget remaining, breach/violation accounting — what
        ``ptpu slo status`` prints."""
        return json_response(server.slo_status())

    # -- streaming incremental training (ISSUE 10) ---------------------------
    @app.route("GET", "/stream.json")
    def stream_json(req: Request) -> Response:
        """Streaming-trainer state + model lineage (what ``ptpu stream
        status`` prints)."""
        trainer = server.stream
        if trainer is None:
            return json_response({
                "running": False,
                "lineage": server.stream_lineage(),
                "hint": "POST /stream/start {\"appName\": ...} (or "
                        "deploy with --stream) to attach the "
                        "incremental trainer"})
        return json_response({**trainer.status(),
                              "lineage": server.stream_lineage()})

    @app.route("POST", "/stream/start")
    def stream_start(req: Request) -> Response:
        """Attach the streaming trainer to this live server:
        ``{"appName": ..., "channelName": ..., "intervalMs": ...,
        "maxEvents": ..., "consumer": ..., "driftThreshold": ...,
        "canaryProbes": ...}`` — every field optional when the deploy
        config already names the app."""
        from ..streaming import StreamConfig

        _auth(req)
        try:
            body = req.json() or {}
        except (ValueError, UnicodeDecodeError):
            body = {}
        scfg = StreamConfig(
            app_name=str(body.get("appName")
                         or cfg.stream_app_name
                         or cfg.feedback_app_name or ""),
            channel_name=body.get("channelName") or None,
            consumer=str(body.get("consumer") or cfg.stream_consumer),
            interval_ms=float(body.get("intervalMs",
                                       cfg.stream_interval_ms)),
            max_events=int(body.get("maxEvents",
                                    cfg.stream_max_events)),
            drift_threshold=float(body.get("driftThreshold",
                                           cfg.stream_drift_threshold)),
            canary_probes=int(body.get("canaryProbes",
                                       cfg.stream_canary_probes)))
        try:
            trainer = server.start_stream(scfg)
        except ValueError as e:
            raise HTTPError(400, str(e))
        return json_response({"message": "Streaming trainer started.",
                              "stream": trainer.status()})

    @app.route("POST", "/stream/stop")
    def stream_stop(req: Request) -> Response:
        _auth(req)
        if not server.stop_stream():
            raise HTTPError(409, "no streaming trainer is running")
        return json_response({"message": "Streaming trainer stopped."})

    # -- serving cache operations (ISSUE 4) ----------------------------------
    @app.route("GET", "/cache.json")
    def cache_json(req: Request) -> Response:
        """Per-tier hit/miss/eviction/invalidation stats (what
        ``ptpu cache stats`` prints)."""
        if server.cache is None:
            return json_response({"enabled": False,
                                  "hint": "deploy with --cache (or "
                                          "ServerConfig(serving_cache="
                                          "True)) to enable the "
                                          "serving cache hierarchy"})
        return json_response(server.cache.stats())

    @app.route("POST", "/cache/flush")
    def cache_flush(req: Request) -> Response:
        """Operator flush of every tier (``ptpu cache flush``);
        key-guarded like the other control routes."""
        _auth(req)
        if server.cache is None:
            raise HTTPError(409, "serving cache is not enabled")
        return json_response({"message": "Flushed.",
                              "removed": server.cache.flush_all()})

    @app.route("POST", "/queries.json")
    def queries(req: Request) -> Response:
        try:
            query_json = req.json()
        except (ValueError, UnicodeDecodeError) as e:
            raise HTTPError(400, str(e))
        try:
            # progressive delivery: the splitter routes a cohort of
            # queries to the candidate (canary) or mirrors them to it
            # (shadow) while the stable arm keeps serving everyone else
            rollout = server.rollout
            if rollout is not None and rollout.active \
                    and rollout.splitter.routes_candidate(query_json):
                if rollout.shadow:
                    server.mirror_to_candidate(query_json)
                else:
                    try:
                        return json_response(server.serve_candidate(
                            query_json, obs=req.obs))
                    except HTTPError as e:
                        if e.status != 503:
                            raise
                        # the candidate unbound mid-flight (rollback
                        # won the race) — the stable arm serves below
            # the cached stable entry: query cache → singleflight →
            # micro-batcher / per-query pipeline (ISSUE 4)
            result = server.serve(query_json, obs=req.obs)
            if isinstance(result, HTTPError):
                raise result
            return json_response(result)
        except HTTPError as e:
            # batch-wide failures are logged ONCE by the batcher, not by
            # each of the coalesced handler threads
            if e.status >= 500 and not getattr(e, "_remote_logged", False):
                server.remote_log(e.message)
            raise
        except Exception as e:  # noqa: BLE001 — log then surface as 500
            server.remote_log(str(e))
            raise

    @app.route("POST", "/reload")
    def reload(req: Request) -> Response:
        _auth(req)
        instance_id = server.reload()
        return json_response({"message": "Reloading...",
                              "engineInstanceId": instance_id})

    # -- progressive delivery routes (ISSUE 3) ------------------------------
    @app.route("GET", "/release.json")
    def release_json(req: Request) -> Response:
        payload = server.releases.to_json()
        rollout = server.rollout
        payload["serving"] = {
            "stableInstanceId": server.instance.id,
            "candidateInstanceId": server.candidate_instance_id,
        }
        payload["rollout"] = (rollout.status()
                              if rollout is not None else None)
        payload["arms"] = server.release_arms()
        return json_response(payload)

    @app.route("POST", "/release/canary")
    def release_canary(req: Request) -> Response:
        """Start a canary (or shadow) rollout of a COMPLETED instance:
        ``{"instanceId": ..., "fraction": 0.05, "shadow": false,
        "reason": ...}``. The health gate ramps or rolls back from
        here; ``/release.json`` tracks it."""
        from ..rollout.splitter import parse_fraction

        _auth(req)
        try:
            body = req.json() or {}
        except (ValueError, UnicodeDecodeError) as e:
            raise HTTPError(400, str(e))
        instance_id = body.get("instanceId") or ""
        if not instance_id:
            raise HTTPError(400, "instanceId required")
        fraction = None
        if body.get("fraction") is not None:
            try:
                fraction = parse_fraction(body["fraction"])
            except ValueError as e:
                raise HTTPError(400, str(e))
        controller = server.start_canary(
            instance_id, fraction=fraction,
            shadow=bool(body.get("shadow")),
            actor=body.get("actor") or "http",
            reason=body.get("reason") or "")
        return json_response({"message": "Rollout started.",
                              "rollout": controller.status()})

    @app.route("POST", "/release/promote")
    def release_promote(req: Request) -> Response:
        """Force-promote the live candidate to stable (skips the rest
        of the ramp; the operator override for shadow rollouts)."""
        _auth(req)
        try:
            body = req.json() or {}
        except (ValueError, UnicodeDecodeError):
            body = {}
        reason = body.get("reason") or "operator promote"
        rollout = server.rollout
        if rollout is not None and rollout.active:
            rollout.promote(reason)
            return json_response({"message": "Promoted.",
                                  "engineInstanceId":
                                      rollout.instance_id})
        instance_id = server.promote_candidate()  # 409 when none bound
        try:
            server.releases.promote(instance_id, actor="http",
                                    reason=reason)
        except Exception as e:  # noqa: BLE001 — serving already moved
            log.error("release history write failed on promote: %s", e)
        return json_response({"message": "Promoted.",
                              "engineInstanceId": instance_id})

    @app.route("POST", "/release/rollback")
    def release_rollback(req: Request) -> Response:
        """Roll back: abort the live candidate, or — with none bound —
        revert stable to the previous release and rebind it."""
        _auth(req)
        try:
            body = req.json() or {}
        except (ValueError, UnicodeDecodeError):
            body = {}
        reason = body.get("reason") or "operator rollback"
        rollout = server.rollout
        if rollout is not None and rollout.active:
            rollout.rollback(reason)
            return json_response({"message": "Rolled back.",
                                  "engineInstanceId":
                                      server.instance.id})
        try:
            server.releases.rollback(actor="http", reason=reason)
        except ValueError as e:
            raise HTTPError(409, str(e))
        instance_id = server.reload()  # binds the re-pinned previous
        return json_response({"message": "Rolled back.",
                              "engineInstanceId": instance_id})

    @app.route("POST", "/drain")
    def drain(req: Request) -> Response:
        """Flip this replica to lifecycle=draining (ISSUE 18): it
        keeps serving until in-flight/in-deadline work finishes, but
        advertises the state so the router sends nothing new and the
        fleet aggregator retires it from rollups without an up-flap.
        Idempotent; does NOT shut the server down — the lifecycle
        manager (or operator) does that once inflight hits zero."""
        _auth(req)
        server.enter_drain()
        return json_response({"lifecycle": server.lifecycle})

    @app.route("POST", "/stop")
    def stop(req: Request) -> Response:
        _auth(req)
        if server.rollout is not None:
            server.rollout.stop()  # loop only; bindings die with us
        server.stop_stream()  # cursor already persisted; no-op if off
        server.stop_slo()  # evaluator thread only; series stay readable

        def delayed_shutdown():
            # grace period so THIS response flushes before the listener
            # dies (otherwise the client sees a closed connection and
            # `undeploy` reports failure for a stop that worked)
            time.sleep(0.25)
            app_server_ref[0].shutdown()
            # listener down → no new submits; drain the batcher /
            # pipeline workers and the sniffer pump
            server.close()

        threading.Thread(target=delayed_shutdown, daemon=True).start()
        return json_response({"message": "Shutting down..."})

    @app.route("GET", "/plugins.json")
    def plugins_json(req: Request) -> Response:
        return json_response({"plugins": server.plugins.describe()})

    @app.route("GET", r"/plugins/(?P<ptype>[^/]+)/(?P<pname>[^/]+)"
                      r"(?P<rest>(/[^/]+)*)")
    def plugin_rest(req: Request) -> Response:
        """Per-plugin REST surface (``CreateServer.scala:684-689``):
        ``/plugins/<outputblockers|outputsniffers>/<name>/<args…>`` calls
        the plugin's ``handle_rest`` with the remaining segments.
        Key-guarded like the other control routes (plugins may expose
        internal state)."""
        from .plugins import resolve_plugin

        _auth(req)
        plugin, args = resolve_plugin(
            {"outputblockers": server.plugins.output_blockers,
             "outputsniffers": server.plugins.output_sniffers},
            req.path_params["ptype"], req.path_params["pname"],
            req.path_params["rest"])
        return json_response(plugin.handle_rest(args))

    # -- on-demand device profiling (ISSUE 12, docs/tracing.md) -------------
    @app.route("POST", "/profile")
    def profile_start(req: Request) -> Response:
        """Capture a ``jax.profiler`` device trace for a bounded window
        into the served artifact dir: ``{"durationMs": 1000}``.
        Key-guarded like every control route — profiles expose
        internals and cost real overhead while running."""
        _auth(req)
        try:
            body = req.json() or {}
        except (ValueError, UnicodeDecodeError):
            body = {}
        try:
            info = server.profiler.start(
                float(body.get("durationMs", 1000.0)))
        except ValueError as e:
            raise HTTPError(400, str(e))
        except RuntimeError as e:
            raise HTTPError(409, str(e))
        return json_response({
            "message": "Profiling.", **info,
            "hint": "poll GET /profile.json; load the artifact dir "
                    "with TensorBoard's profile plugin or "
                    "ui.perfetto.dev"}, 202)

    @app.route("GET", "/profile.json")
    def profile_json(req: Request) -> Response:
        """Capture status + served artifacts + the per-executable
        compile-time table (what ``pio_compiles_since_warm`` counts,
        itemized)."""
        return json_response({
            **server.profiler.status(),
            "compileTable": server.recompile_sentinel.compile_table(),
        })

    # /metrics + request instrumentation through the server's own
    # registry (the engine server keeps its bespoke /status.json above);
    # the tracer mount adds traceparent propagation + GET /trace.json
    mount_metrics(app, server.metrics, server_name="engineserver",
                  tracer=(server.tracer if server.tracer is not None
                          else False))
    app.access_log_sample = cfg.access_log_sample

    def _respond_phase(req: Request) -> None:
        # the last hole of a staged query's residence: the handler
        # woke -> handle() returned (response built, route metrics,
        # trace finish, access log)
        st = req.stamps
        if st.t_wake is not None:
            server._phase("respond").observe(st.t_return - st.t_wake)

    app.on_sent = _respond_phase

    app_server_ref: List[AppServer] = []
    app._server_ref = app_server_ref  # type: ignore[attr-defined]
    return app


class _Submit:
    """One caller's queue entry: query + completion slot + timing. The
    caller blocks on ``done``; whichever stage finishes (or sheds) the
    entry writes ``slot[0]`` and sets the event. ``abandoned`` flips
    when the submitter's deadline expired — later stages skip the
    corpse instead of doing device work nobody will read."""

    __slots__ = ("query_json", "done", "slot", "t_enq", "t_done",
                 "batch", "own", "deadline", "obs", "abandoned")

    def __init__(self, query_json: Any, obs: Optional[dict],
                 deadline_sec: float):
        self.query_json = query_json
        self.done = threading.Event()
        self.slot: List[Any] = [None]
        self.t_enq = time.monotonic()
        #: stamped by the staged pipeline where it completes the entry,
        #: with the timeline of the batch that carried it and the
        #: query's own ``(start, serialized, fed back)`` inside its
        #: ``finish``
        self.t_done: Optional[float] = None
        self.batch: Optional["BatchTimeline"] = None
        self.own: Optional[tuple] = None
        self.deadline = (self.t_enq + deadline_sec) if deadline_sec > 0 \
            else None
        self.obs = obs
        self.abandoned = False


#: close sentinel for the batcher worker queues: each worker consumes
#: exactly one and exits; ``_form_batch`` re-queues any it pulls on a
#: sibling's behalf (see ``StagedPipeline.close``)
_CLOSE = object()


def _deadline_submit(batcher, server: QueryServer, query_json: Any,
                     obs: Optional[dict]) -> Any:
    """Shared submit with the per-query deadline (ISSUE 9 satellite):
    enqueue, wait at most the deadline, and on expiry shed — count it,
    mark the entry abandoned so pickup skips it, and return a 503
    instead of hanging the HTTP worker on a wedged dispatch forever."""
    e = _Submit(query_json, obs, batcher.deadline_sec)
    batcher._q.put(e)
    if e.deadline is None:
        e.done.wait()
        server._stamp_wake(e, obs)
        return e.slot[0]
    if e.done.wait(timeout=batcher.deadline_sec):
        server._stamp_wake(e, obs)
        return e.slot[0]
    e.abandoned = True
    server._deadline_exceeded.inc()
    server._query_errors.labels(status="503").inc()
    ms = batcher.deadline_sec * 1000.0
    return HTTPError(
        503, f"query shed: not served within the {ms:.0f}ms queue "
             f"deadline (server saturated or dispatch wedged)")


def _form_batch(q, first: _Submit, max_batch: int,
                window: float) -> List[_Submit]:
    """Greedy ADAPTIVE batch formation: while a dispatch is in flight,
    arrivals pile up and the next batch takes everything queued (up to
    ``max_batch``) with no timed wait — batch size self-tunes to
    arrival rate × service time. The ``window`` wait applies only when
    the queue held a single query, giving truly concurrent arrivals
    one chance to coalesce.
    (The round-4 batcher waited the window from EVERY first arrival —
    under 8-thread load the backlog grew unboundedly and p99 hit 11.4s;
    greedy draining is the fix.) Entries whose submitter already gave
    up (deadline expired → ``abandoned``) are completed as shed corpses
    and never join the batch."""
    import queue

    batch: List[_Submit] = []

    def admit(e: _Submit) -> None:
        if e.abandoned or (e.deadline is not None
                           and time.monotonic() > e.deadline):
            # the submitter timed out and already returned (and
            # counted) its 503 — complete the corpse so nothing
            # downstream spends device time on it
            e.slot[0] = HTTPError(503, "query deadline exceeded "
                                       "while queued")
            e.done.set()
            return
        batch.append(e)

    admit(first)
    waited = False
    while len(batch) < max_batch:
        try:
            nxt = q.get_nowait()
        except queue.Empty:
            if waited or len(batch) > 1 or window <= 0:
                break
            # a lone query waits the window once: either a concurrent
            # burst lands (batch grows, greedy loop resumes) or it
            # serves solo with bounded latency
            waited = True
            try:
                nxt = q.get(timeout=window)
            except queue.Empty:
                break
        if nxt is _CLOSE:
            # a close sentinel meant for a sibling assemble worker —
            # put it back for that thread and stop batching
            q.put(nxt)
            break
        admit(nxt)
    return batch


#: process-wide batch sequence number: names a batch on the profiler's
#: host plane (``pio:<stage>`` annotations) and in the flight recorder
_batch_seq = itertools.count(1)


class BatchTimeline(NamedTuple):
    """A finished batch's stamps as its queries carry them away: the
    batch's number and size, and ``(phase, start, end)`` of its eight
    consecutive phases from ``t_pick`` to ``t_done``. With a query's
    own ``queue_wait`` before them they cover its whole stay in the
    pipeline. Floats only: a query's record holds no reference back
    into the batch."""

    seq: int
    n: int
    lane: Optional[int]
    instance_id: str
    spans: tuple


def _staged_spans(tr, timeline: BatchTimeline, t_enter: Optional[float],
                  t_enq: float, own: Optional[tuple],
                  t_wake: float) -> None:
    """A staged query's stamps as the rows of its RETAINED trace
    (ISSUE 12, ISSUE 24; deferred: :meth:`Trace.defer`): ``admit``,
    then a ``batch`` parent (pickup → finish) with ``queue_wait`` from
    the query's own enqueue and the batch's eight consecutive phases as
    children — the hand-off waits between the stage threads are spans
    (``dispatch_q``, ``readback_q``), and the query's own
    ``readback``/``feedback`` (``own``) sit inside ``finish`` where
    they ran — then ``wake``. Nothing is reconstructed from
    durations."""
    spans = timeline.spans
    t_pick, t_done = spans[0][1], spans[-1][2]
    tr.set_attr("engineInstanceId", timeline.instance_id)
    tr.set_attr("arm", ARM_STABLE)
    tr.set_attr("pipeline", "staged")
    lane = {} if timeline.lane is None else {"lane": timeline.lane}
    tr.attrs.update(lane)
    if t_enter is not None:
        tr.add_span("admit", t_enter, t_enq)
    pid = tr.add_span("batch", t_pick, t_done, batch=timeline.seq,
                      batchSize=timeline.n, **lane).span_id
    tr.add_span("queue_wait", t_enq, t_pick, parent_id=pid)
    for name, t0, t1 in spans:
        tr.add_span(name, t0, t1, parent_id=pid)
    if own is not None:
        tr0, tr1, tf = own
        tr.add_span("readback", tr0, tr1, parent_id=pid)
        if tf > tr1:
            tr.add_span("feedback", tr1, tf, parent_id=pid)
    tr.add_span("wake", t_done, t_wake)


class _AssembledBatch:
    """A batch between pipeline stages: the parse/supplement output
    plus the binding SNAPSHOT it was assembled against. Every stage
    uses the carried snapshot — a reload/promote mid-flight serves
    either the old or the new binding in full, never a mix.

    It is also the batch's STAMP RECORD (ISSUE 24,
    docs/serving-pipeline.md): nine ``time.monotonic`` stamps taken
    where the work happens, each stage's end being the next one's
    start. Every per-batch series, the tracker's transitions and the
    flight recorder's spans are read off this record; no stage reads
    the clock a second time for the same instant.

    ``t_pick`` (the batch is formed) → ``t_parsed`` → ``t_assembled``
    (supplemented; handed to ``_dispatch_q``) → ``t_dispatch_pick``
    (taken off it) → ``t_enqueued`` (``dispatch_batch`` returned: the
    executable is queued on the device) → ``t_readback_pick`` (taken
    off ``_readback_q``) → ``t_ready`` (the resolvers returned) →
    ``t_served`` → ``t_done`` (serialized, recorded; callers wake)."""

    __slots__ = ("entries", "queries", "out", "live", "supplemented",
                 "algorithms", "models", "lane_models", "serving",
                 "instance_id", "pending", "lane", "seq",
                 "t_pick", "t_parsed", "t_assembled", "t_dispatch_pick",
                 "t_enqueued", "t_readback_pick", "t_ready", "t_served",
                 "t_done")

    def __init__(self, entries, queries, out, live, supplemented,
                 algorithms, models, lane_models, serving, instance_id,
                 seq, t_pick, t_parsed):
        self.entries = entries
        self.queries = queries
        self.out = out
        self.live = live
        self.supplemented = supplemented
        self.algorithms = algorithms
        self.models = models
        self.lane_models = lane_models
        self.serving = serving
        self.instance_id = instance_id
        self.pending = None
        self.lane: Optional[int] = None
        self.seq = seq
        self.t_pick = t_pick
        self.t_parsed = t_parsed
        self.t_assembled = self.t_dispatch_pick = self.t_enqueued = None
        self.t_readback_pick = self.t_ready = self.t_served = None
        self.t_done: Optional[float] = None

    def timeline(self) -> BatchTimeline:
        return BatchTimeline(self.seq, len(self.entries), self.lane,
                             self.instance_id, (
            ("assemble", self.t_pick, self.t_parsed),
            ("supplement", self.t_parsed, self.t_assembled),
            ("dispatch_q", self.t_assembled, self.t_dispatch_pick),
            ("dispatch", self.t_dispatch_pick, self.t_enqueued),
            ("readback_q", self.t_enqueued, self.t_readback_pick),
            ("device_wait", self.t_readback_pick, self.t_ready),
            ("serve", self.t_ready, self.t_served),
            ("finish", self.t_served, self.t_done)))


class StagedPipeline:
    """Continuous-batching serving pipeline (ISSUE 9,
    docs/serving-pipeline.md): the one batcher, on the hottest path in
    the repo.

    Three stages with bounded hand-off queues:

    - **assemble** (host pool, ``assemble_workers`` threads): greedy
      adaptive batch formation (``_form_batch``), JSON→query parse —
      per-query 400s complete IMMEDIATELY, a malformed query never
      waits on a device round trip — and supplement
      (``supplement_batch``: nothing for a serving that inherits
      ``Serving.supplement``, concurrent on the shared pool for one
      that overrides it). All of it runs while the device chews on
      earlier batches.
    - **dispatch** (one thread per lane): takes the next assembled
      batch and ENQUEUES its device executables via
      ``workflow.batch_predict.dispatch_batch``. JAX async dispatch
      returns as soon as the work is queued, so batch k+1 launches
      before batch k's results exist — the device never waits for
      host work. In replicated fan-out each dispatcher owns its lane's
      device; in sharded mode the single dispatcher serializes the
      mesh launches exactly as ``_mesh_dispatch_lock`` requires.
    - **readback** (host pool, ``readback_workers`` threads): blocks on
      the device arrays (``PendingBatch.wait``), serves, serializes,
      records feedback and metrics, wakes the callers
      (``QueryServer._finish_pipeline_batch``).

    Backpressure: the dispatch and readback queues are bounded at
    ``depth`` entries per lane. When the device (or readback) falls
    behind, assemble blocks on the put, arrivals pool in the submit
    queue, and the per-query deadline sheds them with 503 —
    queueing collapse degrades into fast, counted rejections instead
    of unbounded latency.
    """

    def __init__(self, server: QueryServer, window_ms: float = 2.0,
                 max_batch: int = 128, lanes: int = 1,
                 assemble_workers: int = 2, readback_workers: int = 2,
                 depth: int = 4, deadline_ms: float = 0.0,
                 dispatch_workers: int = 1):
        import queue

        self.server = server
        self.window = max(window_ms, 0.0) / 1000.0
        self.max_batch = max(max_batch, 1)
        self.lanes = max(lanes, 1)
        self.deadline_sec = max(deadline_ms, 0.0) / 1000.0
        if depth <= 0:  # auto (ServerConfig.pipeline_depth = 0):
            # one batch on the device and one behind it, on every
            # backend. A batch launched earlier than that only waits on
            # the device behind the others, and every query in it
            # waits too: on the v5e 4 in flight cost 10 ms of the
            # median at 0.8 × the knee and bought no throughput at
            # saturation, where 2 runs fuller, fewer dispatches
            # (PR 26, PERF.md section 6)
            depth = 2
        self.depth = depth
        # ptpu: allow[unbounded-queue] — every entry has an HTTP worker
        # thread blocked on its done-Event, so depth is bounded by the
        # server's connection concurrency; past the queue deadline,
        # _deadline_submit sheds with a counted 503
        self._q: "queue.Queue" = queue.Queue()
        self._dispatch_q: "queue.Queue" = queue.Queue(
            maxsize=depth * self.lanes)
        self._readback_q: "queue.Queue" = queue.Queue(
            maxsize=depth * self.lanes)
        # THE batching-dynamics knob: an assemble worker takes an
        # in-flight slot BEFORE it picks anything up, and the slot
        # frees only when a batch fully resolves. While the pipeline
        # holds `depth` unresolved batches per lane, no one is even
        # reading the submit queue — arrivals pool, and the next
        # pickup drains them greedily into one fat batch. Without
        # this, a fast assemble stage races ahead of the device and
        # shreds the workload into minimum-size batches (measured:
        # mean occupancy 1.7 against 4.8 at the same load — and device
        # efficiency scales with occupancy).
        self._inflight = threading.BoundedSemaphore(depth * self.lanes)
        # bound children: labels() validates and sorts its keywords
        # under a lock on every call
        self._stage = {st: server._pipeline_stage_hist.labels(stage=st)
                       for st in ("assemble", "dispatch", "readback")}
        self._qdepth = {q: server._pipeline_qdepth.labels(queue=q)
                        for q in ("submit", "dispatch", "readback")}
        self._supplemented = {
            way: server._pipeline_supplemented.labels(way=way)
            for way in SUPPLEMENT_WAYS}
        # per-stage rosters so close() can stop the stages in pipeline
        # order (assemble first, readback last)
        self._assemble_threads: List[threading.Thread] = []
        self._dispatch_threads: List[threading.Thread] = []
        self._readback_threads: List[threading.Thread] = []
        for i in range(max(assemble_workers, 1)):
            self._assemble_threads.append(RoleThread(
                target=self._assemble_loop, daemon=True,
                name=f"pipeline-assemble-{i}"))
        if self.lanes > 1:
            # replicated fan-out: ONE dispatcher per lane — a lane's
            # launches stay ordered on its own device
            for lane in range(self.lanes):
                self._dispatch_threads.append(RoleThread(
                    target=self._dispatch_loop, daemon=True,
                    args=(lane,), name=f"pipeline-dispatch-{lane}"))
        else:
            # single binding: several dispatchers enqueue concurrently
            # (JAX async dispatch is thread-safe; sharded-mesh launches
            # serialize on _mesh_dispatch_lock inside the model). On a
            # TPU the device still executes in order; backends whose
            # runtime can overlap independent executions (CPU CI) do.
            for i in range(max(dispatch_workers, 1)):
                self._dispatch_threads.append(RoleThread(
                    target=self._dispatch_loop, daemon=True,
                    args=(None,), name=f"pipeline-dispatch-{i}"))
        for i in range(max(readback_workers, 1)):
            self._readback_threads.append(RoleThread(
                target=self._readback_loop, daemon=True,
                name=f"pipeline-readback-{i}"))
        self._threads: List[threading.Thread] = (
            self._assemble_threads + self._dispatch_threads
            + self._readback_threads)
        for t in self._threads:
            t.start()

    def close(self, timeout: float = 5.0) -> None:
        """Drain and stop the pipeline stage by stage, upstream first:
        assemble workers get their sentinels and join (nothing new
        enters the pipeline), then dispatch, then readback. Joining a
        stage before signalling the next guarantees a sentinel never
        overtakes an in-flight batch — every real batch still resolves
        and wakes its caller before the stage serving it exits.
        Idempotent."""
        deadline = time.monotonic() + timeout
        for q, roster in ((self._q, self._assemble_threads),
                          (self._dispatch_q, self._dispatch_threads),
                          (self._readback_q, self._readback_threads)):
            live = [t for t in roster if t.is_alive()]
            for _ in live:
                q.put(_CLOSE)
            for t in live:
                t.join(timeout=max(0.0, deadline - time.monotonic()))

    def submit(self, query_json: Any, obs: Optional[dict] = None) -> Any:
        return _deadline_submit(self, self.server, query_json, obs)

    # -- stage 1: assemble ---------------------------------------------------
    def _assemble_loop(self) -> None:
        server = self.server
        tracker = server.overlap
        while True:
            batch = None
            with stage_span("wait_submit") as waiting:
                # take an in-flight slot FIRST (see __init__): while
                # the pipeline is full, arrivals pool in the submit
                # queue and the eventual pickup coalesces them —
                # adaptive batching
                self._inflight.acquire()
                try:
                    first = self._q.get()
                    if first is _CLOSE:
                        return  # the finally releases our slot
                    depth = self._q.qsize() + 1
                    server._queue_depth.observe(depth)
                    self._qdepth["submit"].observe(depth)
                    batch = _form_batch(self._q, first, self.max_batch,
                                        self.window)
                finally:
                    if not batch:
                        self._inflight.release()
                if not batch:
                    continue
                seq = next(_batch_seq)
                waiting.set_metadata(batch=seq, n=len(batch))
                t_pick = time.monotonic()
            tracker.step(t_pick, enter="assemble", join="assembling")
            ab = None
            try:
                ab = self._assemble(batch, seq, t_pick)
            except Exception as e:  # noqa: BLE001 — isolate batch
                server.remote_log(str(e))
                err = HTTPError(500, str(e))
                err._remote_logged = True
                for entry in batch:
                    entry.slot[0] = err
                    entry.done.set()
            finally:
                staged = ab is not None and bool(ab.entries)
                t_out = ab.t_assembled if ab is not None \
                    else time.monotonic()
                tracker.step(t_out, exit="assemble", leave="assembling",
                             join="staged" if staged else None)
                self._stage["assemble"].observe(t_out - t_pick)
                if staged:
                    # the slot rides with the batch; the readback
                    # stage releases it after resolve
                    self._dispatch_q.put(ab)
                else:
                    self._inflight.release()

    def _assemble(self, batch: List[_Submit], seq: int = 0,
                  t_pick: Optional[float] = None) -> _AssembledBatch:
        """Parse and supplement a formed batch against ONE snapshot of
        the binding. The loop passes the batch's number and the
        ``t_pick`` it stamped; alone (tests) the pickup is now."""
        server = self.server
        if t_pick is None:
            t_pick = time.monotonic()
        with stage_span("assemble", batch=seq, n=len(batch)):
            with server._lock:
                algorithms = server.algorithms
                models = server.models
                lane_models = list(server.lane_models)
                serving = server.serving
                instance_id = server.instance.id
            qwait = server._phase("queue_wait")
            for e in batch:
                wait = t_pick - e.t_enq
                qwait.observe(wait)
                if e.obs is not None:
                    e.obs["queueWaitMs"] = round(wait * 1000, 3)
            query_cls = algorithms[0].query_class
            entries: List[_Submit] = []
            queries: List[Any] = []
            for e in batch:
                try:
                    queries.append(from_jsonable(query_cls, e.query_json))
                    entries.append(e)
                except (TypeError, ValueError) as err:
                    # a malformed query completes HERE: its 400 never
                    # rides the batch through the device
                    server._query_errors.labels(status="400").inc()
                    e.t_done = time.monotonic()
                    server._latency_hist.observe(e.t_done - e.t_enq)
                    e.slot[0] = HTTPError(400, str(err))
                    e.done.set()
            t_parsed = time.monotonic()
        with stage_span("supplement", batch=seq, n=len(entries)):
            out: List[Any] = [None] * len(entries)
            live: List[int] = []
            supplemented: List[Any] = []
            if entries:
                # the guard is entered only around a supplement that
                # runs: an inherited identity is a list copy
                supplemented, live, way = supplement_batch(
                    serving, queries, out, guard=server._transfer_guard)
                self._supplemented[way].inc()
            ab = _AssembledBatch(
                entries=entries, queries=queries, out=out, live=live,
                supplemented=supplemented, algorithms=algorithms,
                models=models, lane_models=lane_models, serving=serving,
                instance_id=instance_id, seq=seq, t_pick=t_pick,
                t_parsed=t_parsed)
            ab.t_assembled = time.monotonic()
        return ab

    # -- stage 2: dispatch ---------------------------------------------------
    def _dispatch_loop(self, lane: Optional[int] = None) -> None:
        server = self.server
        tracker = server.overlap
        while True:
            with stage_span("wait_dispatch_q") as waiting:
                ab = self._dispatch_q.get()
                if ab is _CLOSE:
                    return
                n = len(ab.entries)
                waiting.set_metadata(batch=ab.seq, n=n)
                ab.t_dispatch_pick = time.monotonic()
            # the ``device`` track opens BEFORE the launch (as it always
            # has: what pio_pipeline_device_idle_fraction means); the
            # starvation clock's ``enqueued`` waits for t_enqueued
            in_flight_before = tracker.step(
                ab.t_dispatch_pick, enter="device", leave="staged",
                join="launching")
            with stage_span("dispatch", batch=ab.seq, n=n):
                self._qdepth["dispatch"].observe(
                    self._dispatch_q.qsize() + 1)
                if lane is not None and ab.lane_models:
                    # lane supervision (ISSUE 11): a dead lane's batches
                    # redistribute across survivors at pickup, and a
                    # dispatch failure fails over to the other lanes
                    # before it is allowed to fail the batch — during
                    # detection no caller sees an error as long as one
                    # lane still serves
                    attempts = server.lane_attempt_order(lane)
                    ab.lane = attempts[0]
                    models = ab.lane_models[ab.lane]
                    server._lane_depth.labels(
                        lane=str(ab.lane)).observe(
                        self._dispatch_q.qsize() + 1)
                else:
                    attempts = [None]
                    models = ab.models
                # fault attribution (ISSUE 12): an injection delivered
                # on this dispatch thread flags exactly this batch's
                # traces
                batch_traces = [server._trace_of(e.obs)
                                for e in ab.entries]
                for n_try, eff in enumerate(attempts):
                    if eff is not None:
                        ab.lane = eff
                        models = ab.lane_models[eff]
                    try:
                        with activate_traces(batch_traces):
                            if eff is not None:
                                fire(F_LANE, lane=str(eff))
                            fire(F_DISPATCH)
                            with server._transfer_guard():
                                resolvers = dispatch_batch(
                                    ab.algorithms, models,
                                    ab.supplemented) if ab.live else []
                        ab.pending = PendingBatch(
                            ab.queries, ab.serving, ab.out, ab.live,
                            resolvers)
                        if eff is not None:
                            server._lane_ok(eff)
                        break
                    except Exception as e:  # noqa: BLE001 — one
                        if eff is not None:  # dispatch, count + maybe
                            server._lane_error(eff, e)  # fail over
                        if n_try + 1 < len(attempts):
                            continue
                        for i in ab.live:   # whole batch, no lane left
                            ab.out[i] = e
                        ab.pending = PendingBatch(
                            ab.queries, ab.serving, ab.out, [], [])
                ab.t_enqueued = time.monotonic()
            tracker.step(ab.t_enqueued, leave="launching",
                         join="enqueued")
            if in_flight_before > 0:
                # launched while an earlier batch was still on the
                # device: the continuous-batching overlap, counted
                server._pipeline_overlapped.inc()
            self._stage["dispatch"].observe(
                ab.t_enqueued - ab.t_dispatch_pick)
            self._readback_q.put(ab)

    # -- stage 3: readback ---------------------------------------------------
    def _readback_loop(self) -> None:
        server = self.server
        tracker = server.overlap
        # ``pending``, ``fetched`` and ``results`` are this LOOP's
        # locals on purpose: a finished batch's resolvers (they hold
        # its device arrays) stay alive until this thread's next batch
        # rebinds them, just before it blocks on the device, where
        # nobody waits for this thread. Freeing a device array gives up
        # the interpreter lock; with the body in a method of its own
        # the arrays died with ``ab``, between a hand-off queue's
        # ``get`` and the pick stamp of whichever stage thread let go
        # of it last: +2.1 ms ``readback_q``, +0.8 ms ``dispatch_q`` a
        # batch, 3 % of the saturated rate (PERF.md finding 29.4)
        while True:
            with stage_span("wait_readback_q") as waiting:
                ab = self._readback_q.get()
                if ab is _CLOSE:
                    return
                n = len(ab.entries)
                waiting.set_metadata(batch=ab.seq, n=n)
                ab.t_readback_pick = time.monotonic()
            self._qdepth["readback"].observe(
                self._readback_q.qsize() + 1)
            pending = ab.pending
            try:
                with stage_span("device_wait", batch=ab.seq, n=n):
                    fetched = pending.wait()
                    ab.t_ready = time.monotonic()
                tracker.step(ab.t_ready, leave="enqueued")
                with stage_span("serve", batch=ab.seq, n=n):
                    results = pending.serve(fetched)
            except Exception as e:  # noqa: BLE001 — resolve isolates
                results = [e] * n   # internally; belt + braces for
                if ab.t_ready is None:  # the rest
                    ab.t_ready = time.monotonic()
                    tracker.step(ab.t_ready, leave="enqueued")
            finally:
                ab.t_served = time.monotonic()
                tracker.step(ab.t_served, exit="device",
                             enter="readback")
                # the batch is off the device: free its in-flight slot
                # so assemble picks up the pooled backlog while WE are
                # still serializing results (that is the overlap)
                self._inflight.release()
            try:
                with stage_span("finish", batch=ab.seq, n=n):
                    server._finish_pipeline_batch(ab, results)
            except Exception as e:  # noqa: BLE001 — isolate to batch
                server.remote_log(str(e))
                err = HTTPError(500, str(e))
                err._remote_logged = True
                for entry in ab.entries:
                    if not entry.done.is_set():
                        entry.slot[0] = err
                        entry.done.set()
            finally:
                if ab.t_done is None:  # the finish never got there
                    ab.t_done = time.monotonic()
                tracker.step(ab.t_done, exit="readback")
                self._stage["readback"].observe(
                    ab.t_done - ab.t_readback_pick)


def create_engine_server(server: QueryServer, host: str = "0.0.0.0",
                         port: int = 8000, ssl_context=None) -> AppServer:
    """Bind the engine server (reference default port 8000,
    ``CreateServer.scala:78``)."""
    app = build_app(server)
    srv = AppServer(app, host, port, ssl_context=ssl_context)
    app._server_ref.append(srv)  # type: ignore[attr-defined]
    srv.query_server = server  # type: ignore[attr-defined]

    def _stop_on_warm_failure() -> None:
        # a failed warm-up fails the deploy: stop listening (off the
        # warm thread — shutdown() blocks until serve_forever exits)
        # and release the workers; `ptpu deploy` then exits non-zero
        def _stop():
            srv.shutdown()
            server.close()

        threading.Thread(target=_stop, daemon=True,
                         name="warm-failure-stop").start()

    server.on_warm_failure = _stop_on_warm_failure
    if server.warm_error is not None:  # failed before the hook existed
        _stop_on_warm_failure()
    return srv


def deploy(ctx: Context, engine: Engine, engine_params: EngineParams,
           engine_id: str = "default", engine_version: str = "1",
           engine_variant: str = "engine.json",
           config: Optional[ServerConfig] = None,
           host: str = "0.0.0.0", port: int = 8000,
           ssl_context=None) -> AppServer:
    """The ``pio deploy`` flow (``commands/Engine.scala:207`` →
    ``CreateServer``), through the release registry: bind the PINNED
    release when one is set, else the latest COMPLETED instance, and
    record the deploy so every model that reaches traffic has a
    recorded, reversible release."""
    from ..workflow import core as wf

    releases = ReleaseRegistry(ctx.storage, engine_id, engine_version,
                               engine_variant)
    pinned = None
    try:
        pinned = releases.pinned_instance()
    except Exception as e:  # noqa: BLE001 — registry must never make a
        log.error(          # model undeployable
            "release registry read failed; deploying latest: %s", e)
    if pinned:
        instance = ctx.storage.engine_instances().get(pinned)
        if instance is None or instance.status != STATUS_COMPLETED:
            raise RuntimeError(
                f"Pinned release {pinned!r} is not a COMPLETED engine "
                f"instance; `ptpu release pin --clear` or re-pin.")
    else:
        instance = ctx.storage.engine_instances().get_latest_completed(
            engine_id, engine_version, engine_variant)
        if instance is None:
            raise RuntimeError(
                f"No COMPLETED engine instance for {engine_id} "
                f"{engine_version} {engine_variant}; run train first.")
    models = wf.load_models_for_deploy(ctx, engine, instance, engine_params)
    server = QueryServer(ctx, engine, engine_params, models, instance, config)
    try:
        releases.record_deploy(
            instance.id, actor="pio deploy",
            reason=("pinned release" if pinned
                    else "latest COMPLETED instance"))
    except Exception as e:  # noqa: BLE001 — history is best-effort
        log.error("release history write failed on deploy: %s", e)
    return create_engine_server(server, host, port, ssl_context=ssl_context)


def build_artifacts(ctx: Context, engine: Engine,
                    engine_params: EngineParams, artifact_dir: str,
                    engine_id: str = "default",
                    engine_version: str = "1",
                    engine_variant: str = "engine.json",
                    config: Optional[ServerConfig] = None) -> dict:
    """The ``ptpu build --aot`` flow (ISSUE 19, docs/cold-start.md):
    bind the latest COMPLETED instance exactly as deploy would —
    same quantize/prepare/placement — then drive the serving warm
    ladder with AOT capture active, so every executable deploy will
    need lands serialized in ``artifact_dir`` under the store key a
    matching deploy derives. Deploys that pass the same dir warm by
    loading instead of compiling.

    ``config`` must match the eventual deploy on the key-bearing
    serving knobs (mode/quant/batching/max_batch); observability
    side-cars are forced off here — they never affect the artifacts.
    """
    from dataclasses import replace

    from .. import aot
    from ..workflow import core as wf

    config = replace(config or ServerConfig(),
                     warm_start=False, streaming=False, feedback=False,
                     tracing=False, slo_interval_ms=0.0, hot_keys_k=0,
                     faults=None, artifact_dir=None)
    instance = ctx.storage.engine_instances().get_latest_completed(
        engine_id, engine_version, engine_variant)
    if instance is None:
        raise RuntimeError(
            f"No COMPLETED engine instance for {engine_id} "
            f"{engine_version} {engine_variant}; run train first.")
    models = wf.load_models_for_deploy(ctx, engine, instance,
                                       engine_params)
    server = QueryServer(ctx, engine, engine_params, models, instance,
                         config)
    try:
        key = server.artifact_key()
        store = aot.ArtifactStore(artifact_dir, key)
        t0 = time.perf_counter()
        with aot.capture_into(store):
            server._warm_serving(server._warm_gen)
        seconds = time.perf_counter() - t0
        path = store.flush()
        return {"path": path, "entries": len(store), "key": key,
                "seconds": seconds, "instance": instance.id}
    finally:
        server.stop_slo()
