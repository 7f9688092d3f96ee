"""Single source of truth for every published ``bigdl_*`` metric name.

Every metric family the framework mints — counters, gauges, histograms,
across obs/serving/resilience/optim/ops/dataset — is declared HERE,
once, with its kind, label names, a label-cardinality ceiling, a
one-line doc and a **fleet aggregation policy**.  Mint sites reference
these constants instead of string literals, which buys three
guarantees:

* a typo'd or ad-hoc metric name is an ImportError / lint failure, not
  a silently-forked time series;
* ``BIGDL_OBS_STRICT=1`` makes :class:`~bigdl_tpu.obs.metrics.
  MetricsRegistry` reject any ``bigdl_*`` registration that is not
  declared here (or whose kind/labels disagree), and cap each family at
  its declared label cardinality — the runtime enforcement of the same
  contract;
* ``graftlint`` rule RD003/RD005 (``bigdl_tpu/analysis``) statically
  pins every mint site in the tree to this registry, RD004 requires
  each declared name to be rendered by ``obs/report.py`` or documented,
  and RD007 requires each family's fleet aggregation policy to be a
  legal policy/kind pair.

The ``cardinality`` ceiling is the maximum number of label-value
combinations (children) the family may grow: a scrape surface is only
as cheap as its widest family, and an unbounded label (request id,
float bucket, raw exception text) is the classic way a registry eats
the host.  Label-less families have ceiling 1.

The ``policy`` is how a fleet tier (``obs/rollup.py``) folds one family
across hosts into a single merged sample per label set:

* ``sum`` — counters and histogram buckets, always (cumulative bucket
  counts sum exactly, so a fleet quantile derived from merged buckets
  is bit-identical to the flat merge — the rollup correctness
  invariant).  A ``sum`` **gauge** is legal only as an explicit opt-in
  (an additive level like a queue depth or a replica count), marked
  with an inline ``# graftlint: disable=RD007`` — by default a summed
  gauge is the classic fleet-dashboard lie (a "p99" that is really a
  sum of p99s).
* ``max`` / ``min`` — worst-host semantics (ages, norms, depths /
  floors like goodput and SLO ratios).
* ``last`` — whole-fleet constants where any live host's value is the
  fleet value (static per-step byte footprints, plan shapes).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    """Declared shape of one metric family."""

    name: str
    kind: str                    # "counter" | "gauge" | "histogram"
    labels: Tuple[str, ...]      # declared label names, order-free
    cardinality: int             # max label-value combinations
    doc: str                     # one-line purpose (RD004 contract)
    policy: str = "sum"          # fleet aggregation policy (RD007)


#: name -> :class:`MetricSpec` for every declared family
REGISTRY: Dict[str, MetricSpec] = {}

_KINDS = ("counter", "gauge", "histogram")

#: legal fleet aggregation policies (RD007 contract)
POLICIES = ("sum", "max", "min", "last")

#: policies a gauge may declare without a lint opt-in
GAUGE_POLICIES = ("max", "min", "last")


def _m(name: str, kind: str, labels: Tuple[str, ...] = (),
       cardinality: int = 1, doc: str = "",
       policy: Optional[str] = None) -> str:
    if kind not in _KINDS:
        raise ValueError(f"{name}: bad kind {kind!r}")
    if name in REGISTRY:
        raise ValueError(f"duplicate metric declaration {name!r}")
    if labels and cardinality <= 1:
        raise ValueError(f"{name}: labeled metric needs a ceiling > 1")
    if policy is None:
        # counters and histogram buckets merge additively by
        # definition; a gauge has no defensible default
        if kind == "gauge":
            raise ValueError(f"{name}: gauge needs an explicit fleet "
                             f"aggregation policy (one of {POLICIES})")
        policy = "sum"
    if policy not in POLICIES:
        raise ValueError(f"{name}: bad policy {policy!r} "
                         f"(one of {POLICIES})")
    if kind in ("counter", "histogram") and policy != "sum":
        raise ValueError(f"{name}: {kind} families merge by 'sum' "
                         f"only, got policy {policy!r}")
    REGISTRY[name] = MetricSpec(name, kind, tuple(labels),
                                int(cardinality), doc, policy)
    return name


# --------------------------------------------------------------- runtime
STEP_TIME_SECONDS = _m(
    "bigdl_step_time_seconds", "gauge", ("quantile",), 4,
    "Observed train-step completion time percentiles", policy="max")
JIT_COMPILE_COUNT = _m(
    "bigdl_jit_compile_count", "gauge", policy="max",
    doc="Distinct jit compile events (new arg signatures)")
JIT_COMPILE_SECONDS_TOTAL = _m(
    "bigdl_jit_compile_seconds_total", "gauge", policy="max",
    doc="Wall seconds spent blocked on jit trace+compile")
STEP_FLOPS = _m(
    "bigdl_step_flops", "gauge", policy="last",
    doc="HLO cost-analysis FLOPs of one compiled train step")
MFU = _m(
    "bigdl_mfu", "gauge", policy="min",
    doc="Model FLOPs utilization vs the chip's peak")
HOST_RSS_BYTES = _m(
    "bigdl_host_rss_bytes", "gauge", policy="max",
    doc="Driver-process resident set size")
DEVICE_MEMORY_BYTES = _m(
    "bigdl_device_memory_bytes", "gauge", ("stat",), 16,
    "Device 0 memory stats, per allocator stat", policy="max")
HBM_PEAK_BYTES = _m(
    "bigdl_hbm_peak_bytes", "gauge", ("device",), 64,
    "Peak HBM bytes in use, per local device", policy="max")
ENGINE_INITS_TOTAL = _m(
    "bigdl_engine_inits_total", "counter",
    doc="Engine.init calls in this process")

# --------------------------------------------------------------- optim
PHASE_SECONDS = _m(
    "bigdl_phase_seconds", "histogram", ("phase",), 24,
    "Driver phase timers (the reference's optim.Metrics)")
OVERLAP_BUCKETS = _m(
    "bigdl_overlap_buckets", "gauge", policy="last",
    doc="Gradient-exchange buckets in the overlap plan")
OVERLAP_EXPOSED_COMM_FRACTION = _m(
    "bigdl_overlap_exposed_comm_fraction", "gauge", policy="max",
    doc="Exposed (non-overlapped) comm seconds / step seconds")
OVERLAP_EXPOSED_COMM_SECONDS = _m(
    "bigdl_overlap_exposed_comm_seconds", "gauge", policy="max",
    doc="Exposed comm seconds per step after overlap")
RETRY_ATTEMPTS_TOTAL = _m(
    "bigdl_retry_attempts_total", "counter",
    ("classification", "error"), 64,
    "Classified-retry attempts, by failure class and error type")
CHECKPOINT_WRITE_FAILURES_TOTAL = _m(
    "bigdl_checkpoint_write_failures_total", "counter",
    doc="Checkpoint writes that raised (sync or background writer)")
PREEMPTIONS_TOTAL = _m(
    "bigdl_preemptions_total", "counter",
    doc="SIGTERM/SIGINT preemptions handled by the elastic exit path")
NONFINITE_SKIPS_TOTAL = _m(
    "bigdl_nonfinite_skips_total", "counter",
    doc="Weight updates skipped by the non-finite step guard")

# --------------------------------------------------------------- kernels
KERNEL_FALLBACKS_TOTAL = _m(
    "bigdl_kernel_fallbacks_total", "counter", ("site",), 16,
    "Kernel dispatches that fell back to the reference path")
TUNER_CACHE_HITS_TOTAL = _m(
    "bigdl_tuner_cache_hits_total", "counter",
    doc="Tuner decisions served from the cache")
TUNER_CACHE_MISSES_TOTAL = _m(
    "bigdl_tuner_cache_misses_total", "counter",
    doc="Tuner cache misses (fresh searches)")
TUNER_MEASUREMENTS_TOTAL = _m(
    "bigdl_tuner_measurements_total", "counter",
    doc="Wall-clock candidate probes run by the auto-tuner")
TUNER_DECISIONS_TOTAL = _m(
    "bigdl_tuner_decisions_total", "counter", ("site", "impl"), 64,
    "Auto-tuner dispatch decisions, by call site and chosen impl")

# --------------------------------------------------------------- wire
COLLECTIVE_BYTES_TOTAL = _m(
    "bigdl_collective_bytes_total", "counter", ("op", "dtype"), 64,
    "Wire bytes programmed into collectives, from static shapes")
COLLECTIVE_BYTES_PER_STEP = _m(
    "bigdl_collective_bytes_per_step", "gauge", ("op", "dtype"), 64,
    "Static per-train-step wire bytes of the collective footprint",
    policy="last")
COLLECTIVE_WIRE_SAVINGS_RATIO = _m(
    "bigdl_collective_wire_savings_ratio", "gauge", ("path",), 8,
    "Uncompressed exchange bytes over what the wire actually ships",
    policy="min")

# --------------------------------------------------------------- goodput
GOODPUT_RATIO = _m(
    "bigdl_goodput_ratio", "gauge", policy="min",
    doc="Productive step seconds over total accounted wall seconds")
GOODPUT_WINDOW_RATIO = _m(
    "bigdl_goodput_window_ratio", "gauge", policy="min",
    doc="Good share of the last classifier window's wall clock")
BADPUT_SECONDS_TOTAL = _m(
    "bigdl_badput_seconds_total", "counter", ("cause",), 16,
    "Non-productive wall seconds, by cause (goodput ledger)")
BOTTLENECK = _m(
    "bigdl_bottleneck", "gauge", ("class",), 8,
    "One-hot per-window bottleneck classification", policy="max")
REWORK_STEPS_TOTAL = _m(
    "bigdl_rework_steps_total", "counter",
    doc="Steps re-executed after a restart")
STRAGGLER_STEPS_TOTAL = _m(
    "bigdl_straggler_steps_total", "counter", ("host",), 1024,
    "Cross-host straggler detections, by slow host")

# --------------------------------------------------------------- health
GRAD_NORM = _m(
    "bigdl_grad_norm", "gauge", ("layer",), 4096,
    "Per-layer gradient norm (BIGDL_HEALTH_EVERY)", policy="max")
PARAM_NORM = _m(
    "bigdl_param_norm", "gauge", ("layer",), 4096,
    "Per-layer parameter norm", policy="max")
UPDATE_RATIO = _m(
    "bigdl_update_ratio", "gauge", ("layer",), 4096,
    "Per-layer update-to-param norm ratio", policy="max")
GLOBAL_GRAD_NORM = _m(
    "bigdl_global_grad_norm", "histogram",
    doc="Global gradient norm distribution")
NONFINITE_LAYERS_TOTAL = _m(
    "bigdl_nonfinite_layers_total", "counter", ("layer",), 4096,
    "Layers whose grads went NaN/inf, by layer")
NUMERICS_ANOMALIES_TOTAL = _m(
    "bigdl_numerics_anomalies_total", "counter", ("kind",), 8,
    "Loss / grad-norm spikes vs the rolling median")

# --------------------------------------------------------------- alerts
ALERTS_TOTAL = _m(
    "bigdl_alerts_total", "counter", ("rule", "severity"), 64,
    "Alert firing transitions, by rule and severity")
ALERTS_RESOLVED_TOTAL = _m(
    "bigdl_alerts_resolved_total", "counter", ("rule",), 64,
    "Alert resolved transitions, by rule")
ALERT_ACTIVE = _m(
    "bigdl_alert_active", "gauge", ("rule",), 64,
    "1 while the rule is firing, 0 otherwise", policy="max")
ALERT_SINK_FAILURES_TOTAL = _m(
    "bigdl_alert_sink_failures_total", "counter",
    doc="Alert sink deliveries that failed after retry")

# --------------------------------------------------------------- resilience
HEARTBEAT_AGE_SECONDS = _m(
    "bigdl_heartbeat_age_seconds", "gauge", ("host",), 1024,
    "Seconds since each peer's last heartbeat touch", policy="max")
PEER_LOST_TOTAL = _m(
    "bigdl_peer_lost_total", "counter",
    doc="PeerLostError raised for silent heartbeat peers")
RESUMES_TOTAL = _m(
    "bigdl_resumes_total", "counter", ("resize",), 32,
    "Checkpoint resumes, by world-size transition (e.g. 2to1)")
SUPERVISOR_RESTARTS_TOTAL = _m(
    "bigdl_supervisor_restarts_total", "counter", ("kind",), 8,
    "Supervisor child restarts, by failure kind")
AUTOSCALE_DECISIONS_TOTAL = _m(
    "bigdl_autoscale_decisions_total", "counter",
    ("direction", "reason"), 32,
    "Autoscale policy decisions, by direction and firing rule")

# --------------------------------------------------------------- fleet
FLEET_SCRAPE_SECONDS = _m(
    "bigdl_fleet_scrape_seconds", "gauge", policy="max",
    doc="Wall seconds of the last full fleet peer-scrape cycle "
        "(bounded-pool concurrent scrape, FleetAggregator.scrape_peers)")
FLEET_SCRAPE_LATENCY_SECONDS = _m(
    "bigdl_fleet_scrape_latency_seconds", "gauge", ("host",), 1024,
    "Per-host wall seconds of the last scrape round trip "
    "(/healthz + /metrics, including the retry when one was spent)",
    policy="max")
FLEET_HOST_STALENESS_SECONDS = _m(
    "bigdl_fleet_host_staleness_seconds", "gauge", ("host",), 1024,
    "Per-host |scraper clock - host /healthz clock| skew; hosts past "
    "BIGDL_STALE_AFTER_S are excluded from fleet merges", policy="max")
# additive level across the fleet tiers — an explicit sum-gauge opt-in
FLEET_STALE_HOSTS = _m(  # graftlint: disable=RD007
    "bigdl_fleet_stale_hosts", "gauge", policy="sum",
    doc="Hosts excluded from the last fleet merge as stale "
        "(skewed clock or staleness past BIGDL_STALE_AFTER_S) — "
        "never silently folded into fleet percentiles")
FLEET_SCRAPE_ERRORS_TOTAL = _m(
    "bigdl_fleet_scrape_errors_total", "counter", ("reason",), 8,
    "Failed per-host scrapes by reason (timeout/refused/protocol), "
    "surfaced without failing the round")

# --------------------------------------------------------------- rollup
# tracked-series level sums across rollup tiers — explicit opt-in
ROLLUP_SERIES_TRACKED = _m(  # graftlint: disable=RD007
    "bigdl_rollup_series_tracked", "gauge", policy="sum",
    doc="Distinct (family, label-set) series the rollup tier is "
        "currently carrying in its merged exposition")
ROLLUP_SERIES_DROPPED_TOTAL = _m(
    "bigdl_rollup_series_dropped_total", "counter", ("family",), 128,
    "Series folded into the 'other' bucket by the top-K cardinality "
    "bound, by family — the fleet-p99-looks-wrong triage counter")
ROLLUP_MEMORY_BYTES = _m(
    "bigdl_rollup_memory_bytes", "gauge", policy="max",
    doc="Approximate bytes the rollup tier holds for its merged "
        "series state (self-scrape of the aggregator)")

# --------------------------------------------------------------- retain
RETAIN_POINTS_TOTAL = _m(
    "bigdl_retain_points_total", "counter",
    doc="Samples ingested by the downsampling retention store")
RETAIN_EVICTIONS_TOTAL = _m(
    "bigdl_retain_evictions_total", "counter", ("ring",), 4,
    "Points evicted from a retention ring (raw/10s/1m) at capacity")
RETAIN_SERIES = _m(
    "bigdl_retain_series", "gauge", policy="max",
    doc="Distinct series the retention store currently tracks "
        "(bounded by BIGDL_RETAIN_SERIES)")

# --------------------------------------------------------------- checkpoint
CHECKPOINT_SNAPSHOT_SECONDS = _m(
    "bigdl_checkpoint_snapshot_seconds", "gauge", policy="max",
    doc="Blocking device-to-host snapshot span of the last checkpoint")
CHECKPOINT_WRITE_SECONDS = _m(
    "bigdl_checkpoint_write_seconds", "gauge", policy="max",
    doc="Serialize+fsync span of the last checkpoint write")
CHECKPOINT_WRITES_TOTAL = _m(
    "bigdl_checkpoint_writes_total", "counter",
    doc="Completed checkpoint writes")
CHECKPOINT_VERIFY_FAILURES_TOTAL = _m(
    "bigdl_checkpoint_verify_failures_total", "counter",
    doc="Checkpoint read-back verifications that failed")

# --------------------------------------------------------------- feed
FEED_STAGING_BATCHES_TOTAL = _m(
    "bigdl_feed_staging_batches_total", "counter", labels=("staging",),
    cardinality=2,
    doc="Training batches gathered into a reused or a new host buffer "
        "(staging=reused|new; all reused once the ring is full)")

# --------------------------------------------------------------- streaming
# fleet-wide buffered-records level is additive — explicit opt-in
STREAM_BUFFER_DEPTH = _m(  # graftlint: disable=RD007
    "bigdl_stream_buffer_depth", "gauge", policy="sum",
    doc="Records buffered between the stream producer and the trainer")
STREAM_BACKPRESSURE_WAITS_TOTAL = _m(
    "bigdl_stream_backpressure_waits_total", "counter",
    doc="Producer blocks on a full stream buffer")
STREAM_OFFSET = _m(
    "bigdl_stream_offset", "gauge", policy="min",
    doc="Last source offset handed to the trainer")
STREAM_WATERMARK = _m(
    "bigdl_stream_watermark", "gauge", policy="max",
    doc="Highest source offset the producer has ingested")
STREAM_LAG_RECORDS = _m(
    "bigdl_stream_lag_records", "gauge", policy="max",
    doc="Producer watermark minus trainer offset")
STREAM_RECORDS_TOTAL = _m(
    "bigdl_stream_records_total", "counter",
    doc="Records handed to the trainer, exactly-once audited")

# --------------------------------------------------------------- serving
SERVE_REQUESTS_TOTAL = _m(
    "bigdl_serve_requests_total", "counter", ("engine", "status"), 16,
    "Completed serve requests, by engine and outcome")
REQUEST_LATENCY_SECONDS = _m(
    "bigdl_request_latency_seconds", "histogram", ("engine", "kind"), 16,
    "Request latency by engine and kind (ttft/per_token/e2e)")
SERVE_TOKENS_TOTAL = _m(
    "bigdl_serve_tokens_total", "counter",
    doc="Tokens decoded by the LM engine")
# fleet decode throughput is additive across engines — explicit opt-in
SERVE_TOKENS_PER_SECOND = _m(  # graftlint: disable=RD007
    "bigdl_serve_tokens_per_second", "gauge", policy="sum",
    doc="Rolling decode throughput")
SERVE_BATCH_OCCUPANCY = _m(
    "bigdl_serve_batch_occupancy", "gauge", policy="max",
    doc="Fraction of decode slots / micro-batch rows in use")
# fleet queue pressure is additive across replicas — explicit opt-in
SERVE_QUEUE_DEPTH = _m(  # graftlint: disable=RD007
    "bigdl_serve_queue_depth", "gauge", policy="sum",
    doc="Requests waiting in the bounded admission queue")
SERVE_KV_PAGES_IN_USE = _m(
    "bigdl_serve_kv_pages_in_use", "gauge", policy="max",
    doc="Pages allocated from the paged KV cache pool")
SERVE_ADMISSION_WAITS_TOTAL = _m(
    "bigdl_serve_admission_waits_total", "counter",
    doc="Client submits that blocked on a full request queue")
SERVE_PREEMPTIONS_TOTAL = _m(
    "bigdl_serve_preemptions_total", "counter",
    doc="In-flight sequences evicted to free KV pages")
SERVE_LATENCY_SLO_RATIO = _m(
    "bigdl_serve_latency_slo_ratio", "gauge", policy="min",
    doc="Share of recent requests inside the e2e latency SLO")
SERVE_DECODE_ATTN_MS = _m(
    "bigdl_serve_decode_attn_ms", "gauge", policy="max",
    doc="Mean milliseconds the engine's thread spends in a decode step: "
        "dispatching step k, waiting for step k-1's tokens and reading "
        "them (host work and slack in one sum: the spans serve.dispatch, "
        "serve.wait and serve.read give the parts)")
SERVE_STEPS_AHEAD_TOTAL = _m(
    "bigdl_serve_steps_ahead_total", "counter",
    doc="Decode steps dispatched while the previous step's tokens were "
        "still unread (the pipelined loop engaging)")
SERVE_STEPS_TOTAL = _m(
    "bigdl_serve_steps_total", "counter", ("pick",), 2,
    "Decode steps dispatched, by the arm their pick took: greedy (no "
    "running slot had a temperature above 0: one pass over the logits, "
    "no draw) or sampled")
SERVE_SETTLES_TOTAL = _m(
    "bigdl_serve_settles_total", "counter", ("reason",), 4,
    "Steps in flight read outside the pipelined loop, by reason "
    "(preempt, swap, idle, close)")
SERVE_DECODE_HBM_BYTES_PER_TOKEN = _m(
    "bigdl_serve_decode_hbm_bytes_per_token", "gauge", policy="max",
    doc="Modeled HBM traffic per decoded token")
SERVE_MOE_ASSIGNMENTS_TOTAL = _m(
    "bigdl_serve_moe_assignments_total", "counter", ("kind",), 3,
    "Token-to-expert assignments routed by a served expert model, by "
    "kind: held (computed here), zero (zero-compute experts), absent "
    "(experts on other chips: left out of this chip's share)")
SERVE_MOE_LOAD_MAX_OVER_MEAN = _m(
    "bigdl_serve_moe_load_max_over_mean", "gauge", policy="max",
    doc="Largest load of a held expert over the mean load of the held "
        "experts, in the last step that routed to one (1 = even)")
SERVE_DRAFT_TOKENS_TOTAL = _m(
    "bigdl_serve_draft_tokens_total", "counter", ("outcome",), 2,
    "Drafts verified by the decode steps of a model that drafts its own "
    "next-but-one token, by outcome: accepted (the step yielded two "
    "tokens) or rejected")
SERVE_BLOCK_POSITIONS_TOTAL = _m(
    "bigdl_serve_block_positions_total", "counter", ("outcome",), 2,
    "Masked positions met by the refining passes of a model that "
    "generates by blocks, by outcome: unmasked (the pass made the "
    "position final) or left_masked (a later pass will)")
SERVE_SLOT_STATE_BYTES = _m(
    "bigdl_serve_slot_state_bytes", "gauge", policy="last",
    doc="Bytes of state a decode slot carries beside its pages, over all "
        "layers, under a model that declares one (state_spec): rows of "
        "the slot's previous token, not keys and values")
SERVE_STATE_REBUILDS_TOTAL = _m(
    "bigdl_serve_state_rebuilds_total", "counter",
    doc="Prefills of a preempted request under a model whose slots carry "
        "state: the state was rebuilt from the request's tokens (nothing "
        "is snapshotted at a preemption)")
SERVE_REJECTS_TOTAL = _m(
    "bigdl_serve_rejects_total", "counter",
    doc="Admissions rejected 503 + Retry-After (queue full past the "
        "admission timeout, or the engine is draining)")

# --------------------------------------------------------------- router
ROUTER_REQUESTS_TOTAL = _m(
    "bigdl_router_requests_total", "counter", ("outcome",), 8,
    "Routed requests by final outcome (ok / shed / failed)")
ROUTER_RETRIES_TOTAL = _m(
    "bigdl_router_retries_total", "counter",
    doc="Re-placements after a transient replica failure (each one "
        "spent a retry-budget token)")
ROUTER_SHED_TOTAL = _m(
    "bigdl_router_shed_total", "counter",
    doc="Requests shed 503 + Retry-After on an exhausted retry budget "
        "or no eligible replica")
ROUTER_HANDOFFS_TOTAL = _m(
    "bigdl_router_handoffs_total", "counter",
    doc="Checkpointed decodes replayed exactly-once off a draining "
        "replica")
ROUTER_DRAINS_TOTAL = _m(
    "bigdl_router_drains_total", "counter",
    doc="Replica drain cycles the router completed")
ROUTER_AFFINITY_HITS_TOTAL = _m(
    "bigdl_router_affinity_hits_total", "counter",
    doc="Placements that landed on the session's bound replica (the "
        "multi-turn KV prefix stayed resident)")
# replica counts sum across routers in a multi-router fleet — opt-in
ROUTER_REPLICAS = _m(  # graftlint: disable=RD007
    "bigdl_router_replicas", "gauge", ("state",), 4,
    "Replicas by router-observed state (up / draining / down)",
    policy="sum")
ROUTER_RETRY_BUDGET_TOKENS = _m(
    "bigdl_router_retry_budget_tokens", "gauge", policy="min",
    doc="Tokens left in the router's shared retry-budget bucket")
ROUTER_STALE_EXCLUDED_TOTAL = _m(
    "bigdl_router_stale_excluded_total", "counter",
    doc="Placement snapshots that marked a replica ineligible because "
        "its host clock skew (staleness_s signal) exceeded "
        "BIGDL_STALE_AFTER_S — the skewed-clock half of fleet "
        "staleness, applied to routing")

# --------------------------------------------------------------- rollout
SERVE_WEIGHT_SWAPS_TOTAL = _m(
    "bigdl_serve_weight_swaps_total", "counter", ("version",), 64,
    "Live weight hot-swaps the engine completed, by promoted version "
    "(one device_put + pointer flip between decode steps — slots, "
    "page tables and in-flight decodes survive)")
ROLLOUT_REJECTED_TOTAL = _m(
    "bigdl_rollout_rejected_total", "counter", ("reason",), 8,
    "Published checkpoints the rollout watcher refused before touching "
    "serving state (manifest verify failed: torn / corrupt / checksum "
    "mismatch / missing pair) — counted and event-stamped, never "
    "loaded")
ROLLOUT_CANARY_DIVERGENCE = _m(
    "bigdl_rollout_canary_divergence", "gauge", policy="max",
    doc="Worst token-level divergence of the canary version's pinned-"
        "prompt replay vs the incumbent (fraction of mismatched "
        "tokens; the auto-rollback signal next to SLO burn)")
ROLLOUT_CANARY_STATE = _m(
    "bigdl_rollout_canary_state", "gauge", policy="max",
    doc="CanaryController phase (0 = idle, 1 = canarying, 2 = rolling "
        "back)")
ROLLOUT_ROLLBACKS_TOTAL = _m(
    "bigdl_rollout_rollbacks_total", "counter", ("reason",), 8,
    "Canary auto-rollback episodes, by the signal that fired "
    "(slo_burn / divergence) — hysteresis-gated, so one noisy window "
    "cannot flap promote/rollback")
ROLLOUT_VERSION_MISMATCH_TOTAL = _m(
    "bigdl_rollout_version_mismatch_total", "counter",
    doc="Drain-handoff replays refused because the absorbing replica "
        "serves a different weight version than the checkpoint pinned "
        "— the request re-queues toward a version-exact replica "
        "instead of silently breaking the bit-equal replay contract")

# --------------------------------------------------------------- reqtrace
REQTRACE_SAMPLED_TOTAL = _m(
    "bigdl_reqtrace_sampled_total", "counter", ("reason",), 8,
    "Request traces kept by the tail sampler, by keep reason "
    "(error/retry/preempt/slo/handoff/forced always keep; 'sampled' "
    "is the probabilistic BIGDL_REQTRACE_SAMPLE tail)")
REQTRACE_DROPPED_TOTAL = _m(
    "bigdl_reqtrace_dropped_total", "counter",
    doc="Completed request traces dropped by the tail sampler "
        "(clean requests past the sampling probability)")
REQTRACE_RING_EVICTED_TOTAL = _m(
    "bigdl_reqtrace_ring_evicted_total", "counter",
    doc="Kept request traces evicted from the bounded completed-trace "
        "ring (BIGDL_REQTRACE_RING)")
REQTRACE_ACTIVE_TRACES = _m(
    "bigdl_reqtrace_active_traces", "gauge", policy="max",
    doc="Request traces currently open — begun, not yet through the "
        "tail sampler")

# ---------------------------------------------- profiling / debug bundles
PROF_SAMPLES_TOTAL = _m(
    "bigdl_prof_samples_total", "counter", policy="sum",
    doc="Stack samples the continuous profiler folded into the "
        "collapsed-stack table (BIGDL_PROF_HZ)")
PROF_SKIPPED_TOTAL = _m(
    "bigdl_prof_skipped_total", "counter", policy="sum",
    doc="Profiler samples skipped because the self-overhead ratio "
        "exceeded BIGDL_PROF_BUDGET (the hard overhead cap)")
PROF_OVERHEAD_RATIO = _m(
    "bigdl_prof_overhead_ratio", "gauge", policy="max",
    doc="Profiler self-overhead: cumulative sampling-work seconds / "
        "wall seconds since the profiler started")
PROF_STACKS = _m(
    "bigdl_prof_stacks", "gauge", policy="max",
    doc="Distinct collapsed stacks held in the profiler's bounded "
        "fold table (overflow folds into the 'other' stack)")
STALLS_TOTAL = _m(
    "bigdl_stalls_total", "counter", ("loop", "cause"), 16,
    "Pauses of a minded loop (the stall watch, obs/prof.py: span "
    "boundaries further apart than the loop's limit under a recording "
    "tracer), by loop and cause", policy="sum")
STALLED_SECONDS_TOTAL = _m(
    "bigdl_stalled_seconds_total", "counter", ("loop",), 4,
    "Seconds a minded loop stood still in pauses", policy="sum")
BUNDLE_WRITES_TOTAL = _m(
    "bigdl_bundle_writes_total", "counter", ("trigger",), 6,
    "Debug bundles written, by trigger (alert / supervisor / http / "
    "manual)", policy="sum")
BUNDLE_ERRORS_TOTAL = _m(
    "bigdl_bundle_errors_total", "counter", policy="sum",
    doc="Debug-bundle builds that failed (the trigger path never "
        "propagates — a bundle failure must not kill serving)")
BUNDLE_LAST_WRITE_SECONDS = _m(
    "bigdl_bundle_last_write_seconds", "gauge", policy="max",
    doc="Wall-clock timestamp of the newest debug bundle this host "
        "wrote (0 until the first bundle)")

#: ``bigdl_``-prefixed spellings that are NOT metric families — process
#: names, trace categories, logger names — so the RD003 "every bigdl_*
#: literal must be declared" rule knows they are deliberate.
KNOWN_STRINGS = frozenset({
    "bigdl_tpu",            # tracer process name / root logger name
    "bigdl_tpu_net",        # caffe export net name
    "bigdl_obs_span",       # Chrome trace category
    "bigdl_flight_recorder",  # postmortem bundle stem
})


def spec(name: str) -> MetricSpec:
    """The declared spec for ``name`` (KeyError when undeclared)."""
    return REGISTRY[name]


def is_declared(name: str) -> bool:
    """Is ``name`` a declared family, or a histogram-derived sample
    (``_bucket``/``_sum``/``_count``) of one?"""
    if name in REGISTRY:
        return True
    for suffix in ("_bucket", "_sum", "_count"):
        if name.endswith(suffix):
            base = name[: -len(suffix)]
            s = REGISTRY.get(base)
            if s is not None and s.kind == "histogram":
                return True
    return False


def fleet_policy(name: str) -> Optional[str]:
    """The fleet aggregation policy for a sample name as it appears on
    the wire — histogram-derived ``_bucket``/``_sum``/``_count``
    samples merge by ``sum`` like their family; ``None`` for
    undeclared names (the rollup tier passes those through with
    ``last`` semantics rather than inventing a merge)."""
    s = REGISTRY.get(name)
    if s is not None:
        return s.policy
    for suffix in ("_bucket", "_sum", "_count"):
        if name.endswith(suffix):
            base = REGISTRY.get(name[: -len(suffix)])
            if base is not None and base.kind == "histogram":
                return "sum"
    return None
