"""Unified configuration (VERDICT r2 missing #5; SURVEY.md §5 "Config").

The reference spreads configuration across three tiers — a required
Spark-conf file (⟦dist/conf/spark-bigdl.conf⟧), ``bigdl.*`` JVM system
properties (bigdl.engineType, bigdl.coreNumber, bigdl.check.singleton,
…), and per-app scopt CLIs — with *no unified typed object*.  SURVEY §5
prescribes the rebuild use "one dataclass-based config + absl-style
flags; keep bigdl.* spellings as env aliases only where examples need
them".

This is that object.  One process-global :class:`BigDLConfig`, resolved
once from (highest wins): explicit ``configure(...)`` calls → ``BIGDL_*``
environment variables → dataclass defaults.  Every ``BIGDL_*`` env var
the framework honours is declared here — subsystems read the config
object, not ``os.environ`` — so ``python -c "import bigdl_tpu;
print(bigdl_tpu.config.describe())"`` is the single source of truth.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return default if v is None else int(v)


def _env_opt_int(name: str, default=None):
    v = os.environ.get(name)
    return default if v in (None, "") else int(v)


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return default if v is None else float(v)


def _env_str(name: str, default):
    return os.environ.get(name, default)


#: Bootstrap variables the smoke harnesses export for their child
#: processes (repo path, scratch dir, A/B arm).  They are process
#: plumbing, not framework configuration, so they are deliberately NOT
#: config fields — but they are declared here so graftlint rule RD001
#: can tell a known harness contract from an ad-hoc env spelling.
#: Scripts may read them; library code may not.
HARNESS_ENV = ("BIGDL_REPO", "BIGDL_SMOKE_DIR", "BIGDL_SMOKE_BASELINE")


@dataclasses.dataclass
class ObsConfig:
    """Observability layer switches (``bigdl_tpu/obs``).

    Everything is off by default: the train loop takes a no-op fast
    path (shared null context managers, no per-step host-device sync).
    Setting ``trace_dir`` or ``metrics_dir`` implies ``enabled``.
    """

    # master switch for runtime stats (step-time reservoirs, compile
    # tracking) without any file output [BIGDL_OBS]
    enabled: bool = False
    # Chrome trace_event JSON (Perfetto-viewable) + JSONL structured
    # events are written here [BIGDL_TRACE_DIR]
    trace_dir: Optional[str] = None
    # Prometheus text exposition + JSONL metric snapshots are written
    # here (falls back to trace_dir when unset) [BIGDL_METRICS_DIR]
    metrics_dir: Optional[str] = None
    # step-time / dispatch-time reservoir capacity [BIGDL_OBS_RESERVOIR]
    reservoir_size: int = 4096
    # flight recorder: how many recent span/event records the tracer
    # retains in memory for postmortem bundles [BIGDL_FLIGHT_SPANS]
    flight_spans: int = 512
    # perf-regression gate: fail when the fresh step time exceeds the
    # trajectory's best by this factor (obs/regress.py)
    # [BIGDL_REGRESS_TOLERANCE]
    regress_tolerance: float = 1.5
    # training-health telemetry (obs/health.py): fetch the per-layer
    # grad/param/update-norm array from the device once every N steps;
    # 0 disables — the train step then compiles WITHOUT the health
    # output (identical signature to a pre-health build, zero extra
    # per-step host transfers) [BIGDL_HEALTH_EVERY]
    health_every: int = 0
    # rolling window for the numerics anomaly detector (loss / global
    # grad-norm spike vs rolling median) [BIGDL_HEALTH_WINDOW]
    health_window: int = 64
    # a loss or grad norm above median * this factor is an anomaly;
    # <= 0 disables the detector [BIGDL_HEALTH_SPIKE_FACTOR]
    health_spike_factor: float = 10.0
    # goodput ledger (obs/goodput.py): the bottleneck classifier runs
    # once every N productive steps; <= 0 disables the windowed
    # classifier (the ledger still records) [BIGDL_GOODPUT_WINDOW]
    goodput_window: int = 32
    # assumed interconnect bandwidth in GB/s for the comm-seconds
    # estimate (static wire bytes / bandwidth); 0 = unknown, the
    # classifier then never reports comm_bound [BIGDL_WIRE_GBPS]
    wire_gbps: float = 0.0
    # cross-host straggler detection (obs/aggregate.py): a host whose
    # step-time p50 exceeds the cross-host median by this factor is
    # flagged; <= 1 disables [BIGDL_STRAGGLER_FACTOR]
    straggler_factor: float = 1.5
    # live telemetry plane (obs/server.py): per-host HTTP endpoint
    # serving /metrics (Prometheus exposition), /healthz (JSON
    # liveness) and /trace?last=K (flight-recorder tail) on a daemon
    # thread.  0 = ephemeral port (tests), unset = off — no thread, no
    # socket, zero overhead [BIGDL_OBS_PORT]
    obs_port: Optional[int] = None
    # the server writes its actually-bound port here (atomic replace)
    # so a supervisor can find an ephemeral (port-0) child endpoint
    # [BIGDL_OBS_PORT_FILE]
    obs_port_file: Optional[str] = None
    # comma-separated host:port peer endpoints scraped into one live
    # fleet snapshot (obs/aggregate.FleetAggregator, report --watch)
    # [BIGDL_OBS_PEERS]
    obs_peers: Optional[str] = None
    # alert rule pack (obs/alerts.py): inline JSON list or a path to a
    # JSON file; unset = the default rule pack [BIGDL_ALERT_RULES]
    alert_rules: Optional[str] = None
    # alert sink: firing/resolved transitions append to this JSONL
    # file, or POST to it when it is an http(s):// webhook
    # [BIGDL_ALERT_SINK]
    alert_sink: Optional[str] = None
    # per-attempt connect/read timeout for the webhook sink POST (one
    # retry on failure; a dead sink costs at most 2x this per
    # transition and can never wedge the goodput window tick)
    # [BIGDL_ALERT_SINK_TIMEOUT]
    alert_sink_timeout: float = 1.0
    # request-scoped distributed tracing for the serving data plane
    # (obs/reqtrace.py): tail-sampling probability in [0, 1] for clean
    # requests — errored / retried / preempted / handed-off /
    # SLO-violating requests are always kept.  0 (the default)
    # disables the subsystem entirely: no contexts, no span buffering,
    # zero work on the decode hot path [BIGDL_REQTRACE_SAMPLE]
    reqtrace_sample: float = 0.0
    # bounded ring of kept completed request traces held in memory for
    # /trace?request=<id> lookups and postmortems
    # [BIGDL_REQTRACE_RING]
    reqtrace_ring: int = 256
    # strict metric registry: reject any bigdl_* metric registration
    # not declared in obs/names.py (or whose kind/labels disagree) and
    # enforce each family's label-cardinality ceiling.  CI and the
    # smokes run with this on; production defaults off so a hotfixed
    # counter can never crash a serving fleet [BIGDL_OBS_STRICT]
    strict: bool = False

    # ---- fleet-scale metrics pipeline (obs/rollup.py, obs/retain.py)
    # report --watch host table cap: render only the worst-K hosts by
    # gating signal (queue depth / step age / status), with a trailing
    # "... and N more hosts" line [BIGDL_WATCH_HOSTS]
    watch_hosts: int = 16
    # hosts per leaf RollupAggregator when assembling a tiered
    # pipeline (rollup.build_tiers); ~sqrt(fleet) keeps root and leaf
    # fan-in balanced [BIGDL_ROLLUP_SHARD]
    rollup_shard: int = 32
    # per-family label-cardinality bound on a rollup's merged
    # exposition: keep the top-K series by value, fold the rest into
    # an 'other' bucket (counted in
    # bigdl_rollup_series_dropped_total); <= 0 disables the bound
    # [BIGDL_ROLLUP_TOP_K]
    rollup_top_k: int = 64
    # staleness threshold: an ok peer whose /healthz clock skews from
    # the scraper's clock by more than this is excluded from fleet
    # merges and accounted in bigdl_fleet_stale_hosts; <= 0 disables
    # skew-based staleness [BIGDL_STALE_AFTER_S]
    stale_after_s: float = 30.0
    # retention store (obs/retain.py): points kept per downsampling
    # ring (raw / 10s / 1m) per series [BIGDL_RETAIN_POINTS]
    retain_points: int = 240
    # retention store hard series budget: past it, new series are
    # rejected (memory stays fixed) [BIGDL_RETAIN_SERIES]
    retain_series: int = 512

    # ---- continuous profiling + debug bundles (obs/prof.py, bundle.py)
    # always-on sampling profiler: samples/sec for the daemon thread
    # walking sys._current_frames(); <= 0 (the default) disables — no
    # thread, no clock reads, the off path is one config read
    # [BIGDL_PROF_HZ]
    prof_hz: float = 0.0
    # profiler self-overhead budget as a fraction of wall time; when
    # the cumulative sampling-work ratio exceeds this, samples are
    # SKIPPED (and counted) until the ratio recovers — the hard cap
    # behind bigdl_prof_overhead_ratio [BIGDL_PROF_BUDGET]
    prof_budget: float = 0.01
    # black-box debug bundles (obs/bundle.py) are written under this
    # directory on alert firings / supervisor restarts / GET /debugz;
    # unset disables every automatic trigger [BIGDL_BUNDLE_DIR]
    bundle_dir: Optional[str] = None
    # minimum seconds between two alert-triggered bundles for the SAME
    # rule (an alert storm must not fill the disk); 0 disables the
    # limit — every episode bundles [BIGDL_BUNDLE_RATE_LIMIT]
    bundle_rate_limit: float = 300.0

    @property
    def active(self) -> bool:
        return bool(self.enabled or self.trace_dir or self.metrics_dir
                    or self.obs_port is not None)

    @classmethod
    def from_env(cls) -> "ObsConfig":
        return cls(
            enabled=_env_bool("BIGDL_OBS", False),
            trace_dir=_env_str("BIGDL_TRACE_DIR", None),
            metrics_dir=_env_str("BIGDL_METRICS_DIR", None),
            reservoir_size=_env_int("BIGDL_OBS_RESERVOIR", 4096),
            flight_spans=_env_int("BIGDL_FLIGHT_SPANS", 512),
            regress_tolerance=_env_float("BIGDL_REGRESS_TOLERANCE", 1.5),
            health_every=_env_int("BIGDL_HEALTH_EVERY", 0),
            health_window=_env_int("BIGDL_HEALTH_WINDOW", 64),
            health_spike_factor=_env_float("BIGDL_HEALTH_SPIKE_FACTOR",
                                           10.0),
            goodput_window=_env_int("BIGDL_GOODPUT_WINDOW", 32),
            wire_gbps=_env_float("BIGDL_WIRE_GBPS", 0.0),
            straggler_factor=_env_float("BIGDL_STRAGGLER_FACTOR", 1.5),
            obs_port=_env_opt_int("BIGDL_OBS_PORT", None),
            obs_port_file=_env_str("BIGDL_OBS_PORT_FILE", None),
            obs_peers=_env_str("BIGDL_OBS_PEERS", None),
            alert_rules=_env_str("BIGDL_ALERT_RULES", None),
            alert_sink=_env_str("BIGDL_ALERT_SINK", None),
            alert_sink_timeout=_env_float("BIGDL_ALERT_SINK_TIMEOUT", 1.0),
            reqtrace_sample=_env_float("BIGDL_REQTRACE_SAMPLE", 0.0),
            reqtrace_ring=_env_int("BIGDL_REQTRACE_RING", 256),
            strict=_env_bool("BIGDL_OBS_STRICT", False),
            watch_hosts=_env_int("BIGDL_WATCH_HOSTS", 16),
            rollup_shard=_env_int("BIGDL_ROLLUP_SHARD", 32),
            rollup_top_k=_env_int("BIGDL_ROLLUP_TOP_K", 64),
            stale_after_s=_env_float("BIGDL_STALE_AFTER_S", 30.0),
            retain_points=_env_int("BIGDL_RETAIN_POINTS", 240),
            retain_series=_env_int("BIGDL_RETAIN_SERIES", 512),
            prof_hz=_env_float("BIGDL_PROF_HZ", 0.0),
            prof_budget=_env_float("BIGDL_PROF_BUDGET", 0.01),
            bundle_dir=_env_str("BIGDL_BUNDLE_DIR", None),
            bundle_rate_limit=_env_float("BIGDL_BUNDLE_RATE_LIMIT",
                                         300.0),
        )


@dataclasses.dataclass
class TunerConfig:
    """Fusion-aware kernel auto-tuner (``bigdl_tpu/ops/autotune.py``).

    Off by default: dispatch then follows the hand-measured static
    policies in ``ops/attention.py`` / ``ops/conv_bn.py`` exactly.
    Enabled, every tunable call site (flash attention fwd/bwd, 1x1 and
    kxk conv+BN) resolves its impl and block sizes from the cached
    cost-model search instead.
    """

    # master switch [BIGDL_TUNER]
    enabled: bool = False
    # JSON decision store, keyed on (site, shape, dtype, platform);
    # unset = in-memory only (decisions die with the process)
    # [BIGDL_TUNER_CACHE]
    cache_path: Optional[str] = None
    # allow one-shot wall-clock measurement of candidates when inputs
    # are concrete (never inside a jit trace — there the cost model
    # decides); measured times are cached like any decision
    # [BIGDL_TUNER_MEASURE]
    measure: bool = False
    # timed iterations per measured candidate [BIGDL_TUNER_MEASURE_ITERS]
    measure_iters: int = 3

    @classmethod
    def from_env(cls) -> "TunerConfig":
        return cls(
            enabled=_env_bool("BIGDL_TUNER", False),
            cache_path=_env_str("BIGDL_TUNER_CACHE", None),
            measure=_env_bool("BIGDL_TUNER_MEASURE", False),
            measure_iters=_env_int("BIGDL_TUNER_MEASURE_ITERS", 3),
        )


@dataclasses.dataclass
class WireConfig:
    """Compressed-collective wire defaults (``bigdl_tpu/parallel/wire``).

    The process-wide answer to "what leaves the chip": DistriOptimizer
    resolves its gradient wire from here when the constructor leaves
    ``wire_dtype``/``wire_block``/``wire_ef`` unset, and every opt-in
    path (TP psum, MoE all_to_all, ring K/V rotation) passed a bare
    dtype string fills block/EF from here too.
    """

    # gradient-exchange wire dtype: "bfloat16" (cast, TPU-native),
    # "int8" / "fp8_e4m3" / "fp8_e5m2" (blockwise-scaled staged ring),
    # "float32"/"none" (uncompressed) [BIGDL_WIRE_DTYPE]
    dtype: str = "bfloat16"
    # elements per quantization scale for the scaled dtypes
    # [BIGDL_WIRE_BLOCK]
    block: int = 512
    # error feedback: carry each device's quantization residual across
    # steps so compression error dithers instead of biasing long runs
    # [BIGDL_WIRE_EF]
    error_feedback: bool = False

    @classmethod
    def from_env(cls) -> "WireConfig":
        return cls(
            dtype=_env_str("BIGDL_WIRE_DTYPE", "bfloat16"),
            block=_env_int("BIGDL_WIRE_BLOCK", 512),
            error_feedback=_env_bool("BIGDL_WIRE_EF", False),
        )


@dataclasses.dataclass
class AutoscaleConfig:
    """Autoscaling supervisor policy loop (``resilience/autoscale.py``).

    Off by default: the supervisor then only restarts, never resizes.
    Enabled, a policy loop inside the supervisor scrapes the live fleet
    signals (PR 8 ``/healthz``/``/metrics``), evaluates declarative
    scale rules, and executes a decision by checkpoint-stop-restart at
    the new world size through the elastic exit-code contract.
    """

    # master switch [BIGDL_AUTOSCALE]
    enabled: bool = False
    # world-size bounds a decision may never leave
    # [BIGDL_AUTOSCALE_MIN_WORLD / BIGDL_AUTOSCALE_MAX_WORLD]
    min_world: int = 1
    max_world: int = 8
    # scale step: up multiplies the world by this, down divides (the
    # ZeRO-1 shard quantum likes powers of two) [BIGDL_AUTOSCALE_FACTOR]
    factor: int = 2
    # seconds between policy evaluations [BIGDL_AUTOSCALE_INTERVAL]
    interval_s: float = 10.0
    # after a (re)launch, no signal is trusted for this long — compile
    # and restore make every fresh child look slow
    # [BIGDL_AUTOSCALE_WARMUP]
    warmup_s: float = 30.0
    # after an executed (or dry-run) decision, no further decision for
    # this long — one restart must finish paying for itself before the
    # next is allowed [BIGDL_AUTOSCALE_COOLDOWN]
    cooldown_s: float = 120.0
    # hysteresis: a rule must breach on this many CONSECUTIVE
    # evaluations before it may decide (a flapping signal resets its
    # streak and can never thrash the world) [BIGDL_AUTOSCALE_HYSTERESIS]
    hysteresis: int = 2
    # target step-time band: sustained step time above `high` scales
    # up, below `low` scales down; 0 disables either edge
    # [BIGDL_AUTOSCALE_STEP_TIME_HIGH / _LOW]
    step_time_high: float = 0.0
    step_time_low: float = 0.0
    # input/serving queue-depth band over the streaming tier's
    # bigdl_stream_buffer_depth / bigdl_stream_lag_records gauges:
    # sustained depth above `high` scales up (ingest outruns training),
    # below `low` scales down (paying for idle chips); 0 disables
    # [BIGDL_AUTOSCALE_QUEUE_HIGH / _LOW]
    queue_high: float = 0.0
    queue_low: float = 0.0
    # cost/throughput ceiling: live goodput ratio sustained below this
    # floor scales DOWN (overhead-bound runs don't get better with more
    # hosts — they get cheaper with fewer); 0 disables
    # [BIGDL_AUTOSCALE_GOODPUT_FLOOR]
    goodput_floor: float = 0.0
    # evict stragglers: a host /healthz reports as stalled triggers a
    # scale-down decision (reason straggler_evict) so the next launch
    # re-forms the world without it [BIGDL_AUTOSCALE_EVICT_STRAGGLERS]
    evict_stragglers: bool = False
    # serving latency band over the bigdl_request_latency_seconds
    # e2e histogram (resilience/autoscale.derive_signals computes the
    # fleet-worst p99 from the scraped buckets): sustained p99 above
    # `high` scales up, below `low` scales down; 0 disables
    # [BIGDL_AUTOSCALE_P99_HIGH / _LOW, seconds]
    p99_high: float = 0.0
    p99_low: float = 0.0
    # current world size as exported by the supervisor for its children
    # (the controller's starting point); 0 = unset, derive from
    # min_world [BIGDL_AUTOSCALE_WORLD]
    world: int = 0
    # dry-run: evaluate + count + trace every decision, execute none
    # [BIGDL_AUTOSCALE_DRY_RUN]
    dry_run: bool = False
    # rule pack override: inline JSON list or a path to a JSON file
    # (schema in resilience/autoscale.py); unset = rules derived from
    # the band knobs above [BIGDL_AUTOSCALE_RULES]
    rules: Optional[str] = None

    @classmethod
    def from_env(cls) -> "AutoscaleConfig":
        return cls(
            enabled=_env_bool("BIGDL_AUTOSCALE", False),
            min_world=_env_int("BIGDL_AUTOSCALE_MIN_WORLD", 1),
            max_world=_env_int("BIGDL_AUTOSCALE_MAX_WORLD", 8),
            factor=_env_int("BIGDL_AUTOSCALE_FACTOR", 2),
            interval_s=_env_float("BIGDL_AUTOSCALE_INTERVAL", 10.0),
            warmup_s=_env_float("BIGDL_AUTOSCALE_WARMUP", 30.0),
            cooldown_s=_env_float("BIGDL_AUTOSCALE_COOLDOWN", 120.0),
            hysteresis=_env_int("BIGDL_AUTOSCALE_HYSTERESIS", 2),
            step_time_high=_env_float("BIGDL_AUTOSCALE_STEP_TIME_HIGH",
                                      0.0),
            step_time_low=_env_float("BIGDL_AUTOSCALE_STEP_TIME_LOW", 0.0),
            queue_high=_env_float("BIGDL_AUTOSCALE_QUEUE_HIGH", 0.0),
            queue_low=_env_float("BIGDL_AUTOSCALE_QUEUE_LOW", 0.0),
            goodput_floor=_env_float("BIGDL_AUTOSCALE_GOODPUT_FLOOR", 0.0),
            evict_stragglers=_env_bool("BIGDL_AUTOSCALE_EVICT_STRAGGLERS",
                                       False),
            p99_high=_env_float("BIGDL_AUTOSCALE_P99_HIGH", 0.0),
            p99_low=_env_float("BIGDL_AUTOSCALE_P99_LOW", 0.0),
            world=_env_int("BIGDL_AUTOSCALE_WORLD", 0),
            dry_run=_env_bool("BIGDL_AUTOSCALE_DRY_RUN", False),
            rules=_env_str("BIGDL_AUTOSCALE_RULES", None),
        )


@dataclasses.dataclass
class ServeConfig:
    """Inference serving tier defaults (``bigdl_tpu/serving``).

    Constructor arguments on :class:`~bigdl_tpu.serving.LMEngine` /
    :class:`~bigdl_tpu.serving.ClassifierEngine` win; these are the
    process-wide fallbacks a deployment sets once.
    """

    # decode slots / classifier micro-batch rows [BIGDL_SERVE_MAX_BATCH]
    max_batch: int = 8
    # tokens per KV-cache page [BIGDL_SERVE_PAGE]
    page_size: int = 16
    # KV page pool size; 0 = full residency (every slot can hold a
    # max_len sequence) [BIGDL_SERVE_PAGES]
    num_pages: int = 0
    # bounded request-queue capacity — submits past it backpressure
    # the client [BIGDL_SERVE_QUEUE]
    queue_capacity: int = 64
    # int8 weights for the memory-bound decode matmuls (LM) / the
    # quantize() module swap (classifier) [BIGDL_SERVE_INT8]
    int8: bool = False
    # e2e latency SLO target in seconds; > 0 publishes the
    # bigdl_serve_latency_slo_ratio gauge the serve_latency_slo_burn
    # alert rule watches [BIGDL_SERVE_SLO_MS, milliseconds]
    slo_s: float = 0.0
    # HTTP front-end port for serving/server.py (0 = ephemeral);
    # unset = constructor default [BIGDL_SERVE_PORT]
    port: Optional[int] = None

    @classmethod
    def from_env(cls) -> "ServeConfig":
        return cls(
            max_batch=_env_int("BIGDL_SERVE_MAX_BATCH", 8),
            page_size=_env_int("BIGDL_SERVE_PAGE", 16),
            num_pages=_env_int("BIGDL_SERVE_PAGES", 0),
            queue_capacity=_env_int("BIGDL_SERVE_QUEUE", 64),
            int8=_env_bool("BIGDL_SERVE_INT8", False),
            slo_s=_env_float("BIGDL_SERVE_SLO_MS", 0.0) / 1000.0,
            port=_env_opt_int("BIGDL_SERVE_PORT", None),
        )


@dataclasses.dataclass
class RouterConfig:
    """Multi-replica serving router (``bigdl_tpu/serving/router.py``).

    The data-plane tier above N :class:`~bigdl_tpu.serving.LMEngine`
    replicas: session-affine, KV-pressure-aware placement, a shared
    retry *budget* (token bucket) so a browning-out replica cannot
    amplify load, and graceful drain/handoff.  Constructor arguments on
    :class:`~bigdl_tpu.serving.router.Router` win; these are the
    process-wide fallbacks.
    """

    # comma-separated replica endpoints ("host:port,host:port") the
    # router front-end load-balances over; unset = replicas are passed
    # programmatically [BIGDL_ROUTER_REPLICAS]
    replicas: Optional[str] = None
    # router HTTP port (0 = ephemeral); unset = constructor default
    # [BIGDL_ROUTER_PORT]
    port: Optional[int] = None
    # session-affinity binding TTL in seconds — a session re-placed
    # within the TTL lands on the replica holding its KV prefix;
    # <= 0 disables affinity [BIGDL_ROUTER_AFFINITY_TTL]
    affinity_ttl_s: float = 300.0
    # retry budget: tokens deposited per admitted request (the token
    # bucket is capped at `retry_budget_burst`), one spent per retry —
    # fleet-wide retries are capped at ~ratio x the request rate
    # [BIGDL_ROUTER_RETRY_BUDGET]
    retry_budget_ratio: float = 0.2
    # token-bucket cap (also the cold-start allowance)
    # [BIGDL_ROUTER_RETRY_BURST]
    retry_budget_burst: float = 8.0
    # per-request placement attempts past the first (a request is tried
    # on at most 1 + max_retries replicas) [BIGDL_ROUTER_MAX_RETRIES]
    max_retries: int = 2
    # per-attempt replica timeout in seconds [BIGDL_ROUTER_TIMEOUT]
    request_timeout_s: float = 30.0
    # drain deadline: a draining replica gets this long to finish its
    # in-flight decodes before the rest are checkpointed and handed
    # off [BIGDL_ROUTER_DRAIN_DEADLINE]
    drain_deadline_s: float = 10.0
    # weight of KV-page pressure (pages_in_use / pool) against queue
    # depth + in-flight count in the placement score
    # [BIGDL_ROUTER_KV_WEIGHT]
    kv_weight: float = 4.0
    # jittered-backoff base between placement retries (seconds)
    # [BIGDL_ROUTER_BACKOFF_BASE]
    backoff_base_s: float = 0.05
    # Retry-After seconds stamped on shed (503) responses
    # [BIGDL_ROUTER_RETRY_AFTER]
    retry_after_s: float = 1.0
    # exclude replicas whose exported host-clock staleness
    # (``staleness_s`` signal) exceeds BIGDL_STALE_AFTER_S from
    # placement — a skewed host's SLO and handoff timestamps cannot be
    # trusted [BIGDL_ROUTER_STALE_EXCLUDE]
    stale_exclude: bool = True

    @classmethod
    def from_env(cls) -> "RouterConfig":
        return cls(
            replicas=_env_str("BIGDL_ROUTER_REPLICAS", None),
            port=_env_opt_int("BIGDL_ROUTER_PORT", None),
            affinity_ttl_s=_env_float("BIGDL_ROUTER_AFFINITY_TTL", 300.0),
            retry_budget_ratio=_env_float("BIGDL_ROUTER_RETRY_BUDGET",
                                          0.2),
            retry_budget_burst=_env_float("BIGDL_ROUTER_RETRY_BURST", 8.0),
            max_retries=_env_int("BIGDL_ROUTER_MAX_RETRIES", 2),
            request_timeout_s=_env_float("BIGDL_ROUTER_TIMEOUT", 30.0),
            drain_deadline_s=_env_float("BIGDL_ROUTER_DRAIN_DEADLINE",
                                        10.0),
            kv_weight=_env_float("BIGDL_ROUTER_KV_WEIGHT", 4.0),
            backoff_base_s=_env_float("BIGDL_ROUTER_BACKOFF_BASE", 0.05),
            retry_after_s=_env_float("BIGDL_ROUTER_RETRY_AFTER", 1.0),
            stale_exclude=_env_bool("BIGDL_ROUTER_STALE_EXCLUDE", True),
        )


@dataclasses.dataclass
class RolloutConfig:
    """Live weight rollout (``bigdl_tpu/serving/rollout.py``).

    The online training->serving pipe: a checkpoint watcher hot-swaps
    manifest-verified weights into a live engine between decode steps,
    and a router-level canary controller promotes a new version to a
    fraction of replicas, auto-rolling back on SLO burn or output
    divergence with autoscaler-style hysteresis.
    """

    # directory the engine-side watcher polls for published checkpoint
    # prefixes (<version>.model.npz + <version>.manifest.json); unset =
    # watcher built programmatically only [BIGDL_ROLLOUT_WATCH]
    watch_dir: Optional[str] = None
    # watcher poll period in seconds [BIGDL_ROLLOUT_POLL]
    poll_s: float = 1.0
    # fraction of replicas a new version canaries on before full
    # promotion (at least one) [BIGDL_ROLLOUT_CANARY_FRACTION]
    canary_fraction: float = 0.25
    # canary replay divergence (fraction of mismatched tokens on the
    # pinned prompt set) past which a rollback breach is counted
    # [BIGDL_ROLLOUT_DIVERGENCE]
    divergence_threshold: float = 0.05
    # consecutive breached evaluations before a rollback fires (the
    # autoscaler's "for" hysteresis — one noisy window cannot flap)
    # [BIGDL_ROLLOUT_FOR]
    for_count: int = 2
    # consecutive CLEAN evaluations before the canary promotes to the
    # whole fleet [BIGDL_ROLLOUT_HOLD]
    hold_evals: int = 3
    # cooldown after a rollback: the same version cannot re-canary (and
    # no new offer is accepted) inside this window
    # [BIGDL_ROLLOUT_COOLDOWN]
    cooldown_s: float = 30.0
    # pinned prompt set the canary replays for the divergence signal:
    # count and per-prompt decode length [BIGDL_ROLLOUT_PROMPTS /
    # BIGDL_ROLLOUT_PROMPT_TOKENS]
    pinned_prompts: int = 4
    pinned_tokens: int = 8

    @classmethod
    def from_env(cls) -> "RolloutConfig":
        return cls(
            watch_dir=_env_str("BIGDL_ROLLOUT_WATCH", None),
            poll_s=_env_float("BIGDL_ROLLOUT_POLL", 1.0),
            canary_fraction=_env_float("BIGDL_ROLLOUT_CANARY_FRACTION",
                                       0.25),
            divergence_threshold=_env_float("BIGDL_ROLLOUT_DIVERGENCE",
                                            0.05),
            for_count=_env_int("BIGDL_ROLLOUT_FOR", 2),
            hold_evals=_env_int("BIGDL_ROLLOUT_HOLD", 3),
            cooldown_s=_env_float("BIGDL_ROLLOUT_COOLDOWN", 30.0),
            pinned_prompts=_env_int("BIGDL_ROLLOUT_PROMPTS", 4),
            pinned_tokens=_env_int("BIGDL_ROLLOUT_PROMPT_TOKENS", 8),
        )


@dataclasses.dataclass
class FleetSimConfig:
    """Fleet-scale control-plane simulator (``bigdl_tpu/sim``).

    The simulator stands up hundreds of synthetic ``/metrics`` +
    ``/healthz`` hosts in one process and drives the REAL autoscaling
    controller, alert engine and fleet aggregator through declarative
    chaos scenarios on a virtual clock (``scripts/fleet_sim.py``).
    These knobs parameterize that harness; they change nothing in a
    training or serving process.
    """

    # synthetic host count the scenarios run at [BIGDL_FLEET_HOSTS]
    hosts: int = 200
    # scenario selection: a builtin name (``bigdl_tpu/sim/scenario.py``
    # BUILTIN_SCENARIOS), a comma-separated list of names, inline JSON,
    # or a path to a JSON scenario file; unset = the smoke's default
    # matrix [BIGDL_FLEET_SCENARIO]
    scenario: Optional[str] = None
    # divide every virtual duration in the scenario (and the autoscale
    # policy windows it carries) by this factor — the CI knob that runs
    # the same scenario shape in fewer ticks.  The tick period itself
    # is preserved, so heavy compression coarsens signal dynamics
    # [BIGDL_FLEET_TIME_COMPRESSION]
    time_compression: float = 1.0
    # deterministic seed for host selection and per-host jitter
    # [BIGDL_FLEET_SEED]
    seed: int = 0

    @classmethod
    def from_env(cls) -> "FleetSimConfig":
        return cls(
            hosts=_env_int("BIGDL_FLEET_HOSTS", 200),
            scenario=_env_str("BIGDL_FLEET_SCENARIO", None),
            time_compression=_env_float("BIGDL_FLEET_TIME_COMPRESSION",
                                        1.0),
            seed=_env_int("BIGDL_FLEET_SEED", 0),
        )


@dataclasses.dataclass
class BigDLConfig:
    """Process-global framework configuration.

    Fields map 1:1 onto the reference's ``bigdl.*`` properties where one
    exists; the env alias is the ``BIGDL_*`` spelling shown per field.
    """

    # --- engine (reference: bigdl.check.singleton, Engine.init) ---------
    # refuse a second Engine.init in one process [BIGDL_CHECK_SINGLETON]
    check_singleton: bool = False
    # multi-host coordinator for jax.distributed.initialize
    # [BIGDL_COORDINATOR_ADDRESS / BIGDL_NUM_PROCESSES / BIGDL_PROCESS_ID]
    coordinator_address: Optional[str] = None
    num_processes: int = 1
    process_id: int = 0

    # --- elastic attempt index [BIGDL_ELASTIC_ATTEMPT] ------------------
    # which incarnation of an elastic run this process is (0 = first
    # launch); the supervisor exports it into every child's environment
    # and the goodput ledger / healthz payload key their shards on it
    elastic_attempt: int = 0

    # --- native host library [BIGDL_TPU_NO_NATIVE] ----------------------
    # skip loading the C++ host data-plane .so (numpy fallback)
    no_native: bool = False

    # --- logging (reference: LoggerFilter) ------------------------------
    # [BIGDL_DISABLE_LOGGER] / [BIGDL_LOG_PATH]
    disable_logger: bool = False
    log_path: Optional[str] = None

    # --- profiling [BIGDL_PROFILE] --------------------------------------
    # directory for a jax.profiler trace of the first optimizer steps
    profile_dir: Optional[str] = None

    # --- resilience (resilience/ package) -------------------------------
    # deterministic fault-injection plan for chaos tests, e.g.
    # "step:3:raise,step:7:nan_grad,ckpt:1:truncate" [BIGDL_FAULT_PLAN]
    fault_plan: Optional[str] = None
    # classified-retry backoff: base * 2^(attempt-1), capped, with
    # deterministic jitter [BIGDL_RETRY_BACKOFF_BASE / _MAX]
    retry_backoff_base: float = 0.5
    retry_backoff_max: float = 30.0
    # sliding-window retry budget: more than `budget` transient failures
    # inside `window` seconds stops retrying even if per-run attempts
    # remain [BIGDL_RETRY_WINDOW_SECONDS / BIGDL_RETRY_WINDOW_BUDGET]
    retry_window_seconds: float = 600.0
    retry_window_budget: int = 16
    # non-finite step guard: skip the weight update when grads/loss go
    # NaN/inf; escalate after N consecutive skips
    # [BIGDL_NONFINITE_GUARD / BIGDL_MAX_NONFINITE_SKIPS]
    nonfinite_guard: bool = True
    max_nonfinite_skips: int = 10
    # checkpoint retention: keep the newest K checkpoint pairs, 0 =
    # unlimited [BIGDL_CHECKPOINT_KEEP_LAST]
    checkpoint_keep_last: int = 0
    # --- elastic training (resilience/elastic.py) -----------------------
    # Engine.init installs a SIGTERM/SIGINT handler: finish the in-flight
    # step, emergency checkpoint, exit EXIT_PREEMPTED
    # [BIGDL_PREEMPTION_HANDLER]
    preemption_handler: bool = True
    # heartbeat peer-liveness for multi-host runs: a shared directory
    # every host touches a host-tagged file in; unset = off
    # [BIGDL_HEARTBEAT_DIR]
    heartbeat_dir: Optional[str] = None
    # touch the heartbeat file every K training steps
    # [BIGDL_HEARTBEAT_EVERY]
    heartbeat_every: int = 1
    # a peer silent past this many seconds raises PeerLostError instead
    # of hanging the next collective [BIGDL_HEARTBEAT_TIMEOUT]
    heartbeat_timeout: float = 60.0
    # supervisor hang watchdog (resilience/supervisor.py): a child
    # whose /healthz step stamp stops advancing for this many seconds
    # is killed and restarted as a transient failure — the hang class
    # heartbeats and exit codes cannot catch; <= 0 disables
    # [BIGDL_HANG_TIMEOUT]
    hang_timeout: float = 0.0
    # --- streaming datasets (dataset/stream.py) -------------------------
    # bounded-buffer capacity (records) of the stream source adapter —
    # the producer thread backpressures when the trainer falls this far
    # behind [BIGDL_STREAM_BUFFER]
    stream_buffer: int = 1024
    # records per "epoch" of an unbounded stream, so epoch-keyed
    # triggers (every_epoch checkpoints, max_epoch) stay meaningful on
    # continuous ingest; 0 = one endless epoch (use max_iteration)
    # [BIGDL_STREAM_EPOCH_RECORDS]
    stream_epoch_records: int = 0

    # --- overlapped training step (ISSUE 11) ----------------------------
    # bucketed comm/compute overlap: DistriOptimizer partitions the
    # flat gradient into ~this many MiB per bucket and launches the
    # compressed reduce-scatter per bucket (last-layer-first) so the
    # wire rides under the remaining backward; <= 0 = one monolithic
    # exchange (the pre-overlap behavior) [BIGDL_OVERLAP_BUCKET_MB]
    overlap_bucket_mb: float = 0.0
    # fully async checkpointing: trigger-driven checkpoints snapshot to
    # host synchronously (the only blocking span), then serialize +
    # fsync + manifest on a background writer thread.  Emergency /
    # preemption checkpoints ALWAYS stay synchronous — the process is
    # about to exit, there is no step to overlap
    # [BIGDL_CHECKPOINT_ASYNC]
    checkpoint_async: bool = False
    # double-buffered host->device input: batch N+1 is fetched,
    # prepared and device_put while step N is still in flight, so the
    # input pipeline overlaps device compute instead of stalling the
    # loop (disabled automatically under an active fault-injection
    # plan — chaos poisoning targets the foreground path)
    # [BIGDL_INPUT_DOUBLE_BUFFER]
    input_double_buffer: bool = False

    # --- autoscaling supervisor (resilience/autoscale.py) ---------------
    # [BIGDL_AUTOSCALE / _MIN_WORLD / _MAX_WORLD / _FACTOR / _INTERVAL /
    #  _WARMUP / _COOLDOWN / _HYSTERESIS / _STEP_TIME_HIGH / _STEP_TIME_LOW
    #  / _QUEUE_HIGH / _QUEUE_LOW / _GOODPUT_FLOOR / _EVICT_STRAGGLERS /
    #  _DRY_RUN / _RULES]
    autoscale: AutoscaleConfig = dataclasses.field(
        default_factory=AutoscaleConfig)

    # --- observability (obs/ package) -----------------------------------
    # span tracer / metrics registry / runtime profiling switches
    # [BIGDL_OBS / BIGDL_TRACE_DIR / BIGDL_METRICS_DIR /
    #  BIGDL_OBS_RESERVOIR]
    obs: ObsConfig = dataclasses.field(default_factory=ObsConfig)

    # --- kernel auto-tuner (ops/autotune.py) ----------------------------
    # [BIGDL_TUNER / BIGDL_TUNER_CACHE / BIGDL_TUNER_MEASURE /
    #  BIGDL_TUNER_MEASURE_ITERS]
    tuner: TunerConfig = dataclasses.field(default_factory=TunerConfig)

    # --- compressed collective wire (parallel/wire.py) ------------------
    # [BIGDL_WIRE_DTYPE / BIGDL_WIRE_BLOCK / BIGDL_WIRE_EF]
    wire: WireConfig = dataclasses.field(default_factory=WireConfig)

    # --- inference serving tier (serving/ package) ----------------------
    # [BIGDL_SERVE_MAX_BATCH / _PAGE / _PAGES / _QUEUE / _INT8 /
    #  _SLO_MS / _ADMISSION / _PORT]
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)

    # --- multi-replica serving router (serving/router.py) ---------------
    # [BIGDL_ROUTER_REPLICAS / _PORT / _AFFINITY_TTL / _RETRY_BUDGET /
    #  _RETRY_BURST / _MAX_RETRIES / _TIMEOUT / _DRAIN_DEADLINE /
    #  _KV_WEIGHT / _BACKOFF_BASE / _RETRY_AFTER / _STALE_EXCLUDE]
    router: RouterConfig = dataclasses.field(default_factory=RouterConfig)

    # --- live weight rollout (serving/rollout.py) -----------------------
    # [BIGDL_ROLLOUT_WATCH / _POLL / _CANARY_FRACTION / _DIVERGENCE /
    #  _FOR / _HOLD / _COOLDOWN / _PROMPTS / _PROMPT_TOKENS]
    rollout: RolloutConfig = dataclasses.field(
        default_factory=RolloutConfig)

    # --- fleet-scale control-plane simulator (sim/ package) -------------
    # [BIGDL_FLEET_HOSTS / _SCENARIO / _TIME_COMPRESSION / _SEED]
    fleet: FleetSimConfig = dataclasses.field(
        default_factory=FleetSimConfig)

    # --- benchmarking [BENCH_* kept for bench.py compat] ----------------

    @classmethod
    def from_env(cls) -> "BigDLConfig":
        return cls(
            check_singleton=_env_bool("BIGDL_CHECK_SINGLETON", False),
            coordinator_address=_env_str("BIGDL_COORDINATOR_ADDRESS", None),
            num_processes=_env_int("BIGDL_NUM_PROCESSES", 1),
            process_id=_env_int("BIGDL_PROCESS_ID", 0),
            elastic_attempt=_env_int("BIGDL_ELASTIC_ATTEMPT", 0),
            no_native=_env_bool("BIGDL_TPU_NO_NATIVE", False),
            disable_logger=_env_bool("BIGDL_DISABLE_LOGGER", False),
            log_path=_env_str("BIGDL_LOG_PATH", None),
            profile_dir=_env_str("BIGDL_PROFILE", None),
            fault_plan=_env_str("BIGDL_FAULT_PLAN", None),
            retry_backoff_base=_env_float("BIGDL_RETRY_BACKOFF_BASE", 0.5),
            retry_backoff_max=_env_float("BIGDL_RETRY_BACKOFF_MAX", 30.0),
            retry_window_seconds=_env_float(
                "BIGDL_RETRY_WINDOW_SECONDS", 600.0),
            retry_window_budget=_env_int("BIGDL_RETRY_WINDOW_BUDGET", 16),
            nonfinite_guard=_env_bool("BIGDL_NONFINITE_GUARD", True),
            max_nonfinite_skips=_env_int("BIGDL_MAX_NONFINITE_SKIPS", 10),
            checkpoint_keep_last=_env_int("BIGDL_CHECKPOINT_KEEP_LAST", 0),
            preemption_handler=_env_bool("BIGDL_PREEMPTION_HANDLER", True),
            heartbeat_dir=_env_str("BIGDL_HEARTBEAT_DIR", None),
            heartbeat_every=_env_int("BIGDL_HEARTBEAT_EVERY", 1),
            heartbeat_timeout=_env_float("BIGDL_HEARTBEAT_TIMEOUT", 60.0),
            hang_timeout=_env_float("BIGDL_HANG_TIMEOUT", 0.0),
            stream_buffer=_env_int("BIGDL_STREAM_BUFFER", 1024),
            stream_epoch_records=_env_int("BIGDL_STREAM_EPOCH_RECORDS", 0),
            overlap_bucket_mb=_env_float("BIGDL_OVERLAP_BUCKET_MB", 0.0),
            checkpoint_async=_env_bool("BIGDL_CHECKPOINT_ASYNC", False),
            input_double_buffer=_env_bool("BIGDL_INPUT_DOUBLE_BUFFER",
                                          False),
            autoscale=AutoscaleConfig.from_env(),
            obs=ObsConfig.from_env(),
            tuner=TunerConfig.from_env(),
            wire=WireConfig.from_env(),
            serve=ServeConfig.from_env(),
            router=RouterConfig.from_env(),
            rollout=RolloutConfig.from_env(),
            fleet=FleetSimConfig.from_env(),
        )

    def describe(self) -> str:
        lines = [f"{f.name} = {getattr(self, f.name)!r}"
                 for f in dataclasses.fields(self)]
        return "BigDLConfig:\n  " + "\n  ".join(lines)


# the process-global instance (resolved from env at import)
config = BigDLConfig.from_env()

# fields pinned by an explicit configure() call: env refreshes skip them
_explicit: set = set()


def configure(**kwargs) -> BigDLConfig:
    """Override config fields programmatically (highest-priority tier).
    Returns the global config for chaining/inspection."""
    for k, v in kwargs.items():
        if not hasattr(config, k):
            raise AttributeError(f"unknown config field {k!r}; fields: "
                                 + ", ".join(f.name for f in
                                             dataclasses.fields(config)))
        setattr(config, k, v)
        _explicit.add(k)
    return config


def refresh_from_env() -> BigDLConfig:
    """Re-read ``BIGDL_*`` env vars for every field NOT pinned by
    configure().  Subsystems with a read-at-call-time contract (e.g.
    ``Engine.init`` honoring a coordinator exported after import) call
    this before reading the config."""
    fresh = BigDLConfig.from_env()
    for f in dataclasses.fields(fresh):
        if f.name not in _explicit:
            setattr(config, f.name, getattr(fresh, f.name))
    return config


def reload_from_env() -> BigDLConfig:
    """Re-resolve everything from the environment, dropping configure()
    overrides (tests mutate os.environ)."""
    _explicit.clear()
    return refresh_from_env()
