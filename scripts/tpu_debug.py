"""Triage guide: where to look first, by symptom.

    python scripts/tpu_debug.py            # prints this guide

DOES IT START ON THE CHIP AT ALL?  ``python chip_smoke.py`` (repo
root, one process) drives the trainer, the serving engine and every
Pallas kernel once on the TPU and fails on the first phase that does
not run or does not match its reference; ``--phases kernels`` runs the
kernel checks alone.  A kernel Mosaic refuses shows up there (and, for
BlockSpec refusals, already in ``tests/test_tpu_lowering.py`` on the
CPU).

For a run that completed (or died) with ``BIGDL_TRACE_DIR`` set, the
post-run analysis lives in the obs CLIs:
``python -m bigdl_tpu.obs.report <trace_dir>`` (step-time percentiles,
collective bytes, slowest spans per host, and — when the run exported
health telemetry via ``BIGDL_HEALTH_EVERY`` — the "training health"
section: per-layer grad/param norms, update ratios, non-finite layer
attributions, numerics anomalies; ``--json`` for machines) and
``python -m bigdl_tpu.obs.aggregate <trace_dir>`` (one Perfetto
timeline from all host shards, with cross-host straggler flags).  A
NaN'd run names its first offending layer in the report's health
section — start there before blaming the compiler.  A run that is
merely SLOW (or restarts a lot) starts at the report's "goodput"
section instead: the wall-clock ledger says how much time went to
compiles, checkpoints, input waits, supervisor backoff, and
restart rework vs. productive steps, and the bottleneck line says
whether the run was input/compute/comm/host bound — see MIGRATION.md
"Goodput & bottleneck attribution" for the knobs and
``scripts/run-tests.sh --goodput`` for the end-to-end smoke.

A run that compiles and is healthy but SLOWER than expected on its hot
kernels (attention, fused conv+BN) is a dispatch question before a
compiler one: enable the auto-tuner (`BIGDL_TUNER=1
BIGDL_TUNER_CACHE=/path/tuner.json`, add `BIGDL_TUNER_MEASURE=1` on a
real chip) and read the report's "kernel auto-tuner" section — which
impl/blocks each site chose, from cache or measurement, and how far
the static policy was off — see MIGRATION.md "Kernel auto-tuning" and
``scripts/run-tests.sh --tune`` for the end-to-end smoke.

A healthy run whose goodput verdict says COMM-bound pays the wire
first: turn on the compressed collective wire (`BIGDL_WIRE_DTYPE=int8
BIGDL_WIRE_EF=1`, or `fp8_e4m3`) and read the report's collective
bytes — `bigdl_collective_wire_savings_ratio{path=...}` says what the
gradient/TP/MoE/ring exchanges ship vs f32 (>= 3.2x on the gradient
path), with error feedback keeping the loss trajectory within the f32
run's — see MIGRATION.md "Quantized collectives v2" and
``scripts/run-tests.sh --wire`` for the measured A/B.  Still
comm-bound (or input-bound, or stalling on checkpoints) after the
wire is compressed?  HIDE the cost instead of shrinking it: the
overlapped step (`BIGDL_OVERLAP_BUCKET_MB` bucketed last-layer-first
gradient exchange, `BIGDL_CHECKPOINT_ASYNC=1` snapshot-then-
background-write checkpoints, `BIGDL_INPUT_DOUBLE_BUFFER=1`
prefetched device transfer) rides comm/IO under backward — the
report's "overlap" block shows buckets, the exposed-comm share and
snapshot-vs-write times, and the `exposed_comm_high` alert pages when
the buckets are too coarse to hide the wire — see MIGRATION.md
"Overlapped step" and ``scripts/run-tests.sh --overlap`` for the
measured on-vs-off A/B.

A run that keeps DYING (preemption, host loss) rather than failing to
compile belongs under the restart supervisor instead: ``python -m
bigdl_tpu.resilience.supervisor -- <train cmd>`` resumes preempted
children from their emergency checkpoint (exit code 170) for free and
transient crashes under the retry budget — see MIGRATION.md "Elastic
training" for the exit-code/heartbeat/resize knobs, and
``scripts/run-tests.sh --elastic`` for the end-to-end smoke.

A run that is the WRONG SIZE for its load — step time over target,
the streaming input buffer backing up, or chips idling on a drained
queue — doesn't need an operator either: add ``--autoscale`` (or
``BIGDL_AUTOSCALE=1``) and the supervisor's policy loop scrapes the
live `/healthz`/`/metrics` signals and executes checkpoint-stop-
restart resizes inside ``BIGDL_AUTOSCALE_MIN_WORLD..MAX_WORLD`` —
with hysteresis + cooldown so flapping signals can't thrash, dry-run
mode to watch it decide, and exactly-once streaming resume
(`dataset/stream.py` offsets ride the checkpoint).  The report's
"autoscaling & stream" section shows every decision; see MIGRATION.md
"Autoscaling & streaming training" and ``scripts/run-tests.sh
--autoscale`` for the end-to-end 1→2→1 smoke.

A SERVING deployment (bigdl_tpu/serving) that is slow or backing up
reads the report's "serving" section first: per-kind request-latency
percentiles (ttft / per_token / e2e), tokens/sec, batcher occupancy
and queue depth.  Low occupancy with a deep queue means admission is
starved (pages exhausted? check bigdl_serve_kv_pages_in_use and
preemptions); high occupancy with a rising p99 means the world is
undersized — the autoscaler's queue band (BIGDL_AUTOSCALE_QUEUE_*) and
latency band (BIGDL_AUTOSCALE_P99_*) scale on exactly these signals.
SLOW DECODE specifically starts at the serving section's "decode:
X ms/step, Y MB/token" line (gauges bigdl_serve_decode_attn_ms /
bigdl_serve_decode_hbm_bytes_per_token): every step reads the pow2
bucket of pages its longest slot uses, so MB/token follows the longest
context in the batch (MIGRATION.md "Decode attention").  A P99
REGRESSION you cannot place from aggregates alone
reads the report's "request traces" section next (run with
BIGDL_REQTRACE_SAMPLE > 0): the slowest decile's per-hop breakdown
(queue / prefill / preempt / decode / placement / retry / handoff)
names the guilty hop, latency-histogram exemplars link a bucket spike
to a kept trace_id, and ``GET /trace?request=<id>`` on the obs server
returns that request's full span list (anomalous requests — errored,
retried, preempted, handed off, SLO-violating — are always kept; see
MIGRATION.md "Request tracing").  See MIGRATION.md "Inference
serving" and ``scripts/run-tests.sh --serve`` for the end-to-end
smoke.

A run you need to watch RIGHT NOW (not post-mortem) has the live
telemetry plane: export ``BIGDL_OBS_PORT`` and curl the host's
``/healthz`` (status / last-step age / live goodput / firing alerts)
and ``/metrics`` (Prometheus, scrapeable), or point ``python -m
bigdl_tpu.obs.report <dir> --watch`` at the fleet
(``BIGDL_OBS_PEERS=h0:P,h1:P`` for live scraping, shard tailing
otherwise).  A run that silently WEDGES — alive, no step progress —
is exactly what ``BIGDL_HANG_TIMEOUT`` + the supervisor's /healthz
hang watchdog restarts; the declarative alert pack
(``BIGDL_ALERT_RULES``/``BIGDL_ALERT_SINK``) pages on goodput SLO
burn, non-finite spikes, stragglers, checkpoint failures and stale
heartbeats — see MIGRATION.md "Live telemetry & alerting" and
``scripts/run-tests.sh --live`` for the end-to-end smoke.

An incident that is GONE by the time anyone attaches tools (the 3am
p99 spike, the once-a-week hang) is what the continuous profiling
plane is for: with ``BIGDL_PROF_HZ`` set a sampling profiler is
*always* on (span-attributed folded stacks, self-overhead capped hard
at ``BIGDL_PROF_BUDGET`` — published as ``bigdl_prof_overhead_ratio``
so a misconfigured rate is itself an alertable signal), served live at
``GET /profilez`` (``?format=collapsed`` feeds any flamegraph tool)
and folded into the report's "profiles" section.  With
``BIGDL_BUNDLE_DIR`` set, every alert *firing* transition (exactly
once per episode, per-rule rate-limited by
``BIGDL_BUNDLE_RATE_LIMIT``), every supervisor crash/hang restart,
and ``GET /debugz`` on demand cuts a black-box debug bundle — the
profile, kept request traces, metrics snapshot, flight ring, runtime
and alert state, sha256-manifested so a torn write is *detected*, not
trusted; ``report`` inventories them and a SIGTERM'd process still
lands its traces + profile through the atexit flush — see MIGRATION.md
"Continuous profiling & debug bundles" and ``scripts/run-tests.sh
--prof`` for the end-to-end smoke.

A FLEET POLICY CHANGE (autoscale bands, alert rules, scrape or
watchdog behavior) is validated BEFORE it meets real traffic by the
control-plane simulator: ``scripts/run-tests.sh --fleet`` runs the
chaos scenario matrix (diurnal wave, correlated stragglers, network
partition, cascading preemptions, flapping hosts + poisoned alert
sink, latency wave) at 200 synthetic hosts against the REAL
controller/alert engine/aggregator on a virtual clock, and the
invariants (no-flap convergence, exactly-once alert episodes,
O(hosts) aggregation, conservative degradation, free preemption
restarts) tell you precisely which property the change broke — read
the report's "fleet simulation" section and FLEET_SIM.json.  Author a
targeted scenario (BIGDL_FLEET_SCENARIO=<file.json>) reproducing the
incident you are chasing; see MIGRATION.md "Fleet simulation & chaos
scenarios".

A FLEET P99 (or any fleet-merged number) that LOOKS WRONG is a
pipeline question before a workload one — check the metrics plane's
own meta-metrics first: ``bigdl_fleet_stale_hosts`` and the report's
``STALE`` lines say which hosts were *excluded* from the merge (clock
skew past BIGDL_STALE_AFTER_S, or failed scrapes — their reasons are
in ``bigdl_fleet_scrape_errors_total{reason}`` and the per-host
``bigdl_fleet_host_staleness_seconds``/``_scrape_latency_seconds``
gauges), and ``bigdl_rollup_series_dropped_total{family}`` says which
families hit the BIGDL_ROLLUP_TOP_K cardinality bound and folded their
tail into the ``other`` bucket (a fleet percentile is exact over what
was merged — the drop counter tells you what wasn't).  A merged value
that still disagrees with a flat scrape is the exactness invariant's
territory: ``scripts/run-tests.sh --fleetobs`` re-proves
hierarchical == flat at 1000 simulated hosts (FLEETOBS_SMOKE.json);
see MIGRATION.md "Fleet-scale metrics".

A STUCK ROLLOUT (new weights published, fleet still on the old
version) or VERSION SKEW (replicas disagree on ``weight_version`` in
``/healthz`` / ``stats()``) is triaged from the rollout plane's own
counters before anyone re-publishes: ``bigdl_rollout_rejected_total
{reason}`` says the watcher *refused* the checkpoint (``torn`` /
``checksum`` / ``size`` / ``missing`` — re-publish via
``publish_checkpoint``, which writes the manifest LAST, rather than
hand-copying files); a publish that verified but never promoted shows
in the CanaryController's stats — ``refused_offers`` (offered inside
the post-rollback cooldown), ``bigdl_rollout_rollbacks_total
{reason}`` (``slo_burn`` vs ``divergence`` says *which* signal keeps
firing) and the ``bigdl_rollout_canary_divergence`` gauge (a high
value is the pinned-prompt replay disagreeing with the incumbent —
usually a genuinely different model, not an infra fault).  Lingering
skew after a settle also shows up as drain replays refusing absorbers
(``bigdl_rollout_version_mismatch_total`` climbing) — find the
replica whose ``/healthz`` ``weight_version`` disagrees and offer it
the incumbent.  ``scripts/run-tests.sh --rollout`` re-proves the
whole plane end-to-end (ROLLOUT_SMOKE.json), and the fleet
simulator's ``weight_rollout`` scenario replays promote / rollback /
corrupt-publish against the real controller — see MIGRATION.md "Live
weight rollout".

A LINT FAILURE (``scripts/run-tests.sh --lint`` /
``tests/test_lint.py::test_repo_is_clean``) is triaged from the
finding line itself — ``path:line: RULE message``.  JX* findings are
tracing hazards (host sync, tracer leak, jit-in-loop, unhashable
static, tracer branch): fix the traced scope, don't suppress — these
are exactly the recompile/host-sync bugs the sections above chase
after the fact.  CC* findings are lock-discipline (acquisition-order
cycle, unlocked shared write, bare acquire): pick one global lock
order / take the class lock.  RD* findings are registry drift: declare
the env var in ``bigdl_tpu/config.py`` (or metric in
``bigdl_tpu/obs/names.py``) instead of minting spellings inline.  A
deliberate exception gets an inline ``# graftlint: disable=RULE`` with
a rationale comment; a legacy finding you must ship around goes in
the baseline via ``--write-baseline`` — see MIGRATION.md "Static
analysis" for rule ids, the baseline lifecycle and suppression syntax.
"""

if __name__ == "__main__":
    print(__doc__)
