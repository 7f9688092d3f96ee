#!/usr/bin/env bash
# Test runner (VERDICT r2 #9; reference: dl/src/test run-tests*.sh).
# Forces the 8-virtual-device CPU backend the suite expects (the
# reference's local[4]-Spark-master trick, SURVEY.md §4.5) and runs
# pytest.  Usage: scripts/run-tests.sh [pytest args]
#   scripts/run-tests.sh --chaos [pytest args]   # only the fault-injection
#                                                # / recovery specs (-m chaos)
#   scripts/run-tests.sh --trace [pytest args]   # observability smoke: tiny
#                                                # traced train loops that
#                                                # assert a well-formed Chrome
#                                                # trace + Prometheus snapshot
#                                                # (-m obs)
#   scripts/run-tests.sh --obs-report            # distributed-obs smoke: a
#                                                # 2-host traced 10-step
#                                                # DistriOptimizer run, shard
#                                                # merge, report render, and
#                                                # the perf-regression gate
#                                                # against a synthetic
#                                                # trajectory (no pytest)
#   scripts/run-tests.sh --elastic               # supervisor chaos smoke: a
#                                                # 2-host run fault-killed at
#                                                # step 7, restarted by the
#                                                # real supervisor at world
#                                                # size 1; asserts the resumed
#                                                # loss trajectory, the
#                                                # bigdl_resumes_total{
#                                                # resize="2to1"} counter, and
#                                                # a cross-attempt goodput
#                                                # ratio with nonzero rework
#                                                # badput (no pytest)
#   scripts/run-tests.sh --tune                  # auto-tuner smoke: tunes one
#                                                # attention, one conv+BN and
#                                                # one int8_mm shape on CPU
#                                                # (interpret mode, measured
#                                                # candidates), asserts a
#                                                # persisted JSON cache,
#                                                # re-runs with zero
#                                                # re-measurements, and checks
#                                                # the report's kernel
#                                                # auto-tuner section
#                                                # (no pytest)
#   scripts/run-tests.sh --goodput               # goodput smoke: a 2-host
#                                                # traced run with a
#                                                # synthetically starved input
#                                                # pipeline -> aggregate ->
#                                                # report; asserts the goodput
#                                                # section renders (text +
#                                                # --json) and the bottleneck
#                                                # classifier says input_bound
#                                                # (no pytest)
#   scripts/run-tests.sh --wire                  # quantized-collectives
#                                                # smoke: a 2-host 200-step
#                                                # A/B of the f32 vs int8-EF
#                                                # vs fp8-EF gradient wires,
#                                                # asserting golden byte
#                                                # counts, savings ratio >=
#                                                # 3.2x, and loss-trajectory
#                                                # agreement with error
#                                                # feedback on; banks
#                                                # WIRE_SMOKE.json for BENCH
#                                                # extras.wire (no pytest)
#   scripts/run-tests.sh --autoscale             # autoscaling + streaming
#                                                # smoke: the REAL supervisor
#                                                # + policy loop resize a
#                                                # streaming training child
#                                                # 1->2->1 from live queue
#                                                # signals; asserts resumed
#                                                # trajectory equivalence, an
#                                                # exactly-once stream audit
#                                                # (every record id trained
#                                                # once across both resizes),
#                                                # and the resize/decision
#                                                # counters; banks
#                                                # AUTOSCALE_SMOKE.json for
#                                                # BENCH extras.autoscale
#                                                # (no pytest)
#   scripts/run-tests.sh --overlap               # overlapped-step smoke: a
#                                                # 2-host 160-step A/B of
#                                                # overlap on (bucketed
#                                                # exchange + async ckpt +
#                                                # double-buffered input) vs
#                                                # off, asserting per-step
#                                                # trajectory equivalence,
#                                                # unchanged golden exchange
#                                                # bytes, lower comm/input
#                                                # badput fractions, smaller
#                                                # checkpoint_save badput and
#                                                # a strictly higher goodput
#                                                # ratio; banks
#                                                # OVERLAP_SMOKE.json for
#                                                # BENCH extras.overlap
#                                                # (no pytest)
#   scripts/run-tests.sh --serve                 # serving-tier smoke: the
#                                                # continuous-batching LM
#                                                # engine on one bursty
#                                                # request trace (slots
#                                                # refilled at step
#                                                # boundaries), long decodes
#                                                # on 32-page tables (the
#                                                # used-page bucket, tokens
#                                                # equal generate()),
#                                                # concurrent HTTP clients
#                                                # against an int8 ResNet +
#                                                # the LM decoder, a queue-
#                                                # driven autoscale decision
#                                                # scraped off the live
#                                                # /metrics endpoint, and
#                                                # the report's serving
#                                                # section; banks
#                                                # SERVE_SMOKE.json for
#                                                # BENCH extras.serve
#                                                # (no pytest)
#   scripts/run-tests.sh --router                # serving router smoke: the
#                                                # three data-plane chaos
#                                                # scenarios (preemption
#                                                # storm, brownout, drain
#                                                # wave) at 8 replicas on the
#                                                # virtual clock with the
#                                                # REAL placement / retry-
#                                                # budget / handoff-ledger
#                                                # policies in the loop (zero
#                                                # lost, zero duplicated,
#                                                # amplification <= the
#                                                # budget factor, SLO-burn
#                                                # never flaps), then the
#                                                # real-engine segment:
#                                                # temperature-0 routed
#                                                # output bit-equal to direct
#                                                # generate(), a mid-decode
#                                                # drain replayed exactly
#                                                # once on the survivor, the
#                                                # full RouterServer ->
#                                                # ServingServer HTTP
#                                                # topology, and queue-full
#                                                # 503 + Retry-After; banks
#                                                # ROUTER_SMOKE.json for
#                                                # BENCH extras.router
#                                                # (no pytest)
#   scripts/run-tests.sh --rollout               # live-weight-rollout smoke:
#                                                # a checkpoint watcher hot-
#                                                # swaps a published version
#                                                # into a live engine mid-
#                                                # decode (in-flight request
#                                                # finishes, pages stable,
#                                                # post-swap output bit-equal
#                                                # to generate() on the new
#                                                # weights), torn and corrupt
#                                                # publishes are rejected by
#                                                # the verify gate without
#                                                # touching serving state, a
#                                                # canary controller promotes
#                                                # a clean version and rolls
#                                                # back a divergent one
#                                                # exactly once (cooldown
#                                                # refuses the re-offer), and
#                                                # the weight_rollout chaos
#                                                # scenario passes all
#                                                # rollout invariants; banks
#                                                # ROLLOUT_SMOKE.json for
#                                                # BENCH extras.rollout
#                                                # (no pytest)
#   scripts/run-tests.sh --reqtrace              # request-tracing smoke: a
#                                                # router over two live
#                                                # engines with one rigged
#                                                # slow replica, every trace
#                                                # kept; routed tokens must
#                                                # bit-match generate() and
#                                                # the report's request-
#                                                # traces section must blame
#                                                # the slow decile on the
#                                                # queue hop with >= 90%
#                                                # attribution coverage;
#                                                # banks REQTRACE_SMOKE.json
#                                                # for BENCH extras.reqtrace
#                                                # (no pytest)
#   scripts/run-tests.sh --lint                  # graftlint static analysis:
#                                                # JAX hazards (JX*), lock
#                                                # discipline (CC*), config/
#                                                # metric registry drift (RD*)
#                                                # over bigdl_tpu + scripts,
#                                                # gated on the checked-in
#                                                # .graftlint-baseline.json
#                                                # (also runs in tier-1 via
#                                                # tests/test_lint.py::
#                                                # test_repo_is_clean)
#   scripts/run-tests.sh --fleet                 # fleet-scale control-plane
#                                                # simulator: the chaos
#                                                # scenario matrix (diurnal
#                                                # wave, stragglers,
#                                                # partition, cascading
#                                                # preemptions, flapping +
#                                                # poisoned sink, latency
#                                                # wave) at 200 synthetic
#                                                # hosts against the REAL
#                                                # autoscaler / alert engine
#                                                # / fleet aggregator on a
#                                                # virtual clock; all
#                                                # invariants must pass
#                                                # (no-flap convergence,
#                                                # exactly-once alert
#                                                # episodes, O(hosts)
#                                                # aggregation, conservative
#                                                # scrape degradation, free
#                                                # preemption restarts);
#                                                # banks FLEET_SIM.json for
#                                                # BENCH extras.fleet
#                                                # (no pytest)
#   scripts/run-tests.sh --fleetobs              # fleet-scale metrics
#                                                # pipeline smoke: the three
#                                                # pinned invariants at 1000
#                                                # simulated hosts on a
#                                                # virtual clock with real
#                                                # registries — hierarchical
#                                                # rollup bit-equal to the
#                                                # flat merge (fleet p99
#                                                # identical), top-K
#                                                # cardinality + memory +
#                                                # scrape-wall bounds, and
#                                                # skewed/partitioned hosts
#                                                # excluded-and-accounted —
#                                                # plus the 1000-address
#                                                # bounded scrape pool and a
#                                                # retention-store
#                                                # downsample/replay pass;
#                                                # banks FLEETOBS_SMOKE.json
#                                                # for BENCH extras.fleetobs
#                                                # (no pytest)
#   scripts/run-tests.sh --prof                  # continuous-profiling +
#                                                # debug-bundle smoke: a
#                                                # rigged run with one
#                                                # synthetically hot span
#                                                # (must take >= 50% of the
#                                                # profiler's self-time at
#                                                # < 1% measured overhead),
#                                                # one fired alert that must
#                                                # cut exactly ONE manifest-
#                                                # valid black-box bundle
#                                                # (profile + traces +
#                                                # metrics + ring inside),
#                                                # /profilez + /debugz over
#                                                # live HTTP, and the
#                                                # report's profiles section
#                                                # (text + --json); banks
#                                                # PROF_SMOKE.json for BENCH
#                                                # extras.prof (no pytest)
#   scripts/run-tests.sh --live                  # live-telemetry smoke: a
#                                                # 2-host run with /metrics +
#                                                # /healthz servers on
#                                                # ephemeral ports, scraped
#                                                # mid-run; fleet snapshot
#                                                # merged from both; a goodput
#                                                # SLO alert fires during a
#                                                # starved window and resolves
#                                                # after; report --watch
#                                                # --once renders the alerts
#                                                # section; the supervisor
#                                                # hang watchdog restarts a
#                                                # deliberately wedged child
#                                                # (no pytest)
# The chaos and obs specs are deterministic and part of the default
# selection; the flags are the focused loops for hacking on those layers.
set -euo pipefail
cd "$(dirname "$0")/.."

export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"
export JAX_PLATFORMS=cpu

MARKER=()
if [[ "${1:-}" == "--chaos" ]]; then
  shift
  MARKER=(-m chaos)
elif [[ "${1:-}" == "--trace" ]]; then
  shift
  MARKER=(-m obs)
elif [[ "${1:-}" == "--obs-report" ]]; then
  shift
  exec python scripts/obs_smoke.py "$@"
elif [[ "${1:-}" == "--elastic" ]]; then
  shift
  exec python scripts/elastic_smoke.py "$@"
elif [[ "${1:-}" == "--goodput" ]]; then
  shift
  exec python scripts/goodput_smoke.py "$@"
elif [[ "${1:-}" == "--tune" ]]; then
  shift
  exec python scripts/tune_smoke.py "$@"
elif [[ "${1:-}" == "--lint" ]]; then
  shift
  exec python -m bigdl_tpu.analysis.lint "$@"
elif [[ "${1:-}" == "--prof" ]]; then
  shift
  exec python scripts/prof_smoke.py "$@"
elif [[ "${1:-}" == "--live" ]]; then
  shift
  exec python scripts/live_smoke.py "$@"
elif [[ "${1:-}" == "--fleet" ]]; then
  shift
  exec python scripts/fleet_sim.py "$@"
elif [[ "${1:-}" == "--fleetobs" ]]; then
  shift
  exec python scripts/fleetobs_smoke.py "$@"
elif [[ "${1:-}" == "--autoscale" ]]; then
  shift
  exec python scripts/autoscale_smoke.py "$@"
elif [[ "${1:-}" == "--wire" ]]; then
  shift
  exec python scripts/wire_smoke.py "$@"
elif [[ "${1:-}" == "--overlap" ]]; then
  shift
  exec python scripts/overlap_smoke.py "$@"
elif [[ "${1:-}" == "--serve" ]]; then
  shift
  exec python scripts/serve_smoke.py "$@"
elif [[ "${1:-}" == "--router" ]]; then
  shift
  exec python scripts/router_smoke.py "$@"
elif [[ "${1:-}" == "--reqtrace" ]]; then
  shift
  exec python scripts/reqtrace_smoke.py "$@"
elif [[ "${1:-}" == "--rollout" ]]; then
  shift
  exec python scripts/rollout_smoke.py "$@"
fi

# tier-1 wall clock is budgeted (ROADMAP: 870s) — print where the suite
# sits so creeping cost is visible on every run, not just when it blows
START=$(date +%s)
set +e
python -m pytest tests/ -q "${MARKER[@]}" "$@"
rc=$?
set -e
ELAPSED=$(( $(date +%s) - START ))
BUDGET=870
echo "[run-tests] wall clock: ${ELAPSED}s of the ${BUDGET}s tier-1 budget ($(( ELAPSED * 100 / BUDGET ))%)"
exit $rc
