"""Distributed-observability specs (ISSUE 3): trace-shard merging with
clock alignment, collective-traffic accounting, the run-report CLI, the
perf-regression gate + flight recorder, and the
one-lock-per-scrape histogram parity.

The acceptance gates live here: a 2-host (simulated, CPU) traced run
merges into one Perfetto-loadable timeline with host-tagged,
clock-aligned spans; ``bigdl_collective_bytes_total`` matches
hand-computed byte counts for the f32 psum_scatter AND the int8
blockwise reduce-scatter paths; and the regression gate flags a
synthetic 2x step-time slowdown while passing on the repo's real
BENCH_r*.json trajectory.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from bigdl_tpu import obs
from bigdl_tpu.engine import Engine
from bigdl_tpu.nn import ClassNLLCriterion, Linear, LogSoftMax, ReLU, Sequential
from bigdl_tpu.obs import aggregate, collectives as C, regress, report
from bigdl_tpu.obs.metrics import MetricsRegistry
from bigdl_tpu.obs.trace import Tracer
from bigdl_tpu.optim import DistriOptimizer, LocalOptimizer, SGD, Trigger
from bigdl_tpu.resilience import reset_injector

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    for var in ("BIGDL_OBS", "BIGDL_TRACE_DIR", "BIGDL_METRICS_DIR",
                "BIGDL_FAULT_PLAN", "BIGDL_REGRESS_TOLERANCE",
                "BIGDL_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    reset_injector()
    obs.reset()
    yield
    obs.reset()
    reset_injector()


def _toy(n=256, d=16, k=4, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(d, k)
    x = rng.randn(n, d).astype(np.float32)
    y = (np.argmax(x @ w, axis=1) + 1).astype(np.float32)
    return x, y


def _model(d=16, k=4):
    return Sequential().add(Linear(d, 32)).add(ReLU()).add(Linear(32, k)) \
        .add(LogSoftMax())


def _counter_value(op, dtype):
    fam = obs.get_registry().counter(
        "bigdl_collective_bytes_total", labels=("op", "dtype"))
    return fam.labels(op=op, dtype=dtype).value


def _gauge_value(name, **labels):
    fam = obs.get_registry().gauge(name, labels=tuple(labels) or ())
    return (fam.labels(**labels) if labels else fam.labels()).value


# ----------------------------------------------------------- cost model
class TestCostModel:
    def test_dtype_bytes(self):
        assert C.dtype_bytes("float32") == 4
        assert C.dtype_bytes("bfloat16") == 2
        assert C.dtype_bytes("int8") == 1
        import jax.numpy as jnp

        assert C.dtype_bytes(jnp.bfloat16) == 2
        assert C.dtype_bytes(jnp.zeros((1,), jnp.float32).dtype) == 4

    def test_ring_formulas(self):
        # 8-way ring, 1024 f32 elements = 4096 payload bytes
        assert C.reduce_scatter_bytes(1024, "float32", 8) == 4096 * 7 / 8
        assert C.all_gather_bytes(1024, "float32", 8) == 4096 * 7 / 8
        assert C.all_reduce_bytes(1024, "float32", 8) == 2 * 4096 * 7 / 8
        assert C.all_to_all_bytes(1024, "float32", 8) == 4096 * 7 / 8
        assert C.ppermute_bytes(1024, "float32", hops=3) == 3 * 4096

    def test_single_device_axis_is_free(self):
        for fn in (C.all_reduce_bytes, C.reduce_scatter_bytes,
                   C.all_gather_bytes, C.all_to_all_bytes):
            assert fn(1024, "float32", 1) == 0.0

    def test_int8_blockwise_exchange(self):
        ex = C.int8_blockwise_exchange_bytes(768, 8, 16)
        assert ex["int8"] == 768 * 7 / 8           # int8 payload
        assert ex["float32"] == 48 * 4 * 7 / 8     # 8*6 f32 scales

    def test_step_footprint_bind_commit(self):
        reg = MetricsRegistry()
        fp = C.StepFootprint()
        fp.add("psum_scatter", "float32", 100.0)
        fp.add("psum_scatter", "float32", 50.0)   # merges per (op,dtype)
        fp.add("all_gather", "float32", 25.0)
        assert fp.total() == 175.0
        fp.bind(reg)
        fp.commit()
        fp.commit()
        ctr = reg.counter("bigdl_collective_bytes_total",
                          labels=("op", "dtype"))
        assert ctr.labels(op="psum_scatter", dtype="float32").value == 300.0
        assert ctr.labels(op="all_gather", dtype="float32").value == 50.0
        g = reg.gauge("bigdl_collective_bytes_per_step",
                      labels=("op", "dtype"))
        assert g.labels(op="psum_scatter", dtype="float32").value == 150.0


# -------------------------------------------- golden DistriOptimizer bytes
class TestCollectiveGolden:
    """Hand-computed wire bytes for the model Linear(16,32)+Linear(32,4):
    676 flat params, 8-way mesh."""

    def _run(self, steps, **kw):
        Engine.reset()
        Engine.init()
        try:
            x, y = _toy(n=32 * steps)
            opt = DistriOptimizer(_model(), (x, y), ClassNLLCriterion(),
                                  batch_size=32, **kw)
            opt.set_optim_method(SGD(learningrate=0.1))
            opt.set_end_when(Trigger.max_iteration(steps))
            opt.optimize()
        finally:
            Engine.reset()
        return opt

    def test_f32_psum_scatter_golden(self):
        steps = 20
        self._run(steps, wire_dtype="float32")
        # pad 676 -> 680; psum_scatter & all_gather: 680*4 bytes * 7/8
        per_step = 680 * 4 * 7 / 8
        assert _counter_value("psum_scatter", "float32") == per_step * steps
        assert _counter_value("all_gather", "float32") == per_step * steps
        # scalar all-reduces: grad-norm psum, guard pmin, loss pmean
        scalar = 2 * 4 * 7 / 8
        assert _counter_value("psum", "float32") == scalar * steps
        assert _counter_value("pmin", "float32") == scalar * steps
        assert _counter_value("pmean", "float32") == scalar * steps
        assert _gauge_value("bigdl_collective_bytes_per_step",
                            op="psum_scatter", dtype="float32") == per_step
        assert _gauge_value("bigdl_collective_wire_savings_ratio",
                            path="grad") == pytest.approx(1.0)

    def test_bf16_wire_halves_exchange(self):
        steps = 5
        self._run(steps, wire_dtype="bfloat16")
        per_step = 680 * 2 * 7 / 8
        assert _counter_value("psum_scatter",
                              "bfloat16") == per_step * steps
        # the gathered weights stay f32
        assert _counter_value("all_gather",
                              "float32") == 680 * 4 * 7 / 8 * steps
        assert _gauge_value("bigdl_collective_wire_savings_ratio",
                            path="grad") == pytest.approx(2.0)

    def test_int8_blockwise_golden(self):
        steps = 5
        self._run(steps, wire_dtype="int8", int8_block=16)
        # quantum 8*16=128: pad 676 -> 768; staged ring: 7 hops x
        # 96-elem chunk payload + 7 hops x 6 f32 chunk scales — the
        # SAME totals as the old quantize-once all_to_all pair, now
        # moved through every reduction stage (op label ring_rs)
        q_bytes = 7 * 96 * 1                 # int8 payload per hop
        s_bytes = 7 * 6 * 4                  # f32 scales per hop
        assert q_bytes == 768 * 1 * 7 / 8    # a2a-model equivalence
        assert _counter_value("ring_rs", "int8") == q_bytes * steps
        assert _counter_value("ring_rs", "float32") == s_bytes * steps
        # EQuARX headline: f32 exchange over int8+scales
        expect = (768 * 4 * 7 / 8) / (q_bytes + s_bytes)
        assert _gauge_value("bigdl_collective_wire_savings_ratio",
                            path="grad") == pytest.approx(expect)
        assert expect == pytest.approx(3.2)

    def test_fp8_ef_golden(self):
        """fp8 wire + error feedback: same 1-byte staged-ring budget
        as int8 (the EF residual rides device-local HBM, never the
        wire), labeled with the fp8 dtype."""
        steps = 3
        self._run(steps, wire_dtype="fp8_e4m3", wire_block=16,
                  wire_ef=True)
        q_bytes = 7 * 96 * 1
        s_bytes = 7 * 6 * 4
        assert _counter_value("ring_rs", "float8_e4m3fn") == \
            q_bytes * steps
        assert _counter_value("ring_rs", "float32") == s_bytes * steps
        assert _gauge_value("bigdl_collective_wire_savings_ratio",
                            path="grad") == pytest.approx(3.2)

    def test_footprint_trace_event(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path))
        obs.reset()
        self._run(3, wire_dtype="float32")
        obs.get_tracer().flush()
        shards = aggregate.read_shards(str(tmp_path))
        evs = [r for s in shards for r in s.records
               if r["name"] == "collective.footprint"]
        assert evs
        a = evs[0]["attrs"]
        assert a["n_shards"] == 8 and a["padded_elems"] == 680
        assert a["breakdown"]["psum_scatter:float32"] == 680 * 4 * 7 / 8


# ------------------------------------------------------- shard aggregation
def _tracer_with_skew(tmp_path, host, skew_s):
    t = Tracer(str(tmp_path), host_id=host)
    # simulate a host whose wall clock runs `skew_s` ahead: every
    # recorded wall_time shifts by the skew while real emission time
    # (this process) is shared — exactly the NTP-skew failure mode
    t._epoch_wall += skew_s
    return t


class TestAggregate:
    def test_four_hosts_skewed_clocks_align_and_stay_monotone(
            self, tmp_path):
        skews = {0: 0.0, 1: 7.5, 2: -3.25, 3: 42.0}
        tracers = {h: _tracer_with_skew(tmp_path, h, s)
                   for h, s in skews.items()}
        for h, t in tracers.items():
            t.event("engine.init_barrier", host=h, processes=4)
        # interleaved spans in a known REAL-time order
        for i in range(6):
            for h, t in tracers.items():
                with t.span("iteration", step=i, host_order=h):
                    pass
        for t in tracers.values():
            t.close()

        doc = aggregate.merge_shards(aggregate.read_shards(str(tmp_path)))
        evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
        # monotone timeline
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)
        # host-tagged
        assert {e["args"]["host"] for e in evs} == {0, 1, 2, 3}
        # barriers coincide after alignment (emitted microseconds apart
        # in real time; the 40s injected skews must be gone)
        bts = [e["ts"] for e in evs if e["name"] == "engine.init_barrier"]
        assert len(bts) == 4
        # emitted microseconds apart in real time; the 7.5/-3.25/42s
        # injected skews must be gone (spread < 5ms, was up to 45s)
        assert max(bts) - min(bts) < 5000
        # the recorded offsets expose the skew instead of hiding it:
        # offset_i - offset_j == skew_j - skew_i
        offs = doc["otherData"]["offsets_s"]
        o = {h: offs[f"host{h}/pid{os.getpid()}"] for h in skews}
        for h in skews:
            assert (o[h] - o[0]) == pytest.approx(
                skews[0] - skews[h], abs=0.05)
        assert doc["otherData"]["unaligned"] == []

    def test_shard_without_barrier_is_flagged_not_dropped(self, tmp_path):
        a = Tracer(str(tmp_path), host_id=0)
        a.event("engine.init_barrier")
        a.event("x")
        a.close()
        b = Tracer(str(tmp_path), host_id=1)  # no barrier (crashed early)
        b.event("y")
        b.close()
        doc = aggregate.merge_shards(aggregate.read_shards(str(tmp_path)))
        assert doc["otherData"]["unaligned"] == [f"host1/pid{os.getpid()}"]
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"x", "y"} <= names

    def test_merge_empty_raises_and_cli_reports(self, tmp_path):
        with pytest.raises(ValueError):
            aggregate.merge_shards([])
        assert aggregate.main([str(tmp_path)]) == 1  # empty dir -> rc 1

    def test_cli_writes_perfetto_loadable_merge(self, tmp_path, capsys):
        t = Tracer(str(tmp_path), host_id=3)
        t.event("engine.init_barrier")
        with t.span("iteration", step=1):
            pass
        t.close()
        out = str(tmp_path / "merged.trace.json")
        assert aggregate.main([str(tmp_path), "-o", out]) == 0
        summary = json.loads(capsys.readouterr().out.strip())
        assert summary["hosts"] == [3]
        doc = json.load(open(out))
        assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert spans and all(
            {"name", "ts", "dur", "pid", "tid"} <= set(e) for e in spans)


# --------------------------------------- 2-host acceptance (subprocesses)
_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, os.environ["BIGDL_REPO"])
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") \\
        + " --xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.nn import (ClassNLLCriterion, Linear, LogSoftMax, ReLU,
                              Sequential)
    from bigdl_tpu.optim import DistriOptimizer, SGD, Trigger

    Engine.init()
    rng = np.random.RandomState(0)
    w = rng.randn(16, 4)
    x = rng.randn(160, 16).astype(np.float32)
    y = (np.argmax(x @ w, axis=1) + 1).astype(np.float32)
    model = Sequential().add(Linear(16, 32)).add(ReLU()) \\
        .add(Linear(32, 4)).add(LogSoftMax())
    opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(), batch_size=32)
    opt.set_optim_method(SGD(learningrate=0.1))
    opt.set_end_when(Trigger.max_iteration(5))
    opt.optimize()
    assert opt.state["neval"] == 6
""")


class TestTwoHostMergeAcceptance:
    def test_two_host_run_merges_host_tagged_and_aligned(self, tmp_path):
        """THE acceptance gate: two simulated hosts (real OS processes,
        CPU devices) trace into one shared dir; the merge is a single
        Perfetto-loadable timeline, host-tagged, barrier-aligned."""
        trace_dir = str(tmp_path / "trace")
        for host in (0, 1):
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)
            env.update({
                "BIGDL_REPO": REPO,
                "BIGDL_PROCESS_ID": str(host),
                "BIGDL_TRACE_DIR": trace_dir,
                "BIGDL_METRICS_DIR": str(tmp_path / "metrics"),
                "JAX_PLATFORMS": "cpu",
            })
            p = subprocess.run([sys.executable, "-c", _WORKER], env=env,
                               capture_output=True, text=True, timeout=240)
            assert p.returncode == 0, p.stdout + p.stderr

        out = str(tmp_path / "merged.trace.json")
        summary = aggregate.merge_trace_dir(trace_dir, out)
        assert summary["hosts"] == [0, 1]
        assert summary["unaligned"] == []
        doc = json.load(open(out))  # Perfetto-loadable: valid JSON +
        evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
        assert evs, "merged timeline is empty"
        for e in evs:  # chrome trace_event required keys
            assert {"name", "ph", "ts", "pid", "tid", "args"} <= set(e)
        # host-tagged spans from BOTH hosts, monotone timeline
        assert {e["args"]["host"] for e in evs} == {0, 1}
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)
        # clock-aligned: the two barrier events (emitted seconds apart
        # in real time, sequential processes) coincide after alignment
        bts = {e["args"]["host"]: e["ts"] for e in evs
               if e["name"] == "engine.init_barrier"}
        assert set(bts) == {0, 1}
        assert abs(bts[0] - bts[1]) < 1.0  # < 1us after alignment
        # both hosts trained: per-host iteration spans survive the merge
        iters = [e for e in evs if e["name"] == "iteration"]
        assert len(iters) == 10  # 5 steps x 2 hosts
        # the report CLI consumes the same dirs
        rep = report.build_report(trace_dir, str(tmp_path / "metrics"))
        assert rep["n_hosts"] == 2
        assert all(h["steps"] == 5 for h in rep["hosts"].values())
        text = report.render_text(rep)
        assert "psum_scatter" in text and "step times" in text


# ------------------------------------------------------ regression gate
def _bench_result(platform="cpu", value=100.0, p50=0.05):
    return {"metric": "m", "value": value, "platform": platform,
            "extras": {"step_time_s": p50,
                       "obs_runtime": {"step_time_p50_s": p50}}}


def _write_traj(path, results):
    os.makedirs(path, exist_ok=True)
    for i, r in enumerate(results, 1):
        with open(os.path.join(path, f"BENCH_r{i:02d}.json"), "w") as fh:
            json.dump({"parsed": r}, fh)


class TestRegressionGate:
    def test_flags_synthetic_2x_slowdown(self, tmp_path):
        traj = str(tmp_path / "traj")
        _write_traj(traj, [_bench_result(p50=0.05),
                           _bench_result(p50=0.06)])
        verdict = regress.gate(_bench_result(value=50.0, p50=0.10), traj)
        assert verdict["status"] == "violation"
        assert verdict["step_time_ratio"] == pytest.approx(2.0)
        assert any("step time" in v for v in verdict["violations"])

    def test_passes_within_tolerance(self, tmp_path):
        traj = str(tmp_path / "traj")
        _write_traj(traj, [_bench_result(p50=0.05)])
        verdict = regress.gate(_bench_result(p50=0.06, value=90.0), traj)
        assert verdict["status"] == "pass"
        assert verdict["violations"] == []

    def test_platform_mismatch_is_no_baseline(self, tmp_path):
        traj = str(tmp_path / "traj")
        _write_traj(traj, [_bench_result(platform="cpu")])
        verdict = regress.gate(
            _bench_result(platform="TPU v5 lite"), traj)
        assert verdict["status"] == "no_baseline"

    def test_tolerance_env_knob(self, tmp_path, monkeypatch):
        traj = str(tmp_path / "traj")
        _write_traj(traj, [_bench_result(p50=0.05)])
        monkeypatch.setenv("BIGDL_REGRESS_TOLERANCE", "1.1")
        verdict = regress.check(_bench_result(p50=0.06),
                                regress.load_trajectory(traj))
        assert verdict["status"] == "violation"  # 1.2x > 1.1x

    def test_passes_on_the_real_trajectory(self):
        """Acceptance: the repo's own BENCH_r*.json rounds gate clean
        when the fresh run equals the trajectory's best round."""
        traj = regress.load_trajectory(REPO)
        assert len(traj) >= 3  # r01..r05 exist
        best = min((e for e in traj if e["step_time_s"]),
                   key=lambda e: e["step_time_s"])
        fresh = {"metric": "m", "value": best["value"],
                 "platform": best["platform"],
                 "extras": {"step_time_s": best["step_time_s"]}}
        verdict = regress.check(fresh, traj)
        assert verdict["status"] == "pass", verdict

    def test_old_artifacts_without_obs_runtime_still_compare(
            self, tmp_path):
        traj = str(tmp_path / "traj")
        old = {"metric": "m", "value": 100.0, "platform": "cpu",
               "extras": {"step_time_s": 0.05}}  # pre-obs round
        _write_traj(traj, [old])
        verdict = regress.gate(_bench_result(p50=0.2), traj)
        assert verdict["status"] == "violation"

    def test_violation_dumps_flight_bundle_from_live_ring(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path / "trace"))
        obs.reset()
        tracer = obs.get_tracer()
        with tracer.span("iteration", step=1):
            pass
        obs.get_registry().counter("bigdl_t_total").inc(3)
        traj = str(tmp_path / "traj")
        _write_traj(traj, [_bench_result(p50=0.05)])
        verdict = regress.gate(_bench_result(p50=0.5), traj,
                               flight_dir=str(tmp_path / "flight"))
        assert verdict["status"] == "violation"
        bundle = json.load(open(verdict["flight_recorder"]))
        assert bundle["kind"] == "bigdl_flight_recorder"
        assert bundle["spans_source"] == "ring_buffer"
        assert any(r["name"] == "iteration" for r in bundle["spans"])
        assert "bigdl_t_total" in bundle["metrics"]["metrics"]
        assert bundle["verdict"]["status"] == "violation"

    def test_offline_bundle_uses_shard_tail(self, tmp_path):
        t = Tracer(str(tmp_path / "trace"), host_id=0)
        t.event("postmortem_marker")
        t.close()
        obs.reset()  # no live tracer in "this" process
        bundle = regress.flight_bundle("r", str(tmp_path / "trace"))
        assert bundle["spans_source"] == "shard_tail"
        assert any(r["name"] == "postmortem_marker"
                   for r in bundle["spans"])

    def test_bench_in_process_gate_hook(self, tmp_path):
        """bench.py's _apply_regression_gate path: gate() on the final
        result dict, verdict riding in extras.regression."""
        traj = str(tmp_path / "traj")
        _write_traj(traj, [_bench_result(p50=0.01)])
        res = _bench_result(p50=0.5)
        verdict = regress.gate(res, traj)
        res["extras"]["regression"] = verdict
        assert res["extras"]["regression"]["status"] == "violation"


# --------------------------------------- one-lock-per-scrape histograms
class TestHistogramScrapeParity:
    def test_sum_count_buckets_consistent_under_concurrent_add(self):
        """Satellite gate: while 8 threads hammer observe(0.01), every
        scrape (snapshot AND exposition) must be internally consistent —
        the +Inf cumulative bucket equals _count and _sum == 0.01 *
        _count within fp error.  Pre-fix, sum/count were read outside
        the bucket-copy lock and could disagree."""
        reg = MetricsRegistry()
        h = reg.histogram("h_seconds", buckets=(0.005, 0.02)).labels()
        stop = threading.Event()
        V = 0.01

        def work():
            while not stop.is_set():
                h.observe(V)

        threads = [threading.Thread(target=work) for _ in range(8)]
        [t.start() for t in threads]
        try:
            for _ in range(300):
                snap = reg.snapshot()["metrics"]["h_seconds"]["samples"][0]
                assert snap["buckets"][-1][1] == snap["count"]
                assert snap["sum"] == pytest.approx(
                    V * snap["count"], rel=1e-9)
                text = reg.to_prometheus()
                vals = {}
                for line in text.splitlines():
                    if line.startswith("h_seconds_count"):
                        vals["count"] = float(line.rsplit(" ", 1)[1])
                    elif line.startswith("h_seconds_sum"):
                        vals["sum"] = float(line.rsplit(" ", 1)[1])
                    elif 'le="+Inf"' in line:
                        vals["inf"] = float(line.rsplit(" ", 1)[1])
                assert vals["inf"] == vals["count"]
                assert vals["sum"] == pytest.approx(
                    V * vals["count"], rel=1e-9)
        finally:
            stop.set()
            [t.join() for t in threads]

    def test_optim_metrics_snapshot_consistent(self):
        from bigdl_tpu.optim.metrics import Metrics

        m = Metrics()
        stop = threading.Event()

        def work():
            while not stop.is_set():
                m.add("computing time", 0.01)

        t = threading.Thread(target=work)
        t.start()
        try:
            for _ in range(200):
                snap = m.snapshot()["computing time"]
                assert snap["total"] == pytest.approx(
                    0.01 * snap["count"], rel=1e-9)
        finally:
            stop.set()
            t.join()


# -------------------------------------------------- parallel/ accounting
class TestParallelAccounting:
    def test_ring_attention_accounts_ppermute(self):
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.parallel.ring import ring_attention_sharded

        mesh = Engine.build_mesh({"seq": 8})
        b, hds, t, d = 1, 2, 64, 8
        q = jnp.zeros((b, hds, t, d), jnp.float32)
        before = _counter_value("ppermute", "float32")
        ring_attention_sharded(q, q, q, mesh, seq_axis="seq")
        moved = _counter_value("ppermute", "float32") - before
        # K and V blocks (size/8 elements, 4B) x 7 hops each
        assert moved == 2 * (b * hds * t * d // 8) * 4 * 7

    def test_pipeline_accounts_ppermute_and_psum(self):
        import jax.numpy as jnp

        from bigdl_tpu.parallel.pipeline import pipelined

        mesh = Engine.build_mesh({"pipe": 8})
        stage = lambda p, x: x + p["b"]
        run = pipelined(stage, mesh, "pipe")
        m, mb, dim = 4, 2, 16
        params = {"b": jnp.zeros((8, dim))}
        x = jnp.ones((m, mb, dim), jnp.float32)
        before_pp = _counter_value("ppermute", "float32")
        before_ps = _counter_value("psum", "float32")
        run(params, x)
        assert _counter_value("ppermute", "float32") - before_pp == \
            (mb * dim) * 4 * (m + 8 - 1)
        assert _counter_value("psum", "float32") - before_ps == \
            2 * (m * mb * dim) * 4 * 7 / 8

    def test_moe_accounts_all_to_all_when_expert_sharded(self):
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.parallel.moe import MoE

        mesh = Engine.build_mesh({"expert": 8})
        moe = MoE(dim=8, hidden=16, n_experts=8, mesh=mesh)
        x = jnp.ones((2, 4, 8), jnp.float32)
        before = _counter_value("all_to_all", "float32")
        with mesh:
            jax.jit(moe.update_output_pure)(moe.params(), x)
        # accounting fired at trace time, exactly once per compile
        moved = _counter_value("all_to_all", "float32") - before
        s, e, d = 8, 8, 8
        cap = int(np.ceil(1.25 * s * 1 / e))
        assert moved == 2 * (e * cap * d) * 4 * 7 / 8

    def test_tp_shard_params_accounts_placement(self):
        from bigdl_tpu.parallel.tensor_parallel import shard_params

        mesh = Engine.build_mesh({"model": 8})
        params = {"attn": {"wq": np.zeros((32, 16), np.float32),
                           "other": np.zeros((4, 4), np.float32)}}
        before = _counter_value("tp_shard_params", "float32")
        shard_params(params, mesh)
        # only wq matches a rule and splits: 32*16 f32
        assert _counter_value("tp_shard_params", "float32") - before == \
            32 * 16 * 4
