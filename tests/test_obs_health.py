"""Training-health telemetry specs (ISSUE 4): per-layer numerics
computed inside the jitted step, non-finite localization naming the
planted layer in BOTH optimizers, the fetch-cadence / zero-overhead
contract, the numerics anomaly detector, HLO-derived FLOPs + MFU, the
and the health fan-out into report / flight bundle / TensorBoard.  (The
profiler annotation and the span tracer became one path in
obs/trace.py: tests/test_trace_one_clock.py.)"""

import json
import os

import numpy as np
import pytest

from bigdl_tpu import obs
from bigdl_tpu.engine import Engine
from bigdl_tpu.nn import ClassNLLCriterion, Linear, LogSoftMax, ReLU, Sequential
from bigdl_tpu.obs import health as H
from bigdl_tpu.obs import regress, report
from bigdl_tpu.obs.runtime import RuntimeStats, instrument_jit
from bigdl_tpu.optim import DistriOptimizer, LocalOptimizer, SGD, Trigger
from bigdl_tpu.resilience import reset_injector

pytestmark = pytest.mark.obs

NAMES = ["0/bias", "0/weight", "2/bias", "2/weight"]


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    for var in ("BIGDL_OBS", "BIGDL_TRACE_DIR", "BIGDL_METRICS_DIR",
                "BIGDL_FAULT_PLAN", "BIGDL_HEALTH_EVERY",
                "BIGDL_HEALTH_WINDOW", "BIGDL_HEALTH_SPIKE_FACTOR",
                "BIGDL_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    reset_injector()
    obs.reset()
    yield
    obs.reset()
    reset_injector()


def _toy(n=160, d=16, k=4, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(d, k)
    x = rng.randn(n, d).astype(np.float32)
    y = (np.argmax(x @ w, axis=1) + 1).astype(np.float32)
    return x, y


def _model(d=16, k=4):
    return Sequential().add(Linear(d, 32)).add(ReLU()).add(Linear(32, k)) \
        .add(LogSoftMax())


# ------------------------------------------------------------ device math
class TestDeviceStats:
    def test_layer_names_and_sizes_follow_flat_order(self):
        m = _model()
        names = H.layer_names(m.params())
        sizes = H.layer_sizes(m.params())
        assert names == NAMES
        assert sizes == [32, 32 * 16, 4, 4 * 32]
        # the flat (ravel_pytree) layout concatenates in the same order
        from jax.flatten_util import ravel_pytree

        flat, _ = ravel_pytree(m.params())
        assert int(flat.size) == sum(sizes)

    def test_tree_stats_exact_values(self):
        import jax
        import jax.numpy as jnp

        g = {"a": jnp.asarray([3.0, 4.0]), "b": jnp.asarray([[1.0, 1.0]])}
        p = {"a": jnp.asarray([2.0, 0.0]), "b": jnp.asarray([[0.0, 2.0]])}
        q = {"a": jnp.asarray([2.0, 1.0]), "b": jnp.asarray([[0.0, 2.0]])}
        stats = np.asarray(jax.jit(H.tree_layer_stats)(g, p, q))
        np.testing.assert_allclose(stats[0], [25.0, 4.0, 1.0, 0.0])
        np.testing.assert_allclose(stats[1], [2.0, 4.0, 0.0, 0.0])
        summ = H.summarize(stats, ["a", "b"])
        assert summ["layers"]["a"]["grad_norm"] == pytest.approx(5.0)
        assert summ["layers"]["a"]["update_ratio"] == pytest.approx(0.5)
        assert summ["global_grad_norm"] == pytest.approx(np.sqrt(27.0))

    def test_tree_stats_localize_planted_nan_exactly(self):
        """LocalOptimizer's device math: a NaN planted in ONE known leaf
        is attributed to exactly that layer."""
        import jax
        import jax.numpy as jnp

        m = _model()
        p = m.params()
        g = jax.tree.map(jnp.ones_like, p)
        # plant into 2/weight only (tree path == metric label)
        g["2"]["weight"] = g["2"]["weight"].at[1, 3].set(jnp.nan)
        stats = np.asarray(jax.jit(H.tree_layer_stats)(g, p, p))
        assert H.nonfinite_layers(stats, NAMES) == ["2/weight"]
        assert stats[NAMES.index("2/weight"), H.NONFINITE] == 1.0

    def test_flat_shard_stats_localize_and_match_tree(self):
        """DistriOptimizer's device math: the segment-summed, psum'd
        shard stats name exactly the planted layer and agree with the
        direct per-layer computation."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from bigdl_tpu.optim.distri_optimizer import _shard_map

        sizes = [32, 512, 4, 128]
        names = ["a", "b", "c", "d"]
        total = sum(sizes)
        n = 8
        pad = (-total) % n
        shard_len = (total + pad) // n
        rng = np.random.RandomState(3)
        g = rng.randn(total).astype(np.float32)
        w = rng.randn(total).astype(np.float32)
        nw = w - 0.1 * g
        off_c = sizes[0] + sizes[1]
        g[off_c + 2] = np.nan       # plant in layer "c" only
        boundaries = jnp.asarray(np.cumsum(sizes), jnp.int32)
        mesh = Engine.build_mesh({"data": 8})

        def body(gp, wp, nwp):
            idx = jax.lax.axis_index("data")
            return H.flat_shard_stats(gp, wp, nwp, idx * shard_len,
                                      boundaries, "data")

        fn = jax.jit(_shard_map(body, mesh, in_specs=(P("data"),) * 3,
                                out_specs=P()))
        zpad = lambda a: jnp.pad(jnp.asarray(a), (0, pad))
        stats = np.asarray(fn(zpad(g), zpad(w), zpad(nw - w + w)))
        assert H.nonfinite_layers(stats, names) == ["c"]
        edges = [0] + list(np.cumsum(sizes))
        for i in range(4):
            s, e = edges[i], edges[i + 1]
            if i == 2:
                assert stats[i, H.NONFINITE] == 1.0
                continue
            np.testing.assert_allclose(
                stats[i, H.GRAD_SQ], np.sum(g[s:e] ** 2), rtol=1e-5)
            np.testing.assert_allclose(
                stats[i, H.PARAM_SQ], np.sum(w[s:e] ** 2), rtol=1e-5)
            np.testing.assert_allclose(
                stats[i, H.UPDATE_SQ], np.sum((nw - w)[s:e] ** 2),
                rtol=1e-4)
            assert stats[i, H.NONFINITE] == 0.0


# ------------------------------------------------------------- the monitor
class TestHealthMonitor:
    def _stats(self, nonfinite_layer=None, grad=1.0):
        arr = np.tile([grad ** 2, 4.0, 0.01, 0.0], (4, 1)).astype(
            np.float64)
        if nonfinite_layer is not None:
            arr[NAMES.index(nonfinite_layer), H.NONFINITE] = 3.0
            arr[NAMES.index(nonfinite_layer), H.GRAD_SQ] = np.nan
        return arr

    def test_fetch_cadence(self):
        m = H.HealthMonitor(NAMES, every=3)
        for n in range(1, 13):
            m.on_step(n, self._stats(), True, 0.5)
        assert m.fetches == 4  # steps 3, 6, 9, 12

    def test_nonfinite_always_fetches_and_localizes_exactly(self,
                                                           tmp_path,
                                                           monkeypatch):
        monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path))
        obs.reset()
        m = H.HealthMonitor(NAMES, every=1000, tracer=obs.get_tracer())
        m.on_step(7, self._stats(nonfinite_layer="2/weight"), False, 1.0)
        assert m.fetches == 1   # cadence says no, the tripped guard says yes
        evs = [r for r in obs.get_tracer().recent()
               if r["name"] == "health.nonfinite_layers"]
        assert len(evs) == 1
        a = evs[0]["attrs"]
        assert a["first"] == "2/weight"
        assert a["layers"] == ["2/weight"]   # exactly the planted layer
        assert a["counts"] == {"2/weight": 3}
        ctr = m.registry.counter("bigdl_nonfinite_layers_total",
                                 labels=("layer",))
        assert ctr.labels(layer="2/weight").value == 1
        for other in ("0/bias", "0/weight", "2/bias"):
            assert ctr.labels(layer=other).value == 0

    def test_gauges_published_per_layer(self):
        m = H.HealthMonitor(NAMES, every=1)
        m.on_step(1, self._stats(grad=3.0), True, 0.5)
        g = m.registry.gauge("bigdl_grad_norm", labels=("layer",))
        assert g.labels(layer="0/weight").value == pytest.approx(3.0)
        r = m.registry.gauge("bigdl_update_ratio", labels=("layer",))
        assert r.labels(layer="2/bias").value == pytest.approx(0.1 / 2.0)

    def test_loss_spike_anomaly(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path))
        obs.reset()
        m = H.HealthMonitor(NAMES, every=10**9, tracer=obs.get_tracer(),
                            spike_factor=10.0)
        for n in range(1, 10):
            m.on_step(n, None, True, 0.5)
        m.on_step(10, None, True, 50.0)   # 100x the median
        assert m.anomalies == 1
        evs = [r for r in obs.get_tracer().recent()
               if r["name"] == "health.anomaly"]
        assert evs and evs[0]["attrs"]["kind"] == "loss_spike"
        ctr = m.registry.counter("bigdl_numerics_anomalies_total",
                                 labels=("kind",))
        assert ctr.labels(kind="loss_spike").value == 1

    def test_grad_norm_spike_anomaly(self):
        m = H.HealthMonitor(NAMES, every=1, spike_factor=10.0)
        for n in range(1, 10):
            m.on_step(n, self._stats(grad=1.0), True, 0.5)
        m.on_step(10, self._stats(grad=1000.0), True, 0.5)
        ctr = m.registry.counter("bigdl_numerics_anomalies_total",
                                 labels=("kind",))
        assert ctr.labels(kind="grad_norm_spike").value == 1

    def test_warmup_and_disabled_factor_do_not_fire(self):
        m = H.HealthMonitor(NAMES, every=1, spike_factor=10.0)
        for n in range(1, 6):   # < 8 observations: warmup
            m.on_step(n, self._stats(), True, 0.5)
        m.on_step(6, self._stats(), True, 9999.0)
        assert m.anomalies == 0
        m2 = H.HealthMonitor(NAMES, every=1, spike_factor=0.0)
        for n in range(1, 20):
            m2.on_step(n, self._stats(), True, 0.5 if n < 19 else 1e9)
        assert m2.anomalies == 0


# --------------------------------------------- LocalOptimizer integration
class TestLocalOptimizerHealth:
    def _opt(self, model=None, n=160):
        x, y = _toy(n)
        opt = LocalOptimizer(model or _model(), (x, y),
                             ClassNLLCriterion(), batch_size=32)
        opt.set_optim_method(SGD(learningrate=0.1))
        return opt

    def test_disabled_keeps_seed_signature_and_fetches_nothing(
            self, monkeypatch):
        """Acceptance: health off => the step compiles to the same
        5-output signature as the seed and there is NO health fetch
        site at all (the monitor, the only np.asarray caller, does not
        exist)."""
        monkeypatch.setenv("BIGDL_OBS", "1")   # obs on, health off
        obs.reset()
        opt = self._opt()
        opt.set_end_when(Trigger.max_iteration(3))
        opt.optimize()
        assert opt._health_monitor is None
        out = opt._build_train_step()(
            *self._step_args(opt))
        assert len(out) == 5   # seed signature: p, opt, mstate, loss, ok

    def test_enabled_adds_exactly_one_output_and_fetches_per_k(
            self, monkeypatch):
        monkeypatch.setenv("BIGDL_HEALTH_EVERY", "4")
        opt = self._opt(n=320)
        opt.set_end_when(Trigger.max_iteration(8))
        opt.optimize()
        m = opt._health_monitor
        assert m is not None
        assert m.fetches == 2       # steps 4 and 8 of 8
        out = opt._build_train_step()(*self._step_args(opt))
        assert len(out) == 6
        assert out[5].shape == (4, 4)   # (L layers, 4 stats)

    def _step_args(self, opt):
        import jax

        pvar = opt._init_params()
        mstate = opt.model.state()
        opt_state = opt._init_opt_state(pvar)
        x, y = _toy(32)
        inp, tgt = opt._put_batch(x, y)
        return pvar, opt_state, mstate, jax.random.key(0), inp, tgt

    def test_nan_grad_run_localizes_and_counts(self, tmp_path,
                                               monkeypatch):
        """Acceptance gate (LocalOptimizer): a nan_grad fault-injected
        run emits the localization trace event naming the first
        offending layer and bumps the per-layer counter."""
        monkeypatch.setenv("BIGDL_FAULT_PLAN", "step:2:nan_grad")
        monkeypatch.setenv("BIGDL_HEALTH_EVERY", "100")  # nonfinite only
        monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path))
        reset_injector()
        obs.reset()
        opt = self._opt()
        opt.set_end_when(Trigger.max_iteration(4))
        opt.optimize()
        assert opt.state["nonfinite_skips"] == 1
        assert opt._health_monitor.fetches == 1   # ONLY the guard trip
        evs = [r for r in obs.get_tracer().recent()
               if r["name"] == "health.nonfinite_layers"]
        assert len(evs) == 1
        a = evs[0]["attrs"]
        assert a["step"] == 2
        # the NaN enters through the poisoned input batch: the
        # input-adjacent layer is the first offender in flat order
        assert a["first"] == "0/bias"
        assert set(a["layers"]) == set(NAMES)
        ctr = obs.get_registry().counter("bigdl_nonfinite_layers_total",
                                         labels=("layer",))
        assert ctr.labels(layer="0/bias").value == 1
        assert ctr.labels(layer="2/weight").value == 1

    def test_tensorboard_health_scalars_roundtrip(self, tmp_path,
                                                  monkeypatch):
        from bigdl_tpu.visualization import TrainSummary

        monkeypatch.setenv("BIGDL_HEALTH_EVERY", "1")
        summary = TrainSummary(str(tmp_path), "health_app")
        opt = self._opt()
        opt.set_train_summary(summary)
        opt.set_end_when(Trigger.max_iteration(3))
        opt.optimize()
        pairs = summary.read_scalar("GradNorm/0/weight")
        assert [s for s, _ in pairs] == [1, 2, 3]
        assert all(np.isfinite(v) and v > 0 for _, v in pairs)
        ratios = summary.read_scalar("UpdateRatio/2/weight")
        assert len(ratios) == 3 and all(v > 0 for _, v in ratios)
        summary.close()


# --------------------------------------------- DistriOptimizer integration
class TestDistriOptimizerHealth:
    @pytest.fixture(autouse=True)
    def _engine(self):
        Engine.reset()
        Engine.init()
        yield
        Engine.reset()

    def _opt(self, model=None, n=160, **kw):
        x, y = _toy(n)
        opt = DistriOptimizer(model or _model(), (x, y),
                              ClassNLLCriterion(), batch_size=32, **kw)
        opt.set_optim_method(SGD(learningrate=0.1))
        return opt

    def test_nan_grad_run_localizes_and_counts(self, tmp_path,
                                               monkeypatch):
        """Acceptance gate (DistriOptimizer): same localization contract
        through the sharded segment-sum + psum path."""
        monkeypatch.setenv("BIGDL_FAULT_PLAN", "step:3:nan_grad")
        monkeypatch.setenv("BIGDL_HEALTH_EVERY", "100")
        monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path))
        reset_injector()
        obs.reset()
        opt = self._opt()
        opt.set_end_when(Trigger.max_iteration(5))
        opt.optimize()
        assert opt.state["nonfinite_skips"] == 1
        evs = [r for r in obs.get_tracer().recent()
               if r["name"] == "health.nonfinite_layers"]
        assert len(evs) == 1
        a = evs[0]["attrs"]
        assert a["step"] == 3 and a["first"] == "0/bias"
        assert set(a["layers"]) == set(NAMES)
        ctr = obs.get_registry().counter("bigdl_nonfinite_layers_total",
                                         labels=("layer",))
        assert ctr.labels(layer="0/weight").value == 1

    def test_sharded_norms_match_local(self, monkeypatch):
        """The psum'd shard stats reconstruct the same GLOBAL per-layer
        norms a single-device run computes (f32 wire so the exchange
        adds no quantization)."""
        import jax
        import jax.numpy as jnp

        monkeypatch.setenv("BIGDL_HEALTH_EVERY", "1")
        m1 = _model()
        weights = jax.tree.map(lambda a: jnp.array(a, copy=True),
                               m1.params())
        lo = LocalOptimizer(m1, _toy(32), ClassNLLCriterion(),
                            batch_size=32)
        lo.set_optim_method(SGD(learningrate=0.1))
        lo.set_end_when(Trigger.max_iteration(1))
        lo.optimize()
        local = lo._health_monitor.last["layers"]

        m2 = _model()
        m2.set_params(jax.tree.map(lambda a: jnp.array(a, copy=True),
                                   weights))
        do = self._opt(model=m2, n=32, wire_dtype="float32")
        do.set_end_when(Trigger.max_iteration(1))
        do.optimize()
        sharded = do._health_monitor.last["layers"]
        for name in NAMES:
            assert sharded[name]["grad_norm"] == pytest.approx(
                local[name]["grad_norm"], rel=1e-4)
            assert sharded[name]["param_norm"] == pytest.approx(
                local[name]["param_norm"], rel=1e-5)
            assert sharded[name]["update_ratio"] == pytest.approx(
                local[name]["update_ratio"], rel=1e-3)

    def test_health_psum_lands_in_collective_footprint(self, monkeypatch):
        from bigdl_tpu.obs import collectives as C

        monkeypatch.setenv("BIGDL_HEALTH_EVERY", "2")
        opt = self._opt(wire_dtype="float32")
        opt.set_end_when(Trigger.max_iteration(2))
        opt.optimize()
        ctr = obs.get_registry().counter("bigdl_collective_bytes_total",
                                         labels=("op", "dtype"))
        # scalar grad-norm psum + the (4 layers x 4 cols) stats psum
        per_step = C.all_reduce_bytes(1, "float32", 8) \
            + C.all_reduce_bytes(16, "float32", 8)
        assert ctr.labels(op="psum", dtype="float32").value == \
            pytest.approx(per_step * 2)


# ------------------------------------------------- HLO FLOPs / MFU gauges
class TestHloCost:
    def test_instrument_jit_records_step_flops(self):
        import jax
        import jax.numpy as jnp

        stats = RuntimeStats()

        @jax.jit
        def f(a):
            return (a @ a).sum()

        g = instrument_jit(f, "train_step", stats=stats)
        float(g(jnp.ones((64, 64))))
        assert stats.step_flops is not None
        # 2 * 64^3 matmul MACs dominate
        assert stats.step_flops >= 2 * 64 ** 3
        assert "train_step" in stats.costs
        snap = stats.snapshot(memory=False)
        assert snap["step_flops"] == stats.step_flops

    def test_scan_body_counts_once_so_bench_needs_no_normalization(self):
        """XLA's HloCostAnalysis counts a while-loop body ONCE — the
        bench's N-step scanned program reports ~one step's FLOPs as-is.
        This pins the behavior bench.py relies on; if a jax upgrade
        starts multiplying by trip count this fails and the bench's
        steps_per_call needs to come back."""
        import jax
        import jax.numpy as jnp

        s1, s10 = RuntimeStats(), RuntimeStats()

        def body(c, _):
            return jnp.tanh(c @ c), None

        @jax.jit
        def once(c):
            return body(c, None)[0].sum()

        @jax.jit
        def scan10(c):
            out, _ = jax.lax.scan(body, c, None, length=10)
            return out.sum()

        x = jnp.ones((32, 32))
        float(instrument_jit(once, "f", stats=s1)(x))
        float(instrument_jit(scan10, "f", stats=s10)(x))
        assert s10.step_flops == pytest.approx(s1.step_flops, rel=0.2)
        # and steps_per_call still divides when a caller asks for it
        s = RuntimeStats()
        s.record_cost("unrolled", {"flops": 100.0}, steps_per_call=10)
        assert s.step_flops == pytest.approx(10.0)

    def test_publish_runtime_exports_flops_and_mfu(self):
        rt = obs.get_runtime()
        rt.record_cost("train_step", {"flops": 1e9})
        rt.record_step(0.01)
        rt.peak_flops = 1e12
        obs.publish_runtime()
        reg = obs.get_registry()
        assert reg.gauge("bigdl_step_flops").labels().value == 1e9
        assert reg.gauge("bigdl_mfu").labels().value == pytest.approx(
            1e9 / (0.01 * 1e12))

    def test_non_jit_callable_degrades_gracefully(self):
        stats = RuntimeStats()
        g = instrument_jit(lambda a: a + 1, "plain", stats=stats)
        assert g(1) == 2
        assert stats.step_flops is None
        assert stats.compile_count == 1   # still a first-signature event


# ------------------------------------------------- report / flight fan-out
class TestHealthFanOut:
    def _traced_run(self, tmp_path, monkeypatch, fault=None):
        monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path / "trace"))
        monkeypatch.setenv("BIGDL_METRICS_DIR", str(tmp_path / "metrics"))
        monkeypatch.setenv("BIGDL_HEALTH_EVERY", "2")
        if fault:
            monkeypatch.setenv("BIGDL_FAULT_PLAN", fault)
        reset_injector()
        obs.reset()
        x, y = _toy(160)
        opt = LocalOptimizer(_model(), (x, y), ClassNLLCriterion(),
                             batch_size=32)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_iteration(5))
        opt.optimize()
        obs.get_tracer().flush()
        return opt

    def test_report_health_section_text_and_json(self, tmp_path,
                                                 monkeypatch, capsys):
        self._traced_run(tmp_path, monkeypatch, fault="step:2:nan_grad")
        rep = report.build_report(str(tmp_path / "trace"),
                                  str(tmp_path / "metrics"))
        h = rep["health"]
        assert set(h["grad_norm"]) == set(NAMES)
        assert h["update_ratio"]["0/weight"] > 0
        assert h["nonfinite_layers_total"]["0/bias"] == 1
        assert h["nonfinite_events"][0]["first"] == "0/bias"
        text = report.render_text(rep)
        assert "training health" in text
        assert "NON-FINITE 0/bias" in text
        assert "upd/w=" in text
        # the CLI --json path emits the same dict
        assert report.main([str(tmp_path / "trace"), "--metrics-dir",
                            str(tmp_path / "metrics"), "--json"]) == 0
        doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert doc["health"]["nonfinite_layers_total"]["0/bias"] == 1

    def test_report_without_health_says_so(self, tmp_path):
        from bigdl_tpu.obs.trace import Tracer

        t = Tracer(str(tmp_path), host_id=0)
        with t.span("computing", step=1):
            pass
        t.close()
        rep = report.build_report(str(tmp_path))
        assert "no health telemetry" in report.render_text(rep)

    def test_flight_bundle_carries_health_columns(self, tmp_path,
                                                  monkeypatch):
        self._traced_run(tmp_path, monkeypatch, fault="step:2:nan_grad")
        bundle = regress.flight_bundle("health check")
        hm = bundle["health"]["metrics"]
        assert "bigdl_grad_norm" in hm
        assert "bigdl_nonfinite_layers_total" in hm
        names = {s["labels"]["layer"]
                 for s in hm["bigdl_nonfinite_layers_total"]}
        assert names == set(NAMES)
        assert any(e["name"] == "health.nonfinite_layers"
                   for e in bundle["health"]["events"])


# ------------------------------------------------------------ config knobs
class TestHealthConfig:
    def test_env_knobs_parse(self, monkeypatch):
        from bigdl_tpu.config import refresh_from_env

        monkeypatch.setenv("BIGDL_HEALTH_EVERY", "7")
        monkeypatch.setenv("BIGDL_HEALTH_WINDOW", "32")
        monkeypatch.setenv("BIGDL_HEALTH_SPIKE_FACTOR", "5.5")
        cfg = refresh_from_env().obs
        assert cfg.health_every == 7
        assert cfg.health_window == 32
        assert cfg.health_spike_factor == 5.5

    def test_default_is_off(self, monkeypatch):
        monkeypatch.delenv("BIGDL_HEALTH_EVERY", raising=False)
        from bigdl_tpu.config import refresh_from_env

        assert refresh_from_env().obs.health_every == 0
        assert H.monitor_from_config({"w": np.zeros(3)}) is None
