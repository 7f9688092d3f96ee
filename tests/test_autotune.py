"""ops/autotune.py — the fusion-aware kernel auto-tuner.

Covers the ISSUE-7 acceptance surface on CPU:

* golden cache keys and the JSON store's contract — hit/miss
  accounting, platform isolation (a TPU decision never steers a CPU
  run), corrupt-file degradation to the static policy (file
  preserved);
* the tuner-OFF pinning: ``impl="auto"`` dispatch must be EXACTLY the
  hand-measured :func:`attention.static_dispatch` policy, with the
  tuner never consulted;
* never-lose-to-static: measured searches keep the static choice on
  ties and losses, and the ``obs.regress.check`` gate rejects a
  "winner" that regresses past tolerance;
* the restored coverage regimes: symmetric VMEM guard (large Tq),
  kv-superblock streaming (long kv at d=128), and kxk stride-2
  conv+BN Pallas numerics with a non-incrementing
  ``bigdl_kernel_fallbacks_total{site="conv_bn_k3s2"}``.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.ops import autotune, conv_bn
from bigdl_tpu.ops import attention as A


@pytest.fixture
def tuner(tmp_path, monkeypatch):
    """Enabled tuner with a fresh tmp cache; disabled + reset after."""
    cache = tmp_path / "tuner.json"
    monkeypatch.setenv("BIGDL_TUNER", "1")
    monkeypatch.setenv("BIGDL_TUNER_CACHE", str(cache))
    monkeypatch.delenv("BIGDL_TUNER_MEASURE", raising=False)
    autotune.reset()
    yield cache
    autotune.reset()


@pytest.fixture(autouse=True)
def _tuner_off_by_default(monkeypatch):
    monkeypatch.delenv("BIGDL_TUNER", raising=False)
    monkeypatch.delenv("BIGDL_TUNER_CACHE", raising=False)
    autotune.reset()
    yield
    autotune.reset()


def _decide_attn(**kw):
    args = dict(causal=True, seq_offset=0, static_impl="lax", plan=None)
    args.update(kw)
    return autotune.decide_attention((1, 2, 128, 16), (1, 2, 256, 16),
                                     jnp.float32, **args)


# ------------------------------------------------------------ cache keys
def test_score_refuses_a_platform_without_peaks():
    """An unknown platform used to be ranked with the CPU's peaks."""
    assert autotune._score(1e9, 1e6, "tpu") > 0
    with pytest.raises(ValueError, match="no roofline peaks"):
        autotune._score(1e9, 1e6, "some_new_chip")


class TestCacheStore:
    def test_golden_key_format(self):
        key = autotune.cache_key("attn", "b1h2tq128tk256d16",
                                 jnp.bfloat16, "tpu", extra="c1o0")
        assert key == "attn|b1h2tq128tk256d16|bfloat16|tpu|c1o0"
        assert autotune.cache_key(
            "conv_bn_kxk", "n2c8h8w8o16k3s2p1", jnp.float32, "cpu"
        ) == "conv_bn_kxk|n2c8h8w8o16k3s2p1|float32|cpu"

    def test_miss_then_hit_and_persistence(self, tuner, monkeypatch):
        monkeypatch.setenv("BIGDL_TUNER", "1")
        d1 = _decide_attn()
        assert d1 is not None and d1["source"] in ("model", "measured")
        stats = autotune.get_cache().stats()
        assert stats["misses"] == 1 and stats["hits"] == 0
        d2 = _decide_attn()
        assert d2["impl"] == d1["impl"]
        stats = autotune.get_cache().stats()
        assert stats["hits"] == 1
        # persisted, well-formed, golden-keyed
        doc = json.load(open(tuner, encoding="utf-8"))
        assert doc["version"] == 1
        key = ("attn|b1h2tq128tk256d16|float32|"
               f"{jax.default_backend()}|c1o0")
        assert list(doc["decisions"]) == [key]

    def test_platform_mismatch_is_a_miss(self, tuner):
        # a TPU-keyed decision must not serve a CPU run
        tpu_key = "attn|b1h2tq128tk256d16|float32|tpu|c1o0"
        tuner.write_text(json.dumps({
            "version": 1,
            "decisions": {tpu_key: {"impl": "pallas",
                                    "blocks": [128, 128, 256, 128],
                                    "site": "attn", "label": "rigged",
                                    "static": "lax"}}}))
        autotune.reset()
        d = _decide_attn()
        assert d["impl"] == "lax"          # fresh CPU search, not rigged
        stats = autotune.get_cache().stats()
        assert stats["misses"] == 1 and stats["hits"] == 0
        doc = json.load(open(tuner, encoding="utf-8"))
        assert len(doc["decisions"]) == 2  # tpu entry kept alongside

    def test_corrupt_cache_falls_back_to_static(self, tuner):
        tuner.write_text("{definitely not json")
        autotune.reset()
        assert autotune.get_cache().corrupt
        d = _decide_attn(static_impl="lax")
        assert d["source"] == "corrupt_cache"
        assert d["impl"] == "lax"
        # the evidence is never clobbered
        assert tuner.read_text() == "{definitely not json"

    def test_cache_rebuilt_when_path_changes(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BIGDL_TUNER", "1")
        monkeypatch.setenv("BIGDL_TUNER_CACHE", str(tmp_path / "a.json"))
        autotune.reset()
        c1 = autotune.get_cache()
        monkeypatch.setenv("BIGDL_TUNER_CACHE", str(tmp_path / "b.json"))
        c2 = autotune.get_cache()
        assert c1 is not c2 and c2.path.endswith("b.json")


# ------------------------------------------------- tuner-off pinning
class TestTunerOffPinning:
    # (q_shape, kv_shape, backend) -> expected impl of the hand-measured
    # static policy; the grid spans the newly-reachable regimes
    CASES = [
        ((1, 8, 512, 64), (1, 8, 512, 64), "cpu", "lax"),
        ((1, 8, 4096, 64), (1, 8, 4096, 64), "cpu", "lax"),
        ((1, 8, 512, 64), (1, 8, 512, 64), "tpu", "lax"),
        ((1, 8, 2048, 64), (1, 8, 2048, 64), "tpu", "lax"),
        ((1, 8, 4096, 64), (1, 8, 4096, 64), "tpu", "pallas"),
        # long-kv chunked regime, previously unreachable at d=128
        ((1, 8, 2048, 128), (1, 8, 32768, 128), "tpu", "pallas"),
        # large-Tq mirror (the dkv kernel streams q/g — symmetric guard)
        ((1, 8, 32768, 128), (1, 8, 2048, 128), "tpu", "pallas"),
        # untileable T never reaches the kernel
        ((1, 8, 4104, 64), (1, 8, 4104, 64), "tpu", "lax"),
    ]

    @pytest.mark.parametrize("qs,ks,backend,want", CASES)
    def test_static_dispatch_pinned(self, qs, ks, backend, want):
        impl, plan = A.static_dispatch(qs, ks, ks, jnp.bfloat16,
                                       backend=backend)
        assert impl == want, (qs, ks, backend, impl)
        if want == "pallas":
            assert plan is not None

    def test_long_kv_plan_streams_superblocks(self):
        _, plan = A.static_dispatch((1, 8, 2048, 128), (1, 8, 32768, 128),
                                    (1, 8, 32768, 128), jnp.bfloat16,
                                    backend="tpu")
        assert plan == (128, 128, 8192, 2048)

    def test_large_tq_plan_streams_q_superblocks(self):
        _, plan = A.static_dispatch((1, 8, 32768, 128), (1, 8, 2048, 128),
                                    (1, 8, 2048, 128), jnp.bfloat16,
                                    backend="tpu")
        assert plan == (128, 128, 2048, 8192)

    def test_tuner_off_never_consults_autotune(self, monkeypatch):
        def boom(*a, **kw):  # pragma: no cover - must not run
            raise AssertionError("tuner consulted while disabled")

        monkeypatch.setattr(autotune, "decide_attention", boom)
        monkeypatch.setattr(autotune, "decide_conv_bn", boom)
        from bigdl_tpu.ops import dot_product_attention

        q = jnp.ones((1, 2, 128, 16), jnp.float32)
        k = jnp.ones((1, 2, 128, 16), jnp.float32)
        dot_product_attention(q, k, k, causal=True)
        x = jnp.ones((1, 4, 8, 8), jnp.float32)
        w = jnp.ones((8, 4, 3, 3), jnp.float32)
        conv_bn.conv_bn_stats(x, w, jnp.zeros(8), stride=1, pad=1,
                              interpret=True)


# -------------------------------------------------- never lose to static
class TestNeverLosesToStatic:
    def _resolve(self, monkeypatch, times):
        """Run _resolve with rigged per-candidate wall-clock times."""
        seq = iter(times)
        monkeypatch.setattr(autotune, "_measure",
                            lambda jitted, args, iters: next(seq))
        candidates = {"lax": {"impl": "lax", "blocks": None},
                      "pallas_x": {"impl": "pallas",
                                   "blocks": [64, 64, 128, 64]}}
        analytic = {"lax": (1e6, 1e6), "pallas_x": (1e6, 1e6)}
        probes = {"lax": lambda x: x, "pallas_x": lambda x: x * 2}
        return autotune._resolve(
            "attn", f"test|{len(times)}x{times[0]}|f32|cpu", candidates,
            "lax", analytic, probes, (jnp.ones((2, 2)),))

    def test_static_kept_on_loss(self, tuner, monkeypatch):
        monkeypatch.setenv("BIGDL_TUNER_MEASURE", "1")
        rec = self._resolve(monkeypatch, [0.001, 0.002])  # pallas slower
        assert rec["label"] == "lax" and rec["source"] == "measured"

    def test_faster_candidate_wins_and_is_gated(self, tuner, monkeypatch):
        monkeypatch.setenv("BIGDL_TUNER_MEASURE", "1")
        rec = self._resolve(monkeypatch, [0.002, 0.001])  # pallas faster
        assert rec["label"] == "pallas_x"
        assert rec["gate"]["status"] == "pass"
        assert rec["measured_s"]["pallas_x"] < rec["measured_s"]["lax"]

    def test_regress_gate_flags_a_regression(self):
        v = autotune._gate_measured("pallas_x", 2.0, "lax", 1.0)
        assert v["status"] == "violation" and v["ratio"] == 2.0
        v = autotune._gate_measured("pallas_x", 0.9, "lax", 1.0)
        assert v["status"] == "pass"

    def test_model_decision_must_beat_static(self, tuner, monkeypatch):
        # equal scores -> static; no measurement configured
        candidates = {"lax": {"impl": "lax", "blocks": None},
                      "pallas_x": {"impl": "pallas",
                                   "blocks": [64, 64, 128, 64]}}
        analytic = {"lax": (1e6, 1e6), "pallas_x": (1e6, 1e6)}
        rec = autotune._resolve("attn", "test|model-tie|f32|cpu",
                                candidates, "lax", analytic, {}, None)
        assert rec["label"] == "lax" and rec["source"] == "model"

    def test_model_impl_flip_needs_decisive_margin(self, tuner):
        candidates = {"lax": {"impl": "lax", "blocks": None},
                      "pallas_x": {"impl": "pallas",
                                   "blocks": [64, 64, 128, 64]}}
        # 25% better than static: a close call — static kept
        analytic = {"lax": (1e6, 1e9), "pallas_x": (1e6, 0.75e9)}
        rec = autotune._resolve("attn", "test|model-margin-1|f32|cpu",
                                candidates, "lax", analytic, {}, None)
        assert rec["label"] == "lax"
        # 10x better (the quadratic-residual regime): flip allowed
        analytic = {"lax": (1e6, 1e9), "pallas_x": (1e6, 1e8)}
        rec = autotune._resolve("attn", "test|model-margin-2|f32|cpu",
                                candidates, "lax", analytic, {}, None)
        assert rec["label"] == "pallas_x" and rec["source"] == "model"

    def test_unmeasurable_cpu_search_never_proposes_pallas(
            self, tuner, monkeypatch):
        # the CPU interpreter is not what the analytic model prices:
        # with measurement off, a flash-eligible shape must stay on
        # the static (lax) side of the impl question
        monkeypatch.delenv("BIGDL_TUNER_MEASURE", raising=False)
        plan = A._flash_plan(128, 256, 16, jnp.float32)
        d = autotune.decide_attention(
            (1, 2, 128, 16), (1, 2, 256, 16), jnp.float32, causal=True,
            seq_offset=0, static_impl="lax", plan=plan, arrays=None)
        assert d["impl"] == "lax" and d["source"] == "model"
        assert all(not lbl.startswith("pallas")
                   for lbl in d["scores"]), d["scores"]


# --------------------------------------------- restored coverage regimes
class TestSymmetricVmemGuard:
    def test_guard_accounts_for_double_buffering(self):
        # 8192 @ d=128 bf16 is exactly the budget (the on-chip
        # validated point); 16384 passed the OLD asymmetric formula
        # and must now be streamed instead
        assert A._kv_fits_vmem(8192, 128, jnp.bfloat16)
        assert not A._kv_fits_vmem(16384, 128, jnp.bfloat16)

    def test_plan_is_symmetric_in_tq_tk(self):
        p1 = A._flash_plan(2048, 32768, 128, jnp.bfloat16)
        p2 = A._flash_plan(32768, 2048, 128, jnp.bfloat16)
        assert p1 == (128, 128, 8192, 2048)
        assert p2 == (128, 128, 2048, 8192)

    def test_explicit_bad_blocks_rejected(self):
        assert A._flash_plan(256, 256, 16, jnp.float32,
                             block_q=96) is None
        assert A._flash_plan(256, 256, 16, jnp.float32,
                             block_kv=192) is None


class TestKvBlockedFlashNumerics:
    @pytest.mark.parametrize("causal,seq_offset", [(False, 0), (True, 0),
                                                   (True, 128)])
    def test_blocked_streams_match_reference(self, causal, seq_offset):
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(1, 2, 128, 16).astype(np.float32))
        k = jnp.asarray(rs.randn(1, 2, 512, 16).astype(np.float32))
        v = jnp.asarray(rs.randn(1, 2, 512, 16).astype(np.float32))
        g = jnp.asarray(rs.randn(1, 2, 128, 16).astype(np.float32))
        kw = dict(causal=causal, interpret=True, seq_offset=seq_offset,
                  block_q=64, block_k=64, block_kv=128, block_qs=64)

        def lf(q, k, v):
            return jnp.sum(A.flash_attention(q, k, v, **kw) * g)

        def lr(q, k, v):
            return jnp.sum(A._reference_attention(
                q, k, v, causal=causal, scale=16 ** -0.5,
                seq_offset=seq_offset) * g)

        np.testing.assert_allclose(float(lf(q, k, v)), float(lr(q, k, v)),
                                   rtol=2e-5)
        gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)


class TestStride2ConvBn:
    def test_stride2_pallas_matches_reference_and_stops_falling_back(
            self):
        from bigdl_tpu import obs

        conv_bn.FALLBACK_LOG.clear()
        counter = obs.get_registry().counter(
            "bigdl_kernel_fallbacks_total",
            "Fused-kernel call sites that fell back to the XLA "
            "reference path, by site (trace-time, once per compile)",
            labels=("site",))
        before = counter.labels(site="conv_bn_k3s2").value

        rs = np.random.RandomState(7)
        x = jnp.asarray(rs.randn(2, 16, 8, 8).astype(np.float32))
        w = jnp.asarray(rs.randn(32, 16, 3, 3).astype(np.float32) * 0.1)
        s = jnp.asarray(rs.randn(32).astype(np.float32))
        coef = jnp.arange(32, dtype=jnp.float32)

        def lk(x, w, s):
            y, s1, s2 = conv_bn.conv_bn_stats(x, w, s, stride=2, pad=1,
                                              interpret=True)
            return (0.5 * jnp.sum(y ** 2) + jnp.sum(s1 * coef)
                    + 0.1 * jnp.sum(s2))

        def lr(x, w, s):
            y, s1, s2 = conv_bn._reference(x, w, s, 2, 1)
            return (0.5 * jnp.sum(y ** 2) + jnp.sum(s1 * coef)
                    + 0.1 * jnp.sum(s2))

        np.testing.assert_allclose(float(lk(x, w, s)), float(lr(x, w, s)),
                                   rtol=1e-5)
        gk = jax.grad(lk, argnums=(0, 1, 2))(x, w, s)
        gr = jax.grad(lr, argnums=(0, 1, 2))(x, w, s)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-3)
        # the r06 regression site stops incrementing
        assert not conv_bn.FALLBACK_LOG, conv_bn.FALLBACK_LOG
        assert counter.labels(site="conv_bn_k3s2").value == before

    def test_all_three_resnet_stage_transitions_dispatch_pallas(self):
        for xs, ws in [((128, 128, 56, 56), (128, 128, 3, 3)),
                       ((128, 256, 28, 28), (256, 256, 3, 3)),
                       ((128, 512, 14, 14), (512, 512, 3, 3))]:
            assert conv_bn.kernel_path(xs, ws, stride=2, pad=1,
                                       itemsize=2) == "pallas_kxk"


# ------------------------------------------------- end-to-end decisions
class TestDecisionFlow:
    def test_conv_decision_golden_key_and_payload(self, tuner):
        d = autotune.decide_conv_bn((2, 8, 8, 8), (16, 8, 3, 3),
                                    jnp.float32, stride=2, pad=1,
                                    interpret=True)
        assert d["impl"] in ("pallas", "xla")
        assert d["key"] == (f"conv_bn_kxk|n2c8h8w8o16k3s2p1|float32|"
                            f"{jax.default_backend()}")
        assert d["static"] == "pallas_o16"

    def test_attention_decision_with_tuner_enabled_dispatches(
            self, tuner, monkeypatch):
        # numerics under the tuner must equal the reference regardless
        # of the winning impl
        from bigdl_tpu.ops import dot_product_attention

        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(1, 2, 128, 16).astype(np.float32))
        k = jnp.asarray(rs.randn(1, 2, 256, 16).astype(np.float32))
        v = jnp.asarray(rs.randn(1, 2, 256, 16).astype(np.float32))
        got = dot_product_attention(q, k, v, causal=True)
        ref = A._reference_attention(q, k, v, causal=True,
                                     scale=16 ** -0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=3e-5)
        assert autotune.summary()["decisions"], "no decision recorded"

    def test_summary_shape(self, tuner):
        _decide_attn()
        s = autotune.summary()
        assert s["enabled"] is True
        assert s["cache"]["entries"] == 1
        d = s["decisions"][0]
        assert {"key", "site", "impl", "label", "source",
                "static"} <= set(d)
