"""ops/decode_attention.py — flash-decode over the paged KV cache
(ISSUE 13).

The load-bearing contracts:

* the dense path is the PR 12 math verbatim (the engine's bit-match
  tests in test_serving.py pin that end to end);
* fused (every page-block chunking) and the Pallas kernel (interpret
  mode here) agree with dense within f32 tolerance across ragged
  lengths, page boundaries and arbitrary page-table permutations;
* the trash page is never READ into an output: arbitrary finite
  garbage in page 0 changes no live slot's result, on every impl;
* the ``decode_attn`` / ``int8_mm`` auto-tuner sites: golden keys,
  model dispatch flips dense -> fused (the analytic gather-tax model),
  the measured prewarm cycle persists and then serves from cache, and
  tuner-off ``impl="auto"`` is exactly the static dense policy.
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from bigdl_tpu.ops import autotune
from bigdl_tpu.ops import decode_attention as D
from bigdl_tpu.ops.decode_attention import (decode_hbm_bytes,
                                            paged_decode_attention,
                                            static_decode_dispatch,
                                            used_page_bucket)
from bigdl_tpu.serving.cache import pool_shape


@pytest.fixture(autouse=True)
def _tuner_off_by_default(monkeypatch):
    monkeypatch.delenv("BIGDL_TUNER", raising=False)
    monkeypatch.delenv("BIGDL_TUNER_CACHE", raising=False)
    monkeypatch.delenv("BIGDL_TUNER_MEASURE", raising=False)
    autotune.reset()
    yield
    autotune.reset()


@pytest.fixture
def tuner(tmp_path, monkeypatch):
    cache = tmp_path / "tuner.json"
    monkeypatch.setenv("BIGDL_TUNER", "1")
    monkeypatch.setenv("BIGDL_TUNER_CACHE", str(cache))
    autotune.reset()
    yield cache
    autotune.reset()


def _state(b=4, h=4, d=16, p=8, maxp=8, pool=24, seed=0,
           lengths=None):
    """Random paged K/V state with ragged lengths (incl. a page
    boundary) and a permuted page table; slot 0 is inactive (length 0,
    trash table row) like a released engine slot."""
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(b, h, d).astype(np.float32))
    shape = pool_shape(pool, p, h, d)
    kp = jnp.asarray(rs.randn(*shape).astype(np.float32))
    vp = jnp.asarray(rs.randn(*shape).astype(np.float32))
    if lengths is None:
        lengths = [0, p - 1, p, min(3 * p - 1, maxp * p - 1)][:b]
        lengths += [1] * (b - len(lengths))
    tbl = np.zeros((b, maxp), np.int32)
    free = list(range(1, pool))
    rs.shuffle(free)
    for i, ln in enumerate(lengths):
        need = ln // p + 1 if ln else 0
        for j in range(min(need, maxp)):
            tbl[i, j] = free.pop()
    return (q, kp, vp, jnp.asarray(tbl),
            jnp.asarray(np.asarray(lengths, np.int32)))


def _numpy_reference(q, kp, vp, tables, lengths, p):
    """Independent numpy oracle (float64 softmax over the masked
    gathered window)."""
    q, kp, vp = (np.asarray(x, np.float64) for x in (q, kp, vp))
    tables, lengths = np.asarray(tables), np.asarray(lengths)
    b, h, d = q.shape
    maxp = tables.shape[1]
    out = np.zeros((b, h, d))
    scale = d ** -0.5
    for i in range(b):
        # token-major pages: (P, H*Dh) rows -> (maxp*P, H, Dh)
        k = np.concatenate([kp[tables[i, j]] for j in range(maxp)],
                           axis=0).reshape(maxp * p, h, d)
        v = np.concatenate([vp[tables[i, j]] for j in range(maxp)],
                           axis=0).reshape(maxp * p, h, d)
        n = int(lengths[i]) + 1
        s = np.einsum("hd,khd->hk", q[i], k[:n]) * scale
        s -= s.max(axis=-1, keepdims=True)
        pr = np.exp(s)
        pr /= pr.sum(axis=-1, keepdims=True)
        out[i] = np.einsum("hk,khd->hd", pr, v[:n])
    return out


class TestPagedDecodeParity:
    def test_dense_matches_numpy_oracle(self):
        q, kp, vp, tbl, lens = _state()
        got = paged_decode_attention(q, kp, vp, tbl, lens, page_size=8,
                                     impl="dense")
        want = _numpy_reference(q, kp, vp, tbl, lens, 8)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)

    @pytest.mark.parametrize("bp", [0, 1, 2, 4])
    def test_fused_matches_dense_ragged(self, bp):
        q, kp, vp, tbl, lens = _state()
        dense = paged_decode_attention(q, kp, vp, tbl, lens,
                                       page_size=8, impl="dense")
        fused = paged_decode_attention(q, kp, vp, tbl, lens,
                                       page_size=8, impl="fused",
                                       block_pages=bp)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(dense),
                                   atol=1e-5)

    def test_fused_matches_dense_across_page_boundaries(self):
        # every length around each page boundary of a 3-page window
        for ln in (1, 7, 8, 9, 15, 16, 17, 23):
            q, kp, vp, tbl, lens = _state(b=2, maxp=3, seed=ln,
                                          lengths=[ln, 1])
            dense = paged_decode_attention(q, kp, vp, tbl, lens,
                                           page_size=8, impl="dense")
            fused = paged_decode_attention(q, kp, vp, tbl, lens,
                                           page_size=8, impl="fused",
                                           block_pages=1)
            np.testing.assert_allclose(np.asarray(fused),
                                       np.asarray(dense), atol=1e-5)

    def test_fused_fori_path_matches(self):
        # > 4 chunks takes the lax.fori_loop branch
        q, kp, vp, tbl, lens = _state(maxp=8)
        dense = paged_decode_attention(q, kp, vp, tbl, lens,
                                       page_size=8, impl="dense")
        fused = paged_decode_attention(q, kp, vp, tbl, lens,
                                       page_size=8, impl="fused",
                                       block_pages=1)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(dense),
                                   atol=1e-5)

    def test_pallas_interpret_matches_dense(self):
        q, kp, vp, tbl, lens = _state(b=3, h=2, d=8, p=4, maxp=4,
                                      pool=16)
        dense = paged_decode_attention(q, kp, vp, tbl, lens,
                                       page_size=4, impl="dense")
        pal = paged_decode_attention(q, kp, vp, tbl, lens, page_size=4,
                                     impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(pal), np.asarray(dense),
                                   atol=1e-5)

    @pytest.mark.parametrize("shape", ["engine", "tiny"])
    def test_three_bodies_agree_on_the_token_major_cache(self, shape):
        """dense, fused and the Pallas kernel read the one cache
        layout — a layer's pool handed in, and the engine's stacked
        buffers with the layer's index — and agree, at the engine's
        shape (chip_smoke.py FULL) and at a tiny one."""
        c = (dict(b=8, h=8, d=64, p=16, maxp=32) if shape == "engine"
             else dict(b=2, h=2, d=8, p=4, maxp=2))
        q, kp, vp, tbl, lens = _state(
            b=c["b"], h=c["h"], d=c["d"], p=c["p"], maxp=c["maxp"],
            pool=1 + c["b"] * c["maxp"], seed=11)
        assert kp.shape == (1 + c["b"] * c["maxp"], c["p"],
                            c["h"] * c["d"])
        outs = {impl: paged_decode_attention(
            q, kp, vp, tbl, lens, page_size=c["p"], impl=impl,
            block_pages=4) for impl in ("dense", "fused",
                                        "pallas_interpret")}
        want = _numpy_reference(q, kp, vp, tbl, lens, c["p"])
        for impl, got in outs.items():
            np.testing.assert_allclose(np.asarray(got), want, atol=2e-5,
                                       err_msg=impl)
        # the stacked cache, read in place at layer 1 of 2
        kps, vps = (jnp.stack([jnp.full_like(x, 7.0), x])
                    for x in (kp, vp))
        for impl, got in outs.items():
            stacked = paged_decode_attention(
                q, kps, vps, tbl, lens, page_size=c["p"], impl=impl,
                block_pages=4, layer=1)
            np.testing.assert_array_equal(np.asarray(stacked),
                                          np.asarray(got), err_msg=impl)

    @pytest.mark.parametrize("impl", ["dense", "fused",
                                      "pallas_interpret"])
    def test_trash_page_never_read(self, impl):
        """Finite garbage in page 0 (the reserved trash page) must not
        change any live slot's output — the `pos <= length` mask
        contract every impl shares."""
        q, kp, vp, tbl, lens = _state()
        clean = paged_decode_attention(q, kp, vp, tbl, lens,
                                       page_size=8, impl=impl)
        kp2 = kp.at[0].set(1e30)
        vp2 = vp.at[0].set(1e30)
        dirty = paged_decode_attention(q, kp2, vp2, tbl, lens,
                                       page_size=8, impl=impl)
        live = np.asarray(lens) > 0
        np.testing.assert_array_equal(np.asarray(dirty)[live],
                                      np.asarray(clean)[live])
        assert np.isfinite(np.asarray(dirty)[live]).all()

    def test_invalid_impl_raises(self):
        q, kp, vp, tbl, lens = _state(b=1, maxp=1)
        with pytest.raises(ValueError, match="impl"):
            paged_decode_attention(q, kp, vp, tbl, lens, page_size=8,
                                   impl="nope")


class TestBucketHelpers:
    def test_used_page_bucket_pow2_and_clamp(self):
        assert used_page_bucket(0, 8, 8) == 1
        assert used_page_bucket(7, 8, 8) == 1
        assert used_page_bucket(8, 8, 8) == 2
        assert used_page_bucket(23, 8, 8) == 4
        assert used_page_bucket(24, 8, 8) == 4
        assert used_page_bucket(32, 8, 8) == 8
        assert used_page_bucket(63, 8, 8) == 8
        assert used_page_bucket(1000, 8, 8) == 8  # clamped

    def test_chunk_pages(self):
        assert D._chunk_pages(8, 0) == 8
        assert D._chunk_pages(8, 16) == 8
        assert D._chunk_pages(8, 3) == 2   # largest divisor <= request
        assert D._chunk_pages(8, 4) == 4
        assert D._chunk_pages(1, 1) == 1

    def test_decode_hbm_bytes_dense_carries_gather_tax(self):
        d = decode_hbm_bytes("dense", 8, 8, 16, 16, 4)
        f = decode_hbm_bytes("fused", 8, 8, 16, 16, 4)
        p = decode_hbm_bytes("pallas", 8, 8, 16, 16, 4)
        assert d > 2 * f        # the materialized copy + score plane
        assert f == p

    def test_static_dispatch_is_dense(self):
        assert static_decode_dispatch() == ("dense", 0)


class TestDecodeAttnTunerSite:
    def test_golden_key_and_model_flips_to_fused(self, tuner):
        rec = autotune.decide_decode_attn((4, 4, 16), 8, 4, jnp.float32)
        assert rec is not None
        assert rec["key"] == "decode_attn|b4h4d16p8m4|float32|cpu"
        assert rec["impl"] == "fused"        # analytic gather-tax model
        assert rec["source"] == "model"
        assert rec["static"] == "dense"
        assert rec["block_pages"] >= 1

    def test_auto_dispatch_consults_and_caches(self, tuner):
        q, kp, vp, tbl, lens = _state()
        out = paged_decode_attention(q, kp, vp, tbl, lens, page_size=8,
                                     impl="auto")
        dense = paged_decode_attention(q, kp, vp, tbl, lens,
                                       page_size=8, impl="dense")
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   atol=1e-5)
        doc = json.loads(tuner.read_text())
        sites = {r["site"] for r in doc["decisions"].values()}
        assert "decode_attn" in sites

    def test_measured_prewarm_cold_then_warm(self, tuner, monkeypatch):
        monkeypatch.setenv("BIGDL_TUNER_MEASURE", "1")
        monkeypatch.setenv("BIGDL_TUNER_MEASURE_ITERS", "1")
        autotune.reset()
        autotune.prewarm_decode_attn(2, 2, 8, page_size=4, maxp=2)
        doc = json.loads(tuner.read_text())
        recs = [r for r in doc["decisions"].values()
                if r["site"] == "decode_attn"]
        assert recs and recs[0]["source"] == "measured"
        assert recs[0]["measured_s"]
        # pallas is measurable (interpret) so it must have been probed
        assert any(lbl.startswith("pallas")
                   for lbl in recs[0]["measured_s"])
        autotune.reset()    # fresh process: everything from the cache
        autotune.prewarm_decode_attn(2, 2, 8, page_size=4, maxp=2)
        st = autotune.get_cache().stats()
        assert st["misses"] == 0 and st["hits"] >= 1

    def test_tuner_off_auto_is_static_dense(self):
        # with the tuner off, impl="auto" must never consult the site:
        # no cache, no decisions, numerics == dense
        q, kp, vp, tbl, lens = _state(b=2, maxp=2)
        out = paged_decode_attention(q, kp, vp, tbl, lens, page_size=8,
                                     impl="auto")
        dense = paged_decode_attention(q, kp, vp, tbl, lens,
                                       page_size=8, impl="dense")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(dense))
        assert autotune.get_cache().decisions == {}


class TestInt8MMSite:
    def _mats(self, m=4, k=32, n=64, seed=0):
        from bigdl_tpu.ops.quantized_matmul import quantize_per_channel

        rs = np.random.RandomState(seed)
        x = jnp.asarray(rs.randn(m, k).astype(np.float32))
        w = jnp.asarray((rs.randn(n, k) * 0.1).astype(np.float32))
        w_q, w_s = quantize_per_channel(w, axis=0)
        return x, w, w_q, w_s

    def test_dequant_impl_close_to_float(self):
        from bigdl_tpu.ops.quantized_matmul import int8_matmul

        x, w, w_q, w_s = self._mats()
        want = np.asarray(jnp.matmul(x, w.T))
        got = np.asarray(int8_matmul(x, w_q, w_s, impl="dequant"))
        np.testing.assert_allclose(got, want, atol=0.05, rtol=0.05)
        # int8 and dequant agree with each other within activation-
        # quantization noise
        i8 = np.asarray(int8_matmul(x, w_q, w_s))
        np.testing.assert_allclose(got, i8, atol=0.1, rtol=0.1)

    def test_auto_is_int8_when_tuner_off(self):
        from bigdl_tpu.ops.quantized_matmul import int8_matmul

        x, _w, w_q, w_s = self._mats()
        np.testing.assert_array_equal(
            np.asarray(int8_matmul(x, w_q, w_s, impl="auto")),
            np.asarray(int8_matmul(x, w_q, w_s)))
        assert autotune.get_cache().decisions == {}

    def test_invalid_impl_raises(self):
        from bigdl_tpu.ops.quantized_matmul import int8_matmul

        x, _w, w_q, w_s = self._mats()
        with pytest.raises(ValueError, match="impl"):
            int8_matmul(x, w_q, w_s, impl="bogus")

    def test_site_golden_key_and_never_lose(self, tuner):
        rec = autotune.decide_int8_mm((4, 32), (64, 32), jnp.float32)
        assert rec is not None
        assert rec["key"] == "int8_mm|m4k32n64|float32|cpu"
        # model-only: the static int8 path wins (dequant's f32 weight
        # round trip costs more bytes at decode shapes)
        assert rec["impl"] == "int8" and rec["static"] == "int8"

    def test_measured_prewarm_persists(self, tuner, monkeypatch):
        monkeypatch.setenv("BIGDL_TUNER_MEASURE", "1")
        monkeypatch.setenv("BIGDL_TUNER_MEASURE_ITERS", "1")
        autotune.reset()
        autotune.prewarm_int8_mm(4, 16, 32)
        doc = json.loads(tuner.read_text())
        recs = [r for r in doc["decisions"].values()
                if r["site"] == "int8_mm"]
        assert recs and recs[0]["source"] == "measured"
        assert set(recs[0]["measured_s"]) == {"int8", "dequant"}
