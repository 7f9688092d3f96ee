"""LongCat-Flash behind ``LMEngine``: the model, its latent paged
cache, the dropless expert layer and the chip's share, each against the
plain float32 reference (``benchmarks/reference/longcat_flash_chat.py``,
the repo's own copy of ``benchmarks/reference/longcat_flash_chat.py``).

A small size with every ratio of the published one kept: two double
layers, 4 heads of 8 + 4 / 8, ranks 24 / 16, 16 routed + 8 zero-compute
experts, top-4.  Tolerances, each with its reason:

* ``F32_TOL`` 2e-4 on logits of magnitude 1-3: program and reference
  are both float32 with ``highest`` products on the CPU and differ by
  the order of their sums (absorbed against rebuilt keys, a sorted
  grouped product against a loop over experts): observed 2e-6 to 3e-5.
  The same comparison with the program's matrices in bfloat16 reads
  1e-2 and more (test (f)), so the tolerance does tell a lower
  precision from the stated one.
* ``GAP_LIMIT`` 1e-3 on the served tokens' logit gap: a greedy token is
  the reference's own first choice unless two logits tie within the
  float32 tolerance; a wrong page or a wrong position reads 0.1 and
  more at this size.
"""

import importlib.util
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import longcat_flash_chat as ref
from bigdl_tpu import obs
from bigdl_tpu.models.longcat_flash import LongCatFlash
from bigdl_tpu.nn.experts import COUNT_NAMES, DroplessExperts, counts_dict
from bigdl_tpu.nn.latent import (GatedMLP, LatentAttention, RMSNorm,
                                 rotary_interleaved)
from bigdl_tpu.serving import LMEngine
from bigdl_tpu.serving.cache import (PagedKVCache, write_prompt_pages)

F32_TOL = 2e-4
GAP_LIMIT = 1e-3

SMALL = dict(vocab_size=96, hidden_size=64, num_layers=2,
             num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
             qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
             ffn_hidden_size=128, expert_ffn_hidden_size=32,
             n_routed_experts=16, zero_expert_num=8, moe_topk=4,
             routed_scaling_factor=6.0, rms_norm_eps=1e-5, rope_theta=1e7)
MAX_LEN = 64


def make(held=(0, 16), seed=7, dtype=jnp.float32, std=0.1, row_align=1):
    """Seeded weights from the reference, the reference's sizes, and the
    program's model built around that tree without weights of its own.
    ``row_align`` 1 keeps a cached row at its 16 + 4 values; the model's
    default pads it to the chip's 128 lanes."""
    cfg = dict(SMALL, held_experts=list(held), max_len=MAX_LEN,
               initializer_range=std)
    sizes = ref.sizes_of(cfg)
    params = ref.init_params(seed, sizes, dtype)
    model = LongCatFlash(max_len=MAX_LEN, held_experts=held, params=params,
                         row_align=row_align, **SMALL)
    return model, params, sizes


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, SMALL["vocab_size"], size=n).astype(np.int32)


# ------------------------------------------------------------ (a) forward
@pytest.mark.parametrize("held,seed,length", [
    ((0, 16), 7, 37), ((4, 8), 8, 20), ((12, 16), 2**31 + 5, 50)])
def test_full_forward_equals_the_reference(held, seed, length):
    model, params, sizes = make(held, seed)
    toks = tokens_of(length, seed)
    logits, _ = model.apply(params, {}, jnp.asarray(toks)[None])
    want = ref.forward_logits(params, sizes, toks)
    assert float(jnp.max(jnp.abs(want))) > 0.5     # not a trivial model
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               atol=F32_TOL, rtol=0)


def test_a_model_given_params_draws_no_weights(monkeypatch):
    from bigdl_tpu import common

    class NoRNG:
        def normal(self, *a, **k):
            raise AssertionError("drew a weight")

        uniform = normal

    _, params, _ = make()
    monkeypatch.setattr(common.RandomGenerator, "RNG", NoRNG())
    model = LongCatFlash(max_len=MAX_LEN, params=params, **SMALL)
    assert model.params() is params
    assert model._children["l0"]._children["mlp0"].gate is None
    with pytest.raises(AssertionError, match="drew a weight"):
        LongCatFlash(max_len=MAX_LEN, **SMALL)


def test_a_model_with_weights_of_its_own_runs():
    model = LongCatFlash(max_len=MAX_LEN, **SMALL)
    params = model.params()
    assert params["l1"]["moe"]["w_gate"].shape == (16, 64, 32)
    logits, _ = model.apply(params, {}, jnp.asarray(tokens_of(9))[None])
    assert logits.shape == (1, 9, 96)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_unknown_sizes_are_refused():
    with pytest.raises(TypeError, match="unknown sizes"):
        LongCatFlash(hidden=64)


# ------------------------------------------------------- the small layers
def test_rms_norm_rotary_and_gated_mlp_equal_the_reference_pieces():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(5, 12)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(12,)), jnp.float32)
    norm = RMSNorm(12, eps=1e-5)
    got, _ = norm.apply({"weight": w}, {}, x)
    np.testing.assert_allclose(got, ref._rms(x, w, 1e-5), atol=1e-6)
    pos = jnp.arange(5) + 3
    np.testing.assert_allclose(
        rotary_interleaved(x, pos, 1e7), ref._rotary(x, pos, 1e7),
        atol=1e-6)
    # a rotation keeps each pair's length, and position 0 is the identity
    np.testing.assert_allclose(
        jnp.sum(rotary_interleaved(x, pos, 1e4) ** 2, -1),
        jnp.sum(x ** 2, -1), rtol=1e-5)
    np.testing.assert_allclose(
        rotary_interleaved(x, jnp.zeros((5,), jnp.int32), 1e4), x, atol=0)
    mlp = GatedMLP(12, 20)
    got, _ = mlp.apply(mlp.params(), {}, x)
    np.testing.assert_allclose(
        got, ref._mlp(mlp.params(), x, "float32"), atol=1e-6)


# --------------------------------- (c) absorbed decode = rebuilt prefill
@pytest.mark.parametrize("t,page,align", [(6, 4, 1), (9, 4, 8),
                                          (16, 8, 128)])
def test_absorbed_decode_equals_rebuilt_prefill(t, page, align):
    """Position t-1 computed twice: by the full-prefix attention that
    rebuilds every head's K and V from the rows, and by the decode path
    that reads the cached rows of positions < t-1 with W_kvb absorbed."""
    attn = LatentAttention(64, 4, 24, 16, 8, 4, 8, theta=1e7,
                           row_align=align)
    p = attn.params()
    x = jnp.asarray(np.random.default_rng(t).normal(size=(1, t, 64)),
                    jnp.float32)
    y_full, rows = attn.prefill(p, x)
    # [c | rotated k_rope] and zeros up to the alignment
    assert attn.row_width == {1: 20, 8: 24, 128: 128}[align]
    assert rows.shape == (1, t, attn.row_width)
    assert not np.any(np.asarray(rows[..., 20:]))
    # the reference's attention is the same function of x
    want = ref._attention(p, x[0], ref.sizes_of(dict(
        SMALL, max_len=MAX_LEN)), "float32")
    np.testing.assert_allclose(y_full[0], want, atol=F32_TOL)
    n_pages = -(-t // page)
    cache = jnp.zeros((3, 1 + n_pages, page, attn.row_width), jnp.float32)
    padded = jnp.zeros((n_pages * page, attn.row_width)).at[:t - 1].set(
        rows[0, :t - 1])
    ids = jnp.arange(1, 1 + n_pages)
    cache = write_prompt_pages(cache, 1, ids, padded)
    y_dec, cache = attn.decode(p, x[:, t - 1], cache, 1, ids[None],
                               jnp.asarray([t - 1]))
    np.testing.assert_allclose(y_dec[0], y_full[0, t - 1], atol=F32_TOL)
    # the step wrote the token's own row where the table says
    np.testing.assert_allclose(
        cache[1, 1 + (t - 1) // page, (t - 1) % page], rows[0, t - 1],
        atol=1e-6)
    assert not np.any(np.asarray(cache[0])) and not np.any(
        np.asarray(cache[2]))


def test_latent_decode_attention_never_reads_past_a_length():
    from bigdl_tpu.ops.decode_attention import latent_decode_attention

    rng = np.random.default_rng(0)
    pages = jnp.asarray(rng.normal(size=(2, 5, 4, 20)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, 3, 20)), jnp.float32)
    tables = jnp.asarray([[1, 2, 0], [3, 4, 0]], jnp.int32)
    lengths = jnp.asarray([5, 2], jnp.int32)
    out = latent_decode_attention(q, pages, tables, lengths, scale=0.3,
                                  value_width=16, layer=1)
    # finite garbage in the trash page and past the lengths (the mask
    # contract of ops/decode_attention.py)
    dirty = pages.at[1, 0].set(7e8).at[1, 2, 2:].set(1e9)
    dirty = dirty.at[1, 3, 3].set(-1e9).at[1, 4].set(-3e8)
    again = latent_decode_attention(q, dirty, tables, lengths, scale=0.3,
                                    value_width=16, layer=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(again))
    assert out.shape == (2, 3, 16) and out.dtype == jnp.float32


# --------------------- (b) prefill, then paged decode, at every position
@pytest.mark.parametrize("prompt_len,new,held,align", [
    (6, 11, (0, 16), 1), (8, 9, (4, 8), 1), (13, 14, (8, 12), 8)])
def test_prefill_then_paged_decode_equals_the_full_forward(prompt_len, new,
                                                           held, align):
    """Teacher-forced through the model's own paged_prefill /
    paged_decode over an engine-shaped cache (pages of 4: prompts that
    end inside a page and on its edge, contexts that cross pages), the
    logits at every position against the reference's full forward."""
    model, params, sizes = make(held, seed=prompt_len, row_align=align)
    toks = tokens_of(prompt_len + new, prompt_len)
    want = np.asarray(ref.forward_logits(params, sizes, toks))
    spec = model.cache_spec(params)
    row = 20 if align == 1 else 24
    assert spec["layers"] == 4 and spec["row_width"] == row \
        and spec["buffers"] == 1
    cache = PagedKVCache(spec["layers"], row_width=spec["row_width"],
                         buffers=1, page_size=4, num_pages=24, max_slots=3,
                         max_len=MAX_LEN)
    assert cache.vp is None and cache.kp.shape == (4, 24, 4, row)
    slot = 1
    pages = cache.alloc(slot, prompt_len)
    bucket = 16
    page_arg = np.zeros((bucket // 4,), np.int32)
    page_arg[:len(pages)] = pages
    prompt = np.zeros((1, bucket), np.int32)
    prompt[0, :prompt_len] = toks[:prompt_len]
    bufs, logits, counts = model.paged_prefill(
        params, cache.buffers(), jnp.asarray(prompt), prompt_len,
        jnp.asarray(page_arg))
    cache.set_buffers(bufs)
    cache.lengths[slot] = prompt_len
    np.testing.assert_allclose(logits[0], want[prompt_len - 1],
                               atol=F32_TOL)
    c = counts_dict(counts)
    assert c["held"] + c["zero"] + c["absent"] == \
        4 * prompt_len * SMALL["num_layers"]
    active = np.zeros((3,), bool)
    active[slot] = True
    for pos in range(prompt_len, prompt_len + new - 1):
        while cache.needs_growth(slot):
            assert cache.grow(slot)
        tables, lengths = cache.device_tables()
        step_tokens = np.zeros((3,), np.int32)
        step_tokens[slot] = toks[pos]
        bufs, logits, counts = model.paged_decode(
            params, cache.buffers(), tables, lengths,
            jnp.asarray(step_tokens), jnp.asarray(active), page_size=4)
        cache.set_buffers(bufs)
        cache.lengths[slot] += 1
        np.testing.assert_allclose(logits[slot], want[pos], atol=F32_TOL,
                                   err_msg=f"position {pos}")
        c = counts_dict(counts)
        # one real token a step: the other slots are not counted
        assert c["held"] + c["zero"] + c["absent"] == \
            4 * SMALL["num_layers"]


# ------------------------------------------------------------ (d) the share
def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of four experts: each share's partial result holds
    the zero-compute part (every chip computes it alike); counted once,
    the shares add up to the uncut reference layer."""
    full = DroplessExperts(64, 32, 16, 8, 4, scale=6.0)
    p = full.params()
    x = jnp.asarray(np.random.default_rng(5).normal(size=(23, 64)),
                    jnp.float32)
    sizes = ref.sizes_of(dict(SMALL, max_len=MAX_LEN))
    want = ref.expert_layer(p, sizes, x)
    idx, w = full.route(p, x)
    zero_part = x * jnp.sum(jnp.where(idx >= 16, w, 0.0), -1)[:, None]
    assert float(jnp.max(jnp.abs(zero_part))) > 0.1   # some are chosen
    total = -3.0 * zero_part
    seen = np.zeros(5, np.int64)
    for s in range(4):
        lo, hi = 4 * s, 4 * s + 4
        share = DroplessExperts(64, 32, 16, 8, 4, scale=6.0, held=(lo, hi),
                                init=False)
        ps = dict(p, w_gate=p["w_gate"][lo:hi], w_up=p["w_up"][lo:hi],
                  w_down=p["w_down"][lo:hi])
        (y, counts), _ = share.apply(ps, {}, x)
        # the share alone equals the reference given the same share
        np.testing.assert_allclose(
            y, ref.expert_layer(ps, dict(sizes, held=(lo, hi)), x),
            atol=F32_TOL)
        total = total + y
        seen += np.asarray(counts)
    np.testing.assert_allclose(total, want, atol=F32_TOL)
    # over the shares every routed assignment is held exactly once
    (_, all_counts), _ = full.apply(p, {}, x)
    assert seen[0] == int(all_counts[0]) and int(all_counts[2]) == 0
    assert seen[0] + int(all_counts[1]) == 4 * 23


# ------------------------------------------------------------- (e) counts
def _moe(held=(4, 12)):
    layer = DroplessExperts(64, 32, 16, 8, 4, scale=6.0, held=held)
    return layer, layer.params()


def test_counts_add_up_and_padding_is_not_counted():
    layer, p = _moe()
    x = jnp.asarray(np.random.default_rng(2).normal(size=(17, 64)),
                    jnp.float32)
    (y, counts), _ = layer.apply(p, {}, x)
    c = counts_dict(counts)
    assert tuple(c) == COUNT_NAMES
    assert c["held"] + c["zero"] + c["absent"] == 4 * 17
    assert 1 <= c["hit"] <= 8 and c["max_load"] * c["hit"] >= c["held"]
    mask = jnp.arange(17) < 10
    (ym, cm), _ = layer.apply(p, {}, x, mask=mask)
    cm = counts_dict(cm)
    assert cm["held"] + cm["zero"] + cm["absent"] == 4 * 10
    np.testing.assert_allclose(ym[:10], y[:10], atol=1e-6)
    assert not np.any(np.asarray(ym[10:]))


def test_a_token_with_only_zero_compute_choices_gets_x_times_its_weights():
    layer, p = _moe()
    p = dict(p, bias=jnp.where(jnp.arange(24) >= 16, 10.0, 0.0))
    x = jnp.asarray(np.random.default_rng(3).normal(size=(9, 64)),
                    jnp.float32)
    (y, counts), _ = layer.apply(p, {}, x)
    idx, w = layer.route(p, x)
    assert bool(jnp.all(idx >= 16))
    # the bias chose them; it is not in their weights
    s = jax.nn.softmax(x @ p["router"].T, axis=-1)
    np.testing.assert_allclose(w, 6.0 * jnp.take_along_axis(s, idx, -1),
                               rtol=1e-5)
    np.testing.assert_allclose(y, x * jnp.sum(w, -1)[:, None], atol=1e-5)
    assert counts_dict(counts) == dict(held=0, zero=36, absent=0, hit=0,
                                       max_load=0)


@pytest.mark.parametrize("n_tokens", [5, 64])
def test_a_bias_that_sends_every_token_to_one_expert_loses_none(n_tokens):
    layer, p = _moe()
    p = dict(p, bias=jnp.zeros((24,)).at[6].set(10.0))
    x = jnp.asarray(np.random.default_rng(4).normal(size=(n_tokens, 64)),
                    jnp.float32)
    (y, counts), _ = layer.apply(p, {}, x)
    c = counts_dict(counts)
    assert c["max_load"] == n_tokens    # no capacity, no dropped token
    sizes = dict(ref.sizes_of(dict(SMALL, max_len=MAX_LEN)), held=(4, 12))
    np.testing.assert_allclose(y, ref.expert_layer(p, sizes, x),
                               atol=F32_TOL)


def test_held_experts_must_be_a_range_of_the_routed_ones():
    with pytest.raises(ValueError, match="held experts"):
        DroplessExperts(64, 32, 16, 8, 4, held=(12, 20))
    with pytest.raises(ValueError, match="top_k"):
        DroplessExperts(64, 32, 2, 1, 4)


# --------------------------------------- (f) a lower precision fails
def test_bfloat16_matrices_fail_the_float32_tolerance():
    """The tolerance of (a) and (b) is tight enough to tell the stated
    precision from the next lower one: the same model with its matrices
    (and so its activations) in bfloat16 is 50 times further from the
    reference than the tolerance allows."""
    model, params, sizes = make((0, 16), seed=7)
    toks = tokens_of(37, 7)
    want = np.asarray(ref.forward_logits(params, sizes, toks))
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    logits, _ = model.apply(low, {}, jnp.asarray(toks)[None])
    err = float(np.max(np.abs(np.asarray(logits[0], np.float32) - want)))
    assert err > 50 * F32_TOL, err
    # and the reference's own int8 control separates from float32
    ctl = np.asarray(ref.forward_logits(params, sizes, toks, "int8"))
    assert float(np.max(np.abs(ctl - want))) > 50 * F32_TOL


# ------------------------------------------------ (g) the engine, end to end
def _served(model, params, prompts, new, **kw):
    eng = LMEngine(model, params=params, **kw)
    reqs = [eng.submit(p, new) for p in prompts]
    eng.run_until_idle(timeout_s=300)
    return eng, reqs


def _gaps(params, sizes, prompts, reqs):
    out = []
    for prompt, req in zip(prompts, reqs):
        assert req.error is None
        g, first = ref.served_gaps(params, sizes, prompt, list(req.tokens))
        out.append(g)
    return np.concatenate(out)


@pytest.mark.parametrize("num_pages,preempts", [(40, False), (9, True)])
def test_engine_serves_tokens_the_reference_would(num_pages, preempts):
    """submit / pump through the engine's own scheduler, allocator,
    buckets and sampling; the greedy tokens scored by the reference's
    logit gap.  With 8 pages of 4 for three requests of up to 7 + 10
    tokens the pool runs out: the youngest request is preempted and
    re-admitted with its prefix, and its tokens still score."""
    model, params, sizes = make((4, 12), seed=21)
    prompts = [list(tokens_of(n, n)) for n in (5, 7, 3)]
    eng, reqs = _served(model, params, prompts, 10, max_batch=3,
                        page_size=4, num_pages=num_pages)
    assert all(len(r.tokens) == 10 for r in reqs)
    st = eng.stats()
    assert (st["preemptions"] > 0) == preempts
    gaps = _gaps(params, sizes, prompts, reqs)
    assert gaps.shape == (30,)
    assert float(gaps.max()) <= GAP_LIMIT, gaps
    assert eng.cache.vp is None and len(eng.cache.buffers()) == 1
    assert st["kv_pages_in_use"] == 0


def test_a_wrong_position_is_caught_by_the_gap_limit():
    """The limit of (g) is not vacuous: tokens served from a cache whose
    lengths are off by one score far over it."""
    model, params, sizes = make((4, 12), seed=21)
    prompts = [list(tokens_of(9, 1))]
    eng = LMEngine(model, params=params, max_batch=2, page_size=4,
                   num_pages=20)
    fn = eng._step_fn

    def off_by_one(params_, buf, tables, lengths, *rest):
        return fn(params_, buf, tables, jnp.maximum(lengths - 1, 0), *rest)

    eng._step_fn = off_by_one
    req = eng.submit(prompts[0], 8)
    eng.run_until_idle(timeout_s=300)
    gaps = _gaps(params, sizes, prompts, [req])
    assert float(gaps.max()) > 20 * GAP_LIMIT


def test_engine_in_bfloat16_has_a_bfloat16_latent_cache():
    model, params, sizes = make((4, 12), seed=5, dtype=jnp.bfloat16)
    # the model's default pads a row to the chip's 128 lanes
    assert LongCatFlash(max_len=MAX_LEN, params=params, **SMALL) \
        .cache_spec(params)["row_width"] == 128
    prompts = [list(tokens_of(6, 2))]
    eng, reqs = _served(model, params, prompts, 6, max_batch=2,
                        page_size=4, num_pages=20)
    assert eng.cache.kp.dtype == jnp.bfloat16
    assert eng.cache.kp.shape == (4, 20, 4, 20)
    gaps = _gaps(params, sizes, prompts, reqs)
    # bf16 rounding against a float32 reference: small, not float32-small
    assert float(gaps.mean()) < 0.05


# ------------------------------------------- (h) spans and counters
def test_spans_carry_the_routing_counts_and_the_registry_counts_them(
        tmp_path, monkeypatch):
    from bigdl_tpu.obs import names
    from bigdl_tpu.serving import spans as S

    monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path / "trace"))
    obs.reset()
    try:
        model, params, _ = make((4, 12), seed=3)
        prompts = [list(tokens_of(n, n)) for n in (5, 6)]
        eng, reqs = _served(model, params, prompts, 5, max_batch=2,
                            page_size=4, num_pages=20)
        tracer = obs.get_tracer()
        tracer.flush()
        with open(tracer.jsonl_path, encoding="utf-8") as fh:
            recs = [json.loads(line) for line in fh]
        spans = [r for r in recs if r["kind"] == "span"]
        steps = sorted((s for s in spans
                        if s["name"] == S.SPAN_STEP_DECODE),
                       key=lambda s: s["wall_time"])
        prefills = [s for s in spans if s["name"] == S.SPAN_STEP_PREFILL]
        settles = [r for r in recs if r["kind"] == "event"
                   and r["name"] == S.EVENT_SETTLE]
        assert len(steps) == eng.stats()["steps"] and len(prefills) == 2
        # one step is in flight: a step's counts come back with its
        # tokens, so they ride on the NEXT step's span (the one that
        # read them) and the last step's on the settle's event
        assert "moe_held" not in steps[0]["attrs"]
        assert [e["attrs"]["reason"] for e in settles] == ["idle"]
        routed = [(s["attrs"], t["attrs"]["active"])
                  for s, t in zip(steps[1:] + settles, steps)]
        # ... and a prefill's with its first token, read late: on its
        # ``serve.read``
        reads = {s["attrs"]["request"]: s["attrs"] for s in spans
                 if s["name"] == S.SPAN_STEP_READ
                 and s["attrs"]["program"] == "prefill"}
        routed += [(reads[s["attrs"]["request"]], s["attrs"]["prompt_len"])
                   for s in prefills]
        total = {k: 0 for k in ("held", "zero", "absent")}
        for a, tokens in routed:
            assert {"moe_held", "moe_zero", "moe_absent", "moe_hit",
                    "moe_max_load"} <= set(a)
            assert a["moe_held"] + a["moe_zero"] + a["moe_absent"] == \
                4 * 2 * tokens
            assert a["moe_hit"] <= 2 * 8
            assert a["moe_max_load"] <= tokens
            for k in total:
                total[k] += a[f"moe_{k}"]
        for (a, tokens) in routed[:len(steps)]:
            # the rows of context that step had to read, its own included
            assert a["context_tokens"] >= tokens
        assert steps[1]["attrs"]["context_tokens"] == (5 + 1) + (6 + 1)
        # ... and what the attention kernel's stream copied for them:
        # each slot's few rows lie in one group of 8 pages of 4
        assert steps[1]["attrs"]["attn_rows_copied"] == 2 * 8 * 4
        reg = obs.get_registry()
        fam = reg.counter(names.SERVE_MOE_ASSIGNMENTS_TOTAL, "",
                          labels=("kind",))
        for k, v in total.items():
            assert fam.labels(kind=k).value == v
        gauge = reg.gauge(names.SERVE_MOE_LOAD_MAX_OVER_MEAN, "")
        assert gauge._solo().value >= 1.0
    finally:
        obs.reset()


def test_step_programs_carry_the_new_scopes():
    model, params, _ = make((4, 12), seed=3)
    eng = LMEngine(model, params=params, max_batch=2, page_size=4,
                   num_pages=20)
    tables, lengths = eng.cache.device_tables(pages=2)
    z = jnp.zeros((2,), jnp.int32)
    no = jnp.zeros((2,), bool)
    step = eng._step_fn.lower(
        eng.params, eng.cache.kp, tables, lengths, z,
        jnp.zeros((2,), jnp.float32), no,
        jax.random.key(0)).as_text(debug_info=True)
    pre = eng._prefill_fn(8).lower(
        eng.params, eng.cache.kp, jnp.zeros((1, 8), jnp.int32), 5,
        jnp.zeros((2,), jnp.int32), 0.0, jax.random.key(1),
        np.int32(1), z).as_text(debug_info=True)
    for scope in ("mla.proj", "kv_write", "mla.attn", "ffn", "moe.route",
                  "moe.experts", "moe.zero", "dense", "sample"):
        assert f"/{scope}/" in step, scope
        assert f"/{scope}/" in pre, scope


# ----------------------------------------------- (i) what is not offered
@pytest.mark.parametrize("kw,what", [
    (dict(int8=True), "int8=True"), (dict(tp=2), "tp > 1")])
def test_int8_and_tp_are_refused_with_a_reason(kw, what):
    model, params, _ = make()
    with pytest.raises(ValueError, match="LongCatFlash does not offer "
                       + what):
        LMEngine(model, params=params, max_batch=2, page_size=4, **kw)


# --------------------------------------------- the cache, as it is stated
def test_a_cache_states_its_row_and_its_buffers():
    one = PagedKVCache(3, row_width=20, buffers=1, page_size=4,
                       num_pages=5, max_slots=2, max_len=16)
    assert one.kp.shape == (3, 5, 4, 20) and one.vp is None
    assert one.buffers() == (one.kp,)
    two = PagedKVCache(2, 4, 8, page_size=4, num_pages=5, max_slots=2,
                       max_len=16)
    assert two.row_width == 32 and len(two.buffers()) == 2
    fresh = jnp.ones_like(two.kp)
    two.set_buffers((fresh, two.vp))
    assert two.kp is fresh
    with pytest.raises(ValueError, match="row_width"):
        PagedKVCache(2, page_size=4)
    with pytest.raises(ValueError, match="buffers"):
        PagedKVCache(2, row_width=8, buffers=3)


def test_transformer_lm_states_its_cache_too():
    from bigdl_tpu.models.transformer import build_transformer_lm

    m = build_transformer_lm(32, dim=16, n_head=2, n_layer=2, max_len=32)
    spec = m.cache_spec(m.params())
    assert (spec["layers"], spec["row_width"], spec["buffers"],
            spec["heads"], spec["head_dim"], spec["max_len"]) == \
        (2, 16, 2, 2, 8, 32)
    eng = LMEngine(m, max_batch=2, page_size=4)
    assert eng.cache.kp.shape == eng.cache.vp.shape == (2, 17, 4, 16)


# ------------------------------------------------- the grouped product
@pytest.mark.parametrize("m,dtype", [(256, jnp.float32), (200, jnp.float32),
                                     (384, jnp.bfloat16)])
def test_the_grouped_kernel_equals_ragged_dot_on_the_rows_of_a_group(m,
                                                                     dtype):
    """The Pallas grouped product the TPU takes, here in the Pallas
    interpreter, against ``jax.lax.ragged_dot`` (what the CPU takes):
    equal on every row of a group; rows behind the last group are
    nobody's.  ``m`` 200 is padded to whole row tiles inside."""
    from bigdl_tpu.ops.grouped_matmul import grouped_matmul

    rng = np.random.default_rng(m)
    g, k, n = 4, 256, 128
    lhs = jnp.asarray(rng.normal(size=(m, k)), dtype)
    rhs = jnp.asarray(rng.normal(size=(g, k, n)) * 0.1, dtype)
    sizes = jnp.asarray([37, 0, 90, 5], jnp.int32)   # one group is empty
    live = int(sizes.sum())
    want = grouped_matmul(lhs, rhs, sizes, impl="ragged",
                          preferred_element_type=jnp.float32)
    got = grouped_matmul(lhs, rhs, sizes, impl="pallas_interpret",
                         preferred_element_type=jnp.float32)
    assert got.shape == want.shape == (m, n)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[:live], want[:live], atol=tol)
    # by hand: row 40 is group 2's (37 + 0 <= 40 < 127)
    np.testing.assert_allclose(
        want[40], lhs[40].astype(jnp.float32) @ rhs[2].astype(jnp.float32),
        atol=tol * 10)
    # on the CPU "auto" is ragged_dot; widths that are not whole lane
    # tiles never reach the kernel
    np.testing.assert_array_equal(
        grouped_matmul(lhs, rhs, sizes, preferred_element_type=jnp.float32),
        want)
    with pytest.raises(ValueError, match="multiples of 128"):
        grouped_matmul(lhs[:, :64], rhs[:, :64], sizes, impl="pallas")
    with pytest.raises(ValueError, match="impl must be"):
        grouped_matmul(lhs, rhs, sizes, impl="dense")


#: the three expert cells' shape families scaled down: name -> (rows M,
#: (K, N), group sizes, the tiles the rule picks).  Each holds an empty
#: group and one that straddles two 128-row tiles; the first two leave
#: most rows behind the last group, as a layer that holds a share of its
#: router's experts does, "sdar" fills every row
GROUPED_FAMILIES = {
    "longcat-2-rows-a-group": (384, (512, 256), [2, 0, 3, 1] * 2 + [120, 9],
                               (128, 512, 256)),
    "joyai-16-rows-a-group-down": (512, (768, 256),
                                   [16, 21, 0, 11, 19, 14, 25, 16, 13, 20],
                                   (128, 768, 256)),
    "sdar-32-rows-a-group": (384, (256, 768),
                             [40, 0, 24, 32, 50, 30, 48, 32, 17, 47, 33, 31],
                             (128, 256, 768)),
    "sdar-32-rows-a-group-down": (384, (768, 256),
                                  [40, 0, 24, 32, 50, 30, 48, 32, 17, 47, 33,
                                   31], (128, 768, 256)),
}


@pytest.mark.parametrize("out", [jnp.bfloat16, jnp.float32],
                         ids=["bf16-out", "f32-out"])
@pytest.mark.parametrize("family", GROUPED_FAMILIES)
def test_the_grouped_kernel_at_the_tiles_the_rule_picks(family, out):
    """bf16 rows and matrices through the kernel (interpreted) at the
    tiles the rule picks for 768-wide experts (the whole width, either
    way round), against ``ragged_dot``: equal on every row of a group,
    with an empty group, groups that straddle row tiles and rows behind
    the last."""
    from bigdl_tpu.ops.grouped_matmul import _tiling, grouped_matmul

    m, (k, n), sizes, tiles = GROUPED_FAMILIES[family]
    assert _tiling(k, n) == tiles
    ends = np.cumsum(sizes)
    straddles = sum((e - s) // 128 != (e - 1) // 128
                    for e, s in zip(ends, sizes) if s)
    assert 0 in sizes and straddles and ends[-1] <= m, family
    rng = np.random.default_rng(len(family))
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), k, n)) * k ** -0.5,
                      jnp.bfloat16)
    live = int(ends[-1])
    sizes = jnp.asarray(sizes, jnp.int32)
    want = grouped_matmul(lhs, rhs, sizes, impl="ragged",
                          preferred_element_type=jnp.float32)
    got = grouped_matmul(lhs, rhs, sizes, impl="pallas_interpret",
                         preferred_element_type=out)
    assert got.shape == (m, n) and got.dtype == out
    # float32 sums of the same bf16 products in another order; a bf16
    # result is rounded once more (2 ** -8 of values near 1)
    tol = 1e-5 if out == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[:live].astype(jnp.float32), want[:live],
                               atol=tol)


#: the rule at the cells' REAL widths, as the table in
#: ``ops/grouped_matmul.py``'s docstring has them: (K, N, bytes a value)
#: -> (tm, tk, tn)
TILES_AT_THE_CELLS = {
    "6144x2048": ((6144, 2048, 2), (128, 2048, 1024)),
    "2048x6144": ((2048, 6144, 2), (128, 2048, 1024)),
    "2048x768": ((2048, 768, 2), (128, 2048, 768)),
    "768x2048": ((768, 2048, 2), (128, 768, 2048)),
    # float32 matrices: half as many values fit; one K tile before two
    "2048x768-float32": ((2048, 768, 4), (128, 2048, 384)),
    "1536x1536": ((1536, 1536, 2), (128, 1536, 768)),
    "256x128": ((256, 128, 2), (128, 256, 128)),
}


@pytest.mark.parametrize("case", TILES_AT_THE_CELLS)
def test_the_lane_tiles_follow_from_the_widths(case):
    """``_tiling`` is a pure function of the matrices' widths: at the
    three cells' widths it returns what the module's table says; a lane
    tile is a multiple of 128 that divides its width; and the blocks
    the kernel keeps in fast memory (two of each operand and of the
    result, the float32 accumulator) fit what the module says a kernel
    has."""
    gm = importlib.import_module("bigdl_tpu.ops.grouped_matmul")
    (k, n, itemsize), want = TILES_AT_THE_CELLS[case]
    tm, tk, tn = got = gm._tiling(k, n, itemsize)
    assert got == want and tm == gm._TM == 128
    assert tk % 128 == 0 and k % tk == 0 and tn % 128 == 0 and n % tn == 0
    assert 2 * tk * tn * itemsize <= gm._RHS_BUFFERS
    assert (2 * tk * tn * itemsize + 2 * tm * tk * itemsize
            + 2 * tm * tn * 4 + tm * tn * 4) <= gm._FAST_MEMORY


def test_the_rule_knows_lanes_and_no_model():
    """Widths off the 128-lane grid have no tiling (they take
    ``ragged_dot``); the module tells shapes apart, never models."""
    gm = importlib.import_module("bigdl_tpu.ops.grouped_matmul")
    assert gm._tiling(64, 32) is None
    assert gm._tiling(2048, 700) is None
    with open(gm.__file__, encoding="utf-8") as fh:
        source = fh.read().lower()
    assert not [name for name in ("longcat", "joyai", "sdar")
                if name in source]


def test_the_kernel_path_says_its_tiles_once_a_distinct_shape(
        tmp_path, monkeypatch):
    """``grouped_matmul.tiling``: one event of the tracer a distinct
    ``(M, G, K, N)`` that reaches the kernel, with the shapes and the
    tiles chosen; ``ragged`` says nothing, and with tracing off nothing
    is asked of the tracer at all."""
    from bigdl_tpu.obs.trace import NullTracer

    # (the package's attribute of this name is the function)
    gm = importlib.import_module("bigdl_tpu.ops.grouped_matmul")
    lhs = jnp.ones((128, 128), jnp.float32)
    rhs = jnp.ones((2, 128, 256), jnp.float32)
    sizes = jnp.asarray([3, 9], jnp.int32)

    def run(rows=128, **kw):
        return gm.grouped_matmul(lhs[:rows], rhs, sizes, **kw)

    def said(tracer):
        return [r["attrs"] for r in tracer.recent()
                if r["name"] == "grouped_matmul.tiling"]

    monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path / "trace"))
    obs.reset()
    gm._say.cache_clear()
    try:
        tracer = obs.get_tracer()
        run(impl="ragged")
        assert said(tracer) == []
        run(impl="pallas_interpret")
        run(impl="pallas_interpret")                  # the same shape
        run(rows=64, impl="pallas_interpret")
        tiles = dict(groups=2, k=128, n=256, tm=128, tk=128, tn=256)
        assert said(tracer) == [dict(m=128, **tiles), dict(m=64, **tiles)]
        # tracing off: the shared null tracer, and not one call of it
        monkeypatch.delenv("BIGDL_TRACE_DIR")
        obs.reset()
        gm._say.cache_clear()
        monkeypatch.setattr(
            NullTracer, "event",
            lambda *a, **k: pytest.fail("the null tracer was asked"))
        run(impl="pallas_interpret")
        assert obs.get_tracer().recent() == []
    finally:
        obs.reset()
        gm._say.cache_clear()


def test_the_expert_layer_round_trips_through_the_serializer(tmp_path):
    from bigdl_tpu.utils.serializer import load_module, save_module

    layer, _ = _moe()
    x = jnp.asarray(np.random.default_rng(8).normal(size=(11, 64)),
                    jnp.float32)
    layer.evaluate()
    y, counts = layer.forward(x)
    loaded = load_module(save_module(layer, str(tmp_path / "moe")))
    loaded.evaluate()
    assert (loaded.lo, loaded.hi, loaded.top_k) == (4, 12, 4)
    y2, counts2 = loaded.forward(x)
    np.testing.assert_allclose(y, y2, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts2))
