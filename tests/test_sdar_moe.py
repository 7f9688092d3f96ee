"""SDAR-30B-A3B-Chat at a tiny size on the CPU, seeded weights, float32:

(a) the layer and the whole model against the plain reference
    (``benchmarks/reference/sdar_30b_a3b_chat.py``) on LOGITS, tight enough that
    bfloat16 matrices fail, and failing with the per-head norms, the
    renormalisation or the in-block attention left out;
(b) the expert layer with softmax scores and renormalised weights: the
    shares of four chips add up to the uncut reference;
(c) the generalised ``paged_decode_attention``: one query a slot and a
    key head a query head bit for bit what it was, a block of four
    positions with eight query heads a key head against a plain einsum
    with page 0 full of garbage; ``decode_hbm_bytes`` by key/value
    heads;
(d) prefill, then block passes through the paged cache (a prompt that
    ends inside a block, a context that crosses pages, a slot whose
    step also writes the final rows of the block before beside one
    whose tail is padding) against the reference's two-pass form: the
    rows the cache ends up holding for every finished block are the
    committed ones, a padding tail changes nothing, and the oracle
    fails where the commit is left out;
(e) the engine: seeded and CONSTRUCTED weights (:func:`confident`: the
    seeded model with its head scaled up, so that confidences pass the
    threshold and a block is done in 1, 2 or 3 passes) against a
    plain replay of the generation procedure over the reference's
    forward; EOS inside a block; an answer that is no whole number of
    blocks; preemption, a settle and a weight swap with a block half
    refined; emission by prefix; one step in flight against a settled
    loop; no forward that yields nothing (a token a forward on seeded
    weights, a tail for every block that finished and did not end its
    request); spans, counters, scopes and refusals.

Tolerances: ``F32_TOL`` bounds float32 accumulation-order noise on
logits of magnitude about 5 (measured 4e-6); ``GAP_LIMIT`` bounds a
logit gap between two float32 computations of the same state (a flipped
near-tie reads its margin, under 1e-4 here).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import late_read_cases

from benchmarks.reference import sdar_30b_a3b_chat as ref
from bigdl_tpu import obs
from bigdl_tpu.models.sdar_moe import (FINISHED, REFINED, SDARMoE,
                                       build_sdar_moe, pass_counts, unmask)
from bigdl_tpu.nn.experts import DroplessExperts
from bigdl_tpu.ops.decode_attention import (decode_hbm_bytes,
                                            paged_decode_attention)
from bigdl_tpu.serving import LMEngine
from bigdl_tpu.serving.cache import PagedKVCache, pool_shape
from bigdl_tpu.serving.steps import GIVEN, NEVER_UNMASKED

F32_TOL = 2e-4
GAP_LIMIT = 1e-3

VOCAB, MASK_ID, B = 96, 95, 4
SMALL = dict(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
             num_attention_heads=8, num_key_value_heads=2, head_dim=8,
             moe_intermediate_size=16, num_experts=8,
             num_experts_per_tok=2, norm_topk_prob=True,
             rms_norm_eps=1e-6, rope_theta=1e6)
GEN = dict(block_length=B, denoising_steps=4,
           rule="low_confidence_dynamic", threshold=0.9,
           mask_token_id=MASK_ID)
MAX_LEN = 64


def make(seed=7, dtype=jnp.float32, std=0.3, held=(0, 8), gen=None):
    """Seeded weights from the reference, the reference's sizes, and the
    program's model built around that tree without weights of its own."""
    cfg = dict(SMALL, held_experts=list(held), max_len=MAX_LEN,
               initializer_range=std, generation=dict(GEN, **(gen or {})))
    sizes = ref.sizes_of(cfg)
    params = ref.init_params(seed, sizes, dtype)
    return build_sdar_moe(cfg, params=params), params, sizes


def confident(seed=7, scale=6.0, **kw):
    """CONSTRUCTED weights: the seeded model with its head scaled up, so
    that the largest softmax probability passes the threshold at some
    positions and not at others."""
    model, params, sizes = make(seed, **kw)
    params = dict(params, head={"weight": params["head"]["weight"] * scale})
    model.set_params(params)
    return model, params, sizes


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, MASK_ID, size=n).astype(np.int32)


# ------------------------------------------------------------ (a) forward
@pytest.mark.parametrize("seed,length", [(7, 19), (8, 33), (9, 6)])
def test_full_forward_equals_the_reference(seed, length):
    model, params, sizes = make(seed)
    toks = tokens_of(length, seed)
    got, _ = model.apply(params, {}, jnp.asarray(toks)[None])
    want = ref.forward_logits(params, sizes, toks)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    np.testing.assert_allclose(got[0], want, atol=F32_TOL)


def test_one_layer_equals_the_reference():
    model, params, sizes = make(5)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 24, 32)),
                    jnp.float32)
    layer = model._children["l1"]
    attn = layer._children["attn"]
    got, counts = layer.run(
        params["l1"], x,
        lambda xn: attn.prefill(params["l1"]["attn"], xn, B)[0], None)
    padded = jnp.zeros((1, 128, 32), jnp.float32).at[:, :24].set(x)
    want = ref.layer_forward(params["l1"], sizes, padded)[0, :24]
    np.testing.assert_allclose(got[0], want, atol=F32_TOL)
    assert int(counts[0]) == 24 * 2 and int(counts[2]) == 0


def test_bfloat16_matrices_fail_the_float32_tolerance():
    model, params, sizes = make(11)
    toks = tokens_of(21, 3)
    want = ref.forward_logits(params, sizes, toks)
    low = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        if a.ndim >= 2 else a, params)
    got, _ = model.apply(low, {}, jnp.asarray(toks)[None])
    assert float(jnp.max(jnp.abs(got[0] - want))) > 10 * F32_TOL


@pytest.mark.parametrize("variant", ["no_head_norm", "no_renorm",
                                     "causal_block"])
def test_a_part_left_out_fails_the_float32_tolerance(variant):
    """The reference without the per-head norms, without the top-k
    renormalisation, or with a causal mask inside the block is another
    function: the program is compared tightly enough to tell."""
    model, params, sizes = make(12)
    toks = tokens_of(22, 4)
    got, _ = model.apply(params, {}, jnp.asarray(toks)[None])
    wrong = ref.forward_logits(params, sizes, toks, variant=variant)
    assert float(jnp.max(jnp.abs(got[0] - wrong))) > 10 * F32_TOL


def test_the_two_reference_copies_are_one_text():
    import os

    import bigdl_tpu

    # the one copy left under ``bigdl_tpu/models/`` (PR 42 took the other
    # three out): ``benchmarks/tests/test_benchmark_serve_lm_block.py``
    # reads it by its path, and no file under ``benchmarks/`` may change
    with open(os.path.join(os.path.dirname(bigdl_tpu.__file__), "models",
                           "sdar_moe_reference.py")) as fh:
        program = fh.read()
    with open(ref.__file__) as fh:
        assert fh.read() == program


def test_a_model_given_params_draws_no_weights_and_builds_from_a_config(
        monkeypatch):
    from bigdl_tpu import common

    _, params, _ = make(3)

    class Never:
        def normal(self, *a, **k):
            raise AssertionError("a weight was drawn")

        uniform = normal

    monkeypatch.setattr(common.RandomGenerator, "RNG", Never())
    cfg = dict(SMALL, max_len=MAX_LEN, generation=GEN,
               intermediate_size=6144, model_type="sdar_moe")
    model = build_sdar_moe(cfg, params=params)
    assert model.params() is params
    spec = model.cache_spec(params)
    assert (spec["heads"], spec["kv_heads"], spec["head_dim"],
            spec["row_width"], spec["buffers"], spec["layers"]) == \
        (8, 2, 8, 16, 2, 2)
    assert model.block_spec(params) == {
        "block_length": 4, "passes": 4, "threshold": 0.9}
    static = build_sdar_moe(dict(cfg, generation=dict(
        GEN, rule="low_confidence_static")), params=params)
    assert static.block_spec(params)["threshold"] == float("inf")
    with pytest.raises(TypeError, match="unknown sizes"):
        SDARMoE(q_lora_rank=4)
    with pytest.raises(ValueError, match="passes"):
        SDARMoE(params=params, generation={"denoising_steps": 5}, **SMALL)
    with pytest.raises(ValueError, match="whole number of blocks"):
        SDARMoE(params=params, max_len=62, **SMALL)


# ------------------------------------------------- (b) the expert layer
def test_the_shares_add_up_to_the_uncut_layer_softmax_renormalised():
    """Four shares of four experts each, softmax scores with the top-k
    weights renormalised over ALL the chosen, held or absent: the sum of
    the shares is the uncut layer, in the program and in the
    reference."""
    kw = dict(score="softmax", renormalise=True, shared_hidden=0,
              scale=1.0)
    full = DroplessExperts(64, 32, 16, 0, 4, **kw)
    p = full.params()
    x = jnp.asarray(np.random.default_rng(2).normal(size=(23, 64)),
                    jnp.float32)
    (want, counts), _ = full.apply(p, {}, x)
    assert int(counts[0]) == 23 * 4 and int(counts[2]) == 0
    idx, w = full.route(p, x)
    np.testing.assert_allclose(np.sum(w, axis=-1), 1.0, atol=1e-6)
    sizes = ref.sizes_of(dict(
        SMALL, hidden_size=64, moe_intermediate_size=32, num_experts=16,
        num_experts_per_tok=4, max_len=MAX_LEN))
    np.testing.assert_allclose(ref.expert_layer(p, sizes, x), want,
                               atol=F32_TOL)
    total = total_ref = 0.0
    for lo in range(0, 16, 4):
        share = DroplessExperts(64, 32, 16, 0, 4, held=(lo, lo + 4),
                                init=False, **kw)
        ps = dict(p, **{n: p[n][lo:lo + 4]
                        for n in ("w_gate", "w_up", "w_down")})
        (y, c), _ = share.apply(ps, {}, x)
        assert int(c[0]) + int(c[2]) == 23 * 4
        total = total + y
        total_ref = total_ref + ref.expert_layer(
            ps, dict(sizes, held=(lo, lo + 4)), x)
    np.testing.assert_allclose(total, want, atol=F32_TOL)
    np.testing.assert_allclose(total_ref, want, atol=F32_TOL)
    # without the renormalisation the layer is another one
    plain = DroplessExperts(64, 32, 16, 0, 4, init=False,
                            **dict(kw, renormalise=False))
    (other, _), _ = plain.apply(p, {}, x)
    assert float(jnp.max(jnp.abs(other - want))) > 10 * F32_TOL


@pytest.mark.parametrize("real_rows", [0, 9, 16, 17, 24])
def test_an_expected_count_of_real_rows_changes_no_count_and_no_row(
        real_rows):
    """A layer told to expect 16 real rows of 24: the expert layer
    sees the real rows packed, 16 of them a run; one run while the mask
    holds no more, two with more, none with none.  Either way the real
    rows' outputs, the padding's zeros and the assignments counted are
    those of the layer without the hint; a second run counts its
    experts as another layer's."""
    model, params, _ = make(5)
    layer, p = model._children["l1"], params["l1"]
    rng = np.random.default_rng(real_rows)
    x = jnp.asarray(rng.normal(size=(6, 4, 32)), jnp.float32)
    mask = jnp.asarray((rng.permutation(24) < real_rows).reshape(6, 4))
    want, counts = layer.run(p, x, lambda xn: xn, mask)
    got, same = layer.run(p, x, lambda xn: xn, mask, expected=16)
    np.testing.assert_allclose(got, want, atol=1e-6)
    same, counts = np.asarray(same), np.asarray(counts)
    assert np.array_equal(same[:3], counts[:3])
    assert int(counts[0]) == real_rows * 2
    if real_rows <= 16:
        assert np.array_equal(same, counts)
    else:
        assert same[3] >= counts[3] and same[4] <= counts[4]
    def program(expected):
        return str(jax.make_jaxpr(lambda x, m: layer.run(
            p, x, lambda xn: xn, m, expected=expected))(x, mask))

    # one expert layer in the program, inside a loop: the products of
    # the layer without the hint and no more, and no second arm
    packed, whole = program(16), program(24)
    assert "while[" in packed and "while[" not in whole
    assert "cond[" not in packed
    assert packed.count("ragged_dot") == whole.count("ragged_dot") > 0


# ------------------------------------------ (c) the attention body
def _pools(rng, n_pages, page, hkv, d, dtype=jnp.float32):
    shape = pool_shape(n_pages, page, hkv, d)
    return (jnp.asarray(rng.normal(size=shape), dtype),
            jnp.asarray(rng.normal(size=shape), dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_query_a_key_head_a_query_head_is_bit_for_bit_the_old_body(
        dtype):
    """``S`` = 1, ``H_kv`` = ``H``: the block-diagonal contraction as it
    was before rows could share a key head (written out here), whether
    ``q`` comes as (B, H, Dh) or as (B, 1, H, Dh)."""
    from bigdl_tpu.serving.cache import gather_pages

    rng = np.random.default_rng(0)
    b, h, d, page = 3, 4, 8, 4
    kp, vp = _pools(rng, 9, page, h, d, dtype)
    q = jnp.asarray(rng.normal(size=(b, h, d)), dtype)
    tables = jnp.asarray(rng.integers(1, 9, size=(b, 4)), jnp.int32)
    lengths = jnp.asarray([5, 0, 14], jnp.int32)

    def old(q, kp, vp, tables, lengths):
        kall, vall = gather_pages(kp, tables), gather_pages(vp, tables)
        eye = jnp.eye(h, dtype=q.dtype)
        qmat = (q[:, :, :, None] * eye[None, :, None, :]).reshape(
            b, h * d, h)
        scores = jnp.einsum("bkc,bch->bhk", kall, qmat) * d ** -0.5
        mask = jnp.arange(kall.shape[1])[None, None, :] \
            <= lengths[:, None, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        full = jnp.einsum("bhk,bkc->bhc", probs, vall)
        eye = jnp.eye(h, dtype=full.dtype)
        return jnp.sum(full.reshape(b, h, h, d) * eye[None, :, :, None],
                       axis=2)

    want = np.asarray(old(q, kp, vp, tables, lengths), np.float32)
    got = paged_decode_attention(q, kp, vp, tables, lengths, page_size=page)
    assert np.array_equal(np.asarray(got, np.float32), want)
    wide = paged_decode_attention(q[:, None], kp, vp, tables, lengths,
                                  page_size=page)
    assert wide.shape == (b, 1, h, d)
    assert np.array_equal(np.asarray(wide[:, 0], np.float32), want)
    assert jax.jit(old).lower(q, kp, vp, tables, lengths).as_text() \
        .count("dot_general") == jax.jit(
            lambda *a: paged_decode_attention(*a, page_size=page)).lower(
                q, kp, vp, tables, lengths).as_text().count("dot_general")


@pytest.mark.parametrize("layer", [None, 1])
def test_a_block_of_four_positions_eight_query_heads_a_key_head(layer):
    """``S`` = 4, 32 query heads over 4 key heads, against a plain
    einsum; page 0 is full of garbage and never read."""
    rng = np.random.default_rng(1)
    b, s, h, hkv, d, page = 3, 4, 32, 4, 8, 4
    kp, vp = _pools(rng, 12, page, hkv, d)
    kp, vp = kp.at[0].set(1e6), vp.at[0].set(-1e6)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    pages = np.zeros((b, 5), np.int32)
    ends = [7, 3, 18]                   # the block's last position
    for i, end in enumerate(ends):
        need = end // page + 1
        pages[i, :need] = rng.choice(np.arange(1, 12), need, replace=False)
    tables, lengths = jnp.asarray(pages), jnp.asarray(ends, jnp.int32)
    if layer is not None:
        kp = jnp.stack([jnp.zeros_like(kp), kp])
        vp = jnp.stack([jnp.zeros_like(vp), vp])
    got = paged_decode_attention(q, kp, vp, tables, lengths, layer=layer,
                                 page_size=page)
    assert got.shape == q.shape
    k1, v1 = (kp, vp) if layer is None else (kp[layer], vp[layer])
    for i, end in enumerate(ends):
        rows_k = np.asarray(k1)[pages[i]].reshape(-1, hkv, d)[:end + 1]
        rows_v = np.asarray(v1)[pages[i]].reshape(-1, hkv, d)[:end + 1]
        for head in range(h):
            j = head // (h // hkv)
            sc = np.asarray(q)[i, :, head] @ rows_k[:, j].T / np.sqrt(d)
            pr = np.exp(sc - sc.max(-1, keepdims=True))
            pr /= pr.sum(-1, keepdims=True)
            np.testing.assert_allclose(got[i, :, head], pr @ rows_v[:, j],
                                       atol=2e-5)


def test_decode_hbm_bytes_by_key_value_heads():
    """The gauge counts what the path taken at the shapes moves.  32
    query heads over 4 key/value heads at 4 positions share a key head:
    the kernel reads the bucket's K and V pages ONCE (by key/value
    heads) beside ``q`` and the output (by query heads at every
    position): no gather tax, no score plane.  So do grouped heads at
    one position.  One query row a key head reads what it read."""
    b, h, hkv, d, p, maxp, item, s = 128, 32, 4, 128, 16, 128, 2, 4
    k = maxp * p
    got = decode_hbm_bytes(b, h, d, p, maxp, item, kv_heads=hkv,
                           positions=s)
    assert got == 2.0 * b * k * hkv * d * item + 2.0 * b * s * h * d * 4
    assert decode_hbm_bytes(b, h, d, p, maxp, item, kv_heads=hkv) == (
        2.0 * b * k * hkv * d * item + 2.0 * b * h * d * 4)
    # several positions over a key head a query head share it too
    assert decode_hbm_bytes(b, hkv, d, p, maxp, item, positions=s) == (
        2.0 * b * k * hkv * d * item + 2.0 * b * s * hkv * d * 4)
    assert decode_hbm_bytes(12, 25, 64, 16, 32, 2) == decode_hbm_bytes(
        12, 25, 64, 16, 32, 2, kv_heads=25, positions=1) == (
        3 * 2.0 * 12 * 512 * 25 * 64 * 2 + 2.0 * 12 * 25 * 512 * 4
        + 2.0 * 12 * 25 * 64 * 4)
    model, params, _ = make(3)
    eng = LMEngine(model, params=params, max_batch=2, page_size=4)
    try:
        eng.submit([1, 2, 3], 2)
        eng.run_until_idle()
        bucket = eng.stats()["last_bucket_pages"]
        # a step hands the kernel the tail's positions and the block's
        want = eng._weight_bytes + 2 * decode_hbm_bytes(
            2, 8, 8, 4, bucket, 4, kv_heads=2, positions=2 * B)
        assert eng.stats()["decode_hbm_bytes_per_token"] == want
    finally:
        eng.close()


def test_the_cache_is_sized_by_key_value_heads():
    assert pool_shape(5, 16, 4, 128) == (5, 16, 512)
    cache = PagedKVCache(2, 4, 128, page_size=16, num_pages=5, max_slots=2,
                         max_len=64)
    assert (cache.kv_heads, cache.row_width) == (4, 512)
    assert cache.kp.shape == cache.vp.shape == (2, 5, 16, 512)


# --------------------------------- (d) prefill, then passes over the cache
def _stepper(model, params, page, slots, pages_per_slot):
    spec = model.cache_spec(params)
    cache = PagedKVCache(spec["layers"], spec["kv_heads"], spec["head_dim"],
                         page_size=page, num_pages=1 + slots * pages_per_slot,
                         max_slots=slots, max_len=page * pages_per_slot,
                         dtype=jnp.float32)
    # page 0 is trash: make it count if it is ever read
    cache.kp = cache.kp.at[:, 0].set(1e4)
    cache.vp = cache.vp.at[:, 0].set(-1e4)
    return cache


def _prefill(model, params, cache, slot, prompt, bucket):
    t0 = len(prompt)
    pages = cache.alloc(slot, t0)
    page_arg = np.zeros((bucket // cache.page_size,), np.int32)
    page_arg[:len(pages)] = pages
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :t0] = prompt
    bufs, (tok, msk), _ = model.paged_prefill(
        params, cache.buffers(), jnp.asarray(padded), t0,
        jnp.asarray(page_arg))
    cache.set_buffers(bufs)
    cache.lengths[slot] = t0 - t0 % B
    while cache.needs_growth(slot, B - 1):
        assert cache.grow(slot)
    return np.asarray(tok), np.asarray(msk)


def _reference_pass(params, sizes, prefix, block_tokens, block_masked,
                    variant=None):
    """Logits (B, V) of one pass: the final tokens before the block,
    then the block in its state, under the block-causal mask."""
    seq = list(prefix) + [MASK_ID if m else int(t)
                          for t, m in zip(block_tokens, block_masked)]
    return np.asarray(ref.forward_logits(params, sizes, seq,
                                         variant=variant))[-B:]


def _rows_at(cache, slot, positions):
    """The K and V rows the cache holds for ``slot`` at ``positions``,
    every layer: (2, layers, n, row)."""
    at = np.asarray(positions)
    pages = np.asarray(cache.page_tables[slot])[at // cache.page_size]
    return np.stack([np.asarray(buf)[:, pages, at % cache.page_size]
                     for buf in (cache.kp, cache.vp)])


def _committed_rows(model, params, page, final):
    """The rows a commit writes for the whole blocks of ``final``: the
    dense prefill of the final tokens under the block-causal mask (the
    program's other path, which (a) holds to the reference's logits)."""
    bucket = 16
    while bucket < len(final):
        bucket *= 2
    cache = _stepper(model, params, page, 1, bucket // page + 2)
    _prefill(model, params, cache, 0, np.asarray(final, np.int32), bucket)
    return _rows_at(cache, 0, np.arange(len(final)))


@pytest.mark.parametrize("page,t0", [(4, 6), (8, 13), (4, 8)],
                         ids=["ends_inside_a_block", "crosses_pages",
                              "ends_with_a_block"])
def test_prefill_then_block_passes_equal_the_reference(page, t0):
    """One slot through ``paged_prefill`` and eleven ``paged_decode``
    steps: every pass's logits against the reference's forward over the
    final tokens before the block and the block in its state, whether
    or not the same forward writes the final rows of the block before;
    the state rolls over in the step whose pass leaves the block final;
    and the rows the cache ends up holding for every finished block
    whose tail has been written are the committed ones."""
    from bigdl_tpu.serving.engine import pick_greedy

    model, params, sizes = make(13)
    cache = _stepper(model, params, page, 1, 64 // page)
    prompt = tokens_of(t0, 5)
    tok, msk = _prefill(model, params, cache, 0, prompt, 16)
    rem = t0 % B
    assert list(msk) == [False] * rem + [True] * (B - rem)
    assert list(tok[:rem]) == list(prompt[t0 - rem:])
    final = list(prompt[:t0 - rem])
    state = (jnp.asarray(tok)[None], jnp.asarray(msk)[None],
             jnp.zeros((1,), jnp.int32),
             jnp.asarray(cache.lengths[:1], jnp.int32),
             jnp.zeros((1, B), jnp.int32), jnp.zeros((1,), bool))
    active = jnp.ones((1,), bool)
    kinds, tails = [], 0
    for _ in range(11):
        tokens, masked, passes, lengths, tail, pending = state
        tables = jnp.asarray(cache.page_tables[:1])
        bufs, logits, _ = model.block_logits(
            params, cache.buffers(), tables, lengths, tokens, masked, active,
            tail, pending)
        want = _reference_pass(params, sizes, final, np.asarray(tokens[0]),
                               np.asarray(masked[0]))
        np.testing.assert_allclose(logits[0], want, atol=F32_TOL)
        bufs, state, kind, _ = model.paged_decode(
            params, cache.buffers(), tables, lengths, tokens, masked,
            passes, tail, pending, active, pick=pick_greedy)
        cache.set_buffers(bufs)
        kinds.append(int(kind[0]))
        tails += int(pending[0])
        # an unmasked position is never changed
        keep = ~np.asarray(masked[0])
        shown = state[4] if kinds[-1] == FINISHED else state[0]
        assert np.array_equal(np.asarray(shown[0])[keep],
                              np.asarray(tokens[0])[keep])
        if kinds[-1] == FINISHED:
            # the block is the tail now, and a new one starts behind it
            final += [int(t) for t in state[4][0]]
            assert int(state[3][0]) == len(final) and bool(state[5][0])
            assert bool(jnp.all(state[1])) and int(state[2][0]) == 0
            assert not np.asarray(state[0]).any()
            cache.lengths[0] = len(final)
            while cache.needs_growth(0, B - 1):
                assert cache.grow(0)
        else:
            assert int(state[2][0]) == int(passes[0]) + 1
            assert not bool(state[5][0])
            assert int(state[3][0]) == int(lengths[0])
    assert kinds.count(FINISHED) >= 2 and kinds.count(REFINED) >= 8
    assert tails == kinds.count(FINISHED) - int(kinds[-1] == FINISHED)
    # every block that finished and whose tail rode a later forward
    # holds its committed rows (the blocks before them the prefill's)
    written = len(final) - B * int(bool(state[5][0]))
    assert written >= t0 - rem + B
    np.testing.assert_allclose(
        _rows_at(cache, 0, np.arange(written)),
        _committed_rows(model, params, page, final)[:, :, :written],
        atol=F32_TOL)


def test_bfloat16_matrices_fail_through_the_cache_too():
    """Prefill and one pass with the matrices rounded to bfloat16: the
    logits leave the float32 tolerance."""
    model, params, sizes = make(13)
    low = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        if a.ndim >= 2 else a, params)
    cache = _stepper(model, params, 4, 1, 16)
    prompt = tokens_of(10, 5)
    tok, msk = _prefill(model, low, cache, 0, prompt, 16)
    _, logits, _ = model.block_logits(
        low, cache.buffers(), jnp.asarray(cache.page_tables[:1]),
        jnp.asarray(cache.lengths[:1], jnp.int32), jnp.asarray(tok)[None],
        jnp.asarray(msk)[None], jnp.ones((1,), bool))
    want = _reference_pass(params, sizes, prompt[:8], tok, msk)
    assert float(np.max(np.abs(np.asarray(logits[0]) - want))) \
        > 10 * F32_TOL


def test_two_slots_in_different_phases_in_one_step():
    """Slot 0's step writes the final rows of the block it finished
    beside the first pass of its next block; slot 1 refines its first
    block and its tail is padding, in one ``paged_decode``: each does
    what it would alone."""
    from bigdl_tpu.serving.engine import pick_greedy

    model, params, sizes = make(14)
    cache = _stepper(model, params, 4, 2, 16)
    p0, p1 = tokens_of(8, 1), tokens_of(5, 2)
    _prefill(model, params, cache, 0, p0, 16)
    t1, m1 = _prefill(model, params, cache, 1, p1, 16)
    done = tokens_of(B, 9)              # slot 0's last block, all final
    cache.lengths[0] = 12
    while cache.needs_growth(0, B - 1):
        assert cache.grow(0)
    tokens = jnp.asarray(np.stack([np.zeros(B, np.int32), t1]))
    masked = jnp.asarray(np.stack([np.ones(B, bool), m1]))
    tail = jnp.asarray(np.stack([done, tokens_of(B, 10)]))   # 1: garbage
    lengths = jnp.asarray(cache.lengths[:2], jnp.int32)
    bufs, state, kind, counts = model.paged_decode(
        params, cache.buffers(), jnp.asarray(cache.page_tables[:2]),
        lengths, tokens, masked, jnp.zeros((2,), jnp.int32), tail,
        jnp.asarray([True, False]), jnp.ones((2,), bool), pick=pick_greedy)
    assert list(np.asarray(kind)) == [REFINED, REFINED]
    assert list(np.asarray(state[3])) == [12, 4]
    assert list(np.asarray(state[2])) == [1, 1]
    assert list(np.asarray(state[5])) == [False, False]
    # real rows x top-2 x layers: slot 0's tail and block, slot 1's block
    assert int(counts[0]) == 3 * B * 2 * 2
    # each unmasked one position: the reference's most confident there
    wants = [_reference_pass(params, sizes, list(p0) + list(done),
                             np.zeros(B), np.ones(B, bool)),
             _reference_pass(params, sizes, p1[:4], t1, m1)]
    for slot, want in enumerate(wants):
        before = np.asarray(masked[slot])
        conf = np.where(before, np.max(jax.nn.softmax(want, axis=-1),
                                       axis=-1), -1)
        newly = before & ~np.asarray(state[1][slot])
        assert newly.sum() == 1
        assert int(np.argmax(newly)) == int(np.argmax(conf))
        assert int(state[0][slot][np.argmax(newly)]) == int(
            np.argmax(want[np.argmax(newly)]))
    # slot 0's tail rows are what later blocks attend: the committed
    # rows of the final sequence; slot 1's garbage tail went nowhere
    cache.set_buffers(bufs)
    np.testing.assert_allclose(
        _rows_at(cache, 0, np.arange(8, 12)),
        _committed_rows(model, params, 4, list(p0) + list(done))[:, :, 8:],
        atol=F32_TOL)
    np.testing.assert_allclose(
        _rows_at(cache, 1, np.arange(4)),
        _committed_rows(model, params, 4, list(p1[:4])), atol=F32_TOL)


def test_a_padding_tail_changes_no_live_row_and_no_routing_count():
    """A slot with no tail pending forwards tail rows that are padding:
    whatever tokens they hold, no row of any page but the trash page
    changes, nor a routing count, a logit or the state after the step;
    and the pages of a slot that is not active are left alone."""
    from bigdl_tpu.serving.engine import pick_greedy

    model, params, _ = make(15)
    outs = []
    for garbage in (False, True):
        cache = _stepper(model, params, 4, 2, 16)
        t0, m0 = _prefill(model, params, cache, 0, tokens_of(6, 1), 16)
        t1, m1 = _prefill(model, params, cache, 1, tokens_of(9, 2), 16)
        before = [np.asarray(b) for b in cache.buffers()]
        tail = np.stack([tokens_of(B, 20), tokens_of(B, 21)]) * int(garbage)
        bufs, state, kind, counts = model.paged_decode(
            params, cache.buffers(), jnp.asarray(cache.page_tables[:2]),
            jnp.asarray(cache.lengths[:2], jnp.int32),
            jnp.asarray(np.stack([t0, t1])), jnp.asarray(np.stack([m0, m1])),
            jnp.zeros((2,), jnp.int32), jnp.asarray(tail),
            # slot 1 is not active, though a tail is pending there
            jnp.asarray([False, True]), jnp.asarray([True, False]),
            pick=pick_greedy)
        outs.append(([np.asarray(b)[:, 1:] for b in bufs],
                     [np.asarray(a) for a in state], np.asarray(kind),
                     np.asarray(counts)))
        # only slot 0's block changed: positions 4..7 of its pages
        for old, new in zip(before, bufs):
            changed = np.any(np.asarray(new) != old, axis=(0, 3))
            changed[0] = False                      # the trash page
            pages, at = np.nonzero(changed)
            assert set(pages) == {int(cache.page_tables[0][1])}
            assert set(at) == {0, 1, 2, 3}
        assert list(np.asarray(kind)) == [REFINED, 0]
        assert int(counts[0]) == B * 2 * 2          # slot 0's block alone
        assert bool(state[5][1]) and not bool(state[5][0])
    clean, dirty = outs
    for a, b in zip(clean[0] + clean[1][:4] + list(clean[2:]),
                    dirty[0] + dirty[1][:4] + list(dirty[2:])):
        assert np.array_equal(a, b)


def test_the_engine_and_the_model_name_a_step_s_kinds_alike():
    from bigdl_tpu.serving import steps

    assert (steps.BLOCK_REFINED, steps.BLOCK_FINISHED) == \
        (REFINED, FINISHED)
    assert steps.BLOCK_RESULT == ("length", "kind", "pass", "tail")
    assert (steps.NEVER_UNMASKED, steps.GIVEN) == \
        (ref.NEVER_UNMASKED, ref.GIVEN)


def test_the_unmasking_rule():
    """Generation's step 2 by hand: the threshold where enough pass it,
    the most confident otherwise, ties to the earlier position, never
    an unmasked one, ``min(n_s, m)``."""
    assert pass_counts(4, 4) == [1, 1, 1, 1]
    assert pass_counts(4, 3) == [2, 1, 1] and pass_counts(8, 3) == [3, 3, 2]
    conf = jnp.asarray([[0.95, 0.2, 0.93, 0.99],      # two pass 0.9
                        [0.5, 0.7, 0.7, 0.99],        # none does: a tie
                        [0.95, 0.2, 0.3, 0.1],        # one passes, n_s 2
                        [0.1, 0.2, 0.3, 0.4]])        # one masked left
    masked = jnp.asarray([[True, True, True, False],
                          [True, True, True, False],
                          [True, True, True, True],
                          [False, False, True, False]])
    got = unmask(conf, masked, jnp.asarray([0, 1, 0, 3]),
                 counts=[1, 1, 1, 1], threshold=0.9)
    assert np.asarray(got).tolist() == [
        [True, False, True, False], [False, True, False, False],
        [True, False, False, False], [False, False, True, False]]
    two = unmask(conf, masked, jnp.asarray([0, 0, 0, 0]),
                 counts=[2, 1, 1], threshold=0.9)
    assert np.asarray(two).tolist() == [
        [True, False, True, False], [False, True, True, False],
        [True, False, True, False], [False, False, True, False]]
    static = unmask(conf, masked, jnp.asarray([0, 0, 0, 0]),
                    counts=[1, 1, 1, 1], threshold=float("inf"))
    assert np.asarray(static).sum(axis=1).tolist() == [1, 1, 1, 1]


# ----------------------------------------------------------- (e) engine
def replay(params, sizes, prompt, new, eos=None):
    """Generation's steps 1-4 in plain Python over the reference's
    forward (no cache, no slot state): the answer, and for every
    generated position up to where the request ends its token and the
    pass that unmasked it."""
    counts = pass_counts(B, sizes["passes"])
    prompt = [int(t) for t in prompt]
    rem = len(prompt) % B
    final = prompt[:len(prompt) - rem]
    tokens = prompt[len(prompt) - rem:] + [0] * (B - rem)
    masked = [False] * rem + [True] * (B - rem)
    when = [GIVEN] * rem + [NEVER_UNMASKED] * (B - rem)
    answer, record, shown, s = [], [], rem, 0

    def close():
        record.extend((tokens[i] if not masked[i] else 0, when[i])
                      for i in range(B) if when[i] != GIVEN)

    while True:
        logits = _reference_pass(params, sizes, final, tokens, masked)
        prob = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
        conf = np.where(masked, prob.max(-1), -np.inf)
        sure = [i for i in range(B) if masked[i]
                and conf[i] > sizes["threshold"]]
        n_s, m = counts[min(s, len(counts) - 1)], sum(masked)
        order = sorted(range(B), key=lambda i: (-conf[i], i))
        chosen = sure if len(sure) >= n_s else order[:min(n_s, m)]
        for i in chosen:
            tokens[i], masked[i], when[i] = int(logits[i].argmax()), False, s
        s += 1
        while shown < B and not masked[shown]:
            answer.append(tokens[shown])
            shown += 1
            if len(answer) == new or answer[-1] == eos:
                close()
                return answer, record
        if not any(masked):
            close()
            final += tokens
            tokens, masked = [0] * B, [True] * B
            when, shown, s = [NEVER_UNMASKED] * B, 0, 0


def serve(model, params, jobs, **kw):
    opts = dict(max_batch=3, page_size=4)
    opts.update(kw)
    eng = LMEngine(model, params=params, **opts)
    reqs = [eng.submit(p, n) for p, n in jobs]
    eng.run_until_idle(timeout_s=300)
    return eng, reqs


def consistent(params, sizes, req, prompt):
    """The oracle the benchmark uses: every position scored in the state
    of the pass that unmasked it; and the answer is the record's
    prefix."""
    toks = [t for t, _ in req.unmasked]
    assert toks[:len(req.tokens)] == [int(t) for t in req.tokens]
    out = ref.block_gaps(params, sizes, prompt, toks,
                         [s for _, s in req.unmasked])
    assert out["token_gap"].size and out["token_gap"].max() < GAP_LIMIT
    assert out["choice_gap"].max() < GAP_LIMIT
    return out


JOBS = [(5, 7), (8, 9), (3, 1), (10, 12), (17, 20), (4, 6)]


@pytest.mark.parametrize("build", [make, confident],
                         ids=["seeded", "constructed"])
def test_engine_serves_what_the_plain_procedure_generates(build):
    """Tokens AND passes equal the replay's, request by request: a
    prompt that ends inside a block, answers that are no whole number
    of blocks, slots in different phases in every step, more requests
    than slots."""
    model, params, sizes = build(7)
    jobs = [(tokens_of(p, 30 + i), n) for i, (p, n) in enumerate(JOBS)]
    eng, reqs = serve(model, params, jobs)
    try:
        lasts = []
        for (prompt, new), req in zip(jobs, reqs):
            assert req.error is None and len(req.tokens) == new
            answer, record = replay(params, sizes, prompt, new)
            assert [int(t) for t in req.tokens] == answer
            assert list(req.unmasked) == record
            assert (len(prompt) + len(record)) % B == 0
            consistent(params, sizes, req, prompt)
            # passes of the blocks that were generated whole
            whens = np.asarray([s for _, s in record])
            for blk in whens[-len(prompt) % B:].reshape(-1, B):
                if blk.min() >= 0:
                    lasts.append(int(blk.max()) + 1)
        st = eng.stats()
        assert st["tokens"] == sum(n for _, n in JOBS)
        # no forward of a slot only commits: a block's final rows ride
        # the first pass of the next
        assert st["block_commits"] == 0
        assert st["block_passes"] > st["block_tails"] > 0
        assert st["tokens_per_forward"] == pytest.approx(
            st["tokens"] / st["block_passes"])
        assert 0 < st["tail_share"] < 0.5
        if build is confident:
            # blocks done in 1, 2 and 3 passes, all in this run (the
            # seeded weights' take 4)
            assert {1, 2, 3} <= set(lasts), lasts
            assert st["positions_unmasked"] > st["block_passes"]
        else:
            assert 4 in lasts
    finally:
        eng.close()


def test_an_eos_inside_a_block_ends_the_request_there():
    model, params, sizes = confident(7)
    prompt, new = tokens_of(10, 33), 12
    answer, _ = replay(params, sizes, prompt, new)
    # a token whose first occurrence is not a block's last position
    at = next(i for i, t in enumerate(answer)
              if answer.index(t) == i and (len(prompt) + i) % B != B - 1)
    eos = answer[at]
    want, record = replay(params, sizes, prompt, new, eos=eos)
    assert want == answer[:at + 1]
    eng = LMEngine(model, params=params, max_batch=2, page_size=4,
                   eos_id=eos)
    try:
        req = eng.submit(prompt, new)
        eng.run_until_idle(timeout_s=120)
        assert [int(t) for t in req.tokens] == want
        assert list(req.unmasked) == record
        assert eng.stats()["kv_pages_in_use"] == 0
    finally:
        eng.close()


def test_one_step_in_flight_gives_the_tokens_of_a_settled_loop():
    model, params, sizes = confident(8)
    jobs = [(tokens_of(p, 50 + i), n) for i, (p, n) in enumerate(JOBS[:4])]
    ahead, reqs = serve(model, params, jobs)
    settled = LMEngine(model, params=params, max_batch=3, page_size=4)
    try:
        same = [settled.submit(p, n) for p, n in jobs]
        while settled.pump(wait_s=0.01) or settled.active_count():
            with settled._lock:
                settled._settle("idle")     # host and chip agree each step
        assert ahead.stats()["steps_ahead"] > 10
        assert settled.stats()["steps_ahead"] == 0
        for a, b in zip(reqs, same):
            assert list(a.tokens) == list(b.tokens)
            assert list(a.unmasked) == list(b.unmasked)
    finally:
        ahead.close()
        settled.close()


def test_emission_is_always_a_prefix():
    """Whatever order a block's positions are unmasked in, a request's
    tokens at any moment are the first ones of its final answer."""
    model, params, sizes = confident(9)
    prompt, new = tokens_of(6, 3), 14
    answer, _ = replay(params, sizes, prompt, new)
    eng = LMEngine(model, params=params, max_batch=2, page_size=4)
    try:
        req = eng.submit(prompt, new)
        seen = []
        while not req.done:
            eng.pump(wait_s=0.01)
            now = [int(t) for t in req.tokens]
            assert now == answer[:len(now)]
            seen.append(len(now))
        assert seen == sorted(seen) and seen[-1] == new
        steps = np.diff([0] + seen)
        assert steps.max() <= B
        assert len(req.token_times) == new
    finally:
        eng.close()


def _pump_until_half_refined(eng, slot=0):
    """Pump, settling every step, until ``slot``'s block has shown a
    token and still has a masked position."""
    for _ in range(40):
        eng.pump(wait_s=0.01)
        with eng._lock:
            eng._settle("idle")
        act = eng._slots[slot]
        if act is not None and act.block.masked.any() and act.req.tokens \
                and act.block.shown > 0 \
                and act.block.passes[:act.block.shown].max() >= 0:
            return act
    raise AssertionError("no block was half refined")


def test_preemption_with_a_block_half_refined():
    """The youngest request is preempted with a block half refined: what
    the block had shown is folded into the prompt and GIVEN on the
    record, the rest of it is generated again, and every position is
    still right in the state of the pass that chose it."""
    model, params, sizes = make(7)
    prompt, new = tokens_of(7, 70), 17
    eng = LMEngine(model, params=params, max_batch=2, page_size=4)
    try:
        req = eng.submit(prompt, new)
        act = _pump_until_half_refined(eng)
        shown, had = act.block.shown, len(req.tokens)
        with eng._lock:
            assert eng._preempt_youngest() == 0
        assert len(req.payload) == len(prompt) + had
        assert req.max_new_tokens == new - had
        given = [s for _, s in req.unmasked].count(GIVEN)
        assert 0 < given <= shown and len(req.unmasked) == had
        assert eng.stats()["kv_pages_in_use"] == 0
        eng.run_until_idle(timeout_s=120)
        assert req.error is None and len(req.tokens) == new
        assert [s for _, s in req.unmasked].count(GIVEN) == given
        consistent(params, sizes, req, prompt)
        assert eng.stats()["preemptions"] == 1
    finally:
        eng.close()


def test_a_pool_too_small_preempts_and_every_request_still_completes():
    model, params, sizes = make(7)
    jobs = [(tokens_of(p, 70 + i), n)
            for i, (p, n) in enumerate([(7, 17), (6, 18), (5, 19)])]
    eng, reqs = serve(model, params, jobs, num_pages=15)
    try:
        st = eng.stats()
        assert st["preemptions"] > 0 and st["settles"]["preempt"] > 0
        for (prompt, new), req in zip(jobs, reqs):
            assert req.error is None and len(req.tokens) == new
            consistent(params, sizes, req, prompt)
        assert st["kv_pages_in_use"] == 0
    finally:
        eng.close()


def test_host_and_chip_agree_whenever_a_step_is_settled():
    """Between two passes of a block: a settle leaves the host's view of
    the block equal to the device's, the pages cover the block, and a
    weight swap (which settles) keeps the block's state, so the same
    weights swapped in change no token."""
    model, params, sizes = confident(7)
    prompt, new = tokens_of(9, 4), 13
    answer, record = replay(params, sizes, prompt, new)
    eng = LMEngine(model, params=params, max_batch=2, page_size=4)
    try:
        req = eng.submit(prompt, new)
        eng.pump(wait_s=0.01)
        assert eng._inflight is not None
        with eng._lock:
            assert eng._settle("preempt") and eng._inflight is None
        act = _pump_until_half_refined(eng)
        tok, msk, pas, length = (np.asarray(a) for a in eng._carry[:4])
        assert np.array_equal(msk[0], act.block.masked)
        assert np.array_equal(tok[0][~msk[0]], act.block.tokens[~msk[0]])
        assert int(length[0]) == int(eng.cache.lengths[0])
        assert 0 < msk[0].sum() < B and int(pas[0]) > 0   # half refined
        assert len(eng.cache.slot_pages(0)) * 4 >= int(length[0]) + B
        eng.swap_weights(params, version="again")
        eng.pump(wait_s=0.01)
        eng.swap_weights(params, version="and again")    # settles a step
        assert eng.stats()["settles"]["swap"] >= 1
        eng.run_until_idle(timeout_s=120)
        assert [int(t) for t in req.tokens] == answer
        assert list(req.unmasked) == record
        assert eng.stats()["settles"]["idle"] >= 1
    finally:
        eng.close()


def test_a_weight_swap_between_passes_serves_the_new_weights_after_it():
    model, params, sizes = confident(7)
    _, other, _ = confident(21)
    old_job, new_job = (tokens_of(9, 4), 16), (tokens_of(7, 5), 10)
    eng = LMEngine(model, params=params, max_batch=2, page_size=4)
    try:
        first = eng.submit(*old_job)
        for _ in range(4):
            eng.pump(wait_s=0.01)
        eng.model.set_params(other)
        eng.swap_weights(other, version="v1")
        second = eng.submit(*new_job)
        eng.run_until_idle(timeout_s=120)
        # the request in flight completes on a mixed trajectory ...
        assert first.error is None and len(first.tokens) == old_job[1]
        toks = [t for t, _ in first.unmasked]
        assert toks[:16] == [int(t) for t in first.tokens]
        # ... the one admitted after the swap is the new weights' own
        answer, record = replay(other, sizes, *new_job)
        assert [int(t) for t in second.tokens] == answer
        assert list(second.unmasked) == record
    finally:
        eng.model.set_params(params)
        eng.close()


WHOLE = [(5, 7), (8, 8), (3, 1), (10, 10), (16, 4), (4, 12), (7, 13)]


def test_no_forward_yields_nothing_on_seeded_weights():
    """Seeded weights never pass the threshold: a pass unmasks one
    position.  Requests whose answers end with a block get every
    position they generate, so the engine runs exactly ONE forward of a
    slot a token (the parent ran five for four: a commit a block), none
    of them only commits, and the forwards that also wrote a pending
    tail's final rows are the blocks that finished less those that
    ended their request."""
    model, params, sizes = make(7)
    jobs = [(tokens_of(p, 90 + i), n) for i, (p, n) in enumerate(WHOLE)]
    eng, reqs = serve(model, params, jobs)
    try:
        blocks = 0
        for (prompt, new), req in zip(jobs, reqs):
            assert (len(prompt) + new) % B == 0
            assert req.error is None and len(req.tokens) == new
            answer, record = replay(params, sizes, prompt, new)
            assert [int(t) for t in req.tokens] == answer
            assert list(req.unmasked) == record
            blocks += -(-(len(prompt) % B + new) // B)
        st = eng.stats()
        assert st["block_commits"] == 0
        assert st["block_passes"] == st["tokens"] == sum(n for _, n in WHOLE)
        assert st["tokens_per_forward"] == 1.0
        assert st["positions_unmasked"] == st["tokens"]
        assert st["block_tails"] == blocks - len(WHOLE)
        assert st["tail_share"] == st["block_tails"] / st["block_passes"]
        assert st["kv_pages_in_use"] == 0
    finally:
        eng.close()


@pytest.mark.parametrize("settled", [True, False],
                         ids=["settled", "one_in_flight"])
def test_a_request_that_ends_with_a_block_takes_no_step_more(settled):
    """A request whose last token is its block's last position is done
    when that pass is read: no step commits the block (nothing reads
    its final rows).  Settled every cycle the engine dispatches a step
    a token; with one step in flight, the one it had dispatched before
    it read the last pass, and no other."""
    model, params, sizes = make(7)
    prompt, new = tokens_of(8, 95), 12
    eng = LMEngine(model, params=params, max_batch=2, page_size=4)
    try:
        req = eng.submit(prompt, new)
        while eng.pump(wait_s=0.01) or eng.active_count():
            if settled:
                with eng._lock:
                    eng._settle("idle")
        assert [int(t) for t in req.tokens] == replay(
            params, sizes, prompt, new)[0]
        st = eng.stats()
        assert st["block_passes"] == new and st["block_commits"] == 0
        assert st["block_tails"] == new // B - 1
        assert st["steps"] == new + (0 if settled else 1)
        assert st["kv_pages_in_use"] == 0
    finally:
        eng.close()


def test_the_oracle_fails_where_the_commit_is_left_out():
    """Later blocks must attend a block's COMMITTED rows: scored against
    a reference that keeps the last refining pass's rows, the engine's
    record does not hold (and against the whole reference it does)."""
    model, params, sizes = make(7)
    prompt, new = tokens_of(6, 8), 18
    eng, (req,) = serve(model, params, [(prompt, new)])
    try:
        consistent(params, sizes, req, prompt)
        toks = [t for t, _ in req.unmasked]
        when = [s for _, s in req.unmasked]
        wrong = ref.block_gaps(params, sizes, prompt, toks, when,
                               variant="no_commit")
        assert wrong["token_gap"].max() > 100 * GAP_LIMIT
    finally:
        eng.close()


def test_a_temperature_is_refused_with_a_reason():
    model, params, _ = make(3)
    eng = LMEngine(model, params=params, max_batch=2, page_size=4)
    try:
        with pytest.raises(ValueError, match="temperature 0.7 is not "
                                             "served"):
            eng.submit([1, 2, 3], 4, temperature=0.7)
        assert eng.submit([1, 2, 3], 4, temperature=0.0) is not None
        eng.run_until_idle(timeout_s=60)
    finally:
        eng.close()


@pytest.mark.parametrize("kw,what", [
    (dict(int8=True), "int8"), (dict(tp=2), "tp"),
    (dict(page_size=6), "do not divide")])
def test_what_a_block_model_cannot_be_served_with_is_refused(kw, what):
    model, params, _ = make(3)
    with pytest.raises(ValueError, match=what):
        LMEngine(model, params=params, max_batch=2,
                 **dict(dict(page_size=4), **kw))


def test_spans_carry_what_a_step_refined_and_committed(tmp_path,
                                                       monkeypatch):
    """... and which of its forwards also wrote a pending tail's final
    rows (``block_tails``); no forward only commits
    (``block_commits`` stays, at 0, for the readers that add it)."""
    from bigdl_tpu.obs import names
    from bigdl_tpu.serving import spans as S

    monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path / "trace"))
    obs.reset()
    try:
        model, params, _ = confident(7)
        prompts = [tokens_of(3, 1), tokens_of(6, 2)]
        eng, reqs = serve(model, params, [(p, 11) for p in prompts],
                          max_batch=2, num_pages=30)
        tracer = obs.get_tracer()
        tracer.flush()
        with open(tracer.jsonl_path, encoding="utf-8") as fh:
            recs = [json.loads(line) for line in fh]
        steps = sorted((r for r in recs if r["kind"] == "span"
                        and r["name"] == S.SPAN_STEP_DECODE),
                       key=lambda s: s["wall_time"])
        settles = [r for r in recs if r["kind"] == "event"
                   and r["name"] == S.EVENT_SETTLE]
        # a step's numbers ride on the span that READ it
        assert "block_passes" not in steps[0]["attrs"]
        read = [s["attrs"] for s in steps[1:] + settles]
        assert all({"block_passes", "block_tails", "block_commits",
                    "positions_unmasked", "tokens_emitted", "moe_held",
                    "context_tokens"} <= set(a) for a in read)
        st = eng.stats()
        # every step is split into its dispatch, and the wait for its
        # result and the read of it, where the next step or a settle
        # took them
        kids = [r["name"] for r in recs if r["kind"] == "span"
                and r["attrs"].get("program") == "step"]
        assert [kids.count(n) for n in (
            S.SPAN_STEP_DISPATCH, S.SPAN_STEP_WAIT, S.SPAN_STEP_READ)] \
            == [len(steps)] * 3 == [st["steps"]] * 3
        assert sum(a["block_passes"] for a in read) == st["block_passes"]
        assert sum(a["block_tails"] for a in read) == st["block_tails"] > 0
        assert sum(a["block_commits"] for a in read) == \
            st["block_commits"] == 0
        assert sum(a["positions_unmasked"] for a in read) == \
            st["positions_unmasked"]
        assert sum(a["tokens_emitted"] for a in read) == 2 * 11
        for a in read:
            assert a["block_tails"] <= a["block_passes"] <= 2
            # the real rows that went through the two expert layers (a
            # slot whose request has ended since ran in the step too)
            assert a["moe_held"] + a["moe_absent"] >= \
                (a["block_passes"] + a["block_tails"]) * B * 2 * 2
            assert a["tokens_emitted"] <= B * a["block_passes"]
            assert a["positions_unmasked"] <= B * a["block_passes"]
        # the first step read: both slots' blocks end at 4 and 8
        assert read[0]["context_tokens"] == 4 + 8
        # ... and what the attention kernel's stream copied for them:
        # each slot's few rows lie in one group of 8 pages of 4
        assert read[0]["attn_rows_copied"] == 2 * 8 * 4
        # every position of both slots went through two expert layers
        assert read[0]["moe_held"] == 2 * B * 2 * 2
        fam = obs.get_registry().counter(
            names.SERVE_BLOCK_POSITIONS_TOTAL, "", labels=("outcome",))
        assert fam.labels(outcome="unmasked").value == \
            st["positions_unmasked"]
        assert fam.labels(outcome="left_masked").value > 0
        assert all(r.error is None for r in reqs)
    finally:
        obs.reset()


def test_step_programs_carry_the_scopes():
    model, params, _ = make(3)
    eng = LMEngine(model, params=params, max_batch=2, page_size=4)
    try:
        b = 2
        wide = jnp.zeros((b, B), jnp.int32)
        ints = jnp.zeros((b,), jnp.int32)
        flags = jnp.zeros((b,), bool)
        text = eng._step_fn.lower(
            eng.params, eng.cache.kp, eng.cache.vp,
            jnp.zeros((b, 4), jnp.int32), ints, wide, wide.astype(bool),
            ints, ints, wide, flags, flags, flags
        ).as_text(debug_info=True)
        for scope in ("gqa.attn", "unmask", "moe.route", "moe.experts",
                      "kv_write", "dense"):
            assert f"/{scope}/" in text or f"{scope}/" in text, scope
        assert "jit_step" in text or "jit(step)" in text
    finally:
        eng.close()


# ------------------- (h) which programs hold the kernel, and which do not
def _lowered_for_a_chip(fn, args, monkeypatch):
    """The StableHLO ``fn`` lowers to for the TPU platform, the backend
    under another name than ``cpu`` (so a kernel is the Mosaic call, not
    the interpreter's operations)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "some_new_chip")
    return fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()


def _like(a):
    return jax.ShapeDtypeStruct(a.shape, a.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_one_token_model_s_step_holds_no_kernel(dtype, monkeypatch):
    """The guard PR 31's and PR 32's builders ran by hand:
    ``TransformerLM``'s paged decode step (one query row a key head)
    lowers with no custom call at all, and with the gather body's
    products: a layer is the q, k and v projections, the body's scores
    and mix (two, each ONE block-diagonal product over all heads), the
    output projection and the two MLP matrices; then the head."""
    import re

    from bigdl_tpu.models.transformer import build_transformer_lm

    n_layer, b = 2, 3
    model = build_transformer_lm(64, dim=32, n_head=4, n_layer=n_layer,
                                 max_len=64)
    params = jax.tree.map(lambda a: a.astype(dtype), model.params())
    eng = LMEngine(model, params=params, max_batch=b, page_size=4)
    try:
        ints = jax.ShapeDtypeStruct((b,), jnp.int32)
        flags = jax.ShapeDtypeStruct((b,), jnp.bool_)
        text = _lowered_for_a_chip(eng._step_fn, (
            jax.tree.map(_like, eng.params), _like(eng.cache.kp),
            _like(eng.cache.vp), jax.ShapeDtypeStruct((b, 4), jnp.int32),
            ints, ints,
            jax.ShapeDtypeStruct((b,), jnp.float32), flags,
            _like(jax.random.key(0))), monkeypatch)
    finally:
        eng.close()
    assert "custom_call" not in text
    assert len(re.findall(r"stablehlo\.dot_general", text)) \
        == (3 + 2 + 1 + 2) * n_layer + 1
    # K and V of each layer are gathered from the stacked buffers
    pool = "x".join(str(n) for n in eng.cache.kp.shape)
    assert len(re.findall(
        r'"stablehlo\.gather"\([^)]*\)[^\n]*: \(tensor<%sx' % pool,
        text)) == 2 * n_layer


def test_the_block_step_holds_one_kernel_a_layer_and_gathers_no_pool(
        monkeypatch):
    """SDAR's block step (32 query rows a key head at the tests' size:
    the tail's 4 positions and the block's 4 x 4 heads): one call of
    the page-walking kernel a layer for both blocks, both buffers
    handed to it whole, no gather of a pool, and the head's product
    over the block's positions alone, flat."""
    import re

    model, params, _ = make(3)
    b = 2
    eng = LMEngine(model, params=params, max_batch=b, page_size=4)
    try:
        ints = jax.ShapeDtypeStruct((b,), jnp.int32)
        flags = jax.ShapeDtypeStruct((b,), jnp.bool_)
        wide = jax.ShapeDtypeStruct((b, B), jnp.int32)
        wide_flags = jax.ShapeDtypeStruct((b, B), jnp.bool_)
        bufs = [_like(x) for x in eng.cache.buffers()]
        text = _lowered_for_a_chip(eng._step_fn, (
            jax.tree.map(_like, eng.params), *bufs,
            jax.ShapeDtypeStruct((b, 4), jnp.int32), ints, wide,
            wide_flags, ints, ints, wide, flags, flags, flags),
            monkeypatch)
    finally:
        eng.close()
    # a model's attentions share one traced program: one private
    # function of the module holds the kernel, called once a layer
    assert text.count('kernel_name = "grouped_decode_attention"') == 1
    holder = re.findall(
        r"func\.func private @([\w.]+)\(",
        text[:text.index('kernel_name = "grouped_decode_attention"')])[-1]
    assert len(re.findall(r"call @%s\(" % re.escape(holder), text)) \
        == SMALL["num_hidden_layers"]
    pool = "x".join(str(n) for n in bufs[0].shape)
    assert f"tensor<{pool}x" in text
    assert not re.findall(
        r'"stablehlo\.gather"\([^)]*\)[^\n]*: \(tensor<%sx' % pool, text)
    # the head sees B positions a slot, flat (logits that leave the
    # product as (slots, B, vocab) are relaid before the pick reads
    # them); the layers see 2B
    assert re.findall(r"stablehlo\.dot_general[^\n]*-> tensor<%dx%dx"
                      % (b * B, VOCAB), text)
    assert not re.findall(r"stablehlo\.dot_general[^\n]*tensor<\d+x\d+x%dx"
                          % VOCAB, text)
    assert not re.findall(r"tensor<%dx%dx" % (b * 2 * B, VOCAB), text)


# ------------------------------------------------ a prefill is read late
# (PR 45) ``tests/late_read_cases.py``'s cases under this file's kind
# of step: ``Block``, whose prefill
# yields no token (so no case about a first token)
@pytest.fixture(scope="module")
def late():
    with jax.default_matmul_precision("highest"):
        model, params, _ = make(7)
        return late_read_cases.prepare(
            lambda **kw: LMEngine(model, params=params, page_size=4, **kw),
            [[int(t) for t in tokens_of(n, 40 + n)] for n in (5, 7, 3, 6)])


@pytest.mark.parametrize("case", sorted(late_read_cases.CASES))
def test_a_prefill_read_late(late, case):
    late_read_cases.CASES[case](*late)
