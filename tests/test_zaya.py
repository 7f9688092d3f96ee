"""ZAYA1 at a tiny size on the CPU, seeded weights, float32:

(a) the whole model against the plain reference
    (``benchmarks/reference/zaya1_8b.py``) on LOGITS, tight enough that
    bfloat16 matrices fail, and failing with any one part of the
    mathematics left out;
(b) the expert layer: the router handed in as the layer's own leaves
    ``DroplessExperts`` bit for bit; the shares of two chips add up to
    the uncut layer, each routing over all the experts;
(c) the decode attention's kernel path at the benchmark cell's head
    shape (8 query heads over 2 key heads of 128, pages of 16) through
    the Pallas interpreter against a plain einsum over gathered rows;
(d) prefill, then decode through the paged cache AND THE SLOTS' STATE
    against the reference's full forward: a prompt that ends inside its
    bucket's padding, a context that crosses pages, an inactive slot;
(e) the engine: greedy tokens scored by the reference; a slot re-used
    by a shorter request after a longer one gives a fresh engine's
    logits; a preempted request resumes to a roomy engine's tokens; a
    prefill whose state is dropped is caught; spans, ``stats()``,
    scopes, refusals; a model without ``state_spec`` carries none.

Tolerances: ``F32_TOL`` bounds float32 accumulation-order noise on
logits of magnitude about 6 (measured 5e-6 between the program's
batched products and the reference's); ``GAP_LIMIT`` bounds a logit gap
between two float32 computations of the same state (a flipped near-tie
reads its margin).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import late_read_cases

from benchmarks.reference import zaya1_8b as ref
from bigdl_tpu import obs
from bigdl_tpu.models.zaya import Zaya, build_zaya
from bigdl_tpu.nn.experts import DroplessExperts
from bigdl_tpu.ops.decode_attention import (_block_pages,
                                            paged_decode_attention)
from bigdl_tpu.serving import LMEngine
from bigdl_tpu.serving.cache import (PagedKVCache, gather_pages,
                                     keep_inactive, pool_shape,
                                     write_slot_state)

F32_TOL = 2e-4
GAP_LIMIT = 1e-3

VOCAB, MAX_LEN = 96, 64
SMALL = dict(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=8,
             moe_intermediate_size=16, num_experts=8, num_experts_per_tok=1,
             router_hidden_size=16, cca_time0=2, cca_time1=2,
             rms_norm_eps=1e-5)
ROPE = {"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5e6,
                   "rope_type": "default"}}


def config(held=(0, 8), **kw):
    return dict(dict(
        SMALL, rope_parameters=ROPE, layer_types=["hybrid"] * 2,
        sliding_window=None, tie_word_embeddings=True, model_type="zaya",
        held_experts=list(held), max_len=MAX_LEN, initializer_range=0.3),
        **kw)


def make(seed=7, dtype=jnp.float32, held=(0, 8), cls=None):
    """Seeded weights from the reference, the reference's sizes, and the
    program's model built around that tree without weights of its own."""
    cfg = config(held)
    sizes = ref.sizes_of(cfg)
    params = ref.init_params(seed, sizes, dtype)
    model = build_zaya(cfg, params=params) if cls is None else cls(
        max_len=MAX_LEN, held_experts=held, params=params,
        partial_rotary_factor=0.5, rope_theta=5e6, **SMALL)
    return model, params, sizes


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


_FORWARD = {}


def forward(model, params, toks):
    """``model.apply`` over one sequence, jitted once a share and
    length (every model here has the same modules; its weights are an
    argument)."""
    key = (model._config["held_experts"], len(toks))
    if key not in _FORWARD:
        _FORWARD[key] = jax.jit(
            lambda p, t: model.apply(p, {}, t[None])[0][0])
    return _FORWARD[key](params, jnp.asarray(toks))


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, VOCAB, size=n).astype(np.int32)


# ------------------------------------------------------------ (a) forward
@pytest.mark.parametrize("seed,length", [(7, 22), (2**31 + 8, 22), (9, 1)])
def test_full_forward_equals_the_reference(seed, length):
    model, params, sizes = make(seed)
    toks = tokens_of(length, seed % 97)
    got = forward(model, params, toks)
    want = ref.forward_logits(params, sizes, toks)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    np.testing.assert_allclose(got, want, atol=F32_TOL)


def test_seeded_weights_keep_every_mechanism_away_from_one_and_zero():
    _, params, _ = make(3)
    a, r = params["l1"]["attn"], params["l1"]["router"]
    assert float(jnp.min(jnp.abs(a["conv0_w"][0]))) >= 0.29
    assert float(jnp.min(jnp.abs(a["tau"] - 1.0))) > 1e-3
    # no expert is preferred by the hidden units' common mean
    assert float(jnp.max(jnp.abs(jnp.mean(r["w3"], axis=1)))) < 1e-6
    assert 0.29 < float(jnp.min(r["gamma"])) and \
        float(jnp.max(r["gamma"])) < 0.81
    assert float(jnp.std(a["conv1_w"][0])) > 0.1
    assert float(jnp.max(jnp.abs(r["bias"]))) > 0.0
    for res in ("res_attn", "res_moe"):
        assert float(jnp.std(params["l0"][res]["out_scale"])) > 0.1
        assert float(jnp.std(params["l0"][res]["stream_bias"])) > 0.005


def test_bfloat16_matrices_fail_the_float32_tolerance():
    model, params, sizes = make(11)
    toks = tokens_of(22, 3)
    want = ref.forward_logits(params, sizes, toks)
    low = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        if a.ndim >= 2 else a, params)
    got = forward(model, low, toks)
    assert float(jnp.max(jnp.abs(got - want))) > 10 * F32_TOL


@pytest.mark.parametrize("part", ref.PARTS)
def test_a_part_left_out_fails_the_float32_tolerance(part):
    """The reference without the value shift, the query-key mean, the
    temperature, the second convolution, the router's depth carry, or
    with the previous position's rows gone at one position is another
    function: the program is compared tightly enough to tell."""
    model, params, sizes = make(12)
    toks = tokens_of(22, 4)
    got = forward(model, params, toks)
    wrong = ref.forward_logits(params, sizes, toks, without=part, boundary=9)
    assert float(jnp.max(jnp.abs(got - wrong))) > 10 * F32_TOL
    with pytest.raises(ValueError, match="one of"):
        ref.forward_hidden(params, sizes, toks, without="rotary")


def test_the_int8_control_separates_from_float32():
    _, params, sizes = make(13)
    toks = tokens_of(20, 5)
    l32 = ref.forward_logits(params, sizes, toks)
    l8 = ref.forward_logits(params, sizes, toks, "int8")
    assert float(jnp.max(jnp.abs(l8 - l32))) > 1e-2
    served = [int(t) for t in np.argmax(np.asarray(l32[8:]), axis=-1)]
    gaps, first = ref.served_gaps(params, sizes, toks[:9],
                                  served[:1] + list(toks[9:19]))
    assert gaps.shape == (11,) and gaps[0] == 0.0 and first[0] == served[0]


@pytest.mark.parametrize("name", [
    "longcat_flash_chat", "joyai_llm_flash", "sdar_30b_a3b_chat", "zaya1_8b",
    "falcon_h1_34b"])
def test_a_reference_the_tests_compare_with_imports_nothing_of_the_program(
        name):
    """The float32 references under ``benchmarks/reference/`` that
    ``tests/`` imports (one text each since PR 42) are independent of
    the code under test."""
    import os

    import benchmarks

    with open(os.path.join(os.path.dirname(benchmarks.__file__),
                           "reference", name + ".py")) as fh:
        program = fh.read()
    assert "import bigdl_tpu" not in program and "from bigdl_tpu" \
        not in program


def test_a_model_given_params_draws_no_weights_and_builds_from_a_config(
        monkeypatch):
    from bigdl_tpu import common

    _, params, _ = make(3)

    class Never:
        def normal(self, *a, **k):
            raise AssertionError("a weight was drawn")

        uniform = normal

    monkeypatch.setattr(common.RandomGenerator, "RNG", Never())
    model = build_zaya(config(), params=params)
    assert model.params() is params
    spec = model.cache_spec(params)
    assert (spec["heads"], spec["kv_heads"], spec["head_dim"],
            spec["row_width"], spec["buffers"], spec["layers"],
            spec["expert_slots"]) == (4, 2, 8, 16, 2, 2, 16)
    state = model.state_spec(params)
    assert state["layers"] == 2 and state["shapes"] == ((48,), (48,), (8,))
    assert state["dtype"] == jnp.float32
    attn = model._children["l0"]._children["attn"]
    assert (attn.rotary, attn.theta) == (4, 5e6)
    with pytest.raises(TypeError, match="unknown sizes"):
        Zaya(q_lora_rank=4)
    with pytest.raises(ValueError, match="hybrid"):
        build_zaya(config(layer_types=["hybrid", "hybrid_sliding"]),
                   params=params)
    with pytest.raises(ValueError, match="top-1"):
        Zaya(params=params, **dict(SMALL, num_experts_per_tok=2))
    with pytest.raises(ValueError, match="halves"):
        Zaya(params=params, **dict(SMALL, num_key_value_heads=1))


def test_a_model_with_weights_of_its_own_runs():
    model = Zaya(max_len=MAX_LEN, **SMALL)
    out = forward(model, model.params(), tokens_of(22))
    assert out.shape == (22, VOCAB) and bool(jnp.all(jnp.isfinite(out)))
    assert float(jnp.std(out)) > 0


# ------------------------------------------------- (b) the expert layer
def test_the_router_handed_in_as_the_layers_own_changes_no_bit():
    """Cells 4-6's layers: ``routed=layer.route(...)`` is the layer
    without the argument, bit for bit, whatever its score."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(23, 64)),
                    jnp.float32)
    for kw in (dict(n_zero=8, top_k=4, scale=6.0, held=(4, 12)),
               dict(n_zero=0, top_k=4, score="softmax", renormalise=True),
               dict(n_zero=0, top_k=2, score="sigmoid", shared_hidden=32)):
        kw = dict(kw)
        layer = DroplessExperts(64, 32, 16, kw.pop("n_zero"),
                                kw.pop("top_k"), **kw)
        p = layer.params()
        (want, counts), _ = layer.apply(p, {}, x)
        (got, counts2), _ = layer.apply(p, {}, x, routed=layer.route(p, x))
        assert np.array_equal(np.asarray(got), np.asarray(want))
        assert np.array_equal(np.asarray(counts), np.asarray(counts2))
    assert set(layer.params()) >= {"router", "bias"}


def test_a_layer_without_a_router_of_its_own_has_no_router_weights():
    layer = DroplessExperts(64, 32, 16, 0, 1, own_router=False)
    assert set(layer.params()) == {"w_gate", "w_up", "w_down"}
    x = jnp.ones((3, 64), jnp.float32)
    with pytest.raises(ValueError, match="handed its routing"):
        layer.apply(layer.params(), {}, x)


def test_the_shares_add_up_to_the_uncut_layer_top_one_by_the_mlp_router():
    """Two shares of four experts each: every share routes over all
    eight (the router is the model's, whole, on every chip), the sum of
    the shares is the uncut layer, in the program and in the reference;
    the weight is the chosen softmax value itself, the bias only
    picks."""
    model, params, sizes = make(5)
    layer, p = model._children["l1"], params["l1"]
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(37, 32)), jnp.float32)
    carry = jnp.asarray(rng.normal(size=(37, 16)), jnp.float32)
    idx, w, r = layer._children["router"].route(p["router"], x, carry)
    assert idx.shape == w.shape == (37, 1) and r.shape == (37, 16)
    ridx, rw, rr = ref._router(p["router"], x, carry, sizes, None)
    assert np.array_equal(np.asarray(idx[:, 0]), np.asarray(ridx))
    np.testing.assert_allclose(w[:, 0], rw, atol=1e-6)
    np.testing.assert_allclose(r, rr, atol=1e-5)
    assert len(set(np.asarray(ridx).tolist())) > 2      # not one expert
    assert 0.1 < float(jnp.median(w)) < 0.999           # a real weight
    # the carry moves the choice: without it the router is another
    other, _, _ = layer._children["router"].route(p["router"], x, None)
    assert not np.array_equal(np.asarray(other), np.asarray(idx))
    (want, counts), _ = layer._children["moe"].apply(
        p["moe"], {}, x, routed=(idx, w))
    assert int(counts[0]) == 37 and int(counts[2]) == 0
    np.testing.assert_allclose(
        ref.expert_layer(p, sizes, x, carry), want, atol=F32_TOL)
    total = total_ref = 0.0
    for lo in (0, 4):
        share = DroplessExperts(32, 16, 8, 0, 1, held=(lo, lo + 4),
                                own_router=False, init=False)
        ps = {n: p["moe"][n][lo:lo + 4] for n in p["moe"]}
        (y, c), _ = share.apply(ps, {}, x, routed=(idx, w))
        assert int(c[0]) + int(c[2]) == 37 and int(c[0]) > 0
        total = total + y
        total_ref = total_ref + ref.expert_layer(
            dict(p, moe=ps), dict(sizes, held=(lo, lo + 4)), x, carry)
    np.testing.assert_allclose(total, want, atol=F32_TOL)
    np.testing.assert_allclose(total_ref, want, atol=F32_TOL)


def test_a_share_of_the_model_is_the_reference_with_the_same_share():
    model, params, sizes = make(6, held=(4, 8))
    assert sizes["held"] == (4, 8)
    assert params["l0"]["moe"]["w_gate"].shape == (4, 32, 16)
    toks = tokens_of(22, 2)
    np.testing.assert_allclose(
        forward(model, params, toks),
        ref.forward_logits(params, sizes, toks), atol=F32_TOL)


# ------------------------------------------ (c) the attention body
def test_the_kernel_at_the_cells_head_shape_equals_a_gather():
    """8 query heads over 2 key heads of 128, pages of 16, bfloat16
    rows: 4 query rows a key head, blocks of 64 pages.  Page 0 is full
    of garbage and must not count; lengths 0, inside a page, at a
    page's edge and across several."""
    rng = np.random.default_rng(0)
    b, h, g, d, page, n_pages = 4, 8, 2, 128, 16, 12
    assert _block_pages(page, g * d, 2, g * (h // g)) == 64
    shape = pool_shape(n_pages, page, g, d)
    kp = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    kp = kp.at[0].set(1e4)
    vp = vp.at[0].set(-1e4)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.bfloat16)
    tables = np.zeros((b, 4), np.int32)
    lengths = np.asarray([0, 9, 31, 50], np.int32)
    free = list(range(1, n_pages))
    for s, n in enumerate(lengths):
        for j in range(n // page + 1):
            tables[s, j] = free.pop()
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    got = paged_decode_attention(q, kp, vp, tables, lengths, page_size=page)
    assert got.shape == q.shape and got.dtype == q.dtype
    kall = gather_pages(kp, tables).astype(jnp.float32) \
        .reshape(b, -1, g, d)
    vall = gather_pages(vp, tables).astype(jnp.float32) \
        .reshape(b, -1, g, d)
    qs = (q.astype(jnp.float32) * d ** -0.5).astype(jnp.bfloat16) \
        .astype(jnp.float32).reshape(b, g, h // g, d)
    scores = jnp.einsum("bgrd,bkgd->bgrk", qs, kall)
    live = jnp.arange(kall.shape[1])[None, None, None, :] \
        <= lengths[:, None, None, None]
    probs = jax.nn.softmax(jnp.where(live, scores, -jnp.inf), axis=-1)
    want = jnp.einsum("bgrk,bkgd->bgrd", probs, vall).reshape(b, h, d)
    # bfloat16 probabilities in the kernel's mix and a bfloat16 result
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=3e-2)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    # stacked buffers, read at a layer, are the same bits
    stacked = paged_decode_attention(
        q, jnp.stack([vp, kp]), jnp.stack([kp, vp]), tables, lengths,
        page_size=page, layer=1)
    assert np.array_equal(np.asarray(stacked, np.float32),
                          np.asarray(got, np.float32))


# --------------------------- (d) prefill, then decode over cache and state
PAGE = 4


def _cache(model, params, slots=2, pages=40):
    spec = model.cache_spec(params)
    return PagedKVCache(
        spec["layers"], spec["kv_heads"], spec["head_dim"],
        row_width=spec["row_width"], buffers=2, page_size=PAGE,
        num_pages=pages, max_slots=slots, max_len=MAX_LEN,
        dtype=jnp.float32, state_spec=model.state_spec(params))


@pytest.fixture(scope="module")
def programs():
    """The model's two entry points, jitted once for the tests of (d)
    (the kernel is interpreted: a step outside a jit takes seconds)."""
    with jax.default_matmul_precision("highest"):
        model, params, sizes = make(21)
        yield (model, params, sizes,
               jax.jit(model.paged_prefill),
               jax.jit(lambda p, pools, tables, lengths, fed, active, state:
                       model.paged_decode(p, pools, tables, lengths, fed,
                                          active, state=state)))


def _prefilled(cache, prefill, params, slot, toks, t0, fill=0):
    bucket = PAGE
    while bucket < t0:
        bucket *= 2
    pages = cache.alloc(slot, t0)
    page_arg = np.zeros((bucket // PAGE,), np.int32)
    page_arg[:len(pages)] = pages
    prompt = np.full((1, bucket), fill, np.int32)
    prompt[0, :t0] = toks[:t0]
    out = prefill(params, cache.pools(), jnp.asarray(prompt), t0,
                  jnp.asarray(page_arg))
    cache.lengths[slot] = t0
    return out


@pytest.mark.parametrize("prompt_len,new", [(11, 9), (1, 6)])
def test_prefill_then_paged_decode_equals_the_full_forward(programs,
                                                           prompt_len, new):
    """Teacher-forced: slot 1 decodes, slot 0 never runs.  The prompt
    ends inside its bucket's padded tail (11 of 16), whose tokens are
    not the prompt's, and the context crosses pages; the prefill's state
    is that of the last REAL token; the idle slot's state is left as it
    was."""
    model, params, sizes, prefill, decode = programs
    toks = tokens_of(prompt_len + new, 6)
    want = np.asarray(ref.forward_logits(params, sizes, toks))
    cache = _cache(model, params)
    assert [s.shape for s in cache.state] == [(2, 2, 48), (2, 2, 48),
                                              (2, 2, 8)]
    assert cache.state_bytes_per_slot() == 2 * (48 + 48 + 8) * 4
    slot = 1
    pools, logits, counts, rows = _prefilled(
        cache, prefill, params, slot, toks, prompt_len, fill=17)
    np.testing.assert_allclose(logits[0], want[prompt_len - 1],
                               atol=F32_TOL)
    assert int(counts[0]) == 2 * prompt_len
    # slot 0 holds a mark that must survive every step
    marked = tuple(s.at[:, 0].set(7.0) for s in cache.state)
    cache.set_buffers((*pools, *write_slot_state(marked, slot, rows)))
    active = jnp.asarray([False, True])
    for j in range(new):
        pos = prompt_len + j
        if cache.needs_growth(slot):
            assert cache.grow(slot)
        tables, lengths = cache.device_tables()
        fed = jnp.asarray([0, int(toks[pos])], jnp.int32)
        pools, logits, counts, state = decode(
            params, cache.pools(), tables, lengths, fed, active, cache.state)
        cache.set_buffers(
            (*pools, *keep_inactive(state, cache.state, active)))
        cache.lengths[slot] += 1
        np.testing.assert_allclose(logits[1], want[pos], atol=F32_TOL,
                                   err_msg=f"position {pos}")
        assert int(counts[0]) == 2      # one token, two layers
    assert all(bool(jnp.all(s[:, 0] == 7.0)) for s in cache.state)
    assert all(float(jnp.max(jnp.abs(s[:, 1]))) > 0 for s in cache.state)


def test_a_state_left_at_zero_is_caught_by_the_float32_tolerance(programs):
    """What (d) pins is not vacuous: the first decoded position from a
    zero state is the reference with the boundary cut, not the
    reference."""
    model, params, sizes, prefill, decode = programs
    toks = tokens_of(12, 6)
    cache = _cache(model, params)
    pools, _, _, _ = _prefilled(cache, prefill, params, 1, toks, 11)
    cache.set_buffers((*pools, *cache.state))       # the rows dropped
    tables, lengths = cache.device_tables()
    _, logits, _, _ = decode(
        params, cache.pools(), tables, lengths,
        jnp.asarray([0, int(toks[11])], jnp.int32),
        jnp.asarray([False, True]), cache.state)
    want = ref.forward_logits(params, sizes, toks)[11]
    cut = ref.forward_logits(params, sizes, toks,
                             without="state_boundary", boundary=11)[11]
    assert float(jnp.max(jnp.abs(logits[1] - want))) > 10 * F32_TOL
    np.testing.assert_allclose(logits[1], cut, atol=F32_TOL)


# ------------------------------------------------ (e) the engine, end to end
def _serve(eng, prompts, new):
    reqs = [eng.submit(p, new) for p in prompts]
    eng.run_until_idle(timeout_s=300)
    return reqs


def _gaps(params, sizes, prompts, reqs):
    out = []
    for prompt, req in zip(prompts, reqs):
        assert req.error is None
        out.append(ref.served_gaps(params, sizes, prompt,
                                   list(req.tokens))[0])
    return np.concatenate(out)


PROMPTS = [list(tokens_of(n, n)) for n in (5, 7, 3)]
STATE_BYTES = 2 * (48 + 48 + 8) * 4


@pytest.fixture(scope="module")
def roomy(programs):
    """One engine with room (4 slots, 39 pages of 4) for the tests that
    need no other: its step and prefill programs compile once."""
    model, params, sizes = programs[:3]
    with jax.default_matmul_precision("highest"):
        yield LMEngine(model, params=params, max_batch=4, page_size=PAGE,
                       num_pages=40), params, sizes


def test_engine_serves_tokens_the_reference_would(roomy):
    """submit / pump through the engine's own scheduler, allocator,
    buckets and sampling; the greedy tokens scored by the reference's
    logit gap.  The fourth slot never runs: the state it holds is left
    alone by every step."""
    eng, params, sizes = roomy
    eng.cache.set_buffers(
        (*eng.cache.pools(), *(s.at[:, 3].set(5.0) for s in eng.cache.state)))
    reqs = _serve(eng, PROMPTS, 8)
    assert all(len(r.tokens) == 8 for r in reqs)
    st = eng.stats()
    assert st["preemptions"] == 0 and st["kv_pages_in_use"] == 0
    gaps = _gaps(params, sizes, PROMPTS, reqs)
    assert gaps.shape == (24,)
    assert float(gaps.max()) <= GAP_LIMIT, gaps
    assert len(eng.cache.buffers()) == 5 and len(eng.cache.pools()) == 2
    assert st["state_bytes_per_slot"] == STATE_BYTES
    for s in eng.cache.state:
        assert bool(jnp.all(s[:, 3] == 5.0))
        assert float(jnp.max(jnp.abs(s[:, :3]))) > 0


def test_a_preempted_request_resumes_to_the_same_tokens(roomy):
    """With 8 pages of 4 for three requests of up to 7 + 8 tokens the
    pool runs out: the youngest request is preempted, and its second
    prefill rebuilds its state from its tokens (no snapshot was taken).
    It resumes to the tokens of the engine with room."""
    eng, params, sizes = roomy
    want = [list(r.tokens) for r in _serve(eng, PROMPTS, 8)]
    before = eng.stats()["preemptions"]
    # the same engine (its programs are compiled) with most of its free
    # pages taken away
    spare = eng.cache.withhold(eng.cache.free_pages() - 8)
    try:
        reqs = _serve(eng, PROMPTS, 8)
    finally:
        eng.cache.hand_back(spare)
    assert eng.stats()["preemptions"] > before
    assert [list(r.tokens) for r in reqs] == want
    assert float(_gaps(params, sizes, PROMPTS, reqs).max()) <= GAP_LIMIT


def test_a_prefill_whose_state_is_dropped_is_caught_by_the_gap_limit(roomy):
    """The limit of (e) is not vacuous: tokens served by an engine whose
    prefill hands no state to the slot score far over it."""
    eng, params, sizes = roomy
    keep = eng._prefill_fn

    def dropping(bucket):
        fn = keep(bucket)

        def call(params_, kp, vp, *rest):
            out = fn(params_, kp, vp, *rest)
            return (*out[:2], *(jnp.zeros_like(s) for s in rest[:3]),
                    *out[5:])

        return call

    eng._prefill_fn = dropping
    try:
        prompt = list(tokens_of(9, 1))
        reqs = _serve(eng, [prompt], 8)
    finally:
        del eng._prefill_fn
    assert float(_gaps(params, sizes, [prompt], reqs).max()) > 20 * GAP_LIMIT


class Spy(Zaya):
    """The model with its decode step's logits copied out, a call."""

    seen: list = []

    def paged_decode(self, params, caches, tables, lengths, tokens, active,
                     **kw):
        out = super().paged_decode(params, caches, tables, lengths, tokens,
                                   active, **kw)
        jax.debug.callback(
            lambda lg, act, ln: Spy.seen.append(
                (np.asarray(lg), np.asarray(act), np.asarray(ln))),
            out[1], active, lengths)
        return out


def _logits_of(eng, prompt, new):
    """The decode logits the engine computed for one request, alone in
    the engine: a row a step in which its slot ran."""
    Spy.seen.clear()
    req = eng.submit(prompt, new)
    eng.run_until_idle(timeout_s=300)
    jax.effects_barrier()
    rows = [(lg[act][0], int(ln[act][0])) for lg, act, ln in Spy.seen
            if act.any()]
    return req, np.stack([r[0] for r in rows]), [r[1] for r in rows]


def test_a_slot_reused_by_a_shorter_request_gives_a_fresh_engines_logits():
    """One slot.  A short request on the fresh engine, then a long one,
    then the short one again in the same slot: nothing of the long
    occupant's state (nor of its pages) reaches it.  Its logits are, bit
    for bit, those the engine gave while it was fresh, and the
    reference's."""
    model, params, sizes = make(22, cls=Spy)
    long_p, short_p = list(tokens_of(8, 1)), list(tokens_of(6, 2))
    eng = LMEngine(model, params=params, max_batch=1, page_size=PAGE,
                   num_pages=20)
    assert all(float(jnp.max(jnp.abs(s))) == 0 for s in eng.cache.state)
    req2, want, at2 = _logits_of(eng, short_p, 5)
    clean = [np.asarray(s) for s in eng.cache.state]
    _logits_of(eng, long_p, 8)
    assert any(not np.array_equal(np.asarray(s), c)
               for s, c in zip(eng.cache.state, clean))
    req, got, at = _logits_of(eng, short_p, 5)
    assert at == at2 == list(range(6, 10))
    assert list(req.tokens) == list(req2.tokens)
    assert np.array_equal(got, want)
    full = np.asarray(ref.forward_logits(
        params, sizes, short_p + list(req.tokens)))
    np.testing.assert_allclose(got, full[6:10], atol=F32_TOL)
    for s, c in zip(eng.cache.state, clean):
        assert np.array_equal(np.asarray(s), c)


def test_an_engine_in_bfloat16_has_bfloat16_rows_and_state():
    from bigdl_tpu.obs import names

    model, params, _ = make(5, dtype=jnp.bfloat16)
    eng = LMEngine(model, params=params, max_batch=2, page_size=PAGE,
                   num_pages=20)
    assert eng.cache.kp.dtype == eng.cache.vp.dtype == jnp.bfloat16
    assert eng.cache.kp.shape == (2, 20, 4, 16)
    assert all(s.dtype == jnp.bfloat16 for s in eng.cache.state)
    assert eng.stats()["state_bytes_per_slot"] == STATE_BYTES // 2
    gauge = obs.get_registry().gauge(names.SERVE_SLOT_STATE_BYTES, "")
    assert gauge._solo().value == STATE_BYTES // 2


def test_spans_and_stats_say_the_state_and_the_routing(roomy, tmp_path,
                                                       monkeypatch):
    from bigdl_tpu.serving import spans as S

    eng, _, _ = roomy
    monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path / "trace"))
    obs.reset()
    try:
        _serve(eng, PROMPTS[:2], 5)
        tracer = obs.get_tracer()
        tracer.flush()
        with open(tracer.jsonl_path, encoding="utf-8") as fh:
            recs = [json.loads(line) for line in fh]
        spans = [r for r in recs if r["kind"] == "span"]
        steps = sorted((s for s in spans
                        if s["name"] == S.SPAN_STEP_DECODE),
                       key=lambda s: s["wall_time"])
        prefills = [s for s in spans if s["name"] == S.SPAN_STEP_PREFILL]
        assert len(prefills) == 2
        # a prefill's counts come back with its first token, read late:
        # they ride on its ``serve.read``, in the prefills' order
        reads = [s for s in spans if s["name"] == S.SPAN_STEP_READ
                 and s["attrs"]["program"] == "prefill"]
        for s, r in zip(prefills, reads):
            assert s["attrs"]["state_bytes"] == STATE_BYTES
            assert r["attrs"]["request"] == s["attrs"]["request"]
            assert r["attrs"]["moe_held"] == 2 * s["attrs"]["prompt_len"]
        # a step's counts ride on the span of the step that read them
        a = steps[1]["attrs"]
        assert a["moe_held"] == 2 * 2 and a["moe_absent"] == 0
        assert a["moe_hit"] <= 2 * 2 and a["moe_max_load"] <= 2
        assert a["context_tokens"] == (5 + 1) + (7 + 1)
        # ... and what the attention kernel's stream copied for them:
        # each slot's few rows lie in one group of 8 pages of 4
        assert a["attn_rows_copied"] == 2 * 8 * 4
    finally:
        obs.reset()


def test_a_kernel_model_s_steps_count_a_copy_a_run(tmp_path, monkeypatch):
    """ZAYA1's tiny engine (grouped attention through the page stream)
    on its default pool: every ``serve.decode_step`` says how many copy
    descriptors the kernel started a pool, one a group of 8 pages of
    each slot that ran, and ``stats()`` the pages a descriptor."""
    from bigdl_tpu.serving import spans as S
    from bigdl_tpu.serving.cache import PAGE_RUN

    model, params, _ = make(3)
    eng = LMEngine(model, params=params, max_batch=2, page_size=4)
    monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path / "trace"))
    obs.reset()
    try:
        reqs = [eng.submit([3, 1, 4, 1, 5, 9, 2, 6, 5, 3][:n], 34)
                for n in (10, 7)]
        eng.run_until_idle(300)
        assert all(r.error is None and len(r.tokens) == 34 for r in reqs)
        tracer = obs.get_tracer()
        tracer.flush()
        with open(tracer.jsonl_path, encoding="utf-8") as fh:
            recs = [json.loads(line) for line in fh]
        steps = [r["attrs"] for r in recs if r["kind"] == "span"
                 and r["name"] == S.SPAN_STEP_DECODE
                 and "attn_copies" in r["attrs"]]
        assert len(steps) >= 30
        # contexts pass 8 pages of 4: a second group, a second copy
        assert max(a["attn_copies"] for a in steps) == 4
        for a in steps:
            pages = a["attn_rows_copied"] // 4
            assert pages % PAGE_RUN == 0
            assert a["attn_copies"] == pages // PAGE_RUN
        assert eng.stats()["attn_pages_a_copy"] == PAGE_RUN
    finally:
        obs.reset()
        eng.close()


def test_step_programs_carry_the_new_scopes():
    model, params, _ = make(3)
    eng = LMEngine(model, params=params, max_batch=2, page_size=4,
                   num_pages=20)
    tables, lengths = eng.cache.device_tables(pages=2)
    z = jnp.zeros((2,), jnp.int32)
    no = jnp.zeros((2,), bool)
    step = eng._step_fn.lower(
        eng.params, *eng.cache.buffers(), tables, lengths, z,
        jnp.zeros((2,), jnp.float32), no,
        jax.random.key(0)).as_text(debug_info=True)
    pre = eng._prefill_fn(8).lower(
        eng.params, *eng.cache.buffers(), jnp.zeros((1, 8), jnp.int32), 5,
        jnp.zeros((2,), jnp.int32), 0.0, jax.random.key(1),
        np.int32(1), z).as_text(debug_info=True)
    for scope in ("cca.mix", "cca.attn", "kv_write", "moe.route",
                  "moe.experts", "dense", "sample"):
        assert f"/{scope}/" in step, scope
        assert f"/{scope}/" in pre, scope


@pytest.mark.parametrize("kw,what", [
    (dict(int8=True), "int8=True"), (dict(tp=2), "tp > 1")])
def test_int8_and_tp_are_refused_with_a_reason(kw, what):
    model, params, _ = make()
    with pytest.raises(ValueError, match="Zaya does not offer " + what):
        LMEngine(model, params=params, max_batch=2, page_size=4, **kw)


def test_state_with_a_draft_or_a_block_is_refused():
    model, params, _ = make()
    model.draft_spec = lambda params: {"tokens_per_step": 2}
    with pytest.raises(ValueError, match="neither drafts nor"):
        LMEngine(model, params=params, max_batch=2, page_size=4)


def test_a_model_without_state_spec_carries_none():
    """Cells 2, 4, 5, 6: the cache's buffers are its pools, the step
    and the prefill take what they always took."""
    from bigdl_tpu.models.transformer import TransformerLM

    lm = TransformerLM(vocab_size=32, dim=16, n_head=2, n_layer=1,
                       max_len=32)
    eng = LMEngine(lm, max_batch=2, page_size=4, num_pages=10)
    assert eng.cache.state == () and \
        eng.cache.buffers() == eng.cache.pools()
    assert eng.stats()["state_bytes_per_slot"] == 0
    req = eng.submit([1, 2, 3], 4)
    eng.run_until_idle(timeout_s=120)
    assert len(req.tokens) == 4


# ------------------------------------------------ a prefill is read late
# (PR 45) ``tests/late_read_cases.py``'s cases under this file's kind
# of step: ``OneToken`` with state a slot carries
@pytest.fixture(scope="module")
def late():
    with jax.default_matmul_precision("highest"):
        model, params, _ = make(21)
        return late_read_cases.prepare(
            lambda **kw: LMEngine(model, params=params, page_size=4, **kw),
            [[int(t) for t in tokens_of(n, 40 + n)] for n in (5, 7, 3, 6)])


@pytest.mark.parametrize("case", sorted(late_read_cases.ALL_CASES))
def test_a_prefill_read_late(late, case):
    late_read_cases.ALL_CASES[case](*late)
