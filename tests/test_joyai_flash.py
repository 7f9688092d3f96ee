"""JoyAI-LLM-Flash behind ``LMEngine``: the model (a leading dense
layer, sigmoid-routed experts with a shared expert, a prediction layer),
the two-position decode step and the engine's loop when a step yields
one or two tokens, each against the plain float32 reference
(``benchmarks/reference/joyai_llm_flash.py``, the repo's own copy of
``benchmarks/reference/joyai_llm_flash.py``).

A small size with every ratio of the published one kept: a dense layer
and two expert layers, 4 heads of 8 + 4 / 8, ranks 24 / 16, 16 routed
experts, top-4, one shared expert.  Tolerances, each with its reason:

* ``F32_TOL`` 2e-4 on logits of magnitude 1-3: program and reference
  are both float32 with ``highest`` products on the CPU and differ by
  the order of their sums: observed 2e-6 to 3e-5.  The same comparison
  with the program's matrices in bfloat16 reads 1e-2 and more, so the
  tolerance does tell a lower precision from the stated one.
* ``GAP_LIMIT`` 1e-3 on the served tokens' and the drafts' logit gap: a
  greedy token is the reference's own first choice unless two logits
  tie within the float32 tolerance.

**The accept path** cannot be reached with seeded weights (a draft
agrees with the main model about once in a vocabulary), so it is tested
with CONSTRUCTED weights (:func:`constructed`): every attention and MLP
writes 0 into the residual, the embedding is one-hot and the head reads
it shifted by one, so the model counts (``x -> x + 1``); the prediction
layer's ``W_eh`` maps the next token ``x`` to ``pi(x)``, so its draft
``pi(x) + 1`` is right exactly where ``pi(x) == x``.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import late_read_cases

from benchmarks.reference import joyai_llm_flash as ref
from bigdl_tpu import obs
from bigdl_tpu.models.joyai_flash import JoyAIFlash, build_joyai_flash
from bigdl_tpu.nn.experts import DroplessExperts
from bigdl_tpu.nn.latent import LatentAttention, gated_mlp
from bigdl_tpu.ops.decode_attention import latent_decode_attention
from bigdl_tpu.serving import LMEngine
from bigdl_tpu.serving.cache import write_prompt_pages, write_token_rows

F32_TOL = 2e-4
GAP_LIMIT = 1e-3

SMALL = dict(vocab_size=96, hidden_size=64, num_hidden_layers=3,
             num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
             qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
             intermediate_size=128, moe_intermediate_size=32,
             n_routed_experts=16, n_shared_experts=1,
             num_experts_per_tok=4, first_k_dense_replace=1,
             routed_scaling_factor=2.5, rms_norm_eps=1e-6, rope_theta=32e6)
MAX_LEN = 64


def make(held=(0, 16), seed=7, dtype=jnp.float32, std=0.1, row_align=1,
         **over):
    """Seeded weights from the reference, the reference's sizes, and the
    program's model built around that tree without weights of its own."""
    small = dict(SMALL, **over)
    cfg = dict(small, held_experts=list(held), max_len=MAX_LEN,
               initializer_range=std)
    sizes = ref.sizes_of(cfg)
    params = ref.init_params(seed, sizes, dtype)
    model = JoyAIFlash(max_len=MAX_LEN, held_experts=held, params=params,
                       row_align=row_align, **small)
    return model, params, sizes


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def tokens_of(n, seed=0, vocab=SMALL["vocab_size"]):
    return np.random.default_rng(seed).integers(
        0, vocab, size=n).astype(np.int32)


# ------------------------------------------------------------ (a) forward
@pytest.mark.parametrize("held,seed,length", [
    ((0, 16), 7, 19), ((4, 12), 8, 33), ((12, 16), 9, 6)])
def test_full_forward_and_draft_equal_the_reference(held, seed, length):
    model, params, sizes = make(held, seed)
    toks = tokens_of(length, seed)
    got, _ = model.apply(params, {}, jnp.asarray(toks)[None])
    want = ref.forward_logits(params, sizes, toks)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    np.testing.assert_allclose(got[0], want, atol=F32_TOL)
    drafted = model.draft_logits(params, jnp.asarray(toks)[None])
    want = ref.draft_logits(params, sizes, toks)
    assert drafted.shape == (1, length - 1, SMALL["vocab_size"])
    np.testing.assert_allclose(drafted[0], want, atol=F32_TOL)


def test_bfloat16_matrices_fail_the_float32_tolerance():
    model, params, sizes = make((4, 12), 11)
    toks = tokens_of(21, 3)
    want = ref.forward_logits(params, sizes, toks)
    low = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        if a.ndim >= 2 else a, params)
    got, _ = model.apply(low, {}, jnp.asarray(toks)[None])
    assert float(jnp.max(jnp.abs(got[0] - want))) > 10 * F32_TOL


def test_a_model_given_params_draws_no_weights_and_builds_from_a_config(
        monkeypatch):
    from bigdl_tpu.nn import latent

    _, params, _ = make()
    monkeypatch.setattr(latent, "_draw", lambda *a, **k: pytest.fail(
        "a model built around a caller's tree drew weights"))
    cfg = dict(SMALL, n_routed_experts=8, router_experts=16,
               held_experts=[4, 12], max_len=MAX_LEN)
    model = build_joyai_flash(cfg, params=params)
    assert model.params() is params
    moe = model._children["l1"]._children["moe"]
    assert (moe.n_routed, moe.lo, moe.hi, moe.score, moe.renormalise,
            moe.shared_hidden, moe.n_zero) == (16, 4, 12, "sigmoid", True,
                                               32, 0)
    attn = model._children["l0"]._children["attn"]
    assert attn.q_scale == attn.kv_scale == 1.0
    assert "mlp" in model._children["l0"]._children
    assert model.cache_spec(params)["layers"] == 4      # 3 + the drafting
    assert model.draft_spec(params) == {"tokens_per_step": 2}
    with pytest.raises(TypeError, match="unknown sizes"):
        JoyAIFlash(zero_expert_num=4)


# ------------------------------------------------- (b) the expert layer
def test_the_shares_add_up_to_the_uncut_layer_shared_expert_once():
    """Four shares of four experts each: the sum of their routed parts
    and ONE shared expert is the uncut layer (the weights are
    renormalised over all chosen, held or absent), in the program and
    in the reference."""
    kw = dict(score="sigmoid", renormalise=True, shared_hidden=32,
              scale=2.5)
    full = DroplessExperts(64, 32, 16, 0, 4, **kw)
    p = full.params()
    x = jnp.asarray(np.random.default_rng(2).normal(size=(23, 64)),
                    jnp.float32)
    (want, counts), _ = full.apply(p, {}, x)
    assert int(counts[0]) == 23 * 4 and int(counts[2]) == 0
    shared = gated_mlp(x, p["s_gate"], p["s_up"], p["s_down"])
    sizes = ref.sizes_of(dict(SMALL, max_len=MAX_LEN))
    np.testing.assert_allclose(
        ref.expert_layer(p, sizes, x), want, atol=F32_TOL)
    total = total_ref = 0.0
    for lo in range(0, 16, 4):
        share = DroplessExperts(64, 32, 16, 0, 4, held=(lo, lo + 4),
                                init=False, **kw)
        ps = dict(p, **{n: p[n][lo:lo + 4]
                        for n in ("w_gate", "w_up", "w_down")})
        (y, c), _ = share.apply(ps, {}, x)
        assert int(c[0]) + int(c[2]) == 23 * 4
        total = total + y - shared
        total_ref = total_ref + ref.expert_layer(
            ps, dict(sizes, held=(lo, lo + 4)), x, shared=False)
    np.testing.assert_allclose(total + shared, want, atol=F32_TOL)
    np.testing.assert_allclose(total_ref + shared, want, atol=F32_TOL)
    assert float(jnp.max(jnp.abs(shared))) > 10 * F32_TOL


def test_longcats_arguments_are_the_defaults():
    layer = DroplessExperts(64, 32, 16, 8, 4, scale=6.0)
    assert (layer.score, layer.renormalise, layer.shared_hidden) == \
        ("softmax", False, 0)
    assert layer.param_names == DroplessExperts.param_names
    assert "s_gate" not in layer.params()
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        DroplessExperts(64, 32, 16, 0, 4, score="tanh")
    attn = LatentAttention(64, 4, 24, 16, 8, 4, 8)
    assert attn.q_scale == pytest.approx((64 / 24) ** 0.5)
    assert attn.kv_scale == 2.0


# ------------------------------------- (c) two positions a slot, one read
@pytest.mark.parametrize("t,page,align", [(6, 4, 1), (10, 4, 8),
                                          (17, 8, 128)])
def test_two_queries_a_slot_equal_the_rebuilt_prefill(t, page, align):
    """Positions t-2 and t-1 computed twice: by the full-prefix
    attention, and by ONE decode call that writes both rows and attends
    with both queries on the head axis over the rows < t-2."""
    attn = LatentAttention(64, 4, 24, 16, 8, 4, 8, theta=32e6,
                           row_align=align, q_scale=1.0, kv_scale=1.0)
    p = attn.params()
    x = jnp.asarray(np.random.default_rng(t).normal(size=(1, t, 64)),
                    jnp.float32)
    y_full, rows = attn.prefill(p, x)
    n_pages = -(-t // page)
    cache = jnp.zeros((3, 1 + n_pages, page, attn.row_width), jnp.float32)
    padded = jnp.zeros((n_pages * page, attn.row_width)).at[:t - 2].set(
        rows[0, :t - 2])
    ids = jnp.arange(1, 1 + n_pages)
    cache = write_prompt_pages(cache, 1, ids, padded)
    y_dec, cache = attn.decode(p, x[:, t - 2:], cache, 1, ids[None],
                               jnp.asarray([t - 2]))
    assert y_dec.shape == (1, 2, 64)
    np.testing.assert_allclose(y_dec[0], y_full[0, t - 2:], atol=F32_TOL)
    for pos in (t - 2, t - 1):
        np.testing.assert_allclose(
            cache[1, 1 + pos // page, pos % page], rows[0, pos], atol=1e-6)
    assert not np.any(np.asarray(cache[0])) and not np.any(
        np.asarray(cache[2]))
    # one query a slot is the same call as ever
    y_one, _ = attn.decode(p, x[:, t - 1], cache, 1, ids[None],
                           jnp.asarray([t - 1]))
    np.testing.assert_allclose(y_one[0], y_full[0, t - 1], atol=F32_TOL)


def test_a_query_never_reads_past_its_own_length():
    """Two queries of a slot on the head axis, a length each: the first
    is blind to the row at the second's position."""
    rng = np.random.default_rng(5)
    pages = jnp.asarray(rng.normal(size=(2, 5, 4, 12)), jnp.float32)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, 6, 12)), jnp.float32)   # 2 x 3 heads
    lens = jnp.asarray([[2, 2, 2, 3, 3, 3], [5, 5, 5, 6, 6, 6]], jnp.int32)
    out = latent_decode_attention(q, pages, tables, lens, scale=0.3,
                                  value_width=8, layer=1)
    for j, n in ((0, [2, 5]), (1, [3, 6])):
        alone = latent_decode_attention(
            q[:, 3 * j:3 * j + 3], pages, tables, jnp.asarray(n, jnp.int32),
            scale=0.3, value_width=8, layer=1)
        np.testing.assert_allclose(out[:, 3 * j:3 * j + 3], alone,
                                   atol=1e-6)
    loud = pages.at[1, 1, 3].set(1e4)        # slot 0, position 3
    again = latent_decode_attention(q, loud, tables, lens, scale=0.3,
                                    value_width=8, layer=1)
    np.testing.assert_array_equal(again[0, :3], out[0, :3])
    assert not np.allclose(again[0, 3:], out[0, 3:])


def test_two_rows_a_slot_land_where_the_table_says():
    pages = jnp.zeros((2, 6, 4, 3), jnp.float32)
    tables = jnp.asarray([[1, 2], [4, 5], [0, 0]], jnp.int32)
    rows = jnp.arange(18, dtype=jnp.float32).reshape(3, 2, 3) + 1
    out = write_token_rows(pages, 1, tables, jnp.asarray([3, 5, 0]), rows)
    # slot 0: position 3 (page 1, row 3) and 4 (page 2, row 0)
    np.testing.assert_array_equal(out[1, 1, 3], rows[0, 0])
    np.testing.assert_array_equal(out[1, 2, 0], rows[0, 1])
    np.testing.assert_array_equal(out[1, 5, 1], rows[1, 0])
    np.testing.assert_array_equal(out[1, 5, 2], rows[1, 1])
    # an inactive slot writes the trash page; nothing else changed
    assert int(jnp.sum(out[1, 1:] != 0)) == 12 and not np.any(
        np.asarray(out[0]))


# ------------------------------------------------ (d) the engine, end to end
def _served(model, params, prompts, new, **kw):
    eng = LMEngine(model, params=params, **kw)
    reqs = [eng.submit(p, n) for p, n in zip(
        prompts, new if isinstance(new, (list, tuple))
        else [new] * len(prompts))]
    eng.run_until_idle(timeout_s=300)
    return eng, reqs


@pytest.mark.parametrize("num_pages,preempts", [(40, False), (9, True)])
def test_engine_serves_tokens_and_drafts_the_reference_would(num_pages,
                                                             preempts):
    """submit / pump through the engine's own scheduler with the draft
    on (it is never off); the greedy tokens AND every verified draft
    scored by the reference's logit gap.  With 8 pages of 4 for three
    requests the pool runs out: the youngest request is preempted with
    a draft in flight and re-admitted with its emitted prefix."""
    model, params, sizes = make((4, 12), seed=21)
    prompts = [list(tokens_of(n, n)) for n in (5, 7, 3)]
    eng, reqs = _served(model, params, prompts, 10, max_batch=3,
                        page_size=4, num_pages=num_pages)
    st = eng.stats()
    assert (st["preemptions"] > 0) == preempts
    assert (st["settles"]["preempt"] > 0) == preempts
    for prompt, req in zip(prompts, reqs):
        assert req.error is None and len(req.tokens) == 10
        g, _ = ref.served_gaps(params, sizes, prompt, list(req.tokens))
        assert float(g.max()) <= GAP_LIMIT, g
        assert len(req.drafts) >= 4
        assert all(1 <= j < 10 for j, _ in req.drafts)
        dg, _ = ref.draft_gaps(params, sizes, prompt, list(req.tokens),
                               req.drafts)
        assert float(dg.max()) <= GAP_LIMIT, dg
    assert st["kv_pages_in_use"] == 0 and eng._inflight is None
    assert st["drafts_verified"] == sum(len(r.drafts) for r in reqs)
    assert 1.0 <= st["tokens_per_step"] <= 2.0


def test_served_tokens_do_not_depend_on_the_draft():
    """The same main model with a prediction layer that drafts rubbish
    (its ``W_eh`` zeroed) serves the same tokens, and both serve the
    greedy continuation of the full forward."""
    model, params, _ = make((0, 16), seed=31)
    prompts = [list(tokens_of(n, 10 + n)) for n in (6, 4)]
    _, reqs = _served(model, params, prompts, 9, max_batch=2,
                      page_size=4, num_pages=30)
    broken = dict(params, mtp=dict(params["mtp"], proj={
        "weight": jnp.zeros_like(params["mtp"]["proj"]["weight"])}))
    other = JoyAIFlash(max_len=MAX_LEN, params=broken, row_align=1, **SMALL)
    _, reqs2 = _served(other, broken, prompts, 9, max_batch=2,
                       page_size=4, num_pages=30)
    for prompt, a, b in zip(prompts, reqs, reqs2):
        assert list(a.tokens) == list(b.tokens)
        assert [d for _, d in a.drafts] != [d for _, d in b.drafts]
        seq = list(prompt)
        for _ in range(9):
            logits, _ = model.apply(params, {}, jnp.asarray(seq)[None])
            seq.append(int(jnp.argmax(logits[0, -1])))
        assert seq[len(prompt):] == list(a.tokens)


# ----------------------------- (e) the accept path, constructed weights
VOCAB = 48


def constructed(right):
    """A model that counts, and a prediction layer whose draft for the
    token after ``x`` is right exactly where ``right(x)`` (module
    docstring)."""
    model, params, _ = make((0, 16), seed=3, vocab_size=VOCAB)
    d = SMALL["hidden_size"]
    eye = jnp.eye(VOCAB, d, dtype=jnp.float32)
    pi = [x if right(x) else (x + 5) % VOCAB for x in range(VOCAB)]
    join = np.zeros((d, 2 * d), np.float32)
    for x in range(VOCAB):
        join[pi[x], x] = 1.0

    def silence(layer):
        out = dict(layer, attn=dict(layer["attn"], wo=jnp.zeros_like(
            layer["attn"]["wo"])))
        if "mlp" in layer:
            out["mlp"] = dict(layer["mlp"], down=jnp.zeros_like(
                layer["mlp"]["down"]))
        else:
            out["moe"] = dict(
                layer["moe"],
                w_down=jnp.zeros_like(layer["moe"]["w_down"]),
                s_down=jnp.zeros_like(layer["moe"]["s_down"]))
        return out

    params = dict(params, embed={"weight": eye},
                  head={"weight": jnp.roll(eye, 1, axis=0)})
    for i in range(SMALL["num_hidden_layers"]):
        params[f"l{i}"] = silence(params[f"l{i}"])
    params["mtp"] = dict(params["mtp"], proj={"weight": jnp.asarray(join)},
                         layer=silence(params["mtp"]["layer"]))
    model.set_params(params)
    return model, params


def expected(prompt, new, right, eos=None):
    """The tokens a request gets, the drafts its steps verify and how
    many of those are accepted, step by step."""
    toks = [(prompt[-1] + 1 + j) % VOCAB for j in range(new)]
    if eos in toks:
        toks = toks[:toks.index(eos) + 1]
    drafts, accepted, steps, i = [], 0, 0, 1
    while i < len(toks) or (i < new and eos not in toks):
        steps += 1
        take = 1
        if new - i >= 2:
            ok = right(toks[i - 1])
            drafts.append((i, (toks[i - 1] + 1 if ok
                               else toks[i - 1] + 6) % VOCAB))
            if ok:
                accepted += 1
                take = 2
        i += take
    return toks, drafts, accepted, steps


@pytest.mark.parametrize("name,right,new", [
    ("accept_even_owed", lambda x: True, 9),
    ("accept_odd_owed", lambda x: True, 10),
    ("accept_one_owed", lambda x: True, 2),
    ("reject", lambda x: False, 9),
    ("mix", lambda x: x % 3 != 0, 14)])
def test_a_step_yields_two_tokens_where_the_draft_is_right(name, right, new):
    model, params = constructed(right)
    prompts = [[3, 9, 4], [7, 1, 40, 2, 45]]
    eng, reqs = _served(model, params, prompts, new, max_batch=2,
                        page_size=4, num_pages=30)
    st = eng.stats()
    verified = accepted = slot_steps = 0
    for prompt, req in zip(prompts, reqs):
        toks, drafts, acc, steps = expected(prompt, new, right)
        assert list(req.tokens) == toks, name
        assert req.drafts == drafts, name
        verified += len(drafts)
        accepted += acc
        slot_steps += steps
    assert (st["drafts_verified"], st["drafts_accepted"]) == \
        (verified, accepted)
    assert st["tokens_per_step"] == pytest.approx(
        len(prompts) * (new - 1) / slot_steps)
    if name.startswith("accept") and new > 2:
        assert st["draft_accept_share"] == 1.0
        assert st["tokens_per_step"] == (2.0 if new % 2 else 1.8)
    if name == "reject":
        assert st["draft_accept_share"] == 0.0
        assert st["tokens_per_step"] == 1.0
    if name == "mix":
        assert 0.0 < st["draft_accept_share"] < 1.0
    assert st["kv_pages_in_use"] == 0 and eng.active_count() == 0
    assert st["tokens"] == len(prompts) * new


@pytest.mark.parametrize("eos_at", [3, 4, 5])
def test_an_eos_on_the_first_or_the_second_token_of_a_step_ends_there(
        eos_at):
    """Steps yield tokens 1-2, 3-4, 5-6 of the answer: token 3 is a
    step's first, token 4 its second (dropped with nothing behind it),
    token 5 a first again."""
    model, params = constructed(lambda x: True)
    prompt = [11, 20]
    eos = (prompt[-1] + 1 + eos_at) % VOCAB
    eng = LMEngine(model, params=params, max_batch=2, page_size=4,
                   num_pages=30, eos_id=eos)
    req = eng.submit(prompt, 12)
    eng.run_until_idle(timeout_s=300)
    assert list(req.tokens) == [(21 + j) % VOCAB for j in range(eos_at + 1)]
    assert req.error is None and eng.stats()["kv_pages_in_use"] == 0


def test_host_and_chip_agree_whenever_a_step_is_settled():
    """Driven pump by pump with every draft accepted: between a
    dispatch and its read ``remaining`` and the length are bounds; a
    settle makes them exact, pages cover the second row's position, and
    a weight swap with a draft in flight loses nothing."""
    model, params = constructed(lambda x: True)
    eng = LMEngine(model, params=params, max_batch=2, page_size=4,
                   num_pages=30)
    prompt, new = [5, 6, 7], 16
    req = eng.submit(prompt, new)
    for cycle in range(4):
        eng.pump()
        act = eng._slots[0]
        assert eng._inflight is not None and act.unread == 1
        # emitted so far: the prefill's token and two a read step
        assert len(req.tokens) == 1 + 2 * cycle
        assert act.left == new - len(req.tokens)
        # one step in flight, counted as one token until it is read
        assert act.remaining == act.left - 1
        assert eng.cache.lengths[0] == len(prompt) + len(req.tokens)
        # pages reach the row a further accepted draft would write
        assert len(eng.cache.slot_pages(0)) * 4 > eng.cache.lengths[0] + 1
    assert eng._settle("preempt") and eng._inflight is None
    act = eng._slots[0]
    assert act.unread == 0 and act.remaining == act.left == \
        new - len(req.tokens)
    assert len(req.tokens) == 9
    assert eng.cache.lengths[0] == len(prompt) + len(req.tokens) - 1
    eng.pump()
    eng.swap_weights(params, version="again")     # settles the step
    assert eng.stats()["settles"]["swap"] == 1 and len(req.tokens) == 11
    eng.run_until_idle(timeout_s=300)
    toks, drafts, _, _ = expected(prompt, new, lambda x: True)
    assert list(req.tokens) == toks and req.drafts == drafts
    assert eng.stats()["settles"]["idle"] >= 1


def test_preemption_refolds_the_emitted_tokens_only():
    """Two long answers in a pool that holds one: the younger is
    preempted with accepted drafts in flight; its prompt grows by what
    was EMITTED and it still gets the counted sequence."""
    model, params = constructed(lambda x: x % 4 != 1)
    prompts = [[2, 8, 30], [9, 17]]
    eng, reqs = _served(model, params, prompts, 24, max_batch=2,
                        page_size=4, num_pages=11)
    assert eng.stats()["preemptions"] > 0
    for prompt, req in zip(prompts, reqs):
        toks = [(prompt[-1] + 1 + j) % VOCAB for j in range(24)]
        assert list(req.tokens) == toks
        for j, d in req.drafts:
            ok = toks[j - 1] % 4 != 1
            assert d == (toks[j - 1] + (1 if ok else 6)) % VOCAB


# ------------------------------------------- (f) spans and counters
def test_spans_carry_what_a_step_verified_and_yielded(tmp_path,
                                                      monkeypatch):
    from bigdl_tpu.obs import names
    from bigdl_tpu.serving import spans as S

    monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path / "trace"))
    obs.reset()
    try:
        right = lambda x: x % 3 != 0    # noqa: E731
        model, params = constructed(right)
        prompts = [[3, 9, 4], [7, 1, 40, 2, 45]]
        eng, reqs = _served(model, params, prompts, 12, max_batch=2,
                            page_size=4, num_pages=30)
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
        assert "draft_verified" not in steps[0]["attrs"]
        read = [s["attrs"] for s in steps[1:] + settles]
        assert all({"draft_verified", "draft_accepted", "tokens_emitted",
                    "moe_held", "context_tokens"} <= set(a) for a in read)
        st = eng.stats()
        # every step is split into its dispatch, and the wait for its
        # result and the read of it, where the next step or a settle
        # took them
        kids = [r["name"] for r in recs if r["kind"] == "span"
                and r["attrs"].get("program") == "step"]
        assert [kids.count(n) for n in (
            S.SPAN_STEP_DISPATCH, S.SPAN_STEP_WAIT, S.SPAN_STEP_READ)] \
            == [len(steps)] * 3 == [st["steps"]] * 3
        assert sum(a["draft_verified"] for a in read) == \
            st["drafts_verified"] == sum(len(r.drafts) for r in reqs)
        assert sum(a["draft_accepted"] for a in read) == \
            st["drafts_accepted"] > 0
        assert sum(a["tokens_emitted"] for a in read) == 2 * 11
        for a in read:
            assert a["draft_accepted"] <= a["draft_verified"] <= 2
            assert a["tokens_emitted"] <= 2 + a["draft_accepted"]
        # the first step read: both slots, their prompts' rows, the
        # token's own and the draft's
        assert read[0]["context_tokens"] == (3 + 2) + (5 + 2)
        # ... and what the attention kernel's stream copied for them:
        # each slot's few rows lie in one group of 8 pages of 4
        assert read[0]["attn_rows_copied"] == 2 * 8 * 4
        # both positions of both slots went through three expert layers
        assert read[0]["moe_held"] + read[0]["moe_absent"] == 4 * 3 * 4
        fam = obs.get_registry().counter(
            names.SERVE_DRAFT_TOKENS_TOTAL, "", labels=("outcome",))
        assert fam.labels(outcome="accepted").value == st["drafts_accepted"]
        assert fam.labels(outcome="rejected").value == \
            st["drafts_verified"] - st["drafts_accepted"]
    finally:
        obs.reset()


def test_step_programs_carry_the_scopes_and_the_prediction_layers():
    model, params, _ = make((4, 12), seed=3)
    eng = LMEngine(model, params=params, max_batch=2, page_size=4,
                   num_pages=20)
    tables, lengths = eng.cache.device_tables(pages=2)
    z = jnp.zeros((2,), jnp.int32)
    no = jnp.zeros((2,), bool)
    step = eng._step_fn.lower(
        eng.params, eng.cache.kp, tables, lengths, z, z, z, z, z,
        no, no).as_text(debug_info=True)
    pre = eng._prefill_fn(8).lower(
        eng.params, eng.cache.kp, jnp.zeros((1, 8), jnp.int32), 5,
        jnp.zeros((2,), jnp.int32), 0.0, jax.random.key(1),
        np.int32(1), z, z).as_text(debug_info=True)
    for text in (step, pre):
        for scope in ("mla.proj", "kv_write", "mla.attn", "ffn",
                      "moe.route", "moe.experts", "dense", "sample"):
            # (the pick is one call of a private function: its scope
            # ends the path)
            assert f"/{scope}/" in text or f'/{scope}"' in text, scope
            assert f"/mtp/{scope}/" in text or f'/mtp/{scope}"' in text, \
                scope
        assert "/moe.zero/" not in text
    assert "jit(step)" in step and "jit(prefill)" in pre


# ----------------------------------------------- (g) what is not offered
def test_a_temperature_is_refused_with_a_reason():
    model, params, _ = make()
    eng = LMEngine(model, params=params, max_batch=2, page_size=4,
                   num_pages=20)
    with pytest.raises(ValueError, match="exact match against the greedy"):
        eng.submit([1, 2, 3], 4, temperature=0.7)
    assert eng.submit([1, 2, 3], 4).temperature == 0.0


@pytest.mark.parametrize("kw,what", [
    (dict(int8=True), "int8=True"), (dict(tp=2), "tp > 1")])
def test_int8_and_tp_are_refused_with_a_reason(kw, what):
    model, params, _ = make()
    with pytest.raises(ValueError, match="JoyAIFlash does not offer "
                       + what):
        LMEngine(model, params=params, **kw)


# ------------------------------------------------ a prefill is read late
# (PR 45) ``tests/late_read_cases.py``'s cases under this file's kind
# of step: ``Drafting``
@pytest.fixture(scope="module")
def late():
    with jax.default_matmul_precision("highest"):
        model, params, _ = make((4, 12), seed=3)
        return late_read_cases.prepare(
            lambda **kw: LMEngine(model, params=params, page_size=4, **kw),
            [[int(t) for t in tokens_of(n, 40 + n)] for n in (5, 7, 3, 6)])


@pytest.mark.parametrize("case", sorted(late_read_cases.ALL_CASES))
def test_a_prefill_read_late(late, case):
    late_read_cases.ALL_CASES[case](*late)
