"""Ling-3.0-flash at a tiny size on the CPU with the pattern kept (a
period of three: KDA, KDA, latent attention, behind a dense first layer;
4 heads of 8 x 8; 16 experts in 4 groups, 2 groups kept, top-4; chunks
of 8 in sub-blocks of 4), seeded weights, float32:

(a) the whole model against the plain reference
    (``benchmarks/reference/ling_3_flash_vl.py``, a per-token
    recurrence) on LOGITS, tight enough that bfloat16 matrices fail, and
    failing with any one part of the mathematics left out;
(b) the mixer alone: the chunked scan against one step a token over
    prompts that are no multiple of the chunk or the sub-block, with a
    padded tail, at the decay's lower bound; the padded tail changes no
    bit of the state;
(c) the router's choice by groups against a plain loop; the shares add
    up; ``route`` without groups and ``LatentAttention``'s defaults are
    what they were;
(d) prefill, then decode through the paged latent cache AND THE SLOTS'
    STATE against the reference's full forward; a slot that sits out
    keeps its state bit for bit WITHOUT the engine's guard;
(e) the engine: the logits of its own prefill and steps against the
    reference, with the bfloat16 engine failing; a preempted request
    continues within the tolerance and counts one ``state_rebuilds``;
    spans (the routing counts and the groups' share beside the state's
    bytes), scopes, refusals.

Tolerances: ``F32_TOL`` bounds float32 accumulation-order noise on
logits of magnitude about 4 (measured 5e-6 between the program's
chunked scan and the reference's per-token recurrence); ``GAP_LIMIT``
bounds a logit gap between two float32 computations of the same state
(a flipped near-tie reads its margin).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import ling_3_flash_vl as ref
from bigdl_tpu import obs
from bigdl_tpu.models.ling_flash import LingFlash, build_ling_flash
from bigdl_tpu.nn.delta import DeltaMixer, _unit_lower_inverse
from bigdl_tpu.nn.experts import DroplessExperts, counts_dict, merge_counts
from bigdl_tpu.nn.latent import LatentAttention
from bigdl_tpu.serving import LMEngine
from bigdl_tpu.serving.cache import PagedKVCache, write_slot_state

F32_TOL = 2e-4
GAP_LIMIT = 1e-3

VOCAB, MAX_LEN, PAGE = 96, 64, 4
SMALL = dict(
    vocab_size=VOCAB, hidden_size=32, num_hidden_layers=4,
    kept_layers=[0, 3, 4, 5], layer_group_size=3, intermediate_size=48,
    first_k_dense_replace=1, num_attention_heads=4, head_dim=8,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    rope_theta=6e6, rms_norm_eps=1e-6, short_conv_kernel_size=4,
    kda_lower_bound=-5, group_norm_size=1, num_experts=16,
    moe_intermediate_size=16, num_experts_per_tok=4,
    moe_shared_expert_intermediate_size=16, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, norm_topk_prob=True,
    score_function="sigmoid", kda_chunk=[8, 4])
#: a slot's state: 3 KDA layers x (4 x 8 x 8 + 3 x 96) float32
STATE_BYTES = 3 * (4 * 8 * 8 + 3 * 96) * 4


def config(**kw):
    return dict(SMALL, max_len=MAX_LEN, **kw)


def make(seed=7, dtype=jnp.float32, cls=None, **kw):
    """Seeded weights from the reference, the reference's sizes, and the
    program's model built around that tree without weights of its own."""
    cfg = config(**kw)
    sizes = ref.sizes_of(cfg)
    params = ref.init_params(seed, sizes, dtype)
    if cls is None:
        return build_ling_flash(cfg, params=params), params, sizes
    return cls.from_config(cfg, params=params), params, sizes


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


_FORWARD = {}


def forward(model, params, toks):
    """``model.apply`` over one sequence, jitted once a configuration
    and length (the weights are an argument)."""
    key = (json.dumps(model._config, sort_keys=True, default=str),
           str(params["embed"]["weight"].dtype), len(toks))
    if key not in _FORWARD:
        _FORWARD[key] = jax.jit(
            lambda p, t: model.apply(p, {}, t[None])[0][0])
    return _FORWARD[key](params, jnp.asarray(toks))


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, VOCAB, size=n).astype(np.int32)


# ------------------------------------------------------------ (a) forward
@pytest.mark.parametrize("seed,length", [(7, 22), (2**31 + 8, 37)])
def test_full_forward_equals_the_reference(seed, length):
    """37 positions are four whole chunks of 8 and a padded fifth."""
    model, params, sizes = make(seed)
    assert (model.n_kda, model.n_latent) == (3, 1)
    assert [model._children[f"l{i}"].latent for i in range(4)] == [
        False, False, False, True]
    toks = tokens_of(length, seed % 97)
    want = ref.forward_logits(params, sizes, toks)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    np.testing.assert_allclose(forward(model, params, toks), want,
                               atol=F32_TOL)


def test_seeded_weights_leave_no_path_dead():
    """A channel's decay at ``f = 0`` spans 0.9 to 0.999 and sits
    nowhere near the bound, no tap is near 0, the selection bias is 0,
    the logits spread."""
    _, params, sizes = make(3)
    kda = params["l1"]["kda"]
    rate = np.exp(np.asarray(kda["a_log"]))[:, None]
    gate = 1.0 / (1.0 + np.exp(-rate * np.asarray(kda["dt_bias"])
                               .reshape(4, 8)))
    decay = np.exp(sizes["lower"] * gate)
    assert 0.899 <= decay.min() and decay.max() <= 0.9991
    assert decay.max() - decay.min() > 0.02
    assert float(jnp.min(jnp.abs(kda["conv_w"]))) >= 0.19
    assert kda["dt_bias"].dtype == kda["a_log"].dtype == jnp.float32
    assert not np.any(np.asarray(params["l1"]["moe"]["bias"]))
    logits = ref.forward_logits(params, sizes, tokens_of(16, 1))
    assert 0.5 < float(jnp.std(logits)) < 2.0


def test_the_routers_rows_are_taken_off_what_every_token_shares():
    """A random network's stream has a component common to all tokens,
    which would make the same experts every token's favourites; the draw
    subtracts each router row's part along the mean of the layer's
    input: the rows' outputs then carry no offset the tokens share, and
    a row keeps all that lies across that mean."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(64, 32)) + 2.0 * rng.normal(size=32),
                    jnp.float32)
    router = jnp.asarray(rng.normal(0, 32 ** -0.5, (16, 32)), jnp.float32)
    gain = jnp.ones((32,), jnp.float32)
    u = ref._rms(a, gain, 1e-6)
    fixed = ref._off_the_mean(router, a, gain, 1e-6)
    before, after = np.asarray(u @ router.T), np.asarray(u @ fixed.T)
    assert np.std(before.mean(axis=0)) > 0.5 * np.std(
        before - before.mean(axis=0))
    assert np.abs(after.mean(axis=0)).max() < 1e-5
    # what moved of a row is its part along that mean, nothing across it
    m = np.asarray(u).mean(axis=0)
    across = rng.normal(size=32)
    across -= across @ m / (m @ m) * m
    np.testing.assert_allclose(np.asarray(fixed) @ across,
                               np.asarray(router) @ across, atol=1e-5)
    # and the weights init_params hands out went through it: 256 tokens
    # of the tiny model hit every one of an expert layer's 16 experts
    _, params, sizes = make(3)
    x = jnp.take(params["embed"]["weight"], jnp.asarray(tokens_of(64, 9)),
                 axis=0)
    keep = (jnp.ones((64, 1, 1, 1)), jnp.ones((64, 4)))
    a = ref._mixed(params["l1"], sizes, ref.layer_forward(
        params["l0"], sizes, x, *keep), *keep)
    idx, _ = ref.route(params["l1"]["moe"], ref._rms(
        a, params["l1"]["norm_mlp"]["weight"], sizes["eps"]), sizes)
    assert len(set(np.asarray(idx).reshape(-1).tolist())) == 16


def test_bfloat16_matrices_fail_the_float32_tolerance():
    model, params, sizes = make(7, dtype=jnp.bfloat16)
    toks = tokens_of(22, 7)
    want = ref.forward_logits(params, sizes, toks)
    err = jnp.max(jnp.abs(forward(model, params, toks) - want))
    assert float(err) > 10 * F32_TOL


@pytest.mark.parametrize("part", ref.PARTS)
def test_a_part_left_out_fails_the_float32_tolerance(part):
    model, params, sizes = make(7)
    toks = tokens_of(22, 7)
    got = forward(model, params, toks)
    cut = ref.forward_logits(params, sizes, toks, without=part, boundary=9)
    assert float(jnp.max(jnp.abs(got - cut))) > 100 * F32_TOL


def test_the_int8_control_separates_from_float32():
    _, params, sizes = make(7)
    toks = tokens_of(22, 7)
    l32 = ref.forward_logits(params, sizes, toks)
    l8 = ref.forward_logits(params, sizes, toks, "int8")
    assert float(jnp.max(jnp.abs(l8 - l32))) > 100 * F32_TOL


def test_a_model_given_params_draws_no_weights_and_builds_from_a_config():
    model, params, _ = make(5)
    assert model.params() is params
    assert all(getattr(model._children["l1"]._children["kda"], n) is None
               for n in DeltaMixer.param_names)
    model.set_params(None)
    assert model.params() is None
    # the published pattern: 6 KDA layers keep state, 1 latent layer pages
    wide = LingFlash(params=params, **dict(
        {k: v for k, v in SMALL.items() if k != "kda_chunk"},
        num_hidden_layers=7, kept_layers=[0, 2, 3, 4, 5, 6, 7],
        layer_group_size=6))
    assert (wide.n_kda, wide.n_latent) == (6, 1)
    assert [wide._children[f"l{i}"].latent for i in range(7)] == [
        False, False, False, False, True, False, False]
    assert wide.cache_spec(params)["layers"] == 1
    assert wide.cache_spec(params)["expert_slots"] == 6 * 16
    assert wide.state_spec(params)["layers"] == 6
    assert wide._own == [0, 1, 2, 3, 0, 4, 5]


@pytest.mark.parametrize("change,match", [
    (dict(kda_safe_gate=False), "kda_safe_gate"),
    (dict(q_lora_rank=1536), "q_lora_rank"),
    (dict(expert_swiglu_limit_list=[0, 0, 0, 4, 0, 0]), "clamp"),
    (dict(share_expert_swiglu_limit_list=[0] * 5 + [7]), "clamp"),
    (dict(kept_layers=[0, 3, 4]), "kept_layers"),
    (dict(kept_layers=[0, 1, 3, 4]), "one of each")])
def test_what_is_not_computed_is_refused_not_ignored(change, match):
    _, params, _ = make(5)
    with pytest.raises(ValueError, match=match):
        build_ling_flash(config(**change), params=params)
    # a clamp on a layer that is NOT kept is no one's business
    build_ling_flash(config(expert_swiglu_limit_list=[0, 4, 4, 0, 0, 0]),
                     params=params)


# ---------------------------------------------------- (b) the mixer alone
def mixer(seed=0, **kw):
    """A mixer with every gate alive: decays from near 1 to near the
    bound."""
    rng = np.random.default_rng(seed)
    m = DeltaMixer(24, 4, 8, 8, chunk=8, sub=4, **kw)
    p = dict(m.params())
    p["w_in"] = jnp.asarray(rng.normal(0, 0.5, p["w_in"].shape), jnp.float32)
    p["dt_bias"] = jnp.asarray(rng.uniform(-4, 4, p["dt_bias"].shape),
                               jnp.float32)
    p["a_log"] = jnp.asarray(rng.uniform(-1, 1, p["a_log"].shape),
                             jnp.float32)
    return m, p


def stepped(m, p, n, t0, slots=2, slot=0):
    """``t0`` tokens through :meth:`DeltaMixer.step`, one at a time, in
    ``slot`` of a stacked state of one layer; the other slots idle."""
    *parts, kept = m.state_shapes()
    states = tuple(jnp.zeros((1, slots) + s, jnp.float32) for s in parts)
    rows = jnp.zeros((1, slots) + kept, jnp.float32)
    active = jnp.arange(slots) == slot
    step = jax.jit(lambda x, s, r: m.step(p, x, s, r, 0, active))
    outs = []
    for t in range(t0):
        o, states, rows = step(jnp.broadcast_to(n[t], (slots, n.shape[1])),
                               states, rows)
        outs.append(o[slot])
    # the parts side by side again: (1, slots, H, d_k, d_v)
    return jnp.stack(outs), jnp.concatenate(states, axis=2), rows


@pytest.mark.parametrize("length,t0,parts", [
    (27, 21, 1), (8, 8, 1), (13, 3, 2), (5, 5, 1), (16, 9, 4), (40, 33, 2)])
def test_the_chunked_scan_is_one_step_a_token(length, t0, parts):
    """Lengths that are no multiple of the chunk (8) or the sub-block
    (4), prompts shorter than the convolution's kernel, a padded tail of
    other tokens' rows: the outputs up to ``t0``, the state and the kept
    rows are the stepped ones, in however many arrays the state is
    kept."""
    m, p = mixer(length, state_parts=parts)
    assert m.state_shapes() == ((4 // parts, 8, 8),) * parts + ((3, 96),)
    n = jnp.asarray(np.random.default_rng(t0).normal(size=(length, 24)),
                    jnp.float32)
    out, state, rows = jax.jit(m.scan)(p, n, t0)
    assert len(state) == parts
    state = jnp.concatenate(state)
    want, states, kept = stepped(m, p, n, t0)
    np.testing.assert_allclose(out[:t0], want, atol=2e-6)
    np.testing.assert_allclose(state, states[0, 0], atol=2e-6)
    np.testing.assert_allclose(rows, kept[0, 0], atol=2e-6)
    assert float(jnp.max(jnp.abs(state))) > 1e-2
    # the idle slot of the stepped run never moved
    assert not np.any(np.asarray(states[0, 1]))
    assert not np.any(np.asarray(kept[0, 1]))


def test_the_scan_holds_at_the_decays_lower_bound():
    """Every channel at ``exp(-5)`` a step, and every channel at 1: the
    sub-blocks keep each factor inside float32 (a whole chunk's
    ``exp(5 x 8)`` would still fit here; the served ``exp(5 x 64)`` does
    not, which is what the refusal below is for)."""
    for bias in (40.0, -40.0):
        m, p = mixer(1)
        p["dt_bias"] = jnp.full_like(p["dt_bias"], bias)
        p["a_log"] = jnp.zeros_like(p["a_log"])
        n = jnp.asarray(np.random.default_rng(2).normal(size=(24, 24)),
                        jnp.float32)
        out, (state,), _ = jax.jit(m.scan)(p, n, 24)
        want, states, _ = stepped(m, p, n, 24)
        assert np.all(np.isfinite(np.asarray(out)))
        np.testing.assert_allclose(out, want, atol=2e-6)
        np.testing.assert_allclose(state, states[0, 0], atol=2e-6)
    with pytest.raises(ValueError, match="parts"):
        DeltaMixer(24, 4, 8, 8, state_parts=3)
    with pytest.raises(ValueError, match="float32"):
        DeltaMixer(24, 4, 8, 8, chunk=64, sub=32)
    with pytest.raises(ValueError, match="power of two"):
        DeltaMixer(24, 4, 8, 8, chunk=24, sub=4)


@pytest.mark.parametrize("t0", [1, 2, 3, 8, 13])
def test_the_padded_tail_changes_no_bit_of_the_state(t0):
    m, p = mixer(4)
    rng = np.random.default_rng(t0)
    n = rng.normal(size=(16, 24)).astype(np.float32)
    other = n.copy()
    other[t0:] = rng.normal(size=(16 - t0, 24))
    scan = jax.jit(m.scan)
    _, (s1,), r1 = scan(p, jnp.asarray(n), t0)
    _, (s2,), r2 = scan(p, jnp.asarray(other), t0)
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    assert np.array_equal(np.asarray(r1), np.asarray(r2))


def test_the_inverse_of_a_unit_lower_triangle_by_doubling():
    rng = np.random.default_rng(0)
    for c in (4, 8, 64):
        n = np.tril(rng.normal(0, 0.3, (3, c, c)), -1).astype(np.float32)
        got = _unit_lower_inverse(jnp.asarray(n))
        want = np.linalg.inv(np.eye(c) + n.astype(np.float64))
        np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


def test_one_norm_over_all_heads_or_one_a_group():
    """``group_norm_size`` counts groups: 1 is one RMS over all ``H d``
    outputs; 4 would be one a head here."""
    m1, p = mixer(6)
    m4, _ = mixer(6, norm_groups=4)
    n = jnp.asarray(np.random.default_rng(1).normal(size=(8, 24)),
                    jnp.float32)
    a, b = m1.scan(p, n, 8)[0], m4.scan(p, n, 8)[0]
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3


# ------------------------------------------------- (c) router and shares
def plain_route(router, bias, x, n_group, topk_group, top_k, scale):
    """The choice by groups as a loop over tokens, in float64."""
    logits = x.astype(np.float64) @ router.astype(np.float64).T
    s = 1.0 / (1.0 + np.exp(-logits))
    out_idx, out_w = [], []
    for row in s:
        biased = row + bias
        groups = biased.reshape(n_group, -1)
        score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        keep = np.argsort(-score, kind="stable")[:topk_group]
        allowed = np.full(biased.shape, -np.inf)
        per = groups.shape[1]
        for g in keep:
            allowed[g * per:(g + 1) * per] = biased[g * per:(g + 1) * per]
        idx = np.argsort(-allowed, kind="stable")[:top_k]
        w = row[idx]
        out_idx.append(idx)
        out_w.append(scale * w / w.sum())
    return np.asarray(out_idx), np.asarray(out_w)


@pytest.mark.parametrize("bias_scale", [0.0, 0.5])
def test_the_choice_by_groups_is_the_plain_loop(bias_scale):
    rng = np.random.default_rng(3)
    layer = DroplessExperts(32, 16, 16, 0, 4, scale=2.5, score="sigmoid",
                            renormalise=True, groups=(4, 2))
    p = dict(layer.params())
    p["router"] = jnp.asarray(rng.normal(0, 0.3, (16, 32)), jnp.float32)
    p["bias"] = jnp.asarray(bias_scale * rng.normal(size=16), jnp.float32)
    x = rng.normal(size=(40, 32)).astype(np.float32)
    idx, w = layer.route(p, jnp.asarray(x))
    want_idx, want_w = plain_route(np.asarray(p["router"]),
                                   np.asarray(p["bias"]), x, 4, 2, 4, 2.5)
    assert np.array_equal(np.sort(np.asarray(idx), axis=1),
                          np.sort(want_idx, axis=1))
    np.testing.assert_allclose(np.sort(np.asarray(w), axis=1),
                               np.sort(want_w, axis=1), rtol=1e-5)
    # every chosen expert lies in one of two groups a token
    assert all(len(set(row // 4)) <= 2 for row in np.asarray(idx))
    # and the limit binds: the plain top-4 of all 16 differs somewhere
    free = DroplessExperts(32, 16, 16, 0, 4, scale=2.5, score="sigmoid",
                           renormalise=True)
    assert not np.array_equal(np.sort(np.asarray(free.route(p, x)[0]), 1),
                              np.sort(np.asarray(idx), 1))


@pytest.mark.parametrize("kw", [
    dict(score="softmax"), dict(score="sigmoid", renormalise=True),
    dict(score="softmax", n_zero=8, scale=6.0)])
def test_route_without_groups_is_what_it_was(kw):
    """The formula every served model's router ran before the groups
    came, written out: bit for bit."""
    rng = np.random.default_rng(5)
    n_zero = kw.pop("n_zero", 0)
    layer = DroplessExperts(32, 16, 16, n_zero, 4, **kw)
    p = dict(layer.params())
    p["bias"] = jnp.asarray(0.1 * rng.normal(size=16 + n_zero), jnp.float32)
    x = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
    logits = jnp.matmul(x.astype(jnp.float32),
                        p["router"].astype(jnp.float32).T,
                        precision="highest")
    s = jax.nn.softmax(logits, axis=-1) if layer.score == "softmax" \
        else jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + p["bias"], 4)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if layer.renormalise:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    got_idx, got_w = layer.route(p, x)
    assert np.array_equal(np.asarray(got_idx), np.asarray(idx))
    assert np.array_equal(np.asarray(got_w), np.asarray(layer.scale * w))
    (_, counts), _ = layer.apply(p, {}, x)
    assert counts.shape == (5,) and "group_hit_share" not in counts_dict(
        counts)


@pytest.mark.parametrize("groups,match", [
    ((3, 2), "equal groups"), ((4, 5), "equal groups"),
    ((16, 2), "equal groups"), ((4, 0), "equal groups")])
def test_groups_that_cannot_hold_the_choice_are_refused(groups, match):
    with pytest.raises(ValueError, match=match):
        DroplessExperts(32, 16, 16, 0, 4, score="sigmoid", groups=groups)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips, one routing group of 4 experts each: the shares'
    routed parts and ONE shared expert are the uncut reference's layer;
    each share is the reference's share; the counts say who kept whose
    group."""
    cfg = config()
    whole = ref.sizes_of(cfg)
    params = ref.init_params(11, whole, jnp.float32)["l1"]["moe"]
    x = jnp.asarray(np.random.default_rng(2).normal(size=(24, 32)),
                    jnp.float32)
    want = ref.expert_layer(params, whole, x)
    shared = ref.expert_layer(params, dict(whole, held=(0, 1)), x) \
        - ref.expert_layer(params, dict(whole, held=(0, 1)), x, shared=False)
    total, hits, kept = jnp.zeros_like(want), [], 0
    for lo in range(0, 16, 4):
        cut = dict(params, **{k: params[k][lo:lo + 4]
                              for k in ("w_gate", "w_up", "w_down")})
        layer = DroplessExperts(
            32, 16, 16, 0, 4, scale=2.5, held=(lo, lo + 4), score="sigmoid",
            renormalise=True, shared_hidden=16, groups=(4, 2), init=False)
        (y, counts), _ = layer.apply(cut, {}, x)
        np.testing.assert_allclose(
            y, ref.expert_layer(cut, dict(whole, held=(lo, lo + 4)), x),
            atol=2e-5)
        total = total + y - shared
        c = counts_dict(counts)
        assert counts.shape == (7,) and int(counts[6]) == 24
        assert c["held"] + c["absent"] == 24 * 4 and c["zero"] == 0
        # a token sends this chip nothing unless it kept the chip's group
        assert c["held"] <= 4 * int(counts[5])
        hits.append(c["group_hit_share"])
        kept += c["held"]
    np.testing.assert_allclose(total + shared, want, atol=5e-5)
    assert kept == 24 * 4
    # every token keeps 2 of the 4 groups
    assert sum(hits) == pytest.approx(2.0)


def test_counts_of_two_layers_merge_sums_and_the_largest_load():
    a = jnp.asarray([8, 0, 24, 3, 5, 6, 10], jnp.int32)
    b = jnp.asarray([4, 0, 28, 2, 7, 2, 10], jnp.int32)
    assert list(np.asarray(merge_counts(a, b))) == [12, 0, 52, 5, 7, 8, 20]
    assert merge_counts(None, b) is b
    assert counts_dict(merge_counts(a, b))["group_hit_share"] == 0.4
    plain = merge_counts(a[:5], b[:5])
    assert list(np.asarray(plain)) == [12, 0, 52, 5, 7]


def test_latent_attentions_defaults_are_the_layer_it_was():
    """The default layer has the old parameters and no gate; the two
    options change what they say and nothing else."""
    old = LatentAttention(32, 4, 12, 16, 8, 4, 8, row_align=8)
    assert old.param_names == ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
                               "wkv_b", "wo")
    assert not old.head_gate and old._config["head_gate"] is False
    full = LatentAttention(32, 4, None, 16, 8, 4, 8, row_align=8,
                           kv_scale=1.0, head_gate=True)
    assert full.param_names == ("wq", "wkv_a", "kv_norm", "wkv_b", "wo",
                                "w_gate")
    assert full.q_scale == 1.0
    p = dict(full.params())
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 6, 32)),
                    jnp.float32)
    y, rows = full.prefill(p, x)
    assert rows.shape == (1, 6, 24)
    # the gate is one factor a head and position on the mix before W_o:
    # with w_gate at 0 every gate is sigmoid(0), half the ungated layer
    plain = LatentAttention(32, 4, None, 16, 8, 4, 8, row_align=8,
                            kv_scale=1.0, init=False)
    y_plain, _ = plain.prefill({k: v for k, v in p.items()
                                if k != "w_gate"}, x)
    assert float(jnp.max(jnp.abs(y - y_plain))) > 1e-4
    y_half, _ = full.prefill(dict(p, w_gate=jnp.zeros_like(p["w_gate"])), x)
    np.testing.assert_allclose(y_half, 0.5 * y_plain, atol=1e-6)


# ------------------------------------ (d) prefill, then decode by the page
def _cache(model, params, slots=2, pages=40):
    spec = model.cache_spec(params)
    return PagedKVCache(
        spec["layers"], row_width=spec["row_width"], buffers=spec["buffers"],
        page_size=PAGE, num_pages=pages, max_slots=slots, max_len=MAX_LEN,
        dtype=spec["dtype"], state_spec=model.state_spec(params))


@pytest.fixture(scope="module")
def programs():
    """The model's two entry points, jitted once for the tests of (d)
    (the kernels are interpreted: a step outside a jit takes seconds)."""
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


@pytest.mark.parametrize("prompt_len,new", [(1, 4), (2, 3), (3, 3), (8, 4),
                                            (11, 9)])
def test_prefill_then_paged_decode_equals_the_full_forward(programs,
                                                           prompt_len, new):
    """Teacher-forced: slot 1 decodes, slot 0 never runs.  Prompts
    shorter than the convolution, one that fills its bucket (8) and one
    that ends inside its bucket's padded tail (11 of 16), whose tokens
    are not the prompt's; the context crosses pages.  The idle slot's
    state is left as it was by the model's own step: no guard is
    applied here."""
    model, params, sizes, prefill, decode = programs
    toks = tokens_of(prompt_len + new, 6)
    want = np.asarray(ref.forward_logits(params, sizes, toks))
    cache = _cache(model, params)
    # ONE cached layer (the latent one), THREE layers of state
    assert cache.kp.shape[0] == 1 and cache.vp is None
    assert [s.shape for s in cache.state] == [(3, 2, 4, 8, 8),
                                              (3, 2, 3, 96)]
    assert cache.state_bytes_per_slot() == STATE_BYTES
    slot = 1
    pools, logits, counts, rows = _prefilled(
        cache, prefill, params, slot, toks, prompt_len, fill=17)
    # three expert layers' counts, the padded tail not counted
    c = counts_dict(counts)
    assert c["held"] + c["absent"] == 3 * 4 * prompt_len
    assert int(counts[6]) == 3 * prompt_len
    np.testing.assert_allclose(logits[0], want[prompt_len - 1],
                               atol=F32_TOL)
    # slot 0 holds a mark that must survive every step
    rng = np.random.default_rng(1)
    marked = tuple(s.at[:, 0].set(jnp.asarray(
        rng.normal(size=s[:, 0].shape), s.dtype)) for s in cache.state)
    mark = [np.asarray(s[:, 0]) for s in marked]
    cache.set_buffers((*pools, *write_slot_state(marked, slot, rows)))
    active = jnp.asarray([False, True])
    for j in range(new):
        pos = prompt_len + j
        if cache.needs_growth(slot):
            assert cache.grow(slot)
        tables, lengths = cache.device_tables()
        fed = jnp.asarray([5, int(toks[pos])], jnp.int32)
        pools, logits, counts, state = decode(
            params, cache.pools(), tables, lengths, fed, active, cache.state)
        cache.set_buffers((*pools, *state))
        cache.lengths[slot] += 1
        np.testing.assert_allclose(logits[1], want[pos], atol=F32_TOL,
                                   err_msg=f"position {pos}")
        assert int(counts[6]) == 3      # one real token, three layers
    for s, m in zip(cache.state, mark):
        assert np.array_equal(np.asarray(s[:, 0]), m)
        assert float(jnp.max(jnp.abs(s[:, 1]))) > 0


def test_a_state_left_at_zero_is_caught_by_the_float32_tolerance(programs):
    """What (d) pins is not vacuous: decoding from a zero state is the
    reference with the carry cut at the boundary, not the reference."""
    model, params, sizes, prefill, decode = programs
    toks = tokens_of(12, 6)
    cache = _cache(model, params)
    pools, _, _, _ = _prefilled(cache, prefill, params, 1, toks, 11)
    cache.set_buffers((*pools, *cache.state))       # the state dropped
    tables, lengths = cache.device_tables()
    _, logits, _, _ = decode(
        params, cache.pools(), tables, lengths,
        jnp.asarray([0, int(toks[11])], jnp.int32),
        jnp.asarray([False, True]), cache.state)
    want = ref.forward_logits(params, sizes, toks)[11]
    cut = ref.forward_logits(params, sizes, toks, without="state_carry",
                             boundary=11)[11]
    assert float(jnp.max(jnp.abs(logits[1] - want))) > 100 * F32_TOL
    np.testing.assert_allclose(logits[1], cut, atol=F32_TOL)


# ------------------------------------------------ (e) the engine, end to end
class Spy(LingFlash):
    """The model with its logits copied out: a prefill's, and a decode
    step's for the slots that ran."""

    seen: list = []

    def paged_prefill(self, params, caches, prompt, t0, pages):
        out = super().paged_prefill(params, caches, prompt, t0, pages)
        jax.debug.callback(
            lambda lg, n: Spy.seen.append(("prefill", np.asarray(lg[0]),
                                           int(n))), out[1], t0)
        return out

    def paged_decode(self, params, caches, tables, lengths, tokens, active,
                     **kw):
        out = super().paged_decode(params, caches, tables, lengths, tokens,
                                   active, **kw)
        jax.debug.callback(
            lambda lg, act, ln: Spy.seen.extend(
                ("step", lg[i], int(ln[i])) for i in np.flatnonzero(act)),
            out[1], active, lengths)
        return out


def _serve(eng, prompts, new):
    Spy.seen.clear()
    reqs = [eng.submit(p, new) for p in prompts]
    eng.run_until_idle(timeout_s=300)
    jax.effects_barrier()
    assert all(r.error is None for r in reqs)
    return reqs


def _worst(params, sizes, prompt, req, seen):
    """The largest difference between the logits the engine computed
    for ``req`` (alone in the engine) and the reference's full forward
    over its prompt and tokens."""
    full = np.asarray(ref.forward_logits(
        params, sizes, list(prompt) + list(req.tokens)))
    assert seen, "no logits seen"
    return max(float(np.max(np.abs(lg - full[at if kind == "step"
                                              else at - 1])))
               for kind, lg, at in seen)


@pytest.fixture(scope="module")
def roomy():
    """One engine with room (3 slots, 39 pages of 4) for the tests that
    need no other: its step and prefill programs compile once."""
    with jax.default_matmul_precision("highest"):
        model, params, sizes = make(22, cls=Spy)
        yield LMEngine(model, params=params, max_batch=3, page_size=PAGE,
                       num_pages=40), params, sizes


PROMPTS = [list(tokens_of(n, n)) for n in (5, 7, 3)]


def test_the_engines_own_logits_are_the_references(roomy):
    """submit / pump through the engine's own scheduler, allocator,
    buckets and sampling: every logit row its prefill and its steps
    computed for a request, against the reference's full forward."""
    eng, params, sizes = roomy
    assert type(eng._kind).__name__ == "OneToken" and eng._kind.guarded
    prompt = list(tokens_of(6, 2))
    req, = _serve(eng, [prompt], 7)
    seen = list(Spy.seen)
    assert [at for _, _, at in seen] == [6] + list(range(6, 12))
    assert _worst(params, sizes, prompt, req, seen) <= F32_TOL
    gaps, _ = ref.served_gaps(params, sizes, prompt, list(req.tokens))
    assert gaps.shape == (7,) and float(gaps.max()) <= GAP_LIMIT
    st = eng.stats()
    assert st["state_bytes_per_slot"] == STATE_BYTES
    assert st["kv_pages_in_use"] == 0 and st["state_rebuilds"] == 0
    assert len(eng.cache.buffers()) == 3 and len(eng.cache.pools()) == 1
    assert all(s.dtype == jnp.float32 for s in eng.cache.state)


def test_a_bfloat16_engine_fails_the_float32_tolerance():
    model, params, sizes = make(22, dtype=jnp.bfloat16, cls=Spy)
    eng = LMEngine(model, params=params, max_batch=1, page_size=PAGE,
                   num_pages=20)
    assert eng.cache.kp.dtype == jnp.bfloat16
    # the state stays float32 whatever the weights are
    assert all(s.dtype == jnp.float32 for s in eng.cache.state)
    prompt = list(tokens_of(6, 2))
    req, = _serve(eng, [prompt], 4)
    seen = [(k, np.asarray(lg, np.float32), at) for k, lg, at in Spy.seen]
    assert _worst(params, sizes, prompt, req, seen) > 10 * F32_TOL


def test_a_preempted_request_is_rebuilt_within_the_tolerance(roomy):
    """With 9 pages of 4 for three requests of up to 7 + 10 tokens the
    pool runs out: the youngest request is preempted, and its second
    prefill REBUILDS its state by the chunked scan over prompt +
    generated prefix (no snapshot was taken).  What differs from the
    stepped state is rounding: every logit the engine computed for it
    afterwards is the reference's within the float32 tolerance."""
    eng, params, sizes = roomy
    want = [list(r.tokens) for r in _serve(eng, PROMPTS, 10)]
    before = eng.stats()
    spare = eng.cache.withhold(eng.cache.free_pages() - 9)
    try:
        reqs = _serve(eng, PROMPTS, 10)
    finally:
        eng.cache.hand_back(spare)
    st = eng.stats()
    preempted = st["preemptions"] - before["preemptions"]
    assert preempted >= 1
    assert st["state_rebuilds"] - before["state_rebuilds"] == preempted
    assert [list(r.tokens) for r in reqs] == want
    for prompt, req in zip(PROMPTS, reqs):
        gaps, _ = ref.served_gaps(params, sizes, prompt, list(req.tokens))
        assert float(gaps.max()) <= GAP_LIMIT
    # the rebuilt prefills' logits, against the reference
    victim = max(reqs, key=lambda r: r.preempted)
    prompt = PROMPTS[reqs.index(victim)]
    full = np.asarray(ref.forward_logits(
        params, sizes, prompt + list(victim.tokens)))
    longer = [(lg, at) for kind, lg, at in Spy.seen
              if kind == "prefill" and at > len(prompt)
              and at - len(prompt) <= len(victim.tokens)]
    assert longer
    assert any(float(np.max(np.abs(lg - full[at - 1]))) <= F32_TOL
               for lg, at in longer)


def test_spans_say_the_state_the_routing_and_the_groups_share(
        roomy, tmp_path, monkeypatch):
    from bigdl_tpu.obs import names
    from bigdl_tpu.serving import spans as S

    eng, _, _ = roomy
    gauge = obs.get_registry().gauge(names.SERVE_SLOT_STATE_BYTES, "")
    assert gauge._solo().value == STATE_BYTES
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
        for s, r, n in zip(sorted(prefills, key=lambda s: s["wall_time"]),
                           sorted(reads, key=lambda s: s["wall_time"]),
                           (5, 7)):
            a = s["attrs"]
            assert a["state_bytes"] == STATE_BYTES and "rebuilt" not in a
            assert r["attrs"]["request"] == a["request"]
            a = r["attrs"]
            assert a["moe_held"] + a["moe_absent"] == 3 * 4 * n
            assert 0.0 <= a["moe_group_hit_share"] <= 1.0
        # a step's numbers ride on the span of the step that read them:
        # both slots' state in and out, their contexts' rows, three
        # expert layers' routing of two tokens
        a = steps[1]["attrs"]
        assert a["state_bytes"] == 2 * 2 * STATE_BYTES
        assert a["context_tokens"] == (5 + 1) + (7 + 1)
        assert a["attn_rows_copied"] == 2 * 8 * 4
        assert a["moe_held"] + a["moe_absent"] == 3 * 4 * 2
        assert a["moe_zero"] == 0 and a["active"] == 2
        # every expert is held here, so every token keeps a held group
        assert a["moe_group_hit_share"] == 1.0
    finally:
        obs.reset()


def test_step_programs_carry_the_scopes_and_no_guard_of_the_state(roomy):
    eng, _, _ = roomy
    tables, lengths = eng.cache.device_tables(pages=2)
    z = jnp.zeros((3,), jnp.int32)
    no = jnp.zeros((3,), bool)
    step = eng._step_fn.lower(
        eng.params, *eng.cache.buffers(), tables, lengths, z,
        jnp.zeros((3,), jnp.float32), no,
        jax.random.key(0)).as_text(debug_info=True)
    pre = eng._prefill_fn(8).lower(
        eng.params, *eng.cache.buffers(), jnp.zeros((1, 8), jnp.int32), 5,
        jnp.zeros((2,), jnp.int32), 0.0, jax.random.key(1),
        np.int32(1), z).as_text(debug_info=True)
    shared = ("kda.proj", "kda.conv", "mla.proj", "mla.attn", "ffn",
              "moe.route", "moe.experts", "kv_write", "dense", "sample")
    for scope in shared + ("kda.state",):
        assert f"/{scope}/" in step, scope
    for scope in shared + ("kda.scan",):
        assert f"/{scope}/" in pre, scope
    assert "/kda.scan/" not in step and "/kda.state/" not in pre
    assert "kda_state_update" in step and "kda_state_update" not in pre
    # no select over the whole of S: the step's update is the guard
    assert "select" not in "".join(
        line for line in step.splitlines() if "3x4x8x8" in line)


def test_a_state_buffer_over_two_gib_is_refused_before_it_is_built():
    """All 32 heads of ``S`` over 6 layers and 256 slots are ONE buffer
    of 3 GiB, which served wrong tokens on the chip; two arrays of 16
    heads are 1.5 GiB each and Falcon-H1's ``H`` exactly 2 GiB: both
    pass.  The refusal comes before anything is allocated."""
    from bigdl_tpu.serving.cache import (STATE_BUFFER_BYTES,
                                         state_buffer_bytes)

    assert state_buffer_bytes(6, 256, (16, 128, 128), 4) == 3 << 29
    assert state_buffer_bytes(4, 128, (32, 256, 128), 4) \
        == STATE_BUFFER_BYTES
    with pytest.raises(ValueError, match="3.00 GiB.*in parts"):
        state_buffer_bytes(6, 256, (32, 128, 128), 4)
    with pytest.raises(ValueError, match="in parts"):
        PagedKVCache(1, row_width=8, buffers=1, page_size=4, num_pages=4,
                     max_slots=256, max_len=16, dtype=jnp.float32,
                     state_spec={"layers": 6, "shapes": ((32, 128, 128),),
                                 "dtype": jnp.float32})


@pytest.mark.parametrize("kw,what", [
    (dict(int8=True), "int8=True"), (dict(tp=2), "tp > 1")])
def test_int8_and_tp_are_refused_with_a_reason(kw, what):
    model, params, _ = make()
    with pytest.raises(ValueError, match="LingFlash does not offer " + what):
        LMEngine(model, params=params, max_batch=2, page_size=4, **kw)
