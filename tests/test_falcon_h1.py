"""Falcon-H1 at a tiny size on the CPU with every ratio kept (5 query
rows a key head, 2 groups of 3 mixer heads, a convolution of 4, chunks
of 8), seeded weights, float32:

(a) the whole model against the plain reference
    (``benchmarks/reference/falcon_h1_34b.py``, a per-token recurrence)
    on LOGITS, tight enough that bfloat16 matrices fail, and failing
    with any one part of the mathematics left out or any multiplier
    moved;
(b) the mixer alone: one step a token against the chunked scan, the
    padded tail of a bucket changes no bit of the state;
(c) prefill, then decode through the paged cache AND THE SLOTS' STATE
    against the reference's full forward: prompts of 1, 2, 3, a
    bucket's edge and mid-bucket; a slot that sits out keeps its state
    bit for bit WITHOUT the engine's guard;
(d) the engine: the logits of its own prefill and steps against the
    reference, with the bfloat16 engine failing; a slot's second
    occupant sees nothing of the first; a preempted request continues
    within the tolerance and counts one ``state_rebuilds`` (nothing was
    snapshotted); spans, ``stats()``, scopes.

Tolerances: ``F32_TOL`` bounds float32 accumulation-order noise on
logits of magnitude about 3 (measured 3e-6 between the program's
chunked scan and the reference's per-token recurrence; 2e-5 after 40
stepped tokens); ``GAP_LIMIT`` bounds a logit gap between two float32
computations of the same state (a flipped near-tie reads its margin).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import falcon_h1_34b as ref
from bigdl_tpu import obs
from bigdl_tpu.models.falcon_h1 import FalconH1, build_falcon_h1
from bigdl_tpu.nn.ssm import Mamba2Mixer
from bigdl_tpu.serving import LMEngine
from bigdl_tpu.serving.cache import PagedKVCache, write_slot_state

F32_TOL = 2e-4
GAP_LIMIT = 1e-3

VOCAB, MAX_LEN, PAGE = 96, 64, 4
SMALL = dict(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
             intermediate_size=48, num_attention_heads=10,
             num_key_value_heads=2, head_dim=8, rope_theta=1e11,
             rms_norm_eps=1e-5, mamba_d_ssm=48, mamba_n_heads=6,
             mamba_d_head=8, mamba_d_state=16, mamba_n_groups=2,
             mamba_d_conv=4, mamba_chunk_size=8)
MULTIPLIERS = dict(
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
    attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284])
#: a slot's state: 2 layers x (6 x 16 x 8 + 3 x (48 + 2 x 2 x 16)) float32
STATE_BYTES = 2 * (6 * 8 * 16 + 3 * 112) * 4


def config(**kw):
    return dict(dict(SMALL, **MULTIPLIERS), max_len=MAX_LEN,
                model_type="falcon_h1", mamba_rms_norm=True,
                mamba_norm_before_gate=False, tie_word_embeddings=False,
                **kw)


def make(seed=7, dtype=jnp.float32, cls=None, **kw):
    """Seeded weights from the reference, the reference's sizes, and the
    program's model built around that tree without weights of its own."""
    cfg = config(**kw)
    sizes = ref.sizes_of(cfg)
    params = ref.init_params(seed, sizes, dtype)
    if cls is None:
        return build_falcon_h1(cfg, params=params), params, sizes
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
           len(toks))
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
    toks = tokens_of(length, seed % 97)
    want = ref.forward_logits(params, sizes, toks)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    np.testing.assert_allclose(forward(model, params, toks), want,
                               atol=F32_TOL)


def test_seeded_weights_leave_no_path_dead():
    """The draw undoes the multipliers: scores and logits spread, the
    heads' decays span slow to fast, no tap is near 0."""
    _, params, sizes = make(3)
    s, a = params["l1"]["ssm"], params["l1"]["attn"]
    dt = np.log1p(np.exp(np.asarray(s["dt_bias"])))
    decay = np.exp(-dt * np.exp(np.asarray(s["a_log"])))
    assert 1e-3 <= dt.min() and dt.max() <= 0.1
    assert 0.899 <= decay.min() and decay.max() <= 0.9991
    assert float(jnp.min(jnp.abs(s["conv_w"]))) >= 0.19
    assert 0.5 <= float(jnp.min(s["d"])) and float(jnp.max(s["d"])) <= 1.5
    assert all(s[k].dtype == jnp.float32 for k in ("dt_bias", "a_log", "d"))
    # a key row's size once its multiplier has acted is the query's
    k_rms = float(jnp.sqrt(jnp.mean(a["wk"] ** 2))) * sizes["key_mult"]
    q_rms = float(jnp.sqrt(jnp.mean(a["wq"] ** 2)))
    assert 0.8 < k_rms / q_rms < 1.25
    logits = ref.forward_logits(params, sizes, tokens_of(16, 1))
    assert 0.5 < float(jnp.std(logits)) < 2.0


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


@pytest.mark.parametrize("name,index", [
    ("embedding_multiplier", None), ("lm_head_multiplier", None),
    ("attention_in_multiplier", None), ("attention_out_multiplier", None),
    ("key_multiplier", None), ("ssm_in_multiplier", None),
    ("ssm_out_multiplier", None), ("ssm_multipliers", 0),
    ("ssm_multipliers", 1), ("ssm_multipliers", 2), ("ssm_multipliers", 3),
    ("ssm_multipliers", 4), ("mlp_multipliers", 0), ("mlp_multipliers", 1)])
def test_each_multiplier_acts_where_the_reference_has_it(name, index):
    """The same weights under one multiplier halved: the program moves
    away from the published forward, and to where the reference with
    that multiplier halved is."""
    value = MULTIPLIERS[name]
    if index is None:
        moved = value / 2
    else:
        moved = list(value)
        moved[index] /= 2
    _, params, sizes = make(7, num_hidden_layers=1)
    model = build_falcon_h1(config(num_hidden_layers=1, **{name: moved}),
                            params=params)
    toks = tokens_of(12, 3)
    got = forward(model, params, toks)
    base = ref.forward_logits(params, sizes, toks)
    want = ref.forward_logits(
        params, ref.sizes_of(config(num_hidden_layers=1, **{name: moved})),
        toks)
    assert float(jnp.max(jnp.abs(got - base))) > 100 * F32_TOL
    np.testing.assert_allclose(got, want, atol=F32_TOL)


def test_the_int8_control_separates_from_float32():
    _, params, sizes = make(7)
    toks = tokens_of(22, 7)
    want = ref.forward_logits(params, sizes, toks)
    low = ref.forward_logits(params, sizes, toks, precision="int8")
    assert float(jnp.max(jnp.abs(low - want))) > 50 * F32_TOL


def test_a_model_given_params_draws_no_weights_and_builds_from_a_config():
    with open("benchmarks/tests/data/tiny_falcon_h1.json",
              encoding="utf-8") as fh:
        cfg = json.load(fh)
    sizes = ref.sizes_of(cfg)
    params = ref.init_params(3, sizes, jnp.float32)
    model = build_falcon_h1(cfg, params=params)
    assert model.params() is params and model.n_layer == 2
    assert model._children["l0"]._children["ssm"].w_in is None
    spec = model.cache_spec(params)
    assert (spec["heads"], spec["kv_heads"], spec["head_dim"],
            spec["buffers"], spec["attn_query_rows"]) == (10, 2, 8, 2, 10)
    state = model.state_spec(params)
    assert state["shapes"] == ((6, 16, 8), (3, 48 + 2 * 2 * 16))
    assert state["dtype"] == jnp.float32 and state["keeps_inactive"]
    with pytest.raises(ValueError, match="mamba_norm_before_gate"):
        build_falcon_h1(dict(cfg, mamba_norm_before_gate=True))
    with pytest.raises(TypeError, match="unknown sizes"):
        FalconH1(num_experts=4)
    own = FalconH1(max_len=MAX_LEN, **SMALL)       # weights of its own
    out = forward(own, own.params(), tokens_of(22))
    assert out.shape == (22, VOCAB) and bool(jnp.all(jnp.isfinite(out)))


# ------------------------------------------------------- (b) the mixer
def _mixer(seed=5):
    _, params, _ = make(seed)
    mixer = Mamba2Mixer(32, 6, 8, 16, 2, d_conv=4, chunk=8,
                        in_multiplier=0.25,
                        zone_multipliers=MULTIPLIERS["ssm_multipliers"],
                        init=False)
    return mixer, params["l0"]["ssm"]


def test_one_step_a_token_equals_the_chunked_scan():
    """20 real positions of a bucket of 32: the scan's outputs and the
    state it ends on against 20 steps from a zero state (slot 1 of 2, at
    layer 1 of a stacked state of 3; slot 0 never runs and keeps what it
    holds, bit for bit, as do the other layers)."""
    mixer, p = _mixer()
    t0, bucket = 20, 32
    rng = np.random.default_rng(0)
    n = jnp.asarray(rng.normal(size=(bucket, 32)), jnp.float32)
    out, h, rows = jax.jit(mixer.scan)(p, n, t0)
    hs = jnp.asarray(rng.normal(size=(3, 2, 6, 16, 8)), jnp.float32) \
        .at[1, 1].set(0.0)
    rs = jnp.asarray(rng.normal(size=(3, 2, 3, 112)), jnp.float32) \
        .at[1, 1].set(0.0)
    mark_h, mark_r = np.array(hs), np.array(rs)
    step = jax.jit(mixer.step, static_argnums=4)
    active = jnp.asarray([False, True])
    for t in range(t0):
        y, hs, rs = step(p, jnp.stack([n[(t * 7) % bucket], n[t]]), hs, rs,
                         1, active)
        np.testing.assert_allclose(y[1], out[t], atol=2e-5)
    np.testing.assert_allclose(hs[1, 1], h, atol=2e-5)
    assert float(jnp.max(jnp.abs(h))) > 0.01
    # the kept rows are the last three REAL rows before the convolution
    assert np.array_equal(np.asarray(rs[1, 1]), np.asarray(rows))
    for got, mark in ((np.array(hs), mark_h), (np.array(rs), mark_r)):
        got[1, 1] = mark[1, 1]
        assert np.array_equal(got, mark)


def test_the_state_kernel_is_the_plain_update():
    """``ops/ssm_state.py`` against ``jax.numpy`` at a shape with
    several head blocks a group; a slot under decay 1 and ``xdt`` 0
    keeps its bits."""
    from bigdl_tpu.ops.ssm_state import _heads_a_block, state_update

    rng = np.random.default_rng(3)
    layers, s, heads, groups, n, p = 2, 3, 8, 2, 16, 8
    assert _heads_a_block(4, 1 << 19) == 2 and _heads_a_block(16, 1 << 17) == 8
    h = jnp.asarray(rng.normal(size=(layers, s, heads, n, p)), jnp.float32)
    decay = jnp.asarray(rng.uniform(0.9, 1.0, (s, heads)), jnp.float32) \
        .at[0].set(1.0)
    xdt = jnp.asarray(rng.normal(size=(s, heads, p)), jnp.float32) \
        .at[0].set(0.0)
    b = jnp.asarray(rng.normal(size=(s, groups, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(s, groups, n)), jnp.float32)
    got, y = state_update(h, 1, decay, xdt, b, c)
    bh, ch = (jnp.repeat(a, heads // groups, axis=1) for a in (b, c))
    want = h[1] * decay[..., None, None] \
        + bh[..., :, None] * xdt[..., None, :]
    np.testing.assert_allclose(got[1], want, atol=1e-6)
    np.testing.assert_allclose(y, jnp.sum(want * ch[..., None], axis=2),
                               atol=1e-5)
    assert np.array_equal(np.asarray(got[0]), np.asarray(h[0]))
    assert np.array_equal(np.asarray(got[1, 0]), np.asarray(h[1, 0]))


@pytest.mark.parametrize("t0", [1, 2, 3, 8, 13])
def test_the_padded_tail_changes_no_bit_of_the_state(t0):
    """Prompts shorter than the convolution, at a chunk's edge and
    inside one: what lies past ``t0`` in the bucket is not the
    prompt's."""
    mixer, p = _mixer()
    rng = np.random.default_rng(t0)
    n = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    scan = jax.jit(mixer.scan)
    out, h, rows = scan(p, n, t0)
    other = n.at[t0:].set(jnp.asarray(rng.normal(size=(16 - t0, 32)),
                                      jnp.float32) * 5)
    out2, h2, rows2 = scan(p, other, t0)
    assert np.array_equal(np.asarray(h), np.asarray(h2))
    assert np.array_equal(np.asarray(rows), np.asarray(rows2))
    assert np.array_equal(np.asarray(out[:t0]), np.asarray(out2[:t0]))
    # zeros to the left of a prompt shorter than the convolution
    assert bool(jnp.all(rows[:max(0, 3 - t0)] == 0))
    assert float(jnp.max(jnp.abs(rows[-1]))) > 0
    # ... and the state of the prompt alone, unpadded
    _, alone, _ = jax.jit(mixer.scan)(p, n[:t0], t0)
    np.testing.assert_allclose(h, alone, atol=1e-6)


# --------------------------- (c) prefill, then decode over cache and state
def _cache(model, params, slots=2, pages=40):
    spec = model.cache_spec(params)
    return PagedKVCache(
        spec["layers"], spec["kv_heads"], spec["head_dim"],
        row_width=spec["row_width"], buffers=2, page_size=PAGE,
        num_pages=pages, max_slots=slots, max_len=MAX_LEN,
        dtype=jnp.float32, state_spec=model.state_spec(params))


@pytest.fixture(scope="module")
def programs():
    """The model's two entry points, jitted once for the tests of (c)
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
    assert [s.shape for s in cache.state] == [(2, 2, 6, 16, 8),
                                              (2, 2, 3, 112)]
    assert cache.state_bytes_per_slot() == STATE_BYTES
    slot = 1
    pools, logits, counts, rows = _prefilled(
        cache, prefill, params, slot, toks, prompt_len, fill=17)
    assert counts is None
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
        pools, logits, _, state = decode(
            params, cache.pools(), tables, lengths, fed, active, cache.state)
        cache.set_buffers((*pools, *state))
        cache.lengths[slot] += 1
        np.testing.assert_allclose(logits[1], want[pos], atol=F32_TOL,
                                   err_msg=f"position {pos}")
    for s, m in zip(cache.state, mark):
        assert np.array_equal(np.asarray(s[:, 0]), m)
        assert float(jnp.max(jnp.abs(s[:, 1]))) > 0


def test_a_state_left_at_zero_is_caught_by_the_float32_tolerance(programs):
    """What (c) pins is not vacuous: decoding from a zero state is the
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


# ------------------------------------------------ (d) the engine, end to end
class Spy(FalconH1):
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
    assert len(eng.cache.buffers()) == 4 and len(eng.cache.pools()) == 2
    assert all(s.dtype == jnp.float32 for s in eng.cache.state)


def test_a_bfloat16_engine_fails_the_float32_tolerance():
    model, params, sizes = make(22, dtype=jnp.bfloat16, cls=Spy)
    eng = LMEngine(model, params=params, max_batch=1, page_size=PAGE,
                   num_pages=20)
    assert eng.cache.kp.dtype == jnp.bfloat16
    # the state stays float32 whatever the weights are
    assert all(s.dtype == jnp.float32 for s in eng.cache.state)
    assert eng.stats()["state_bytes_per_slot"] == STATE_BYTES
    prompt = list(tokens_of(6, 2))
    req, = _serve(eng, [prompt], 4)
    seen = [(k, np.asarray(lg, np.float32), at) for k, lg, at in Spy.seen]
    assert _worst(params, sizes, prompt, req, seen) > 10 * F32_TOL


def test_a_slots_second_occupant_sees_nothing_of_the_first(roomy):
    """One request at a time, so each takes slot 0.  A short request on
    a fresh state, then a long one, then the short one again in the same
    slot: nothing of the long occupant's state (nor of its pages)
    reaches it.  Its logits are, bit for bit, those of its first run."""
    eng, params, sizes = roomy
    long_p, short_p = list(tokens_of(9, 1)), list(tokens_of(6, 2))
    first, = _serve(eng, [short_p], 5)
    want = [lg for _, lg, _ in Spy.seen]
    clean = [np.asarray(s[:, 0]) for s in eng.cache.state]
    _serve(eng, [long_p], 8)
    assert any(not np.array_equal(np.asarray(s[:, 0]), c)
               for s, c in zip(eng.cache.state, clean))
    again, = _serve(eng, [short_p], 5)
    got = [lg for _, lg, _ in Spy.seen]
    assert list(again.tokens) == list(first.tokens)
    assert len(got) == len(want) == 5
    assert all(np.array_equal(g, w) for g, w in zip(got, want))
    for s, c in zip(eng.cache.state, clean):
        assert np.array_equal(np.asarray(s[:, 0]), c)


def test_a_preempted_request_is_rebuilt_within_the_tolerance(roomy):
    """With 9 pages of 4 for three requests of up to 7 + 10 tokens the
    pool runs out: the youngest request is preempted, and its second
    prefill REBUILDS its state by the scan over prompt + generated
    prefix (no snapshot was taken).  What differs from the stepped state
    is rounding: every logit the engine computed for it afterwards is
    the reference's within the float32 tolerance."""
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
    assert sum(r.preempted for r in reqs) == preempted
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


def test_spans_and_stats_say_the_state(roomy, tmp_path, monkeypatch):
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
        for s in prefills:
            assert s["attrs"]["state_bytes"] == STATE_BYTES
            assert "rebuilt" not in s["attrs"]
        # a step's numbers ride on the span of the step that read them:
        # both slots' state in and out, their contexts' rows
        a = steps[1]["attrs"]
        assert a["state_bytes"] == 2 * 2 * STATE_BYTES
        assert a["context_tokens"] == (5 + 1) + (7 + 1)
        assert a["attn_rows_copied"] == 2 * 8 * 4
        assert a["active"] == 2
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
    for scope in ("ssm.proj", "ssm.conv", "ssm.scan", "gqa.attn", "ffn",
                  "kv_write", "dense", "sample"):
        assert f"/{scope}/" in step, scope
        assert f"/{scope}/" in pre, scope
    # no select over the whole of H: the step's update is the guard
    assert "select" not in "".join(
        line for line in step.splitlines() if "3x6x16x8" in line)


@pytest.mark.parametrize("kw,what", [
    (dict(int8=True), "int8=True"), (dict(tp=2), "tp > 1")])
def test_int8_and_tp_are_refused_with_a_reason(kw, what):
    model, params, _ = make()
    with pytest.raises(ValueError, match="FalconH1 does not offer " + what):
        LMEngine(model, params=params, max_batch=2, page_size=4, **kw)
