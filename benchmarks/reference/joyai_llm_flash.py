"""Plain reference for the ``joyai_llm_flash`` configuration:
JoyAI-LLM-Flash's decoder (latent attention, a leading dense layer,
expert layers with a sigmoid router and a shared expert) and its
prediction layer as straightforward ``jax.numpy`` in float32 with matmul
precision ``highest``.  Full causal attention with K and V rebuilt per
head: no absorption, no cache, no batching, no sorting, no kernels, no
drafting.  It imports nothing of the program.

Source: ``huggingface.co/jdopensource/JoyAI-LLM-Flash`` ``config.json``
(``model_type`` ``joyai_llm_flash``).  **Departures and assumptions**
(what that file does not state is from modelling code of the same
family, from memory, unverified here: there is no network):

* SiLU in every gated MLP (``hidden_act``); interleaved rotary pairs
  ``(2i, 2i+1)`` (``rope_interleave``); no scaling of the rotary
  frequencies (``rope_scaling`` null); no factor on the normalised
  low-rank vectors;
* the router (``topk_method`` ``noaux_tc``) has no bias of its own, runs
  in float32, takes a sigmoid of each of its 256 outputs; the selection
  bias picks the experts and is not in their weights; with ``n_group``
  1 and ``topk_group`` 1 the group stage keeps every expert; the eight
  chosen weights are divided by their sum + 1e-20 (``norm_topk_prob``)
  and multiplied by ``routed_scaling_factor``;
* the prediction layer: ``u = W_eh [rms_e(Emb(x_{i+1})) ; rms_h(h_i)]``
  with the embedding FIRST, and ``h_i`` the last layer's output BEFORE
  the final norm: both assumed (with every gain at 1, as drawn here,
  before and after the final norm differ only by the two ``eps``); one
  decoder layer of the expert kind; a norm of its own; the main model's
  embedding and head;
* an untied head; N(0, 0.02) matrices and embeddings, norms at 1, the
  selection bias at 0;
* **the chip's share**: ``sizes["held"] = (lo, hi)`` — a token's result
  from the expert layer is the sum over its chosen experts that are
  held, plus the shared expert; what the other routed experts would have
  added is left out, here exactly as in the program.  The weights are
  renormalised over all eight chosen, so the shares of a layer add up to
  the uncut layer once the shared expert is counted once.

One layer: ``a = h + A(rms(h))``, ``h' = a + F(rms(a))``; ``F`` the
dense MLP (width 7168) in layer 0, ``sum_e w_e E_e(x) + S(x)`` in the
others (``E_e``, ``S`` gated MLPs of width 768).  ``A`` at position t:
``c_q = rms(W_qa x)``, ``q = W_qb c_q`` split per head into ``q_nope``
(128) and ``q_rope`` (64); ``[c_kv | k_rope] = W_kva x``, ``c =
rms(c_kv)``; rotary on ``q_rope`` and ``k_rope`` at t; per head
``[k_nope_h | v_h] = W_kvb,h c``, ``k_h = [k_nope_h | k_rope]``; scores
``q_h . k_h / sqrt(192)``, causal softmax; ``W_o`` over the heads'
mixes.

The weights' tree (the program's model takes the same tree; ``y = x @
w.T`` unless said):

    embed.weight (V, D)   norm_f.weight (D,)   head.weight (V, D)
    l<i>.norm_attn.weight, l<i>.norm_mlp.weight (D,)
    l<i>.attn.{wq_a (Rq, D), q_norm (Rq,), wq_b (H*(nope+rope), Rq),
               wkv_a (Rkv+rope, D), kv_norm (Rkv,),
               wkv_b (H*(nope+v), Rkv), wo (D, H*v)}
    l0.mlp.{gate (F, D), up (F, D), down (D, F)}
    l<i>.moe.{router (E, D), bias (E,),
              w_gate (G, D, Fe), w_up (G, D, Fe), w_down (G, Fe, D),
              s_gate (Fs, D), s_up (Fs, D), s_down (D, Fs)}     i >= 1
              G held experts, y = x @ w[g]
    mtp.{norm_e.weight, norm_h.weight, norm_f.weight (D,),
         proj.weight (D, 2D), layer.<an expert layer's tree>}

``precision="int8"`` is the control of "How correct is decided": the
same forward with every weight matrix rounded to int8 per output channel
and every such product's input rounded to int8 per row (W8A8; the router
stays in float32 there as well), the nearest precision below the
configuration's bfloat16.  ``"float8"`` rounds every weight matrix to
float8 (e4m3) and back, a second control for the readings.

Every matrix is upcast where it is used, one at a time, and the
attention runs eight heads at a time, so the reference fits beside the
served bfloat16 weights.
"""

from __future__ import annotations

import functools
import math

import numpy as np

# sizes a jitted piece is specialised on (hashable)
_KEYS = ("dim", "n_head", "q_rank", "kv_rank", "nope", "rope", "v_dim",
         "n_routed", "top_k", "scale", "eps", "theta", "held")


def sizes_of(config: dict) -> dict:
    """The reference's sizes from a configuration file in the published
    ``config.json`` spelling.  The file's own keys: ``held_experts``
    ([lo, hi), default all), ``router_experts`` (the router's published
    width where ``n_routed_experts`` counts the experts held) and
    ``max_len``."""
    n_routed = int(config.get("router_experts", config["n_routed_experts"]))
    held = config.get("held_experts", [0, n_routed])
    if "router_experts" in config and \
            held[1] - held[0] != int(config["n_routed_experts"]):
        raise ValueError("held_experts does not hold n_routed_experts")
    for key, want in (("scoring_func", "sigmoid"), ("norm_topk_prob", True),
                      ("n_group", 1), ("topk_group", 1),
                      ("num_nextn_predict_layers", 1), ("moe_layer_freq", 1)):
        if config.get(key, want) != want:
            raise ValueError(f"the reference computes {key} = {want!r} only")
    return dict(
        n_layer=int(config["num_hidden_layers"]),
        n_dense=int(config.get("first_k_dense_replace", 1)),
        dim=int(config["hidden_size"]),
        n_head=int(config["num_attention_heads"]),
        q_rank=int(config["q_lora_rank"]),
        kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]),
        rope=int(config["qk_rope_head_dim"]),
        v_dim=int(config["v_head_dim"]),
        ffn=int(config["intermediate_size"]),
        expert_ffn=int(config["moe_intermediate_size"]),
        shared_ffn=int(config.get("n_shared_experts", 1))
        * int(config["moe_intermediate_size"]),
        n_routed=n_routed, top_k=int(config["num_experts_per_tok"]),
        scale=float(config["routed_scaling_factor"]),
        eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]),
        vocab=int(config["vocab_size"]),
        max_len=int(config.get("max_len", config.get(
            "max_position_embeddings", 2048))),
        held=(int(held[0]), int(held[1])),
        init_std=float(config.get("initializer_range", 0.02)))


def _key(sizes: dict) -> tuple:
    return tuple(sizes[k] for k in _KEYS)


def init_params(seed: int, sizes: dict, dtype):
    """All weights from ``seed`` on the default device: one jitted call
    for the embedding, the head and the final norm, one for the dense
    layer, one an expert layer (the same program for every one of them
    and for the prediction layer's), one for the prediction layer's own
    matrices.  Matrices and embeddings N(0, init_std), norms at 1, the
    selection bias at 0."""
    import jax
    import jax.numpy as jnp

    d, v, std = sizes["dim"], sizes["vocab"], sizes["init_std"]
    h = sizes["n_head"]
    g = sizes["held"][1] - sizes["held"][0]

    def normal(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    def ones(n):
        return {"weight": jnp.ones((n,), dtype)}

    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": {"weight": normal(k[0], (v, d))},
                "norm_f": ones(d),
                "head": {"weight": normal(k[1], (v, d))}}

    def attention(key):
        k = jax.random.split(key, 5)
        return {
            "wq_a": normal(k[0], (sizes["q_rank"], d)),
            "q_norm": jnp.ones((sizes["q_rank"],), dtype),
            "wq_b": normal(k[1], (h * (sizes["nope"] + sizes["rope"]),
                                  sizes["q_rank"])),
            "wkv_a": normal(k[2], (sizes["kv_rank"] + sizes["rope"], d)),
            "kv_norm": jnp.ones((sizes["kv_rank"],), dtype),
            "wkv_b": normal(k[3], (h * (sizes["nope"] + sizes["v_dim"]),
                                   sizes["kv_rank"])),
            "wo": normal(k[4], (d, h * sizes["v_dim"]))}

    def layer(key, dense):
        ka, km = jax.random.split(key, 2)
        out = {"norm_attn": ones(d), "norm_mlp": ones(d),
               "attn": attention(ka)}
        if dense:
            k = jax.random.split(km, 3)
            out["mlp"] = {"gate": normal(k[0], (sizes["ffn"], d)),
                          "up": normal(k[1], (sizes["ffn"], d)),
                          "down": normal(k[2], (d, sizes["ffn"]))}
            return out
        k = jax.random.split(km, 7)
        fe, fs = sizes["expert_ffn"], sizes["shared_ffn"]
        out["moe"] = {"router": normal(k[0], (sizes["n_routed"], d)),
                      "bias": jnp.zeros((sizes["n_routed"],), jnp.float32),
                      "w_gate": normal(k[1], (g, d, fe)),
                      "w_up": normal(k[2], (g, d, fe)),
                      "w_down": normal(k[3], (g, fe, d)),
                      "s_gate": normal(k[4], (fs, d)),
                      "s_up": normal(k[5], (fs, d)),
                      "s_down": normal(k[6], (d, fs))}
        return out

    def mtp_own(key):
        return {"norm_e": ones(d), "norm_h": ones(d), "norm_f": ones(d),
                "proj": {"weight": normal(key, (d, 2 * d))}}

    # a seed may exceed 32 signed bits: fold it into the key in two
    # halves; the rbg generator is the chip's own and several times
    # faster than threefry over 2e9 draws
    seed = int(seed)
    key = jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)
    keys = jax.random.split(key, sizes["n_layer"] + 3)
    tree = jax.jit(ends)(keys[0])
    dense_layer = jax.jit(functools.partial(layer, dense=True))
    expert_layer_ = jax.jit(functools.partial(layer, dense=False))
    for i in range(sizes["n_layer"]):
        make = dense_layer if i < sizes["n_dense"] else expert_layer_
        tree[f"l{i}"] = make(keys[1 + i])
    tree["mtp"] = jax.jit(mtp_own)(keys[sizes["n_layer"] + 1])
    tree["mtp"]["layer"] = expert_layer_(keys[sizes["n_layer"] + 2])
    return tree


# ------------------------------------------------------------- the pieces
def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(w)


def _round8(a, axis):
    """``a`` rounded to 127 levels of its largest magnitude along
    ``axis``."""
    import jax.numpy as jnp

    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True),
                    1e-8) / 127.0
    return jnp.clip(jnp.round(a / s), -127, 127) * s


def _matmul(x, w, precision, out_in=True):
    """``x (T, K) @ w``: ``w`` is ``(N, K)`` (``out_in``) or ``(K, N)``;
    float32 ``highest``; in W8A8 (weights a output channel, inputs a
    row); or with the weights rounded to float8."""
    import jax.numpy as jnp

    w = _f32(w)
    if precision == "int8":
        w = _round8(w, axis=1 if out_in else 0)
        x = _round8(x, axis=-1)
    elif precision == "float8":
        w = _f32(w.astype(jnp.float8_e4m3fn))
    return jnp.matmul(x, w.T if out_in else w, precision="highest")


def _rotary(x, positions, theta):
    """Interleaved pairs of the last axis rotated at ``positions``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, None].astype(jnp.float32) * inv      # (T, d/2)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _mlp(p, x, precision, names=("gate", "up", "down")):
    import jax

    h = jax.nn.silu(_matmul(x, p[names[0]], precision)) \
        * _matmul(x, p[names[1]], precision)
    return _matmul(h, p[names[2]], precision)


def _attention(p, x, s: dict, precision):
    """Latent attention over one sequence ``x`` (T, D), K and V rebuilt
    per head, eight heads at a time."""
    import jax
    import jax.numpy as jnp

    t, _ = x.shape
    h, nope, rope, vd = s["n_head"], s["nope"], s["rope"], s["v_dim"]
    rkv = s["kv_rank"]
    pos = jnp.arange(t)
    c_q = _rms(_matmul(x, p["wq_a"], precision), p["q_norm"], s["eps"])
    q = _matmul(c_q, p["wq_b"], precision)
    q = q.reshape(t, h, nope + rope).transpose(1, 0, 2)      # (H, T, 192)
    kv = _matmul(x, p["wkv_a"], precision)
    c = _rms(kv[:, :rkv], p["kv_norm"], s["eps"])
    k_rope = _rotary(kv[:, rkv:], pos, s["theta"])           # (T, rope)
    wkv = p["wkv_b"].reshape(h, nope + vd, rkv)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def head(args):
        q_h, w_h = args
        kv_h = _matmul(c, w_h, precision)                    # (T, nope+v)
        k_h = jnp.concatenate([kv_h[:, :nope], k_rope], axis=-1)
        q_h = jnp.concatenate(
            [q_h[:, :nope], _rotary(q_h[:, nope:], pos, s["theta"])],
            axis=-1)
        scores = jnp.matmul(q_h, k_h.T, precision="highest") \
            / math.sqrt(nope + rope)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.matmul(probs, kv_h[:, nope:], precision="highest")

    o = jax.lax.map(head, (q, wkv), batch_size=min(8, h))    # (H, T, v)
    return _matmul(o.transpose(1, 0, 2).reshape(t, h * vd), p["wo"],
                   precision)


def _experts(p, x, s: dict, precision, shared=True):
    """The expert layer's share for the held experts ``s["held"]``: a
    loop over them, each over every token, weighted by the router; and
    the shared expert."""
    import jax
    import jax.numpy as jnp

    lo, hi = s["held"]
    logits = jnp.matmul(x, _f32(p["router"]).T, precision="highest")
    sc = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(sc + _f32(p["bias"]), s["top_k"])
    w = jnp.take_along_axis(sc, idx, axis=-1)                # (T, k)
    w = s["scale"] * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    y = _mlp(p, x, precision, ("s_gate", "s_up", "s_down")) if shared \
        else jnp.zeros_like(x)

    def one_expert(g, y):
        def of(name):
            return jax.lax.dynamic_index_in_dim(p[name], g, keepdims=False)

        w_e = jnp.sum(jnp.where(idx == lo + g, w, 0.0), axis=-1,
                      keepdims=True)
        hmid = jax.nn.silu(_matmul(x, of("w_gate"), precision, False)) \
            * _matmul(x, of("w_up"), precision, False)
        return y + w_e * _matmul(hmid, of("w_down"), precision, False)

    return jax.lax.fori_loop(0, hi - lo, one_expert, y)


@functools.lru_cache(maxsize=None)
def _piece(name: str, key: tuple, precision: str):
    """One jitted piece of a layer at these sizes: a layer never exists
    in float32 as a whole."""
    import jax
    import jax.numpy as jnp

    s = dict(zip(_KEYS, key))
    if name == "attn":
        return jax.jit(lambda p, nw, x: x + _attention(
            p, _rms(x, nw, s["eps"]), s, precision))
    if name == "mlp":
        return jax.jit(lambda p, nw, x: x + _mlp(
            p, _rms(x, nw, s["eps"]), precision))
    if name == "moe":
        return jax.jit(lambda p, nw, x: x + _experts(
            p, _rms(x, nw, s["eps"]), s, precision))
    if name == "moe_alone":
        return jax.jit(lambda p, x, shared: _experts(
            p, x, s, precision, shared), static_argnums=2)
    if name == "join":
        return jax.jit(lambda p, emb, h: _matmul(jnp.concatenate(
            [_rms(emb, p["norm_e"]["weight"], s["eps"]),
             _rms(h, p["norm_h"]["weight"], s["eps"])], axis=-1),
            p["proj"]["weight"], precision))
    raise KeyError(name)


def layer_forward(p, sizes: dict, h, precision: str = "float32"):
    """One layer over one sequence ``h`` (T, D), float32: the dense kind
    where its tree holds ``mlp``, the expert kind where ``moe``."""
    key = _key(sizes)
    a = _piece("attn", key, precision)(p["attn"], p["norm_attn"]["weight"],
                                       h)
    kind = "mlp" if "mlp" in p else "moe"
    return _piece(kind, key, precision)(p[kind], p["norm_mlp"]["weight"], a)


def expert_layer(p, sizes: dict, x, precision: str = "float32",
                 shared: bool = True):
    """The expert layer alone, ``x`` (T, D) -> (T, D): the share of
    ``sizes["held"]``, with or without the shared expert (for the tests
    of the share)."""
    import jax.numpy as jnp

    return _piece("moe_alone", _key(sizes), precision)(
        p, jnp.asarray(x, jnp.float32), shared)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, precision: str):
    import jax
    import jax.numpy as jnp

    def head(norm_w, w, x, served):
        """Per position: the logits, the reference's best logit minus
        its logit for the token that was served, and the token it puts
        first."""
        logits = _matmul(_rms(x, norm_w, eps), w, precision)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
        return logits, best - got, jnp.argmax(logits, axis=-1)

    return jax.jit(head)


def _pad_to(n: int, step: int = 128) -> int:
    """``n`` rounded up to ``step``, or to 1024 beyond 512: a long
    sequence's pieces take ten seconds each to compile, so few lengths
    (one, 2048, for the requests of a long-generation mix)."""
    step = 1024 if n > 512 and step == 128 else step
    return -(-n // step) * step


def _padded(sizes: dict, tokens):
    tokens = np.asarray(tokens, np.int32)
    t = len(tokens)
    tp = min(_pad_to(t), max(_pad_to(sizes["max_len"]), t))
    padded = np.zeros((tp,), np.int32)
    padded[:t] = tokens
    return padded, t


def forward_hidden(params, sizes: dict, tokens, precision: str = "float32",
                   keep_padding: bool = False):
    """The last layer's outputs (T, D), float32, of one sequence, before
    the final norm, a piece of a layer at a time.  The sequence is
    padded to a multiple of 128 (causal attention keeps the real prefix
    exact, and no token's expert result depends on another token) to
    bound the number of compiled shapes."""
    import jax.numpy as jnp

    padded, t = _padded(sizes, tokens)
    x = _f32(jnp.take(params["embed"]["weight"], jnp.asarray(padded),
                      axis=0))
    for i in range(sizes["n_layer"]):
        x = layer_forward(params[f"l{i}"], sizes, x, precision)
    return x if keep_padding else x[:t]


def _gaps(params, sizes, norm_w, x, n: int, scored, precision):
    """``x`` (n, D) through ``norm_w`` and the head: the gap of
    ``scored`` (n,) at each position, and the first choices."""
    import jax.numpy as jnp

    npad = _pad_to(n)
    xp = jnp.zeros((npad, x.shape[1]), jnp.float32).at[:n].set(x[:n])
    sp = np.zeros((npad,), np.int32)
    sp[:n] = scored
    logits, gaps, first = _head_fn(sizes["eps"], precision)(
        norm_w, params["head"]["weight"], xp, jnp.asarray(sp))
    return logits[:n], np.asarray(gaps)[:n], np.asarray(first)[:n]


def forward_logits(params, sizes: dict, tokens,
                   precision: str = "float32"):
    """Logits (T, V), float32, at every position of one sequence."""
    x = forward_hidden(params, sizes, tokens, precision)
    n = x.shape[0]
    return _gaps(params, sizes, params["norm_f"]["weight"], x, n,
                 np.zeros((n,), np.int32), precision)[0]


def draft_hidden(params, sizes: dict, tokens, precision: str = "float32"):
    """The prediction layer's full forward over one sequence ``tokens``
    (T,): its output (T - 1, D) before its norm; position ``i`` reads
    the main model's ``h_i`` and ``tokens[i + 1]``, attends the layer's
    own positions ``<= i`` and predicts ``tokens[i + 2]``."""
    import jax.numpy as jnp

    padded, t = _padded(sizes, tokens)
    h = forward_hidden(params, sizes, tokens, precision, keep_padding=True)
    nxt = np.zeros_like(padded)
    nxt[:-1] = padded[1:]
    emb = _f32(jnp.take(params["embed"]["weight"], jnp.asarray(nxt), axis=0))
    p = params["mtp"]
    u = _piece("join", _key(sizes), precision)(p, emb, h)
    return layer_forward(p["layer"], sizes, u, precision)[:t - 1]


def draft_logits(params, sizes: dict, tokens, precision: str = "float32"):
    """The prediction layer's logits (T - 1, V) over one sequence."""
    g = draft_hidden(params, sizes, tokens, precision)
    n = g.shape[0]
    return _gaps(params, sizes, params["mtp"]["norm_f"]["weight"], g, n,
                 np.zeros((n,), np.int32), precision)[0]


def served_gaps(params, sizes: dict, prompt, served,
                precision: str = "float32", score=None):
    """For one finished request: at each served position, how far the
    served token's logit lies below the reference's best (0 where the
    reference would have served the same token).  Also returns the
    tokens this forward puts first at those positions.  ``score`` gives
    other tokens to read the gap of, at the same positions of the same
    prompt and served tokens (the control: what a lower precision put
    first)."""
    prompt = [int(t) for t in prompt]
    served = [int(t) for t in served]
    tokens = prompt + served
    x = forward_hidden(params, sizes, tokens[:-1], precision)
    # position len(prompt) - 1 + j predicts served[j]
    x = x[len(prompt) - 1:]
    _, gaps, first = _gaps(params, sizes, params["norm_f"]["weight"], x,
                           len(served), served if score is None else score,
                           precision)
    return gaps, first


def draft_gaps(params, sizes: dict, prompt, served, drafts,
               precision: str = "float32", score=None):
    """For one finished request and the drafts the engine recorded for
    it (``ServeRequest.drafts``: pairs ``(j, d)``, draft ``d`` was
    checked against ``served[j]``): the reference's prediction layer
    over the prompt and the served tokens, and for each draft the
    reference's best draft logit minus its logit for ``d`` (0 where the
    reference would have drafted the same token).  Also returns the
    reference's own drafts at those places.  ``score`` gives other
    drafts to read the gap of at the same places (the control)."""
    prompt = [int(t) for t in prompt]
    served = [int(t) for t in served]
    where = np.asarray([int(j) for j, _ in drafts], np.int64)
    if not len(where):
        return np.zeros((0,)), np.zeros((0,), np.int64)
    if where.min() < 1 or where.max() >= len(served):
        raise ValueError("a draft is checked against served[1:] only")
    # the draft of the token at position q comes from position q - 2
    tokens = prompt + served
    g = draft_hidden(params, sizes, tokens[:-1], precision)
    rows = g[len(prompt) - 2 + where]
    scored = [int(d) for _, d in drafts] if score is None else score
    _, gaps, first = _gaps(params, sizes,
                           params["mtp"]["norm_f"]["weight"], rows,
                           len(where), scored, precision)
    return gaps, first
