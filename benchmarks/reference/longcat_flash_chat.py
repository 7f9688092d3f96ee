"""Plain reference for the ``longcat_flash_chat`` configuration:
LongCat-Flash's shortcut-connected double layer (latent attention, a
gated SiLU MLP, an expert layer with zero-compute experts) as
straightforward ``jax.numpy`` in float32 with matmul precision
``highest``.  Full causal attention with K and V rebuilt per head: no
absorption, no cache, no batching, no sorting, no kernels.  It imports
nothing of the program.

Source: ``huggingface.co/meituan-longcat/LongCat-Flash-Chat``
``config.json``.  **Departures and assumptions** (what that file does
not state is from the upstream modelling code, from memory, unverified
here: there is no network):

* SiLU in every gated MLP; interleaved rotary pairs ``(2i, 2i+1)``; no
  scaling of the rotary frequencies (the config has no ``rope_scaling``);
* ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` multiply the normalised
  low-rank vectors by ``sqrt(hidden / rank)`` (2 and sqrt(12)): the
  factor and the place are assumed;
* the router has no bias of its own, runs in float32, takes a softmax
  over all 768 outputs; the selection bias picks the experts and is not
  in their weights; the weights are not renormalised;
* an untied head; N(0, 0.02) matrices and embeddings, norms at 1, the
  selection bias at 0;
* **the chip's share**: ``sizes["held"] = (lo, hi)`` — a token's result
  from the expert layer is the sum over its chosen experts that are
  held or zero-compute; what the other routed experts would have added
  is left out, here exactly as in the program.

One layer, ``h`` in, ``h''`` out:

    a   = h  + A_0(rms(h))
    u   = rms(a);  m = M(u)
    h'  = a  + mlp_0(u)
    a'  = h' + A_1(rms(h'))
    h'' = a' + mlp_1(rms(a')) + m

``A`` at position t: ``c_q = rms(W_qa x)``, ``q = 2 W_qb c_q`` split per
head into ``q_nope`` (128) and ``q_rope`` (64); ``[c_kv | k_rope] =
W_kva x``, ``c = sqrt(12) rms(c_kv)``; rotary on ``q_rope`` and
``k_rope`` at t; per head ``[k_nope_h | v_h] = W_kvb,h c``, ``k_h =
[k_nope_h | k_rope]``; scores ``q_h . k_h / sqrt(192)``, causal softmax;
``W_o`` over the heads' mixes.  ``M``: ``s = softmax(W_r x)``; the 12
largest of ``s + b``; ``w_e = 6 s_e``; ``sum_e w_e E_e(x)`` with ``E_e``
a gated MLP of width 2048 for e < 512 and the identity for the 256
zero-compute experts.

The weights' tree (the program's model takes the same tree, so the
benchmark hands it over unchanged; ``y = x @ w.T`` unless said):

    embed.weight (V, D)   norm_f.weight (D,)   head.weight (V, D)
    l<i>.norm_attn<j>.weight, l<i>.norm_mlp<j>.weight (D,)       j = 0, 1
    l<i>.attn<j>.{wq_a (Rq, D), q_norm (Rq,), wq_b (H*(nope+rope), Rq),
                  wkv_a (Rkv+rope, D), kv_norm (Rkv,),
                  wkv_b (H*(nope+v), Rkv), wo (D, H*v)}
    l<i>.mlp<j>.{gate (F, D), up (F, D), down (D, F)}
    l<i>.moe.{router (E+Z, D), bias (E+Z,),
              w_gate (G, D, Fe), w_up (G, D, Fe), w_down (G, Fe, D)}
              G held experts, y = x @ w[g]

``precision="int8"`` is the control of "How correct is decided": the
same forward with every weight matrix rounded to int8 per output channel
and every such product's input rounded to int8 per row (W8A8, what int8
serving computes; the router stays in float32 there as well), the
nearest precision below the configuration's bfloat16.

Beside 10 GB of bf16 weights a chip cannot hold a layer in float32
(5 GB): every matrix is upcast where it is used, one at a time, and the
attention runs eight heads at a time.
"""

from __future__ import annotations

import functools
import math

import numpy as np

# sizes a jitted piece is specialised on (hashable)
_KEYS = ("dim", "n_head", "q_rank", "kv_rank", "nope", "rope", "v_dim",
         "n_routed", "n_zero", "top_k", "scale", "eps", "theta", "held")


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
    return dict(
        n_layer=int(config["num_layers"]), dim=int(config["hidden_size"]),
        n_head=int(config["num_attention_heads"]),
        q_rank=int(config["q_lora_rank"]),
        kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]),
        rope=int(config["qk_rope_head_dim"]),
        v_dim=int(config["v_head_dim"]),
        ffn=int(config["ffn_hidden_size"]),
        expert_ffn=int(config["expert_ffn_hidden_size"]),
        n_routed=n_routed, n_zero=int(config["zero_expert_num"]),
        top_k=int(config["moe_topk"]),
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
    for the embedding, the head and the final norm, and one a layer (the
    same program for every layer, so that no more than one layer's
    float32 draws exist at a time).  Matrices and embeddings
    N(0, init_std), norms at 1, the selection bias at 0."""
    import jax
    import jax.numpy as jnp

    d, v, std = sizes["dim"], sizes["vocab"], sizes["init_std"]
    h = sizes["n_head"]
    g = sizes["held"][1] - sizes["held"][0]
    n_out = sizes["n_routed"] + sizes["n_zero"]

    def normal(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    def ones(n):
        return {"weight": jnp.ones((n,), dtype)}

    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": {"weight": normal(k[0], (v, d))},
                "norm_f": ones(d),
                "head": {"weight": normal(k[1], (v, d))}}

    def layer(key):
        keys = jax.random.split(key, 5)
        out = {}
        for j in (0, 1):
            k = jax.random.split(keys[j], 8)
            out[f"norm_attn{j}"] = ones(d)
            out[f"norm_mlp{j}"] = ones(d)
            out[f"attn{j}"] = {
                "wq_a": normal(k[0], (sizes["q_rank"], d)),
                "q_norm": jnp.ones((sizes["q_rank"],), dtype),
                "wq_b": normal(k[1], (h * (sizes["nope"] + sizes["rope"]),
                                      sizes["q_rank"])),
                "wkv_a": normal(k[2], (sizes["kv_rank"] + sizes["rope"], d)),
                "kv_norm": jnp.ones((sizes["kv_rank"],), dtype),
                "wkv_b": normal(k[3], (h * (sizes["nope"] + sizes["v_dim"]),
                                       sizes["kv_rank"])),
                "wo": normal(k[4], (d, h * sizes["v_dim"]))}
            out[f"mlp{j}"] = {"gate": normal(k[5], (sizes["ffn"], d)),
                              "up": normal(k[6], (sizes["ffn"], d)),
                              "down": normal(k[7], (d, sizes["ffn"]))}
        k = jax.random.split(keys[2], 4)
        fe = sizes["expert_ffn"]
        out["moe"] = {"router": normal(k[0], (n_out, d)),
                      "bias": jnp.zeros((n_out,), jnp.float32),
                      "w_gate": normal(k[1], (g, d, fe)),
                      "w_up": normal(k[2], (g, d, fe)),
                      "w_down": normal(k[3], (g, fe, d))}
        return out

    # a seed may exceed 32 signed bits: fold it into the key in two
    # halves; the rbg generator is the chip's own and several times
    # faster than threefry over 5e9 draws
    seed = int(seed)
    key = jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)
    keys = jax.random.split(key, sizes["n_layer"] + 1)
    tree = jax.jit(ends)(keys[0])
    make_layer = jax.jit(layer)
    for i in range(sizes["n_layer"]):
        tree[f"l{i}"] = make_layer(keys[1 + i])
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
    float32 ``highest``, or the same in W8A8 (weights a output channel,
    inputs a row)."""
    import jax.numpy as jnp

    w = _f32(w)
    if precision == "int8":
        w = _round8(w, axis=1 if out_in else 0)
        x = _round8(x, axis=-1)
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


def _mlp(p, x, precision):
    import jax

    h = jax.nn.silu(_matmul(x, p["gate"], precision)) \
        * _matmul(x, p["up"], precision)
    return _matmul(h, p["down"], precision)


def _attention(p, x, s: dict, precision):
    """Latent attention over one sequence ``x`` (T, D), K and V rebuilt
    per head, eight heads at a time."""
    import jax
    import jax.numpy as jnp

    t, d = x.shape
    h, nope, rope, vd = s["n_head"], s["nope"], s["rope"], s["v_dim"]
    rkv = s["kv_rank"]
    pos = jnp.arange(t)
    c_q = _rms(_matmul(x, p["wq_a"], precision), p["q_norm"], s["eps"])
    q = math.sqrt(d / s["q_rank"]) * _matmul(c_q, p["wq_b"], precision)
    q = q.reshape(t, h, nope + rope).transpose(1, 0, 2)      # (H, T, 192)
    kv = _matmul(x, p["wkv_a"], precision)
    c = math.sqrt(d / rkv) * _rms(kv[:, :rkv], p["kv_norm"], s["eps"])
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


def _experts(p, x, s: dict, precision):
    """The expert layer's share for the held experts ``s["held"]``: a
    loop over them, each over every token, weighted by the router."""
    import jax
    import jax.numpy as jnp

    lo, hi = s["held"]
    logits = jnp.matmul(x, _f32(p["router"]).T, precision="highest")
    sc = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(sc + _f32(p["bias"]), s["top_k"])
    w = s["scale"] * jnp.take_along_axis(sc, idx, axis=-1)   # (T, k)
    # zero-compute experts: the identity, weighted
    y = x * jnp.sum(jnp.where(idx >= s["n_routed"], w, 0.0), axis=-1,
                    keepdims=True)

    def one_expert(g, y):
        def of(name):
            return jax.lax.dynamic_index_in_dim(p[name], g, keepdims=False)

        w_e = jnp.sum(jnp.where(idx == lo + g, w, 0.0), axis=-1,
                      keepdims=True)
        hmid = jax.nn.silu(_matmul(x, of("w_gate"), precision, False)) \
            * _matmul(x, of("w_up"), precision, False)
        return y + w_e * _matmul(hmid, of("w_down"), precision, False)

    # a loop (not unrolled: sixteen experts' float32 products take a
    # minute to compile at the published widths)
    return jax.lax.fori_loop(0, hi - lo, one_expert, y)


@functools.lru_cache(maxsize=None)
def _piece(name: str, key: tuple, precision: str):
    """One jitted piece of a layer at these sizes: a layer never exists
    in float32 as a whole."""
    import jax

    s = dict(zip(_KEYS, key))
    if name == "attn":
        return jax.jit(lambda p, nw, x: x + _attention(
            p, _rms(x, nw, s["eps"]), s, precision))
    if name == "norm":
        return jax.jit(lambda nw, x: _rms(x, nw, s["eps"]))
    if name == "mlp":
        return jax.jit(lambda p, x: _mlp(p, x, precision))
    if name == "moe":
        return jax.jit(lambda p, x: _experts(p, x, s, precision))
    raise KeyError(name)


def layer_forward(p, sizes: dict, h, precision: str = "float32"):
    """One double layer over one sequence ``h`` (T, D), float32."""
    key = _key(sizes)

    def piece(name):
        return _piece(name, key, precision)

    a = piece("attn")(p["attn0"], p["norm_attn0"]["weight"], h)
    u = piece("norm")(p["norm_mlp0"]["weight"], a)
    m = piece("moe")(p["moe"], u)
    h1 = a + piece("mlp")(p["mlp0"], u)
    a1 = piece("attn")(p["attn1"], p["norm_attn1"]["weight"], h1)
    return a1 + piece("mlp")(
        p["mlp1"], piece("norm")(p["norm_mlp1"]["weight"], a1)) + m


def expert_layer(p, sizes: dict, x, precision: str = "float32"):
    """The expert layer alone, ``x`` (T, D) -> (T, D): the share of
    ``sizes["held"]`` (for the tests of the share)."""
    import jax.numpy as jnp

    return _piece("moe", _key(sizes), precision)(
        p, jnp.asarray(x, jnp.float32))


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


def forward_hidden(params, sizes: dict, tokens, precision: str = "float32"):
    """Final hidden states (T, D), float32, of one sequence, a piece of
    a layer at a time.  The sequence is padded to a multiple of 128
    (causal attention keeps the real prefix exact, and no token's expert
    result depends on another token) to bound the number of compiled
    shapes."""
    import jax.numpy as jnp

    tokens = np.asarray(tokens, np.int32)
    t = len(tokens)
    tp = min(_pad_to(t), max(_pad_to(sizes["max_len"]), t))
    padded = np.zeros((tp,), np.int32)
    padded[:t] = tokens
    x = _f32(jnp.take(params["embed"]["weight"], jnp.asarray(padded),
                      axis=0))
    for i in range(sizes["n_layer"]):
        x = layer_forward(params[f"l{i}"], sizes, x, precision)
    return x[:t]


def forward_logits(params, sizes: dict, tokens,
                   precision: str = "float32"):
    """Logits (T, V), float32, at every position of one sequence."""
    import jax.numpy as jnp

    x = forward_hidden(params, sizes, tokens, precision)
    logits, _, _ = _head_fn(sizes["eps"], precision)(
        params["norm_f"]["weight"], params["head"]["weight"], x,
        jnp.zeros((x.shape[0],), jnp.int32))
    return logits


def served_gaps(params, sizes: dict, prompt, served,
                precision: str = "float32", score=None):
    """For one finished request: at each served position, how far the
    served token's logit lies below the reference's best (0 where the
    reference would have served the same token).  Also returns the
    tokens this forward puts first at those positions.  ``score`` gives
    other tokens to read the gap of, at the same positions of the same
    prompt and served tokens (the control: what a lower precision put
    first)."""
    import jax.numpy as jnp

    prompt = [int(t) for t in prompt]
    served = [int(t) for t in served]
    tokens = prompt + served
    x = forward_hidden(params, sizes, tokens[:-1], precision)
    # position len(prompt) - 1 + j predicts served[j]
    x = x[len(prompt) - 1:]
    n = len(served)
    npad = _pad_to(n)
    xp = jnp.zeros((npad, x.shape[1]), jnp.float32).at[:n].set(x)
    sp = np.zeros((npad,), np.int32)
    sp[:n] = served if score is None else score
    _, gaps, first = _head_fn(sizes["eps"], precision)(
        params["norm_f"]["weight"], params["head"]["weight"], xp,
        jnp.asarray(sp))
    return np.asarray(gaps)[:n], np.asarray(first)[:n]
