"""Plain reference for the ``zaya1_8b`` configuration: ZAYA1's layer
(attention inside a compressed latent with two causal convolutions and
a shifted value, then top-1 experts chosen by an MLP router that
averages over depth, both under a scaled residual) as straightforward
``jax.numpy`` in float32 with matmul precision ``highest``.  A whole
sequence at once: the convolutions and the value shift are shifts along
the sequence, the attention a ``(T, T)`` causal plane.  No cache, no
state carried from step to step, no batching, no sorting, no kernels.
It imports nothing of the program.

Source: ``huggingface.co/Zyphra/ZAYA1-8B`` ``config.json``
(``model_type`` ``zaya``).  That file fixes the widths.  What it does
not fix is marked *(assumed)*: from the two public papers (CCA,
arXiv:2510.04476; the ZAYA1 technical report, arXiv:2511.17127) and the
family's published modelling code, from memory, unverified here (there
is no network).

One layer at position ``t`` (``D`` hidden, ``H`` query heads over ``G``
key/value heads of ``d``, ``g(h) = h // (H / G)``; a quantity at
``t-1`` is that of the sequence's previous position and 0 before the
first):

    a_t  = rms(x_t; g1)
    z_t  = W_qk a_t = [qd_t ; kd_t]          (H + G) heads of d
    v_t  = [W_v a_t's first half ; W_v a_{t-1}'s second half]
           the value shift: the first G/2 key/value heads from this
           token, the others from the previous *(assumed: halves =
           heads, in this order)*
    c_t  = u1 * z_t + u0 * z_{t-1} + b_u      depthwise causal
           convolution, kernel ``cca_time0`` = 2
    e_t[h] = U1_h c_t[h] + U0_h c_{t-1}[h] + b_U[h]
           causal convolution, kernel ``cca_time1`` = 2, grouped: one
           d x d matrix a tap and head *(assumed: groups = the H + G
           heads; biases present)*
    m_q[h] = (qd_t[h] + kd_t[g(h)]) / 2       the query-key mean of the
    m_k[j] = (mean over h in j of qd_t[h] + kd_t[j]) / 2    PRE-conv rows
    q[h] = l2(e_t^q[h] + m_q[h])      k[j] = tau_j l2(e_t^k[j] + m_k[j])
           ``l2(x) = sqrt(d) x / |x|`` (written ``x / sqrt(mean(x^2) +
           eps)``); tau a learned temperature a key head *(assumed: on
           the key side, one scalar a head)*
    q, k = rotary by halves on the first ``partial_rotary_factor * d``
           values of each head, at t
    o[h] = softmax over j <= t of (q[h] . k_j[g(h)] / sqrt(d)) v_j[g(h)]
    x = (s_r * x + b_r) + (s_o * W_o [o_0 .. o_{H-1}] + b_o)
           the residual scaling: learned vectors of D on the stream and
           on the sublayer's output *(assumed in this form)*
    b_t = rms(x; g2)
    r^l = W_d b_t + gamma_l * r^{l-1}         ``router_hidden_size``
           wide; depth averaging: the SAME token's router row of the
           layer below, 0 under the first *(assumed: a learned vector)*
    s = softmax(W_3 gelu(W_2 gelu(W_1 rms(r^l; g_r))))   in float32
           *(assumed: three matrices, exact GELU)*
    e* = argmax(s + bias)                     the balancing buffer picks
    x = (s_r' * x + b_r') + (s_o' * s_{e*} E_{e*}(b_t) + b_o')
           top-1; the weight is s_{e*} itself; E a gated SiLU MLP

then ``rms(x; g_f)`` and the tied head (the embedding's matrix).

**A chip's share**: ``sizes["held"] = (lo, hi)`` — a token's result from
the expert layer is its chosen expert's if that expert is held and 0
otherwise, here exactly as in the program.

The weights' tree (the program's model takes the same tree, so the
benchmark hands it over unchanged; ``y = x @ w.T`` unless said):

    embed.weight (V, D)   norm_f.weight (D,)
    l<i>.norm_attn.weight, l<i>.norm_mlp.weight (D,)
    l<i>.attn.{w_qk ((H+G)*d, D), w_v (G*d, D), w_o (D, H*d),
               conv0_w (2, (H+G)*d), conv0_b ((H+G)*d,)   tap 0: t-1
               conv1_w (2, H+G, d, d) [tap, head, out, in],
               conv1_b (H+G, d), tau (G,)}
    l<i>.res_attn.*, l<i>.res_moe.*: {stream_scale, stream_bias,
               out_scale, out_bias} (D,)
    l<i>.router.{down (R, D), gamma (R,), norm (R,), w1 (R, R),
               w2 (R, R), w3 (E, R), bias (E,) float32}
    l<i>.moe.{w_gate (held, D, F), w_up (held, D, F), w_down (held, F, D)}
               y = x @ w[g]

**Seeded weights must not make a mechanism vanish** (a tap at 0, a
temperature or a scale at 1 would let a program without it pass):
matrices and embeddings are N(0, ``initializer_range``); ``tau`` is
uniform in [0.75, 1.5], ``gamma`` in [0.3, 0.8], a stream scale in
[0.8, 1.2], an output scale in
[0.6, 1.4], the residual's biases in [-0.02, 0.02], the convolutions'
in [-0.1, 0.1], ``u1`` in [0.6, 1.4], ``u0`` in [0.3, 0.7] with a
random sign, ``U1`` N(0, 1/sqrt(d)) and ``U0`` half of that (so the
convolved rows and the query-key mean are of one size), the selection
bias in [-0.03, 0.03]; the router's three matrices are N(0,
1.5/sqrt(R)), which makes its softmax peaked (the chosen expert's
weight is mostly 0.15 to 0.5, not 1/16: the expert layer is a sizeable
part of the stream, and a near tie flips a real weight).  Norm gains
are 1.  **Nor may they make the ROUTING collapse**: what every token's
stream has in common (the residual's biases, and the hidden units'
common mean inside the router's MLP) makes the router prefer the same
expert for every token.  With residual biases of 0.1 and plain router
matrices, 7 to 13 of a layer's 16 experts got any of a decode step's
256 tokens and one took up to 13 in 16, differently on every seed
(measured through this file at the published widths, tokens of many
sequences).  So the residual's biases are small and each row of the
router's three matrices has its mean taken out: 15.4 to 16 of the 16
experts of every layer get one of 256 tokens, the fullest two to three
times its share.  (A temperature of 3 to 6, attention that picks a few
rows, balances the routing as well, and makes the whole network so
sensitive that bfloat16 and float32 agree on hardly a token: measured,
not taken.)

``precision="int8"`` is the control of "How correct is decided": the
same forward with every weight matrix rounded to int8 per output
channel and every such product's input rounded to int8 per row (W8A8;
the router stays in float32 there as well), the nearest precision below
the configuration's bfloat16.  ``without=`` leaves ONE part of the
mathematics out (:data:`PARTS`): a program that lacks it agrees with
that forward and not with this one.

Beside 5 GB of bf16 weights every matrix is upcast where it is used,
one at a time; the head runs in blocks of positions.
"""

from __future__ import annotations

import functools
import math

import numpy as np

#: what ``without=`` can leave out: the value shift (every key/value
#: head's value from this token), the query-key mean, the key
#: temperature, the second convolution, the router's carry from the
#: layer below, and the previous position's rows at ONE position,
#: ``boundary`` (what a decode step sees whose slot state was never
#: handed over by the prefill)
PARTS = ("value_shift", "qk_mean", "temperature", "conv1", "depth_carry",
         "state_boundary")

# sizes a jitted piece is specialised on (hashable)
_KEYS = ("dim", "n_head", "kv_heads", "head_dim", "rot", "theta",
         "n_experts", "eps", "held")


def sizes_of(config: dict) -> dict:
    """The reference's sizes from a configuration file in the published
    ``config.json`` spelling.  The file's own keys: ``held_experts``
    ([lo, hi), default all) and ``max_len``."""
    n = int(config["num_hidden_layers"])
    kinds = config.get("layer_types", ["hybrid"] * n)
    if len(kinds) != n or set(kinds) != {"hybrid"}:
        raise ValueError(f"{n} layers, all of them 'hybrid', are what is "
                         f"written down here; layer_types has {kinds}")
    if (int(config.get("cca_time0", 2)), int(config.get("cca_time1", 2))) \
            != (2, 2) or int(config["num_experts_per_tok"]) != 1:
        raise ValueError("convolutions of kernel 2 and top-1 experts are "
                         "what is written down here")
    if config.get("sliding_window") is not None:
        raise ValueError("no window is written down here")
    rope = config.get("rope_parameters", {}).get("hybrid", {})
    heads, kv = int(config["num_attention_heads"]), \
        int(config["num_key_value_heads"])
    if heads % kv or kv % 2:
        raise ValueError(f"{heads} query heads over {kv} key/value heads: "
                         "the value shift halves the key/value heads")
    d = int(config["head_dim"])
    n_experts = int(config["num_experts"])
    held = config.get("held_experts", [0, n_experts])
    return dict(
        n_layer=n, dim=int(config["hidden_size"]), n_head=heads,
        kv_heads=kv, head_dim=d,
        rot=int(d * float(rope.get("partial_rotary_factor", config.get(
            "partial_rotary_factor", 1.0)))),
        theta=float(rope.get("rope_theta", config.get("rope_theta", 1e4))),
        expert_ffn=int(config["moe_intermediate_size"]),
        n_experts=n_experts, router_hidden=int(config["router_hidden_size"]),
        eps=float(config["rms_norm_eps"]), vocab=int(config["vocab_size"]),
        max_len=int(config.get("max_len", config.get(
            "max_position_embeddings", 2048))),
        held=(int(held[0]), int(held[1])),
        init_std=float(config.get("initializer_range", 0.02)))


def _key(sizes: dict) -> tuple:
    return tuple(sizes[k] for k in _KEYS)


_INIT_KEYS = ("dim", "vocab", "init_std", "n_head", "kv_heads", "head_dim",
              "router_hidden", "n_experts", "expert_ffn", "held")


@functools.lru_cache(maxsize=None)
def _init_fns(key: tuple, dtype_name: str):
    """The two jitted programs that draw the weights at these sizes:
    the embedding with the final norm, and one layer."""
    import jax
    import jax.numpy as jnp

    s = dict(zip(_INIT_KEYS, key))
    dtype = jnp.dtype(dtype_name)
    dm, v, std = s["dim"], s["vocab"], s["init_std"]
    h, g, d = s["n_head"], s["kv_heads"], s["head_dim"]
    c, r, e = (h + g) * d, s["router_hidden"], s["n_experts"]
    f, held = s["expert_ffn"], s["held"][1] - s["held"][0]

    def normal(k, shape, scale=std):
        return (scale * jax.random.normal(k, shape, jnp.float32)) \
            .astype(dtype)

    def uniform(k, shape, lo, hi, out=dtype):
        return jax.random.uniform(k, shape, jnp.float32, lo, hi).astype(out)

    def ones(n):
        return {"weight": jnp.ones((n,), dtype)}

    def centred(k, shape, scale):
        w = scale * jax.random.normal(k, shape, jnp.float32)
        return (w - jnp.mean(w, axis=1, keepdims=True)).astype(dtype)

    def ends(key):
        return {"embed": {"weight": normal(key, (v, dm))},
                "norm_f": ones(dm)}

    def residual(key):
        k = jax.random.split(key, 4)
        return {"stream_scale": uniform(k[0], (dm,), 0.8, 1.2),
                "stream_bias": uniform(k[1], (dm,), -0.02, 0.02),
                "out_scale": uniform(k[2], (dm,), 0.6, 1.4),
                "out_bias": uniform(k[3], (dm,), -0.02, 0.02)}

    def layer(key):
        k = jax.random.split(key, 24)
        sign = jnp.where(jax.random.bernoulli(k[5], 0.5, (c,)), 1.0, -1.0)
        u0 = jax.random.uniform(k[4], (c,), jnp.float32, 0.3, 0.7) * sign
        u1 = jax.random.uniform(k[6], (c,), jnp.float32, 0.6, 1.4)
        big = 1.0 / math.sqrt(d)
        taps = jnp.stack([normal(k[8], (h + g, d, d), 0.5 * big),
                          normal(k[9], (h + g, d, d), big)])
        wide = 1.5 / math.sqrt(r)
        return {
            "norm_attn": ones(dm), "norm_mlp": ones(dm),
            "attn": {"w_qk": normal(k[0], (c, dm)),
                     "w_v": normal(k[1], (g * d, dm)),
                     "w_o": normal(k[2], (dm, h * d)),
                     "conv0_w": jnp.stack([u0, u1]).astype(dtype),
                     "conv0_b": uniform(k[7], (c,), -0.1, 0.1),
                     "conv1_w": taps,
                     "conv1_b": uniform(k[10], (h + g, d), -0.1, 0.1),
                     "tau": uniform(k[3], (g,), 0.75, 1.5)},
            "res_attn": residual(k[11]), "res_moe": residual(k[12]),
            "router": {"down": normal(k[13], (r, dm)),
                       "gamma": uniform(k[14], (r,), 0.3, 0.8),
                       "norm": jnp.ones((r,), dtype),
                       "w1": centred(k[15], (r, r), wide),
                       "w2": centred(k[16], (r, r), wide),
                       "w3": centred(k[17], (e, r), wide),
                       "bias": uniform(k[18], (e,), -0.03, 0.03,
                                       jnp.float32)},
            "moe": {"w_gate": normal(k[19], (held, dm, f)),
                    "w_up": normal(k[20], (held, dm, f)),
                    "w_down": normal(k[21], (held, f, dm))}}

    return jax.jit(ends), jax.jit(layer)


def init_params(seed: int, sizes: dict, dtype):
    """All weights from ``seed`` on the default device: one jitted call
    for the embedding and the final norm, and one a layer (the same
    program for every layer, so that no more than one layer's float32
    draws exist at a time).  How each is drawn: module docstring."""
    import jax
    import jax.numpy as jnp

    ends, layer = _init_fns(tuple(sizes[k] for k in _INIT_KEYS),
                            jnp.dtype(dtype).name)
    # a seed may exceed 32 signed bits: fold it into the key in two
    # halves; the rbg generator is the chip's own and several times
    # faster than threefry over 2.6e9 draws
    seed = int(seed)
    key = jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)
    keys = jax.random.split(key, sizes["n_layer"] + 1)
    tree = ends(keys[0])
    for i in range(sizes["n_layer"]):
        tree[f"l{i}"] = layer(keys[1 + i])
    return tree


# ------------------------------------------------------------- the pieces
def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps)
    return y if w is None else y * _f32(w)


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


def _rotary_halves(x, positions, theta):
    """The pairs ``(x[i], x[i + n/2])`` of the last axis (``n`` wide)
    rotated at ``positions`` (T,); ``x`` is (T, heads, n)."""
    import jax.numpy as jnp

    n = x.shape[-1]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = positions[:, None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :n // 2], x[..., n // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _before(a, keep):
    """``a`` (T, ...) one position earlier: row ``t`` holds ``a[t-1]``,
    zeros at the first position and wherever ``keep`` (T,) is 0."""
    import jax.numpy as jnp

    prev = jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]], axis=0)
    return prev * keep.reshape((-1,) + (1,) * (a.ndim - 1))


def _attention(p, a, s: dict, precision, without, keep):
    """CCA over one normalised sequence ``a`` (T, D) -> (T, D)."""
    import jax
    import jax.numpy as jnp

    t = a.shape[0]
    h, g, d, rot = s["n_head"], s["kv_heads"], s["head_dim"], s["rot"]
    per = h // g
    z = _matmul(a, p["w_qk"], precision)                     # (T, C)
    v = _matmul(a, p["w_v"], precision)                      # (T, G*d)
    half = (g // 2) * d
    late = v[:, half:]
    v = jnp.concatenate(
        [v[:, :half],
         late if without == "value_shift" else _before(late, keep)], axis=-1)
    u = _f32(p["conv0_w"])
    c = u[1] * z + u[0] * _before(z, keep) + _f32(p["conv0_b"])
    ch = c.reshape(t, h + g, d)
    if without == "conv1":
        e = ch
    else:
        w = _f32(p["conv1_w"])                               # (2, hd, o, i)
        prev = _before(ch, keep)
        if precision == "int8":
            w, ch, prev = _round8(w, -1), _round8(ch, -1), _round8(prev, -1)
        e = jnp.einsum("thi,hoi->tho", ch, w[1], precision="highest") \
            + jnp.einsum("thi,hoi->tho", prev, w[0], precision="highest") \
            + _f32(p["conv1_b"])
    zh = z.reshape(t, h + g, d)
    qd, kd = zh[:, :h], zh[:, h:]
    q, k = e[:, :h], e[:, h:]
    if without != "qk_mean":
        q = q + (qd + jnp.repeat(kd, per, axis=1)) / 2.0
        k = k + (jnp.mean(qd.reshape(t, g, per, d), axis=2) + kd) / 2.0
    tau = 1.0 if without == "temperature" else _f32(p["tau"])[:, None]
    q = _rms(q, None, s["eps"])
    k = tau * _rms(k, None, s["eps"])
    pos = jnp.arange(t)

    def rotate(x):
        return jnp.concatenate(
            [_rotary_halves(x[..., :rot], pos, s["theta"]), x[..., rot:]],
            axis=-1)

    q, k = rotate(q), rotate(k)
    scores = jnp.einsum("tjrd,ujd->jrtu", q.reshape(t, g, per, d), k,
                        precision="highest") / math.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("jrtu,ujd->tjrd", probs, v.reshape(t, g, d),
                   precision="highest").reshape(t, h * d)
    return _matmul(o, p["w_o"], precision)


def _router(p, b, carry, s: dict, without):
    """The MLP router over ``b`` (T, D), float32 whatever the
    precision: the chosen expert (T,), its weight (T,) and this layer's
    router row (T, R), the next layer's ``carry``."""
    import jax
    import jax.numpy as jnp

    def mm(x, w):
        return jnp.matmul(x, _f32(w).T, precision="highest")

    r = mm(b, p["down"])
    if without != "depth_carry":
        r = r + _f32(p["gamma"]) * carry
    hid = _rms(r, p["norm"], s["eps"])
    hid = jax.nn.gelu(mm(hid, p["w1"]), approximate=False)
    hid = jax.nn.gelu(mm(hid, p["w2"]), approximate=False)
    sc = jax.nn.softmax(mm(hid, p["w3"]), axis=-1)
    idx = jnp.argmax(sc + _f32(p["bias"]), axis=-1)
    return idx, jnp.take_along_axis(sc, idx[:, None], axis=-1)[:, 0], r


def _experts(p, x, idx, w, s: dict, precision):
    """The expert layer's share for the held experts ``s["held"]``: a
    loop over them, each over every token, weighted where chosen."""
    import jax
    import jax.numpy as jnp

    lo, hi = s["held"]

    def one_expert(g, y):
        def of(name):
            return jax.lax.dynamic_index_in_dim(p[name], g, keepdims=False)

        w_e = jnp.where(idx == lo + g, w, 0.0)[:, None]
        hmid = jax.nn.silu(_matmul(x, of("w_gate"), precision, False)) \
            * _matmul(x, of("w_up"), precision, False)
        return y + w_e * _matmul(hmid, of("w_down"), precision, False)

    # a loop (not unrolled: sixteen experts' float32 products take long
    # to compile at the published widths)
    return jax.lax.fori_loop(0, hi - lo, one_expert, jnp.zeros_like(x))


def _scaled(res, x, y):
    return (_f32(res["stream_scale"]) * x + _f32(res["stream_bias"])) \
        + (_f32(res["out_scale"]) * y + _f32(res["out_bias"]))


@functools.lru_cache(maxsize=None)
def _piece(name: str, key: tuple, precision: str, without):
    """One jitted piece of a layer at these sizes."""
    import jax

    s = dict(zip(_KEYS, key))
    if name == "attn":
        return jax.jit(lambda p, nw, res, x, keep: _scaled(
            res, x, _attention(p, _rms(x, nw, s["eps"]), s, precision,
                               without, keep)))
    if name == "moe":
        def moe(pr, pm, nw, res, x, carry):
            b = _rms(x, nw, s["eps"])
            idx, w, r = _router(pr, b, carry, s, without)
            return _scaled(res, x, _experts(pm, b, idx, w, s, precision)), r

        return jax.jit(moe)
    raise KeyError(name)


def layer_forward(p, sizes: dict, x, carry, keep,
                  precision: str = "float32", without=None):
    """One layer over one sequence ``x`` (T, D), float32, with the
    router row ``carry`` (T, R) of the layer below -> ``(x', carry')``."""
    key = _key(sizes)
    x = _piece("attn", key, precision, without)(
        p["attn"], p["norm_attn"]["weight"], p["res_attn"], x, keep)
    return _piece("moe", key, precision, without)(
        p["router"], p["moe"], p["norm_mlp"]["weight"], p["res_moe"], x,
        carry)


def expert_layer(p, sizes: dict, x, carry=None, precision: str = "float32"):
    """Router and expert layer alone over normalised rows ``x`` (T, D)
    -> (T, D): the share of ``sizes["held"]`` (for the tests of the
    share).  ``p`` holds ``router`` and ``moe``."""
    import jax.numpy as jnp

    x = jnp.asarray(x, jnp.float32)
    if carry is None:
        carry = jnp.zeros((x.shape[0], p["router"]["down"].shape[0]),
                          jnp.float32)
    idx, w, _ = _router(p["router"], x, carry, sizes, None)
    return _experts(p["moe"], x, idx, w, sizes, precision)


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
    sequence's pieces take seconds each to compile, so few lengths
    (one, 2048, for the requests of a long-generation mix)."""
    step = 1024 if n > 512 and step == 128 else step
    return -(-n // step) * step


def forward_hidden(params, sizes: dict, tokens, precision: str = "float32",
                   without=None, boundary=None):
    """Final hidden states (T, D), float32, of one sequence, a piece of
    a layer at a time.  The sequence is padded to a multiple of 128
    (everything looks back only, and no token's expert result depends
    on another token) to bound the number of compiled shapes.
    ``boundary`` is the position ``without="state_boundary"`` cuts
    at."""
    import jax.numpy as jnp

    if without is not None and without not in PARTS:
        raise ValueError(f"without={without!r}: one of {PARTS}")
    tokens = np.asarray(tokens, np.int32)
    t = len(tokens)
    tp = min(_pad_to(t), max(_pad_to(sizes["max_len"]), t))
    padded = np.zeros((tp,), np.int32)
    padded[:t] = tokens
    keep = np.ones((tp,), np.float32)
    if without == "state_boundary" and int(boundary) < tp:
        keep[int(boundary)] = 0.0
    keep = jnp.asarray(keep)
    x = _f32(jnp.take(params["embed"]["weight"], jnp.asarray(padded),
                      axis=0))
    carry = jnp.zeros((tp, params["l0"]["router"]["down"].shape[0]),
                      jnp.float32)
    for i in range(sizes["n_layer"]):
        x, carry = layer_forward(params[f"l{i}"], sizes, x, carry, keep,
                                 precision, without)
    return x[:t]


#: positions a call of the head: 256 x 262272 float32 logits are 0.27 GB
_HEAD_BLOCK = 256


def _head_blocks(params, sizes, x, served, precision):
    """The head over ``x`` (N, D) in blocks of positions: the gaps and
    first choices, and the logits' blocks as they come (an iterator)."""
    import jax.numpy as jnp

    head = _head_fn(sizes["eps"], precision)
    n = x.shape[0]
    for lo in range(0, n, _HEAD_BLOCK):
        m = min(_HEAD_BLOCK, n - lo)
        xp = jnp.zeros((_HEAD_BLOCK, x.shape[1]), jnp.float32) \
            .at[:m].set(x[lo:lo + m])
        sp = np.zeros((_HEAD_BLOCK,), np.int32)
        sp[:m] = served[lo:lo + m]
        logits, gaps, first = head(params["norm_f"]["weight"],
                                   params["embed"]["weight"], xp,
                                   jnp.asarray(sp))
        yield logits[:m], np.asarray(gaps)[:m], np.asarray(first)[:m]


def forward_logits(params, sizes: dict, tokens, precision: str = "float32",
                   without=None, boundary=None):
    """Logits (T, V), float32, at every position of one sequence."""
    import jax.numpy as jnp

    x = forward_hidden(params, sizes, tokens, precision, without, boundary)
    zeros = np.zeros((x.shape[0],), np.int32)
    return jnp.concatenate(
        [b[0] for b in _head_blocks(params, sizes, x, zeros, precision)])


def served_gaps(params, sizes: dict, prompt, served,
                precision: str = "float32", score=None, without=None):
    """For one finished request: at each served position, how far the
    served token's logit lies below the reference's best (0 where the
    reference would have served the same token).  Also returns the
    tokens this forward puts first at those positions.  ``score`` gives
    other tokens to read the gap of, at the same positions of the same
    prompt and served tokens (the control: what a lower precision put
    first).  ``without="state_boundary"`` cuts at the first position a
    decode step computed, ``len(prompt)``."""
    prompt = [int(t) for t in prompt]
    served = [int(t) for t in served]
    tokens = prompt + served
    x = forward_hidden(params, sizes, tokens[:-1], precision, without,
                       boundary=len(prompt))
    # position len(prompt) - 1 + j predicts served[j]
    x = x[len(prompt) - 1:]
    gaps, first = [], []
    for _, g, f in _head_blocks(params, sizes, x,
                                served if score is None else score,
                                precision):
        gaps.append(g)      # the block's logits go as the next come
        first.append(f)
    return np.concatenate(gaps), np.concatenate(first)
