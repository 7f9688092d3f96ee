"""Plain reference for the ``ling_3_flash_vl`` configuration:
Ling-3.0-flash's language model (five layers of six a **delta-rule
linear attention with a decay a channel**, KDA; the sixth **latent
attention**; a gated MLP in the leading dense layers and elsewhere 512
experts **chosen by groups** beside one shared expert) as
straightforward ``jax.numpy`` in float32 with matmul precision
``highest``.  A whole sequence at once: the latent attention a ``(T,
T)`` causal plane with keys and values rebuilt per head, the KDA
recurrence a plain ``lax.scan`` over the tokens, one update of the state
a token (NOT the chunked form the program prefills with).  No cache, no
state carried from call to call, no batching, no kernels.  It imports
nothing of the program.

Source: ``huggingface.co/inclusionAI/Ling-3.0-flash-VL`` ``config.json``.
That file fixes the widths, the layer pattern and the router's numbers.
**Departures**: the vision tower and the MTP module are left out (text
traffic; the catalog's ``config`` gives the language model only and no
``num_nextn_predict_layers``).  What the file does not fix is marked
*(assumed)*: from the family's published kernels and modelling code,
from memory, unverified here (there is no network).

Every layer (``D`` hidden, ``H`` heads)::

    a  = x + Mix(rms(x; g_mix))
    x' = a + F(rms(a; g_mlp))

Layer ``i`` of the PUBLISHED model mixes by latent attention where ``(i
+ 1) % layer_group_size == 0`` and by KDA elsewhere (``config``: 35 KDA
and 7 latent layers of 42); ``kept_layers`` says which published layers
a cut in depth keeps.  ``F`` is a gated SiLU MLP of
``intermediate_size`` in the first ``first_k_dense_replace`` layers and
the expert layer in the others.

**KDA** at position ``t``, ``n`` the normed stream, ``d_k = d_v =
head_dim``, kernel ``K = short_conv_kernel_size``::

    [q ; k ; v ; f ; z ; b] = W_in n       H d_k, H d_k, H d_v, H d_k,
                                           H d_v, H  *(assumed: W_f and
           the output gate W_z full rank, no_kda_lora true; as many key
           heads as query heads, num_kv_heads_for_linear_attn 0)*
    [q ; k ; v]_t <- silu(sum_j w_j [q ; k ; v]_{t-K+1+j})
           depthwise causal convolution, zeros before the first
           position, no bias (linear_silu true: SiLU after it)
    q, k <- q / sqrt(|q|^2 + 1e-6), k / sqrt(|k|^2 + 1e-6) a head
    q <- q * d_k ** -0.5
    beta_h = sigmoid(b_h)
    g_h = kda_lower_bound * sigmoid(exp(a_log_h) * (f_h + dt_bias_h))
           a head AND channel, in [-5, 0] *(assumed: the gate's form
           under kda_safe_gate true, from the family's published
           kernel)*
    S_h <- Diag(exp(g_h)) S_h              S_h: d_k x d_v, float32
    S_h <- S_h + beta_h k_h (v_h - S_h^T k_h)^T
    o_h = S_h^T q_h
    y = W_out (rms([o_0 .. o_{H-1}]; gain) * sigmoid(z))
           *(assumed: group_norm_size 1 counts the GROUPS, as in the
           family's earlier linear-attention code, so the norm is ONE
           RMS over all H d_v outputs; a norm a head is the other
           reading)*

**Latent attention** (``q_lora_rank`` null: no low-rank query)::

    q = W_q n -> per head [q_nope (128) | q_rope (64)]
    [c_kv | k_rope] = W_kva n;  c = rms(c_kv; g_kv)
    rotary (interleaved pairs *(assumed)*, theta, no scaling) on q_rope
    and k_rope only (rotary_dim 64; partial_rotary_factor 0.5 says the
    same of the 128 + 64 query values' rope part *(assumed)*)
    per head h: [k_nope_h | v_h] = W_kvb,h c;  k_h = [k_nope_h | k_rope]
    o_h = softmax over s <= t of (q_h . k_h,s / sqrt(192)) v_h,s
    y = W_o [sigmoid(w_gate,h . n) o_h]_h   one gate a head (head_wise)
           *(assumed: use_qk_norm true is the latent's norm before W_kvb,
           as every MLA has; a head's query and rebuilt key get no norm
           of their own: a norm of the rebuilt key is not linear in c,
           and the absorbed decode every MLA serves with could not be
           formed)*

**Experts**::

    s = sigmoid(W_r u)                     float32, 512 outputs
    a group's score: the sum of its 2 largest (s + bias)   8 groups of 64
           *(assumed: as the family's earlier routers and DeepSeek-V3's)*
    keep the topk_group = 4 best groups; chosen = the 8 largest (s +
    bias) inside them
    w_e = 2.5 * s_e / (sum of the chosen s + 1e-20)
    M(u) = sum over chosen e of w_e E_e(u) + E_shared(u)

``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list`` are 0
on every kept layer; a non-zero limit is refused, not ignored.  A final
``rms`` and an untied head.

**A chip's share.**  ``held = (lo, hi)``: the routed experts whose
weights the tree holds.  The reference computes the same share as the
program: the chosen experts that are held, and the shared expert; what
the absent ones would add is left out.

The weights' tree (the program's model takes the same tree; ``y = x @
w.T`` except the experts' ``(group, in, out)``)::

    embed.weight (V, D)   head.weight (V, D)   norm_f.weight (D,)
    l<i>.norm_mix.weight, l<i>.norm_mlp.weight (D,)
    l<i>.kda.{w_in (3 H d + 2 H d + H, D), conv_w (K, 3 H d) tap K-1:
              this position, norm (H d,), w_out (D, H d),
              dt_bias (H d,), a_log (H,) float32 whatever the dtype}
    l<i>.attn.{wq (H 192, D), wkv_a (576, D), kv_norm (512,),
               wkv_b (H 256, 512), wo (D, H 128), w_gate (H, D)}
    l<i>.mlp.{gate (F, D), up (F, D), down (D, F)}
    l<i>.moe.{router (E, D), bias (E,) float32, w_gate (G, D, f),
              w_up (G, D, f), w_down (G, f, D), s_gate (f, D),
              s_up (f, D), s_down (D, f)}

**Seeded weights must leave no path dead.**  Every matrix is N(0, s /
sqrt(fan_in)), ``s`` the gain its product has over a unit-RMS input: 1
for what reads the normed stream (logits, gates and router outputs with
a spread of order 1), 2.5 for ``W_q`` (a query then attends a few dozen
rows, not a thousand alike), 0.5 for ``W_f``, 0.3 for what writes the
stream (``W_out``, ``W_o``, every ``down``).  A routed expert's ``down``
at 1 was tried, to keep this chip's one group of eight visible, and made
the model chaotic: a choice of experts is discontinuous, a near-tie
between the eighth and ninth score (or the fourth and fifth group)
flips on rounding, and at that gain one flip moved the stream by a
quarter of its size (the int8 control then flipped 80 % of the served
tokens, ``PERF.md`` section 6, PR 44); at 0.3 the router's group limit
left out still reads above the control.  The embedding is N(0, 0.1).  ``a_log`` is uniform in
[-0.5, 0.5] and ``dt_bias`` is set so that at ``f = 0`` a channel's
decay ``exp(g)`` is ``1 - u``, ``u`` log-uniform in [0.001, 0.1]
(decays from 0.9 to 0.999 a step; ``f`` moves ``u`` by about e either
way, nowhere near the bound); ``beta`` is ``sigmoid(N(0, 1))``; the
convolution's taps are uniform in +-[0.2, 0.6]; norm gains are 1, the
selection bias 0.  **The router's rows are drawn independent and then
taken off the direction every token shares**: a random network's stream
has a component common to all its tokens (the SiLU after the
convolution has a positive mean, so ``S`` gathers a running average: a
fifth of the normed stream's energy here), every router output would
carry the same offset for every token, and every token would have the
same favourites (378 of 512 experts hit by 256 tokens and one expert at
ten times the mean load, measured).  So :func:`init_params` runs
:data:`_CALIBRATION` random tokens through the layers as it goes and
subtracts from each expert layer's router rows their part along the
mean of that layer's input: every group and every held expert is hit,
with the bias at 0, as a trained router is balanced by its bias.

``precision="int8"`` is the control of "How correct is decided": the
same forward with every weight matrix rounded to int8 per output
channel and every such product's input rounded to int8 per row (W8A8;
the recurrence, the convolution, the router and the softmax stay
float32), the nearest precision below the configuration's bfloat16.
``without=`` leaves ONE part of the mathematics out (:data:`PARTS`): a
program that lacks it agrees with that forward and not with this one.
"""

from __future__ import annotations

import functools
import math

import numpy as np

#: what ``without=`` can leave out: the state's carry across ONE
#: position, ``boundary`` (``S`` and the convolution's rows zeroed there:
#: what a decode step sees whose slot state was never handed over by the
#: prefill); the delta term (``S^T k`` taken as 0: plain gated linear
#: attention); the decay a channel (each head's channels take their
#: mean); the router's group limit (the 8 best of all 512); the latent
#: attention's head-wise gate
PARTS = ("state_carry", "delta", "channel_decay", "group_limit", "mla_gate")

# sizes a jitted piece is specialised on (hashable)
_KEYS = ("dim", "n_head", "head_dim", "d_conv", "lower", "norm_groups",
         "kv_rank", "nope", "rope", "v_dim", "theta", "eps", "n_routed",
         "top_k", "scale", "n_group", "topk_group", "held")

_FIXED = dict(q_lora_rank=None, num_kv_heads_for_linear_attn=0,
              linear_silu=True, use_mla_nope=False, rotary_dim=64,
              partial_rotary_factor=0.5, use_qk_norm=True, use_nGPT=False,
              scale_router_input=False, value_norm=False,
              up_proj_norm=False, no_kda_lora=True, use_kda_lora=False,
              kda_safe_gate=True, moe_router_enable_expert_bias=True,
              gated_attention_proj_granularity_type="head_wise",
              score_function="sigmoid", norm_topk_prob=True)

_L2_EPS = 1e-6


def sizes_of(config: dict) -> dict:
    """The reference's sizes from a configuration file in the published
    ``config.json`` spelling.  The file's own keys: ``held_experts``
    ([lo, hi), default all), ``router_experts`` (the router's published
    width where ``num_experts`` counts the experts held),
    ``kept_layers`` (published indices, default all) and ``max_len``."""
    for k, want in _FIXED.items():
        if config.get(k, want) != want:
            raise ValueError(f"{k} = {config[k]!r}: {want!r} is what is "
                             "written down here")
    n_layer = int(config["num_hidden_layers"])
    kept = tuple(int(i) for i in config.get("kept_layers", range(n_layer)))
    if len(kept) != n_layer:
        raise ValueError("kept_layers names num_hidden_layers layers")
    for k in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        limits = config.get(k) or []
        if any(limits[i] for i in kept if i < len(limits)):
            raise ValueError(f"{k}: a clamp on a kept layer is not "
                             "written down here")
    n_routed = int(config.get("router_experts", config["num_experts"]))
    held = config.get("held_experts", [0, n_routed])
    if "router_experts" in config and \
            held[1] - held[0] != int(config["num_experts"]):
        raise ValueError("held_experts does not hold num_experts")
    group = int(config["layer_group_size"])
    return dict(
        n_layer=n_layer, kept=kept,
        latent=tuple((i + 1) % group == 0 for i in kept),
        n_dense=int(config["first_k_dense_replace"]),
        dim=int(config["hidden_size"]),
        n_head=int(config["num_attention_heads"]),
        head_dim=int(config["head_dim"]),
        d_conv=int(config["short_conv_kernel_size"]),
        lower=float(config["kda_lower_bound"]),
        norm_groups=int(config["group_norm_size"]),
        kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]),
        rope=int(config["qk_rope_head_dim"]),
        v_dim=int(config["v_head_dim"]),
        theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        ffn=int(config["intermediate_size"]),
        expert_ffn=int(config["moe_intermediate_size"]),
        shared_ffn=int(config["moe_shared_expert_intermediate_size"]),
        n_routed=n_routed, top_k=int(config["num_experts_per_tok"]),
        scale=float(config["routed_scaling_factor"]),
        n_group=int(config["n_group"]),
        topk_group=int(config["topk_group"]),
        held=(int(held[0]), int(held[1])),
        vocab=int(config["vocab_size"]),
        max_len=int(config.get("max_len", config.get(
            "max_position_embeddings", 2048))))


def _key(sizes: dict) -> tuple:
    return tuple(sizes[k] for k in _KEYS)


def zones_of(s: dict) -> tuple:
    """Widths of ``W_in``'s six zones: q, k, v, f, z, b."""
    inner = s["n_head"] * s["head_dim"]
    return (inner,) * 5 + (s["n_head"],)


_INIT_KEYS = _KEYS + ("vocab", "ffn", "expert_ffn", "shared_ffn")

#: the gains of the module docstring
_READS, _QUERY, _RATE, _WRITES = 1.0, 2.5, 0.5, 0.3
#: tokens :func:`init_params` runs to find the direction the routers'
#: inputs share
_CALIBRATION = 256


@functools.lru_cache(maxsize=None)
def _init_fns(key: tuple, dtype_name: str):
    """The jitted programs that draw the weights at these sizes: the
    embedding, the head and the final norm; a KDA mixer; a latent
    attention; a dense MLP; an expert layer."""
    import jax
    import jax.numpy as jnp

    s = dict(zip(_INIT_KEYS, key))
    dtype = jnp.dtype(dtype_name)
    dm, v, h, hd = s["dim"], s["vocab"], s["n_head"], s["head_dim"]
    inner, zones = h * hd, zones_of(s)
    held = s["held"][1] - s["held"][0]

    def matrix(k, shape, gain, fan_in=None):
        """N(0, gain / sqrt(fan_in)); ``fan_in`` defaults to the last
        axis (``(out, in)``)."""
        fan = shape[-1] if fan_in is None else fan_in
        return (jax.random.normal(k, shape, jnp.float32)
                * (gain / math.sqrt(fan))).astype(dtype)

    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": {"weight": (0.1 * jax.random.normal(
                    k[0], (v, dm), jnp.float32)).astype(dtype)},
                "head": {"weight": matrix(k[1], (v, dm), _READS)},
                "norm_f": {"weight": jnp.ones((dm,), dtype)}}

    def kda(key):
        k = jax.random.split(key, 8)
        gains = np.repeat(np.asarray(
            [_READS, _READS, _READS, _RATE, _READS, _READS], np.float32),
            zones)[:, None]
        w_in = jax.random.normal(k[0], (sum(zones), dm), jnp.float32) \
            * gains / math.sqrt(dm)
        sign = jnp.where(jax.random.bernoulli(
            k[2], 0.5, (s["d_conv"], 3 * inner)), 1.0, -1.0)
        taps = jax.random.uniform(k[1], (s["d_conv"], 3 * inner),
                                  jnp.float32, 0.2, 0.6) * sign
        a_log = jax.random.uniform(k[3], (h,), jnp.float32, -0.5, 0.5)
        u = jnp.exp(jax.random.uniform(
            k[4], (h, hd), jnp.float32, math.log(1e-3), math.log(1e-1)))
        # the gate's sigmoid at f = 0, and the bias that gives it
        want = -jnp.log1p(-u) / -s["lower"]
        dt_bias = (jnp.log(want) - jnp.log1p(-want)) \
            / jnp.exp(a_log)[:, None]
        return {"w_in": w_in.astype(dtype), "conv_w": taps.astype(dtype),
                "dt_bias": dt_bias.reshape(inner), "a_log": a_log,
                "norm": jnp.ones((inner,), dtype),
                "w_out": matrix(k[5], (dm, inner), _WRITES)}

    def attention(key):
        k = jax.random.split(key, 5)
        return {"wq": matrix(k[0], (h * (s["nope"] + s["rope"]), dm),
                             _QUERY),
                "wkv_a": matrix(k[1], (s["kv_rank"] + s["rope"], dm),
                                _READS),
                "kv_norm": jnp.ones((s["kv_rank"],), dtype),
                "wkv_b": matrix(k[2], (h * (s["nope"] + s["v_dim"]),
                                       s["kv_rank"]), _READS),
                "wo": matrix(k[3], (dm, h * s["v_dim"]), _WRITES),
                "w_gate": matrix(k[4], (h, dm), _READS)}

    def mlp(key):
        k = jax.random.split(key, 3)
        return {"gate": matrix(k[0], (s["ffn"], dm), _READS),
                "up": matrix(k[1], (s["ffn"], dm), _READS),
                "down": matrix(k[2], (dm, s["ffn"]), _WRITES)}

    def moe(key):
        k = jax.random.split(key, 7)
        fe, fs = s["expert_ffn"], s["shared_ffn"]
        return {"router": matrix(k[0], (s["n_routed"], dm), _READS),
                "bias": jnp.zeros((s["n_routed"],), jnp.float32),
                "w_gate": matrix(k[1], (held, dm, fe), _READS, dm),
                "w_up": matrix(k[2], (held, dm, fe), _READS, dm),
                "w_down": matrix(k[3], (held, fe, dm), _WRITES, fe),
                "s_gate": matrix(k[4], (fs, dm), _READS),
                "s_up": matrix(k[5], (fs, dm), _READS),
                "s_down": matrix(k[6], (dm, fs), _WRITES)}

    return {name: jax.jit(fn) for name, fn in (
        ("ends", ends), ("kda", kda), ("attn", attention), ("mlp", mlp),
        ("moe", moe))}


def init_params(seed: int, sizes: dict, dtype):
    """All weights from ``seed`` on the default device, a jitted call a
    part (the same program for every layer's part of a kind).  How each
    is drawn: module docstring."""
    import jax
    import jax.numpy as jnp

    fns = _init_fns(tuple(sizes[k] for k in _INIT_KEYS),
                    jnp.dtype(dtype).name)

    def ones(n):
        return {"weight": jnp.ones((n,), dtype)}

    # a seed may exceed 32 signed bits: fold it into the key in two
    # halves; the rbg generator is the chip's own and several times
    # faster than threefry over 3e9 draws
    seed = int(seed)
    key = jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)
    keys = jax.random.split(key, 2 * sizes["n_layer"] + 2)
    tree = fns["ends"](keys[0])
    tokens = jax.random.randint(keys[-1], (_CALIBRATION,), 0, sizes["vocab"])
    x = _f32(jnp.take(tree["embed"]["weight"], tokens, axis=0))
    keep = (jnp.ones((_CALIBRATION, 1, 1, 1), jnp.float32),
            jnp.ones((_CALIBRATION, sizes["d_conv"]), jnp.float32))
    for i in range(sizes["n_layer"]):
        mix = "attn" if sizes["latent"][i] else "kda"
        ffn = "mlp" if i < sizes["n_dense"] else "moe"
        p = tree[f"l{i}"] = {
            "norm_mix": ones(sizes["dim"]), "norm_mlp": ones(sizes["dim"]),
            mix: fns[mix](keys[1 + 2 * i]), ffn: fns[ffn](keys[2 + 2 * i])}
        a = _mixed(p, sizes, x, *keep)
        if ffn == "moe":
            p["moe"]["router"] = _off_the_mean(
                p["moe"]["router"], a, p["norm_mlp"]["weight"], sizes["eps"])
        x = _piece(ffn, _key(sizes), "float32", None)(
            p[ffn], p["norm_mlp"]["weight"], a)
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


def _kda(p, n, s: dict, precision, without, keep_state, keep_taps):
    """The KDA mixer over one sequence ``n`` (T, D) -> (T, D), one
    update of the state a token.  ``keep_state`` (T,) is 0 where the
    state is zeroed BEFORE the position's update, ``keep_taps`` (T, K) 0
    where a position's tap reads zeros (``state_carry``)."""
    import jax
    import jax.numpy as jnp

    t = n.shape[0]
    h, d, k = s["n_head"], s["head_dim"], s["d_conv"]
    inner = h * d
    proj = _matmul(n, p["w_in"], precision)
    qkv, f = proj[:, :3 * inner], proj[:, 3 * inner:4 * inner]
    z, b = proj[:, 4 * inner:5 * inner], proj[:, 5 * inner:]
    w = _f32(p["conv_w"])
    padded = jnp.concatenate(
        [jnp.zeros((k - 1, 3 * inner), jnp.float32), qkv])
    qkv = jax.nn.silu(sum(w[j] * padded[j:j + t] * keep_taps[:, j:j + 1]
                          for j in range(k)))

    def unit(x):
        x = x.reshape(t, h, d)
        return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                            + _L2_EPS)

    q = unit(qkv[:, :inner]) * d ** -0.5
    key = unit(qkv[:, inner:2 * inner])
    v = qkv[:, 2 * inner:].reshape(t, h, d)
    beta = jax.nn.sigmoid(b)                                   # (T, H)
    g = s["lower"] * jax.nn.sigmoid(
        jnp.exp(_f32(p["a_log"]))[:, None]
        * (f + _f32(p["dt_bias"])).reshape(t, h, d))           # (T, H, d)
    if without == "channel_decay":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)

    def token(state, row):
        q_t, k_t, v_t, g_t, beta_t, keep = row
        state = state * keep * jnp.exp(g_t)[:, :, None]
        read = jnp.zeros_like(v_t) if without == "delta" else jnp.einsum(
            "hkv,hk->hv", state, k_t, precision="highest")
        state = state + k_t[:, :, None] \
            * (beta_t[:, None] * (v_t - read))[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t,
                                 precision="highest")

    _, o = jax.lax.scan(token, jnp.zeros((h, d, d), jnp.float32),
                        (q, key, v, g, beta, keep_state))
    groups = s["norm_groups"]
    o = _rms(o.reshape(t, groups, inner // groups), None,
             s["eps"]).reshape(t, inner) * _f32(p["norm"])
    return _matmul(o * jax.nn.sigmoid(z), p["w_out"], precision)


def _attention(p, x, s: dict, precision, without):
    """Latent attention over one sequence ``x`` (T, D), K and V rebuilt
    per head, eight heads at a time."""
    import jax
    import jax.numpy as jnp

    t, _ = x.shape
    h, nope, rope, vd = s["n_head"], s["nope"], s["rope"], s["v_dim"]
    rkv = s["kv_rank"]
    pos = jnp.arange(t)
    q = _matmul(x, p["wq"], precision)
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
    o = o.transpose(1, 0, 2)
    if without != "mla_gate":
        o = o * jax.nn.sigmoid(_matmul(x, p["w_gate"], precision))[..., None]
    return _matmul(o.reshape(t, h * vd), p["wo"], precision)


def route(p, x, s: dict, without=None):
    """The chosen experts (T, top_k) and their weights, the choice
    limited to the ``topk_group`` best groups."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    sc = jax.nn.sigmoid(jnp.matmul(x, _f32(p["router"]).T,
                                   precision="highest"))
    biased = sc + _f32(p["bias"])
    if without != "group_limit":
        by_group = biased.reshape(t, s["n_group"], -1)
        score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(score, s["topk_group"])
        kept = jnp.zeros((t, s["n_group"]), bool).at[
            jnp.arange(t)[:, None], keep].set(True)
        biased = jnp.where(kept[:, :, None], by_group,
                           -jnp.inf).reshape(t, -1)
    _, idx = jax.lax.top_k(biased, s["top_k"])
    w = jnp.take_along_axis(sc, idx, axis=-1)                # (T, k)
    return idx, s["scale"] * w / (jnp.sum(w, axis=-1, keepdims=True)
                                  + 1e-20)


def _experts(p, x, s: dict, precision, without=None, shared=True):
    """The expert layer's share for the held experts ``s["held"]``: a
    loop over them, each over every token, weighted by the router; and
    the shared expert."""
    import jax
    import jax.numpy as jnp

    lo, hi = s["held"]
    idx, w = route(p, x, s, without)
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
def _piece(name: str, key: tuple, precision: str, without):
    """One jitted piece of a layer at these sizes: a layer never exists
    in float32 as a whole."""
    import jax

    s = dict(zip(_KEYS, key))
    if name == "kda":
        return jax.jit(lambda p, nw, x, keep_state, keep_taps: x + _kda(
            p, _rms(x, nw, s["eps"]), s, precision, without, keep_state,
            keep_taps))
    if name == "attn":
        return jax.jit(lambda p, nw, x: x + _attention(
            p, _rms(x, nw, s["eps"]), s, precision, without))
    if name == "mlp":
        return jax.jit(lambda p, nw, x: x + _mlp(
            p, _rms(x, nw, s["eps"]), precision))
    if name == "moe":
        return jax.jit(lambda p, nw, x: x + _experts(
            p, _rms(x, nw, s["eps"]), s, precision, without))
    if name == "moe_alone":
        return jax.jit(lambda p, x, shared: _experts(
            p, x, s, precision, without, shared), static_argnums=2)
    raise KeyError(name)


def _mixed(p, sizes: dict, x, keep_state, keep_taps,
           precision: str = "float32", without=None):
    """A layer's first half, ``x + Mix(rms(x))``: KDA where its tree
    holds ``kda``, latent attention where ``attn``."""
    key = _key(sizes)
    if "kda" in p:
        return _piece("kda", key, precision, without)(
            p["kda"], p["norm_mix"]["weight"], x, keep_state, keep_taps)
    return _piece("attn", key, precision, without)(
        p["attn"], p["norm_mix"]["weight"], x)


def _off_the_mean(router, a, norm_w, eps):
    """``router`` (E, D) less each row's part along the mean over the
    positions of the expert layer's input ``rms(a)`` (module
    docstring)."""
    import jax.numpy as jnp

    m = jnp.mean(_rms(a, norm_w, eps), axis=0)
    m = m / jnp.sqrt(jnp.sum(jnp.square(m)))
    r = _f32(router)
    return (r - jnp.outer(jnp.matmul(r, m, precision="highest"), m)) \
        .astype(router.dtype)


def layer_forward(p, sizes: dict, x, keep_state, keep_taps,
                  precision: str = "float32", without=None):
    """One layer over one sequence ``x`` (T, D), float32: the mixer,
    then the dense kind where its tree holds ``mlp``, the expert kind
    where ``moe``."""
    a = _mixed(p, sizes, x, keep_state, keep_taps, precision, without)
    kind = "mlp" if "mlp" in p else "moe"
    return _piece(kind, _key(sizes), precision, without)(
        p[kind], p["norm_mlp"]["weight"], a)


def expert_layer(p, sizes: dict, x, precision: str = "float32",
                 shared: bool = True):
    """The expert layer alone, ``x`` (T, D) -> (T, D): the share of
    ``sizes["held"]``, with or without the shared expert (for the tests
    of the share)."""
    import jax.numpy as jnp

    return _piece("moe_alone", _key(sizes), precision, None)(
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
    sequence's pieces take seconds each to compile, so few lengths
    (one, 2048, for the requests of a long-generation mix)."""
    step = 1024 if n > 512 and step == 128 else step
    return -(-n // step) * step


def forward_hidden(params, sizes: dict, tokens, precision: str = "float32",
                   without=None, boundary=None):
    """The last layer's outputs (T, D), float32, of one sequence, before
    the final norm, a piece of a layer at a time.  The sequence is
    padded to a multiple of 128 (everything looks back only, and no
    token's expert result depends on another token) to bound the number
    of compiled shapes.  ``boundary`` is the position
    ``without="state_carry"`` cuts at."""
    import jax.numpy as jnp

    if without is not None and without not in PARTS:
        raise ValueError(f"without={without!r}: one of {PARTS}")
    tokens = np.asarray(tokens, np.int32)
    t, k = len(tokens), sizes["d_conv"]
    tp = min(_pad_to(t), max(_pad_to(sizes["max_len"]), t))
    padded = np.zeros((tp,), np.int32)
    padded[:t] = tokens
    keep_state = np.ones((tp, 1, 1, 1), np.float32)
    keep_taps = np.ones((tp, k), np.float32)
    if without == "state_carry" and int(boundary) < tp:
        cut, at = int(boundary), np.arange(tp)
        keep_state[cut] = 0.0
        for j in range(k - 1):          # tap j reads position t - K + 1 + j
            keep_taps[(at >= cut) & (at - (k - 1) + j < cut), j] = 0.0
    x = _f32(jnp.take(params["embed"]["weight"], jnp.asarray(padded),
                      axis=0))
    for i in range(sizes["n_layer"]):
        x = layer_forward(params[f"l{i}"], sizes, x,
                          jnp.asarray(keep_state), jnp.asarray(keep_taps),
                          precision, without)
    return x[:t]


def _gaps(params, sizes, x, n: int, scored, precision):
    """``x`` (n, D) through the final norm and the head: the gap of
    ``scored`` (n,) at each position, and the first choices."""
    import jax.numpy as jnp

    npad = _pad_to(n)
    xp = jnp.zeros((npad, x.shape[1]), jnp.float32).at[:n].set(x[:n])
    sp = np.zeros((npad,), np.int32)
    sp[:n] = scored
    logits, gaps, first = _head_fn(sizes["eps"], precision)(
        params["norm_f"]["weight"], params["head"]["weight"], xp,
        jnp.asarray(sp))
    return logits[:n], np.asarray(gaps)[:n], np.asarray(first)[:n]


def forward_logits(params, sizes: dict, tokens, precision: str = "float32",
                   without=None, boundary=None):
    """Logits (T, V), float32, at every position of one sequence."""
    x = forward_hidden(params, sizes, tokens, precision, without, boundary)
    n = x.shape[0]
    return _gaps(params, sizes, x, n, np.zeros((n,), np.int32),
                 precision)[0]


def served_gaps(params, sizes: dict, prompt, served,
                precision: str = "float32", score=None, without=None):
    """For one finished request: at each served position, how far the
    served token's logit lies below the reference's best (0 where the
    reference would have served the same token).  Also returns the
    tokens this forward puts first at those positions.  ``score`` gives
    other tokens to read the gap of, at the same positions of the same
    prompt and served tokens (the control: what a lower precision put
    first).  ``without="state_carry"`` cuts at the first position a
    decode step computed, ``len(prompt)``."""
    prompt = [int(t) for t in prompt]
    served = [int(t) for t in served]
    tokens = prompt + served
    x = forward_hidden(params, sizes, tokens[:-1], precision, without,
                       boundary=len(prompt))
    # position len(prompt) - 1 + j predicts served[j]
    x = x[len(prompt) - 1:]
    _, gaps, first = _gaps(params, sizes, x, len(served),
                           served if score is None else score, precision)
    return gaps, first
