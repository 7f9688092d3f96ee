"""Plain reference for the ``falcon_h1_34b`` configuration: Falcon-H1's
block (a Mamba-2 state-space mixer BESIDE a grouped-query attention on
one normalised input, then a gated MLP, every sublayer under the
configuration's multipliers) as straightforward ``jax.numpy`` in float32
with matmul precision ``highest``.  A whole sequence at once: the
attention a ``(T, T)`` causal plane, the mixer's recurrence a plain
``lax.scan`` over the tokens, one update of the state a token (NOT the
chunked form the program prefills with).  No cache, no state carried
from call to call, no batching, no kernels.  It imports nothing of the
program.

Source: ``huggingface.co/tiiuae/Falcon-H1-34B-Instruct`` ``config.json``
(``model_type`` ``falcon_h1``).  That file fixes the widths and the
multipliers.  What it does not fix is marked *(assumed)*: from the
family's published modelling code and the Mamba-2 paper
(arXiv:2405.21060), from memory, unverified here (there is no network).

One block at position ``t`` (``D`` hidden; the attention's ``H`` query
heads over ``G`` key heads of ``d``; the mixer's ``Hs`` heads of ``P``
in ``Gs`` groups with ``N`` state values a group, ``g(h) = h // (Hs /
Gs)``, convolution kernel ``K``)::

    n   = rms(x; g_in)
    -- attention, on u = attention_in_multiplier * n *(assumed: the
       multiplier is on the attention's input only)*
    q = W_q u    k = key_multiplier * W_k u    v = W_v u
           *(assumed: the key's multiplier before the rotary)*
    q, k = rotary by halves over the whole head at theta, position t
    o[h] = softmax over s <= t of (q[h] . k_s[h // (H/G)] / sqrt(d))
           v_s[h // (H/G)]
    att = W_o [o_0 .. o_{H-1}]
    -- mixer
    p = (W_in (ssm_in_multiplier * n)) * m
           m holds ssm_multipliers[0..4] over p's five zones, in this
           order *(assumed)*: gate z (Hs P), x (Hs P), B (Gs N), C (Gs
           N), dt (Hs)
    [x ; B ; C]_t <- silu(sum_j w_j [x ; B ; C]_{t-K+1+j} + b)
           depthwise causal convolution, zeros before the first
           position *(assumed: SiLU after it)*
    dt_h = softplus(dt_h + dt_bias_h)      A_h = -exp(a_log_h)
    S_h <- exp(dt_h A_h) S_h + dt_h x_h (x) B_g(h)         S_h: P x N
    y_h = S_h C_g(h) + D_h x_h
    y   = rms over each group's Hs P / Gs values of (y * silu(z)),
           times a gain *(assumed: the gate before the norm, as
           mamba_norm_before_gate false says, and the norm by groups)*
    ssm = W_out y
    x = x + ssm_out_multiplier * ssm + attention_out_multiplier * att
    r = rms(x; g_ff)
    x = x + down_multiplier * W_down (silu(gate_multiplier * W_gate r)
           * W_up r)               mlp_multipliers = [gate, down]

Embedding rows times ``embedding_multiplier``; logits ``lm_head_multiplier
* W_head rms(x; g_f)``, the head untied; no bias but the convolution's.

The weights' tree (the program's model takes the same tree, so the
benchmark hands it over unchanged; ``y = x @ w.T``)::

    embed.weight (V, D)   head.weight (V, D)   norm_f.weight (D,)
    l<i>.norm_in.weight, l<i>.norm_ff.weight (D,)
    l<i>.attn.{wq (H d, D), wk (G d, D), wv (G d, D), wo (D, H d)}
    l<i>.ssm.{w_in (2 Hs P + 2 Gs N + Hs, D), w_out (D, Hs P),
              conv_w (K, Hs P + 2 Gs N)  tap K-1: this position,
              conv_b (Hs P + 2 Gs N,), norm (Hs P,),
              dt_bias, a_log, d (Hs,) float32 whatever the dtype}
    l<i>.mlp.{gate (F, D), up (F, D), down (D, F)}

**Seeded weights must leave no path dead.**  The multipliers are made
for trained weights: under N(0, 0.02) matrices ``key_multiplier`` 0.011
makes every score 0 (uniform attention hides a wrong mask or rotary)
and ``lm_head_multiplier`` flattens the logits.  So every matrix is
drawn N(0, s / sqrt(fan_in)) DIVIDED BY the multipliers that act on its
product, ``s`` the gain its product should have over a unit-RMS input:
1 for what reads the normalised stream (``W_q``, ``W_k``, ``W_v``,
``W_in`` zone by zone, ``W_gate``, ``W_up``, the head: scores and logits
with a spread of order 1), 0.3 for what writes the stream (``W_o``,
``W_out``, ``W_down``).  The three additions to the stream, measured
through this file at the published widths (RMS over positions 768 to
1024 of a sequence of 1024, the cell's contexts; `PERF.md` section 6):
the mixer's 0.30 and the MLP's 0.18 in every layer; the attention's
0.083, 0.100 and 0.105 in the second to fourth layer (2.8 to 3.6 times
under the mixer's) and 0.018 in the FIRST, 16 times under it: what the
attention hands ``W_o`` is the mean of a context's value rows under
weights that random keys leave near uniform, which shrinks as 1 /
sqrt(context), and the first layer's rows are independent embeddings.
A larger gain on ``W_o`` (1.8 was tried) makes that mean, which is the
same for every position of a long context, pile up in the stream layer
by layer (the attention's addition 0.11, 0.57, 1.25, 1.5 over the four
layers): not taken.  Left out, the attention still misses the cell's
limit by 160 times (``tools/readings_lm_parts.py``).  The
embedding is N(0, 0.02) (0.11 after its multiplier).  ``dt_bias`` is the
inverse softplus of a ``dt`` log-uniform in [0.001, 0.1]; ``a_log`` is
set so that at that ``dt`` the head's decay ``exp(dt A)`` is ``1 - u``
with ``u`` log-uniform in [0.001, 0.1] (decays from 0.9 to 0.999 a
step); ``D`` is uniform in [0.5, 1.5]; the convolution's taps are
uniform in +-[0.2, 0.6] and its bias in [-0.1, 0.1]; norm gains are 1.

``precision="int8"`` is the control of "How correct is decided": the
same forward with every weight matrix rounded to int8 per output
channel and every such product's input rounded to int8 per row (W8A8;
the recurrence, the convolution and the softmax stay float32), the
nearest precision below the configuration's bfloat16.  ``without=``
leaves ONE part of the mathematics out (:data:`PARTS`): a program that
lacks it agrees with that forward and not with this one.

Beside 9 GB of bf16 weights every matrix is upcast where it is used,
one at a time; the head runs in blocks of positions and of the
vocabulary.
"""

from __future__ import annotations

import functools
import math

import numpy as np

#: what ``without=`` can leave out: the mixer's addition to the stream,
#: the attention's, the convolution (its rows go to the SiLU as they
#: are), the gate and the group norm (``y`` goes to ``W_out`` as it is),
#: and the state's carry across ONE position, ``boundary`` (the state
#: and the convolution's rows zeroed there: what a decode step sees
#: whose slot state was never handed over by the prefill)
PARTS = ("ssm", "attn", "conv", "gate_norm", "state_carry")

# sizes a jitted piece is specialised on (hashable)
_KEYS = ("dim", "n_head", "kv_heads", "head_dim", "theta", "eps",
         "ssm_heads", "ssm_head_dim", "d_state", "groups", "d_conv",
         "attn_in", "attn_out", "key_mult", "ssm_in", "ssm_out",
         "ssm_zones", "mlp_mults")

_FIXED = dict(mamba_rms_norm=True, mamba_norm_before_gate=False,
              mamba_conv_bias=True, mamba_proj_bias=False,
              attention_bias=False, mlp_bias=False, projectors_bias=False,
              tie_word_embeddings=False, rope_scaling=None,
              attn_layer_indices=None, hidden_act="silu")


def sizes_of(config: dict) -> dict:
    """The reference's sizes from a configuration file in the published
    ``config.json`` spelling.  The file's own key: ``max_len``."""
    for k, want in _FIXED.items():
        if config.get(k, want) != want:
            raise ValueError(f"{k} = {config[k]!r}: {want!r} is what is "
                             "written down here")
    heads, hd = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if int(config["mamba_d_ssm"]) != heads * hd:
        raise ValueError("mamba_d_ssm is mamba_n_heads x mamba_d_head")
    return dict(
        n_layer=int(config["num_hidden_layers"]),
        dim=int(config["hidden_size"]),
        n_head=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        ffn=int(config["intermediate_size"]),
        ssm_heads=heads, ssm_head_dim=hd,
        d_state=int(config["mamba_d_state"]),
        groups=int(config["mamba_n_groups"]),
        d_conv=int(config["mamba_d_conv"]),
        embed_mult=float(config["embedding_multiplier"]),
        head_mult=float(config["lm_head_multiplier"]),
        attn_in=float(config["attention_in_multiplier"]),
        attn_out=float(config["attention_out_multiplier"]),
        key_mult=float(config["key_multiplier"]),
        ssm_in=float(config["ssm_in_multiplier"]),
        ssm_out=float(config["ssm_out_multiplier"]),
        ssm_zones=tuple(float(m) for m in config["ssm_multipliers"]),
        mlp_mults=tuple(float(m) for m in config["mlp_multipliers"]),
        vocab=int(config["vocab_size"]),
        max_len=int(config.get("max_len", config.get(
            "max_position_embeddings", 2048))))


def _key(sizes: dict) -> tuple:
    return tuple(sizes[k] for k in _KEYS)


def zones_of(s: dict) -> tuple:
    """Widths of ``p``'s five zones: z, x, B, C, dt."""
    inner, gn = s["ssm_heads"] * s["ssm_head_dim"], s["groups"] * s["d_state"]
    return (inner, inner, gn, gn, s["ssm_heads"])


_INIT_KEYS = _KEYS + ("vocab", "ffn", "embed_mult", "head_mult")

#: the gain over a unit-RMS input of a product that reads the
#: normalised stream, and of one that writes the stream
_READS, _WRITES = 1.0, 0.3


@functools.lru_cache(maxsize=None)
def _init_fns(key: tuple, dtype_name: str):
    """The two jitted programs that draw the weights at these sizes:
    the embedding, the head and the final norm, and one layer."""
    import jax
    import jax.numpy as jnp

    s = dict(zip(_INIT_KEYS, key))
    dtype = jnp.dtype(dtype_name)
    dm, v, f = s["dim"], s["vocab"], s["ffn"]
    h, g, d = s["n_head"], s["kv_heads"], s["head_dim"]
    hs, zones = s["ssm_heads"], zones_of(s)
    inner, conv = zones[0], zones[1] + zones[2] + zones[3]
    gate_mult, down_mult = s["mlp_mults"]

    def matrix(k, shape, gain, mult):
        """(out, in): N(0, gain / sqrt(in)) over the multiplier(s) that
        act on its product."""
        w = jax.random.normal(k, shape, jnp.float32) \
            * (gain / math.sqrt(shape[1]))
        return (w / mult).astype(dtype)

    def uniform(k, shape, lo, hi, out=dtype):
        return jax.random.uniform(k, shape, jnp.float32, lo, hi).astype(out)

    def log_uniform(k, shape, lo, hi):
        return jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, math.log(lo), math.log(hi)))

    def ones(n):
        return {"weight": jnp.ones((n,), dtype)}

    def table(key, std):
        """(V, D) N(0, std), drawn in blocks of rows: the generator's
        32 bits a value, for the whole table at once, would be twice the
        table's own bytes."""
        blocks = 8 if v % 8 == 0 and v >= 8192 else 1
        rows = jax.lax.map(
            lambda k: (std * jax.random.normal(
                k, (v // blocks, dm), jnp.float32)).astype(dtype),
            jax.random.split(key, blocks))
        return {"weight": rows.reshape(v, dm)}

    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": table(k[0], 0.02),
                "head": table(k[1], _READS / math.sqrt(dm)
                              / s["head_mult"]),
                "norm_f": ones(dm)}

    def layer(key):
        k = jax.random.split(key, 20)
        zone_mult = np.repeat(np.asarray(s["ssm_zones"], np.float32),
                              zones)[:, None] * s["ssm_in"]
        dt = log_uniform(k[10], (hs,), 1e-3, 1e-1)
        decay = 1.0 - log_uniform(k[11], (hs,), 1e-3, 1e-1)
        sign = jnp.where(jax.random.bernoulli(k[13], 0.5, (s["d_conv"],
                                                           conv)), 1.0, -1.0)
        taps = jax.random.uniform(k[12], (s["d_conv"], conv), jnp.float32,
                                  0.2, 0.6) * sign
        return {
            "norm_in": ones(dm), "norm_ff": ones(dm),
            "attn": {"wq": matrix(k[0], (h * d, dm), _READS, s["attn_in"]),
                     "wk": matrix(k[1], (g * d, dm), _READS,
                                  s["attn_in"] * s["key_mult"]),
                     "wv": matrix(k[2], (g * d, dm), _READS, s["attn_in"]),
                     "wo": matrix(k[3], (dm, h * d), _WRITES,
                                  s["attn_out"])},
            "ssm": {"w_in": matrix(k[4], (sum(zones), dm), _READS,
                                   zone_mult),
                    "w_out": matrix(k[5], (dm, inner), _WRITES,
                                    s["ssm_out"]),
                    "conv_w": taps.astype(dtype),
                    "conv_b": uniform(k[14], (conv,), -0.1, 0.1),
                    "norm": jnp.ones((inner,), dtype),
                    # inverse softplus; A from the decay at that dt
                    "dt_bias": jnp.log(jnp.expm1(dt)),
                    "a_log": jnp.log(-jnp.log(decay) / dt),
                    "d": uniform(k[15], (hs,), 0.5, 1.5, jnp.float32)},
            "mlp": {"gate": matrix(k[6], (f, dm), _READS, gate_mult),
                    "up": matrix(k[7], (f, dm), _READS, 1.0),
                    "down": matrix(k[8], (dm, f), _WRITES,
                                   down_mult)}}

    return jax.jit(ends), jax.jit(layer)


def init_params(seed: int, sizes: dict, dtype):
    """All weights from ``seed`` on the default device: one jitted call
    for the embedding, the head and the final norm, and one a layer (the
    same program for every layer, so that no more than one layer's
    float32 draws exist at a time).  How each is drawn: module
    docstring."""
    import jax
    import jax.numpy as jnp

    ends, layer = _init_fns(tuple(sizes[k] for k in _INIT_KEYS),
                            jnp.dtype(dtype).name)
    # a seed may exceed 32 signed bits: fold it into the key in two
    # halves; the rbg generator is the chip's own and several times
    # faster than threefry over 3e9 draws
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


def _matmul(x, w, precision):
    """``x (T, K) @ w.T``, ``w`` ``(N, K)``: float32 ``highest``, or the
    same in W8A8 (weights a output channel, inputs a row)."""
    import jax.numpy as jnp

    w = _f32(w)
    if precision == "int8":
        w = _round8(w, axis=1)
        x = _round8(x, axis=-1)
    return jnp.matmul(x, w.T, precision="highest")


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


def _attention(p, u, s: dict, precision):
    """Grouped-query attention over one sequence ``u`` (T, D) -> (T,
    D)."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    h, g, d = s["n_head"], s["kv_heads"], s["head_dim"]
    pos = jnp.arange(t)
    q = _rotary_halves(_matmul(u, p["wq"], precision).reshape(t, h, d),
                       pos, s["theta"])
    k = _rotary_halves(
        (s["key_mult"] * _matmul(u, p["wk"], precision)).reshape(t, g, d),
        pos, s["theta"])
    v = _matmul(u, p["wv"], precision).reshape(t, g, d)
    scores = jnp.einsum("tjrd,ujd->jrtu", q.reshape(t, g, h // g, d), k,
                        precision="highest") / math.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("jrtu,ujd->tjrd", probs, v,
                   precision="highest").reshape(t, h * d)
    return _matmul(o, p["wo"], precision)


def _mixer(p, n, s: dict, precision, without, keep_state, keep_taps):
    """The state-space mixer over one sequence ``n`` (T, D) -> (T, D),
    one update of the state a token.  ``keep_state`` (T,) is 0 where
    the state is zeroed BEFORE the position's update, ``keep_taps`` (T,
    K) 0 where a position's tap reads zeros (``state_carry``)."""
    import jax
    import jax.numpy as jnp

    t = n.shape[0]
    hs, pd, ns = s["ssm_heads"], s["ssm_head_dim"], s["d_state"]
    gs, k = s["groups"], s["d_conv"]
    zones = zones_of(s)
    m = np.repeat(np.asarray(s["ssm_zones"], np.float32), zones)
    proj = _matmul(s["ssm_in"] * n, p["w_in"], precision) * m
    inner, gn = zones[0], zones[2]
    z, xbc, dt = proj[:, :inner], proj[:, inner:2 * inner + 2 * gn], \
        proj[:, 2 * inner + 2 * gn:]
    if without != "conv":
        w = _f32(p["conv_w"])
        padded = jnp.concatenate(
            [jnp.zeros((k - 1, xbc.shape[1]), jnp.float32), xbc])
        xbc = sum(w[j] * padded[j:j + t] * keep_taps[:, j:j + 1]
                  for j in range(k)) + _f32(p["conv_b"])
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :inner].reshape(t, hs, pd)
    b = jnp.repeat(xbc[:, inner:inner + gn].reshape(t, gs, ns), hs // gs,
                   axis=1)
    c = jnp.repeat(xbc[:, inner + gn:].reshape(t, gs, ns), hs // gs, axis=1)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))              # (T, Hs)
    decay = jnp.exp(dt * -jnp.exp(_f32(p["a_log"])))

    def token(state, row):
        x_t, b_t, c_t, dt_t, decay_t, keep = row
        state = state * keep * decay_t[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((hs, pd, ns), jnp.float32),
                        (x, b, c, dt, decay, keep_state))
    y = (y + _f32(p["d"])[:, None] * x).reshape(t, inner)
    if without != "gate_norm":
        y = _rms((y * jax.nn.silu(z)).reshape(t, gs, inner // gs), None,
                 s["eps"]).reshape(t, inner) * _f32(p["norm"])
    return _matmul(y, p["w_out"], precision)


@functools.lru_cache(maxsize=None)
def _piece(name: str, key: tuple, precision: str, without):
    """One jitted piece of a block at these sizes."""
    import jax

    s = dict(zip(_KEYS, key))
    if name == "mix":
        def mix(pa, ps, nw, x, keep_state, keep_taps):
            n = _rms(x, nw, s["eps"])
            if without != "ssm":
                x = x + s["ssm_out"] * _mixer(ps, n, s, precision, without,
                                              keep_state, keep_taps)
            if without != "attn":
                x = x + s["attn_out"] * _attention(pa, s["attn_in"] * n, s,
                                                   precision)
            return x

        return jax.jit(mix)
    if name == "mlp":
        gate_mult, down_mult = s["mlp_mults"]

        def mlp(p, nw, x):
            r = _rms(x, nw, s["eps"])
            mid = jax.nn.silu(gate_mult * _matmul(r, p["gate"], precision)) \
                * _matmul(r, p["up"], precision)
            return x + down_mult * _matmul(mid, p["down"], precision)

        return jax.jit(mlp)
    raise KeyError(name)


def layer_forward(p, sizes: dict, x, keep_state, keep_taps,
                  precision: str = "float32", without=None):
    """One block over one sequence ``x`` (T, D), float32 -> (T, D)."""
    key = _key(sizes)
    x = _piece("mix", key, precision, without)(
        p["attn"], p["ssm"], p["norm_in"]["weight"], x, keep_state,
        keep_taps)
    return _piece("mlp", key, precision, without)(
        p["mlp"], p["norm_ff"]["weight"], x)


#: rows of the vocabulary a pass of the head: 32640 x 5120 float32 are
#: 0.67 GB where the whole head's would be 5.3
_VOCAB_BLOCK = 32768


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, head_mult: float, precision: str):
    import jax
    import jax.numpy as jnp

    def head(norm_w, w, x, served):
        """Per position: the logits, the reference's best logit minus
        its logit for the token that was served, and the token it puts
        first.  The head's rows in blocks (one block's float32 at a
        time)."""
        v = w.shape[0]
        blocks = max(1, -(-v // _VOCAB_BLOCK))
        while v % blocks:
            blocks += 1
        xn = _rms(x, norm_w, eps)
        logits = jax.lax.map(
            lambda lo: _matmul(xn, jax.lax.dynamic_slice_in_dim(
                w, lo, v // blocks), precision),
            jnp.arange(blocks) * (v // blocks))
        logits = head_mult * jnp.moveaxis(logits, 0, 1).reshape(
            x.shape[0], v)
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
    a block at a time.  The sequence is padded to a multiple of 128
    (everything looks back only) to bound the number of compiled
    shapes.  ``boundary`` is the position ``without="state_carry"``
    cuts at."""
    import jax.numpy as jnp

    if without is not None and without not in PARTS:
        raise ValueError(f"without={without!r}: one of {PARTS}")
    tokens = np.asarray(tokens, np.int32)
    t, k = len(tokens), sizes["d_conv"]
    tp = min(_pad_to(t), max(_pad_to(sizes["max_len"]), t))
    padded = np.zeros((tp,), np.int32)
    padded[:t] = tokens
    keep_state = np.ones((tp,), np.float32)
    keep_taps = np.ones((tp, k), np.float32)
    if without == "state_carry" and int(boundary) < tp:
        cut, at = int(boundary), np.arange(tp)
        keep_state[cut] = 0.0
        for j in range(k - 1):          # tap j reads position t - K + 1 + j
            keep_taps[(at >= cut) & (at - (k - 1) + j < cut), j] = 0.0
    x = _f32(jnp.take(params["embed"]["weight"], jnp.asarray(padded),
                      axis=0)) * sizes["embed_mult"]
    for i in range(sizes["n_layer"]):
        x = layer_forward(params[f"l{i}"], sizes, x,
                          jnp.asarray(keep_state), jnp.asarray(keep_taps),
                          precision, without)
    return x[:t]


#: positions a call of the head: 256 x 261120 float32 logits are 0.27 GB
_HEAD_BLOCK = 256


def _head_blocks(params, sizes, x, served, precision):
    """The head over ``x`` (N, D) in blocks of positions: the gaps and
    first choices, and the logits' blocks as they come (an iterator)."""
    import jax.numpy as jnp

    head = _head_fn(sizes["eps"], sizes["head_mult"], precision)
    n = x.shape[0]
    for lo in range(0, n, _HEAD_BLOCK):
        m = min(_HEAD_BLOCK, n - lo)
        xp = jnp.zeros((_HEAD_BLOCK, x.shape[1]), jnp.float32) \
            .at[:m].set(x[lo:lo + m])
        sp = np.zeros((_HEAD_BLOCK,), np.int32)
        sp[:m] = served[lo:lo + m]
        logits, gaps, first = head(params["norm_f"]["weight"],
                                   params["head"]["weight"], xp,
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
    first).  ``without="state_carry"`` cuts at the first position a
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
