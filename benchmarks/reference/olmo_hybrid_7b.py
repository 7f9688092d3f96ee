"""Plain reference for the ``olmo_hybrid_7b`` configuration:
Olmo-Hybrid-7B (three layers of four a **gated delta rule**, a linear
attention whose matrix state every token decays by ONE factor a head,
reads along its key, corrects by a rank-1 write and reads again along
its query; the fourth a **full attention** of 30 heads; a gated MLP
behind either; the family's REORDERED norm) as straightforward
``jax.numpy`` in float32 with matmul precision ``highest``.  A whole
sequence at once: the full attention a ``(T, T)`` causal plane a head,
the recurrence a plain ``lax.scan`` over the tokens, one update of the
state a token (NOT the chunked form the program prefills with).  No
cache, no state carried from call to call, no batching, no kernels.  It
imports nothing of the program.

Source: ``huggingface.co/allenai/Olmo-Hybrid-7B`` ``config.json``
(``model_type`` ``olmo_hybrid``).  That file fixes the widths, the layer
pattern (``layer_types``), the convolution's kernel and
``linear_allow_neg_eigval``.  What it does not fix is marked *(assumed)*:
the ``linear_*`` keys are the gated delta rule's (Yang, Kautz,
Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464) and the full
layers are the OLMo family's attention, both from the published
descriptions and modelling code, from memory, unverified here (there is
no network).  **CHECK FIRST**: the block's order (the reordered norm in
BOTH kinds of layer; pre-norm in the linear layers is the other
reading) and the absence of any rotary.

Every layer (``D`` hidden)::

    a  = x + rms(Mix(x); g_mix)        the norm AFTER the sublayer, on
    x' = a + rms(MLP(a); g_mlp)        what it adds *(assumed)*
    MLP(a) = W_down (silu(W_gate a) * W_up a)

Layer ``i`` of the PUBLISHED model mixes as ``layer_types[i]`` says
(``linear_attention`` in 24 layers, ``full_attention`` in every fourth);
``kept_layers`` says which published layers a cut in depth keeps.

**The gated delta rule** at position ``t``, ``n`` the mixer's input (the
stream itself), ``H = linear_num_value_heads`` heads of ``d_k =
linear_key_head_dim`` and ``d_v = linear_value_head_dim``, kernel ``K =
linear_conv_kernel_dim``::

    [q ; k ; v ; z ; a ; b] = W_in n       H d_k, H d_k, H d_v, H d_v, H, H
           (the six published matrices side by side; no bias)
    [q ; k ; v]_t <- silu(sum_j w_j [q ; k ; v]_{t-K+1+j})
           depthwise causal convolution, zeros before the first
           position, no bias
    q, k <- q / sqrt(|q|^2 + 1e-6), k / sqrt(|k|^2 + 1e-6) a head
    q <- q * d_k ** -0.5
    beta_h = 2 sigmoid(b_h)                linear_allow_neg_eigval true:
           I - beta k k^T has the eigenvalue 1 - beta in (-1, 1) along k
    g_h = -exp(A_log_h) softplus(a_h + dt_bias_h)     ONE number a head
    S_h <- exp(g_h) S_h                    S_h: d_k x d_v, float32
    S_h <- S_h + k_h (beta_h (v_h - S_h^T k_h))^T
    o_h = S_h^T q_h
    y = W_out [rms(o_h; gain) * silu(z_h)]_h
           a norm a HEAD over its d_v values, one gain vector of d_v

**Full attention**, ``num_attention_heads`` heads of ``hidden /
heads`` over ``num_key_value_heads`` key heads::

    q = rms(W_q n; g_q), k = rms(W_k n; g_k)     the norm over the WHOLE
           projection (as OLMo 2 and 3), not a head
    v = W_v n;   no rotary (``rope_parameters.rope_theta`` null: read as
           no positional rotation *(assumed)*; the linear layers carry
           the order)
    o_h = softmax over s <= t of (q_h . k_h,s / sqrt(head_dim)) v_h,s
    y = W_o [o_h]_h

A final ``rms`` and an untied head; eps ``rms_norm_eps`` everywhere.

The weights' tree (the program's model takes the same tree; ``y = x @
w.T``)::

    embed.weight (V, D)   head.weight (V, D)   norm_f.weight (D,)
    l<i>.norm_mix.weight, l<i>.norm_mlp.weight (D,)
    l<i>.gdn.{w_in (2 H d_k + 2 H d_v + 2 H, D), conv_w (K, 2 H d_k +
              H d_v) tap K-1: this position, norm (d_v,), w_out (D,
              H d_v), dt_bias (H,), a_log (H,) float32 whatever the
              dtype}
    l<i>.attn.{wq (H hd, D), wk (G hd, D), wv (G hd, D), q_norm (H hd,),
               k_norm (G hd,), wo (D, H hd)}
    l<i>.mlp.{gate (F, D), up (F, D), down (D, F)}

**Seeded weights must leave no path dead.**  Under the reordered norm
whatever a sublayer adds is normalised first, so the gain of a matrix
that WRITES (``W_out``, ``W_o``, ``down``) changes nothing, nor does
that of ``W_q``, ``W_k`` (normalised a head, or whole) or ``W_v`` (the
linear layers' output is normalised a head): every matrix is N(0, 1 /
sqrt(fan_in)) but ``W_a`` (0.5).  The embedding is N(0, 1): every
sublayer adds a vector of unit RMS, and an embedding of 0.02 would be a
fiftieth of the first one.  The queries' gain ``g_q`` is 2 (scores of
spread 2: a query attends a few dozen rows, not a thousand alike; a
head's scores under the norm of the whole projection have spread 1 at
gain 1 whatever ``W_q`` is).  ``a_log`` is uniform in [-0.5, 0.5] and
``dt_bias`` is set so that at ``a = 0`` a head's decay ``exp(g)`` is ``1
- u``, ``u`` log-uniform in [0.001, 0.1] (decays from 0.9 to 0.999 a
step; ``a`` moves ``u`` by about e either way); ``beta`` is ``2
sigmoid(N(0, 1))``; the convolution's taps are uniform in +-[0.2, 0.6];
the other norm gains are 1.

``precision="int8"`` is the control of "How correct is decided": the
same forward with every weight matrix rounded to int8 per output
channel and every such product's input rounded to int8 per row (W8A8;
the recurrence, the convolution, the norms and the softmax stay
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
#: attention); the doubling of ``beta`` (no negative eigenvalue); the
#: decay a head (``alpha = 1``); the full layers' norm of the query and
#: key projections; the linear layers' output gate ``silu(z)``
PARTS = ("state_carry", "delta", "neg_eigval", "head_decay", "qk_norm",
         "out_gate")

# sizes a jitted piece is specialised on (hashable)
_KEYS = ("dim", "n_head", "kv_heads", "head_dim", "lin_heads", "dk", "dv",
         "d_conv", "beta_max", "eps")

_FIXED = dict(hidden_act="silu", attention_bias=False,
              tie_word_embeddings=False)

_L2_EPS = 1e-6


def sizes_of(config: dict) -> dict:
    """The reference's sizes from a configuration file in the published
    ``config.json`` spelling.  The file's own keys: ``kept_layers``
    (published indices into ``layer_types``, default the first
    ``num_hidden_layers``) and ``max_len``."""
    for k, want in _FIXED.items():
        if config.get(k, want) != want:
            raise ValueError(f"{k} = {config[k]!r}: {want!r} is what is "
                             "written down here")
    if (config.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("rope_parameters.rope_theta is set: a rotary is "
                         "not written down here")
    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError("linear key heads shared between value heads are "
                         "not written down here")
    n_layer = int(config["num_hidden_layers"])
    kept = tuple(int(i) for i in config.get("kept_layers", range(n_layer)))
    if len(kept) != n_layer:
        raise ValueError("kept_layers names num_hidden_layers layers")
    kinds = tuple(config["layer_types"][i] for i in kept)
    for kind in kinds:
        if kind not in ("linear_attention", "full_attention"):
            raise ValueError(f"layer type {kind!r} is not written down here")
    heads = int(config["num_attention_heads"])
    return dict(
        n_layer=n_layer, kept=kept,
        full=tuple(kind == "full_attention" for kind in kinds),
        dim=int(config["hidden_size"]), n_head=heads,
        kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim")
                     or int(config["hidden_size"]) // heads),
        lin_heads=int(config["linear_num_value_heads"]),
        dk=int(config["linear_key_head_dim"]),
        dv=int(config["linear_value_head_dim"]),
        d_conv=int(config["linear_conv_kernel_dim"]),
        beta_max=2.0 if config["linear_allow_neg_eigval"] else 1.0,
        eps=float(config["rms_norm_eps"]),
        ffn=int(config["intermediate_size"]),
        vocab=int(config["vocab_size"]),
        max_len=int(config.get("max_len", config.get(
            "max_position_embeddings", 2048))))


def _key(sizes: dict) -> tuple:
    return tuple(sizes[k] for k in _KEYS)


def zones_of(s: dict) -> tuple:
    """Widths of ``W_in``'s six zones: q, k, v, z, a, b."""
    h = s["lin_heads"]
    return (h * s["dk"], h * s["dk"], h * s["dv"], h * s["dv"], h, h)


_INIT_KEYS = _KEYS + ("vocab", "ffn")

#: the gains of the module docstring
_READS, _RATE, _QUERY_GAIN = 1.0, 0.5, 2.0


@functools.lru_cache(maxsize=None)
def _init_fns(key: tuple, dtype_name: str):
    """The jitted programs that draw the weights at these sizes: the
    embedding, the head and the final norm; a gated-delta mixer; a full
    attention; an MLP."""
    import jax
    import jax.numpy as jnp

    s = dict(zip(_INIT_KEYS, key))
    dtype = jnp.dtype(dtype_name)
    dm, v = s["dim"], s["vocab"]
    h, dk, dv = s["lin_heads"], s["dk"], s["dv"]
    zones = zones_of(s)
    conv = 2 * h * dk + h * dv

    def matrix(k, shape, gain=_READS):
        """N(0, gain / sqrt(fan_in)), ``(out, in)``."""
        return (jax.random.normal(k, shape, jnp.float32)
                * (gain / math.sqrt(shape[-1]))).astype(dtype)

    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": {"weight": jax.random.normal(
                    k[0], (v, dm), jnp.float32).astype(dtype)},
                "head": {"weight": matrix(k[1], (v, dm))},
                "norm_f": {"weight": jnp.ones((dm,), dtype)}}

    def gdn(key):
        k = jax.random.split(key, 6)
        gains = np.repeat(np.asarray(
            [_READS, _READS, _READS, _READS, _RATE, _READS], np.float32),
            zones)[:, None]
        w_in = jax.random.normal(k[0], (sum(zones), dm), jnp.float32) \
            * gains / math.sqrt(dm)
        sign = jnp.where(jax.random.bernoulli(
            k[2], 0.5, (s["d_conv"], conv)), 1.0, -1.0)
        taps = jax.random.uniform(k[1], (s["d_conv"], conv), jnp.float32,
                                  0.2, 0.6) * sign
        a_log = jax.random.uniform(k[3], (h,), jnp.float32, -0.5, 0.5)
        u = jnp.exp(jax.random.uniform(
            k[4], (h,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        # softplus(dt_bias) * exp(a_log) = -log(1 - u): the decay at a = 0
        want = -jnp.log1p(-u) / jnp.exp(a_log)
        dt_bias = jnp.log(jnp.expm1(want))
        return {"w_in": w_in.astype(dtype), "conv_w": taps.astype(dtype),
                "dt_bias": dt_bias, "a_log": a_log,
                "norm": jnp.ones((dv,), dtype),
                "w_out": matrix(k[5], (dm, h * dv))}

    def attention(key):
        k = jax.random.split(key, 4)
        nq, nk = s["n_head"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
        return {"wq": matrix(k[0], (nq, dm)), "wk": matrix(k[1], (nk, dm)),
                "wv": matrix(k[2], (nk, dm)),
                "q_norm": jnp.full((nq,), _QUERY_GAIN, dtype),
                "k_norm": jnp.ones((nk,), dtype),
                "wo": matrix(k[3], (dm, nq))}

    def mlp(key):
        k = jax.random.split(key, 3)
        return {"gate": matrix(k[0], (s["ffn"], dm)),
                "up": matrix(k[1], (s["ffn"], dm)),
                "down": matrix(k[2], (dm, s["ffn"]))}

    return {name: jax.jit(fn) for name, fn in (
        ("ends", ends), ("gdn", gdn), ("attn", attention), ("mlp", mlp))}


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
    # faster than threefry over 1e9 draws
    seed = int(seed)
    key = jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)
    keys = jax.random.split(key, 2 * sizes["n_layer"] + 1)
    tree = fns["ends"](keys[0])
    for i in range(sizes["n_layer"]):
        mix = "attn" if sizes["full"][i] else "gdn"
        tree[f"l{i}"] = {
            "norm_mix": ones(sizes["dim"]), "norm_mlp": ones(sizes["dim"]),
            mix: fns[mix](keys[1 + 2 * i]), "mlp": fns["mlp"](keys[2 + 2 * i])}
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
    """``x (T, K) @ w.T`` with ``w`` ``(N, K)``: float32 ``highest``, or
    the same in W8A8 (weights a output channel, inputs a row)."""
    import jax.numpy as jnp

    w = _f32(w)
    if precision == "int8":
        w = _round8(w, axis=1)
        x = _round8(x, axis=-1)
    return jnp.matmul(x, w.T, precision="highest")


def _mlp(p, x, precision):
    import jax

    h = jax.nn.silu(_matmul(x, p["gate"], precision)) \
        * _matmul(x, p["up"], precision)
    return _matmul(h, p["down"], precision)


def _gdn(p, n, s: dict, precision, without, keep_state, keep_taps):
    """The gated delta rule over one sequence ``n`` (T, D) -> (T, D), one
    update of the state a token.  ``keep_state`` (T,) is 0 where the
    state is zeroed BEFORE the position's update, ``keep_taps`` (T, K) 0
    where a position's tap reads zeros (``state_carry``)."""
    import jax
    import jax.numpy as jnp

    t = n.shape[0]
    h, dk, dv, k = s["lin_heads"], s["dk"], s["dv"], s["d_conv"]
    at = np.cumsum(zones_of(s))
    proj = _matmul(n, p["w_in"], precision)
    qkv, z = proj[:, :at[2]], proj[:, at[2]:at[3]]
    a, b = proj[:, at[3]:at[4]], proj[:, at[4]:]
    w = _f32(p["conv_w"])
    padded = jnp.concatenate([jnp.zeros((k - 1, at[2]), jnp.float32), qkv])
    qkv = jax.nn.silu(sum(w[j] * padded[j:j + t] * keep_taps[:, j:j + 1]
                          for j in range(k)))

    def unit(x):
        x = x.reshape(t, h, dk)
        return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                            + _L2_EPS)

    q = unit(qkv[:, :at[0]]) * dk ** -0.5
    key = unit(qkv[:, at[0]:at[1]])
    v = qkv[:, at[1]:].reshape(t, h, dv)
    beta = (1.0 if without == "neg_eigval" else s["beta_max"]) \
        * jax.nn.sigmoid(b)                                    # (T, H)
    g = -jnp.exp(_f32(p["a_log"])) * jax.nn.softplus(
        a + _f32(p["dt_bias"]))                                # (T, H)
    if without == "head_decay":
        g = jnp.zeros_like(g)

    def token(state, row):
        q_t, k_t, v_t, g_t, beta_t, keep = row
        state = state * keep * jnp.exp(g_t)[:, None, None]
        read = jnp.zeros_like(v_t) if without == "delta" else jnp.einsum(
            "hkv,hk->hv", state, k_t, precision="highest")
        state = state + k_t[:, :, None] \
            * (beta_t[:, None] * (v_t - read))[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t,
                                 precision="highest")

    _, o = jax.lax.scan(token, jnp.zeros((h, dk, dv), jnp.float32),
                        (q, key, v, g, beta, keep_state))
    o = (_rms(o, p["norm"], s["eps"])).reshape(t, h * dv)
    if without != "out_gate":
        o = o * jax.nn.silu(z)
    return _matmul(o, p["w_out"], precision)


def _attention(p, x, s: dict, precision, without):
    """Full attention over one sequence ``x`` (T, D), a ``(T, T)`` plane
    a head, eight heads at a time."""
    import jax
    import jax.numpy as jnp

    t, _ = x.shape
    h, g, d = s["n_head"], s["kv_heads"], s["head_dim"]
    q = _matmul(x, p["wq"], precision)
    key = _matmul(x, p["wk"], precision)
    if without != "qk_norm":
        q = _rms(q, p["q_norm"], s["eps"])
        key = _rms(key, p["k_norm"], s["eps"])
    v = _matmul(x, p["wv"], precision)
    q = q.reshape(t, h, d).transpose(1, 0, 2)                 # (H, T, d)
    # every query head beside its own key head
    key = jnp.repeat(key.reshape(t, g, d), h // g, axis=1).transpose(1, 0, 2)
    v = jnp.repeat(v.reshape(t, g, d), h // g, axis=1).transpose(1, 0, 2)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def head(args):
        q_h, k_h, v_h = args
        scores = jnp.matmul(q_h, k_h.T, precision="highest") / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.matmul(probs, v_h, precision="highest")

    o = jax.lax.map(head, (q, key, v), batch_size=min(8, h))  # (H, T, d)
    return _matmul(o.transpose(1, 0, 2).reshape(t, h * d), p["wo"],
                   precision)


@functools.lru_cache(maxsize=None)
def _piece(name: str, key: tuple, precision: str, without):
    """One jitted piece of a layer at these sizes: a layer never exists
    in float32 as a whole.  Each is ``x + rms(F(x); gain)``."""
    import jax

    s = dict(zip(_KEYS, key))
    if name == "gdn":
        return jax.jit(lambda p, nw, x, keep_state, keep_taps: x + _rms(
            _gdn(p, x, s, precision, without, keep_state, keep_taps), nw,
            s["eps"]))
    if name == "attn":
        return jax.jit(lambda p, nw, x: x + _rms(
            _attention(p, x, s, precision, without), nw, s["eps"]))
    if name == "mlp":
        return jax.jit(lambda p, nw, x: x + _rms(
            _mlp(p, x, precision), nw, s["eps"]))
    raise KeyError(name)


def layer_forward(p, sizes: dict, x, keep_state, keep_taps,
                  precision: str = "float32", without=None):
    """One layer over one sequence ``x`` (T, D), float32: the gated
    delta rule where its tree holds ``gdn``, full attention where
    ``attn``; then the MLP."""
    key = _key(sizes)
    if "gdn" in p:
        a = _piece("gdn", key, precision, without)(
            p["gdn"], p["norm_mix"]["weight"], x, keep_state, keep_taps)
    else:
        a = _piece("attn", key, precision, without)(
            p["attn"], p["norm_mix"]["weight"], x)
    return _piece("mlp", key, precision, without)(
        p["mlp"], p["norm_mlp"]["weight"], a)


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
    padded to a multiple of 128 (everything looks back only) to bound
    the number of compiled shapes.  ``boundary`` is the position
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
