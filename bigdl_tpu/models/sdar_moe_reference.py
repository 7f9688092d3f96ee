"""Plain reference for the ``sdar_30b_a3b_chat`` configuration:
SDAR-30B-A3B-Chat's decoder (grouped-query attention with a norm on
every head's query and key, rotary positions by halves, softmax top-8
experts with renormalised weights) and its generation by blocks, as
straightforward ``jax.numpy`` in float32 with matmul precision
``highest``.  Dense masked attention with a key for every query head's
group: no cache, no batching, no sorting, no kernels, no slot state.  It
imports nothing of the program.

Source: ``huggingface.co/JetLM/SDAR-30B-A3B-Chat`` ``config.json``
(``model_type`` ``sdar_moe``).  **Departures and assumptions** (what
that file does not state is from the family's published code, from
memory, unverified here: there is no network):

* an RMS norm over each head's 128 query and key values, one gain
  vector for all heads, before the rotary; rotary by halves ``(x1, x2)
  -> (x1 cos - x2 sin, x2 cos + x1 sin)``, ``theta`` 1e6, no scaling;
  SiLU in the experts (``hidden_act``); no bias anywhere;
* the router in float32, softmax over its 128 outputs, the 8 largest,
  their weights divided by their sum (``norm_topk_prob``); every layer
  is an expert layer (``decoder_sparse_step`` 1, ``mlp_only_layers``
  empty: ``intermediate_size`` is used by none); no shared expert;
* an untied head; N(0, 0.02) matrices and embeddings, gains at 1;
* **the mask is block-causal**: position ``i`` attends ``j`` iff ``j //
  B <= i // B``; a logit at position ``i`` is for the token AT ``i``; a
  masked position is fed the mask token's embedding;
* **generation** (block length 4, 4 passes, ``low_confidence_dynamic``
  at threshold 0.9, mask token 151669: the defaults of the family's
  generation script, assumed): ``models/sdar_moe.py``'s docstring has
  the procedure.  The reference does not replay it.  Under the mask a
  block's pass depends only on the final tokens before it, so **one
  forward over the final sequence** (stream 0: the keys and values of
  every block as committed) **and one forward a pass index s over all
  blocks in their state at pass s** (stream 1 + s: a position shows its
  token if it was unmasked in a pass before ``s``, the mask token
  otherwise), each noisy block attending stream 0's blocks before it
  and itself, give every pass's logits.  All streams run in lockstep, a
  layer at a time;
* **two departures of the program's, followed here**: a mask FLAG a
  position instead of ``token == mask id``, and ``min(n_s, m)``
  positions where fewer than ``n_s`` are masked.

``precision="int8"`` is the control of "How correct is decided": the
same forward with every weight matrix rounded to int8 per output channel
and every such product's input rounded to int8 per row (W8A8; the router
stays in float32 there as well), the nearest precision below the
configuration's bfloat16.  ``"float8"`` rounds every weight matrix to
float8 (e4m3) and back.  ``variant`` leaves one part of the mathematics
out, to show that the comparison would catch its absence in the
program: ``"no_head_norm"``, ``"no_renorm"``, ``"causal_block"`` (a
causal mask inside the block) and ``"no_commit"`` (later blocks attend
the rows of a block's LAST REFINING pass, not its committed ones).

The weights' tree (the program's model takes the same tree; ``y = x @
w.T`` unless said):

    embed.weight (V, D)   norm_f.weight (D,)   head.weight (V, D)
    l<i>.norm_attn.weight, l<i>.norm_mlp.weight (D,)
    l<i>.attn.{wq (H*Dh, D), wk (Hkv*Dh, D), wv (Hkv*Dh, D),
               wo (D, H*Dh), q_norm (Dh,), k_norm (Dh,)}
    l<i>.moe.{router (E, D), bias (E,) zeros,
              w_gate (G, D, F), w_up (G, D, F), w_down (G, F, D)}
              G held experts, y = x @ w[g]

Every matrix is upcast where it is used, one at a time, and the
attention runs one stream and key head at a time, so the reference fits
beside the served bfloat16 weights.
"""

from __future__ import annotations

import functools
import math

import numpy as np

#: ``unmasked_at``'s values below 0 (``serving/engine.py`` writes the
#: same): a position still masked when its request ended, and one that
#: is given, like the prompt's
NEVER_UNMASKED, GIVEN = -1, -2
VARIANTS = (None, "no_head_norm", "no_renorm", "causal_block", "no_commit")

# sizes a jitted piece is specialised on (hashable)
_KEYS = ("dim", "n_head", "kv_heads", "head_dim", "n_experts", "top_k",
         "eps", "theta", "held", "block")


def sizes_of(config: dict) -> dict:
    """The reference's sizes from a configuration file in the published
    ``config.json`` spelling.  The file's own keys: ``held_experts``
    ([lo, hi), default all), ``max_len`` and ``generation``."""
    for key, want in (("norm_topk_prob", True), ("decoder_sparse_step", 1),
                      ("mlp_only_layers", []), ("rope_scaling", None),
                      ("attention_bias", False),
                      ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise ValueError(f"the reference computes {key} = {want!r} only")
    n_experts = int(config["num_experts"])
    held = config.get("held_experts", [0, n_experts])
    gen = config.get("generation", {})
    rule = gen.get("rule", "low_confidence_dynamic")
    if rule not in ("low_confidence_dynamic", "low_confidence_static"):
        raise ValueError(f"the reference knows no rule {rule!r}")
    return dict(
        n_layer=int(config["num_hidden_layers"]),
        dim=int(config["hidden_size"]),
        n_head=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        expert_ffn=int(config["moe_intermediate_size"]),
        n_experts=n_experts, top_k=int(config["num_experts_per_tok"]),
        eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]),
        vocab=int(config["vocab_size"]),
        max_len=int(config.get("max_len", config.get(
            "max_position_embeddings", 2048))),
        held=(int(held[0]), int(held[1])),
        init_std=float(config.get("initializer_range", 0.02)),
        block=int(gen.get("block_length", 4)),
        passes=int(gen.get("denoising_steps", 4)),
        threshold=float(gen.get("threshold", 0.9))
        if rule == "low_confidence_dynamic" else math.inf,
        mask_id=int(gen.get("mask_token_id", 151669)))


def _key(sizes: dict) -> tuple:
    return tuple(sizes[k] for k in _KEYS)


def init_params(seed: int, sizes: dict, dtype):
    """All weights from ``seed`` on the default device: one jitted call
    for the embedding, the head and the final norm, one a layer (the
    same program for every one).  Matrices and embeddings N(0,
    init_std), gains at 1, the selection bias at 0."""
    import jax
    import jax.numpy as jnp

    d, v, std = sizes["dim"], sizes["vocab"], sizes["init_std"]
    h, hkv, dh = sizes["n_head"], sizes["kv_heads"], sizes["head_dim"]
    g = sizes["held"][1] - sizes["held"][0]
    fe = sizes["expert_ffn"]

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
        k = jax.random.split(key, 8)
        return {
            "norm_attn": ones(d), "norm_mlp": ones(d),
            "attn": {"wq": normal(k[0], (h * dh, d)),
                     "wk": normal(k[1], (hkv * dh, d)),
                     "wv": normal(k[2], (hkv * dh, d)),
                     "wo": normal(k[3], (d, h * dh)),
                     "q_norm": jnp.ones((dh,), dtype),
                     "k_norm": jnp.ones((dh,), dtype)},
            "moe": {"router": normal(k[4], (sizes["n_experts"], d)),
                    "bias": jnp.zeros((sizes["n_experts"],), jnp.float32),
                    "w_gate": normal(k[5], (g, d, fe)),
                    "w_up": normal(k[6], (g, d, fe)),
                    "w_down": normal(k[7], (g, fe, d))}}

    # a seed may exceed 32 signed bits: fold it into the key in two
    # halves; the rbg generator is the chip's own and several times
    # faster than threefry over 4e9 draws
    seed = int(seed)
    key = jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)
    keys = jax.random.split(key, sizes["n_layer"] + 1)
    tree = jax.jit(ends)(keys[0])
    make = jax.jit(layer)
    for i in range(sizes["n_layer"]):
        tree[f"l{i}"] = make(keys[1 + i])
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
    """The halves ``(x1, x2)`` of the last axis of ``x`` (T, heads, d)
    rotated at ``positions`` (T,)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, None, None].astype(jnp.float32) * inv  # (T, 1, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attention(p, x, prev_from, n_real, s: dict, precision, variant):
    """Grouped-query attention over the streams ``x`` (S, T, D) of one
    sequence.  A query of any stream attends, of the blocks BEFORE its
    own, the keys and values of the stream ``prev_from`` (T,) names at
    each position (stream 0, the committed ones, unless ``variant``
    says otherwise), and of its OWN block, its own stream's.  Positions
    from ``n_real`` on are padding and attended by none (they matter
    only where the sequence ends inside a block)."""
    import jax
    import jax.numpy as jnp

    n, t, _ = x.shape
    h, hkv, dh, b = s["n_head"], s["kv_heads"], s["head_dim"], s["block"]
    g = h // hkv
    pos = jnp.arange(t)
    flat = x.reshape(n * t, -1)
    q = _matmul(flat, p["wq"], precision).reshape(n * t, h, dh)
    k = _matmul(flat, p["wk"], precision).reshape(n * t, hkv, dh)
    v = _matmul(flat, p["wv"], precision).reshape(n, t, hkv, dh)
    if variant != "no_head_norm":
        q = _rms(q, p["q_norm"], s["eps"])
        k = _rms(k, p["k_norm"], s["eps"])
    every = jnp.tile(pos, n)
    q = _rotary(q, every, s["theta"]).reshape(n, t, hkv, g, dh)
    k = _rotary(k, every, s["theta"]).reshape(n, t, hkv, dh)
    k_prev = k[prev_from, pos]                               # (T, Hkv, Dh)
    v_prev = v[prev_from, pos]
    blk = pos // b
    before = (blk[None, :] < blk[:, None])[None]             # (1, T, T)
    inside = jnp.ones((b, b), bool) if variant != "causal_block" \
        else jnp.tril(jnp.ones((b, b), bool))
    inside = inside[None, None] & (pos < n_real).reshape(1, t // b, 1, b)

    def one(args):
        """One stream and key head: its ``g`` query heads."""
        q_j, k_j, v_j, kp_j, vp_j = args       # (T, g, Dh), (T, Dh) x 4
        sp = jnp.einsum("tgd,sd->gts", q_j, kp_j, precision="highest")
        sp = jnp.where(before, sp, -jnp.inf) / math.sqrt(dh)
        so = jnp.einsum("nigd,njd->gnij", q_j.reshape(t // b, b, g, dh),
                        k_j.reshape(t // b, b, dh), precision="highest")
        so = jnp.where(inside, so, -jnp.inf) / math.sqrt(dh)
        both = jnp.concatenate([sp, so.reshape(g, t, b)], axis=-1)
        probs = jax.nn.softmax(both, axis=-1)
        o = jnp.einsum("gts,sd->tgd", probs[..., :t], vp_j,
                       precision="highest")
        o = o + jnp.einsum(
            "gnij,njd->nigd", probs[..., t:].reshape(g, t // b, b, b),
            v_j.reshape(t // b, b, dh), precision="highest"
        ).reshape(t, g, dh)
        return o

    def tiled(a):                       # (T, Hkv, Dh) -> (S*Hkv, T, Dh)
        return jnp.tile(a.transpose(1, 0, 2), (n, 1, 1))

    o = jax.lax.map(one, (
        q.transpose(0, 2, 1, 3, 4).reshape(n * hkv, t, g, dh),
        k.transpose(0, 2, 1, 3).reshape(n * hkv, t, dh),
        v.transpose(0, 2, 1, 3).reshape(n * hkv, t, dh),
        tiled(k_prev), tiled(v_prev)))                       # (S*Hkv, T, g, Dh)
    o = o.reshape(n, hkv, t, g, dh).transpose(0, 2, 1, 3, 4)
    return _matmul(o.reshape(n * t, h * dh), p["wo"],
                   precision).reshape(n, t, -1)


def _experts(p, x, s: dict, precision, variant=None):
    """The expert layer's share for the held experts ``s["held"]`` over
    ``x`` (N, D): a loop over them, each over every token, weighted by
    the router."""
    import jax
    import jax.numpy as jnp

    lo, hi = s["held"]
    logits = jnp.matmul(x, _f32(p["router"]).T, precision="highest")
    sc = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(sc + _f32(p["bias"]), s["top_k"])
    w = jnp.take_along_axis(sc, idx, axis=-1)                # (N, k)
    if variant != "no_renorm":
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)

    def one_expert(g, y):
        def of(name):
            return jax.lax.dynamic_index_in_dim(p[name], g, keepdims=False)

        w_e = jnp.sum(jnp.where(idx == lo + g, w, 0.0), axis=-1,
                      keepdims=True)
        hmid = jax.nn.silu(_matmul(x, of("w_gate"), precision, False)) \
            * _matmul(x, of("w_up"), precision, False)
        return y + w_e * _matmul(hmid, of("w_down"), precision, False)

    return jax.lax.fori_loop(0, hi - lo, one_expert, jnp.zeros_like(x))


@functools.lru_cache(maxsize=None)
def _piece(name: str, key: tuple, precision: str, variant):
    """One jitted piece of a layer at these sizes: a layer never exists
    in float32 as a whole."""
    import jax

    s = dict(zip(_KEYS, key))
    if name == "attn":
        return jax.jit(lambda p, nw, x, prev, n_real: x + _attention(
            p, _rms(x, nw, s["eps"]), prev, n_real, s, precision, variant))
    if name == "moe":
        return jax.jit(lambda p, nw, x: x + _experts(
            p, _rms(x, nw, s["eps"]).reshape(-1, x.shape[-1]), s,
            precision, variant).reshape(x.shape))
    if name == "moe_alone":
        return jax.jit(lambda p, x: _experts(p, x, s, precision, variant))
    raise KeyError(name)


def layer_forward(p, sizes: dict, x, prev_from=None,
                  precision: str = "float32", variant=None, n_real=None):
    """One layer over the streams ``x`` (S, T, D), float32 (``T`` a
    whole number of blocks, of which the first ``n_real`` positions are
    the sequence's: default all)."""
    import jax.numpy as jnp

    if prev_from is None:
        prev_from = jnp.zeros((x.shape[1],), jnp.int32)
    key = _key(sizes)
    a = _piece("attn", key, precision, variant)(
        p["attn"], p["norm_attn"]["weight"], x, prev_from,
        jnp.int32(x.shape[1] if n_real is None else n_real))
    return _piece("moe", key, precision, variant)(
        p["moe"], p["norm_mlp"]["weight"], a)


def expert_layer(p, sizes: dict, x, precision: str = "float32"):
    """The expert layer alone, ``x`` (T, D) -> (T, D): the share of
    ``sizes["held"]`` (for the tests of the share)."""
    import jax.numpy as jnp

    return _piece("moe_alone", _key(sizes), precision, None)(
        p, jnp.asarray(x, jnp.float32))


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, precision: str):
    import jax
    import jax.numpy as jnp

    def head(norm_w, w, x, scored):
        """Per row: the logits, the best logit, the token it belongs
        to, the logit of ``scored`` and the log of the best's softmax
        probability."""
        logits = _matmul(_rms(x, norm_w, eps), w, precision)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, scored[:, None], axis=-1)[:, 0]
        conf = best - jax.nn.logsumexp(logits, axis=-1)
        return logits, best, jnp.argmax(logits, axis=-1), got, conf

    return jax.jit(head)


def _pad_to(n: int, block: int) -> int:
    """``n`` rounded up to 128, or to 1024 beyond 512 (a long sequence's
    pieces take ten seconds each to compile, so few lengths), and to a
    whole number of blocks."""
    step = 1024 if n > 512 else 128
    step = step * block // math.gcd(step, block)
    return -(-n // step) * step


def _head_rows(params, sizes, x, scored, precision, chunk: int = 512):
    """``x`` (R, D) through the final norm and the head, ``chunk`` rows
    at a time: (best, first, got, conf), each (R,), on the host."""
    import jax.numpy as jnp

    r = x.shape[0]
    fn = _head_fn(sizes["eps"], precision)
    outs = []
    for lo in range(0, r, chunk):
        n = min(chunk, r - lo)
        xp = jnp.zeros((chunk, x.shape[1]), jnp.float32).at[:n].set(
            x[lo:lo + n])
        sp = np.zeros((chunk,), np.int32)
        sp[:n] = scored[lo:lo + n]
        _, best, first, got, conf = fn(
            params["norm_f"]["weight"], params["head"]["weight"], xp,
            jnp.asarray(sp))
        outs.append([np.asarray(a)[:n] for a in (best, first, got, conf)])
    if not outs:
        return tuple(np.zeros((0,)) for _ in range(4))
    return tuple(np.concatenate(col) for col in zip(*outs))


def forward_streams(params, sizes: dict, streams, prev_from=None,
                    precision: str = "float32", variant=None, n_real=None):
    """The last layer's outputs (S, T, D), float32, before the final
    norm, for the token streams ``streams`` (S, T) of one sequence."""
    import jax.numpy as jnp

    x = _f32(jnp.take(params["embed"]["weight"], jnp.asarray(streams),
                      axis=0))
    if prev_from is not None:
        prev_from = jnp.asarray(prev_from, jnp.int32)
    for i in range(sizes["n_layer"]):
        x = layer_forward(params[f"l{i}"], sizes, x, prev_from, precision,
                          variant, n_real)
    return x


def forward_logits(params, sizes: dict, tokens, precision: str = "float32",
                   variant=None):
    """Logits (T, V), float32, at every position of one sequence under
    the block-causal mask (position ``i``'s are for the token at
    ``i``)."""
    import jax.numpy as jnp

    tokens = np.asarray(tokens, np.int32)
    t = len(tokens)
    padded = np.zeros((1, _pad_to(t, sizes["block"])), np.int32)
    padded[0, :t] = tokens
    x = forward_streams(params, sizes, padded, None, precision, variant,
                        n_real=t)[0]
    fn = _head_fn(sizes["eps"], precision)
    return fn(params["norm_f"]["weight"], params["head"]["weight"], x,
              jnp.zeros((x.shape[0],), jnp.int32))[0][:t]


def pass_streams(sizes: dict, prompt, generated, unmasked_at,
                 variant=None):
    """The token streams of one finished request, ``(streams (1 + T_p,
    T), when (T,), prev_from (T,), start)``: stream 0 the final
    sequence, stream ``1 + s`` every block in its state at pass ``s``;
    ``when`` each position's pass (``GIVEN`` for the prompt's and the
    padding's); ``start`` the first generated position."""
    b, mask_id = sizes["block"], sizes["mask_id"]
    prompt = [int(t) for t in prompt]
    start = len(prompt)
    total = start + len(generated)
    if total % b or len(generated) != len(unmasked_at):
        raise ValueError("the generated positions end with a whole block, "
                         "each with its pass")
    t = _pad_to(total, b)
    when = np.full((t,), GIVEN, np.int64)
    when[start:total] = unmasked_at
    tokens = np.zeros((t,), np.int32)
    tokens[:start] = prompt
    tokens[start:total] = generated
    n_pass = sizes["passes"]
    if when.max() >= n_pass:
        raise ValueError(f"a block takes {n_pass} passes at most")
    streams = np.empty((1 + n_pass, t), np.int32)
    streams[0] = np.where(when == NEVER_UNMASKED, mask_id, tokens)
    for s in range(n_pass):
        shown = (when == GIVEN) | ((when >= 0) & (when < s))
        streams[1 + s] = np.where(shown, tokens, mask_id)
    prev_from = np.zeros((t,), np.int32)
    if variant == "no_commit":
        # a generated block's rows are those of its last refining pass
        last = when.clip(0).reshape(-1, b).max(axis=1)
        generated_block = (when.reshape(-1, b) != GIVEN).any(axis=1)
        prev_from = np.repeat(np.where(generated_block, 1 + last, 0), b)
    return streams, when, prev_from.astype(np.int32), start


def block_gaps(params, sizes: dict, prompt, generated, unmasked_at,
               precision: str = "float32", variant=None, score=None):
    """For one finished request: ``generated`` the tokens of every
    generated position up to the end of its last block (those the
    answer cuts off included) and ``unmasked_at`` the pass of its block
    that unmasked each (``ServeRequest.unmasked``).  A position is
    **scored** where that pass is 0 or more.  Returns a dict:

    * ``token_gap`` (n,): at each scored position, in the state of the
      pass that unmasked it, the reference's largest logit minus its
      logit for the served token (0 where it would have picked it);
    * ``choice_gap`` (n,): for the same positions, how far the
      reference's ranking of that pass's masked positions disagrees
      with the set the engine unmasked: the largest log-confidence
      among those left masked minus the smallest among those unmasked,
      0 where the unmasked are the most confident;
    * ``first`` (n,): the reference's own token there;
    * ``positions`` (n,): which generated positions were scored;
    * ``rows``: for every (block, pass, masked position) the reference
      read, its own token ``first``, its log-confidence ``conf`` and
      ``own``, whether the reference's ranking would unmask it then (as
      many as the engine did).

    ``score`` is the ``rows`` of another precision's run over the same
    request (the control): the gaps are then read for ITS tokens and
    ITS choices."""
    b = sizes["block"]
    streams, when, prev_from, start = pass_streams(
        sizes, prompt, generated, unmasked_at, variant)
    x = forward_streams(params, sizes, streams, prev_from, precision,
                        variant)
    # every (pass, position) at which a position was masked while its
    # block was being refined
    last = when.clip(-1).reshape(-1, b).max(axis=1)      # a block's last pass
    rows = [(s, pos) for pos in np.flatnonzero(when != GIVEN)
            for s in range(int(last[pos // b]) + 1)
            if when[pos] == NEVER_UNMASKED or when[pos] >= s]
    rows.sort(key=lambda r: (r[1] // b, r[0], r[1]))
    at_pass = np.asarray([r[0] for r in rows], np.int64)
    at_pos = np.asarray([r[1] for r in rows], np.int64)
    mine = when[at_pos] == at_pass          # unmasked in this very pass
    scored = streams[0][at_pos] if score is None \
        else np.asarray(score["first"], np.int32)
    import jax.numpy as jnp

    picked = x[jnp.asarray(1 + at_pass), jnp.asarray(at_pos)] if len(rows) \
        else jnp.zeros((0, x.shape[-1]), jnp.float32)
    best, first, got, conf = _head_rows(params, sizes, picked, scored,
                                        precision)
    chosen = mine if score is None else np.asarray(score["own"], bool)
    own = np.zeros(len(rows), bool)
    choice = np.zeros(len(rows))
    group = (at_pos // b) * (sizes["passes"] + 1) + at_pass
    for gid in np.unique(group):
        idx = np.flatnonzero(group == gid)
        # the reference's own choice: as many, by confidence, ties to
        # the earlier position
        order = idx[np.argsort(-conf[idx], kind="stable")]
        own[order[:int(mine[idx].sum())]] = True
        took, left = idx[chosen[idx]], idx[~chosen[idx]]
        if len(took) and len(left):
            choice[idx] = max(0.0, float(conf[left].max()
                                         - conf[took].min()))
    return {"token_gap": (best - got)[mine], "choice_gap": choice[mine],
            "first": first[mine], "positions": at_pos[mine] - start,
            "rows": {"first": first, "conf": conf, "own": own,
                     "pass": at_pass, "position": at_pos - start}}
