"""Plain reference for the ``gpt2_xl`` configuration: a pre-LN GPT-2
decoder (learned positions, GELU MLP, untied head) as straightforward
``jax.numpy`` in float32 with matmul precision ``highest``.  No cache,
no batching, no kernels; it imports nothing of the program.

It also makes the weights, from the seed, on the device, in one jitted
call, in the tree the sizes imply (the serving engine takes the same
tree, so the benchmark hands it over unchanged):

    wte.weight (V, D)   wpe.weight (T, D)   ln_f.{weight,bias}   head.weight (V, D)
    h<i>.ln1 / ln2 .{weight,bias}
    h<i>.attn.{wq,wk,wv,wo} (D, D), .{bq,bk,bv,bo} (D,)      y = x @ w.T + b
    h<i>.fc1.{weight (4D, D), bias}   h<i>.fc2.{weight (D, 4D), bias}

``precision="int8"`` is the control of "How correct is decided": the
same forward with every weight matrix rounded to int8 per output channel
and every matmul input rounded to int8 per row (W8A8, what int8 serving
computes), the nearest precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import numpy as np

LN_EPS = 1e-5


def sizes_of(config: dict) -> dict:
    """The reference's sizes from a configuration file in the published
    ``config.json`` spelling."""
    return dict(n_layer=int(config["n_layer"]), dim=int(config["n_embd"]),
                n_head=int(config["n_head"]),
                max_len=int(config["n_positions"]),
                vocab=int(config["vocab_size"]), mlp_ratio=4,
                init_std=float(config["initializer_range"]))


def init_params(seed: int, sizes: dict, dtype):
    """All weights from ``seed`` in one jitted call on the default device.
    Matrices and embeddings N(0, initializer_range) (GPT-2's initializer,
    0.02), layer norms at 1 / 0, biases drawn like the matrices so that a
    dropped bias shows."""
    import jax
    import jax.numpy as jnp

    d, v, t = sizes["dim"], sizes["vocab"], sizes["max_len"]
    hidden = sizes["mlp_ratio"] * d
    n_layer, std = sizes["n_layer"], sizes["init_std"]

    def make(key):
        def normal(k, shape):
            return (std * jax.random.normal(k, shape, jnp.float32)
                    ).astype(dtype)

        def ln():
            return {"weight": jnp.ones((d,), dtype),
                    "bias": jnp.zeros((d,), dtype)}

        keys = jax.random.split(key, n_layer + 3)
        tree = {"wte": {"weight": normal(keys[0], (v, d))},
                "wpe": {"weight": normal(keys[1], (t, d))},
                "ln_f": ln(),
                "head": {"weight": normal(keys[2], (v, d))}}
        for i in range(n_layer):
            k = jax.random.split(keys[3 + i], 12)
            tree[f"h{i}"] = {
                "ln1": ln(), "ln2": ln(),
                "attn": {"wq": normal(k[0], (d, d)),
                         "wk": normal(k[1], (d, d)),
                         "wv": normal(k[2], (d, d)),
                         "wo": normal(k[3], (d, d)),
                         "bq": normal(k[4], (d,)), "bk": normal(k[5], (d,)),
                         "bv": normal(k[6], (d,)), "bo": normal(k[7], (d,))},
                "fc1": {"weight": normal(k[8], (hidden, d)),
                        "bias": normal(k[9], (hidden,))},
                "fc2": {"weight": normal(k[10], (d, hidden)),
                        "bias": normal(k[11], (d,))},
            }
        return tree

    # a seed may exceed 32 signed bits: fold it into the key in two halves
    seed = int(seed)
    # the rbg generator: the chip's own, several times faster than
    # threefry over 1.6e9 draws
    key = jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)
    return jax.jit(make)(key)


def _layer_norm(x, p):
    import jax
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + LN_EPS)
            * p["weight"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def _matmul(x, w, precision):
    """x (T, K) @ w (N, K).T in float32, or the same in W8A8."""
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    if precision == "int8":
        ws = jnp.maximum(jnp.max(jnp.abs(w), axis=1, keepdims=True),
                         1e-8) / 127.0
        w = jnp.clip(jnp.round(w / ws), -127, 127) * ws
        xs = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                         1e-8) / 127.0
        x = jnp.clip(jnp.round(x / xs), -127, 127) * xs
    return jnp.matmul(x, w.T, precision="highest")


@functools.lru_cache(maxsize=None)
def _block_fn(n_head: int, precision: str):
    import jax
    import jax.numpy as jnp

    def block(p, x):
        t, d = x.shape
        hd = d // n_head
        pa = p["attn"]
        h = _layer_norm(x, p["ln1"])
        f32 = lambda a: a.astype(jnp.float32)

        def heads(a):
            return a.reshape(t, n_head, hd).transpose(1, 0, 2)

        q = heads(_matmul(h, pa["wq"], precision) + f32(pa["bq"]))
        k = heads(_matmul(h, pa["wk"], precision) + f32(pa["bk"]))
        v = heads(_matmul(h, pa["wv"], precision) + f32(pa["bv"]))
        scores = jnp.einsum("hqd,hkd->hqk", q, k,
                            precision="highest") / np.sqrt(hd)
        causal = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("hqk,hkd->hqd", probs, v, precision="highest")
        o = o.transpose(1, 0, 2).reshape(t, d)
        x = x + _matmul(o, pa["wo"], precision) + f32(pa["bo"])
        h = _layer_norm(x, p["ln2"])
        h = _matmul(h, p["fc1"]["weight"], precision) + f32(p["fc1"]["bias"])
        h = jax.nn.gelu(h)
        h = _matmul(h, p["fc2"]["weight"], precision) + f32(p["fc2"]["bias"])
        return x + h

    return jax.jit(block)


@functools.lru_cache(maxsize=None)
def _head_fn(precision: str):
    import jax
    import jax.numpy as jnp

    def head(ln_f, w, x, served):
        """Per position: the reference's best logit minus its logit for
        the token that was served, and the token it puts first."""
        logits = _matmul(_layer_norm(x, ln_f), w, precision)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
        return best - got, jnp.argmax(logits, axis=-1)

    return jax.jit(head)


def _pad_to(n: int, step: int = 128) -> int:
    return -(-n // step) * step


def forward_hidden(params, sizes: dict, tokens, precision: str = "float32"):
    """Final hidden states (T, D), float32, of one sequence, a layer at a
    time so that only one layer's float32 weights exist at once.  The
    sequence is padded to a multiple of 128 (causal attention keeps the
    real prefix exact) to bound the number of compiled shapes."""
    import jax.numpy as jnp

    tokens = np.asarray(tokens, np.int32)
    t = len(tokens)
    tp = min(_pad_to(t), sizes["max_len"])
    padded = np.zeros((tp,), np.int32)
    padded[:t] = tokens
    x = (jnp.take(params["wte"]["weight"], jnp.asarray(padded), axis=0)
         .astype(jnp.float32)
         + params["wpe"]["weight"][:tp].astype(jnp.float32))
    block = _block_fn(sizes["n_head"], precision)
    for i in range(sizes["n_layer"]):
        x = block(params[f"h{i}"], x)
    return x[:t]


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
    gaps, first = _head_fn(precision)(
        params["ln_f"], params["head"]["weight"], xp, jnp.asarray(sp))
    return np.asarray(gaps)[:n], np.asarray(first)[:n]
