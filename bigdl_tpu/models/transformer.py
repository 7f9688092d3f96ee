"""Transformer language model — the long-context flagship.

No reference analogue: classic BigDL's sequence stack tops out at
Recurrent/LSTM BPTT windows (SURVEY.md §5 "long-context: absent").  This
model is the rebuild's new capability and the vehicle for the
sequence-parallel / ring-attention / tensor-parallel paths in
``bigdl_tpu.parallel``:

* token + learned positional embeddings,
* N pre-LN TransformerBlocks (Pallas flash attention on TPU),
* final LayerNorm + vocab projection.

Tokens are 0-based int32 (unlike LookupTable's 1-based parity
convention — this model has no reference API to mirror).
"""

from __future__ import annotations

import numpy as np

from bigdl_tpu.nn.attention import (
    LayerNorm,
    PositionalEmbedding,
    TransformerBlock,
    _Composite,
)
from bigdl_tpu.nn.layers import Linear, _to_device
from bigdl_tpu.nn.module import AbstractModule


class TokenEmbedding(AbstractModule):
    """0-based token embedding, N(0, 0.02) init (GPT convention)."""

    param_names = ("weight",)

    def __init__(self, vocab_size: int, dim: int):
        super().__init__()
        self._config = dict(vocab_size=vocab_size, dim=dim)
        self.vocab_size = vocab_size
        self.dim = dim
        self.reset()

    def reset(self):
        from bigdl_tpu.common import RandomGenerator

        self.weight = _to_device(
            RandomGenerator.RNG.normal(
                0.0, 0.02, size=(self.vocab_size, self.dim)
            ).astype(np.float32)
        )
        return self

    def update_output_pure(self, params, input, *, training=False, rng=None):
        import jax.numpy as jnp

        return jnp.take(params["weight"], input.astype(jnp.int32), axis=0)


#: lanes of one row of the TPU's vector tile
_LANES = 128


def row_table(weight):
    """``weight`` (rows, width) in the form a gather of its ROWS takes
    it where it lies: the width padded with zeros to whole lane tiles.

    The TPU's compiler lays a matrix in memory in the order that pads
    its tiles least.  A width of whole lane tiles keeps a row's values
    together and a gather reads the rows it picks; GPT-2 XL's
    ``(50257, 1600)`` (12.5 tiles wide) is laid ids-along-the-lanes,
    and a program that gathers 12 rows of it first copies the whole
    160 MB table into row order, in every call (0.49 of a 6.83 ms
    decode step; PERF.md section 6, PR 46).  Whether a table needs
    this follows from its width alone: one that has whole tiles is
    handed back as it is."""
    import jax.numpy as jnp

    pad = -weight.shape[1] % _LANES
    return jnp.pad(weight, ((0, 0), (0, pad))) if pad else weight


def take_rows(table, ids, width: int):
    """Rows ``ids`` of a table, :func:`row_table`'s or the matrix
    itself, at the matrix's ``width``: bit for bit its rows."""
    import jax.numpy as jnp

    return jnp.take(table, ids, axis=0)[..., :width]


class TransformerLM(_Composite):
    """Decoder-only causal LM over (batch, seq) int tokens -> logits
    (batch, seq, vocab)."""

    def __init__(self, vocab_size: int, dim: int = 256, n_head: int = 4,
                 n_layer: int = 4, max_len: int = 1024, mlp_ratio: int = 4,
                 dropout: float = 0.0, attn_impl: str = "auto",
                 remat: bool = False):
        super().__init__()
        self._config = dict(vocab_size=vocab_size, dim=dim, n_head=n_head,
                            n_layer=n_layer, max_len=max_len,
                            mlp_ratio=mlp_ratio, dropout=dropout,
                            attn_impl=attn_impl, remat=remat)
        self.vocab_size = vocab_size
        self.dim = dim
        self.n_layer = n_layer
        # remat=True: per-block gradient checkpointing — backward
        # recomputes each block's forward instead of storing its
        # activations, cutting peak HBM from O(n_layer * seq * dim)
        # activations to O(sqrt-ish) at ~1/3 extra FLOPs (the long-
        # context training lever; pairs with ring/ulysses seq-parallel)
        self.remat = remat
        self._add_child("wte", TokenEmbedding(vocab_size, dim))
        self._add_child("wpe", PositionalEmbedding(max_len, dim))
        for i in range(n_layer):
            self._add_child(f"h{i}", TransformerBlock(
                dim, n_head, mlp_ratio=mlp_ratio, causal=True,
                attn_impl=attn_impl, dropout=dropout))
        self._add_child("ln_f", LayerNorm(dim))
        self._add_child("head", Linear(dim, vocab_size, with_bias=False))

    def apply(self, params, state, input, *, training=False, rng=None):
        import jax

        c = self._children
        x, _ = c["wte"].apply(params["wte"], {}, input)
        x, _ = c["wpe"].apply(params["wpe"], {}, x)
        for i in range(self.n_layer):
            key = None
            if rng is not None:
                key = jax.random.fold_in(rng, i)
            block = c[f"h{i}"]
            if self.remat:
                def blk(p, xx, _b=block, _k=key):
                    out, _ = _b.apply(p, {}, xx, training=training, rng=_k)
                    return out
                x = jax.checkpoint(blk)(params[f"h{i}"], x)
            else:
                x, _ = block.apply(params[f"h{i}"], {}, x,
                                   training=training, rng=key)
        x, _ = c["ln_f"].apply(params["ln_f"], {}, x)
        logits, _ = c["head"].apply(params["head"], {}, x)
        return logits, state

    def generate(self, params, prompt, max_new_tokens: int, *,
                 temperature: float = 0.0, rng=None, cache_dtype=None):
        """Autoregressive decoding with a static-shape KV cache.

        TPU-idiomatic two-phase decode: the prompt is prefetched in ONE
        batched forward (``TransformerBlock.prefill`` — the identical
        attention path training uses — also yields each layer's K/V),
        then a single compiled ``lax.scan`` step generates tokens, with
        per-layer (B, H, T_total, Dh) cache buffers updated in place by
        ``dynamic_update_slice`` (``TransformerBlock.decode_step``).
        All shapes static — no per-token retrace or dispatch.

        ``temperature=0`` is greedy argmax; ``>0`` samples categorical
        (requires ``rng``).  Returns (B, prompt_len + max_new_tokens)
        int32 token ids.

        ``cache_dtype`` sets the K/V buffer dtype; the default honors
        the model dtype (``wte`` weight) instead of hardcoding f32 —
        a bf16 model gets a bf16 cache, halving decode HBM traffic
        (scores still accumulate in the query dtype).
        """
        import jax
        import jax.numpy as jnp
        from jax import lax

        prompt = jnp.asarray(prompt).astype(jnp.int32)
        bsz, t0 = prompt.shape
        total = t0 + max_new_tokens
        max_len = self._config["max_len"]
        if total > max_len:
            raise ValueError(
                f"prompt {t0} + {max_new_tokens} new tokens exceeds "
                f"max_len {max_len}")
        if temperature > 0.0 and rng is None:
            raise ValueError("temperature sampling needs an rng key")
        if max_new_tokens <= 0:
            return prompt
        n_head = self._config["n_head"]
        head_dim = self.dim // n_head
        c = self._children
        key = rng if rng is not None else jax.random.key(0)
        if cache_dtype is None:
            cache_dtype = params["wte"]["weight"].dtype
        cache_dtype = jnp.dtype(cache_dtype)

        def sample(logits, key):
            if temperature > 0.0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(
                    sub, logits / temperature, axis=-1)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            return nxt.astype(jnp.int32), key

        # ---- prefill: one batched forward over the whole prompt ----
        x = jnp.take(params["wte"]["weight"], prompt, axis=0)
        x = x + params["wpe"]["weight"][:t0][None]
        caches = {}
        for i in range(self.n_layer):
            x, kh, vh = c[f"h{i}"].prefill(params[f"h{i}"], x)
            ck = jnp.zeros((bsz, n_head, total, head_dim), cache_dtype)
            cv = jnp.zeros((bsz, n_head, total, head_dim), cache_dtype)
            caches[f"h{i}"] = (
                lax.dynamic_update_slice(ck, kh.astype(cache_dtype),
                                         (0, 0, 0, 0)),
                lax.dynamic_update_slice(cv, vh.astype(cache_dtype),
                                         (0, 0, 0, 0)),
            )
        h, _ = c["ln_f"].apply(params["ln_f"], {}, x[:, -1:, :])
        logits, _ = c["head"].apply(params["head"], {}, h)
        first, key = sample(logits[:, 0, :], key)

        tokens = jnp.zeros((bsz, total), jnp.int32)
        tokens = lax.dynamic_update_slice(tokens, prompt, (0, 0))
        tokens = lax.dynamic_update_slice(tokens, first[:, None], (0, t0))

        # ---- decode: scan over the remaining new tokens ------------
        def step(carry, t):
            tokens, caches, key = carry
            cur = lax.dynamic_slice(tokens, (0, t), (bsz, 1))
            x = jnp.take(params["wte"]["weight"], cur, axis=0)
            x = x + lax.dynamic_slice(
                params["wpe"]["weight"], (t, 0), (1, self.dim))[None]
            new_caches = {}
            for i in range(self.n_layer):
                ck, cv = caches[f"h{i}"]
                x, ck, cv = c[f"h{i}"].decode_step(
                    params[f"h{i}"], x, ck, cv, t)
                new_caches[f"h{i}"] = (ck, cv)
            h, _ = c["ln_f"].apply(params["ln_f"], {}, x)
            logits, _ = c["head"].apply(params["head"], {}, h)
            nxt, key = sample(logits[:, 0, :], key)
            tokens = lax.dynamic_update_slice(
                tokens, nxt[:, None], (0, t + 1))
            return (tokens, new_caches, key), None

        if max_new_tokens > 1:
            (tokens, _, _), _ = lax.scan(
                step, (tokens, caches, key),
                jnp.arange(t0, total - 1))
        return tokens

    # ------------------------------------------------------------ serving
    # What ``serving.LMEngine`` asks of a model: its cache, and one
    # prompt or one token a slot over that cache, up to the logits.
    def cache_spec(self, params) -> dict:
        """K and V of every layer, a row of ``n_head * head_dim``
        values each, in two buffers of ``heads`` x ``head_dim`` lanes
        (the bytes-per-token gauge reads them)."""
        n_head = int(self._config["n_head"])
        return {"layers": self.n_layer, "row_width": self.dim,
                "buffers": 2, "max_len": int(self._config["max_len"]),
                "dtype": params["wte"]["weight"].dtype,
                "heads": n_head, "head_dim": self.dim // n_head}

    def serving_tables(self, params) -> dict:
        """What the engine's programs take IN PLACE of parts of
        ``params``, made once when the engine takes its weights and
        again at a swap: the two embeddings as :func:`row_table` lays
        them, each under its place in the tree; nothing for one that
        gathers as it lies."""
        made = {}
        for name in ("wte", "wpe"):
            weight = params[name]["weight"]
            table = row_table(weight)
            if table is not weight:
                made[name] = {"weight": table}
        return made

    def paged_prefill(self, params, caches, prompt, t0, pages):
        """One prompt, ``prompt`` (1, bucket) zero-padded past ``t0`` —
        causal attention keeps the real prefix exact — into the pages
        ``pages``; ``(caches, logits (1, vocab) at t0 - 1, None)``.
        The token rows come as in :func:`paged_decode_logits`."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from bigdl_tpu.serving.cache import write_prompt_pages

        kp, vp = caches
        c = self._children
        bucket = prompt.shape[1]
        x = take_rows(params["wte"]["weight"], prompt, self.dim)
        x = x + params["wpe"]["weight"][:bucket, :self.dim][None]
        for i in range(self.n_layer):
            # the block's prefill names its own attn and dense parts
            x, k, v = c[f"h{i}"].prefill_rows(params[f"h{i}"], x)
            with jax.named_scope("kv_write"):
                # one scatter a layer over the bucket's pages
                kp = write_prompt_pages(kp, i, pages, k[0])
                vp = write_prompt_pages(vp, i, pages, v[0])
        h = lax.dynamic_slice(x, (0, t0 - 1, 0), (1, 1, self.dim))
        h, _ = c["ln_f"].apply(params["ln_f"], {}, h)
        with jax.named_scope("dense"):
            logits, _ = c["head"].apply(params["head"], {}, h)
        return (kp, vp), logits[:, 0, :], None

    def paged_decode(self, params, caches, tables, lengths, tokens, active,
                     *, page_size, qparams=None):
        """One token a slot: ``(caches, logits (B, vocab), None)``."""
        del active  # an inactive slot writes the trash page
        kp, vp, logits = paged_decode_logits(
            self._children, self.n_layer, page_size, params, qparams,
            *caches, tables, lengths, tokens)
        return (kp, vp), logits, None

    def quantize_for_decode(self, params):
        """The int8 twins ``paged_decode(qparams=)`` takes."""
        return _quantize_tree(params, self.n_layer)

    def tp_decode_step(self, **kw):
        """The decode step sharded over ``tp`` devices (serving/tp.py)."""
        from bigdl_tpu.serving.tp import build_tp_decode_step

        return build_tp_decode_step(self, **kw)

    def __repr__(self):
        return (f"TransformerLM(vocab={self.vocab_size}, dim={self.dim}, "
                f"layers={self.n_layer})")


def _quantize_tree(params, n_layer):
    """Per-output-channel int8 twins of every decode matmul weight —
    the ``quantize_per_channel`` path ``module.quantize()`` uses."""
    from bigdl_tpu.ops.quantized_matmul import quantize_per_channel

    q = {}
    for i in range(n_layer):
        pa = params[f"h{i}"]["attn"]
        blk = {"attn": {}, "fc1": None, "fc2": None}
        for w in ("wq", "wk", "wv", "wo"):
            blk["attn"][w] = quantize_per_channel(pa[w], axis=0)
        blk["fc1"] = quantize_per_channel(
            params[f"h{i}"]["fc1"]["weight"], axis=0)
        blk["fc2"] = quantize_per_channel(
            params[f"h{i}"]["fc2"]["weight"], axis=0)
        q[f"h{i}"] = blk
    q["head"] = quantize_per_channel(params["head"]["weight"], axis=0)
    return q


def paged_decode_logits(children, n_layer, page_size, params, qparams,
                        kp, vp, tables, lengths, tokens, *, n_head=None,
                        psum=None):
    """One decode step over the paged cache, up to the logits — the
    single source of truth shared by the jitted single-host step and
    the TP shard_map body (``n_head`` is the LOCAL head count there, ``psum`` the
    compressed block reduction).  Mirrors
    ``TransformerBlock.decode_step`` exactly in the float path so paged
    decode bit-matches ``generate()`` at temperature 0.

    The slots' token rows are gathered from ``params["wte"]["weight"]``
    (and their positions' from ``wpe``), which is the embedding itself
    or, behind the engine, the table ``TransformerLM.serving_tables``
    made of it once (:func:`row_table`: the width padded to whole lane
    tiles where it has none, so that the compiled gather reads the rows
    it picks and not, after a copy of the whole table into row order,
    the copy; the pad is cut off the gathered rows); the rows handed
    to the first layer are the embedding's either way, bit for bit.

    The attention body is ``ops.decode_attention.paged_decode_attention``
    (the body of a cache of per-head K/V rows); ``tables`` may be the
    engine's used-page prefix bucket rather than the full table width
    (same mask contract either way).

    The ``jax.named_scope`` blocks (``kv_write``, ``attn``, ``dense``;
    the engine adds ``sample``) are metadata only: they name the step's operations in a
    profiler trace and in the HLO, and change no math."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.decode_attention import paged_decode_attention
    from bigdl_tpu.ops.quantized_matmul import int8_matmul
    from bigdl_tpu.serving.cache import write_token_rows

    attn0 = children["h0"]._children["attn"]
    heads = attn0.n_head if n_head is None else int(n_head)
    head_dim = attn0.head_dim
    bsz = tokens.shape[0]
    scale = 1.0 / float(np.sqrt(head_dim))

    def mm(x, w, qw):
        if qparams is not None and qw is not None:
            return int8_matmul(x, qw[0], qw[1], impl="auto")
        return jnp.matmul(x, w.T)

    dim = children["wte"].dim
    x = take_rows(params["wte"]["weight"], tokens, dim)[:, None, :]
    x = x + take_rows(params["wpe"]["weight"], lengths, dim)[:, None, :]
    for i in range(n_layer):
        block = children[f"h{i}"]
        p = params[f"h{i}"]
        pa = p["attn"]
        qb = None if qparams is None else qparams[f"h{i}"]
        h, _ = block._children["ln1"].apply(p["ln1"], {}, x)
        with jax.named_scope("dense"):
            if qb is None:
                q, k, v = block._project_qkv(pa, h)
            else:
                q = mm(h, pa["wq"], qb["attn"]["wq"])
                k = mm(h, pa["wk"], qb["attn"]["wk"])
                v = mm(h, pa["wv"], qb["attn"]["wv"])
                if pa.get("bq") is not None:
                    q, k, v = q + pa["bq"], k + pa["bk"], v + pa["bv"]

        qh = q.reshape(bsz, heads, head_dim)
        with jax.named_scope("kv_write"):
            # one token row per slot, the projection's output as it
            # comes (the cache is token-major: no split into heads)
            kp = write_token_rows(kp, i, tables, lengths, k[:, 0, :])
            vp = write_token_rows(vp, i, tables, lengths, v[:, 0, :])
        with jax.named_scope("attn"):
            # the stacked buffers and the layer's index, not kp[i]:
            # the pages are read where they lie
            o = paged_decode_attention(
                qh, kp, vp, tables, lengths, layer=i,
                page_size=page_size, scale=scale)   # (B, H, Dh)
        o = o.reshape(bsz, 1, heads * head_dim)
        with jax.named_scope("dense"):
            y = mm(o, pa["wo"], None if qb is None else qb["attn"]["wo"])
            if psum is not None:
                y = psum(y)
            if pa.get("bo") is not None:
                y = y + pa["bo"]
        x = x + y
        # MLP (pre-LN): bias of the row-parallel fc1 is local, the
        # col-parallel fc2's bias is added once, after the reduction
        h, _ = block._children["ln2"].apply(p["ln2"], {}, x)
        with jax.named_scope("dense"):
            h = mm(h, p["fc1"]["weight"],
                   None if qb is None else qb["fc1"]) + p["fc1"]["bias"]
            h = jax.nn.gelu(h)
            h = mm(h, p["fc2"]["weight"],
                   None if qb is None else qb["fc2"])
            if psum is not None:
                h = psum(h)
            if p["fc2"].get("bias") is not None:
                h = h + p["fc2"]["bias"]
        x = x + h
    h, _ = children["ln_f"].apply(params["ln_f"], {}, x)
    with jax.named_scope("dense"):
        logits = mm(h, params["head"]["weight"],
                    None if qparams is None else qparams["head"])[:, 0, :]
    return kp, vp, logits


def paged_decode_math(children, n_layer, page_size, params, qparams,
                      kp, vp, tables, lengths, tokens, temps, active,
                      key, **kw):
    """:func:`paged_decode_logits` and the engine's sampling: the whole
    step, as the TP ``shard_map`` body runs it."""
    from bigdl_tpu.serving.engine import sample_step

    kp, vp, logits = paged_decode_logits(
        children, n_layer, page_size, params, qparams, kp, vp, tables,
        lengths, tokens, **kw)
    return kp, vp, sample_step(logits, temps, active, key)



def build_transformer_lm(vocab_size: int, **kw) -> TransformerLM:
    return TransformerLM(vocab_size, **kw)
