"""Attention / Transformer layers — the long-context stack.

The reference framework has **no attention anywhere** (SURVEY.md §5:
sequence handling is `Recurrent`'s per-timestep loop; long-context is
explicitly absent).  These layers are the rebuild's new capability,
designed TPU-first:

* the hot op is ``bigdl_tpu.ops.dot_product_attention`` (measured
  ``auto`` policy: lax reference until the long-context regime, the
  Pallas flash kernel at T >= 4096 on TPU — see ops/attention.py);
* all shapes are static, heads are a batch dimension for the MXU;
* the sequence axis is left shardable: ``MultiHeadAttention`` accepts an
  ``attn_impl`` override so ``parallel.ring_attention`` can slot in a
  sequence-parallel implementation without touching the layer
  (parallel/ring_attention.py).

They keep the framework's module contract (params()/apply()) so they
serialize, gradcheck, and compose with Sequential/Graph like every other
layer.
"""

from __future__ import annotations

import math
from typing import Optional

import numpy as np

from bigdl_tpu.nn.module import AbstractModule
from bigdl_tpu.nn.layers import Xavier, _to_device


def _jnp():
    import jax.numpy as jnp

    return jnp


class LayerNorm(AbstractModule):
    """Layer normalization over the last dimension (new capability; the
    reference's closest analogue is Normalize, «bigdl»/nn/Normalize.scala).
    """

    param_names = ("weight", "bias")

    def __init__(self, n_output: int, eps: float = 1e-5):
        super().__init__()
        self._config = dict(n_output=n_output, eps=eps)
        self.n_output = n_output
        self.eps = eps
        self.reset()

    def reset(self):
        self.weight = _to_device(np.ones(self.n_output, np.float32))
        self.bias = _to_device(np.zeros(self.n_output, np.float32))
        return self

    def update_output_pure(self, params, input, *, training=False, rng=None):
        import jax

        jnp = _jnp()
        x32 = input.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        return (y * params["weight"] + params["bias"]).astype(input.dtype)

    def __repr__(self):
        return f"LayerNorm({self.n_output})"


class MultiHeadAttention(AbstractModule):
    """Multi-head self/cross attention.

    Input (batch, seq, dim) -> output (batch, seq, dim).  Projections are
    single fused matmuls (one MXU call each); head split/merge are free
    reshapes.  ``attn_impl`` picks the inner kernel ("auto" is the
    measured policy in ops/attention.py: lax below T=4096, Pallas
    flash in the long-context regime on TPU).
    """

    param_names = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo")

    def __init__(self, dim: int, n_head: int, causal: bool = False,
                 with_bias: bool = True, attn_impl: str = "auto",
                 dropout: float = 0.0):
        super().__init__()
        if dim % n_head:
            raise ValueError(f"dim {dim} not divisible by n_head {n_head}")
        self._config = dict(dim=dim, n_head=n_head, causal=causal,
                            with_bias=with_bias, dropout=dropout,
                            attn_impl=attn_impl)
        self.dim = dim
        self.n_head = n_head
        self.head_dim = dim // n_head
        self.causal = causal
        self.with_bias = with_bias
        self.attn_impl = attn_impl
        self.dropout = dropout
        self._init_method = Xavier()
        self.reset()

    def reset(self):
        d = self.dim
        for name in ("wq", "wk", "wv", "wo"):
            setattr(self, name, _to_device(self._init_method.init((d, d), d, d)))
        for name in ("bq", "bk", "bv", "bo"):
            setattr(
                self, name,
                _to_device(np.zeros(d, np.float32)) if self.with_bias else None,
            )
        return self

    def _split(self, x):
        b, t, _ = x.shape
        return x.reshape(b, t, self.n_head, self.head_dim).transpose(0, 2, 1, 3)

    def _inner_attention(self, q, k, v):
        """softmax(QKᵀ)V on (B, H, T, D) heads — the override seam for
        parallel.RingMultiHeadAttention and other attention variants."""
        from bigdl_tpu.ops import dot_product_attention

        return dot_product_attention(q, k, v, causal=self.causal,
                                     impl=self.attn_impl)

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        x = input
        q = jnp.matmul(x, params["wq"].T)
        k = jnp.matmul(x, params["wk"].T)
        v = jnp.matmul(x, params["wv"].T)
        if self.with_bias:
            q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
        q, k, v = self._split(q), self._split(k), self._split(v)
        o = self._inner_attention(q, k, v)
        b, h, t, hd = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(b, t, h * hd)
        if training and self.dropout > 0 and rng is not None:
            import jax

            keep = 1.0 - self.dropout
            mask = jax.random.bernoulli(rng, keep, o.shape)
            o = jnp.where(mask, o / keep, 0.0)
        y = jnp.matmul(o, params["wo"].T)
        if self.with_bias:
            y = y + params["bo"]
        return y

    def __repr__(self):
        return (f"MultiHeadAttention(dim={self.dim}, heads={self.n_head},"
                f" causal={self.causal})")


class _Composite(AbstractModule):
    """Module built from named children; params/state nest by child name."""

    def __init__(self):
        super().__init__()
        self._children: dict[str, AbstractModule] = {}

    def _add_child(self, name: str, module: AbstractModule):
        self._children[name] = module
        return module

    def params(self):
        return {n: m.params() for n, m in self._children.items()}

    def set_params(self, params):
        for n, m in self._children.items():
            m.set_params(params.get(n, {}))

    def state(self):
        return {n: m.state() for n, m in self._children.items()}

    def set_state(self, state):
        for n, m in self._children.items():
            m.set_state(state.get(n, {}))

    def _ordered_params(self):
        out = []
        for m in self._children.values():
            out.extend(m._ordered_params())
        return out

    def reset(self):
        for m in self._children.values():
            m.reset()
        return self

    def regularization_loss(self, params):
        loss = super().regularization_loss(params)
        for n, m in self._children.items():
            loss = loss + m.regularization_loss(params.get(n, {}))
        return loss

    def training(self):
        super().training()
        for m in self._children.values():
            m.training()
        return self

    def evaluate(self, dataset=None, methods=None, batch_size: int = 32):
        for m in self._children.values():
            m.evaluate()
        return super().evaluate(dataset, methods, batch_size)


class TransformerBlock(_Composite):
    """Pre-LN transformer block: x + MHA(LN(x)); x + MLP(LN(x)).

    The MLP hidden is ``mlp_ratio * dim`` with GELU — all MXU-friendly
    big matmuls that XLA fuses with the residual adds.
    """

    def __init__(self, dim: int, n_head: int, mlp_ratio: int = 4,
                 causal: bool = True, attn_impl: str = "auto",
                 dropout: float = 0.0):
        super().__init__()
        from bigdl_tpu.nn.layers import Linear

        self._config = dict(dim=dim, n_head=n_head, mlp_ratio=mlp_ratio,
                            causal=causal, dropout=dropout,
                            attn_impl=attn_impl)
        self.dim = dim
        self._add_child("ln1", LayerNorm(dim))
        self._add_child("attn", MultiHeadAttention(
            dim, n_head, causal=causal, attn_impl=attn_impl, dropout=dropout))
        self._add_child("ln2", LayerNorm(dim))
        self._add_child("fc1", Linear(dim, mlp_ratio * dim))
        self._add_child("fc2", Linear(mlp_ratio * dim, dim))

    def apply(self, params, state, input, *, training=False, rng=None):
        c = self._children
        h, _ = c["ln1"].apply(params["ln1"], {}, input)
        a, _ = c["attn"].apply(params["attn"], {}, h, training=training, rng=rng)
        x = input + a
        return self._mlp(params, x), state

    def _mlp(self, params, x):
        """Shared pre-LN MLP half — used by apply, prefill and
        decode_step so the three paths cannot drift apart."""
        import jax

        c = self._children
        h, _ = c["ln2"].apply(params["ln2"], {}, x)
        h, _ = c["fc1"].apply(params["fc1"], {}, h)
        h = jax.nn.gelu(h)
        h, _ = c["fc2"].apply(params["fc2"], {}, h)
        return x + h

    def _project_qkv(self, pa, h):
        jnp = _jnp()
        q = jnp.matmul(h, pa["wq"].T)
        k = jnp.matmul(h, pa["wk"].T)
        v = jnp.matmul(h, pa["wv"].T)
        if pa.get("bq") is not None:
            q, k, v = q + pa["bq"], k + pa["bk"], v + pa["bv"]
        return q, k, v

    def _out_proj(self, pa, o):
        jnp = _jnp()
        y = jnp.matmul(o, pa["wo"].T)
        if pa.get("bo") is not None:
            y = y + pa["bo"]
        return y

    def prefill_rows(self, params, x):
        """Full-prefix block forward that ALSO returns K and V as the
        projections produce them, ``(B, T, H*Dh)`` token rows — what a
        token-major decode cache (serving/cache.py) stores.  Attention
        math is the identical projection + ``_inner_attention`` path
        apply() takes (dropout off — decoding is inference)."""
        import jax

        attn = self._children["attn"]
        h, _ = self._children["ln1"].apply(params["ln1"], {}, x)
        # scopes as in models/transformer.py paged_decode_logits: names in a
        # profiler trace, no change to the math
        with jax.named_scope("dense"):
            q, k, v = self._project_qkv(params["attn"], h)
        with jax.named_scope("attn"):
            o = attn._inner_attention(attn._split(q), attn._split(k),
                                      attn._split(v))
        b, nh, t, hd = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(b, t, nh * hd)
        with jax.named_scope("dense"):
            x = x + self._out_proj(params["attn"], o)
            out = self._mlp(params, x)
        return out, k, v

    def prefill(self, params, x):
        """:meth:`prefill_rows` with K/V split per head,
        ``(B, H, T, Dh)`` — generate()'s contiguous cache."""
        attn = self._children["attn"]
        out, k, v = self.prefill_rows(params, x)
        return out, attn._split(k), attn._split(v)

    def decode_step(self, params, x, cache_k, cache_v, t):
        """One-token decode: ``x`` is (B, 1, dim), caches are
        (B, H, T_total, Dh) buffers updated in place at position ``t``
        (static shapes; the single query attends over positions <= t).
        Returns (out, cache_k, cache_v)."""
        import jax
        from jax import lax

        jnp = _jnp()
        attn = self._children["attn"]
        h, _ = self._children["ln1"].apply(params["ln1"], {}, x)
        q, k, v = self._project_qkv(params["attn"], h)
        qh = attn._split(q)
        # the caches may be narrower than the activations (bf16 K/V on
        # an f32 model — generate()'s cache_dtype); cast on write
        cache_k = lax.dynamic_update_slice(
            cache_k, attn._split(k).astype(cache_k.dtype), (0, 0, t, 0))
        cache_v = lax.dynamic_update_slice(
            cache_v, attn._split(v).astype(cache_v.dtype), (0, 0, t, 0))
        scale = 1.0 / float(np.sqrt(attn.head_dim))
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, cache_k) * scale
        mask = (jnp.arange(cache_k.shape[2]) <= t)[None, None, None, :]
        scores = jnp.where(mask, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", probs, cache_v)
        b, nh, _, hd = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(b, 1, nh * hd)
        x = x + self._out_proj(params["attn"], o)
        return self._mlp(params, x), cache_k, cache_v

    def __repr__(self):
        return f"TransformerBlock(dim={self.dim})"


class PositionalEmbedding(AbstractModule):
    """Learned absolute positional embedding added to (B, T, D) input."""

    param_names = ("weight",)

    def __init__(self, max_len: int, dim: int):
        super().__init__()
        self._config = dict(max_len=max_len, dim=dim)
        self.max_len = max_len
        self.dim = dim
        self.reset()

    def reset(self):
        from bigdl_tpu.common import RandomGenerator

        self.weight = _to_device(
            RandomGenerator.RNG.normal(
                0.0, 0.02, size=(self.max_len, self.dim)
            ).astype(np.float32)
        )
        return self

    def update_output_pure(self, params, input, *, training=False, rng=None):
        t = input.shape[1]
        return input + params["weight"][:t][None, :, :]


__all__ = [
    "LayerNorm",
    "MultiHeadAttention",
    "TransformerBlock",
    "PositionalEmbedding",
]
