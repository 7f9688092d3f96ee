"""Layers of today's large decoders that the classic stack lacks:
RMSNorm, interleaved rotary positions, the gated SiLU MLP, and
**latent attention** (MLA: a low-rank query path and one compressed
row a token in place of per-head keys and values).

They keep the module contract (``params()`` / ``apply()``), store every
matrix ``(out, in)`` like :class:`~bigdl_tpu.nn.layers.Linear`
(``y = x @ w.T``), and can be built **without drawing weights**
(``init=False``) for a caller that brings its own tree — a serving
engine handed ``params=`` never reads the modules' own.

Latent attention at position ``t`` (``models/longcat_flash.py`` has the
whole model's equations):

    c_q = rms(W_qa x);  q = s_q * W_qb c_q  -> per head [q_nope | q_rope]
    [c_kv | k_rope] = W_kva x;  c = s_kv * rms(c_kv)
    rotary (interleaved pairs) on q_rope and k_rope at t
    per head h: [k_nope_h | v_h] = W_kvb,h c;  k_h = [k_nope_h | k_rope]
    scores q_h . k_h / sqrt(nope + rope), causal softmax in float32
    y = W_o concat_h(sum_s p_hs v_hs)

with ``s_q = sqrt(dim / q_rank)`` and ``s_kv = sqrt(dim / kv_rank)``
(LongCat-Flash's ``mla_scale_*``) unless the constructor is given
``q_scale`` / ``kv_scale``: a model without such factors
(``models/joyai_flash.py``) gives 1, and nothing is multiplied.
Two options whose defaults are the layer above, bit for bit
(``models/ling_flash.py`` takes both): ``q_rank=None`` is a query WITHOUT
the low-rank step, ``q = W_q x`` (one matrix ``wq`` in place of ``wq_a``,
``q_norm``, ``wq_b``; no factor unless ``q_scale`` is given);
``head_gate=True`` multiplies head ``h``'s mix by ``sigmoid(w_gate,h .
x)`` before ``W_o`` (one gate a head and position, from the layer's own
input).
**The cached row of a token is ``[c | rotated k_rope]``** — ``kv_rank +
rope`` values for all heads together, and zeros up to a multiple of
``row_align`` lanes (a TPU works on a buffer of 640-lane rows as it
lies; 576-lane rows the prefill copies whole, in and out, and the
decode kernel cannot copy a page of them at all:
``tests/test_tpu_lowering.py``).  :meth:`LatentAttention.prefill`
rebuilds K and V per head from the rows; :meth:`LatentAttention.decode`
absorbs ``W_kvb`` into the query (``q_nope_h W_k,h`` scores against
``c``) and into the output, so a decode step never builds a key or a
value: ``ops/decode_attention.latent_decode_attention``, a kernel,
copies each slot's pages from where they lie in the paged cache into
fast memory, up to the slot's own length, and reads each row once.  Handed
``(B, Q, dim)`` it advances ``Q`` consecutive positions a slot in one
pass (a step that verifies a draft, ``serving/engine.py``): ``Q`` rows
written, and the ``Q`` queries of a slot ride the head axis, each with
its own length, over ONE read of the slot's rows.
"""

from __future__ import annotations

import math

import numpy as np

from bigdl_tpu.nn.module import AbstractModule


def _jnp():
    import jax.numpy as jnp

    return jnp


def _draw(shape, std=0.02):
    """N(0, std) from the global seedable generator, on the device."""
    from bigdl_tpu.common import RandomGenerator

    return _jnp().asarray(RandomGenerator.RNG.normal(
        0.0, std, size=shape).astype(np.float32))


def rms_norm(x, weight, eps: float):
    """``weight * x / sqrt(mean(x^2) + eps)``, computed in float32 and
    returned in ``x``'s dtype."""
    import jax

    jnp = _jnp()
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def rotary_interleaved(x, positions, theta: float):
    """Rotate the pairs ``(x[2i], x[2i+1])`` of the last axis by the
    angle ``position * theta ** (-2i / d)``.  ``positions`` broadcasts
    against ``x.shape[:-1]``.  Float32 inside, ``x``'s dtype out."""
    jnp = _jnp()
    d = x.shape[-1]
    inv = jnp.asarray(theta, jnp.float32) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.asarray(positions)[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def rotary_halves(x, positions, theta: float):
    """Rotate the pairs ``(x[i], x[i + d/2])`` of the last axis by the
    angle ``position * theta ** (-2i / d)``: the half-split rotary of
    the decoders with per-head keys (``models/sdar_moe.py``), where
    :func:`rotary_interleaved` pairs neighbours.  ``positions``
    broadcasts against ``x.shape[:-1]``.  Float32 inside, ``x``'s dtype
    out."""
    jnp = _jnp()
    d = x.shape[-1]
    inv = jnp.asarray(theta, jnp.float32) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.asarray(positions)[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def gated_mlp(x, gate, up, down):
    """``down (silu(gate x) * up x)`` with ``(out, in)`` matrices."""
    import jax

    jnp = _jnp()
    h = jax.nn.silu(jnp.matmul(x, gate.T)) * jnp.matmul(x, up.T)
    return jnp.matmul(h, down.T)


class RMSNorm(AbstractModule):
    """Root-mean-square normalisation over the last axis, one gain a
    channel, no bias and no mean."""

    param_names = ("weight",)

    def __init__(self, n_output: int, eps: float = 1e-5, init: bool = True):
        super().__init__()
        self._config = dict(n_output=n_output, eps=eps)
        self.n_output, self.eps = n_output, eps
        self.weight = None
        if init:
            self.reset()

    def reset(self):
        self.weight = _jnp().ones((self.n_output,), _jnp().float32)
        return self

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return rms_norm(input, params["weight"], self.eps)

    def __repr__(self):
        return f"RMSNorm({self.n_output})"


class GatedMLP(AbstractModule):
    """``down (silu(gate x) * up x)``: the gated feed-forward block."""

    param_names = ("gate", "up", "down")

    def __init__(self, dim: int, hidden: int, init: bool = True):
        super().__init__()
        self._config = dict(dim=dim, hidden=hidden)
        self.dim, self.hidden = dim, hidden
        self.gate = self.up = self.down = None
        if init:
            self.reset()

    def reset(self):
        self.gate = _draw((self.hidden, self.dim))
        self.up = _draw((self.hidden, self.dim))
        self.down = _draw((self.dim, self.hidden))
        return self

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return gated_mlp(input, params["gate"], params["up"], params["down"])

    def __repr__(self):
        return f"GatedMLP({self.dim} -> {self.hidden} -> {self.dim})"


class LatentAttention(AbstractModule):
    """Causal multi-head latent attention (equations in the module's
    docstring).  ``apply`` is the full-prefix forward; ``prefill`` also
    returns the rows a decode cache stores; ``decode`` advances one
    token a slot over the paged cache of ``serving/cache.py``."""

    param_names = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b",
                   "wo")

    def __init__(self, dim: int, n_head: int, q_rank: int, kv_rank: int,
                 nope_dim: int, rope_dim: int, v_dim: int,
                 eps: float = 1e-5, theta: float = 1e4, row_align: int = 1,
                 q_scale=None, kv_scale=None, head_gate: bool = False,
                 init: bool = True):
        super().__init__()
        self._config = dict(dim=dim, n_head=n_head, q_rank=q_rank,
                            kv_rank=kv_rank, nope_dim=nope_dim,
                            rope_dim=rope_dim, v_dim=v_dim, eps=eps,
                            theta=theta, row_align=row_align,
                            q_scale=q_scale, kv_scale=kv_scale,
                            head_gate=head_gate)
        self.head_gate = bool(head_gate)
        if q_rank is None:
            # a full-rank query: one matrix, no norm, no factor
            self.param_names = ("wq",) + type(self).param_names[3:]
            q_scale = 1.0 if q_scale is None else q_scale
        if self.head_gate:
            self.param_names = self.param_names + ("w_gate",)
        self.dim, self.n_head = dim, n_head
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.eps, self.theta = eps, theta
        #: width of a token's cached row, ``[c | rotated k_rope]`` and
        #: zeros up to a multiple of ``row_align`` lanes
        self.row_width = -(-(kv_rank + rope_dim) // row_align) * row_align
        # None: LongCat-Flash's sqrt(dim / rank); 1 multiplies nothing
        self.q_scale = math.sqrt(dim / q_rank) if q_scale is None \
            else float(q_scale)
        self.kv_scale = math.sqrt(dim / kv_rank) if kv_scale is None \
            else float(kv_scale)
        self.score_scale = 1.0 / math.sqrt(nope_dim + rope_dim)
        for n in self.param_names:
            setattr(self, n, None)
        if init:
            self.reset()

    def reset(self):
        jnp = _jnp()
        h = self.n_head
        if self.q_rank is None:
            self.wq = _draw((h * (self.nope_dim + self.rope_dim), self.dim))
        else:
            self.wq_a = _draw((self.q_rank, self.dim))
            self.q_norm = jnp.ones((self.q_rank,), jnp.float32)
            self.wq_b = _draw((h * (self.nope_dim + self.rope_dim),
                               self.q_rank))
        if self.head_gate:
            self.w_gate = _draw((h, self.dim))
        self.wkv_a = _draw((self.kv_rank + self.rope_dim, self.dim))
        self.kv_norm = jnp.ones((self.kv_rank,), jnp.float32)
        self.wkv_b = _draw((h * (self.nope_dim + self.v_dim), self.kv_rank))
        self.wo = _draw((self.dim, h * self.v_dim))
        return self

    # ------------------------------------------------------------ parts
    def project(self, params, x, positions):
        """``x`` (..., dim) at ``positions`` (...) -> the scaled query
        ``q_nope`` (..., H, nope), the scaled and rotated ``q_rope``
        (..., H, rope), and the token's row ``[c | rotated k_rope |
        zeros]`` (..., row_width)."""
        jnp = _jnp()
        h = self.n_head
        if self.q_rank is None:
            q = jnp.matmul(x, params["wq"].T)
        else:
            c_q = rms_norm(jnp.matmul(x, params["wq_a"].T),
                           params["q_norm"], self.eps)
            q = jnp.matmul(c_q, params["wq_b"].T)
        if self.q_scale != 1.0:
            q = q * jnp.asarray(self.q_scale, x.dtype)
        q = q.reshape(*x.shape[:-1], h, self.nope_dim + self.rope_dim)
        q_nope, q_rope = q[..., :self.nope_dim], q[..., self.nope_dim:]
        kv = jnp.matmul(x, params["wkv_a"].T)
        c = rms_norm(kv[..., :self.kv_rank], params["kv_norm"], self.eps)
        if self.kv_scale != 1.0:
            c = c * jnp.asarray(self.kv_scale, x.dtype)
        q_rope = rotary_interleaved(q_rope, jnp.asarray(positions)[..., None],
                                    self.theta)
        k_rope = rotary_interleaved(kv[..., self.kv_rank:], positions,
                                    self.theta)
        return q_nope, q_rope, self._row(c, k_rope)

    def _row(self, latent, rope):
        """``[latent | rope | zeros]``, ``row_width`` wide: a token's
        cached row, or a query laid out against it."""
        jnp = _jnp()
        pad = self.row_width - self.kv_rank - self.rope_dim
        parts = [latent, rope] + (
            [jnp.zeros(latent.shape[:-1] + (pad,), latent.dtype)]
            if pad else [])
        return jnp.concatenate(parts, axis=-1)

    def _gated(self, params, x, o):
        """The heads' mixes ``o`` (..., H, v) under the head-wise gate
        of the layer's input ``x`` (..., dim); ``o`` itself without
        one."""
        import jax

        if not self.head_gate:
            return o
        gate = jax.nn.sigmoid(_jnp().matmul(x, params["w_gate"].T))
        return o * gate[..., None].astype(o.dtype)

    def _kv_heads(self, params):
        """``W_kvb`` as ``(H, nope + v, kv_rank)``: head ``h``'s rows
        are ``[W_k,h | W_v,h]``."""
        return params["wkv_b"].reshape(
            self.n_head, self.nope_dim + self.v_dim, self.kv_rank)

    # ---------------------------------------------------------- prefill
    def prefill(self, params, x, positions=None):
        """Full-prefix forward, ``x`` (B, T, dim) -> ``(y, rows)`` with
        ``rows`` (B, T, kv_rank + rope), what the cache stores.  K and V
        are rebuilt per head from the rows; the causal softmax is in
        float32."""
        import jax

        jnp = _jnp()
        b, t, _ = x.shape
        if positions is None:
            positions = jnp.arange(t)[None, :]
        with jax.named_scope("mla.proj"):
            q_nope, q_rope, rows = self.project(params, x, positions)
            c = rows[..., :self.kv_rank]
            k_rope = rows[..., self.kv_rank:self.kv_rank + self.rope_dim]
            wkv = self._kv_heads(params)
            kvh = jnp.einsum("btc,hdc->bthd", c, wkv)
            k_nope, v = kvh[..., :self.nope_dim], kvh[..., self.nope_dim:]
        with jax.named_scope("mla.attn"):
            scores = (
                jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                           preferred_element_type=jnp.float32)
                + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                             preferred_element_type=jnp.float32)
            ) * self.score_scale
            causal = jnp.tril(jnp.ones((t, t), bool))
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            o = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        with jax.named_scope("mla.proj"):
            o = self._gated(params, x, o)
            y = jnp.matmul(o.reshape(b, t, self.n_head * self.v_dim),
                           params["wo"].T)
        return y, rows

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return self.prefill(params, input)[0]

    # ----------------------------------------------------------- decode
    def decode(self, params, x, pages, layer: int, tables, lengths):
        """One token a slot: ``x`` (B, dim) at positions ``lengths``
        (B,).  Writes the token's row into ``pages`` (the stacked
        ``(cached layers, pages, P, row)`` buffer, at ``layer``) and
        attends over positions ``<= length`` with ``W_kvb`` absorbed:
        the query becomes a row-shaped vector, the mix a ``kv_rank``
        vector a head; between the two absorptions (plain einsums) the
        latent kernel reads the slot's pages, at ``[layer, page]``, up
        to its length.  Returns ``(y (B, dim), pages)``.

        ``x`` (B, Q, dim) is ``Q`` consecutive tokens a slot, at
        positions ``lengths + 0 .. Q-1``: ``Q`` rows written, query
        ``j`` attends positions ``<= length + j`` (its own row and the
        rows written before it in this call included), ``y`` (B, Q,
        dim)."""
        import jax

        from bigdl_tpu.ops.decode_attention import latent_decode_attention
        from bigdl_tpu.serving.cache import write_token_rows

        jnp = _jnp()
        lead = x.shape[:-1]                   # (B,) or (B, Q)
        positions = lengths if len(lead) == 1 else \
            lengths[:, None] + jnp.arange(lead[1], dtype=lengths.dtype)
        with jax.named_scope("mla.proj"):
            q_nope, q_rope, row = self.project(params, x, positions)
        with jax.named_scope("kv_write"):
            pages = write_token_rows(pages, layer, tables, lengths, row)
        with jax.named_scope("mla.attn"):
            # W_kvb absorbed, on both sides of the rows
            wkv = self._kv_heads(params)
            q_abs = jnp.einsum("...hd,hdc->...hc", q_nope,
                               wkv[:, :self.nope_dim, :])
            q_rows = self._row(q_abs, q_rope)
            q_len = lengths
            if len(lead) == 2:
                # the Q queries of a slot beside each other on the head
                # axis, a length a query: one read of the slot's rows
                q_rows = q_rows.reshape(lead[0], -1, self.row_width)
                q_len = jnp.repeat(positions, self.n_head, axis=1)
            o_lat = latent_decode_attention(
                q_rows, pages, tables, q_len, layer=layer,
                scale=self.score_scale, value_width=self.kv_rank)
            o_lat = o_lat.reshape(*lead, self.n_head, self.kv_rank)
            o = jnp.einsum("...hc,hdc->...hd", o_lat.astype(x.dtype),
                           wkv[:, self.nope_dim:, :])
        with jax.named_scope("mla.proj"):
            o = self._gated(params, x, o)
            y = jnp.matmul(o.reshape(*lead, self.n_head * self.v_dim),
                           params["wo"].T)
        return y, pages

    def __repr__(self):
        return (f"LatentAttention(dim={self.dim}, heads={self.n_head}, "
                f"row={self.row_width})")


__all__ = ["GatedMLP", "LatentAttention", "RMSNorm", "gated_mlp",
           "rms_norm", "rotary_interleaved"]
