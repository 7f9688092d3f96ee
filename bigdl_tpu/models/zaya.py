"""ZAYA1: a decoder whose attention runs **inside a compressed latent**
(CCA: the queries are half the hidden width, the keys and values an
eighth, mixed along the sequence by two causal convolutions of kernel 2
and a value shifted by one position) and whose every layer has **top-1
experts chosen by an MLP router that averages over depth**, both
sublayers under a scaled residual.

Source: ``huggingface.co/Zyphra/ZAYA1-8B`` ``config.json``
(``model_type`` ``zaya``).  What that file does not state is marked
*(assumed)* in ``benchmarks/reference/zaya1_8b.py``, which has the layer's
equations; the names here are its names.

**What is new to serve.**  A token's cached rows are NOT a function of
that token alone: position ``t``'s K and V rows need position
``t - 1``'s down-projected rows ``z`` (the first convolution's
previous tap), its convolved rows ``c`` (the second's) and the half of
its value projection that belongs to the next position (the value
shift).  So a slot carries, a layer, ``(H + G) d + (H + G) d + G d / 2``
values beside its pages (2688 at the published widths), which the model
declares (:meth:`Zaya.state_spec`) and ``serving.LMEngine`` keeps
(``serving/cache.py``): :meth:`Zaya.paged_prefill` returns the state
after the prompt's last REAL token, :meth:`Zaya.paged_decode` takes the
slots' state and returns it advanced.  A token's cached rows are its
``G`` normed, rotated keys in one buffer and its ``G`` values (half of
them the previous position's projection) in the other: the per-head K/V
cache with ``kv_heads`` ``G``, an eighth of full heads' rows.  The
decode attention is ``ops/decode_attention.paged_decode_attention``'s
kernel path (a key head's ``H / G`` query heads share its rows).

**The router** is a module of this model's (:class:`ZayaRouter`); the
expert layer is ``nn/experts.py`` ``DroplessExperts`` without a router
of its own, handed the chosen expert and its weight.

**A chip's share**, weights brought by the caller, no weights drawn: as
``models/longcat_flash.py``.
"""

from __future__ import annotations

import math
from typing import Optional

from bigdl_tpu.models.longcat_flash import _Table
from bigdl_tpu.nn.attention import _Composite
from bigdl_tpu.nn.experts import DroplessExperts, merge_counts
from bigdl_tpu.nn.latent import RMSNorm, _draw, rms_norm, rotary_halves
from bigdl_tpu.nn.module import AbstractModule

#: the published ``config.json`` (the keys that shape the model; the
#: two rotary numbers are its ``rope_parameters.hybrid``)
PUBLISHED = dict(
    vocab_size=262272, hidden_size=2048, num_hidden_layers=40,
    num_attention_heads=8, num_key_value_heads=2, head_dim=128,
    moe_intermediate_size=2048, num_experts=16, num_experts_per_tok=1,
    router_hidden_size=256, cca_time0=2, cca_time1=2,
    partial_rotary_factor=0.5, rope_theta=5e6, rms_norm_eps=1e-5)


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def before(a):
    """``a`` (1, T, ...) one position earlier along the sequence: row
    ``t`` holds ``a[t - 1]``, zeros at the first."""
    import jax.numpy as jnp

    return jnp.concatenate([jnp.zeros_like(a[:, :1]), a[:, :-1]], axis=1)


class CCAttention(AbstractModule):
    """``H`` query heads over ``G`` key/value heads of ``d``, all formed
    inside the down-projected rows ``z = [qd ; kd]`` by two causal
    convolutions, the query-key mean, L2 norms with a key temperature
    and rotary positions on part of a head (module docstring)."""

    param_names = ("w_qk", "w_v", "w_o", "conv0_w", "conv0_b", "conv1_w",
                   "conv1_b", "tau")

    def __init__(self, dim: int, n_head: int, kv_heads: int, head_dim: int,
                 rotary: int, eps: float = 1e-5, theta: float = 5e6,
                 init: bool = True):
        super().__init__()
        if n_head % kv_heads or kv_heads % 2:
            raise ValueError(f"{n_head} query heads over {kv_heads} "
                             "key/value heads: the value shift halves "
                             "the key/value heads")
        self._config = dict(dim=dim, n_head=n_head, kv_heads=kv_heads,
                            head_dim=head_dim, rotary=rotary, eps=eps,
                            theta=theta)
        self.dim, self.n_head, self.kv_heads = dim, n_head, kv_heads
        self.head_dim, self.rotary = head_dim, rotary
        self.eps, self.theta = eps, theta
        #: channels of ``z`` and of ``c``; width of a cached K (or V)
        #: row; values of the value projection the next position takes
        self.channels = (n_head + kv_heads) * head_dim
        self.row_width = kv_heads * head_dim
        self.late = (kv_heads // 2) * head_dim
        for n in self.param_names:
            setattr(self, n, None)
        if init:
            self.reset()

    def reset(self):
        import jax.numpy as jnp

        h, g, d, c = self.n_head, self.kv_heads, self.head_dim, self.channels
        self.w_qk = _draw((c, self.dim))
        self.w_v = _draw((g * d, self.dim))
        self.w_o = _draw((self.dim, h * d))
        self.conv0_w = jnp.stack([jnp.full((c,), 0.5, jnp.float32),
                                  jnp.ones((c,), jnp.float32)])
        self.conv0_b = jnp.zeros((c,), jnp.float32)
        self.conv1_w = jnp.stack([_draw((h + g, d, d), 0.5 / math.sqrt(d)),
                                  _draw((h + g, d, d), 1.0 / math.sqrt(d))])
        self.conv1_b = jnp.zeros((h + g, d), jnp.float32)
        self.tau = jnp.ones((g,), jnp.float32)
        return self

    def state_shapes(self) -> tuple:
        """What a slot carries for this layer: ``z``, ``c`` and the
        late half of the value projection of its last position."""
        return ((self.channels,), (self.channels,), (self.late,))

    # ------------------------------------------------------------ parts
    def project(self, params, x):
        """``x`` (..., dim) -> ``z`` (..., C) and the value projection
        (..., G * d): its first half is this position's, its second the
        next position's."""
        import jax.numpy as jnp

        return jnp.matmul(x, params["w_qk"].T), jnp.matmul(x, params["w_v"].T)

    def conv0(self, params, z, z_prev):
        """The depthwise convolution: ``c = u1 * z + u0 * z_prev + b``,
        float32 inside, ``z``'s dtype out (what the second convolution
        reads at this position and, as the slot's state, at the
        next)."""
        u = _f32(params["conv0_w"])
        return (u[1] * _f32(z) + u[0] * _f32(z_prev)
                + _f32(params["conv0_b"])).astype(z.dtype)

    def rows(self, params, z, c, c_prev, v, v_prev, positions):
        """From the mixed rows to what attends and what is cached: the
        query (..., H, d), the token's K row (..., G * d), normed, under
        its temperature and rotated, and its V row (..., G * d): the
        first half of this position's value projection ``v``, then the
        late half of the previous position's, ``v_prev``."""
        import jax.numpy as jnp

        h, g, d = self.n_head, self.kv_heads, self.head_dim
        lead, per = z.shape[:-1], h // g
        w = params["conv1_w"]

        def taps(rows, tap):
            return jnp.einsum("...hi,hoi->...ho",
                              rows.reshape(*lead, h + g, d).astype(w.dtype),
                              w[tap], preferred_element_type=jnp.float32)

        e = taps(c, 1) + taps(c_prev, 0) + _f32(params["conv1_b"])
        zh = _f32(z).reshape(*lead, h + g, d)
        qd, kd = zh[..., :h, :], zh[..., h:, :]
        q = e[..., :h, :] + (qd + jnp.repeat(kd, per, axis=-2)) / 2.0
        k = e[..., h:, :] + (
            jnp.mean(qd.reshape(*lead, g, per, d), axis=-2) + kd) / 2.0
        ones = jnp.ones((d,), jnp.float32)
        pos = jnp.asarray(positions)[..., None]
        r = self.rotary

        def finish(rows, scale):
            # sqrt(d) x / |x| is x / rms(x); then the rotary on the
            # head's first ``rotary`` values
            rows = rms_norm(rows, ones, self.eps) * scale
            return jnp.concatenate(
                [rotary_halves(rows[..., :r], pos, self.theta),
                 rows[..., r:]], axis=-1).astype(z.dtype)

        q = finish(q, 1.0)
        k = finish(k, _f32(params["tau"])[:, None])
        v_row = jnp.concatenate(
            [v[..., :self.row_width - self.late], v_prev], axis=-1)
        return q, k.reshape(*lead, self.row_width), v_row

    def prefill(self, params, x):
        """One sequence ``x`` (1, T, dim), every position's previous
        rows the sequence's own (zeros before the first) -> ``(y,
        k_rows, v_rows, state)``: the rows (1, T, G * d) what the cache
        stores, ``state`` the three rows (1, T, ·) a slot would carry
        after each position.  Dense causal attention, the softmax in
        float32."""
        import jax
        import jax.numpy as jnp

        _, t, _ = x.shape
        h, g, d = self.n_head, self.kv_heads, self.head_dim
        with jax.named_scope("dense"):
            z, v = self.project(params, x)
        with jax.named_scope("cca.mix"):
            c = self.conv0(params, z, before(z))
            late = v[..., self.row_width - self.late:]
            q, k_rows, v_rows = self.rows(
                params, z, c, before(c), v, before(late),
                jnp.arange(t)[None])
        with jax.named_scope("cca.attn"):
            qg = q[0].reshape(t, g, h // g, d)
            scores = jnp.einsum("tgrd,sgd->grts", qg,
                                k_rows[0].reshape(t, g, d),
                                preferred_element_type=jnp.float32) \
                / math.sqrt(d)
            scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None],
                               scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(v_rows.dtype)
            o = jnp.einsum("grts,sgd->tgrd", probs,
                           v_rows[0].reshape(t, g, d)).reshape(1, t, h * d)
        with jax.named_scope("dense"):
            y = jnp.matmul(o, params["w_o"].T)
        return y, k_rows, v_rows, (z, c, late)

    def decode(self, params, x, kp, vp, layer: int, tables, lengths, state):
        """One token a slot, ``x`` (S, dim) at positions ``lengths``,
        ``state`` the slots' ``(z, c, late value)`` of the position
        before: the token's K and V rows are formed from both and
        written, then its queries attend every row up to its own (one
        call of the page-walking kernel over the stacked buffers at
        ``layer``).  Returns ``(y, kp, vp, state)`` with the state of
        THIS position."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.ops.decode_attention import paged_decode_attention
        from bigdl_tpu.serving.cache import write_token_rows

        z_prev, c_prev, v_prev = state
        with jax.named_scope("dense"):
            z, v = self.project(params, x)
        with jax.named_scope("cca.mix"):
            c = self.conv0(params, z, z_prev)
            late = v[..., self.row_width - self.late:]
            q, k_rows, v_rows = self.rows(params, z, c, c_prev, v, v_prev,
                                          lengths)
        with jax.named_scope("kv_write"):
            kp = write_token_rows(kp, layer, tables, lengths, k_rows)
            vp = write_token_rows(vp, layer, tables, lengths, v_rows)
        with jax.named_scope("cca.attn"):
            o = paged_decode_attention(q, kp, vp, tables, lengths,
                                       layer=layer, page_size=kp.shape[2])
        with jax.named_scope("dense"):
            y = jnp.matmul(o.reshape(x.shape[0], -1), params["w_o"].T)
        return y, kp, vp, (z, c, late)


class ZayaRouter(AbstractModule):
    """The MLP router: a down-projection to ``hidden`` that adds the
    SAME token's router row of the layer below (``gamma`` times it), an
    RMS norm, two GELU layers and a softmax over the experts, all in
    float32; the balancing ``bias`` picks the expert and is not in its
    weight."""

    param_names = ("down", "gamma", "norm", "w1", "w2", "w3", "bias")

    def __init__(self, dim: int, hidden: int, n_experts: int,
                 eps: float = 1e-5, init: bool = True):
        super().__init__()
        self._config = dict(dim=dim, hidden=hidden, n_experts=n_experts,
                            eps=eps)
        self.dim, self.hidden = dim, hidden
        self.n_experts, self.eps = n_experts, eps
        for n in self.param_names:
            setattr(self, n, None)
        if init:
            self.reset()

    def reset(self):
        import jax.numpy as jnp

        r, wide = self.hidden, 1.5 / math.sqrt(self.hidden)
        self.down = _draw((r, self.dim))
        self.gamma = jnp.full((r,), 0.5, jnp.float32)
        self.norm = jnp.ones((r,), jnp.float32)
        self.w1 = _draw((r, r), wide)
        self.w2 = _draw((r, r), wide)
        self.w3 = _draw((self.n_experts, r), wide)
        self.bias = jnp.zeros((self.n_experts,), jnp.float32)
        return self

    def route(self, params, x, carry):
        """``x`` (N, dim), ``carry`` (N, hidden) the router rows of the
        layer below (None under the first) -> the chosen expert (N, 1),
        its weight (N, 1) float32 (``nn/experts.py``
        ``DroplessExperts.route``'s form) and this layer's router rows
        (N, hidden) float32."""
        import jax
        import jax.numpy as jnp

        def mm(a, w):
            return jnp.matmul(a, _f32(w).T, precision="highest")

        with jax.named_scope("moe.route"):
            r = mm(_f32(x), params["down"])
            if carry is not None:
                r = r + _f32(params["gamma"]) * carry
            hid = rms_norm(r, params["norm"], self.eps)
            hid = jax.nn.gelu(mm(hid, params["w1"]), approximate=False)
            hid = jax.nn.gelu(mm(hid, params["w2"]), approximate=False)
            s = jax.nn.softmax(mm(hid, params["w3"]), axis=-1)
            idx = jnp.argmax(s + _f32(params["bias"]), axis=-1)[:, None]
            return (idx.astype(jnp.int32),
                    jnp.take_along_axis(s, idx, axis=-1), r)


class ScaledResidual(AbstractModule):
    """``(s_r * x + b_r) + (s_o * y + b_o)``: learned vectors on the
    stream and on the sublayer's output."""

    param_names = ("stream_scale", "stream_bias", "out_scale", "out_bias")

    def __init__(self, dim: int, init: bool = True):
        super().__init__()
        self._config = dict(dim=dim)
        self.dim = dim
        for n in self.param_names:
            setattr(self, n, None)
        if init:
            self.reset()

    def reset(self):
        import jax.numpy as jnp

        self.stream_scale = jnp.ones((self.dim,), jnp.float32)
        self.out_scale = jnp.ones((self.dim,), jnp.float32)
        self.stream_bias = jnp.zeros((self.dim,), jnp.float32)
        self.out_bias = jnp.zeros((self.dim,), jnp.float32)
        return self

    def merge(self, params, x, y):
        """Float32 inside, ``x``'s dtype out."""
        return ((_f32(params["stream_scale"]) * _f32(x)
                 + _f32(params["stream_bias"]))
                + (_f32(params["out_scale"]) * _f32(y)
                   + _f32(params["out_bias"]))).astype(x.dtype)


class ZayaLayer(_Composite):
    """One decoder layer: CCA, then the routed top-1 expert layer, each
    under its scaled residual."""

    def __init__(self, cfg: dict, init: bool = True):
        super().__init__()
        self._config = dict(cfg)
        d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self._add_child("norm_attn", RMSNorm(d, eps, init=init))
        self._add_child("attn", CCAttention(
            d, cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"],
            int(cfg["head_dim"] * cfg["partial_rotary_factor"]), eps=eps,
            theta=cfg["rope_theta"], init=init))
        self._add_child("res_attn", ScaledResidual(d, init=init))
        self._add_child("norm_mlp", RMSNorm(d, eps, init=init))
        self._add_child("router", ZayaRouter(
            d, cfg["router_hidden_size"], cfg["num_experts"], eps=eps,
            init=init))
        self._add_child("moe", DroplessExperts(
            d, cfg["moe_intermediate_size"], cfg["num_experts"], 0,
            cfg["num_experts_per_tok"], scale=1.0,
            held=cfg["held_experts"], own_router=False, init=init))
        self._add_child("res_moe", ScaledResidual(d, init=init))

    def run(self, params, h, attend, mask, carry):
        """The layer's wiring, once, for every path: ``attend(x)`` is
        the attention over the normalised input; ``mask`` marks the real
        tokens for the expert layer's counts; ``carry`` is the router
        rows of the layer below (None under the first).  Returns ``(h',
        counts, carry')``."""
        c = self._children
        a = c["res_attn"].merge(
            params["res_attn"], h,
            attend(c["norm_attn"].apply(params["norm_attn"], {}, h)[0]))
        u = c["norm_mlp"].apply(params["norm_mlp"], {}, a)[0]
        flat = u.reshape(-1, u.shape[-1])
        idx, w, carry = c["router"].route(params["router"], flat, carry)
        (m, counts), _ = c["moe"].apply(
            params["moe"], {}, flat, routed=(idx, w),
            mask=None if mask is None else mask.reshape(-1))
        return (c["res_moe"].merge(params["res_moe"], a, m.reshape(u.shape)),
                counts, carry)


class Zaya(_Composite):
    """Decoder-only LM over (batch, seq) int tokens -> logits (batch,
    seq, vocab), the head tied to the embedding.  Sizes default to the
    published ones; a test, or a chip's share, overrides them by
    keyword."""

    def __init__(self, *, max_len: int = 2048, held_experts=None,
                 params: Optional[dict] = None, **sizes):
        super().__init__()
        unknown = set(sizes) - set(PUBLISHED)
        if unknown:
            raise TypeError(f"unknown sizes {sorted(unknown)}; the model "
                            f"takes {sorted(PUBLISHED)}")
        cfg = dict(PUBLISHED, **sizes)
        if (cfg["cca_time0"], cfg["cca_time1"],
                cfg["num_experts_per_tok"]) != (2, 2, 1):
            raise ValueError("convolutions of kernel 2 and top-1 experts "
                             "are what this model computes")
        cfg["max_len"] = int(max_len)
        cfg["held_experts"] = (
            (0, cfg["num_experts"]) if held_experts is None
            else (int(held_experts[0]), int(held_experts[1])))
        self._config = cfg
        self.vocab_size = cfg["vocab_size"]
        self.dim = cfg["hidden_size"]
        self.n_layer = cfg["num_hidden_layers"]
        init = params is None
        self._weight_free, self._given = not init, params
        self._add_child("embed", _Table(self.vocab_size, self.dim, init))
        for i in range(self.n_layer):
            self._add_child(f"l{i}", ZayaLayer(cfg, init=init))
        self._add_child("norm_f", RMSNorm(self.dim, cfg["rms_norm_eps"],
                                          init=init))

    def params(self):
        return self._given if self._weight_free else super().params()

    def set_params(self, params):
        """A model built around a caller's tree holds that tree and no
        copy: handing it another (or None) lets the old one go."""
        if self._weight_free:
            self._given = params
        else:
            super().set_params(params)

    @classmethod
    def from_config(cls, config: dict, params: Optional[dict] = None):
        """The model a configuration file in the published
        ``config.json`` spelling describes (the rotary's two numbers
        under ``rope_parameters.hybrid``; every ``layer_types`` entry
        ``hybrid``, no window); ``held_experts`` and ``max_len`` are the
        file's own keys."""
        kinds = config.get("layer_types")
        if kinds is not None and (
                len(kinds) != config["num_hidden_layers"]
                or set(kinds) != {"hybrid"}):
            raise ValueError("layer_types: one 'hybrid' a layer is what "
                             "this model computes")
        if config.get("sliding_window") is not None:
            raise ValueError("this model computes no window")
        sizes = {k: config[k] for k in PUBLISHED if k in config}
        rope = config.get("rope_parameters", {}).get("hybrid", {})
        for k in ("partial_rotary_factor", "rope_theta"):
            if k in rope:
                sizes[k] = rope[k]
        return cls(max_len=int(config.get("max_len", 2048)),
                   held_experts=config.get("held_experts"), params=params,
                   **sizes)

    # ------------------------------------------------------- full forward
    def _embed(self, params, tokens):
        import jax.numpy as jnp

        return jnp.take(params["embed"]["weight"], tokens.astype(jnp.int32),
                        axis=0)

    def _logits(self, params, x):
        import jax
        import jax.numpy as jnp

        h, _ = self._children["norm_f"].apply(params["norm_f"], {}, x)
        with jax.named_scope("dense"):
            return jnp.matmul(h, params["embed"]["weight"].T)

    def _layers(self, params, x, attend, mask):
        """Every layer over ``x``, the router rows handed up from layer
        to layer; ``attend(i, attn, p, xn)`` is layer ``i``'s attention.
        Returns the last layer's output and the summed routing
        counts."""
        counts = carry = None
        for i in range(self.n_layer):
            layer, p = self._children[f"l{i}"], params[f"l{i}"]
            x, n, carry = layer.run(
                p, x, lambda xn, i=i, layer=layer, p=p: attend(
                    i, layer._children["attn"], p["attn"], xn), mask, carry)
            counts = merge_counts(counts, n)
        return x, counts

    def apply(self, params, state, input, *, training=False, rng=None):
        """Logits at every position of ``input`` (batch, seq), each
        sequence on its own (the convolutions and the value shift look
        one position back along it)."""
        import jax.numpy as jnp

        outs = []
        for row in range(input.shape[0]):
            x, _ = self._layers(
                params, self._embed(params, input[row:row + 1]),
                lambda i, attn, p, xn: attn.prefill(p, xn)[0], None)
            outs.append(self._logits(params, x))
        return jnp.concatenate(outs, axis=0), state

    # ------------------------------------------------------------ serving
    def cache_spec(self, params) -> dict:
        """What ``serving.LMEngine`` builds its paged cache from: per-
        head K/V rows of ``kv_heads`` heads, two buffers, under
        ``heads`` query heads (the dtype is that of the weights it was
        given)."""
        layer = self._children["l0"]._children
        attn = layer["attn"]
        return {"layers": self.n_layer, "heads": attn.n_head,
                "kv_heads": attn.kv_heads, "head_dim": attn.head_dim,
                "row_width": attn.row_width, "buffers": 2,
                "max_len": self._config["max_len"],
                "dtype": params["embed"]["weight"].dtype,
                # the attention kernel's query rows a slot
                "attn_query_rows": attn.n_head,
                "expert_slots": self.n_layer * layer["moe"].n_held}

    def state_spec(self, params) -> dict:
        """What a slot carries beside its pages, a layer (module
        docstring): the engine keeps one ``(layers, max_batch, *shape)``
        array a shape."""
        return {"layers": self.n_layer,
                "shapes": self._children["l0"]._children["attn"]
                .state_shapes(),
                "dtype": params["embed"]["weight"].dtype}

    def paged_prefill(self, params, caches, prompt, t0, pages):
        """One prompt, ``prompt`` (1, bucket) zero-padded past ``t0``,
        into the pages ``pages`` (bucket // page_size,): every layer's K
        and V rows with one scatter a buffer and layer.  Returns
        ``(caches, logits (1, vocab) at position t0 - 1, counts,
        rows)``, ``rows`` one ``(layers, ·)`` array a declared shape:
        the state after position ``t0 - 1``, the prompt's last real
        token, whatever the bucket's padded tail computed."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from bigdl_tpu.serving.cache import write_prompt_pages

        kp, vp = caches
        bucket = prompt.shape[1]
        mask = (jnp.arange(bucket) < t0)[None, :]
        kept = []

        def attend(i, attn, p, xn):
            nonlocal kp, vp
            y, k_rows, v_rows, state = attn.prefill(p, xn)
            with jax.named_scope("kv_write"):
                kp = write_prompt_pages(kp, i, pages, k_rows[0])
                vp = write_prompt_pages(vp, i, pages, v_rows[0])
            with jax.named_scope("cca.mix"):
                kept.append([lax.dynamic_slice_in_dim(s[0], t0 - 1, 1)[0]
                             for s in state])
            return y

        x, counts = self._layers(params, self._embed(params, prompt),
                                 attend, mask)
        h = lax.dynamic_slice(x, (0, t0 - 1, 0), (1, 1, self.dim))
        rows = tuple(jnp.stack(part) for part in zip(*kept))
        return (kp, vp), self._logits(params, h)[:, 0, :], counts, rows

    def paged_decode(self, params, caches, tables, lengths, tokens, active,
                     *, state, page_size=None, qparams=None):
        """One token a slot over the paged K/V cache and the slots'
        state: ``(caches, logits (S, vocab), counts, state)``, the state
        that of the positions just computed (for every slot: the engine
        keeps an inactive slot's old one).  ``page_size`` is the cache's
        own (read from the buffer)."""
        import jax

        del page_size
        if qparams is not None:
            raise ValueError("Zaya offers no int8 decode")
        kp, vp = caches
        state = list(state)

        def attend(i, attn, p, xn):
            nonlocal kp, vp
            y, kp, vp, new = attn.decode(p, xn, kp, vp, i, tables, lengths,
                                         [s[i] for s in state])
            with jax.named_scope("cca.mix"):
                for j, rows in enumerate(new):
                    state[j] = state[j].at[i].set(
                        rows.astype(state[j].dtype))
            return y

        x, counts = self._layers(params, self._embed(params, tokens),
                                 attend, active)
        return (kp, vp), self._logits(params, x), counts, tuple(state)

    def __repr__(self):
        return (f"Zaya(vocab={self.vocab_size}, dim={self.dim}, "
                f"layers={self.n_layer})")


def build_zaya(config: Optional[dict] = None, params: Optional[dict] = None,
               **kw) -> Zaya:
    """From a configuration file's object, or from sizes by keyword."""
    if config is not None:
        return Zaya.from_config(config, params=params)
    return Zaya(params=params, **kw)


__all__ = ["CCAttention", "PUBLISHED", "ScaledResidual", "Zaya",
           "ZayaLayer", "ZayaRouter", "build_zaya", "before"]
