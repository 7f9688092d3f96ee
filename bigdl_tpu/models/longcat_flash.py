"""LongCat-Flash: a decoder of **shortcut-connected double layers**
with latent attention and a dropless expert layer that has
zero-compute experts.

Source: ``huggingface.co/meituan-longcat/LongCat-Flash-Chat``
``config.json``.  What that file does not state is marked *(assumed)*:
taken from the upstream modelling code from memory, unverified here.

One layer, ``h`` in, ``h''`` out (``A`` latent attention, ``mlp`` the
gated SiLU MLP *(SiLU assumed)*, ``M`` the expert layer; ``nn/latent.py``
and ``nn/experts.py`` have their equations):

    a   = h  + A_0(rms(h))
    u   = rms(a);  m = M(u)            # the shortcut: computed here ...
    h'  = a  + mlp_0(u)
    a'  = h' + A_1(rms(h'))
    h'' = a' + mlp_1(rms(a')) + m      # ... added here

so the expert layer's (in a deployment: its exchange's) latency hides
behind a whole attention and a dense MLP.  Every norm, attention and
MLP has weights of its own.  Rotary positions (interleaved pairs
*(assumed)*, ``rope_theta``, no frequency scaling) on ``qk_rope_head_dim``
of a head's dimensions; no learned positions.  A final ``rms`` and an
untied head *(untied assumed)*.

**Serving.**  The model tells ``serving.LMEngine`` what its cache is
(:meth:`LongCatFlash.cache_spec`: two cached attentions a layer, one
row of ``kv_lora_rank + qk_rope_head_dim`` values a token padded to
``row_align`` lanes (128: 576 -> 640), ONE buffer: there is no separate
V) and offers :meth:`paged_prefill` and
:meth:`paged_decode` over it.  Both also return the step's routing
counts (``nn/experts.py`` ``COUNT_NAMES``).

**A chip's share.**  ``held_experts=(lo, hi)`` gives the layer's expert
layers the weights of those routed experts only (``nn/experts.py``);
``vocab_size`` may be a slice of the published vocabulary.

``LongCatFlash(..., params=tree)`` builds the modules **without
drawing weights** and serves ``tree`` (the tree of
:meth:`LongCatFlash.params`): at the published widths one layer's
weights are 2.5 GB, and a caller that brings them must not pay for a
second set.
"""

from __future__ import annotations

from typing import Optional

from bigdl_tpu.nn.attention import _Composite
from bigdl_tpu.nn.experts import DroplessExperts, merge_counts
from bigdl_tpu.nn.latent import GatedMLP, LatentAttention, RMSNorm, _draw
from bigdl_tpu.nn.module import AbstractModule

#: the published ``config.json`` (the keys that shape the model)
PUBLISHED = dict(
    vocab_size=131072, hidden_size=6144, num_layers=28,
    num_attention_heads=64, q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    ffn_hidden_size=12288, expert_ffn_hidden_size=2048,
    n_routed_experts=512, zero_expert_num=256, moe_topk=12,
    routed_scaling_factor=6.0, rms_norm_eps=1e-5, rope_theta=1e7)


class _Table(AbstractModule):
    """A ``(rows, dim)`` matrix: the token embedding or the head."""

    param_names = ("weight",)

    def __init__(self, rows: int, dim: int, init: bool = True):
        super().__init__()
        self._config = dict(rows=rows, dim=dim)
        self.rows, self.dim = rows, dim
        self.weight = None
        if init:
            self.reset()

    def reset(self):
        self.weight = _draw((self.rows, self.dim))
        return self


class LongCatLayer(_Composite):
    """One shortcut-connected double layer (module docstring)."""

    def __init__(self, cfg: dict, init: bool = True):
        super().__init__()
        self._config = dict(cfg)
        d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        for j in (0, 1):
            self._add_child(f"norm_attn{j}", RMSNorm(d, eps, init=init))
            self._add_child(f"attn{j}", LatentAttention(
                d, cfg["num_attention_heads"], cfg["q_lora_rank"],
                cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                cfg["qk_rope_head_dim"], cfg["v_head_dim"], eps=eps,
                theta=cfg["rope_theta"], row_align=cfg["row_align"],
                init=init))
            self._add_child(f"norm_mlp{j}", RMSNorm(d, eps, init=init))
            self._add_child(f"mlp{j}", GatedMLP(
                d, cfg["ffn_hidden_size"], init=init))
        self._add_child("moe", DroplessExperts(
            d, cfg["expert_ffn_hidden_size"], cfg["n_routed_experts"],
            cfg["zero_expert_num"], cfg["moe_topk"],
            scale=cfg["routed_scaling_factor"], held=cfg["held_experts"],
            init=init))

    def run(self, params, h, attend, mask):
        """The layer's wiring, once, for every path: ``attend(j, x)``
        is attention ``j`` over the normalised input ``x`` (full-prefix
        or one token over the cache); ``h`` is (..., dim), ``mask``
        (...) marks the real tokens for the expert layer's counts."""
        import jax

        c = self._children

        def norm(name, x):
            return c[name].apply(params[name], {}, x)[0]

        def mlp(name, x):
            with jax.named_scope("ffn"):
                return c[name].apply(params[name], {}, x)[0]

        a = h + attend(0, norm("norm_attn0", h))
        u = norm("norm_mlp0", a)
        flat = u.reshape(-1, u.shape[-1])
        (m, counts), _ = c["moe"].apply(
            params["moe"], {}, flat,
            mask=None if mask is None else mask.reshape(-1))
        h1 = a + mlp("mlp0", u)
        a1 = h1 + attend(1, norm("norm_attn1", h1))
        out = a1 + mlp("mlp1", norm("norm_mlp1", a1)) + m.reshape(u.shape)
        return out, counts


class LongCatFlash(_Composite):
    """Decoder-only LM over (batch, seq) int tokens -> logits (batch,
    seq, vocab).  Sizes default to the published ones; a test, or a
    chip's share, overrides them by keyword."""

    def __init__(self, *, max_len: int = 2048, held_experts=None,
                 row_align: int = 128, params: Optional[dict] = None,
                 **sizes):
        super().__init__()
        unknown = set(sizes) - set(PUBLISHED)
        if unknown:
            raise TypeError(f"unknown sizes {sorted(unknown)}; the model "
                            f"takes {sorted(PUBLISHED)}")
        cfg = dict(PUBLISHED, **sizes)
        cfg["max_len"] = int(max_len)
        cfg["row_align"] = int(row_align)
        cfg["held_experts"] = (
            (0, cfg["n_routed_experts"]) if held_experts is None
            else (int(held_experts[0]), int(held_experts[1])))
        self._config = cfg
        self.vocab_size = cfg["vocab_size"]
        self.dim = cfg["hidden_size"]
        self.n_layer = cfg["num_layers"]
        init = params is None
        self._weight_free, self._given = not init, params
        self._add_child("embed", _Table(self.vocab_size, self.dim, init))
        for i in range(self.n_layer):
            self._add_child(f"l{i}", LongCatLayer(cfg, init=init))
        self._add_child("norm_f", RMSNorm(self.dim, cfg["rms_norm_eps"],
                                          init=init))
        self._add_child("head", _Table(self.vocab_size, self.dim, init))

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
        ``config.json`` spelling describes.  A chip's share states
        ``held_experts`` ([lo, hi)) and, since ``n_routed_experts`` then
        counts the experts held, the router's published width as
        ``router_experts``; ``max_len`` is the longest context served."""
        sizes = {k: config[k] for k in PUBLISHED if k in config}
        sizes["n_routed_experts"] = int(config.get(
            "router_experts", config["n_routed_experts"]))
        return cls(max_len=int(config.get("max_len", 2048)),
                   held_experts=config.get("held_experts"),
                   params=params, **sizes)

    # ------------------------------------------------------- full forward
    def _logits(self, params, x):
        import jax
        import jax.numpy as jnp

        h, _ = self._children["norm_f"].apply(params["norm_f"], {}, x)
        with jax.named_scope("dense"):
            return jnp.matmul(h, params["head"]["weight"].T)

    def apply(self, params, state, input, *, training=False, rng=None):
        import jax.numpy as jnp

        c = self._children
        x = jnp.take(params["embed"]["weight"], input.astype(jnp.int32),
                     axis=0)
        for i in range(self.n_layer):
            layer, p = c[f"l{i}"], params[f"l{i}"]

            def attend(j, xn, layer=layer, p=p):
                return layer._children[f"attn{j}"].prefill(
                    p[f"attn{j}"], xn)[0]

            x, _ = layer.run(p, x, attend, None)
        return self._logits(params, x), state

    # ------------------------------------------------------------ serving
    def cache_spec(self, params) -> dict:
        """What ``serving.LMEngine`` builds its paged cache from (the
        dtype is that of the weights it was given)."""
        layer = self._children["l0"]._children
        return {"layers": 2 * self.n_layer,
                "row_width": layer["attn0"].row_width,
                "buffers": 1, "max_len": self._config["max_len"],
                "dtype": params["embed"]["weight"].dtype,
                # the attention kernel's query rows a slot
                "attn_query_rows": layer["attn0"].n_head,
                # held experts over the step's expert layers: what the
                # mean load of a held expert is taken over
                "expert_slots": self.n_layer * layer["moe"].n_held}

    def paged_prefill(self, params, caches, prompt, t0, pages):
        """One prompt, ``prompt`` (1, bucket) zero-padded past ``t0``,
        into the pages ``pages`` (bucket // page_size,): writes every
        cached layer's rows with one scatter each and returns
        ``(caches, logits (1, vocab) at position t0 - 1, counts)``."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from bigdl_tpu.serving.cache import write_prompt_pages

        (buf,) = caches
        c = self._children
        bucket = prompt.shape[1]
        mask = (jnp.arange(bucket) < t0)[None, :]
        x = jnp.take(params["embed"]["weight"], prompt, axis=0)
        counts = None
        for i in range(self.n_layer):
            layer, p = c[f"l{i}"], params[f"l{i}"]

            def attend(j, xn, i=i, layer=layer, p=p):
                nonlocal buf
                y, rows = layer._children[f"attn{j}"].prefill(
                    p[f"attn{j}"], xn)
                with jax.named_scope("kv_write"):
                    buf = write_prompt_pages(buf, 2 * i + j, pages, rows[0])
                return y

            x, n = layer.run(p, x, attend, mask)
            counts = merge_counts(counts, n)
        h = lax.dynamic_slice(x, (0, t0 - 1, 0), (1, 1, self.dim))
        return (buf,), self._logits(params, h)[:, 0, :], counts

    def paged_decode(self, params, caches, tables, lengths, tokens, active,
                     *, page_size=None, qparams=None):
        """One token a slot over the paged latent cache: ``(caches,
        logits (B, vocab), counts)``.  ``page_size`` is the cache's own
        (read from the buffer)."""
        import jax.numpy as jnp

        del page_size
        if qparams is not None:
            raise ValueError("LongCatFlash offers no int8 decode")
        (buf,) = caches
        c = self._children
        x = jnp.take(params["embed"]["weight"], tokens, axis=0)
        counts = None
        for i in range(self.n_layer):
            layer, p = c[f"l{i}"], params[f"l{i}"]

            def attend(j, xn, i=i, layer=layer, p=p):
                nonlocal buf
                y, buf = layer._children[f"attn{j}"].decode(
                    p[f"attn{j}"], xn, buf, 2 * i + j, tables, lengths)
                return y

            x, n = layer.run(p, x, attend, active)
            counts = merge_counts(counts, n)
        return (buf,), self._logits(params, x), counts

    def __repr__(self):
        return (f"LongCatFlash(vocab={self.vocab_size}, dim={self.dim}, "
                f"layers={self.n_layer})")


def build_longcat_flash(config: Optional[dict] = None,
                        params: Optional[dict] = None,
                        **kw) -> LongCatFlash:
    """From a configuration file's object, or from sizes by keyword."""
    if config is not None:
        return LongCatFlash.from_config(config, params=params)
    return LongCatFlash(params=params, **kw)


__all__ = ["LongCatFlash", "LongCatLayer", "PUBLISHED",
           "build_longcat_flash"]
