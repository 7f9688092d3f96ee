"""Ling-3.0-flash: a decoder whose layers differ in what they mix with
and in what a slot keeps for them.  Five layers of six run a
**delta-rule linear attention with a decay a channel** (KDA,
``nn/delta.py``: a slot keeps a ``d_k x d_v`` float32 matrix a head,
whatever its length, and no pages); the sixth runs **latent attention**
(``nn/latent.py``: a slot keeps one compressed row a token in pages, and
no state).  Behind either, a gated MLP in the leading dense layers and
elsewhere an expert layer whose router **chooses by groups**
(``nn/experts.py``), with one shared expert.

Source: ``huggingface.co/inclusionAI/Ling-3.0-flash-VL`` ``config.json``
(the language model; the vision tower and the MTP module are left out).
What that file does not state is marked *(assumed)* in
``benchmarks/reference/ling_3_flash_vl.py``, which has every layer's
equations; the names here are its names.

Layer ``i`` of the PUBLISHED model is latent attention where ``(i + 1)
% layer_group_size == 0`` and KDA elsewhere; a cut in depth says which
published layers it keeps (``kept_layers``), and a kept layer is what
its published index says.  Every layer: ``a = h + Mix(rms(h))``, ``h' =
a + F(rms(a))``.

**Serving.**  The first model whose layers differ in what a slot keeps:
:meth:`LingFlash.cache_spec` counts the latent-attention layers (one
640-lane row a token, one buffer) and :meth:`LingFlash.state_spec` the
KDA layers (``S`` 32 x 128 x 128, in ``kda_state_parts`` arrays, and the
convolution's 3 rows, float32, 2.25 MB a slot and layer), each indexed
by its own count.  A decode
step reads and writes every running slot's ``S`` once, in place (one
Pallas kernel a layer, ``ops/delta_state.py``), and leaves an idle
slot's bit for bit (``keeps_inactive``); a prompt runs the mixer's
chunked scan and hands over the state after its last REAL token; a
preempted request's second prefill rebuilds it from all its tokens, to
rounding.  Both entry points also return the step's routing counts.

**A chip's share**, weights brought by the caller, no weights drawn: as
``models/longcat_flash.py``.  A chip that holds one routing group of
every layer's experts states it as ``held_experts``.
"""

from __future__ import annotations

import functools
from typing import Optional

from bigdl_tpu.models.longcat_flash import _Table
from bigdl_tpu.nn.attention import _Composite
from bigdl_tpu.nn.delta import DeltaMixer
from bigdl_tpu.nn.experts import DroplessExperts, merge_counts
from bigdl_tpu.nn.latent import GatedMLP, LatentAttention, RMSNorm

#: the published ``config.json`` (the keys that shape the model)
PUBLISHED = dict(
    vocab_size=157184, hidden_size=2560, num_hidden_layers=42,
    intermediate_size=6144, first_k_dense_replace=2, layer_group_size=6,
    num_attention_heads=32, head_dim=128, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=6e6, rms_norm_eps=1e-6, short_conv_kernel_size=4,
    kda_lower_bound=-5.0, group_norm_size=1,
    num_experts=512, moe_intermediate_size=768, num_experts_per_tok=8,
    moe_shared_expert_intermediate_size=768, n_group=8, topk_group=4,
    routed_scaling_factor=2.5, norm_topk_prob=True,
    score_function="sigmoid")

#: what the configuration must say for this file to compute it
_FIXED = dict(q_lora_rank=None, num_kv_heads_for_linear_attn=0,
              linear_silu=True, use_mla_nope=False, rotary_dim=64,
              partial_rotary_factor=0.5, use_qk_norm=True, use_nGPT=False,
              scale_router_input=False, value_norm=False,
              up_proj_norm=False, no_kda_lora=True, use_kda_lora=False,
              kda_safe_gate=True, moe_router_enable_expert_bias=True,
              gated_attention_proj_granularity_type="head_wise")


class LingLayer(_Composite):
    """One layer: KDA (``latent=False``) or latent attention, then the
    dense MLP (``dense=True``) or the expert layer with its shared
    expert."""

    def __init__(self, cfg: dict, latent: bool, dense: bool,
                 init: bool = True):
        super().__init__()
        self._config = dict(cfg, latent=latent, dense=dense)
        d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.latent, self.dense = latent, dense
        self._add_child("norm_mix", RMSNorm(d, eps, init=init))
        if latent:
            self._add_child("attn", LatentAttention(
                d, cfg["num_attention_heads"], None, cfg["kv_lora_rank"],
                cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"], eps=eps, theta=cfg["rope_theta"],
                row_align=cfg["row_align"], kv_scale=1.0, head_gate=True,
                init=init))
        else:
            self._add_child("kda", DeltaMixer(
                d, cfg["num_attention_heads"], cfg["head_dim"],
                cfg["head_dim"], d_conv=cfg["short_conv_kernel_size"],
                lower_bound=cfg["kda_lower_bound"],
                norm_groups=cfg["group_norm_size"], chunk=cfg["kda_chunk"][0],
                sub=cfg["kda_chunk"][1],
                state_parts=cfg["kda_state_parts"], eps=eps, init=init))
        self._add_child("norm_mlp", RMSNorm(d, eps, init=init))
        if dense:
            self._add_child("mlp", GatedMLP(d, cfg["intermediate_size"],
                                            init=init))
        else:
            self._add_child("moe", DroplessExperts(
                d, cfg["moe_intermediate_size"], cfg["num_experts"], 0,
                cfg["num_experts_per_tok"],
                scale=cfg["routed_scaling_factor"],
                held=cfg["held_experts"], score=cfg["score_function"],
                renormalise=cfg["norm_topk_prob"],
                shared_hidden=cfg["moe_shared_expert_intermediate_size"],
                groups=(cfg["n_group"], cfg["topk_group"]), init=init))

    def run(self, params, h, mix, mask):
        """The layer's wiring, once, for every path: ``mix(x)`` is the
        layer's mixer over the normalised input (a prompt's, or one
        token a slot over what the slot keeps); ``h`` is (..., dim),
        ``mask`` (...) marks the real tokens for the expert layer's
        counts.  Returns ``(h', counts or None)``."""
        import jax

        c = self._children
        a = h + mix(c["norm_mix"].apply(params["norm_mix"], {}, h)[0])
        u = c["norm_mlp"].apply(params["norm_mlp"], {}, a)[0]
        if self.dense:
            with jax.named_scope("ffn"):
                return a + c["mlp"].apply(params["mlp"], {}, u)[0], None
        (m, counts), _ = c["moe"].apply(
            params["moe"], {}, u.reshape(-1, u.shape[-1]),
            mask=None if mask is None else mask.reshape(-1))
        return a + m.reshape(u.shape), counts


class LingFlash(_Composite):
    """Decoder-only LM over (batch, seq) int tokens -> logits (batch,
    seq, vocab), the head untied.  Sizes default to the published ones;
    a test, or a chip's share, overrides them by keyword.
    ``kept_layers`` are the published indices of the layers built (a cut
    in depth; default all ``num_hidden_layers``): the first
    ``first_k_dense_replace`` BUILT layers are dense.  ``kda_chunk`` is
    the prompt scan's chunk and sub-block and ``kda_state_parts`` the
    arrays a KDA layer's ``S`` is kept in (``nn/delta.py``: what keeps
    an engine's stacked buffer under 2 GiB), engine sizes."""

    def __init__(self, *, max_len: int = 2048, held_experts=None,
                 kept_layers=None, row_align: int = 128,
                 kda_chunk=(64, 16), kda_state_parts: int = 1,
                 params: Optional[dict] = None, **sizes):
        super().__init__()
        unknown = set(sizes) - set(PUBLISHED)
        if unknown:
            raise TypeError(f"unknown sizes {sorted(unknown)}; the model "
                            f"takes {sorted(PUBLISHED)}")
        cfg = dict(PUBLISHED, **sizes)
        cfg["max_len"] = int(max_len)
        cfg["row_align"] = int(row_align)
        cfg["kda_chunk"] = (int(kda_chunk[0]), int(kda_chunk[1]))
        cfg["kda_state_parts"] = int(kda_state_parts)
        cfg["held_experts"] = (
            (0, cfg["num_experts"]) if held_experts is None
            else (int(held_experts[0]), int(held_experts[1])))
        kept = tuple(range(cfg["num_hidden_layers"])) if kept_layers is None \
            else tuple(int(i) for i in kept_layers)
        if len(kept) != cfg["num_hidden_layers"] or list(kept) != sorted(
                set(kept)):
            raise ValueError(
                f"kept_layers {kept}: {cfg['num_hidden_layers']} published "
                "indices, ascending")
        cfg["kept_layers"] = kept
        self._config = cfg
        self.vocab_size = cfg["vocab_size"]
        self.dim = cfg["hidden_size"]
        self.n_layer = len(kept)
        init = params is None
        self._weight_free, self._given = not init, params
        self._add_child("embed", _Table(self.vocab_size, self.dim, init))
        #: a layer's index among the layers of its kind: the cached
        #: layer of a latent attention, the state layer of a KDA mixer
        self._own = []
        n_latent = n_kda = 0
        for i, published in enumerate(kept):
            latent = (published + 1) % cfg["layer_group_size"] == 0
            self._add_child(f"l{i}", LingLayer(
                cfg, latent, i < cfg["first_k_dense_replace"], init=init))
            self._own.append(n_latent if latent else n_kda)
            n_latent, n_kda = n_latent + latent, n_kda + (not latent)
        self.n_latent, self.n_kda = n_latent, n_kda
        if not n_latent or not n_kda:
            raise ValueError(
                f"kept_layers {kept} hold {n_kda} KDA and {n_latent} "
                "latent-attention layers: a slot keeps both pages and "
                "state, so keep one of each at least")
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
        ``held_experts`` ([lo, hi)) and, since ``num_experts`` then
        counts the experts held, the router's published width as
        ``router_experts``; a cut in depth states ``kept_layers``;
        ``max_len`` is the longest context served."""
        for k, want in _FIXED.items():
            if config.get(k, want) != want:
                raise ValueError(f"{k} = {config[k]!r}: this model "
                                 f"computes {want!r}")
        for k in ("expert_swiglu_limit_list",
                  "share_expert_swiglu_limit_list"):
            limits = config.get(k) or [0]
            kept = config.get("kept_layers", range(len(limits)))
            if any(limits[i] for i in kept if i < len(limits)):
                raise ValueError(f"{k}: a clamp on a kept layer's gated "
                                 "product is not computed here")
        sizes = {k: config[k] for k in PUBLISHED if k in config}
        sizes["num_experts"] = int(config.get(
            "router_experts", config["num_experts"]))
        return cls(max_len=int(config.get("max_len", 2048)),
                   held_experts=config.get("held_experts"),
                   kept_layers=config.get("kept_layers"),
                   kda_chunk=config.get("kda_chunk", (64, 16)),
                   kda_state_parts=config.get("kda_state_parts", 1),
                   params=params, **sizes)

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
            return jnp.matmul(h, params["head"]["weight"].T)

    def _layers(self, params, x, kda, attend, mask):
        """Every layer over ``x``; ``kda(j, mixer, p, n)`` is the
        ``j``-th KDA layer's mixer and ``attend(j, attn, p, n)`` the
        ``j``-th latent attention.  Returns ``(x, counts)``."""
        counts = None
        for i in range(self.n_layer):
            layer, p = self._children[f"l{i}"], params[f"l{i}"]
            run, name = (attend, "attn") if layer.latent else (kda, "kda")
            x, n = layer.run(
                p, x, functools.partial(run, self._own[i],
                                        layer._children[name], p[name]),
                mask)
            counts = merge_counts(counts, n) if n is not None else counts
        return x, counts

    def apply(self, params, state, input, *, training=False, rng=None):
        """Logits at every position of ``input`` (batch, seq), each
        sequence on its own from a zero state."""
        import jax.numpy as jnp

        outs = []
        for row in range(input.shape[0]):
            t = input.shape[1]
            x, _ = self._layers(
                params, self._embed(params, input[row:row + 1]),
                lambda j, kda, p, n: kda.scan(p, n[0], t)[0][None],
                lambda j, attn, p, n: attn.prefill(p, n)[0], None)
            outs.append(self._logits(params, x))
        return jnp.concatenate(outs, axis=0), state

    # ------------------------------------------------------------ serving
    def _child_of(self, latent: bool, name: str):
        return next(self._children[f"l{i}"]._children[name]
                    for i in range(self.n_layer)
                    if self._children[f"l{i}"].latent == latent)

    def cache_spec(self, params) -> dict:
        """What ``serving.LMEngine`` builds its paged cache from: the
        latent-attention layers' rows alone, one buffer (the dtype is
        that of the weights it was given)."""
        attn = self._child_of(True, "attn")
        moe = next(self._children[f"l{i}"]._children["moe"]
                   for i in range(self.n_layer)
                   if not self._children[f"l{i}"].dense)
        n_moe = self.n_layer - self._config["first_k_dense_replace"]
        return {"layers": self.n_latent, "row_width": attn.row_width,
                "buffers": 1, "max_len": self._config["max_len"],
                "dtype": params["embed"]["weight"].dtype,
                # the attention kernel's query rows a slot
                "attn_query_rows": attn.n_head,
                # held experts over the step's expert layers: what the
                # mean load of a held expert is taken over
                "expert_slots": n_moe * moe.n_held}

    def state_spec(self, params) -> dict:
        """What a slot carries beside its pages, a KDA layer (module
        docstring): ``S`` and the convolution's rows, in float32 (the
        rows hold the weights' dtype's values, kept exactly).  The
        step's update leaves a slot that did not run as it was
        (``keeps_inactive``): the engine adds no guard of its own."""
        import jax.numpy as jnp

        del params
        return {"layers": self.n_kda,
                "shapes": self._child_of(False, "kda").state_shapes(),
                "dtype": jnp.float32, "keeps_inactive": True}

    def paged_prefill(self, params, caches, prompt, t0, pages):
        """One prompt, ``prompt`` (1, bucket) zero-padded past ``t0``,
        into the pages ``pages`` (bucket // page_size,): a latent
        attention's rows with one scatter a layer, a KDA mixer by its
        chunked scan.  Returns ``(caches, logits (1, vocab) at position
        t0 - 1, counts, rows)``, ``rows`` one ``(KDA layers, ·)`` array a
        declared shape: the state after position ``t0 - 1``, the
        prompt's last real token, whatever the bucket's padded tail
        holds."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from bigdl_tpu.serving.cache import write_prompt_pages

        (buf,) = caches
        kept = []

        def kda(j, mixer, p, n):
            out, parts, rows = mixer.scan(p, n[0], t0)
            kept.append((*parts, rows))
            return out[None]

        def attend(j, attn, p, n):
            nonlocal buf
            y, rows = attn.prefill(p, n)
            with jax.named_scope("kv_write"):
                buf = write_prompt_pages(buf, j, pages, rows[0])
            return y

        mask = (jnp.arange(prompt.shape[1]) < t0)[None, :]
        x, counts = self._layers(params, self._embed(params, prompt), kda,
                                 attend, mask)
        h = lax.dynamic_slice(x, (0, t0 - 1, 0), (1, 1, self.dim))
        rows = tuple(jnp.stack(part) for part in zip(*kept))
        return (buf,), self._logits(params, h)[:, 0, :], counts, rows

    def paged_decode(self, params, caches, tables, lengths, tokens, active,
                     *, state, page_size=None, qparams=None):
        """One token a slot over the paged latent cache and the slots'
        state: ``(caches, logits (S, vocab), counts, state)``, the state
        advanced where ``active`` and untouched elsewhere.
        ``page_size`` is the cache's own (read from the buffer)."""
        del page_size
        if qparams is not None:
            raise ValueError("LingFlash offers no int8 decode")
        (buf,) = caches
        *states, rows = state

        def kda(j, mixer, p, n):
            nonlocal states, rows
            out, states, rows = mixer.step(p, n, states, rows, j, active)
            return out

        def attend(j, attn, p, n):
            nonlocal buf
            y, buf = attn.decode(p, n, buf, j, tables, lengths)
            return y

        x, counts = self._layers(params, self._embed(params, tokens), kda,
                                 attend, active)
        return (buf,), self._logits(params, x), counts, (*states, rows)

    def __repr__(self):
        return (f"LingFlash(vocab={self.vocab_size}, dim={self.dim}, "
                f"layers={self.n_layer}: {self.n_kda} KDA, "
                f"{self.n_latent} latent)")


def build_ling_flash(config: Optional[dict] = None,
                     params: Optional[dict] = None, **kw) -> LingFlash:
    """From a configuration file's object, or from sizes by keyword."""
    if config is not None:
        return LingFlash.from_config(config, params=params)
    return LingFlash(params=params, **kw)


__all__ = ["LingFlash", "LingLayer", "PUBLISHED", "build_ling_flash"]
