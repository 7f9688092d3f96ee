"""Olmo-Hybrid: a decoder whose layers differ in what they mix with and
in what a slot keeps for them.  Three layers of four run a **gated
delta rule** (``nn/delta.py`` ``GatedDeltaMixer``: a delta-rule linear
attention with ONE decay a head; a slot keeps a ``d_k x d_v`` float32
matrix a head, 30 of 96 x 192, whatever its length, and no pages); the
fourth runs a **full attention** of 30 heads over 30 key heads of 128 (a
slot keeps a K and a V row of 3840 values a token in pages, and no
state).  Behind either a gated MLP.  The family's REORDERED norm: a
sublayer reads the stream as it is and what it adds is normalised, ``x
+ rms(F(x))``.

Source: ``huggingface.co/allenai/Olmo-Hybrid-7B`` ``config.json``
(``model_type`` ``olmo_hybrid``).  What that file does not state is
marked *(assumed)* in ``benchmarks/reference/olmo_hybrid_7b.py``, which
has every layer's equations; the names here are its names.

Layer ``i`` of the PUBLISHED model is what ``layer_types[i]`` says; a
cut in depth says which published layers it keeps (``kept_layers``),
and a kept layer is what its published index says.

**Serving.**  As ``models/ling_flash.py``: :meth:`OlmoHybrid.cache_spec`
counts the full-attention layers (per-head K/V rows, two buffers) and
:meth:`OlmoHybrid.state_spec` the linear ones (``S`` kept ``(96, 30 x
192)``, every head's values side by side along the lanes, because a
``(.., 96, 192)`` array is stored a third larger than it is; and the
convolution's 3 rows; float32, 2.35 MB a slot and layer), each indexed
by its own count.  A decode step reads and writes every running slot's
``S`` once, in place (one Pallas kernel a layer, ``ops/delta_state.py``
``head_decay_update``), and leaves an idle slot's bit for bit
(``keeps_inactive``); its full attention reads the slots' rows where
they lie (``ops/decode_attention.py``: one query row a key head goes
through the page stream once the pool is too large to gather); a prompt
runs the mixer's chunked scan and hands over the state after its last
REAL token; a preempted request's second prefill rebuilds it from all
its tokens, to rounding.

Weights brought by the caller, no weights drawn: as
``models/longcat_flash.py``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

from bigdl_tpu.models.longcat_flash import _Table
from bigdl_tpu.nn.attention import _Composite
from bigdl_tpu.nn.delta import GatedDeltaMixer
from bigdl_tpu.nn.latent import GatedMLP, RMSNorm, _draw, rms_norm
from bigdl_tpu.nn.module import AbstractModule

_PERIOD = ("linear_attention",) * 3 + ("full_attention",)

#: the published ``config.json`` (the keys that shape the model)
PUBLISHED = dict(
    vocab_size=100352, hidden_size=3840, intermediate_size=11008,
    num_hidden_layers=32, num_attention_heads=30, num_key_value_heads=30,
    rms_norm_eps=1e-6, layer_types=_PERIOD * 8,
    linear_num_key_heads=30, linear_num_value_heads=30,
    linear_key_head_dim=96, linear_value_head_dim=192,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True)

#: what the configuration must say for this file to compute it
_FIXED = dict(hidden_act="silu", attention_bias=False,
              tie_word_embeddings=False)


class NormedAttention(AbstractModule):
    """``H`` query heads over ``G`` key/value heads of ``d``; the query
    and the key projection each under ONE RMS norm over the whole
    projection (not a head's), no rotary, no bias."""

    param_names = ("wq", "wk", "wv", "q_norm", "k_norm", "wo")

    def __init__(self, dim: int, n_head: int, kv_heads: int, head_dim: int,
                 eps: float = 1e-6, init: bool = True):
        super().__init__()
        if n_head % kv_heads:
            raise ValueError(f"{n_head} query heads over {kv_heads} "
                             "key/value heads")
        self._config = dict(dim=dim, n_head=n_head, kv_heads=kv_heads,
                            head_dim=head_dim, eps=eps)
        self.dim, self.n_head, self.kv_heads = dim, n_head, kv_heads
        self.head_dim, self.eps = head_dim, eps
        #: width of a token's cached K (or V) row
        self.row_width = kv_heads * head_dim
        for n in self.param_names:
            setattr(self, n, None)
        if init:
            self.reset()

    def reset(self):
        import jax.numpy as jnp

        h, g, d = self.n_head, self.kv_heads, self.head_dim
        self.wq = _draw((h * d, self.dim))
        self.wk = _draw((g * d, self.dim))
        self.wv = _draw((g * d, self.dim))
        self.q_norm = jnp.ones((h * d,), jnp.float32)
        self.k_norm = jnp.ones((g * d,), jnp.float32)
        self.wo = _draw((self.dim, h * d))
        return self

    def project(self, params, x):
        """``x`` (..., dim) -> the normed query (..., H, d) and the
        token's K (normed) and V rows (..., G * d)."""
        import jax.numpy as jnp

        q = rms_norm(jnp.matmul(x, params["wq"].T), params["q_norm"],
                     self.eps)
        k = rms_norm(jnp.matmul(x, params["wk"].T), params["k_norm"],
                     self.eps)
        return (q.reshape(*x.shape[:-1], self.n_head, self.head_dim), k,
                jnp.matmul(x, params["wv"].T))

    def prefill(self, params, x):
        """One sequence ``x`` (1, T, dim) -> ``(y, k_rows, v_rows)``,
        the rows (1, T, G * d) what the cache stores.  Dense causal
        attention, the softmax in float32."""
        import jax
        import jax.numpy as jnp

        _, t, _ = x.shape
        h, g, d = self.n_head, self.kv_heads, self.head_dim
        with jax.named_scope("dense"):
            q, k_rows, v_rows = self.project(params, x)
        with jax.named_scope("attn"):
            scores = jnp.einsum("tgrd,sgd->grts",
                                q[0].reshape(t, g, h // g, d),
                                k_rows[0].reshape(t, g, d),
                                preferred_element_type=jnp.float32) \
                / math.sqrt(d)
            scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None],
                               scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(v_rows.dtype)
            o = jnp.einsum("grts,sgd->tgrd", probs,
                           v_rows[0].reshape(t, g, d)).reshape(1, t, h * d)
        with jax.named_scope("dense"):
            return jnp.matmul(o, params["wo"].T), k_rows, v_rows

    def decode(self, params, x, kp, vp, layer: int, tables, lengths):
        """One token a slot, ``x`` (S, dim) at positions ``lengths``: its
        K and V rows are written, then its queries attend every row up
        to its own over the stacked buffers at ``layer``.  Returns
        ``(y, kp, vp)``."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.ops.decode_attention import paged_decode_attention
        from bigdl_tpu.serving.cache import write_token_rows

        with jax.named_scope("dense"):
            q, k_rows, v_rows = self.project(params, x)
        with jax.named_scope("kv_write"):
            kp = write_token_rows(kp, layer, tables, lengths, k_rows)
            vp = write_token_rows(vp, layer, tables, lengths, v_rows)
        with jax.named_scope("attn"):
            o = paged_decode_attention(q, kp, vp, tables, lengths,
                                       layer=layer, page_size=kp.shape[2])
        with jax.named_scope("dense"):
            y = jnp.matmul(o.reshape(x.shape[0], -1), params["wo"].T)
        return y, kp, vp

    def update_output_pure(self, params, input, *, training=False, rng=None):
        """``input`` (batch, T, dim) -> (batch, T, dim), every sequence
        on its own."""
        import jax.numpy as jnp

        return jnp.concatenate([self.prefill(params, seq[None])[0]
                                for seq in input])

    def __repr__(self):
        return (f"NormedAttention({self.dim} -> {self.n_head} over "
                f"{self.kv_heads} x {self.head_dim})")


class OlmoHybridLayer(_Composite):
    """One layer: the gated delta rule (``full=False``) or full
    attention, then the gated MLP; each sublayer's output normalised
    before it is added."""

    def __init__(self, cfg: dict, full: bool, init: bool = True):
        super().__init__()
        self._config = dict(cfg, full=full)
        d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.full = full
        if full:
            heads = cfg["num_attention_heads"]
            self._add_child("attn", NormedAttention(
                d, heads, cfg["num_key_value_heads"], d // heads, eps=eps,
                init=init))
        else:
            self._add_child("gdn", GatedDeltaMixer(
                d, cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                cfg["linear_value_head_dim"],
                d_conv=cfg["linear_conv_kernel_dim"],
                beta_max=2.0 if cfg["linear_allow_neg_eigval"] else 1.0,
                chunk=cfg["gdn_chunk"], eps=eps, init=init))
        self._add_child("norm_mix", RMSNorm(d, eps, init=init))
        self._add_child("mlp", GatedMLP(d, cfg["intermediate_size"],
                                        init=init))
        self._add_child("norm_mlp", RMSNorm(d, eps, init=init))

    def run(self, params, h, mix):
        """The layer's wiring, once, for every path: ``mix(x)`` is the
        layer's mixer over the stream (a prompt's, or one token a slot
        over what the slot keeps)."""
        import jax

        c = self._children
        a = h + c["norm_mix"].apply(params["norm_mix"], {}, mix(h))[0]
        with jax.named_scope("ffn"):
            m = c["mlp"].apply(params["mlp"], {}, a)[0]
            return a + c["norm_mlp"].apply(params["norm_mlp"], {}, m)[0]


class OlmoHybrid(_Composite):
    """Decoder-only LM over (batch, seq) int tokens -> logits (batch,
    seq, vocab), the head untied.  Sizes default to the published ones;
    a test, or a cut in depth, overrides them by keyword.
    ``kept_layers`` are the published indices (into ``layer_types``) of
    the layers built (default the first ``num_hidden_layers``).
    ``gdn_chunk`` is the prompt scan's chunk, an engine size."""

    def __init__(self, *, max_len: int = 2048, kept_layers=None,
                 gdn_chunk: int = 64, params: Optional[dict] = None,
                 **sizes):
        super().__init__()
        unknown = set(sizes) - set(PUBLISHED)
        if unknown:
            raise TypeError(f"unknown sizes {sorted(unknown)}; the model "
                            f"takes {sorted(PUBLISHED)}")
        cfg = dict(PUBLISHED, **sizes)
        cfg["layer_types"] = tuple(cfg["layer_types"])
        cfg["max_len"], cfg["gdn_chunk"] = int(max_len), int(gdn_chunk)
        kept = tuple(range(cfg["num_hidden_layers"])) if kept_layers is None \
            else tuple(int(i) for i in kept_layers)
        if len(kept) != cfg["num_hidden_layers"] or list(kept) != sorted(
                set(kept)) or kept[-1] >= len(cfg["layer_types"]):
            raise ValueError(
                f"kept_layers {kept}: {cfg['num_hidden_layers']} published "
                f"indices under {len(cfg['layer_types'])}, ascending")
        kinds = [cfg["layer_types"][i] for i in kept]
        strange = set(kinds) - set(_PERIOD)
        if strange:
            raise ValueError(f"layer_types {sorted(strange)}: this model "
                             f"builds {sorted(set(_PERIOD))}")
        if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
            raise ValueError(
                f"{cfg['linear_num_key_heads']} linear key heads under "
                f"{cfg['linear_num_value_heads']} value heads: a key head "
                "shared between value heads is not computed here")
        cfg["kept_layers"] = kept
        self._config = cfg
        self.vocab_size = cfg["vocab_size"]
        self.dim = cfg["hidden_size"]
        self.n_layer = len(kept)
        init = params is None
        self._weight_free, self._given = not init, params
        self._add_child("embed", _Table(self.vocab_size, self.dim, init))
        #: a layer's index among the layers of its kind: the cached
        #: layer of a full attention, the state layer of a linear one
        self._own = []
        n_full = n_linear = 0
        for i, kind in enumerate(kinds):
            full = kind == "full_attention"
            self._add_child(f"l{i}", OlmoHybridLayer(cfg, full, init=init))
            self._own.append(n_full if full else n_linear)
            n_full, n_linear = n_full + full, n_linear + (not full)
        self.n_full, self.n_linear = n_full, n_linear
        if not n_full or not n_linear:
            raise ValueError(
                f"kept_layers {kept} hold {n_linear} linear and {n_full} "
                "full-attention layers: a slot keeps both state and "
                "pages, so keep one of each at least")
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
        ``config.json`` spelling describes.  A cut in depth states
        ``kept_layers``; ``max_len`` is the longest context served,
        ``gdn_chunk`` the prompt scan's chunk."""
        for k, want in _FIXED.items():
            if config.get(k, want) != want:
                raise ValueError(f"{k} = {config[k]!r}: this model "
                                 f"computes {want!r}")
        if (config.get("rope_parameters") or {}).get("rope_theta") \
                is not None:
            raise ValueError(
                "rope_parameters.rope_theta = "
                f"{config['rope_parameters']['rope_theta']!r}: this model "
                "rotates nothing (the published value is null)")
        if config.get("head_dim") not in (None, config["hidden_size"]
                                          // config["num_attention_heads"]):
            raise ValueError(f"head_dim = {config['head_dim']!r}: this "
                             "model's heads are hidden_size over the heads")
        sizes = {k: config[k] for k in PUBLISHED if k in config}
        return cls(max_len=int(config.get("max_len", 2048)),
                   kept_layers=config.get("kept_layers"),
                   gdn_chunk=int(config.get("gdn_chunk", 64)),
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

    def _layers(self, params, x, gdn, attend):
        """Every layer over ``x``; ``gdn(j, mixer, p, n)`` is the
        ``j``-th linear layer's mixer and ``attend(j, attn, p, n)`` the
        ``j``-th full attention."""
        for i in range(self.n_layer):
            layer, p = self._children[f"l{i}"], params[f"l{i}"]
            run, name = (attend, "attn") if layer.full else (gdn, "gdn")
            x = layer.run(p, x, functools.partial(
                run, self._own[i], layer._children[name], p[name]))
        return x

    def apply(self, params, state, input, *, training=False, rng=None):
        """Logits at every position of ``input`` (batch, seq), each
        sequence on its own from a zero state."""
        import jax.numpy as jnp

        outs = []
        for row in range(input.shape[0]):
            t = input.shape[1]
            x = self._layers(
                params, self._embed(params, input[row:row + 1]),
                lambda j, gdn, p, n: gdn.scan(p, n[0], t)[0][None],
                lambda j, attn, p, n: attn.prefill(p, n)[0])
            outs.append(self._logits(params, x))
        return jnp.concatenate(outs, axis=0), state

    # ------------------------------------------------------------ serving
    def _child_of(self, full: bool, name: str):
        return next(self._children[f"l{i}"]._children[name]
                    for i in range(self.n_layer)
                    if self._children[f"l{i}"].full == full)

    def cache_spec(self, params) -> dict:
        """What ``serving.LMEngine`` builds its paged cache from: the
        full-attention layers' per-head K/V rows alone, two buffers (the
        dtype is that of the weights it was given)."""
        attn = self._child_of(True, "attn")
        return {"layers": self.n_full, "heads": attn.n_head,
                "kv_heads": attn.kv_heads, "head_dim": attn.head_dim,
                "row_width": attn.row_width, "buffers": 2,
                "max_len": self._config["max_len"],
                "dtype": params["embed"]["weight"].dtype,
                # the page stream's query rows a slot
                "attn_query_rows": attn.n_head}

    def state_spec(self, params) -> dict:
        """What a slot carries beside its pages, a linear layer (module
        docstring): ``S`` and the convolution's rows, in float32 (the
        rows hold the weights' dtype's values, kept exactly).  The
        step's update leaves a slot that did not run as it was
        (``keeps_inactive``): the engine adds no guard of its own."""
        import jax.numpy as jnp

        del params
        return {"layers": self.n_linear,
                "shapes": self._child_of(False, "gdn").state_shapes(),
                "dtype": jnp.float32, "keeps_inactive": True}

    def paged_prefill(self, params, caches, prompt, t0, pages):
        """One prompt, ``prompt`` (1, bucket) zero-padded past ``t0``,
        into the pages ``pages`` (bucket // page_size,): a full
        attention's K and V rows with one scatter a buffer and layer, a
        linear layer by its chunked scan.  Returns ``(caches, logits (1,
        vocab) at position t0 - 1, None, rows)``, ``rows`` one ``(linear
        layers, ·)`` array a declared shape: the state after position
        ``t0 - 1``, the prompt's last real token, whatever the bucket's
        padded tail holds."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from bigdl_tpu.serving.cache import write_prompt_pages

        kp, vp = caches
        kept = []

        def gdn(j, mixer, p, n):
            out, parts, rows = mixer.scan(p, n[0], t0)
            kept.append((*parts, rows))
            return out[None]

        def attend(j, attn, p, n):
            nonlocal kp, vp
            y, k_rows, v_rows = attn.prefill(p, n)
            with jax.named_scope("kv_write"):
                kp = write_prompt_pages(kp, j, pages, k_rows[0])
                vp = write_prompt_pages(vp, j, pages, v_rows[0])
            return y

        x = self._layers(params, self._embed(params, prompt), gdn, attend)
        h = lax.dynamic_slice(x, (0, t0 - 1, 0), (1, 1, self.dim))
        rows = tuple(jnp.stack(part) for part in zip(*kept))
        return (kp, vp), self._logits(params, h)[:, 0, :], None, rows

    def paged_decode(self, params, caches, tables, lengths, tokens, active,
                     *, state, page_size=None, qparams=None):
        """One token a slot over the paged K/V cache and the slots'
        state: ``(caches, logits (S, vocab), None, state)``, the state
        advanced where ``active`` and untouched elsewhere.
        ``page_size`` is the cache's own (read from the buffer)."""
        del page_size
        if qparams is not None:
            raise ValueError("OlmoHybrid offers no int8 decode")
        kp, vp = caches
        *states, rows = state

        def gdn(j, mixer, p, n):
            nonlocal states, rows
            out, states, rows = mixer.step(p, n, states, rows, j, active)
            return out

        def attend(j, attn, p, n):
            nonlocal kp, vp
            y, kp, vp = attn.decode(p, n, kp, vp, j, tables, lengths)
            return y

        x = self._layers(params, self._embed(params, tokens), gdn, attend)
        return (kp, vp), self._logits(params, x), None, (*states, rows)

    def __repr__(self):
        return (f"OlmoHybrid(vocab={self.vocab_size}, dim={self.dim}, "
                f"layers={self.n_layer}: {self.n_linear} linear, "
                f"{self.n_full} full)")


def build_olmo_hybrid(config: Optional[dict] = None,
                      params: Optional[dict] = None, **kw) -> OlmoHybrid:
    """From a configuration file's object, or from sizes by keyword."""
    if config is not None:
        return OlmoHybrid.from_config(config, params=params)
    return OlmoHybrid(params=params, **kw)


__all__ = ["NormedAttention", "OlmoHybrid", "OlmoHybridLayer", "PUBLISHED",
           "build_olmo_hybrid"]
