"""JoyAI-LLM-Flash: a decoder with latent attention, **one leading
dense layer**, then expert layers with a **sigmoid router and a shared
expert**, and a **prediction layer** that drafts the token after next,
so that a decode step verifies two positions a slot and yields one or
two tokens.

Source: ``huggingface.co/jdopensource/JoyAI-LLM-Flash`` ``config.json``
(``model_type`` ``joyai_llm_flash``).  What that file does not state is
marked *(assumed)*: taken from modelling code of the same family from
memory, unverified here.

Every layer (``A`` latent attention WITHOUT LongCat's
``sqrt(hidden / rank)`` factors, ``nn/latent.py``):

    a  = h + A(rms(h))
    h' = a + F(rms(a))

``F`` is a gated SiLU MLP of ``intermediate_size`` in the first
``first_k_dense_replace`` layers and the expert layer in the others
(``nn/experts.py``: ``s = sigmoid(W_r x)`` in float32, the ``top_k`` of
``s + b``, weights renormalised over the chosen and multiplied by
``routed_scaling_factor``, plus the shared expert ``S(x)``; ``n_group``
1 makes the group stage the identity).  Rotary positions on
``qk_rope_head_dim`` of a head's dimensions, interleaved pairs, no
frequency scaling.  A final ``rms`` and an untied head.

**The prediction layer** (``num_nextn_predict_layers`` 1), at position
``i`` with the last layer's output ``h_i`` (before the final norm
*(assumed)*) and the NEXT token ``x_{i+1}``:

    u = W_eh [rms_e(Emb(x_{i+1})) ; rms_h(h_i)]     # 2 dim -> dim, the
                                                    # order *(assumed)*
    g = one decoder layer of the expert kind over u, with latent cache
        rows of its own (cached layer ``n_layer``)
    logits for x_{i+2} = head(rms_p(g))             # the model's head

Embedding and head are the main model's.

**Serving.**  Beside ``cache_spec`` the model declares a draft
(:meth:`JoyAIFlash.draft_spec`: 2 tokens a step), and its
``paged_prefill`` / ``paged_decode`` take the engine's ``pick`` and
return the tokens themselves (``serving/engine.py`` "What the engine
asks of a model"): one decode step runs the main model over positions
``t, t+1`` with inputs ``x_t`` and the draft ``d``, picks ``y1, y2``,
accepts ``y2`` where ``y1 == d`` and the slot owes two tokens, then
runs the prediction layer on ``(h_t, y1)`` and ``(h_{t+1}, y2)``; the
draft of the next step is its output at the last accepted position.
The row written at ``t+1`` under a rejected draft (the main model's and
the prediction layer's) is overwritten by the next step.  Everything
the prediction layer computes runs under an outer scope ``mtp``.

**A chip's share**, weights brought by the caller, no weights drawn:
as ``models/longcat_flash.py``.
"""

from __future__ import annotations

from typing import Optional

from bigdl_tpu.models.longcat_flash import _Table
from bigdl_tpu.nn.attention import _Composite
from bigdl_tpu.nn.experts import DroplessExperts, merge_counts
from bigdl_tpu.nn.latent import GatedMLP, LatentAttention, RMSNorm

#: the published ``config.json`` (the keys that shape the model)
PUBLISHED = dict(
    vocab_size=129280, hidden_size=2048, num_hidden_layers=40,
    num_attention_heads=32, q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    intermediate_size=7168, moe_intermediate_size=768,
    n_routed_experts=256, n_shared_experts=1, num_experts_per_tok=8,
    first_k_dense_replace=1, routed_scaling_factor=2.5,
    norm_topk_prob=True, scoring_func="sigmoid", rms_norm_eps=1e-6,
    rope_theta=32e6, num_nextn_predict_layers=1)

#: tokens a decode step verifies a slot: the certain one and one draft
DRAFT_TOKENS = 2


class JoyAILayer(_Composite):
    """One decoder layer: latent attention, then the dense MLP
    (``dense=True``) or the expert layer with its shared expert."""

    def __init__(self, cfg: dict, dense: bool, init: bool = True):
        super().__init__()
        self._config = dict(cfg, dense=dense)
        d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.dense = dense
        self._add_child("norm_attn", RMSNorm(d, eps, init=init))
        self._add_child("attn", LatentAttention(
            d, cfg["num_attention_heads"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], eps=eps,
            theta=cfg["rope_theta"], row_align=cfg["row_align"],
            q_scale=1.0, kv_scale=1.0, init=init))
        self._add_child("norm_mlp", RMSNorm(d, eps, init=init))
        if dense:
            self._add_child("mlp", GatedMLP(d, cfg["intermediate_size"],
                                            init=init))
        else:
            self._add_child("moe", DroplessExperts(
                d, cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                0, cfg["num_experts_per_tok"],
                scale=cfg["routed_scaling_factor"],
                held=cfg["held_experts"], score=cfg["scoring_func"],
                renormalise=cfg["norm_topk_prob"],
                shared_hidden=cfg["n_shared_experts"]
                * cfg["moe_intermediate_size"], init=init))

    def run(self, params, h, attend, mask):
        """The layer's wiring, once, for every path: ``attend(x)`` is
        the attention over the normalised input (full-prefix, or the
        step's tokens over the cache); ``h`` is (..., dim), ``mask``
        (...) marks the real tokens for the expert layer's counts.
        Returns ``(h', counts or None)``."""
        import jax

        c = self._children
        a = h + attend(c["norm_attn"].apply(params["norm_attn"], {}, h)[0])
        u = c["norm_mlp"].apply(params["norm_mlp"], {}, a)[0]
        if self.dense:
            with jax.named_scope("ffn"):
                return a + c["mlp"].apply(params["mlp"], {}, u)[0], None
        (m, counts), _ = c["moe"].apply(
            params["moe"], {}, u.reshape(-1, u.shape[-1]),
            mask=None if mask is None else mask.reshape(-1))
        return a + m.reshape(u.shape), counts


class PredictionLayer(_Composite):
    """The multi-token prediction module (module docstring): two norms,
    ``W_eh``, one expert layer and a norm before the shared head."""

    def __init__(self, cfg: dict, init: bool = True):
        super().__init__()
        self._config = dict(cfg)
        d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self._add_child("norm_e", RMSNorm(d, eps, init=init))
        self._add_child("norm_h", RMSNorm(d, eps, init=init))
        self._add_child("proj", _Table(d, 2 * d, init))
        self._add_child("layer", JoyAILayer(cfg, dense=False, init=init))
        self._add_child("norm_f", RMSNorm(d, eps, init=init))

    @property
    def attention(self) -> LatentAttention:
        return self._children["layer"]._children["attn"]

    def run(self, params, emb_next, h, attend, mask):
        """``emb_next`` (..., dim) the next tokens' embeddings, ``h``
        (..., dim) the main model's last-layer outputs at the same
        positions -> the layer's output, normalised for the head, and
        its routing counts."""
        import jax.numpy as jnp

        c = self._children
        u = jnp.concatenate(
            [c["norm_e"].apply(params["norm_e"], {}, emb_next)[0],
             c["norm_h"].apply(params["norm_h"], {}, h)[0]], axis=-1)
        u = jnp.matmul(u, params["proj"]["weight"].T)
        g, counts = c["layer"].run(params["layer"], u, attend, mask)
        return c["norm_f"].apply(params["norm_f"], {}, g)[0], counts


class JoyAIFlash(_Composite):
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
        if cfg["num_nextn_predict_layers"] != 1:
            raise ValueError("the model has one prediction layer")
        cfg["max_len"] = int(max_len)
        cfg["row_align"] = int(row_align)
        cfg["held_experts"] = (
            (0, cfg["n_routed_experts"]) if held_experts is None
            else (int(held_experts[0]), int(held_experts[1])))
        self._config = cfg
        self.vocab_size = cfg["vocab_size"]
        self.dim = cfg["hidden_size"]
        self.n_layer = cfg["num_hidden_layers"]
        self.n_dense = min(cfg["first_k_dense_replace"], self.n_layer)
        init = params is None
        self._weight_free, self._given = not init, params
        self._add_child("embed", _Table(self.vocab_size, self.dim, init))
        for i in range(self.n_layer):
            self._add_child(f"l{i}", JoyAILayer(cfg, i < self.n_dense,
                                                init=init))
        self._add_child("norm_f", RMSNorm(self.dim, cfg["rms_norm_eps"],
                                          init=init))
        self._add_child("head", _Table(self.vocab_size, self.dim, init))
        self._add_child("mtp", PredictionLayer(cfg, init=init))

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
    def _embed(self, params, tokens):
        import jax.numpy as jnp

        return jnp.take(params["embed"]["weight"], tokens.astype(jnp.int32),
                        axis=0)

    def _head(self, params, h):
        """``h`` already normalised -> logits."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("dense"):
            return jnp.matmul(h, params["head"]["weight"].T)

    def _logits(self, params, x):
        h, _ = self._children["norm_f"].apply(params["norm_f"], {}, x)
        return self._head(params, h)

    def _layers(self, params, x, attend, mask):
        """Every main layer over ``x``; ``attend(i, layer, p, xn)`` is
        layer ``i``'s attention.  Returns the last layer's output and
        the summed routing counts."""
        counts = None
        for i in range(self.n_layer):
            layer, p = self._children[f"l{i}"], params[f"l{i}"]
            x, n = layer.run(
                p, x, lambda xn, i=i, layer=layer, p=p: attend(
                    i, layer._children["attn"], p["attn"], xn), mask)
            if n is not None:
                counts = merge_counts(counts, n)
        return x, counts

    def hidden(self, params, tokens):
        """The last layer's outputs (batch, seq, dim), before the final
        norm: what the head and the prediction layer both read."""
        return self._layers(
            params, self._embed(params, tokens),
            lambda i, attn, p, xn: attn.prefill(p, xn)[0], None)[0]

    def apply(self, params, state, input, *, training=False, rng=None):
        return self._logits(params, self.hidden(params, input)), state

    def draft_logits(self, params, tokens):
        """The prediction layer's full forward: ``tokens`` (batch, seq)
        -> logits (batch, seq - 1, vocab); position ``i`` reads ``h_i``
        and ``tokens[i + 1]`` and predicts ``tokens[i + 2]``."""
        import jax

        h = self.hidden(params, tokens)[:, :-1]
        mtp, p = self._children["mtp"], params["mtp"]
        with jax.named_scope("mtp"):
            g, _ = mtp.run(
                p, self._embed(params, tokens[:, 1:]), h,
                lambda xn: mtp.attention.prefill(p["layer"]["attn"],
                                                 xn)[0], None)
            return self._head(params, g)

    # ------------------------------------------------------------ serving
    def cache_spec(self, params) -> dict:
        """What ``serving.LMEngine`` builds its paged cache from: one
        cached attention a layer and the prediction layer's behind them
        (the dtype is that of the weights it was given)."""
        layer = self._children["mtp"]._children["layer"]._children
        n_moe = self.n_layer - self.n_dense + 1     # the drafting one too
        return {"layers": self.n_layer + 1,
                "row_width": layer["attn"].row_width,
                "buffers": 1, "max_len": self._config["max_len"],
                "dtype": params["embed"]["weight"].dtype,
                # the attention kernel's query rows a slot: the heads
                # of both verified positions
                "attn_query_rows": DRAFT_TOKENS * layer["attn"].n_head,
                "expert_slots": n_moe * layer["moe"].n_held}

    def draft_spec(self, params) -> dict:
        """The model drafts its own next-but-one token: a decode step
        verifies ``tokens_per_step`` positions a slot (the certain token
        and one draft) and yields 1 to that many tokens."""
        del params
        return {"tokens_per_step": DRAFT_TOKENS}

    def paged_prefill(self, params, caches, prompt, t0, pages, *, pick):
        """One prompt, ``prompt`` (1, bucket) zero-padded past ``t0``,
        into the pages ``pages`` (bucket // page_size,): every cached
        layer's rows with one scatter each, the prediction layer's over
        the prompt's positions too.  Returns ``(caches, first, draft,
        counts)``: the token at position ``t0`` (``pick`` of the logits
        at ``t0 - 1``) and the prediction layer's draft of the token at
        ``t0 + 1``."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from bigdl_tpu.serving.cache import write_prompt_pages

        (buf,) = caches
        bucket = prompt.shape[1]
        mask = (jnp.arange(bucket) < t0)[None, :]

        def attend(i, attn, p, xn):
            nonlocal buf
            y, rows = attn.prefill(p, xn)
            with jax.named_scope("kv_write"):
                buf = write_prompt_pages(buf, i, pages, rows[0])
            return y

        h, counts = self._layers(params, self._embed(params, prompt),
                                 attend, mask)
        last = lax.dynamic_slice(h, (0, t0 - 1, 0), (1, 1, self.dim))
        first = pick(self._logits(params, last)[:, 0, :])      # (1,)
        # the prediction layer at position i takes token i + 1: the
        # prompt shifted by one, the first token at t0 - 1
        nxt = jnp.concatenate([prompt[:, 1:], jnp.zeros_like(prompt[:, :1])],
                              axis=1)
        nxt = lax.dynamic_update_slice(nxt, first[None, :].astype(nxt.dtype),
                                       (0, t0 - 1))
        mtp, p = self._children["mtp"], params["mtp"]
        with jax.named_scope("mtp"):
            g, n = mtp.run(
                p, self._embed(params, nxt), h,
                lambda xn: attend(self.n_layer, mtp.attention,
                                  p["layer"]["attn"], xn), mask)
            g = lax.dynamic_slice(g, (0, t0 - 1, 0), (1, 1, self.dim))
            draft = pick(self._head(params, g)[:, 0, :])
        return (buf,), first[0], draft[0], merge_counts(counts, n)

    def paged_decode(self, params, caches, tables, lengths, tokens, drafts,
                     owed, active, *, pick, page_size=None, qparams=None):
        """Verify-and-draft, one step (module docstring): ``tokens``
        (B,) at positions ``lengths``, ``drafts`` (B,) one further;
        ``owed`` (B,) how many tokens each slot may still take.
        Returns ``(caches, picked (B, 2), accepted (B,) bool,
        next_draft (B,), counts)``; the step yields ``picked[:, 0]`` and,
        where accepted, ``picked[:, 1]``."""
        import jax
        import jax.numpy as jnp

        del page_size
        if qparams is not None:
            raise ValueError("JoyAIFlash offers no int8 decode")
        (buf,) = caches
        b = tokens.shape[0]
        both = jnp.broadcast_to(active[:, None], (b, DRAFT_TOKENS))

        def attend(i, attn, p, xn):
            nonlocal buf
            y, buf = attn.decode(p, xn, buf, i, tables, lengths)
            return y

        x = self._embed(params, jnp.stack([tokens, drafts], axis=1))
        h, counts = self._layers(params, x, attend, both)       # (B, 2, D)
        picked = pick(self._logits(params, h).reshape(
            b * DRAFT_TOKENS, -1)).reshape(b, DRAFT_TOKENS)
        accepted = active & (picked[:, 0] == drafts) & (owed >= 2)
        mtp, p = self._children["mtp"], params["mtp"]
        with jax.named_scope("mtp"):
            g, n = mtp.run(
                p, self._embed(params, picked), h,
                lambda xn: attend(self.n_layer, mtp.attention,
                                  p["layer"]["attn"], xn), both)
            # the draft comes from the last position that stands
            g = jnp.where(accepted[:, None], g[:, 1], g[:, 0])
            next_draft = pick(self._head(params, g))
        return (buf,), picked, accepted, next_draft, merge_counts(counts, n)

    def __repr__(self):
        return (f"JoyAIFlash(vocab={self.vocab_size}, dim={self.dim}, "
                f"layers={self.n_layer} + 1 predicting)")


def build_joyai_flash(config: Optional[dict] = None,
                      params: Optional[dict] = None, **kw) -> JoyAIFlash:
    """From a configuration file's object, or from sizes by keyword."""
    if config is not None:
        return JoyAIFlash.from_config(config, params=params)
    return JoyAIFlash(params=params, **kw)


__all__ = ["DRAFT_TOKENS", "JoyAIFlash", "JoyAILayer", "PUBLISHED",
           "PredictionLayer", "build_joyai_flash"]
