"""Falcon-H1: a decoder whose every block runs **a state-space mixer
(Mamba-2) beside a grouped-query attention** on the same normalised
input and adds both to the stream, then a gated MLP; every sublayer
under the configuration's own multipliers (muP).

Source: ``huggingface.co/tiiuae/Falcon-H1-34B-Instruct`` ``config.json``
(``model_type`` ``falcon_h1``).  What that file does not state is
marked *(assumed)* in ``benchmarks/reference/falcon_h1_34b.py``, which
has the layer's equations; the names here are its names.

**What is new to serve.**  A slot holds two kinds of memory in every
layer: paged K/V rows that grow with its context (the per-head K/V
cache, 4 key heads under 20 query heads) and the mixer's running state,
which does not grow but SUMS OVER THE SLOT'S WHOLE PAST: ``H`` (32 x 256
x 128 float32, 4.19 MB a layer) and the convolution's last 3 rows.  The
model declares it (:meth:`FalconH1.state_spec`) and ``serving.LMEngine``
keeps it (``serving/cache.py``).  A decode step reads and writes every
running slot's ``H`` once, which at 128 slots is more bytes than the
layer's weights: the update leaves a slot that did not run bit for bit
as it was (``nn/ssm.py``: ``dt = 0``), so the engine adds no pass over
the state to guard it (``state_spec``'s ``keeps_inactive``).  A prompt
runs the mixer's chunked scan, and the state it ends on is the state
after the prompt's last REAL token: a preempted request's second
prefill rebuilds ``H`` from all its tokens, to rounding.

**A chip's share**, weights brought by the caller, no weights drawn: as
``models/longcat_flash.py``.
"""

from __future__ import annotations

import math
from typing import Optional

from bigdl_tpu.models.longcat_flash import _Table
from bigdl_tpu.nn.attention import _Composite
from bigdl_tpu.nn.latent import GatedMLP, RMSNorm, _draw, rotary_halves
from bigdl_tpu.nn.module import AbstractModule
from bigdl_tpu.nn.ssm import Mamba2Mixer

#: the published ``config.json`` (the keys that shape the model)
PUBLISHED = dict(
    vocab_size=261120, hidden_size=5120, num_hidden_layers=72,
    intermediate_size=21504, num_attention_heads=20, num_key_value_heads=4,
    head_dim=128, rope_theta=1e11, rms_norm_eps=1e-5,
    mamba_d_ssm=4096, mamba_n_heads=32, mamba_d_head=128,
    mamba_d_state=256, mamba_n_groups=2, mamba_d_conv=4,
    mamba_chunk_size=128,
    embedding_multiplier=5.656854249492381,
    lm_head_multiplier=0.0078125,
    attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804,
    ssm_in_multiplier=0.25, ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284))

#: what the configuration must say for this file to compute it
_FIXED = dict(mamba_rms_norm=True, mamba_norm_before_gate=False,
              mamba_conv_bias=True, mamba_proj_bias=False,
              attention_bias=False, mlp_bias=False, projectors_bias=False,
              tie_word_embeddings=False, rope_scaling=None,
              attn_layer_indices=None, hidden_act="silu")


class HybridAttention(AbstractModule):
    """``H`` query heads over ``G`` key/value heads of ``d``, the key
    under ``key_multiplier``, rotary positions by halves over the whole
    head, no bias and no norm of a head."""

    param_names = ("wq", "wk", "wv", "wo")

    def __init__(self, dim: int, n_head: int, kv_heads: int, head_dim: int,
                 theta: float = 1e11, key_multiplier: float = 1.0,
                 init: bool = True):
        super().__init__()
        if n_head % kv_heads:
            raise ValueError(f"{n_head} query heads over {kv_heads} "
                             "key/value heads")
        self._config = dict(dim=dim, n_head=n_head, kv_heads=kv_heads,
                            head_dim=head_dim, theta=theta,
                            key_multiplier=key_multiplier)
        self.dim, self.n_head, self.kv_heads = dim, n_head, kv_heads
        self.head_dim, self.theta = head_dim, theta
        self.key_multiplier = float(key_multiplier)
        #: width of a token's cached K (or V) row
        self.row_width = kv_heads * head_dim
        for n in self.param_names:
            setattr(self, n, None)
        if init:
            self.reset()

    def reset(self):
        h, g, d = self.n_head, self.kv_heads, self.head_dim
        self.wq = _draw((h * d, self.dim))
        self.wk = _draw((g * d, self.dim))
        self.wv = _draw((g * d, self.dim))
        self.wo = _draw((self.dim, h * d))
        return self

    def project(self, params, x, positions):
        """``x`` (..., dim) at ``positions`` (...) -> the rotated query
        (..., H, d) and the token's K and V rows (..., G * d), the K row
        under its multiplier and rotated."""
        import jax.numpy as jnp

        lead = x.shape[:-1]
        q = jnp.matmul(x, params["wq"].T).reshape(
            *lead, self.n_head, self.head_dim)
        k = (jnp.matmul(x, params["wk"].T) * self.key_multiplier).reshape(
            *lead, self.kv_heads, self.head_dim)
        v = jnp.matmul(x, params["wv"].T)
        pos = jnp.asarray(positions)[..., None]
        return (rotary_halves(q, pos, self.theta),
                rotary_halves(k, pos, self.theta).reshape(
                    *lead, self.row_width), v)

    def prefill(self, params, x):
        """One sequence ``x`` (1, T, dim) -> ``(y, k_rows, v_rows)``,
        the rows (1, T, G * d) what the cache stores.  Dense causal
        attention, the softmax in float32."""
        import jax
        import jax.numpy as jnp

        _, t, _ = x.shape
        h, g, d = self.n_head, self.kv_heads, self.head_dim
        with jax.named_scope("dense"):
            q, k_rows, v_rows = self.project(params, x, jnp.arange(t)[None])
        with jax.named_scope("gqa.attn"):
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
        to its own (the page-walking kernel over the stacked buffers at
        ``layer``: a key head's 5 query heads share its rows).  Returns
        ``(y, kp, vp)``."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.ops.decode_attention import paged_decode_attention
        from bigdl_tpu.serving.cache import write_token_rows

        with jax.named_scope("dense"):
            q, k_rows, v_rows = self.project(params, x, lengths)
        with jax.named_scope("kv_write"):
            kp = write_token_rows(kp, layer, tables, lengths, k_rows)
            vp = write_token_rows(vp, layer, tables, lengths, v_rows)
        with jax.named_scope("gqa.attn"):
            o = paged_decode_attention(q, kp, vp, tables, lengths,
                                       layer=layer, page_size=kp.shape[2])
        with jax.named_scope("dense"):
            y = jnp.matmul(o.reshape(x.shape[0], -1), params["wo"].T)
        return y, kp, vp


class FalconH1Layer(_Composite):
    """One block: the mixer and the attention side by side on one
    normalised input, then the gated MLP."""

    def __init__(self, cfg: dict, init: bool = True):
        super().__init__()
        self._config = dict(cfg)
        d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.attn_in = float(cfg["attention_in_multiplier"])
        self.attn_out = float(cfg["attention_out_multiplier"])
        self.ssm_out = float(cfg["ssm_out_multiplier"])
        self.gate_mult, self.down_mult = (
            float(m) for m in cfg["mlp_multipliers"])
        self._add_child("norm_in", RMSNorm(d, eps, init=init))
        self._add_child("ssm", Mamba2Mixer(
            d, cfg["mamba_n_heads"], cfg["mamba_d_head"],
            cfg["mamba_d_state"], cfg["mamba_n_groups"],
            d_conv=cfg["mamba_d_conv"], chunk=cfg["mamba_chunk_size"],
            eps=eps, in_multiplier=cfg["ssm_in_multiplier"],
            zone_multipliers=cfg["ssm_multipliers"], init=init))
        self._add_child("attn", HybridAttention(
            d, cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], theta=cfg["rope_theta"],
            key_multiplier=cfg["key_multiplier"], init=init))
        self._add_child("norm_ff", RMSNorm(d, eps, init=init))
        self._add_child("mlp", GatedMLP(d, cfg["intermediate_size"],
                                        init=init))

    def run(self, params, h, mix, attend):
        """The block's wiring, once, for every path: ``mix(n)`` is the
        mixer and ``attend(u)`` the attention over the normalised
        input."""
        import jax
        import jax.numpy as jnp

        c = self._children
        n = c["norm_in"].apply(params["norm_in"], {}, h)[0]
        a = h + self.ssm_out * mix(n) \
            + self.attn_out * attend(n * self.attn_in)
        r = c["norm_ff"].apply(params["norm_ff"], {}, a)[0]
        p = params["mlp"]
        with jax.named_scope("ffn"):
            mid = jax.nn.silu(self.gate_mult * jnp.matmul(r, p["gate"].T)) \
                * jnp.matmul(r, p["up"].T)
            return a + self.down_mult * jnp.matmul(mid, p["down"].T)


class FalconH1(_Composite):
    """Decoder-only LM over (batch, seq) int tokens -> logits (batch,
    seq, vocab), the head untied.  Sizes default to the published ones;
    a test, or a chip's share, overrides them by keyword."""

    def __init__(self, *, max_len: int = 2048,
                 params: Optional[dict] = None, **sizes):
        super().__init__()
        unknown = set(sizes) - set(PUBLISHED)
        if unknown:
            raise TypeError(f"unknown sizes {sorted(unknown)}; the model "
                            f"takes {sorted(PUBLISHED)}")
        cfg = dict(PUBLISHED, **sizes)
        if cfg["mamba_d_ssm"] != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
            raise ValueError("mamba_d_ssm is mamba_n_heads x mamba_d_head")
        cfg["max_len"] = int(max_len)
        self._config = cfg
        self.vocab_size = cfg["vocab_size"]
        self.dim = cfg["hidden_size"]
        self.n_layer = cfg["num_hidden_layers"]
        init = params is None
        self._weight_free, self._given = not init, params
        self._add_child("embed", _Table(self.vocab_size, self.dim, init))
        for i in range(self.n_layer):
            self._add_child(f"l{i}", FalconH1Layer(cfg, init=init))
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
        ``config.json`` spelling describes; ``max_len`` is the file's
        own key."""
        for k, want in _FIXED.items():
            if config.get(k, want) != want:
                raise ValueError(f"{k} = {config[k]!r}: this model "
                                 f"computes {want!r}")
        return cls(max_len=int(config.get("max_len", 2048)), params=params,
                   **{k: config[k] for k in PUBLISHED if k in config})

    # ------------------------------------------------------- full forward
    def _embed(self, params, tokens):
        import jax.numpy as jnp

        return jnp.take(params["embed"]["weight"], tokens.astype(jnp.int32),
                        axis=0) * self._config["embedding_multiplier"]

    def _logits(self, params, x):
        import jax
        import jax.numpy as jnp

        h, _ = self._children["norm_f"].apply(params["norm_f"], {}, x)
        with jax.named_scope("dense"):
            return jnp.matmul(h, params["head"]["weight"].T) \
                * self._config["lm_head_multiplier"]

    def _layers(self, params, x, mix, attend):
        """Every block over ``x``; ``mix(i, ssm, p, n)`` and
        ``attend(i, attn, p, u)`` are block ``i``'s two mixers."""
        for i in range(self.n_layer):
            layer, p = self._children[f"l{i}"], params[f"l{i}"]
            c = layer._children
            x = layer.run(
                p, x,
                lambda n, i=i, c=c, p=p: mix(i, c["ssm"], p["ssm"], n),
                lambda u, i=i, c=c, p=p: attend(i, c["attn"], p["attn"], u))
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
                lambda i, ssm, p, n: ssm.scan(p, n[0], t)[0][None],
                lambda i, attn, p, u: attn.prefill(p, u)[0])
            outs.append(self._logits(params, x))
        return jnp.concatenate(outs, axis=0), state

    # ------------------------------------------------------------ serving
    def cache_spec(self, params) -> dict:
        """What ``serving.LMEngine`` builds its paged cache from: per-
        head K/V rows of ``kv_heads`` heads, two buffers, under
        ``heads`` query heads (the dtype is that of the weights it was
        given)."""
        attn = self._children["l0"]._children["attn"]
        return {"layers": self.n_layer, "heads": attn.n_head,
                "kv_heads": attn.kv_heads, "head_dim": attn.head_dim,
                "row_width": attn.row_width, "buffers": 2,
                "max_len": self._config["max_len"],
                "dtype": params["embed"]["weight"].dtype,
                # the attention kernel's query rows a slot
                "attn_query_rows": attn.n_head}

    def state_spec(self, params) -> dict:
        """What a slot carries beside its pages, a layer (module
        docstring): the mixer's ``H`` and its convolution's rows, in
        float32 (the rows hold bfloat16 values, kept exactly).  The
        step's update leaves a slot that did not run as it was
        (``keeps_inactive``): the engine adds no guard of its own."""
        import jax.numpy as jnp

        del params
        return {"layers": self.n_layer,
                "shapes": self._children["l0"]._children["ssm"]
                .state_shapes(),
                "dtype": jnp.float32, "keeps_inactive": True}

    def paged_prefill(self, params, caches, prompt, t0, pages):
        """One prompt, ``prompt`` (1, bucket) zero-padded past ``t0``,
        into the pages ``pages`` (bucket // page_size,): every layer's K
        and V rows with one scatter a buffer and layer, the mixer by its
        chunked scan.  Returns ``(caches, logits (1, vocab) at position
        t0 - 1, None, rows)``, ``rows`` one ``(layers, ·)`` array a
        declared shape: the state after position ``t0 - 1``, the
        prompt's last real token, whatever the bucket's padded tail
        holds."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from bigdl_tpu.serving.cache import write_prompt_pages

        kp, vp = caches
        kept = []

        def mix(i, ssm, p, n):
            out, h, rows = ssm.scan(p, n[0], t0)
            kept.append((h, rows))
            return out[None]

        def attend(i, attn, p, u):
            nonlocal kp, vp
            y, k_rows, v_rows = attn.prefill(p, u)
            with jax.named_scope("kv_write"):
                kp = write_prompt_pages(kp, i, pages, k_rows[0])
                vp = write_prompt_pages(vp, i, pages, v_rows[0])
            return y

        x = self._layers(params, self._embed(params, prompt), mix, attend)
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
            raise ValueError("FalconH1 offers no int8 decode")
        kp, vp = caches
        hs, rows = state

        def mix(i, ssm, p, n):
            nonlocal hs, rows
            out, hs, rows = ssm.step(p, n, hs, rows, i, active)
            return out

        def attend(i, attn, p, u):
            nonlocal kp, vp
            y, kp, vp = attn.decode(p, u, kp, vp, i, tables, lengths)
            return y

        x = self._layers(params, self._embed(params, tokens), mix, attend)
        return (kp, vp), self._logits(params, x), None, (hs, rows)

    def __repr__(self):
        return (f"FalconH1(vocab={self.vocab_size}, dim={self.dim}, "
                f"layers={self.n_layer})")


def build_falcon_h1(config: Optional[dict] = None,
                    params: Optional[dict] = None, **kw) -> FalconH1:
    """From a configuration file's object, or from sizes by keyword."""
    if config is not None:
        return FalconH1.from_config(config, params=params)
    return FalconH1(params=params, **kw)


__all__ = ["FalconH1", "FalconH1Layer", "HybridAttention", "PUBLISHED",
           "build_falcon_h1"]
