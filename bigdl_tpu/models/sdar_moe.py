"""SDAR-30B-A3B-Chat: a decoder with **grouped-query attention** (32
query heads over 4 key/value heads, an RMS norm on every head's query
and key, rotary positions by halves), **softmax top-8 experts with
renormalised weights** in every layer, and **generation by blocks**: a
block of ``B`` positions is refined over several passes, each pass
unmasking the positions the model is most confident of, and its final
rows are in the cache before the next block attends.

Source: ``huggingface.co/JetLM/SDAR-30B-A3B-Chat`` ``config.json``
(``model_type`` ``sdar_moe``).  What that file does not state (block
length, passes, the unmasking rule, the mask token, the per-head norms)
is marked *(assumed)*: from the family's published code, from memory,
unverified here.

One layer, for hidden rows ``x`` at positions ``t`` under a mask ``M``
(``nn/experts.py`` for the expert layer):

    a = rms(x; g1)
    q = W_q a -> H heads of Dh;  k = W_k a, v = W_v a -> H_kv heads of Dh
    q_h = rope(rms_Dh(q_h; g_q), t);  k_j = rope(rms_Dh(k_j; g_k), t)
        (one gain vector for all heads *(assumed)*; rope by halves:
         (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin))
    o_h = softmax_M(q_h . k_{h // (H / H_kv)} / sqrt(Dh)) v_{h // (H / H_kv)}
    x = x + W_o [o_0 .. o_{H-1}]
    b = rms(x; g2);  s = softmax(W_r b) in float32;  chosen = top 8 of s
    w_e = s_e / sum of the chosen s                    (norm_topk_prob)
    x = x + sum over chosen e of w_e E_e(b)            (E_e a gated SiLU MLP)

then ``rms(x; g_f)`` and an untied head.  A token's cached row is its
``H_kv`` rotated, normed keys in one buffer and its values in the other
(``serving/cache.py``: per-head K/V rows, ``kv_heads`` of them).

**The mask is block-causal, block length B**: position ``i`` attends
``j`` iff ``j // B <= i // B``.  **A logit at position i predicts the
token AT position i**: a masked position is fed the mask token's
embedding and the model fills it in.

**Generation** (greedy) *(assumed as a whole)*: the prompt's whole
blocks are prefilled and cached; what is left of the prompt sits,
fixed, at the head of the first block generated.  A **pass** forwards
the block's ``B`` positions against the cached rows of the earlier
blocks and against each other; at each masked position ``x0`` is the
largest logit's token and ``c`` its softmax probability (float32).
With ``m`` positions masked and ``n_s`` the static count of pass ``s``
(``B // T``, the remainder spread over the first passes): every masked
position with ``c > threshold`` is unmasked if there are at least
``n_s`` of them, otherwise the ``min(n_s, m)`` most confident (ties to
the earlier position).  An unmasked position is never changed.  When
none is masked, the block's final rows are written (**the commit**: its
final tokens forwarded once more) and the next block starts all masked.
**Departures**: the program keeps a mask FLAG a position and does not
test ``token == mask id`` (a prompt or a pick may hold that id),
``min(n_s, m)``, and the commit is no forward of its own (below).

**Serving.**  Beside ``cache_spec`` the model declares
:meth:`SDARMoE.block_spec` and carries a slot's state from step to
step (``serving/engine.py`` "What the engine asks of a model").  **A
block whose last mask has gone is given no forward of its own: its
final rows are written by the next forward of its slot.**  The mask is
block-causal, so a block's final rows depend on the earlier blocks and
on its own final tokens, never on the block behind it: the forward that
computes them can be the next block's first pass.  One ``paged_decode``
forwards ``2B`` positions for every slot: the **tail** (the block that
became final in the slot's last step, its final tokens, at ``length - B
.. length - 1``) and the **current** block (``length .. length + B -
1``).  The rows of both are WRITTEN before anything attends; the tail's
query rows attend ``pos < length`` and the current block's ``pos <
length + B``, so every row the cache ends up holding and every logit a
pick is made from is what a forward of the tail alone and then one of
the block would give.  In the step whose pass unmasks a block's last
position the device rolls the state over: the block becomes the tail,
the length advances by ``B``, a new block starts all masked.  A slot
with NO tail pending (passes 2 to ``T`` of a block, the first block
behind a prompt, an inactive slot) forwards tail rows that are
**padding**: written nowhere, not real to the expert layer (routed
nowhere, 0, not counted), attending nothing, read by nobody.
The head, the pick and the unmasking rule see the current block's ``B``
rows only.  **A request's last block never gets final rows**: nothing
reads them (a preemption folds what was shown into the prompt and
prefills again).  The attention is
``ops/decode_attention.paged_decode_attention``'s KERNEL path (a key
head's ``2B x H / H_kv`` query rows share its rows, each under the
length of its position): each slot's pages of K and of V are read once,
where they lie, up to the current block's end, and nothing is gathered.
The expert layer is handed the real rows packed into the rows it is
told to expect (every block and the tails of half the slots), so the
padding costs it what a quarter of a block a slot costs, not what a
block does; a step with more tails runs it a second time over the rest
(:meth:`SDARLayer.run`: one expert layer in the program, in a loop
over the mask's own count).

**A chip's share**, weights brought by the caller, no weights drawn:
as ``models/longcat_flash.py``.
"""

from __future__ import annotations

import math
from typing import Optional

from bigdl_tpu.models.longcat_flash import _Table
from bigdl_tpu.nn.attention import _Composite
from bigdl_tpu.nn.experts import DroplessExperts, merge_counts
from bigdl_tpu.nn.latent import RMSNorm, _draw, rms_norm, rotary_halves
from bigdl_tpu.nn.module import AbstractModule

#: the published ``config.json`` (the keys that shape the model)
PUBLISHED = dict(
    vocab_size=151936, hidden_size=2048, num_hidden_layers=48,
    num_attention_heads=32, num_key_value_heads=4, head_dim=128,
    moe_intermediate_size=768, num_experts=128, num_experts_per_tok=8,
    norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e6)

#: how a block is generated: not in ``config.json`` *(assumed)*
GENERATION = dict(block_length=4, denoising_steps=4,
                  rule="low_confidence_dynamic", threshold=0.9,
                  mask_token_id=151669)
RULES = ("low_confidence_dynamic", "low_confidence_static")

#: what a step did for a slot (``paged_decode``'s ``kind``): nothing,
#: a refining pass, or the refining pass that left its block final
IDLE, REFINED, FINISHED = 0, 1, 2


class GroupedQueryAttention(AbstractModule):
    """``H`` query heads over ``H_kv`` key/value heads of ``Dh``, an RMS
    norm over each head's query and key (one gain vector for all
    heads), rotary positions by halves (module docstring)."""

    param_names = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")

    def __init__(self, dim: int, n_head: int, kv_heads: int, head_dim: int,
                 eps: float = 1e-6, theta: float = 1e6, init: bool = True):
        super().__init__()
        if n_head % kv_heads:
            raise ValueError(f"{n_head} query heads over {kv_heads} "
                             "key/value heads")
        self._config = dict(dim=dim, n_head=n_head, kv_heads=kv_heads,
                            head_dim=head_dim, eps=eps, theta=theta)
        self.dim, self.n_head, self.kv_heads = dim, n_head, kv_heads
        self.head_dim, self.eps, self.theta = head_dim, eps, theta
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
        self.wo = _draw((self.dim, h * d))
        self.q_norm = jnp.ones((d,), jnp.float32)
        self.k_norm = jnp.ones((d,), jnp.float32)
        return self

    def project(self, params, x, positions):
        """``x`` (..., dim) at ``positions`` (...) -> the normed, rotated
        query (..., H, Dh) and the token's K and V rows (..., H_kv *
        Dh), the K row normed and rotated."""
        import jax.numpy as jnp

        lead = x.shape[:-1]
        q = jnp.matmul(x, params["wq"].T).reshape(
            *lead, self.n_head, self.head_dim)
        k = jnp.matmul(x, params["wk"].T).reshape(
            *lead, self.kv_heads, self.head_dim)
        v = jnp.matmul(x, params["wv"].T)
        pos = jnp.asarray(positions)[..., None]
        q = rotary_halves(rms_norm(q, params["q_norm"], self.eps), pos,
                          self.theta)
        k = rotary_halves(rms_norm(k, params["k_norm"], self.eps), pos,
                          self.theta)
        return q, k.reshape(*lead, self.row_width), v

    def prefill(self, params, x, block: int):
        """One sequence ``x`` (1, T, dim) under the block-causal mask of
        block length ``block`` -> ``(y, k_rows, v_rows)``, the rows (1,
        T, H_kv * Dh) what the cache stores.  Dense masked attention,
        the softmax in float32."""
        import jax
        import jax.numpy as jnp

        _, t, _ = x.shape
        h, g, d = self.n_head, self.kv_heads, self.head_dim
        with jax.named_scope("dense"):
            q, k_rows, v_rows = self.project(params, x, jnp.arange(t)[None])
        with jax.named_scope("gqa.attn"):
            qg = q[0].reshape(t, g, h // g, d)
            k = k_rows[0].reshape(t, g, d)
            v = v_rows[0].reshape(t, g, d)
            scores = jnp.einsum("tgrd,sgd->grts", qg, k,
                                preferred_element_type=jnp.float32) \
                / math.sqrt(d)
            at = jnp.arange(t) // block
            scores = jnp.where((at[None, :] <= at[:, None])[None, None],
                               scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
            o = jnp.einsum("grts,sgd->tgrd", probs, v).reshape(1, t, h * d)
        with jax.named_scope("dense"):
            return jnp.matmul(o, params["wo"].T), k_rows, v_rows

    def decode(self, params, x, kp, vp, layer: int, tables, lengths, real):
        """Two blocks a slot, ``x`` (S, 2B, dim): the **tail** (the
        block before ``lengths``, final tokens) and the **current**
        block (``lengths + 0 .. B-1``), where ``real`` (S, 2B).  The
        rows of both are written first, a row that is not real nowhere
        (``write_token_rows``); then the tail's positions attend every
        row up to the tail's last and the current block's up to theirs
        (module docstring): one call of the page-walking kernel over
        the stacked buffers at ``layer``, the ``2B x H`` queries of a
        slot each under its own length, float32 softmax whatever the
        rows' dtype.  A tail that is not real attends nothing and comes
        out 0.  Returns ``(y, kp, vp)``."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.ops.decode_attention import paged_decode_attention
        from bigdl_tpu.serving.cache import write_token_rows

        s, wide, _ = x.shape
        b = wide // 2
        first = lengths - b
        pos = first[:, None] + jnp.arange(wide, dtype=lengths.dtype)
        with jax.named_scope("dense"):
            q, k_rows, v_rows = self.project(params, x, pos)
        with jax.named_scope("kv_write"):
            kp = write_token_rows(kp, layer, tables, first, k_rows, real)
            vp = write_token_rows(vp, layer, tables, first, v_rows, real)
        with jax.named_scope("gqa.attn"):
            ends = jnp.where(real[:, :b], lengths[:, None] - 1, -1)
            ends = jnp.concatenate(
                [ends, jnp.broadcast_to(lengths[:, None] + (b - 1), (s, b))],
                axis=1)
            o = paged_decode_attention(
                q, kp, vp, tables, ends, layer=layer,
                page_size=kp.shape[2])
        with jax.named_scope("dense"):
            y = jnp.matmul(o.reshape(s, wide, self.n_head * self.head_dim),
                           params["wo"].T)
        return y, kp, vp


class SDARLayer(_Composite):
    """One decoder layer: grouped-query attention, then the expert
    layer."""

    def __init__(self, cfg: dict, init: bool = True):
        super().__init__()
        self._config = dict(cfg)
        d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self._add_child("norm_attn", RMSNorm(d, eps, init=init))
        self._add_child("attn", GroupedQueryAttention(
            d, cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], eps=eps, theta=cfg["rope_theta"], init=init))
        self._add_child("norm_mlp", RMSNorm(d, eps, init=init))
        self._add_child("moe", DroplessExperts(
            d, cfg["moe_intermediate_size"], cfg["num_experts"], 0,
            cfg["num_experts_per_tok"], scale=1.0,
            held=cfg["held_experts"], score="softmax",
            renormalise=cfg["norm_topk_prob"], shared_hidden=0, init=init))

    def run(self, params, h, attend, mask, expected=None):
        """The layer's wiring, once, for every path: ``attend(x)`` is
        the attention over the normalised input; ``mask`` marks the real
        tokens for the expert layer's counts.  ``expected`` (a static
        count under the rows of ``h``) is how many of them the caller
        expects to be real at most: the expert layer is then handed the
        real rows PACKED, ``expected`` of them a run, and runs as often
        as the mask's own count needs (once while it holds no more: its
        padding then costs what ``expected`` rows cost, not what all
        do; a second run reads the experts' matrices again, and ``hit``
        and ``max_load`` count it as they count another layer).
        Returns ``(h', counts)``."""
        import jax
        import jax.numpy as jnp

        c = self._children
        a = h + attend(c["norm_attn"].apply(params["norm_attn"], {}, h)[0])
        u = c["norm_mlp"].apply(params["norm_mlp"], {}, a)[0]
        rows = u.reshape(-1, u.shape[-1])
        real = None if mask is None else mask.reshape(-1)
        n = rows.shape[0]

        def experts(x, real):
            return c["moe"].apply(params["moe"], {}, x, mask=real)[0]

        if expected is None or expected >= n:
            m, counts = experts(rows, real)
            return a + m.reshape(u.shape), counts
        # the real rows first, in their order; behind the last of them
        # a run's rows are padding: not real, and written nowhere
        order = jnp.concatenate([jnp.argsort(~real, stable=True),
                                 jnp.full((-n % expected,), n, jnp.int32)])

        def one_run(i, carry):
            m, counts = carry
            at = jax.lax.dynamic_slice(order, (i * expected,), (expected,))
            out, more = experts(
                jnp.take(rows, at, axis=0, mode="clip"),
                jnp.take(real, at, mode="fill", fill_value=False))
            return m.at[at].set(out, mode="drop"), merge_counts(counts, more)

        m, counts = jax.lax.fori_loop(
            0, -(-jnp.sum(real) // expected), one_run,
            (jnp.zeros_like(rows), jnp.zeros((5,), jnp.int32)))
        return a + m.reshape(u.shape), counts


def pass_counts(block: int, passes: int):
    """``n_s``, the static count of positions pass ``s`` unmasks:
    ``block // passes``, the remainder one each over the first
    passes."""
    base, rem = divmod(block, passes)
    return [base + (s < rem) for s in range(passes)]


def unmask(conf, masked, pass_index, *, counts, threshold: float):
    """Generation's step 2 for every slot at once: ``conf`` (S, B) the
    confidences, ``masked`` (S, B) the positions still masked,
    ``pass_index`` (S,) -> the positions (S, B) this pass unmasks."""
    import jax.numpy as jnp

    b = conf.shape[1]
    n_s = jnp.take(jnp.asarray(counts, jnp.int32),
                   jnp.clip(pass_index, 0, len(counts) - 1))[:, None]
    m = jnp.sum(masked, axis=1, keepdims=True)
    sure = masked & (conf > threshold)
    # rank among the masked by confidence, ties to the earlier position
    c = jnp.where(masked, conf, -jnp.inf)
    above = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None])
        & (jnp.arange(b)[None, None, :] < jnp.arange(b)[None, :, None]))
    rank = jnp.sum(above, axis=2)
    ranked = masked & (rank < jnp.minimum(n_s, m))
    return jnp.where(jnp.sum(sure, axis=1, keepdims=True) >= n_s, sure,
                     ranked)


class SDARMoE(_Composite):
    """Decoder-only LM over (batch, seq) int tokens -> logits (batch,
    seq, vocab) under the block-causal mask.  Sizes default to the
    published ones; a test, or a chip's share, overrides them by
    keyword."""

    def __init__(self, *, max_len: int = 2048, held_experts=None,
                 generation: Optional[dict] = None,
                 params: Optional[dict] = None, **sizes):
        super().__init__()
        unknown = set(sizes) - set(PUBLISHED)
        if unknown:
            raise TypeError(f"unknown sizes {sorted(unknown)}; the model "
                            f"takes {sorted(PUBLISHED)}")
        cfg = dict(PUBLISHED, **sizes)
        gen = dict(GENERATION, **(generation or {}))
        unknown = set(gen) - set(GENERATION)
        if unknown or gen["rule"] not in RULES:
            raise ValueError(f"generation takes {sorted(GENERATION)} and "
                             f"a rule of {RULES}")
        if not 1 <= gen["denoising_steps"] <= gen["block_length"]:
            raise ValueError("a block takes between 1 and block_length "
                             "passes")
        if max_len % gen["block_length"]:
            raise ValueError(f"max_len {max_len} is no whole number of "
                             f"blocks of {gen['block_length']}")
        cfg["max_len"] = int(max_len)
        cfg["held_experts"] = (
            (0, cfg["num_experts"]) if held_experts is None
            else (int(held_experts[0]), int(held_experts[1])))
        self._config = cfg
        self.generation = gen
        self.block = int(gen["block_length"])
        self.vocab_size = cfg["vocab_size"]
        self.dim = cfg["hidden_size"]
        self.n_layer = cfg["num_hidden_layers"]
        init = params is None
        self._weight_free, self._given = not init, params
        self._add_child("embed", _Table(self.vocab_size, self.dim, init))
        for i in range(self.n_layer):
            self._add_child(f"l{i}", SDARLayer(cfg, init=init))
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
        ``config.json`` spelling describes; ``generation`` (block
        length, passes, rule, threshold, mask token), ``held_experts``
        and ``max_len`` are the file's own keys."""
        sizes = {k: config[k] for k in PUBLISHED if k in config}
        return cls(max_len=int(config.get("max_len", 2048)),
                   held_experts=config.get("held_experts"),
                   generation=config.get("generation"), params=params,
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
            return jnp.matmul(h, params["head"]["weight"].T)

    def _layers(self, params, x, attend, mask, expected=None):
        """Every layer over ``x``; ``attend(i, attn, p, xn)`` is layer
        ``i``'s attention.  Returns the last layer's output and the
        summed routing counts."""
        counts = None
        for i in range(self.n_layer):
            layer, p = self._children[f"l{i}"], params[f"l{i}"]
            x, n = layer.run(
                p, x, lambda xn, i=i, layer=layer, p=p: attend(
                    i, layer._children["attn"], p["attn"], xn), mask,
                expected)
            counts = merge_counts(counts, n)
        return x, counts

    def apply(self, params, state, input, *, training=False, rng=None):
        """Logits at every position of ``input`` (batch, seq), each
        sequence under the block-causal mask: position ``i``'s logits
        are for the token at ``i``."""
        import jax.numpy as jnp

        outs = []
        for row in range(input.shape[0]):
            x, _ = self._layers(
                params, self._embed(params, input[row:row + 1]),
                lambda i, attn, p, xn: attn.prefill(p, xn, self.block)[0],
                None)
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
                # the attention kernel's query rows a slot: every head
                # at each position of the tail and of the block
                "attn_query_rows": 2 * self.block * attn.n_head,
                "expert_slots": self.n_layer * layer["moe"].n_held}

    def block_spec(self, params) -> dict:
        """The model generates by blocks of ``block_length``
        positions; a block takes 1 to ``passes`` refining passes, and
        the first of the next block's writes its final rows."""
        del params
        g = self.generation
        return {"block_length": self.block, "passes": g["denoising_steps"],
                "threshold": g["threshold"] if g["rule"] ==
                "low_confidence_dynamic" else math.inf}

    def first_block(self, prompt, t0):
        """The state of the first block a prompt's request generates:
        what the prompt's whole blocks leave over, fixed, at its head,
        the rest masked.  ``prompt`` (1, bucket), ``t0`` traced ->
        ``(tokens (B,), masked (B,))``."""
        import jax.numpy as jnp
        from jax import lax

        b = self.block
        start = (t0 // b) * b
        padded = jnp.concatenate(
            [prompt[0], jnp.zeros((b,), prompt.dtype)])
        at = start + jnp.arange(b)
        masked = at >= t0
        tokens = lax.dynamic_slice(padded, (start,), (b,))
        return jnp.where(masked, 0, tokens).astype(jnp.int32), masked

    def paged_prefill(self, params, caches, prompt, t0, pages, *, pick=None):
        """One prompt, ``prompt`` (1, bucket) zero-padded past ``t0``,
        into the pages ``pages`` (bucket // page_size,): every layer's K
        and V rows under the block-causal mask, with one scatter a
        buffer and layer.  The rows of the prompt's whole blocks are
        final; those behind them are overwritten by the first block's
        passes before anything reads them.  No token is picked: returns
        ``(caches, (tokens (B,), masked (B,)), counts)``, the first
        block's state."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.serving.cache import write_prompt_pages

        del pick
        kp, vp = caches
        bucket = prompt.shape[1]
        mask = (jnp.arange(bucket) < t0)[None, :]

        def attend(i, attn, p, xn):
            nonlocal kp, vp
            y, k_rows, v_rows = attn.prefill(p, xn, self.block)
            with jax.named_scope("kv_write"):
                kp = write_prompt_pages(kp, i, pages, k_rows[0])
                vp = write_prompt_pages(vp, i, pages, v_rows[0])
            return y

        _, counts = self._layers(params, self._embed(params, prompt),
                                 attend, mask)
        return (kp, vp), self.first_block(prompt, t0), counts

    def block_logits(self, params, caches, tables, lengths, tokens, masked,
                     active, tail=None, pending=None):
        """One forward of a slot's pending tail and its current block
        (module docstring, Serving): ``tokens`` (S, B) at positions
        ``lengths + 0 .. B-1``, the mask token where ``masked``, behind
        ``tail`` (S, B), the final tokens of the block before it, real
        where ``pending`` (S,) (None: no slot has one).  Writes the rows
        of both and returns ``(caches, logits (S, B, vocab), counts)``:
        the logits of the current block only, the counts of the real
        rows only."""
        import jax.numpy as jnp

        kp, vp = caches
        b = tokens.shape[1]
        if tail is None:
            tail, pending = jnp.zeros_like(tokens), jnp.zeros_like(active)
        real = jnp.concatenate(
            [jnp.broadcast_to((pending & active)[:, None], tokens.shape),
             jnp.broadcast_to(active[:, None], tokens.shape)], axis=1)

        def attend(i, attn, p, xn):
            nonlocal kp, vp
            y, kp, vp = attn.decode(p, xn, kp, vp, i, tables, lengths, real)
            return y

        fed = jnp.concatenate(
            [tail, jnp.where(masked, self.generation["mask_token_id"],
                             tokens)], axis=1)
        # the expert layer expects every block and the tails of half
        # the slots: one slot in four has a tail pending where a block
        # takes four passes, and a step with more runs it twice
        h, counts = self._layers(params, self._embed(params, fed), attend,
                                 real, expected=real.size * 3 // 4)
        # the block's rows go to the head flat: logits of (S, B, vocab)
        # out of the product are relaid twice before the pick reads them
        logits = self._logits(params, h[:, b:].reshape(-1, h.shape[-1]))
        return (kp, vp), logits.reshape(*tokens.shape, -1), counts

    def paged_decode(self, params, caches, tables, lengths, tokens, masked,
                     passes, tail, pending, active, *, pick, page_size=None,
                     qparams=None):
        """One step of every slot (module docstring, Serving):
        ``tokens`` / ``masked`` (S, B) the block at positions ``lengths
        + 0 .. B-1``, ``passes`` (S,) the refining passes it has had,
        ``tail`` (S, B) / ``pending`` (S,) the block before it where its
        final rows are still to be written.  Every active slot refines
        (``pick`` chooses ``x0``) and, in the same forward, a pending
        tail's final rows are written.  Where the pass unmasks the
        block's last position the state rolls over: the block becomes
        the pending tail, the length advances by ``B`` and the next
        block starts all masked.  Returns ``(caches, (tokens, masked,
        passes, lengths, tail, pending) after the step, kind (S,),
        counts)`` with ``kind`` one of ``IDLE``, ``REFINED``,
        ``FINISHED`` (refined, and the block is final: its tokens are
        the ``tail`` handed back)."""
        import jax
        import jax.numpy as jnp

        del page_size
        if qparams is not None:
            raise ValueError("SDARMoE offers no int8 decode")
        s, b = tokens.shape
        caches, logits, counts = self.block_logits(
            params, caches, tables, lengths, tokens, masked, active, tail,
            pending)
        with jax.named_scope("unmask"):
            flat = logits.reshape(s * b, -1)
            x0 = pick(flat).reshape(s, b)
            l32 = flat.astype(jnp.float32)
            top = jnp.max(l32, axis=-1, keepdims=True)
            conf = (1.0 / jnp.sum(jnp.exp(l32 - top), axis=-1)) \
                .reshape(s, b)
            spec = self.block_spec(params)
            newly = unmask(conf, masked, passes,
                           counts=pass_counts(b, spec["passes"]),
                           threshold=spec["threshold"])
        newly = newly & active[:, None]
        tokens = jnp.where(newly, x0, tokens)
        masked = masked & ~newly
        done = (active & ~jnp.any(masked, axis=1))[:, None]
        tail = jnp.where(done, tokens, tail)
        tokens = jnp.where(done, 0, tokens)
        masked = masked | done
        done = done[:, 0]
        pending = jnp.where(active, done, pending)
        passes = jnp.where(done, 0, passes + active.astype(passes.dtype))
        lengths = lengths + jnp.where(done, b, 0).astype(lengths.dtype)
        kind = jnp.where(done, FINISHED,
                         jnp.where(active, REFINED, IDLE)).astype(jnp.int32)
        return (caches, (tokens, masked, passes, lengths, tail, pending),
                kind, counts)

    def __repr__(self):
        return (f"SDARMoE(vocab={self.vocab_size}, dim={self.dim}, "
                f"layers={self.n_layer}, blocks of {self.block})")


def build_sdar_moe(config: Optional[dict] = None,
                   params: Optional[dict] = None, **kw) -> SDARMoE:
    """From a configuration file's object, or from sizes by keyword."""
    if config is not None:
        return SDARMoE.from_config(config, params=params)
    return SDARMoE(params=params, **kw)


__all__ = ["FINISHED", "GENERATION", "GroupedQueryAttention", "IDLE",
           "PUBLISHED", "REFINED", "SDARLayer", "SDARMoE",
           "build_sdar_moe", "pass_counts", "unmask"]
