"""Operations and bytes a decoder with latent attention and a sparse
expert layer needs for one decode step, from its configuration in the
published ``config.json`` spelling (``configs/longcat_flash_chat.json``).

They are the numerators of ``mla_attn_roofline``,
``moe_experts_roofline`` and ``latent_moe_decode_step_roofline``.  Like
``lib/flops.py`` they count what the mathematics must move and multiply
(2 per multiply-add), never what a program happens to execute: the rows
of the contexts and not of the page bucket, a row's ``kv_lora_rank +
qk_rope_head_dim`` values and not the lanes it is padded to, the experts
that got a token and not the experts held.  A program that reads
padding or an idle expert's weights gets no credit for it, and one that
skips them shows as a gain.
"""

from __future__ import annotations

from benchmarks.lib.flops import roofline_seconds

#: the TPU compiler turns ``jax.lax.ragged_dot`` into kernels of its own
#: whose ``op_name`` is one of these and carries no scope path: they are
#: the expert layer's grouped products (and their group bookkeeping)
GROUPED = ("ragged-dot-none", "ragged-dot-none:", "ragged-dot-metadata",
           "ragged-dot-metadata:")
#: the scopes (``jax.named_scope``) of a latent-attention expert model's
#: ``jit_step``, as ``hostgaps.scope_ms_per_call`` takes them
SCOPES = ("mla.proj", "kv_write", "mla.attn", "ffn", "moe.route",
          "moe.experts", "moe.zero", "dense", "sample") + GROUPED
MOE_SCOPES = ("moe.route", "moe.experts", "moe.zero") + GROUPED
EXPERT_SCOPES = ("moe.experts",) + GROUPED


def row_values(cfg: dict) -> int:
    """Values a token's cached row must hold: ``[c | rotated k_rope]``."""
    return int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])


def attention_params(cfg: dict) -> float:
    """Matrix weights of one latent attention: ``W_qa``, ``W_qb``,
    ``W_kva``, ``W_kvb``, ``W_o``."""
    d, h = float(cfg["hidden_size"]), float(cfg["num_attention_heads"])
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    v, rq, rkv = cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * rq + rq * h * (nope + rope) + d * (rkv + rope)
            + rkv * h * (nope + v) + h * v * d)


def kv_up_params(cfg: dict) -> float:
    """``W_kvb`` alone: what the absorbed decode attention reads beside
    the rows."""
    return float(cfg["kv_lora_rank"]) * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["v_head_dim"])


def router_outputs(cfg: dict) -> int:
    """The router's width: every routed expert of the published model
    (``router_experts`` where ``n_routed_experts`` counts a chip's
    share) and the zero-compute ones."""
    return int(cfg.get("router_experts", cfg["n_routed_experts"])) \
        + int(cfg["zero_expert_num"])


def dense_layer_params(cfg: dict) -> float:
    """Matrix weights of one double layer outside its experts: two
    attentions, two dense MLPs, the router."""
    d = float(cfg["hidden_size"])
    return (2.0 * attention_params(cfg)
            + 2.0 * 3.0 * d * cfg["ffn_hidden_size"]
            + d * router_outputs(cfg))


def expert_params(cfg: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3.0 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def head_params(cfg: dict) -> float:
    """The output head over the vocabulary (slice) served."""
    return float(cfg["vocab_size"]) * cfg["hidden_size"]


def cached_attentions(cfg: dict) -> int:
    return 2 * int(cfg["num_layers"])


def mla_attn_bytes(cfg: dict, context_tokens: float, itemsize: int) -> float:
    """One step's decode attention, all cached attentions: the contexts'
    rows once each, and ``W_kvb`` once an attention."""
    return cached_attentions(cfg) * itemsize * (
        context_tokens * row_values(cfg) + kv_up_params(cfg))


def mla_attn_flops(cfg: dict, context_tokens: float, batch: int) -> float:
    """Scores over a row's values and the mix over its ``kv_lora_rank``
    for every head and context token, and the two absorptions of
    ``W_kvb`` a sequence."""
    h = float(cfg["num_attention_heads"])
    over_rows = 2.0 * h * (row_values(cfg) + cfg["kv_lora_rank"]) \
        * context_tokens
    absorbed = 2.0 * kv_up_params(cfg) * batch
    return cached_attentions(cfg) * (over_rows + absorbed)


def moe_experts_bytes(cfg: dict, experts_hit: float, itemsize: int) -> float:
    """The weights of the held experts that got a token (summed over the
    step's expert layers)."""
    return experts_hit * expert_params(cfg) * itemsize


def moe_experts_flops(cfg: dict, held_assignments: float) -> float:
    return 2.0 * expert_params(cfg) * held_assignments


def decode_step_bytes(cfg: dict, context_tokens: float, experts_hit: float,
                      itemsize: int) -> float:
    """Everything one decode step has to read: every dense matrix and
    the head once, the experts that got a token, the contexts' rows.
    Norm gains, the selection bias, the embedding rows of the step's
    tokens and the rows it writes are left out (under 0.1 %)."""
    weights = cfg["num_layers"] * dense_layer_params(cfg) + head_params(cfg)
    rows = cached_attentions(cfg) * context_tokens * row_values(cfg)
    return itemsize * (weights + rows) \
        + moe_experts_bytes(cfg, experts_hit, itemsize)


def decode_step_flops(cfg: dict, batch: int, context_tokens: float,
                      held_assignments: float) -> float:
    """2 per weight per sequence outside the experts (``W_kvb`` is
    counted with the attention), the experts' assignments, the attention
    over the contexts."""
    dense = cfg["num_layers"] * (dense_layer_params(cfg)
                                 - 2.0 * kv_up_params(cfg)) \
        + head_params(cfg)
    return (2.0 * dense * batch
            + moe_experts_flops(cfg, held_assignments)
            + mla_attn_flops(cfg, context_tokens, batch))


def scopes_ms_per_call(run, scopes):
    """Device ms a call of ``jit_step`` under ``scopes`` together; None
    where the trace holds no scoped operation of the program."""
    from benchmarks.lib import hostgaps

    parts = [hostgaps.scope_ms_per_call(run, "jit_step", SCOPES, scope)
             for scope in scopes]
    return None if any(p is None for p in parts) else sum(parts)


def routed_steps(run) -> list:
    """The attributes of the window's ``serve.decode_step`` spans that
    carry routing counts (an expert model's; none on a program without
    them)."""
    return [s["attrs"] for s in run.spans
            if s["name"] == "serve.decode_step"
            and "moe_held" in s["attrs"]
            and "context_tokens" in s["attrs"]]


def mean_least_ms(run, per_step) -> float:
    """Mean over the window's routed steps of ``per_step(attrs) ->
    (flops, bytes)``'s least time, in ms; None without such steps."""
    steps = routed_steps(run)
    if not steps:
        return None
    total = sum(roofline_seconds(*per_step(a), run.peaks) for a in steps)
    return 1e3 * total / len(steps)
