"""Operations and bytes one **block step** must move: a decoder with
grouped-query attention over per-head K/V rows and softmax top-k
experts in every layer, generating by blocks, from its configuration in
the published ``config.json`` spelling
(``configs/sdar_30b_a3b_chat.json``).

A step forwards ``block_length`` positions a slot, whatever the slot's
phase (a refining pass or the commit).  The counts are the numerators
of ``block_attn_roofline``, ``block_moe_experts_roofline`` and
``block_step_roofline``.  Like ``lib/flops_latent_moe.py`` they count
what the mathematics must move and multiply (2 per multiply-add), never
what a program happens to execute: the rows of the contexts ONCE a slot
and layer (a block's positions and a key head's query heads share them)
and not the page bucket nor a gathered copy, the experts that got a
token and not the experts held, the head's matrix once and the
embedding's rows not at all.  Scores and mixes are counted per query
head against ITS key head's ``head_dim`` values, not against the whole
row.
"""

from __future__ import annotations

from benchmarks.lib.flops import roofline_seconds

#: the scopes (``jax.named_scope``) of the model's ``jit_step``, as
#: ``hostgaps.scope_ms_per_call`` takes them
SCOPES = ("gqa.attn", "kv_write", "unmask", "moe.route", "moe.experts",
          "dense", "sample")


def block_length(cfg: dict) -> int:
    return int(cfg["generation"]["block_length"])


def layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def row_values(cfg: dict) -> int:
    """Values of a token's cached K row (and of its V row)."""
    return int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])


def attention_params(cfg: dict) -> float:
    """``W_q``, ``W_k``, ``W_v``, ``W_o``."""
    d = float(cfg["hidden_size"])
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return 2.0 * d * q + 2.0 * d * row_values(cfg)


def router_params(cfg: dict) -> float:
    return float(cfg["hidden_size"]) * cfg["num_experts"]


def expert_params(cfg: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def head_params(cfg: dict) -> float:
    return float(cfg["vocab_size"]) * cfg["hidden_size"]


def dense_params(cfg: dict) -> float:
    """Every matrix outside the routed experts: the layers' attention
    and router, and the head."""
    return layers(cfg) * (attention_params(cfg) + router_params(cfg)) \
        + head_params(cfg)


def attn_bytes(cfg: dict, context_tokens: float, itemsize: int) -> float:
    """The contexts' K and V rows once a slot and layer
    (``context_tokens``: a slot's rows up to its block's end)."""
    return layers(cfg) * 2.0 * context_tokens * row_values(cfg) * itemsize


def attn_flops(cfg: dict, context_tokens: float) -> float:
    """Scores and mix of every query head at each of the block's
    positions over its key head's values of the context's rows."""
    per_row = 2.0 * 2.0 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * block_length(cfg)
    return layers(cfg) * per_row * context_tokens


def experts_bytes(cfg: dict, experts_hit: float, itemsize: int) -> float:
    """The weights of the experts that got a token (summed over the
    step's layers)."""
    return experts_hit * expert_params(cfg) * itemsize


def experts_flops(cfg: dict, held_assignments: float) -> float:
    return 2.0 * expert_params(cfg) * held_assignments


def step_bytes(cfg: dict, context_tokens: float, experts_hit: float,
               itemsize: int) -> float:
    """Everything one step has to read: every dense matrix and the head
    once, the experts that got a token, the contexts' rows once a slot
    and layer.  Norm gains, the embedding rows of the step's tokens and
    the rows it writes are left out (under 0.1 %)."""
    return itemsize * dense_params(cfg) \
        + attn_bytes(cfg, context_tokens, itemsize) \
        + experts_bytes(cfg, experts_hit, itemsize)


def step_flops(cfg: dict, slots: float, context_tokens: float,
               held_assignments: float) -> float:
    """2 per weight per position outside the experts at every slot's
    ``block_length`` positions, the experts' assignments, the attention
    over the contexts."""
    return (2.0 * dense_params(cfg) * block_length(cfg) * slots
            + experts_flops(cfg, held_assignments)
            + attn_flops(cfg, context_tokens))


def scopes_ms_per_call(run, scopes):
    """Device ms a call of ``jit_step`` under ``scopes`` together; None
    where the trace holds no scoped operation of the program."""
    from benchmarks.lib import hostgaps

    parts = [hostgaps.scope_ms_per_call(run, "jit_step", SCOPES, scope)
             for scope in scopes]
    return None if any(p is None for p in parts) else sum(parts)


def block_steps(run) -> list:
    """The attributes of the window's ``serve.decode_step`` spans of a
    block model: what the step's slots did, the routing counts and the
    contexts' rows (none on a program without them)."""
    return [s["attrs"] for s in run.spans
            if s["name"] == "serve.decode_step"
            and "block_passes" in s["attrs"]
            and "moe_held" in s["attrs"]
            and "context_tokens" in s["attrs"]]


def mean_least_ms(run, per_step):
    """Mean over the window's block steps of ``per_step(attrs) ->
    (flops, bytes)``'s least time, in ms; None without such steps."""
    steps = block_steps(run)
    if not steps:
        return None
    total = sum(roofline_seconds(*per_step(a), run.peaks) for a in steps)
    return 1e3 * total / len(steps)
