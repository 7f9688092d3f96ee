"""Operations and bytes one decode step of a decoder with **attention
inside a compressed latent** (CCA: few key/value heads formed by causal
convolutions, per-head K/V rows) and **top-1 experts chosen by an MLP
router** must move, from its configuration in the published
``config.json`` spelling (``configs/zaya1_8b.json``).

The counts are the numerators of ``cca_attn_roofline``,
``top1_moe_experts_roofline`` and ``cca_step_roofline``.  Like
``lib/flops_latent_moe.py`` they count what the mathematics must move
and multiply (2 per multiply-add), never what a program happens to
execute: the rows of the contexts ONCE a slot and layer (a key head's
query heads share them) and not the page bucket nor a gathered copy,
the experts that got a token and not the experts held, the tied head's
matrix once and the embedding's rows not at all.  Scores and mixes are
counted per query head against ITS key head's ``head_dim`` values, not
against the whole row.  The slots' state (2688 values a slot and layer,
read and written) is under 0.2 % of a step's bytes and is left out, as
the rows a step writes are.
"""

from __future__ import annotations

from benchmarks.lib.flops_latent_moe import mean_least_ms

#: the scopes (``jax.named_scope``) of the model's ``jit_step``, as
#: ``hostgaps.scope_ms_per_call`` takes them
SCOPES = ("cca.mix", "cca.attn", "kv_write", "moe.route", "moe.experts",
          "dense", "sample")


def layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def row_values(cfg: dict) -> int:
    """Values of a token's cached K row (and of its V row)."""
    return int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])


def channels(cfg: dict) -> int:
    """Channels of the down-projected query and key together."""
    return (int(cfg["num_attention_heads"])
            + int(cfg["num_key_value_heads"])) * int(cfg["head_dim"])


def attention_params(cfg: dict) -> float:
    """``W_qk``, ``W_v``, ``W_o`` and the second convolution's two
    ``head_dim x head_dim`` matrices a head."""
    d, hd = float(cfg["hidden_size"]), int(cfg["head_dim"])
    heads = channels(cfg) // hd
    return (d * channels(cfg) + d * row_values(cfg)
            + d * cfg["num_attention_heads"] * hd + 2.0 * heads * hd * hd)


def router_params(cfg: dict) -> float:
    """The down-projection, two square layers and the output layer."""
    r = float(cfg["router_hidden_size"])
    return r * cfg["hidden_size"] + 2.0 * r * r + r * cfg["num_experts"]


def expert_params(cfg: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def head_params(cfg: dict) -> float:
    """The tied head: the embedding's matrix, read whole once a step."""
    return float(cfg["vocab_size"]) * cfg["hidden_size"]


def dense_params(cfg: dict) -> float:
    """Every matrix outside the routed experts: the layers' attention
    and router, and the head."""
    return layers(cfg) * (attention_params(cfg) + router_params(cfg)) \
        + head_params(cfg)


def attn_bytes(cfg: dict, context_tokens: float, itemsize: int) -> float:
    """The contexts' K and V rows once a slot and layer
    (``context_tokens``: a slot's rows up to the token it computes)."""
    return layers(cfg) * 2.0 * context_tokens * row_values(cfg) * itemsize


def attn_flops(cfg: dict, context_tokens: float) -> float:
    """Scores and mix of every query head over its key head's values of
    the context's rows."""
    per_row = 2.0 * 2.0 * cfg["num_attention_heads"] * cfg["head_dim"]
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
    and layer."""
    return itemsize * dense_params(cfg) \
        + attn_bytes(cfg, context_tokens, itemsize) \
        + experts_bytes(cfg, experts_hit, itemsize)


def step_flops(cfg: dict, slots: float, context_tokens: float,
               held_assignments: float) -> float:
    """2 per weight per slot outside the experts, the experts'
    assignments, the attention over the contexts."""
    return (2.0 * dense_params(cfg) * slots
            + experts_flops(cfg, held_assignments)
            + attn_flops(cfg, context_tokens))


def scopes_ms_per_call(run, scopes):
    """Device ms a call of ``jit_step`` under ``scopes`` together; None
    where the trace holds no scoped operation of the program."""
    from benchmarks.lib import hostgaps

    parts = [hostgaps.scope_ms_per_call(run, "jit_step", SCOPES, scope)
             for scope in scopes]
    return None if any(p is None for p in parts) else sum(parts)


def share(run, ms, per_step):
    """The least time of the window's routed steps (``per_step(attrs)
    -> (flops, bytes)`` over each ``serve.decode_step`` span that
    carries routing counts and ``context_tokens``, as
    ``lib/flops_latent_moe.py`` reads them) over ``ms``, in per cent;
    None where either is missing."""
    least = mean_least_ms(run, per_step)
    if not ms or least is None:
        return None
    return 100.0 * least / ms
