"""Operations and bytes one decode step of a decoder that mixes by **a
delta-rule linear attention with a decay a channel (KDA) in most layers
and by latent attention in the others**, with a sparse expert layer
behind either, must move, from its configuration in the published
``config.json`` spelling (``configs/ling_3_flash_vl.json``) and the
attributes of the engine's ``serve.decode_step`` spans.  Nothing is read
from the program.

The counts are the numerators of ``kda_state_roofline``,
``kda_mla_attn_roofline``, ``kda_moe_experts_roofline`` and
``kda_step_roofline``.  Like ``lib/flops_latent_moe.py`` and
``lib/flops_hybrid_ssm.py`` they count what the mathematics must move
and multiply (2 per multiply-add), never what a program happens to
execute: the slots' state ONCE in and ONCE out (``state_bytes`` of the
span: the slots that ran x a slot's bytes x 2, whatever implements the
update), the latent rows of the contexts once a slot and latent layer
(a row's ``kv_lora_rank + qk_rope_head_dim`` values, not the lanes it is
padded to), the experts that got a token and not the experts held, every
other layer matrix and the head once, the embedding's rows not at all.
The rows a step writes and the norms' gains are left out (under 0.1 %).
"""

from __future__ import annotations

from benchmarks.lib.flops import roofline_seconds
from benchmarks.lib.flops_latent_moe import GROUPED

#: the scopes (``jax.named_scope``) of the model's ``jit_step``, as
#: ``hostgaps.scope_ms_per_call`` takes them
SCOPES = ("kda.proj", "kda.conv", "kda.state", "mla.proj", "kv_write",
          "mla.attn", "ffn", "moe.route", "moe.experts", "moe.zero",
          "dense", "sample") + GROUPED
EXPERT_SCOPES = ("moe.experts",) + GROUPED

STATE_ITEMSIZE = 4      # the state is float32 whatever the weights are


def layer_kinds(cfg: dict) -> list:
    """``(latent, dense)`` of every layer built: a kept layer mixes as
    its PUBLISHED index says, the first ``first_k_dense_replace`` built
    layers are dense."""
    kept = cfg.get("kept_layers", range(int(cfg["num_hidden_layers"])))
    return [((int(pub) + 1) % int(cfg["layer_group_size"]) == 0,
             i < int(cfg["first_k_dense_replace"]))
            for i, pub in enumerate(kept)]


def kda_layers(cfg: dict) -> int:
    return sum(not latent for latent, _ in layer_kinds(cfg))


def latent_layers(cfg: dict) -> int:
    return sum(latent for latent, _ in layer_kinds(cfg))


def moe_layers(cfg: dict) -> int:
    return sum(not dense for _, dense in layer_kinds(cfg))


def inner(cfg: dict) -> int:
    """``H d``: a KDA layer's query (or key, or value) channels."""
    return int(cfg["num_attention_heads"]) * int(cfg["head_dim"])


def state_values(cfg: dict) -> int:
    """Values of ``S`` a slot and KDA layer: ``H x d_k x d_v``."""
    return inner(cfg) * int(cfg["head_dim"])


def conv_channels(cfg: dict) -> int:
    """Channels of ``[q ; k ; v]``."""
    return 3 * inner(cfg)


def slot_state_bytes(cfg: dict) -> int:
    """Bytes of state a slot carries over all KDA layers: ``S`` and the
    convolution's kept rows."""
    kept = (int(cfg["short_conv_kernel_size"]) - 1) * conv_channels(cfg)
    return kda_layers(cfg) * (state_values(cfg) + kept) * STATE_ITEMSIZE


def kda_params(cfg: dict) -> float:
    """``W_in`` (q, k, v, f, z: five ``H d`` zones, and beta's ``H``)
    and ``W_out``."""
    d = float(cfg["hidden_size"])
    return d * (5 * inner(cfg) + cfg["num_attention_heads"]) \
        + d * inner(cfg)


def row_values(cfg: dict) -> int:
    """Values a token's cached row must hold: ``[c | rotated k_rope]``."""
    return int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])


def kv_up_params(cfg: dict) -> float:
    """``W_kvb`` alone: what the absorbed decode attention reads beside
    the rows."""
    return float(cfg["kv_lora_rank"]) * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["v_head_dim"])


def latent_params(cfg: dict) -> float:
    """One latent attention: the full-rank ``W_q``, ``W_kva``, ``W_kvb``,
    ``W_o`` and the head-wise gate."""
    d, h = float(cfg["hidden_size"]), float(cfg["num_attention_heads"])
    return (d * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
            + d * row_values(cfg) + kv_up_params(cfg)
            + h * cfg["v_head_dim"] * d + h * d)


def expert_params(cfg: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def outside_experts_params(cfg: dict) -> float:
    """Every matrix a step reads whole: the mixers, the dense MLP, each
    expert layer's router (its published width) and shared expert, the
    head."""
    d = float(cfg["hidden_size"])
    dense = len(layer_kinds(cfg)) - moe_layers(cfg)
    router = d * int(cfg.get("router_experts", cfg["num_experts"]))
    shared = 3.0 * d * cfg["moe_shared_expert_intermediate_size"]
    return (kda_layers(cfg) * kda_params(cfg)
            + latent_layers(cfg) * latent_params(cfg)
            + dense * 3.0 * d * cfg["intermediate_size"]
            + moe_layers(cfg) * (router + shared)
            + float(cfg["vocab_size"]) * d)


def expert_slots(cfg: dict) -> int:
    """Held experts over the step's expert layers: what the mean load
    of a held expert is taken over."""
    return moe_layers(cfg) * int(cfg["num_experts"])


def slots_of(cfg: dict, state_bytes: float) -> float:
    """The slots a step ran for, from what it says it moved."""
    return state_bytes / (2.0 * slot_state_bytes(cfg))


def state_flops(cfg: dict, state_bytes: float) -> float:
    """The recurrence over the slots that ran: the decay (1 a value of
    ``S``), the read along the key, the rank-1 write and the read along
    the query (2 each), and the convolution's taps."""
    per_layer = 7.0 * state_values(cfg) \
        + 2.0 * cfg["short_conv_kernel_size"] * conv_channels(cfg)
    return slots_of(cfg, state_bytes) * kda_layers(cfg) * per_layer


def mla_attn_bytes(cfg: dict, context_tokens: float, itemsize: int) -> float:
    """The contexts' rows once a slot and latent layer, and ``W_kvb``
    once a latent layer."""
    return latent_layers(cfg) * itemsize * (
        context_tokens * row_values(cfg) + kv_up_params(cfg))


def mla_attn_flops(cfg: dict, context_tokens: float, slots: float) -> float:
    """Scores over a row's values and the mix over its ``kv_lora_rank``
    for every head and context token, and the two absorptions of
    ``W_kvb`` a sequence."""
    h = float(cfg["num_attention_heads"])
    over_rows = 2.0 * h * (row_values(cfg) + cfg["kv_lora_rank"]) \
        * context_tokens
    return latent_layers(cfg) * (over_rows
                                 + 2.0 * kv_up_params(cfg) * slots)


def moe_experts_bytes(cfg: dict, experts_hit: float, itemsize: int) -> float:
    """The weights of the held experts that got a token (summed over the
    step's expert layers)."""
    return experts_hit * expert_params(cfg) * itemsize


def moe_experts_flops(cfg: dict, held_assignments: float) -> float:
    return 2.0 * expert_params(cfg) * held_assignments


def step_bytes(cfg: dict, attrs: dict, itemsize: int) -> float:
    """Everything one step has to move: the state in and out, every
    matrix outside the experts and the head once, the experts that got
    a token, the contexts' latent rows."""
    return (attrs["state_bytes"]
            + itemsize * (outside_experts_params(cfg)
                          - latent_layers(cfg) * kv_up_params(cfg))
            + moe_experts_bytes(cfg, attrs["moe_hit"], itemsize)
            + mla_attn_bytes(cfg, attrs["context_tokens"], itemsize))


def step_flops(cfg: dict, attrs: dict) -> float:
    """2 per weight per slot outside the experts (``W_kvb`` is counted
    with the attention), the recurrence, the experts' assignments, the
    attention over the contexts."""
    slots = slots_of(cfg, attrs["state_bytes"])
    dense = outside_experts_params(cfg) \
        - latent_layers(cfg) * kv_up_params(cfg)
    return (2.0 * dense * slots
            + state_flops(cfg, attrs["state_bytes"])
            + moe_experts_flops(cfg, attrs["moe_held"])
            + mla_attn_flops(cfg, attrs["context_tokens"], slots))


def scopes_ms_per_call(run, scopes):
    """Device ms a call of ``jit_step`` under ``scopes`` together; None
    where the trace holds no scoped operation of the program."""
    from benchmarks.lib import hostgaps

    parts = [hostgaps.scope_ms_per_call(run, "jit_step", SCOPES, scope)
             for scope in scopes]
    return None if any(p is None for p in parts) else sum(parts)


def state_steps(run) -> list:
    """The attributes of the window's ``serve.decode_step`` spans that
    say what state, context and routing their step moved (none on a
    program without them)."""
    return [s["attrs"] for s in run.spans
            if s["name"] == "serve.decode_step"
            and all(k in s["attrs"] for k in (
                "state_bytes", "context_tokens", "moe_held", "moe_hit"))]


def share(run, ms, per_step):
    """The mean least time of the window's steps (``per_step(attrs) ->
    (flops, bytes)`` over :func:`state_steps`) over ``ms``, in per
    cent; None where either is missing."""
    steps = state_steps(run)
    if not ms or not steps:
        return None
    least = sum(roofline_seconds(*per_step(a), run.peaks) for a in steps) \
        / len(steps)
    return 100.0 * 1e3 * least / ms
