"""Operations and bytes one **verify-and-draft** decode step must move:
a decoder with latent attention, a leading dense layer, expert layers
with a shared expert, and a prediction layer that drafts, from its
configuration in the published ``config.json`` spelling
(``configs/joyai_llm_flash.json``).

A step runs the main model over two positions a slot (the certain token
and the draft) and the prediction layer behind it.  The counts are the
numerators of ``verify_attn_roofline``, ``verify_moe_experts_roofline``
and ``verify_step_roofline``.  Like ``lib/flops_latent_moe.py`` they
count what the mathematics must move and multiply (2 per multiply-add),
never what a program happens to execute: the rows of the contexts ONCE a
slot (the two queries of a slot share them) and not the page bucket, a
row's ``kv_lora_rank + qk_rope_head_dim`` values and not the lanes it is
padded to, the experts that got a token and not the experts held, the
head's matrix once (the program multiplies by it twice, for the main
model's positions and for the draft) and the embedding's rows not at
all.  The prediction layer is counted where its results can stand: one
position a slot and one more where the draft was accepted
(``tokens_emitted``).
"""

from __future__ import annotations

from benchmarks.lib.flops_latent_moe import (attention_params,  # noqa: F401
                                             kv_up_params, row_values)

#: positions the main model verifies a slot and step
VERIFIED = 2


def main_layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def dense_layers(cfg: dict) -> int:
    return min(int(cfg.get("first_k_dense_replace", 0)), main_layers(cfg))


def draft_layers(cfg: dict) -> int:
    return int(cfg.get("num_nextn_predict_layers", 0))


def cached_attentions(cfg: dict) -> int:
    """One a main layer and one a prediction layer."""
    return main_layers(cfg) + draft_layers(cfg)


def expert_layers(cfg: dict) -> int:
    """Expert layers a step runs: the main model's and the prediction
    layer's."""
    return main_layers(cfg) - dense_layers(cfg) + draft_layers(cfg)


def expert_params(cfg: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> float:
    return cfg.get("n_shared_experts", 0) * expert_params(cfg)


def router_params(cfg: dict) -> float:
    """The router keeps the published width (``router_experts`` where
    ``n_routed_experts`` counts a chip's share)."""
    return float(cfg["hidden_size"]) * int(
        cfg.get("router_experts", cfg["n_routed_experts"]))


def expert_layer_dense_params(cfg: dict) -> float:
    """An expert layer outside its routed experts: the attention, the
    shared expert, the router."""
    return attention_params(cfg) + shared_params(cfg) + router_params(cfg)


def dense_layer_params(cfg: dict) -> float:
    """A leading dense layer: the attention and its gated MLP."""
    return attention_params(cfg) \
        + 3.0 * cfg["hidden_size"] * cfg["intermediate_size"]


def head_params(cfg: dict) -> float:
    return float(cfg["vocab_size"]) * cfg["hidden_size"]


def join_params(cfg: dict) -> float:
    """The prediction layer's ``W_eh``: two hidden vectors to one."""
    return draft_layers(cfg) * 2.0 * cfg["hidden_size"] ** 2


def main_dense_params(cfg: dict) -> float:
    """The main model's matrices outside the routed experts, the head
    included."""
    return (dense_layers(cfg) * dense_layer_params(cfg)
            + (main_layers(cfg) - dense_layers(cfg))
            * expert_layer_dense_params(cfg) + head_params(cfg))


def draft_dense_params(cfg: dict) -> float:
    """The prediction layer's own matrices outside its routed experts
    (the head is the main model's)."""
    return draft_layers(cfg) * expert_layer_dense_params(cfg) \
        + join_params(cfg)


def attn_bytes(cfg: dict, context_tokens: float, itemsize: int) -> float:
    """One step's decode attention, all cached attentions: the contexts'
    rows once each (``context_tokens``: a slot's rows up to its second
    query's position), and ``W_kvb`` once an attention."""
    return cached_attentions(cfg) * itemsize * (
        context_tokens * row_values(cfg) + kv_up_params(cfg))


def attn_flops(cfg: dict, context_tokens: float, positions: float) -> float:
    """Scores over a row's values and the mix over its ``kv_lora_rank``
    for every head, context token and each of a slot's two queries (the
    second reads one row more: counted as the first), and the two
    absorptions of ``W_kvb`` a position."""
    h = float(cfg["num_attention_heads"])
    over_rows = 2.0 * h * (row_values(cfg) + cfg["kv_lora_rank"]) \
        * context_tokens * VERIFIED
    absorbed = 2.0 * kv_up_params(cfg) * positions
    return cached_attentions(cfg) * (over_rows + absorbed)


def experts_bytes(cfg: dict, experts_hit: float, itemsize: int) -> float:
    """The weights of the held experts that got a token (summed over
    the step's expert layers)."""
    return experts_hit * expert_params(cfg) * itemsize


def experts_flops(cfg: dict, held_assignments: float) -> float:
    return 2.0 * expert_params(cfg) * held_assignments


def step_bytes(cfg: dict, context_tokens: float, experts_hit: float,
               itemsize: int) -> float:
    """Everything one step has to read: every dense matrix, the shared
    experts and the head once, the experts that got a token, the
    contexts' rows once a slot and cached attention.  Norm gains, the
    selection bias, the embedding rows of the step's tokens and the rows
    it writes are left out (under 0.1 %)."""
    weights = main_dense_params(cfg) + draft_dense_params(cfg)
    rows = cached_attentions(cfg) * context_tokens * row_values(cfg)
    return itemsize * (weights + rows) \
        + experts_bytes(cfg, experts_hit, itemsize)


def step_flops(cfg: dict, slots: float, tokens_emitted: float,
               context_tokens: float, held_assignments: float) -> float:
    """2 per weight per position outside the experts (``W_kvb`` is
    counted with the attention): the main model at two positions a slot,
    the prediction layer and its head product at the positions that can
    stand (``tokens_emitted``), the experts' assignments, the attention
    over the contexts."""
    kv_up = kv_up_params(cfg)
    main = main_dense_params(cfg) - main_layers(cfg) * kv_up
    draft = draft_dense_params(cfg) - draft_layers(cfg) * kv_up
    return (2.0 * main * VERIFIED * slots
            + 2.0 * draft * tokens_emitted
            + 2.0 * head_params(cfg) * slots * min(1, draft_layers(cfg))
            + experts_flops(cfg, held_assignments)
            + attn_flops(cfg, context_tokens, VERIFIED * slots))


def drafted_steps(run) -> list:
    """The attributes of the window's ``serve.decode_step`` spans of a
    drafting expert model: routing counts, the contexts' rows and what
    the step yielded (none on a program without them)."""
    return [s["attrs"] for s in run.spans
            if s["name"] == "serve.decode_step"
            and "draft_verified" in s["attrs"]
            and "moe_held" in s["attrs"]
            and "context_tokens" in s["attrs"]]


def mean_least_ms(run, per_step):
    """Mean over the window's drafted steps of ``per_step(attrs) ->
    (flops, bytes)``'s least time, in ms; None without such steps."""
    from benchmarks.lib.flops import roofline_seconds

    steps = drafted_steps(run)
    if not steps:
        return None
    total = sum(roofline_seconds(*per_step(a), run.peaks) for a in steps)
    return 1e3 * total / len(steps)
