"""Operations and bytes one decode step of a decoder that mixes by **a
gated delta rule (a delta-rule linear attention with a decay a head) in
most layers and by full attention in the others**, a gated MLP behind
either, must move, from its configuration in the published
``config.json`` spelling (``configs/olmo_hybrid_7b.json``) and the
attributes of the engine's ``serve.decode_step`` spans.  Nothing is read
from the program.

The counts are the numerators of ``gdn_state_roofline``,
``gdn_attn_roofline`` and ``gdn_step_roofline``.  Like
``lib/flops_delta_moe.py`` and ``lib/flops_hybrid_ssm.py`` they count
what the mathematics must move and multiply (2 per multiply-add), never
what a program happens to execute: the slots' state ONCE in and ONCE out
by its VALUES (``state_bytes`` of the span: the slots that ran x a
slot's bytes x 2: ``H x d_k x d_v`` float32 a layer and the
convolution's rows, whatever lanes a layout pads them to, so a padded
layout reads as a LOWER share, never a higher one), the K and V rows of
the contexts once a slot and full layer (``context_tokens``: rows up to
each slot's own length, not the bucket, not what a stream copies),
every layer matrix and the head once, the embedding's rows not at all.
The rows a step writes and the norms' gains are left out (under 0.1 %).
"""

from __future__ import annotations

# the window's steps that say what state and context they moved, and the
# share of the memory roofline over them: what a state model's cell reads,
# whichever mixer holds the state
from benchmarks.lib.flops_hybrid_ssm import share, state_steps  # noqa: F401

#: the scopes (``jax.named_scope``) of the model's ``jit_step``, as
#: ``hostgaps.scope_ms_per_call`` takes them
SCOPES = ("gdn.proj", "gdn.conv", "gdn.state", "attn", "kv_write", "ffn",
          "dense", "sample")

STATE_ITEMSIZE = 4      # the state is float32 whatever the weights are


def layer_kinds(cfg: dict) -> list:
    """``True`` for a full-attention layer, of every layer built: a kept
    layer is what ``layer_types`` says at its PUBLISHED index."""
    kept = cfg.get("kept_layers", range(int(cfg["num_hidden_layers"])))
    return [cfg["layer_types"][int(i)] == "full_attention" for i in kept]


def linear_layers(cfg: dict) -> int:
    return sum(not full for full in layer_kinds(cfg))


def full_layers(cfg: dict) -> int:
    return sum(layer_kinds(cfg))


def key_channels(cfg: dict) -> int:
    return int(cfg["linear_num_key_heads"]) * int(cfg["linear_key_head_dim"])


def value_channels(cfg: dict) -> int:
    return int(cfg["linear_num_value_heads"]) \
        * int(cfg["linear_value_head_dim"])


def state_values(cfg: dict) -> int:
    """Values of ``S`` a slot and linear layer: ``H x d_k x d_v``."""
    return value_channels(cfg) * int(cfg["linear_key_head_dim"])


def conv_channels(cfg: dict) -> int:
    """Channels of ``[q ; k ; v]``."""
    return 2 * key_channels(cfg) + value_channels(cfg)


def slot_state_bytes(cfg: dict) -> int:
    """Bytes of state a slot carries over all linear layers, by its
    values: ``S`` and the convolution's kept rows."""
    kept = (int(cfg["linear_conv_kernel_dim"]) - 1) * conv_channels(cfg)
    return linear_layers(cfg) * (state_values(cfg) + kept) * STATE_ITEMSIZE


def mixer_params(cfg: dict) -> float:
    """``W_in`` (q, k, v, z and the two gates' ``H`` rows each) and
    ``W_out``."""
    d = float(cfg["hidden_size"])
    return d * (2 * key_channels(cfg) + 2 * value_channels(cfg)
                + 2 * int(cfg["linear_num_value_heads"])) \
        + d * value_channels(cfg)


def head_dim(cfg: dict) -> int:
    return int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])


def row_values(cfg: dict) -> int:
    """Values of a token's cached K row (and of its V row)."""
    return int(cfg["num_key_value_heads"]) * head_dim(cfg)


def attention_params(cfg: dict) -> float:
    d = float(cfg["hidden_size"])
    return d * (2 * cfg["num_attention_heads"] * head_dim(cfg)
                + 2 * row_values(cfg))


def mlp_params(cfg: dict) -> float:
    return 3.0 * cfg["hidden_size"] * cfg["intermediate_size"]


def matrix_params(cfg: dict) -> float:
    """Every matrix a step reads: the layers' and the untied head."""
    return (linear_layers(cfg) * mixer_params(cfg)
            + full_layers(cfg) * attention_params(cfg)
            + len(layer_kinds(cfg)) * mlp_params(cfg)
            + float(cfg["vocab_size"]) * cfg["hidden_size"])


def slots_of(cfg: dict, state_bytes: float) -> float:
    """The slots a step ran for, from what it says it moved."""
    return state_bytes / (2.0 * slot_state_bytes(cfg))


def state_flops(cfg: dict, state_bytes: float) -> float:
    """The recurrence over the slots that ran: the decay (1 a value of
    ``S``), the read along the key, the rank-1 write and the read along
    the query (2 each), and the convolution's taps."""
    per_layer = 7.0 * state_values(cfg) \
        + 2.0 * cfg["linear_conv_kernel_dim"] * conv_channels(cfg)
    return slots_of(cfg, state_bytes) * linear_layers(cfg) * per_layer


def attn_bytes(cfg: dict, context_tokens: float, itemsize: int) -> float:
    """The contexts' K and V rows once a slot and full layer
    (``context_tokens``: a slot's rows up to the token it computes)."""
    return full_layers(cfg) * 2.0 * context_tokens * row_values(cfg) \
        * itemsize


def attn_flops(cfg: dict, context_tokens: float) -> float:
    """Scores and mix of every query head over its OWN key head's values
    of the context's rows (not the block-diagonal product a program may
    make of them)."""
    per_row = 2.0 * 2.0 * cfg["num_attention_heads"] * head_dim(cfg)
    return full_layers(cfg) * per_row * context_tokens


def step_bytes(cfg: dict, attrs: dict, itemsize: int) -> float:
    """Everything one step has to move: the state in and out, every
    layer matrix and the head once, the contexts' rows once a slot and
    full layer."""
    return attrs["state_bytes"] + itemsize * matrix_params(cfg) \
        + attn_bytes(cfg, attrs["context_tokens"], itemsize)


def step_flops(cfg: dict, attrs: dict) -> float:
    """2 per weight per slot, the recurrence, the attention over the
    contexts."""
    return (2.0 * matrix_params(cfg) * slots_of(cfg, attrs["state_bytes"])
            + state_flops(cfg, attrs["state_bytes"])
            + attn_flops(cfg, attrs["context_tokens"]))


def scopes_ms_per_call(run, scopes):
    """Device ms a call of ``jit_step`` under ``scopes`` together; None
    where the trace holds no scoped operation of the program, or none
    under these scopes (a program without this model's mixer)."""
    from benchmarks.lib import hostgaps

    parts = [hostgaps.scope_ms_per_call(run, "jit_step", SCOPES, scope)
             for scope in scopes]
    if any(p is None for p in parts) or not sum(parts):
        return None
    return sum(parts)
