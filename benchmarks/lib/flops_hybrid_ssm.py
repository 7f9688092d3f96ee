"""Operations and bytes one decode step of a decoder whose every block
runs **a state-space mixer (Mamba-2) beside a grouped-query attention**
must move, from its configuration in the published ``config.json``
spelling (``configs/falcon_h1_34b.json``) and the attributes of the
engine's ``serve.decode_step`` spans.  Nothing is read from the program.

The counts are the numerators of ``ssm_state_roofline``,
``hybrid_attn_roofline`` and ``hybrid_step_roofline``.  Like
``lib/flops_cca_moe.py`` they count what the mathematics must move and
multiply (2 per multiply-add), never what a program happens to execute:
the slots' state ONCE in and ONCE out (``state_bytes`` of the span: the
slots that ran x a slot's bytes x 2, whatever implements the update),
the rows of the contexts once a slot and layer (a key head's 5 query
heads share them), every layer matrix and the head once, the
embedding's rows not at all.  Scores and mixes are counted per query
head against ITS key head's ``head_dim`` values.  The rows a step writes
and the norms' gains are left out (under 0.1 %).
"""

from __future__ import annotations

from benchmarks.lib.flops import roofline_seconds

#: the scopes (``jax.named_scope``) of the model's ``jit_step``, as
#: ``hostgaps.scope_ms_per_call`` takes them
SCOPES = ("ssm.proj", "ssm.conv", "ssm.scan", "gqa.attn", "ffn",
          "kv_write", "dense", "sample")

STATE_ITEMSIZE = 4      # the state is float32 whatever the weights are


def layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def row_values(cfg: dict) -> int:
    """Values of a token's cached K row (and of its V row)."""
    return int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])


def conv_channels(cfg: dict) -> int:
    """Channels of ``[x ; B ; C]``."""
    return int(cfg["mamba_d_ssm"]) \
        + 2 * int(cfg["mamba_n_groups"]) * int(cfg["mamba_d_state"])


def state_values(cfg: dict) -> int:
    """Values of ``H`` a slot and layer."""
    return int(cfg["mamba_n_heads"]) * int(cfg["mamba_d_head"]) \
        * int(cfg["mamba_d_state"])


def slot_state_bytes(cfg: dict) -> int:
    """Bytes of state a slot carries over all layers: ``H`` and the
    convolution's kept rows."""
    kept = (int(cfg["mamba_d_conv"]) - 1) * conv_channels(cfg)
    return layers(cfg) * (state_values(cfg) + kept) * STATE_ITEMSIZE


def mixer_params(cfg: dict) -> float:
    """The mixer's in- and out-projection."""
    d, inner = float(cfg["hidden_size"]), int(cfg["mamba_d_ssm"])
    return d * (inner + conv_channels(cfg) + cfg["mamba_n_heads"]) \
        + d * inner


def attention_params(cfg: dict) -> float:
    d, hd = float(cfg["hidden_size"]), int(cfg["head_dim"])
    return d * (2 * cfg["num_attention_heads"] * hd + 2 * row_values(cfg))


def mlp_params(cfg: dict) -> float:
    return 3.0 * cfg["hidden_size"] * cfg["intermediate_size"]


def head_params(cfg: dict) -> float:
    """The untied head, read whole once a step."""
    return float(cfg["vocab_size"]) * cfg["hidden_size"]


def matrix_params(cfg: dict) -> float:
    """Every matrix a step reads: the layers' and the head."""
    return layers(cfg) * (mixer_params(cfg) + attention_params(cfg)
                          + mlp_params(cfg)) + head_params(cfg)


def slots_of(cfg: dict, state_bytes: float) -> float:
    """The slots a step ran for, from what it says it moved."""
    return state_bytes / (2.0 * slot_state_bytes(cfg))


def state_flops(cfg: dict, state_bytes: float) -> float:
    """The recurrence over the slots that ran: decay, increment and
    ``y`` (5 a value of ``H``), and the convolution's taps."""
    per_layer = 5.0 * state_values(cfg) \
        + 2.0 * cfg["mamba_d_conv"] * conv_channels(cfg)
    return slots_of(cfg, state_bytes) * layers(cfg) * per_layer


def attn_bytes(cfg: dict, context_tokens: float, itemsize: int) -> float:
    """The contexts' K and V rows once a slot and layer
    (``context_tokens``: a slot's rows up to the token it computes)."""
    return layers(cfg) * 2.0 * context_tokens * row_values(cfg) * itemsize


def attn_flops(cfg: dict, context_tokens: float) -> float:
    """Scores and mix of every query head over its key head's values of
    the context's rows."""
    per_row = 2.0 * 2.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return layers(cfg) * per_row * context_tokens


def step_bytes(cfg: dict, context_tokens: float, state_bytes: float,
               itemsize: int) -> float:
    """Everything one step has to move: the state in and out, every
    layer matrix and the head once, the contexts' rows once a slot and
    layer."""
    return state_bytes + itemsize * matrix_params(cfg) \
        + attn_bytes(cfg, context_tokens, itemsize)


def step_flops(cfg: dict, context_tokens: float,
               state_bytes: float) -> float:
    """2 per weight per slot, the recurrence, the attention over the
    contexts."""
    return (2.0 * matrix_params(cfg) * slots_of(cfg, state_bytes)
            + state_flops(cfg, state_bytes)
            + attn_flops(cfg, context_tokens))


def scopes_ms_per_call(run, scopes):
    """Device ms a call of ``jit_step`` under ``scopes`` together; None
    where the trace holds no scoped operation of the program."""
    from benchmarks.lib import hostgaps

    parts = [hostgaps.scope_ms_per_call(run, "jit_step", SCOPES, scope)
             for scope in scopes]
    return None if any(p is None for p in parts) else sum(parts)


def state_steps(run) -> list:
    """The attributes of the window's ``serve.decode_step`` spans that
    say what state and context their step moved (none on a program
    without them)."""
    return [s["attrs"] for s in run.spans
            if s["name"] == "serve.decode_step"
            and "state_bytes" in s["attrs"]
            and "context_tokens" in s["attrs"]]


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
