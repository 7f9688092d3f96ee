"""Model math: device time of everything the prediction layer runs in a
decode step (its join, attention, experts, shared expert, norm, head
product and pick: the outer scope ``mtp`` of
``models/joyai_flash.py``), per call of ``jit_step``.

``decode_device_ms.mla_attn`` / ``.moe`` / ``.ffn`` read with a scope
list that has no ``mtp``, so the prediction layer's attention, experts
and shared expert are in them as well: the two views overlap by
design."""

from benchmarks.lib import hostgaps


def read(run):
    return hostgaps.scope_ms_per_call(run, "jit_step", ("mtp",), "mtp")
