"""Kernels: the least time the chip could take for the window's block
steps, over the device time they took (``decode_step_device_ms``).

Every dense matrix and the head once, the experts that got a token, the
K and V rows of the slots' contexts once a slot and layer; operations by
the same counts at every slot's 4 positions
(``lib/flops_block_moe.py``).  The counts are read from the engine's
``serve.decode_step`` spans, so a window whose routing or contexts move
is weighted as it ran."""

from benchmarks.lib import flops_block_moe as f
from benchmarks.lib import xplane


def read(run):
    ms = xplane.program_ms_per_call(run.trace, "step")
    cfg, c = run.config, run.counters
    least = f.mean_least_ms(run, lambda a: (
        f.step_flops(cfg, c["batch"], a["context_tokens"], a["moe_held"]),
        f.step_bytes(cfg, a["context_tokens"], a["moe_hit"],
                     c["weight_itemsize"])))
    if not ms or least is None:
        return None
    return 100.0 * least / ms
