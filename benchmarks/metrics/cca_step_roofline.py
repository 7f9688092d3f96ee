"""Kernels: the least time the chip could take for the window's decode
steps, over the device time they took (``decode_step_device_ms``): the
cell's share of the whole step.

Every attention and router matrix and the tied head once, the experts
that got a token, the K and V rows of the slots' contexts once a slot
and layer; operations by the same counts (``lib/flops_cca_moe.py``).
The counts are read from the engine's ``serve.decode_step`` spans, so a
window whose routing or contexts move is weighted as it ran."""

from benchmarks.lib import flops_cca_moe as f
from benchmarks.lib import xplane


def read(run):
    cfg, c = run.config, run.counters
    return f.share(
        run, xplane.program_ms_per_call(run.trace, "step"), lambda a: (
            f.step_flops(cfg, c["batch"], a["context_tokens"],
                         a["moe_held"]),
            f.step_bytes(cfg, a["context_tokens"], a["moe_hit"],
                         c["weight_itemsize"])))
