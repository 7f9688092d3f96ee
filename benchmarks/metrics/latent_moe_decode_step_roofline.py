"""Kernels: the least time the chip could take for the window's decode
steps of a latent-attention expert model, over the device time they
took (``decode_step_device_ms``).

Every dense matrix and the head's slice once, the held experts that got
a token, the rows of the slots' contexts; operations by the same counts
(``lib/flops_latent_moe.py``).  The counts are read from the engine's
``serve.decode_step`` spans, so a window whose routing or contexts move
is weighted as it ran."""

from benchmarks.lib import flops_latent_moe as f
from benchmarks.lib import xplane


def read(run):
    ms = xplane.program_ms_per_call(run.trace, "step")
    cfg, c = run.config, run.counters
    least = f.mean_least_ms(run, lambda a: (
        f.decode_step_flops(cfg, c["batch"], a["context_tokens"],
                            a["moe_held"]),
        f.decode_step_bytes(cfg, a["context_tokens"], a["moe_hit"],
                            c["weight_itemsize"])))
    if not ms or least is None:
        return None
    return 100.0 * least / ms
