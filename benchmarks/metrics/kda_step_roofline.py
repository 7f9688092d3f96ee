"""Kernels: the least time the chip could take for the window's decode
steps, over the device time they took (``decode_step_device_ms``): the
cell's share of the whole step.

The slots' state once in and once out, every matrix outside the experts
and the untied head once, the held experts that got a token, the latent
rows of the slots' contexts once a slot and latent layer; operations by
the same counts (``lib/flops_delta_moe.py``).  The counts are read from
the engine's ``serve.decode_step`` spans, so a window whose contexts and
routing move is weighted as it ran."""

from benchmarks.lib import flops_delta_moe as f
from benchmarks.lib import xplane


def read(run):
    cfg, c = run.config, run.counters
    return f.share(
        run, xplane.program_ms_per_call(run.trace, "step"), lambda a: (
            f.step_flops(cfg, a), f.step_bytes(cfg, a, c["weight_itemsize"])))
