"""Kernels: the least time the chip could take for the window's decode
steps, over the device time they took (``decode_step_device_ms``): the
cell's share of the whole step.

The slots' state once in and once out by its values, every layer matrix
and the untied head once, the K and V rows of the slots' contexts once a
slot and full layer; operations by the same counts
(``lib/flops_gated_delta.py``).  The counts are read from the engine's
``serve.decode_step`` spans, so a window whose contexts move is weighted
as it ran."""

from benchmarks.lib import flops_gated_delta as f
from benchmarks.lib import xplane


def read(run):
    cfg, c = run.config, run.counters
    return f.share(
        run, xplane.program_ms_per_call(run.trace, "step"), lambda a: (
            f.step_flops(cfg, a), f.step_bytes(cfg, a, c["weight_itemsize"])))
