"""Kernels: the least time the chip could take for the window's decode
steps, over the device time they took (``decode_step_device_ms``): the
cell's share of the whole step.

The slots' state once in and once out, every layer matrix and the
untied head once, the K and V rows of the slots' contexts once a slot
and layer; operations by the same counts
(``lib/flops_hybrid_ssm.py``).  The counts are read from the engine's
``serve.decode_step`` spans, so a window whose contexts move is
weighted as it ran."""

from benchmarks.lib import flops_hybrid_ssm as f
from benchmarks.lib import xplane


def read(run):
    cfg, c = run.config, run.counters
    return f.share(
        run, xplane.program_ms_per_call(run.trace, "step"), lambda a: (
            f.step_flops(cfg, a["context_tokens"], a["state_bytes"]),
            f.step_bytes(cfg, a["context_tokens"], a["state_bytes"],
                         c["weight_itemsize"])))
