"""Kernels: the least time the chip could take for the window's
verify-and-draft decode steps, over the device time they took
(``decode_step_device_ms``).

Every dense matrix, the shared experts and the head once, the held
experts that got a token, the rows of the slots' contexts once a slot;
operations by the same counts (``lib/flops_draft_moe.py``).  The counts
are read from the engine's ``serve.decode_step`` spans, so a window
whose routing, contexts or acceptance move is weighted as it ran."""

from benchmarks.lib import flops_draft_moe as d
from benchmarks.lib import xplane


def read(run):
    ms = xplane.program_ms_per_call(run.trace, "step")
    cfg, c = run.config, run.counters
    least = d.mean_least_ms(run, lambda a: (
        d.step_flops(cfg, c["batch"], a["tokens_emitted"],
                     a["context_tokens"], a["moe_held"]),
        d.step_bytes(cfg, a["context_tokens"], a["moe_hit"],
                     c["weight_itemsize"])))
    if not ms or least is None:
        return None
    return 100.0 * least / ms
