"""Kernels: the least time the chip could take for the grouped products
of the held experts in the window's verify-and-draft steps (the main
model's expert layers over two positions a slot and the prediction
layer's), over the device time of the ``moe.experts`` scope and of the
compiler's grouped-product kernels (``lib/flops_latent_moe.py``
``GROUPED``).

Bytes: the weights of the held experts that got a token (``moe_hit`` of
each ``serve.decode_step`` span x one expert's three matrices);
operations: 2 per weight per assignment to a held expert (``moe_held``)
(``lib/flops_draft_moe.py``)."""

from benchmarks.lib import flops_draft_moe as d
from benchmarks.lib import flops_latent_moe as f


def read(run):
    ms = f.scopes_ms_per_call(run, f.EXPERT_SCOPES)
    cfg, c = run.config, run.counters
    least = d.mean_least_ms(run, lambda a: (
        d.experts_flops(cfg, a["moe_held"]),
        d.experts_bytes(cfg, a["moe_hit"], c["weight_itemsize"])))
    if not ms or least is None:
        return None
    return 100.0 * least / ms
