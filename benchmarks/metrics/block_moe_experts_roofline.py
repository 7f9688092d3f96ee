"""Kernels: the least time the chip could take for the grouped products
of the experts in the window's block steps (6 layers, 4 positions a
slot), over the device time of the ``moe.experts`` scope.

Bytes: the weights of the experts that got a token (``moe_hit`` of each
``serve.decode_step`` span x one expert's three matrices); operations: 2
per weight per assignment (``moe_held``) (``lib/flops_block_moe.py``)."""

from benchmarks.lib import flops_block_moe as f


def read(run):
    ms = f.scopes_ms_per_call(run, ("moe.experts",))
    cfg, c = run.config, run.counters
    least = f.mean_least_ms(run, lambda a: (
        f.experts_flops(cfg, a["moe_held"]),
        f.experts_bytes(cfg, a["moe_hit"], c["weight_itemsize"])))
    if not ms or least is None:
        return None
    return 100.0 * least / ms
