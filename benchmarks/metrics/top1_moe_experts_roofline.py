"""Kernels: the least time the chip could take for the grouped products
of the top-1 experts in the window's decode steps, over the device time
of the ``moe.experts`` scope.

Bytes: the weights of the experts that got a token (``moe_hit`` of each
``serve.decode_step`` span x one expert's three 2048 x 2048 matrices);
operations: 2 per weight per assignment (``moe_held``: one a slot and
layer) (``lib/flops_cca_moe.py``)."""

from benchmarks.lib import flops_cca_moe as f


def read(run):
    cfg, c = run.config, run.counters
    return f.share(
        run, f.scopes_ms_per_call(run, ("moe.experts",)), lambda a: (
            f.experts_flops(cfg, a["moe_held"]),
            f.experts_bytes(cfg, a["moe_hit"], c["weight_itemsize"])))
