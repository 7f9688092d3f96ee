"""Kernels: the least time the chip could take for the grouped products
of the held experts in the window's steps, over the device time of the
``moe.experts`` scope, under a configuration that spells an expert's
width ``moe_intermediate_size`` (``moe_experts_roofline`` reads
``expert_ffn_hidden_size``).

Bytes: the weights of the held experts that got a token (``moe_hit`` of
each ``serve.decode_step`` span x one expert's three matrices);
operations: 2 per weight per assignment to a held expert (``moe_held``)
(``lib/flops_delta_moe.py``)."""

from benchmarks.lib import flops_delta_moe as f


def read(run):
    cfg, c = run.config, run.counters
    return f.share(run, f.scopes_ms_per_call(run, f.EXPERT_SCOPES), lambda a: (
        f.moe_experts_flops(cfg, a["moe_held"]),
        f.moe_experts_bytes(cfg, a["moe_hit"], c["weight_itemsize"])))
