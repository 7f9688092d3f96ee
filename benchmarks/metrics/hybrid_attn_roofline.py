"""Kernels: the least time the chip could take for the attention over
the paged K/V rows in the window's decode steps, over the device time
of the ``gqa.attn`` scope.

Bytes: the K and V rows of the slots' contexts once a slot and layer
(``context_tokens`` of each ``serve.decode_step`` span x 2 x 512
values: a key head's 5 query heads share one read); operations: scores
and mix of 20 heads, each against its own key head
(``lib/flops_hybrid_ssm.py``)."""

from benchmarks.lib import flops_hybrid_ssm as f


def read(run):
    cfg, c = run.config, run.counters
    return f.share(run, f.scopes_ms_per_call(run, ("gqa.attn",)), lambda a: (
        f.attn_flops(cfg, a["context_tokens"]),
        f.attn_bytes(cfg, a["context_tokens"], c["kv_itemsize"])))
