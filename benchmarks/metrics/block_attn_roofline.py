"""Kernels: the least time the chip could take for the grouped-query
attention of the window's block steps, over the device time of the
``gqa.attn`` scope.

Bytes: the K and V rows of the slots' contexts once a slot and layer
(``context_tokens`` of each ``serve.decode_step`` span x 2 x 512 values:
a block's 4 positions and a key head's 8 query heads share one read);
operations: scores and mix of 32 heads at 4 positions over those rows
(``lib/flops_block_moe.py``)."""

from benchmarks.lib import flops_block_moe as f


def read(run):
    ms = f.scopes_ms_per_call(run, ("gqa.attn",))
    cfg, c = run.config, run.counters
    least = f.mean_least_ms(run, lambda a: (
        f.attn_flops(cfg, a["context_tokens"]),
        f.attn_bytes(cfg, a["context_tokens"], c["kv_itemsize"])))
    if not ms or least is None:
        return None
    return 100.0 * least / ms
