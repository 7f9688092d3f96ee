"""Kernels: the least time the chip could take for the full attention
over the paged K/V rows in the window's decode steps, over the device
time of the ``attn`` scope.

Bytes: the K and V rows of the slots' contexts once a slot and full
layer (``context_tokens`` of each ``serve.decode_step`` span x 2 x 3840
values: rows up to each slot's own length, not the bucket and not what
the stream copies); operations: scores and mix of 30 heads, each against
its own key head (``lib/flops_gated_delta.py``)."""

from benchmarks.lib import flops_gated_delta as f


def read(run):
    cfg, c = run.config, run.counters
    return f.share(run, f.scopes_ms_per_call(run, ("attn",)), lambda a: (
        f.attn_flops(cfg, a["context_tokens"]),
        f.attn_bytes(cfg, a["context_tokens"], c["kv_itemsize"])))
