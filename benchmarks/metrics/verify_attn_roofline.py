"""Kernels: the least time the chip could take for the latent attention
of the window's verify-and-draft steps, over the device time of the
``mla.attn`` scope (the prediction layer's attention included: it is
one of the cached attentions).

Bytes: the rows of the slots' contexts once a slot (``context_tokens``
of each ``serve.decode_step`` span x the row's 576 values, in every
cached attention: the two queries of a slot share one read) and
``W_kvb`` once an attention; operations: scores and mix of 32 heads and
two queries over those rows (``lib/flops_draft_moe.py``)."""

from benchmarks.lib import flops_draft_moe as d
from benchmarks.lib import flops_latent_moe as f


def read(run):
    ms = f.scopes_ms_per_call(run, ("mla.attn",))
    cfg, c = run.config, run.counters
    least = d.mean_least_ms(run, lambda a: (
        d.attn_flops(cfg, a["context_tokens"], d.VERIFIED * c["batch"]),
        d.attn_bytes(cfg, a["context_tokens"], c["kv_itemsize"])))
    if not ms or least is None:
        return None
    return 100.0 * least / ms
