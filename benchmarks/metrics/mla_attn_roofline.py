"""Kernels: the least time the chip could take for the decode
attention of the window's steps, over the device time of the
``mla.attn`` scope.

Bytes: the rows of the slots' contexts (``context_tokens`` of each
``serve.decode_step`` span x the row's 576 values, in every cached
attention) and ``W_kvb`` once an attention; operations: scores and mix
of 64 heads over those rows (``lib/flops_latent_moe.py``)."""

from benchmarks.lib import flops_latent_moe as f


def read(run):
    ms = f.scopes_ms_per_call(run, ("mla.attn",))
    cfg, c = run.config, run.counters
    least = f.mean_least_ms(run, lambda a: (
        f.mla_attn_flops(cfg, a["context_tokens"], c["batch"]),
        f.mla_attn_bytes(cfg, a["context_tokens"], c["kv_itemsize"])))
    if not ms or least is None:
        return None
    return 100.0 * least / ms
