"""Kernels: the least time the chip could take for the latent decode
attention of the window's steps, over the device time of the
``mla.attn`` scope, under a model whose latent layers are one in six
(``mla_attn_roofline`` counts two cached attentions a layer of
``num_layers``; this configuration's keys say which layers are latent).

Bytes: the rows of the slots' contexts (``context_tokens`` of each
``serve.decode_step`` span x the row's 576 values, a latent layer) and
``W_kvb`` once a latent layer; operations: scores and mix of 32 heads
over those rows (``lib/flops_delta_moe.py``)."""

from benchmarks.lib import flops_delta_moe as f


def read(run):
    cfg, c = run.config, run.counters
    return f.share(run, f.scopes_ms_per_call(run, ("mla.attn",)), lambda a: (
        f.mla_attn_flops(cfg, a["context_tokens"],
                         f.slots_of(cfg, a["state_bytes"])),
        f.mla_attn_bytes(cfg, a["context_tokens"], c["kv_itemsize"])))
