"""Model math: device time of the decode step's operations traced
under ``jax.named_scope("cca.attn")`` (the paged attention of 8 query
heads over the 2 key heads' rows, ``models/zaya.py``), per call of
``jit_step``."""

from benchmarks.lib import flops_cca_moe as f


def read(run):
    return f.scopes_ms_per_call(run, ("cca.attn",))
