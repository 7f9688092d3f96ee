"""Model math: device time of the decode step's operations traced
under ``jax.named_scope("gqa.attn")`` (the grouped-query attention of a
block's positions over the paged per-head K/V rows,
``models/sdar_moe.py``), per call of ``jit_step``."""

from benchmarks.lib import flops_block_moe as f


def read(run):
    return f.scopes_ms_per_call(run, ("gqa.attn",))
