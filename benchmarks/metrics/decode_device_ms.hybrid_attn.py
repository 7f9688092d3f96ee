"""Model math: device time of the decode step's operations traced
under ``jax.named_scope("gqa.attn")`` where a block runs it BESIDE a
state-space mixer (the paged attention of 20 query heads over 4 key
heads' rows, ``models/falcon_h1.py``), per call of ``jit_step``.  A
reader of its own: ``decode_device_ms.gqa_attn`` splits the step by
another module's list of scopes, which has no ``ssm.*``."""

from benchmarks.lib import flops_hybrid_ssm as f


def read(run):
    return f.scopes_ms_per_call(run, ("gqa.attn",))
