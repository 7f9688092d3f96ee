"""Model math: device time of the decode step's operations traced
under ``jax.named_scope("ffn")`` (the gated MLP of 21504 after the
mixer and the attention, ``models/falcon_h1.py``), per call of
``jit_step``.  A reader of its own: ``decode_device_ms.ffn`` splits the
step by another module's list of scopes, which has no ``ssm.*``."""

from benchmarks.lib import flops_hybrid_ssm as f


def read(run):
    return f.scopes_ms_per_call(run, ("ffn",))
