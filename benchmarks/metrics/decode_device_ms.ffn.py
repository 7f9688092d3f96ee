"""Model math: device time of the decode step's operations traced
under ``jax.named_scope("ffn")`` (the dense gated MLPs), per call of
``jit_step``."""

from benchmarks.lib import flops_latent_moe as f


def read(run):
    return f.scopes_ms_per_call(run, ("ffn",))
