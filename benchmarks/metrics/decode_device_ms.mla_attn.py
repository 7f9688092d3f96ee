"""Model math: device time of the decode step's operations traced
under ``jax.named_scope("mla.attn")`` (the latent attention over the
paged rows with ``W_kvb`` absorbed, ``nn/latent.py``), per call of
``jit_step``."""

from benchmarks.lib import flops_latent_moe as f


def read(run):
    return f.scopes_ms_per_call(run, ("mla.attn",))
