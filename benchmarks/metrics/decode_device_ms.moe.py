"""Model math: device time of the decode step's expert layers (the
scopes ``moe.route``, ``moe.experts`` and ``moe.zero`` of
``nn/experts.py`` and the compiler's own grouped-product kernels, which
carry no scope), per call of ``jit_step``."""

from benchmarks.lib import flops_latent_moe as f


def read(run):
    return f.scopes_ms_per_call(run, f.MOE_SCOPES)
