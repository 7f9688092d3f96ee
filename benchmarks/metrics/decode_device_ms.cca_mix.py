"""Model math: device time of the decode step's operations traced
under ``jax.named_scope("cca.mix")`` (the two causal convolutions over
the slot's state, the query-key mean, the value shift, the norms, the
rotary and the state's update, ``models/zaya.py``), per call of
``jit_step``."""

from benchmarks.lib import flops_cca_moe as f


def read(run):
    return f.scopes_ms_per_call(run, ("cca.mix",))
