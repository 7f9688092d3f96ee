"""Model math: device time of the decode step's operations traced
under the state-space mixer's scopes, ``ssm.proj`` (its two
projections), ``ssm.conv`` (the convolution over the slot's kept rows)
and ``ssm.scan`` (the update of the slots' state, ``y``, the gate and
the group norm; ``nn/ssm.py``), per call of ``jit_step``."""

from benchmarks.lib import flops_hybrid_ssm as f


def read(run):
    return f.scopes_ms_per_call(run, ("ssm.proj", "ssm.conv", "ssm.scan"))
