"""Model math: device time of the decode step's operations traced
under the KDA mixer's scopes, ``kda.proj`` (its two projections),
``kda.conv`` (the convolution over the slot's kept rows) and
``kda.state`` (the gates, the update of the slots' matrix state by the
``kda_state_update`` kernel, the norm and the output gate;
``nn/delta.py``), per call of ``jit_step``."""

from benchmarks.lib import flops_delta_moe as f


def read(run):
    return f.scopes_ms_per_call(run, ("kda.proj", "kda.conv", "kda.state"))
