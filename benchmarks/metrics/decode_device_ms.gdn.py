"""Model math: device time of the decode step's operations traced
under the gated-delta-rule mixer's scopes, ``gdn.proj`` (its two
projections), ``gdn.conv`` (the convolution over the slot's kept rows)
and ``gdn.state`` (the gates, the update of the slots' matrix state by
the ``gdn_state_update`` kernel, the norm a head and the output gate;
``nn/delta.py`` ``GatedDeltaMixer``), per call of ``jit_step``."""

from benchmarks.lib import flops_gated_delta as f


def read(run):
    return f.scopes_ms_per_call(run, ("gdn.proj", "gdn.conv", "gdn.state"))
