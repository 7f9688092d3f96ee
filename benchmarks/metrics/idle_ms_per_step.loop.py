"""Trainer host loop: the chips' idle time, a step and chip, while
the loop was in ``iteration``, ``step_dispatch``, ``loss_readback`` or
between two iterations (``lib/hostgaps.attribute_training``)."""

from benchmarks.lib import hostgaps


def read(run):
    return hostgaps.idle_ms_per_step(run, "loop")
