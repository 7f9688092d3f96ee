"""Feed path: the chips' idle time, a step and chip, while the
``feed.h2d`` of the step that starts after the gap was still open: the
chip waiting for its batch (``lib/hostgaps.attribute_training``)."""

from benchmarks.lib import hostgaps


def read(run):
    return hostgaps.idle_ms_per_step(run, "h2d")
