"""Serving host loop: the chip's idle time a decode step while the
engine was in ``serve.prep`` (``lib/hostgaps.attribute_serving``)."""

from benchmarks.lib import hostgaps


def read(run):
    return hostgaps.idle_ms_per_step(run, "prep")
