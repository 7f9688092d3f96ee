"""Serving host loop: the chip's idle time a decode step while the
engine was in ``serve.emit`` (``lib/hostgaps.attribute_serving``)."""

from benchmarks.lib import hostgaps


def read(run):
    return hostgaps.idle_ms_per_step(run, "emit")
