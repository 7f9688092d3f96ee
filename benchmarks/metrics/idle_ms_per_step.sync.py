"""Serving host loop: the chip's idle time a decode step while the
engine was inside ``serve.decode_step`` or ``serve.prefill``:
dispatching to the chip or reading back from it
(``lib/hostgaps.attribute_serving``)."""

from benchmarks.lib import hostgaps


def read(run):
    return hostgaps.idle_ms_per_step(run, "sync")
