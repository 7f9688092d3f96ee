"""Serving host loop: the chip's idle time a decode step while the
engine was in a ``serve.wait``: the result on its way to the host and
nothing queued behind it (``lib/servecycle.cut_sync``: a part of
``idle_ms_per_step.sync``)."""

from benchmarks.lib import servecycle


def read(run):
    return servecycle.idle_ms_per_step(run, "wait")
