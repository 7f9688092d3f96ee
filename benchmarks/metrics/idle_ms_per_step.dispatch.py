"""Serving host loop: the chip's idle time a decode step while the
engine was in a ``serve.dispatch`` (either program) inside
``serve.decode_step`` or ``serve.prefill``: the chip waiting for the
host to launch (``lib/servecycle.cut_sync``: a part of
``idle_ms_per_step.sync``)."""

from benchmarks.lib import servecycle


def read(run):
    return servecycle.idle_ms_per_step(run, "dispatch")
