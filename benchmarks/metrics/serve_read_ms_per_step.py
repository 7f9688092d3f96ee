"""Serving host loop: the engine thread's milliseconds a decode step in
``serve.read{program=step}``: the per-slot loop over the tokens it has
read, the draft and block counters, the routing counts
(``lib/servecycle``).  Host WORK."""

from benchmarks.lib import servecycle


def read(run):
    return servecycle.span_ms_per_step(run, "read")
