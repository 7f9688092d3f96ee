"""Serving host loop: the engine thread's milliseconds a decode step in
``serve.wait{program=step}``: blocked on the result of the step before
(``lib/servecycle``).  The host's SLACK: at 0 the host sets the pace."""

from benchmarks.lib import servecycle


def read(run):
    return servecycle.span_ms_per_step(run, "wait")
