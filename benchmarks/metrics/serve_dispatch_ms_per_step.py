"""Serving host loop: the engine thread's milliseconds a decode step in
``serve.dispatch{program=step}``: the host's arrays to the device, the
call of ``jit_step`` until it returns, the bookkeeping over the running
slots (``lib/servecycle``).  Host WORK."""

from benchmarks.lib import servecycle


def read(run):
    return servecycle.span_ms_per_step(run, "dispatch")
