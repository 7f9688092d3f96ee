"""Serving host loop: how late a freshly woken thread of the serving
process runs, in milliseconds a wake-up: ``late_ms_sum`` over ``ticks``
of the window's ``obs.host{loop=serve}`` spans (the stall watch's own
thread, which sleeps 20 ms at a time and shares the interpreter with the
engine's thread and 12 to 256 client threads; ``lib/stalls``)."""

from benchmarks.lib import stalls


def read(run):
    return stalls.wake_late_ms(run, "serve")
