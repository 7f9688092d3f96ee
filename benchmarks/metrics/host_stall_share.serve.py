"""Serving host loop: percent of the window the engine's loop stood
still in pauses: ``obs.stall{loop=serve}`` spans of the program's stall
watch (``bigdl_tpu/obs/prof.py``), those a profiler session's start
caused aside, clipped to the window (``lib/stalls``).  0.0 where the
watch ran and met none; a 45 s window that loses 2 s reads 4.4 %."""

from benchmarks.lib import stalls


def read(run):
    return stalls.stall_share(run, "serve")
