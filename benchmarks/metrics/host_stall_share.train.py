"""Trainer host loop: percent of the window the trainer's loop stood
still in pauses: ``obs.stall{loop=train}`` spans of the program's stall
watch (``bigdl_tpu/obs/prof.py``), those a profiler session's start
caused aside, clipped to the window (``lib/stalls``).  0.0 where the
watch ran and met none."""

from benchmarks.lib import stalls


def read(run):
    return stalls.stall_share(run, "train")
