"""Model math: device time of the trainer's jitted step (``train_step``
on one chip, ``sharded_step`` across chips), per call and chip."""

from benchmarks.lib import xplane


def read(run):
    return xplane.program_ms_per_call(run.trace, "train_step", "sharded_step")
