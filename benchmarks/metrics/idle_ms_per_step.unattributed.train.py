"""Device: the chips' idle time, a step and chip, that no span of the
trainer's loop covers (``lib/hostgaps.attribute_training``)."""

from benchmarks.lib import hostgaps


def read(run):
    if run.config["kind"] != "train":
        return None
    return hostgaps.idle_ms_per_step(run, "unattributed")
