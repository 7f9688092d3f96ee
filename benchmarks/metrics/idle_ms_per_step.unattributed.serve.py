"""Device: the chip's idle time a decode step that no span of the
engine's loop covers (``lib/hostgaps.attribute_serving``)."""

from benchmarks.lib import hostgaps


def read(run):
    if run.config["kind"] != "serve":
        return None
    return hostgaps.idle_ms_per_step(run, "unattributed")
