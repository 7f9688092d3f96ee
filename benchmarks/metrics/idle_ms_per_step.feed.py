"""Feed path: the chips' idle time, a step and chip, while the
trainer's loop was in ``input_prefetch``, ``data_wait``, ``batch_prep``
or ``device_put`` (``lib/hostgaps.attribute_training``)."""

from benchmarks.lib import hostgaps


def read(run):
    return hostgaps.idle_ms_per_step(run, "feed")
