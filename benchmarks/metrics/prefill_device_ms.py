"""Model math: device time of the engine's jitted ``prefill`` programs,
per call, from the profiler's trace."""

from benchmarks.lib import xplane


def read(run):
    return xplane.program_ms_per_call(run.trace, "prefill")
