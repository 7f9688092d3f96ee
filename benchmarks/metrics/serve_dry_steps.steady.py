"""Serving host loop: percent of decode steps dispatched onto a chip
that had run dry (``serve.dispatch{program=step, dry=1}``) in a cycle
that admitted NO request: the host's per-slot work of read, emit and
prep outlasted the step in flight (``lib/servecycle``)."""

from benchmarks.lib import servecycle


def read(run):
    return servecycle.dry_share(run, admitting=False)
