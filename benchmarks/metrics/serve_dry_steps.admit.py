"""Serving host loop: percent of decode steps dispatched onto a chip
that had run dry (``serve.dispatch{program=step, dry=1}``) in a cycle
that DID admit a request: the chip ran dry behind a synchronous prefill
(``lib/servecycle``)."""

from benchmarks.lib import servecycle


def read(run):
    return servecycle.dry_share(run, admitting=True)
