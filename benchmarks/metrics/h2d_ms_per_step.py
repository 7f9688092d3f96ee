"""Feed path: median of the trainer's ``feed.h2d`` span, from the start
of ``_put_batch`` until the batch is ready on every chip."""

from benchmarks.lib import harness


def read(run):
    durs = [s["dur_s"] for s in run.spans if s["name"] == "feed.h2d"]
    if not durs:
        return None
    return 1e3 * harness.median(durs)
