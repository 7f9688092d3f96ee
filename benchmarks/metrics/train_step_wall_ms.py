"""Trainer host loop: the median time between two steps' losses
arriving, over the window (host clock, the benchmark's own stamps)."""

from benchmarks.lib import harness


def read(run):
    stamps = run.counters.get("loss_stamps")
    if not stamps or len(stamps) < 3:
        return None
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    return 1e3 * harness.median(gaps)
