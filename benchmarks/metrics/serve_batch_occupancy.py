"""Serving host loop: mean share of the engine's decode slots in use per
step, over the window (``LMEngine.stats()`` read at both edges)."""


def read(run):
    steps = run.counters.get("steps")
    if not steps or "occupancy_sum" not in run.counters:
        return None
    return 100.0 * run.counters["occupancy_sum"] / steps
