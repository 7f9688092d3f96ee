"""Feed path: busy time of the prefetch thread producing batches (the
``feed.gather`` spans: the native row gather), per dispatched step."""


def read(run):
    steps = sum(1 for s in run.spans if s["name"] == "step_dispatch")
    gathers = [s["dur_s"] for s in run.spans if s["name"] == "feed.gather"]
    if not steps or not gathers:
        return None
    return 1e3 * sum(gathers) / steps
