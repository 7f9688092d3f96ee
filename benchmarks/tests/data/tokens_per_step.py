"""A throw-away per-layer metric of the benchmark's own tests: tokens
the engine emitted per decode step in the window."""


def read(run):
    steps = run.counters.get("steps")
    if not steps:
        return None
    return run.counters["engine_tokens"] / steps
