"""Serving host loop: median of the engine's ``serve.prefill`` span, the
jitted prefill call and the read-back of its first token."""

from benchmarks.lib import harness


def read(run):
    durs = [s["dur_s"] for s in run.spans if s["name"] == "serve.prefill"]
    if not durs:
        return None
    return 1e3 * harness.median(durs)
