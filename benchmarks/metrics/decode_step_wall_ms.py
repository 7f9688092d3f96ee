"""Serving host loop: median of the engine's ``serve.decode_step`` span,
dispatch to tokens on the host."""

from benchmarks.lib import harness


def read(run):
    durs = [s["dur_s"] for s in run.spans if s["name"] == "serve.decode_step"]
    if not durs:
        return None
    return 1e3 * harness.median(durs)
