"""Serving host loop: the engine thread's own work a decode step:
``serve.prep`` + ``serve.emit`` + ``serve.admission`` less the
``serve.prefill`` inside it, per ``serve.decode_step``."""


def read(run):
    total = {name: sum(s["dur_s"] for s in run.spans if s["name"] == name)
             for name in ("serve.prep", "serve.emit", "serve.admission",
                          "serve.prefill")}
    steps = sum(1 for s in run.spans if s["name"] == "serve.decode_step")
    if not steps or not total["serve.prep"]:
        return None
    return 1e3 * (total["serve.prep"] + total["serve.emit"]
                  + total["serve.admission"]
                  - total["serve.prefill"]) / steps
