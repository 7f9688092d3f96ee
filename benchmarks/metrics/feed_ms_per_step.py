"""Feed path: host time in the trainer's ``batch_prep`` and
``device_put`` spans, per dispatched step (the program's own spans, in
the traced run)."""


def read(run):
    steps = sum(1 for s in run.spans if s["name"] == "step_dispatch")
    if not steps:
        return None
    feed = sum(s["dur_s"] for s in run.spans
               if s["name"] in ("batch_prep", "device_put"))
    return 1e3 * feed / steps
