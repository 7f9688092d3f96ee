"""Trainer host loop: time the loop's thread blocks on the chip for a
loss (the trainer's ``loss_readback`` spans), per dispatched step."""


def read(run):
    steps = sum(1 for s in run.spans if s["name"] == "step_dispatch")
    waits = [s["dur_s"] for s in run.spans if s["name"] == "loss_readback"]
    if not steps or not waits:
        return None
    return 1e3 * sum(waits) / steps
