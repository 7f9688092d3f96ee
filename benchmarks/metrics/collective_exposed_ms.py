"""Collectives: device time of collective operations during which no
other operation ran on that chip, per step and chip."""


def read(run):
    rec = run.trace.get("programs", {}).get("jit_sharded_step")
    if not rec or not rec["calls"]:
        return None
    return 1e3 * run.trace["collective_exposed_s"] / rec["calls"]
