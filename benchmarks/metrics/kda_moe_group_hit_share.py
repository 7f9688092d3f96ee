"""Expert layer: the share of a step's tokens (a layer) that kept the
routing group this chip holds, in per cent, averaged over the window's
decode steps; from the ``moe_group_hit_share`` of the
``serve.decode_step`` spans (``nn/experts.py``: a router that chooses by
groups counts it).  The others send this chip nothing: with 4 of 8
groups kept and an even router it reads 50."""

from benchmarks.lib import flops_delta_moe as f


def read(run):
    steps = [a["moe_group_hit_share"] for a in f.state_steps(run)
             if "moe_group_hit_share" in a]
    if not steps:
        return None
    return 100.0 * sum(steps) / len(steps)
