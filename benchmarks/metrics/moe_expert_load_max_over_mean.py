"""Expert layer: the largest load of a held expert over the mean load of
the held experts, a step (1 = even), averaged over the window's decode
steps that routed a token to a held expert; from the ``moe_max_load``
and ``moe_held`` of the ``serve.decode_step`` spans."""

from benchmarks.lib import flops_latent_moe as f


def read(run):
    steps = [a for a in f.routed_steps(run) if a["moe_held"]]
    if not steps:
        return None
    # held experts over the step's expert layers: the mean is over these
    slots = int(run.config["num_layers"]) * int(run.config["n_routed_experts"])
    return sum(a["moe_max_load"] * slots / a["moe_held"]
               for a in steps) / len(steps)
