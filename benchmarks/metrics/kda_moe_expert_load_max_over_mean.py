"""Expert layer: the largest load of a held expert over the mean load of
the held experts, a step (1 = even), averaged over the window's decode
steps that routed a token to a held expert; from the ``moe_max_load``
and ``moe_held`` of the ``serve.decode_step`` spans, the held experts
counted from this configuration's keys (``moe_expert_load_max_over_mean``
reads ``num_layers`` x ``n_routed_experts``)."""

from benchmarks.lib import flops_delta_moe as f


def read(run):
    steps = [a for a in f.state_steps(run) if a["moe_held"]]
    if not steps:
        return None
    slots = f.expert_slots(run.config)
    return sum(a["moe_max_load"] * slots / a["moe_held"]
               for a in steps) / len(steps)
