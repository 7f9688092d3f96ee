"""Expert layer: share of the window's token-to-expert assignments that
went to zero-compute experts (256 of the router's 768 outputs: about a
third under an even router), from the ``serve.decode_step`` spans."""

from benchmarks.lib import flops_latent_moe as f


def read(run):
    steps = f.routed_steps(run)
    total = sum(a["moe_held"] + a["moe_zero"] + a["moe_absent"]
                for a in steps)
    if not total:
        return None
    return 100.0 * sum(a["moe_zero"] for a in steps) / total
