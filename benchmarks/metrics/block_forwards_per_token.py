"""Serving host loop: forwards of a block a slot ran for each token the
window's steps yielded: (``block_passes`` + ``block_commits``) /
``tokens_emitted`` over the ``serve.decode_step`` spans
(``bigdl_tpu/serving/spans.py``).

Blocks of 4 that take 4 passes and a commit read 1.25, the floor of the
design under weights whose confidence never passes the threshold; 1.0
would be a block a pass and a commit that cost nothing."""


def read(run):
    steps = [s["attrs"] for s in run.spans
             if s["name"] == "serve.decode_step"
             and "block_passes" in s["attrs"]]
    tokens = sum(a["tokens_emitted"] for a in steps)
    if not tokens:
        return None
    return sum(a["block_passes"] + a["block_commits"]
               for a in steps) / tokens
