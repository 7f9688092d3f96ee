"""Model math: device time of what chooses a block step's tokens (the
float32 softmax over the head's logits, the confidence ranking and the
pick: the scope ``unmask`` of ``models/sdar_moe.py``), per call of
``jit_step``.  The head's product itself runs under ``dense``."""

from benchmarks.lib import flops_block_moe as f


def read(run):
    return f.scopes_ms_per_call(run, ("unmask",))
