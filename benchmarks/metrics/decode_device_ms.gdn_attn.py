"""Model math: device time of the decode step's operations traced
under ``jax.named_scope("attn")`` where the full attention is one layer
in four of a decoder that mixes by the gated delta rule elsewhere (one
query row a key head over the paged K/V rows, through the page stream:
``models/olmo_hybrid.py``, ``ops/decode_attention.py``), per call of
``jit_step``.  A reader of its own: ``decode_device_ms.attn`` splits the
step by another module's list of scopes, which has no ``gdn.*``."""

from benchmarks.lib import flops_gated_delta as f


def read(run):
    return f.scopes_ms_per_call(run, ("attn",))
