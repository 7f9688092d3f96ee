"""Model math: device time of the decode step's operations traced
under ``jax.named_scope("attn")`` (``paged_decode_attention``), per
call of ``jit_step``."""

from benchmarks.lib import hostgaps


def read(run):
    return hostgaps.scope_ms_per_call(
        run, "jit_step", hostgaps.DECODE_SCOPES, "attn")
