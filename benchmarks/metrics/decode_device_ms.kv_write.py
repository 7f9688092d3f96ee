"""Model math: device time of the decode step's operations traced
under ``jax.named_scope("kv_write")`` (the new row's write into the
paged cache), per call of ``jit_step``."""

from benchmarks.lib import hostgaps


def read(run):
    return hostgaps.scope_ms_per_call(
        run, "jit_step", hostgaps.DECODE_SCOPES, "kv_write")
