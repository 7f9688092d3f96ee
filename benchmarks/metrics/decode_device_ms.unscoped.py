"""Model math: device time of the decode step under none of
``kv_write``, ``attn``, ``dense`` (embedding, layer norms, residual
adds, ``sample``, layout copies the compiler put outside any scope, and
time of the execution in which no operation ran), per call of ``jit_step``."""

from benchmarks.lib import hostgaps


def read(run):
    return hostgaps.scope_ms_per_call(
        run, "jit_step", hostgaps.DECODE_SCOPES, "unscoped")
