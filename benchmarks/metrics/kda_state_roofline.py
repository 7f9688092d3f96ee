"""Kernels: the least time the chip could take to advance the slots'
delta-rule state in the window's decode steps, over the device time of
the ``kda.state`` and ``kda.conv`` scopes.

Bytes: ``state_bytes`` of each ``serve.decode_step`` span, the state of
the slots that ran once in and once out (13.5 MB a slot over 6 KDA
layers, float32), whatever implements the update; operations: 7 a value
of ``S`` (decay, the read along the key, the rank-1 write, the read
along the query) and the convolution's taps
(``lib/flops_delta_moe.py``).  Under 50 % the state takes a second
pass."""

from benchmarks.lib import flops_delta_moe as f


def read(run):
    cfg = run.config
    return f.share(
        run, f.scopes_ms_per_call(run, ("kda.state", "kda.conv")),
        lambda a: (f.state_flops(cfg, a["state_bytes"]), a["state_bytes"]))
