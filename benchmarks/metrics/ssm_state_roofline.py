"""Kernels: the least time the chip could take to advance the slots'
state-space state in the window's decode steps, over the device time of
the ``ssm.scan`` and ``ssm.conv`` scopes.

Bytes: ``state_bytes`` of each ``serve.decode_step`` span, the state of
the slots that ran once in and once out (17 MB a slot over 4 layers,
float32), whatever implements the update; operations: 5 a value of
``H`` and the convolution's taps (``lib/flops_hybrid_ssm.py``).  Under
50 % the state takes a second pass."""

from benchmarks.lib import flops_hybrid_ssm as f


def read(run):
    cfg = run.config
    return f.share(
        run, f.scopes_ms_per_call(run, ("ssm.scan", "ssm.conv")),
        lambda a: (f.state_flops(cfg, a["state_bytes"]), a["state_bytes"]))
