"""Kernels: the least time the chip could take to advance the slots'
gated-delta-rule state in the window's decode steps, over the device
time of the ``gdn.state`` and ``gdn.conv`` scopes.

Bytes: ``state_bytes`` of each ``serve.decode_step`` span, the VALUES of
the state of the slots that ran once in and once out (2.35 MB a slot and
linear layer, float32; a layout that pads its lanes moves more and reads
lower here), whatever implements the update; operations: 7 a value of
``S`` (decay, the read along the key, the rank-1 write, the read along
the query) and the convolution's taps (``lib/flops_gated_delta.py``).
Under 50 % the state takes a second pass."""

from benchmarks.lib import flops_gated_delta as f


def read(run):
    cfg = run.config
    return f.share(
        run, f.scopes_ms_per_call(run, ("gdn.state", "gdn.conv")),
        lambda a: (f.state_flops(cfg, a["state_bytes"]), a["state_bytes"]))
