"""Which kxk im2col construction does this Mosaic accept?

The pure-2-D kxk kernel (ops/conv_bn.py) builds a tap-major im2col from
k*k lane-shifted slices.  The 2026-07 Mosaic rejects concatenating
vectors whose lane offsets differ ("result/input offset mismatch on
non-concat dimension"), so this probe tries the candidate relayout
mechanisms on the real chip, each in a subprocess, and checks numerics
against the XLA reference:

  scratch — store each tap slice into a VMEM scratch ref (stores
            materialize the ref's layout), then one deep dot
  taps    — k*k separate accumulated dots, no concat (relies on dot
            operand relayout; k*k-fold shallower contraction)
  roll    — jnp.roll the whole block to lane offset 0, slice, concat

    python scripts/kxk_probe.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N, C, H, W, O, K = 8, 64, 16, 16, 64, 3


def _build(variant: str):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pad = (K - 1) // 2
    hp, wp_ = H + 2 * pad, W + 2 * pad
    ho, wo = H, W
    L = hp * wp_ + K - 1

    def kern(x_ref, w_ref, y_ref, *scratch):
        xp = x_ref[0]                       # (C, L)
        if variant == "scratch":
            xcat_ref, = scratch
            for t in range(K * K):
                dy, dx = t // K, t % K
                s = dy * wp_ + dx
                xcat_ref[t * C:(t + 1) * C, :] = xp[:, s:s + ho * wp_]
            acc = jax.lax.dot_general(
                w_ref[...], xcat_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        elif variant == "taps":
            acc = None
            for t in range(K * K):
                dy, dx = t // K, t % K
                s = dy * wp_ + dx
                part = jax.lax.dot_general(
                    w_ref[:, t * C:(t + 1) * C], xp[:, s:s + ho * wp_],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc = part if acc is None else acc + part
        else:  # roll
            taps = []
            for t in range(K * K):
                dy, dx = t // K, t % K
                s = dy * wp_ + dx
                taps.append(jnp.roll(xp, -s, axis=1)[:, :ho * wp_])
            acc = jax.lax.dot_general(
                w_ref[...], jnp.concatenate(taps, axis=0),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        y_ref[0] = acc.astype(y_ref.dtype)

    def run(x, w):
        xpad = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
        xflat = jnp.pad(xpad.reshape(N, C, hp * wp_),
                        ((0, 0), (0, 0), (0, K - 1)))
        wt = jnp.transpose(w, (0, 2, 3, 1)).reshape(O, K * K * C)
        y2 = pl.pallas_call(
            kern,
            grid=(1, N),
            in_specs=[
                pl.BlockSpec((1, C, L), lambda oi, ni: (ni, 0, 0)),
                pl.BlockSpec((O, K * K * C), lambda oi, ni: (oi, 0)),
            ],
            out_specs=pl.BlockSpec((1, O, ho * wp_),
                                   lambda oi, ni: (ni, oi, 0)),
            out_shape=jax.ShapeDtypeStruct((N, O, ho * wp_), x.dtype),
            scratch_shapes=(
                [pltpu.VMEM((K * K * C, ho * wp_), x.dtype)]
                if variant == "scratch" else []),
        )(xflat, wt)
        return y2.reshape(N, O, ho, wp_)[:, :, :, :wo]

    return run


def _run_variant(variant: str):
    import numpy as np

    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "tpu")  # a chip, or an error
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(N, C, H, W), dtype=jnp.bfloat16)
    w = jnp.asarray(rs.randn(O, C, K, K) * 0.05, dtype=jnp.bfloat16)
    t0 = time.time()
    y = jax.jit(_build(variant))(x, w)
    y.block_until_ready()
    ref = jax.lax.conv_general_dilated(
        x, w, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=jnp.float32).astype(jnp.bfloat16)
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    print(json.dumps({"variant": variant, "ok": True,
                      "max_err": round(err, 5),
                      "seconds": round(time.time() - t0, 1)}))


def main():
    if os.environ.get("KXK_PROBE_CHILD"):
        _run_variant(os.environ["KXK_PROBE_CHILD"])
        return
    for v in ("scratch", "taps", "roll"):
        t0 = time.time()
        env = dict(os.environ, KXK_PROBE_CHILD=v)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                capture_output=True, text=True, timeout=240, env=env)
            ok = proc.returncode == 0
            tail = (proc.stdout or proc.stderr or "").strip().splitlines()
            detail = tail[-1][:220] if tail else ""
        except subprocess.TimeoutExpired:
            ok, detail = False, "TIMEOUT 240s"
        print(f"{v:8s} {'OK' if ok else 'FAIL'} "
              f"{time.time()-t0:6.1f}s  {detail}", flush=True)


if __name__ == "__main__":
    main()
