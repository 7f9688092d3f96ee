"""A/B the framework ResNet-50 train step's BatchNormalization on chip.

Round-5 regression hunt (2026-07, on another toolchain): the
unchanged-since-r03b framework step dropped from 1867 img/s (b32) to
355 under a new AOT compile path, while bench.py's raw-JAX baseline
(naive two-pass BN) kept its speed.  This script measures the framework
step with the BN training-mode formulation swapped, one subprocess per
variant (one at a time: the chip belongs to one process) so a hung
compile costs only that variant:

  cur     — shipping code (whatever layers.py currently does)
  nocond  — rm-shifted single-pass stats, straight-line (the winner;
            what shipping code adopted after this hunt)
  cond    — rm-shifted single-pass + the r03b/r04 lax.cond stale-shift
            rescue (the pre-hunt shipping formulation)
  where   — rm-shifted single-pass + branch-free jnp.where rescue onto
            an exact-centered 1/16-subsample variance
  s0      — single-pass shifted by sample 0's per-channel mean
            (data-derived shift, stop_gradient)
  pix     — single-pass shifted by one pixel per channel (x[0,:,0,0])
  twopass — naive two-pass f32 stats (the baseline's formulation)
  fused   — fused conv+BN Pallas kernels (nn/fused.py), static dispatch
  tuned   — fused conv+BN with the kernel auto-tuner on
            (ops/autotune.py, BIGDL_TUNER=1): per-site impl/block-o
            from the cached cost-model search; the fused-vs-tuned pair
            is the tuner's A/B, and the never-lose gate means tuned
            can only match or beat fused per shape

Measured 2026-07-31 on a TPU v5 lite under another toolchain, b128
ms/step: nocond 50.1-53.5, pix 53.4, twopass 57.8, s0 64.2-64.5, where
85.5, cond OOM at b64+ and 89.8 ms at b32 (vs 18.1 nocond) — hot-path
control flow and stats-shift data dependencies both defeated that XLA's
fusion.

Usage: python scripts/bn_ab.py [batch] [iters] [variant...]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCH = int(sys.argv[1]) if len(sys.argv) > 1 else 32
ITERS = int(sys.argv[2]) if len(sys.argv) > 2 else 10
VARIANTS = sys.argv[3:] or ["cur", "nocond", "twopass"]


def _patch_bn(variant: str):
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.nn.layers import BatchNormalization

    if variant == "cur":
        return

    def apply(self, params, state, input, *, training=False, rng=None):
        axes, bshape = self._axes_and_shape(input)
        if not training:
            rm = state["running_mean"]
            scale, offset = self._fold(params, rm, state["running_var"], rm)
            dt = input.dtype
            y = (input - rm.astype(dt).reshape(bshape)) \
                * scale.astype(dt).reshape(bshape) \
                + offset.astype(dt).reshape(bshape)
            return y, state

        xf = input.astype(jnp.float32)
        if variant in ("cond", "where"):
            # rm-shifted single-pass with the two historical rescue
            # styles for the stale-shift cancellation
            rm = state["running_mean"]
            xc = xf - rm.reshape(bshape)
            d = jnp.mean(xc, axis=axes)
            m2 = jnp.mean(lax.square(xc), axis=axes)
            mean = rm + d
            var_sp = jnp.maximum(m2 - lax.square(d), 0.0)
            dt = input.dtype
            if variant == "cond":
                # r03b/r04 shipping formulation: lax.cond recomputes
                # two-pass and renormalizes when the shift went stale
                def _pathological():
                    var = jnp.maximum(
                        jnp.mean(lax.square(xf - mean.reshape(bshape)),
                                 axis=axes), 0.0)
                    sc, of = self._fold(params, mean, var, mean)
                    out = (xf - mean.reshape(bshape)) \
                        * sc.reshape(bshape) + of.reshape(bshape)
                    return out.astype(dt), var

                def _fast():
                    sc, of = self._fold(params, mean, var_sp, rm)
                    out = (input - rm.astype(dt).reshape(bshape)) \
                        * sc.astype(dt).reshape(bshape) \
                        + of.astype(dt).reshape(bshape)
                    return out, var_sp

                y, var = lax.cond(
                    jnp.any(lax.square(d) > 4096.0 * var_sp),
                    _pathological, _fast)
            else:
                # branch-free: always compute an exact-centered
                # subsample variance, per-channel select
                sub = xf if input.ndim == 2 else xf[:, :, ::4, ::4]
                var_sub = jnp.mean(
                    lax.square(sub - mean.reshape(bshape)), axis=axes)
                badc = lax.square(d) > 4096.0 * var_sp
                var = jnp.where(badc, var_sub, var_sp)
                center = jnp.where(badc, mean, rm)
                sc, of = self._fold(params, mean, var, center)
                y = (input - center.astype(dt).reshape(bshape)) \
                    * sc.astype(dt).reshape(bshape) \
                    + of.astype(dt).reshape(bshape)
        elif variant == "s0":
            # data-derived shift: sample 0's per-channel mean
            s = lax.stop_gradient(jnp.mean(xf[:1], axis=axes))
            xc = xf - s.reshape(bshape)
            d = jnp.mean(xc, axis=axes)
            m2 = jnp.mean(lax.square(xc), axis=axes)
            mean = s + d
            var = jnp.maximum(m2 - lax.square(d), 0.0)
            scale, offset = self._fold(params, mean, var, s)
            dt = input.dtype
            y = (input - s.astype(dt).reshape(bshape)) \
                * scale.astype(dt).reshape(bshape) \
                + offset.astype(dt).reshape(bshape)
        elif variant == "pix":
            # single-element-per-channel data-derived shift: one gather,
            # no reduction dependency before the fused stats pass
            s = lax.stop_gradient(
                xf[0, :, 0, 0] if input.ndim == 4 else xf[0])
            xc = xf - s.reshape(bshape)
            d = jnp.mean(xc, axis=axes)
            m2 = jnp.mean(lax.square(xc), axis=axes)
            mean = s + d
            var = jnp.maximum(m2 - lax.square(d), 0.0)
            scale, offset = self._fold(params, mean, var, s)
            dt = input.dtype
            y = (input - s.astype(dt).reshape(bshape)) \
                * scale.astype(dt).reshape(bshape) \
                + offset.astype(dt).reshape(bshape)
        elif variant == "nocond":
            shift = state["running_mean"].reshape(bshape)
            xc = xf - shift
            d = jnp.mean(xc, axis=axes)
            m2 = jnp.mean(lax.square(xc), axis=axes)
            mean = state["running_mean"] + d
            var = jnp.maximum(m2 - lax.square(d), 0.0)
            scale, offset = self._fold(params, mean, var,
                                       state["running_mean"])
            dt = input.dtype
            y = (input - state["running_mean"].astype(dt).reshape(bshape)) \
                * scale.astype(dt).reshape(bshape) \
                + offset.astype(dt).reshape(bshape)
        elif variant == "twopass":
            mean = jnp.mean(xf, axis=axes)
            var = jnp.mean(
                lax.square(xf - mean.reshape(bshape)), axis=axes)
            scale, offset = self._fold(params, mean, var, mean)
            dt = input.dtype
            y = (input - mean.astype(dt).reshape(bshape)) \
                * scale.astype(dt).reshape(bshape) \
                + offset.astype(dt).reshape(bshape)
        else:
            raise SystemExit(f"unknown variant {variant}")
        n = 1
        for a in axes:
            n *= input.shape[a]
        unbiased = var * (n / max(1, n - 1))
        new_state = {
            "running_mean": (1 - self.momentum) * state["running_mean"]
            + self.momentum * mean,
            "running_var": (1 - self.momentum) * state["running_var"]
            + self.momentum * unbiased,
        }
        return y, new_state

    BatchNormalization.apply = apply


def _run_one(variant: str):
    import numpy as np

    import jax

    jax.config.update("jax_platforms", "tpu")  # a chip, or an error
    fuse = variant in ("fused", "tuned")
    tuner_info = None
    if variant == "tuned":
        os.environ.setdefault("BIGDL_TUNER", "1")
        os.environ.setdefault(
            "BIGDL_TUNER_CACHE",
            os.environ.get("BN_AB_TUNER_CACHE",
                           "/tmp/bigdl_bn_ab_tuner.json"))
    if not fuse:
        _patch_bn(variant)
    import bench as B

    rs = np.random.RandomState(0)
    x = rs.randn(BATCH, 3, 224, 224).astype(np.float32)
    y = (rs.randint(0, 1000, BATCH) + 1).astype(np.float32)
    t0 = time.time()
    ips, step_s = B._bench_framework(x, y, BATCH, ITERS,
                                     compute_dtype="bfloat16",
                                     fuse=fuse)
    if variant == "tuned":
        from bigdl_tpu.ops import autotune

        tuner_info = [f"{d['site']}:{d['label']}<-{d['source']}"
                      for d in autotune.summary()["decisions"]]
    rec = {
        "variant": variant, "batch": BATCH,
        "images_per_sec": round(ips, 1),
        "step_ms": round(step_s * 1e3, 2),
        "wall_s": round(time.time() - t0, 1),
    }
    if tuner_info is not None:
        rec["tuner"] = tuner_info
    print(json.dumps(rec), flush=True)


def main():
    if os.environ.get("BN_AB_CHILD"):
        _run_one(os.environ["BN_AB_CHILD"])
        return
    for v in VARIANTS:
        t0 = time.time()
        env = dict(os.environ, BN_AB_CHILD=v)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 str(BATCH), str(ITERS)],
                capture_output=True, text=True, timeout=420, env=env,
            )
            out = (proc.stdout or "").strip().splitlines()
            line = out[-1] if out else (proc.stderr or "")[-240:]
        except subprocess.TimeoutExpired:
            line = f'{{"variant": "{v}", "error": "TIMEOUT 420s"}}'
        print(f"{line}   [{time.time()-t0:.0f}s]", flush=True)


if __name__ == "__main__":
    main()
