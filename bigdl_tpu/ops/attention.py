"""Scaled-dot-product attention: lax reference + Pallas flash kernel.

The reference framework predates attention entirely (SURVEY.md §5
"long-context: absent") — this op is a *new* capability, the hot inner
op of the Transformer/long-context stack (nn/attention.py,
parallel/ring_attention.py).

Design for the MXU/VMEM (pallas_guide.md):

* the Pallas kernel is a classic flash attention: grid over
  (batch*heads, query blocks, kv superblocks), ``lax.fori_loop`` over
  key tiles inside each superblock, online softmax with running max
  ``m`` and normalizer ``l`` carried in VMEM scratch ACROSS the kv
  grid dimension so the (T, T) score matrix never materialises in HBM
  and no kv length is too long to stream;
* block sizes are multiples of the fp32 (8, 128) tile, MXU-sized 128
  where the sequence allows; the kv superblock (``block_kv``) and the
  backward's q superblock (``block_qs``) are sized by the symmetric
  VMEM model in :func:`_flash_plan` — and are tunable per shape by
  ``ops.autotune``;
* matmuls carry ``preferred_element_type=jnp.float32`` so bf16 inputs
  accumulate in fp32 on the MXU.

``dot_product_attention`` is the public entry.  ``impl="auto"`` is a
measured policy (:func:`static_dispatch`): the lax reference wins
throughput on the 2026-07 toolchain at every length whose softmax
residuals fit, so auto takes lax below Tq*Tk = 4096^2 and the Pallas
kernel in the long-context regime, where flash's O(T) residuals — (q,
k, v, out, logsumexp) instead of per-layer (B, H, Tq, Tk) — are the
difference between fitting and OOM.  When the fusion-aware auto-tuner
is enabled (``BIGDL_TUNER=1``, ops/autotune.py) the static policy is
only the fallback: dispatch and block sizes come from the cached
cost-model search instead.  Both paths are differentiable — the Pallas
path via ``jax.custom_vjp`` with blockwise backward kernels that never
materialize a (Tq, Tk) array in either direction.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax

from bigdl_tpu.ops._pallas import resolve_interpret


# --------------------------------------------------------------------------
# lax reference implementation
# --------------------------------------------------------------------------


def _reference_attention(q, k, v, *, causal: bool, scale: float,
                         mask=None, seq_offset: int = 0):
    """Plain softmax(q k^T) v.  (B, H, Tq, D) x (B, H, Tk, D).

    ``seq_offset`` shifts query positions for causal masking — used by
    ring attention where the local query block starts at a nonzero
    absolute position.
    """
    import jax.numpy as jnp

    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        qpos = jnp.arange(tq)[:, None] + seq_offset
        kpos = jnp.arange(tk)[None, :]
        scores = jnp.where(qpos >= kpos, scores, -jnp.inf)
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    # guard fully-masked rows (ring attention partial blocks): softmax of
    # all -inf must give zeros, not NaN
    row_max = jnp.max(scores, axis=-1, keepdims=True)
    row_max = jnp.where(jnp.isfinite(row_max), row_max, 0.0)
    unnorm = jnp.exp(scores - row_max)
    denom = jnp.sum(unnorm, axis=-1, keepdims=True)
    probs = unnorm / jnp.maximum(denom, 1e-30)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", probs, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# Pallas flash attention (TPU)
# --------------------------------------------------------------------------


def _mask_causal(s, qi, block_q, ki, block_k, seq_offset=0):
    """-inf the future positions of a (block_q, block_k) score tile at
    GLOBAL block coordinates (qi, ki); ``seq_offset`` (static) shifts
    the query positions — chunked causal attention where the local
    query block starts at a nonzero absolute position.  Single
    definition shared by the forward and both backward kernels so the
    mask convention can never desynchronize between them."""
    import jax.numpy as jnp
    from jax import lax

    qpos = seq_offset + qi * block_q + lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    kpos = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(qpos >= kpos, s, -jnp.inf)


def _diag_kblocks(qi, block_q, block_k, seq_offset=0, kv_len=None):
    """Number of key tiles a causal q-block touches (through its
    diagonal at query offset ``seq_offset``), clamped to the kv
    extent; shared by the forward and dq kernels."""
    import jax.numpy as jnp
    from jax import lax

    nk = lax.div(seq_offset + (qi + 1) * block_q + block_k - 1, block_k)
    if kv_len is not None:
        nk = jnp.minimum(nk, kv_len // block_k)
    return nk


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *,
                      block_k: int, scale: float, causal: bool,
                      kv_len: int, seq_offset: int = 0):
    """One (batch*head, q-block, kv-superblock) program: stream the
    superblock's key tiles, online softmax.  Refs are VMEM blocks: q
    (1, block_q, d), k/v (1, block_kv, d).  The running (m, l, acc)
    state lives in VMEM scratch and is CARRIED across the kv grid
    dimension (sequential on TPU, fastest-varying), so any kv length
    streams in superblocks the VMEM budget allows; output and the
    per-row logsumexp (scaled-score units, so the blockwise backward
    can rebuild P = exp(s - lse)) are written on the final superblock
    only."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    block_q = q_ref.shape[1]
    d = q_ref.shape[2]
    block_kv = k_ref.shape[1]
    spk = block_kv // block_k            # key tiles per superblock
    qi = pl.program_id(1)
    s = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        m_scr[0] = jnp.full((block_q,), -jnp.inf, jnp.float32)
        l_scr[0] = jnp.zeros((block_q,), jnp.float32)
        acc_scr[...] = jnp.zeros((block_q, d), jnp.float32)

    q = q_ref[0].astype(jnp.float32) * scale  # (block_q, d)

    def body(ki, carry):
        m, l, acc = carry
        ks = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        vs = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        st = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        if causal:
            st = _mask_causal(st, qi, block_q, s * spk + ki, block_k,
                              seq_offset)
        m_new = jnp.maximum(m, jnp.max(st, axis=-1))
        # fully-masked rows keep m=-inf; use 0 shift there to avoid NaNs
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(st - shift[:, None])
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - shift, -jnp.inf))
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    if causal:
        # global diagonal tile count, clamped into this superblock
        nk = _diag_kblocks(qi, block_q, block_k, seq_offset, kv_len)
        hi = jnp.clip(nk - s * spk, 0, spk)
    else:
        hi = spk
    m, l, acc = lax.fori_loop(
        0, hi, body, (m_scr[0], l_scr[0], acc_scr[...]))
    m_scr[0] = m
    l_scr[0] = l
    acc_scr[...] = acc

    @pl.when(s == ns - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[0], 1e-30)[:, None]
        o_ref[0] = out.astype(o_ref.dtype)
        # lse rides as (1, T//block_q, block_q): Mosaic's block rule
        # wants the last two dims (8, 128)-divisible-or-full, which a
        # (1, block_q) row block violates.  The full plane is mapped
        # for every (j, s) and revisited (same block index), so each
        # program writes only its row and the block flushes once per
        # batch*head.
        lse = m_scr[0] + jnp.log(jnp.maximum(l_scr[0], 1e-30))
        lse_ref[0, pl.ds(qi, 1), :] = lse[None, :]


# the flash kernels stream two whole (1, T, d) tensors per program when
# the sequence fits — k+v in the forward/dq kernels, q+g in the dkv
# kernel — as GRID-VARYING blocks, which Pallas double-buffers; cap
# their combined footprint (2 tensors x 2 buffers) well under the
# ~16 MB VMEM so the f32 accumulators and compiler temporaries still
# fit.  Sequences past the cap stream in superblocks instead
# (block_kv / block_qs below) — the budget then sizes the superblock,
# it no longer forbids the shape.  On-chip validated point: Tk=8192 at
# d=128 bf16 (8 MB with double-buffering).
_KV_VMEM_BUDGET = 8 * 1024 * 1024


def _kv_fits_vmem(t: int, d: int, dtype) -> bool:
    """Do two whole grid-varying (1, t, d) VMEM streams fit the budget?

    SYMMETRIC guard (round-5 ADVICE): the forward and dq kernels
    stream k+v over the kv length, but the dkv kernel streams q+g over
    the QUERY length — a large-Tq config that only checked Tk passed
    the forward and blew VMEM under ``jax.grad``.  Callers must hold
    this for both Tq and Tk (or fall back to superblock streaming, see
    :func:`_flash_plan`).  The factor 4 = 2 tensors x the
    double-buffering Pallas applies to grid-varying input blocks."""
    import jax.numpy as jnp

    return 4 * t * d * jnp.dtype(dtype).itemsize <= _KV_VMEM_BUDGET


def _pick_block(t: int, preferred: int = 128) -> int:
    for b in (preferred, 64, 32, 16, 8):
        if t % b == 0:
            return b
    return 0


def _largest_stream_block(t: int, tile: int, d: int, itemsize: int) -> int:
    """Largest superblock — a multiple of ``tile`` dividing ``t`` —
    whose two double-buffered (1, c, d) streams fit the VMEM budget;
    0 when even a single tile does not fit."""
    cap = _KV_VMEM_BUDGET // (4 * d * itemsize)
    if tile > cap:
        return 0
    nt = t // tile
    best = 0
    for m in range(1, nt + 1):
        if nt % m == 0 and m * tile <= cap:
            best = m * tile
    return best


def _flash_plan(tq: int, tk: int, d: int, dtype, *, block_q: int = 0,
                block_k: int = 0, block_kv: int = 0, block_qs: int = 0):
    """Symmetric VMEM feasibility model + tile plan for the flash
    kernels.  Returns ``(block_q, block_k, block_kv, block_qs)`` — the
    q/k tile sizes, the kv superblock streamed by the forward and dq
    kernels, and the q superblock streamed by the dkv kernel — or
    ``None`` when no feasible tiling exists (untileable T, or even one
    tile would blow the budget).  Explicit nonzero arguments (the
    auto-tuner's choices) are validated, not overridden."""
    import jax.numpy as jnp

    itemsize = jnp.dtype(dtype).itemsize
    bq = block_q or _pick_block(tq)
    bk = block_k or _pick_block(tk)
    if not bq or not bk or tq % bq or tk % bk:
        return None
    bkv = block_kv or (tk if _kv_fits_vmem(tk, d, dtype)
                       else _largest_stream_block(tk, bk, d, itemsize))
    bqs = block_qs or (tq if _kv_fits_vmem(tq, d, dtype)
                       else _largest_stream_block(tq, bq, d, itemsize))
    if (not bkv or not bqs or tk % bkv or bkv % bk
            or tq % bqs or bqs % bq):
        return None
    return (bq, bk, bkv, bqs)


# blocks = (block_q, block_k, block_kv, block_qs); 0 means auto
_AUTO_BLOCKS = (0, 0, 0, 0)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "interpret",
                              "seq_offset", "block_q", "block_k",
                              "block_kv", "block_qs")
)
def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    seq_offset: int = 0, block_q: int = 0, block_k: int = 0,
                    block_kv: int = 0, block_qs: int = 0):
    """Pallas flash attention.  q (B, H, Tq, D) against k/v
    (B, H, Tk, D) — Tq and Tk each a multiple of 8, D anything (padded
    to 128 lanes by Mosaic).  ``seq_offset`` (STATIC int >= 0) places
    the query block at a global position for chunked causal
    attention: q covers absolute positions [seq_offset, seq_offset+Tq)
    of the kv sequence.

    ``block_q``/``block_k`` override the q/k tile sizes and
    ``block_kv``/``block_qs`` the streamed superblocks (0 = let
    :func:`_flash_plan` choose) — the auto-tuner's knobs.  A shape or
    override :func:`_flash_plan` cannot tile raises: the caller asked
    for the kernel by name (``impl="auto"`` never does, its predicate
    shares the plan).  ``interpret=None`` runs the Pallas interpreter
    on the CPU backend only (``ops/_pallas.resolve_interpret``).

    Differentiable with a true blockwise backward: the forward saves
    (q, k, v, out, logsumexp) — O(T) extra — and the backward kernels
    (_flash_bwd_dq_kernel / _flash_bwd_dkv_kernel) rebuild the score
    tiles from the logsumexp, so no (Tq, Tk) array is ever
    materialized, as residual OR transient, in either direction.
    """
    if seq_offset < 0:
        raise ValueError("seq_offset must be >= 0")
    return _flash_attention_vjp(q, k, v, causal,
                                scale if scale is not None else q.shape[-1] ** -0.5,
                                resolve_interpret(interpret), seq_offset,
                                (block_q, block_k, block_kv, block_qs))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_vjp(q, k, v, causal, scale, interpret, seq_offset,
                         blocks):
    return _flash_forward(q, k, v, causal, scale, interpret,
                          seq_offset=seq_offset, blocks=blocks)


def _flash_forward(q, k, v, causal, scale, interpret, *,
                   with_lse: bool = False, seq_offset: int = 0,
                   blocks=_AUTO_BLOCKS):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk = k.shape[2]
    plan = _flash_plan(tq, tk, d, k.dtype, block_q=blocks[0],
                       block_k=blocks[1], block_kv=blocks[2],
                       block_qs=blocks[3])
    if plan is None:
        # auto dispatch never lands here (its predicate shares this
        # plan), so this is an explicit request for the kernel
        raise ValueError(
            f"flash attention cannot tile Tq={tq} Tk={tk} d={d} "
            f"{k.dtype} with blocks {tuple(blocks)}: T must be a "
            "multiple of 8 and one tile must fit the VMEM budget; use "
            "impl='lax' or impl='auto'")

    block_q, block_k, block_kv, _ = plan
    kernel = functools.partial(
        _flash_fwd_kernel, block_k=block_k, scale=scale, causal=causal,
        kv_len=tk, seq_offset=seq_offset,
    )
    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, tq // block_q, tk // block_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, s: (i, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda i, j, s: (i, s, 0)),
            pl.BlockSpec((1, block_kv, d), lambda i, j, s: (i, s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, s: (i, j, 0)),
            pl.BlockSpec((1, tq // block_q, block_q),
                         lambda i, j, s: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, tq // block_q, block_q),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    out = out.reshape(b, h, tq, d)
    return (out, lse) if with_lse else out


# ---- blockwise backward (the true flash backward: no T^2 residuals,
# no T^2 transients — scores are rebuilt tile by tile from the saved
# logsumexp) ----


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         dq_ref, acc_scr, *, block_k: int, scale: float,
                         causal: bool, kv_len: int, seq_offset: int = 0):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    block_q = q_ref.shape[1]
    d = q_ref.shape[2]
    block_kv = k_ref.shape[1]
    spk = block_kv // block_k
    qi = pl.program_id(1)
    s = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        acc_scr[...] = jnp.zeros((block_q, d), jnp.float32)

    qs = q_ref[0].astype(jnp.float32) * scale      # (bq, d)
    do = g_ref[0].astype(jnp.float32)              # (bq, d)
    lse = lse_ref[0, pl.ds(qi, 1), :][0]           # (bq,)
    dlt = delta_ref[0, pl.ds(qi, 1), :][0]         # (bq,)

    def body(ki, acc):
        ks = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        vs = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        st = jax.lax.dot_general(
            qs, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bq, bk)
        if causal:
            st = _mask_causal(st, qi, block_q, s * spk + ki, block_k,
                              seq_offset)
        p = jnp.exp(st - lse[:, None])
        dp = jax.lax.dot_general(
            do, vs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bq, bk)
        ds = p * (dp - dlt[:, None])
        return acc + jax.lax.dot_general(
            ds, ks, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bq, d)

    if causal:
        nk = _diag_kblocks(qi, block_q, block_k, seq_offset, kv_len)
        hi = jnp.clip(nk - s * spk, 0, spk)
    else:
        hi = spk
    acc_scr[...] = lax.fori_loop(0, hi, body, acc_scr[...])

    @pl.when(s == ns - 1)
    def _finalize():
        dq_ref[0] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *,
                          block_q: int, scale: float, causal: bool,
                          seq_offset: int = 0):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    block_k = k_ref.shape[1]
    d = k_ref.shape[2]
    block_qs = q_ref.shape[1]
    spq = block_qs // block_q            # q tiles per superblock
    kj = pl.program_id(1)
    s = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        dk_scr[...] = jnp.zeros((block_k, d), jnp.float32)
        dv_scr[...] = jnp.zeros((block_k, d), jnp.float32)

    ks = k_ref[0].astype(jnp.float32)              # (bk, d)
    vs = v_ref[0].astype(jnp.float32)              # (bk, d)

    def body(qi, carry):
        # ``qi`` is LOCAL to this q superblock; masks use the global
        # tile index s * spq + qi
        acc_dk, acc_dv = carry
        qs = q_ref[0, pl.ds(qi * block_q, block_q), :] \
            .astype(jnp.float32) * scale           # (bq, d)
        do = g_ref[0, pl.ds(qi * block_q, block_q), :] \
            .astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qi, 1), :][0]       # (bq,)
        dlt = delta_ref[0, pl.ds(qi, 1), :][0]
        st = jax.lax.dot_general(
            qs, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bq, bk)
        if causal:
            st = _mask_causal(st, s * spq + qi, block_q, kj, block_k,
                              seq_offset)
        p = jnp.exp(st - lse[:, None])
        acc_dv = acc_dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bk, d)
        dp = jax.lax.dot_general(
            do, vs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dlt[:, None])
        acc_dk = acc_dk + jax.lax.dot_general(
            ds, qs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bk, d)
        return acc_dk, acc_dv

    if causal:
        # first GLOBAL q tile whose rows reach this key block, clamped
        # into this superblock's local tile range:
        # q0 = floor(max(kj*block_k - seq_offset, 0) / block_q)
        q0 = lax.div(jnp.maximum(kj * block_k - seq_offset, 0), block_q)
        lo = jnp.clip(q0 - s * spq, 0, spq)
    else:
        lo = 0
    acc_dk, acc_dv = lax.fori_loop(lo, spq, body,
                                   (dk_scr[...], dv_scr[...]))
    dk_scr[...] = acc_dk
    dv_scr[...] = acc_dv

    @pl.when(s == ns - 1)
    def _finalize():
        # qs carried the scale, so dk_scr is dL/dk exactly
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, scale, interpret,
                    seq_offset=0, blocks=_AUTO_BLOCKS):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk = k.shape[2]
    # same deterministic plan as the forward (residual lse layout
    # depends on block_q, so the two must agree)
    block_q, block_k, block_kv, block_qs = _flash_plan(
        tq, tk, d, k.dtype, block_q=blocks[0], block_k=blocks[1],
        block_kv=blocks[2], block_qs=blocks[3])
    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)
    gr = g.reshape(b * h, tq, d)
    outr = out.reshape(b * h, tq, d)
    # delta_i = sum_d dO_i . O_i — one fused elementwise+reduce in XLA;
    # carried at the lse layout (bh, Tq//bq, bq), see the fwd kernel
    delta = jnp.sum(gr.astype(jnp.float32) * outr.astype(jnp.float32),
                    axis=-1).reshape(b * h, tq // block_q, block_q)

    lse_plane = pl.BlockSpec((1, tq // block_q, block_q),
                             lambda i, j, s: (i, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_k=block_k,
                          scale=scale, causal=causal, kv_len=tk,
                          seq_offset=seq_offset),
        grid=(b * h, tq // block_q, tk // block_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, s: (i, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda i, j, s: (i, s, 0)),
            pl.BlockSpec((1, block_kv, d), lambda i, j, s: (i, s, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j, s: (i, j, 0)),
            lse_plane,
            lse_plane,
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, s: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, gr, lse, delta)

    spq = block_qs // block_q
    lse_super = pl.BlockSpec((1, spq, block_q), lambda i, j, s: (i, s, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                          scale=scale, causal=causal,
                          seq_offset=seq_offset),
        grid=(b * h, tk // block_k, tq // block_qs),
        in_specs=[
            pl.BlockSpec((1, block_qs, d), lambda i, j, s: (i, s, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, s: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, s: (i, j, 0)),
            pl.BlockSpec((1, block_qs, d), lambda i, j, s: (i, s, 0)),
            lse_super,
            lse_super,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j, s: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, s: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, gr, lse, delta)

    return (dq.reshape(b, h, tq, d), dk.reshape(b, h, tk, d),
            dv.reshape(b, h, tk, d))


def _flash_fwd_rule(q, k, v, causal, scale, interpret, seq_offset, blocks):
    out, lse = _flash_forward(q, k, v, causal, scale, interpret,
                              with_lse=True, seq_offset=seq_offset,
                              blocks=blocks)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, scale, interpret, seq_offset, blocks, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, scale,
                           interpret, seq_offset, blocks=blocks)


_flash_attention_vjp.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# --------------------------------------------------------------------------
# public dispatcher
# --------------------------------------------------------------------------


def static_dispatch(q_shape, k_shape, v_shape, dtype, *, mask_is_none=True,
                    seq_offset=0, backend: Optional[str] = None):
    """The hand-measured ``impl="auto"`` policy as a pure function of
    STATIC shapes: returns ``(impl, plan)`` with impl in
    {"lax", "pallas"} and plan the :func:`_flash_plan` tiling (None on
    the lax path when flash is infeasible).  Single source of truth
    for the dispatcher, the auto-tuner's static baseline, and the
    tuner-off pinning tests."""
    t, d = q_shape[-2], q_shape[-1]
    tk = k_shape[-2]
    tiles = (
        mask_is_none
        and tuple(k_shape) == tuple(v_shape)
        and tuple(q_shape[:2]) == tuple(k_shape[:2])
        and q_shape[-1] == k_shape[-1]
        and t >= 128 and t % 128 == 0
        and tk >= 128 and tk % 128 == 0
        and isinstance(seq_offset, int) and seq_offset >= 0
    )
    # the plan holds the SYMMETRIC VMEM guard: _kv_fits_vmem over both
    # Tq and Tk (the dkv kernel streams whole q/g blocks, round-5
    # ADVICE), falling back to superblock streaming past the budget
    plan = _flash_plan(t, tk, d, dtype) if tiles else None
    if backend is None:
        backend = jax.default_backend()
    # Measured on the 2026-07 toolchain (TransformerLM train step,
    # TPU v5 lite, ms/step): XLA's fused attention beats the Pallas
    # flash forward at every length that fits its residuals —
    # T=512: 59.3 lax vs 64.7 pallas; T=1024: 76.2 vs 80.2;
    # T=2048: 114.1 vs 124.6.  What flash buys on TPU is MEMORY:
    # under jax.grad the lax path saves (B, H, Tq, Tk) softmax
    # residuals for EVERY layer simultaneously — the long-context
    # cliff.  The flash path saves (q, k, v, out, lse) — O(T) — and
    # its blockwise backward kernels rebuild score tiles from the
    # logsumexp, so no (Tq, Tk) array exists in either direction.
    # So auto prefers lax until the quadratic-residual regime and
    # flips to the kernel there.  The residual is (B, H, Tq, Tk), so
    # the flip watches the PRODUCT, and kv-superblock streaming keeps
    # the whole product regime reachable: a 2048-query chunk against a
    # 32k kv at d=128 streams the kv in 8k superblocks and takes the
    # flash path, where it previously bailed on the whole-kv VMEM
    # guard.
    impl = ("pallas" if (backend == "tpu" and plan is not None
                         and t * tk >= 4096 * 4096)
            else "lax")
    return impl, plan


def dot_product_attention(q, k, v, *, causal: bool = False, mask=None,
                          scale: Optional[float] = None, impl: str = "auto",
                          seq_offset: int = 0):
    """Attention entry point used by nn.MultiHeadAttention.

    q, k, v: (batch, heads, seq, head_dim).

    impl: "auto" (the measured :func:`static_dispatch` policy — lax
    below Tq*Tk = 4096^2, the Pallas flash kernel on TPU in the
    long-context regime where lax's per-layer (B, H, Tq, Tk) residuals
    stop fitting; with ``BIGDL_TUNER=1`` the cached auto-tuner search
    overrides it per shape), "pallas", "pallas_interpret" (testing),
    or "lax".
    """
    import jax

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    blocks = {}
    if impl == "auto":
        impl, plan = static_dispatch(
            q.shape, k.shape, v.shape, q.dtype,
            mask_is_none=mask is None, seq_offset=seq_offset)
        from bigdl_tpu.ops import autotune

        if autotune.enabled():
            decision = autotune.decide_attention(
                q.shape, k.shape, q.dtype, causal=causal,
                seq_offset=seq_offset, static_impl=impl, plan=plan,
                arrays=(q, k, v) if mask is None else None)
            if decision is not None:
                impl = decision["impl"]
                if decision.get("blocks"):
                    bq, bk, bkv, bqs = decision["blocks"]
                    blocks = dict(block_q=bq, block_k=bk,
                                  block_kv=bkv, block_qs=bqs)
    if impl in ("pallas", "pallas_interpret"):
        if mask is not None:
            raise ValueError(
                "the Pallas flash kernel has no explicit-mask support; "
                "use impl='lax'"
            )
        if not isinstance(seq_offset, int):
            raise ValueError(
                "the Pallas flash kernel needs a STATIC (python int) "
                "seq_offset; traced offsets (ring attention's hops) "
                "use impl='lax'"
            )
        return flash_attention(
            q, k, v, causal=causal, scale=scale,
            interpret=True if impl == "pallas_interpret" else None,
            seq_offset=seq_offset, **blocks)
    if impl != "lax":
        raise ValueError(
            f"impl must be auto|lax|pallas|pallas_interpret, got {impl!r}")
    return _reference_attention(q, k, v, causal=causal, scale=scale,
                                mask=mask, seq_offset=seq_offset)
