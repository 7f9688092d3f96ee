"""Flash-decode over the paged KV cache — the serving hot path's kernel.

PR 12's continuous-batching decode step ran its attention the naive
way: ``gather_pages`` materialized a dense ``(B, H, max_pages*P, Dh)``
K/V copy **per layer per step** (page gather + transpose + reshape),
then full-width einsum attention masked the mostly-unallocated tail
with ``-inf`` — pure wasted HBM bandwidth in a regime that is entirely
memory-bound (one query token against a long scattered KV).  This
module is the flash-decoding answer (the decode-side sibling of
ops/attention.py's flash kernel):

* ``impl="dense"`` — the PR 12 math, verbatim: gather + masked softmax
  einsum.  It is the **static baseline** the auto-tuner can never lose
  to, and the path that preserves the temperature-0 bit-match-vs-
  ``generate()`` contract;
* ``impl="fused"`` — split-KV online softmax in plain lax: K/V are
  read **page-block by page-block through the page table** (a chunk of
  ``block_pages`` pages per iteration), each block's scores are
  softmax-accumulated into a carried ``(m, l, acc)`` running state,
  and one final rescale produces the output — the gathered dense copy
  (and its transpose materialization) never exists.  Runs everywhere
  XLA runs, including inside the TP ``shard_map`` body on the
  head-sharded cache;
* ``impl="pallas"`` — the true flash-decode TPU kernel: grid
  ``(B, H, pages)`` with the page table and lengths as **scalar
  prefetch** so each program's BlockSpec index map DMAs exactly the
  page the table names (trash-page contract below), ``(m, l, acc)``
  carried in VMEM scratch across the page grid dimension, output
  written on the final page.  The CPU backend (tests) runs it in
  the Pallas interpreter; an accelerator compiles it or fails.

Mask contract (identical across impls, pinned by tests): position
``pos <= length`` attends, everything else is ``-inf`` before the
softmax — so page 0 (the reserved trash page unallocated table entries
point at) can hold arbitrary finite garbage and never contributes a
bit to any output.

Dispatch: ``impl="auto"`` follows :func:`static_decode_dispatch`
(always "dense" — the measured PR 12 baseline) unless the auto-tuner
is enabled (``BIGDL_TUNER=1``), in which case the cached
``decode_attn`` site search (ops/autotune.py) picks impl and
``block_pages`` per ``(B, H, Dh, P, pages, dtype, platform)`` — with
the dense path as the never-lose static policy.

The used-page prefix bucket (:func:`used_page_bucket`) is the other
half of the win and benefits **every** impl including dense: the
engine slices each step's page tables to the pow2 bucket covering
``max(lengths)//P + 1`` pages, so even the static baseline stops
paying for the empty pool.
"""

from __future__ import annotations

import functools
from typing import Optional


def used_page_bucket(max_length: int, page_size: int,
                     max_pages: int) -> int:
    """Host-side pow2 page bucket for one decode step: the smallest
    power of two >= the pages needed to cover position ``max_length``
    (the batch's longest slot writes its next token there, so
    ``max_length // P + 1`` pages are live), clamped to the table
    width.  Pow2 buckets keep the number of compiled step variants
    logarithmic."""
    page_size = max(1, int(page_size))
    need = max(1, int(max_length) // page_size + 1)
    b = 1
    while b < need:
        b *= 2
    return min(b, max(1, int(max_pages)))


def decode_hbm_bytes(impl: str, b: int, h: int, d: int, page_size: int,
                     maxp: int, kv_itemsize: int = 4) -> float:
    """Analytic HBM traffic of ONE layer's decode attention (the
    auto-tuner's Pallas/fused costing model, and the engine's
    bytes-per-token gauge).  All impls read the ``2 * B * maxp`` K/V
    pages the tables name; the dense path additionally writes and
    re-reads the materialized contiguous copy (the gather tax), plus
    the f32 score plane's round trip."""
    k = maxp * page_size
    pages = 2.0 * b * maxp * page_size * h * d * kv_itemsize  # K + V
    qio = 2.0 * b * h * d * 4                                 # q + out
    if impl == "dense":
        return pages * 3 + 2.0 * b * h * k * 4 + qio
    return pages + qio


def _mask_neg_inf(scores, pos, lengths):
    """``pos <= length`` attends; everything else -inf (the trash-page
    contract — one definition shared by dense and fused)."""
    import jax.numpy as jnp

    return jnp.where(pos <= lengths, scores, -jnp.inf)


# --------------------------------------------------------------------------
# dense — the PR 12 math, verbatim (static baseline / bit-match path)
# --------------------------------------------------------------------------


def _dense(q, kp, vp, tables, lengths, *, scale: float):
    """Gather + masked softmax einsum — exactly the op sequence the
    PR 12 ``paged_decode_math`` inlined, so the temperature-0 bit-match
    contract vs ``generate()`` is preserved byte for byte."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.serving.cache import gather_pages

    qh = q[:, :, None, :]                     # (B, H, 1, Dh)
    kall = gather_pages(kp, tables)           # (B, H, maxp*P, Dh)
    vall = gather_pages(vp, tables)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kall) * scale
    mask = (jnp.arange(kall.shape[2])[None, None, None, :]
            <= lengths[:, None, None, None])
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, vall)
    return o[:, :, 0, :]


# --------------------------------------------------------------------------
# fused — split-KV online softmax over page blocks (XLA, runs anywhere)
# --------------------------------------------------------------------------


def _chunk_pages(maxp: int, block_pages: int) -> int:
    """Largest valid page-block size <= the request that divides the
    table width (0 / oversize requests collapse to the full width —
    one block, no loop)."""
    maxp = int(maxp)
    bp = int(block_pages)
    if bp <= 0 or bp >= maxp:
        return maxp
    while bp > 1 and maxp % bp:
        bp -= 1
    return bp


def _fused(q, kp, vp, tables, lengths, *, page_size: int, scale: float,
           block_pages: int = 0):
    """Online-softmax paged decode: page blocks are gathered one chunk
    at a time through the table (``(B, bp, H, P, Dh)`` — page layout,
    never the transposed contiguous copy), each chunk's masked scores
    fold into the carried ``(m, l, acc)``, one final rescale.  f32
    accumulation throughout."""
    import jax.numpy as jnp
    from jax import lax

    b, maxp = tables.shape
    h, d = q.shape[1], q.shape[2]
    p = int(page_size)
    bp = _chunk_pages(maxp, block_pages)
    n_chunks = maxp // bp
    qf = q.astype(jnp.float32) * scale        # (B, H, Dh)
    len_b = lengths[:, None, None, None]      # (B, 1, 1, 1)

    def block(tbl_c, c0, m, l, acc):
        """Fold pages [c0, c0+bp) (table slice ``tbl_c``) into the
        running state.  ``c0`` may be traced (fori path)."""
        kc = kp[tbl_c].astype(jnp.float32)    # (B, bp, H, P, Dh)
        vc = vp[tbl_c].astype(jnp.float32)
        s = jnp.einsum("bhd,bmhpd->bhmp", qf, kc)     # (B, H, bp, P)
        pos = ((c0 + jnp.arange(bp)) * p)[None, None, :, None] \
            + jnp.arange(p)[None, None, None, :]
        s = _mask_neg_inf(s, pos, len_b)
        s = s.reshape(b, h, bp * p)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # fully-masked-so-far rows keep m=-inf; shift 0 avoids NaN
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        pr = jnp.exp(s - shift[..., None])
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - shift, -jnp.inf))
        l_new = l * alpha + jnp.sum(pr, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhmp,bmhpd->bhd", pr.reshape(b, h, bp, p), vc)
        return m_new, l_new, acc_new

    init = (jnp.full((b, h), -jnp.inf, jnp.float32),
            jnp.zeros((b, h), jnp.float32),
            jnp.zeros((b, h, d), jnp.float32))
    if n_chunks == 1:
        m, l, acc = block(tables, 0, *init)
    elif n_chunks <= 4:
        m, l, acc = init
        for c in range(n_chunks):
            m, l, acc = block(tables[:, c * bp:(c + 1) * bp],
                              c * bp, m, l, acc)
    else:
        def body(c, carry):
            tbl_c = lax.dynamic_slice_in_dim(tables, c * bp, bp, axis=1)
            return block(tbl_c, c * bp, *carry)

        m, l, acc = lax.fori_loop(0, n_chunks, body, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# pallas — the TPU flash-decode kernel (scalar-prefetched page table)
# --------------------------------------------------------------------------


def _decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, page_size: int,
                   scale: float):
    """One (slot, head, page) program.  The BlockSpec index maps below
    already resolved this program's K/V block to the page the table
    names (scalar prefetch), so the kernel only sees a (P, Dh) tile;
    (m, l, acc) carry in VMEM scratch across the page grid dimension
    (fastest-varying, sequential on TPU).  m and l stay (1, 1) arrays
    end to end: Mosaic stores vectors to VMEM, not scalars."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # (1, Dh)
    ks = k_ref[0, 0].astype(jnp.float32)               # (P, Dh)
    vs = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, ks, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # (1, P)
    pos = j * page_size + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    length = len_ref[pl.program_id(0)]
    s = jnp.where(pos <= length, s, -jnp.inf)

    m = m_scr[...]                                     # (1, 1)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # fully-masked-so-far rows keep m=-inf; shift 0 avoids NaN
    shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - shift)
    alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - shift, -jnp.inf))
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, vs, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (1, Dh)
    m_scr[...] = m_new

    @pl.when(j == ns - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _pallas(q, kp, vp, tables, lengths, *, page_size: int, scale: float,
            interpret: bool = False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    maxp = tables.shape[1]
    p = int(page_size)

    # q and out ride as (B, H, 1, Dh): Mosaic wants a block's last two
    # dims (8, 128)-divisible or full, which a (1, 1, Dh) block over
    # (B, H, Dh) is not and a (1, 1, 1, Dh) block over this shape is
    qo_spec = pl.BlockSpec((1, 1, 1, d), lambda i, hh, j, tbl, lens:
                           (i, hh, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, p, d), lambda i, hh, j, tbl, lens:
                           (tbl[i, j], hh, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # tables, lengths
        grid=(b, h, maxp),
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, page_size=p, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q[:, :, None, :], kp, vp)
    return out[:, :, 0, :]


# --------------------------------------------------------------------------
# public dispatcher
# --------------------------------------------------------------------------


def static_decode_dispatch() -> tuple:
    """The hand-measured ``impl="auto"`` policy: the dense gather path
    — the PR 12 baseline and the auto-tuner's never-lose static
    choice.  (The fused/pallas paths must EARN dispatch through the
    tuner's cost model or a measured probe.)"""
    return "dense", 0


def paged_decode_attention(q, kp, vp, tables, lengths, *,
                           page_size: int, scale: Optional[float] = None,
                           impl: str = "auto", block_pages: int = 0,
                           interpret: Optional[bool] = None):
    """One decode-attention step over the paged KV cache.

    q: ``(B, H, Dh)`` — one query token per slot.
    kp/vp: ``(num_pages, H, P, Dh)`` — one layer's page pool.
    tables: ``(B, maxp)`` int32 page table (maxp may be the engine's
    used-page bucket, not the full table width); lengths: ``(B,)``
    int32 — position ``pos <= length`` attends.

    impl: "auto" (static dense policy, overridden per shape by the
    cached ``decode_attn`` auto-tuner site when ``BIGDL_TUNER=1``),
    "dense", "fused", "pallas", or "pallas_interpret" (testing).
    ``block_pages`` sets the fused path's page-block chunk (0 = whole
    width, one block).  ``interpret=None`` interprets the Pallas
    kernel on the CPU backend only.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if impl == "auto":
        impl, block_pages = static_decode_dispatch()
        from bigdl_tpu.ops import autotune

        if autotune.enabled():
            rec = autotune.decide_decode_attn(
                q.shape, int(page_size), int(tables.shape[1]), q.dtype,
                kv_dtype=kp.dtype,
                arrays=(q, kp, vp, tables, lengths))
            if rec is not None:
                impl = rec.get("impl", impl)
                block_pages = int(rec.get("block_pages") or 0)
    if impl in ("pallas", "pallas_interpret"):
        from bigdl_tpu.ops._pallas import resolve_interpret

        return _pallas(q, kp, vp, tables, lengths, page_size=page_size,
                       scale=scale, interpret=resolve_interpret(
                           True if impl == "pallas_interpret"
                           else interpret))
    if impl == "fused":
        return _fused(q, kp, vp, tables, lengths, page_size=page_size,
                      scale=scale, block_pages=block_pages)
    if impl != "dense":
        raise ValueError(
            f"impl must be auto|dense|fused|pallas, got {impl!r}")
    return _dense(q, kp, vp, tables, lengths, scale=scale)


__all__ = ["paged_decode_attention", "static_decode_dispatch",
           "used_page_bucket", "decode_hbm_bytes"]
