"""Flash-decode over the paged KV cache — the serving hot path's kernel.

PR 12's continuous-batching decode step ran its attention the naive
way: ``gather_pages`` materialized a dense per-slot K/V copy **per
layer per step**, then full-width einsum attention masked the
mostly-unallocated tail with ``-inf`` — pure wasted HBM bandwidth in a
regime that is entirely memory-bound (one query token against a long
scattered KV).  This module is the flash-decoding answer (the
decode-side sibling of ops/attention.py's flash kernel).  All three
bodies read the one cache layout serving/cache.py states: a layer's
pool is ``(num_pages, P, H*Dh)``, token-major, and a head is a range
of ``Dh`` lanes of a row:

* ``impl="dense"`` — the PR 12 math, verbatim: gather + masked softmax
  einsum.  It is the **static baseline** the auto-tuner can never lose
  to, and the path that preserves the temperature-0 bit-match-vs-
  ``generate()`` contract;
* ``impl="fused"`` — split-KV online softmax in plain lax: K/V are
  read **page-block by page-block through the page table** (a chunk of
  ``block_pages`` pages per iteration), each block's scores are
  softmax-accumulated into a carried ``(m, l, acc)`` running state,
  and one final rescale produces the output — the gathered dense copy
  never exists.  Runs everywhere
  XLA runs, including inside the TP ``shard_map`` body on the
  head-sharded cache;
* ``impl="pallas"`` — the true flash-decode TPU kernel: grid
  ``(B, pages)`` with the page table and lengths as **scalar
  prefetch** so each program's BlockSpec index map DMAs exactly the
  page the table names, all heads of it at once (one contiguous
  ``(P, H*Dh)`` block; trash-page contract below), per-head
  ``(m, l, acc)`` carried in VMEM scratch across the page grid
  dimension, output written on the final page.  The CPU backend
  (tests) runs it in
  the Pallas interpreter; an accelerator compiles it or fails.

A fourth body, :func:`latent_decode_attention`, serves models whose
cache holds one compressed row a token for all heads and no V buffer
(latent attention, ``nn/latent.py``); it has one implementation and
takes no part in the dispatch below.

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


def _head_scores(q, rows):
    """``q·k`` per head: q ``(B, H, Dh)``, token rows ``(B, K, H*Dh)``
    -> ``(B, H, K)``.  A head is a lane range of a row, so the rows
    are contracted whole, on the MXU, against q laid out block-
    diagonally (column ``h`` holds ``q[b, h]`` in head ``h``'s lanes
    and exact zeros elsewhere): the same products and the same f32
    accumulation as a per-head dot, with no ``(.., H*Dh) -> (.., H,
    Dh)`` view of the rows.  (That view is a padded relayout of every
    gathered page on the TPU — 64 -> 128 lanes, 25 -> 32 sublanes; at
    GPT-2 XL's widths it made the 48 layers' attention 16.5 ms a step
    against 4.0 this way — chip run, PR 25.)"""
    import jax.numpy as jnp

    b, h, d = q.shape
    eye = jnp.eye(h, dtype=q.dtype)
    qmat = (q[:, :, :, None] * eye[None, :, None, :]).reshape(b, h * d, h)
    return jnp.einsum("bkc,bch->bhk", rows, qmat)


def _head_mix(probs, rows):
    """``probs·v`` per head: probs ``(B, H, K)``, token rows ``(B, K,
    H*Dh)`` -> ``(B, H, Dh)``.  Every head's weights meet the whole
    row on the MXU; head ``h`` keeps its own ``Dh`` lanes of the
    result (the other blocks are dropped, not summed in)."""
    import jax.numpy as jnp

    b, h, _ = probs.shape
    d = rows.shape[2] // h
    full = jnp.einsum("bhk,bkc->bhc", probs, rows)        # (B, H, H*Dh)
    eye = jnp.eye(h, dtype=full.dtype)
    return jnp.sum(full.reshape(b, h, h, d) * eye[None, :, :, None],
                   axis=2)


def _dense(q, kp, vp, tables, lengths, *, scale: float, layer=None):
    """Gather + masked softmax — the op sequence of
    ``TransformerBlock.decode_step`` (scores, ``-inf`` mask, softmax,
    weighted sum, in the same dtypes) on the token-major cache, so the
    temperature-0 token-match contract vs ``generate()`` holds."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.serving.cache import gather_pages

    kall = gather_pages(kp, tables, layer)    # (B, maxp*P, H*Dh)
    vall = gather_pages(vp, tables, layer)
    scores = _head_scores(q, kall) * scale    # (B, H, maxp*P)
    mask = (jnp.arange(kall.shape[1])[None, None, :]
            <= lengths[:, None, None])
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return _head_mix(probs, vall)


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
           block_pages: int = 0, layer=None):
    """Online-softmax paged decode: page blocks are gathered one chunk
    at a time through the table (``bp`` whole pages a slot, as they
    lie — never the full contiguous copy), each chunk's masked scores
    fold into the carried ``(m, l, acc)``, one final rescale.  f32
    accumulation throughout."""
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.serving.cache import gather_pages

    b, maxp = tables.shape
    h, d = q.shape[1], q.shape[2]
    p = int(page_size)
    bp = _chunk_pages(maxp, block_pages)
    n_chunks = maxp // bp
    qf = q.astype(jnp.float32) * scale        # (B, H, Dh)
    len_b = lengths[:, None, None]            # (B, 1, 1)

    def block(tbl_c, c0, m, l, acc):
        """Fold pages [c0, c0+bp) (table slice ``tbl_c``) into the
        running state.  ``c0`` may be traced (fori path)."""
        kc = gather_pages(kp, tbl_c, layer).astype(jnp.float32)
        vc = gather_pages(vp, tbl_c, layer).astype(jnp.float32)
        s = _head_scores(qf, kc)                      # (B, H, bp*P)
        pos = c0 * p + jnp.arange(bp * p)[None, None, :]
        s = _mask_neg_inf(s, pos, len_b)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # fully-masked-so-far rows keep m=-inf; shift 0 avoids NaN
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        pr = jnp.exp(s - shift[..., None])
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - shift, -jnp.inf))
        l_new = l * alpha + jnp.sum(pr, axis=-1)
        acc_new = acc * alpha[..., None] + _head_mix(pr, vc)
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
    """One (slot, page) program over ALL heads.  The BlockSpec index
    maps below already resolved this program's K/V block to the page
    the table names (scalar prefetch), so the kernel sees one whole
    page as it lies in the cache, ``(P, H*Dh)``; head ``h`` is the
    static lane slice ``[:, h*Dh:(h+1)*Dh]``.  (m, l, acc) carry in
    VMEM scratch — one row per head — across the page grid dimension
    (fastest-varying, sequential on TPU).  m and l rows stay (1, 1)
    arrays end to end: Mosaic stores vectors to VMEM, not scalars."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    ns = pl.num_programs(1)
    n_head, d = acc_scr.shape

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[...].astype(jnp.float32) * scale         # (H, Dh)
    ks = k_ref[...].astype(jnp.float32)                # (P, H*Dh)
    vs = v_ref[...].astype(jnp.float32)
    pos = j * page_size + lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    live = pos <= len_ref[pl.program_id(0)]
    for h in range(n_head):
        row, lanes = slice(h, h + 1), slice(h * d, (h + 1) * d)
        s = jax.lax.dot_general(
            q[row], ks[:, lanes], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (1, P)
        s = jnp.where(live, s, -jnp.inf)
        m = m_scr[row, :]                              # (1, 1)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # fully-masked-so-far rows keep m=-inf; shift 0 avoids NaN
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - shift)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - shift, -jnp.inf))
        l_scr[row, :] = l_scr[row, :] * alpha + jnp.sum(
            p, axis=-1, keepdims=True)
        acc_scr[row, :] = acc_scr[row, :] * alpha + jax.lax.dot_general(
            p, vs[:, lanes], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (1, Dh)
        m_scr[row, :] = m_new

    @pl.when(j == ns - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)


def _pallas(q, kp, vp, tables, lengths, *, page_size: int, scale: float,
            interpret: bool = False, layer=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    maxp = tables.shape[1]
    p = int(page_size)

    # every block's last two dims are full — (H, Dh) of q and out,
    # (P, H*Dh) of a page — which is what Mosaic asks of a block that
    # is not (8, 128)-divisible; the leading dims are squeezed away
    qo_spec = pl.BlockSpec((None, h, d), lambda i, j, tbl, lens: (i, 0, 0))
    if layer is None:
        kv_spec = pl.BlockSpec((None, p, h * d), lambda i, j, tbl, lens:
                               (tbl[i, j], 0, 0))
    else:   # the stacked cache: the layer rides in the index map
        kv_spec = pl.BlockSpec((None, None, p, h * d),
                               lambda i, j, tbl, lens:
                               (layer, tbl[i, j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # tables, lengths
        grid=(b, maxp),
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, page_size=p, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q, kp, vp)


# --------------------------------------------------------------------------
# latent — multi-query attention over one shared compressed row a token
# --------------------------------------------------------------------------


def latent_decode_attention(q, pages, tables, lengths, *, scale: float,
                            value_width: int, layer: Optional[int] = None,
                            block_pages: int = 16):
    """Decode attention over a **latent** paged cache (``nn/latent.py``:
    a token's row is ``[c | rotated k_rope]``, shared by all heads, and
    there is no V buffer).

    q: ``(B, H, R)`` — a row-shaped query a head (``W_kvb`` absorbed:
    ``[q_nope W_k | q_rope]``); pages: one layer's ``(num_pages, P, R)``
    pool or, with ``layer``, the stacked buffer, read where it lies;
    tables / lengths as in :func:`paged_decode_attention` (``pos <=
    length`` attends, everything else is ``-inf``).  Returns the mix
    over the rows' first ``value_width`` lanes, ``(B, H, value_width)``
    in float32 — the caller applies ``W_v`` and ``W_o``.

    It is multi-query attention with ``H`` query heads on one row: both
    contractions take the gathered rows whole, on the MXU, with float32
    accumulation, and the softmax is in float32.  The rows are read
    ``block_pages`` pages a slot at a time and folded into a running
    ``(m, l, acc)`` (the online softmax of :func:`_fused`): gathered
    whole, a slot's rows are a temporary of the bucket's size an
    attention (335 MB at 128 slots x 2048 positions x 640 lanes) that
    the TPU compiler, short of memory beside the weights, builds again
    for each of its uses (26 ms a step for 8 attentions; chip run,
    PR 26)."""
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.serving.cache import gather_pages

    b, maxp = tables.shape
    h = q.shape[1]
    p = pages.shape[-2]
    vw = int(value_width)
    bp = _chunk_pages(maxp, block_pages)
    qs = (q.astype(jnp.float32) * scale).astype(pages.dtype)
    len_b = lengths[:, None, None]

    def block(tbl_c, c0, m, l, acc):
        rows = gather_pages(pages, tbl_c, layer)       # (B, bp*P, R)
        s = jnp.einsum("bhc,bkc->bhk", qs, rows,
                       preferred_element_type=jnp.float32)
        pos = c0 * p + jnp.arange(bp * p)[None, None, :]
        s = _mask_neg_inf(s, pos, len_b)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # fully-masked-so-far rows keep m=-inf; shift 0 avoids NaN
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        pr = jnp.exp(s - shift[..., None])
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - shift, -jnp.inf))
        mix = jnp.einsum("bhk,bkc->bhc", pr.astype(rows.dtype),
                         rows[..., :vw],
                         preferred_element_type=jnp.float32)
        return (m_new, l * alpha + jnp.sum(pr, axis=-1),
                acc * alpha[..., None] + mix)

    init = (jnp.full((b, h), -jnp.inf, jnp.float32),
            jnp.zeros((b, h), jnp.float32),
            jnp.zeros((b, h, vw), jnp.float32))
    if bp == maxp:
        _, l, acc = block(tables, 0, *init)
    else:
        def body(c, carry):
            tbl_c = lax.dynamic_slice_in_dim(tables, c * bp, bp, axis=1)
            return block(tbl_c, c * bp, *carry)

        _, l, acc = lax.fori_loop(0, maxp // bp, body, init)
    return acc / jnp.maximum(l, 1e-30)[..., None]


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
                           interpret: Optional[bool] = None,
                           layer: Optional[int] = None):
    """One decode-attention step over the paged KV cache.

    q: ``(B, H, Dh)`` — one query token per slot.
    kp/vp: ``(num_pages, P, H*Dh)`` — one layer's page pool, token-
    major (serving/cache.py ``pool_shape``); or, with ``layer``, the
    engine's stacked ``(n_layer, num_pages, P, H*Dh)`` buffers, read
    in place (a ``kp[layer]`` handed in instead costs a copy of the
    layer's pool on the TPU).
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
            # the site's probes take one layer's pool
            pools = (kp, vp) if layer is None else (kp[layer], vp[layer])
            rec = autotune.decide_decode_attn(
                q.shape, int(page_size), int(tables.shape[1]), q.dtype,
                kv_dtype=kp.dtype,
                arrays=(q, *pools, tables, lengths))
            if rec is not None:
                impl = rec.get("impl", impl)
                block_pages = int(rec.get("block_pages") or 0)
    if impl in ("pallas", "pallas_interpret"):
        from bigdl_tpu.ops._pallas import resolve_interpret

        return _pallas(q, kp, vp, tables, lengths, page_size=page_size,
                       scale=scale, layer=layer,
                       interpret=resolve_interpret(
                           True if impl == "pallas_interpret"
                           else interpret))
    if impl == "fused":
        return _fused(q, kp, vp, tables, lengths, page_size=page_size,
                      scale=scale, block_pages=block_pages, layer=layer)
    if impl != "dense":
        raise ValueError(
            f"impl must be auto|dense|fused|pallas, got {impl!r}")
    return _dense(q, kp, vp, tables, lengths, scale=scale, layer=layer)


__all__ = ["paged_decode_attention", "latent_decode_attention",
           "static_decode_dispatch", "used_page_bucket",
           "decode_hbm_bytes"]
