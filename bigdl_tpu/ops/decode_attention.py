"""Decode attention over the paged cache: one query token a slot (in
the latent body: or the few consecutive ones of a step that verifies a
draft) against the rows its page table names — the serving hot path.

Two bodies, one a cache kind (``serving/cache.py`` states the layouts);
the model calls the body of the cache it states, and nothing chooses
between them:

* :func:`paged_decode_attention` — a cache of per-head K/V rows: a
  layer's pool is ``(num_pages, P, H*Dh)``, token-major, a head a
  range of ``Dh`` lanes of a row.  Gather the pages the table names,
  masked softmax, weighted sum: the op sequence of
  ``TransformerBlock.decode_step``, so paged decode matches
  ``generate()`` token for token at temperature 0;
* :func:`latent_decode_attention` — a latent cache (``nn/latent.py``):
  one compressed row a token for all heads and no V buffer; the rows
  are read a block of pages at a time into an online softmax.

Mask contract (both bodies, pinned by tests): position ``pos <=
length`` attends, everything else is ``-inf`` before the softmax — so
page 0 (the reserved trash page unallocated table entries point at)
can hold arbitrary finite garbage and never contributes a bit to any
output.

The engine slices each step's page tables to the used-page bucket
(:func:`used_page_bucket`): the pow2 count of pages covering
``max(lengths)//P + 1``, so neither body pays for the empty pool.

A faster body REPLACES one of these two, in a ``perf_opt`` PR that
shows its gain in a cell of the benchmark; it is not added beside one
behind a switch.
"""

from __future__ import annotations

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


def decode_hbm_bytes(b: int, h: int, d: int, page_size: int,
                     maxp: int, kv_itemsize: int = 4) -> float:
    """Analytic HBM traffic of ONE layer's decode attention (the
    engine's bytes-per-token gauge): the ``2 * B * maxp`` K/V pages the
    tables name are read, and the gathered contiguous copy is written
    and read again (the gather tax), plus the f32 score plane's round
    trip."""
    k = maxp * page_size
    pages = 2.0 * b * maxp * page_size * h * d * kv_itemsize  # K + V
    qio = 2.0 * b * h * d * 4                                 # q + out
    return pages * 3 + 2.0 * b * h * k * 4 + qio


def _mask_neg_inf(scores, pos, lengths):
    """``pos <= length`` attends; everything else -inf (the trash-page
    contract)."""
    import jax.numpy as jnp

    return jnp.where(pos <= lengths, scores, -jnp.inf)


# --------------------------------------------------------------------------
# per-head K/V rows — gather, masked softmax, weighted sum
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


def paged_decode_attention(q, kp, vp, tables, lengths, *,
                           page_size: int, scale: Optional[float] = None,
                           layer: Optional[int] = None):
    """One decode-attention step over a paged cache of per-head K/V
    rows.

    q: ``(B, H, Dh)`` — one query token per slot.
    kp/vp: ``(num_pages, P, H*Dh)`` — one layer's page pool, token-
    major (serving/cache.py ``pool_shape``); or, with ``layer``, the
    engine's stacked ``(n_layer, num_pages, P, H*Dh)`` buffers, read
    in place (a ``kp[layer]`` handed in instead costs a copy of the
    layer's pool on the TPU).
    tables: ``(B, maxp)`` int32 page table (maxp may be the engine's
    used-page bucket, not the full table width); lengths: ``(B,)``
    int32 — position ``pos <= length`` attends.

    Gather + masked softmax — the op sequence of
    ``TransformerBlock.decode_step`` (scores, ``-inf`` mask, softmax,
    weighted sum, in the same dtypes) on the token-major cache, so the
    temperature-0 token-match contract vs ``generate()`` holds.
    """
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.serving.cache import gather_pages

    del page_size  # the pool's own (its rows are gathered whole)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    kall = gather_pages(kp, tables, layer)    # (B, maxp*P, H*Dh)
    vall = gather_pages(vp, tables, layer)
    scores = _head_scores(q, kall) * scale    # (B, H, maxp*P)
    mask = (jnp.arange(kall.shape[1])[None, None, :]
            <= lengths[:, None, None])
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return _head_mix(probs, vall)


# --------------------------------------------------------------------------
# latent — multi-query attention over one shared compressed row a token
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
    length`` attends, everything else is ``-inf``); ``lengths`` (B, H)
    gives every query a length of its own: the ``Q`` queries a slot of
    a step that verifies a draft ride the head axis (``H = Q x heads``)
    and share the one read of the slot's rows, each attending up to its
    own position (``nn/latent.py``).  Returns the mix
    over the rows' first ``value_width`` lanes, ``(B, H, value_width)``
    in float32 — the caller applies ``W_v`` and ``W_o``.

    It is multi-query attention with ``H`` query heads on one row: both
    contractions take the gathered rows whole, on the MXU, with float32
    accumulation, and the softmax is in float32.  The rows are read
    ``block_pages`` pages a slot at a time and folded into a running
    ``(m, l, acc)`` (an online softmax): gathered whole, a slot's rows
    are a temporary of the bucket's size an attention (335 MB at 128
    slots x 2048 positions x 640 lanes) that the TPU compiler, short of
    memory beside the weights, builds again for each of its uses (26 ms
    a step for 8 attentions; chip run, PR 26)."""
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.serving.cache import gather_pages

    b, maxp = tables.shape
    h = q.shape[1]
    p = pages.shape[-2]
    vw = int(value_width)
    bp = _chunk_pages(maxp, block_pages)
    qs = (q.astype(jnp.float32) * scale).astype(pages.dtype)
    len_b = lengths[:, None, None] if lengths.ndim == 1 \
        else lengths[:, :, None]

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


__all__ = ["paged_decode_attention", "latent_decode_attention",
           "used_page_bucket", "decode_hbm_bytes"]
