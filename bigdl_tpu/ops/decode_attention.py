"""Decode attention over the paged cache: one query token a slot (or
the few consecutive ones of a step that verifies a draft or refines a
block) against the rows its page table names — the serving hot path.

Two bodies, one a cache kind (``serving/cache.py`` states the layouts);
the model calls the body of the cache it states, and nothing chooses
between them:

* :func:`paged_decode_attention` — a cache of per-head K/V rows: a
  layer's pool is ``(num_pages, P, H_kv*Dh)``, token-major, a key/value
  head a range of ``Dh`` lanes of a row.  Gather the pages the table
  names, masked softmax, weighted sum: with one query a slot and a key
  head a query head the op sequence of
  ``TransformerBlock.decode_step``, so paged decode matches
  ``generate()`` token for token at temperature 0.  **Grouped heads
  and several positions a slot** (``models/sdar_moe.py``: 32 query
  heads over 4 key heads, a block of 4 positions a step) go through
  the same body: the ``H / H_kv`` query heads and the ``S`` positions
  that share a key head's rows are that head's query rows, all under
  the slot's one mask;
* :func:`latent_decode_attention` — a latent cache (``nn/latent.py``):
  one compressed row a token for all heads and no V buffer.  A Pallas
  kernel: it walks each slot's page list up to the slot's own length,
  copies the pages from where they lie in the pool into fast memory a
  block at a time, and folds each block into an online softmax there;
  the contexts' rows are read once, and nothing is gathered in HBM.

Mask contract (both bodies, pinned by tests): position ``pos <=
length`` attends, everything else is ``-inf`` before the softmax — so
page 0 (the reserved trash page unallocated table entries point at)
can hold arbitrary finite garbage and never contributes a bit to any
output.

The engine slices each step's page tables to the used-page bucket
(:func:`used_page_bucket`): the pow2 count of pages covering
``max(lengths)//P + 1``, so the gather body does not pay for the empty
pool (the latent kernel stops at each slot's length whatever the
width; the bucket only bounds the table it is handed).

A faster body REPLACES one of these two, in a ``perf_opt`` PR that
shows its gain in a cell of the benchmark; it is not added beside one
behind a switch.
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


def decode_hbm_bytes(b: int, h: int, d: int, page_size: int,
                     maxp: int, kv_itemsize: int = 4,
                     kv_heads: Optional[int] = None,
                     positions: int = 1) -> float:
    """Analytic HBM traffic of ONE layer's decode attention (the
    engine's bytes-per-token gauge): the ``2 * B * maxp`` K/V pages the
    tables name are read, and the gathered contiguous copy is written
    and read again (the gather tax), plus the f32 score plane's round
    trip.  A cached row holds ``kv_heads`` heads (default: one a query
    head); ``q``, the output and the score plane have ``h`` heads at
    each of a slot's ``positions``."""
    k = maxp * page_size
    rows = h if kv_heads is None else kv_heads
    pages = 2.0 * b * maxp * page_size * rows * d * kv_itemsize  # K + V
    qio = 2.0 * b * positions * h * d * 4                     # q + out
    return pages * 3 + 2.0 * b * positions * h * k * 4 + qio


# --------------------------------------------------------------------------
# per-head K/V rows — gather, masked softmax, weighted sum
# --------------------------------------------------------------------------


def _key_head_of(n: int, kv_heads: int, dtype):
    """``(kv_heads, n)`` selector: 1 where query row ``n`` reads key
    head ``j`` (the rows are laid out key head by key head, ``n //
    kv_heads`` of them each)."""
    import jax.numpy as jnp

    return jnp.repeat(jnp.eye(kv_heads, dtype=dtype), n // kv_heads, axis=1)


def _head_scores(q, rows, dtype=None):
    """``q·k`` per head: q ``(B, N, Dh)``, token rows ``(B, K,
    H_kv*Dh)`` -> ``(B, N, K)``.  A key head is a lane range of a row,
    so the rows are contracted whole, on the MXU, against q laid out
    block-diagonally (column ``n`` holds ``q[b, n]`` in the lanes of its
    key head and exact zeros elsewhere): the same products and the same
    f32 accumulation as a per-head dot, with no ``(.., H*Dh) -> (.., H,
    Dh)`` view of the rows.  (That view is a padded relayout of every
    gathered page on the TPU — 64 -> 128 lanes, 25 -> 32 sublanes; at
    GPT-2 XL's widths it made the 48 layers' attention 16.5 ms a step
    against 4.0 this way — chip run, PR 25.)  With a key head a query
    row (``N == H_kv``) this is, op for op, what it was before rows
    could share a key head; ``N > H_kv`` rows come key head by key
    head, ``N / H_kv`` to each."""
    import jax.numpy as jnp

    b, n, d = q.shape
    hkv = rows.shape[2] // d
    if n == hkv:
        eye = jnp.eye(n, dtype=q.dtype)
        qmat = (q[:, :, :, None] * eye[None, :, None, :]).reshape(
            b, n * d, n)
        return jnp.einsum("bkc,bch->bhk", rows, qmat)
    sel = _key_head_of(n, hkv, q.dtype)                     # (H_kv, N)
    qmat = (q.transpose(0, 2, 1)[:, None, :, :]
            * sel[None, :, None, :]).reshape(b, hkv * d, n)
    return jnp.einsum("bkc,bcn->bnk", rows, qmat,
                      preferred_element_type=dtype)


def _head_mix(probs, rows, d: int):
    """``probs·v`` per head: probs ``(B, N, K)``, token rows ``(B, K,
    H_kv*Dh)`` -> ``(B, N, Dh)``.  Every query row's weights meet the
    whole row on the MXU; row ``n`` keeps its key head's ``Dh`` lanes of
    the result (the other blocks are dropped, not summed in)."""
    import jax.numpy as jnp

    b, n, _ = probs.shape
    hkv = rows.shape[2] // d
    full = jnp.einsum("bhk,bkc->bhc", probs, rows)        # (B, N, H_kv*Dh)
    keep = jnp.eye(n, dtype=full.dtype) if n == hkv \
        else _key_head_of(n, hkv, full.dtype).T           # (N, H_kv)
    return jnp.sum(full.reshape(b, n, hkv, d) * keep[None, :, :, None],
                   axis=2)


def paged_decode_attention(q, kp, vp, tables, lengths, *,
                           page_size: int, scale: Optional[float] = None,
                           layer: Optional[int] = None, score_dtype=None):
    """One decode-attention step over a paged cache of per-head K/V
    rows.

    q: ``(B, H, Dh)`` — one query token per slot; or ``(B, S, H, Dh)``
    — ``S`` positions a slot (a block being refined), which all attend
    the same rows.
    kp/vp: ``(num_pages, P, H_kv*Dh)`` — one layer's page pool, token-
    major (serving/cache.py ``pool_shape``), ``H_kv`` dividing ``H``
    (query head ``h`` reads key head ``h // (H / H_kv)``); or, with
    ``layer``, the engine's stacked ``(n_layer, num_pages, P,
    H_kv*Dh)`` buffers, read in place (a ``kp[layer]`` handed in
    instead costs a copy of the layer's pool on the TPU).
    tables: ``(B, maxp)`` int32 page table (maxp may be the engine's
    used-page bucket, not the full table width); lengths: ``(B,)``
    int32 — position ``pos <= length`` attends, for every query of the
    slot (a block's step gives its last position: the block's rows are
    written before it attends).
    ``score_dtype``: the scores' and the softmax's dtype where rows
    share a key head (None: the operands').

    Gather + masked softmax — for ``(B, H, Dh)`` and ``H_kv == H`` the
    op sequence of ``TransformerBlock.decode_step`` (scores, ``-inf``
    mask, softmax, weighted sum, in the same dtypes) on the token-major
    cache, so the temperature-0 token-match contract vs ``generate()``
    holds.  Returns ``q``'s shape.
    """
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.serving.cache import gather_pages

    del page_size  # the pool's own (its rows are gathered whole)
    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5
    shape = q.shape
    if q.ndim == 4:
        # key head by key head: its H / H_kv query heads at each of the
        # S positions are its query rows
        b, s, h, _ = shape
        hkv = kp.shape[-1] // d
        q = q.reshape(b, s, hkv, h // hkv, d).transpose(0, 2, 1, 3, 4) \
            .reshape(b, s * h, d)
    kall = gather_pages(kp, tables, layer)    # (B, maxp*P, H_kv*Dh)
    vall = gather_pages(vp, tables, layer)
    scores = _head_scores(q, kall, score_dtype) * scale   # (B, N, maxp*P)
    mask = (jnp.arange(kall.shape[1])[None, None, :]
            <= lengths[:, None, None])
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    if score_dtype is not None:
        probs = probs.astype(vall.dtype)
    out = _head_mix(probs, vall, d)
    if len(shape) == 4:
        out = out.reshape(b, hkv, s, h // hkv, d).transpose(0, 2, 1, 3, 4) \
            .reshape(shape)
    return out


# --------------------------------------------------------------------------
# latent — multi-query attention over one shared compressed row a token
# --------------------------------------------------------------------------


# A block of pages is what one buffer of the kernel's ring holds, about
# this many bytes; the copies of the ring's other blocks are in flight
# while one is contracted.  Chosen on the chip (PERF.md section 6,
# PR 31): blocks of 16 pages are a tenth slower than of 32, and a third
# or fourth buffer buys nothing.
_BLOCK_BYTES = 640 * 1024
_BUFFERS = 2
# copies started a trip of the kernel's issue loop: a branch a page
# would cost the scalar core as much as the copy's descriptor
_COPIES_A_TRIP = 8


def _block_pages(page_size: int, row_width: int, itemsize: int,
                 head_rows: int) -> int:
    """Pages a block of the latent kernel, from the shapes alone (never
    from the table's width: a narrower bucket must change no bit): as
    many whole pages as ``_BLOCK_BYTES`` hold, and no more positions
    than 1024 or than keep a block's float32 scores (``head_rows`` x
    positions) within 256 KB."""
    by_bytes = _BLOCK_BYTES // (page_size * row_width * itemsize)
    positions = min(1024, (64 * 1024) // max(1, head_rows))
    bp = max(1, min(by_bytes, positions // page_size))
    return bp if bp < _COPIES_A_TRIP else bp - bp % _COPIES_A_TRIP


def _latent_kernel(bp: int, page: int, maxp: int, vw: int):
    """The kernel body of :func:`latent_decode_attention` for blocks of
    ``bp`` pages of ``page`` rows, a table ``maxp`` wide and a mix over
    the rows' first ``vw`` lanes.  One grid step a slot; the blocks of
    all slots, in order, are one stream through the ring of buffers it
    is given: all but one block's copies are in flight while one is
    contracted, across the slots' edges."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows_blk = bp * page
    unroll = min(_COPIES_A_TRIP, bp)
    assert bp % unroll == 0, bp

    def kernel(tables, need, layer, q_ref, len_ref, pool, o_ref,
               buf, sems, ring):
        # ring: [0] slot and [1] block the next copies are for, [2]
        # blocks issued, [3] blocks contracted
        b = pl.program_id(0)
        nslots = pl.num_programs(0)
        nbuf = buf.shape[0]
        lyr = layer[0]

        def blocks_of(slot):
            return (need[slot] + bp - 1) // bp

        def issue():
            slot, blk = ring[0], ring[1]

            @pl.when(slot < nslots)
            def _():
                half = ring[2] % nbuf

                def group(g, c):
                    for j in range(unroll):
                        j += g * unroll
                        # past the slot's last page: what the table names
                        # there (page 0, finite by the cache's contract;
                        # past the table's width, its last entry again)
                        pg = tables[slot * maxp
                                    + jnp.minimum(blk * bp + j, maxp - 1)]
                        pltpu.make_async_copy(pool.at[lyr, pg],
                                              buf.at[half, j],
                                              sems.at[half]).start()
                    return c

                lax.fori_loop(0, bp // unroll, group, 0)
                last = blk + 1 >= blocks_of(slot)
                ring[0] = jnp.where(last, slot + 1, slot)
                ring[1] = jnp.where(last, 0, blk + 1)
                ring[2] = ring[2] + 1

        @pl.when(b == 0)
        def _():
            for k in range(4):
                ring[k] = 0

        nblk = blocks_of(b)
        qs = q_ref[0]                                  # (H, R)
        lens = len_ref[0]                              # (H, 1)
        h = qs.shape[0]

        def block(i, carry):
            m, l, acc = carry
            # one more block's copies under way (before the very first
            # block: the whole ring's)
            def more(_, c):
                issue()
                return c

            lax.fori_loop(0, jnp.where(ring[2] == 0, nbuf, 1), more, 0)
            half = ring[3] % nbuf
            ring[3] = ring[3] + 1
            # one wait for the block's bytes, whichever copy ends last
            pltpu.make_async_copy(pool.at[lyr, pl.ds(0, bp)], buf.at[half],
                                  sems.at[half]).wait()
            rows = buf[half].reshape(rows_blk, buf.shape[-1])
            s = lax.dot_general(qs, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            pos = i * rows_blk + lax.broadcasted_iota(
                jnp.int32, (h, rows_blk), 1)
            s = jnp.where(pos <= lens, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            # fully-masked-so-far rows keep m=-inf; shift 0 avoids NaN
            shift = jnp.where(m_new == -jnp.inf, 0.0, m_new)
            pr = jnp.exp(s - shift)
            alpha = jnp.exp(m - shift)
            mix = jnp.dot(pr.astype(rows.dtype), rows[:, :vw],
                          preferred_element_type=jnp.float32)
            return (m_new, l * alpha + jnp.sum(pr, axis=-1, keepdims=True),
                    acc * alpha + mix)

        init = (jnp.full((h, 1), -jnp.inf, jnp.float32),
                jnp.zeros((h, 1), jnp.float32),
                jnp.zeros((h, vw), jnp.float32))
        _, l, acc = lax.fori_loop(0, nblk, block, init)
        o_ref[0] = acc / jnp.maximum(l, 1e-30)

    return kernel


def latent_decode_attention(q, pages, tables, lengths, *, scale: float,
                            value_width: int, layer: Optional[int] = None):
    """Decode attention over a **latent** paged cache (``nn/latent.py``:
    a token's row is ``[c | rotated k_rope]``, shared by all heads, and
    there is no V buffer).

    q: ``(B, H, R)`` — a row-shaped query a head (``W_kvb`` absorbed:
    ``[q_nope W_k | q_rope]``); pages: one layer's ``(num_pages, P, R)``
    pool or, with ``layer``, the stacked buffer, read where it lies;
    tables / lengths as in :func:`paged_decode_attention` (``pos <=
    length`` attends, everything else contributes nothing); ``lengths``
    (B, H) gives every query a length of its own: the ``Q`` queries a
    slot of a step that verifies a draft ride the head axis (``H = Q x
    heads``) and share the one read of the slot's rows, each attending
    up to its own position (``nn/latent.py``).  Returns the mix over
    the rows' first ``value_width`` lanes, ``(B, H, value_width)`` in
    float32 — the caller applies ``W_v`` and ``W_o``.

    A Pallas kernel, one grid step a slot.  Tables, lengths and
    ``layer`` are scalar prefetch; the pool stays in HBM and a slot's
    pages are copied from ``[layer, page]`` into fast memory a block at
    a time, up to the slot's OWN length (the blocks that cover the
    ``length // P + 1`` pages of its longest query; the last block is
    copied whole, from what the table names there: page 0, finite by
    the cache's contract, and masked) — not the table's width, and
    with no gathered copy in HBM — the next blocks' copies (at a slot's
    end: the next slot's first) in flight while this one is
    contracted.  It is multi-query attention with ``H`` query heads on
    one row: a block is one ``(H, R) x (R, rows)`` and one ``(H, rows)
    x (rows, value_width)`` product on the MXU with float32
    accumulation, operands in the pool's dtype (``q`` is scaled in
    float32 first), folded into a running float32 ``(m, l, acc)`` (an
    online softmax).
    The pages a block are taken from the shapes (:func:`_block_pages`),
    so a table cut to the used-page bucket changes no bit.  Off the CPU
    it is the Mosaic kernel or an error; on the CPU backend the Pallas
    interpreter (``ops/_pallas.resolve_interpret``)."""
    import jax.numpy as jnp

    from bigdl_tpu.ops._pallas import resolve_interpret

    return _latent_program(float(scale), int(value_width),
                           resolve_interpret(None))(
        q, pages if layer is not None else pages[None], tables, lengths,
        jnp.full((1,), layer or 0, jnp.int32))


@functools.lru_cache(maxsize=None)
def _latent_program(scale: float, vw: int, interpret: bool):
    """The jitted call of the latent kernel for one ``(scale,
    value_width)``, the layer an argument: built once, so a model's
    attentions share one traced program a shape, and a caller outside
    a ``jit`` (the tests' teacher-forced loops) compiles it once a
    shape and not once a call."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(q, pool, tables, lengths, layer):
        b, maxp = tables.shape
        h, r = q.shape[1], q.shape[2]
        p = pool.shape[-2]
        bp = _block_pages(p, r, pool.dtype.itemsize, h)
        qs = (q.astype(jnp.float32) * scale).astype(pool.dtype)
        lens = jnp.broadcast_to(
            lengths[:, None] if lengths.ndim == 1 else lengths,
            (b, h)).astype(jnp.int32)
        # pages a slot must read: up to its longest query's position
        need = jnp.clip(jnp.max(lens, axis=1) // p + 1, 1, maxp)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h, r), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, h, 1), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, h, vw), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((_BUFFERS, bp, p, r), pool.dtype),
                pltpu.SemaphoreType.DMA((_BUFFERS,)),
                pltpu.SMEM((4,), jnp.int32),
            ])
        return pl.pallas_call(
            _latent_kernel(bp, p, maxp, vw),
            out_shape=jax.ShapeDtypeStruct((b, h, vw), jnp.float32),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="latent_decode_attention",
        )(tables.reshape(-1).astype(jnp.int32), need, layer, qs,
          lens[:, :, None], pool)

    return jax.jit(call)


__all__ = ["paged_decode_attention", "latent_decode_attention",
           "used_page_bucket", "decode_hbm_bytes"]
