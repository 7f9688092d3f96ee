"""Decode attention over the paged cache: one query token a slot (or
the few consecutive ones of a step that verifies a draft or refines a
block) against the rows its page table names — the serving hot path.

Two bodies, one a cache kind (``serving/cache.py`` states the layouts);
the model calls the body of the cache it states, and nothing chooses
between them:

* :func:`paged_decode_attention` — a cache of per-head K/V rows: a
  layer's pool is ``(num_pages, P, H_kv*Dh)``, token-major, a key/value
  head a range of ``Dh`` lanes of a row.  **Which shapes take which
  path** (the function looks at ``q`` and the pool, at nothing else):

  - *one query row a key head* (``S x H == H_kv``: ``models/
    transformer.py``, 25 heads of 64 lanes, one token a slot): gather
    the pages the table names, masked softmax, weighted sum — the op
    sequence of ``TransformerBlock.decode_step``, so paged decode
    matches ``generate()`` token for token at temperature 0.  A
    per-head kernel measured 6x slower than this at those widths (25
    tiny dots a page; PERF.md section 6, PRs 25 and 28).  **The
    gather is a copy**, ``(B, maxp x P, H_kv x Dh)`` for K and again
    for V, and it grows with the pool: 20 MB at GPT-2 XL's cell, 4 GB
    each at 256 slots of 30 heads of 128 lanes over 2048 positions
    (``models/olmo_hybrid.py``), beside 13 GB of arguments.  So where
    a layer's pool is over :data:`_GATHER_POOL_BYTES` and a row is
    whole lane tiles, one query row a key head goes through the page
    stream as well (:func:`_single_kernel`: the same stream, mask and
    online softmax as the kernel below; the two products are ONE
    matrix product each, the queries laid block-diagonally as
    :func:`_head_scores` lays them, because ``H_kv`` products of one
    row are what measured 6x slower);
  - *query rows that share a key head* (``S x H > H_kv``: grouped
    heads and/or several positions a slot; ``models/sdar_moe.py``, 32
    query heads over 4 key heads of 128 lanes, a block of 4 positions a
    step): a Pallas kernel that walks each slot's page list up to the
    slot's own length over the K pool and the V pool, copies the pages
    from where they lie into fast memory a block at a time and folds
    each block into a float32 online softmax, key head by key head:
    a key head's ``S x H / H_kv`` query rows share the one read of its
    lanes.  Nothing is gathered in HBM and no score plane is written.
    ZAYA1 (``models/zaya.py``: 8 query heads over 2 key heads, one
    token a slot) takes the same kernel at 4 query rows a key head.

  The two paths share no logic: their needs conflict (PERF.md section
  6, PR 33), so they are separated, not adapted;
* :func:`latent_decode_attention` — a latent cache (``nn/latent.py``):
  one compressed row a token for all heads and no V buffer.  A Pallas
  kernel of the same build (the two kernels share the ring of buffers
  their pages stream through, :func:`_page_stream`): the contexts' rows
  are read once, and nothing is gathered in HBM.

**What the kernels' stream copies** (PR 38): of each slot the pages
its length needs (``length // P + 1``), rounded up to a group of
``_COPIES_A_TRIP`` = 8 pages, and no more: every block of a slot but
the last whole, the last in whole groups.  A block is still CONTRACTED
whole (static shapes); the rows of its buffer that were not copied are
an earlier block's or the zeros the ring starts with, all finite, all
masked.  Over the long-generation mixes' lengths that is 1.07 rows
copied for every row a slot holds, where whole blocks copied 1.275 (32
pages a block) and 1.58 (ZAYA1's 64); :func:`stream_rows_copied` is
the count, and the engine puts it on ``serve.decode_step``.
**With how many copies** (PR 49): a group whose table entries name
neighbouring pages (``first, first + 1, ...`` for the pages the slot
holds of it, and ``first + 8`` inside the pool) is ONE copy of 8 pages,
64-160 KB of a layer's pool as they lie; any other group is a copy a
page.  The scalar core that starts a block's copies runs in the same
instruction stream as the block's products, so a kernel's time is its
products PLUS the issue of its descriptors, and at a descriptor a page
that issue was as long as the products (cells 4-6) or twice the bytes'
transfer (cell 7's 8 KB pages).  Which groups are runs is decided
from the table alone, in the program around the kernel
(:func:`_run_starts`: the same comparisons cost the kernel's scalar
core 7 descriptors' time a group); ``serving/cache.py``'s allocator
makes runs the usual case.  Of
a slot's last group a run's copy brings along the pages BEHIND the
slot's, as they lie (another slot's rows, or the zeros the pool starts
with): finite and masked like everything else past the length, so any
table gives the bits a copy a page gives.  :func:`stream_copies` is the
count (``attn_copies`` on the span).

Mask contract (every path, pinned by tests): position ``pos <=
length`` attends, everything else is ``-inf`` before the softmax — so
page 0 (the reserved trash page unallocated table entries point at)
can hold arbitrary finite garbage and never contributes a bit to any
output, and neither can any other page of the pool (the kernels may
read the 7 pages behind a slot's last one: the pool holds finite rows
everywhere, which zeros and written rows are).

The engine slices each step's page tables to the used-page bucket
(:func:`used_page_bucket`): the pow2 count of pages covering
``max(lengths)//P + 1``, so the gather path does not pay for the empty
pool (the kernels stop at each slot's last needed group of pages
whatever the width; the bucket only bounds the table they are handed).

A faster body REPLACES one of these, in a ``perf_opt`` PR that shows
its gain in a cell of the benchmark; it is not added beside one behind
a switch.
"""

from __future__ import annotations

import functools
import math
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
    """Analytic HBM traffic of ONE layer's decode attention over
    per-head K/V rows (the engine's bytes-per-token gauge), by the
    path :func:`paged_decode_attention` takes at these shapes.  A
    cached row holds ``kv_heads`` heads (default: one a query head);
    ``q`` and the output have ``h`` heads at each of a slot's
    ``positions``.

    One query row a key head (the gather): the ``2 * B * maxp`` K/V
    pages the tables name are read, and the gathered contiguous copy is
    written and read again (the gather tax), plus the f32 score plane's
    round trip.  Rows that share a key head (the kernel): the bucket's
    K and V pages ONCE, the queries and the outputs; nothing is
    gathered and no score plane leaves fast memory.  That is an upper
    bound, since the gauge does not know the slots' lengths: the kernel
    copies each slot's pages up to its length, rounded up to a group of
    ``_COPIES_A_TRIP`` pages (:func:`stream_rows_copied` counts them
    for given lengths).  The gauge is not told the pool: one query row
    a key head is counted as the gather even over a pool that
    :func:`_streams` sends through the kernel (three times too much
    there; the spans' ``attn_rows_copied`` is the count to read)."""
    k = maxp * page_size
    rows = h if kv_heads is None else kv_heads
    pages = 2.0 * b * maxp * page_size * rows * d * kv_itemsize  # K + V
    qio = 2.0 * b * positions * h * d * 4                     # q + out
    if positions * h > rows:
        return pages + qio
    return pages * 3 + 2.0 * b * positions * h * k * 4 + qio


def stream_rows_copied(lengths, page_size: int, maxp: int, row_width: int,
                       itemsize: int, query_rows: int) -> int:
    """Rows of a pool that ONE call of a kernel's page stream
    (:func:`_page_stream`) copies for slots that attend ``pos <=
    lengths``, by the kernel's own arithmetic: a slot needs ``length //
    P + 1`` pages (clipped to the table's ``maxp``), in blocks of
    :func:`_block_pages` pages (from the page, the pool's
    ``row_width`` and ``itemsize`` and the ``query_rows`` a slot hands
    the kernel); every block but the last is copied whole, the last in
    whole groups of ``_COPIES_A_TRIP`` pages.  Over the rows the slots
    hold (``sum(lengths + 1)``) it says what the stream reads for
    every row it must: the engine puts both on ``serve.decode_step``
    (``attn_rows_copied``, ``context_tokens``)."""
    import numpy as np

    bp = _block_pages(page_size, row_width, itemsize, query_rows)
    trip = min(_COPIES_A_TRIP, bp)
    need = np.clip(np.asarray(lengths, np.int64) // page_size + 1, 1, maxp)
    whole, last = np.divmod(need - 1, bp)    # last block: ``last + 1`` pages
    pages = whole * bp + _groups(last + 1, trip) * trip
    return int(pages.sum()) * page_size


def stream_copies(tables, lengths, page_size: int, num_pages: int,
                  row_width: int, itemsize: int, query_rows: int) -> int:
    """Copy descriptors that ONE call of a kernel's page stream starts a
    pool for slots that attend ``pos <= lengths`` over the table rows
    ``tables`` (one a slot, as the step was handed them), by the
    kernel's own arithmetic (:func:`_run_starts`): a slot
    copies the groups of ``_COPIES_A_TRIP`` pages that hold its
    ``length // P + 1`` pages; a group whose held entries name
    neighbouring pages, with a whole group's pages from the first
    inside the pool of ``num_pages``, is ONE descriptor, any other one
    a page.  :func:`stream_rows_copied` over the page and over this is
    the pages a descriptor: 1 under a scattered table, the trip under a
    table of runs (the engine puts it on ``serve.decode_step``,
    ``attn_copies``)."""
    import numpy as np

    tables = np.asarray(tables, np.int32)
    bp = _block_pages(page_size, row_width, itemsize, query_rows)
    trip = min(_COPIES_A_TRIP, bp)
    need = np.clip(np.asarray(lengths, np.int64) // page_size + 1, 1,
                   tables.shape[1])
    groups = _groups(tables.shape[1], trip)
    starts = _run_starts(tables, need, bp, num_pages, np) \
        .reshape(len(tables), groups)
    copied = np.arange(groups) * trip < need[:, None]
    return int(np.where(starts >= 0, 1, trip)[copied].sum())


# --------------------------------------------------------------------------
# per-head K/V rows, one query row a key head — gather, masked softmax,
# weighted sum
# --------------------------------------------------------------------------


def _head_scores(q, rows):
    """``q·k`` per head: q ``(B, H, Dh)``, token rows ``(B, K, H*Dh)``
    -> ``(B, H, K)``.  A head is a lane range of a row, so the rows are
    contracted whole, on the MXU, against q laid out block-diagonally
    (column ``h`` holds ``q[b, h]`` in the lanes of its head and exact
    zeros elsewhere): the same products and the same f32 accumulation
    as a per-head dot, with no ``(.., H*Dh) -> (.., H, Dh)`` view of
    the rows.  (That view is a padded relayout of every gathered page
    on the TPU — 64 -> 128 lanes, 25 -> 32 sublanes; at GPT-2 XL's
    widths it made the 48 layers' attention 16.5 ms a step against 4.0
    this way — chip run, PR 25.)"""
    import jax.numpy as jnp

    b, h, d = q.shape
    eye = jnp.eye(h, dtype=q.dtype)
    qmat = (q[:, :, :, None] * eye[None, :, None, :]).reshape(b, h * d, h)
    return jnp.einsum("bkc,bch->bhk", rows, qmat)


def _head_mix(probs, rows, d: int):
    """``probs·v`` per head: probs ``(B, H, K)``, token rows ``(B, K,
    H*Dh)`` -> ``(B, H, Dh)``.  Every head's weights meet the whole row
    on the MXU; head ``h`` keeps its own ``Dh`` lanes of the result
    (the other blocks are dropped, not summed in)."""
    import jax.numpy as jnp

    b, h, _ = probs.shape
    full = jnp.einsum("bhk,bkc->bhc", probs, rows)        # (B, H, H*Dh)
    eye = jnp.eye(h, dtype=full.dtype)
    return jnp.sum(full.reshape(b, h, h, d) * eye[None, :, :, None], axis=2)


def paged_decode_attention(q, kp, vp, tables, lengths, *,
                           page_size: int, scale: Optional[float] = None,
                           layer: Optional[int] = None):
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

    Two paths that share no logic, chosen by the shapes of ``q`` and
    the pool alone (module docstring):

    * ``S x H == H_kv`` (one query row a key head, whether ``q`` comes
      as ``(B, H, Dh)`` or ``(B, 1, H, Dh)``): gather + masked softmax,
      the op sequence of ``TransformerBlock.decode_step`` (scores,
      ``-inf`` mask, softmax, weighted sum, in the same dtypes) on the
      token-major cache, so the temperature-0 token-match contract vs
      ``generate()`` holds; unless the pool is one the gather cannot
      be asked to copy (:func:`_streams`: a layer's pool over
      :data:`_GATHER_POOL_BYTES`, rows of whole lane tiles), which goes
      the second way;
    * ``S x H > H_kv`` (rows that share a key head): the Pallas kernel
      of :func:`_grouped_program` — each slot's pages of K and of V are
      copied from ``[layer, page]`` where they lie, a block at a time,
      up to the slot's own length (in groups of 8 pages:
      :func:`_page_stream`), and contracted in fast memory:
      operands in the pools' dtype (``q`` is scaled in float32 first),
      float32 scores, online softmax and accumulation.  Off the CPU it
      is the Mosaic kernel or an error; on the CPU backend the Pallas
      interpreter.

    Returns ``q``'s shape and dtype.
    """
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5
    shape = q.shape
    if math.prod(shape[1:-1]) > kp.shape[-1] // d or _streams(kp):
        from bigdl_tpu.ops._pallas import resolve_interpret

        stacked = layer is not None
        return _grouped_program(float(scale), resolve_interpret(None))(
            q.reshape(shape[0], -1, *shape[-2:]),
            kp if stacked else kp[None], vp if stacked else vp[None],
            tables, lengths, jnp.full((1,), layer or 0, jnp.int32)
        ).reshape(shape)

    from bigdl_tpu.serving.cache import gather_pages

    del page_size  # the pool's own (its rows are gathered whole)
    if q.ndim == 4:
        q = q[:, 0]
    kall = gather_pages(kp, tables, layer)    # (B, maxp*P, H*Dh)
    vall = gather_pages(vp, tables, layer)
    scores = _head_scores(q, kall) * scale    # (B, H, maxp*P)
    mask = (jnp.arange(kall.shape[1])[None, None, :]
            <= lengths[:, None, None])
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _head_mix(probs, vall, d)
    return out if len(shape) == 3 else out[:, None]


# --------------------------------------------------------------------------
# the kernels — a slot's pages streamed through fast memory where they lie
# --------------------------------------------------------------------------


# One query row a key head: a layer's K pool (and its V pool) over this
# many bytes is streamed, not gathered.  The gathered copy of a full
# table is the pool's size again under the engine's default pool (a
# slot's longest context for every slot): 25 MB for GPT-2 XL's cell (481
# pages), 4 GB for 256 slots of 7,680-byte rows.
_GATHER_POOL_BYTES = 256 << 20


def _streams(pool) -> bool:
    """Whether one query row a key head over ``pool`` (a layer's
    ``(num_pages, P, row)`` or the stacked ``(layers, ...)``) goes
    through the page stream: by the pool's shape alone."""
    pages, page, row = pool.shape[-3:]
    return row % 128 == 0 and \
        pages * page * row * pool.dtype.itemsize > _GATHER_POOL_BYTES


# A block of pages is what one buffer of a kernel's ring holds, about
# this many bytes; the copies of the ring's other blocks are in flight
# while one is contracted.  Chosen on the chip (PERF.md section 6,
# PR 31): blocks of 16 pages are a tenth slower than of 32, and a third
# or fourth buffer buys nothing.
_BLOCK_BYTES = 640 * 1024
_BUFFERS = 2
# copies started a trip of the kernels' issue loop (a branch a page
# would cost the scalar core as much as the copy's descriptor), and so
# the granule in which a slot's last block is copied and awaited
_COPIES_A_TRIP = 8


def _block_pages(page_size: int, row_width: int, itemsize: int,
                 head_rows: int) -> int:
    """Pages a block of a kernel, from the shapes alone (never from
    the table's width: a narrower bucket must change no bit): as many
    whole pages as ``_BLOCK_BYTES`` hold, and no more positions than
    1024 or than keep a block's float32 scores (``head_rows`` x
    positions) within 256 KB, but 512 positions whatever the rows: over
    128 query rows a slot (``models/sdar_moe.py``: a tail and a block,
    256) the scores pass 256 KB and a key head's share of them, which
    is what the grouped kernel folds at a time, does not, while blocks
    of 256 positions cost that step 0.4 of its attention's 4.15 ms
    (chip run, PR 41; PR 31 found the same tenth).  A page so wide that
    ``_BLOCK_BYTES`` hold less than one trip of copies (16 rows of
    7,680 B: 5 pages) takes a whole trip where twice those bytes hold
    it."""
    page_bytes = page_size * row_width * itemsize
    by_bytes = max(_BLOCK_BYTES // page_bytes,
                   min(_COPIES_A_TRIP, 2 * _BLOCK_BYTES // page_bytes))
    positions = min(1024, max(512, (64 * 1024) // max(1, head_rows)))
    bp = max(1, min(by_bytes, positions // page_size))
    return bp if bp < _COPIES_A_TRIP else bp - bp % _COPIES_A_TRIP


def _groups(pages, trip: int):
    """Groups of ``trip`` pages that hold ``pages`` pages: what a
    kernel's stream copies of a block (traced in the kernel, numbers in
    :func:`stream_rows_copied`)."""
    return (pages + trip - 1) // trip


def _run_starts(tables, need, bp: int, num_pages: int, xp=None):
    """Which groups of a kernel's stream are RUNS, decided from the
    table alone in the program around the kernel (a few vector
    operations over ``(B, maxp)``, where the kernel's scalar core would
    spend about 130 ns a group on the same comparisons: as long as 7 of
    the 8 descriptors they save; chip run, PR 49): for every group of
    ``min(_COPIES_A_TRIP, bp)`` table entries, flattened ``(B x
    groups,)`` int32, the page its ONE copy starts from where the
    entries the slot holds of it (``need``) name neighbouring pages
    ``first, first + 1, ...`` and a whole group's pages from ``first``
    lie inside the pool of ``num_pages``; -1 where the group is copied
    a page at a time.  ``xp`` is ``jax.numpy``, or ``numpy`` where
    :func:`stream_copies` counts the same on the host."""
    if xp is None:
        import jax.numpy as xp

    b, maxp = tables.shape
    trip = min(_COPIES_A_TRIP, bp)
    groups = _groups(maxp, trip)
    ent = tables.astype(xp.int32)
    if groups * trip > maxp:
        # past the table's width: its last entry again, as the kernel
        # reads
        ent = xp.pad(ent, ((0, 0), (0, groups * trip - maxp)), mode="edge")
    ent = ent.reshape(b, groups, trip)
    first = ent[:, :, 0]
    at = xp.arange(groups, dtype=xp.int32) * trip - need.astype(
        xp.int32)[:, None]            # entry ``j`` is held where at + j < 0
    run = first + trip <= num_pages
    for j in range(1, trip):
        run = run & ((ent[:, :, j] == first + j) | (at + j >= 0))
    return xp.where(run, first, -1).reshape(-1)


def _page_stream(tables, need, starts, layer, ring, streams, bp: int,
                 maxp: int):
    """What the kernels do about their pages, inside the kernel body
    (one grid step a slot): the blocks of ``bp`` pages of all slots, in
    order, are one stream through a ring of buffers: all but one
    block's copies are in flight while one is contracted, across the
    slots' edges.  ``tables`` (flattened, ``maxp`` a slot), ``need``
    (the pages a slot must read), ``starts`` and ``layer`` are scalar
    prefetch;
    ``streams`` is one ``(pool, buffers, semaphores)`` a pool read: the
    pool in HBM, read at ``[layer, page]``, a ring of buffers ``(n,
    bp, P, row)`` and a DMA semaphore a buffer; ``ring`` four SMEM
    ints: [0] slot and [1] block the next copies are for, [2] blocks
    issued, [3] blocks contracted.

    **Of a block only the pages the slot needs are copied, a group of
    ``_COPIES_A_TRIP`` at a time** (:func:`_groups`): every block but a
    slot's last is whole, the last is cut to the groups that hold its
    ``need``.  The rest of that buffer keeps what it held: the rows of
    an earlier block (of any slot), or the zeros the ring is filled
    with at the first grid step.  Both are finite, and the caller masks
    every position past the slot's length, so they meet probability
    exactly 0 and add exactly 0: a block contracted whole gives the
    bits it would give with the whole block copied.

    **A group that is a run is ONE copy**: ``starts`` (scalar prefetch
    too; :func:`_run_starts`, from the table alone) holds for every
    group of a slot's table the page its copy starts from, where the
    entries the slot holds of it are ``first, first + 1, ...`` and
    ``first + _COPIES_A_TRIP`` pages lie inside the pool: the scalar
    core reads that one number and starts one descriptor of
    ``_COPIES_A_TRIP`` pages a pool from ``[layer, first]``; under a -1
    one a page from the table's entries, as before PR 49.  Either way
    the group's bytes are the same and so is the wait (a DMA semaphore
    counts bytes).  Behind the pages a slot holds of its last group a
    run's copy reads the pool as it lies, where a copy a page reads
    what the table names there (page 0): finite and masked both.

    Returns ``(blocks, next_block)``: the blocks of this grid step's
    slot, and a function of the slot's block index that puts one more
    block's copies under way (before the very first block: the whole
    ring's), waits for that block's bytes (one wait a group and pool)
    and returns the index of the buffer that holds it."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    unroll = min(_COPIES_A_TRIP, bp)
    assert bp % unroll == 0, bp
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    nbuf = streams[0][1].shape[0]
    lyr = layer[0]

    def blocks_of(slot):
        return (need[slot] + bp - 1) // bp

    def groups_of(slot, blk):
        return _groups(jnp.minimum(need[slot] - blk * bp, bp), unroll)

    def issue():
        slot, blk = ring[0], ring[1]

        @pl.when(slot < nslots)
        def _():
            half = ring[2] % nbuf

            def group(g, c):
                j0 = g * unroll
                first = starts[slot * _groups(maxp, unroll)
                               + blk * (bp // unroll) + g]

                def run():
                    for pool, buf, sems in streams:
                        pltpu.make_async_copy(
                            pool.at[lyr, pl.ds(first, unroll)],
                            buf.at[half, pl.ds(j0, unroll)],
                            sems.at[half]).start()

                def pages():
                    for j in range(unroll):
                        # past the slot's last page, in its last
                        # group: what the table names there (page 0,
                        # finite by the cache's contract; past the
                        # table's width, its last entry again)
                        pg = tables[slot * maxp + jnp.minimum(
                            blk * bp + j0 + j, maxp - 1)]
                        for pool, buf, sems in streams:
                            pltpu.make_async_copy(pool.at[lyr, pg],
                                                  buf.at[half, j0 + j],
                                                  sems.at[half]).start()

                if unroll > streams[0][0].shape[1]:
                    pages()     # a pool smaller than a run holds none
                else:
                    lax.cond(first >= 0, run, pages)
                return c

            lax.fori_loop(0, groups_of(slot, blk), group, 0)
            last = blk + 1 >= blocks_of(slot)
            ring[0] = jnp.where(last, slot + 1, slot)
            ring[1] = jnp.where(last, 0, blk + 1)
            ring[2] = ring[2] + 1

    @pl.when(b == 0)
    def _():
        for k in range(4):
            ring[k] = 0
        # fast memory starts as anything: 0 x NaN would be NaN
        for _, buf, _ in streams:
            buf[...] = jnp.zeros(buf.shape, buf.dtype)

    def next_block(blk):
        def more(_, c):
            issue()
            return c

        lax.fori_loop(0, jnp.where(ring[2] == 0, nbuf, 1), more, 0)
        half = ring[3] % nbuf
        ring[3] = ring[3] + 1

        def arrived(_, c):
            for pool, buf, sems in streams:
                pltpu.make_async_copy(pool.at[lyr, pl.ds(0, unroll)],
                                      buf.at[half, pl.ds(0, unroll)],
                                      sems.at[half]).wait()
            return c

        lax.fori_loop(0, groups_of(b, blk), arrived, 0)
        return half

    return blocks_of(b), next_block


def _fold(carry, s, rows, lanes):
    """One block into a running float32 online softmax ``(m, l, acc)``:
    ``s`` the block's masked float32 scores (query rows x positions),
    the mix over lanes ``lanes`` of the block's ``rows``."""
    import jax.numpy as jnp

    m, l, acc = carry
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # fully-masked-so-far rows keep m=-inf; shift 0 avoids NaN
    shift = jnp.where(m_new == -jnp.inf, 0.0, m_new)
    pr = jnp.exp(s - shift)
    alpha = jnp.exp(m - shift)
    mix = jnp.dot(pr.astype(rows.dtype), rows[:, lanes],
                  preferred_element_type=jnp.float32)
    return (m_new, l * alpha + jnp.sum(pr, axis=-1, keepdims=True),
            acc * alpha + mix)


def _fold_start(rows: int, width: int):
    """:func:`_fold`'s ``(m, l, acc)`` before the first block."""
    import jax.numpy as jnp

    return (jnp.full((rows, 1), -jnp.inf, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, width), jnp.float32))


# --------------------------------------------------------------------------
# per-head K/V rows, query rows that share a key head — the kernel
# --------------------------------------------------------------------------


def _grouped_kernel(bp: int, page: int, maxp: int, hkv: int, d: int,
                    per_row: bool):
    """The kernel body of :func:`_grouped_program` for blocks of ``bp``
    pages of ``page`` rows of ``hkv`` heads of ``d`` lanes and a table
    ``maxp`` wide.  A block of K pages and the same pages of V arrive
    together (:func:`_page_stream`); per key head, its query rows meet
    its lanes of the K rows, then of the V rows.  ``per_row``: every
    query row masks by a length of its own (an input beside the
    queries, as :func:`_latent_kernel`'s) and not by the slot's one
    prefetched length; nothing else differs."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    rows_blk = bp * page

    def kernel(tables, need, starts, *refs):
        if per_row:
            (layer, q_ref, lens, kpool, vpool, o_ref,
             kbuf, vbuf, ksems, vsems, ring) = refs
        else:
            (lens, layer, q_ref, kpool, vpool, o_ref,
             kbuf, vbuf, ksems, vsems, ring) = refs
        nblk, next_block = _page_stream(
            tables, need, starts, layer, ring,
            ((kpool, kbuf, ksems), (vpool, vbuf, vsems)), bp, maxp)
        # a length a query row, (R, 1) beside the queries; or the slot's
        # one, a prefetched scalar
        length = lens[0] if per_row else lens[pl.program_id(0)]
        qs = [q_ref[0, j] for j in range(hkv)]         # (R, Dh) each
        r = qs[0].shape[0]

        def block(i, carry):
            half = next_block(i)
            krows = kbuf[half].reshape(rows_blk, hkv * d)
            vrows = vbuf[half].reshape(rows_blk, hkv * d)
            live = i * rows_blk + lax.broadcasted_iota(
                jnp.int32, (r, rows_blk), 1) <= length
            out = []
            for j in range(hkv):
                lanes = slice(j * d, (j + 1) * d)
                s = lax.dot_general(qs[j], krows[:, lanes],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
                out.append(_fold(carry[j], jnp.where(live, s, -jnp.inf),
                                 vrows, lanes))
            return tuple(out)

        heads = lax.fori_loop(0, nblk, block,
                              tuple(_fold_start(r, d) for _ in range(hkv)))
        for j, (_, l, acc) in enumerate(heads):
            o_ref[0, j] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    return kernel


def _single_kernel(bp: int, page: int, maxp: int, hkv: int, d: int):
    """:func:`_grouped_kernel` for ONE query row a key head and one
    length a slot.  Head by head that is ``hkv`` products of one row a
    block, each of which loads its head's lanes of the block into the
    matrix unit for a single row; here the ``hkv`` queries are laid
    block-diagonally, ``(hkv, hkv x d)`` with query ``j`` in the lanes
    of its head and exact zeros elsewhere, and a block is ONE ``(hkv,
    row) x (row, positions)`` product for the scores and ONE ``(hkv,
    positions) x (positions, row)`` for the mix, of which head ``j``
    keeps its own ``d`` lanes (the same products and the same float32
    sums as a dot a head: :func:`_head_scores`, :func:`_head_mix`).
    The stream, the mask and the fold are the grouped kernel's."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    rows_blk, row = bp * page, hkv * d

    def kernel(tables, need, starts, lens, layer, q_ref, kpool, vpool,
               o_ref, kbuf, vbuf, ksems, vsems, ring):
        nblk, next_block = _page_stream(
            tables, need, starts, layer, ring,
            ((kpool, kbuf, ksems), (vpool, vbuf, vsems)), bp, maxp)
        length = lens[pl.program_id(0)]
        head = lax.broadcasted_iota(jnp.int32, (hkv, row), 0) * d
        lane = lax.broadcasted_iota(jnp.int32, (hkv, row), 1)
        own = (lane >= head) & (lane < head + d)
        qs = q_ref[0]                                  # (hkv, d)
        qmat = jnp.where(own, jnp.concatenate([qs] * hkv, axis=1),
                         jnp.zeros((), qs.dtype))

        def block(i, carry):
            half = next_block(i)
            s = lax.dot_general(qmat, kbuf[half].reshape(rows_blk, row),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            live = i * rows_blk + lax.broadcasted_iota(
                jnp.int32, (hkv, rows_blk), 1) <= length
            return _fold(carry, jnp.where(live, s, -jnp.inf),
                         vbuf[half].reshape(rows_blk, row), slice(None))

        _, l, acc = lax.fori_loop(0, nblk, block, _fold_start(hkv, row))
        out = jnp.where(own, acc / jnp.maximum(l, 1e-30), 0.0)
        o_ref[0] = jnp.sum(out, axis=0, keepdims=True).astype(o_ref.dtype)

    return kernel


@functools.lru_cache(maxsize=None)
def _grouped_program(scale: float, interpret: bool):
    """The jitted call of the grouped kernel for one ``scale``, the
    layer an argument (as :func:`_latent_program`): ``q`` ``(B, S, H,
    Dh)``, both pools stacked, ``lengths`` ``(B,)`` -> ``(B, S, H,
    Dh)`` in ``q``'s dtype.

    One grid step a slot.  Tables, the pages each slot needs
    (``length // P + 1``, clipped to the table's width), lengths and
    ``layer`` are scalar prefetch; both pools stay in HBM.  Key head
    ``j``'s query rows are its ``H / H_kv`` query heads at each of the
    ``S`` positions, ``(B, H_kv, S x H / H_kv, Dh)``.  The pages a
    block are taken from the shapes (:func:`_block_pages`), so a table
    cut to the used-page bucket changes no bit."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(q, kpool, vpool, tables, lengths, layer):
        b, maxp = tables.shape
        _, s, h, d = q.shape
        p, row = kpool.shape[-2:]
        hkv = row // d
        r = s * h // hkv
        bp = _block_pages(p, row, kpool.dtype.itemsize, hkv * r)
        qs = (q.astype(jnp.float32) * scale).astype(kpool.dtype)
        qs = qs.reshape(b, s, hkv, h // hkv, d).transpose(0, 2, 1, 3, 4) \
            .reshape(b, hkv, r, d)
        lens = lengths.astype(jnp.int32)
        per_row = lens.ndim == 2
        if per_row:
            # a position's length for each of its query heads, in the
            # order of a key head's query rows; the pages a slot must
            # read reach its longest position
            scalars = (layer,)
            rows = [jnp.repeat(lens, h // hkv, axis=1)[:, :, None]]
            row_specs = [pl.BlockSpec((1, r, 1), lambda i, *_: (i, 0, 0))]
            lens = jnp.max(lens, axis=1)
        else:
            scalars, rows, row_specs = (lens, layer), [], []
        need = jnp.clip(lens // p + 1, 1, maxp)
        flat = (tables.reshape(-1).astype(jnp.int32), need,
                _run_starts(tables, need, bp, kpool.shape[1]))
        buffers = pltpu.VMEM((_BUFFERS, bp, p, row), kpool.dtype)
        sems = pltpu.SemaphoreType.DMA((_BUFFERS,))
        scratch = [buffers, buffers, sems, sems, pltpu.SMEM((4,), jnp.int32)]
        if r == 1 and not per_row:
            # one query row a key head: queries (B, H_kv, Dh) in, the
            # heads' mixes side by side (B, 1, H_kv x Dh) out
            return pl.pallas_call(
                _single_kernel(bp, p, maxp, hkv, d),
                out_shape=jax.ShapeDtypeStruct((b, 1, row), q.dtype),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=5,
                    grid=(b,),
                    in_specs=[
                        pl.BlockSpec((1, hkv, d), lambda i, *_: (i, 0, 0)),
                        pl.BlockSpec(memory_space=pl.ANY),
                        pl.BlockSpec(memory_space=pl.ANY),
                    ],
                    out_specs=pl.BlockSpec((1, 1, row),
                                           lambda i, *_: (i, 0, 0)),
                    scratch_shapes=scratch),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary",)),
                interpret=interpret,
                name="single_decode_attention",
            )(*flat, *scalars, qs.reshape(b, hkv, d), kpool,
              vpool).reshape(q.shape)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(scalars),
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hkv, r, d), lambda i, *_: (i, 0, 0, 0)),
                *row_specs,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, hkv, r, d),
                                   lambda i, *_: (i, 0, 0, 0)),
            scratch_shapes=scratch)
        out = pl.pallas_call(
            _grouped_kernel(bp, p, maxp, hkv, d, per_row),
            out_shape=jax.ShapeDtypeStruct((b, hkv, r, d), q.dtype),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="grouped_decode_attention",
        )(*flat, *scalars, qs, *rows, kpool, vpool)
        return out.reshape(b, hkv, s, h // hkv, d).transpose(0, 2, 1, 3, 4) \
            .reshape(q.shape)

    return jax.jit(call)


# --------------------------------------------------------------------------
# latent — multi-query attention over one shared compressed row a token
# --------------------------------------------------------------------------


def _latent_kernel(bp: int, page: int, maxp: int, vw: int):
    """The kernel body of :func:`latent_decode_attention` for blocks of
    ``bp`` pages of ``page`` rows, a table ``maxp`` wide and a mix over
    the rows' first ``vw`` lanes.  One grid step a slot; the slot's
    blocks arrive through :func:`_page_stream`."""
    import jax.numpy as jnp
    from jax import lax

    rows_blk = bp * page

    def kernel(tables, need, starts, layer, q_ref, len_ref, pool, o_ref,
               buf, sems, ring):
        nblk, next_block = _page_stream(tables, need, starts, layer, ring,
                                        ((pool, buf, sems),), bp, maxp)
        qs = q_ref[0]                                  # (H, R)
        lens = len_ref[0]                              # (H, 1)
        h = qs.shape[0]

        def block(i, carry):
            rows = buf[next_block(i)].reshape(rows_blk, buf.shape[-1])
            s = lax.dot_general(qs, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            pos = i * rows_blk + lax.broadcasted_iota(
                jnp.int32, (h, rows_blk), 1)
            return _fold(carry, jnp.where(pos <= lens, s, -jnp.inf), rows,
                         slice(0, vw))

        _, l, acc = lax.fori_loop(0, nblk, block, _fold_start(h, vw))
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
    a time, up to the slot's OWN length (the ``length // P + 1`` pages
    of its longest query, rounded up to a group of 8, a group of
    neighbouring pages in ONE copy: the last group's pages past the
    slot's are page 0 or the slot's pages' neighbours, finite by the
    cache's contract, and masked; :func:`_page_stream`) — not the
    table's width, and with no gathered copy in HBM — the next blocks'
    copies (at a slot's end: the next slot's first) in flight while
    this one is contracted.  It is multi-query attention with ``H``
    query heads on one row: a block is one ``(H, R) x (R, rows)`` and
    one ``(H, rows) x (rows, value_width)`` product on the MXU with
    float32 accumulation, operands in the pool's dtype (``q`` is scaled in
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
            num_scalar_prefetch=4,
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
        )(tables.reshape(-1).astype(jnp.int32), need,
          _run_starts(tables, need, bp, pool.shape[1]), layer, qs,
          lens[:, :, None], pool)

    return jax.jit(call)


__all__ = ["paged_decode_attention", "latent_decode_attention",
           "used_page_bucket", "decode_hbm_bytes", "stream_copies",
           "stream_rows_copied"]
