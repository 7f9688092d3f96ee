"""Paged KV cache — the memory substrate of continuous batching.

``TransformerLM.generate`` keeps one contiguous ``(B, H, T_total, Dh)``
cache per layer, sized for the *longest possible* sequence and owned by
the whole batch for the whole decode — a request that finishes early
keeps its columns hot until the slowest batchmate drains.  Serving
needs the vLLM-style alternative: K/V live in fixed-size **pages**
(``page_size`` token rows of ``kv_heads * Dh``), each request owns only
the pages its tokens actually fill (a per-slot **page table**), pages
return to the pool the moment a request completes, and a new
request is admitted into the freed slot at the next step boundary.

Layout (one array per K and V, all layers stacked so the decode step
carries two device buffers instead of 2·L; a model whose cache has no
separate V — latent attention, ``nn/latent.py`` — states ``buffers=1``
and its row's width, and gets ``kp`` alone).  It is written down once,
here — :func:`pool_shape`, :func:`write_token_rows`,
:func:`write_prompt_pages`, :func:`gather_pages` — and every cache in
the repo (the engine's, the tests', the smokes') is built,
written and read through them:

* ``kp``/``vp``: ``(n_layer, num_pages, page_size, row)`` device
  arrays, ``row`` the width the model states (``kv_heads * head_dim``
  for per-head K/V, ``kv_heads`` the key/value heads: as many as query
  heads in ``models/transformer.py``, 4 under 32 query heads in
  ``models/sdar_moe.py``; ``kv_rank + rope`` for a latent cache;
  ``n_layer`` counts cached attentions, two a layer in
  ``models/longcat_flash.py``) in the cache dtype (defaults to the model dtype — bf16
  weights get a bf16 cache, halving decode HBM traffic).  The layout is
  **token-major**: one row is one token's K (or V) for all heads, as
  the projection produces it, so a page is ``page_size`` whole rows.
  On the TPU that is the shape the chip works on as it lies — the
  ``kv_heads * head_dim`` lanes are full (1600 of 1664 for GPT-2 XL), a
  page's 16 rows fill a bf16 tile — so the buffer the engine donates
  to the decode step and the prefill is the buffer those programs
  update in place: no layout conversion in or out, no padded twin
  (heads-as-sublanes, ``(.., n_head, page_size, head_dim)``, cost four
  whole-cache copies a step and a working copy 2.56x the cache);
* page table: ``(max_slots, max_pages_per_slot)`` int32, host-owned and
  shipped to the device per step (a few hundred bytes);
* page 0 is a reserved **trash page**: unallocated table entries and
  the padded tail of a bucketed prefill write there, and the decode
  mask (``position <= length``) guarantees it is never read.

**State that is not keys and values** lives here too, beside the pages
(a model's ``state_spec``: ``serving/steps.py`` ``OneToken`` says what
the engine asks).  Some layers need more than a token's own rows, in one of
three ways.  **A bounded past**: to form a token's rows, a few rows of the
slot's PREVIOUS token (``models/zaya.py``: two causal convolutions and a
shifted value; 5.4 KB a slot and layer).  **The whole past**: a running
state that every token decays and adds to (``models/falcon_h1.py``: a
state-space mixer's ``H``, 32 x 256 x 128 float32, 4.19 MB a slot and
layer, and its convolution's last 3 rows).  **The whole past, read
before it is written**: a matrix a head whose transition is not diagonal
(``models/ling_flash.py``: a delta-rule linear attention's ``S``, 32 x
128 x 128 float32, 2.10 MB a slot and layer: every token reads the
decayed state along its key, writes a rank-1 correction, reads again
along its query; kept only by the layers that mix so, 6 of that model's
7, while the seventh keeps pages and no state: ``state_spec``'s
``layers`` and ``cache_spec``'s count different layers;
``models/olmo_hybrid.py``: the same rule under a decay a head, 30 heads
of 96 x 192 kept ``(96, 5760)``, 2.21 MB a slot and layer, in 3 layers
of 4).  None is a
function of the token alone, so no page holds it.  It is one array a declared shape,
``(layers, max_slots, *shape)``, indexed by SLOT (not by page: a slot
has exactly one, whatever its length), handed to the step and the
prefill and taken back with the pools (:meth:`PagedKVCache.buffers`),
written by :func:`write_slot_state` (a prefill: the whole of one slot's,
so nothing of the slot's previous occupant survives) and, in a step,
left alone for a slot that did not run: by :func:`keep_inactive` (a
``where`` over the state the model handed back), or by the model's own
update where the model says so (``state_spec``'s ``keeps_inactive``).
**What a step costs** is the state of the slots that run, once in and
once out: 14 MB for ZAYA1's 256 slots, where one more pass to guard it
shows nowhere; 4.3 GB for Falcon-H1's 128, as much as the page pools
hold and more than the layers' weights, where the guard's pass would be
a third of the step's floor: there the update itself leaves an idle
slot bit for bit (``dt = 0``) and runs in place (``ops/ssm_state.py``);
6.9 GB for Ling's 256, half of everything its step moves
(``ops/delta_state.py``, ``beta = 0`` and ``g = 0``).  **One declared
shape is one buffer, and a buffer may not pass 2 GiB**
(:data:`STATE_BUFFER_BYTES`): Ling's ``S`` as ONE array of 6 layers x
256 slots x 32 heads (3.0 GiB) served wrong tokens on the chip and as
two arrays of 16 heads serves right ones (``PERF.md`` section 6, PR 44;
Falcon-H1's 2.0 GiB is the largest that is known to work), so a model
whose state is larger declares it in parts and the constructor refuses
a larger buffer.  **What counts is what the device holds**
(:func:`state_buffer_bytes`): lanes in tiles of 128 and rows in sublane
groups, so 3 layers x 256 slots x 30 heads of 96 x 192 float32 are 1.58
GiB of values and 2.11 GiB as ``(.., 30, 96, 192)``, refused, while the
same values as ``(.., 96, 30 x 192)`` (``models/olmo_hybrid.py``: 45
whole lane tiles a row) are 1.58 GiB on the device too.
**Nothing is snapshotted**: a preempted request's second prefill
rebuilds its state from its tokens.  That is exact for a bounded past,
and for the whole past it is the prefill's scan over prompt + generated
prefix: the state a step-by-step run would hold, to rounding
(``tests/test_falcon_h1.py`` bounds it).  It holds because a prefill is
never cut into chunks (``LMEngine._bucket`` goes to ``max_len``); a
prefill in chunks would need the state at a chunk's start kept.

The allocator is plain host Python and numpy — which pages are free,
how many, how many of each **run** of ``PAGE_RUN`` = 8 pages (pages 1-8,
9-16, ...), and per-slot page lists.  Pages that are neighbours in the
pool are one contiguous piece of a layer's HBM, and the decode kernels'
page stream copies a group of 8 table entries that name neighbours with
ONE descriptor where it starts one a page for any other (``ops/
decode_attention.py`` ``_page_stream``: the descriptors, not the bytes,
bound those kernels; PERF.md section 6, PR 49).  So a slot is handed
its pages in runs, **by preference and never by reservation**: a prompt
takes wholly free runs, lowest first, and its last pages from the head
of one more; decode grows a slot one page at a time as its length
crosses a page boundary, by the page behind its last while its table's
group of 8 is open, else the head of the lowest wholly free run, else
the lowest free page.  What comes out is that table entries ``8k .. 8k
+ 7`` of a slot name neighbouring pages: always under the engine's
default pool (every slot's longest context: a wholly free run then
exists whenever a slot starts a group), as far as the pool allows under
a tighter one.  **How many pages are free, who is admitted, when
``grow`` fails are what a free list gives for the same calls**: no page
is held back for a slot that has not asked for it, a tight pool loses
runs and never capacity (``tests/test_paged_cache_runs.py`` holds the
two side by side).  Exhaustion is surfaced to the engine, which
preempts the youngest request (its pages return to the pool, the
request re-queues with its generated prefix as prompt) — the standard
paged-attention answer to overcommit.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np
from bigdl_tpu.obs import names
# the pages of a run: what the decode kernels' stream copies with one
# descriptor, defined there
from bigdl_tpu.ops.decode_attention import _COPIES_A_TRIP as PAGE_RUN


#: the largest buffer of slot state the cache builds (module docstring)
STATE_BUFFER_BYTES = 1 << 31


def state_buffer_bytes(layers: int, slots: int, shape, itemsize: int) -> int:
    """Bytes of the ``(layers, slots, *shape)`` buffer of one declared
    shape AS THE DEVICE LAYS IT OUT: the minor dimension in whole tiles
    of 128 lanes and the one before it in whole sublane groups (8 rows
    of 4 bytes, 16 of 2), so a float32 ``(.., 96, 192)`` counts 256
    lanes a row, a third more than its values; a ``ValueError`` over
    :data:`STATE_BUFFER_BYTES`, which says both counts."""
    dims = (int(layers), int(slots)) + tuple(int(n) for n in shape)
    rows = 8 * max(1, 4 // int(itemsize))
    laid = dims[:-2] + (-(-dims[-2] // rows) * rows, -(-dims[-1] // 128) * 128)
    nbytes = int(np.prod(laid, dtype=np.int64)) * int(itemsize)
    if nbytes > STATE_BUFFER_BYTES:
        values = int(np.prod(dims, dtype=np.int64)) * int(itemsize)
        raise ValueError(
            f"a slot-state buffer of {layers} layers x {slots} slots x "
            f"{tuple(shape)} is {nbytes / 2 ** 30:.2f} GiB as the device "
            f"lays it out ({values / 2 ** 30:.2f} GiB of values; lanes in "
            "tiles of 128, rows in sublane groups): over 2 GiB the served "
            "tokens came out wrong on the chip (PERF.md section 6, PR 44); "
            "the model declares such a state in a shape that is dense in "
            "lanes, or in parts (state_spec's shapes: one buffer a shape)")
    return nbytes


class PagedKVCache:
    """Host-side page allocator + device-side paged K/V buffers."""

    def __init__(self, n_layer: int, kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None, *,
                 row_width: Optional[int] = None, buffers: int = 2,
                 page_size: int = 16, num_pages: int = 64,
                 max_slots: int = 8, max_len: int = 256,
                 dtype=None, state_spec: Optional[dict] = None):
        import jax.numpy as jnp

        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if row_width is None:
            if kv_heads is None or head_dim is None:
                raise ValueError("give row_width, or kv_heads and head_dim")
            row_width = int(kv_heads) * int(head_dim)
        if buffers not in (1, 2):
            raise ValueError(f"buffers must be 1 or 2, got {buffers}")
        self.n_layer = int(n_layer)
        #: key/value heads a cached row holds (the query heads may be a
        #: multiple of them)
        self.kv_heads = None if kv_heads is None else int(kv_heads)
        self.head_dim = None if head_dim is None else int(head_dim)
        self.row_width = int(row_width)
        self.page_size = int(page_size)
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        # every slot must be able to address a full-length sequence
        self.max_pages_per_slot = -(-self.max_len // self.page_size)
        # +1: page 0 is the reserved trash page, never allocated
        self.num_pages = max(int(num_pages), 2)
        self.dtype = jnp.dtype(dtype) if dtype is not None else jnp.float32
        shape = pool_shape(self.num_pages, self.page_size, 1,
                           self.row_width, n_layer=self.n_layer)
        self.kp = jnp.zeros(shape, self.dtype)
        # a model without a separate V has one buffer, not a second of
        # zeros
        self.vp = jnp.zeros(shape, self.dtype) if buffers == 2 else None
        #: what a slot carries a layer beside its pages (module
        #: docstring): one ``(layers, max_slots, *shape)`` array a shape
        #: of the model's ``state_spec`` (``layers``, ``shapes``,
        #: ``dtype``), none for a model without one
        spec = state_spec or {"layers": 0, "shapes": ()}
        for shp in spec["shapes"]:
            state_buffer_bytes(
                spec["layers"], self.max_slots, shp,
                jnp.dtype(spec.get("dtype") or self.dtype).itemsize)
        self.state = tuple(
            jnp.zeros((int(spec["layers"]), self.max_slots)
                      + tuple(int(n) for n in shp),
                      spec.get("dtype") or self.dtype)
            for shp in spec["shapes"])
        self.page_tables = np.zeros(
            (self.max_slots, self.max_pages_per_slot), np.int32)
        self.lengths = np.zeros((self.max_slots,), np.int32)
        # the allocator's books (module docstring): which pages are
        # free, how many, and how many of each run of PAGE_RUN pages
        # (run r: pages 1 + r x PAGE_RUN and the PAGE_RUN - 1 behind
        # it; the pool's last pages, fewer than a run, are in none)
        self._is_free = np.ones((self.num_pages,), bool)
        self._is_free[0] = False
        self._n_free = self.num_pages - 1
        self._run_free = np.full(((self.num_pages - 1) // PAGE_RUN,),
                                 PAGE_RUN, np.int64)
        self._slot_pages: List[List[int]] = [[] for _ in
                                             range(self.max_slots)]
        from bigdl_tpu import obs

        self._pages_gauge = obs.get_registry().gauge(
            names.SERVE_KV_PAGES_IN_USE,
            "KV-cache pages currently owned by in-flight requests")

    # --------------------------------------------------------- allocator
    def pages_for(self, n_tokens: int) -> int:
        return max(1, -(-int(n_tokens) // self.page_size))

    def free_pages(self) -> int:
        return self._n_free

    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - self._n_free

    def can_admit(self, n_tokens: int) -> bool:
        return self._n_free >= self.pages_for(n_tokens)

    def _mark(self, pages, free: bool):
        """``pages`` leave the pool, or come back to it."""
        pages = np.asarray(pages, np.int64)
        self._is_free[pages] = free
        runs = (pages - 1) // PAGE_RUN
        step = 1 if free else -1
        np.add.at(self._run_free, runs[runs < len(self._run_free)], step)
        self._n_free += step * len(pages)
        self._pages_gauge.set(float(self.pages_in_use()))

    def alloc(self, slot: int, n_tokens: int) -> List[int]:
        """Give ``slot`` enough pages for ``n_tokens``; returns the page
        ids (raises on exhaustion — the engine checks ``can_admit``
        first and preempts on decode-time growth failure).  Wholly free
        runs, lowest first, the last pages from the head of one more;
        where those run out, the lowest free pages."""
        need = self.pages_for(n_tokens)
        if self._n_free < need:
            raise RuntimeError(
                f"KV cache exhausted: need {need} pages, "
                f"{self._n_free} free")
        whole = np.flatnonzero(self._run_free == PAGE_RUN)
        taken = (1 + whole[:-(-need // PAGE_RUN), None] * PAGE_RUN
                 + np.arange(PAGE_RUN)).ravel()[:need]
        self._mark(taken, False)
        if len(taken) < need:
            rest = np.flatnonzero(self._is_free)[:need - len(taken)]
            self._mark(rest, False)
            taken = np.concatenate([taken, rest])
        pages = [int(pg) for pg in taken]
        self._slot_pages[slot] = pages
        row = np.zeros((self.max_pages_per_slot,), np.int32)
        row[:need] = pages
        self.page_tables[slot] = row
        self.lengths[slot] = 0
        return pages

    def grow(self, slot: int) -> bool:
        """One more page for ``slot`` (its length is about to cross a
        page boundary).  False on exhaustion — the engine preempts.
        The page behind the slot's last, while the table's group of
        PAGE_RUN entries it falls in is open and that page is free;
        else the head of the lowest wholly free run; else the lowest
        free page."""
        if not self._n_free:
            return False
        pages = self._slot_pages[slot]
        if len(pages) >= self.max_pages_per_slot:
            return False
        page = pages[-1] + 1 if len(pages) % PAGE_RUN else self.num_pages
        if page >= self.num_pages or not self._is_free[page]:
            whole = np.flatnonzero(self._run_free == PAGE_RUN)
            page = 1 + int(whole[0]) * PAGE_RUN if len(whole) \
                else int(np.argmax(self._is_free))
        self._mark([page], False)
        pages.append(page)
        self.page_tables[slot, len(pages) - 1] = page
        return True

    def needs_growth(self, slot: int, ahead: int = 0) -> bool:
        """True when the next token's position (or the one ``ahead``
        of it: a step that writes more than one row) lands past the
        slot's allocated pages."""
        return ((int(self.lengths[slot]) + int(ahead)) // self.page_size
                >= len(self._slot_pages[slot]))

    def release(self, slot: int):
        """Request finished (or preempted): pages back to the pool, the
        table row points at the trash page again."""
        self._mark(self._slot_pages[slot], True)
        self._slot_pages[slot] = []
        self.page_tables[slot] = 0
        self.lengths[slot] = 0

    def withhold(self, n: int) -> List[int]:
        """Take the pool's ``n`` highest free pages out of it for no
        slot (how a test makes a built engine's pool tight); returns
        them for :meth:`hand_back`."""
        pages = np.flatnonzero(self._is_free)[self._n_free - int(n):]
        self._mark(pages, False)
        return [int(pg) for pg in pages]

    def hand_back(self, pages: List[int]):
        """Withheld ``pages`` are free again."""
        self._mark(pages, True)

    def slot_pages(self, slot: int) -> List[int]:
        return list(self._slot_pages[slot])

    # ------------------------------------------------------ device state
    def pools(self) -> tuple:
        """The page pools: K, or K and V."""
        return (self.kp,) if self.vp is None else (self.kp, self.vp)

    def buffers(self) -> tuple:
        """The device buffers a step takes and returns, in order: the
        page pools, then the slots' state (none for most models)."""
        return self.pools() + self.state

    def set_buffers(self, bufs):
        self.kp = bufs[0]
        n = len(self.pools())
        if n > 1:
            self.vp = bufs[1]
        self.state = tuple(bufs[n:])

    def state_bytes_per_slot(self) -> int:
        """Bytes of state a slot carries over all its layers."""
        return sum(s.dtype.itemsize * s.size // self.max_slots
                   for s in self.state)

    def device_tables(self, pages: Optional[int] = None):
        """(page_tables, lengths) as jnp arrays for the next step.

        ``pages`` slices the table to its first N columns — the
        engine's used-page prefix bucket (ops/decode_attention.py
        ``used_page_bucket``), so a mostly-empty pool ships a few
        dozen bytes and the decode step never gathers the unallocated
        tail.  Entries past a slot's pages are 0 (trash) either way —
        the mask contract is unchanged.

        Both are COPIES of the host's arrays: the engine advances
        lengths and grows tables while the step that was given these is
        still in flight, and a host array put on the device may be read
        later than the call (or, on the CPU backend, be the device
        array)."""
        import jax.numpy as jnp

        tables = self.page_tables
        if pages is not None and pages < self.max_pages_per_slot:
            tables = tables[:, :int(pages)]
        return (jnp.asarray(np.array(tables)),
                jnp.asarray(np.array(self.lengths)))

    def padded_positions(self) -> int:
        """Columns of the gathered per-slot attention window."""
        return self.max_pages_per_slot * self.page_size


def pool_shape(num_pages: int, page_size: int, kv_heads: int,
               head_dim: int, n_layer: Optional[int] = None) -> tuple:
    """Shape of one layer's K (or V) page pool, ``(num_pages,
    page_size, kv_heads * head_dim)``, ``kv_heads`` the KEY/VALUE heads
    (a model with grouped heads caches fewer than it has query heads);
    with ``n_layer`` the engine's stacked ``(n_layer, ...)`` buffer.
    The one statement of the layout: everything that builds a cache
    asks here."""
    pool = (int(num_pages), int(page_size), int(kv_heads) * int(head_dim))
    return pool if n_layer is None else (int(n_layer),) + pool


def write_token_rows(pages, layer: int, tables, lengths, rows, real=None):
    """Decode write: slot ``b``'s new token row ``rows[b]`` (``(B,
    kv_heads * head_dim)``, the projection's output as it comes) lands
    at position ``lengths[b]`` of its table — exactly the one row
    ``pages[layer, page, slot_in_page, :]``; no other byte changes.
    Inactive slots (length 0, trash table row) write the trash page.

    ``rows`` ``(B, Q, row)`` are ``Q`` consecutive tokens a slot, at
    positions ``lengths[b] + 0 .. Q-1`` (a step that verifies a draft
    writes two, a step that refines a block its block's), still one
    scatter; the engine names the page of the last of them before it
    dispatches the step.  ``real`` ``(B, Q)`` marks the rows that are
    tokens: the others are padding (``lengths[b]`` may be negative
    there) and are written nowhere, whatever the table says (they name
    a page past the pool's last, and a scatter drops what is out of
    bounds)."""
    import jax.numpy as jnp

    page_size = pages.shape[2]
    if rows.ndim == 2:
        page = jnp.take_along_axis(
            tables, (lengths // page_size)[:, None], axis=1)[:, 0]
        pos = lengths
    else:
        pos = lengths[:, None] + jnp.arange(rows.shape[1],
                                            dtype=lengths.dtype)
        if real is not None:
            pos = jnp.where(real, pos, 0)
        page = jnp.take_along_axis(tables, pos // page_size, axis=1)
        if real is not None:
            page = jnp.where(real, page, pages.shape[1])
    return pages.at[layer, page, pos % page_size, :].set(
        rows.astype(pages.dtype))


def write_prompt_pages(pages, layer: int, page_ids, rows):
    """Prefill write: ``rows`` (``(T, kv_heads * head_dim)``, ``T`` a
    multiple of the page size) fills the pages ``page_ids`` (``(T //
    page_size,)``) in order, as ONE scatter.  Never a loop of per-page
    updates: on the TPU that makes the compiler convert the whole
    cache to another layout and back (four whole-cache copies)."""
    n = page_ids.shape[0]
    return pages.at[layer, page_ids].set(
        rows.reshape(n, pages.shape[2], pages.shape[3])
        .astype(pages.dtype))


def write_slot_state(state, slot, rows):
    """A prefill's write: ``rows`` (one ``(layers, *shape)`` array a
    declared shape: the state after the prompt's last REAL token) become
    the whole state of ``slot`` (a traced scalar): every layer of it, so
    nothing of the slot's previous occupant is left."""
    return tuple(s.at[:, slot].set(r.astype(s.dtype))
                 for s, r in zip(state, rows))


def keep_inactive(new, old, active):
    """A step's guard: the state ``new`` where ``active`` (slots,), what
    the slot had (``old``) where it did not run."""
    import jax.numpy as jnp

    return tuple(
        jnp.where(active.reshape((1, -1) + (1,) * (n.ndim - 2)), n, o)
        for n, o in zip(new, old))


def gather_pages(pages, page_table, layer: Optional[int] = None):
    """The pages a ``(B, maxp)`` table names, as per-slot contiguous
    token rows ``(B, maxp*P, H*Dh)``: position ``t`` of slot ``b`` is
    row ``[b, t]``, head ``h`` its lanes ``[h*Dh, (h+1)*Dh)``
    (positions past a slot's length are trash and must be masked by
    the caller).  ``pages`` is one layer's pool, or with ``layer`` the
    stacked buffer — the layer then rides in the same gather as the
    page ids, so no per-layer slice of the cache is ever materialised
    (``pages[layer][table]`` costs a copy of the layer's pool per call
    on the TPU)."""
    g = pages[page_table] if layer is None else pages[layer, page_table]
    return g.reshape(g.shape[0], g.shape[1] * g.shape[2], g.shape[3])


__all__ = ["PagedKVCache", "STATE_BUFFER_BYTES", "gather_pages",
           "keep_inactive", "pool_shape", "state_buffer_bytes",
           "write_prompt_pages", "write_slot_state", "write_token_rows"]
