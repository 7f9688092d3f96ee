#!/usr/bin/env python
"""The two decode-attention kernels alone on the chip, at the shapes of
the four cells that run them (PR 38): what ``ops/decode_attention.py``
``_page_stream``'s granule (``_COPIES_A_TRIP``) was read from.

    chiprun --chips 1 -- python scripts/decode_stream_probe.py
    python scripts/decode_stream_probe.py --rehearse-cpu      # toy sizes
    ... --tree .scratch/parent        # another checkout's kernels

For each cell (slots, the pool's row, the query rows a slot, pages of
16, a table of 128 pages) it draws every slot's length the way the
cell's closed-loop mix leaves them in a steady step (a pair of the
mix's multiset, a position inside its answer), hands every slot the
pages its length reaches (page 0 past them; ``--layout``: in ``runs``
of 8 neighbouring pages, as the cache's allocator hands them out since
PR 49 and the stream copies with one descriptor, or ``scattered`` a
page at a time, as a free list left them before; both by default) and
times the kernel as the model calls it, over ``--layers`` layers of a stacked
pool in one jitted call, ``--inner`` calls in flight: the median and
the least of ``--calls`` readings, per kernel call.  Beside the time:
the rows the stream copies over the rows the slots hold
(``stream_rows_copied``), the pages a copy descriptor
(``stream_copies``) and the held rows' bytes over the time as a share
of the chip's memory rate.

``--granules`` times the stream at other groups of pages a trip of its
copy loop (``0``: a block whole, as before PR 38); ``--without copies``
/ ``--without products`` time the kernel with one half taken out (no
page is copied and the blocks contract what the buffers hold; every
page is copied and awaited and a block's contraction is one page
read), as PR 31 did by hand: what each half would take alone.

Writes ``chiprun_out/decode_stream/probe.<tree>.json`` and prints one
line a timing.  Exit 2 unless the backend is a TPU (or ``--rehearse-cpu``).
Nothing a cell runs imports this file.
"""

from __future__ import annotations

import argparse
import inspect
import itertools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: cell -> kernel, slots, the pool's row (values), query heads, positions
#: (grouped) or queries (latent) a slot, key heads, the traffic mix
CELLS = {
    "longcat_flash_long_gen": ("latent", 128, 640, 64, 1, 1,
                               "long_gen_closed128"),
    "joyai_flash_draft_gen": ("latent", 256, 640, 32, 2, 1,
                              "long_gen_closed256"),
    # 8 positions: a step forwards the pending tail beside the block
    "sdar_moe_block_gen": ("grouped", 128, 512, 32, 8, 4,
                           "long_gen_closed128"),
    "zaya1_cca_long_gen": ("grouped", 256, 256, 8, 1, 2,
                           "long_gen_closed256"),
}
TOY = {
    "toy_latent": ("latent", 4, 640, 4, 2, 1, "long_gen_closed128"),
    "toy_grouped": ("grouped", 4, 256, 4, 1, 2, "long_gen_closed128"),
}
PAGE, MAXP, VALUE_WIDTH = 16, 128, 512


def steady_lengths(rng, mix, slots, cap):
    """A length a slot as the mix's closed loop leaves them in a steady
    step: a pair of the multiset, a position inside its answer (a
    request passes every length from its prompt's to its last, a step
    each).  ``pos <= length`` attends."""
    from benchmarks.lib.traffic import multiset

    pairs = multiset(mix)
    out = []
    for i in rng.integers(len(pairs), size=slots):
        prompt, new = pairs[int(i)]
        out.append(min(prompt + int(rng.integers(new)), cap))
    return out


def page_tables(rng, lengths, pool_pages, layout, run):
    """The pages each slot's length reaches, page 0 past them.
    ``scattered``: drawn a page at a time without order from the pool
    (what a free list popped a page at a time leaves after a churn);
    ``runs``: every group of ``run`` table entries names ``run``
    neighbouring pages, the runs drawn without order (what
    ``serving/cache.py`` hands out since PR 49: a slot's last run is
    its own whole, only its head is in the table)."""
    import numpy as np

    tables = np.zeros((len(lengths), MAXP), np.int32)
    if layout == "scattered":
        free = rng.permutation(np.arange(1, pool_pages))
    else:
        heads = 1 + run * rng.permutation((pool_pages - 1) // run)
        free = (heads[:, None] + np.arange(run)).ravel()
    at = 0
    for i, ln in enumerate(lengths):
        n = ln // PAGE + 1
        tables[i, :n] = free[at:at + n]
        at += n if layout == "scattered" else -(-n // run) * run
    return tables


def time_calls(program, args, inner, calls):
    """Milliseconds a call of ``program`` traced and compiled anew
    (``jax.jit`` of the same function would hand back the trace it has,
    whatever ``_COPIES_A_TRIP`` has become since), ``inner`` calls in
    flight a reading."""
    import jax

    fn = jax.jit(lambda *a: program(*a))
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(inner)])
        out.append((time.perf_counter() - t0) * 1e3 / inner)
    return out


def without_copies(D):
    """``_page_stream`` with no copy and no wait: the buffers are
    zeroed once and every block contracts what they hold."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def stream(tables, need, *rest):
        # (before PR 49 the stream was handed no ``starts``)
        ring, streams, bp, maxp = rest[-4:]
        b = pl.program_id(0)

        @pl.when(b == 0)
        def _():
            for _, buf, _ in streams:
                buf[...] = jnp.zeros(buf.shape, buf.dtype)

        nbuf = streams[0][1].shape[0]
        return (need[b] + bp - 1) // bp, lambda blk: blk % nbuf

    D._page_stream = stream


def without_products(D):
    """Both kernel bodies with a block's contraction cut down to one
    read of its first page: the stream's copies and waits alone."""
    import jax.numpy as jnp
    from jax import lax

    def first_pages(nblk, next_block, bufs):
        # before PR 38 the stream's ``next_block`` took no block index
        indexed = bool(inspect.signature(next_block).parameters)

        def block(i, acc):
            half = next_block(i) if indexed else next_block()
            return acc + sum(buf[half, 0].astype(jnp.float32)
                             for buf in bufs)

        zero = jnp.zeros(bufs[0].shape[-2:], jnp.float32)
        return lax.fori_loop(0, nblk, block, zero)[0, 0]

    # the scalar operands before the queries: tables, need, (since
    # PR 49) starts, then the lengths (grouped) and the layer
    def grouped(bp, page, maxp, hkv, d, per_row=False):
        def kernel(*refs):
            (*scalars, _lens, layer, q_ref, kpool, vpool, o_ref,
             kbuf, vbuf, ksems, vsems, ring) = refs
            nblk, next_block = D._page_stream(
                *scalars, layer, ring,
                ((kpool, kbuf, ksems), (vpool, vbuf, vsems)), bp, maxp)
            o_ref[...] = jnp.full(
                o_ref.shape, first_pages(nblk, next_block, (kbuf, vbuf)),
                o_ref.dtype)

        return kernel

    def latent(bp, page, maxp, vw):
        def kernel(*refs):
            *scalars, layer, q_ref, len_ref, pool, o_ref, buf, sems, ring \
                = refs
            nblk, next_block = D._page_stream(
                *scalars, layer, ring, ((pool, buf, sems),), bp, maxp)
            o_ref[...] = jnp.full(
                o_ref.shape, first_pages(nblk, next_block, (buf,)),
                o_ref.dtype)

        return kernel

    D._grouped_kernel, D._latent_kernel = grouped, latent


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--cells", default="")
    ap.add_argument("--granules", default="",
                    help="comma list of pages a trip; 0 = a block whole; "
                    "default: the tree's own")
    ap.add_argument("--without", choices=("copies", "products"))
    ap.add_argument("--layout", default="runs,scattered",
                    help="comma list of runs, scattered: how a slot's "
                    "pages lie in the pool")
    ap.add_argument("--tree", default=ROOT,
                    help="the checkout whose kernels are timed")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--inner", type=int, default=8)
    ap.add_argument("--calls", type=int, default=12)
    ap.add_argument("--seed", type=int, default=38)
    ap.add_argument("--out", default="chiprun_out/decode_stream")
    a = ap.parse_args(argv)

    sys.path.insert(0, ROOT)              # the mixes and the peaks
    sys.path.insert(0, os.path.abspath(a.tree))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib.peaks import peaks_for
    from bigdl_tpu.ops import decode_attention as D

    dev = jax.devices()[0]
    print(f"platform {dev.platform} kind {dev.device_kind} count "
          f"{jax.device_count()} tree {os.path.dirname(D.__file__)}",
          flush=True)
    if dev.platform != "tpu" and not a.rehearse_cpu:
        print("no TPU: a time from this backend is nobody's", flush=True)
        return 2
    rate = peaks_for(dev.device_kind)["hbm_bytes_per_s"] \
        if dev.platform == "tpu" else None
    cells = TOY if a.rehearse_cpu else CELLS
    if a.cells:
        cells = {c: cells[c] for c in a.cells.split(",")}
    layers, inner, calls = (2, 1, 2) if a.rehearse_cpu \
        else (a.layers, a.inner, a.calls)
    if a.without:
        {"copies": without_copies, "products": without_products}[
            a.without](D)
    trip = D._COPIES_A_TRIP
    granules = [int(g) for g in a.granules.split(",")] if a.granules \
        else [trip]
    rng = np.random.default_rng(a.seed)
    dt = jnp.bfloat16
    results = []

    for name, (kind, slots, row, heads, per, hkv, mix) in cells.items():
        with open(os.path.join(ROOT, "benchmarks", "traffic",
                               mix + ".json")) as f:
            lengths = steady_lengths(rng, json.load(f), slots,
                                     MAXP * PAGE - 1 - per)
        pool_pages = 1 + slots * MAXP
        lens = jnp.asarray(lengths, jnp.int32)
        query_rows = heads * per
        key = jax.random.PRNGKey(a.seed)
        pool_shape = (layers, pool_pages, PAGE, row)
        pools = [jax.random.normal(jax.random.fold_in(key, i), pool_shape,
                                   dt)
                 for i in range(2 if kind == "grouped" else 1)]
        if kind == "grouped":
            d = row // hkv
            shape = (slots, per, heads, d) if per > 1 else (slots, heads, d)
            q = jax.random.normal(key, shape, dt)
            # a block attends up to its last row
            top = reach = lens + per - 1

            def program(q, lens, tables, kp, vp):
                return sum(D.paged_decode_attention(
                    q, kp, vp, tables, lens, page_size=PAGE, layer=i)
                    .astype(jnp.float32) for i in range(layers))
        else:
            q = jax.random.normal(key, (slots, heads * per, row), dt)
            # a verified draft's second query attends one row more
            top = lens[:, None] + jnp.repeat(jnp.arange(per), heads)[None]
            reach = top.max(axis=1)

            def program(q, lens, tables, pool):
                return sum(D.latent_decode_attention(
                    q, pool, tables, lens, scale=0.1,
                    value_width=VALUE_WIDTH, layer=i)
                    for i in range(layers))
        reach = np.asarray(reach)
        held = int((reach + 1).sum())
        held_bytes = held * row * 2 * len(pools)

        for layout, granule in itertools.product(a.layout.split(","),
                                                 granules):
            D._COPIES_A_TRIP = granule or 1 << 20
            for prog in (D._grouped_program, D._latent_program):
                prog.cache_clear()
            bp = D._block_pages(PAGE, row, 2, query_rows)
            tables = page_tables(rng, reach, pool_pages, layout,
                                 min(trip, bp))
            count = getattr(D, "stream_rows_copied", None)
            if a.without == "copies":
                copied = None
            elif count:
                copied = count(reach, PAGE, MAXP, row, 2, query_rows)
            else:   # a tree from before PR 38: every block whole
                copied = int((-(-(reach // PAGE + 1) // bp) * bp * PAGE)
                             .sum())
            # (a tree from before PR 49 starts a descriptor a page)
            count = getattr(D, "stream_copies", None)
            copies = count and copied and count(
                tables, reach, PAGE, pool_pages, row, 2, query_rows)
            times = [t / layers for t in time_calls(
                program, (q, top, jnp.asarray(tables), *pools), inner,
                calls)]
            med, least = statistics.median(times), min(times)
            rec = dict(cell=name, kernel=kind, slots=slots, row=row,
                       query_rows=query_rows, block_pages=bp,
                       granule=min(granule or bp, bp), layout=layout,
                       without=a.without, rows_held=held,
                       rows_copied=copied,
                       copied_over_held=copied and copied / held,
                       pages_a_copy=copies and copied / PAGE / copies,
                       ms_a_call=med, ms_least=least,
                       memory_rate_share=rate and held_bytes
                       / (med * 1e-3) / rate)
            results.append(rec)
            print(json.dumps(rec), flush=True)
        D._COPIES_A_TRIP = trip
        del pools

    os.makedirs(a.out, exist_ok=True)
    tag = os.path.basename(os.path.abspath(a.tree)) \
        + (f".without_{a.without}" if a.without else "")
    with open(os.path.join(a.out, f"probe.{tag}.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
