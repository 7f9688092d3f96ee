"""ops/decode_attention.py — decode attention over the paged cache:
two bodies, one a cache kind.

The load-bearing contracts:

* ``paged_decode_attention`` (per-head K/V rows) and
  ``latent_decode_attention`` (one shared compressed row a token) each
  agree with an independent float64 numpy oracle across ragged
  lengths, lengths around every page edge, a live slot of length 0,
  full slots and arbitrary page-table permutations — on a layer's own
  pool and on the engine's stacked buffer with ``layer=``;
* ``paged_decode_attention`` takes its path by the shapes of ``q`` and
  the pool alone: one query row a key head is the gather (no kernel in
  a ``TransformerLM`` step, its products counted), rows that share a
  key head are the page-walking kernel (one a layer of a grouped
  model's block step, no gather of a pool), which gives the oracle's
  answer for 1 and 4 positions a slot, 2 and 8 query heads a key head,
  float32 and bfloat16 pools, over several blocks of pages a slot and
  at their edges;
* one query row a key head over a pool too large to gather (by the
  pool's shape alone) goes through the page stream too, by a body that
  lays the queries block-diagonally: it gives the oracle's answer and
  the gather's at 6 and 30 heads of 128 lanes, and a pool of GPT-2 XL's
  size, or rows that are no whole lane tiles, stay the gather;
* ``lengths`` of rank 2 gives every position of a slot a length of its
  own (a position that attends nothing comes out zero); one length a
  slot builds the program it built before (operations counted on the
  parent), and ``_block_pages`` answers as it did for every count of
  query rows up to 128;
* the latent kernel gives the oracle's answer over several blocks of
  pages a slot, with one and two queries a slot (a length each), in
  float32 and bfloat16, at rows 640 lanes wide;
* the trash page is never READ into an output: arbitrary finite
  garbage in page 0 (and, in the latent kernel, in the rows past a
  slot's length) changes no live slot's result, and NaNs in pages no
  table names change no bit;
* a table sliced to the used-page bucket gives the full table's
  output to the last bit (what lets the engine slice every step);
* the kernels' page stream copies what a slot's length needs, a group
  of 8 pages at a time: NaNs in the pages a table names past a slot's
  last needed group change no bit, the outputs are those of the stream
  that copied every block whole (kept here as the second witness), and
  ``stream_rows_copied`` counts what the kernel copies;
* the ``int8_mm`` auto-tuner site: golden key, never-lose, the
  measured prewarm cycle persists.
"""

import contextlib
import json

import numpy as np
import pytest

import jax.numpy as jnp

from bigdl_tpu.ops import autotune
from bigdl_tpu.ops import decode_attention as D
from bigdl_tpu.ops.decode_attention import (decode_hbm_bytes,
                                            latent_decode_attention,
                                            paged_decode_attention,
                                            stream_rows_copied,
                                            used_page_bucket)
from bigdl_tpu.serving.cache import pool_shape


@pytest.fixture(autouse=True)
def _tuner_off_by_default(monkeypatch):
    monkeypatch.delenv("BIGDL_TUNER", raising=False)
    monkeypatch.delenv("BIGDL_TUNER_CACHE", raising=False)
    monkeypatch.delenv("BIGDL_TUNER_MEASURE", raising=False)
    autotune.reset()
    yield
    autotune.reset()


@pytest.fixture
def tuner(tmp_path, monkeypatch):
    cache = tmp_path / "tuner.json"
    monkeypatch.setenv("BIGDL_TUNER", "1")
    monkeypatch.setenv("BIGDL_TUNER_CACHE", str(cache))
    autotune.reset()
    yield cache
    autotune.reset()


P, MAXP = 8, 8
# lengths a slot (``pos <= length`` attends); None is a released slot:
# length 0 and a table row of trash pages
LENGTHS = {
    "ragged": [None, P - 1, P, 3 * P - 1],
    "page_edges": [k * P + e for k in (1, 2, 5) for e in (-1, 0, 1)],
    "length_zero": [0, None, 5, 0],
    "all_full": [MAXP * P - 1] * 4,
}


def _tables(lengths, p, maxp, pool, rs):
    """A permuted page table: a live slot owns the pages its length
    reaches, every other entry points at the trash page."""
    tbl = np.zeros((len(lengths), maxp), np.int32)
    free = list(range(1, pool))
    rs.shuffle(free)
    for i, ln in enumerate(lengths):
        for j in range(0 if ln is None else ln // p + 1):
            tbl[i, j] = free.pop()
    lens = np.asarray([ln or 0 for ln in lengths], np.int32)
    return jnp.asarray(tbl), jnp.asarray(lens)


def _stream_walk(tbl, lengths, p, bp, num_pages):
    """What the kernels' stream does for slots that attend ``pos <=
    lengths`` over the table ``tbl``, walked the slow way: ``(pages,
    copies)``, the set of pages it copies and the descriptors it
    starts a pool.  A group of ``trip`` entries whose held ones name
    neighbouring pages, a whole group's pages from the first inside
    the pool, is one copy of those ``trip`` pages as they lie; any
    other group a copy an entry (past the table's width: its last
    entry again)."""
    trip = min(D._COPIES_A_TRIP, bp)
    tbl = np.asarray(tbl)
    maxp = tbl.shape[1]
    pages, copies = set(), 0
    for row, ln in zip(tbl, np.asarray(lengths)):
        need = min(max(int(ln), 0) // p + 1, maxp)
        for at in range(0, need, trip):
            ent = [int(row[min(at + j, maxp - 1)]) for j in range(trip)]
            held = min(need - at, trip)
            if ent[0] + trip <= num_pages and all(
                    ent[j] == ent[0] + j for j in range(held)):
                pages.update(range(ent[0], ent[0] + trip))
                copies += 1
            else:
                pages.update(ent)
                copies += trip
    return pages, copies


def _never_copied(tbl, lengths, p, bp, num_pages):
    """A mask over the pool: the pages the stream copies for no slot."""
    pages, _ = _stream_walk(tbl, lengths, p, bp, num_pages)
    out = np.ones(num_pages, bool)
    out[sorted(pages)] = False
    return out


def _state(lengths=LENGTHS["ragged"], h=4, d=16, p=P, maxp=MAXP, seed=0):
    """Random paged K/V state under ``lengths``."""
    rs = np.random.RandomState(seed)
    b = len(lengths)
    pool = 1 + b * maxp
    q = jnp.asarray(rs.randn(b, h, d).astype(np.float32))
    shape = pool_shape(pool, p, h, d)
    kp = jnp.asarray(rs.randn(*shape).astype(np.float32))
    vp = jnp.asarray(rs.randn(*shape).astype(np.float32))
    return (q, kp, vp, *_tables(lengths, p, maxp, pool, rs))


def _latent_state(lengths=LENGTHS["ragged"], h=4, r=24, p=P, maxp=MAXP,
                  seed=0):
    """Random latent rows (one buffer, ``r`` lanes a token) under
    ``lengths``."""
    rs = np.random.RandomState(seed)
    b = len(lengths)
    pool = 1 + b * maxp
    q = jnp.asarray(rs.randn(b, h, r).astype(np.float32))
    pages = jnp.asarray(rs.randn(pool, p, r).astype(np.float32))
    return (q, pages, *_tables(lengths, p, maxp, pool, rs))


def _stacked(pool, layer, n_layer=3):
    """The engine's stacked buffer with ``pool`` at ``layer`` and other
    values in every other layer."""
    return jnp.stack([pool if i == layer else jnp.full_like(pool, 7.0 + i)
                      for i in range(n_layer)])


def _softmax_rows(s):
    s = s - s.max(axis=-1, keepdims=True)
    pr = np.exp(s)
    return pr / pr.sum(axis=-1, keepdims=True)


def _numpy_reference(q, kp, vp, tables, lengths, p):
    """Independent numpy oracle (float64 softmax over the masked
    gathered window)."""
    q, kp, vp = (np.asarray(x, np.float64) for x in (q, kp, vp))
    tables, lengths = np.asarray(tables), np.asarray(lengths)
    b, h, d = q.shape
    maxp = tables.shape[1]
    out = np.zeros((b, h, d))
    scale = d ** -0.5
    for i in range(b):
        # token-major pages: (P, H*Dh) rows -> (maxp*P, H, Dh)
        k = np.concatenate([kp[tables[i, j]] for j in range(maxp)],
                           axis=0).reshape(maxp * p, h, d)
        v = np.concatenate([vp[tables[i, j]] for j in range(maxp)],
                           axis=0).reshape(maxp * p, h, d)
        n = int(lengths[i]) + 1
        pr = _softmax_rows(np.einsum("hd,khd->hk", q[i], k[:n]) * scale)
        out[i] = np.einsum("hk,khd->hd", pr, v[:n])
    return out


def _latent_reference(q, pages, tables, lengths, scale, vw):
    """The same oracle for a latent cache: every head scores the whole
    shared row and mixes its first ``vw`` lanes."""
    q, pages = np.asarray(q, np.float64), np.asarray(pages, np.float64)
    tables, lengths = np.asarray(tables), np.asarray(lengths)
    b, h, _ = q.shape
    out = np.zeros((b, h, vw))
    for i in range(b):
        rows = np.concatenate([pages[j] for j in tables[i]], axis=0)
        n = int(lengths[i]) + 1
        pr = _softmax_rows(q[i] @ rows[:n].T * scale)
        out[i] = pr @ rows[:n, :vw]
    return out


class TestPagedDecodeParity:
    @pytest.mark.parametrize("layout", ["pool", "stacked"])
    @pytest.mark.parametrize("case", sorted(LENGTHS))
    def test_dense_matches_numpy_oracle(self, case, layout):
        q, kp, vp, tbl, lens = _state(LENGTHS[case])
        want = _numpy_reference(q, kp, vp, tbl, lens, P)
        if layout == "stacked":  # read in place at layer 1 of 3
            got = paged_decode_attention(
                q, _stacked(kp, 1), _stacked(vp, 1), tbl, lens,
                page_size=P, layer=1)
        else:
            got = paged_decode_attention(q, kp, vp, tbl, lens,
                                         page_size=P)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)

    @pytest.mark.parametrize("layout", ["pool", "stacked"])
    def test_trash_page_never_read(self, layout):
        """Finite garbage in page 0 (the reserved trash page) must not
        change any live slot's output — the `pos <= length` mask
        contract."""
        q, kp, vp, tbl, lens = _state()
        kw = {}
        if layout == "stacked":  # what the engine hands the body
            kp, vp, kw = _stacked(kp, 1), _stacked(vp, 1), {"layer": 1}
        clean = paged_decode_attention(q, kp, vp, tbl, lens,
                                       page_size=P, **kw)
        trash = (slice(None), 0) if layout == "stacked" else 0
        dirty = paged_decode_attention(
            q, kp.at[trash].set(1e30), vp.at[trash].set(1e30), tbl,
            lens, page_size=P, **kw)
        live = np.asarray(lens) > 0
        np.testing.assert_array_equal(np.asarray(dirty)[live],
                                      np.asarray(clean)[live])
        assert np.isfinite(np.asarray(dirty)[live]).all()


def _grouped_state(lengths, s, g, hkv=2, d=128, p=32, maxp=40, seed=0,
                   dtype="float32"):
    """Random paged K/V state for ``s`` positions a slot and ``g`` query
    heads a key head: ``q`` (B, s, hkv*g, d) over pools of ``hkv`` heads
    of ``d`` lanes."""
    rs = np.random.RandomState(seed)
    b = len(lengths)
    pool = 1 + b * maxp
    q = jnp.asarray(rs.randn(b, s, hkv * g, d), dtype)
    shape = pool_shape(pool, p, hkv, d)
    kp = jnp.asarray(rs.randn(*shape), dtype)
    vp = jnp.asarray(rs.randn(*shape), dtype)
    return (q, kp, vp, *_tables(lengths, p, maxp, pool, rs))


def _grouped_reference(q, kp, vp, tables, lengths, hkv):
    """Float64 oracle for query rows that share a key head: query head
    ``h`` of every position reads key head ``h // (H / hkv)``, all
    under the slot's one length, or each position under its own
    (``lengths`` (B, S); -1 attends nothing and gives zeros).  The
    contract's operands: ``q`` scaled
    in float32 and cast to the pools' dtype, the probabilities cast to
    the pools' dtype before the mix."""
    dtype = kp.dtype
    b, s, h, d = q.shape
    qs = np.asarray((q.astype(jnp.float32) * d ** -0.5).astype(dtype),
                    np.float64)
    kp, vp = np.asarray(kp, np.float64), np.asarray(vp, np.float64)
    tables, lengths = np.asarray(tables), np.asarray(lengths)
    out = np.zeros((b, s, h, d))
    per_position = np.broadcast_to(
        lengths[:, None] if lengths.ndim == 1 else lengths, (b, s))
    for i in range(b):
        rows_k = np.concatenate(kp[tables[i]], axis=0)
        rows_v = np.concatenate(vp[tables[i]], axis=0)
        for t in range(s):
            n = int(per_position[i, t]) + 1
            if n <= 0:
                continue                # attends nothing: zeros
            k = rows_k[:n].reshape(n, hkv, d)
            v = rows_v[:n].reshape(n, hkv, d)
            for head in range(h):
                j = head // (h // hkv)
                pr = _softmax_rows(qs[i, t:t + 1, head] @ k[:, j].T)
                out[i, t, head] = (pr @ v[:, j])[0]
    return out


class TestGroupedDecodeParity:
    """The kernel path of ``paged_decode_attention``: query rows that
    share a key head.  Rows of 256 lanes in pages of 32, 40 pages a
    slot: the kernel takes its block from the shapes (16 pages in
    float32, 32 in bfloat16), so a full slot is three or two blocks,
    the last of them partly past the slot's pages."""
    P, MAXP, HKV = 32, 40, 2

    def _lengths(self, s, g, dtype):
        """0, a full slot, a block's last row, the row one past it, a
        page's last row, inside a page, a released slot."""
        bp = D._block_pages(self.P, self.HKV * 128,
                            jnp.dtype(dtype).itemsize, self.HKV * s * g)
        assert 8 <= bp < self.MAXP
        edge = bp * self.P
        return [0, self.MAXP * self.P - 1, edge - 1, edge, 3 * self.P - 1,
                edge + self.P + 3, None]

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("layout", ["pool", "stacked"])
    @pytest.mark.parametrize("g", [2, 8])
    @pytest.mark.parametrize("s", [1, 4])
    def test_kernel_matches_float64_oracle(self, s, g, layout, dtype):
        lengths = self._lengths(s, g, dtype)
        q, kp, vp, tbl, lens = _grouped_state(lengths, s, g, dtype=dtype,
                                              seed=s + g)
        want = _grouped_reference(q, kp, vp, tbl, lens, self.HKV)
        kw = {}
        if layout == "stacked":     # read in place at layer 1 of 3
            kp, vp, kw = _stacked(kp, 1), _stacked(vp, 1), {"layer": 1}
        got = paged_decode_attention(q if s > 1 else q[:, 0], kp, vp, tbl,
                                     lens, page_size=self.P, **kw)
        assert got.dtype == q.dtype
        assert got.shape == (q.shape if s > 1 else q[:, 0].shape)
        np.testing.assert_allclose(
            np.asarray(got, np.float64).reshape(want.shape), want,
            atol=2e-5 if dtype == "float32" else 2e-2)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_five_query_rows_a_key_head_match_the_oracle(self, dtype):
        """The hybrid cell's shape (``models/falcon_h1.py``): 20 query
        heads over 4 key heads of 128, rows of 512 lanes in pages of
        16, 128 pages a slot.  5 query rows a key head is no multiple
        of the 8 sublanes; bfloat16 blocks are 40 pages.  Lengths at a
        copy group's edge (8 pages), a block's, and the table's end."""
        p, maxp, hkv, g = 16, 128, 4, 5
        bp = D._block_pages(p, hkv * 128, jnp.dtype(dtype).itemsize,
                            hkv * g)
        assert bp == (40 if dtype == "bfloat16" else 16)
        lengths = [0, 8 * p - 1, 8 * p, bp * p - 1, bp * p,
                   maxp * p - 1, None, 2 * bp * p + 5]
        q, kp, vp, tbl, lens = _grouped_state(
            lengths, 1, g, hkv=hkv, p=p, maxp=maxp, dtype=dtype, seed=11)
        want = _grouped_reference(q, kp, vp, tbl, lens, hkv)
        got = paged_decode_attention(q[:, 0], _stacked(kp, 1),
                                     _stacked(vp, 1), tbl, lens,
                                     page_size=p, layer=1)
        assert got.shape == (len(lengths), hkv * g, 128)
        assert got.dtype == q.dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float64).reshape(want.shape), want,
            atol=2e-5 if dtype == "float32" else 2e-2)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_trash_page_and_unwritten_tail_never_reach_an_output(
            self, dtype):
        """Huge finite values in page 0 (what unallocated table entries
        name, and what the last block of a slot is filled up with) and
        in the rows past each slot's length change no bit of a live
        slot's output, in K or in V."""
        lengths = self._lengths(4, 8, dtype)
        q, kp, vp, tbl, lens = _grouped_state(lengths, 4, 8, dtype=dtype,
                                              seed=7)
        clean = np.asarray(paged_decode_attention(
            q, kp, vp, tbl, lens, page_size=self.P), np.float32)
        dk, dv = np.array(kp, np.float32), np.array(vp, np.float32)
        dk[0], dv[0] = 1e30, -1e30
        for i, ln in enumerate(lengths):
            if ln is not None:
                last = int(tbl[i, ln // self.P])
                dk[last, ln % self.P + 1:] = -1e30
                dv[last, ln % self.P + 1:] = 1e30
        got = np.asarray(paged_decode_attention(
            q, jnp.asarray(dk, dtype), jnp.asarray(dv, dtype), tbl, lens,
            page_size=self.P), np.float32)
        live = np.asarray([ln is not None for ln in lengths])
        np.testing.assert_array_equal(got[live], clean[live])
        assert np.isfinite(got[live]).all()

    def test_pages_no_table_names_are_never_read(self):
        """NaNs in every page no table entry names change no bit: the
        kernel copies the pages a slot's table names and nothing else
        (but the neighbours behind the pages a slot holds of its last
        group where those are a run, PR 49: one copy of 8 pages as they
        lie, masked; ``_stream_walk`` says which)."""
        lengths = [0, 35 * self.P, 17 * self.P + 3, 5]
        q, kp, vp, tbl, lens = _grouped_state(lengths, 4, 2, seed=9)
        clean = np.asarray(paged_decode_attention(
            q, kp, vp, tbl, lens, page_size=self.P))
        named = np.zeros(kp.shape[0], bool)
        named[np.asarray(tbl).ravel()] = True
        bp = D._block_pages(self.P, kp.shape[-1], 4, 2 * 4 * 2)
        named |= ~_never_copied(tbl, lens, self.P, bp, kp.shape[0])
        assert (~named).sum() > 50
        poison = lambda pool: jnp.where(named[:, None, None], pool, jnp.nan)
        got = np.asarray(paged_decode_attention(
            q, poison(kp), poison(vp), tbl, lens, page_size=self.P))
        np.testing.assert_array_equal(got, clean)

    def test_a_block_of_positions_is_its_positions_one_at_a_time(self):
        """The slot's one mask holds for every position of a block: the
        4-position result is four 1-position calls under the same
        ``lengths`` (what the model hands: the block's last row)."""
        lengths = self._lengths(4, 8, "float32")
        q, kp, vp, tbl, lens = _grouped_state(lengths, 4, 8, seed=5)
        whole = np.asarray(paged_decode_attention(
            q, kp, vp, tbl, lens, page_size=self.P))
        for i in range(4):
            one = np.asarray(paged_decode_attention(
                q[:, i:i + 1], kp, vp, tbl, lens, page_size=self.P))
            np.testing.assert_allclose(one[:, 0], whole[:, i], atol=1e-6)

    def test_the_scale_is_the_callers(self):
        lengths = [5, 20 * self.P]
        q, kp, vp, tbl, lens = _grouped_state(lengths, 1, 2, seed=2)
        d = q.shape[-1]
        default = paged_decode_attention(q, kp, vp, tbl, lens,
                                         page_size=self.P)
        given = paged_decode_attention(q * 2.0, kp, vp, tbl, lens,
                                       page_size=self.P,
                                       scale=0.5 * d ** -0.5)
        np.testing.assert_allclose(np.asarray(given), np.asarray(default),
                                   atol=1e-6)


class TestALengthAQueryPosition:
    """``lengths`` of rank 2, ``(B, S)``: every position of a slot
    attends up to a length of its own (a block model's step forwards
    the block that just became final beside the current one,
    ``models/sdar_moe.py``).  One kernel: what differs is the rank of
    ``lengths``, and a call with one length a slot builds the program
    it built before lengths could have a rank."""
    P, MAXP, HKV, S, G = 32, 40, 2, 8, 4

    def _lengths(self, dtype):
        """A slot each: a tail that ends a page behind a block that
        starts the next; both inside one page; a tail that attends
        NOTHING behind the slot's first block; a block that ends the
        table; a block's worth of pages exactly, and one row more; a
        tail alone past a block's edge (the current block attends
        nothing: no caller's case, the kernel's all the same)."""
        bp = D._block_pages(self.P, self.HKV * 128,
                            jnp.dtype(dtype).itemsize,
                            self.HKV * self.S * self.G)
        assert 8 <= bp < self.MAXP
        edge, half = bp * self.P, self.S // 2
        ends = [(self.P - 1, self.P + half - 1), (5, 9), (-1, 3),
                (self.MAXP * self.P - half - 1, self.MAXP * self.P - 1),
                (edge - half - 1, edge - 1), (edge - 1, edge + half - 1),
                (edge + 2, -1)]
        return np.asarray([[a] * half + [b] * half for a, b in ends])

    def _state(self, dtype, seed=0):
        per = self._lengths(dtype)
        q, kp, vp, tbl, _ = _grouped_state(
            [int(x) for x in per.max(axis=1)], self.S, self.G,
            dtype=dtype, seed=seed)
        return q, kp, vp, tbl, jnp.asarray(per, jnp.int32)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("layer", [None, 1])
    def test_every_position_attends_up_to_its_own_length(self, layer,
                                                         dtype):
        q, kp, vp, tbl, per = self._state(dtype, seed=3)
        want = _grouped_reference(q, kp, vp, tbl, per, self.HKV)
        kw = {}
        if layer is not None:
            kp, vp, kw = _stacked(kp, layer), _stacked(vp, layer), \
                {"layer": layer}
        got = np.asarray(paged_decode_attention(
            q, kp, vp, tbl, per, page_size=self.P, **kw), np.float64)
        assert got.shape == want.shape and np.isfinite(got).all()
        np.testing.assert_allclose(
            got, want, atol=2e-5 if dtype == "float32" else 2e-2)
        # a position that attends nothing: finite, and exactly zero
        nothing = np.asarray(per) < 0
        assert nothing.sum() == self.S and not got[nothing].any()

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_one_length_broadcast_is_the_rank_one_call_bit_for_bit(
            self, dtype):
        lengths = TestGroupedDecodeParity()._lengths(4, 8, dtype)
        q, kp, vp, tbl, lens = _grouped_state(lengths, 4, 8, dtype=dtype,
                                              seed=6)
        one = paged_decode_attention(q, kp, vp, tbl, lens, page_size=self.P)
        wide = paged_decode_attention(
            q, kp, vp, tbl, jnp.broadcast_to(lens[:, None], (len(lengths), 4)),
            page_size=self.P)
        np.testing.assert_array_equal(_bits(wide), _bits(one))

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_pages_past_the_longest_position_s_group_are_never_copied(
            self, dtype):
        """The stream follows the slot's LONGEST position: every table
        entry past its last needed group of 8 pages names a page of
        NaNs, and no bit changes."""
        q, kp, vp, tbl, per = self._state(dtype, seed=4)
        clean = np.asarray(paged_decode_attention(
            q, kp, vp, tbl, per, page_size=self.P))
        # a page the stream copies for no slot (not even as a last
        # group's neighbour, PR 49)
        bp = D._block_pages(self.P, kp.shape[-1], kp.dtype.itemsize,
                            q.shape[1] * q.shape[2])
        spare = int(np.flatnonzero(_never_copied(
            tbl, np.asarray(per).max(axis=1), self.P, bp, kp.shape[0]))[0])
        poisoned = np.array(tbl)
        for i, longest in enumerate(np.asarray(per).max(axis=1)):
            need = max(int(longest), 0) // self.P + 1
            poisoned[i, -(-need // 8) * 8:] = spare
        assert (poisoned == spare).sum() > 8 * len(poisoned)
        got = np.asarray(paged_decode_attention(
            q, kp.at[spare].set(jnp.nan), vp.at[spare].set(jnp.nan),
            jnp.asarray(poisoned), per, page_size=self.P))
        np.testing.assert_array_equal(_bits(got), _bits(clean))

    #: the operations of the rank-1 call's program at the shapes below,
    #: counted on the commit before lengths could have a rank (6a5aff6),
    #: and what the copy of a run of pages has added since (PR 49: 91
    #: operations, 66 of them ``_run_starts``' around the kernel; in
    #: the kernel a group's start read from its scalar operand, the
    #: choice between one copy of 8 pages a pool and 8 of one)
    PARENT_OPS = {
        "float32": 342 + 91, "bfloat16": 348 + 91, "dot_general": 4,
        "exp": 4, "dma_start": 16 + 2, "dma_wait": 2, "get": 25 + 1,
        "swap": 12, "while": 4, "cond": 2 + 1, "select_n": 16 + 1,
        "le": 1 + 1, "broadcast_in_dim": 17 + 3, "pallas_call": 1}

    @staticmethod
    def _eqns(jaxpr):
        """Every equation of ``jaxpr`` and of the programs inside it."""
        for eqn in jaxpr.eqns:
            yield eqn
            for v in eqn.params.values():
                for inner in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        yield from TestALengthAQueryPosition._eqns(inner)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_one_length_a_slot_builds_the_program_it_built(self, dtype):
        """Operation for operation: the rank-1 call's program holds the
        parent's count of every primitive (the lengths a prefetched
        scalar a slot, no input beside the queries); the rank-2 call's
        differs from it by the one input and by no product, copy or
        loop."""
        import jax

        b, s, h, d = 3, 4, 16, 128
        S = jax.ShapeDtypeStruct
        pool = S((2, 1 + b * self.MAXP, self.P, self.HKV * d), dtype)

        def program(lens):
            return jax.make_jaxpr(
                lambda *a: paged_decode_attention(
                    *a, layer=1, page_size=self.P))(
                S((b, s, h, d), dtype), pool, pool,
                S((b, self.MAXP), jnp.int32), S(lens, jnp.int32))

        import collections

        def count(lens):
            eqns = list(self._eqns(program(lens).jaxpr))
            call, = (e for e in eqns if e.primitive.name == "pallas_call")
            return collections.Counter(e.primitive.name for e in eqns), \
                len(call.invars)

        one, operands = count((b,))
        assert sum(one.values()) == self.PARENT_OPS[dtype]
        for name, n in self.PARENT_OPS.items():
            assert one.get(name, n) == n, name
        # tables, need, starts, lengths, layer; the queries; the pools
        assert operands == 8
        two, operands = count((b, s))
        assert operands == 8    # ... the lengths beside the queries
        for name in ("dot_general", "exp", "dma_start", "dma_wait", "while",
                     "cond", "swap", "pallas_call"):
            assert two[name] == one[name], name

    def test_block_pages_answers_as_it_did_up_to_128_query_rows(self):
        """The rule that sizes a kernel's block, for every count of
        query rows a slot that a kernel had before this one's doubled:
        the formula as it stood, written out."""
        for page, row, item in [(16, 512, 2), (16, 640, 2), (16, 256, 2),
                                (32, 256, 4), (32, 256, 2), (4, 16, 4),
                                (16, 512, 4)]:
            for head_rows in range(1, 129):
                by_bytes = (640 * 1024) // (page * row * item)
                positions = min(1024, (64 * 1024) // head_rows)
                bp = max(1, min(by_bytes, positions // page))
                want = bp if bp < 8 else bp - bp % 8
                assert D._block_pages(page, row, item, head_rows) == want, \
                    (page, row, item, head_rows)


class TestThePathIsChosenByTheShapes:
    """One query row a key head is the gather, op for op; rows that
    share a key head are the kernel: nothing else decides."""

    @staticmethod
    def _lowered(q, monkeypatch, hkv=4, d=16):
        import jax

        monkeypatch.setattr(jax, "default_backend", lambda: "some_new_chip")
        b = q[0]
        S = jax.ShapeDtypeStruct
        pool = S(pool_shape(1 + b * MAXP, P, hkv, d), jnp.float32)
        return jax.jit(
            lambda q, kp, vp, t, l: paged_decode_attention(
                q, kp, vp, t, l, page_size=P)).trace(
            S(q, jnp.float32), pool, pool, S((b, MAXP), jnp.int32),
            S((b,), jnp.int32)).lower(lowering_platforms=("tpu",)).as_text()

    @pytest.mark.parametrize("q", [(3, 4, 16), (3, 1, 4, 16)],
                             ids=["B_H_Dh", "B_1_H_Dh"])
    def test_one_query_row_a_key_head_is_the_gather(self, q, monkeypatch):
        text = self._lowered(q, monkeypatch)
        assert "tpu_custom_call" not in text
        assert text.count("#stablehlo.gather<") == 2        # K and V
        assert text.count("stablehlo.dot_general") == 2     # scores, mix

    @pytest.mark.parametrize("q", [(3, 8, 16), (3, 2, 4, 16), (3, 4, 32, 16)],
                             ids=["grouped_heads", "two_positions", "both"])
    def test_rows_that_share_a_key_head_are_the_kernel(self, q,
                                                       monkeypatch):
        text = self._lowered(q, monkeypatch)
        assert text.count("tpu_custom_call") == 1
        assert 'kernel_name = "grouped_decode_attention"' in text
        assert "stablehlo.gather" not in text


    @staticmethod
    def _one_row(monkeypatch, b, heads, d, pages, page=16):
        import jax

        monkeypatch.setattr(jax, "default_backend", lambda: "some_new_chip")
        S = jax.ShapeDtypeStruct
        pool = S(pool_shape(pages, page, heads, d), jnp.bfloat16)
        return jax.jit(
            lambda q, kp, vp, t, l: paged_decode_attention(
                q, kp, vp, t, l, page_size=page)).trace(
            S((b, heads, d), jnp.bfloat16), pool, pool,
            S((b, 32), jnp.int32), S((b,), jnp.int32)).lower(
            lowering_platforms=("tpu",)).as_text()

    def test_one_query_row_a_key_head_streams_a_pool_too_large_to_gather(
            self, monkeypatch):
        """A layer's pool over ``_GATHER_POOL_BYTES`` (the gathered copy
        of a full table is the pool's size again): 30 heads of 128 in
        32769 pages of 16 are 4 GB; the same heads in 2048 pages (252
        MB) are the gather, as are GPT-2 XL's 481 pages of 25 x 64 and,
        whatever the pool's size, rows that are no whole lane tiles."""
        big = self._one_row(monkeypatch, 256, 30, 128, 32769)
        assert big.count("tpu_custom_call") == 1
        assert 'kernel_name = "single_decode_attention"' in big
        assert "stablehlo.gather" not in big
        for b, heads, d, pages in [(256, 30, 128, 2048), (12, 25, 64, 481),
                                   (256, 25, 64, 65536)]:
            text = self._one_row(monkeypatch, b, heads, d, pages)
            assert "tpu_custom_call" not in text, (heads, d, pages)
            assert text.count("#stablehlo.gather<") == 2
        assert D._GATHER_POOL_BYTES == 256 << 20


class TestSingleRowStreamParity:
    """ONE query row a key head through the page stream
    (``_single_kernel``): reached here by taking the pool's size out of
    the choice.  Rows of ``hkv`` x 128 lanes in pages of 16."""
    P, MAXP = 16, 40

    @pytest.fixture(autouse=True)
    def _stream_everything(self, monkeypatch):
        monkeypatch.setattr(D, "_GATHER_POOL_BYTES", 0)

    def _lengths(self, hkv, dtype):
        bp = D._block_pages(self.P, hkv * 128, jnp.dtype(dtype).itemsize,
                            hkv)
        assert 5 <= bp < self.MAXP      # 30 heads in float32: 5 pages
        edge = bp * self.P
        return [0, self.MAXP * self.P - 1, edge - 1, edge, 3 * self.P - 1,
                edge + self.P + 3, None]

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("layout", ["pool", "stacked"])
    @pytest.mark.parametrize("hkv", [6, 30])
    def test_the_streamed_and_the_gathered_body_agree(self, hkv, layout,
                                                      dtype, monkeypatch):
        """Against the float64 oracle, and against the gather body over
        the same arguments (6 heads, and the served 30: neither a power
        of two): both masks, both softmaxes, the heads' own lanes."""
        q, kp, vp, tbl, lens = _grouped_state(
            self._lengths(hkv, dtype), 1, 1, hkv=hkv, p=self.P,
            maxp=self.MAXP, dtype=dtype, seed=hkv)
        want = _grouped_reference(q, kp, vp, tbl, lens, hkv)
        kw = {}
        if layout == "stacked":     # read in place at layer 1 of 3
            kp, vp, kw = _stacked(kp, 1), _stacked(vp, 1), {"layer": 1}
        assert D._streams(kp)
        got = paged_decode_attention(q[:, 0], kp, vp, tbl, lens,
                                     page_size=self.P, **kw)
        assert got.dtype == q.dtype and got.shape == q[:, 0].shape
        tol = 2e-5 if dtype == "float32" else 2e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float64).reshape(want.shape), want, atol=tol)
        monkeypatch.setattr(D, "_GATHER_POOL_BYTES", 1 << 40)
        assert not D._streams(kp)
        gathered = paged_decode_attention(q[:, 0], kp, vp, tbl, lens,
                                          page_size=self.P, **kw)
        live = np.asarray(lens) >= 0
        np.testing.assert_allclose(
            np.asarray(got, np.float64)[live],
            np.asarray(gathered, np.float64)[live], atol=2 * tol)
        # (B, 1, H, Dh) is the same call
        monkeypatch.setattr(D, "_GATHER_POOL_BYTES", 0)
        np.testing.assert_array_equal(
            np.asarray(paged_decode_attention(
                q, kp, vp, tbl, lens, page_size=self.P, **kw))[:, 0],
            np.asarray(got))

    def test_trash_page_and_unnamed_pages_never_reach_an_output(self):
        q, kp, vp, tbl, lens = _grouped_state(
            [5, 200, None, 17 * 16 - 1], 1, 1, hkv=6, p=self.P,
            maxp=self.MAXP, seed=2)
        clean = paged_decode_attention(q[:, 0], kp, vp, tbl, lens,
                                       page_size=self.P)
        bp = D._block_pages(self.P, kp.shape[-1], 4, 6)
        free = np.flatnonzero(_never_copied(tbl, lens, self.P, bp,
                                            kp.shape[0]))
        free = np.setdiff1d(free, np.asarray(tbl))
        assert len(free) > 50
        dirty = paged_decode_attention(
            q[:, 0], kp.at[0].set(1e30).at[free].set(np.nan),
            vp.at[0].set(1e30).at[free].set(np.nan), tbl, lens,
            page_size=self.P)
        live = np.asarray(lens) > 0
        np.testing.assert_array_equal(np.asarray(dirty)[live],
                                      np.asarray(clean)[live])

    def test_a_table_cut_to_its_bucket_changes_no_bit(self):
        lengths = [None, self.P - 1, self.P, 2 * self.P - 1]
        q, kp, vp, tbl, lens = _grouped_state(
            lengths, 1, 1, hkv=6, p=self.P, maxp=self.MAXP, seed=4)
        full, cut = (paged_decode_attention(q[:, 0], kp, vp, t, lens,
                                            page_size=self.P)
                     for t in (tbl, tbl[:, :2]))
        np.testing.assert_array_equal(np.asarray(cut), np.asarray(full))

    def test_a_wide_page_takes_a_whole_trip_of_copies(self):
        """Pages of 16 rows of 30 x 128 bfloat16 lanes are 122,880 B:
        640 KB hold 5 of them, and a block is 8 (one trip of the issue
        loop, 128 positions); a page twice as wide still fits a trip
        into twice those bytes, one four times as wide takes the 2 pages
        that do."""
        assert D._block_pages(16, 3840, 2, 30) == 8
        assert D._block_pages(16, 3840, 4, 30) == 5
        assert D._block_pages(16, 5120, 2, 40) == 8
        assert D._block_pages(16, 15360, 2, 120) == 2


class TestLatentDecodeParity:
    SCALE, VW = 0.3, 16

    def _run(self, q, pages, tbl, lens, **kw):
        return np.asarray(latent_decode_attention(
            q, pages, tbl, lens, scale=self.SCALE, value_width=self.VW,
            **kw))

    # rows as wide as the served models' (640 lanes), 80 pages a slot:
    # the kernel takes its block from the shapes (32 pages in float32,
    # 64 in bfloat16), so a full slot is three or two blocks, the last
    # of them partly past the slot's pages
    WIDE = dict(h=4, r=640, p=P, maxp=80)
    WIDE_LENGTHS = [0, 80 * P - 1, 33 * P + 3, None, 32 * P - 1, 64 * P]

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("layout", ["pool", "stacked"])
    @pytest.mark.parametrize("queries", [1, 2])
    def test_kernel_matches_numpy_oracle(self, queries, layout, dtype):
        """What the kernel does see: one or two queries a slot on the
        head axis with a length each, a layer's pool or the stacked
        buffer, float32 or bfloat16 rows; a slot of length 0 beside one
        that fills its last page, several blocks a slot."""
        vw = 512
        q, pages, tbl, lens = _latent_state(self.WIDE_LENGTHS, seed=11,
                                            **self.WIDE)
        assert D._block_pages(P, 640, jnp.dtype(dtype).itemsize, 4) < 80
        q, pages = q.astype(dtype), pages.astype(dtype)
        if queries == 2:   # heads 0-1 at the length, 2-3 one past it
            lens = jnp.minimum(lens[:, None] + jnp.asarray([0, 0, 1, 1]),
                               80 * P - 1)
        # the contract's operands: q scaled in float32, then cast to
        # the rows' dtype; everything after it in float64
        qs = (q.astype(jnp.float32) * self.SCALE).astype(dtype)
        want = np.stack([
            _latent_reference(qs[:, i:i + 1], pages, tbl,
                              lens if lens.ndim == 1 else lens[:, i],
                              1.0, vw)[:, 0]
            for i in range(4)], axis=1)
        kw = {}
        if layout == "stacked":
            pages, kw = _stacked(pages, 1), {"layer": 1}
        got = np.asarray(latent_decode_attention(
            q, pages, tbl, lens, scale=self.SCALE, value_width=vw, **kw))
        assert got.shape == (6, 4, vw) and got.dtype == np.float32
        np.testing.assert_allclose(
            got, want, atol=2e-5 if dtype == "float32" else 2e-2)

    def test_page_edges_and_length_zero_in_one_batch(self):
        lengths = LENGTHS["page_edges"] + [0, None]
        q, pages, tbl, lens = _latent_state(lengths, seed=3)
        want = _latent_reference(q, pages, tbl, lens, self.SCALE, self.VW)
        got = self._run(q, pages, tbl, lens)
        assert got.shape == (11, 4, self.VW) and got.dtype == np.float32
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_stacked_buffer_is_read_at_its_layer(self):
        q, pages, tbl, lens = _latent_state(seed=5)
        own = self._run(q, pages, tbl, lens)
        stacked = self._run(q, _stacked(pages, 2), tbl, lens, layer=2)
        np.testing.assert_array_equal(stacked, own)

    def test_trash_page_and_unwritten_tail_never_reach_an_output(self):
        """Huge finite values in page 0 (what unallocated table entries
        name) and in the rows past each slot's length change no bit of
        a live slot's output: their scores are masked before the
        softmax, and the pages of a block past the slot's last are not
        read at all."""
        lengths = [0, 80 * P - 1, 33 * P + 3, None, 5]
        q, pages, tbl, lens = _latent_state(lengths, seed=7, **self.WIDE)
        clean = np.asarray(latent_decode_attention(
            q, pages, tbl, lens, scale=self.SCALE, value_width=512))
        dirty = np.array(pages)
        dirty[0] = 1e30
        for i, ln in enumerate(lengths):
            if ln is not None:
                last = int(tbl[i, ln // P])
                dirty[last, ln % P + 1:] = -1e30
        got = np.asarray(latent_decode_attention(
            q, jnp.asarray(dirty), tbl, lens, scale=self.SCALE,
            value_width=512))
        live = np.asarray([ln is not None for ln in lengths])
        np.testing.assert_array_equal(got[live], clean[live])
        assert np.isfinite(got[live]).all()

    def test_pages_no_table_names_are_never_read(self):
        """NaNs in every page no table entry names change no bit: the
        kernel copies the pages a slot's table names up to its length
        and nothing else."""
        lengths = [0, 70 * P, 33 * P + 3, 5]
        q, pages, tbl, lens = _latent_state(lengths, seed=9, **self.WIDE)
        clean = np.asarray(latent_decode_attention(
            q, pages, tbl, lens, scale=self.SCALE, value_width=512))
        named = np.zeros(pages.shape[0], bool)
        named[np.asarray(tbl).ravel()] = True
        # (and the neighbours a last group's one copy brings along)
        bp = D._block_pages(P, self.WIDE["r"], 4, self.WIDE["h"])
        named |= ~_never_copied(tbl, lens, P, bp, pages.shape[0])
        assert (~named).sum() > 100
        poisoned = jnp.where(named[:, None, None], pages, jnp.nan)
        got = np.asarray(latent_decode_attention(
            q, poisoned, tbl, lens, scale=self.SCALE, value_width=512))
        np.testing.assert_array_equal(got, clean)


# --------------------------------------------------------------------------
# the page stream: what a slot's length needs, a group of pages at a time
# --------------------------------------------------------------------------


def _whole_block_page_stream(tables, need, _starts, layer, ring, streams,
                             bp, maxp):
    """The kernels' page stream as it was before it followed the
    slots' lengths (PR 31 to PR 37): every block is copied whole, page
    0 standing in past a slot's pages, and awaited with one wait a
    pool.  Kept as the second witness of the stream that replaced it:
    same outputs, bit for bit."""
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    unroll = min(D._COPIES_A_TRIP, bp)
    b, nslots = pl.program_id(0), pl.num_programs(0)
    nbuf = streams[0][1].shape[0]
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
                    pg = tables[slot * maxp
                                + jnp.minimum(blk * bp + j, maxp - 1)]
                    for pool, buf, sems in streams:
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

    def next_block(_blk):
        def more(_, c):
            issue()
            return c

        lax.fori_loop(0, jnp.where(ring[2] == 0, nbuf, 1), more, 0)
        half = ring[3] % nbuf
        ring[3] = ring[3] + 1
        for pool, buf, sems in streams:
            pltpu.make_async_copy(pool.at[lyr, pl.ds(0, bp)], buf.at[half],
                                  sems.at[half]).wait()
        return half

    return blocks_of(b), next_block


@contextlib.contextmanager
def _whole_blocks():
    """Both kernels over :func:`_whole_block_page_stream` (their
    programs are built anew on both sides of the swap)."""
    programs = (D._grouped_program, D._latent_program)
    stream, D._page_stream = D._page_stream, _whole_block_page_stream
    for prog in programs:
        prog.cache_clear()
    try:
        yield
    finally:
        D._page_stream = stream
        for prog in programs:
            prog.cache_clear()


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _stream_lengths(bp, p, maxp):
    """The table's last row (then a short slot: the long one's rows
    lie in both buffers when it is contracted), the last row of a
    group of 8 pages and the first of the next, a block's last row and
    the page after it, length 0, a released slot."""
    assert 8 < bp < maxp
    return [maxp * p - 1, 5, 8 * p - 1, 8 * p, bp * p - 1, bp * p + 3, 0,
            None]


def _grouped_run(s, g, dtype, lengths=None, seed=3):
    """``(run, tables, pools)`` of one grouped-kernel call:
    ``run(tables, kp, vp)`` -> the output."""
    c = TestGroupedDecodeParity
    if lengths is None:
        lengths = c()._lengths(s, g, dtype)
    q, kp, vp, tbl, lens = _grouped_state(lengths, s, g, dtype=dtype,
                                          seed=seed)

    def run(tbl, kp, vp):
        return np.asarray(paged_decode_attention(q, kp, vp, tbl, lens,
                                                 page_size=c.P))

    return run, tbl, (kp, vp)


def _latent_run(queries, dtype, lengths=None, seed=3):
    c = TestLatentDecodeParity
    wide = c.WIDE
    if lengths is None:
        lengths = c.WIDE_LENGTHS
    q, pages, tbl, lens = _latent_state(lengths, seed=seed, **wide)
    q, pages = q.astype(dtype), pages.astype(dtype)
    if queries == 2:   # heads 0-1 at the length, 2-3 one past it
        lens = jnp.minimum(lens[:, None] + jnp.asarray([0, 0, 1, 1]),
                           wide["maxp"] * wide["p"] - 1)

    def run(tbl, pages):
        return np.asarray(latent_decode_attention(
            q, pages, tbl, lens, scale=c.SCALE, value_width=512))

    return run, tbl, (pages,)


_STREAM_CASES = (
    [("grouped", s, g, dt) for s in (1, 4) for g in (2, 8)
     for dt in ("float32", "bfloat16")]
    + [("latent", qn, 0, dt) for qn in (1, 2)
       for dt in ("float32", "bfloat16")])


def _stream_case(body, a, g, dtype, **kw):
    return _grouped_run(a, g, dtype, **kw) if body == "grouped" \
        else _latent_run(a, dtype, **kw)


class TestThePageStreamFollowsTheLength:
    """Both kernels, under the interpreter (whose fast memory starts as
    NaN, like nothing a kernel may count on)."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("body", ["grouped", "latent"])
    def test_pages_past_the_last_needed_group_are_never_copied(self, body,
                                                               dtype):
        """Every table entry past a slot's last needed GROUP of 8 pages
        names a page of NaNs, in K and in V: no bit of any output
        changes.  (Copied whole, the block would bring the NaNs into
        the mix's product: 0 x NaN.)  Lengths that end a group, a block
        and the table exactly, length 0, and a short slot behind a full
        one, whose rows lie in the buffers it is contracted from."""
        c = TestGroupedDecodeParity if body == "grouped" \
            else TestLatentDecodeParity
        p, maxp = (c.P, c.MAXP) if body == "grouped" \
            else (c.WIDE["p"], c.WIDE["maxp"])
        item = jnp.dtype(dtype).itemsize
        bp = D._block_pages(p, 256, item, 4) if body == "grouped" \
            else D._block_pages(p, 640, item, 4)
        lengths = _stream_lengths(bp, p, maxp)
        run, tbl, pools = _stream_case(body, 1, 2, dtype, lengths=lengths)
        clean = run(tbl, *pools)
        assert np.isfinite(clean.astype(np.float32)).all()
        spare = int(np.flatnonzero(_never_copied(
            tbl, [ln or 0 for ln in lengths], p, bp,
            pools[0].shape[0]))[0])
        poisoned = np.array(tbl)
        for i, ln in enumerate(lengths):
            need = (ln or 0) // p + 1
            poisoned[i, -(-need // 8) * 8:] = spare
        assert (poisoned == spare).sum() > 8 * len(lengths)
        got = run(jnp.asarray(poisoned),
                  *(pool.at[spare].set(jnp.nan) for pool in pools))
        np.testing.assert_array_equal(_bits(got), _bits(clean))

    @pytest.mark.parametrize("case", _STREAM_CASES,
                             ids=["-".join(map(str, c))
                                  for c in _STREAM_CASES])
    def test_outputs_are_the_whole_block_streams_to_the_last_bit(self,
                                                                 case):
        """Over the float64 oracle's cases: what a block's buffer keeps
        past the groups that were copied (an earlier block's rows, or
        the zeros of the first grid step) meets probability exactly 0,
        as page 0's rows did when every block was copied whole."""
        run, tbl, pools = _stream_case(*case)
        got = run(tbl, *pools)
        with _whole_blocks():
            want = run(tbl, *pools)
        np.testing.assert_array_equal(_bits(got), _bits(want))


# the four cells whose attention is a kernel: the pool's row (values),
# the query rows a slot, the pages a block (bfloat16, pages of 16) and
# what the stream copied for every row a slot holds when every block
# was copied whole
_STREAM_CELLS = {
    "longcat_flash_long_gen": (640, 64, 32, 1.275),
    "joyai_flash_draft_gen": (640, 64, 32, 1.275),
    # 256 query rows since a step forwards the pending tail beside the
    # block (PR 41), and still 32 pages a block
    "sdar_moe_block_gen": (512, 256, 32, 1.275),
    "zaya1_cca_long_gen": (256, 8, 64, 1.58),
    # one query row a key head through the stream (PR 48): a block is
    # one trip of 8 pages, so whole blocks ARE the groups
    "olmo_hybrid_gdn_long_gen": (3840, 30, 8, 1.07),
}


@pytest.mark.parametrize("cell", sorted(_STREAM_CELLS))
def test_stream_rows_copied_counts_what_the_kernel_copies(cell):
    """``stream_rows_copied`` against a count made by walking every
    slot's blocks and groups the slow way, over the lengths the long
    generation mixes visit (prompts of 128-256, 1024-1790 new tokens: a
    request passes every length from its prompt's to its last, a step
    each): 1.07 rows copied for every row held, where whole blocks
    copied 1.275 (blocks of 32 pages) and 1.58 (ZAYA1's 64)."""
    row, query_rows, bp, whole_ratio = _STREAM_CELLS[cell]
    p, maxp, n = 16, 128, 512
    assert D._block_pages(p, row, 2, query_rows) == bp
    prompts = np.rint(np.linspace(128, 256, n)).astype(int)
    news = np.rint(np.linspace(1024, 1790, n)).astype(int)
    lengths = np.concatenate([
        np.arange(prompts[i], prompts[i] + news[(i * 317) % n])
        for i in range(n)])
    assert lengths.min() == 128 and lengths.max() > 2030
    values, counts = np.unique(lengths, return_counts=True)

    def walk(length, granule):
        need, pages, blk = min(length // p + 1, maxp), 0, 0
        while blk * bp < need:
            in_block, g = min(bp, need - blk * bp), 0
            while g * granule < in_block:
                pages, g = pages + granule, g + 1
            blk += 1
        return pages * p

    held = int((lengths + 1).sum())
    slow = sum(walk(int(v), 8) * int(k) for v, k in zip(values, counts))
    whole = sum(walk(int(v), bp) * int(k) for v, k in zip(values, counts))
    got = stream_rows_copied(lengths, p, maxp, row, 2, query_rows)
    assert got == slow
    assert abs(got / held - 1.07) < 0.02
    assert abs(whole / held - whole_ratio) < 0.02
    # a table narrower than a slot's pages bounds what is copied
    assert stream_rows_copied([2000, 5], p, 16, row, 2, query_rows) \
        == (16 + 8) * p



# --------------------------------------------------------------------------
# the page stream: a run of neighbouring pages is one copy (PR 49)
# --------------------------------------------------------------------------

# body -> page, table width, the pool's row and the query rows a slot
# (what ``_block_pages`` is asked), float32
_RUN_BODIES = {"grouped": (32, 40, 256, 4), "single": (16, 40, 768, 6),
               "latent": (P, 80, 640, 4)}


def _run_lengths(body):
    """Length 0, a full slot, a block's last row, past it inside a
    page, a short slot, a released one, and LAST a slot whose last
    group holds 6 pages."""
    p, maxp, row, rows = _RUN_BODIES[body]
    bp = D._block_pages(p, row, 4, rows)
    assert bp % 8 == 0 and bp < maxp
    return [0, maxp * p - 1, bp * p - 1, bp * p + p + 3, 3 * p - 1, None,
            21 * p + 5]


def _run_state(body, lengths, seed=5):
    """``(call, tables, lens, pools, reference)`` of one call of
    ``body``'s kernel under ``_tables``' scattered table: ``call(tables,
    *pools)`` -> the output, ``reference(tables, *pools)`` -> the
    float64 oracle's."""
    p, maxp, row, rows = _RUN_BODIES[body]
    if body == "latent":
        c = TestLatentDecodeParity
        q, pages, tbl, lens = _latent_state(lengths, seed=seed, **c.WIDE)

        def call(tbl, pages):
            return np.asarray(latent_decode_attention(
                q, pages, tbl, lens, scale=c.SCALE, value_width=512))

        def reference(tbl, pages):
            return _latent_reference(q, pages, tbl, lens, c.SCALE, 512)

        return call, tbl, lens, (pages,), reference
    hkv, s, g = (2, 1, 2) if body == "grouped" else (6, 1, 1)
    q, kp, vp, tbl, lens = _grouped_state(lengths, s, g, hkv=hkv, p=p,
                                          maxp=maxp, seed=seed)

    def call(tbl, kp, vp):
        return np.asarray(paged_decode_attention(q, kp, vp, tbl, lens,
                                                 page_size=p))

    def reference(tbl, kp, vp):
        return _grouped_reference(q, kp, vp, tbl, lens, hkv)

    return call, tbl, lens, (kp, vp), reference


def _relaid(tbl, lengths, p, pools, layout):
    """The SAME rows under another table: ``(tables, pools)`` with the
    pages the slots hold moved to where ``layout`` puts them (what the
    pools held there before stays wherever nothing lands: finite).
    ``runs``: slot ``i``'s pages are neighbours from ``1 + i x maxp``
    on, so every group of 8 entries is a run.  ``half``: so, but every
    odd slot's first run is broken in its middle (entries 3 and 4
    change places) and the last slot's last group lies at the pool's
    very end, where a copy of 8 pages from its first would pass the
    pool's last page."""
    tbl = np.asarray(tbl)
    b, maxp = tbl.shape
    n = pools[0].shape[0]
    new = np.zeros_like(tbl)
    needs = [0 if ln is None else ln // p + 1 for ln in lengths]
    for i, need in enumerate(needs):
        new[i, :need] = 1 + i * maxp + np.arange(need)
    if layout == "half":
        for i in range(1, b, 2):
            if needs[i] >= 8:
                new[i, [3, 4]] = new[i, [4, 3]]
        last = needs[-1] % 8
        assert 1 <= last <= 7
        new[-1, needs[-1] - last:needs[-1]] = n - last + np.arange(last)
    held = new > 0
    assert len(np.unique(new[held])) == held.sum() == (tbl > 0).sum()
    return jnp.asarray(new), tuple(
        pool.at[new[held]].set(pool[tbl[held]]) for pool in pools)


@pytest.fixture
def _single_streams(monkeypatch):
    """One query row a key head goes through the stream whatever the
    pool's size (``TestSingleRowStreamParity``)."""
    monkeypatch.setattr(D, "_GATHER_POOL_BYTES", 0)


@pytest.mark.usefixtures("_single_streams")
class TestARunOfPagesIsOneCopy:
    """Each of the three kernels over the SAME rows laid scattered, in
    runs, and half and half: live rows reach the same buffer positions
    by one copy of 8 pages or by 8 of one, and what a run's copy brings
    along behind a slot's last pages is masked."""

    @pytest.mark.parametrize("layout", ["runs", "half"])
    @pytest.mark.parametrize("body", sorted(_RUN_BODIES))
    def test_the_output_is_the_scattered_tables_to_the_last_bit(self, body,
                                                                layout):
        lengths = _run_lengths(body)
        p, _, row, rows = _RUN_BODIES[body]
        call, tbl, lens, pools, reference = _run_state(body, lengths)
        want = call(tbl, *pools)
        new, moved = _relaid(tbl, lengths, p, pools, layout)
        got = call(new, *moved)
        np.testing.assert_array_equal(_bits(got), _bits(want))
        live = np.asarray([ln is not None for ln in lengths])
        np.testing.assert_allclose(
            got.astype(np.float64).reshape(
                reference(new, *moved).shape)[live],
            reference(new, *moved)[live], atol=2e-5)
        # the stream did take the other arm: the descriptors it starts
        n, bp = pools[0].shape[0], D._block_pages(p, row, 4, rows)
        _, scattered = _stream_walk(tbl, lens, p, bp, n)
        _, laid = _stream_walk(new, lens, p, bp, n)
        groups = sum(-(-(int(ln) // p + 1) // 8) for ln in np.asarray(lens))
        if layout == "runs":
            assert laid == groups
        else:
            # two broken runs and the group at the pool's end
            assert laid == groups + 3 * 7
        assert scattered > 6 * groups
        for t, count in ((tbl, scattered), (new, laid)):
            assert D.stream_copies(np.asarray(t), np.asarray(lens), p, n,
                                   row, 4, rows) == count

    @pytest.mark.parametrize("bucket", [1, 2, 4, 8])
    @pytest.mark.parametrize("body", sorted(_RUN_BODIES))
    def test_a_table_of_runs_cut_to_its_bucket_changes_no_bit(self, body,
                                                              bucket):
        """A table narrower than a group: the copy of 8 pages from a
        slot's first still lies inside the pool, and what it brings
        past the table's width is masked."""
        p = _RUN_BODIES[body][0]
        lengths = [0, bucket * p - 1, None, (bucket - 1) * p + 2,
                   bucket * p // 2]
        call, tbl, _, pools, _ = _run_state(body, lengths, seed=6)
        want = call(tbl, *pools)
        new, moved = _relaid(tbl, lengths, p, pools, "runs")
        np.testing.assert_array_equal(_bits(call(new[:, :bucket], *moved)),
                                      _bits(want))
        np.testing.assert_array_equal(_bits(call(tbl[:, :bucket], *pools)),
                                      _bits(want))


def test_a_pool_smaller_than_a_run_is_copied_a_page_at_a_time():
    """5 pages under groups of 8: no copy of a run fits the pool (the
    kernel builds none), and the oracle's answer comes out."""
    q, pages, tbl, lens = _latent_state([3 * P - 1], maxp=4, seed=8)
    assert pages.shape[0] == 5 < D._COPIES_A_TRIP
    tbl = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    got = latent_decode_attention(q, pages, tbl, lens, scale=0.3,
                                  value_width=16)
    np.testing.assert_allclose(
        np.asarray(got, np.float64),
        _latent_reference(q, pages, tbl, lens, 0.3, 16), atol=2e-5)
    assert D.stream_copies(np.asarray(tbl), np.asarray(lens), P, 5, 24, 4,
                           4) == 8


@pytest.mark.parametrize("cell", sorted(_STREAM_CELLS))
def test_stream_copies_counts_a_descriptor_a_run(cell):
    """``stream_copies`` at the cells' shapes against the slow walk,
    over a table of runs, a scattered one and one whose every other
    group is broken: with ``stream_rows_copied`` it gives 8, about 1
    and 16 / 9 pages a descriptor."""
    row, query_rows, _, _ = _STREAM_CELLS[cell]
    p, maxp, slots = 16, 128, 24
    rs = np.random.RandomState(7)
    lengths = rs.randint(128, 2047, size=slots)
    need = lengths // p + 1
    pool = 1 + slots * maxp
    bp = D._block_pages(p, row, 2, query_rows)
    runs = np.zeros((slots, maxp), np.int64)
    scattered = np.zeros_like(runs)
    free = rs.permutation(np.arange(1, pool))
    for i, n in enumerate(need):
        runs[i, :n] = 1 + i * maxp + np.arange(n)
        scattered[i, :n], free = free[:n], free[n:]
    broken = runs.copy()
    broken[:, 2::16], broken[:, 3::16] = runs[:, 3::16], runs[:, 2::16]
    pages = stream_rows_copied(lengths, p, maxp, row, 2, query_rows) // p
    want = {}
    for name, tbl in (("runs", runs), ("scattered", scattered),
                      ("broken", broken)):
        got = D.stream_copies(tbl, lengths, p, pool, row, 2, query_rows)
        assert got == _stream_walk(tbl, lengths, p, bp, pool)[1], name
        want[name] = pages / got
    assert want["runs"] == 8.0
    # (a last group of one page is a run of one)
    assert 1.0 <= want["scattered"] < 1.03
    assert abs(want["broken"] - 16 / 9) < 0.1
    # no slot ran (a step all of whose slots completed since): nothing
    assert D.stream_copies(runs[:0], lengths[:0], p, pool, row, 2,
                           query_rows) == 0
    # a run that would pass the pool's last page is copied a page at a
    # time: the same table over a pool that ends inside its last run
    last = int(runs.max())
    assert D.stream_copies(runs, lengths, p, last + 1, row, 2, query_rows) \
        > pages // 8


@pytest.mark.parametrize("body", ["paged", "grouped", "latent"])
def test_bucket_slice_equals_the_full_table_to_the_last_bit(body):
    """The engine hands a step the table's first ``used_page_bucket``
    columns: every position past the longest length is masked, so the
    narrower read changes no bit of the output."""
    lengths = [None, P - 1, P, 2 * P - 1]
    bucket = used_page_bucket(max(ln or 0 for ln in lengths), P, MAXP)
    assert bucket == 2 < MAXP
    if body == "paged":
        q, kp, vp, tbl, lens = _state(lengths)
        full, cut = (paged_decode_attention(q, kp, vp, t, lens,
                                            page_size=P)
                     for t in (tbl, tbl[:, :bucket]))
    elif body == "grouped":   # a block of 2 positions, 2 heads a key head
        q, kp, vp, tbl, lens = _grouped_state(lengths, 2, 2, p=P, maxp=MAXP)
        full, cut = (paged_decode_attention(q, kp, vp, t, lens,
                                            page_size=P)
                     for t in (tbl, tbl[:, :bucket]))
    else:   # the kernels' block is taken from the shapes, not the width
        q, pages, tbl, lens = _latent_state(lengths)
        full, cut = (latent_decode_attention(
            q, pages, t, lens, scale=0.3, value_width=16)
            for t in (tbl, tbl[:, :bucket]))
    np.testing.assert_array_equal(np.asarray(cut), np.asarray(full))


class TestBucketHelpers:
    def test_used_page_bucket_pow2_and_clamp(self):
        assert used_page_bucket(0, 8, 8) == 1
        assert used_page_bucket(7, 8, 8) == 1
        assert used_page_bucket(8, 8, 8) == 2
        assert used_page_bucket(23, 8, 8) == 4
        assert used_page_bucket(24, 8, 8) == 4
        assert used_page_bucket(32, 8, 8) == 8
        assert used_page_bucket(63, 8, 8) == 8
        assert used_page_bucket(1000, 8, 8) == 8  # clamped

    def test_block_pages_come_from_the_shapes(self):
        """Pages a block of a kernel: from the page, the row, the
        itemsize and the head rows (never the table's width, which is
        not an argument)."""
        # the block cell: pages of 16 rows of 4 x 128 bfloat16 lanes,
        # 128 query rows a slot -> 32 pages (512 positions: the scores)
        assert D._block_pages(16, 512, 2, 128) == 32
        # the served models: pages of 16 rows of 640 bfloat16 lanes, 64
        # head rows -> 32 pages (655 KB, 512 positions)
        assert D._block_pages(16, 640, 2, 64) == 32
        assert D._block_pages(16, 640, 4, 64) == 16     # float32 rows
        # over 128 head rows a block keeps 512 positions (PR 41: the
        # block cell's step hands the kernel 256, a tail and a block,
        # and blocks of 16 pages cost it a tenth)
        assert D._block_pages(16, 512, 2, 256) == 32
        assert D._block_pages(16, 640, 2, 512) == 32
        assert D._block_pages(16, 640, 2, 100) == 32    # scores: 655 -> 32
        assert D._block_pages(32, 256, 2, 90) == 16     # scores bound it
        assert D._block_pages(4096, 640, 2, 64) == 1    # never under 1

    def test_decode_hbm_bytes_carries_gather_tax(self):
        b, h, d, p, maxp, item = 8, 8, 16, 16, 4, 4
        pages = 2 * b * maxp * p * h * d * item       # K + V, once
        # read, written as the gathered copy, read again; the f32 score
        # plane out and back; q in and the output out
        assert decode_hbm_bytes(b, h, d, p, maxp, item) == (
            3 * pages + 2 * b * h * maxp * p * 4 + 2 * b * h * d * 4)


class TestInt8MMSite:
    def _mats(self, m=4, k=32, n=64, seed=0):
        from bigdl_tpu.ops.quantized_matmul import quantize_per_channel

        rs = np.random.RandomState(seed)
        x = jnp.asarray(rs.randn(m, k).astype(np.float32))
        w = jnp.asarray((rs.randn(n, k) * 0.1).astype(np.float32))
        w_q, w_s = quantize_per_channel(w, axis=0)
        return x, w, w_q, w_s

    def test_dequant_impl_close_to_float(self):
        from bigdl_tpu.ops.quantized_matmul import int8_matmul

        x, w, w_q, w_s = self._mats()
        want = np.asarray(jnp.matmul(x, w.T))
        got = np.asarray(int8_matmul(x, w_q, w_s, impl="dequant"))
        np.testing.assert_allclose(got, want, atol=0.05, rtol=0.05)
        # int8 and dequant agree with each other within activation-
        # quantization noise
        i8 = np.asarray(int8_matmul(x, w_q, w_s))
        np.testing.assert_allclose(got, i8, atol=0.1, rtol=0.1)

    def test_auto_is_int8_when_tuner_off(self):
        from bigdl_tpu.ops.quantized_matmul import int8_matmul

        x, _w, w_q, w_s = self._mats()
        np.testing.assert_array_equal(
            np.asarray(int8_matmul(x, w_q, w_s, impl="auto")),
            np.asarray(int8_matmul(x, w_q, w_s)))
        assert autotune.get_cache().decisions == {}

    def test_invalid_impl_raises(self):
        from bigdl_tpu.ops.quantized_matmul import int8_matmul

        x, _w, w_q, w_s = self._mats()
        with pytest.raises(ValueError, match="impl"):
            int8_matmul(x, w_q, w_s, impl="bogus")

    def test_site_golden_key_and_never_lose(self, tuner):
        rec = autotune.decide_int8_mm((4, 32), (64, 32), jnp.float32)
        assert rec is not None
        assert rec["key"] == "int8_mm|m4k32n64|float32|cpu"
        # model-only: the static int8 path wins (dequant's f32 weight
        # round trip costs more bytes at decode shapes)
        assert rec["impl"] == "int8" and rec["static"] == "int8"

    def test_measured_prewarm_persists(self, tuner, monkeypatch):
        monkeypatch.setenv("BIGDL_TUNER_MEASURE", "1")
        monkeypatch.setenv("BIGDL_TUNER_MEASURE_ITERS", "1")
        autotune.reset()
        autotune.prewarm_int8_mm(4, 16, 32)
        doc = json.loads(tuner.read_text())
        recs = [r for r in doc["decisions"].values()
                if r["site"] == "int8_mm"]
        assert recs and recs[0]["source"] == "measured"
        assert set(recs[0]["measured_s"]) == {"int8", "dequant"}
