"""serving/cache.py's allocator hands a slot its pages in runs of
``PAGE_RUN`` neighbouring pages (PR 49), by preference and never by
reservation.

* **How many never changes**: against a copy of the free list the
  allocator was before (a list popped from its end), over thousands of
  random ``alloc`` / ``grow`` / ``release`` at four pool sizes (the
  engine's default, a tight one, one run and a page, one page)
  ``free_pages()``, ``pages_in_use()``, ``can_admit()``, ``alloc``'s
  count or its ``KV cache exhausted`` and ``grow``'s answer agree call
  for call; no page is owned twice, page 0 is never handed out, the
  tables say what the slots own and the books (free pages, free pages a
  run) say what they do not.
* **Where they lie**: under the default pool every group of 8 table
  entries of every slot names neighbouring pages, whatever the order of
  the calls; a tight pool only loses runs.
* **Through the engine**: after a long closed-loop churn on a tiny
  engine with the default pool every running slot's table is runs
  (under a model whose attention is a page-walking kernel
  ``serve.decode_step``'s ``attn_copies`` is then the groups counted by
  hand: ``tests/test_zaya.py``).
"""

import numpy as np
import pytest

from bigdl_tpu.serving.cache import PAGE_RUN, PagedKVCache

SLOTS, PAGE, MAX_LEN = 6, 4, 128
MAXP = MAX_LEN // PAGE                      # 32 pages a slot: 4 runs
DEFAULT = 1 + SLOTS * MAXP
#: name -> num_pages
POOLS = {"default": DEFAULT, "tight": 1 + 2 * MAXP + 5,
         "a_run_and_a_page": 2 + PAGE_RUN, "one_page": 2}


class _FreeList:
    """The allocator as it was before PR 49, its counts and nothing
    else: a list of free pages popped from its end."""

    def __init__(self, num_pages, max_slots, maxp, page_size):
        self.free = list(range(1, max(num_pages, 2)))
        self.own = [[] for _ in range(max_slots)]
        self.maxp, self.page_size = maxp, page_size

    def pages_for(self, n):
        return max(1, -(-n // self.page_size))

    def can_admit(self, n):
        return len(self.free) >= self.pages_for(n)

    def alloc(self, slot, n):
        need = self.pages_for(n)
        if len(self.free) < need:
            raise RuntimeError("KV cache exhausted")
        self.own[slot] = [self.free.pop() for _ in range(need)]
        return self.own[slot]

    def grow(self, slot):
        if not self.free or len(self.own[slot]) >= self.maxp:
            return False
        self.own[slot].append(self.free.pop())
        return True

    def release(self, slot):
        self.free.extend(self.own[slot])
        self.own[slot] = []


def _cache(num_pages):
    return PagedKVCache(1, 1, 8, page_size=PAGE, num_pages=num_pages,
                        max_slots=SLOTS, max_len=MAX_LEN)


def _check_books(c):
    """No page owned twice, page 0 never, the tables and the books say
    what the slots own."""
    owned = [pg for i in range(SLOTS) for pg in c.slot_pages(i)]
    assert len(owned) == len(set(owned)) and 0 not in owned
    assert all(0 < pg < c.num_pages for pg in owned)
    free = np.ones(c.num_pages, bool)
    free[0] = False
    free[owned] = False
    np.testing.assert_array_equal(c._is_free, free)
    assert c.free_pages() == int(free.sum())
    whole = (c.num_pages - 1) // PAGE_RUN * PAGE_RUN
    np.testing.assert_array_equal(
        c._run_free, free[1:1 + whole].reshape(-1, PAGE_RUN).sum(axis=1))
    for i in range(SLOTS):
        pages = c.slot_pages(i)
        assert list(c.page_tables[i, :len(pages)]) == pages
        assert not c.page_tables[i, len(pages):].any()


def _groups_are_runs(c, slot):
    pages = c.slot_pages(slot)
    return all(pages[at + j] == pages[at] + j
               for at in range(0, len(pages), PAGE_RUN)
               for j in range(min(PAGE_RUN, len(pages) - at)))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("pool", sorted(POOLS))
def test_capacity_is_the_free_lists_call_for_call(pool, seed):
    rs = np.random.RandomState(seed)
    c = _cache(POOLS[pool])
    old = _FreeList(POOLS[pool], SLOTS, MAXP, PAGE)
    refused = grown = 0
    for step in range(3000):
        slot = int(rs.randint(SLOTS))
        if not c.slot_pages(slot):
            n = int(rs.randint(1, MAX_LEN + 1))
            assert c.can_admit(n) == old.can_admit(n)
            if old.can_admit(n):
                assert len(c.alloc(slot, n)) == len(old.alloc(slot, n))
            else:
                refused += 1
                with pytest.raises(RuntimeError, match="exhausted"):
                    c.alloc(slot, n)
        elif rs.rand() < 0.8:
            got = c.grow(slot)
            assert got == old.grow(slot)
            grown += got
        else:
            c.release(slot)
            old.release(slot)
        assert c.free_pages() == len(old.free)
        assert c.pages_in_use() == POOLS[pool] - 1 - len(old.free)
        if step % 50 == 0 or pool == "one_page":
            _check_books(c)
        if pool == "default":
            assert all(_groups_are_runs(c, i) for i in range(SLOTS))
    _check_books(c)
    assert grown > 100 or pool == "one_page"
    assert refused > 0 or pool == "default"


def test_a_prompt_takes_whole_runs_and_the_head_of_one_more():
    c = _cache(DEFAULT)
    assert c.alloc(0, 19 * PAGE) == list(range(1, 20))
    # the next prompt starts at a wholly free run, not behind the first
    assert c.alloc(1, 3 * PAGE) == [25, 26, 27]
    # a slot grows behind its last page while its group of 8 is open
    assert c.grow(1) and c.slot_pages(1)[-1] == 28
    for _ in range(5):
        assert c.grow(0)
    assert c.slot_pages(0)[-5:] == [20, 21, 22, 23, 24]
    # a slot that holds whole groups starts a new run: the lowest free
    assert c.grow(0) and c.slot_pages(0)[-1] == 33
    c.release(1)
    assert c.alloc(2, PAGE) == [25]


def test_a_tight_pool_loses_runs_never_pages():
    """11 pages, no run wholly free after the first prompt: a second
    slot is handed what there is, to the last page."""
    c = _cache(12)
    assert c.alloc(0, 6 * PAGE) == [1, 2, 3, 4, 5, 6]
    assert c.alloc(1, 3 * PAGE) == [7, 8, 9]
    assert c.grow(0) and c.grow(1)
    assert c.slot_pages(0)[-1] == 10 and c.slot_pages(1)[-1] == 11
    assert c.free_pages() == 0 and not c.grow(0) and not c.grow(1)
    c.release(0)
    assert c.free_pages() == 7 and c.can_admit(7 * PAGE)
    assert sorted(c.alloc(2, 7 * PAGE)) == [1, 2, 3, 4, 5, 6, 10]
    _check_books(c)


def test_withheld_pages_are_no_ones_until_handed_back():
    c = _cache(DEFAULT)
    c.alloc(0, 5 * PAGE)
    spare = c.withhold(c.free_pages() - 3)
    assert c.free_pages() == 3 and len(spare) == DEFAULT - 1 - 5 - 3
    assert not c.can_admit(4 * PAGE) and c.can_admit(3 * PAGE)
    assert c.grow(0) and c.grow(0) and c.grow(0) and not c.grow(0)
    assert not set(c.slot_pages(0)) & set(spare)
    c.hand_back(spare)
    c.release(0)
    assert c.free_pages() == DEFAULT - 1
    _check_books(c)


# ---------------------------------------------------------------- engines
def _churn(eng, rs, vocab, requests, new=(3, 40), prompt=(2, 30)):
    """A closed loop: as many requests in flight as the engine has
    slots, a new one at every completion; every slot's table checked
    for runs at every pump."""
    live, done, pumps = [], 0, 0
    while done < requests:
        while len(live) < eng.max_batch and done + len(live) < requests:
            live.append(eng.submit(
                [int(t) for t in rs.randint(1, vocab,
                                            rs.randint(*prompt))],
                int(rs.randint(*new))))
        eng.pump()
        pumps += 1
        for i in range(eng.max_batch):
            assert _groups_are_runs(eng.cache, i), eng.cache.slot_pages(i)
        done += sum(r.done for r in live)
        live = [r for r in live if not r.done]
    eng.run_until_idle(60)
    return pumps


def test_every_table_is_runs_after_a_long_churn_on_the_default_pool():
    from bigdl_tpu.models.transformer import build_transformer_lm
    from bigdl_tpu.serving import LMEngine

    model = build_transformer_lm(48, dim=16, n_head=2, n_layer=1,
                                 max_len=128, attn_impl="lax")
    eng = LMEngine(model, max_batch=4, page_size=2)
    assert eng.cache.num_pages == 1 + 4 * 64
    try:
        pumps = _churn(eng, np.random.RandomState(3), 48, 60,
                       new=(10, 90))
        st = eng.stats()
        assert pumps > 300 and st["requests"] == 60
        assert st["preemptions"] == 0
        assert st["attn_pages_a_copy"] is None      # the gather: no stream
        assert eng.cache.free_pages() == 4 * 64
    finally:
        eng.close()


# ----------------------------------------------------------- the metric
@pytest.mark.parametrize("attrs, want", [
    # a program from before the counter, or a model that streams none
    ([{"context_tokens": 900}], None),
    ([{"attn_rows_copied": 1024, "attn_copies": 8}], 8.0),
    ([{"attn_rows_copied": 1024, "attn_copies": 64}], 1.0),
    # the mean over the steps, each its own ratio; a step in which no
    # slot ran a copy is left out
    ([{"attn_rows_copied": 2048, "attn_copies": 16},
      {"attn_rows_copied": 1024, "attn_copies": 16},
      {"attn_rows_copied": 0, "attn_copies": 0}], 6.0),
])
def test_the_benchmark_reads_pages_a_copy_off_the_spans(attrs, want):
    """``benchmarks/metrics/attn_pages_a_copy.py`` over the window's
    ``serve.decode_step`` spans (pages of 16): ``None`` where none
    carries ``attn_copies``, and it is declared for the seven cells
    whose attention streams pages."""
    import json
    import os
    import types

    from benchmarks.metrics import attn_pages_a_copy as metric

    spans = [{"name": "serve.decode_step", "attrs": a} for a in attrs]
    spans.append({"name": "serve.prep", "attrs": {"attn_copies": 3,
                                                  "attn_rows_copied": 48}})
    run = types.SimpleNamespace(spans=spans, counters={"page_size": 16})
    assert metric.read(run) == want
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        entry, = (m for m in json.load(fh)["per_layer"]
                  if m["name"] == "attn_pages_a_copy")
    assert entry["moves"] == "serve_tokens_per_s"
    assert len(entry["workloads"]) == 7
