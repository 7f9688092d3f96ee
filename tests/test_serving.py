"""Serving tier (ISSUE 12): paged KV cache, continuous batching,
int8/TP decode, queue machinery, and the obs/autoscale loop closure.

The load-bearing contract: paged decode must BIT-MATCH the contiguous-
cache ``TransformerLM.generate`` at temperature 0 for identical
prompts — including requests admitted into the middle of an in-flight
batch, and across a page-exhaustion preemption."""

import numpy as np
import pytest

import late_read_cases


def _model(max_len=64):
    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.models.transformer import build_transformer_lm

    RandomGenerator.RNG.set_seed(13)
    return build_transformer_lm(48, dim=32, n_head=4, n_layer=2,
                                max_len=max_len, attn_impl="lax")


@pytest.fixture(scope="module")
def lm_model():
    return _model()


@pytest.fixture(scope="module")
def lm_params(lm_model):
    return lm_model.params()


def _ref(model, params, prompt, n):
    return list(np.asarray(model.generate(
        params, np.asarray(prompt)[None, :], n))[0])


def _out(prompt, req):
    return [int(t) for t in list(prompt) + req.tokens]


# ---------------------------------------------------------------- cache
class TestPagedKVCache:
    def _cache(self, **kw):
        from bigdl_tpu.serving import PagedKVCache

        kw.setdefault("page_size", 4)
        kw.setdefault("num_pages", 9)
        kw.setdefault("max_slots", 2)
        kw.setdefault("max_len", 32)
        return PagedKVCache(2, 4, 8, **kw)

    def test_alloc_release_roundtrip(self):
        c = self._cache()
        assert c.free_pages() == 8  # page 0 reserved as trash
        pages = c.alloc(0, 10)      # ceil(10/4) = 3 pages
        assert len(pages) == 3 and 0 not in pages
        assert c.free_pages() == 5
        assert list(c.page_tables[0][:3]) == pages
        c.release(0)
        assert c.free_pages() == 8
        assert not c.page_tables[0].any()

    def test_grow_and_exhaustion(self):
        c = self._cache(num_pages=4)  # 3 usable
        c.alloc(0, 4)
        c.lengths[0] = 4
        assert c.needs_growth(0)
        assert c.grow(0) and c.grow(0)
        assert not c.grow(0)  # pool empty
        assert c.free_pages() == 0

    def test_gather_pages_layout(self):
        import jax.numpy as jnp

        from bigdl_tpu.serving import gather_pages
        from bigdl_tpu.serving.cache import pool_shape

        shape = pool_shape(3, 4, 2, 5)       # 3 pages of 4 rows, H=2
        assert shape == (3, 4, 10)
        pages = jnp.arange(3 * 4 * 10, dtype=jnp.float32).reshape(shape)
        table = jnp.asarray([[2, 1], [0, 0]], jnp.int32)
        g = gather_pages(pages, table)
        assert g.shape == (2, 8, 10)         # (B, maxp*P, H*Dh)
        np.testing.assert_array_equal(
            np.asarray(g[0, :4]), np.asarray(pages[2]))
        np.testing.assert_array_equal(
            np.asarray(g[0, 4:]), np.asarray(pages[1]))
        # the stacked buffer + a layer index reads the same rows
        stacked = jnp.stack([pages + 1000.0, pages])
        np.testing.assert_array_equal(
            np.asarray(gather_pages(stacked, table, layer=1)),
            np.asarray(g))


    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    def test_prompt_write_round_trips_through_gather(self, dtype):
        """The prefill's one-scatter write, then ``gather_pages``,
        against a contiguous ``(B, T, H, Dh)`` reference — through a
        table whose pages are out of order and whose tail points at
        the trash page."""
        import jax.numpy as jnp

        from bigdl_tpu.serving.cache import (gather_pages, pool_shape,
                                             write_prompt_pages)

        n_layer, n_head, head_dim, page = 3, 4, 8, 4
        rs = np.random.RandomState(5)
        # two prompts of 3 and 2 live pages in a 4-page bucket
        ref = jnp.asarray(rs.randn(2, 16, n_head, head_dim), dtype)
        ids = np.asarray([[7, 2, 5, 0], [1, 8, 0, 0]], np.int32)
        pages = jnp.full(pool_shape(9, page, n_head, head_dim,
                                    n_layer=n_layer), -3.0, dtype)
        for b in range(2):
            pages = write_prompt_pages(
                pages, 1, jnp.asarray(ids[b]),
                ref[b].reshape(16, n_head * head_dim))
        assert pages.dtype == jnp.dtype(dtype)
        got = gather_pages(pages, jnp.asarray(ids), layer=1)
        got = np.asarray(got.astype(jnp.float32)).reshape(
            2, 16, n_head, head_dim)
        want = np.asarray(ref.astype(jnp.float32))
        np.testing.assert_array_equal(got[0, :12], want[0, :12])
        np.testing.assert_array_equal(got[1, :8], want[1, :8])
        # the other layers were not touched, and nothing but the named
        # pages (trash included) of layer 1
        flat = np.asarray(pages.astype(jnp.float32))
        assert (flat[0] == -3.0).all() and (flat[2] == -3.0).all()
        assert (flat[1][[3, 4, 6]] == -3.0).all()

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    def test_decode_write_lands_in_exactly_one_row(self, dtype):
        """``write_token_rows`` sets ``[layer, page, slot_in_page, :]``
        for each slot and changes no other byte of a cache filled
        with a sentinel."""
        import jax.numpy as jnp

        from bigdl_tpu.serving.cache import pool_shape, write_token_rows

        page, width = 4, 4 * 8
        tables = jnp.asarray([[6, 3, 0], [2, 5, 1]], jnp.int32)
        lengths = jnp.asarray([5, 8], jnp.int32)   # -> [3, 1], [1, 0]
        rows = jnp.asarray(
            np.random.RandomState(6).randn(2, width), dtype)
        before = jnp.full(pool_shape(7, page, 4, 8, n_layer=2), 9.0,
                          dtype)
        after = np.asarray(write_token_rows(
            before, 1, tables, lengths, rows).astype(jnp.float32))
        want = np.full(after.shape, 9.0, np.float32)
        want[1, 3, 1] = np.asarray(rows[0].astype(jnp.float32))
        want[1, 1, 0] = np.asarray(rows[1].astype(jnp.float32))
        np.testing.assert_array_equal(after, want)


# --------------------------------------------------------------- engine
class TestContinuousBatching:
    def test_mid_batch_admission_bit_matches(self, lm_model, lm_params):
        """Paged decode must bit-match the contiguous-cache generate()
        — for the initial batch (different prompt lengths) AND for a
        request admitted into a freed slot mid-flight."""
        from bigdl_tpu.serving import LMEngine

        rs = np.random.RandomState(1)
        p1, p2, p3 = (rs.randint(0, 48, (n,)) for n in (5, 9, 4))
        eng = LMEngine(lm_model, max_batch=2, page_size=8)
        r1 = eng.submit(p1, 10)
        r2 = eng.submit(p2, 3)
        for _ in range(3):     # r2 completes, r1 still in flight
            eng.pump()
        assert r2.done and not r1.done
        r3 = eng.submit(p3, 7)  # admitted into the freed slot
        eng.pump()
        assert eng.active_count() == 2
        eng.run_until_idle(60)
        eng.close()
        assert _out(p1, r1) == _ref(lm_model, lm_params, p1, 10)
        assert _out(p2, r2) == _ref(lm_model, lm_params, p2, 3)
        assert _out(p3, r3) == _ref(lm_model, lm_params, p3, 7)

    def test_slot_and_page_reuse(self, lm_model):
        from bigdl_tpu.serving import LMEngine

        eng = LMEngine(lm_model, max_batch=2, page_size=8, num_pages=9)
        total = eng.cache.free_pages()
        for wave in range(3):
            reqs = [eng.submit([1 + wave, 2, 3], 4) for _ in range(2)]
            eng.run_until_idle(60)
            assert all(r.done for r in reqs)
            # everything returned to the pool between waves
            assert eng.cache.free_pages() == total
            assert eng.active_count() == 0
        assert eng.stats()["requests"] == 6
        eng.close()

    def test_preemption_bit_exact_and_counted(self, lm_model, lm_params):
        from bigdl_tpu.serving import LMEngine

        rs = np.random.RandomState(2)
        p1, p2 = rs.randint(0, 48, (5,)), rs.randint(0, 48, (9,))
        # contended-but-feasible pool: both requests cannot be resident
        # together at full length, so the youngest gets preempted and
        # re-prefilled — output must still match the uninterrupted run
        eng = LMEngine(lm_model, max_batch=2, page_size=4, num_pages=8)
        a, b = eng.submit(p1, 12), eng.submit(p2, 12)
        eng.run_until_idle(120)
        assert eng.stats()["preemptions"] >= 1
        eng.close()
        assert _out(p1, a) == _ref(lm_model, lm_params, p1, 12)
        assert _out(p2, b) == _ref(lm_model, lm_params, p2, 12)

    def test_infeasible_request_rejected(self, lm_model):
        from bigdl_tpu.serving import LMEngine

        eng = LMEngine(lm_model, max_batch=2, page_size=4, num_pages=5)
        with pytest.raises(ValueError, match="KV pages"):
            eng.submit([1, 2, 3], 40)  # needs 11 pages, pool has 4
        with pytest.raises(ValueError, match="max_len"):
            eng.submit([1, 2, 3], 100)
        eng.close()

    def test_int8_decode(self, lm_model):
        from bigdl_tpu.serving import LMEngine

        eng = LMEngine(lm_model, max_batch=2, page_size=8, int8=True)
        assert eng._qparams is not None
        assert eng._qparams["h0"]["attn"]["wq"][0].dtype.name == "int8"
        r = eng.submit([3, 1, 4, 1, 5], 8)
        eng.run_until_idle(60)
        eng.close()
        assert r.done and len(r.tokens) == 8
        assert all(0 <= t < 48 for t in r.tokens)

    def test_int8_tokens_do_not_depend_on_page_placement(self, lm_model):
        """The int8 step reads and writes the same token-major cache:
        its greedy tokens are the same wherever the request's pages
        lie and whatever the pool's size."""
        from bigdl_tpu.serving import LMEngine

        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5]
        runs = []
        for kw, first in ((dict(), None),
                          (dict(num_pages=40), [7, 7, 7])):
            eng = LMEngine(lm_model, max_batch=2, page_size=8, int8=True,
                           **kw)
            if first is not None:        # takes the pages run 1 used
                eng.submit(first, 12)
                eng.pump()
            r = eng.submit(prompt, 10)
            eng.run_until_idle(60)
            eng.close()
            assert r.done and len(r.tokens) == 10
            runs.append(list(r.tokens))
        assert runs[0] == runs[1]

    def test_int8_excludes_tp(self, lm_model):
        from bigdl_tpu.serving import LMEngine

        with pytest.raises(ValueError, match="exclusive"):
            LMEngine(lm_model, int8=True, tp=2)


class TestTPDecode:
    def test_tp_decode_bit_matches(self, lm_model, lm_params):
        from bigdl_tpu.serving import LMEngine

        rs = np.random.RandomState(3)
        p1, p2 = rs.randint(0, 48, (5,)), rs.randint(0, 48, (9,))
        eng = LMEngine(lm_model, max_batch=2, page_size=8, tp=4)
        r1, r2 = eng.submit(p1, 6), eng.submit(p2, 3)
        eng.run_until_idle(120)
        eng.close()
        assert _out(p1, r1) == _ref(lm_model, lm_params, p1, 6)
        assert _out(p2, r2) == _ref(lm_model, lm_params, p2, 3)

    def test_tp_wire_accounting(self, lm_model):
        from bigdl_tpu import obs
        from bigdl_tpu.serving import LMEngine

        eng = LMEngine(lm_model, max_batch=2, page_size=8, tp=4,
                       wire="int8")
        r = eng.submit([5, 6, 7], 6)
        eng.run_until_idle(120)
        eng.close()
        assert r.done and len(r.tokens) == 6
        snap = obs.get_registry().snapshot()["metrics"]
        sv = {tuple(s["labels"].items()): s["value"] for s in
              snap["bigdl_collective_wire_savings_ratio"]["samples"]}
        assert sv[(("path", "serve"),)] > 2.0
        ops = {s["labels"]["op"] for s in
               snap["bigdl_collective_bytes_total"]["samples"]}
        assert "serve_tp_psum" in ops

    def test_tp_must_divide_heads(self, lm_model):
        from bigdl_tpu.serving import LMEngine

        with pytest.raises(ValueError, match="divide"):
            LMEngine(lm_model, tp=3)


# ------------------------------------------------ one step in flight
def _until_eos(prompt, ref, eos):
    """``generate()``'s output cut after the first ``eos`` it emits."""
    gen = ref[len(prompt):]
    if eos in gen:
        gen = gen[:gen.index(eos) + 1]
    return [int(t) for t in list(prompt) + gen]


def _drive_step_by_step(eng):
    """The engine with NO step in flight: every step's tokens are read
    before the next is dispatched (what the loop was before)."""
    while eng.pump():
        eng._settle("idle")
    assert eng.stats()["steps_ahead"] == 0


class TestOneStepInFlight:
    # (prompt length, new tokens); 1 and 2 new tokens end at the
    # prefill and at the first step
    MIX = ((5, 10), (9, 3), (4, 7), (7, 1), (6, 2), (3, 12), (8, 5))

    def _mixed(self, eng):
        """MIX through ``eng``, most of it submitted while a step is in
        flight; returns [(prompt, new, request)]."""
        rs = np.random.RandomState(11)
        todo = [(rs.randint(0, 48, (n,)), new) for n, new in self.MIX]
        sent = [(p, new, eng.submit(p, new)) for p, new in todo[:2]]
        eng.pump()
        for p, new in todo[2:]:
            eng.pump()
            assert eng._inflight is not None  # admitted behind a step
            sent.append((p, new, eng.submit(p, new)))
        eng.run_until_idle(120)
        assert eng._inflight is None and eng.active_count() == 0
        assert eng.cache.pages_in_use() == 0
        return sent

    @pytest.mark.parametrize("kind", ["float", "tp2", "int8"])
    def test_pipelined_greedy_is_token_for_token_the_reference(
            self, lm_model, lm_params, kind):
        """Tokens never visit the host on their way into the next step
        and are emitted one step late; none is lost, reordered or
        changed.  The reference is generate() (float, tp), and for int8
        the same engine driven with no step in flight."""
        from bigdl_tpu.serving import LMEngine

        kw = {"float": {}, "tp2": {"tp": 2}, "int8": {"int8": True}}[kind]
        eng = LMEngine(lm_model, max_batch=3, page_size=4, **kw)
        sent = self._mixed(eng)
        st = eng.stats()
        eng.close()
        # the loop engaged: all but the first step of a busy stretch
        assert st["steps_ahead"] >= st["steps"] - 2 > 0
        assert st["tokens"] == sum(new for _, new in self.MIX)
        if kind == "int8":
            ref = LMEngine(lm_model, max_batch=3, page_size=4, int8=True)
            want = [ref.submit(p, new) for p, new, _ in sent]
            _drive_step_by_step(ref)
            ref.close()
            for (p, new, req), w in zip(sent, want):
                assert w.done and list(req.tokens) == list(w.tokens)
                assert len(req.tokens) == new
            return
        for p, new, req in sent:
            assert req.error is None
            assert _out(p, req) == _ref(lm_model, lm_params, p, new)

    def test_eos_mid_stream_wastes_a_row_never_a_token(self, lm_model,
                                                       lm_params):
        """An EOS is learnt one step late: the slot is in the step
        already dispatched, whose token for it is dropped.  The slot
        and its pages are reused at once and decode bit-equal."""
        from bigdl_tpu.serving import LMEngine

        rs = np.random.RandomState(11)
        pa, pb, pc = (rs.randint(0, 48, (n,)) for n in (5, 9, 4))
        ref_b = _ref(lm_model, lm_params, pb, 14)
        eos = int(ref_b[len(pb) + 3])        # b's fourth token
        want = {k: _until_eos(p, _ref(lm_model, lm_params, p, 14), eos)
                for k, p in (("a", pa), ("b", pb), ("c", pc))}
        assert len(want["b"]) == len(pb) + 4
        assert len(want["a"]) == len(pa) + 14    # a never emits it
        eng = LMEngine(lm_model, max_batch=2, page_size=4, eos_id=eos)
        a, b = eng.submit(pa, 14), eng.submit(pb, 14)
        pages_b = None
        while not b.done:
            eng.pump()
            pages_b = eng.cache.slot_pages(1) or pages_b
        assert not a.done and eng._inflight is not None
        # the step in flight still carries b's slot: its row is wasted
        assert [slot for slot, _ in eng._inflight.entries] == [0, 1]
        c = eng.submit(pc, 14)
        eng.pump()
        assert eng._slots[1] is not None and eng._slots[1].req is c
        assert set(eng.cache.slot_pages(1)) & set(pages_b)
        eng.run_until_idle(60)
        assert eng.cache.pages_in_use() == 0
        eng.close()
        for k, p, req in (("a", pa, a), ("b", pb, b), ("c", pc, c)):
            assert _out(p, req) == want[k], k   # nothing after the EOS
        assert b.tokens[-1] == eos and eos not in b.tokens[:-1]

    def test_preemption_settles_the_step_in_flight_first(self, lm_model,
                                                         lm_params):
        """The fold of a preempted request's tokens into its prompt
        needs every dispatched token on the host."""
        from bigdl_tpu.serving import LMEngine

        rs = np.random.RandomState(2)
        p1, p2 = rs.randint(0, 48, (5,)), rs.randint(0, 48, (9,))
        eng = LMEngine(lm_model, max_batch=2, page_size=4, num_pages=8)
        prompts = {}
        folds = []
        preempt = eng._preempt_youngest

        def watched():
            slot = preempt()
            assert eng._inflight is None
            if slot is not None:
                req = eng._stash[0]
                folds.append((list(req.payload),
                              prompts[req.id] + list(req.tokens),
                              req.max_new_tokens + len(req.tokens)))
            return slot

        eng._preempt_youngest = watched
        counted = eng.stats()["preemptions"]   # the registry's, so far
        a, b = eng.submit(p1, 12), eng.submit(p2, 12)
        prompts.update({a.id: list(p1), b.id: list(p2)})
        eng.run_until_idle(120)
        st = eng.stats()
        eng.close()
        assert folds and st["preemptions"] - counted == len(folds)
        assert st["settles"]["preempt"] >= 1
        for payload, prompt_and_tokens, total in folds:
            assert payload == prompt_and_tokens and total == 12
        assert _out(p1, a) == _ref(lm_model, lm_params, p1, 12)
        assert _out(p2, b) == _ref(lm_model, lm_params, p2, 12)

    def test_swap_weights_settles_first(self, lm_model, lm_params):
        """The step in flight ran on the old weights: its tokens are
        emitted before the flip, and the first step on the new weights
        is dispatched with nothing pending."""
        import jax

        from bigdl_tpu.serving import LMEngine

        new_params = jax.tree.map(lambda a: a * 1.5, lm_params)
        prompt = [3, 7, 11, 2, 9]
        eng = LMEngine(lm_model, max_batch=2, page_size=4)
        req = eng.submit(prompt, 12)
        for _ in range(4):
            eng.pump()
        before = len(req.tokens)
        assert eng._inflight is not None
        eng.swap_weights(new_params, version="v1")
        assert eng._inflight is None
        assert eng.stats()["settles"]["swap"] == 1
        old = _ref(lm_model, lm_params, prompt, 12)
        emitted = len(req.tokens)
        assert emitted == before + 1
        assert _out(prompt, req) == old[:len(prompt) + emitted]
        late = eng.submit(prompt, 6)
        eng.run_until_idle(60)
        eng.close()
        assert len(req.tokens) == 12 and req.error is None
        assert _out(prompt, late) == _ref(lm_model, new_params, prompt, 6)

    def test_sampling_with_a_fixed_seed_repeats(self, lm_model):
        """Temperature > 0: the sampled token feeds the next step on the
        device, and the key is split in dispatch order, so a seed fixes
        the run."""
        from bigdl_tpu.serving import LMEngine

        runs = []
        for _ in range(2):
            eng = LMEngine(lm_model, max_batch=2, page_size=4, seed=7)
            reqs = [eng.submit(p, 9, temperature=0.9)
                    for p in ([3, 7, 11], [5, 1, 4, 8, 2], [9, 9])]
            eng.run_until_idle(60)
            eng.close()
            runs.append([list(r.tokens) for r in reqs])
            assert all(len(t) == 9 for t in runs[-1])
        assert runs[0] == runs[1]
        greedy = LMEngine(lm_model, max_batch=2, page_size=4, seed=7)
        g = greedy.submit([3, 7, 11], 9)
        greedy.run_until_idle(60)
        greedy.close()
        assert runs[0][0] != list(g.tokens)   # it did sample

    def test_a_greedy_request_is_untouched_by_sampling_neighbours(
            self, lm_model, lm_params, tmp_path, monkeypatch):
        """The draw runs only in a step in which a running slot samples
        (``sample_step``), and which arm a step took changes no greedy
        slot's token: the host counts the arms."""
        import json

        from bigdl_tpu import obs
        from bigdl_tpu.obs import names
        from bigdl_tpu.serving import LMEngine, spans as S

        prompt, new = [3, 7, 11, 2], 10
        want = _ref(lm_model, lm_params, prompt, new)
        monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path / "trace"))
        shares = {}
        for temp in (0.0, 0.9):
            obs.reset()
            try:
                eng = LMEngine(lm_model, max_batch=3, page_size=4, seed=7)
                g = eng.submit(prompt, new)
                # the neighbours end before the greedy request does:
                # the mixed run's last steps take the greedy arm
                others = [eng.submit(p, 5, temperature=temp)
                          for p in ([5, 1, 4, 8], [9, 9])]
                eng.run_until_idle(60)
                st = eng.stats()
                eng.close()
                assert _out(prompt, g) == want
                assert all(len(r.tokens) == 5 for r in others)
                shares[temp] = st["greedy_step_share"]
                picks = obs.get_registry().counter(
                    names.SERVE_STEPS_TOTAL, "", labels=("pick",))
                took = {k: picks.labels(pick=k).value
                        for k in ("greedy", "sampled")}
                assert sum(took.values()) == st["steps"]
                assert took["greedy"] == round(
                    st["greedy_step_share"] * st["steps"])
                tracer = obs.get_tracer()
                tracer.flush()
                with open(tracer.jsonl_path, encoding="utf-8") as fh:
                    recs = [json.loads(line) for line in fh]
                sampling = [r["attrs"]["sampling"] for r in recs
                            if r["kind"] == "span"
                            and r["name"] == S.SPAN_STEP_DECODE]
                assert len(sampling) == st["steps"]
                assert sum(1 for n in sampling if n == 0) == took["greedy"]
                assert max(sampling) == (2 if temp else 0)
            finally:
                obs.reset()
        assert shares[0.0] == 1.0
        assert 0.0 < shares[0.9] < 1.0

    def test_step_k_plus_1_is_dispatched_before_step_k_is_read(
            self, lm_model, tmp_path, monkeypatch):
        """The order, without a clock: a stub step whose tokens record
        when the host first reads them."""
        import json

        from bigdl_tpu import obs
        from bigdl_tpu.obs import names
        from bigdl_tpu.serving import LMEngine, spans as S

        monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path / "trace"))
        obs.reset()
        try:
            log = []

            class Tokens:
                def __init__(self, k):
                    self.k = k

                def copy_to_host_async(self):
                    pass

                def is_ready(self):   # asked at the next dispatch
                    return False

                def __array__(self, dtype=None, copy=None):
                    log.append(("read", self.k))
                    return np.full((2,), 1 + self.k % 40, np.int32)

            def stub(params, kp, vp, tables, lengths, prev, *rest):
                k = sum(1 for what, _ in log if what == "dispatch")
                if k:   # the last step's tokens, not a host copy
                    assert isinstance(prev, Tokens) and prev.k == k - 1
                log.append(("dispatch", k))
                return kp, vp, Tokens(k)

            eng = LMEngine(lm_model, max_batch=2, page_size=4)
            eng._step_fn = stub
            reqs = [eng.submit([1, 2, 3], 7), eng.submit([4, 5], 7)]
            eng.run_until_idle(60)
            st = eng.stats()
            eng.close()
            assert st["steps"] == 6
            at = {ev: i for i, ev in enumerate(log)}
            for k in range(5):
                assert at[("dispatch", k + 1)] < at[("read", k)]
            assert sum(1 for what, _ in log if what == "read") == 6
            for r in reqs:      # step k's token, in order, none lost
                assert r.tokens[1:] == [1 + k for k in range(6)]
            assert st["steps_ahead"] == 5
            assert st["settles"] == {"preempt": 0, "swap": 0, "idle": 1,
                                     "close": 0}
            reg = obs.get_registry()
            assert reg.counter(
                names.SERVE_STEPS_AHEAD_TOTAL)._solo().value == 5
            assert reg.counter(names.SERVE_SETTLES_TOTAL, "", labels=(
                "reason",)).labels(reason="idle").value == 1
            tracer = obs.get_tracer()
            tracer.flush()
            with open(tracer.jsonl_path, encoding="utf-8") as fh:
                recs = [json.loads(line) for line in fh]
            steps = sorted((r for r in recs if r["kind"] == "span"
                            and r["name"] == S.SPAN_STEP_DECODE),
                           key=lambda r: r["wall_time"])
            assert [s["attrs"]["ahead"] for s in steps] == [0] + [1] * 5
        finally:
            obs.reset()

    def test_close_settles_what_a_lone_pump_left_in_flight(self, lm_model):
        from bigdl_tpu.serving import LMEngine

        eng = LMEngine(lm_model, max_batch=2, page_size=4)
        req = eng.submit([1, 2, 3], 5)
        assert eng.pump()              # prefill, and step 0 dispatched
        assert len(req.tokens) == 1 and eng._inflight is not None
        assert eng.stats()["steps"] == 1
        eng.close()
        assert len(req.tokens) == 2 and eng._inflight is None
        assert eng.stats()["settles"]["close"] == 1


# ------------------------------------------- a prefill is read late
class TestPrefillReadLate:
    """The cases of ``tests/late_read_cases.py`` under ``OneToken``, and
    what only a host that counts pages by hand can set up."""

    PROMPTS = [list(np.random.RandomState(n).randint(0, 48, (n,)))
               for n in (5, 7, 3, 6)]

    @pytest.fixture(scope="class")
    def late(self, lm_model):
        from bigdl_tpu.serving import LMEngine

        return late_read_cases.prepare(
            lambda **kw: LMEngine(lm_model, page_size=4, **kw),
            self.PROMPTS)

    @pytest.fixture(scope="class")
    def factory(self, late):
        return late[0]

    def test_alone_is_generate(self, lm_model, lm_params, late):
        for prompt, tokens in zip(*late[1:]):
            assert prompt + tokens == _ref(lm_model, lm_params, prompt,
                                           len(tokens))

    @pytest.mark.parametrize("case", sorted(late_read_cases.ALL_CASES))
    def test_case(self, late, case):
        late_read_cases.ALL_CASES[case](*late)

    def test_a_preemption_in_the_cycle_of_an_admission_folds_the_first_token(
            self, factory, lm_model, lm_params):
        """Four pages: A holds two and B's prompt of eight fills the
        other two, so B's first step finds no page for its row in the
        very cycle that admitted B.  The settle before the preemption
        reads B's prefill, and the fold finds B's first token."""
        eng = factory(num_pages=5)
        pa, pb = [3, 7, 11, 2, 9, 1], [5, 1, 4, 8, 8, 2, 6, 1]
        a = eng.submit(pa, 10)
        assert eng.pump() and eng.cache.free_pages() == 2
        b = eng.submit(pb, 6)
        assert eng.pump()
        assert b.preempted == 1 and list(eng._stash) == [b]
        assert len(b.tokens) == 1 and b.payload == pb + b.tokens
        assert b.max_new_tokens == 5
        st = eng.stats()
        assert st["admitted"] == 2 and st["prefills_read_late"] == 1
        assert st["settles"]["preempt"] == 1
        eng.run_until_idle(120)
        eng.close()
        for prompt, req, n in ((pa, a, 10), (pb, b, 6)):
            assert req.done and req.error is None
            assert prompt + [int(t) for t in req.tokens] == \
                _ref(lm_model, lm_params, prompt, n)
        st = eng.stats()
        # every admission but the one the settle read
        assert st["prefills_read_late"] == st["admitted"] - 1 == 2


# ----------------------------------------------- the used-page bucket
class TestDecodeBucket:
    def test_bucket_slices_tables_and_gauges_publish(self, lm_model):
        from bigdl_tpu import obs
        from bigdl_tpu.serving import LMEngine

        eng = LMEngine(lm_model, max_batch=2, page_size=8)
        r = eng.submit([1, 2, 3], 4)   # short: 1 page in use
        eng.run_until_idle(60)
        st = eng.stats()
        eng.close()
        assert r.done
        assert st["last_bucket_pages"] < eng.cache.max_pages_per_slot
        assert st["decode_ms_mean"] and st["decode_ms_mean"] > 0
        assert st["decode_hbm_bytes_per_token"] > 0
        reg = obs.get_registry()
        assert reg.gauge(
            "bigdl_serve_decode_attn_ms")._solo().value > 0
        assert reg.gauge(
            "bigdl_serve_decode_hbm_bytes_per_token")._solo().value > 0

    def test_nothing_chooses_a_decode_path(self):
        """The bucket is always on, admission continuous and the
        attention body the cache's kind's: no constructor argument, no
        config field and no environment name selects another."""
        import dataclasses
        import inspect

        from bigdl_tpu.config import ServeConfig, refresh_from_env
        from bigdl_tpu.serving import LMEngine

        gone = ("decode_attn", "decode_bucket", "admission")
        offered = set(inspect.signature(LMEngine.__init__).parameters)
        offered |= {f.name for f in dataclasses.fields(ServeConfig)}
        assert not offered & set(gone)
        text = refresh_from_env().describe() + inspect.getsource(
            inspect.getmodule(ServeConfig))
        for name in gone + ("BIGDL_SERVE_DECODE_ATTN",
                            "BIGDL_SERVE_DECODE_BUCKET",
                            "BIGDL_SERVE_ADMISSION"):
            assert name not in text, name


# ----------------------------------------------------- queue / batcher
class TestRequestQueue:
    def test_fifo_and_depth_gauge(self):
        from bigdl_tpu import obs
        from bigdl_tpu.serving import RequestQueue, ServeRequest

        q = RequestQueue(capacity=8)
        reqs = [q.submit(ServeRequest(payload=i)) for i in range(5)]
        assert q.depth() == 5
        gauge = obs.get_registry().gauge("bigdl_serve_queue_depth")
        assert gauge._solo().value == 5.0
        got = q.take(3, timeout=1.0)
        assert [r.payload for r in got] == [0, 1, 2]
        got += q.take(8, timeout=1.0)
        assert [r.payload for r in got] == [0, 1, 2, 3, 4]
        assert q.depth() == 0
        assert all(r is s for r, s in zip(got, reqs))
        q.close()

    def test_backpressure_blocks_submit(self):
        from bigdl_tpu import obs
        from bigdl_tpu.serving import RequestQueue, ServeRequest

        q = RequestQueue(capacity=1)
        waits0 = obs.get_registry().counter(
            "bigdl_serve_admission_waits_total")._solo().value
        with pytest.raises(TimeoutError):
            for i in range(5):  # no consumer: must block within 5
                q.submit(ServeRequest(payload=i), timeout=0.15)
        assert obs.get_registry().counter(
            "bigdl_serve_admission_waits_total")._solo().value > waits0
        q.close()

    def test_closed_queue_rejects(self):
        from bigdl_tpu.serving import RequestQueue, ServeRequest

        q = RequestQueue(capacity=2)
        q.close()
        with pytest.raises(RuntimeError, match="closed"):
            q.submit(ServeRequest(payload=0))


# ------------------------------------------------------ classifier tier
class TestClassifierEngine:
    def _mlp(self):
        from bigdl_tpu.common import RandomGenerator
        from bigdl_tpu.nn import Linear, LogSoftMax, ReLU, Sequential

        RandomGenerator.RNG.set_seed(7)
        return Sequential().add(Linear(16, 32)).add(ReLU()) \
            .add(Linear(32, 4)).add(LogSoftMax())

    def test_batches_match_direct_forward(self):
        from bigdl_tpu.serving import ClassifierEngine

        mod = self._mlp()
        eng = ClassifierEngine(mod, max_batch=4, batch_window_s=0.0)
        x = np.random.RandomState(0).randn(6, 16).astype(np.float32)
        reqs = [eng.submit(row) for row in x]
        while any(not r.done for r in reqs):
            eng.pump(wait_s=0.05)
        got = np.stack([r.result for r in reqs])
        want = np.asarray(mod.forward(x))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        st = eng.stats()
        assert st["requests"] == 6 and st["batches"] >= 2
        eng.close()

    def test_int8_rides_quantize_path(self):
        from bigdl_tpu.nn.quantized import QuantizedLinear
        from bigdl_tpu.serving import ClassifierEngine

        mod = self._mlp()
        want_cls = np.argmax(np.asarray(mod.forward(
            np.random.RandomState(1).randn(4, 16).astype(np.float32))),
            axis=-1)
        eng = ClassifierEngine(mod, max_batch=4, int8=True,
                               batch_window_s=0.0)
        assert any(isinstance(m, QuantizedLinear)
                   for m in eng.module.modules)
        x = np.random.RandomState(1).randn(4, 16).astype(np.float32)
        reqs = [eng.submit(row) for row in x]
        while any(not r.done for r in reqs):
            eng.pump(wait_s=0.05)
        got = np.stack([r.result for r in reqs])
        assert np.isfinite(got).all()
        # per-channel int8 on a tiny MLP: classes survive quantization
        assert (np.argmax(got, axis=-1) == want_cls).mean() >= 0.75
        eng.close()


# ----------------------------------------------------- http front-end
class TestServingServer:
    def test_generate_classify_stats_roundtrip(self, lm_model):
        import json
        import urllib.request

        from bigdl_tpu.serving import (ClassifierEngine, LMEngine,
                                       ServingServer)

        lm = LMEngine(lm_model, max_batch=2, page_size=8).start()
        clf = ClassifierEngine(TestClassifierEngine()._mlp(),
                               max_batch=2).start()
        srv = ServingServer(lm=lm, classifier=clf, port=0)
        try:
            url = f"http://127.0.0.1:{srv.port}"

            def post(path, payload):
                req = urllib.request.Request(
                    url + path, data=json.dumps(payload).encode(),
                    headers={"Content-Type": "application/json"})
                return json.loads(urllib.request.urlopen(
                    req, timeout=60).read())

            g = post("/v1/generate", {"prompt": [1, 2, 3],
                                      "max_new_tokens": 4})
            assert len(g["tokens"]) == 4 and g["e2e_s"] > 0
            c = post("/v1/classify",
                     {"inputs": np.zeros((2, 16)).tolist()})
            assert len(c["classes"]) == 2
            st = json.loads(urllib.request.urlopen(
                url + "/stats", timeout=10).read())
            assert st["lm"]["requests"] >= 1
            assert st["classifier"]["requests"] >= 2
            bad = urllib.request.Request(
                url + "/v1/generate", data=b'{"prompt": []}',
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(bad, timeout=10)
        finally:
            srv.close()
            lm.close()
            clf.close()


# ------------------------------------------ obs / autoscale loop closure
class TestServingLoopClosure:
    def test_report_serving_section(self, lm_model, tmp_path):
        from bigdl_tpu import obs
        from bigdl_tpu.obs.report import build_report, render_text
        from bigdl_tpu.serving import LMEngine

        eng = LMEngine(lm_model, max_batch=2, page_size=8, slo_s=30.0)
        reqs = [eng.submit([1 + i, 2, 3], 3) for i in range(3)]
        eng.run_until_idle(60)
        eng.close()
        assert all(r.done for r in reqs)
        obs.get_registry().write_snapshot(str(tmp_path), host_id=0)
        rep = build_report(str(tmp_path))
        sv = rep["serving"]
        assert sv is not None
        assert sv["latency"]["lm:e2e"]["count"] >= 3
        assert sv["latency"]["lm:ttft"]["p99_s"] is not None
        assert sv["latency"]["lm:per_token"]["count"] >= 3
        assert sv["tokens_total"] >= 9
        assert sv["slo_ratio"] is not None
        text = render_text(rep)
        assert "-- serving --" in text
        assert "latency lm:e2e" in text

    def test_autoscale_p99_and_queue_signals(self):
        from bigdl_tpu.resilience.autoscale import derive_signals

        buckets = [(0.05, 90.0), (0.25, 96.0), (1.0, 100.0),
                   (float("inf"), 100.0)]
        samples = [{"name": "bigdl_serve_queue_depth", "labels": {},
                    "value": 17.0}]
        for le, c in buckets:
            samples.append(
                {"name": "bigdl_request_latency_seconds_bucket",
                 "labels": {"engine": "lm", "kind": "e2e",
                            "le": "+Inf" if le == float("inf")
                            else str(le)},
                 "value": c})
        # a ttft histogram must NOT leak into the e2e p99
        samples.append({"name": "bigdl_request_latency_seconds_bucket",
                        "labels": {"engine": "lm", "kind": "ttft",
                                   "le": "+Inf"}, "value": 5.0})
        peer = {"ok": True, "addr": "h:1", "health": {},
                "metrics": {"samples": samples}}
        sig = derive_signals([peer], {}, 1)
        assert sig["queue_depth"] == 17.0
        # 99% of 100 falls in the (0.25, 1.0] bucket
        assert sig["p99_latency_s"] == 1.0

    def test_autoscale_default_rules_gain_latency_band(self):
        import dataclasses

        from bigdl_tpu.config import AutoscaleConfig
        from bigdl_tpu.resilience.autoscale import default_rules

        cfg = dataclasses.replace(AutoscaleConfig(), p99_high=0.5,
                                  p99_low=0.05, queue_high=10)
        names = [r["name"] for r in default_rules(cfg)]
        assert "latency_p99_high" in names
        assert "latency_p99_low" in names
        by = {r["name"]: r for r in default_rules(cfg)}
        assert by["latency_p99_high"]["signal"] == "p99_latency_s"
        assert by["latency_p99_high"]["action"] == "up"
        assert by["latency_p99_low"]["action"] == "down"

    def test_queue_breach_drives_decision(self):
        from bigdl_tpu.config import AutoscaleConfig
        from bigdl_tpu.resilience.autoscale import (AutoscaleController,
                                                    load_rules)
        import dataclasses

        cfg = dataclasses.replace(
            AutoscaleConfig(), queue_high=8, hysteresis=1,
            cooldown_s=0.0, dry_run=True)
        ctl = AutoscaleController(cfg=cfg, world=1,
                                  rules=load_rules(None, cfg),
                                  scrape=lambda: [])
        d = ctl.evaluate({"world": 1, "queue_depth": 20.0,
                          "alerts": [], "stragglers": []})
        assert d is not None and d.direction == "up" \
            and d.reason == "queue_high" and d.dry_run

    def test_alert_pack_serve_slo_burn(self):
        from bigdl_tpu.obs.alerts import AlertEngine, default_rules
        from bigdl_tpu.obs.metrics import MetricsRegistry

        rules = [r for r in default_rules()
                 if r["name"] == "serve_latency_slo_burn"]
        assert rules and rules[0]["type"] == "burn_rate"
        reg = MetricsRegistry()
        eng = AlertEngine(rules, registry=reg)
        # absent gauge: a non-serving run can never fire this rule
        assert eng.evaluate() == []
        reg.gauge("bigdl_serve_latency_slo_ratio").set(0.5)
        assert eng.evaluate() == []          # for: 2 debounce
        trans = eng.evaluate()
        assert [t["state"] for t in trans] == ["firing"]
        reg.gauge("bigdl_serve_latency_slo_ratio").set(1.0)
        trans = eng.evaluate()
        assert [t["state"] for t in trans] == ["resolved"]


# ---------------------------------------------- generate() cache dtype
def test_generate_cache_honors_model_dtype(lm_model, lm_params):
    """Satellite: the decode KV buffers follow the model dtype instead
    of hardcoded f32 — and a bf16 cache reproduces the f32 greedy
    tokens on this model (parity)."""
    import jax
    import jax.numpy as jnp

    prompt = np.random.RandomState(4).randint(0, 48, (2, 5))
    ref = np.asarray(lm_model.generate(lm_params, prompt, 8))
    bf = np.asarray(lm_model.generate(lm_params, prompt, 8,
                                      cache_dtype=jnp.bfloat16))
    np.testing.assert_array_equal(ref, bf)
    # the default (no cache_dtype arg) follows the model dtype: bf16
    # params must yield bf16 cache buffers, not hardcoded f32
    cast = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if hasattr(x, "dtype") and x.dtype == jnp.float32 else x,
        lm_params)
    del jax  # buffers are internal to generate(); pin via the engine
    from bigdl_tpu.serving import LMEngine

    eng = LMEngine(lm_model, params=cast, max_batch=1, page_size=8)
    assert eng.cache.kp.dtype == jnp.bfloat16
    eng.close()


def test_engine_cache_dtype_follows_params(lm_model):
    from bigdl_tpu.serving import LMEngine
    import jax.numpy as jnp

    eng = LMEngine(lm_model, max_batch=2, page_size=8,
                   cache_dtype=jnp.bfloat16)
    assert eng.cache.kp.dtype == jnp.bfloat16
    r = eng.submit([1, 2, 3], 4)
    eng.run_until_idle(60)
    eng.close()
    assert r.done and len(r.tokens) == 4
