"""One clock for host and chip (ISSUE 24): a recording tracer's live
span is also a ``jax.profiler.TraceAnnotation`` with the span's id, the
null tracer makes none; the trainer's loop and feed path and the
serving engine's loop record the spans a chip's idle gap is named by;
the engine stamps every token.  CPU, tiny sizes; every test has a time
limit of its own (``_time_limit``)."""

import glob
import json
import signal
import threading

import numpy as np
import pytest

from bigdl_tpu import obs
from bigdl_tpu.obs.trace import NULL_TRACER, Tracer

pytestmark = pytest.mark.obs

TEST_LIMIT_S = 120


@pytest.fixture(autouse=True)
def _time_limit():
    """Each test of this file is cut after TEST_LIMIT_S seconds."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def cut(signum, frame):
        raise TimeoutError(f"test ran longer than {TEST_LIMIT_S}s")

    old = signal.signal(signal.SIGALRM, cut)
    signal.alarm(TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """The obs tracer on, writing under tmp_path."""
    monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path / "trace"))
    obs.reset()
    yield obs.get_tracer()
    obs.reset()


def _records(tracer):
    tracer.flush()
    with open(tracer.jsonl_path, encoding="utf-8") as fh:
        return [json.loads(line) for line in fh]


def _spans(tracer):
    return [r for r in _records(tracer) if r["kind"] == "span"]


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


class _CountingAnnotation:
    made = []

    def __init__(self, name, **kw):
        _CountingAnnotation.made.append((name, kw))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def counting(monkeypatch):
    import jax.profiler

    _CountingAnnotation.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _CountingAnnotation)
    return _CountingAnnotation.made


# ------------------------------------------------------------- A: tracer
class TestOneClock:
    def test_span_is_an_annotation_with_its_id(self, tmp_path, counting):
        t = Tracer(str(tmp_path))
        with t.span("outer", step=3, bucket=8) as outer:
            with t.span("inner") as inner:
                pass
        assert counting == [("outer", {"id": outer, "step": 3}),
                            ("inner", {"id": inner})]
        t.close()

    def test_retroactive_spans_and_events_are_no_annotations(
            self, tmp_path, counting):
        t = Tracer(str(tmp_path))
        t.complete("feed.h2d", 0.0, 0.1, step=1)
        t.event("serve.admit", slot=0)
        t.counter("rss", bytes=1)
        assert counting == []
        t.close()

    def test_null_tracer_makes_no_annotation(self, counting, monkeypatch):
        monkeypatch.delenv("BIGDL_TRACE_DIR", raising=False)
        obs.reset()
        tracer = obs.get_tracer()
        assert tracer is NULL_TRACER
        with tracer.span("untraced", step=1) as sid:
            tracer.add_attrs(sid, more=1)
        tracer.complete("x", 0.0, 0.0)
        assert counting == []

    def test_span_lands_in_a_profiler_session_with_id_and_step(
            self, tmp_path):
        """The real thing: a profiler session someone else started
        holds the tracer's span on its host plane, and the span's
        wall_time and the annotation's start differ by one offset."""
        import jax
        from jax.profiler import ProfileData

        t = Tracer(str(tmp_path / "obs"))
        jax.profiler.start_trace(str(tmp_path / "prof"))
        try:
            ids = []
            for k in range(3):
                with t.span("one_clock.probe", step=k) as sid:
                    ids.append(sid)
                    sum(range(20000))
        finally:
            jax.profiler.stop_trace()
        spans = {s["id"]: s for s in _spans(t)}
        pb = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                       recursive=True)
        assert pb
        seen = {}
        for plane in ProfileData.from_file(pb[-1]).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "one_clock.probe":
                        stats = dict(ev.stats)
                        seen[int(stats["id"])] = (
                            int(stats["step"]), ev.start_ns, ev.duration_ns)
        assert sorted(seen) == ids
        assert [seen[i][0] for i in ids] == [0, 1, 2]
        offsets = [seen[i][1] - spans[i]["wall_time"] * 1e9 for i in ids]
        # one offset puts the tracer's clock on the profiler's: the
        # three agree to well under a millisecond
        assert max(offsets) - min(offsets) < 5e5
        for i in ids:
            assert abs(seen[i][2] * 1e-9 - spans[i]["dur_s"]) < 5e-4
        t.close()

    def test_records_stay_in_memory_until_flush(self, tmp_path):
        t = Tracer(str(tmp_path))
        with t.span("a", step=1):
            pass
        t.event("e")
        assert open(t.jsonl_path).read() == ""
        assert [r["name"] for r in t.recent()] == ["a", "e"]
        t.flush()
        t.flush()  # nothing twice
        lines = open(t.jsonl_path).read().splitlines()
        assert [json.loads(ln)["name"] for ln in lines] == ["a", "e"]
        with t.span("b"):
            pass
        t.close()
        lines = open(t.jsonl_path).read().splitlines()
        assert [json.loads(ln)["name"] for ln in lines] == ["a", "e", "b"]
        t.flush()  # after close: safe, and writes nothing
        assert len(open(t.jsonl_path).read().splitlines()) == 3

    def test_add_attrs_reaches_both_exports(self, tmp_path):
        t = Tracer(str(tmp_path))
        with t.span("serve.admission", step=4) as sid:
            t.add_attrs(sid, admitted=2)
        t.close()
        rec = json.loads(open(t.jsonl_path).read().splitlines()[0])
        assert rec["attrs"] == {"step": 4, "admitted": 2}
        doc = json.load(open(t.trace_path))
        ev = [e for e in doc["traceEvents"]
              if e["name"] == "serve.admission"][0]
        assert ev["args"] == {"step": 4, "admitted": 2}

    def test_a_named_region_is_a_tracer_span(self, traced):
        """What utils.profiler.annotate was: one path, the tracer's."""
        with obs.get_tracer().span("my_region", step=3):
            pass
        recs = [r for r in obs.get_tracer().recent()
                if r["name"] == "my_region"]
        assert len(recs) == 1 and recs[0]["kind"] == "span"
        assert recs[0]["attrs"]["step"] == 3

    def test_profiler_module_has_one_span_path(self):
        import bigdl_tpu.utils.profiler as prof

        assert not hasattr(prof, "annotate")
        assert not hasattr(prof, "_AnnotatedRegion")
        assert hasattr(prof, "trace") and hasattr(prof, "StepProfiler")


# ------------------------------------------------- B: trainer + feed path
def _toy(n, d=16, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(d, classes)
    x = rng.randn(n, d).astype(np.float32)
    y = (np.argmax(x @ w, axis=1) + 1).astype(np.float32)
    return x, y


def _mlp():
    from bigdl_tpu.nn import Linear, LogSoftMax, ReLU, Sequential

    return Sequential().add(Linear(16, 32)).add(ReLU()) \
        .add(Linear(32, 4)).add(LogSoftMax())


BATCH = 32
BATCHES_AN_EPOCH = 4


def _check_trainer_spans(spans, steps, chips):
    by = {name: _named(spans, name) for name in
          ("loss_readback", "feed.h2d", "feed.gather", "step_dispatch",
           "device_put")}
    want = list(range(1, steps + 1))
    for name in ("loss_readback", "feed.h2d", "feed.gather",
                 "step_dispatch"):
        got = sorted(s["attrs"]["step"] for s in by[name])
        assert got == want, (name, got)
    batch_bytes = BATCH * 16 * 4 + BATCH * 4
    for s in by["feed.h2d"]:
        assert s["attrs"]["bytes"] == batch_bytes
        assert s["attrs"]["chips"] == chips
    loop_tid = {s["tid"] for s in by["step_dispatch"]}
    assert len(loop_tid) == 1
    assert {s["tid"] for s in by["loss_readback"]} == loop_tid
    # the copy's end is observed off the loop's thread, the gather
    # runs on the prefetcher's
    assert not {s["tid"] for s in by["feed.h2d"]} & loop_tid
    assert not {s["tid"] for s in by["feed.gather"]} & loop_tid
    # feed.h2d starts with the put and cannot end before it returns
    puts = {s["attrs"]["step"]: s for s in by["device_put"]}
    for s in by["feed.h2d"]:
        put = puts[s["attrs"]["step"]]
        assert s["wall_time"] <= put["wall_time"]
        assert s["wall_time"] + s["dur_s"] >= \
            put["wall_time"] + put["dur_s"] - 1e-6


class TestTrainerSpans:
    def test_local_optimizer_two_epochs(self, traced):
        from bigdl_tpu.nn import ClassNLLCriterion
        from bigdl_tpu.optim import SGD, LocalOptimizer, Trigger

        x, y = _toy(BATCH * BATCHES_AN_EPOCH)
        opt = LocalOptimizer(_mlp(), (x, y), ClassNLLCriterion(),
                             batch_size=BATCH)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_epoch(2))
        opt.optimize()
        assert opt._h2d_waiter is None  # joined: nothing left running
        assert not [t for t in threading.enumerate()
                    if t.name == "bigdl-h2d-waiter"]
        _check_trainer_spans(_spans(traced), 2 * BATCHES_AN_EPOCH, chips=1)

    def test_distri_optimizer_four_virtual_devices(self, traced):
        import jax

        from bigdl_tpu.engine import Engine
        from bigdl_tpu.nn import ClassNLLCriterion
        from bigdl_tpu.optim import SGD, DistriOptimizer, Trigger

        Engine.reset()
        try:
            mesh = Engine.build_mesh({"data": 4},
                                     devices=jax.devices()[:4])
            x, y = _toy(BATCH * BATCHES_AN_EPOCH)
            opt = DistriOptimizer(_mlp(), (x, y), ClassNLLCriterion(),
                                  batch_size=BATCH, mesh=mesh)
            opt.set_optim_method(SGD(learningrate=0.1))
            opt.set_end_when(Trigger.max_iteration(6))
            opt.optimize()
        finally:
            Engine.reset()
        spans = _spans(traced)
        # the prefetcher runs ahead of the loop: it may have gathered
        # batches the six steps never trained
        trained = [s for s in spans if not (
            s["name"] in ("feed.gather", "feed.h2d")
            and s["attrs"]["step"] > 6)]
        _check_trainer_spans(trained, 6, chips=4)

    def test_untraced_run_starts_no_waiter(self, monkeypatch):
        from bigdl_tpu.nn import ClassNLLCriterion
        from bigdl_tpu.optim import SGD, LocalOptimizer, Trigger

        monkeypatch.delenv("BIGDL_TRACE_DIR", raising=False)
        obs.reset()
        seen = []
        import bigdl_tpu.optim.optimizer as mod

        monkeypatch.setattr(mod, "_H2DWaiter",
                            lambda tracer: seen.append(tracer))
        x, y = _toy(BATCH * 2)
        opt = LocalOptimizer(_mlp(), (x, y), ClassNLLCriterion(),
                             batch_size=BATCH)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_iteration(2))
        opt.optimize()
        assert seen == []

    def test_prefetch_iterator_counts_steps_from_first_step(self, traced):
        from bigdl_tpu.native import PrefetchIterator

        assert list(PrefetchIterator(iter(range(3)), first_step=7)) \
            == [0, 1, 2]
        gathers = _named(_spans(traced), "feed.gather")
        assert [s["attrs"]["step"] for s in gathers] == [7, 8, 9]


# ------------------------------------------------------ C: serving engine
@pytest.fixture(scope="module")
def lm_model():
    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.models.transformer import build_transformer_lm

    RandomGenerator.RNG.set_seed(13)
    return build_transformer_lm(48, dim=32, n_head=4, n_layer=2,
                                max_len=64, attn_impl="lax")


def _serve(lm_model, prompts, new):
    from bigdl_tpu.serving import LMEngine

    eng = LMEngine(lm_model, max_batch=2, page_size=4, num_pages=33)
    reqs = [eng.submit(p, new) for p in prompts]
    eng.run_until_idle()
    return eng, reqs


PROMPTS = [[3, 7, 11, 2, 9], [5, 1, 4], [8, 8, 2, 6, 1, 3, 7]]
# what serve.decode_step and serve.prefill are split into, in order
CHILDREN = ("serve.dispatch", "serve.wait", "serve.read")
# clock reads of a steady untraced pump with two slots: two around the
# step, one stamp a token (the same at the parent of PR 34)
UNTRACED_CLOCK_READS = 4


def _two_busy_stretches(lm_model, tracer):
    """Two busy stretches (an idle settle between them) over a pool
    small enough to preempt; the stats after ``close()`` and the
    tracer's records in the order they were made."""
    from bigdl_tpu.serving import LMEngine

    eng = LMEngine(lm_model, max_batch=2, page_size=4, num_pages=8)
    reqs = [eng.submit(p, 9) for p in PROMPTS[:2]]
    eng.run_until_idle()
    reqs += [eng.submit(p, n) for p, n in zip(PROMPTS, (14, 2, 14))]
    eng.run_until_idle()
    eng.close()
    st = eng.stats()
    assert all(r.done and r.error is None for r in reqs)
    assert st["preemptions"] >= 1 and st["settles"]["idle"] >= 2
    return st, sorted(_records(tracer), key=lambda r: r["wall_time"])


class _Result:
    """Stands in for a step's result on the device: says ``ready`` when
    asked, counts the askings of the thread that made it (the engine's;
    the stall watch's probe asks from its own thread while a first
    compile holds the loop), reads as the array it holds."""

    def __init__(self, arr, ready):
        self.arr, self.ready, self.asked = arr, ready, 0
        self._asker = threading.get_ident()

    def is_ready(self):
        self.asked += threading.get_ident() == self._asker
        return self.ready

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.arr, dtype)


class _Clock:
    """``time`` with its clock reads counted."""

    def __init__(self):
        import time

        self._time, self.reads = time, 0

    def __getattr__(self, name):
        fn = getattr(self._time, name)
        if name not in ("perf_counter", "monotonic", "time"):
            return fn

        def counted():
            self.reads += 1
            return fn()

        return counted


class TestEngineSpans:
    def test_cycle_spans_in_order_with_one_step(self, traced, lm_model):
        from bigdl_tpu.serving import spans as S

        eng, reqs = _serve(lm_model, PROMPTS, 6)
        spans = sorted(_spans(traced), key=lambda s: s["wall_time"])
        live = [s for s in spans if s["name"] in (
            S.SPAN_ADMISSION, S.SPAN_STEP_PREFILL, S.SPAN_STEP_PREP,
            S.SPAN_STEP_DECODE, S.SPAN_STEP_EMIT)]
        assert len({s["tid"] for s in live}) == 1
        preps = _named(live, S.SPAN_STEP_PREP)
        assert [s["attrs"]["step"] for s in preps] == \
            list(range(eng.stats()["steps"]))
        # prep -> decode_step -> emit, one after the other, one step;
        # an emit with no decode_step before it is a settled step's
        order = [s for s in live if s["name"] in (
            S.SPAN_STEP_PREP, S.SPAN_STEP_DECODE, S.SPAN_STEP_EMIT)]
        settled = [k for k, s in enumerate(order)
                   if s["name"] == S.SPAN_STEP_EMIT
                   and order[k - 1]["name"] != S.SPAN_STEP_DECODE]
        assert len(settled) == sum(eng.stats()["settles"].values()) >= 1
        order = [s for k, s in enumerate(order) if k not in settled]
        assert len(order) == 3 * eng.stats()["steps"]
        for k in range(0, len(order), 3):
            prep, dec, emit = order[k:k + 3]
            assert (prep["name"], dec["name"], emit["name"]) == (
                S.SPAN_STEP_PREP, S.SPAN_STEP_DECODE, S.SPAN_STEP_EMIT)
            assert prep["attrs"]["step"] == emit["attrs"]["step"]
            assert prep["attrs"]["bucket"] == dec["attrs"]["bucket"]
            assert prep["attrs"]["active"] == dec["attrs"]["active"]
            assert "step" not in dec["attrs"]  # as the benchmark reads it
            assert prep["wall_time"] + prep["dur_s"] <= \
                dec["wall_time"] + 1e-6
            assert dec["wall_time"] + dec["dur_s"] <= \
                emit["wall_time"] + 1e-6
        # admission contains its prefills and comes before the cycle's
        # prep
        admissions = _named(live, S.SPAN_ADMISSION)
        prefills = _named(live, S.SPAN_STEP_PREFILL)
        assert len(prefills) == len(PROMPTS)
        assert sum(a["attrs"]["admitted"] for a in admissions) == \
            len(PROMPTS)
        by_id = {a["id"]: a for a in admissions}
        for p in prefills:
            adm = by_id[p["parent"]]
            assert adm["attrs"]["step"] == p["attrs"]["step"]
            assert adm["wall_time"] <= p["wall_time"]
            assert p["attrs"]["bucket"] >= p["attrs"]["prompt_len"]
        assert sorted(p["attrs"]["request"] for p in prefills) == \
            sorted(r.id for r in reqs)
        prep_start = {s["attrs"]["step"]: s["wall_time"] for s in preps}
        for a in admissions:
            assert a["wall_time"] + a["dur_s"] <= \
                prep_start[a["attrs"]["step"]] + 1e-6
        # the point event of one request entering a slot is still there
        admits = _records(traced)
        assert len([r for r in admits if r["kind"] == "event"
                    and r["name"] == S.EVENT_ADMIT]) == len(PROMPTS)

    def test_one_decode_step_span_per_executed_step(self, traced,
                                                    lm_model):
        """The benchmark's readers divide by and average over the
        ``serve.decode_step`` spans: one per execution of the step
        program, with that step's bucket, however the step's tokens
        came to be read (by the next step, or by a settle)."""
        from bigdl_tpu.serving import LMEngine, spans as S

        eng = LMEngine(lm_model, max_batch=2, page_size=4, num_pages=8)
        executed = []
        fn = eng._step_fn

        def counted(params, kp, vp, tables, *rest):
            executed.append(int(tables.shape[1]))
            return fn(params, kp, vp, tables, *rest)

        eng._step_fn = counted
        # two busy stretches (an idle settle between them) and a pool
        # small enough to preempt
        reqs = [eng.submit(p, 9) for p in PROMPTS[:2]]
        eng.run_until_idle()
        reqs += [eng.submit(p, n) for p, n in zip(PROMPTS, (14, 2, 14))]
        eng.run_until_idle()
        st = eng.stats()
        eng.close()
        assert all(r.done and r.error is None for r in reqs)
        assert st["preemptions"] >= 1 and st["settles"]["idle"] >= 2
        recs = _records(traced)
        steps = sorted((r for r in recs if r["kind"] == "span"
                        and r["name"] == S.SPAN_STEP_DECODE),
                       key=lambda r: r["wall_time"])
        assert len(steps) == len(executed) == st["steps"]
        assert [s["attrs"]["bucket"] for s in steps] == executed
        assert all(1 <= s["attrs"]["active"] <= 2 for s in steps)
        # ahead is 0 exactly on the first step after a settle
        settles = [r for r in recs if r["kind"] == "event"
                   and r["name"] == S.EVENT_SETTLE]
        assert len(settles) == sum(st["settles"].values())
        assert sum(s["attrs"]["ahead"] for s in steps) == \
            st["steps_ahead"] == len(steps) - len(settles)
        assert steps[0]["attrs"]["ahead"] == 0

    def test_children_lie_inside_their_parent_in_order(self, traced,
                                                       lm_model):
        """``serve.decode_step`` is split into dispatch, wait and read:
        children of the parent, in that order, disjoint, with the
        cycle's ``step`` (a ``serve.decode_step`` has none: joined
        through the ``serve.prep`` before it).  ``serve.prefill`` holds
        its dispatch alone: a prefill's wait and read are no one's
        children, like a settled step's."""
        from bigdl_tpu.serving import spans as S

        eng, _ = _serve(lm_model, PROMPTS, 6)
        spans = sorted(_spans(traced), key=lambda s: s["wall_time"])
        kids = {}
        for s in spans:
            if s["name"] in CHILDREN:
                kids.setdefault(s["parent"], []).append(s)
        parents = [s for s in spans if s["name"] in (
            S.SPAN_STEP_DECODE, S.SPAN_STEP_PREFILL)]
        # a settled step's wait and read are no one's children, nor a
        # prefill's: they lie inside no span of the cycle at all
        st = eng.stats()
        loose = kids.pop(None)
        assert len(loose) == 2 * (sum(st["settles"].values())
                                  + st["admitted"])
        assert sum(len(v) for v in kids.values()) == \
            sum(len(kids.get(p["id"], ())) for p in parents)
        step_of = None
        for s in spans:
            if s["name"] == S.SPAN_STEP_PREP:
                step_of = s["attrs"]["step"]
            if s not in parents:
                continue
            mine = kids[s["id"]]
            prefill = s["name"] == S.SPAN_STEP_PREFILL
            names = [k["name"] for k in mine]
            # a prefill is dispatched and no more; the first step after
            # an idle engine reads nothing
            assert names == [S.SPAN_STEP_DISPATCH] if prefill or \
                not s["attrs"]["ahead"] else names == list(CHILDREN)
            end = s["wall_time"]
            for k in mine:
                assert k["tid"] == s["tid"]
                assert k["attrs"]["program"] == \
                    ("prefill" if prefill else "step")
                assert k["attrs"]["step"] == \
                    (s["attrs"]["step"] if prefill else step_of)
                assert k["wall_time"] >= end - 1e-6
                end = k["wall_time"] + k["dur_s"]
            assert end <= s["wall_time"] + s["dur_s"] + 1e-6
            assert set(mine[0]["attrs"]) == {"step", "program", "dry"}
            assert all(set(k["attrs"]) == {"step", "program"}
                       for k in mine[1:])

    def test_every_executed_step_is_waited_for_and_read_once(
            self, traced, lm_model):
        """One ``serve.dispatch`` a ``serve.decode_step``; one
        ``serve.wait`` and one ``serve.read`` an executed step, whether
        the next step read it (its ``serve.decode_step``'s children,
        with the cycle's ``step``) or a settle did (outside any, with
        the settled step's own)."""
        from bigdl_tpu.serving import spans as S

        st, recs = _two_busy_stretches(lm_model, traced)
        spans = [r for r in recs if r["kind"] == "span"]
        steps = {s["id"]: s for s in _named(spans, S.SPAN_STEP_DECODE)}
        of_step = [s for s in spans if s["name"] in CHILDREN
                   and s["attrs"]["program"] == "step"]
        dispatches = _named(of_step, S.SPAN_STEP_DISPATCH)
        assert sorted(d["parent"] for d in dispatches) == sorted(steps)
        assert [d["attrs"]["step"] for d in dispatches] == \
            list(range(st["steps"]))
        for name in (S.SPAN_STEP_WAIT, S.SPAN_STEP_READ):
            mine = _named(of_step, name)
            assert len(mine) == st["steps"]
            settled = [s for s in mine if s["parent"] not in steps]
            assert len(settled) == sum(st["settles"].values())
            # a step's child reads the step before it
            was_read = [s["attrs"]["step"] - (s["parent"] in steps)
                        for s in mine]
            assert sorted(was_read) == list(range(st["steps"]))
        # a wait is followed by its read
        order = [s["name"] for s in of_step
                 if s["name"] != S.SPAN_STEP_DISPATCH]
        assert order == [S.SPAN_STEP_WAIT, S.SPAN_STEP_READ] * st["steps"]

    def test_one_dispatch_wait_and_read_a_prefill(self, traced, lm_model):
        """A prefill is dispatched inside its ``serve.prefill`` and
        waited for and read once, late: after the cycle's
        ``serve.emit``, behind the dispatch of the cycle's step, before
        the next cycle waits for that step; ``late`` says so, and is 0
        where a settle read it with no step dispatched behind it."""
        from bigdl_tpu.serving import spans as S

        st, recs = _two_busy_stretches(lm_model, traced)
        spans = [r for r in recs if r["kind"] == "span"]
        prefills = _named(spans, S.SPAN_STEP_PREFILL)
        assert len(prefills) == st["admitted"] == 5 + st["preemptions"]
        of_prefill = [s for s in spans if s["name"] in CHILDREN
                      and s["attrs"]["program"] == "prefill"]
        for p in prefills:
            mine = [s for s in of_prefill if s["parent"] == p["id"]]
            assert [s["name"] for s in mine] == [S.SPAN_STEP_DISPATCH]
            assert mine[0]["attrs"]["dry"] in (0, 1)
        assert len(of_prefill) == 3 * len(prefills)
        # in the order of their dispatch: the reads say whose they are
        reads = _named(of_prefill, S.SPAN_STEP_READ)
        waits = _named(of_prefill, S.SPAN_STEP_WAIT)
        assert [r["attrs"]["request"] for r in reads] == \
            [p["attrs"]["request"] for p in prefills]
        of_step = [s for s in spans if s["name"] in CHILDREN
                   and s["attrs"]["program"] == "step"]
        emits = _named(spans, S.SPAN_STEP_EMIT)
        inside = [s for s in spans if s["name"] in (
            S.SPAN_STEP_DECODE, S.SPAN_STEP_PREP, S.SPAN_STEP_EMIT,
            S.SPAN_ADMISSION, S.SPAN_STEP_PREFILL)]
        late = 0
        for p, w, r in zip(prefills, waits, reads):
            k = p["attrs"]["step"]
            assert w["attrs"]["step"] == r["attrs"]["step"] == k
            assert p["wall_time"] + p["dur_s"] <= w["wall_time"] + 1e-6
            assert w["wall_time"] + w["dur_s"] <= r["wall_time"] + 1e-6
            # outside every span of the cycle
            assert w["parent"] is None and r["parent"] is None
            assert not any(
                s["wall_time"] < w["wall_time"] + 1e-7
                < s["wall_time"] + s["dur_s"] for s in inside)
            behind = [d for d in _named(of_step, S.SPAN_STEP_DISPATCH)
                      if d["attrs"]["step"] == k
                      and d["wall_time"] < w["wall_time"]]
            assert r["attrs"]["late"] == len(behind)
            if not behind:
                continue
            late += 1
            # ... after the emit of the cycle that dispatched step k
            emit, = [e for e in emits if e["attrs"]["step"] == k
                     and e["wall_time"] > behind[0]["wall_time"]][:1]
            assert emit["wall_time"] + emit["dur_s"] <= \
                w["wall_time"] + 1e-6
            # ... and before anything waits for step k: the next
            # cycle, or a settle
            of_k = [s for s in _named(of_step, S.SPAN_STEP_WAIT)
                    if s["wall_time"] > emit["wall_time"]][0]
            assert r["wall_time"] + r["dur_s"] <= of_k["wall_time"] + 1e-6
        assert late == st["prefills_read_late"] >= 5

    def test_dry_on_the_first_step_and_after_every_settle(self, traced,
                                                          lm_model):
        """With no step in flight and no prefill unread nothing the
        host launched is still running: ``dry`` is 1 wherever ``ahead``
        is 0, but behind a prefill dispatched in the same cycle."""
        from bigdl_tpu.serving import spans as S

        st, recs = _two_busy_stretches(lm_model, traced)
        spans = [r for r in recs if r["kind"] == "span"]
        steps = {s["id"]: s for s in _named(spans, S.SPAN_STEP_DECODE)}
        dispatches = [s for s in _named(spans, S.SPAN_STEP_DISPATCH)
                      if s["attrs"]["program"] == "step"]
        assert all(d["attrs"]["dry"] in (0, 1) for d in dispatches)
        first = [d for d in dispatches
                 if not steps[d["parent"]]["attrs"]["ahead"]]
        assert len(first) == sum(st["settles"].values())
        unread = {s["attrs"]["step"] for s in spans
                  if s["name"] == S.SPAN_STEP_READ
                  and s["attrs"].get("late")}
        alone = [d for d in first if d["attrs"]["step"] not in unread]
        assert alone and all(d["attrs"]["dry"] == 1 for d in alone)

    @pytest.mark.parametrize("ready", [False, True])
    def test_dry_is_what_the_result_in_flight_says(self, traced, lm_model,
                                                   ready):
        """``dry`` is the device buffers' own ``is_ready()``, asked
        before a dispatch: of the step in flight and of every prefill
        not yet read, so a step dispatched behind an unread, unfinished
        prefill does not say the chip was dry."""
        from bigdl_tpu.serving import LMEngine, spans as S

        eng = LMEngine(lm_model, max_batch=2, page_size=4, num_pages=33)
        for p in PROMPTS[:2]:
            eng.submit(p, 8)
        admit, prefilled = eng._admit, []

        def admit_and_stub(**kw):
            n = admit(**kw)
            for rec in eng._unread:
                prefilled.append(_Result(rec.result, ready))
                rec.result = prefilled[-1]
            return n

        eng._admit = admit_and_stub
        stubs = []
        while eng.pump():
            if eng._inflight is not None:
                stubs.append(_Result(eng._inflight.result, ready))
                eng._inflight.result = stubs[-1]
        eng.close()
        dispatches = [s for s in _named(_spans(traced),
                                        S.SPAN_STEP_DISPATCH)
                      if s["attrs"]["program"] == "step"]
        assert len(dispatches) == len(stubs) == eng.stats()["steps"] > 4
        # the first step has no step before it and two prefills
        assert len(prefilled) == 2 and prefilled[0].asked >= 1
        assert [d["attrs"]["dry"] for d in dispatches] == \
            [int(ready)] * len(stubs)
        # the last step's result is settled, never dispatched upon
        assert [r.asked for r in stubs] == [1] * (len(stubs) - 1) + [0]

    def test_untraced_steps_ask_nothing_and_read_no_new_clock(
            self, lm_model, monkeypatch):
        """With tracing off the split costs the no-op ``span()`` calls
        alone: the result in flight is never asked whether it is ready,
        nothing is recorded, and a steady pump (two slots, no admission)
        reads the engine's clock as often as before the split: around
        the step, and once a token."""
        from bigdl_tpu.serving import LMEngine, engine

        monkeypatch.delenv("BIGDL_TRACE_DIR", raising=False)
        obs.reset()
        clock = _Clock()
        monkeypatch.setattr(engine, "time", clock)
        try:
            assert obs.get_tracer() is NULL_TRACER
            eng = LMEngine(lm_model, max_batch=2, page_size=4,
                           num_pages=33)
            reqs = [eng.submit(p, 12) for p in PROMPTS[:2]]
            stubs, per_pump = [], []
            while True:
                before = clock.reads
                if not eng.pump():
                    break
                per_pump.append(clock.reads - before)
                if eng._inflight is not None:
                    stubs.append(_Result(eng._inflight.result, True))
                    eng._inflight.result = stubs[-1]
            eng.close()
            assert all(r.done and r.error is None for r in reqs)
            assert len(stubs) == eng.stats()["steps"] == 11
            assert all(r.asked == 0 for r in stubs)
            # pumps 2..10: both slots decoding, step k-1 emitted
            assert per_pump[2:10] == [UNTRACED_CLOCK_READS] * 8
            assert obs.get_tracer().recent() == []
        finally:
            obs.reset()

    def test_tokens_are_stamped_and_stats_report_itl(self, lm_model):
        eng, reqs = _serve(lm_model, PROMPTS, 6)
        for r in reqs:
            assert len(r.token_times) == len(r.tokens) == 6
            assert all(b >= a for a, b in
                       zip(r.token_times, r.token_times[1:]))
            assert r.token_times[-1] > r.token_times[0]
        st = eng.stats()
        gaps = np.concatenate([np.diff(r.token_times) for r in reqs])
        assert st["itl_p50_s"] == pytest.approx(np.percentile(gaps, 50))
        assert st["itl_p95_s"] == pytest.approx(np.percentile(gaps, 95))
        assert st["itl_p95_s"] >= st["itl_p50_s"] > 0.0

    def test_stats_before_any_request(self, lm_model):
        from bigdl_tpu.serving import LMEngine

        st = LMEngine(lm_model, max_batch=2, page_size=4,
                      num_pages=33).stats()
        assert st["itl_p50_s"] is None and st["itl_p95_s"] is None

    @pytest.mark.parametrize("tracing", [False, True])
    def test_greedy_tokens_still_equal_generate(self, lm_model, tracing,
                                                tmp_path, monkeypatch):
        """The scopes and spans are metadata: temperature 0 still
        bit-matches generate(), traced or not."""
        if tracing:
            monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path / "t"))
        else:
            monkeypatch.delenv("BIGDL_TRACE_DIR", raising=False)
        obs.reset()
        try:
            params = lm_model.params()
            _, reqs = _serve(lm_model, PROMPTS, 6)
            for prompt, req in zip(PROMPTS, reqs):
                ref = list(np.asarray(lm_model.generate(
                    params, np.asarray(prompt)[None, :], 6))[0])
                assert list(prompt) + [int(t) for t in req.tokens] == ref
        finally:
            obs.reset()

    def test_step_programs_carry_the_scopes(self, lm_model):
        """kv_write, attn, dense and sample name the operations of the
        decode step and of prefill in the lowered program."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.serving import LMEngine

        eng = LMEngine(lm_model, max_batch=2, page_size=4, num_pages=33)
        tables, lengths = eng.cache.device_tables()
        z = jnp.zeros((2,), jnp.int32)
        no = jnp.zeros((2,), bool)
        step_txt = eng._step_fn.lower(
            eng.weights(), eng.cache.kp, eng.cache.vp, tables, lengths, z,
            jnp.zeros((2,), jnp.float32), no,
            jax.random.key(0)).as_text(debug_info=True)
        pre_txt = eng._prefill_fn(8).lower(
            eng.weights(), eng.cache.kp, eng.cache.vp,
            jnp.zeros((1, 8), jnp.int32), 5, jnp.zeros((2,), jnp.int32),
            0.0, jax.random.key(0), np.int32(0),
            z).as_text(debug_info=True)
        for scope in ("kv_write", "attn", "dense", "sample"):
            assert f"/{scope}/" in step_txt, scope
            assert f"/{scope}/" in pre_txt, scope
