"""The stall watch (ISSUE 50, ``bigdl_tpu/obs/prof.py``): a minded
loop's span boundaries are its heartbeat, a pause between two of them
becomes one ``obs.stall`` span that says where every thread stood and a
``cause``, once a second the loop gets an ``obs.host`` span, and with
tracing off none of it exists.  CPU, tiny sizes, limits and ticks passed
to the constructor; every test has a time limit of its own."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from bigdl_tpu import obs
from bigdl_tpu.obs import prof, report, trace
from bigdl_tpu.obs.trace import NULL_TRACER, NullTracer

pytestmark = pytest.mark.obs

TEST_LIMIT_S = 120
NULL_SPAN_WAS = NullTracer.span
SCHEDSTAT = os.path.exists(
    f"/proc/self/task/{threading.get_native_id()}/schedstat")
# every attribute an ``obs.stall`` carries whatever the stall (``step``
# needs a span that has one, ``chip_idle`` a probe, the two schedstat
# times the kernel's file)
STALL_ATTRS = {"loop", "tid", "stall", "phase", "span", "frame",
               "frames_distinct", "loop_state", "proc_cpu_ms", "busiest",
               "busiest_cpu_ms", "watch_late_ms", "gc_ms", "compiles",
               "compile_ms", "samples", "cause"}


@pytest.fixture(autouse=True)
def _time_limit():
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
    monkeypatch.delenv("BIGDL_PROF_HZ", raising=False)
    obs.reset()
    yield obs.get_tracer()
    obs.reset()


@pytest.fixture
def watch(traced):
    """A watch of its own on the tracer: 5 ms ticks, 50 ms limit."""
    w = prof.StallWatch(traced, tick_s=0.005, limits={"loop": 0.05})
    yield w
    w.close()
    assert not _watch_threads()


def _watch_threads(gone_within=0.0):
    """The live watch threads; ``gone_within`` waits that long for them
    to end (one ends within a tick of its last loop's drop)."""
    deadline = time.time() + gone_within
    while True:
        alive = [t for t in threading.enumerate()
                 if t.name == "bigdl-stallwatch" and t.is_alive()]
        if not alive or time.time() >= deadline:
            return alive
        time.sleep(0.005)


def _records(tracer, name):
    tracer.flush()
    with open(tracer.jsonl_path, encoding="utf-8") as fh:
        return [r for r in map(json.loads, fh) if r["name"] == name]


def _beat(tracer, seconds, step=0):
    """A loop that is well: a span every 5 ms."""
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        with tracer.span("cycle", step=step):
            time.sleep(0.005)


def _stalls(tracer, handle, phase=None, settle=0.1):
    """Let the watch write up what it saw, stop minding, read: the
    stalls inside spans named ``phase`` (this machine has pauses of its
    own, which fall in the beating ``cycle`` spans or between them),
    or all of them."""
    _beat(tracer, settle)
    handle.drop()
    return [s for s in _records(tracer, "obs.stall")
            if phase is None or s["attrs"]["phase"] == phase]


def _nap(seconds):
    time.sleep(seconds)


def _collect_for(watch, seconds):
    """Tell ``watch`` of a collection that takes ``seconds``, by the
    callback the collector itself calls (which is kept from running
    meanwhile: its runs do not nest)."""
    import gc

    was = gc.isenabled()
    gc.disable()
    try:
        watch._on_gc("start", {})
        _nap(seconds)
        watch._on_gc("stop", {})
    finally:
        if was:
            gc.enable()


def _spin(seconds):
    """Work on a core that leaves the interpreter lock alone (so the
    watch wakes on time whatever the machine's load)."""
    import hashlib

    buf = bytes(1 << 20)
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        hashlib.sha256(buf).digest()


# ------------------------------------------------------- the causes, live
class TestCauses:
    def test_a_sleep_in_a_span_reads_blocked(self, traced, watch):
        h = watch.add("loop")
        _beat(traced, 0.1)
        with traced.span("work", step=7) as sid:
            _nap(0.3)
        stalls = _stalls(traced, h, "work")
        assert len(stalls) == 1
        s, a = stalls[0], stalls[0]["attrs"]
        assert 0.3 <= s["dur_s"] < 1.5
        assert STALL_ATTRS <= set(a)
        assert a["loop"] == "loop" and a["cause"] == "blocked"
        assert a["phase"] == "work" and a["span"] == sid and a["step"] == 7
        assert a["frame"] == "test_stallwatch.py:_nap"
        assert a["frames_distinct"] == 1 and a["loop_state"] == "S"
        assert a["samples"] >= 5 and a["compiles"] == 0
        assert "chip_idle" not in a   # no probe
        if SCHEDSTAT:
            assert a["loop_cpu_ms"] < 150 and a["loop_runq_ms"] < 150
        # retroactive, on the watch's own line, naming the loop's
        loop_tid = _records(traced, "work")[0]["tid"]
        assert a["tid"] == loop_tid and s["tid"] != loop_tid
        # the samples carry the stall's number, every thread's stack
        # folded, and the loop thread's marked
        samples = [r for r in _records(traced, "obs.stall.sample")
                   if r["attrs"]["stall"] == a["stall"]]
        assert len(samples) == a["samples"]
        assert [r["attrs"]["n"] for r in samples] == \
            list(range(1, len(samples) + 1))
        for r in samples:
            assert r["kind"] == "event"
            assert s["wall_time"] <= r["wall_time"] <= \
                s["wall_time"] + s["dur_s"] + 0.05
            mine = [st for st in r["attrs"]["stacks"] if st["loop"]]
            assert len(mine) == 1 and mine[0]["phase"] == "work"
            assert mine[0]["stack"][-1] == "test_stallwatch.py:_nap"
        table = [r for r in _records(traced, "obs.stall.threads")
                 if r["attrs"]["stall"] == a["stall"]]
        assert len(table) == 1 and table[0]["attrs"]["rows"]
        assert table[0]["attrs"]["rows"][0][0] == threading.get_native_id()
        # an operator's counters
        reg = obs.get_registry()
        every = _records(traced, "obs.stall")
        fam = reg.counter("bigdl_stalls_total", labels=("loop", "cause"))
        assert fam.labels(loop="loop", cause="blocked").value == \
            sum(r["attrs"]["cause"] == "blocked" for r in every) >= 1
        secs = reg.counter("bigdl_stalled_seconds_total", labels=("loop",))
        assert secs.labels(loop="loop").value == \
            pytest.approx(sum(r["dur_s"] for r in every))

    @pytest.mark.skipif(not SCHEDSTAT, reason="the kernel keeps no "
                        "schedstat: busy cannot be told from blocked")
    def test_work_on_a_core_reads_busy(self, traced, watch):
        h = watch.add("loop")
        _beat(traced, 0.1)
        with traced.span("work"):
            _spin(0.4)
        (s,) = _stalls(traced, h, "work")
        a = s["attrs"]
        # (on a crowded machine the thread may wait for its core)
        assert a["cause"] in ("busy", "starved")
        assert a["loop_cpu_ms"] + a["loop_runq_ms"] >= 500 * s["dur_s"]
        assert a["frame"] == "test_stallwatch.py:_spin"
        assert "step" not in a   # the span had none

    def test_a_first_jit_reads_compile(self, traced, watch):
        import jax
        import jax.numpy as jnp

        def fresh(x):
            for i in range(40):
                x = jnp.tanh(x @ x + i)
            return x

        x = jnp.ones((8, 8))
        x.block_until_ready()
        h = watch.add("loop")
        _beat(traced, 0.1)
        with traced.span("step_dispatch", step=1):
            jax.jit(fresh)(x).block_until_ready()
        stalls = _stalls(traced, h, "step_dispatch")
        assert len(stalls) == 1
        a = stalls[0]["attrs"]
        assert a["cause"] == "compile" and a["compiles"] >= 1
        assert a["compile_ms"] >= 200 * stalls[0]["dur_s"]
        assert a["phase"] == "step_dispatch"

    def test_the_collector_reads_gc(self, traced, watch):
        h = watch.add("loop")
        _beat(traced, 0.1)
        with traced.span("work"):
            _collect_for(watch, 0.2)
        (s,) = _stalls(traced, h, "work")
        assert s["attrs"]["cause"] == "gc"
        assert 150 <= s["attrs"]["gc_ms"] <= 1e3 * s["dur_s"]
        # and the real collector reaches the same callback while the
        # watch's thread lives
        h = watch.add("loop")
        _beat(traced, 0.05)
        before = len(watch._gc_runs)
        import gc

        gc.collect()
        assert len(watch._gc_runs) == before + 1
        h.drop()

    def test_a_stopped_process_reads_process_stopped(self, traced, watch):
        """SIGSTOP from a helper, SIGCONT a second later: the watch
        slept through it too, and says so."""
        h = watch.add("loop")
        _beat(traced, 0.1)
        helper = subprocess.Popen([sys.executable, "-c", (
            "import os, signal, sys, time\n"
            "pid = int(sys.argv[1])\n"
            "time.sleep(0.3)\n"
            "os.kill(pid, signal.SIGSTOP)\n"
            "try:\n"
            "    time.sleep(1.0)\n"
            "finally:\n"
            "    os.kill(pid, signal.SIGCONT)\n"), str(os.getpid())])
        try:
            _beat(traced, 2.5)
        finally:
            helper.wait(timeout=30)
            os.kill(os.getpid(), signal.SIGCONT)
        stalls = [s for s in _stalls(traced, h) if s["dur_s"] >= 0.5]
        assert len(stalls) == 1
        s, a = stalls[0], stalls[0]["attrs"]
        assert a["cause"] == "process_stopped"
        assert 0.8 <= s["dur_s"] <= 3.0
        assert 500 * s["dur_s"] <= a["watch_late_ms"] <= 1e3 * s["dur_s"] + 50
        assert a["phase"] in ("cycle", "") and a["samples"] <= 2
        assert STALL_ATTRS <= set(a)    # whatever the watch saw of it
        # nothing of the process ran meanwhile
        assert a["proc_cpu_ms"] < 300

    def test_a_session_started_meanwhile_reads_profiler(self, traced, watch,
                                                        tmp_path):
        """A profiler session that starts while the loop stands still is
        the harness's doing, whatever else the stall looks like."""
        import jax

        h = watch.add("loop")
        _beat(traced, 0.1)
        with traced.span("work"):
            jax.profiler.start_trace(str(tmp_path / "session"))
            _nap(0.1)
        try:
            (s,) = _stalls(traced, h, "work")
        finally:
            jax.profiler.stop_trace()
        assert s["attrs"]["cause"] == "profiler"


@pytest.mark.parametrize("facts, cause", [
    (dict(profiler=True, compiles=3, compile_ms=900.0), "profiler"),
    (dict(compiles=1, compile_ms=200.0, gc_ms=600.0), "compile"),
    (dict(compiles=1, compile_ms=199.0, gc_ms=500.0), "gc"),
    (dict(gc_ms=499.0, watch_late_ms=500.0, proc_cpu_ms=499.0,
          loop_runq_ms=900.0), "process_stopped"),
    # late while the process burns CPU: a thread held the interpreter
    (dict(watch_late_ms=900.0, proc_cpu_ms=500.0, loop_cpu_ms=600.0),
     "busy"),
    (dict(watch_late_ms=900.0, proc_cpu_ms=4000.0, loop_cpu_ms=10.0),
     "blocked"),
    (dict(watch_late_ms=499.0, loop_runq_ms=500.0, loop_cpu_ms=500.0),
     "starved"),
    (dict(loop_runq_ms=499.0, loop_cpu_ms=500.0), "busy"),
    (dict(loop_runq_ms=499.0, loop_cpu_ms=499.0), "blocked"),
    (dict(loop_runq_ms=None, loop_cpu_ms=None), "blocked"),
])
def test_the_rule_of_cause_first_match_wins(facts, cause):
    """A stall of one second, by the rule ``prof.py``'s docstring gives."""
    base = dict(profiler=False, compiles=0, compile_ms=0.0, gc_ms=0.0,
                watch_late_ms=0.0, proc_cpu_ms=0.0, loop_runq_ms=0.0,
                loop_cpu_ms=0.0)
    assert prof.stall_cause(1.0, **dict(base, **facts)) == cause
    assert cause in prof.CAUSES


# ------------------------------------------------ the watch's own conduct
class TestWatch:
    def test_at_most_so_many_samples_a_stall(self, traced):
        w = prof.StallWatch(traced, tick_s=0.002, max_samples=5,
                            limits={"loop": 0.03})
        try:
            h = w.add("loop")
            _beat(traced, 0.05)
            with traced.span("work"):
                _nap(0.3)
            (s,) = _stalls(traced, h, "work")
            assert s["attrs"]["samples"] == 5
            assert len([r for r in _records(traced, "obs.stall.sample")
                        if r["attrs"]["stall"] == s["attrs"]["stall"]]) == 5
        finally:
            w.close()
        assert prof.MAX_STALL_SAMPLES == 40
        assert prof.DENSE_SAMPLES < prof.MAX_STALL_SAMPLES

    def test_a_quiet_span_is_no_stall(self, traced, watch):
        h = watch.add("loop", quiet=("validation", "checkpoint"))
        _beat(traced, 0.05)
        with traced.span("validation", step=3):
            _nap(0.15)
        with traced.span("iteration", step=4):
            with traced.span("checkpoint", step=4):
                with traced.span("checkpoint.write"):
                    _nap(0.15)
        assert not [s for s in _stalls(traced, h)
                    if s["attrs"]["phase"] != "cycle"]
        assert not [r for r in _records(traced, "obs.stall.sample")
                    if any(st["phase"] != "cycle" for st in
                           r["attrs"]["stacks"] if st["loop"])]
        # the same pause in any other span is one
        h = watch.add("loop", quiet=("validation", "checkpoint"))
        with traced.span("iteration", step=5):
            _nap(0.15)
        (s,) = _stalls(traced, h, "iteration")
        assert s["attrs"]["frame"] == "test_stallwatch.py:_nap"

    def test_between_spans_the_phase_is_empty(self, traced, watch):
        h = watch.add("loop")
        _beat(traced, 0.05)
        _nap(0.15)
        (s,) = [s for s in _stalls(traced, h, "")
                if s["attrs"].get("frame") == "test_stallwatch.py:_nap"]
        assert s["attrs"]["span"] is None and "step" not in s["attrs"]

    def test_host_span_once_a_second(self, traced):
        w = prof.StallWatch(traced, tick_s=0.005, host_every_s=0.2,
                            limits={"loop": 0.1})
        try:
            h = w.add("loop")
            _beat(traced, 0.9)
            h.drop()
        finally:
            w.close()
        hosts = _records(traced, "obs.host")
        assert 3 <= len(hosts) <= 4
        loop_tid = _records(traced, "cycle")[0]["tid"]
        for r in hosts:
            a = r["attrs"]
            assert r["kind"] == "span" and r["tid"] != loop_tid
            assert a["loop"] == "loop" and a["tid"] == loop_tid
            assert 0.2 <= r["dur_s"] < 0.5
            assert 10 <= a["ticks"] <= r["dur_s"] / 0.005 + 1
            assert 0.0 <= a["late_ms_max"] <= a["late_ms_sum"]
            assert a["proc_cpu_ms"] >= 0 and a["nivcsw"] >= 0
            assert a["gc_ms"] >= 0
            assert ("loop_runq_ms" in a) == SCHEDSTAT and "loop_cpu_ms" in a
        assert prof.HOST_EVERY_S == 1.0 and prof.TICK_S == 0.02

    def test_no_schedstat_leaves_the_run_queue_wait_out(self, traced,
                                                        monkeypatch):
        """A sandboxed kernel (the chip's machines): the loop thread's
        CPU comes from its own clock, its wait for a core from nowhere."""
        real = os.open
        monkeypatch.setattr(os, "open", lambda p, *a, **k: (_ for _ in ()
                            ).throw(FileNotFoundError(p))
                            if str(p).endswith("schedstat")
                            else real(p, *a, **k))
        w = prof.StallWatch(traced, tick_s=0.005, host_every_s=0.1,
                            limits={"loop": 0.05})
        try:
            h = w.add("loop")
            assert h.fd is None
            _beat(traced, 0.15)
            with traced.span("work"):
                _nap(0.15)
            (s,) = _stalls(traced, h, "work", settle=0.15)
        finally:
            w.close()
        assert "loop_runq_ms" not in s["attrs"]
        assert 0.0 <= s["attrs"]["loop_cpu_ms"] < 100
        assert s["attrs"]["cause"] == "blocked"
        hosts = _records(traced, "obs.host")
        assert hosts and all("loop_runq_ms" not in r["attrs"]
                             and r["attrs"]["loop_cpu_ms"] >= 0
                             and "ticks" in r["attrs"] for r in hosts)

    def test_the_thread_lives_while_a_loop_is_minded(self, traced):
        w = prof.get_watch()
        assert isinstance(w, prof.StallWatch) and w.tracer is traced
        assert prof.get_watch() is w and not _watch_threads()
        h = w.add("serve")
        assert h.beat.limit == prof.LIMITS["serve"] == 0.1
        assert prof.LIMITS["train"] == 0.4
        assert len(_watch_threads()) == 1
        assert trace._BEATS[threading.get_ident()] is h.beat
        h.drop()
        h.drop()    # idempotent
        assert threading.get_ident() not in trace._BEATS
        assert not _watch_threads(gone_within=2)
        # a new tracer, a new watch; the old one minds nothing
        obs.reset()
        assert prof.get_watch() is not w

    def test_profiler_and_watch_share_one_walker(self, traced, monkeypatch):
        """``BIGDL_PROF_HZ`` and the watch together: both walk through
        ``prof._stacks``, and the profiler's snapshot is what it was."""
        walked = []
        real = prof._stacks

        def counting(me):
            walked.append(threading.current_thread().name)
            return real(me)

        monkeypatch.setattr(prof, "_stacks", counting)
        monkeypatch.setenv("BIGDL_PROF_HZ", "200")
        p = prof.get_profiler()
        w = prof.StallWatch(traced, tick_s=0.005, limits={"loop": 0.05})
        try:
            h = w.add("loop")
            _beat(traced, 0.1)
            with traced.span("work"):
                _nap(0.2)
            (s,) = _stalls(traced, h, "work")
        finally:
            w.close()
        assert {"bigdl-prof", "bigdl-stallwatch"} <= set(walked)
        snap = p.snapshot()
        assert set(snap) == {"enabled", "hz", "budget", "samples",
                             "skipped", "overhead_ratio", "stacks",
                             "phases", "collapsed"}
        assert snap["samples"] > 0
        frames = dict(snap["phases"]["work"]["frames"])
        assert "test_stallwatch.py:_nap" in frames
        assert any(line.startswith("work;") and
                   "test_stallwatch.py:_nap " in line
                   for line in snap["collapsed"])
        assert s["attrs"]["frame"] == "test_stallwatch.py:_nap"


# ------------------------------------------------------- the two loops
@pytest.fixture(scope="module")
def lm_model():
    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.models.transformer import build_transformer_lm

    RandomGenerator.RNG.set_seed(13)
    return build_transformer_lm(48, dim=32, n_head=4, n_layer=2,
                                max_len=64, attn_impl="lax")


PROMPTS = [[3, 7, 11, 2, 9], [5, 1, 4]]


def _engine(lm_model):
    from bigdl_tpu.serving import LMEngine

    return LMEngine(lm_model, max_batch=2, page_size=4, num_pages=33)


def _wait(reqs, seconds=60):
    deadline = time.time() + seconds
    while not all(r.done for r in reqs) and time.time() < deadline:
        time.sleep(0.01)
    assert all(r.done and r.error is None for r in reqs)


class TestEngine:
    def test_an_idle_engine_is_no_stall(self, traced, lm_model):
        eng = _engine(lm_model).start()
        try:
            time.sleep(3 * prof.LIMITS["serve"])
            # never had work: never minded, no thread
            assert eng._minded is None and not _watch_threads()
            _wait([eng.submit(p, 6) for p in PROMPTS])
            time.sleep(0.05)
            warm = len(_records(traced, "obs.stall"))   # first compiles
            time.sleep(3 * prof.LIMITS["serve"])
            assert eng._minded is None
            assert len(_records(traced, "obs.stall")) == warm
        finally:
            eng.close()
        assert not _watch_threads(gone_within=2)

    def test_a_held_step_is_a_stall_of_the_serve_loop(self, traced,
                                                      lm_model):
        eng = _engine(lm_model).start()
        try:
            _wait([eng.submit(p, 4) for p in PROMPTS])   # compiled
            step, calls = eng._step, []

            def held_step():
                calls.append(1)
                if len(calls) == 3:
                    _nap(0.3)
                return step()

            eng._step = held_step
            _wait([eng.submit(p, 10) for p in PROMPTS])
            time.sleep(0.1)
        finally:
            eng.close()
        stalls = [s for s in _records(traced, "obs.stall")
                  if s["attrs"].get("frame") == "test_stallwatch.py:_nap"]
        assert len(stalls) == 1
        s, a = stalls[0], stalls[0]["attrs"]
        assert a["loop"] == "serve" and a["cause"] == "blocked"
        assert 0.3 <= s["dur_s"] < 1.5
        assert 0.0 <= a["chip_idle"] <= 1.0
        assert a["phase"] == ""     # between admission and prep
        engine_tid = {r["tid"] for r in _records(traced,
                                                 "serve.decode_step")}
        assert engine_tid == {a["tid"]} and s["tid"] != a["tid"]
        hosts = _records(traced, "obs.host")
        assert all(r["attrs"]["loop"] == "serve" for r in hosts)
        assert eng._minded is None

    def test_tracing_off_there_is_no_watch(self, lm_model, monkeypatch):
        """``BIGDL_TRACE_DIR`` unset: no watch is built, asked for or
        fed, by either loop; no thread; the null tracer is what it
        was."""
        from bigdl_tpu.nn import ClassNLLCriterion
        from bigdl_tpu.optim import SGD, LocalOptimizer, Trigger

        monkeypatch.delenv("BIGDL_TRACE_DIR", raising=False)
        obs.reset()

        def never(*a, **k):
            raise AssertionError("the stall watch was touched untraced")

        monkeypatch.setattr(prof, "get_watch", never)
        monkeypatch.setattr(prof.StallWatch, "__init__", never)
        monkeypatch.setattr(prof, "native_threads", never)
        monkeypatch.setattr(prof, "_read_schedstat", never)
        monkeypatch.setattr(trace.Beat, "mark", never)
        try:
            assert obs.get_tracer() is NULL_TRACER
            eng = _engine(lm_model)
            monkeypatch.setattr(eng, "_mind", never)
            monkeypatch.setattr(eng, "_chip_ready", never)
            reqs = [eng.submit(p, 6) for p in PROMPTS]
            eng.run_until_idle()
            eng.close()
            assert all(r.done and r.error is None for r in reqs)
            x, y = _toy(64)
            opt = LocalOptimizer(_mlp(), (x, y), ClassNLLCriterion(),
                                 batch_size=32)
            opt.set_optim_method(SGD(learningrate=0.1))
            opt.set_end_when(Trigger.max_epoch(1))
            opt.optimize()
            assert not _watch_threads(gone_within=2) and not trace._BEATS
            assert prof._watch is prof.NULL_WATCH
            assert NullTracer.span is NULL_SPAN_WAS
            assert NULL_TRACER.span("x", step=1) is trace._NULL_SPAN
        finally:
            obs.reset()


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


class TestTrainer:
    def test_validation_and_checkpoint_are_no_stall(self, traced, tmp_path,
                                                    monkeypatch):
        """The trainer's loop is minded under ``train``; a slow
        validation and a slow checkpoint are no stall, the same pause in
        ``device_put`` is one."""
        from bigdl_tpu.nn import ClassNLLCriterion
        from bigdl_tpu.optim import (SGD, LocalOptimizer, Top1Accuracy,
                                     Trigger)

        monkeypatch.setitem(prof.LIMITS, "train", 0.05)
        prof.reset_profiler()   # a watch with the limit above
        x, y = _toy(128)
        opt = LocalOptimizer(_mlp(), (x, y), ClassNLLCriterion(),
                             batch_size=32)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_epoch(2))
        opt.set_validation(Trigger.every_epoch(), (x, y), [Top1Accuracy()],
                           batch_size=32)
        opt.set_checkpoint(str(tmp_path / "ckpt"), Trigger.every_epoch())
        slowed = []

        def slow(name, fn, when=lambda: True):
            def wrapper(*a, **k):
                if when():
                    slowed.append(name)
                    _nap(0.15)
                return fn(*a, **k)
            return wrapper

        puts = []
        opt._run_validation = slow("validation", opt._run_validation)
        opt._checkpoint = slow("checkpoint", opt._checkpoint)
        opt._put_batch = slow(
            "device_put", opt._put_batch,
            when=lambda: puts.append(1) or len(puts) == 6)
        opt.optimize()
        assert sorted(set(slowed)) == ["checkpoint", "device_put",
                                       "validation"]
        assert slowed.count("validation") == slowed.count("checkpoint") == 2
        stalls = _records(traced, "obs.stall")
        assert all(s["attrs"]["loop"] == "train" for s in stalls)
        naps = [s for s in stalls
                if s["attrs"].get("frame") == "test_stallwatch.py:_nap"]
        assert [s["attrs"]["phase"] for s in naps] == ["device_put"]
        assert naps[0]["attrs"]["cause"] == "blocked"
        assert "step" in naps[0]["attrs"]
        assert not {"validation", "checkpoint", "build_train_step"} & \
            {s["attrs"]["phase"] for s in stalls}
        # what else stood still here is the first step's compile
        assert {s["attrs"]["cause"] for s in stalls} <= set(prof.CAUSES)
        assert not trace._BEATS and not _watch_threads(gone_within=2)


# ---------------------------------------------------------- the report
def test_report_lists_stalls_by_cause(traced, watch):
    h = watch.add("loop")
    _beat(traced, 0.05)
    for step in (1, 2):
        with traced.span("work", step=step):
            _nap(0.1 * step)
        _beat(traced, 0.03)
    with traced.span("work", step=3):
        _collect_for(watch, 0.12)
    assert len(_stalls(traced, h, "work")) == 3
    every = _records(traced, "obs.stall")
    rep = report.build_report(os.path.dirname(traced.jsonl_path))
    by = rep["stalls"]["by_cause"]
    assert {"loop blocked", "loop gc"} <= set(by)
    for key, (n, seconds) in by.items():
        mine = [s for s in every
                if f"loop {s['attrs']['cause']}" == key]
        assert n == len(mine) and n >= (2 if key == "loop blocked" else 1)
        assert seconds == pytest.approx(sum(s["dur_s"] for s in mine),
                                        abs=1e-4)
    longest = rep["stalls"]["longest"]
    assert [s["dur_s"] for s in longest] == \
        sorted((round(s["dur_s"], 6) for s in every), reverse=True)[:8]
    assert {(s["phase"], s["step"]) for s in longest} >= \
        {("work", 1), ("work", 2), ("work", 3)}
    json.dumps(rep, default=str)    # the --json form
    text = report.render_text(rep)
    assert "-- stalls (a minded loop stood still) --" in text
    assert f"loop blocked: {by['loop blocked'][0]} stall(s)" in text
    assert "in work step 2: blocked, S in test_stallwatch.py:_nap" in text
    assert "slow step" not in text
