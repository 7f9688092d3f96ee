"""Continuous sampling profiler, and the stall watch.

The observability stack can *detect* trouble (alerts, request traces,
fleet rollups) but could not answer "what was the process actually
doing when the alert fired" — by the time a human attaches a profiler,
the p99 spike is gone.  This module is the standard closing move: a
daemon thread walks ``sys._current_frames()`` at ``BIGDL_PROF_HZ`` and
folds every thread's stack into a bounded collapsed-stack table, so a
profile is *always* available — to ``GET /profilez``, to the debug
bundles (obs/bundle.py), and to the report's "profiles" section.

Two properties make it safe to leave on in production:

* **Span attribution.**  Each sampled stack is prefixed with the
  innermost live span name of its thread (the tracer's per-thread
  phase stack, :func:`bigdl_tpu.obs.trace.current_phase`), so output
  reads ``serve.decode_step;engine.py:_step;...  61`` — "the decode
  step spends 61% here" — not anonymous frames.  Threads outside any
  span fold under ``(no span)``.
* **Hard overhead cap.**  The cumulative sampling-work ratio
  (seconds spent walking/folding / wall seconds) is published as
  ``bigdl_prof_overhead_ratio`` and checked *before* every sample:
  over ``BIGDL_PROF_BUDGET`` the sample is skipped (and counted in
  ``bigdl_prof_skipped_total``) until the ratio recovers.  A
  misconfigured 10 kHz profiler degrades to the budget, never past it.

Off by default: ``BIGDL_PROF_HZ`` unset/<=0 yields the shared
:data:`NULL_PROFILER` — no thread, no clock reads, the disabled path
is one config read (the same null-object contract as NULL_TRACER).

**The stall watch** (:class:`StallWatch`) answers the other question a
folded table cannot: what every thread was doing while a loop stood
still for THESE two seconds.  It has no switch of its own: it exists
while the tracer records (``BIGDL_TRACE_DIR``) and a loop has asked to
be minded (:func:`get_watch` ``.add``: the serving engine while it has
work, the trainer around its loop).  With ``NULL_TRACER`` there is no
watch, no thread, no clock read and no call on any step's path.

* The heartbeat is the span log's own (``obs/trace.py`` ``Beat``): the
  minded thread's ``Tracer.span`` keeps the instant of each boundary
  and writes down, itself, any two boundaries further apart than the
  loop's limit (``LIMITS``: 0.1 s for the engine's cycle, 0.4 s for the
  trainer's; a loop's ``quiet`` spans, the trainer's ``validation``,
  ``checkpoint`` and ``build_train_step``, are no stall).
* One daemon thread wakes every ``TICK_S`` (20 ms), notes how late it
  woke, from wake-up to wake-up (the time a freshly woken thread waits
  for the interpreter and a core), and reads the loop thread's
  ``schedstat``: about 10 us of work a tick, 50-70 us of CPU with the
  wake-up itself.  Once a second a minded loop gets a retroactive span
  ``obs.host``: ``loop``, ``tid`` (the loop's line), ``ticks``,
  ``late_ms_sum``, ``late_ms_max``, ``loop_cpu_ms`` and
  ``loop_runq_ms`` (the loop's native thread on a core, and runnable
  but waiting for one: ``schedstat``; where ``/proc`` has none, as in
  a sandboxed kernel, the first is the thread's CPU clock and the
  second is left out),
  ``proc_cpu_ms`` (all threads), ``nivcsw`` (involuntary switches),
  ``gc_ms``, ``steal_ms`` (the machine's cores' time a hypervisor gave
  to someone else: ``/proc/stat``; left out where it has none).
* While a beat is older than its limit the watch samples at every tick
  (after ``DENSE_SAMPLES`` every ``SPARSE_EVERY`` ticks, at most
  ``MAX_STALL_SAMPLES`` a stall): every Python thread's stack with its
  live span, folded by equal stacks, as an ``obs.stall.sample`` event;
  the loop thread's state; the loop's probe (the engine's: is the
  program in flight ready, asked without blocking).  When the beat
  returns: the native threads' table (``/proc/self/task/*``) as an
  ``obs.stall.threads`` event and one retroactive span ``obs.stall``
  from the last beat to the next, with ``loop``, ``tid``, ``stall``
  (its number, which its events carry too), ``step`` (where the span
  has one), ``phase`` (innermost span of the loop's thread, ``""``
  between spans), ``span`` (its id), ``frame`` (the loop thread's leaf
  ``file.py:func`` in most samples), ``frames_distinct``,
  ``loop_state`` (R / S / D in most samples), ``loop_cpu_ms``,
  ``loop_runq_ms``, ``proc_cpu_ms``, ``busiest`` / ``busiest_cpu_ms``
  (the native thread other than the loop's with most CPU since the
  stall was seen, by its Python name or else its ``comm``),
  ``watch_late_ms`` (the part of the stall the watch's own thread
  overslept), ``steal_ms`` (as above, from
  the start of the loop's ``obs.host`` second the stall began in: at
  most a second more than the stall), ``gc_ms``,
  ``compiles`` / ``compile_ms`` (``jax.monitoring``'s
  ``/jax/core/compile/backend_compile_duration``), ``chip_idle`` (share
  of samples whose probe said ready; left out without a probe),
  ``samples`` and ``cause``.  A stall the watch slept through itself
  has ``samples=0`` and empty ``frame``, ``loop_state``, ``busiest``.

**The rule of ``cause``**, first match wins (:func:`stall_cause`):

1. ``profiler``: in some sample a Python thread's stack stands in
   ``start_trace`` or ``stop_trace`` of ``jax/_src/profiler.py``, or a
   profiler session started or stopped during the stall (a harness's
   doing, not the program's);
2. ``compile``: a compilation ended inside the stall and the
   compilations are at least a fifth of it (tracing and lowering go
   before the compiler's own time, which is all the event counts);
3. ``gc``: the collector ran for at least half of it;
4. ``process_stopped``: the watch woke late by at least half of it
   and the whole process had a core for less than half of it (nothing
   of the process ran: stopped, or frozen with its machine.  A watch
   that is late while the process burns CPU was kept off the
   interpreter by a thread that held it in native code: the stall goes
   on to the rules below, and ``watch_late_ms`` says so);
5. ``starved``: the loop's thread was runnable without a core for at
   least half of it;
6. ``busy``: the loop's thread was on a core for at least half of it;
7. ``blocked``: the rest: asleep in ``frame``.

Counted for an operator in ``bigdl_stalls_total{loop, cause}`` and
``bigdl_stalled_seconds_total{loop}``.
"""

from __future__ import annotations

import collections
import gc
import json
import logging
import os
import resource
import sys
import threading
import time
from typing import Callable, Dict, NamedTuple, Optional

from bigdl_tpu.obs import names, trace

log = logging.getLogger("bigdl_tpu.obs")

#: bounded fold table: distinct collapsed stacks kept before new ones
#: fold into the per-phase ``(other)`` bucket
MAX_STACKS = 2048
#: frames walked per sampled stack (deeper stacks truncate at the root)
MAX_DEPTH = 64
#: label attributed to a sampled thread with no live span
NO_SPAN = "(no span)"
#: overflow stack suffix once the fold table is full
OTHER = "(other)"


def _frame_label(frame) -> str:
    """``file.py:func`` — base name only; full paths explode the fold
    table across venvs without adding attribution value."""
    code = frame.f_code
    return f"{os.path.basename(code.co_filename)}:{code.co_name}"


def _stacks(me: int):
    """Every live thread's stack but ``me``'s, the one walk both the
    profiler and the stall watch make: ``(ident, phase, frames)`` with
    ``phase`` the thread's innermost live span (``NO_SPAN`` outside
    any) and ``frames`` leaf first, at most ``MAX_DEPTH``."""
    for ident, frame in sys._current_frames().items():
        if ident == me:
            continue
        frames = []
        while frame is not None and len(frames) < MAX_DEPTH:
            frames.append(frame)
            frame = frame.f_back
        yield ident, trace.current_phase(ident) or NO_SPAN, frames


class NullProfiler:
    """No-op profiler with the full :class:`SamplingProfiler` surface —
    the pinned zero-overhead off path (no thread, no state)."""

    __slots__ = ()
    enabled = False
    hz = 0.0

    def snapshot(self) -> dict:
        return {"enabled": False, "hz": 0.0, "samples": 0,
                "skipped": 0, "overhead_ratio": 0.0, "stacks": 0,
                "phases": {}, "collapsed": []}

    def render_collapsed(self) -> str:
        return ""

    def close(self):
        pass


NULL_PROFILER = NullProfiler()


class SamplingProfiler:
    """One daemon thread sampling every live thread's stack at ``hz``.

    All mutation happens on the sampler thread; readers
    (:meth:`snapshot`, the /profilez handler, bundle builds) copy
    under the lock.  The sampler never touches the thread it runs on.
    """

    enabled = True

    def __init__(self, hz: float, budget: float = 0.01):
        self.hz = float(hz)
        self.budget = float(budget)
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        # (phase, leaf frame) -> samples: the "top self-time frames per
        # phase" table the report renders
        self._self: Dict[tuple, int] = {}
        self._samples = 0
        self._skipped = 0
        self._work_s = 0.0
        self._started = time.perf_counter()
        self._stop = threading.Event()
        from bigdl_tpu import obs

        reg = obs.get_registry()
        self._samples_c = reg.counter(
            names.PROF_SAMPLES_TOTAL,
            "Stack samples folded into the collapsed-stack table")
        self._skipped_c = reg.counter(
            names.PROF_SKIPPED_TOTAL,
            "Samples skipped by the overhead budget")
        self._overhead_g = reg.gauge(
            names.PROF_OVERHEAD_RATIO,
            "Profiler self-overhead ratio (work seconds / wall seconds)")
        self._stacks_g = reg.gauge(
            names.PROF_STACKS,
            "Distinct collapsed stacks in the bounded fold table")
        self._thread = threading.Thread(
            target=self._run, name="bigdl-prof", daemon=True)
        self._thread.start()
        log.info("obs.prof: continuous profiler on at %.1f Hz "
                 "(budget %.3f)", self.hz, self.budget)

    # -------------------------------------------------------------- core
    def overhead_ratio(self) -> float:
        wall = time.perf_counter() - self._started
        return self._work_s / max(wall, 1e-9)

    def _run(self):
        period = 1.0 / max(self.hz, 1e-6)
        me = threading.get_ident()
        while not self._stop.wait(period):
            ratio = self.overhead_ratio()
            self._overhead_g.set(ratio)
            if ratio > self.budget:
                # the hard cap: over budget, the profiler degrades to
                # bookkeeping-only until the ratio recovers
                self._skipped += 1
                self._skipped_c.inc()
                continue
            t0 = time.perf_counter()
            try:
                self._sample(me)
            except Exception:  # noqa: BLE001 — profiling never kills a host
                log.exception("obs.prof: sample failed; continuing")
            self._work_s += time.perf_counter() - t0

    def _sample(self, me: int):
        with self._lock:
            for _, phase, frames in _stacks(me):
                parts = [_frame_label(f) for f in frames]
                leaf = parts[0]
                # root-first, phase as the fold root
                key = phase + ";" + ";".join(reversed(parts))
                if key not in self._counts \
                        and len(self._counts) >= MAX_STACKS:
                    key = phase + ";" + OTHER
                self._counts[key] = self._counts.get(key, 0) + 1
                sk = (phase, leaf)
                self._self[sk] = self._self.get(sk, 0) + 1
            self._samples += 1
        self._samples_c.inc()
        self._stacks_g.set(len(self._counts))

    # ------------------------------------------------------------ readers
    def snapshot(self, top: int = 8) -> dict:
        """JSON-able profile state: totals, overhead, and the top
        self-time frames per phase (what the report + bundles carry)."""
        with self._lock:
            counts = dict(self._counts)
            self_t = dict(self._self)
            samples, skipped = self._samples, self._skipped
        phases: Dict[str, dict] = {}
        for (phase, leaf), n in self_t.items():
            p = phases.setdefault(phase, {"samples": 0, "frames": {}})
            p["samples"] += n
            p["frames"][leaf] = p["frames"].get(leaf, 0) + n
        for p in phases.values():
            p["frames"] = sorted(p["frames"].items(),
                                 key=lambda kv: -kv[1])[:max(1, top)]
        collapsed = sorted(counts.items(), key=lambda kv: -kv[1])
        return {
            "enabled": True,
            "hz": self.hz,
            "budget": self.budget,
            "samples": samples,
            "skipped": skipped,
            "overhead_ratio": round(self.overhead_ratio(), 6),
            "stacks": len(counts),
            "phases": phases,
            "collapsed": [f"{k} {v}" for k, v in collapsed],
        }

    def render_collapsed(self) -> str:
        """The folded-stack text format every flamegraph tool eats:
        one ``stack count`` line per distinct collapsed stack."""
        with self._lock:
            counts = sorted(self._counts.items(), key=lambda kv: -kv[1])
        return "".join(f"{k} {v}\n" for k, v in counts)

    def close(self):
        """Stop the sampler thread (idempotent)."""
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=2.0)


# ------------------------------------------------------------ stall watch
#: the watch thread's period
TICK_S = 0.02
#: a minded loop gets its ``obs.host`` span once this long
HOST_EVERY_S = 1.0
#: a beat older than this is a stall: the engine's cycle (4.5-65 ms in
#: every cell of the benchmark; the shortest pauses met were 0.12 s) and
#: the trainer's iteration
LIMITS = {"serve": 0.1, "train": 0.4}
#: samples a stall: at every tick up to DENSE_SAMPLES, then every
#: SPARSE_EVERY ticks (an 11 s stall is sampled to its end)
MAX_STALL_SAMPLES = 40
DENSE_SAMPLES = 20
SPARSE_EVERY = 25
#: native threads listed for a stall: those that ran or waited, by CPU
MAX_THREAD_ROWS = 32
CAUSES = ("profiler", "compile", "gc", "process_stopped", "starved",
          "busy", "blocked")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: where a thread stands while a profiler session starts or stops
#: (the file's ``annotate_function`` wrappers are in every jax stack)
PROFILER_FILE = os.path.join("jax", "_src", "profiler.py")
PROFILER_CALLS = ("start_trace", "stop_trace")
SPAN_HOST = "obs.host"
SPAN_STALL = "obs.stall"
EVENT_SAMPLE = "obs.stall.sample"
EVENT_THREADS = "obs.stall.threads"


def stall_cause(dur_s: float, *, profiler: bool, compiles: int,
                compile_ms: float, gc_ms: float, watch_late_ms: float,
                proc_cpu_ms: float, loop_runq_ms, loop_cpu_ms) -> str:
    """One of ``CAUSES`` for a stall of ``dur_s``, first match wins
    (the rule this module's docstring gives)."""
    ms = 1e3 * dur_s
    if profiler:
        return "profiler"
    if compiles and compile_ms >= ms / 5:
        return "compile"
    if gc_ms >= ms / 2:
        return "gc"
    if watch_late_ms >= ms / 2 and proc_cpu_ms < ms / 2:
        return "process_stopped"
    if loop_runq_ms is not None and loop_runq_ms >= ms / 2:
        return "starved"
    if loop_cpu_ms is not None and loop_cpu_ms >= ms / 2:
        return "busy"
    return "blocked"


def _read_schedstat(path_or_fd):
    """``(ns on a core, ns runnable and waiting for one)`` of a native
    thread, from its ``schedstat`` (an open descriptor or a path); None
    where the kernel keeps none or the thread is gone."""
    try:
        if isinstance(path_or_fd, int):
            raw = os.pread(path_or_fd, 96, 0)
        else:
            with open(path_or_fd, "rb") as fh:
                raw = fh.read()
        cpu, runq = raw.split()[:2]
        return int(cpu), int(runq)
    except (OSError, ValueError):
        return None


def _steal_ms():
    """Milliseconds, summed over the machine's cores, that a hypervisor
    ran someone else while this machine wanted to run (the eighth
    column of ``/proc/stat``'s first line); None where there is none."""
    try:
        with open("/proc/stat", "rb") as fh:
            parts = fh.readline().split()
        return int(parts[8]) * 1e3 / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def _stolen_since(before_ms):
    """The machine's steal since an earlier reading of it, or None."""
    now_ms = _steal_ms()
    if before_ms is None or now_ms is None:
        return None
    return round(now_ms - before_ms, 3)


def _not_after(items, t0: float):
    """The newest of ``items`` (oldest first, each beginning with its
    instant) not after ``t0``; the oldest where all are later."""
    found = items[0]
    for item in items:
        if item[0] > t0:
            break
        found = item
    return found


def _task_stat(tid):
    """``(comm, state, cpu ns)`` of a native thread of this process (its
    user and system time, in the clock ticks ``stat`` counts), or
    None."""
    try:
        with open(f"/proc/self/task/{tid}/stat", "rb") as fh:
            raw = fh.read()
        # a comm may hold spaces and brackets: the state follows the last
        close = raw.rindex(b")")
        rest = raw[close + 2:].split()
        ticks = int(rest[11]) + int(rest[12])
        return (raw[raw.index(b"(") + 1:close].decode(errors="replace"),
                rest[0].decode(), ticks * 10**9 // os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return None


def native_threads() -> dict:
    """``tid -> (comm, state, cpu_ns, runq_ns)`` for every native thread
    of the process: how the TPU runtime's and the compiler's threads,
    which are not Python's, are seen.  Where ``/proc`` has no
    ``schedstat`` the CPU time is ``stat``'s (in clock ticks) and the
    run-queue wait None; {} where it has no ``task``."""
    out = {}
    try:
        tids = os.listdir("/proc/self/task")
    except OSError:
        return out
    for tid in tids:
        stat = _task_stat(tid)
        if stat is not None:
            sched = _read_schedstat(f"/proc/self/task/{tid}/schedstat")
            out[int(tid)] = stat[:2] + (sched or (stat[2], None))
    return out


def _thread_rows(before: dict, after: dict, skip: int) -> list:
    """The native threads that ran or waited between two tables, most
    CPU first: ``[tid, comm, state, cpu_ms, runq_ms, name]`` (``name``
    a Python thread's, ``""`` for the rest); ``skip`` (the loop's own)
    comes first whatever it did."""
    py = {t.native_id: t.name for t in threading.enumerate()}
    rows = []
    for tid, (comm, state, cpu, runq) in after.items():
        was = before.get(tid, (comm, state, 0, 0 if runq is not None
                               else None))
        cpu_ms = (cpu - was[2]) / 1e6
        runq_ms = None if runq is None or was[3] is None \
            else round((runq - was[3]) / 1e6, 3)
        if tid == skip or cpu_ms > 0 or runq_ms or state in "RD":
            rows.append([tid, comm, state, round(cpu_ms, 3), runq_ms,
                         py.get(tid, "")])
    rows.sort(key=lambda r: (r[0] != skip, -r[3]))
    return rows[:MAX_THREAD_ROWS]


class _Reading(NamedTuple):
    """What the watch reads of a minded loop at a tick."""

    t: float                    # the tick's instant
    cpu_ns: Optional[int]       # the loop's thread on a core (schedstat,
    #                             or else the thread's CPU clock)
    runq_ns: Optional[int]      # ... runnable and waiting for one (schedstat)
    proc_s: float               # the process's CPU seconds
    session: Optional[bool]     # a profiler session is open


class _Second:
    """The running second of a minded loop's ``obs.host`` span."""

    __slots__ = ("first", "nivcsw", "gc_s", "steal_ms", "ticks", "late",
                 "late_max")

    def __init__(self, first: _Reading, gc_s: float):
        self.first, self.gc_s = first, gc_s
        self.nivcsw = resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw
        self.steal_ms = _steal_ms()
        self.ticks = 0
        self.late = self.late_max = 0.0


class _Live:
    """A stall while it lasts: what the watch saw of it, tick by tick."""

    __slots__ = ("start", "seen", "seq", "base", "table", "ticks",
                 "samples", "frames", "states", "asked", "ready", "profiler")

    def __init__(self, start, seen, seq, base, table):
        self.start, self.seen, self.seq = start, seen, seq
        self.base, self.table = base, table
        self.ticks = self.samples = self.asked = self.ready = 0
        self.frames: collections.Counter = collections.Counter()
        self.states: collections.Counter = collections.Counter()
        self.profiler = False


class _Loop:
    """One minded loop: the thread that called ``StallWatch.add``."""

    def __init__(self, watch, name, probe, quiet):
        self.watch, self.name = watch, name
        limit_s = watch.limits[name]
        self.ident = threading.get_ident()
        self.native = threading.get_native_id()
        self.probe = probe
        self.quiet = frozenset(quiet)
        self.beat = trace.Beat(limit_s)
        try:
            self.fd = os.open(f"/proc/self/task/{self.native}/schedstat",
                              os.O_RDONLY)
        except OSError:
            self.fd = None
        # without a schedstat the thread's own CPU clock still says how
        # long it was on a core (not how long it waited for one)
        try:
            self.cpu_clock = time.pthread_getcpuclockid(self.ident)
        except (AttributeError, OSError):
            self.cpu_clock = None
        # a reading a tick, far enough back to reach a stall's start
        self.readings: collections.deque = collections.deque(
            maxlen=int(limit_s / watch.tick_s) + 8)
        self.second: Optional[_Second] = None
        # (instant, the machine's steal ms) at the newest seconds' starts
        self.steals: collections.deque = collections.deque(maxlen=16)
        self.live: Optional[_Live] = None

    def drop(self):
        """Stop minding this loop (from any thread)."""
        self.watch._drop(self)


class NullWatch:
    """What :func:`get_watch` hands out while no tracer records: it
    minds nothing and has no thread."""

    __slots__ = ()
    tracer = trace.NULL_TRACER

    def add(self, name, probe=None, quiet=()):
        return None

    def close(self):
        pass


NULL_WATCH = NullWatch()

# jax.monitoring offers no way to take a listener back: one is
# registered once a process, and hands what it hears to the live watch
_compile_sink = None
_compile_listening = False


def _on_compile(event, duration, **_):
    sink = _compile_sink
    if sink is not None and event == COMPILE_EVENT:
        sink.append((time.perf_counter(), float(duration)))


class StallWatch:
    """The recorder of pauses (this module's docstring): one daemon
    thread, alive while a loop is minded, bound to one recording
    tracer.  ``tick_s``, ``host_every_s``, ``max_samples`` and
    ``limits`` are the constants above unless a test says otherwise."""

    def __init__(self, tracer, tick_s: float = TICK_S,
                 host_every_s: float = HOST_EVERY_S,
                 max_samples: int = MAX_STALL_SAMPLES, limits=None):
        self.tracer = tracer
        self.tick_s = float(tick_s)
        self.host_every_s = float(host_every_s)
        self.max_samples = int(max_samples)
        self.limits = dict(LIMITS if limits is None else limits)
        self._lock = threading.Lock()
        self._loops: Dict[int, _Loop] = {}
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._seq = 0
        # (wake instant, seconds late) of the newest ticks
        self._lates: collections.deque = collections.deque(maxlen=1024)
        self._compiles: collections.deque = collections.deque(maxlen=256)
        # (start, seconds) of the collector's newest runs, and their sum
        self._gc_runs: collections.deque = collections.deque(maxlen=256)
        self._gc_s = 0.0
        self._gc_t0 = None
        try:
            from jax._src import profiler as jax_profiler

            self._profile_state = getattr(jax_profiler, "_profile_state",
                                          None)
        except ImportError:
            self._profile_state = None
        from bigdl_tpu import obs

        reg = obs.get_registry()
        self._stalls_c = reg.counter(
            names.STALLS_TOTAL, "Pauses of a minded loop, by loop and "
            "cause", labels=("loop", "cause"))
        self._stalled_c = reg.counter(
            names.STALLED_SECONDS_TOTAL, "Seconds a minded loop stood "
            "still in pauses", labels=("loop",))

    # ----------------------------------------------------------- minding
    def add(self, name: str, probe: Optional[Callable[[], bool]] = None,
            quiet=()):
        """Mind the CALLING thread as loop ``name`` (one of ``limits``)
        until the handle's ``drop()``: its span boundaries further apart
        than the loop's limit are stalls, but for a gap inside a span
        named in ``quiet``.  ``probe`` is asked at every sample, from
        the watch's thread, and must not block."""
        global _compile_sink, _compile_listening
        loop = _Loop(self, name, probe, quiet)
        with self._lock:
            old = self._loops.pop(loop.ident, None)
            self._loops[loop.ident] = loop
            trace._BEATS[loop.ident] = loop.beat
            if self._thread is None:
                if not _compile_listening:
                    import jax

                    jax.monitoring.register_event_duration_secs_listener(
                        _on_compile)
                    _compile_listening = True
                _compile_sink = self._compiles
                self._stop.clear()
                self._thread = threading.Thread(
                    target=self._run, name="bigdl-stallwatch", daemon=True)
                self._thread.start()
        if old is not None and old.fd is not None:
            os.close(old.fd)
            old.fd = None
        return loop

    def _drop(self, loop: _Loop):
        with self._lock:
            if self._loops.get(loop.ident) is not loop:
                return
            del self._loops[loop.ident]
            if trace._BEATS.get(loop.ident) is loop.beat:
                del trace._BEATS[loop.ident]
        # (the watch's thread may be reading it: gone before it is closed)
        fd, loop.fd = loop.fd, None
        if fd is not None:
            os.close(fd)

    def close(self):
        """Drop every loop and stop the thread (idempotent)."""
        with self._lock:
            loops = list(self._loops.values())
        for loop in loops:
            self._drop(loop)
        self._stop.set()
        thread = self._thread
        if thread is not None and thread is not threading.current_thread():
            thread.join(timeout=2.0)

    # ------------------------------------------------------------ thread
    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            dur = time.perf_counter() - self._gc_t0
            self._gc_runs.append((self._gc_t0, dur))
            self._gc_s += dur
            self._gc_t0 = None

    def _run(self):
        me = threading.get_ident()
        gc.callbacks.append(self._on_gc)
        try:
            last = time.perf_counter()
            while True:
                if self._stop.wait(self.tick_s):
                    return
                now = time.perf_counter()
                # from wake-up to wake-up, the tick's own work included:
                # a thread that keeps the interpreter holds the watch
                # off inside its work as well as at its wake-up
                late = max(0.0, now - last - self.tick_s)
                last = now
                self._lates.append((now, late))
                with self._lock:
                    loops = list(self._loops.values())
                    if not loops or self.tracer._closed:
                        return
                for loop in loops:
                    try:
                        self._tick(loop, now, late, me)
                    except Exception:  # noqa: BLE001 — a watch never kills a host
                        log.exception("obs.prof: stall watch tick failed; "
                                      "continuing")
        finally:
            gc.callbacks.remove(self._on_gc)
            with self._lock:
                self._thread = None

    def _reading(self, loop: _Loop, now: float) -> _Reading:
        fd = loop.fd
        sched = _read_schedstat(fd) if fd is not None else None
        if sched is None and loop.cpu_clock is not None:
            try:
                sched = (time.clock_gettime_ns(loop.cpu_clock), None)
            except OSError:      # the thread has gone
                sched = None
        state = self._profile_state
        return _Reading(
            now, *(sched or (None, None)), time.process_time(),
            None if state is None else state.profile_session is not None)

    def _tick(self, loop: _Loop, now: float, late: float, me: int):
        r = self._reading(loop, now)
        loop.readings.append(r)
        sec = loop.second
        if sec is None:
            sec = loop.second = _Second(r, self._gc_s)
            loop.steals.append((now, sec.steal_ms))
        sec.ticks += 1
        sec.late += late
        sec.late_max = max(sec.late_max, late)
        if now - sec.first.t >= self.host_every_s:
            self._host(loop, sec, r)
            loop.second = None
        beat = loop.beat
        while beat.gaps:
            self._finish(loop, beat.gaps.popleft(), r)
        live = loop.live
        if live is None and now - beat.t > beat.limit and not (
                loop.quiet and loop.quiet.intersection(
                    trace._PHASES.get(loop.ident, ()))):
            self._seq += 1
            live = loop.live = _Live(beat.t, now, self._seq,
                                     _not_after(loop.readings, beat.t),
                                     native_threads())
        if live is not None:
            live.ticks += 1
            if live.samples < self.max_samples and (
                    live.samples < DENSE_SAMPLES
                    or live.ticks % SPARSE_EVERY == 0):
                self._sample(loop, live, me)

    def _host(self, loop: _Loop, sec: _Second, r: _Reading):
        first = sec.first
        attrs = {"loop": loop.name,
                 "tid": self.tracer._tids.get(loop.ident),
                 "ticks": sec.ticks,
                 "late_ms_sum": round(1e3 * sec.late, 3),
                 "late_ms_max": round(1e3 * sec.late_max, 3)}
        if r.cpu_ns is not None and first.cpu_ns is not None:
            attrs["loop_cpu_ms"] = round((r.cpu_ns - first.cpu_ns) / 1e6, 3)
        if r.runq_ns is not None and first.runq_ns is not None:
            attrs["loop_runq_ms"] = round(
                (r.runq_ns - first.runq_ns) / 1e6, 3)
        attrs["proc_cpu_ms"] = round(1e3 * (r.proc_s - first.proc_s), 3)
        attrs["nivcsw"] = resource.getrusage(
            resource.RUSAGE_SELF).ru_nivcsw - sec.nivcsw
        attrs["gc_ms"] = round(1e3 * (self._gc_s - sec.gc_s), 3)
        stolen = _stolen_since(sec.steal_ms)
        if stolen is not None:
            attrs["steal_ms"] = stolen
        self.tracer.complete(SPAN_HOST, first.t, r.t - first.t, **attrs)

    def _sample(self, loop: _Loop, live: _Live, me: int):
        thread_names = {t.ident: t.name for t in threading.enumerate()}
        folded: dict = {}
        for ident, phase, frames in _stacks(me):
            labels = tuple(_frame_label(f) for f in reversed(frames))
            if not live.profiler and any(
                    f.f_code.co_name in PROFILER_CALLS
                    and f.f_code.co_filename.endswith(PROFILER_FILE)
                    for f in frames):
                live.profiler = True
            mine = ident == loop.ident
            if mine:
                live.frames[labels[-1]] += 1
            entry = folded.setdefault((phase, labels, mine), [0, []])
            entry[0] += 1
            if len(entry[1]) < 4:
                entry[1].append(thread_names.get(ident, str(ident)))
        stat = _task_stat(loop.native)
        if stat is not None:
            live.states[stat[1]] += 1
        ready = None
        if loop.probe is not None:
            try:
                ready = bool(loop.probe())
            except Exception:  # noqa: BLE001 — the loop's state moves under a probe
                ready = None
            if ready is not None:
                live.asked += 1
                live.ready += ready
        live.samples += 1
        self.tracer.event(
            EVENT_SAMPLE, loop=loop.name, stall=live.seq, n=live.samples,
            loop_state=stat[1] if stat else None, chip_ready=ready,
            stacks=[{"threads": n, "names": who, "phase": phase,
                     "loop": mine, "stack": list(labels)}
                    for (phase, labels, mine), (n, who) in folded.items()])

    def _finish(self, loop: _Loop, gap: tuple, r: _Reading):
        """One gap the loop's thread wrote down becomes one
        ``obs.stall`` span, with what the watch saw of it."""
        t0, t1, sid, phases, step = gap
        live = loop.live
        if live is not None and live.start == t0:
            loop.live = None
        else:
            live = None     # the watch slept through it
        if loop.quiet.intersection(phases):
            return
        if live is None:
            self._seq += 1
            live = _Live(t0, None, self._seq,
                         _not_after(loop.readings, t0), None)
        base = live.base
        dur = t1 - t0
        attrs = {"loop": loop.name,
                 "tid": self.tracer._tids.get(loop.ident),
                 "stall": live.seq,
                 "phase": phases[-1] if phases else "", "span": sid}
        if step is not None:
            attrs["step"] = step
        attrs["frame"] = live.frames.most_common(1)[0][0] \
            if live.frames else ""
        attrs["frames_distinct"] = len(live.frames)
        attrs["loop_state"] = live.states.most_common(1)[0][0] \
            if live.states else ""
        cpu_ms = runq_ms = None
        if r.cpu_ns is not None and base.cpu_ns is not None:
            cpu_ms = attrs["loop_cpu_ms"] = round(
                (r.cpu_ns - base.cpu_ns) / 1e6, 3)
        if r.runq_ns is not None and base.runq_ns is not None:
            runq_ms = attrs["loop_runq_ms"] = round(
                (r.runq_ns - base.runq_ns) / 1e6, 3)
        attrs["proc_cpu_ms"] = round(1e3 * (r.proc_s - base.proc_s), 3)
        rows = [] if live.table is None else _thread_rows(
            live.table, native_threads(), loop.native)
        if rows:
            # the rows count from where the watch saw the stall
            self.tracer.event(EVENT_THREADS, loop=loop.name, stall=live.seq,
                              from_s=round(live.seen - t0, 3), rows=rows)
        # (this thread is the watch's own: the observer is no suspect)
        other = [row for row in rows
                 if row[0] not in (loop.native, threading.get_native_id())]
        attrs["busiest"] = (other[0][5] or other[0][1]) if other else ""
        attrs["busiest_cpu_ms"] = other[0][3] if other else 0.0
        # a tick that woke at ``t``, ``late`` late, overslept (t - late, t)
        late_ms = 1e3 * sum(max(0.0, min(t1, t) - max(t0, t - late))
                            for t, late in self._lates)
        attrs["watch_late_ms"] = round(late_ms, 3)
        stolen = _stolen_since(_not_after(loop.steals, t0)[1])
        if stolen is not None:
            attrs["steal_ms"] = stolen
        gc_ms = 1e3 * sum(max(0.0, min(t1, g0 + g) - max(t0, g0))
                          for g0, g in self._gc_runs)
        attrs["gc_ms"] = round(gc_ms, 3)
        done = [c for t, c in self._compiles if t0 <= t <= t1]
        attrs["compiles"] = len(done)
        attrs["compile_ms"] = round(1e3 * sum(done), 3)
        if live.asked:
            attrs["chip_idle"] = round(live.ready / live.asked, 3)
        attrs["samples"] = live.samples
        profiler = live.profiler or (
            base.session is not None and r.session is not None
            and base.session != r.session)
        cause = attrs["cause"] = stall_cause(
            dur, profiler=profiler, compiles=len(done),
            compile_ms=attrs["compile_ms"], gc_ms=gc_ms,
            watch_late_ms=late_ms, proc_cpu_ms=attrs["proc_cpu_ms"],
            loop_runq_ms=runq_ms, loop_cpu_ms=cpu_ms)
        self.tracer.complete(SPAN_STALL, t0, dur, **attrs)
        self._stalls_c.labels(loop=loop.name, cause=cause).inc()
        self._stalled_c.labels(loop=loop.name).inc(dur)
        log.info("obs.prof: the %s loop stood still for %.3fs in %r (%s)",
                 loop.name, dur, attrs["phase"], cause)


# ------------------------------------------------------------- singleton
_lock = threading.Lock()
_profiler = NULL_PROFILER
_profiler_key = None
_watch = NULL_WATCH


def get_watch():
    """The process's stall watch: a :class:`StallWatch` bound to the
    recording tracer, the shared :data:`NULL_WATCH` while tracing is
    off.  Rebuilt when the tracer is.  Call it where a loop starts to be
    minded, behind ``tracer.enabled``, never once a step."""
    global _watch
    from bigdl_tpu import obs

    tracer = obs.get_tracer()
    with _lock:
        if _watch.tracer is not tracer:
            _watch.close()
            _watch = StallWatch(tracer) if tracer.enabled else NULL_WATCH
        return _watch


def get_profiler():
    """The process profiler — a :class:`SamplingProfiler` when
    ``BIGDL_PROF_HZ`` > 0, the shared :data:`NULL_PROFILER` otherwise
    (no thread ever starts on the off path).  Rebuilt when the
    hz/budget config changes."""
    global _profiler, _profiler_key
    from bigdl_tpu.config import refresh_from_env

    cfg = refresh_from_env().obs
    key = (cfg.prof_hz, cfg.prof_budget)
    with _lock:
        if key == _profiler_key:
            return _profiler
        if _profiler is not NULL_PROFILER:
            _profiler.close()
        _profiler_key = key
        _profiler = (SamplingProfiler(cfg.prof_hz, cfg.prof_budget)
                     if cfg.prof_hz > 0 else NULL_PROFILER)
        return _profiler


def current():
    """The live profiler WITHOUT building one — cheap reads (health
    payloads, report columns) must not start a sampler thread as a
    side effect."""
    return _profiler


def reset_profiler():
    """Test hook: stop the sampler and the stall watch; the next
    accessor rebuilds."""
    global _profiler, _profiler_key, _watch
    with _lock:
        if _profiler is not NULL_PROFILER:
            _profiler.close()
        _profiler = NULL_PROFILER
        _profiler_key = None
        _watch.close()
        _watch = NULL_WATCH


def write_profile(out_dir: str, stem: str) -> Optional[str]:
    """One ``<stem>.profile.json`` shard in ``out_dir`` (the obs.flush
    hook — how an offline report gets the run's folded profile); None
    when the profiler is off or has no samples yet."""
    prof = _profiler
    if prof is NULL_PROFILER:
        return None
    snap = prof.snapshot()
    if not snap["samples"]:
        return None
    path = os.path.join(out_dir, stem + ".profile.json")
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(snap, fh)
    os.replace(tmp, path)
    return path
