"""Span tracer — Chrome ``trace_event`` JSON + JSONL structured events.

The reference's only timeline attribution was the driver-side phase
averages in «bigdl»/optim/Metrics.scala; averages cannot answer "where
did *this* step spend its time".  This tracer gives the training
stack nested wall-clock spans:

* contextvar-based nesting — spans opened inside a span become its
  children automatically, per thread/task, with deterministic ids
  (a per-tracer monotonic counter, no uuids);
* two export formats per run: a Chrome ``trace_event`` JSON file
  (open in Perfetto / ``chrome://tracing``) and a JSONL stream of
  structured span/event records for log pipelines;
* thread-safe — the background checkpoint writer and the training
  thread record into the same tracer (each gets its own Chrome tid).

Off by default: when ``BIGDL_TRACE_DIR`` is unset, callers get the
shared :data:`NULL_TRACER` whose ``span()`` returns one reusable no-op
context manager — no allocation, no clock reads, no device syncs.

**One clock with the device.**  A recording tracer's live span also
enters a ``jax.profiler.TraceAnnotation`` of the same name that carries
the span's ``id`` (and ``step`` where the span has one), so a profiler
session started by anyone holds the program's spans on the host plane
of the same ``.xplane.pb`` as the chip's operations.  Retroactive spans
(:meth:`Tracer.complete`) cannot be annotations; a reader puts them on
the profiler's clock by the offset the live spans give (a span's
``wall_time`` here against its annotation's start there, joined by
``id``).  This module is the only place in ``bigdl_tpu`` that makes a
``TraceAnnotation``.

**The trainer's span names** (``optim/optimizer.py``, shared by
``DistriOptimizer``; ``native.PrefetchIterator``), each with ``step=``;
the serving engine's are listed in ``serving/spans.py`` (with an expert
model's ``moe_*`` and a drafting model's ``draft_verified`` /
``draft_accepted`` / ``tokens_emitted`` attributes):

=================  =====  ==================================================
name               kind   covers
=================  =====  ==================================================
``iteration``      live   one turn of the loop, from the batch in hand to
                          the end trigger
``batch_prep``     live   ``_prepare_batch`` of one host batch
``device_put``     live   the call of ``_put_batch`` (returns when the copy
                          is enqueued)
``step_dispatch``  live   the call of the jitted train step
``loss_readback``  live   ``float(loss)`` / ``bool(ok)`` of a dispatched
                          step: the loop's thread waiting for the chip
``data_wait``      retro  the loop top blocked on the batch iterator
``input_prefetch`` retro  fetch + prepare + put of the next batch while
                          the step is in flight (double buffer)
``computing``      retro  dispatch to the loss on the host
``feed.h2d``       retro  ``_put_batch``'s start until the batch is ready
                          on every chip (``bytes=``, ``chips=``); closed by
                          a waiter thread, never by the loop's
``feed.gather``    retro  the prefetch thread producing one batch (the
                          native row gather); from a ``StagingRing`` also
                          ``bytes=``, ``threads=`` (row ranges copied side
                          by side) and ``staging="reused"|"new"`` (into a
                          buffer that came back, or one made for it)
``validation``, ``checkpoint``, ``build_train_step``  live, as named
=================  =====  ==================================================

**The stall watch's names** (``obs/prof.py``, which gives every
attribute and the rule of ``cause``; both spans are retroactive and lie
on the WATCH thread's line, ``tid=`` naming the loop's, so a reader of
the loop's line meets neither):

===================  =====  ================================================
name                 kind   covers
===================  =====  ================================================
``obs.host``         retro  one second of a watched loop (``loop=``): the
                            watch's ticks and how late it woke, the loop
                            thread's CPU and run-queue wait, the process's
                            CPU, involuntary switches, collector time
``obs.stall``        retro  from a watched loop's last span boundary to
                            the next, where they lie further apart than
                            the loop's limit: where the loop's thread
                            stood and ``cause=``
``obs.stall.sample`` event  every Python thread's stack with its live
                            span while a stall lasts (``stall=`` joins it
                            to the span), at most 40 a stall
``obs.stall.threads`` event the process's native threads over a stall:
                            ``comm``, state, CPU and run-queue ms
===================  =====  ================================================

**The heartbeat** is the span log's own: a recording tracer's ``span()``
reads the clock at enter and exit anyway, and for a thread the watch
minds (``_BEATS``, one ``dict.get`` for any other) it keeps that instant
and the open span's id in the thread's :class:`Beat`, and writes down,
itself, any two boundaries further apart than the limit.

**Events of the kernels' call sites** (instant, made while a program is
traced, so once a compile and never in a step): ``kernel.fallback``
(``ops/conv_bn.py``: ``site=`` and the shapes that fell back to XLA) and
``grouped_matmul.tiling`` (``ops/grouped_matmul.py``: once a distinct
shape that reaches the grouped kernel, ``m=``, ``groups=``, ``k=``,
``n=`` and the tiles chosen from them, ``tm=``, ``tk=``, ``tn=``: which
tiles an expert layer's products run on).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import json
import os
import threading
import time


def _default_host_id() -> int:
    """This process's host rank: the launcher's BIGDL_PROCESS_ID via the
    config object (0 in single-host runs).  The tag is what lets
    :mod:`bigdl_tpu.obs.aggregate` attribute merged spans to hosts."""
    try:
        from bigdl_tpu.config import config

        return int(config.process_id)
    except Exception:  # noqa: BLE001 — tracing must never fail bring-up
        return 0

# the active span id for the current thread/task (None at top level)
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "bigdl_obs_span", default=None)

# live span NAMES per thread, innermost last — what the sampling
# profiler (obs/prof.py) attributes its stacks to.  _CURRENT carries
# only the span *id* (all the nesting logic needs), so the name stack
# is kept separately: one dict keyed by thread ident holding a plain
# list.  Push/pop are single list ops under the GIL; the profiler
# thread reads racily (a sample landing inside a push/pop window lands
# in the adjacent phase — one sample of noise, by design).
_PHASES: dict = {}


class Beat:
    """The heartbeat of one thread a stall watch minds (``obs/prof.py``):
    ``t`` the ``perf_counter`` instant of its newest span boundary and
    ``sid`` the span open at it (None between spans).  Two boundaries
    further apart than ``limit`` are a gap, and the thread itself writes
    it down in ``gaps`` as ``(start, end, span id, the names of the
    spans open during it, their step)``: exact, whatever the watch's
    own thread was doing meanwhile."""

    __slots__ = ("t", "sid", "limit", "gaps")

    def __init__(self, limit: float):
        self.t = time.perf_counter()
        self.sid = None
        self.limit = float(limit)
        self.gaps: collections.deque = collections.deque(maxlen=256)

    def mark(self, t: float, sid, stack, open_spans: dict):
        """A boundary at ``t`` that leaves span ``sid`` open; ``stack``
        holds the names of the spans that were open until it."""
        if t - self.t > self.limit:
            step = (open_spans.get(self.sid) or {}).get("step")
            self.gaps.append((self.t, t, self.sid, tuple(stack), step))
        self.t = t
        self.sid = sid


# thread ident -> Beat, for the threads a stall watch minds (it adds and
# drops them; ``Tracer.span`` only looks)
_BEATS: dict = {}


def current_phase(ident: int):
    """Innermost live span name for thread ``ident`` (None when that
    thread is not inside any recorded span) — the profiler's
    attribution read.  Never raises: the stack may vanish between the
    membership check and the index (thread exiting a span)."""
    try:
        return _PHASES[ident][-1]
    except (KeyError, IndexError):
        return None


def _push_phase(name: str) -> int:
    ident = threading.get_ident()
    _PHASES.setdefault(ident, []).append(name)
    return ident


def _pop_phase(ident: int):
    try:
        stack = _PHASES[ident]
        stack.pop()
        if not stack:
            del _PHASES[ident]
    except (KeyError, IndexError):  # torn by a concurrent reset
        pass


class _NullSpan:
    """Reusable no-op context manager — the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """No-op tracer with the full :class:`Tracer` surface."""

    __slots__ = ()
    enabled = False

    def span(self, name, **attrs):
        return _NULL_SPAN

    def add_attrs(self, span_id, **attrs):
        pass

    def event(self, name, **attrs):
        pass

    def complete(self, name, start_perf, duration_s, **attrs):
        pass

    def counter(self, name, **values):
        pass

    def recent(self):
        return []

    def flush(self):
        pass

    def close(self):
        pass


NULL_TRACER = NullTracer()


class Tracer:
    """Recording tracer bound to one output directory.

    File names carry pid + a process-wide monotonic counter so two
    tracers created in the same second (fast tests, retries) can never
    collide or interleave.
    """

    enabled = True
    _FILE_SEQ = itertools.count()

    def __init__(self, trace_dir: str, app_name: str = "bigdl_tpu",
                 host_id: int = None, ring_size: int = 512):
        os.makedirs(trace_dir, exist_ok=True)
        self.pid = os.getpid()
        self.host_id = (_default_host_id() if host_id is None
                        else int(host_id))
        # host rank in the stem: N hosts share one trace_dir (a mounted
        # volume) without shard-name collisions even at equal pids
        stem = (f"{app_name}.h{self.host_id}.{self.pid}."
                f"{next(Tracer._FILE_SEQ)}")
        self.trace_path = os.path.join(trace_dir, stem + ".trace.json")
        self.jsonl_path = os.path.join(trace_dir, stem + ".events.jsonl")
        # the profiler's annotation, looked up once a tracer (the null
        # tracer never gets here, so tracing off makes no annotation)
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._events: list = []
        # structured records not yet written: serialised at flush()
        self._unwritten: list = []
        # attributes of the spans now open, by span id (add_attrs)
        self._open: dict = {}
        self._tids: dict = {}
        self._closed = False
        # flight recorder: the last `ring_size` structured records stay
        # in memory for postmortem bundles (obs/regress.py)
        self._recent: collections.deque = collections.deque(
            maxlen=max(1, int(ring_size)))
        # one wall-clock anchor + perf_counter timeline: Chrome wants a
        # monotonic microsecond ts, the JSONL wants wall time
        self._epoch_wall = time.time()
        self._epoch_perf = time.perf_counter()
        self._jsonl = open(self.jsonl_path, "a", encoding="utf-8")
        self._events.append({"name": "process_name", "ph": "M",
                             "pid": self.pid, "tid": 0,
                             "args": {"name":
                                      f"{app_name} host{self.host_id}"}})

    # ------------------------------------------------------------- internals
    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            tid = self._tids.get(ident)
            if tid is None:
                tid = len(self._tids) + 1
                self._tids[ident] = tid
                self._events.append(
                    {"name": "thread_name", "ph": "M", "pid": self.pid,
                     "tid": tid,
                     "args": {"name": threading.current_thread().name}})
            return tid

    def _record(self, chrome_ev: dict, jsonl_rec: dict = None):
        if jsonl_rec is not None:
            # every structured record carries its origin: the aggregator
            # groups shards and tags merged spans by (host, pid)
            jsonl_rec["host"] = self.host_id
            jsonl_rec["pid"] = self.pid
        with self._lock:
            if self._closed:
                return
            self._events.append(chrome_ev)
            if jsonl_rec is not None:
                self._recent.append(jsonl_rec)
                self._unwritten.append(jsonl_rec)

    def recent(self) -> list:
        """The flight-recorder ring: the newest records (oldest first),
        bounded by ``ring_size``."""
        with self._lock:
            return list(self._recent)

    def _ts_us(self, perf_t: float) -> float:
        return round((perf_t - self._epoch_perf) * 1e6, 3)

    def _wall(self, perf_t: float) -> float:
        return self._epoch_wall + (perf_t - self._epoch_perf)

    # ------------------------------------------------------------------ API
    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Timed nested span; yields its deterministic span id.  The
        same extent goes into a running profiler session as an
        annotation named alike, with the span's id."""
        sid = next(self._ids)
        parent = _CURRENT.get()
        token = _CURRENT.set(sid)
        ident = _push_phase(name)
        tid = self._tid()
        self._open[sid] = attrs
        if "step" in attrs:
            ann = self._annotation(name, id=sid, step=attrs["step"])
        else:
            ann = self._annotation(name, id=sid)
        t0 = time.perf_counter()
        beat = _BEATS.get(ident)
        if beat is not None:
            # (the time since the last boundary passed in the parent)
            beat.mark(t0, sid, _PHASES[ident][:-1], self._open)
        ann.__enter__()
        try:
            yield sid
        finally:
            ann.__exit__(None, None, None)
            t1 = time.perf_counter()
            dur = t1 - t0
            beat = _BEATS.get(ident)
            if beat is not None:
                beat.mark(t1, parent, _PHASES.get(ident, ()), self._open)
            _CURRENT.reset(token)
            _pop_phase(ident)
            del self._open[sid]
            self._record(
                {"name": name, "ph": "X", "ts": self._ts_us(t0),
                 "dur": round(dur * 1e6, 3), "pid": self.pid, "tid": tid,
                 "args": attrs},
                {"kind": "span", "name": name, "id": sid, "parent": parent,
                 "tid": tid, "wall_time": self._wall(t0),
                 "dur_s": round(dur, 9), "attrs": attrs})

    def add_attrs(self, span_id, **attrs):
        """Attributes of an open span that are known only inside it
        (how many requests an admission admitted)."""
        self._open[span_id].update(attrs)

    def event(self, name: str, **attrs):
        """Instant (zero-duration) structured event."""
        t = time.perf_counter()
        tid = self._tid()
        self._record(
            {"name": name, "ph": "i", "s": "t", "ts": self._ts_us(t),
             "pid": self.pid, "tid": tid, "args": attrs},
            {"kind": "event", "name": name, "id": next(self._ids),
             "parent": _CURRENT.get(), "tid": tid,
             "wall_time": self._wall(t), "attrs": attrs})

    def complete(self, name: str, start_perf: float, duration_s: float,
                 **attrs):
        """Retroactive span from a ``perf_counter()`` start + duration —
        for phases measured outside the contextvar flow (e.g. the
        pipelined loss readback that resolves one iteration late)."""
        tid = self._tid()
        self._record(
            {"name": name, "ph": "X", "ts": self._ts_us(start_perf),
             "dur": round(duration_s * 1e6, 3), "pid": self.pid,
             "tid": tid, "args": attrs},
            {"kind": "span", "name": name, "id": next(self._ids),
             "parent": _CURRENT.get(), "tid": tid,
             "wall_time": self._wall(start_perf),
             "dur_s": round(duration_s, 9), "attrs": attrs})

    def counter(self, name: str, **values):
        """Chrome counter track (e.g. host RSS over time)."""
        t = time.perf_counter()
        self._record({"name": name, "ph": "C", "ts": self._ts_us(t),
                      "pid": self.pid, "tid": 0, "args": values})

    def flush(self):
        """Write the full Chrome trace JSON (atomic replace) and append
        the structured records made since the last flush to the JSONL
        stream.  Safe to call repeatedly; the trace file is valid after
        every flush."""
        with self._lock:
            events = list(self._events)
            if not self._jsonl.closed:
                self._jsonl.writelines(
                    json.dumps(rec, default=str) + "\n"
                    for rec in self._unwritten)
                self._unwritten.clear()
                self._jsonl.flush()
        doc = {"traceEvents": events, "displayTimeUnit": "ms",
               "otherData": {"pid": self.pid, "host_id": self.host_id,
                             "wall_epoch": self._epoch_wall}}
        tmp = self.trace_path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, default=str)
        os.replace(tmp, self.trace_path)

    def close(self):
        """Flush and stop recording (idempotent)."""
        if self._closed:
            return
        self.flush()
        with self._lock:
            self._closed = True
            if not self._jsonl.closed:
                self._jsonl.close()
