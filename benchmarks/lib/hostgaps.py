"""From a traced run's raw profiler trace and the program's span log to
the host's share of the chips' idle time, and the device's time by
named scope.

A recording ``bigdl_tpu.obs`` tracer writes each live span into the
running profiler session as an annotation of the same name that carries
the span's ``id`` (``bigdl_tpu/obs/trace.py``), so the host plane of the
``.xplane.pb`` holds the program's spans on the clock of the chips'
operations.  This module

* reads that file in a plain form a test can write by hand
  (:func:`load`): for each plane its lines, for each line its events as
  ``[name, start_ns, duration_ns]`` or, where the event has stats worth
  keeping, ``[name, start_ns, duration_ns, {stat: value}]``: the ``id``
  and ``step`` of an annotation, the scope path of an operation;
* puts every span of the tracer's log on the profiler's clock
  (:func:`place_spans`): a live span takes its annotation's own times;
  a retroactive one (``Tracer.complete``: ``feed.h2d``, ``feed.gather``,
  ``data_wait``, ``input_prefetch``, ``computing``) and a live one that
  began before the session did are placed by the offset the annotated
  spans give (annotation start less the span's ``wall_time``, joined by
  id; the median);
* finds each chip's idle gaps (the time inside the traced window during
  which no operation ran on that chip: their sum is ``window_s`` less
  ``busy_s`` of ``xplane.reduce``) and splits each gap over what the
  host was doing during it (:func:`attribute`);
* sums the device time of a program's operations by the
  ``jax.named_scope`` they were traced under (:func:`scope_seconds`).

A per-layer metric's reader calls :func:`for_run`, which finds the raw
trace under ``run.extra["profile"].dir``, parses it once a process and
returns None where there is nothing to read (a CPU rehearsal; a program
from before the spans were annotations).
"""

from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import re
import statistics

from benchmarks.lib import xplane

HOST_PLANE = re.compile(r"^/host:")

# ---- what the host's loop is doing, by span name (obs/trace.py and
# serving/spans.py list the names)
TRAIN_DISPATCH = "step_dispatch"
TRAIN_H2D = "feed.h2d"
TRAIN_READBACK = "loss_readback"
TRAIN_FEED = ("input_prefetch", "data_wait", "batch_prep", "device_put")
TRAIN_LOOP = ("iteration", TRAIN_DISPATCH, TRAIN_READBACK)
TRAIN_PROGRAMS = ("jit_train_step", "jit_sharded_step")
SERVE_DECODE = "serve.decode_step"
SERVE_PREFILL = "serve.prefill"
SERVE_ADMISSION = "serve.admission"
SERVE_PREP = "serve.prep"
SERVE_EMIT = "serve.emit"
SERVE_PROGRAMS = ("jit_step",)
UNATTRIBUTED = "unattributed"
# the scopes of ``paged_decode_math`` a reader splits the decode step
# by; ``sample`` is small and counts with what is under none of them
DECODE_SCOPES = ("kv_write", "attn", "dense")
# how much later than a step's execution began the waiter may see its
# batch ready (a thread's wake-up) without the execution being taken
# for an earlier step's
ANCHOR_SLACK_NS = 2_000_000


# ------------------------------------------------------------ intervals
def _union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(merged) -> int:
    return sum(e - s for s, e in merged)


def _intersect(a, b) -> list:
    """Overlap of two merged interval lists, merged."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a, b) -> list:
    """What of merged ``a`` lies outside merged ``b``."""
    out = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


# --------------------------------------------------------------- loading
def _varint(buf, i: int):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf):
    """``(field number, value)`` for each field of one protobuf message:
    an int for a varint, the bytes for a length-delimited field.  Just
    enough of the wire format to read what ``ProfileData`` leaves out."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind in (1, 2, 5):
            size = 8 if kind == 1 else 4
            if kind == 2:
                size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, val


def op_paths(pb_bytes: bytes) -> dict:
    """Operation event name -> scope path, from the chips' planes of a
    raw ``.xplane.pb``.  The profiler keeps an operation's ``tf_op``
    (the ``op_name`` of its HLO instruction: the ``jax.named_scope``
    path, as ``jit(step)/dense/dot_general:``) among the stats of the
    event's METADATA, which ``jax.profiler.ProfileData`` does not show
    (an event's own stats are its device offset and duration alone).
    Field numbers are those of ``tsl/profiler/protobuf/xplane.proto``:
    XSpace.planes 1; XPlane.name 2, .event_metadata 4, .stat_metadata 5
    (maps: key 1, value 2); XEventMetadata.name 2, .stats 5;
    XStatMetadata.name 2; XStat.metadata_id 1, .str_value 5."""
    paths = {}
    for field, plane in _fields(memoryview(pb_bytes)):
        if field != 1:
            continue
        parts = list(_fields(plane))
        name = next((bytes(v).decode() for f, v in parts if f == 2), "")
        if not xplane.DEVICE_PLANE.match(name):
            continue
        tf_op = None
        for f, entry in parts:
            if f == 5:
                kv = dict(_fields(entry))
                if dict(_fields(kv[2])).get(2) == b"tf_op":
                    tf_op = kv[1]
        if tf_op is None:
            continue
        for f, entry in parts:
            if f != 4:
                continue
            meta = list(_fields(dict(_fields(entry))[2]))
            for g, stat in meta:
                if g == 5:
                    st = dict(_fields(stat))
                    if st.get(1) == tf_op and 5 in st:
                        op = next(bytes(v).decode() for h, v in meta
                                  if h == 2)
                        paths.setdefault(op, bytes(st[5]).decode())
    return paths


def load(pb_path: str, span_names) -> dict:
    """The raw ``.xplane.pb`` in the plain form: the chips' ``XLA Ops``
    (each distinct operation's first event with its scope path, where
    the trace has one) and ``XLA Modules`` lines, and of the host planes
    the events named like one of the program's spans, with their ``id``
    and ``step``."""
    from jax.profiler import ProfileData

    with open(pb_path, "rb") as fh:
        raw = fh.read()
    paths = op_paths(raw)
    names = set(span_names)
    planes = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        device = bool(xplane.DEVICE_PLANE.match(plane.name))
        if not device and not HOST_PLANE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            if device:
                if line.name not in (xplane.OPS_LINE, xplane.MODULES_LINE):
                    continue
                events, seen = [], set()
                for ev in line.events:
                    rec = [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    if ev.name in paths and ev.name not in seen:
                        seen.add(ev.name)
                        rec.append({"tf_op": paths[ev.name]})
                    events.append(rec)
            else:
                events = []
                for ev in line.events:
                    if ev.name in names:
                        stats = dict(ev.stats)
                        if "id" in stats:
                            events.append(
                                [ev.name, int(ev.start_ns),
                                 int(ev.duration_ns),
                                 {k: stats[k] for k in ("id", "step")
                                  if k in stats}])
                if not events:
                    continue
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def timing_only(trace: dict) -> dict:
    """The trace as ``xplane.reduce`` takes it: every event cut to its
    name, start and duration."""
    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [ev[:3] for ev in ln["events"]]}
            for ln in p["lines"]]} for p in trace["planes"]]}


def paths_of(trace: dict) -> dict:
    """Operation event name -> scope path, from the events of the plain
    form that carry one."""
    paths = {}
    for plane in trace["planes"]:
        for line in plane["lines"]:
            if line["name"] == xplane.OPS_LINE:
                for ev in line["events"]:
                    if len(ev) > 3:
                        paths.setdefault(ev[0], ev[3]["tf_op"])
    return paths


def load_records(jsonl_path: str) -> list:
    """The span records of a tracer's ``.events.jsonl``."""
    out = []
    with open(jsonl_path, encoding="utf-8") as fh:
        for line in fh:
            rec = json.loads(line)
            if rec.get("kind") == "span":
                out.append(rec)
    return out


def place_spans(trace: dict, records: list):
    """``(spans, offset_ns)``: every record as a dict with ``name``,
    ``start`` and ``end`` (ns on the profiler's clock), ``tid``,
    ``step``, ``attrs``, ``id`` and ``annotated``; or None where no
    span of the log is an annotation in the trace, so that the two
    clocks cannot be joined."""
    annotated = {}
    for plane in trace["planes"]:
        if not HOST_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                if len(ev) > 3 and "id" in ev[3]:
                    annotated[(ev[0], int(ev[3]["id"]))] = (ev[1], ev[2])
    offsets = []
    for rec in records:
        hit = annotated.get((rec["name"], rec["id"]))
        if hit is not None:
            offsets.append(hit[0] - rec["wall_time"] * 1e9)
    if not offsets:
        return None
    offset = statistics.median(offsets)
    spans = []
    for rec in records:
        hit = annotated.get((rec["name"], rec["id"]))
        if hit is not None:
            start, dur = hit
        else:
            start = round(rec["wall_time"] * 1e9 + offset)
            dur = round(rec["dur_s"] * 1e9)
        attrs = rec.get("attrs") or {}
        spans.append({"name": rec["name"], "start": start,
                      "end": start + dur, "tid": rec.get("tid"),
                      "step": attrs.get("step"), "attrs": attrs,
                      "id": rec["id"], "annotated": hit is not None})
    return spans, offset


# ------------------------------------------------------------- the chips
def chips_of(trace: dict) -> list:
    """One dict a chip that ran anything: ``busy`` (merged operation
    intervals), ``modules`` (sorted ``(program, start, end)``) and
    ``ops`` (sorted ``(name, start, end)``)."""
    chips = []
    for plane in trace["planes"]:
        if not xplane.DEVICE_PLANE.match(plane["name"]):
            continue
        ops = mods = ()
        for line in plane["lines"]:
            if line["name"] == xplane.OPS_LINE:
                ops = line["events"]
            elif line["name"] == xplane.MODULES_LINE:
                mods = line["events"]
        if not ops:
            continue
        chips.append({
            "busy": _union((ev[1], ev[1] + ev[2]) for ev in ops),
            # by start, and an event before the events nested in it
            "ops": sorted(((ev[0], ev[1], ev[1] + ev[2]) for ev in ops),
                          key=lambda op: (op[1], -op[2])),
            "modules": sorted(
                ((xplane.program_name(ev[0]), ev[1], ev[1] + ev[2])
                 for ev in mods), key=lambda m: m[1])})
    return chips


def idle_gaps(chips: list) -> list:
    """For each chip, the merged intervals of the traced window (first
    operation's start to last operation's end over all chips) in which
    no operation ran on it."""
    if not chips:
        return []
    window = [[min(c["busy"][0][0] for c in chips),
               max(c["busy"][-1][1] for c in chips)]]
    return [_subtract(window, c["busy"]) for c in chips]


def _calls(chip: dict, programs) -> list:
    return [m for m in chip["modules"] if m[0] in programs]


# ----------------------------------------------------------- attribution
def _named(spans, tid, *names) -> list:
    return _union((s["start"], s["end"]) for s in spans
                  if s["tid"] == tid and s["name"] in names)


def _loop_thread(spans, marker: str, names):
    """The thread that records ``marker`` spans, and the time between
    two of its spans named in ``names`` (the loop between two turns)."""
    tids = [s["tid"] for s in spans if s["name"] == marker]
    if not tids:
        return None, []
    tid = statistics.mode(tids)
    own = _named(spans, tid, *names)
    return tid, _subtract([[own[0][0], own[-1][1]]], own)


def _steps_of_calls(calls: list, spans: list) -> list:
    """The training step each execution of the step program belongs to.
    Executions run in the order the steps were dispatched, so one shift
    maps positions to steps.  No execution starts before its step's
    ``step_dispatch`` does: the largest shift that allows is the
    answer, unless the chip was waiting for its batch while the loop
    had already dispatched the step after (then an execution would
    start before its own ``feed.h2d`` ended, and the shift is one
    less)."""
    dispatched = sorted((s["start"], int(s["step"])) for s in spans
                        if s["name"] == TRAIN_DISPATCH
                        and s["step"] is not None)
    if not dispatched or not calls:
        return [None] * len(calls)
    starts = [d[0] for d in dispatched]
    shifts = []
    for i, call in enumerate(calls):
        k = bisect.bisect_right(starts, call[1]) - 1
        if k >= 0:
            shifts.append(dispatched[k][1] - i)
    if not shifts:
        return [None] * len(calls)
    shift = min(shifts)
    copied = {int(s["step"]): s["end"] for s in spans
              if s["name"] == TRAIN_H2D and s["step"] is not None}
    for _ in range(2):
        if any(copied.get(i + shift, call[1]) > call[1] + ANCHOR_SLACK_NS
               for i, call in enumerate(calls)):
            shift -= 1
    return [i + shift for i in range(len(calls))]


def _split(gaps: list, rules: list, out: dict):
    """Cut a chip's gaps (merged intervals) over ``rules`` (bucket,
    merged intervals), first match wins; the rest is unattributed.  All
    the gaps of a chip go through in one pass: a traced window holds a
    gap between every two operations, some hundred thousand a chip."""
    rest = gaps
    for bucket, where in rules:
        hit = _intersect(rest, where)
        if hit:
            out[bucket] = out.get(bucket, 0) + _length(hit)
            rest = _subtract(rest, hit)
    out[UNATTRIBUTED] = out.get(UNATTRIBUTED, 0) + _length(rest)


def attribute_training(chips: list, spans: list):
    """Seconds of idle a chip in the traced window, by what the
    trainer's host was doing: ``h2d`` (the ``feed.h2d`` of the step
    whose program starts after the gap is still open), ``feed`` (the
    loop's thread in ``input_prefetch``, ``data_wait``, ``batch_prep``
    or ``device_put``), ``loop`` (in ``iteration``, ``step_dispatch``,
    ``loss_readback``, or between two iterations), ``unattributed``;
    and ``steps``, the step program's executions a chip."""
    tid, between = _loop_thread(spans, TRAIN_DISPATCH,
                                TRAIN_FEED + TRAIN_LOOP)
    if tid is None:
        return None
    feed = _named(spans, tid, *TRAIN_FEED)
    loop = _union(_named(spans, tid, *TRAIN_LOOP) + between)
    h2d = {s["step"]: (s["start"], s["end"]) for s in spans
           if s["name"] == TRAIN_H2D}
    out = {"h2d": 0, "feed": 0, "loop": 0, UNATTRIBUTED: 0}
    steps = 0
    for chip, gaps in zip(chips, idle_gaps(chips)):
        calls = _calls(chip, TRAIN_PROGRAMS)
        steps += len(calls)
        starts = [c[1] for c in calls]
        step_of = _steps_of_calls(calls, spans)
        # the part of each gap during which the next step's copy is open
        # (disjoint and sorted, as the gaps are)
        copying = []
        for g0, g1 in gaps:
            nxt = bisect.bisect_left(starts, g1)
            copy = h2d.get(step_of[nxt]) if nxt < len(calls) else None
            if copy is not None:
                lo, hi = max(g0, copy[0]), min(g1, copy[1])
                if hi > lo:
                    copying.append([lo, hi])
        _split(gaps, [("h2d", copying), ("feed", feed), ("loop", loop)],
               out)
    return _per_chip(out, steps, len(chips))


def attribute_serving(chips: list, spans: list):
    """As :func:`attribute_training`, for the engine's thread: ``prep``
    (in ``serve.prep``), ``emit`` (``serve.emit``), ``admit`` (in
    ``serve.admission`` outside ``serve.prefill``, or the loop between
    two pumps), ``sync`` (inside ``serve.decode_step`` or
    ``serve.prefill``: the chip idle while the host dispatches to it or
    reads back from it), ``unattributed``; ``steps`` counts the decode
    program's executions."""
    tid, between = _loop_thread(
        spans, SERVE_DECODE, (SERVE_DECODE, SERVE_PREFILL,
                              SERVE_ADMISSION, SERVE_PREP, SERVE_EMIT))
    if tid is None:
        return None
    sync = _named(spans, tid, SERVE_DECODE, SERVE_PREFILL)
    rules = [("prep", _named(spans, tid, SERVE_PREP)),
             ("emit", _named(spans, tid, SERVE_EMIT)),
             ("sync", sync),
             ("admit", _union(_named(spans, tid, SERVE_ADMISSION)
                              + between))]
    out = {"prep": 0, "emit": 0, "admit": 0, "sync": 0, UNATTRIBUTED: 0}
    steps = 0
    for chip, gaps in zip(chips, idle_gaps(chips)):
        steps += len(_calls(chip, SERVE_PROGRAMS))
        _split(gaps, rules, out)
    return _per_chip(out, steps, len(chips))


def _per_chip(out: dict, steps: int, n_chips: int) -> dict:
    res = {k: v * 1e-9 / n_chips for k, v in out.items()}
    res["steps"] = steps / n_chips
    return res


def attribute(trace: dict, spans: list):
    """The split for whichever loop the spans are of; None for neither."""
    chips = chips_of(trace)
    if not chips:
        return None
    return attribute_training(chips, spans) \
        or attribute_serving(chips, spans)


# ---------------------------------------------------------------- scopes
def scope_of(path, scopes):
    """The first part of an operation's scope path that is one of
    ``scopes`` (``jit(step)/dense/dot_general:`` -> ``dense``)."""
    if not path:
        return None
    for part in str(path).split("/"):
        if part in scopes:
            return part
    return None


def scope_seconds(trace: dict, program: str, scopes):
    """Device seconds of ``program``'s executions by scope, a chip:
    ``{"calls", "scoped_ops", <scope>: s, ..., "unscoped": s}``.  An
    operation's own time is its event less the events nested in it;
    ``unscoped`` holds the operations under none of ``scopes`` and the
    time of an execution in which no operation ran.  None where the
    trace holds no execution of the program."""
    chips = chips_of(trace)
    paths = paths_of(trace)
    out = {s: 0 for s in scopes}
    out["unscoped"] = 0
    calls = scoped_ops = 0
    for chip in chips:
        runs = _calls(chip, (program,))
        calls += len(runs)
        starts = [op[1] for op in chip["ops"]]
        for _, m0, m1 in runs:
            inside = chip["ops"][bisect.bisect_left(starts, m0):
                                 bisect.bisect_left(starts, m1)]
            own = _own_times(inside)
            covered = 0
            for (name, _, _), dur in zip(inside, own):
                scope = scope_of(paths.get(name), scopes)
                scoped_ops += scope is not None
                out[scope or "unscoped"] += dur
                covered += dur
            out["unscoped"] += max(0, (m1 - m0) - covered)
    if not calls:
        return None
    res = {k: v * 1e-9 / len(chips) for k, v in out.items()}
    res["calls"] = calls / len(chips)
    res["scoped_ops"] = scoped_ops
    return res


def _own_times(ops: list) -> list:
    """Each event's duration less that of the events nested in it
    (``ops`` sorted by start: a ``while`` holds its body's events)."""
    own = [e - s for _, s, e in ops]
    stack: list = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


# ----------------------------------------------------------- for readers
class HostGaps:
    """What a traced run's raw trace and span log give a reader."""

    def __init__(self, trace: dict, records: list):
        self.trace = trace
        placed = place_spans(trace, records)
        self.spans, self.offset_ns = placed if placed else (None, None)
        self.idle = attribute(trace, self.spans) if placed else None
        self._by_scope: dict = {}

    def idle_ms_per_step(self, bucket: str):
        """Idle milliseconds a step and chip in ``bucket``; None where
        the run's loop has no such bucket or nothing was read."""
        if not self.idle or bucket not in self.idle \
                or not self.idle["steps"]:
            return None
        return 1e3 * self.idle[bucket] / self.idle["steps"]

    def scope_ms_per_call(self, program: str, scopes, scope: str):
        """Device milliseconds a call of ``program`` under ``scope``
        (or ``"unscoped"``); None where no operation of the program
        carries any of ``scopes``: a trace without scope paths, or a
        program compiled from code without the scopes."""
        key = (program, tuple(scopes))
        if key not in self._by_scope:   # one pass for a program's readers
            self._by_scope[key] = scope_seconds(self.trace, program, scopes)
        res = self._by_scope[key]
        if not res or not res["scoped_ops"]:
            return None
        return 1e3 * res[scope] / res["calls"]


@functools.lru_cache(maxsize=2)
def _parse(pb_path: str) -> HostGaps:
    from bigdl_tpu import obs

    tracer = obs.get_tracer()
    tracer.flush()
    records = load_records(tracer.jsonl_path)
    return HostGaps(load(pb_path, {r["name"] for r in records}), records)


def for_run(run):
    """The :class:`HostGaps` of a traced run, parsed once a process;
    None where the run left no raw trace or no span log."""
    profile = getattr(run, "extra", {}).get("profile")
    if profile is None or not getattr(profile, "dir", None):
        return None
    files = sorted(glob.glob(os.path.join(profile.dir, "**", "*.xplane.pb"),
                             recursive=True))
    from bigdl_tpu import obs

    if not files or not getattr(obs.get_tracer(), "enabled", False):
        return None
    return _parse(files[-1])


def idle_ms_per_step(run, bucket: str):
    gaps = for_run(run)
    return None if gaps is None else gaps.idle_ms_per_step(bucket)


def scope_ms_per_call(run, program: str, scopes, scope: str):
    gaps = for_run(run)
    return None if gaps is None else \
        gaps.scope_ms_per_call(program, scopes, scope)
