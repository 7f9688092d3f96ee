"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: busy and idle time of each chip, time by operation and by
jitted program, idle gaps named by the programs on either side, and the
collective time during which nothing else ran on the chip.

A trace is kept here in a plain form, so that the reduction can be
checked on a recorded sample without a chip:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

On a TPU plane the profiler writes one line of operations (``XLA Ops``)
and one of whole programs (``XLA Modules``, an event per execution of a
jitted function, named ``jit_<function>(<fingerprint>)``).  Busy time is
the union of the operation intervals; a gap is the time between the end
of one program and the start of the next on the same chip.
"""

from __future__ import annotations

import json
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# HLO names of the operations that move data between chips
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|send|recv)", re.IGNORECASE)


def load(path: str) -> dict:
    """Read an ``.xplane.pb`` with nothing but JAX."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def device_only(trace: dict) -> dict:
    """The chips' operation and program lines alone (what a sample kept
    in the repo needs), with times counted from the first event."""
    planes = []
    for plane in trace["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        lines = [ln for ln in plane["lines"]
                 if ln["name"] in (OPS_LINE, MODULES_LINE)]
        planes.append({"name": plane["name"], "lines": lines})
    starts = [ev[1] for p in planes for ln in p["lines"]
              for ev in ln["events"]]
    t0 = min(starts) if starts else 0
    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"],
             "events": [[n, s - t0, d] for n, s, d in ln["events"]]}
            for ln in p["lines"]]} for p in planes]}


def op_name(event: str) -> str:
    """``%copy.17 = bf16[...] copy(...)`` -> ``copy.17``: the profiler
    names an operation by its whole HLO line."""
    return event.split(" = ", 1)[0].lstrip("%")


def program_name(module_event: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", module_event)


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(merged) -> int:
    return sum(e - s for s, e in merged)


def _intersect_length(a, b) -> int:
    """Total overlap of two merged interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _line(plane, name):
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def reduce(trace: dict) -> dict:
    """The reduction.  Seconds throughout; averaged over the chips that
    ran anything, so four chips in step read like one.

    Returns ``window_s`` (first operation's start to last operation's
    end over all chips), ``busy_s``, ``chips``, ``ops`` (name -> seconds
    per chip), ``programs`` (name -> {"calls", "seconds"} per chip),
    ``idle_gaps`` (``after_<program>_before_<program>`` -> seconds per
    chip) and ``collective_exposed_s``.
    """
    planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])
              and _line(p, OPS_LINE)]
    if not planes:
        return {"window_s": 0.0, "busy_s": 0.0, "chips": 0, "ops": {},
                "programs": {}, "idle_gaps": {}, "collective_exposed_s": 0.0}
    n = len(planes)
    t_first = min(ev[1] for p in planes for ev in _line(p, OPS_LINE))
    t_last = max(ev[1] + ev[2] for p in planes for ev in _line(p, OPS_LINE))
    busy = exposed = 0
    ops: dict = {}
    programs: dict = {}
    gaps: dict = {}
    for plane in planes:
        events = [(op_name(nm), s, d) for nm, s, d in _line(plane, OPS_LINE)]
        busy += _length(_union([(s, s + d) for _, s, d in events]))
        for name, _, d in events:
            ops[name] = ops.get(name, 0) + d
        coll = _union([(s, s + d) for nm, s, d in events
                       if COLLECTIVE.match(nm)])
        rest = _union([(s, s + d) for nm, s, d in events
                       if not COLLECTIVE.match(nm)])
        exposed += _length(coll) - _intersect_length(coll, rest)
        mods = sorted(_line(plane, MODULES_LINE), key=lambda ev: ev[1])
        prev = None
        for name, s, d in mods:
            prog = program_name(name)
            rec = programs.setdefault(prog, {"calls": 0, "seconds": 0})
            rec["calls"] += 1
            rec["seconds"] += d
            if prev is not None and s > prev[1]:
                label = f"after_{prev[0]}_before_{prog}"
                gaps[label] = gaps.get(label, 0) + (s - prev[1])
            if prev is None or s + d > prev[1]:
                prev = (prog, s + d)
    ns = 1e-9 / n
    return {
        "window_s": (t_last - t_first) * 1e-9,
        "busy_s": busy * ns,
        "chips": n,
        "ops": {k: v * ns for k, v in ops.items()},
        "programs": {k: {"calls": v["calls"] / n, "seconds": v["seconds"] * ns}
                     for k, v in programs.items()},
        "idle_gaps": {k: v * ns for k, v in gaps.items()},
        "collective_exposed_s": exposed * ns,
    }


def top(table: dict, k: int = 10) -> list:
    """The ``k`` largest entries of a name -> seconds table, as the
    result line's ``breakdown`` wants them."""
    return [[name, sec] for name, sec in
            sorted(table.items(), key=lambda kv: -kv[1])[:k]]


def program_ms_per_call(reduced: dict, *names: str):
    """Device milliseconds per call of the jitted programs whose name
    (without ``jit_``) is one of ``names``; None when the trace holds
    none."""
    calls = seconds = 0.0
    for prog, rec in reduced.get("programs", {}).items():
        bare = prog[4:] if prog.startswith("jit_") else prog
        if bare in names:
            calls += rec["calls"]
            seconds += rec["seconds"]
    if not calls:
        return None
    return 1e3 * seconds / calls
