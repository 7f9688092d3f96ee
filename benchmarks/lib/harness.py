"""What both drivers share: the clock of a run, the count of
compilations, the profiler session, the program's host spans, memory,
percentiles, and the record a per-layer metric's reader is given.
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import shutil
import statistics
import time

from benchmarks.lib import xplane

# a traced run profiles the last seconds of its window: traces are
# large, and tracing slows the host
TRACE_SECONDS = 5.0


def reference_for(config: dict):
    """The plain reference a configuration names (default: its own name)."""
    return importlib.import_module(
        "benchmarks.reference." + config.get("reference", config["name"]))


class CompileLog:
    """Compilations and persistent-cache hits and misses, stamped, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.compiles: list = []   # (perf_counter, function name, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(),
                                  str(kw.get("fun_name")), float(duration)))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def between(self, t0: float, t1: float) -> list:
        return [c for c in self.compiles if t0 <= c[0] <= t1]


class Profile:
    """One ``jax.profiler`` session over the last seconds of the window,
    reduced after it.  ``poll(now)`` is called from the loop that owns
    the window (the serving driver's main thread, the trainer's end
    trigger) and starts the session at its time; the driver stops it
    once the window has closed, because collecting a trace blocks the
    caller for seconds."""

    def __init__(self, out_dir: str, enabled: bool):
        self.dir = os.path.join(out_dir, "profile")
        self.enabled = enabled
        self.t_start = self.t_stop = None
        self.reduced = None
        self._at = None
        self._state = "idle" if enabled else "done"

    def arm(self, t_open: float, seconds: float):
        self._at = t_open + max(0.0, seconds - TRACE_SECONDS)

    def poll(self, now: float):
        if self._state == "idle" and self._at is not None \
                and now >= self._at:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.t_start = time.perf_counter()
            self._state = "tracing"

    def stop(self):
        if self._state != "tracing":
            return
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self._state = "done"

    def reduce(self):
        """Read the trace that the session wrote (the parse takes a
        second or two of host time)."""
        if not self.enabled or self.t_stop is None:
            return None
        files = sorted(glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not files:
            return None
        self.reduced = xplane.reduce(xplane.load(files[-1]))
        self.reduced["host_window_s"] = self.t_stop - self.t_start
        return self.reduced


def program_spans(t0_wall: float, t1_wall: float) -> list:
    """The program's own host spans (``bigdl_tpu.obs`` tracer, on only in
    a traced run) that started inside [t0, t1] by the wall clock:
    dicts with ``name``, ``start`` (wall), ``dur_s`` and ``attrs``."""
    from bigdl_tpu import obs

    tracer = obs.get_tracer()
    if not getattr(tracer, "enabled", False):
        return []
    tracer.flush()
    out = []
    with open(tracer.jsonl_path, encoding="utf-8") as fh:
        for line in fh:
            rec = json.loads(line)
            if rec.get("kind") != "span":
                continue
            if t0_wall <= rec["wall_time"] <= t1_wall:
                out.append({"name": rec["name"], "start": rec["wall_time"],
                            "dur_s": rec["dur_s"],
                            "attrs": rec.get("attrs") or {}})
    return out


def memory_peak_bytes(devices) -> int:
    """Peak bytes on the fullest chip, as the backend reports them.  The
    TPU backend keeps two counters: ``peak_bytes_in_use`` (live arrays)
    and ``peak_bytes_reserved`` (what running programs reserved for
    their temporaries; a ResNet-50 step's activations are all there).
    Their peaks need not coincide, so the larger of the two is reported:
    a measured lower bound of the true peak, never over the capacity."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"memory_stats chip {d.id}: peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use')}, peak_bytes_reserved "
              f"{stats.get('peak_bytes_reserved')}, bytes_limit "
              f"{stats.get('bytes_limit')}", flush=True)
        peaks.append(max(int(stats.get("peak_bytes_in_use", 0)),
                         int(stats.get("peak_bytes_reserved", 0))))
    return max(peaks) if peaks else 0


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation; the tail of
    all the values given, never of a trimmed set."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    if len(vals) == 1:
        return float(vals[0])
    pos = (len(vals) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (pos - lo))


def median(values) -> float:
    return float(statistics.median(values))


class Check:
    """The numbers a run compares, each beside its limit; ``correct`` is
    that every one holds.  Printed in every run."""

    def __init__(self):
        self.rows = []

    def at_most(self, name: str, value: float, limit: float):
        ok = bool(value <= limit)  # NaN fails
        self.rows.append((name, float(value), "<=", float(limit), ok))
        return ok

    def at_least(self, name: str, value: float, limit: float):
        ok = bool(value >= limit)
        self.rows.append((name, float(value), ">=", float(limit), ok))
        return ok

    def equal(self, name: str, value, want):
        ok = value == want
        self.rows.append((name, value, "==", want, ok))
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r[-1] for r in self.rows)

    def print(self):
        for name, value, op, limit, ok in self.rows:
            print(f"check {name}: {value!r} {op} {limit!r} "
                  f"{'ok' if ok else 'FAILED'}", flush=True)
