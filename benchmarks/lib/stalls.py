"""The stall watch's two spans, as a per-layer metric's reader takes
them (PR 50).

While a traced run's tracer records, ``bigdl_tpu/obs/prof.py``'s watch
minds the program's loop (``loop="serve"``: the engine's thread while it
has work; ``loop="train"``: the trainer's) and writes, on a line of its
own, once a second an ``obs.host`` span (``ticks``, ``late_ms_sum``: how
late the watch's own thread woke) and for every pause of the loop an
``obs.stall`` span from its last span boundary to the next, with a
``cause``.  This module reads them two ways, by ``loop`` and never by
the configuration's kind:

* the share of the window lost to pauses that are not the harness's own
  doing (:func:`stall_share`; a stall whose cause is ``profiler`` is the
  session's start);
* the watch's mean lateness a tick (:func:`wake_late_ms`).

(``obs.host`` also holds ``loop_runq_ms``, the loop's thread runnable
and without a core, where ``/proc`` keeps a ``schedstat``.  The chip's
machines keep none, so no metric reads it: a metric is declared for the
cells in which its reader finds something to read.)

Spans are clipped to the window.  The harness hands a reader the spans
that START inside the window and the window's length, not its ends: the
window is taken to begin at the first of them (the loops' periods are
4.5-65 ms, so it is that much short at the far end at most).  A run
without an ``obs.host`` span for the loop is a program from before the
watch, or a loop that was never minded: every reader returns None.
"""

from __future__ import annotations

HOST = "obs.host"
STALL = "obs.stall"
# causes that are the measurement's doing, not the program's or the
# machine's
NOT_COUNTED = ("profiler",)


def _of(run, name: str, loop: str) -> list:
    return [s for s in run.spans
            if s["name"] == name and s["attrs"].get("loop") == loop]


def window(run):
    """``(start, end)`` of the window on the spans' clock."""
    start = min(s["start"] for s in run.spans)
    return start, start + run.window_s


def _inside(span, window) -> float:
    """Seconds of ``span`` inside ``window``."""
    return max(0.0, min(span["start"] + span["dur_s"], window[1])
               - max(span["start"], window[0]))


def stall_share(run, loop: str):
    """Percent of the window in which ``loop`` stood still in a pause
    the watch wrote down (causes in ``NOT_COUNTED`` aside); 0.0 where it
    watched and met none."""
    if not _of(run, HOST, loop):
        return None
    win = window(run)
    lost = sum(_inside(s, win) for s in _of(run, STALL, loop)
               if s["attrs"].get("cause") not in NOT_COUNTED)
    return 100.0 * lost / run.window_s


def wake_late_ms(run, loop: str):
    """How late the watch's thread woke, a tick, in milliseconds: what a
    freshly woken thread of this process waits for the interpreter and
    a core."""
    hosts = _of(run, HOST, loop)
    ticks = sum(s["attrs"].get("ticks", 0) for s in hosts)
    if not ticks:
        return None
    return sum(s["attrs"]["late_ms_sum"] for s in hosts) / ticks
