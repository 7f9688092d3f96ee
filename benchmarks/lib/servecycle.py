"""The engine's cycle by what the HOST was doing (PR 34).

``serve.decode_step`` and ``serve.prefill`` each hold three children
(``bigdl_tpu/serving/spans.py``): ``serve.dispatch`` (host work: arrays
to the device, the jitted call, the bookkeeping over the running slots;
``dry=`` 1 where nothing launched before was still running),
``serve.wait`` (host slack: the one blocking read of a result) and
``serve.read`` (host work: the per-slot loop over what was read), each
with the cycle's ``step=`` and ``program="step"|"prefill"``.  This
module reads them three ways:

* the host's milliseconds a step in each (:func:`span_ms_per_step`);
* the share of steps dispatched onto a chip that had run dry, apart by
  whether the cycle admitted a request (:func:`dry_share`);
* the chips' idle gaps that ``hostgaps.attribute_serving`` puts under
  ``sync``, cut once more by the child they fall in (:func:`cut_sync`):
  the same gaps, the same engine thread, the same rules before it, so
  the three children and what is left (the parents' own time: the cost
  of the spans) add up to ``sync`` exactly.

A program from before the split has none of the three spans, and every
reader returns None.
"""

from __future__ import annotations

import functools

from benchmarks.lib import hostgaps

CHILDREN = {"dispatch": "serve.dispatch", "wait": "serve.wait",
            "read": "serve.read"}
# idle inside a parent and outside its children
SELF = "self"


def _of_step(run, name: str) -> list:
    return [s for s in run.spans if s["name"] == name
            and s["attrs"].get("program") == "step"]


def span_ms_per_step(run, child: str):
    """Milliseconds the engine's thread spent in the decode step's
    ``child`` spans over the window, a ``serve.decode_step`` span (a
    settled step's wait and read count: they are the same host time)."""
    mine = _of_step(run, CHILDREN[child])
    steps = sum(1 for s in run.spans if s["name"] == hostgaps.SERVE_DECODE)
    if not mine or not steps:
        return None
    return 1e3 * sum(s["dur_s"] for s in mine) / steps


def dry_share(run, admitting: bool):
    """Percent of the window's decode-step dispatches that found the
    chip dry (``dry=1``) in a cycle that admitted a request
    (``admitting``: the chip ran dry behind a synchronous prefill) or in
    one that admitted none (the host's per-slot work outlasted the step
    in flight).  A cycle admitted where a ``serve.admission`` span of
    its ``step`` says ``admitted >= 1``."""
    dispatches = _of_step(run, CHILDREN["dispatch"])
    if not dispatches or "dry" not in dispatches[0]["attrs"]:
        return None
    admitted = {s["attrs"].get("step") for s in run.spans
                if s["name"] == hostgaps.SERVE_ADMISSION
                and s["attrs"].get("admitted", 0) >= 1}
    hits = sum(1 for d in dispatches if d["attrs"]["dry"]
               and (d["attrs"].get("step") in admitted) == admitting)
    return 100.0 * hits / len(dispatches)


@functools.lru_cache(maxsize=2)
def cut_sync(gaps):
    """Nanoseconds of idle, summed over the chips, inside
    ``serve.decode_step`` or ``serve.prefill`` by the child span they
    fall in (``dispatch``, ``wait``, ``read``; ``self`` for the rest),
    and ``chips``.  ``gaps`` is a ``hostgaps.HostGaps``; None where its
    spans hold no engine loop or none of the three children."""
    spans = gaps.spans
    if not spans:
        return None
    tid, _ = hostgaps._loop_thread(
        spans, hostgaps.SERVE_DECODE,
        (hostgaps.SERVE_DECODE, hostgaps.SERVE_PREFILL,
         hostgaps.SERVE_ADMISSION, hostgaps.SERVE_PREP, hostgaps.SERVE_EMIT))
    if tid is None:
        return None
    sync = hostgaps._named(spans, tid, hostgaps.SERVE_DECODE,
                           hostgaps.SERVE_PREFILL)
    # a settled step's wait and read lie outside any parent: not sync's
    inside = [(bucket, hostgaps._intersect(
        sync, hostgaps._named(spans, tid, name)))
        for bucket, name in CHILDREN.items()]
    if not any(where for _, where in inside):
        return None
    # attribute_serving's rules up to ``sync``, first match wins
    rules = [("prep", hostgaps._named(spans, tid, hostgaps.SERVE_PREP)),
             ("emit", hostgaps._named(spans, tid, hostgaps.SERVE_EMIT)),
             *inside, (SELF, sync)]
    chips = hostgaps.chips_of(gaps.trace)
    out: dict = {}
    for chip_gaps in hostgaps.idle_gaps(chips):
        hostgaps._split(chip_gaps, rules, out)
    cut = {bucket: out.get(bucket, 0) for bucket in (*CHILDREN, SELF)}
    cut["chips"] = len(chips)
    return cut


def idle_ms_per_step(run, bucket: str):
    """Idle milliseconds a decode step and chip inside ``bucket``'s
    spans (either program's); steps as ``attribute_serving`` counts
    them."""
    gaps = hostgaps.for_run(run)
    if gaps is None or not gaps.idle or not gaps.idle.get("steps"):
        return None
    cut = cut_sync(gaps)
    if cut is None:
        return None
    return 1e-6 * cut[bucket] / cut["chips"] / gaps.idle["steps"]
