"""Request queue + dynamic batcher front half of the serving tier.

Requests flow ``client -> RequestQueue -> engine admission``.  The
queue deliberately reuses :class:`bigdl_tpu.dataset.stream.BoundedBuffer`
— the streaming tier's bounded producer/consumer adapter — because its
behavior is exactly what a serving ingress needs and its depth gauge
(``bigdl_stream_buffer_depth``) is already the queue-depth signal the
autoscaling policy loop (resilience/autoscale.py) natively scrapes:

* a full buffer **backpressures** (clients block in ``submit``, counted
  in ``bigdl_serve_admission_waits_total`` — requests are never
  dropped);
* the live total queue depth is additionally published as
  ``bigdl_serve_queue_depth`` (also in the autoscaler's queue-metric
  set), so a serving process and a streaming trainer can coexist
  without clobbering each other's signal.

Unlike stream records, requests are *not replayable* — the
:class:`_PushSource` ignores the replay offset contract and simply
yields submissions in arrival order; exactly-once here is trivial (a
request completes or its client times out and retries).
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from typing import Any, List, Optional

import numpy as np

from bigdl_tpu.dataset.stream import BoundedBuffer, StreamSource
from bigdl_tpu.obs import names

_ids = itertools.count()


@dataclasses.dataclass
class ServeRequest:
    """One in-flight request (LM decode or classifier forward)."""

    payload: Any                      # prompt token ids / feature array
    max_new_tokens: int = 0           # LM only
    temperature: float = 0.0          # LM only
    id: int = dataclasses.field(default_factory=lambda: next(_ids))
    t_submit: float = dataclasses.field(default_factory=time.monotonic)
    t_first: Optional[float] = None   # first generated token (TTFT)
    t_done: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    # perf_counter() where the engine appended each of ``tokens``
    token_times: List[float] = dataclasses.field(default_factory=list)
    # under a model that drafts its own next-but-one token: for each
    # draft a step verified, (index in ``tokens`` of the token it was
    # checked against, the draft)
    drafts: List[tuple] = dataclasses.field(default_factory=list)
    # under a model that generates by blocks: for every generated
    # position, in order (those of the last block that the answer cuts
    # off included), (its token, the pass of its block that unmasked
    # it); the pass is -1 where the position was still masked when the
    # request ended and -2 where a preemption made it part of the
    # prompt (serving/steps.py NEVER_UNMASKED, GIVEN)
    unmasked: List[tuple] = dataclasses.field(default_factory=list)
    # times the LM engine preempted it (pages reclaimed, the generated
    # prefix folded into the prompt, prefilled again)
    preempted: int = 0
    result: Optional[np.ndarray] = None  # classifier output row(s)
    error: Optional[str] = None
    # request-trace context (obs.reqtrace.RequestTraceContext) when the
    # distributed tracing collector is on; None = untraced, and the
    # engine does zero trace work for this request
    trace: Optional[Any] = None
    _event: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False)

    def finish(self, error: Optional[str] = None):
        self.error = error
        self.t_done = time.monotonic()
        self._event.set()

    def wait(self, timeout: Optional[float] = None) -> "ServeRequest":
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.id} not done after "
                               f"{timeout:g}s")
        return self

    @property
    def done(self) -> bool:
        return self._event.is_set()

    @property
    def e2e_s(self) -> Optional[float]:
        return None if self.t_done is None else self.t_done - self.t_submit

    @property
    def ttft_s(self) -> Optional[float]:
        return None if self.t_first is None \
            else self.t_first - self.t_submit


class _PushSource(StreamSource):
    """Push-fed source: ``put`` appends, ``read`` yields in arrival
    order until :meth:`close`.  The bounded buffer downstream provides
    the depth gauge and producer backpressure; ``put`` itself blocks
    when the *unpulled* backlog reaches ``capacity`` so client-side
    backpressure composes with the buffer's."""

    def __init__(self, capacity: int):
        self.capacity = max(1, int(capacity))
        self._q: collections.deque = collections.deque()
        self._cond = threading.Condition()
        self._closed = False
        from bigdl_tpu import obs

        self._wait_counter = obs.get_registry().counter(
            names.SERVE_ADMISSION_WAITS_TOTAL,
            "Client submits that blocked on a full request queue")

    def put(self, item, timeout: Optional[float] = None):
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while len(self._q) >= self.capacity and not self._closed:
                self._wait_counter.inc()
                remain = None if deadline is None \
                    else deadline - time.monotonic()
                if remain is not None and remain <= 0:
                    raise TimeoutError(
                        f"request queue full for {timeout:g}s")
                self._cond.wait(timeout=0.05 if remain is None
                                else min(0.05, remain))
            if self._closed:
                raise RuntimeError("request queue is closed")
            self._q.append(item)
            self._cond.notify_all()

    def backlog(self) -> int:
        with self._cond:
            return len(self._q)

    def close(self):
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def read(self, offset: int):
        del offset  # requests are not replayable records
        while True:
            with self._cond:
                while not self._q and not self._closed:
                    self._cond.wait(timeout=0.05)
                if self._q:
                    item = self._q.popleft()
                    self._cond.notify_all()
                elif self._closed:
                    return
                else:
                    continue
            yield item


class RequestQueue:
    """Bounded request ingress: ``submit`` on any number of client
    threads, ``take`` on the engine's step loop."""

    def __init__(self, capacity: int = 64):
        self.capacity = max(1, int(capacity))
        self._source = _PushSource(self.capacity)
        self._buf = BoundedBuffer(self._source, self.capacity).start(0)
        self._closed = False
        from bigdl_tpu import obs

        self._depth_gauge = obs.get_registry().gauge(
            names.SERVE_QUEUE_DEPTH,
            "Requests queued ahead of engine admission (backlog + "
            "bounded buffer)")

    def depth(self) -> int:
        d = self._source.backlog() + self._buf.depth()
        self._depth_gauge.set(float(d))
        return d

    def submit(self, req: ServeRequest,
               timeout: Optional[float] = None) -> ServeRequest:
        if self._closed:
            raise RuntimeError("request queue is closed")
        self._source.put(req, timeout=timeout)
        self.depth()
        return req

    def take(self, max_n: int, timeout: float = 0.0) -> List[ServeRequest]:
        """Up to ``max_n`` queued requests; waits at most ``timeout``
        for the *first* one, then drains greedily without blocking."""
        out: List[ServeRequest] = []
        try:
            first = self._buf.get(timeout=max(1e-4, timeout))
        except TimeoutError:
            first = None
            if self._source.backlog():
                # submitted, and the producer thread has not moved it
                # into the buffer yet: it is on its way, so wait for it
                # rather than report an empty queue (a pump or a drain
                # right after a submit would otherwise miss it)
                try:
                    first = self._buf.get(timeout=0.05)
                except TimeoutError:
                    pass
            if first is None:
                self.depth()
                return out
        if first is not None:
            out.append(first)
        while len(out) < max_n:
            if self._buf.depth() <= 0 and not self._source.backlog():
                break
            try:
                rec = self._buf.get(timeout=0.02)
            except TimeoutError:
                break
            if rec is None:
                break
            out.append(rec)
        self.depth()
        return out

    def close(self):
        self._closed = True
        self._source.close()
        self._buf.stop()
        self._depth_gauge.set(0.0)


__all__ = ["ServeRequest", "RequestQueue"]
