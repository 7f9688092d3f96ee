"""Continuous-batching LM decode engine.

``TransformerLM.generate`` decodes one fixed batch to completion: the
batch waits for its slowest member, and a new request for the batch to
drain.  This engine is the production shape:

* **slots**: up to ``max_batch`` requests decode together in one jitted
  step over the paged KV cache (serving/cache.py);
* **continuous admission**: at every step boundary free slots are
  refilled from the request queue (serving/batcher.py); a finished
  request's slot and pages are reused at once;
* **prefill/decode split**: a new request's prompt runs one batched
  forward (the attention path training uses) padded to a page-aligned
  bucket, writing its K/V pages and producing its first token; the
  shared decode step then advances every active slot;
* **int8 decode** (``int8=True``): the decode matmuls run on
  pre-quantized per-output-channel int8 weights
  (``ops.quantized_matmul``): decode is memory-bound, so the weights'
  bytes are the lever; prefill stays float;
* **TP-sharded decode** (``tp=N``): the step runs under shard_map with
  Megatron row/col-split weights and the block reductions on
  ``parallel/wire.py``'s compressed collectives (serving/tp.py);
* **preemption**: if the page pool is exhausted mid-decode, the
  youngest request is preempted (pages freed, the request re-queued
  with its generated prefix as prompt) instead of deadlocking the batch;
* **one step in flight**: step k+1 is dispatched before step k's
  tokens are read, so the host's emit, admission and prep run while the
  chip decodes.  What a step hands the next is an argument of the next
  step and never visits the host on the way; the host's lengths, pages
  and owed counts advance at dispatch, tokens are emitted one step
  behind, and whatever needs host and chip to agree (preemption, a
  weight swap, ``close``, an engine with nothing left to run) settles
  the step in flight first.  An EOS is learnt one step late: the
  slot's row in the step already dispatched is wasted, never emitted;
* **a prefill is read late too**: what the step behind an admission
  needs of the prefill's result, the prefill itself writes into the
  slot's row of what the steps carry on the device, and the slot's
  bookkeeping is made at the prefill's dispatch from the prompt and the
  request alone.  So a cycle that admits runs ``dispatch prefill(s) ->
  prep -> dispatch step k -> wait/read/emit step k-1 -> wait/read the
  prefill(s)`` and the chip's queue reads ``step k-1, prefill, step k``
  with no gap.  **The order that holds**: a prefill's result is read in
  the pump that dispatched it, after that pump's emit and so BEFORE the
  read of any step dispatched behind it (which needs what the read
  leaves: a block's mask flags, a first token that was an EOS), and a
  settle reads the prefills not yet read after the step in flight,
  which was launched before them.

**What the engine asks of a model**: ``cache_spec(params)`` — how many
cached layers, the width of a token's row, one buffer or two, the
longest context, and, where its decode attention is a page-walking
kernel of ``ops/decode_attention.py``, the query rows a slot it hands
that kernel (``attn_query_rows``: every ``serve.decode_step`` then says
what the kernel's stream copied, ``attn_rows_copied``, and with how
many copy descriptors, ``attn_copies``) — and
``paged_prefill`` / ``paged_decode`` over the cache's buffers, with the
signature of its **kind of step**.  What a kind carries from step to
step, hands a fresh slot, reads back, how far ahead it names pages and
how a read reconciles the host's bounds is ONE object the loop holds
and never asks the name of (``serving/steps.py``, one class a kind,
each with its contract): no further declaration, ``OneToken``
(``models/transformer.py``, ``models/longcat_flash.py``); ``state_spec``,
the same with state a slot carries beside its pages (``models/zaya.py``,
``models/falcon_h1.py``, ``models/ling_flash.py``,
``models/olmo_hybrid.py``); ``draft_spec``, ``Drafting``, one or two
tokens a slot (``models/joyai_flash.py``); ``block_spec``, ``Block``, a
block refined in place (``models/sdar_moe.py``).  Sampling, buckets,
donation, the page tables, spans and ``stats()`` are the engine's; the
layers' internals are the model's.  ``int8=True`` needs the model's
``quantize_for_decode``, ``tp > 1`` its ``tp_decode_step``; a model
without them is refused with a ``ValueError`` that says so.  A model
may also offer ``serving_tables(params)``: parts of the tree its
programs take in another form than the caller's
(``models/transformer.py``: the embeddings with their width padded to
whole lane tiles, so that the gather of a step's rows copies no table),
made once here and again at a ``swap_weights``; ``weights()`` is the
tree the programs are handed, ``params`` stays the caller's.

Telemetry closes the serving loop: ``bigdl_request_latency_seconds
{engine,kind=ttft|per_token|e2e}`` histograms, token/request counters,
batch-occupancy and queue-depth gauges (the autoscaler's signals), a
``bigdl_serve_latency_slo_ratio`` gauge the p99 burn-rate alert rule
watches, and the live ``/healthz`` step stamp via ``obs.server``.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import List, Optional

import numpy as np

from bigdl_tpu import obs
from bigdl_tpu.serving import spans, steps
from bigdl_tpu.serving.batcher import RequestQueue, ServeRequest
from bigdl_tpu.serving.cache import (PagedKVCache, keep_inactive,
                                     write_slot_state)
from bigdl_tpu.serving.drain import HANDOFF_ERROR
from bigdl_tpu.obs import names

LAT_META = (names.REQUEST_LATENCY_SECONDS,
            "Request latency by engine and kind (ttft = time to first "
            "token, per_token = mean inter-token, e2e = submit to done)")
#: why a step in flight was read outside the pipelined loop
SETTLE_REASONS = ("preempt", "swap", "idle", "close")


def sample_step(logits, temps, active, key):
    """The decode step's next tokens, ``logits`` (B, vocab): greedy at
    temperature 0, categorical above it, 0 for an inactive slot.  The
    draw (a random word and two logarithms a logit) runs only in a step
    in which a running slot samples: what decides is the batch's own
    temperatures, inside the one program."""
    import jax
    import jax.numpy as jnp

    def draw():
        sampled = jax.random.categorical(
            key, logits / jnp.maximum(temps, 1e-6)[:, None],
            axis=-1).astype(jnp.int32)
        return jnp.where(temps > 0.0, sampled, pick_greedy(logits))

    with jax.named_scope("sample"):
        nxt = jax.lax.cond(jnp.any(active & (temps > 0.0)), draw,
                           lambda: pick_greedy(logits))
        nxt = jnp.where(active, nxt, 0)
    return nxt


def pick_greedy(logits):
    """The greedy pick, ``logits`` (N, vocab) -> (N,) int32: the largest
    logit, the first among equals.  What a drafting or block model's
    step picks with (a draft is verified by exact match against it) and
    what ``sample_step`` and ``sample_first`` pick with where nothing
    samples."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("sample"):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def sample_first(logits, temp, key):
    """A prompt's first token, ``logits`` (1, vocab)."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("sample"):
        first = jax.lax.cond(
            temp > 0.0,
            lambda: jax.random.categorical(
                key, logits / jnp.maximum(temp, 1e-6),
                axis=-1).astype(jnp.int32),
            lambda: pick_greedy(logits))
    return first[0]


class _InFlight:
    """A dispatched decode step whose tokens the host has not read:
    ``result``, a (B,) device array, the step's tokens (a drafting or
    block model's: a row of results a slot); ``counts``, an expert
    model's routing counts or None; ``entries``, the [(slot, _Active)]
    it ran for; ``context_rows``, the rows of context it reads a slot it
    ran for (a one-token model's: the others' come back with the
    step); ``tables``, the host's copy of the page tables it was handed
    (under a page-walking attention kernel, whose copies are counted at
    the read; else None)."""

    __slots__ = ("result", "counts", "entries", "context_rows", "tables")

    def __init__(self, result, counts, entries, context_rows, tables):
        self.result, self.counts = result, counts
        self.entries, self.context_rows = entries, context_rows
        self.tables = tables


class _Prefilled:
    """A dispatched prefill whose first result the host has not read:
    ``result``, the device array; ``counts``, an expert model's routing
    counts or None; the ``slot`` and its ``act``; ``step``, the cycle's;
    ``admit``, the request trace's record of this admission or None."""

    __slots__ = ("result", "counts", "slot", "act", "step", "admit")

    def __init__(self, result, counts, slot, act, step, admit):
        self.result, self.counts = result, counts
        self.slot, self.act, self.step, self.admit = slot, act, step, admit


class LMEngine:
    """Continuous-batching decode over a :class:`PagedKVCache`."""

    def __init__(self, model, params=None, *, max_batch: Optional[int] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 queue_capacity: Optional[int] = None,
                 int8: Optional[bool] = None, tp: int = 1, wire=None,
                 cache_dtype=None, eos_id: Optional[int] = None,
                 slo_s: Optional[float] = None, seed: int = 0,
                 weight_version: str = "v0"):
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.config import refresh_from_env

        cfg = refresh_from_env().serve
        self.model = model
        self.params = model.params() if params is None else params
        self.max_batch = int(max_batch or cfg.max_batch)
        self.page_size = int(page_size or cfg.page_size)
        self.int8 = cfg.int8 if int8 is None else bool(int8)
        self.tp = int(tp or 1)
        self.eos_id = eos_id
        self.slo_s = cfg.slo_s if slo_s is None else float(slo_s)
        if self.int8 and self.tp > 1:
            raise ValueError("int8 decode and tp-sharded decode are "
                             "currently exclusive")
        for feature, on, method in (
                ("int8=True", self.int8, "quantize_for_decode"),
                ("tp > 1", self.tp > 1, "tp_decode_step")):
            if on and not hasattr(model, method):
                raise ValueError(
                    f"{type(model).__name__} does not offer {feature} "
                    f"serving (it has no {method})")
        # the model states its cache; the engine builds and owns it
        spec = model.cache_spec(self.params)
        self._cache_spec = spec
        self.max_len = int(spec["max_len"])
        # ... and the kind of step it takes, with the state a slot
        # carries beside its pages (serving/steps.py)
        kind, state = steps.choose(
            model, self.params, page_size=self.page_size,
            max_len=self.max_len, int8=self.int8, tp=self.tp)
        if cache_dtype is None:
            cache_dtype = spec["dtype"]
        pages = num_pages or cfg.num_pages or (
            1 + self.max_batch * -(-self.max_len // self.page_size))
        self.cache = PagedKVCache(
            int(spec["layers"]), spec.get("kv_heads", spec.get("heads")),
            spec.get("head_dim"),
            row_width=int(spec["row_width"]), buffers=int(spec["buffers"]),
            page_size=self.page_size, num_pages=pages,
            max_slots=self.max_batch, max_len=self.max_len,
            dtype=cache_dtype, state_spec=state)
        self.queue = RequestQueue(queue_capacity or cfg.queue_capacity)
        self._slots: List[Optional[steps._Active]] = [None] * self.max_batch
        #: all that differs between kinds of step.  The picks and the
        #: cache's writes of a slot's state go in by THIS module's names:
        #: replaced here (a fault the benchmark's tests inject), they
        #: are replaced in the programs
        self._kind = kind(model, spec, self.cache, self.page_size, eos_id,
                          steps.DeviceOps(sample_step, sample_first,
                                          pick_greedy, write_slot_state,
                                          keep_inactive))
        self._stash: collections.deque = collections.deque()
        self._key = jax.random.key(int(seed))
        self._qparams = (model.quantize_for_decode(self.params)
                        if self.int8 else None)
        self._order = 0
        self._steps = 0
        self._occ_sum = 0.0
        self._tokens_total = 0
        self._t_first_work: Optional[float] = None
        self._t_last_done: Optional[float] = None
        self.completed: List[dict] = []
        self._slo_window: collections.deque = collections.deque(maxlen=256)
        self.weight_version = str(weight_version)
        self.manifest_sha: Optional[str] = None
        self.swaps = 0
        self.draining = False
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.RLock()

        self._last_bucket = self.cache.max_pages_per_slot
        self._decode_ms_sum = 0.0
        # the step in flight (dispatched, its tokens unread) and the
        # last dispatched step's tokens, the next step's input
        self._inflight: Optional[_InFlight] = None
        # the prefills dispatched since, their first results unread:
        # empty between two pumps
        self._unread: List[_Prefilled] = []
        self._admitted = self._read_late = 0
        # what a step hands the next on the device
        self._carry = self._kind.carry()
        self._slot_steps = self._step_tokens = 0
        self._steps_ahead = 0
        self._steps_sampled = 0
        self._settles = dict.fromkeys(SETTLE_REASONS, 0)
        self._weight_bytes = self._decode_weight_bytes()
        if self.tp > 1:
            self._step_fn = model.tp_decode_step(
                tp=self.tp, wire=wire, page_size=self.page_size,
                max_batch=self.max_batch,
                positions=self.cache.padded_positions())
        else:
            self._step_fn = self._build_step()
            self.params = jax.tree.map(
                jnp.asarray, self.params,
                is_leaf=lambda x: x is None or hasattr(x, "shape"))
        # what the model's programs take in place of parts of the tree
        # (a table re-laid once for their gather; a swap renews it)
        self._tables = self._serving_tables(self.params)
        self._prefill_fns: dict = {}
        self._tracer = obs.NULL_TRACER  # pump() looks it up each cycle
        # the stall watch's handle while this loop is minded (``_mind``)
        self._minded = None
        from bigdl_tpu.obs import prof as _obs_prof

        # continuous profiler: starts with the engine when
        # BIGDL_PROF_HZ > 0 (unset: one config read, no thread)
        _obs_prof.get_profiler()
        reg = obs.get_registry()
        self._lat = reg.histogram(*LAT_META, labels=("engine", "kind"))
        self._tokens_counter = reg.counter(
            names.SERVE_TOKENS_TOTAL, "Tokens generated by the LM "
            "decode engine")
        self._req_counter = reg.counter(
            names.SERVE_REQUESTS_TOTAL,
            "Requests completed, by engine and status",
            labels=("engine", "status"))
        self._occ_gauge = reg.gauge(
            names.SERVE_BATCH_OCCUPANCY,
            "Mean fraction of decode slots occupied per step")
        self._tps_gauge = reg.gauge(
            names.SERVE_TOKENS_PER_SECOND,
            "LM decode throughput over the engine's busy wall clock")
        self._slo_gauge = reg.gauge(
            names.SERVE_LATENCY_SLO_RATIO,
            "Fraction of recent requests completing within the "
            "latency SLO (feeds the serve_latency_slo_burn alert)")
        self._preempt_counter = reg.counter(
            names.SERVE_PREEMPTIONS_TOTAL,
            "Requests preempted (pages reclaimed, request re-queued) "
            "on KV-page exhaustion")
        self._decode_ms_gauge = reg.gauge(
            names.SERVE_DECODE_ATTN_MS,
            "Mean wall-clock of a decode step on the engine's thread, "
            "in milliseconds: the dispatch of step k, the wait for step "
            "k-1's tokens and their read, work and slack in one sum "
            "(the spans serve.dispatch, serve.wait and serve.read give "
            "the parts)")
        self._ahead_counter = reg.counter(
            names.SERVE_STEPS_AHEAD_TOTAL,
            "Decode steps dispatched while the previous step's tokens "
            "were still unread")
        self._pick_counter = reg.counter(
            names.SERVE_STEPS_TOTAL,
            "Decode steps dispatched, by the arm their pick took",
            labels=("pick",))
        self._settle_counter = reg.counter(
            names.SERVE_SETTLES_TOTAL,
            "Steps in flight read outside the pipelined loop, by "
            "reason", labels=("reason",))
        self._decode_bytes_gauge = reg.gauge(
            names.SERVE_DECODE_HBM_BYTES_PER_TOKEN,
            "Analytic HBM bytes streamed per generated token (decode "
            "weights + the KV pages the step's page-table bucket "
            "names)")
        self._moe_counter = self._moe_gauge = None  # an expert model's
        self._swap_counter = reg.counter(
            names.SERVE_WEIGHT_SWAPS_TOTAL,
            "Live weight hot-swaps completed, by promoted version",
            labels=("version",))

    def _note_routing(self, counts) -> dict:
        """An expert model's routing counts (``nn/experts.py``
        ``COUNT_NAMES``, summed over the step's expert layers) into the
        registry; returns them as the span's ``moe_*`` attributes."""
        from bigdl_tpu.nn.experts import counts_dict

        c = counts_dict(counts)
        if self._moe_counter is None:
            reg = obs.get_registry()
            self._moe_counter = reg.counter(
                names.SERVE_MOE_ASSIGNMENTS_TOTAL,
                "Token-to-expert assignments by kind (held, zero, "
                "absent)", labels=("kind",))
            self._moe_gauge = reg.gauge(
                names.SERVE_MOE_LOAD_MAX_OVER_MEAN,
                "Largest over mean load of the held experts, last step")
        for kind in ("held", "zero", "absent"):
            self._moe_counter.labels(kind=kind).inc(c[kind])
        slots = self._cache_spec.get("expert_slots")
        if c["held"] and slots:
            self._moe_gauge.set(c["max_load"] * slots / c["held"])
        return {f"moe_{k}": v for k, v in c.items()}

    def _decode_weight_bytes(self) -> float:
        """Static per-step weight-stream bytes of the decode matmuls —
        one read of every parameter byte per token (decode is
        memory-bound; int8 engines stream the 1-byte twins instead of
        the float matmul weights)."""
        total = 0.0
        leaves = []

        def walk(t):
            if isinstance(t, dict):
                for v in t.values():
                    walk(v)
            elif t is not None and hasattr(t, "size"):
                leaves.append(t)

        walk(self.params)
        for leaf in leaves:
            item = leaf.dtype.itemsize if hasattr(leaf, "dtype") else 4
            # int8 decode replaces every >=2-D matmul weight with its
            # 1-byte twin (+ negligible per-channel scales)
            if self._qparams is not None and getattr(leaf, "ndim", 0) >= 2:
                item = 1
            total += float(leaf.size) * item
        return total

    def _serving_tables(self, params) -> dict:
        made = getattr(self.model, "serving_tables", None)
        return made(params) if made is not None else {}

    def weights(self):
        """The tree ``jit_step`` and ``jit_prefill`` are handed:
        ``params`` with what the model prepared for serving
        (``serving_tables``) in its place.  Merged at each dispatch (a
        dict of the tree's top level) and not kept: a kept tree would
        hold the caller's weights alive after ``params`` lets go."""
        if not self._tables:
            return self.params
        return {**self.params, **self._tables}

    # ------------------------------------------------------------ hot swap
    def swap_weights(self, params, *, version: str,
                     manifest_sha: Optional[str] = None) -> None:
        """Hot-swap the served weights between decode steps.

        All the expensive work — the host->device transfer of the new
        tree and (int8) requantizing the per-channel twins — happens on
        the CALLER's thread, outside the engine lock; the swap itself
        is a pointer flip the decode loop observes at its next
        ``pump`` cycle.  Under the lock, before the flip, the step in
        flight is settled (its tokens read and emitted): it ran on the
        old weights, so every token emitted before this call returns
        follows them, and the first step on the new weights is
        dispatched with nothing pending.  A step's wall time on the
        engine's thread (``decode_ms_mean``) spans the dispatch of step
        k and the wait for step k-1's tokens; the settle here is
        outside any step.  Page tables, slots and in-flight decodes
        survive untouched: the step and prefill functions take the
        params tree as an argument, so nothing recompiles on the float
        path.  The int8 step closes over the quantized twins, so that
        engine rebuilds its jitted step under the lock (retraced
        lazily on the next step dispatch).

        Requests already decoding keep their old-weights KV prefix and
        continue on the new weights — they complete, on a mixed
        trajectory; requests admitted after the swap decode bit-equal
        to ``generate()`` on the new weights at temperature 0.
        """
        import jax
        import jax.numpy as jnp

        if self.tp == 1:
            params = jax.tree.map(
                jnp.asarray, params,
                is_leaf=lambda x: x is None or hasattr(x, "shape"))
        qparams = (self.model.quantize_for_decode(params)
                   if self.int8 else None)
        tables = self._serving_tables(params)
        with self._lock:
            self._settle("swap")
            self.params = params
            self._qparams = qparams
            self._tables = tables
            if self.int8:
                self._step_fn = self._build_step()
            self._weight_bytes = self._decode_weight_bytes()
            self.weight_version = str(version)
            self.manifest_sha = manifest_sha
            self.swaps += 1
        self._swap_counter.labels(version=str(version)).inc()
        obs.get_tracer().event(spans.EVENT_WEIGHT_SWAP,
                               version=str(version),
                               sha=manifest_sha or "",
                               swaps=self.swaps)

    # -------------------------------------------------------- jit builders
    def _build_step(self):
        import jax

        # the kind's body under the one name the profiler and the
        # readers of its trace know the decode step by: ``jit_step``
        return jax.jit(self._kind.step(self._qparams), donate_argnums=tuple(
            range(1, 1 + len(self.cache.buffers()))))

    def _prefill_fn(self, bucket: int):
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            import jax

            fn = self._prefill_fns[bucket] = jax.jit(
                self._kind.prefill(), donate_argnums=tuple(
                    range(1, 1 + len(self.cache.buffers()))))
        return fn

    def _bucket(self, t0: int) -> int:
        b = self.page_size
        while b < t0:
            b *= 2
        return min(b, -(-self.max_len // self.page_size) * self.page_size)

    # ------------------------------------------------------------- clients
    def submit(self, prompt, max_new_tokens: int, *,
               temperature: float = 0.0,
               timeout: Optional[float] = None,
               trace=None) -> ServeRequest:
        if self.draining:
            raise RuntimeError("engine is draining — admissions closed")
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) + int(max_new_tokens) > self.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + {max_new_tokens} new tokens "
                f"exceeds max_len {self.max_len}")
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self._kind.refuse(temperature)
        # feasibility: a request that can NEVER fit the page pool even
        # alone would preempt-loop forever — reject it at the door
        worst = self.cache.pages_for(len(prompt) + int(max_new_tokens))
        if worst > self.cache.num_pages - 1:
            raise ValueError(
                f"request needs {worst} KV pages but the pool has "
                f"{self.cache.num_pages - 1}")
        # request tracing: attach (or mint) a context only when the
        # collector is on — with BIGDL_REQTRACE_SAMPLE=0 this whole
        # branch is two attribute loads and the engine carries no
        # trace state at all
        from bigdl_tpu.obs import reqtrace
        col = reqtrace.get_collector()
        if col.enabled:
            if trace is None:
                trace = col.new_context()
            col.begin(trace)
        else:
            trace = None
        req = ServeRequest(payload=prompt,
                           max_new_tokens=int(max_new_tokens),
                           temperature=float(temperature),
                           trace=trace)
        if trace is not None:
            req._tr_admits = []    # [{t, dur, bucket, prompt_len, slot}]
            req._tr_preempts = []  # [t_preempted]
        return self.queue.submit(req, timeout=timeout)

    # ----------------------------------------------------------- admission
    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def active_count(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    def _admit(self, wait_s: float = 0.0) -> int:
        free = self._free_slots()
        if not free:
            return 0
        wanted = len(free)
        incoming = list(self._stash)
        self._stash.clear()
        if len(incoming) < wanted:
            incoming.extend(
                self.queue.take(wanted - len(incoming), timeout=wait_s))
        if not incoming:
            return 0  # an idle engine's empty polls leave no span
        admitted = 0
        tracer = self._tracer
        with tracer.span(spans.SPAN_ADMISSION, step=self._steps,
                         offered=len(incoming)) as span_id:
            for req in incoming:
                slot = None
                for i, s in enumerate(self._slots):
                    if s is None:
                        slot = i
                        break
                # pages are allocated for the PROMPT, not the (pow2)
                # compile bucket — the bucket's padded tail writes to
                # the trash page
                if slot is None or \
                        not self.cache.can_admit(len(req.payload)):
                    self._stash.append(req)  # head-of-line, retried first
                    continue
                self._prefill_into(slot, req,
                                   self._bucket(len(req.payload)))
                admitted += 1
            tracer.add_attrs(span_id, admitted=admitted)
        return admitted

    def _prefill_into(self, slot: int, req: ServeRequest, bucket: int):
        """Dispatch ``req``'s prefill for ``slot`` and make the slot's
        bookkeeping: nothing of the result is read here
        (``_read_prefills``)."""
        import jax
        import jax.numpy as jnp

        t_admit = time.monotonic()
        t0 = len(req.payload)
        pages = self.cache.alloc(slot, t0)
        page_arg = np.zeros((bucket // self.page_size,), np.int32)
        page_arg[:len(pages)] = pages
        prompt = np.zeros((1, bucket), np.int32)
        prompt[0, :t0] = req.payload
        self._key, sub = jax.random.split(self._key)
        tracer = self._tracer
        n, handed = len(self.cache.buffers()), self._kind.handed
        step = self._steps
        self._order += 1
        with tracer.span(spans.SPAN_STEP_PREFILL, step=step,
                         bucket=bucket, prompt_len=t0,
                         request=req.id) as span_id:
            self._kind.begin_prefill(req, tracer, span_id)
            with tracer.span(spans.SPAN_STEP_DISPATCH, step=step,
                             program="prefill") as dispatch_id:
                self._note_dry(tracer, dispatch_id)
                # behind the engine's arguments, the slot and the arrays
                # of the carry the prefill writes the slot's row of
                out = self._prefill_fn(bucket)(
                    self.weights(), *self.cache.buffers(),
                    jnp.asarray(prompt), t0, jnp.asarray(page_arg),
                    float(req.temperature), sub, np.int32(slot),
                    *self._carry[:handed])
                self.cache.set_buffers(out[:n])
                self._carry = (*out[n:n + handed], *self._carry[handed:])
                # what the host reads: the first result, and an expert
                # model's counts
                result = out[n + handed:]
                for arr in result:
                    arr.copy_to_host_async()
                self.cache.lengths[slot] = t0
                act = self._kind.admit(slot, req, self._order)
        admit = None
        if req.trace is not None:
            # (``dur`` runs to the read of the first result)
            admit = {"t": t_admit, "dur": time.monotonic() - t_admit,
                     "bucket": bucket, "prompt_len": t0, "slot": slot}
            req._tr_admits.append(admit)
        if self._t_first_work is None:
            self._t_first_work = time.monotonic()
        self._slots[slot] = act
        self._admitted += 1
        self._unread.append(_Prefilled(
            result[0], result[1] if len(result) > 1 else None, slot, act,
            step, admit))
        tracer.event(spans.EVENT_ADMIT, slot=slot, request=req.id,
                     prompt_len=t0, bucket=bucket)

    def _read_prefills(self, tracer, late: bool) -> bool:
        """Read, in the order of their dispatch, the prefills not read
        yet: the first token to its request, the kind's bookkeeping, an
        expert model's counts, and the end of a request whose first
        token was its last.  ``late``: a step was dispatched behind
        them, the pipelined loop's case.  False where there was none."""
        if not self._unread:
            return False
        pending, self._unread = self._unread, []
        for rec in pending:
            with tracer.span(spans.SPAN_STEP_WAIT, step=rec.step,
                             program="prefill"):
                first = np.asarray(rec.result)
            slot, act = rec.slot, rec.act
            req = act.req
            with tracer.span(spans.SPAN_STEP_READ, step=rec.step,
                             program="prefill", request=req.id,
                             late=int(late)) as span_id:
                tok = self._kind.prefilled(act, first)
                if rec.counts is not None:
                    tracer.add_attrs(span_id,
                                     **self._note_routing(rec.counts))
                if rec.admit is not None:
                    rec.admit["dur"] = time.monotonic() - rec.admit["t"]
                self._read_late += late
                if tok is not None:
                    self._first_token(req)
                    self._give(req, tok)
                    # by count, or an EOS learnt one cycle late: the
                    # slot's row in a step dispatched since is wasted
                    if act.left <= 0 or tok == self.eos_id:
                        self._complete(slot)
        return True

    def _give(self, req: ServeRequest, tok: int):
        req.tokens.append(tok)
        req.token_times.append(time.perf_counter())
        self._tokens_total += 1
        self._tokens_counter.inc()

    def _first_token(self, req: ServeRequest):
        """Stamp a request's first token (once: a preempted request's
        second admission has it)."""
        if req.t_first is None:
            req.t_first = time.monotonic()
            self._lat.labels(engine="lm", kind="ttft").observe(
                req.t_first - req.t_submit)

    def _preempt_youngest(self) -> Optional[int]:
        """Free the youngest active slot's pages; its request re-queues
        with the generated prefix folded into the prompt.  The step in
        flight and the prefills unread are settled first: the fold needs
        every dispatched token in ``req.tokens``, a first token
        included (and a slot it completes is no victim)."""
        self._settle("preempt")
        victims = [(s.order, i) for i, s in enumerate(self._slots)
                   if s is not None]
        if not victims:
            return None
        _, slot = max(victims)
        act = self._slots[slot]
        req = act.req
        # generated-since-admission tokens fold into the prompt; the
        # still-owed budget becomes the new max_new_tokens (req.tokens
        # keeps everything, so the client sees one contiguous output)
        gen = req.max_new_tokens - act.remaining
        req.payload = list(req.payload) + [
            int(t) for t in req.tokens[len(req.tokens) - gen:]]
        req.max_new_tokens = act.remaining
        self._kind.record(slot, act, preempted=True)
        self.cache.release(slot)
        self._slots[slot] = None
        self._stash.appendleft(req)
        self._preempt_counter.inc()
        req.preempted += 1
        if req.trace is not None:
            req._tr_preempts.append(time.monotonic())
        obs.get_tracer().event(spans.EVENT_PREEMPT, slot=slot,
                               request=req.id, owed=act.remaining)
        return slot

    # ---------------------------------------------------------------- step
    def _complete(self, slot: int, error: Optional[str] = None):
        act = self._slots[slot]
        self._kind.record(slot, act)
        self.cache.release(slot)
        self._slots[slot] = None
        req = act.req
        now = time.monotonic()
        exemplar = None
        if req.trace is not None:
            # finalize BEFORE finish() wakes the client thread, so the
            # engine's spans reach the collector before a same-process
            # router can race the tail-sampling decision
            kept = self._finalize_trace(req, error, now)
            if kept:
                exemplar = {"trace_id": req.trace.trace_id}
        req.finish(error)
        self._t_last_done = now
        e2e = req.e2e_s
        self._lat.labels(engine="lm", kind="e2e").observe(
            e2e, exemplar=exemplar)
        n_tok = len(req.tokens)
        if n_tok > 1:
            self._lat.labels(engine="lm", kind="per_token").observe(
                (req.t_done - req.t_first) / (n_tok - 1))
        self._req_counter.labels(
            engine="lm", status="error" if error else "ok").inc()
        self.completed.append(
            {"id": req.id, "e2e_s": e2e, "ttft_s": req.ttft_s,
             "tokens": n_tok,
             "itl_s": np.diff(np.asarray(req.token_times, np.float64))})
        if self.slo_s > 0:
            self._slo_window.append(1.0 if e2e <= self.slo_s else 0.0)
            self._slo_gauge.set(
                sum(self._slo_window) / len(self._slo_window))
        if self._t_first_work is not None and now > self._t_first_work:
            self._tps_gauge.set(
                self._tokens_total / (now - self._t_first_work))

    def _finalize_trace(self, req: ServeRequest, error: Optional[str],
                        now: float) -> bool:
        """Partition the request's engine-side e2e into lifecycle spans
        and push them through the tail sampler.  The partition is EXACT:
        queue + prefill + preempt + decode == e2e by construction
        (decode is the remainder), which is what makes the report's
        per-hop attribution sum to the measured end-to-end time.
        Returns whether the tail sampler kept the trace."""
        from bigdl_tpu.obs import reqtrace
        col = reqtrace.get_collector()
        ctx = req.trace
        e2e = max(0.0, now - req.t_submit)
        admits = getattr(req, "_tr_admits", [])
        preempts = getattr(req, "_tr_preempts", [])
        queue = (max(0.0, admits[0]["t"] - req.t_submit)
                 if admits else e2e)
        prefill = sum(a["dur"] for a in admits)
        col.span(ctx, spans.SPAN_QUEUE, req.t_submit, queue, engine="lm")
        for a in admits:
            col.span(ctx, spans.SPAN_PREFILL, a["t"], a["dur"],
                     slot=a["slot"], bucket=a["bucket"],
                     prompt_len=a["prompt_len"], engine="lm")
        # each preemption pairs with the NEXT admission: the gap is the
        # refold + re-queue wait the preemption cost this request
        preempt_wait = 0.0
        for i, tp in enumerate(preempts):
            if i + 1 < len(admits):
                gap = max(0.0, admits[i + 1]["t"] - tp)
                preempt_wait += gap
                col.span(ctx, spans.SPAN_PREEMPT, tp, gap, engine="lm")
        decode = max(0.0, e2e - queue - prefill - preempt_wait)
        t_dec = req.t_first if req.t_first is not None else now
        col.span(ctx, spans.SPAN_DECODE, t_dec, decode,
                 tokens=len(req.tokens), engine="lm")
        kept, _ = col.finish(
            ctx,
            request=str(getattr(req, "router_id", None) or req.id),
            error=error, preempted=bool(preempts),
            slo_violation=(self.slo_s > 0 and e2e > self.slo_s),
            handoff=(error == HANDOFF_ERROR), e2e_s=e2e)
        return kept

    def _runs(self, slot: int) -> bool:
        """Whether ``slot`` owes a token beyond those dispatched."""
        act = self._slots[slot]
        return act is not None and act.remaining > 0

    def _step(self):
        """Dispatch the next decode step, THEN read and emit the one
        before it: while the host does that, reads the cycle's prefills
        (``pump``), admits and prepares again, the chip runs the step
        just dispatched."""
        import jax
        import jax.numpy as jnp

        if not any(self._runs(i) for i in range(self.max_batch)):
            # nothing to dispatch: what is in flight is all there is
            return self._settle("idle")
        # used-page prefix bucket (pow2): the step stops gathering the
        # empty pool; each bucket is one compiled variant
        from bigdl_tpu.ops.decode_attention import (decode_hbm_bytes,
                                                    used_page_bucket)

        tracer = self._tracer
        step = self._steps
        with tracer.span(spans.SPAN_STEP_PREP, step=step) as span_id:
            # grow pages where the step's last row crosses a page
            # boundary.  Exhaustion first settles the step in flight
            # (a request it completes frees pages), then preempts the
            # youngest request (possibly this one)
            for slot in range(self.max_batch):
                while self._runs(slot) and self.cache.needs_growth(
                        slot, self._ahead(slot)):
                    if self.cache.grow(slot) or self._settle("preempt"):
                        continue
                    victim = self._preempt_youngest()
                    if victim is None or victim == slot:
                        break
            running = [i for i in range(self.max_batch) if self._runs(i)]
            if not running:
                return False
            acts = [(i, self._slots[i]) for i in running]
            self._key, sub = jax.random.split(self._key)
            # the step's arguments behind the carry, as host arrays:
            # what the host knows of a slot admitted since the last
            # step without reading its prefill (the rest of its input
            # the prefill wrote into the carry)
            host = self._kind.host_args(acts, sub)
            longest = max(int(self.cache.lengths[i]) + self._ahead(i)
                          for i in running)
            bucket = used_page_bucket(longest, self.page_size,
                                      self.cache.max_pages_per_slot)
            self._last_bucket = bucket
            tables, lengths = self.cache.device_tables(pages=bucket)
            # (the tables grow while the step is in flight)
            host_tables = np.array(self.cache.page_tables[:, :bucket]) \
                if self._kind.query_rows else None
            # running slots that sample: 0 means the step's pick takes
            # its greedy arm (``sample_step``)
            sampling = sum(act.req.temperature > 0.0 for _, act in acts)
            tracer.add_attrs(span_id, bucket=bucket, active=len(running))
        t0 = time.perf_counter()
        # a LIVE span (not a retroactive reqtrace hop).  It covers this
        # step's dispatch, then the wait for the PREVIOUS step's tokens
        # and their read: its three children, and the continuous
        # profiler attributes a sample to the innermost of them by name
        n = len(self.cache.buffers())
        prev = self._inflight
        with tracer.span(spans.SPAN_STEP_DECODE, bucket=bucket,
                         active=len(running), sampling=sampling,
                         ahead=int(prev is not None)) as span_id:
            with tracer.span(spans.SPAN_STEP_DISPATCH, step=step,
                             program="step") as dispatch_id:
                self._note_dry(tracer, dispatch_id)
                # (a key among them is the device's already)
                host = [jnp.asarray(a) if isinstance(a, np.ndarray) else a
                        for a in host]
                out = self._step_fn(
                    self.weights(), *self.cache.buffers(), tables, lengths,
                    *self._carry, *host)
                self.cache.set_buffers(out[:n])
                k = len(self._carry)
                self._carry = out[n:n + k]
                # what the host reads: the tokens or result rows, and an
                # expert model's counts
                result = out[n + self._kind.result_at:]
                for arr in result:
                    # on their way to the host as soon as they exist,
                    # not when the next pump asks for them
                    arr.copy_to_host_async()
                # the host's state advances at dispatch, by the tokens
                # a step is sure to yield: the next prep (growth,
                # bucket, who runs) needs no token.  (Where a step may
                # yield none, the host's state advances when the step
                # is read.)
                context, sure = [], self._kind.sure
                for i, act in acts:
                    self.cache.lengths[i] += sure
                    context.append(int(self.cache.lengths[i]))
                    act.remaining -= sure
                    act.unread += 1
                self._inflight = _InFlight(
                    result[0], result[1] if len(result) > 1 else None,
                    acts, context, host_tables)
            if prev is not None:
                read = self._read(prev, tracer, step)
                # what the step just read routed and yielded, and the
                # rows of context it had to read
                tracer.add_attrs(span_id, **read.attrs)
        step_ms = (time.perf_counter() - t0) * 1000.0
        with tracer.span(spans.SPAN_STEP_EMIT, step=step):
            self._steps += 1
            self._decode_ms_sum += step_ms
            self._decode_ms_gauge.set(self._decode_ms_sum / self._steps)
            kv_item = self.cache.dtype.itemsize
            # without per-head rows a row is one head; one buffer is
            # half of K + V; a row holds the key/value heads, the
            # queries and the output the query heads at each position
            heads = self._cache_spec.get("heads", 1)
            kv_heads = self._cache_spec.get("kv_heads", heads)
            step_bytes = self._weight_bytes + \
                self.cache.n_layer * len(self.cache.pools()) / 2.0 \
                * decode_hbm_bytes(
                    self.max_batch, heads,
                    self.cache.row_width // kv_heads, self.page_size,
                    bucket, kv_item, kv_heads=kv_heads,
                    positions=self._kind.positions)
            self._decode_bytes_gauge.set(step_bytes / len(running))
            self._occ_sum += len(running) / self.max_batch
            self._occ_gauge.set(self._occ_sum / self._steps)
            self._steps_sampled += sampling > 0
            self._pick_counter.labels(
                pick="sampled" if sampling else "greedy").inc()
            if prev is not None:
                self._steps_ahead += 1
                self._ahead_counter.inc()
                self._emit(prev, read)
            try:
                from bigdl_tpu.obs import server as obs_server

                obs_server.note_step(self._steps)
            except Exception:  # noqa: BLE001 — telemetry must not kill serving
                pass
        return True

    def _ahead(self, slot: int) -> int:
        """How far past its (lower-bound) length the slot's next step
        may write."""
        return self._kind.ahead(self._slots[slot],
                                int(self.cache.lengths[slot]))

    def _note_dry(self, tracer, dispatch_id):
        """Say on an open ``serve.dispatch`` whether the chip had run
        dry: nothing launched before is still running, neither the
        step in flight nor a prefill not yet read.  Asked of the device
        buffers without blocking, before the host ships its arrays, and
        only under a recording tracer."""
        if tracer.enabled:
            tracer.add_attrs(dispatch_id, dry=int(self._chip_ready()))

    def _read(self, rec: _InFlight, tracer, step: int) -> steps._StepRead:
        """Wait for a dispatched step's tokens (``serve.wait``: the one
        blocking read), then take them apart (``serve.read``)."""
        with tracer.span(spans.SPAN_STEP_WAIT, step=step, program="step"):
            res = np.asarray(rec.result)
        with tracer.span(spans.SPAN_STEP_READ, step=step, program="step"):
            return self._kind.read(rec, res, self._slots,
                                   self._note_routing)

    def _emit(self, rec: _InFlight, read: steps._StepRead):
        """Hand a read step's tokens to their requests, and bring the
        host's bounds up to what the step turned out to yield."""
        for slot, act in rec.entries:
            act.unread -= 1
            if self._slots[slot] is not act:
                # completed on an EOS after this step was dispatched:
                # the row was wasted, its token is no one's
                continue
            n, start = self._kind.yielded(slot, act, read)
            if n:       # 0: a wasted row, or no position final yet
                req = act.req
                self._slot_steps += 1
                self._first_token(req)
                for j in range(n):
                    tok = int(read.tokens[slot, start + j])
                    self._give(req, tok)
                    self._step_tokens += 1
                    act.left -= 1
                    if act.left <= 0 or tok == self.eos_id:
                        self._complete(slot)
                        break
            if self._slots[slot] is act:
                self._kind.after_emit(slot, act, read)

    def _settle(self, reason: str) -> bool:
        """Read and emit the step in flight, then the prefills not yet
        read (launched behind it), outside the pipelined loop: before
        anything that needs the host and the chip to agree (``reason``
        one of ``SETTLE_REASONS``).  An event, not a
        ``serve.decode_step`` span: that step has its span already.
        False where nothing was in flight; only a step counts as a
        settle."""
        rec = self._inflight
        if rec is None and not self._unread:
            return False
        tracer = obs.get_tracer()  # not always inside a pump
        if rec is not None:
            self._inflight = None
            # the last step dispatched: its wait and read lie outside
            # any ``serve.decode_step`` and carry its own step
            read = self._read(rec, tracer, self._steps - 1)
            tracer.event(spans.EVENT_SETTLE, reason=reason, **read.attrs)
            with tracer.span(spans.SPAN_STEP_EMIT, step=self._steps):
                self._emit(rec, read)
            self._settles[reason] += 1
            self._settle_counter.labels(reason=reason).inc()
        self._read_prefills(tracer, late=False)
        return True

    # ---------------------------------------------------------- driving
    def pump(self, wait_s: float = 0.0) -> bool:
        """One cycle: admit (the prefills dispatched, nothing read),
        dispatch the next decode step, read and emit the one before it,
        then read the cycle's prefills: their first tokens.  True while
        there is work, a step in flight included: a request's last
        token is emitted by the cycle AFTER the one that dispatched it
        (drive with :meth:`run_until_idle`, not with a counted number of
        pumps)."""
        with self._lock:
            # one look at the configuration a cycle; its spans share it
            self._tracer = obs.get_tracer()
            self._admit(wait_s=wait_s if not self.active_count() else 0.0)
            stepped = self._step()
            # the prefills a settle has not read lie behind a step this
            # cycle dispatched: no pump ends with one unread
            self._read_prefills(self._tracer, late=True)
            work = stepped or self._inflight is not None \
                or bool(self._stash) or self.queue.depth() > 0
            if self._tracer.enabled:
                self._mind(work)
            return work

    def _mind(self, work: bool):
        """Under a recording tracer the stall watch (``obs/prof.py``)
        minds the thread that pumps, from the first cycle with work
        until a cycle finds none (an idle engine is no stall): the
        cycle's span boundaries are its heartbeat, and a pause between
        two of them becomes an ``obs.stall`` span that says where every
        thread stood and whether the chip was waiting."""
        minded = self._minded
        if minded is not None and not (
                work and minded.ident == threading.get_ident()
                and minded.watch.tracer is self._tracer):
            minded.drop()
            minded = self._minded = None
        if work and minded is None:
            from bigdl_tpu.obs import prof

            self._minded = prof.get_watch().add(
                "serve", probe=self._chip_ready)

    def _chip_ready(self) -> bool:
        """The stall watch's probe, asked from its thread without
        blocking: whether nothing launched is still running (the
        question ``dry=`` asks at a dispatch)."""
        return all(bool(rec.result.is_ready())
                   for rec in (self._inflight, *self._unread)
                   if rec is not None)

    def run_until_idle(self, timeout_s: float = 60.0):
        """Drive synchronously until queue + slots drain (tests/smokes)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if not self.pump(wait_s=0.01):
                if self.queue.depth() == 0 and not self.active_count() \
                        and not self._stash:
                    return
        raise TimeoutError(f"engine not idle after {timeout_s:g}s")

    def start(self):
        if self._thread is not None:
            return self
        self._stop = False

        def loop():
            while not self._stop:
                if not self.pump(wait_s=0.02):
                    time.sleep(0.002)
                elif self.draining:
                    # a drain waits for the lock between two cycles; the
                    # lock is not fair, and on an idle host this loop
                    # would take it back at once until the work is done
                    time.sleep(0.001)

        self._thread = threading.Thread(
            target=loop, name="bigdl-serve-lm", daemon=True)
        self._thread.start()
        return self

    def drain(self, deadline_s: float = 10.0):
        """Stop admissions, finish in-flight decodes within the
        deadline, checkpoint the rest (serving/drain.py).  Returns the
        :class:`~bigdl_tpu.serving.drain.HandoffRecord` list a router
        replays elsewhere exactly once."""
        from bigdl_tpu.serving.drain import drain_engine

        return drain_engine(self, deadline_s=deadline_s)

    def close(self):
        self._stop = True
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        if self._minded is not None:
            self._minded.drop()
            self._minded = None
        with self._lock:
            self._settle("close")
        self.queue.close()

    # ------------------------------------------------------------- stats
    def stats(self) -> dict:
        e2e = [c["e2e_s"] for c in self.completed]
        ttft = [c["ttft_s"] for c in self.completed
                if c["ttft_s"] is not None]
        # gaps between consecutive tokens of one request, as stamped
        # where the engine appends them (ServeRequest.token_times)
        itl = np.concatenate([c["itl_s"] for c in self.completed]
                             or [np.zeros((0,))])
        busy = None
        if self._t_first_work is not None and self._t_last_done:
            busy = self._t_last_done - self._t_first_work

        def pct(vals, q):
            return float(np.percentile(vals, q)) if len(vals) else None

        return {
            "requests": len(self.completed),
            "tokens": self._tokens_total,
            "steps": self._steps,
            "steps_ahead": self._steps_ahead,
            # admissions, and those whose first result was read behind
            # the next step's dispatch (all of them but a settle's)
            "admitted": self._admitted,
            "prefills_read_late": self._read_late,
            # the share of steps in which no running slot sampled: the
            # pick made one pass over the logits and drew nothing
            "greedy_step_share": (1.0 - self._steps_sampled / self._steps
                                  if self._steps else None),
            # tokens a slot and step that yielded any (1 unless the
            # model drafts or generates by blocks)
            "tokens_per_step": (self._step_tokens / self._slot_steps
                                if self._slot_steps else None),
            # the kind's tallies: every kind reports every key
            **self._kind.stats(self._step_tokens),
            "settles": dict(self._settles),
            "busy_s": busy,
            "tokens_per_s": (self._tokens_total / busy
                             if busy else None),
            "occupancy_mean": (self._occ_sum / self._steps
                               if self._steps else None),
            "queue_depth": self.queue.depth(),
            "kv_pages_in_use": self.cache.pages_in_use(),
            "kv_pages_total": self.cache.num_pages - 1,
            # what a slot carries beside its pages (0: nothing)
            "state_bytes_per_slot": self.cache.state_bytes_per_slot(),
            # prefills that rebuilt a preempted request's state
            "state_rebuilds": self._kind.state_rebuilds,
            "draining": self.draining,
            "weight_version": self.weight_version,
            "manifest_sha": self.manifest_sha,
            "weight_swaps": self.swaps,
            "preemptions": int(self._preempt_counter._solo().value),
            "e2e_p50_s": pct(e2e, 50), "e2e_p99_s": pct(e2e, 99),
            "ttft_p50_s": pct(ttft, 50), "ttft_p99_s": pct(ttft, 99),
            "itl_p50_s": pct(itl, 50), "itl_p95_s": pct(itl, 95),
            "int8": self.int8,
            "tp": self.tp,
            "last_bucket_pages": self._last_bucket,
            "decode_ms_mean": (self._decode_ms_sum / self._steps
                               if self._steps else None),
            "decode_hbm_bytes_per_token":
                float(self._decode_bytes_gauge._solo().value)
                if self._steps else None,
        }


__all__ = ["LMEngine", "pick_greedy", "sample_first", "sample_step"]
