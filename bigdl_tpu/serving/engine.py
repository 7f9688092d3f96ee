"""Continuous-batching LM decode engine.

``TransformerLM.generate`` decodes one fixed batch to completion — the
whole batch waits for its slowest member (head-of-line blocking), and a
new request waits for the whole batch to drain.  This engine replaces
that with the production shape:

* **slots**: up to ``max_batch`` requests decode together in one jitted
  step over the paged KV cache (serving/cache.py);
* **continuous admission**: at every step boundary, free slots are
  refilled from the request queue (serving/batcher.py) — a finished
  request's slot and pages are reused immediately, not when the batch
  drains;
* **prefill/decode split**: a new request's prompt runs one batched
  forward (``TransformerBlock.prefill`` — the identical attention path
  training uses) padded to a page-aligned bucket, writing its K/V pages
  and producing its first token; the shared decode step then advances
  every active slot one token;
* **int8 decode** (``int8=True``): the decode matmuls run on
  pre-quantized per-output-channel int8 weights via the existing
  ``ops.quantized_matmul`` path (the same math ``module.quantize()``
  rides) — decode is memory-bound, so halving/quartering weight bytes
  is the lever; prefill stays float (it is compute-bound);
* **TP-sharded decode** (``tp=N``): the step runs under shard_map with
  Megatron row/col-split weights and the block reductions on
  ``parallel/wire.py``'s compressed collectives (serving/tp.py);
* **preemption**: if the page pool is exhausted mid-decode, the
  youngest request is preempted — pages freed, the request re-queued
  with its generated prefix as prompt — instead of deadlocking the
  batch;
* **one step in flight**: step k+1 is dispatched before step k's
  tokens are read, so the host's emit, admission and prep run while the
  chip decodes.  A step's tokens are an argument of the next step and
  never visit the host on the way (a slot admitted since is overridden
  from the host inside the program); lengths, pages and the owed count
  advance at dispatch, tokens are emitted one step behind, and
  whatever needs host and chip to agree (preemption, a weight swap,
  ``close``, an engine with nothing left to run) settles the step in
  flight first.  An EOS is learnt one step late: the slot's row in the
  step already dispatched is wasted, never emitted;
* **a step that yields one or two tokens a slot**: a model that drafts
  its own next-but-one token (``draft_spec``; ``models/joyai_flash.py``)
  has each step verify two positions a slot, the certain token and the
  draft, and emit the second token where the draft was right.  How many
  tokens a step yielded is known on the device one step after the host
  has dispatched the next, so the slot's next input token, next draft,
  length and owed count are device arrays carried from step to step
  (a freshly admitted slot's come from the host, selected inside the
  program), the host keeps bounds (a length's lower bound advances by
  one a dispatch, pages and the bucket are named for the upper bound,
  a slot runs while it MAY still owe a token) and reconciles when it
  reads the step's tokens and emitted counts one step late.  A slot
  that owed nothing on the device in a step already dispatched is a
  wasted row, as after an EOS.  Same loop, same settles; greedy only
  (a temperature on such a model is refused at ``submit``);
* **a step that refines a block a slot**: a model that generates by
  blocks (``block_spec``; ``models/sdar_moe.py``) has each step refine
  a block of ``B`` positions a slot.  A pass unmasks between 1 and
  ``B`` of them by confidence; a block takes 1 to ``T`` passes.  In
  the step whose pass unmasks its last position the slot's length
  advances by ``B`` ON THE DEVICE and a new block starts; the finished
  block's final rows are written by the slot's NEXT forward, which is
  the new block's first pass (the step forwards ``2B`` positions a
  slot: that **tail**, or padding where none is pending, and the
  current block), so no step of a slot yields nothing.  How many
  positions a pass unmasked is known on the device one step before
  the host reads it, so the block's tokens, its mask flags, its pass
  count, the slot's length, the tail's tokens and whether one is
  pending are device arrays carried from step to step, as a draft is
  (a freshly admitted slot's come from the host, selected inside the
  program); the host keeps bounds (pages and the bucket are named for
  the end of the block after the one it last saw, a slot runs until
  its request is done) and reconciles when it reads: a result row says
  whether the block it shows became final, and the host then records
  it, advances its own length and renews its view in that same read.
  **Emission is by prefix**: a token is handed to its request by the
  step after which it and every position before it are final, so a
  step yields 0 to ``B`` tokens a slot; ``ServeRequest.unmasked``
  keeps every generated position's token and the pass that unmasked
  it.  A request's last block is never given final rows (nothing reads
  them).  Same loop, same settles; greedy only;
* **state a slot carries that is not keys and values**: a model whose
  layers need more than a token's own rows declares the shapes
  (``state_spec``), and the engine keeps them for ``max_batch`` slots
  BESIDE the pages, in the cache manager (``serving/cache.py``),
  donated to ``jit_step`` and ``jit_prefill`` with the pools and
  carried on the device from step to step like a draft's or a block's
  state.  Two cases.  **A bounded past** (``models/zaya.py``: two
  causal convolutions and a shifted value need rows of the slot's
  previous token; 5.4 KB a slot and layer).  **The whole past**
  (``models/falcon_h1.py``: a state-space mixer's running state, 4.19 MB
  of float32 a slot and layer, which every token decays and adds to).
  Its life is the slot's either way: **the prefill writes it** (the
  state after the prompt's last REAL token, whatever the bucket's
  padded tail holds), all of it, so nothing of the slot's previous
  occupant survives an admission; a step advances it for the slots that
  ran and leaves an inactive slot's alone; a release leaves it where it
  is (the next admission overwrites it).  **What a step costs in
  bytes** is the running slots' state once in and once out
  (``serve.decode_step``'s ``state_bytes``): 14 MB under ZAYA1, 4.3 GB
  under Falcon-H1 at 128 slots, as much as the page pools hold.  So the
  guard of an idle slot is the engine's ``where`` over what the model
  handed back (``keep_inactive``) unless the model's own update keeps an
  idle slot bit for bit (``state_spec``'s ``keeps_inactive``): then the
  engine adds no pass over the state.  **No snapshot is taken at a
  preemption**: the request comes back with its generated prefix as
  prompt and its second prefill rebuilds the state from the tokens
  (``serve.prefill``'s ``rebuilt=1``, ``stats()["state_rebuilds"]``):
  exactly for a bounded past; for the whole past by the prefill's scan
  over all its tokens, which gives the stepped state to rounding.  This
  holds because a prefill is never cut into chunks (``_bucket`` goes to
  ``max_len``): a prefill in chunks WILL need the state kept at a
  chunk's start; nothing here does that yet.

**What the engine asks of a model** (``models/transformer.py`` and
``models/longcat_flash.py`` both answer): ``cache_spec(params)`` — how
many cached layers, the width of a token's row, one buffer or two, the
longest context, and, where its decode attention is a page-walking
kernel of ``ops/decode_attention.py``, the query rows a slot it hands
that kernel (``attn_query_rows``: the engine then says on every
``serve.decode_step`` what the kernel's stream copied,
``attn_rows_copied``); ``paged_prefill(params, caches, prompt, t0,
pages)`` and ``paged_decode(params, caches, tables, lengths, tokens, active,
...)``, each returning ``(caches, logits, counts)`` with ``counts`` the
step's expert-routing counts or None.  Sampling, buckets, donation, the
page tables, spans and ``stats()`` are the engine's; the layers'
internals are the model's.  **A model that drafts**
(``models/joyai_flash.py``) also answers ``draft_spec(params)``
(``tokens_per_step``: 2) and must choose tokens in the middle of its
step, so it is handed the engine's ``pick`` (logits ``(N, vocab)`` ->
tokens ``(N,)``) and returns tokens, not logits:
``paged_prefill(..., pick=)`` -> ``(caches, first, draft, counts)`` and
``paged_decode(params, caches, tables, lengths, tokens, drafts, owed,
active, pick=)`` -> ``(caches, picked (B, 2), accepted (B,), next_draft
(B,), counts)``.  A model that declares no draft runs the programs it
always ran.  **A model that generates by blocks**
(``models/sdar_moe.py``) answers ``block_spec(params)``
(``block_length``, ``passes``, ``threshold``) instead and owns the
rule by which a pass unmasks: ``paged_prefill(..., pick=)`` ->
``(caches, (tokens (B,), masked (B,)), counts)``, the first block's
state and no token (what the prompt's whole blocks leave over sits,
fixed, at that block's head), and ``paged_decode(params, caches,
tables, lengths, tokens (S, B), masked (S, B), passes (S,), tail (S,
B), pending (S,), active, pick=)`` -> ``(caches, (tokens, masked,
passes, lengths, tail, pending) after the step, kind (S,), counts)``:
it forwards every slot's block, writes its rows at ``lengths + 0 ..
B-1`` and unmasks; where ``pending`` it also writes the final rows of
the block before (``tail``, at ``lengths - B .. lengths - 1``) in the
same forward; where the pass leaves no position masked it rolls the
state over (the block becomes the pending tail, ``lengths + B``, a new
block all masked) and says so in ``kind``; the engine carries that
state to the next step untouched.  **A model
whose slots carry state** (``models/zaya.py``) answers
``state_spec(params)`` -> ``{"layers", "shapes", "dtype"}`` (one array
``(layers, max_batch, *shape)`` a shape) beside ``cache_spec``; it
generates one token a step and is sampled by the engine like
``models/transformer.py``, and its two entry points hand the state
through: ``paged_prefill(params, caches, prompt, t0, pages)`` ->
``(caches, logits, counts, rows)`` with ``rows`` one ``(layers,
*shape)`` array a shape, the state after position ``t0 - 1`` (the
engine writes them into the slot), and ``paged_decode(params, caches,
tables, lengths, tokens, active, state=, ...)`` -> ``(caches, logits,
counts, state)`` (the engine keeps an inactive slot's old state,
unless ``state_spec`` says ``keeps_inactive``: the model's step then
hands an idle slot's state back as it was, ``models/falcon_h1.py``).  A
model without ``state_spec`` runs the programs it always ran; one with
it neither drafts nor generates by blocks.  ``int8=True``
needs the model's
``quantize_for_decode``, ``tp > 1`` its ``tp_decode_step``; a model
without them is refused with a ``ValueError`` that says so.

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
from bigdl_tpu.serving.batcher import RequestQueue, ServeRequest
from bigdl_tpu.serving.cache import (PagedKVCache, keep_inactive,
                                     write_slot_state)
from bigdl_tpu.serving.drain import HANDOFF_ERROR
from bigdl_tpu.serving import spans
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


class _Active:
    """Host bookkeeping for one occupied slot.  ``remaining`` counts
    the tokens not yet DISPATCHED (emission lags one step) and ``left``
    those not yet EMITTED; a dispatched step is taken to yield one
    token until it is read, so under a drafting model ``remaining`` is
    an upper bound between a dispatch and its read, and the two agree
    whenever nothing is in flight.  ``first_token`` (and a drafting
    model's ``first_draft``) is the prefill's until the slot's first
    decode step has taken it from the host, then None: the slot's
    input is the previous step's output, on the device.  Under a model
    that generates by blocks nothing is counted at dispatch
    (``remaining`` is ``left``), ``block`` is the host's view of the
    slot's current block as of the last step read, and ``first_token``
    only marks the slot as fresh.  ``unread`` is
    1 while a step the slot ran in has not been read; ``last_pos`` is
    the last position the request can ever write a row at."""

    __slots__ = ("req", "remaining", "left", "first_token", "first_draft",
                 "prompt_len", "last_pos", "unread", "t_admit", "order",
                 "block")

    def __init__(self, req, remaining, first_token, prompt_len, order,
                 first_draft=None):
        self.req = req
        self.remaining = self.left = remaining
        self.first_token = first_token
        self.first_draft = first_draft
        self.block: Optional[_Block] = None
        self.prompt_len = prompt_len
        self.last_pos = prompt_len + remaining
        self.unread = 0
        self.t_admit = time.monotonic()
        self.order = order


class _Block:
    """The host's view of a slot's current block, as of the last step
    read: its tokens, which positions are still masked, the pass that
    unmasked each (-1: none yet), and ``shown``, how many of its
    leading positions are with the request already (emitted, or the
    prompt's: ``origin`` is the position the request's first generated
    token has)."""

    __slots__ = ("tokens", "masked", "passes", "shown", "origin")

    def __init__(self, tokens, masked, origin: int):
        self.tokens = np.array(tokens, np.int32)
        self.masked = np.array(masked, bool)
        self.passes = np.full(self.tokens.shape, -1, np.int32)
        self.shown = int(np.sum(~self.masked))
        self.origin = origin

    def renew(self):
        self.tokens[:] = 0
        self.masked[:] = True
        self.passes[:] = -1
        self.shown = 0


class _InFlight:
    """A dispatched decode step whose tokens the host has not read."""

    __slots__ = ("result", "counts", "entries", "context_rows")

    def __init__(self, result, counts, entries, context_rows):
        # (B,) device array, the step's tokens; a drafting model's
        # (B, len(DRAFT_RESULT)) rows
        self.result = result
        self.counts = counts        # an expert model's routing counts
        self.entries = entries      # [(slot, _Active)] it ran for
        # the rows of context it reads, a slot it ran for (a one-token
        # model's: the others' come back with the step)
        self.context_rows = context_rows


#: columns of a drafting step's result, a slot: the two tokens picked,
#: how many of them the step yields (0 where the slot owed nothing), the
#: draft it verified and the slot's length before the step
DRAFT_RESULT = ("first", "second", "emitted", "draft", "length")
#: what follows the block's tokens and its mask flags in a row of a
#: block step's result: the slot's length before the step, what the
#: step did (the model's ``kind``: 0 nothing, then the two below), the
#: pass index it ran, and whether it wrote a pending tail's final rows
BLOCK_RESULT = ("length", "kind", "pass", "tail")
BLOCK_REFINED, BLOCK_FINISHED = 1, 2
#: ``ServeRequest.unmasked``'s pass for a position that was still masked
#: when its request ended, and for one that was with the request when a
#: preemption folded it into the prompt (the state that chose it is gone)
NEVER_UNMASKED, GIVEN = -1, -2


class _StepRead:
    """A read step on the host: ``tokens`` (B, k), ``emitted`` (B,)
    tokens each slot yields (None: one each), a drafting model's
    verified ``drafts`` by slot, a block model's ``blocks`` by slot
    (mask flags after the step, what the step did, its pass index), and
    the span's attributes."""

    __slots__ = ("tokens", "emitted", "drafts", "blocks", "attrs")

    def __init__(self, tokens, emitted, drafts, attrs, blocks=None):
        self.tokens, self.emitted = tokens, emitted
        self.drafts, self.attrs = drafts, attrs
        self.blocks = blocks or {}


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
        # ... and whether it drafts: tokens a step verifies a slot
        self._tokens_per_step = int(
            model.draft_spec(self.params)["tokens_per_step"]
            if hasattr(model, "draft_spec") else 1)
        if self._tokens_per_step not in (1, 2):
            raise ValueError("a step verifies one draft a slot at most")
        self._drafts = self._tokens_per_step > 1
        # ... or generates by blocks: positions a step forwards a slot
        self._block = int(model.block_spec(self.params)["block_length"]
                          if hasattr(model, "block_spec") else 0)
        if self._block and (self._drafts or self.int8 or self.tp > 1):
            raise ValueError("a model that generates by blocks neither "
                             "drafts nor offers int8 or tp decode")
        # ... or carries state that is not keys and values, a slot
        state = model.state_spec(self.params) \
            if hasattr(model, "state_spec") else None
        #: the model's step keeps an inactive slot's state itself
        self._state_guarded = bool(state and state.get("keeps_inactive"))
        if state and (self._drafts or self._block):
            raise ValueError("a model whose slots carry state neither "
                             "drafts nor generates by blocks")
        self.max_len = int(spec["max_len"])
        if self._block and (self.page_size % self._block
                            or self.max_len % self._block):
            raise ValueError(
                f"blocks of {self._block} do not divide the page size "
                f"{self.page_size} and the longest context {self.max_len}")
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
        self._slots: List[Optional[_Active]] = [None] * self.max_batch
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
        # what a step hands the next on the device: its tokens; under a
        # drafting model each slot's next token, next draft, length and
        # owed count
        zeros = jnp.zeros((self.max_batch,), jnp.int32)
        self._carry = (zeros,) * (4 if self._drafts else 1)
        if self._block:
            # a block model's: tokens, mask flags, pass count, length,
            # and the block before it while its final rows are pending
            wide = jnp.zeros((self.max_batch, self._block), jnp.int32)
            self._carry = (wide, wide.astype(bool), zeros, zeros, wide,
                           zeros.astype(bool))
        self._draft_verified = self._draft_accepted = 0
        self._block_passes = self._block_tails = 0
        self._positions_unmasked = 0
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
        self._prefill_fns: dict = {}
        self._tracer = obs.NULL_TRACER  # pump() looks it up each cycle
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
        self._draft_counter = reg.counter(
            names.SERVE_DRAFT_TOKENS_TOTAL,
            "Drafts a self-drafting model's steps verified, by outcome",
            labels=("outcome",)) if self._drafts else None
        self._block_counter = reg.counter(
            names.SERVE_BLOCK_POSITIONS_TOTAL,
            "Masked positions a block model's refining passes met, by "
            "outcome", labels=("outcome",)) if self._block else None
        self._rebuild_counter, self._state_rebuilds = None, 0
        #: fixed for the engine's life: read on every step
        self._slot_state_bytes = self.cache.state_bytes_per_slot()
        if self.cache.state:
            reg.gauge(
                names.SERVE_SLOT_STATE_BYTES,
                "Bytes of state a slot carries beside its pages, over "
                "all layers").set(float(self.cache.state_bytes_per_slot()))
            self._rebuild_counter = reg.counter(
                names.SERVE_STATE_REBUILDS_TOTAL,
                "Prefills of a preempted request under a model whose "
                "slots carry state: the state rebuilt from the tokens")
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
        with self._lock:
            self._settle("swap")
            self.params = params
            self._qparams = qparams
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
        import jax.numpy as jnp

        model, page_size = self.model, self.page_size
        qparams = self._qparams
        # the cache's buffers: its pools and, behind them, the slots'
        # state (none unless the model declares one)
        n, pools = len(self.cache.buffers()), len(self.cache.pools())

        # one name for both programs: the profiler and the readers of its
        # trace know the decode step as ``jit_step``
        if self._drafts:
            def step(params, *rest):
                # rest: the cache's buffers (donated), then tables, the
                # host's lengths, the four arrays the last step carried
                # (token, draft, length, owed), the host's values of the
                # four for the slots in fresh (admitted since that step),
                # active
                (tables, h_len, c_tok, c_draft, c_len, c_owed,
                 h_tok, h_draft, h_owed, fresh, active) = rest[n:]
                tok = jnp.where(fresh, h_tok, c_tok)
                draft = jnp.where(fresh, h_draft, c_draft)
                length = jnp.where(fresh, h_len, c_len)
                owed = jnp.where(fresh, h_owed, c_owed)
                # the host runs a slot while it MAY owe a token; the count
                # here is exact, and a slot that owes nothing computes a
                # wasted row at position 0 of its own pages
                run = active & (owed > 0)
                caches, picked, accepted, next_draft, counts = \
                    model.paged_decode(
                        params, rest[:n], tables, jnp.where(run, length, 0),
                        tok, draft, owed, run, pick=pick_greedy,
                        page_size=page_size, qparams=qparams)
                emitted = jnp.where(run, 1 + accepted.astype(jnp.int32), 0)
                nxt = jnp.where(accepted, picked[:, 1], picked[:, 0])
                result = jnp.stack([picked[:, 0], picked[:, 1], emitted,
                                    draft, length], axis=1)
                out = (*caches, nxt, next_draft, length + emitted,
                       owed - emitted, result)
                return out if counts is None else (*out, counts)
        elif self._block:
            def step(params, *rest):
                # rest: the cache's buffers (donated), then tables, the
                # host's lengths, the six arrays the last step carried
                # (block tokens, mask flags, pass count, length, the
                # tail's tokens, whether a tail is pending), the host's
                # block for the slots in fresh (admitted since that
                # step; their pass count is 0 and no tail is pending),
                # active
                (tables, h_len, c_tok, c_mask, c_pass, c_len, c_tail, c_pend,
                 h_tok, h_mask, fresh, active) = rest[n:]
                tok = jnp.where(fresh[:, None], h_tok, c_tok)
                mask = jnp.where(fresh[:, None], h_mask, c_mask)
                done = jnp.where(fresh, 0, c_pass)
                length = jnp.where(fresh, h_len, c_len)
                pend = c_pend & ~fresh
                # an inactive slot computes a wasted block at position 0
                # of the trash page
                caches, state, kind, counts = model.paged_decode(
                    params, rest[:n], tables, jnp.where(active, length, 0),
                    tok, mask, done, c_tail, pend, active, pick=pick_greedy,
                    page_size=page_size, qparams=qparams)
                new_tok, new_mask, new_pass, new_len, new_tail, new_pend = \
                    state
                # where the pass left the block final, the host wants
                # that block (the new tail), not the fresh one behind it
                final = kind[:, None] == BLOCK_FINISHED
                result = jnp.concatenate(
                    [jnp.where(final, new_tail, new_tok),
                     (new_mask & ~final).astype(jnp.int32),
                     jnp.stack([length, kind, done,
                                (pend & active).astype(jnp.int32)], axis=1)],
                    axis=1)
                out = (*caches, new_tok, new_mask, new_pass,
                       jnp.where(active, new_len, length), new_tail,
                       new_pend, result)
                return out if counts is None else (*out, counts)
        else:
            def step(params, *rest):
                # rest: the cache's buffers (donated), then tables, lengths,
                # prev (the last step's tokens, still on the device), the
                # host's tokens and fresh (the slots that take theirs from
                # the host: admitted since that step), temps, active, key
                tables, lengths, prev, tokens, fresh, temps, active, key = \
                    rest[n:]
                tokens = jnp.where(fresh, tokens, prev)
                if n > pools:
                    # the slots' state goes through the model and comes
                    # back advanced where the slot ran
                    caches, logits, counts, state = model.paged_decode(
                        params, rest[:pools], tables, lengths, tokens,
                        active, state=rest[pools:n], page_size=page_size,
                        qparams=qparams)
                    # ... unless the model's own update leaves a slot
                    # that did not run as it was (no pass over the state
                    # to put the old values back)
                    caches = (*caches, *(
                        state if self._state_guarded
                        else keep_inactive(state, rest[pools:n], active)))
                else:
                    caches, logits, counts = model.paged_decode(
                        params, rest[:n], tables, lengths, tokens, active,
                        page_size=page_size, qparams=qparams)
                nxt = sample_step(logits, temps, active, key)
                # the routing counts ride back with the tokens
                return (*caches, nxt) if counts is None \
                    else (*caches, nxt, counts)

        return jax.jit(step, donate_argnums=tuple(range(1, 1 + n)))

    def _prefill_fn(self, bucket: int):
        fn = self._prefill_fns.get(bucket)
        if fn is not None:
            return fn
        import jax

        model = self.model
        n, pools = len(self.cache.buffers()), len(self.cache.pools())

        if self._drafts:
            def prefill(params, *rest):
                # as below; greedy, so the temperature and the key are not
                # read, and the first token comes with the first draft
                prompt, t0, pages = rest[n:n + 3]
                caches, first, draft, counts = model.paged_prefill(
                    params, rest[:n], prompt, t0, pages, pick=pick_greedy)
                pair = jax.numpy.stack([first, draft])
                return (*caches, pair) if counts is None \
                    else (*caches, pair, counts)
        elif self._block:
            def prefill(params, *rest):
                # as below; no token is picked: the prompt's whole blocks
                # are cached, and the first block's state comes back
                prompt, t0, pages = rest[n:n + 3]
                caches, (tokens, masked), counts = model.paged_prefill(
                    params, rest[:n], prompt, t0, pages, pick=pick_greedy)
                first = jax.numpy.stack(
                    [tokens, masked.astype(tokens.dtype)])
                return (*caches, first) if counts is None \
                    else (*caches, first, counts)
        else:
            def prefill(params, *rest):
                # rest: the cache's buffers (donated), then the prompt
                # (1, bucket) zero-padded past t0, t0, the bucket's pages,
                # the temperature, the key; with state, the slot it is for
                prompt, t0, pages, temp, key = rest[n:n + 5]
                if n > pools:
                    caches, logits, counts, rows = model.paged_prefill(
                        params, rest[:pools], prompt, t0, pages)
                    caches = (*caches, *write_slot_state(
                        rest[pools:n], rest[n + 5], rows))
                else:
                    caches, logits, counts = model.paged_prefill(
                        params, rest[:n], prompt, t0, pages)
                first = sample_first(logits, temp, key)
                return (*caches, first) if counts is None \
                    else (*caches, first, counts)

        fn = jax.jit(prefill, donate_argnums=tuple(range(1, 1 + n)))
        self._prefill_fns[bucket] = fn
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
        if self._drafts and float(temperature) > 0.0:
            raise ValueError(
                f"{type(self.model).__name__} verifies its own drafts by "
                "exact match against the greedy token: temperature "
                f"{temperature:g} is not served (give 0)")
        if self._block and float(temperature) > 0.0:
            raise ValueError(
                f"{type(self.model).__name__} unmasks a block's positions "
                "by the confidence of the greedy token: temperature "
                f"{temperature:g} is not served (give 0)")
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
        n = len(self.cache.buffers())
        step = self._steps
        # a model whose slots carry state: the slot the prefill writes
        extra = (np.int32(slot),) if self.cache.state else ()
        with tracer.span(spans.SPAN_STEP_PREFILL, step=step,
                         bucket=bucket, prompt_len=t0,
                         request=req.id) as span_id:
            if extra:
                tracer.add_attrs(
                    span_id,
                    state_bytes=self.cache.state_bytes_per_slot())
                if req.preempted:
                    # its whole past is computed again: the state is
                    # rebuilt from the tokens, not restored
                    tracer.add_attrs(span_id, rebuilt=1)
                    self._state_rebuilds += 1
                    self._rebuild_counter.inc()
            with tracer.span(spans.SPAN_STEP_DISPATCH, step=step,
                             program="prefill") as dispatch_id:
                self._note_dry(tracer, dispatch_id)
                out = self._prefill_fn(bucket)(
                    self.params, *self.cache.buffers(),
                    jnp.asarray(prompt), t0, jnp.asarray(page_arg),
                    float(req.temperature), sub, *extra)
                self.cache.set_buffers(out[:n])
                self.cache.lengths[slot] = t0
            # the prefill was enqueued behind the step in flight: the
            # wait holds what was left of that step too
            with tracer.span(spans.SPAN_STEP_WAIT, step=step,
                             program="prefill"):
                first = np.asarray(out[n])
            with tracer.span(spans.SPAN_STEP_READ, step=step,
                             program="prefill"):
                # a drafting model's first token comes with its first
                # draft; a block model's prefill yields its first block
                # and no token
                tok, draft = (int(t) for t in first) if self._drafts \
                    else (None, None) if self._block \
                    else (int(first), None)
                if len(out) > n + 1:
                    tracer.add_attrs(span_id,
                                     **self._note_routing(out[n + 1]))
        if req.trace is not None:
            req._tr_admits.append(
                {"t": t_admit, "dur": time.monotonic() - t_admit,
                 "bucket": bucket, "prompt_len": t0, "slot": slot})
        if self._t_first_work is None:
            self._t_first_work = time.monotonic()
        self._order += 1
        if self._block:
            # the slot's length is its block's first position; every
            # generated position so far is on record (a preemption
            # folded them into the prompt)
            b = self._block
            self.cache.lengths[slot] = t0 - t0 % b
            act = _Active(req, req.max_new_tokens, -1, t0, self._order)
            act.last_pos = -(-(t0 + req.max_new_tokens) // b) * b - 1
            act.block = _Block(first[0], first[1],
                               origin=t0 - len(req.unmasked))
        else:
            self._first_token(req)
            req.tokens.append(tok)
            req.token_times.append(time.perf_counter())
            self._tokens_total += 1
            self._tokens_counter.inc()
            act = _Active(req, req.max_new_tokens - 1, tok, t0, self._order,
                          first_draft=draft)
        self._slots[slot] = act
        tracer.event(spans.EVENT_ADMIT, slot=slot, request=req.id,
                     prompt_len=t0, bucket=bucket)
        if tok is not None and (act.remaining <= 0 or tok == self.eos_id):
            self._complete(slot)

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
        flight is settled first: the fold needs every dispatched token
        in ``req.tokens`` (and a slot it completes is no victim)."""
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
        if act.block is not None:
            # what the block has shown is the request's; the rest of it
            # is generated again
            self._record_block(act, slot, GIVEN)
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
        if act.block is not None:
            self._record_block(act, slot)
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
        before it: while the host does that, admits and prepares again,
        the chip runs the step just dispatched."""
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
            tokens = np.zeros((self.max_batch,), np.int32)
            fresh = np.zeros((self.max_batch,), bool)
            temps = np.zeros((self.max_batch,), np.float32)
            active = np.zeros((self.max_batch,), bool)
            drafts = np.zeros((self.max_batch,), np.int32)
            owed = np.zeros((self.max_batch,), np.int32)
            if self._block:
                tokens = np.zeros((self.max_batch, self._block), np.int32)
                masked = np.zeros((self.max_batch, self._block), bool)
            for i in running:
                act = self._slots[i]
                if act.first_token is not None:
                    # admitted since the last step: its input is the
                    # prefill's token (a block model's: its first
                    # block), from the host, this once
                    fresh[i] = True
                    if self._block:
                        tokens[i], masked[i] = (act.block.tokens,
                                                act.block.masked)
                    else:
                        tokens[i] = act.first_token
                    if self._drafts:
                        drafts[i], owed[i] = act.first_draft, act.left
                    act.first_token = act.first_draft = None
                temps[i] = act.req.temperature
                active[i] = True
            longest = max(int(self.cache.lengths[i]) + self._ahead(i)
                          for i in running)
            bucket = used_page_bucket(longest, self.page_size,
                                      self.cache.max_pages_per_slot)
            self._last_bucket = bucket
            tables, lengths = self.cache.device_tables(pages=bucket)
            self._key, sub = jax.random.split(self._key)
            # running slots that sample: 0 means the step's pick takes
            # its greedy arm (``sample_step``)
            sampling = int(np.count_nonzero(temps > 0.0))
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
                if self._drafts:
                    host = (jnp.asarray(tokens), jnp.asarray(drafts),
                            jnp.asarray(owed), jnp.asarray(fresh),
                            jnp.asarray(active))
                elif self._block:
                    host = (jnp.asarray(tokens), jnp.asarray(masked),
                            jnp.asarray(fresh), jnp.asarray(active))
                else:
                    host = (jnp.asarray(tokens), jnp.asarray(fresh),
                            jnp.asarray(temps), jnp.asarray(active), sub)
                out = self._step_fn(
                    self.params, *self.cache.buffers(), tables, lengths,
                    *self._carry, *host)
                self.cache.set_buffers(out[:n])
                k = len(self._carry)
                self._carry = out[n:n + k]
                # what the host reads: the tokens (a one-token model's
                # are the carry itself), and an expert model's counts
                result = out[n + k:] if self._drafts or self._block \
                    else out[n:]
                for arr in result:
                    # on their way to the host as soon as they exist,
                    # not when the next pump asks for them
                    arr.copy_to_host_async()
                # the host's state advances at dispatch, by the one
                # token a step yields at least: the next prep (growth,
                # bucket, who runs) needs no token.  (A block's step may
                # yield none: that host's state advances when the step
                # is read.)
                entries, context = [], []
                sure = 0 if self._block else 1
                for i in running:
                    act = self._slots[i]
                    self.cache.lengths[i] += sure
                    context.append(int(self.cache.lengths[i]))
                    act.remaining -= sure
                    act.unread += 1
                    entries.append((i, act))
                self._inflight = _InFlight(
                    result[0], result[1] if len(result) > 1 else None,
                    entries, context)
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
                    positions=2 * self._block or 1)
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
        may write: 0 for a one-token model; a drafting model's step
        writes a second row, and a step not yet read may have taken its
        draft; a block's step writes its block's rows, and every step
        not yet read may have finished a block and moved on to the
        next.  Never past the request's last position."""
        if not (self._drafts or self._block):
            return 0
        act = self._slots[slot]
        reach = self._block * (1 + act.unread) - 1 if self._block \
            else 1 + act.unread
        return max(0, min(reach,
                          act.last_pos - int(self.cache.lengths[slot])))

    def _note_dry(self, tracer, dispatch_id):
        """Say on an open ``serve.dispatch`` whether the chip had run
        dry: nothing launched before is still running.  Asked of the
        device buffer without blocking, before the host ships its
        arrays, and only under a recording tracer."""
        if tracer.enabled:
            rec = self._inflight
            tracer.add_attrs(dispatch_id, dry=int(
                rec is None or bool(rec.result.is_ready())))

    def _read(self, rec: _InFlight, tracer, step: int) -> _StepRead:
        """Wait for a dispatched step's tokens (``serve.wait``: the one
        blocking read), then take them apart (``serve.read``)."""
        with tracer.span(spans.SPAN_STEP_WAIT, step=step, program="step"):
            res = np.asarray(rec.result)
        with tracer.span(spans.SPAN_STEP_READ, step=step, program="step"):
            if self._block:
                return self._read_block(rec, res)
            return self._read_tokens(rec, res)

    def _read_tokens(self, rec: _InFlight, res) -> _StepRead:
        """A one-token or drafting model's read.  With the tokens come
        an expert model's routing counts and a drafting model's emitted
        counts: returned as span attributes, beside the rows of context
        that step had to read."""
        attrs, drafts = {}, {}
        if self._drafts:
            first, second, emitted, draft, length = res.T  # DRAFT_RESULT
            toks = np.stack([first, second], axis=1)
            accepted = tokens = 0
            context = []
            for slot, act in rec.entries:
                if self._slots[slot] is not act or not emitted[slot]:
                    continue    # completed since, or owed nothing there
                # a draft counts as verified where the slot owed the
                # token it drafts (the device's owed count is the
                # host's ``left`` once every earlier step is emitted,
                # as here)
                if act.left >= 2:
                    drafts[slot] = int(draft[slot])
                accepted += int(emitted[slot] == 2)
                tokens += int(emitted[slot])
                # the rows the step had to read, once a slot: up to
                # its second query's position
                context.append(int(length[slot]) + 2)
            attrs.update(draft_verified=len(drafts),
                         draft_accepted=accepted, tokens_emitted=tokens)
            self._draft_verified += len(drafts)
            self._draft_accepted += accepted
            self._draft_counter.labels(outcome="accepted").inc(accepted)
            self._draft_counter.labels(outcome="rejected").inc(
                len(drafts) - accepted)
        else:
            toks, emitted, context = res[:, None], None, rec.context_rows
        if rec.counts is not None:
            attrs.update(self._note_routing(rec.counts))
        if rec.counts is not None or self.cache.state:
            attrs.update(self._context_attrs(context))
        if self.cache.state:
            # what the step read and wrote of the slots' state: in and
            # out, for the slots that ran
            attrs["state_bytes"] = (2 * len(rec.entries)
                                    * self._slot_state_bytes)
        return _StepRead(toks, emitted, drafts, attrs)

    def _context_attrs(self, rows) -> dict:
        """What a step's attention had to read and what it copied:
        ``context_tokens``, the sum of ``rows`` (the rows of context a
        slot the step ran for, its own positions included), and, under
        a model whose decode attention is a page-walking kernel
        (``cache_spec``'s ``attn_query_rows``), ``attn_rows_copied``:
        the rows one call of that kernel copies a pool for those
        slots."""
        attrs = {"context_tokens": sum(rows)}
        query_rows = self._cache_spec.get("attn_query_rows")
        if query_rows:
            from bigdl_tpu.ops.decode_attention import stream_rows_copied

            attrs["attn_rows_copied"] = stream_rows_copied(
                np.asarray(rows, np.int64) - 1, self.page_size,
                self.cache.max_pages_per_slot, self.cache.row_width,
                self.cache.dtype.itemsize, query_rows)
        return attrs

    def _read_block(self, rec: _InFlight, res) -> _StepRead:
        """A block model's read: what each live slot's step did, how
        many positions it unmasked and how many tokens that shows (the
        unmasked prefix beyond what is shown already, up to the
        request's last token or an EOS)."""
        b = self._block
        toks, after = res[:, :b], res[:, b:2 * b].astype(bool)
        length, kind, done, tail = res[:, 2 * b:].T    # BLOCK_RESULT
        emitted = np.zeros((self.max_batch,), np.int32)
        blocks = {}
        passes = tails = unmasked = left = 0
        context = []
        for slot, act in rec.entries:
            if self._slots[slot] is not act or not kind[slot]:
                continue    # completed since: a wasted block
            blocks[slot] = (after[slot], int(kind[slot]), int(done[slot]))
            context.append(int(length[slot]) + b)
            passes += 1
            tails += int(tail[slot])
            unmasked += int(np.sum(act.block.masked & ~after[slot]))
            left += int(np.sum(after[slot]))
            shown = act.block.shown
            prefix = b if not after[slot].any() \
                else int(np.argmax(after[slot]))
            n = max(0, min(prefix - shown, act.left))
            for j in range(n):
                if int(toks[slot, shown + j]) == self.eos_id:
                    n = j + 1
                    break
            emitted[slot] = n
        self._block_passes += passes
        self._block_tails += tails
        self._positions_unmasked += unmasked
        self._block_counter.labels(outcome="unmasked").inc(unmasked)
        self._block_counter.labels(outcome="left_masked").inc(left)
        # (no forward of a slot only commits a block: the count stays
        # for the readers that add it to the passes)
        attrs = dict(block_passes=passes, block_tails=tails,
                     block_commits=0,
                     positions_unmasked=unmasked,
                     tokens_emitted=int(emitted.sum()))
        if rec.counts is not None:
            attrs.update(self._note_routing(rec.counts),
                         **self._context_attrs(context))
        return _StepRead(toks, emitted, {}, attrs, blocks)

    def _advance_block(self, slot: int, act: _Active, read: _StepRead):
        """Bring the host's view of ``slot``'s block up to the step
        read; returns the block position its shown tokens start at."""
        after, _, done = read.blocks[slot]
        blk = act.block
        newly = blk.masked & ~after
        blk.tokens[newly] = read.tokens[slot][newly]
        blk.passes[newly] = done
        blk.masked = after.copy()
        start = blk.shown
        blk.shown += int(read.emitted[slot])
        return start

    def _record_block(self, act: _Active, slot: int, given=None):
        """Put the block's generated positions that are not on record
        yet into ``ServeRequest.unmasked``: all of them, or with
        ``given`` (a preemption) only those already shown, marked
        so."""
        blk, req = act.block, act.req
        at = int(self.cache.lengths[slot]) - blk.origin
        for i in range(blk.shown if given is not None else self._block):
            if at + i < len(req.unmasked):
                continue    # the prompt's, or recorded before
            if given is not None:
                req.unmasked.append((int(blk.tokens[i]), given))
            elif blk.masked[i]:
                req.unmasked.append((0, NEVER_UNMASKED))
            else:
                req.unmasked.append((int(blk.tokens[i]),
                                     int(blk.passes[i])))

    def _emit(self, rec: _InFlight, read: _StepRead):
        """Hand a read step's tokens to their requests, and bring the
        host's bounds up to what the step turned out to yield."""
        for slot, act in rec.entries:
            act.unread -= 1
            if self._slots[slot] is not act:
                # completed on an EOS after this step was dispatched:
                # the row was wasted, its token is no one's
                continue
            n = 1 if read.emitted is None else int(read.emitted[slot])
            start = 0
            if slot in read.blocks:
                start = self._advance_block(slot, act, read)
            if n:       # 0: owed nothing on the device, a wasted row
                req = act.req
                if slot in read.drafts:
                    req.drafts.append((len(req.tokens), read.drafts[slot]))
                # the dispatch counted one token; the step may have
                # yielded another (a block's step was counted for none)
                if self._block:
                    act.remaining -= n
                else:
                    self.cache.lengths[slot] += n - 1
                    act.remaining -= n - 1
                self._slot_steps += 1
                self._first_token(req)
                for j in range(n):
                    tok = int(read.tokens[slot, start + j])
                    req.tokens.append(tok)
                    req.token_times.append(time.perf_counter())
                    self._tokens_total += 1
                    self._step_tokens += 1
                    self._tokens_counter.inc()
                    act.left -= 1
                    if act.left <= 0 or tok == self.eos_id:
                        self._complete(slot)
                        break
            if slot in read.blocks and self._slots[slot] is act \
                    and read.blocks[slot][1] == BLOCK_FINISHED:
                # the pass left the block final and its request goes
                # on: on record, the length advances (the device's did
                # in that step), a new block
                self._record_block(act, slot)
                self.cache.lengths[slot] += self._block
                act.block.renew()

    def _settle(self, reason: str) -> bool:
        """Read and emit the step in flight, outside the pipelined loop:
        before anything that needs the host and the chip to agree
        (``reason`` one of ``SETTLE_REASONS``).  An event, not a
        ``serve.decode_step`` span: that step has its span already.
        False where nothing was in flight."""
        rec = self._inflight
        if rec is None:
            return False
        self._inflight = None
        tracer = obs.get_tracer()  # not always inside a pump
        # the last step dispatched: its wait and read lie outside any
        # ``serve.decode_step`` and carry its own step
        read = self._read(rec, tracer, self._steps - 1)
        tracer.event(spans.EVENT_SETTLE, reason=reason, **read.attrs)
        with tracer.span(spans.SPAN_STEP_EMIT, step=self._steps):
            self._emit(rec, read)
        self._settles[reason] += 1
        self._settle_counter.labels(reason=reason).inc()
        return True

    # ---------------------------------------------------------- driving
    def pump(self, wait_s: float = 0.0) -> bool:
        """One cycle: admit, dispatch the next decode step, read and
        emit the one before it.  True while there is work, a step in
        flight included: a request's last token is emitted by the cycle
        AFTER the one that dispatched it (drive with
        :meth:`run_until_idle`, not with a counted number of pumps)."""
        with self._lock:
            # one look at the configuration a cycle; its spans share it
            self._tracer = obs.get_tracer()
            self._admit(wait_s=wait_s if not self.active_count() else 0.0)
            stepped = self._step()
            return stepped or self._inflight is not None \
                or bool(self._stash) or self.queue.depth() > 0

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
        with self._lock:
            self._settle("close")
        self.queue.close()

    # ------------------------------------------------------------- stats
    def stats(self) -> dict:
        passes = self._block_passes
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
            # the share of steps in which no running slot sampled: the
            # pick made one pass over the logits and drew nothing
            "greedy_step_share": (1.0 - self._steps_sampled / self._steps
                                  if self._steps else None),
            # tokens a slot and step that yielded any (1 unless the
            # model drafts), and the share of verified drafts accepted
            "tokens_per_step": (self._step_tokens / self._slot_steps
                                if self._slot_steps else None),
            "drafts_verified": self._draft_verified,
            "drafts_accepted": self._draft_accepted,
            "draft_accept_share": (
                self._draft_accepted / self._draft_verified
                if self._draft_verified else None),
            # a block model's: forwards of a slot (each a refining
            # pass), those that also wrote a pending tail's final rows,
            # those that only committed a block (none: the tail rides a
            # pass), tokens a forward, the tails' share of the forwards
            "block_passes": passes,
            "block_tails": self._block_tails,
            "block_commits": 0,
            "positions_unmasked": self._positions_unmasked,
            "tokens_per_forward": (self._step_tokens / passes
                                   if passes else None),
            "tail_share": (self._block_tails / passes if passes else None),
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
            "state_rebuilds": self._state_rebuilds,
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
