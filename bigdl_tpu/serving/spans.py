"""Span-name constants for the serving data plane.

Every span or event the serving tier emits through the tracer is named
HERE, once — mint sites reference these constants instead of string
literals, exactly like metric names live in ``obs/names.py``.  A typo'd
span name is then an AttributeError, not a silently-forked timeline,
and graftlint rule RD006 (``bigdl_tpu/analysis/registry_rules.py``)
flags any ``tracer.span(...)`` / ``.event(...)`` / ``.complete(...)``
call in ``bigdl_tpu/serving/`` (or in a module importing this one)
whose first argument is a string literal.

Three families:

* ``SPAN_*`` — the per-request lifecycle hops of the distributed
  request trace (``obs/reqtrace.py``).  Each kept request trace is one
  set of these spans sharing a ``trace`` attribute; ``report.py``'s
  "request traces" section groups them by the hop key (the part after
  ``req.``) for p99 attribution.
* ``SPAN_STEP_*`` / ``SPAN_ADMISSION`` — the LIVE spans of the
  engine's thread, one set a ``pump`` cycle, on when the obs tracer is
  (``BIGDL_TRACE_DIR``).  A recording tracer writes each into a running
  profiler session too (obs/trace.py), so a chip's idle gap can be
  named by the span it falls in.  The new four carry ``step=`` (the
  engine's step count when the cycle began), so the spans of one cycle
  share an identifier:

  ======================  ==============================================
  ``serve.admission``     the admission loop of ``_admit`` once there is
                          a request to place (``offered=``,
                          ``admitted=``); not ``serve.admit``, the point
                          event of one request entering a slot
  ``serve.prefill``       inside it: the DISPATCH of one request's
                          jitted prefill and the slot's bookkeeping,
                          made from the prompt and the request alone
                          (``bucket=``, ``prompt_len=``, ``request=``).
                          Nothing is read inside it since PR 45: its
                          length is a dispatch, and the benchmark's
                          ``prefill_wall_ms`` (its median) with it
  ``serve.prep``          ``_step`` from its top to the dispatch: page
                          growth, preemption, host arrays, page tables
                          to the device, the key split (``bucket=``,
                          ``active=``)
  ``serve.decode_step``   one a ``jit_step`` execution: the dispatch of
                          step k, then the wait for step k-1's tokens
                          (one step is in flight: engine.py), so its
                          length is about the step period less the
                          host's own work (``bucket=``, ``active=`` of
                          step k; ``sampling=`` its running slots with
                          a temperature above 0: 0 means its pick drew
                          nothing, ``engine.sample_step``; the registry
                          counts the same as ``bigdl_serve_steps_total
                          {pick}``; ``ahead=`` 1 where step k-1 was still
                          unread at the dispatch, 0 for the first step
                          after an idle engine or a settle; no ``step``:
                          older than the others, and read as it is by
                          the benchmark)
  ``serve.emit``          after the read-back: token bookkeeping and
                          stamps, ``_complete``, gauges; one more, with
                          no ``serve.decode_step`` before it, where a
                          step in flight is settled (``serve.settle``)
  ======================  ==============================================
  What the HOST was doing with a program is said by three spans, each
  with ``step=`` (the cycle's, as ``serve.prep`` and ``serve.admission``
  carry it) and ``program="step"|"prefill"``.  ``serve.decode_step``
  holds the three as children, in this order and disjoint (the parent's
  length less theirs is the cost of the spans themselves);
  ``serve.prefill`` holds its ``serve.dispatch`` alone, and a prefill's
  ``serve.wait`` and ``serve.read`` stand where its result is read,
  LATE: after the cycle's ``serve.emit``, inside no span of the cycle,
  behind the dispatch of the cycle's step and before anything waits
  for that step (a settle's stand behind the settled step's two and
  its ``serve.emit``):

  ==================  ==================================================
  ``serve.dispatch``  host WORK: the host's arrays to the device, the
                      jitted call until it returns, ``set_buffers``,
                      the results sent on their way to the host, and
                      for a step the bookkeeping over the running slots
                      (lengths, ``remaining``, ``unread``).  ``dry=`` 1
                      where, as the host was about to launch, nothing it
                      had launched before was still running (no step in
                      flight and no prefill unread, or their results
                      ``is_ready()``: asked before the arrays are
                      shipped, without blocking): the chip was waiting
                      for the host, as ``ahead=1`` says the host was
                      not waiting for the chip.  A step dispatched
                      behind a prefill that has not finished is not
                      dry, so the benchmark's ``serve_dry_steps.admit``
                      is no longer the share of cycles that admit: it
                      is the share of steps that admitted AND still
                      found the chip dry
  ``serve.wait``      host SLACK: the one blocking read of a dispatched
                      program's result and nothing else; a prefill's
                      holds what is left of the prefill once the step
                      behind it is dispatched and the step before it
                      emitted (often nothing)
  ``serve.read``      host WORK: what the host does with the array once
                      it has it: the per-slot loop over the step's
                      slots, the draft and block counters, the routing
                      counts; a prefill's: the kind's bookkeeping, the
                      first token to its request, stamped, and the end
                      of a request whose first token was its last
                      (``request=``; ``late=`` 1 where a step was
                      dispatched behind the prefill before this read, 0
                      where a settle read it:
                      ``stats()["prefills_read_late"]`` counts the
                      ones, against ``["admitted"]``)
  ==================  ==================================================
  A step's ``serve.wait`` and ``serve.read`` lie in the
  ``serve.decode_step`` of the NEXT step, which reads it (as the
  ``moe_*`` attributes do), so the first step after a settle has a
  ``serve.dispatch`` only.  A settled step's two are top-level spans
  before the settle's ``serve.emit`` and carry the SETTLED step's own
  ``step``: every executed step is waited for and read exactly once.
  An **expert model**'s ``serve.decode_step`` and a prefill's
  ``serve.read`` also carry what the program routed, summed over its
  expert layers
  (``nn/experts.py`` ``COUNT_NAMES``; the counts ride back from the
  device with the tokens, in the same transfer, so a
  ``serve.decode_step`` carries those of the step it READ, k-1, and a
  settled step's go on its ``serve.settle`` event), listed here once:

  ====================  ================================================
  ``moe_held``          assignments to experts this chip holds
  ``moe_zero``          assignments to zero-compute (identity) experts
  ``moe_absent``        assignments to experts on other chips (left out
                        of this chip's share); the three add up to
                        ``top_k`` x tokens x expert layers
  ``moe_hit``           held experts that got a token (their weights are
                        what the grouped product has to read)
  ``moe_max_load``      the largest load of a held expert, a layer
  ``moe_group_hit_share``  under a router that chooses by groups
                        (``nn/experts.py`` ``groups``;
                        ``models/ling_flash.py``) only: the share of
                        the step's tokens (a layer) that kept a group
                        this chip holds experts of; the others send
                        this chip nothing
  ``context_tokens``    ``serve.decode_step`` only: the sum of the
                        active slots' contexts, the step's own token
                        included (the cache rows attention has to
                        read), of the same step as the counts
  ``attn_rows_copied``  beside ``context_tokens``, under a model whose
                        decode attention is a page-walking kernel
                        (``cache_spec``'s ``attn_query_rows``): the
                        rows ONE call of that kernel copies a pool for
                        the same slots (``ops/decode_attention.py``
                        ``stream_rows_copied``: whole blocks, the last
                        in groups of 8 pages); over ``context_tokens``
                        it is what the stream reads for every row it
                        must
  ``attn_copies``       beside ``attn_rows_copied``: the copy
                        descriptors that call starts a pool for those
                        rows, over the page tables the step was handed
                        (``stream_copies``: ONE for a group of 8 table
                        entries that name neighbouring pages, as
                        ``serving/cache.py`` hands them out, else one a
                        page); the rows over the page size over this is
                        the pages a descriptor, 8 under tables of runs
                        and 1 under scattered ones
                        (``stats()["attn_pages_a_copy"]``)
  ====================  ================================================

  The registry has the same counts as
  ``bigdl_serve_moe_assignments_total{kind}`` and
  ``bigdl_serve_moe_load_max_over_mean`` (``obs/names.py``).

  A **model that drafts** (``draft_spec``; ``models/joyai_flash.py``)
  verifies two positions a slot in a step, so its ``moe_*`` count both
  positions (and the prediction layer's expert layer), its
  ``context_tokens`` is still the rows the step had to read ONCE a slot
  (up to the second query's position: the two queries share one read),
  and its ``serve.decode_step`` (of the step it READ, like the counts;
  a settled step's on ``serve.settle``) also carries:

  ====================  ================================================
  ``draft_verified``    slots whose draft the step checked while they
                        owed the token it drafts (two or more left)
  ``draft_accepted``    of those, the drafts that were right: the step
                        yielded two tokens for the slot
  ``tokens_emitted``    tokens the step yielded over its live slots:
                        between their count and twice it
  ====================  ================================================

  Registry: ``bigdl_serve_draft_tokens_total{outcome="accepted"|
  "rejected"}``; ``stats()["draft_accept_share"]``,
  ``["tokens_per_step"]``.  ``ServeRequest.drafts`` keeps, for each
  verified draft, the index of the token it was checked against and the
  draft.  With two tokens a step ``serve.decode_step``'s length is still
  one step period less the host's work, not a gap between tokens, and
  ``active=`` still counts slots, not tokens.

  A **model that generates by blocks** (``block_spec``;
  ``models/sdar_moe.py``) refines a block of ``B`` positions a slot in
  a step and, in the same forward, writes the final rows of the block
  the slot finished in its last step (the pending **tail**), so its
  ``moe_*`` count every REAL position of every live slot (the block's,
  and the tail's where one was pending; padding is not counted; in a
  step with more tails than the expert layer expects, it runs a second
  time over the rest and ``moe_hit`` / ``moe_max_load`` count that run
  as another layer's), its
  ``context_tokens`` is the rows the step had to read ONCE a slot, up
  to its current block's end (the positions of both blocks and a key
  head's query heads share one read), and its ``serve.decode_step`` (of
  the step it READ; a settled step's on ``serve.settle``) also carries:

  ======================  ==============================================
  ``block_passes``        live slots that ran a refining pass: the
                          forwards of a slot (a wasted block of a slot
                          that had completed is none)
  ``block_tails``         of those, the slots whose forward also wrote
                          a pending block's final rows: the blocks that
                          became final a step before and did not end
                          their request
  ``block_commits``       forwards of a slot that ONLY commit a block:
                          0 since the final rows ride the next pass
                          (kept for the readers that add it to the
                          passes)
  ``positions_unmasked``  positions the refining passes made final:
                          between ``block_passes`` and ``B`` times it
  ``tokens_emitted``      tokens the step handed to requests: a
                          position's token goes out with the step after
                          which it and every position before it are
                          final, so 0 to ``B`` a slot
  ======================  ==============================================

  Registry: ``bigdl_serve_block_positions_total{outcome="unmasked"|
  "left_masked"}``; ``stats()["tokens_per_forward"]`` (tokens over
  passes of a slot), ``["tail_share"]`` (tails over passes: 1 / T where
  every block takes ``T`` passes, up to 1 where blocks take one),
  ``["block_passes"]``, ``["block_tails"]``, ``["block_commits"]``,
  ``["positions_unmasked"]``.  ``ServeRequest.unmasked`` keeps, for
  every generated position, its token and the pass of its block that
  unmasked it.  ``serve.decode_step``'s length is still one step period
  less the host's work; a reader sees a gap of one to FOUR periods
  (``B`` passes a block at most, and no step between two blocks).

  **Under a model whose slots carry state beside their pages**
  (``state_spec``; ``models/zaya.py``) ``serve.prefill`` also carries
  ``state_bytes=``, what the prefill wrote into the slot over all its
  layers; the same number is ``stats()["state_bytes_per_slot"]`` and
  the gauge ``bigdl_serve_slot_state_bytes``.  ``serve.decode_step``
  carries the routing counts and ``context_tokens`` as under any expert
  model.
* ``EVENT_*`` — point events the engine/simulator stamp regardless of
  request tracing.
"""

from __future__ import annotations

# ---------------------------------------------------- request-trace hops
#: whole routed request, router-side (placement -> final answer)
SPAN_ROUTE = "req.route"
#: one placement decision (PlacementPolicy.choose + signals snapshot)
SPAN_PLACEMENT = "req.placement"
#: one budget-gated retry: the backoff wait before re-placement
SPAN_RETRY = "req.retry"
#: a drain-handoff replay being absorbed (claim + prompt refold)
SPAN_HANDOFF = "req.handoff"
#: submit -> first slot admission (queue wait in batcher.py)
SPAN_QUEUE = "req.queue"
#: one batched prefill forward (per admission, attrs carry the bucket)
SPAN_PREFILL = "req.prefill"
#: preemption refold: pages lost -> re-admitted (KV-pressure eviction)
SPAN_PREEMPT = "req.preempt"
#: aggregated per-token decode time (everything not queue/prefill/
#: preempt inside the engine's e2e — exact partition, see engine.py)
SPAN_DECODE = "req.decode"

#: the hop keys the report attributes, in render order
HOP_ORDER = ("queue", "placement", "retry", "prefill", "decode",
             "preempt", "handoff", "route")

# ----------------------------------------------------------- live phases
#: one live batched decode step (its dispatch -> the previous step's
#: tokens on the host) — stamped by Engine._step as a REAL tracer span
#: (not a retroactive reqtrace hop) so the continuous profiler
#: (obs/prof.py) attributes decode-time samples to it: to the innermost
#: of its three children (dispatch, wait, read), work apart from slack
SPAN_STEP_DECODE = "serve.decode_step"
#: placing queued requests into free slots (contains SPAN_STEP_PREFILL)
SPAN_ADMISSION = "serve.admission"
#: the dispatch of one request's jitted prefill (its result is read
#: late: SPAN_STEP_WAIT / SPAN_STEP_READ with ``program="prefill"``)
SPAN_STEP_PREFILL = "serve.prefill"
#: host work of a decode step before its dispatch
SPAN_STEP_PREP = "serve.prep"
#: host work of a decode step after its tokens are on the host
SPAN_STEP_EMIT = "serve.emit"
#: inside a decode step or a prefill: launching the program (``dry=``)
SPAN_STEP_DISPATCH = "serve.dispatch"
#: the blocking read of a dispatched program's result (a step's: inside
#: the next decode step; a prefill's or a settled step's: in no span)
SPAN_STEP_WAIT = "serve.wait"
#: behind it: the host's work on the array it has read
SPAN_STEP_READ = "serve.read"

# ------------------------------------------------------------ point events
#: a request entered a decode slot (engine admission)
EVENT_ADMIT = "serve.admit"
#: a request was preempted off its slot (pages reclaimed)
EVENT_PREEMPT = "serve.preempt"
#: one chaos-scenario verdict (sim/serve.py)
EVENT_SCENARIO = "serve.scenario"
#: a step in flight was read and emitted outside the pipelined loop
#: (``reason=`` preempt | swap | idle | close)
EVENT_SETTLE = "serve.settle"
#: a live weight hot-swap completed (pointer flip between decode steps)
EVENT_WEIGHT_SWAP = "serve.weight_swap"
#: the rollout watcher refused a published checkpoint (verify failed)
EVENT_ROLLOUT_REJECT = "rollout.reject"
#: one canary decision (offer / promote / rollback / suppressed)
EVENT_ROLLOUT_DECISION = "rollout.decision"


def hop_key(span_name: str) -> str:
    """The attribution key of one request-trace span name
    (``"req.prefill"`` -> ``"prefill"``; foreign names pass through)."""
    return span_name[4:] if span_name.startswith("req.") else span_name


__all__ = ["SPAN_ROUTE", "SPAN_PLACEMENT", "SPAN_RETRY", "SPAN_HANDOFF",
           "SPAN_QUEUE", "SPAN_PREFILL", "SPAN_PREEMPT", "SPAN_DECODE",
           "SPAN_STEP_DECODE", "SPAN_ADMISSION", "SPAN_STEP_PREFILL",
           "SPAN_STEP_PREP", "SPAN_STEP_EMIT", "SPAN_STEP_DISPATCH",
           "SPAN_STEP_WAIT", "SPAN_STEP_READ", "HOP_ORDER", "EVENT_ADMIT",
           "EVENT_PREEMPT", "EVENT_SCENARIO", "EVENT_SETTLE",
           "EVENT_WEIGHT_SWAP",
           "EVENT_ROLLOUT_REJECT", "EVENT_ROLLOUT_DECISION", "hop_key"]
