"""What differs between kinds of decode step: one class a kind.

``serving/engine.py`` runs one loop for every model and holds ONE
object of this module, chosen once from what the model declares
(:func:`choose`).  :class:`OneToken` is the seam, in the order a request
meets it, with the defaults; :class:`Drafting` and :class:`Block`
override what differs; a class's docstring is that kind's contract with
its model.  A fourth kind of step is a fourth class and a line of
:func:`choose`.
"""

from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import obs
from bigdl_tpu.obs import names

#: what the engine hands a kind's traced bodies: its three picks and the
#: cache's two writes of a slot's state
DeviceOps = collections.namedtuple(
    "DeviceOps", "sample_step sample_first pick write_slot_state "
    "keep_inactive")

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


class _Active:
    """Host bookkeeping for one occupied slot.  ``remaining`` counts
    the tokens not yet DISPATCHED (a dispatched step is taken to yield
    the kind's ``sure`` tokens until it is read, one step late) and
    ``left`` those not yet EMITTED: the two agree whenever nothing is in
    flight.  It is made at the prefill's DISPATCH, from the prompt and
    the request alone: what the prefill yields (the first token;
    ``block``, the host's view under :class:`Block`) comes with the late
    read of its result (``prefilled``).  ``fresh`` is True until the
    slot's first step has taken from the host what the host knows of a
    slot admitted since the last step (its length, its owed count);
    what the prefill computed for that step is on the device already, in
    the carry.  ``unread`` is 1 while a step the slot ran in is unread;
    ``last_pos`` is the last position it can ever write at."""

    __slots__ = ("req", "remaining", "left", "fresh", "last_pos", "unread",
                 "order", "block")

    def __init__(self, req, remaining, prompt_len, order):
        self.req = req
        self.remaining = self.left = remaining
        self.fresh = True
        self.block: Optional[_Block] = None
        self.last_pos = prompt_len + remaining
        self.unread = 0
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


#: a read step on the host: ``tokens`` (B, k), ``emitted`` (B,) tokens
#: each slot yields (None: one each), the span's attributes, a drafting
#: model's verified ``drafts`` by slot, a block model's ``blocks`` by slot
#: (mask flags after the step, what the step did, its pass index)
_StepRead = collections.namedtuple(
    "_StepRead", "tokens emitted attrs drafts blocks", defaults=((), ()))


def choose(model, params, *, page_size: int, max_len: int,
           int8: bool = False, tp: int = 1):
    """The kind of step ``model`` declares, and every refusal between
    kinds and options.  Returns the kind's constructor (``(model,
    cache_spec, cache, page_size, eos_id, ops)`` -> the kind) and the
    model's ``state_spec`` or None, which the cache is built with."""
    per_step = int(model.draft_spec(params)["tokens_per_step"]
                   if hasattr(model, "draft_spec") else 1)
    if per_step not in (1, 2):
        raise ValueError("a step verifies one draft a slot at most")
    block = int(model.block_spec(params)["block_length"]
                if hasattr(model, "block_spec") else 0)
    if block and (per_step > 1 or int8 or tp > 1):
        raise ValueError("a model that generates by blocks neither "
                         "drafts nor offers int8 or tp decode")
    state = model.state_spec(params) if hasattr(model, "state_spec") \
        else None
    if state and (per_step > 1 or block):
        raise ValueError("a model whose slots carry state neither "
                         "drafts nor generates by blocks")
    if block and (page_size % block or max_len % block):
        raise ValueError(
            f"blocks of {block} do not divide the page size "
            f"{page_size} and the longest context {max_len}")
    if block:
        return functools.partial(Block, block=block), state
    if per_step > 1:
        return Drafting, state
    return functools.partial(
        OneToken, guarded=bool(state and state.get("keeps_inactive"))), state


class OneToken:
    """**One token a slot a step**, sampled by the engine
    (``models/transformer.py``, ``models/longcat_flash.py``):
    ``paged_prefill(params, caches, prompt, t0, pages)`` and
    ``paged_decode(params, caches, tables, lengths, tokens, active,
    ...)`` each return ``(caches, logits, counts)``, ``counts`` the
    step's expert-routing counts or None.  A step's tokens are the next
    step's input and never visit the host on the way, and neither does
    a prefill's first token: the prefill writes it into the slot's row
    of the same array (``handed``), so the step behind an admission is
    dispatched before anything of the prefill is read.  The host's
    lengths and owed counts advance by one at dispatch.

    **With state a slot carries that is not keys and values**
    (``state_spec(params)`` -> ``{"layers", "shapes", "dtype"}``:
    ``models/zaya.py``, ``models/falcon_h1.py``, ``models/ling_flash.py``,
    ``models/olmo_hybrid.py``;
    ``serving/cache.py`` keeps it beside the pages and tells its life,
    its cost and why a preemption snapshots nothing; ``layers`` counts
    the layers that KEEP state and ``cache_spec``'s the layers that keep
    pages, each indexed by its own count: Ling's are 6 and 1 of 7) both
    entry points hand it through:
    ``paged_prefill`` -> ``(caches, logits, counts, rows)``, ``rows``
    one ``(layers, *shape)`` array a shape, the state after the
    prompt's last REAL token, which the prefill writes into the slot
    whole; ``paged_decode(..., state=)`` -> ``(caches, logits, counts,
    state)``, advanced for the slots that ran.  An idle slot's is kept
    by ``keep_inactive`` over what the model handed back, unless the
    model's own update keeps it bit for bit (``state_spec``'s
    ``keeps_inactive``): then no pass over the state is added."""

    #: tokens a dispatch is sure to yield a slot; the query positions a
    #: slot a step forwards; where, in a step's outputs behind the
    #: cache's buffers, what the host reads begins (behind the next
    #: step's carry, or with it where the tokens are both); why this
    #: kind serves no temperature (None: it serves any); how many of
    #: the carry's leading arrays a prefill is handed, to write the
    #: fresh slot's row of: what the step behind it needs of its result
    sure, positions, result_at, greedy_because, handed = 1, 1, 0, None, 1
    # tallies (``stats``): 0 under the kinds that do not count one
    verified = accepted = passes = tails = unmasked = state_rebuilds = 0
    pages_copied = copies = 0

    def __init__(self, model, spec: dict, cache, page_size, eos_id,
                 ops: DeviceOps, guarded: bool = False):
        self.model, self.cache, self.ops = model, cache, ops
        self.page_size, self.eos_id = page_size, eos_id
        #: the query rows a slot the model hands a page-walking decode
        #: attention kernel (None: its attention is XLA's)
        self.query_rows = spec.get("attn_query_rows")
        #: the model's step keeps an inactive slot's state itself
        self.guarded = guarded
        self.slot_state_bytes = cache.state_bytes_per_slot()
        if cache.state:
            reg = obs.get_registry()
            reg.gauge(
                names.SERVE_SLOT_STATE_BYTES,
                "Bytes of state a slot carries beside its pages, over "
                "all layers").set(float(self.slot_state_bytes))
            self._rebuild_counter = reg.counter(
                names.SERVE_STATE_REBUILDS_TOTAL,
                "Prefills of a preempted request under a model whose "
                "slots carry state: the state rebuilt from the tokens")

    def carry(self) -> tuple:
        return (jnp.zeros((self.cache.max_slots,), jnp.int32),)

    def step(self, qparams):
        model, page_size, ops = self.model, self.page_size, self.ops
        # the cache's buffers: its pools and, behind them, the slots'
        # state (none unless the model declares one)
        n, pools = len(self.cache.buffers()), len(self.cache.pools())

        def step(params, *rest):
            # rest: the cache's buffers (donated), then tables, lengths,
            # the tokens on the device (the last step's, and in the row
            # of a slot admitted since its prefill's), temps, active, key
            tables, lengths, tokens, temps, active, key = rest[n:]
            if n > pools:
                caches, logits, counts, state = model.paged_decode(
                    params, rest[:pools], tables, lengths, tokens,
                    active, state=rest[pools:n], page_size=page_size,
                    qparams=qparams)
                # no pass over the state where the model's own update
                # leaves a slot that did not run as it was
                caches = (*caches, *(
                    state if self.guarded
                    else ops.keep_inactive(state, rest[pools:n], active)))
            else:
                caches, logits, counts = model.paged_decode(
                    params, rest[:n], tables, lengths, tokens, active,
                    page_size=page_size, qparams=qparams)
            nxt = ops.sample_step(logits, temps, active, key)
            # the routing counts ride back with the tokens
            return (*caches, nxt) if counts is None \
                else (*caches, nxt, counts)

        return step

    def prefill(self):
        model, ops = self.model, self.ops
        n, pools = len(self.cache.buffers()), len(self.cache.pools())

        def prefill(params, *rest):
            # rest: the cache's buffers (donated), then the prompt
            # (1, bucket) zero-padded past t0, t0, the bucket's pages,
            # the temperature, the key, the slot it is for, and the
            # tokens on the device, whose row of the slot it writes
            prompt, t0, pages, temp, key, slot, tokens = rest[n:]
            if n > pools:
                caches, logits, counts, rows = model.paged_prefill(
                    params, rest[:pools], prompt, t0, pages)
                caches = (*caches, *ops.write_slot_state(
                    rest[pools:n], slot, rows))
            else:
                caches, logits, counts = model.paged_prefill(
                    params, rest[:n], prompt, t0, pages)
            first = ops.sample_first(logits, temp, key)
            out = (*caches, tokens.at[slot].set(first), first)
            return out if counts is None else (*out, counts)

        return prefill

    def _picked_prefill(self):
        """The prefill of a model that picks inside its forward: greedy,
        so the temperature and the key are not read.  What the model
        hands back between the caches and the counts is, leaf by leaf,
        the slot's row of each array the prefill is handed
        (``handed``); the host reads the same rows, stacked."""
        model, pick, n = self.model, self.ops.pick, len(self.cache.buffers())

        def prefill(params, *rest):
            prompt, t0, pages = rest[n:n + 3]
            slot, *handed = rest[n + 5:]
            caches, *first, counts = model.paged_prefill(
                params, rest[:n], prompt, t0, pages, pick=pick)
            rows = jax.tree.leaves(first)
            handed = [arr.at[slot].set(row.astype(arr.dtype))
                      for arr, row in zip(handed, rows)]
            out = (*caches, *handed,
                   jnp.stack([row.astype(jnp.int32) for row in rows]))
            return out if counts is None else (*out, counts)

        return prefill

    def refuse(self, temperature: float):
        """At the door: what a request may not ask of this kind."""
        if self.greedy_because and float(temperature) > 0.0:
            raise ValueError(
                f"{type(self.model).__name__} {self.greedy_because}: "
                f"temperature {temperature:g} is not served (give 0)")

    def begin_prefill(self, req, tracer, span_id):
        """Inside an open ``serve.prefill``: what it says of the slot's
        state."""
        if not self.cache.state:
            return
        tracer.add_attrs(span_id, state_bytes=self.slot_state_bytes)
        if req.preempted:
            # its whole past is computed again: the state is rebuilt
            # from the tokens, not restored
            tracer.add_attrs(span_id, rebuilt=1)
            self.state_rebuilds += 1
            self._rebuild_counter.inc()

    def admit(self, slot: int, req, order: int) -> _Active:
        """At the prefill's dispatch -> the slot's bookkeeping, from the
        prompt and the request alone: the prefill yields the first
        token, so the steps owe one fewer."""
        return _Active(req, req.max_new_tokens - 1, len(req.payload), order)

    def prefilled(self, act: _Active, first):
        """At the late read of the prefill's first result, before the
        read of any step dispatched behind it: what the result means ->
        the token to emit (None: none yet)."""
        return int(first)

    def host_args(self, acts, key) -> tuple:
        """The step's arguments behind the carry, as host arrays: what
        the host knows of a slot without reading anything back."""
        b = self.cache.max_slots
        temps, active = np.zeros((b,), np.float32), np.zeros((b,), bool)
        for i, act in acts:
            temps[i] = act.req.temperature
            active[i] = True
        return temps, active, key

    def ahead(self, act: _Active, length: int) -> int:
        """How far past its (lower-bound) ``length`` the slot's next
        step may write: never past the request's last position."""
        return 0

    def read(self, rec, res, slots, note_routing) -> _StepRead:
        attrs = self._shared_attrs(rec, [i for i, _ in rec.entries],
                                   rec.context_rows, note_routing)
        if self.cache.state:
            # what the step read and wrote of the slots' state: in and
            # out, for the slots that ran
            attrs["state_bytes"] = (2 * len(rec.entries)
                                    * self.slot_state_bytes)
        return _StepRead(res[:, None], None, attrs)

    def _shared_attrs(self, rec, ran, context, note_routing) -> dict:
        """An expert model's routing counts and, beside them or a
        slot's state, ``context_tokens``: the sum of ``context`` (the
        rows a slot of ``ran``, those the step ran for, had to read,
        its own included), and under a page-walking decode attention
        kernel ``attn_rows_copied`` and ``attn_copies``: the rows one
        call copies a pool, and the descriptors it starts for them
        over the tables the step was handed."""
        attrs = {} if rec.counts is None else note_routing(rec.counts)
        if rec.counts is None and not self.cache.state:
            return attrs
        attrs["context_tokens"] = sum(context)
        if self.query_rows:
            from bigdl_tpu.ops.decode_attention import (stream_copies,
                                                        stream_rows_copied)

            lengths = np.asarray(context, np.int64) - 1
            shapes = (self.cache.row_width, self.cache.dtype.itemsize,
                      self.query_rows)
            rows = stream_rows_copied(lengths, self.page_size,
                                      rec.tables.shape[1], *shapes)
            copies = stream_copies(rec.tables[ran], lengths, self.page_size,
                                   self.cache.num_pages, *shapes)
            attrs["attn_rows_copied"], attrs["attn_copies"] = rows, copies
            self.pages_copied += rows // self.page_size
            self.copies += copies
        return attrs

    def yielded(self, slot: int, act: _Active, read: _StepRead) -> tuple:
        """Bring the host's bounds up to what the read step yielded
        ``slot`` -> tokens to emit, their first column in ``read.tokens``."""
        return 1, 0

    def after_emit(self, slot: int, act: _Active, read: _StepRead):
        """After the step's tokens went to a request that goes on."""

    def record(self, slot: int, act: _Active, preempted: bool = False):
        """Before the slot's pages go back: at its request's end, or
        before a preemption folds the generated prefix into the prompt."""

    def stats(self, step_tokens: int) -> dict:
        """The kinds' part of ``LMEngine.stats()``, every key of it."""
        verified, passes = self.verified, self.passes
        return {
            # the share of verified drafts accepted
            "drafts_verified": verified, "drafts_accepted": self.accepted,
            "draft_accept_share": (self.accepted / verified
                                   if verified else None),
            # a block model's: forwards of a slot (each a refining
            # pass), those that also wrote a pending tail's final rows,
            # those that only committed a block (none: the tail rides a
            # pass), tokens a forward, the tails' share of the forwards
            "block_passes": passes, "block_tails": self.tails,
            "block_commits": 0, "positions_unmasked": self.unmasked,
            "tokens_per_forward": step_tokens / passes if passes else None,
            "tail_share": self.tails / passes if passes else None,
            # a page-walking attention kernel's: the pages its stream
            # copied a descriptor (8 over tables of runs, 1 scattered)
            "attn_pages_a_copy": (self.pages_copied / self.copies
                                  if self.copies else None)}


class Drafting(OneToken):
    """**One or two tokens a slot a step**: a model that drafts its own
    next-but-one token (``draft_spec(params)``, ``tokens_per_step``: 2;
    ``models/joyai_flash.py``) has each step verify two positions a
    slot, the certain token and the draft, and yield the second token
    where the draft was right.  It chooses tokens in the middle of its
    step, so it is handed the engine's ``pick`` (logits ``(N, vocab)``
    -> tokens ``(N,)``) and returns tokens, not logits:
    ``paged_prefill(..., pick=)`` -> ``(caches, first, draft, counts)``,
    ``paged_decode(params, caches, tables, lengths, tokens, drafts,
    owed, active, pick=)`` -> ``(caches, picked (B, 2), accepted (B,),
    next_draft (B,), counts)``.  What a step yielded is known on the
    device a step before the host reads it, so a slot's next token,
    next draft, length and owed count are carried there (a prefill
    writes a fresh slot's token and draft); the host keeps bounds and
    reconciles at the read."""

    result_at = 4   # behind the token, the draft, the length, the owed count
    handed = 2      # the token and the draft
    greedy_because = ("verifies its own drafts by exact match against "
                      "the greedy token")

    def __init__(self, *args):
        super().__init__(*args)
        self._counter = obs.get_registry().counter(
            names.SERVE_DRAFT_TOKENS_TOTAL,
            "Drafts a self-drafting model's steps verified, by outcome",
            labels=("outcome",))

    def carry(self) -> tuple:
        return super().carry() * 4

    def step(self, qparams):
        model, page_size, pick = self.model, self.page_size, self.ops.pick
        n = len(self.cache.buffers())

        def step(params, *rest):
            # rest: the cache's buffers (donated), then tables, the
            # host's lengths, the four arrays the last step carried
            # (token, draft, length, owed; a prefill since wrote its
            # slot's token and draft), the host's owed counts: its
            # lengths and those for the slots in fresh (admitted since
            # that step), active
            (tables, h_len, tok, draft, c_len, c_owed,
             h_owed, fresh, active) = rest[n:]
            length = jnp.where(fresh, h_len, c_len)
            owed = jnp.where(fresh, h_owed, c_owed)
            # the host runs a slot while it MAY owe a token; the count
            # here is exact, and a slot that owes nothing computes a
            # wasted row at position 0 of its own pages
            run = active & (owed > 0)
            caches, picked, accepted, next_draft, counts = \
                model.paged_decode(
                    params, rest[:n], tables, jnp.where(run, length, 0),
                    tok, draft, owed, run, pick=pick,
                    page_size=page_size, qparams=qparams)
            emitted = jnp.where(run, 1 + accepted.astype(jnp.int32), 0)
            nxt = jnp.where(accepted, picked[:, 1], picked[:, 0])
            result = jnp.stack([picked[:, 0], picked[:, 1], emitted,
                                draft, length], axis=1)
            out = (*caches, nxt, next_draft, length + emitted,
                   owed - emitted, result)
            return out if counts is None else (*out, counts)

        return step

    def prefill(self):
        # the first token comes with the first draft
        return self._picked_prefill()

    def prefilled(self, act: _Active, first):
        return int(first[0])

    def host_args(self, acts, key) -> tuple:
        b = self.cache.max_slots
        owed = np.zeros((b,), np.int32)
        fresh, active = np.zeros((b,), bool), np.zeros((b,), bool)
        for i, act in acts:
            if act.fresh:
                fresh[i], owed[i], act.fresh = True, act.left, False
            active[i] = True
        return owed, fresh, active

    def ahead(self, act: _Active, length: int) -> int:
        # a step writes a second row, and a step not yet read may have
        # taken its draft
        return max(0, min(1 + act.unread, act.last_pos - length))

    def read(self, rec, res, slots, note_routing) -> _StepRead:
        # a result row: the two tokens picked, how many of them the step
        # yields (0 where the slot owed nothing), the draft it verified
        # and the slot's length before the step
        first, second, emitted, draft, length = res.T
        drafts, ran, context = {}, [], []
        accepted = tokens = 0
        for slot, act in rec.entries:
            if slots[slot] is not act or not emitted[slot]:
                continue    # completed since, or owed nothing there
            ran.append(slot)
            # a draft counts as verified where the slot owed the token
            # it drafts (the device's owed count is the host's ``left``
            # once every earlier step is emitted, as here)
            if act.left >= 2:
                drafts[slot] = int(draft[slot])
            accepted += int(emitted[slot] == 2)
            tokens += int(emitted[slot])
            # the rows the step had to read, once a slot: up to its
            # second query's position
            context.append(int(length[slot]) + 2)
        attrs = dict(draft_verified=len(drafts), draft_accepted=accepted,
                     tokens_emitted=tokens)
        self.verified += len(drafts)
        self.accepted += accepted
        self._counter.labels(outcome="accepted").inc(accepted)
        self._counter.labels(outcome="rejected").inc(len(drafts) - accepted)
        attrs.update(self._shared_attrs(rec, ran, context, note_routing))
        return _StepRead(np.stack([first, second], axis=1), emitted, attrs,
                         drafts=drafts)

    def yielded(self, slot: int, act: _Active, read: _StepRead) -> tuple:
        n = int(read.emitted[slot])
        if n:       # 0: owed nothing on the device, a wasted row
            if slot in read.drafts:
                req = act.req
                req.drafts.append((len(req.tokens), read.drafts[slot]))
            # the dispatch counted one token; the step may have yielded
            # another
            self.cache.lengths[slot] += n - 1
            act.remaining -= n - 1
        return n, 0


class Block(OneToken):
    """**A block refined a slot a step**: a model that generates by
    blocks (``block_spec(params)``: ``block_length``, ``passes``,
    ``threshold``; ``models/sdar_moe.py`` tells the forward) refines
    ``B`` positions a slot a step and owns the rule by which a pass
    unmasks.  ``paged_prefill(..., pick=)`` -> ``(caches, (tokens (B,),
    masked (B,)), counts)``: the first block and no token (what the
    prompt's whole blocks leave over sits, fixed, at its head).
    ``paged_decode(params, caches, tables, lengths, tokens (S, B),
    masked (S, B), passes (S,), tail (S, B), pending (S,), active,
    pick=)`` -> ``(caches, (tokens, masked, passes, lengths, tail,
    pending) after the step, kind (S,), counts)``: where the pass
    leaves no position masked the model rolls that state over ON THE
    DEVICE (the block becomes the pending tail, whose final rows the
    slot's next forward writes; ``lengths + B``; a new block all
    masked) and says so in ``kind``.  The state is carried from step to
    step untouched (a prefill writes a fresh slot's block and mask
    flags into it); the host keeps bounds and reconciles at the read.
    **Emission is by prefix**: a token goes to its request by the step
    after which it and every position before it are final, so a step
    yields 0 to ``B`` tokens a slot; ``ServeRequest.unmasked`` keeps
    every generated position's token and the pass that unmasked it."""

    sure, result_at, handed = 0, 6, 2   # handed: block tokens, mask flags
    greedy_because = ("unmasks a block's positions by the confidence of "
                      "the greedy token")

    def __init__(self, *args, block: int):
        super().__init__(*args)
        self.block, self.positions = block, 2 * block
        self._counter = obs.get_registry().counter(
            names.SERVE_BLOCK_POSITIONS_TOTAL,
            "Masked positions a block model's refining passes met, by "
            "outcome", labels=("outcome",))

    def carry(self) -> tuple:
        zeros, = super().carry()
        wide = jnp.zeros((self.cache.max_slots, self.block), jnp.int32)
        return (wide, wide.astype(bool), zeros, zeros, wide,
                zeros.astype(bool))

    def step(self, qparams):
        model, page_size, pick = self.model, self.page_size, self.ops.pick
        n = len(self.cache.buffers())

        def step(params, *rest):
            # rest: the cache's buffers (donated), then tables, the
            # host's lengths, the six arrays the last step carried
            # (block tokens, mask flags, pass count, length, the
            # tail's tokens, whether a tail is pending; a prefill since
            # wrote its slot's block and flags), the slots in fresh
            # (admitted since that step: the host's length, pass count
            # 0 and no tail pending), active
            (tables, h_len, tok, mask, c_pass, c_len, c_tail, c_pend,
             fresh, active) = rest[n:]
            done = jnp.where(fresh, 0, c_pass)
            length = jnp.where(fresh, h_len, c_len)
            pend = c_pend & ~fresh
            # an inactive slot computes a wasted block at position 0
            # of the trash page
            caches, state, kind, counts = model.paged_decode(
                params, rest[:n], tables, jnp.where(active, length, 0),
                tok, mask, done, c_tail, pend, active, pick=pick,
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

        return step

    def prefill(self):
        # no token is picked: the prompt's whole blocks are cached, and
        # the first block's tokens and mask flags come back
        return self._picked_prefill()

    def admit(self, slot: int, req, order: int) -> _Active:
        # the slot's length is its block's first position, and the
        # prefill yields no token
        b, t0 = self.block, len(req.payload)
        self.cache.lengths[slot] = t0 - t0 % b
        act = _Active(req, req.max_new_tokens, t0, order)
        act.last_pos = -(-(t0 + req.max_new_tokens) // b) * b - 1
        return act

    def prefilled(self, act: _Active, first):
        # the host's view of the first block, which the read of the
        # step behind the prefill needs; every generated position so
        # far is on record (a preemption folded them into the prompt)
        req = act.req
        act.block = _Block(first[0], first[1],
                           origin=len(req.payload) - len(req.unmasked))
        return None

    def host_args(self, acts, key) -> tuple:
        b = self.cache.max_slots
        fresh, active = np.zeros((b,), bool), np.zeros((b,), bool)
        for i, act in acts:
            fresh[i], act.fresh, active[i] = act.fresh, False, True
        return fresh, active

    def ahead(self, act: _Active, length: int) -> int:
        # a step writes its block's rows, and every step not yet read
        # may have finished a block and moved on to the next
        return max(0, min(self.block * (1 + act.unread) - 1,
                          act.last_pos - length))

    def read(self, rec, res, slots, note_routing) -> _StepRead:
        """What each live slot's step did, and how many tokens it shows:
        the unmasked prefix beyond what is shown already, up to the
        request's last token or an EOS."""
        b = self.block
        toks, after = res[:, :b], res[:, b:2 * b].astype(bool)
        length, kind, done, tail = res[:, 2 * b:].T    # BLOCK_RESULT
        emitted = np.zeros((self.cache.max_slots,), np.int32)
        blocks, ran, context = {}, [], []
        passes = tails = unmasked = left = 0
        for slot, act in rec.entries:
            if slots[slot] is not act or not kind[slot]:
                continue    # completed since: a wasted block
            ran.append(slot)
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
        self.passes += passes
        self.tails += tails
        self.unmasked += unmasked
        self._counter.labels(outcome="unmasked").inc(unmasked)
        self._counter.labels(outcome="left_masked").inc(left)
        # (no forward of a slot only commits a block: the count stays
        # for the readers that add it to the passes)
        attrs = dict(block_passes=passes, block_tails=tails,
                     block_commits=0, positions_unmasked=unmasked,
                     tokens_emitted=int(emitted.sum()))
        attrs.update(self._shared_attrs(rec, ran, context, note_routing))
        return _StepRead(toks, emitted, attrs, blocks=blocks)

    def yielded(self, slot: int, act: _Active, read: _StepRead) -> tuple:
        n, start = int(read.emitted[slot]), 0
        if slot in read.blocks:
            # the host's view of the block, up to the step read
            after, _, done = read.blocks[slot]
            blk = act.block
            newly = blk.masked & ~after
            blk.tokens[newly] = read.tokens[slot][newly]
            blk.passes[newly] = done
            blk.masked = after.copy()
            start = blk.shown
            blk.shown += n
        act.remaining -= n      # the dispatch counted none
        return n, start

    def after_emit(self, slot: int, act: _Active, read: _StepRead):
        if slot in read.blocks and read.blocks[slot][1] == BLOCK_FINISHED:
            # the pass left the block final and its request goes on: on
            # record, the length advances (the device's did in that
            # step), a new block
            self.record(slot, act)
            self.cache.lengths[slot] += self.block
            act.block.renew()

    def record(self, slot: int, act: _Active, preempted: bool = False):
        """The block's generated positions that are not on record yet,
        into ``ServeRequest.unmasked``: all of them, or at a preemption
        those already shown, as ``GIVEN`` (the rest is generated again)."""
        blk, req = act.block, act.req
        at = int(self.cache.lengths[slot]) - blk.origin
        for i in range(blk.shown if preempted else self.block):
            if at + i < len(req.unmasked):
                continue    # the prompt's, or recorded before
            if preempted:
                req.unmasked.append((int(blk.tokens[i]), GIVEN))
            elif blk.masked[i]:
                req.unmasked.append((0, NEVER_UNMASKED))
            else:
                req.unmasked.append((int(blk.tokens[i]),
                                     int(blk.passes[i])))
