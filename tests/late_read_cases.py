"""A prefill's first result is read late (PR 45): the cases every kind
of step has to pass, written once.  Each kind's own file builds its tiny
engine and runs them (``tests/test_serving.py``: ``OneToken``;
``test_zaya.py``: ``OneToken`` with slot state; ``test_joyai_flash.py``:
``Drafting``; ``test_sdar_moe.py``: ``Block``, whose prefill yields no
token, so it leaves ``FIRST_TOKEN_CASES`` out).

``prepare(build, prompts)`` makes what a file's fixture hands a
case: ``factory(**kw)``, a fresh engine of the kind (``max_batch``,
``num_pages``, ``eos_id``; pages of 4); the four ``prompts``, of
different lengths; and ``alone``, what each of them yields served alone
with ``NEW`` new tokens, greedy, which every mixed run has to reproduce
bit for bit.
"""

NEW = 9
#: a pool in which two of the prompts cannot both run to ``NEW`` tokens
TIGHT_PAGES = 6


def prepare(build, prompts):
    """``build(**kw)`` is the file's ``LMEngine(model, ..., page_size=4,
    **kw)``."""

    def factory(**kw):
        return build(**{"max_batch": 2, "num_pages": 40, **kw})

    eng = factory()
    alone = []
    for p in prompts:       # one request at a time on an idle engine
        req = eng.submit(p, NEW)
        eng.run_until_idle(timeout_s=300)
        assert req.error is None
        alone.append([int(t) for t in req.tokens])
    eng.close()
    return factory, prompts, alone


def _done(reqs, alone, new):
    for req, ref, n in zip(reqs, alone, new):
        assert req.done and req.error is None
        assert [int(t) for t in req.tokens] == ref[:n]


def _mixed(factory, prompts, alone):
    """Two admissions in one cycle, one while a step is in flight, one
    that waits for a slot: the tokens of each request served alone, and
    every prefill read behind a step's dispatch."""
    eng = factory(max_batch=3)
    new = (6, 5, NEW, 4)
    reqs = [eng.submit(p, n) for p, n in zip(prompts[:2], new)]
    assert eng.pump() and eng._inflight is not None
    assert [r is not None for r in eng._slots] == [True, True, False]
    assert not eng._unread      # read in the pump that dispatched them
    reqs.append(eng.submit(prompts[2], new[2]))
    assert eng.pump() and eng._slots[2] is not None
    reqs.append(eng.submit(prompts[3], new[3]))
    eng.run_until_idle(timeout_s=300)
    _done(reqs, alone, new)
    st = eng.stats()
    assert st["admitted"] == st["prefills_read_late"] == 4
    assert st["kv_pages_in_use"] == 0
    assert not any(req.preempted for req in reqs)
    eng.close()


def _one_new_token(factory, prompts, alone):
    """A request of one new token beside one that goes on."""
    eng = factory()
    new = (5, 1)
    reqs = [eng.submit(p, n) for p, n in zip(prompts, new)]
    eng.run_until_idle(timeout_s=300)
    _done(reqs, alone, new)
    assert eng.stats()["kv_pages_in_use"] == 0
    eng.close()


def _every_request_of_one_token(factory, prompts, alone):
    """Nothing to step: the prefills are read and their requests
    finished all the same, by the idle settle of the pump that
    dispatched them."""
    eng = factory()
    reqs = [eng.submit(p, 1) for p in prompts[:3]]
    eng.run_until_idle(timeout_s=300)
    _done(reqs, alone, (1, 1, 1))
    st = eng.stats()
    assert st["steps"] == 0 and st["admitted"] == 3
    assert st["prefills_read_late"] == 0 and st["kv_pages_in_use"] == 0
    eng.close()


def _eos_first(factory, prompts, alone):
    """An EOS as first token is learnt one cycle late: the slot's row in
    the step dispatched behind its prefill is wasted, never emitted, and
    its pages come back."""
    eng = factory(eos_id=alone[0][0])
    req = eng.submit(prompts[0], 4)
    assert eng.pump() and req.done        # ... in the pump that admitted
    assert [int(t) for t in req.tokens] == alone[0][:1]
    assert eng.stats()["kv_pages_in_use"] == 0
    eng.run_until_idle(timeout_s=300)
    st = eng.stats()
    assert st["steps"] == 1 and st["tokens"] == 1
    assert len(req.tokens) == 1 and st["prefills_read_late"] == 1
    eng.close()


def _preempt(factory, prompts, alone):
    """A pool too small for two requests to run to their ends: the fold
    of a preempted request sees every token it was given, a first token
    included, so its client still reads what it would have alone."""
    eng = factory(num_pages=TIGHT_PAGES)
    new = (NEW, NEW, NEW)
    reqs = [eng.submit(p, n) for p, n in zip(prompts, new)]
    eng.run_until_idle(timeout_s=300)
    _done(reqs, alone, new)
    st = eng.stats()
    preempted = sum(req.preempted for req in reqs)
    assert preempted >= 1 and st["kv_pages_in_use"] == 0
    assert st["admitted"] == 3 + preempted
    eng.close()


def _unread(factory, prompts):
    """An engine with one prefill dispatched and not read."""
    eng = factory()
    req = eng.submit(prompts[0], 4)
    assert eng._admit() == 1 and len(eng._unread) == 1
    assert not req.tokens and eng._slots[0] is not None
    return eng, req


def _swap(factory, prompts, alone):
    eng, req = _unread(factory, prompts)
    eng.swap_weights(eng.params, version="v1")
    assert not eng._unread and eng.stats()["prefills_read_late"] == 0
    eng.run_until_idle(timeout_s=300)
    _done([req], alone, (4,))
    eng.close()


def _close(factory, prompts, alone):
    eng, req = _unread(factory, prompts)
    eng.close()
    assert not eng._unread
    # what the prefill yielded is with the request: a first token
    # wherever the steps owe one fewer than was asked
    assert len(req.tokens) == int(eng._slots[0].left < 4)
    assert [int(t) for t in req.tokens] == alone[0][:len(req.tokens)]


def _drain(factory, prompts, alone):
    eng, req = _unread(factory, prompts)
    records = eng.drain(deadline_s=0.0)
    assert not eng._unread and eng.active_count() == 0
    rec, = records
    # the fold met the first token, where the prefill yields one
    assert rec.tokens_done == alone[0][:len(rec.tokens_done)]
    assert rec.prompt == [int(t) for t in prompts[0]] + rec.tokens_done
    assert rec.max_new_tokens == 4 - len(rec.tokens_done)
    assert eng.stats()["kv_pages_in_use"] == 0
    eng.close()


#: cases that hold for every kind
CASES = {fn.__name__[1:]: fn for fn in (
    _mixed, _one_new_token, _preempt, _swap, _drain, _close)}
#: ... and those about the token a prefill yields (a block model's
#: yields none)
FIRST_TOKEN_CASES = {fn.__name__[1:]: fn for fn in (
    _every_request_of_one_token, _eos_first)}
ALL_CASES = {**CASES, **FIRST_TOKEN_CASES}
