"""The pick at a decode step's end (ISSUE 40): ``serving/engine.py``
``sample_step`` and ``sample_first`` draw only where a running slot
samples, bit for bit what they drew, and pick with ``pick_greedy``
(``jnp.argmax``) where none does."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.serving.engine import pick_greedy, sample_first, sample_step

DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


def _random(rows, vocab, dtype, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, vocab),
                             jnp.float32).astype(dtype)


def _tied(x):
    """Rows that tie: equal maxima far apart, a constant row, and 1.0
    beside 1 + 2**-9 (one bfloat16 value, two float32 values)."""
    hi = jnp.asarray(1.0 + 2.0 ** -9, jnp.float32).astype(x.dtype)
    x = x.at[0::3, 900].set(9.0).at[0::3, 300].set(9.0)
    x = x.at[1].set(0.25)
    return x.at[2].min(0.5).at[2, 70].set(1.0).at[2, 700].set(hi)


def _not_finite(x):
    """A NaN between two infinities, a row of ``-inf``, one of ``-inf``
    but for its last logit."""
    x = x.at[0, 40].set(jnp.inf).at[0, 777].set(jnp.nan) \
        .at[0, 1000].set(jnp.inf)
    return x.at[1].set(-jnp.inf).at[3].set(-jnp.inf).at[3, -1].set(-3.0)


LOGITS = {"random": lambda x: x, "tied": _tied, "not_finite": _not_finite}


# ------------------------------------------------------------ the engine
def _old_sample_step(logits, temps, active, key):
    """``sample_step`` as it was before ISSUE 40: the plain reference."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled = jax.random.categorical(
        key, logits / jnp.maximum(temps, 1e-6)[:, None],
        axis=-1).astype(jnp.int32)
    nxt = jnp.where(temps > 0.0, sampled, greedy)
    return jnp.where(active, nxt, 0)


def _old_sample_first(logits, temp, key):
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled = jax.random.categorical(
        key, logits / jnp.maximum(temp, 1e-6), axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0.0, sampled, greedy)[0]


ACTIVE = jnp.asarray([True] * 9 + [False, True, False])
TEMPS = {
    "all_greedy": [0.0] * 12,
    "mixed": [0.0, 0.7, 1.0, 0.0, 0.0, 1.3, 0.0, 0.0, 0.2, 0.0, 0.0, 0.0],
    "one_samples": [0.0] * 10 + [0.9, 0.0],
    "all_sample": [0.8] * 12,
    # the only temperature belongs to a slot that does not run
    "an_idle_slot_alone": [0.0] * 9 + [0.9, 0.0, 0.9],
}


@pytest.mark.parametrize("temps", sorted(TEMPS))
@pytest.mark.parametrize("logits", sorted(LOGITS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_sample_step_is_the_old_formula_slot_for_slot(dtype, logits, temps):
    x = LOGITS[logits](_random(12, 1411, DTYPES[dtype], seed=3))
    t = jnp.asarray(TEMPS[temps], jnp.float32)
    for seed in (0, 5):
        key = jax.random.key(seed)
        got = jax.jit(sample_step)(x, t, ACTIVE, key)
        want = _old_sample_step(x, t, ACTIVE, key)
        assert got.dtype == want.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if temps in ("all_greedy", "an_idle_slot_alone"):
        np.testing.assert_array_equal(
            np.asarray(got),
            np.where(ACTIVE, np.asarray(jnp.argmax(x, axis=-1)), 0))
    else:   # it did sample
        assert (np.asarray(got) != np.asarray(
            _old_sample_step(x, jnp.zeros(12), ACTIVE, key))).any()


def test_the_rows_built_to_tie_do():
    """Else the test above would hold whatever the pick did with
    equals."""
    y = _tied(_random(12, 1411, jnp.bfloat16, seed=3))
    top = jnp.max(y, axis=-1, keepdims=True)
    held = [tuple(np.flatnonzero(r)) for r in np.asarray(y == top)]
    assert held[0] == (300, 900) and len(held[1]) == 1411
    assert held[2] == (70, 700)
    assert np.asarray(pick_greedy(y))[:3].tolist() == [300, 0, 70]
    y = _tied(_random(12, 1411, jnp.float32, seed=3))
    assert int(pick_greedy(y)[2]) == 700


@pytest.mark.parametrize("temp", [0.0, 0.6, 1.0])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_sample_first_is_the_old_formula(dtype, temp):
    logits = _random(1, 1411, DTYPES[dtype], seed=4)
    for seed in (1, 2):
        key = jax.random.key(seed)
        # the engine hands the temperature over as a Python float
        got = jax.jit(sample_first)(logits, temp, key)
        want = jax.jit(_old_sample_first)(logits, temp, key)
        assert int(got) == int(want)
    if not temp:
        assert int(got) == int(jnp.argmax(logits[0]))


def _primitives(jaxpr):
    """Every primitive's name in ``jaxpr``, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def _conds(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _conds(sub)


def _reduces(names):
    return [n for n in names if n == "argmax" or n.startswith("reduce_m")]


def _draws(names):
    return [n for n in names if n.startswith("random_")
            or "threefry" in n or n in ("log", "erf_inv")]


@pytest.mark.parametrize("fn", ["sample_step", "sample_first"])
def test_the_greedy_arm_draws_nothing(fn):
    """One ``cond`` on the batch's own temperatures; its greedy branch
    holds no random-bits primitive (nor a logarithm), the other does,
    and nothing outside the ``cond`` draws either."""
    logits = _random(12, 1411, jnp.bfloat16)
    key = jax.random.key(0)
    if fn == "sample_step":
        closed = jax.make_jaxpr(sample_step)(
            logits, jnp.zeros((12,), jnp.float32), ACTIVE, key)
    else:
        closed = jax.make_jaxpr(sample_first)(logits[:1], 0.0, key)
    cond, = _conds(closed.jaxpr)
    greedy, sampled = (list(_primitives(b.jaxpr))
                       for b in cond.params["branches"])
    assert not _draws(greedy), _draws(greedy)
    assert "random_bits" in sampled and "log" in sampled
    outside = [n for eqn in closed.jaxpr.eqns if eqn is not cond
               for n in [eqn.primitive.name] + [
                   m for sub in jax.core.jaxprs_in_params(eqn.params)
                   for m in _primitives(sub)]]
    assert not _draws(outside), _draws(outside)
    # each arm picks for itself: a sampling step pays for no pass over
    # the logits that it did not pay for before
    assert greedy.count("argmax") == 1 and not _reduces(outside)
    # (a categorical draw ends in an argmax of its own)
    assert sampled.count("argmax") == 1 + (fn == "sample_step")


def test_pick_greedy_is_argmax_under_the_scope_sample():
    """What a drafting or block model picks with is as it was."""
    x = _tied(_random(24, 1152, jnp.bfloat16))
    got = pick_greedy(x)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.argmax(x, axis=-1)))
    eqn, = (e for e in jax.make_jaxpr(pick_greedy)(x).jaxpr.eqns
            if e.primitive.name == "argmax")
    assert str(eqn.source_info.name_stack) == "sample"
