"""The tables ``TransformerLM``'s serving programs gather token rows
from (PR 46).  The engine hands ``jit_step`` and ``jit_prefill`` the
caller's tree with the two embeddings laid for a row gather
(``TransformerLM.serving_tables``: a width that is no whole number of
lane tiles padded up to one; a width that is, handed through).  What
the first layer receives is the embedding's rows bit for bit, whatever
the table's shape and dtype, so the logits are the caller's tables'
and the greedy tokens ``generate()``'s; a swap renews what was
prepared; the int8 and the tensor-parallel steps read the same
table."""

import numpy as np
import pytest

#: vocabulary, width: neither a whole tile (rows odd, 0.75 and 1.5625
#: lane tiles wide), both whole
SHAPES = [(257, 96), (257, 200), (256, 128), (384, 256)]


def _model(vocab, dim, max_len=64):
    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.models.transformer import build_transformer_lm

    RandomGenerator.RNG.set_seed(46)
    return build_transformer_lm(vocab, dim=dim, n_head=4, n_layer=2,
                                max_len=max_len, attn_impl="lax")


def _params(model, dtype="float32", scale=6.0):
    """The model's weights in ``dtype``, the matrices drawn wide enough
    that two tables give two different greedy continuations."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda a: (a * (scale if a.ndim == 2 else 1.0)).astype(
            jnp.dtype(dtype)), model.params())


def _other_tables(params):
    """The same weights with other embeddings: the token rows in
    another order, the positions' reversed."""
    import jax.numpy as jnp

    return {**params,
            "wte": {"weight": jnp.roll(params["wte"]["weight"], 7, axis=0)},
            "wpe": {"weight": params["wpe"]["weight"][::-1]}}


def _bits(a):
    return np.asarray(a).astype(np.float32)


def _ref(model, params, prompt, n):
    return [int(t) for t in np.asarray(model.generate(
        params, np.asarray(prompt)[None, :], n))[0]]


def _served(eng, prompt, n):
    req = eng.submit(prompt, n)
    eng.run_until_idle(120)
    assert req.error is None and len(req.tokens) == n
    return [int(t) for t in list(prompt) + req.tokens]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("vocab,dim", SHAPES)
def test_paged_logits_are_the_embedding_s_rows_to_the_bit(vocab, dim, dtype):
    import jax.numpy as jnp

    from bigdl_tpu.serving import LMEngine

    model = _model(vocab, dim)
    params = _params(model, dtype)
    eng = LMEngine(model, params=params, max_batch=2, page_size=8)
    weights = eng.weights()
    # ``params`` stays the caller's: the same arrays, as they came
    assert eng.params["wte"]["weight"] is params["wte"]["weight"]
    wide = -(-dim // 128) * 128
    for name in ("wte", "wpe"):
        table, weight = weights[name]["weight"], eng.params[name]["weight"]
        assert table.shape == (weight.shape[0], wide)
        assert table.dtype == weight.dtype
        # prepared only where the width has no whole tiles
        assert (table is weight) == (dim == wide)
        assert np.array_equal(_bits(table[:, :dim]), _bits(weight))
        assert not _bits(table[:, dim:]).any()
    assert all(weights[k] is eng.params[k] for k in params
               if k not in ("wte", "wpe"))

    rs = np.random.RandomState(dim)
    t0, bucket = 11, 16
    prompt = np.zeros((1, bucket), np.int32)
    prompt[0, :t0] = rs.randint(0, vocab, (t0,))
    prompt[0, 0] = vocab - 1              # the table's last row
    pages = jnp.asarray([1, 2], jnp.int32)
    tables = jnp.asarray([[1, 2], [0, 0]], jnp.int32)
    lengths = jnp.asarray([t0, 0], jnp.int32)
    active = jnp.asarray([True, False])
    got = {}
    for side, tree in (("served", weights), ("caller", params)):
        caches, first, _ = model.paged_prefill(
            tree, eng.cache.buffers(), jnp.asarray(prompt), t0, pages)
        tokens = jnp.asarray([int(jnp.argmax(first[0])), 0], jnp.int32)
        caches, nxt, _ = model.paged_decode(
            tree, caches, tables, lengths, tokens, active, page_size=8)
        got[side] = (first, nxt, *caches)
        assert first.dtype == nxt.dtype == jnp.dtype(dtype)
    eng.close()
    for a, b in zip(got["served"], got["caller"]):
        assert np.array_equal(_bits(a), _bits(b))
    # ... and the tokens they pick are generate()'s
    first, nxt = got["served"][:2]
    assert [int(jnp.argmax(first[0])), int(jnp.argmax(nxt[0]))] \
        == _ref(model, params, prompt[0, :t0], 2)[t0:]


@pytest.mark.parametrize("kw", [dict(), dict(int8=True)],
                         ids=["float", "int8"])
@pytest.mark.parametrize("dim", [96, 128])
def test_a_swap_renews_the_prepared_tables(dim, kw):
    """Greedy tokens follow the embeddings a swap brings: a table left
    from the old weights would give the old tokens."""
    from bigdl_tpu.serving import LMEngine

    model = _model(257, dim)
    old = _params(model)
    new = _other_tables(old)
    prompt = [256, 3, 1, 4, 1, 5, 9, 2, 6]
    eng = LMEngine(model, params=old, max_batch=2, page_size=8, **kw)
    before = _served(eng, prompt, 8)
    eng.swap_weights(new, version="v1")
    assert bool(eng._tables) == bool(dim % 128)
    assert np.array_equal(
        _bits(eng.weights()["wte"]["weight"][:, :dim]),
        _bits(new["wte"]["weight"]))
    after = _served(eng, prompt, 8)
    eng.close()
    fresh = LMEngine(model, params=new, max_batch=2, page_size=8, **kw)
    want = _served(fresh, prompt, 8)
    fresh.close()
    assert after == want and after != before
    if not kw:
        assert before == _ref(model, old, prompt, 8)
        assert after == _ref(model, new, prompt, 8)


@pytest.mark.parametrize("dim", [96, 128])
def test_the_tensor_parallel_step_reads_the_same_table(dim):
    from bigdl_tpu.serving import LMEngine

    model = _model(257, dim)
    params = _params(model)
    rs = np.random.RandomState(5)
    p1, p2 = rs.randint(0, 257, (5,)), rs.randint(0, 257, (9,))
    eng = LMEngine(model, params=params, max_batch=2, page_size=8, tp=4)
    r1, r2 = eng.submit(p1, 6), eng.submit(p2, 3)
    eng.run_until_idle(120)
    eng.close()
    assert [int(t) for t in list(p1) + r1.tokens] \
        == _ref(model, params, p1, 6)
    assert [int(t) for t in list(p2) + r2.tokens] \
        == _ref(model, params, p2, 3)


def test_a_model_that_prepares_nothing_is_handed_its_caller_s_tree():
    """An engine whose model offers no ``serving_tables`` (the six
    other serving models) passes ``params`` itself: no copy, no second
    reference that would keep a swapped-out tree alive."""
    from bigdl_tpu.serving import LMEngine

    model = _model(64, 32)
    params = _params(model)

    class Plain:
        """The same model behind a face without ``serving_tables``."""

        def __getattr__(self, name):
            if name == "serving_tables":
                raise AttributeError(name)
            return getattr(model, name)

    eng = LMEngine(Plain(), params=params, max_batch=2, page_size=8)
    assert eng._tables == {} and eng.weights() is eng.params
    prompt = [3, 1, 4, 1, 5]
    assert _served(eng, prompt, 5) == _ref(model, params, prompt, 5)
    eng.close()
