"""DistriOptimizer's own unpack and pack between the flat ZeRO-1 vector
and the parameter leaves (PR 51): the flat order is ``ravel_pytree``'s,
element for element; a leaf's route follows from its shape alone; and a
run on four devices equals, to the last bit, the same run with the unpack
and the pack patched back to ``ravel_pytree``'s closure and the gradient
taken with respect to the flat vector.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from bigdl_tpu import obs
from bigdl_tpu.dataset import ArrayDataSet
from bigdl_tpu.engine import Engine
from bigdl_tpu.nn import (ClassNLLCriterion, Linear, LogSoftMax, ReLU,
                          Sequential, SpatialConvolution, View)
from bigdl_tpu.optim import DistriOptimizer, SGD, Trigger
from bigdl_tpu.optim import distri_optimizer as D


@pytest.fixture(autouse=True)
def _engine(monkeypatch):
    for var in ("BIGDL_OBS", "BIGDL_TRACE_DIR", "BIGDL_HEALTH_EVERY"):
        monkeypatch.delenv(var, raising=False)
    obs.reset()
    Engine.reset()
    Engine.init()
    yield
    Engine.reset()
    obs.reset()


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _leaf(shape, seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


# (shape, takes the transposition)
SHAPES = [
    ((64,), False),
    ((1000, 2048), False),
    ((64, 3, 7, 7), True),       # the stem
    ((16, 8, 3, 3), True),
    ((8, 1, 3, 3), True),        # depthwise
    ((8, 4, 1, 1), False),       # a 1 x 1 kernel: a tail of one
    ((4, 2, 3, 3, 3), True),     # volumetric
    ((4, 2, 16, 8), False),      # a tail of 128 fills its lanes
]


@pytest.mark.parametrize("shape,relaid", SHAPES,
                         ids=["x".join(map(str, s)) for s, _ in SHAPES])
def test_a_leaf_takes_the_route_its_shape_says_and_keeps_every_bit(
        shape, relaid):
    assert D.leaf_is_relaid(shape) is relaid
    leaf = _leaf(shape, seed=len(shape))
    want, unravel = ravel_pytree({"w": leaf})
    layout = D.FlatLayout({"w": leaf})
    assert layout.said() == dict(
        leaves=1, relaid_leaves=int(relaid), elems=leaf.size,
        relaid_elems=leaf.size if relaid else 0)
    for pack in (layout.pack, jax.jit(layout.pack)):
        got = pack({"w": leaf})
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(_bits(got), _bits(want))
    for unpack in (layout.unpack, jax.jit(layout.unpack)):
        got = unpack(want)["w"]
        assert got.shape == shape and got.dtype == leaf.dtype
        np.testing.assert_array_equal(_bits(got), _bits(unravel(want)["w"]))
    # a pack in the wire's dtype is the flat vector cast
    got = jax.jit(lambda t: layout.pack(t, jnp.bfloat16))({"w": leaf})
    np.testing.assert_array_equal(_bits(got),
                                  _bits(want.astype(jnp.bfloat16)))


def _tree():
    """Every route at once, in a tree whose 1207 elements need padding
    at 4 shards."""
    return {"conv": {"weight": _leaf((5, 3, 3, 3), 1), "bias": _leaf((5,), 2)},
            "deep": [_leaf((4, 2, 3, 3, 3), 3), _leaf((3, 4, 1, 1), 4)],
            "fc": {"weight": _leaf((7, 100), 5), "bias": _leaf((7,), 6)},
            "wide": _leaf((1, 1, 16, 8), 7)}


def test_a_tree_that_needs_padding_unpacks_from_the_padded_vector():
    tree = _tree()
    want, unravel = ravel_pytree(tree)
    layout = D.FlatLayout(tree)
    assert layout.elems == want.size and want.size % 4
    assert layout.said()["relaid_leaves"] == 2
    flat = jax.jit(layout.pack)(tree)
    np.testing.assert_array_equal(_bits(flat), _bits(want))
    padded = jnp.pad(flat, (0, (-flat.size) % 4))
    got = jax.jit(layout.unpack)(padded)
    assert jax.tree.structure(got) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(unravel(want))):
        assert a.shape == b.shape
        np.testing.assert_array_equal(_bits(a), _bits(b))
    got16 = jax.jit(lambda t: layout.pack(t, jnp.bfloat16))(tree)
    np.testing.assert_array_equal(_bits(got16),
                                  _bits(want.astype(jnp.bfloat16)))


def test_leaves_of_two_dtypes_share_ravel_pytrees_common_dtype():
    tree = {"a": _leaf((4, 2, 3, 3), 1, jnp.bfloat16), "b": _leaf((6,), 2)}
    want, unravel = ravel_pytree(tree)
    layout = D.FlatLayout(tree)
    got = layout.pack(tree)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_array_equal(_bits(got), _bits(want))
    back = layout.unpack(got)
    assert back["a"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(_bits(back["a"]), _bits(tree["a"]))


# ---------------------------------------------------------- four devices
def _conv_model(seed=11):
    from bigdl_tpu.common import RandomGenerator

    RandomGenerator.RNG.set_seed(seed)
    return Sequential() \
        .add(SpatialConvolution(3, 6, 3, 3, 1, 1, 1, 1).set_name("stem")) \
        .add(ReLU()) \
        .add(SpatialConvolution(6, 4, 3, 3, 2, 2, 1, 1, n_group=2)
             .set_name("grouped")) \
        .add(ReLU()) \
        .add(SpatialConvolution(4, 4, 1, 1).set_name("pointwise")) \
        .add(View(4 * 4 * 4)) \
        .add(Linear(64, 5).set_name("head")) \
        .add(LogSoftMax())


def _images(n, seed=3):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 3, 8, 8).astype(np.float32)
    y = (rng.randint(0, 5, size=n) + 1).astype(np.float32)
    return x, y


def _ravel_closure(opt):
    """The optimizer as it was before PR 51: ``ravel_pytree``'s
    ``unravel`` inside the differentiated function, the gradient with
    respect to the flat vector, cast to the wire behind it."""
    _, unravel = ravel_pytree(opt.model.params())

    def grads(loss_fn, flat_p, rest, pack_dtype):
        (_, aux), grad = jax.value_and_grad(
            lambda f, *r: loss_fn(unravel(f), *r), has_aux=True)(
                flat_p, *rest)
        return aux, grad if pack_dtype is None else grad.astype(pack_dtype)

    class Closure:
        unpack = staticmethod(unravel)
        pack = staticmethod(lambda tree, dtype=None: ravel_pytree(tree)[0])
        said = opt._layout.said

    return grads, Closure


class _RaggedDataSet(ArrayDataSet):
    """Yields the ragged tail batch in train mode too, as a user's own
    DataSet may."""

    def data(self, train: bool = True):
        bs = self.batch_size
        for b in range(0, self._n, bs):
            yield self.features[b: b + bs], self.labels[b: b + bs]


class _Tape:
    def __init__(self):
        self.loss = {}

    def add_scalar(self, tag, v, s):
        if tag == "Loss":
            self.loss[s] = float(v)

    def add_histogram(self, *a, **k):
        pass

    def get_summary_trigger(self, name):
        return None

    def add_resilience(self, *a, **k):
        pass

    def close(self):
        pass


def _run(patched, n=192, freeze=None, compute=None, **kw):
    """Six steps of batch 32 on four devices (``n`` 190: the sixth batch
    holds 30 rows and is padded).  Returns losses, ``ok`` of every step,
    the velocity and the parameters written back."""
    x, y = _images(n)
    model = _conv_model()
    if freeze:
        model.freeze(freeze)
    mesh = Engine.build_mesh({"data": 4}, devices=jax.devices()[:4])
    opt = DistriOptimizer(model, _RaggedDataSet(x, y, 32, shuffle=False),
                          ClassNLLCriterion(), batch_size=32, mesh=mesh, **kw)
    opt.set_optim_method(SGD(learningrate=0.1, momentum=0.9, dampening=0.0,
                             weightdecay=1e-3))
    opt.set_end_when(Trigger.max_iteration(6))
    if compute:
        opt.set_compute_dtype(compute)
    tape = _Tape()
    opt.set_train_summary(tape)
    oks = []
    if patched:
        init = opt._init_params

        def init_then_patch():
            flat = init()
            opt._value_and_flat_grad, opt._layout = _ravel_closure(opt)
            opt._unpack = opt._layout.unpack
            return flat

        opt._init_params = init_then_patch
    build = opt._build_train_step

    def build_and_watch():
        step = build()

        def watched(*a):
            out = step(*a)
            oks.append(out[4])
            return out

        return watched

    opt._build_train_step = build_and_watch
    opt.optimize()
    assert len(tape.loss) == 6, tape.loss
    return dict(
        losses=[tape.loss[s] for s in sorted(tape.loss)],
        oks=[bool(o) for o in oks],
        velocity=np.asarray(opt.optim_method.state["velocity"]),
        params=[np.asarray(p) for p in jax.tree.leaves(model.params())],
        opt=opt)


CASES = {
    "plain": dict(),
    "bfloat16_compute": dict(compute="bfloat16"),
    "two_buckets": dict(overlap_bucket_mb=0.001),
    "float32_wire": dict(wire_dtype="float32"),
    "no_wire_cast": dict(wire_dtype="none"),
    "int8_staged_ring": dict(wire_dtype="int8", wire_block=8),
    "int8_ring_error_feedback": dict(wire_dtype="int8", wire_block=8,
                                     wire_ef=True),
    "one_frozen_leaf": dict(freeze="grouped"),
    "health_on": dict(health=True),
    "padded_last_batch": dict(n=190),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_six_steps_on_four_devices_equal_the_ravel_closures_to_the_last_bit(
        case, monkeypatch):
    kw = dict(CASES[case])
    if kw.pop("health", False):
        monkeypatch.setenv("BIGDL_HEALTH_EVERY", "2")
    new = _run(False, **kw)
    old = _run(True, **kw)
    if case == "two_buckets":
        assert len(new["opt"]._buckets) > 1, new["opt"]._buckets
    if case == "health_on":
        assert new["opt"]._health_monitor.fetches == 3
    if case == "padded_last_batch":
        assert new["opt"]._masked_step is not None
    assert new["losses"] == old["losses"], (new["losses"], old["losses"])
    assert new["oks"] == old["oks"] == [True] * 6
    np.testing.assert_array_equal(_bits(new["velocity"]),
                                  _bits(old["velocity"]))
    assert np.abs(new["velocity"]).max() > 0
    for a, b in zip(new["params"], old["params"]):
        np.testing.assert_array_equal(_bits(a), _bits(b))
    # the flat order is ravel_pytree's: the velocity unravels with it
    want, _ = ravel_pytree(new["opt"].model.params())
    got = new["opt"]._init_params()
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_the_unpack_says_once_a_program_how_often_it_engages(
        tmp_path, monkeypatch, caplog):
    monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path))
    obs.reset()
    with caplog.at_level(logging.DEBUG, logger="bigdl_tpu.optim"):
        out = _run(False)
    tracer = obs.get_tracer()
    tracer.flush()
    import json

    with open(tracer.jsonl_path, encoding="utf-8") as fh:
        events = [json.loads(line) for line in fh]
    said = [e for e in events if e.get("name") == "distri.unpack"]
    assert len(said) == 1, said
    attrs = said[0]["attrs"]
    assert attrs == dict(leaves=8, relaid_leaves=2,
                         elems=out["opt"]._flat_elems,
                         relaid_elems=6 * 3 * 9 + 4 * 3 * 9)
    assert any("distri.unpack" in r.getMessage() for r in caplog.records)


def test_a_model_without_a_relaid_leaf_says_zero(tmp_path, monkeypatch):
    monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path))
    obs.reset()
    rng = np.random.RandomState(0)
    x = rng.randn(64, 16).astype(np.float32)
    y = (rng.randint(0, 4, size=64) + 1).astype(np.float32)
    model = Sequential().add(Linear(16, 32)).add(ReLU()) \
        .add(Linear(32, 4)).add(LogSoftMax())
    mesh = Engine.build_mesh({"data": 4}, devices=jax.devices()[:4])
    opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(), batch_size=32,
                          mesh=mesh)
    opt.set_optim_method(SGD(learningrate=0.1))
    opt.set_end_when(Trigger.max_iteration(2))
    opt.optimize()
    tracer = obs.get_tracer()
    tracer.flush()
    import json

    with open(tracer.jsonl_path, encoding="utf-8") as fh:
        said = [json.loads(line) for line in fh]
    said = [e for e in said if e.get("name") == "distri.unpack"]
    assert [e["attrs"]["relaid_leaves"] for e in said] == [0]
    assert said[0]["attrs"]["relaid_elems"] == 0


def test_the_steps_phase_scopes_are_six():
    """``get_weights`` joins the five phase names in the lowered step."""
    out = _run(False)
    opt = out["opt"]
    pvar = opt._init_params()
    x, y = _images(32)
    inp, tgt = opt._put_batch(x, y)
    text = opt._build_step_impl(masked=False).lower(
        pvar, opt.optim_method.state, opt.model.state(), jax.random.key(0),
        inp, tgt).as_text(debug_info=True)
    for scope in ("get_weights", "computing", "put_gradient",
                  "aggregate_gradient", "optimizer_update", "send_weights"):
        assert scope in text, scope
