"""DistriOptimizer specs — the real sharded step on 8 virtual devices.

Mirrors the reference's DistriOptimizerSpec / AllReduceParameterSpec run
on a local[4] Spark master (SURVEY.md §4.5): the REAL collective path
(psum_scatter + owner update + all_gather via shard_map), no mocks.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.dataset import ArrayDataSet, DistributedDataSet
from bigdl_tpu.engine import Engine
from bigdl_tpu.nn import ClassNLLCriterion, Linear, LogSoftMax, ReLU, Sequential
from bigdl_tpu.optim import (
    DistriOptimizer, LocalOptimizer, Optimizer, SGD, Top1Accuracy, Trigger,
)
from bigdl_tpu.optim.evaluator import evaluate_dataset


@pytest.fixture(autouse=True)
def _engine():
    Engine.reset()
    Engine.init()
    yield
    Engine.reset()


def _toy(n=512, d=16, k=4, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(d, k)
    x = rng.randn(n, d).astype(np.float32)
    y = (np.argmax(x @ w, axis=1) + 1).astype(np.float32)
    return x, y


def _model(d=16, k=4):
    return Sequential().add(Linear(d, 32)).add(ReLU()).add(Linear(32, k)) \
        .add(LogSoftMax())


def test_mesh_has_8_devices():
    assert Engine.mesh().shape["data"] == 8


def test_sharded_step_compiles_once_and_batch_is_put_shardwise():
    """The first call's params and model state come from the model (one
    device), every later call's are step outputs (replicated over the
    mesh): without committing them to the mesh up front the step was
    traced and compiled twice — a second ResNet-50-sized compile on a
    chip.  And the global batch goes from host memory straight to each
    device's shard, never through one device."""
    compiled = []

    def on_compile(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name"))

    x, y = _toy(n=256)
    opt = DistriOptimizer(_model(), (x, y), ClassNLLCriterion(),
                          batch_size=64)
    opt.set_optim_method(SGD(learningrate=0.1, momentum=0.9))
    opt.set_end_when(Trigger.max_iteration(4))
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        opt.optimize()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert len([f for f in compiled if "sharded_step" in str(f)]) == 1
    inp, _ = opt._put_batch(x[:64], y[:64])
    assert {s.data.shape for s in inp.addressable_shards} == {(8, 16)}
    assert len({s.device for s in inp.addressable_shards}) == 8


def test_distri_optimizer_converges():
    x, y = _toy()
    model = _model()
    opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learningrate=0.5))
    opt.set_end_when(Trigger.max_epoch(10))
    trained = opt.optimize()
    ds = ArrayDataSet(x, y, 64)
    (acc,) = evaluate_dataset(trained, ds, [Top1Accuracy()])
    value, _ = acc.result()
    assert value > 0.9, f"accuracy {value}"


def test_distri_matches_local_single_step():
    """ZeRO-1 sharded update must equal the local update exactly
    (modulo float assoc): same batch, same init, one step, compare
    weights — the reference's semantics-parity requirement
    (SURVEY.md §7 hard part 2)."""
    from bigdl_tpu.common import RandomGenerator

    x, y = _toy(64)
    RandomGenerator.RNG.set_seed(7)
    m1 = _model()
    RandomGenerator.RNG.set_seed(7)
    m2 = _model()
    for a, b in zip(m1.get_weights(), m2.get_weights()):
        np.testing.assert_allclose(a, b)

    ds = ArrayDataSet(x, y, 64, shuffle=False)
    lo = LocalOptimizer(m1, ds, ClassNLLCriterion(), batch_size=64)
    lo.set_optim_method(SGD(learningrate=0.1))
    lo.set_end_when(Trigger.max_iteration(1))
    lo.optimize()

    ds2 = ArrayDataSet(x, y, 64, shuffle=False)
    do = DistriOptimizer(m2, ds2, ClassNLLCriterion(), batch_size=64,
                         wire_dtype="none")
    do.set_optim_method(SGD(learningrate=0.1))
    do.set_end_when(Trigger.max_iteration(1))
    do.optimize()

    for a, b in zip(m1.get_weights(), m2.get_weights()):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_distri_bf16_wire_still_converges():
    x, y = _toy(256)
    model = _model()
    opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(), batch_size=64,
                          wire_dtype="bfloat16")
    opt.set_optim_method(SGD(learningrate=0.5))
    opt.set_end_when(Trigger.max_epoch(8))
    trained = opt.optimize()
    ds = ArrayDataSet(x, y, 64)
    (acc,) = evaluate_dataset(trained, ds, [Top1Accuracy()])
    assert acc.result()[0] > 0.85


def test_distri_gradient_clipping():
    x, y = _toy(128)
    model = _model()
    opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learningrate=0.5))
    opt.set_gradient_clipping_by_l2_norm(0.1)
    opt.set_end_when(Trigger.max_epoch(2))
    opt.optimize()  # just exercises the psum-based global-norm path


def test_optimizer_factory_dispatches_distributed():
    x, y = _toy(64)
    model = _model()
    ds = DistributedDataSet(x, y, 32)
    opt = Optimizer(model=model, training_set=ds,
                    criterion=ClassNLLCriterion(), batch_size=32)
    assert isinstance(opt, DistriOptimizer)


def test_distri_momentum_state_sharded():
    """Optimizer state must live sharded over the mesh (ZeRO-1) — check
    the velocity buffer's sharding spec."""
    x, y = _toy(64)
    model = _model()
    opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learningrate=0.1, momentum=0.9))
    opt.set_end_when(Trigger.max_iteration(2))
    opt.optimize()
    vel = opt.optim_method.state["velocity"]
    sharding = vel.sharding
    spec = sharding.spec
    assert spec[0] == "data", f"velocity not sharded: {spec}"


class _RaggedDataSet(ArrayDataSet):
    """Yields the ragged tail batch even in train mode — models custom
    user DataSets whose generators are not tail-trimmed."""

    def data(self, train: bool = True):
        bs = self.batch_size
        for b in range(0, self._n, bs):
            yield self.features[b: b + bs], self.labels[b: b + bs]


def test_distri_partial_batch_padded(caplog):
    """VERDICT r1 weak 3 / r3 weak 7: batches not divisible by the mesh
    are PADDED with masked copies (reference SampleToMiniBatch
    semantics) — never trimmed — and training still converges."""
    import logging

    x, y = _toy(n=166)  # 166 = 2*64 + 38; 38 % 8 = 6 -> pad to 40
    model = _model()
    ds = _RaggedDataSet(x, y, 64)
    opt = DistriOptimizer(model, ds, ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learningrate=0.5))
    opt.set_end_when(Trigger.max_epoch(6))
    with caplog.at_level(logging.INFO, logger="bigdl_tpu.optim"):
        trained = opt.optimize()
    assert any("padding with" in r.message for r in caplog.records)
    eval_ds = ArrayDataSet(x, y, 64)
    (acc,) = evaluate_dataset(trained, eval_ds, [Top1Accuracy()])
    value, _ = acc.result()
    assert value > 0.85, f"accuracy {value}"


def test_distri_batch_smaller_than_mesh_padded(caplog):
    """A batch smaller than the mesh was previously dropped outright;
    now it pads to one sample-per-device with the rest masked."""
    import logging

    x, y = _toy(n=64 + 5)  # last batch of 5 < 8 devices -> pad to 8
    model = _model()
    ds = _RaggedDataSet(x, y, 64)
    opt = DistriOptimizer(model, ds, ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learningrate=0.5))
    opt.set_end_when(Trigger.max_epoch(2))
    with caplog.at_level(logging.INFO, logger="bigdl_tpu.optim"):
        opt.optimize()
    assert any("padding with" in r.message for r in caplog.records)


class _LossTape:
    """Minimal train-summary stub capturing the per-iteration Loss."""

    def __init__(self):
        self.losses = []

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            self.losses.append(float(value))

    def add_histogram(self, *a, **k):
        pass

    def get_summary_trigger(self, name):
        return None


def test_partial_batch_loss_trajectory_matches_local():
    """VERDICT r3 item 5 'done' gate: the loss trajectory must be
    IDENTICAL (fp tolerance) whether or not the dataset size divides
    the mesh — i.e. the masked padded step computes the same
    mean-over-valid-samples gradient a single-device run does on the
    ragged tail."""
    x, y = _toy(n=64 + 37, seed=3)  # tail batch of 37: 37 % 8 = 5
    losses = {}
    for cls in (LocalOptimizer, DistriOptimizer):
        model = _model()  # same RandomGenerator seed via autouse fixture
        from bigdl_tpu.common import RandomGenerator

        RandomGenerator.RNG.set_seed(7)
        model = _model()
        ds = _RaggedDataSet(x, y, 64)
        opt = cls(model, ds, ClassNLLCriterion(), batch_size=64)
        if isinstance(opt, DistriOptimizer):
            opt.wire_dtype = "none"  # bf16 wire would blur the comparison
        opt.set_optim_method(SGD(learningrate=0.3))
        opt.set_end_when(Trigger.max_epoch(3))
        tape = _LossTape()
        opt.set_train_summary(tape)
        opt.optimize()
        losses[cls.__name__] = tape.losses
    local, distri = losses["LocalOptimizer"], losses["DistriOptimizer"]
    assert len(local) == len(distri) == 6  # 2 batches x 3 epochs
    np.testing.assert_allclose(local, distri, rtol=2e-4, atol=2e-5)


def test_distri_metrics_phases():
    """VERDICT r1 weak 2: Distri runs expose >= 3 host phases under the
    reference Metrics naming."""
    x, y = _toy(n=128)
    model = _model()
    opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(), batch_size=64)
    opt.set_end_when(Trigger.max_epoch(2))
    opt.optimize()
    s = opt.metrics.summary()
    for phase in ("data wait time", "put batch time", "computing time"):
        assert phase in s, s
    assert opt.metrics.value("computing time") > 0


def test_distri_plateau_schedule_applies():
    """VERDICT r1 weak 6: Plateau's host-side lr_scale poke must reach
    the sharded optimizer state between jitted steps."""
    from bigdl_tpu.optim.optim_method import Plateau

    x, y = _toy(n=256)
    model = _model()
    # epsilon=0.5: "improvement" requires +0.5 accuracy — impossible
    # after epoch 1, so the schedule must decay deterministically
    method = SGD(learningrate=0.5,
                 learningrate_schedule=Plateau(monitor="score", factor=0.5,
                                               patience=0, mode="max",
                                               epsilon=0.5))
    opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(method)
    opt.set_end_when(Trigger.max_epoch(6))
    opt.set_validation(
        trigger=Trigger.every_epoch(),
        dataset=(x, y),
        methods=[Top1Accuracy()],
    )
    opt.optimize()
    # patience=0: any non-improving epoch halves the lr; after 6 epochs
    # of a near-converged toy the scale must have dropped at least once
    assert float(method.state["lr_scale"]) < 1.0
    # and training still behaves
    ds = ArrayDataSet(x, y, 64)
    (acc,) = evaluate_dataset(model, ds, [Top1Accuracy()])
    assert acc.result()[0] > 0.85


def test_distributed_dataset_per_process_slices():
    """DistributedDataSet's iterator contract: every process derives the
    same global permutation and takes its contiguous slice of each
    global batch."""
    from bigdl_tpu.common import RandomGenerator

    x = np.arange(64, dtype=np.float32).reshape(64, 1)
    y = np.arange(64, dtype=np.float32)
    views = []
    for pid in range(2):
        RandomGenerator.RNG.set_seed(7)  # same seed on every "process"
        ds = DistributedDataSet(x, y, batch_size=16, process_id=pid,
                                num_processes=2)
        views.append(list(ds.data(train=True)))
    assert len(views[0]) == 4  # 64 / 16 global batches
    for (f0, l0), (f1, l1) in zip(*views):
        assert f0.shape == (8, 1) and f1.shape == (8, 1)  # local slices
        # slices are disjoint rows of the same global batch
        assert not set(l0.tolist()) & set(l1.tolist())
    # union over one epoch covers every sample exactly once
    seen = np.concatenate(
        [l for view in views for _, l in view]
    )
    assert sorted(seen.tolist()) == list(range(64))


def test_distributed_dataset_trains_single_process():
    x, y = _toy(n=256)
    model = _model()
    ds = DistributedDataSet(x, y, batch_size=64, process_id=0,
                            num_processes=1)
    opt = DistriOptimizer(model, ds, ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learningrate=0.5))
    opt.set_end_when(Trigger.max_epoch(8))
    trained = opt.optimize()
    (acc,) = evaluate_dataset(trained, ArrayDataSet(x, y, 64),
                              [Top1Accuracy()])
    assert acc.result()[0] > 0.9


def test_sharded_evaluate_matches_single_device():
    """Distributed evaluate (VERDICT r2 #3): the P(data)-sharded eval
    forward over the 8-device mesh must reproduce single-device results
    exactly, including a ragged tail batch (padded + sliced)."""
    x, y = _toy(100)  # 100 % 8 != 0: exercises the pad/slice path
    model = _model()
    model.evaluate()
    ds = ArrayDataSet(x, y, 32, shuffle=False)
    (single,) = evaluate_dataset(model, ds, [Top1Accuracy()])
    (sharded,) = evaluate_dataset(model, ds, [Top1Accuracy()],
                                  mesh=Engine.mesh())
    assert single.result() == sharded.result()


def test_sharded_predict_matches_single_device():
    from bigdl_tpu.optim.evaluator import predict

    x, _ = _toy(37)
    model = _model()
    np.testing.assert_allclose(
        predict(model, x, batch_size=16),
        predict(model, x, batch_size=16, mesh=Engine.mesh()),
        rtol=1e-6,
    )


def test_distri_validation_uses_device_resident_params():
    """_run_validation must not round-trip weights through the host:
    _write_back is only called at the end of optimize(), not per
    validation trigger."""
    x, y = _toy(256)
    model = _model()
    opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learningrate=0.5))
    opt.set_end_when(Trigger.max_epoch(3))
    opt.set_validation(Trigger.every_epoch(), (x, y), [Top1Accuracy()])

    calls = {"write_back": 0, "validate": 0}
    orig_wb = opt._write_back
    orig_rv = opt._run_validation

    def counting_wb(pvar, mstate):
        calls["write_back"] += 1
        return orig_wb(pvar, mstate)

    def counting_rv(pvar=None, mstate=None):
        calls["validate"] += 1
        assert pvar is not None, "validation must receive device params"
        return orig_rv(pvar, mstate)

    opt._write_back = counting_wb
    opt._run_validation = counting_rv
    opt.optimize()
    assert calls["validate"] >= 3
    assert calls["write_back"] == 1, calls  # only the final write-back
    assert opt.state["score"] is not None


def test_distri_retry_from_checkpoint(tmp_path):
    """Failure semantics (VERDICT r2 #4; SURVEY.md §5): inject a failure
    mid-training; DistriOptimizer must reload the last checkpoint, rewind
    epoch/neval, and converge to EXACTLY the same weights as an
    uninterrupted run (same data order, same per-step RNG folding)."""
    from bigdl_tpu.common import RandomGenerator

    x, y = _toy(256)
    ds = ArrayDataSet(x, y, 64, shuffle=False)  # 4 iterations / epoch

    def build(seed=11):
        RandomGenerator.RNG.set_seed(seed)
        return _model()

    # --- uninterrupted reference run ---
    m_ref = build()
    ref = DistriOptimizer(m_ref, ds, ClassNLLCriterion(), batch_size=64)
    ref.set_optim_method(SGD(learningrate=0.2, momentum=0.9))
    ref.set_end_when(Trigger.max_epoch(3))
    ref.optimize()

    # --- run with injected failure at epoch 2, first batch ---
    m = build()
    opt = DistriOptimizer(m, ds, ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learningrate=0.2, momentum=0.9))
    opt.set_end_when(Trigger.max_epoch(3))
    opt.set_checkpoint(str(tmp_path), Trigger.every_epoch())

    armed = {"on": True}
    orig_put = opt._put_batch

    def poisoned_put(inp, tgt):
        if armed["on"] and opt.state["neval"] == 5:
            armed["on"] = False
            raise RuntimeError("injected executor loss")
        return orig_put(inp, tgt)

    opt._put_batch = poisoned_put
    opt.optimize()

    assert not armed["on"], "failure was never injected"
    # resumed counters continued correctly (3 epochs * 4 iters + 1)
    assert opt.state["neval"] == 13, opt.state
    for a, b in zip(m.get_weights(), m_ref.get_weights()):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_metrics_logged_per_epoch(caplog):
    """VERDICT r2 #7: metrics.summary() phase averages must appear in
    the training log each epoch, with the reference's metric names."""
    import logging

    x, y = _toy(128)
    model = _model()
    opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learningrate=0.1))
    opt.set_end_when(Trigger.max_epoch(1))
    with caplog.at_level(logging.INFO, logger="bigdl_tpu.optim"):
        opt.optimize()
    lines = [r.message for r in caplog.records if r.message.startswith("Metrics:")]
    assert lines, "no Metrics summary line logged"
    assert "computing time average" in lines[-1]
    assert "data wait time average" in lines[-1]


def test_hierarchical_data_axes_multislice():
    """Multi-slice seam: data parallelism over a 2-level ('dcn','ici')
    mesh — batch and ZeRO shards split over BOTH axes, XLA free to build
    the hierarchical collective.  Must converge like the flat 8-way run."""
    x, y = _toy(n=256, seed=5)
    flat_losses, hier_losses = [], []
    from bigdl_tpu.common import RandomGenerator

    for mode in ("flat", "hier"):
        RandomGenerator.RNG.set_seed(11)
        model = _model()
        if mode == "flat":
            mesh = Engine.build_mesh({"data": 8})
            opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(),
                                  batch_size=64, mesh=mesh,
                                  wire_dtype="none")
        else:
            mesh = Engine.build_mesh({"dcn": 2, "ici": 4})
            opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(),
                                  batch_size=64, mesh=mesh,
                                  wire_dtype="none",
                                  data_axes=("dcn", "ici"))
        opt.set_optim_method(SGD(learningrate=0.5, momentum=0.9))
        opt.set_end_when(Trigger.max_epoch(4))
        tape = _LossTape()
        opt.set_train_summary(tape)
        opt.optimize()
        (flat_losses if mode == "flat" else hier_losses).extend(tape.losses)
        if mode == "hier":
            vel = opt.optim_method.state["velocity"]
            spec = vel.sharding.spec
            flat_axes = []
            for entry in spec:
                if isinstance(entry, (tuple, list)):
                    flat_axes.extend(entry)
                elif entry:
                    flat_axes.append(entry)
            assert set(flat_axes) == {"dcn", "ici"}, spec
    # same data order (shared seeded RNG), same math to fp tolerance
    np.testing.assert_allclose(flat_losses, hier_losses,
                               rtol=2e-4, atol=2e-5)


def test_freeze_and_parameters_table():
    """Reference module.freeze / getParametersTable: frozen subtrees
    take zero updates (incl. no weight-decay drift) under BOTH
    optimizers; unfreeze resumes learning."""
    from bigdl_tpu.optim.regularizer import L2Regularizer

    x, y = _toy(n=128, seed=6)

    def build():
        from bigdl_tpu.common import RandomGenerator

        RandomGenerator.RNG.set_seed(21)
        m = Sequential() \
            .add(Linear(16, 32, w_regularizer=L2Regularizer(1e-2))
                 .set_name("stem")) \
            .add(ReLU()) \
            .add(Linear(32, 4).set_name("head")) \
            .add(LogSoftMax())
        return m

    for cls in (LocalOptimizer, DistriOptimizer):
        model = build()
        model.freeze("stem")
        w_before = np.asarray(model.modules[0].weight).copy()
        h_before = np.asarray(model.modules[2].weight).copy()
        opt = cls(model, (x, y), ClassNLLCriterion(), batch_size=32)
        opt.set_optim_method(SGD(learningrate=0.5))
        opt.set_end_when(Trigger.max_epoch(2))
        opt.optimize()
        np.testing.assert_array_equal(
            np.asarray(model.modules[0].weight), w_before,
            err_msg=f"{cls.__name__} moved frozen weights")
        assert not np.allclose(np.asarray(model.modules[2].weight),
                               h_before), f"{cls.__name__} head frozen too"

        model.unfreeze("stem")
        opt2 = cls(model, (x, y), ClassNLLCriterion(), batch_size=32)
        opt2.set_optim_method(SGD(learningrate=0.5))
        opt2.set_end_when(Trigger.max_epoch(1))
        opt2.optimize()
        assert not np.allclose(np.asarray(model.modules[0].weight),
                               w_before), f"{cls.__name__} unfreeze inert"

    table = build().get_parameters_table()
    assert "stem" in table and "head" in table
    assert set(table["stem"]) == {"weight", "bias"}


def test_freeze_survives_optimizer_weight_decay():
    """Freeze must hold against optimizer-INTERNAL weight decay (wd*p
    added past the zeroed gradient) in both optimizers."""
    x, y = _toy(n=64, seed=8)
    from bigdl_tpu.common import RandomGenerator

    for cls in (LocalOptimizer, DistriOptimizer):
        RandomGenerator.RNG.set_seed(23)
        model = Sequential() \
            .add(Linear(16, 8).set_name("stem")) \
            .add(ReLU()).add(Linear(8, 4)).add(LogSoftMax())
        model.freeze("stem")
        w_before = np.asarray(model.modules[0].weight).copy()
        opt = cls(model, (x, y), ClassNLLCriterion(), batch_size=32)
        opt.set_optim_method(SGD(learningrate=0.5, weightdecay=1e-2))
        opt.set_end_when(Trigger.max_epoch(2))
        opt.optimize()
        np.testing.assert_array_equal(
            np.asarray(model.modules[0].weight), w_before,
            err_msg=f"{cls.__name__}: weight decay moved frozen weights")


def test_int8_blockwise_reduce_scatter_matches_exact():
    """Unit spec for the quantized wire: the staged-ring int8 exchange
    (parallel/wire.py) reproduces psum_scatter within the per-hop
    quantization bound.  The partial for chunk ``c`` is quantized once
    per hop; at hop ``h`` it holds peers ``c+1..c+h``, so each hop's
    element error is bounded by that running partial's blockmax/254 —
    the bound is the triangular cumsum of peer blockmaxes, not the old
    quantize-once sum."""
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.optim.distri_optimizer import (
        _shard_map,
        int8_blockwise_reduce_scatter,
    )

    mesh = Engine.mesh()
    n, block = 8, 64
    L = n * block * 3  # 3 blocks per shard
    rs = np.random.RandomState(0)
    # heavy-tailed gradients: mix of tiny and large magnitudes
    g_all = (rs.randn(n, L) * np.exp(rs.randn(n, L))).astype(np.float32)

    def quantized(gl):
        return int8_blockwise_reduce_scatter(gl[0], "data", n, block)[None]

    def exact(gl):
        return jax.lax.psum_scatter(
            gl[0], "data", scatter_dimension=0, tiled=True)[None]

    sm = lambda f: _shard_map(f, mesh=mesh, in_specs=(P("data", None),),
                              out_specs=P("data", None))
    got = np.asarray(sm(quantized)(jnp.asarray(g_all))).reshape(-1)
    want = np.asarray(sm(exact)(jnp.asarray(g_all))).reshape(-1)

    # blockmax[p, c, b]: device p's max |g| in block b of chunk c
    bm = np.abs(g_all.reshape(n, n, -1, block)).max(-1)
    # hop h of chunk c quantizes the partial over peers c+1..c+h:
    # error <= partial blockmax / 254 <= cumsum of peer blockmaxes/254
    bound = np.zeros_like(bm[0])  # (n_chunks, nblocks)
    for c in range(n):
        run = np.zeros_like(bm[0, 0])
        for h in range(1, n):
            run = run + bm[(c + h) % n, c]
            bound[c] += run / 254.0
    # 1% headroom: earlier hops' errors enter later partials' amax
    bound = bound * 1.01 + 1e-6
    err = np.abs(got - want).reshape(bound.shape + (block,))
    assert np.all(err <= bound[..., None]), (err.max(), bound.min())
    # and close in aggregate — per-hop staging compounds ~n/2 vs the
    # quantize-once shape on this deliberately heavy-tailed data; the
    # error-feedback residual is what cancels it across steps
    # (tests/test_wire.py TestErrorFeedback)
    rel = np.abs(got - want).mean() / (np.abs(want).mean() + 1e-9)
    assert rel < 0.15, rel


def test_distri_int8_wire_converges_and_tracks_exact():
    """End-to-end: training under the int8 wire reaches the same
    accuracy as the uncompressed wire and its loss trajectory stays
    close — the FP16CompressedTensor parity claim at int8."""
    x, y = _toy()

    losses = {}
    for wire in ("none", "int8"):
        model = _model()
        opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(),
                              batch_size=64, wire_dtype=wire,
                              int8_block=128)
        opt.set_optim_method(SGD(learningrate=0.5))
        opt.set_end_when(Trigger.max_epoch(6))
        trained = opt.optimize()
        losses[wire] = opt.state["loss"]
        (acc,) = evaluate_dataset(trained, ArrayDataSet(x, y, 64),
                                  [Top1Accuracy()])
        value, _ = acc.result()
        assert value > 0.95, f"{wire} wire accuracy {value}"
    assert abs(losses["int8"] - losses["none"]) < 0.15, losses


def test_int8_wire_pads_to_block_multiple():
    """A parameter count far from a block multiple still shards: the
    pad rounds the flat vector up to n*block."""
    x, y = _toy(d=13, k=3)
    model = Sequential().add(Linear(13, 7)).add(ReLU()) \
        .add(Linear(7, 3)).add(LogSoftMax())
    opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(),
                          batch_size=64, wire_dtype="int8",
                          int8_block=32)
    opt.set_optim_method(SGD(learningrate=0.5))
    opt.set_end_when(Trigger.max_epoch(2))
    opt.optimize()
    n_params = sum(int(np.size(p)) for p in jax.tree.leaves(model.params()))
    assert (n_params + opt._pad) % (8 * 32) == 0


def test_wire_dtype_validation():
    x, y = _toy(64)
    with pytest.raises(ValueError, match="wire_dtype"):
        DistriOptimizer(_model(), (x, y), ClassNLLCriterion(),
                        batch_size=64, wire_dtype="fp16")
    with pytest.raises(ValueError, match="int8_block"):
        DistriOptimizer(_model(), (x, y), ClassNLLCriterion(),
                        batch_size=64, wire_dtype="int8", int8_block=0)


def test_int8_wire_with_ragged_masked_batches(caplog):
    """Combination seam: the quantized exchange under the MASKED final
    -batch step (pad + masked-mean) — both features at once."""
    import logging

    x, y = _toy(n=166)  # ragged tail: 38 -> padded to 40
    model = _model()
    ds = _RaggedDataSet(x, y, 64)
    opt = DistriOptimizer(model, ds, ClassNLLCriterion(), batch_size=64,
                          wire_dtype="int8", int8_block=64)
    opt.set_optim_method(SGD(learningrate=0.5))
    opt.set_end_when(Trigger.max_epoch(6))
    with caplog.at_level(logging.INFO, logger="bigdl_tpu.optim"):
        trained = opt.optimize()
    assert any("padding with" in r.message for r in caplog.records)
    (acc,) = evaluate_dataset(trained, ArrayDataSet(x, y, 64),
                              [Top1Accuracy()])
    assert acc.result()[0] > 0.85, acc.result()


def test_background_checkpoint_with_distri_retry(tmp_path):
    """Combination seam: background checkpoint writes + the
    retry-from-checkpoint path — the retry must see complete files."""
    x, y = _toy(256)
    model = _model()
    opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(),
                          batch_size=64)
    opt.set_optim_method(SGD(learningrate=0.5))
    opt.set_end_when(Trigger.max_epoch(4))
    opt.set_checkpoint(str(tmp_path), Trigger.every_epoch(),
                       background=True)
    opt.max_retry = 1

    # inject one failure after epoch 2's checkpoint: monkeypatch the
    # step dispatcher to throw once
    orig_build = opt._build_train_step
    calls = {"n": 0, "failed": False}

    def flaky_build():
        dispatch = orig_build()

        def wrapper(*a, **k):
            calls["n"] += 1
            if calls["n"] == 10 and not calls["failed"]:
                calls["failed"] = True
                raise RuntimeError("injected executor loss")
            return dispatch(*a, **k)

        return wrapper

    opt._build_train_step = flaky_build
    trained = opt.optimize()  # retries from the background checkpoint
    assert calls["failed"]
    (acc,) = evaluate_dataset(trained, ArrayDataSet(x, y, 64),
                              [Top1Accuracy()])
    assert acc.result()[0] > 0.9, acc.result()


# ---------------------------------------------- overlapped step (ISSUE 11)
def _seeded_model(seed=7):
    from bigdl_tpu.common import RandomGenerator

    RandomGenerator.RNG.set_seed(seed)
    return _model()


def _small_mesh(n):
    return Engine.build_mesh({"data": n}, devices=jax.devices()[:n])


def _overlap_run(**kw):
    x, y = _toy(128)
    opt = DistriOptimizer(_seeded_model(), ArrayDataSet(x, y, 32,
                                                        shuffle=False),
                          ClassNLLCriterion(), batch_size=32,
                          mesh=_small_mesh(2), **kw)
    opt.set_optim_method(SGD(learningrate=0.5, momentum=0.9))
    opt.set_end_when(Trigger.max_epoch(2))

    class Tape:
        loss: dict = {}

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

    tape = Tape()
    opt.set_train_summary(tape)
    opt.optimize()
    return tape.loss, opt


def test_bucketed_exchange_matches_monolithic_trajectory():
    """ISSUE 11 tentpole: splitting the f32 gradient exchange into
    last-layer-first buckets changes WHEN bytes move, not the math —
    the per-step loss trajectory matches the monolithic exchange."""
    base, mono = _overlap_run(wire_dtype="none")
    over, bopt = _overlap_run(wire_dtype="none", overlap_bucket_mb=0.0005)
    assert len(bopt._buckets) > 1, bopt._buckets
    assert mono._buckets == [(0, mono._flat_elems + mono._pad)]
    worst = max(abs(over[s] - base[s]) / (abs(base[s]) + 1e-9)
                for s in base)
    assert worst < 1e-5, worst
    # the shard-major layout is recorded for the resize path
    topo = bopt._topology()
    assert topo["buckets"] == [[s, z] for s, z in bopt._buckets]
    assert "buckets" not in mono._topology()


def test_bucketed_wire_bytes_match_monolithic_golden():
    """Golden byte-count parity: the bucketed int8 staged ring ships
    EXACTLY the monolithic wire's bytes (payload and scales) — overlap
    is free on the wire."""
    from bigdl_tpu import obs
    from bigdl_tpu.obs import collectives as C

    def ring_bytes():
        fam = obs.get_registry().counter(
            "bigdl_collective_bytes_total", labels=("op", "dtype"))
        return {d: fam.labels(op="ring_rs", dtype=d).value
                for d in ("int8", "float32")}

    obs.reset()
    _, mono = _overlap_run(wire_dtype="int8", wire_block=64)
    mono_bytes = ring_bytes()
    obs.reset()
    _, bopt = _overlap_run(wire_dtype="int8", wire_block=64,
                           overlap_bucket_mb=0.001)
    over_bytes = ring_bytes()
    assert len(bopt._buckets) > 1
    assert over_bytes == mono_bytes and mono_bytes["int8"] > 0
    # and both match the static model exactly
    padded = mono._flat_elems + mono._pad
    model = C.staged_ring_exchange_bytes(padded, 2, 64, "int8")
    steps = 8  # 2 epochs x 128/32 batches over the 2-shard mesh
    assert mono_bytes["int8"] == model["int8"] * steps
    assert mono_bytes["float32"] >= model["float32"] * steps


def test_exposed_comm_gauges_published_with_buckets():
    """Satellite: the overlap gauges say how much of the wire stays
    exposed — 1/K of the exchange with K buckets (plus the serialized
    gathers), and nothing is published for monolithic runs."""
    from bigdl_tpu import obs

    obs.reset()
    _, mono = _overlap_run(wire_dtype="none")
    reg = obs.get_registry()
    assert reg.gauge(
        "bigdl_overlap_buckets", "x").labels().value == 1.0
    obs.reset()
    _, bopt = _overlap_run(wire_dtype="none", overlap_bucket_mb=0.0005)
    reg = obs.get_registry()
    k = len(bopt._buckets)
    assert reg.gauge("bigdl_overlap_buckets", "x").labels().value == float(k)
    frac = reg.gauge("bigdl_overlap_exposed_comm_fraction",
                     "x").labels().value
    assert 0.0 < frac < 1.0, frac
    # exposed = total - hidden exchange share
    fp = bopt._collective_footprint
    exchange = sum(b for op, _d, b in fp.entries if op == "ring_rs"
                   or op == "psum_scatter")
    expected = (fp.total() - exchange * (k - 1) / k) / fp.total()
    assert abs(frac - expected) < 1e-4, (frac, expected)
