"""The trainer's feed gathers its batches into a ring of reused host
buffers (ISSUE 27).  On the CPU backend a 64-byte-aligned host array
put on the "device" IS the device array (no copy), so a buffer that went
back to the ring one step early would change the numbers the step reads:
the losses with the ring have to equal, to the last bit, the losses with
a fresh array every batch.  CPU, tiny sizes."""

import json
import threading

import numpy as np
import pytest

from bigdl_tpu import native, obs

BATCH = 64
BATCHES_AN_EPOCH = 6
STEPS = 12
ROW = (3, 64, 64)  # 48 KiB a row, 3 MiB a batch: the pool splits it


def _data(seed=0):
    rng = np.random.RandomState(seed)
    n = BATCH * BATCHES_AN_EPOCH
    x = rng.randn(n, *ROW).astype(np.float32)
    y = rng.randint(1, 5, n).astype(np.float32)
    return x, y


def _model():
    from bigdl_tpu.nn import Linear, LogSoftMax, Reshape, Sequential

    width = int(np.prod(ROW))
    return Sequential().add(Reshape([width])).add(Linear(width, 4)) \
        .add(LogSoftMax())


class _Losses:
    """A train summary that keeps every loss as it arrives."""

    def __init__(self):
        self.losses = []

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            self.losses.append((step, float(value)))

    def get_summary_trigger(self, name):
        return None

    def close(self):
        pass


def _train(kind, fresh_arrays=False, steps=STEPS, optimizer_class=None):
    """STEPS steps over two shuffled epochs; the losses, and the
    optimizer.  ``fresh_arrays``: the dataset offers no ``data_into``,
    so every batch is an array of its own, as before the ring."""
    import jax

    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.dataset.dataset import ArrayDataSet
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.nn import ClassNLLCriterion
    from bigdl_tpu.optim import SGD, DistriOptimizer, LocalOptimizer, Trigger

    RandomGenerator.RNG.set_seed(11)
    x, y = _data()
    ds = ArrayDataSet(x, y, BATCH, shuffle=True)
    if fresh_arrays:
        ds.data_into = None
    model = _model()
    Engine.reset()
    try:
        if kind == "distri4":
            mesh = Engine.build_mesh({"data": 4}, devices=jax.devices()[:4])
            opt = (optimizer_class or DistriOptimizer)(
                model, ds, ClassNLLCriterion(), batch_size=BATCH, mesh=mesh)
        else:
            opt = (optimizer_class or LocalOptimizer)(
                model, ds, ClassNLLCriterion(), batch_size=BATCH)
        opt.set_optim_method(SGD(learningrate=0.05, momentum=0.9))
        rec = _Losses()
        opt.set_train_summary(rec)
        opt.set_end_when(Trigger.max_iteration(steps))
        opt.optimize()
    finally:
        Engine.reset()
    assert [s for s, _ in rec.losses] == list(range(1, steps + 1))
    return [v for _, v in rec.losses], opt


def _bits(losses):
    return np.asarray(losses, np.float64).tobytes()


def _put_aliases_host_memory():
    """Does this backend take an aligned host array without a copy?"""
    import jax.numpy as jnp

    host = native._aligned_empty((8,) + ROW)
    host[:] = 1.0
    dev = jnp.asarray(host)
    host[:] = 2.0
    return float(dev.reshape(-1)[0]) == 2.0


@pytest.mark.parametrize("kind", ["local", "distri4"])
def test_losses_with_the_ring_equal_fresh_arrays_to_the_last_bit(kind):
    fresh, _ = _train(kind, fresh_arrays=True)
    assert len(set(fresh)) == STEPS  # every batch its own loss
    ringed, opt = _train(kind)
    assert _bits(ringed) == _bits(fresh)
    # the ring and its threads ended with optimize()
    assert opt._staging is None
    assert not [t for t in threading.enumerate()
                if t.name.startswith("bigdl-gather")]


def test_a_ring_that_takes_a_batch_back_early_changes_the_losses(
        monkeypatch):
    """The test above has teeth: hand every batch's buffer straight back
    and the steps read rows that were gathered for later batches."""
    if not _put_aliases_host_memory():
        pytest.skip("this backend copies host arrays on the way in")
    fresh, _ = _train("local", fresh_arrays=True)
    gather = native.StagingRing.gather

    def gather_and_give_back(self, src, idx):
        buf = gather(self, src, idx)
        self.release(buf)
        return buf

    monkeypatch.setattr(native.StagingRing, "gather", gather_and_give_back)
    early, _ = _train("local")
    assert _bits(early) != _bits(fresh)


@pytest.fixture
def traced(tmp_path, monkeypatch):
    monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path / "trace"))
    obs.reset()
    yield obs.get_tracer()
    obs.reset()


def _counted(staging):
    for fam in obs.get_registry().families():
        if fam.name == "bigdl_feed_staging_batches_total":
            for key, child in fam.child_items():
                if dict(zip(fam.labelnames, key)) == {"staging": staging}:
                    return child.value
    return 0.0


def test_after_the_first_round_every_gather_is_reused(traced):
    new0, reused0 = _counted("new"), _counted("reused")
    _, _ = _train("local")
    traced.flush()
    with open(traced.jsonl_path, encoding="utf-8") as fh:
        spans = [json.loads(line) for line in fh]
    gathers = sorted((s for s in spans if s["kind"] == "span"
                      and s["name"] == "feed.gather"),
                     key=lambda s: s["attrs"]["step"])
    # the prefetcher may run ahead of the last trained step
    assert [s["attrs"]["step"] for s in gathers][:STEPS] \
        == list(range(1, STEPS + 1))
    depth = native.StagingRing().depth
    kinds = [s["attrs"]["staging"] for s in gathers]
    assert 1 <= kinds.count("new") <= depth
    first_reused = kinds.index("reused")
    assert first_reused <= depth
    assert set(kinds[first_reused:]) == {"reused"}
    for s in gathers:
        assert s["attrs"]["bytes"] == BATCH * int(np.prod(ROW)) * 4
        assert s["attrs"]["threads"] >= 1
    assert _counted("new") - new0 == kinds.count("new")
    assert _counted("reused") - reused0 == kinds.count("reused")


def test_dropped_batches_give_their_buffers_back(monkeypatch):
    from bigdl_tpu.optim import LocalOptimizer

    class DropsEveryOther(LocalOptimizer):
        seen = 0

        def _prepare_batch(self, inp, tgt):
            DropsEveryOther.seen += 1
            return None if DropsEveryOther.seen % 2 == 0 else (inp, tgt)

    lent = []
    gather = native.StagingRing.gather

    def noting(self, src, idx):
        buf = gather(self, src, idx)
        lent.append((buf, self.last["staging"]))
        return buf

    monkeypatch.setattr(native.StagingRing, "gather", noting)
    losses, _ = _train("local", steps=6, optimizer_class=DropsEveryOther)
    assert len(losses) == 6 and DropsEveryOther.seen >= 11
    depth = native.StagingRing().depth
    # eleven batches and more went through at most a ring's worth
    assert len(lent) >= 11
    assert len({id(buf) for buf, _ in lent}) <= depth
    assert [how for _, how in lent].count("new") <= depth
