"""Every per-layer metric's reader, on a hand-made run: each finds its
number where there is something to read and returns nothing otherwise."""

import json
import os

import pytest

from benchmarks import run as runner
from benchmarks.lib import peaks, xplane
from benchmarks.tests.helpers import BENCH, REPO


def make_run(**over):
    trace = {
        "window_s": 5.0, "busy_s": 4.5, "chips": 1, "ops": {},
        "idle_gaps": {}, "collective_exposed_s": 0.3,
        "programs": {"jit_step": {"calls": 100.0, "seconds": 5.0},
                     "jit_prefill": {"calls": 10.0, "seconds": 1.0},
                     "jit_train_step": {"calls": 50.0, "seconds": 2.0},
                     "jit_sharded_step": {"calls": 30.0, "seconds": 3.0}}}
    spans = ([{"name": "serve.decode_step", "start": 0, "dur_s": 0.05,
               "attrs": {"bucket": 32}}] * 3
             + [{"name": "step_dispatch", "start": 0, "dur_s": 0.001,
                 "attrs": {}}] * 4
             + [{"name": "batch_prep", "start": 0, "dur_s": 0.002,
                 "attrs": {}}] * 4
             + [{"name": "device_put", "start": 0, "dur_s": 0.006,
                 "attrs": {}}] * 4)
    kw = dict(
        cell={}, traffic={}, chips=1, device_kind="TPU v5 lite",
        peaks=peaks.peaks_for("TPU v5 lite"), seconds=5.0, window_s=5.0,
        config={"kind": "serve", "image_size": 224, "num_classes": 1000},
        e2e={"train_samples_per_s": 2600.0}, spans=spans, trace=trace,
        counters={"window_compiles": 0, "steps": 100, "occupancy_sum": 95.0,
                  "loss_stamps": [0.0, 0.05, 0.10, 0.16], "batch": 32,
                  "page_size": 16, "weight_itemsize": 2, "kv_itemsize": 2},
        extra={"sizes": {"n_layer": 48, "dim": 1600, "vocab": 50257,
                         "mlp_ratio": 4}})
    kw.update(over)
    return runner.Run(**kw)


def names():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return [m["name"] for m in json.load(fh)["per_layer"]]


def test_every_declared_metric_has_a_reader_file():
    for name in names():
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))


@pytest.mark.parametrize("name,want", [
    ("train_step_wall_ms", 50.0),
    ("feed_ms_per_step", 8.0),
    ("serve_batch_occupancy", 95.0),
    ("decode_step_wall_ms", 50.0),
    ("decode_step_device_ms", 50.0),
    ("prefill_device_ms", 100.0),
    ("train_step_device_ms", 1e3 * 5.0 / 80.0),
    ("collective_exposed_ms", 10.0),
    ("window_compiles.serve", 0.0),
])
def test_reader_values(name, want):
    assert runner.metric_reader(name)(make_run()) == pytest.approx(want)


def test_roofline_and_mfu_by_hand():
    run = make_run()
    # one step at bucket 32: 2 * 1_554_971_200 B of weights + 5.03 GB of
    # K and V over 819 GB/s = 9.94 ms, of a 50 ms step
    assert runner.metric_reader("decode_step_roofline")(run) == \
        pytest.approx(100 * 9.94 / 50.0, rel=0.01)
    mfu = runner.metric_reader("train_mfu")(run)
    assert mfu == pytest.approx(100 * 3 * 2 * 4.09e9 * 2600 / 197e12,
                                rel=0.02)
    assert mfu < 100


def test_readers_return_nothing_where_there_is_nothing_to_read():
    empty = make_run(spans=[], counters={"window_compiles": 0},
                     e2e={}, trace=dict(make_run().trace, programs={}))
    for name in names():
        if name.startswith("window_compiles"):
            continue
        assert runner.metric_reader(name)(empty) is None, name
    assert runner.metric_reader("window_compiles.train")(empty) is None
    assert xplane.program_ms_per_call(empty.trace, "step") is None
