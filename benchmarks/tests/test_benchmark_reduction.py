"""The trace reduction on the small recorded trace, and the functions
that count operations and bytes, against hand-worked values."""

import os

import pytest

from benchmarks.lib import flops, peaks, xplane
from benchmarks.tests.helpers import BENCH

SAMPLE = os.path.join(BENCH, "lib", "testdata", "small_trace.json")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce(xplane.load_json(SAMPLE))


def test_sample_trace_busy_share_and_programs(reduced):
    # recorded on one v5e chip by tools/record_trace.py: 12 rounds of two
    # small programs, a 2 ms host sleep after every third round
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(0.015003229, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.00155886, rel=1e-9)
    assert reduced["busy_s"] / reduced["window_s"] == pytest.approx(
        0.1039, abs=1e-4)
    progs = reduced["programs"]
    assert set(progs) == {"jit_small_matmul", "jit_small_copy"}
    assert progs["jit_small_matmul"]["calls"] == 12
    assert xplane.program_ms_per_call(reduced, "small_matmul") == \
        pytest.approx(1e3 * 0.001230722 / 12, rel=1e-9)
    assert xplane.program_ms_per_call(reduced, "absent") is None


def test_sample_trace_operations_and_named_gaps(reduced):
    ops = reduced["ops"]
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"], rel=1e-9)
    assert xplane.top(ops, 1)[0][0] == "fusion"
    gaps = reduced["idle_gaps"]
    assert set(gaps) == {"after_jit_small_matmul_before_jit_small_copy",
                         "after_jit_small_copy_before_jit_small_matmul"}
    # the host's sleeps fall after the copy, so that gap is the long one
    assert gaps["after_jit_small_copy_before_jit_small_matmul"] > \
        4 * 0.002
    assert reduced["busy_s"] + sum(gaps.values()) == pytest.approx(
        reduced["window_s"], rel=0.02)
    assert reduced["collective_exposed_s"] == 0.0


def test_reduction_on_a_hand_made_trace():
    ops = [["%fusion.1 = f32[] fusion()", 0, 10],
           ["%all-reduce.2 = f32[] all-reduce()", 10, 10],
           ["%fusion.3 = f32[] fusion()", 15, 10],   # overlaps 5 of it
           ["%all-gather-done.4 = f32[] all-gather-done()", 40, 10]]
    mods = [["jit_a(1)", 0, 25], ["jit_b(2)", 40, 10]]
    plane = lambda i: {"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": mods}]}
    red = xplane.reduce({"planes": [plane(0), plane(1),
                                    {"name": "/host:CPU", "lines": []}]})
    assert red["chips"] == 2
    assert red["window_s"] == pytest.approx(50e-9)
    assert red["busy_s"] == pytest.approx(35e-9)
    assert red["collective_exposed_s"] == pytest.approx(15e-9)
    assert red["idle_gaps"] == {"after_jit_a_before_jit_b":
                                pytest.approx(15e-9)}
    assert red["ops"]["all-reduce.2"] == pytest.approx(10e-9)
    assert red["programs"]["jit_a"] == {"calls": 1.0,
                                        "seconds": pytest.approx(25e-9)}


def test_resnet50_operations_by_hand():
    # stem: 7x7x3x64 at 112x112; classifier 2048x1000
    stem = 2 * 49 * 3 * 64 * 112 * 112
    fwd = flops.resnet50_forward_flops_per_image(224, 1000)
    assert fwd > stem
    # the published count is about 4.1 G multiply-adds with the stride on
    # the 3x3 (the "v1.5" placement the program uses)
    assert fwd == pytest.approx(2 * 4.09e9, rel=0.02)
    assert flops.resnet50_train_flops_per_image() == 3 * fwd
    # one stage-3 block that is not the first: 1x1 1024->256, 3x3 256,
    # 1x1 256->1024, all at 14x14
    blk = 2 * 196 * (1024 * 256 + 9 * 256 * 256 + 256 * 1024)
    tiny = dict(img=224, classes=1000)
    assert blk == 2 * 196 * 1114112
    assert flops.resnet50_forward_flops_per_image(**tiny) == fwd


def test_gpt2_decode_step_operations_and_bytes_by_hand():
    n_layer, dim, vocab = 48, 1600, 50257
    mats = 48 * 12 * 1600 * 1600 + 50257 * 1600
    assert flops.gpt2_matmul_params(n_layer, dim, vocab) == mats
    assert mats == 1_554_971_200
    f = flops.gpt2_decode_step_flops(n_layer, dim, vocab, batch=32,
                                     context=512)
    assert f == 32 * (2 * mats + 4 * 48 * 512 * 1600)
    b = flops.gpt2_decode_step_bytes(n_layer, dim, vocab, batch=32,
                                     pages=32, page_size=16,
                                     weight_itemsize=2, kv_itemsize=2)
    kv = 2 * 48 * 32 * 32 * 16 * 1600 * 2
    assert b == 2 * mats + kv
    p = peaks.peaks_for("TPU v5 lite")
    least = flops.roofline_seconds(f, b, p)
    assert least == pytest.approx(b / 819e9)      # bandwidth bounds it
    assert least == pytest.approx(0.00994, rel=0.01)


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
