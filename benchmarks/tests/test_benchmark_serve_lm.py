"""The ``serve_lm`` kind of cell: rehearsed on the CPU at a tiny
LongCat-Flash configuration added to a temporary copy as new files and
entries; its eight per-layer readers on synthetic runs; the operation
and byte counts against hand counts; the reference's int8 control."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from benchmarks import run as runner
from benchmarks.lib import flops_latent_moe as f
from benchmarks.lib import hostgaps, peaks, xplane
from benchmarks.tests import helpers

NEW_METRICS = ("decode_device_ms.mla_attn", "decode_device_ms.moe",
               "decode_device_ms.ffn", "mla_attn_roofline",
               "moe_experts_roofline", "latent_moe_decode_step_roofline",
               "moe_expert_load_max_over_mean", "moe_zero_share")


def real_config() -> dict:
    return runner.load_json(os.path.join(
        helpers.BENCH, "configs", "longcat_flash_chat.json"))


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``helpers.make_copy`` and, on top, the tiny LongCat cell: one
    configuration file and entries, nothing edited."""
    copy = helpers.make_copy(str(tmp_path_factory.mktemp("bench_lm")))
    shutil.copy(os.path.join(helpers.DATA, "tiny_longcat.json"),
                os.path.join(copy, "benchmarks", "configs"))
    path = os.path.join(copy, "BENCHMARK.json")
    bench = runner.load_json(path)
    bench["configs"].append(
        {"name": "tiny_longcat", "source": "tests", "reduced": [],
         "why": "test", "file": "benchmarks/configs/tiny_longcat.json"})
    bench["workloads"].append(
        {"name": "tiny_lm", "config": "tiny_longcat",
         "traffic": "tiny_closed4", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "longcat_flash_long_gen" in m.get("workloads", ()):
            m["workloads"].append("tiny_lm")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(bench, fh, indent=1)
    return copy


def test_the_declared_cell_is_data_beside_the_others():
    bench = runner.load_json(os.path.join(helpers.REPO, "BENCHMARK.json"))
    cell, config, mix = runner.load_cell(bench, "longcat_flash_long_gen")
    assert cell["chips"] == 1 and config["kind"] == "serve_lm"
    assert (mix["clients"], mix["requests"], mix["check_requests"]) == \
        (128, 256, 4)
    assert config["engine"]["max_batch"] == mix["clients"]
    # no context passes the engine's longest
    assert mix["prompt_len"][1] + mix["new_tokens"][1] <= config["max_len"]
    declared = {m["name"] for m in bench["per_layer"]
                if runner.applies(m, "longcat_flash_long_gen")}
    assert set(NEW_METRICS) <= declared
    assert "decode_step_roofline" not in declared   # it counts GPT-2's bytes
    for name in declared:
        assert callable(runner.metric_reader(name))
    # every published number under its key; the three cuts named
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (config["hidden_size"], config["moe_topk"],
            config["zero_expert_num"], config["router_experts"]) == \
        (6144, 12, 256, 512)
    assert config["held_experts"] == [0, config["n_routed_experts"]]


def test_the_tiny_cell_runs_through_the_programs_constructor(copy):
    rc, result, out = helpers.rehearse(copy, "tiny_lm", seed=2**31 + 91,
                                       seconds=2.0)
    assert rc == 0, out
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    assert result["device"]["platform"] == "cpu"
    assert "check served_gap_mean" in out
    assert "compiled inside the window" not in out


def test_a_program_without_the_model_fails_at_once(copy, tmp_path):
    """What the parent commit does with this cell: the driver imports
    the model first of all, and a program that lacks it ends the run
    with an ImportError before a weight is made."""
    cfg_path = os.path.join(copy, "benchmarks", "configs",
                            "tiny_longcat.json")
    saved = open(cfg_path, encoding="utf-8").read()
    cfg = json.loads(saved)
    cfg["model"]["module"] = "bigdl_tpu.models.not_in_this_program"
    try:
        with open(cfg_path, "w", encoding="utf-8") as fh:
            json.dump(cfg, fh)
        rc, result, out = helpers.rehearse(copy, "tiny_lm", seconds=1.0)
    finally:
        with open(cfg_path, "w", encoding="utf-8") as fh:
            fh.write(saved)
    assert rc != 0 and result is None
    assert "ModuleNotFoundError" in out
    assert "weights on the device" not in out


# ----------------------------------------------------------- hand counts
def test_operation_and_byte_counts_against_hand_counts():
    cfg = real_config()
    # one latent attention: q_a, q_b, kv_a, kv_b, o
    assert f.attention_params(cfg) == (
        6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
        + 64 * 128 * 6144) == 90570752
    assert f.kv_up_params(cfg) == 512 * 64 * 256
    assert f.router_outputs(cfg) == 768
    assert f.dense_layer_params(cfg) == (
        2 * 90570752 + 2 * 3 * 6144 * 12288 + 6144 * 768) == 638844928
    assert f.expert_params(cfg) == 3 * 6144 * 2048 == 37748736
    assert f.head_params(cfg) == 16384 * 6144
    assert f.row_values(cfg) == 576 and f.cached_attentions(cfg) == 8
    ctx = 128 * 1500.0
    assert f.mla_attn_bytes(cfg, ctx, 2) == \
        8 * 2 * (ctx * 576 + 512 * 64 * 256)
    assert f.mla_attn_flops(cfg, ctx, 128) == 8 * (
        2 * 64 * (576 + 512) * ctx + 2 * 512 * 64 * 256 * 128)
    assert f.moe_experts_bytes(cfg, 56, 2) == 56 * 37748736 * 2
    assert f.moe_experts_flops(cfg, 130) == 2 * 37748736 * 130
    # the whole step: ISSUE 26's 5.11 GB of dense matrices, 0.20 of head
    dense = 4 * 638844928 * 2
    assert 5.10e9 < dense < 5.12e9
    assert f.decode_step_bytes(cfg, ctx, 56, 2) == pytest.approx(
        dense + 16384 * 6144 * 2 + 8 * ctx * 576 * 2 + 56 * 37748736 * 2)
    flops = f.decode_step_flops(cfg, 128, ctx, 130)
    assert 0.85e12 < flops < 1.0e12      # ISSUE 26: about 0.95 TFLOP
    v5e = peaks.peaks_for("TPU v5 lite")
    assert f.roofline_seconds(1e12, 8.19e9, v5e) == pytest.approx(0.01)


# ------------------------------------------------------ synthetic runs
def _run(spans, **kw):
    base = dict(config=real_config(), spans=spans, trace={"programs": {}},
                counters={"batch": 128, "weight_itemsize": 2,
                          "kv_itemsize": 2},
                peaks=peaks.peaks_for("TPU v5 lite"), extra={})
    base.update(kw)
    return types.SimpleNamespace(**base)


def _step(held, zero, absent, hit, max_load, ctx):
    return {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.02,
            "attrs": {"bucket": 128, "active": 128, "moe_held": held,
                      "moe_zero": zero, "moe_absent": absent,
                      "moe_hit": hit, "moe_max_load": max_load,
                      "context_tokens": ctx}}


def test_readers_return_nothing_on_a_program_without_the_counts():
    """The parent's spans carry no routing counts and its trace no such
    scopes: every new reader returns None and raises nothing."""
    old = {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.01,
           "attrs": {"bucket": 32, "active": 12}}
    run = _run([old])
    for name in NEW_METRICS:
        assert runner.metric_reader(name)(run) is None, name
    for name in NEW_METRICS:
        assert runner.metric_reader(name)(_run([])) is None, name


def test_counter_readers_on_a_synthetic_window():
    spans = [_step(128, 512, 896, 56, 4, 190000),
             _step(64, 576, 896, 40, 8, 191000),
             _step(0, 640, 896, 0, 0, 192000)]
    run = _run(spans)
    share = runner.metric_reader("moe_zero_share")(run)
    assert share == pytest.approx(100.0 * (512 + 576 + 640) / (3 * 1536))
    ratio = runner.metric_reader("moe_expert_load_max_over_mean")(run)
    # mean load = held / (4 layers x 16 experts); steps with no held
    # assignment have no ratio
    assert ratio == pytest.approx((4 * 64 / 128 + 8 * 64 / 64) / 2)


def test_roofline_readers_divide_the_least_time_by_the_scope(monkeypatch):
    spans = [_step(128, 512, 896, 56, 4, 190000),
             _step(130, 510, 896, 58, 5, 192000)]
    run = _run(spans)
    cfg, v5e = run.config, run.peaks
    # the grouped products are the compiler's own kernels, named
    # without a scope: counted with the expert layer
    times = {"mla.attn": 4.0, "moe.experts": 0.5, "moe.route": 0.5,
             "moe.zero": 0.25, "ffn": 5.0, "ragged-dot-none": 5.75,
             "ragged-dot-none:": 0.0, "ragged-dot-metadata": 0.25,
             "ragged-dot-metadata:": 0.0}
    monkeypatch.setattr(
        hostgaps, "scope_ms_per_call",
        lambda r, program, scopes, scope: times[scope]
        if program == "jit_step" and scopes == f.SCOPES else None)
    monkeypatch.setattr(xplane, "program_ms_per_call",
                        lambda trace, program: 20.0)
    read = runner.metric_reader
    assert read("decode_device_ms.mla_attn")(run) == 4.0
    assert read("decode_device_ms.ffn")(run) == 5.0
    assert read("decode_device_ms.moe")(run) == 7.25
    attn = np.mean([f.mla_attn_bytes(cfg, c, 2) for c in (190000, 192000)]) \
        / v5e["hbm_bytes_per_s"]
    assert read("mla_attn_roofline")(run) == pytest.approx(
        100 * 1e3 * attn / 4.0)
    moe = np.mean([f.moe_experts_bytes(cfg, h, 2) for h in (56, 58)]) \
        / v5e["hbm_bytes_per_s"]
    assert read("moe_experts_roofline")(run) == pytest.approx(
        100 * 1e3 * moe / 6.5)
    step = np.mean([f.decode_step_bytes(cfg, c, h, 2)
                    for c, h in ((190000, 56), (192000, 58))]) \
        / v5e["hbm_bytes_per_s"]
    got = read("latent_moe_decode_step_roofline")(run)
    assert got == pytest.approx(100 * 1e3 * step / 20.0)
    assert 60.0 < got < 80.0      # 14 ms of reads in a 20 ms step


# --------------------------------------------- the reference's control
def test_the_int8_control_separates_from_float32():
    import jax.numpy as jnp

    from benchmarks.reference import longcat_flash_chat as ref

    cfg = runner.load_json(os.path.join(helpers.DATA, "tiny_longcat.json"))
    sizes = ref.sizes_of(cfg)
    assert sizes["held"] == (4, 8) and sizes["n_routed"] == 16
    params = ref.init_params(2**31 + 5, sizes, jnp.float32)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 96, size=9)
    # the float32 reference's own greedy continuation scores 0 against
    # itself; what int8 puts first lies measurably below the best
    served = []
    for _ in range(12):
        logits = ref.forward_logits(params, sizes,
                                    list(prompt) + served)
        served.append(int(np.argmax(np.asarray(logits[-1]))))
    gaps, first = ref.served_gaps(params, sizes, prompt, served)
    assert np.all(gaps == 0.0) and list(first) == served
    _, first8 = ref.served_gaps(params, sizes, prompt, served, "int8")
    ctl, _ = ref.served_gaps(params, sizes, prompt, served, "float32",
                             score=first8)
    l8 = np.asarray(ref.forward_logits(params, sizes,
                                       list(prompt) + served, "int8"))
    l32 = np.asarray(ref.forward_logits(params, sizes,
                                        list(prompt) + served))
    assert np.max(np.abs(l8 - l32)) > 1e-2
    assert np.all(ctl >= 0.0)
