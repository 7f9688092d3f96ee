"""The ``serve_lm`` kind of cell under a model whose slots carry state
(``zaya1_cca_long_gen``): rehearsed on the CPU at a tiny ZAYA
configuration added to a temporary copy as new files and entries (it
serves in float32, so its limits catch a program that hands no state
from the prefill to the decode step); the five new readers on synthetic
runs (and reading nothing where nothing is); the operation and byte
counts against hand counts at the published widths."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from benchmarks import run as runner
from benchmarks.lib import flops_cca_moe as f
from benchmarks.lib.flops import roofline_seconds
from benchmarks.lib import hostgaps, peaks, xplane
from benchmarks.tests import helpers

CELL = "zaya1_cca_long_gen"
NEW_METRICS = ("decode_device_ms.cca_attn", "decode_device_ms.cca_mix",
               "cca_attn_roofline", "top1_moe_experts_roofline",
               "cca_step_roofline")
DROP_STATE = """
from bigdl_tpu.serving import cache, engine
engine.write_slot_state = lambda state, slot, rows: state
"""


def real_config() -> dict:
    return runner.load_json(os.path.join(
        helpers.BENCH, "configs", "zaya1_8b.json"))


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``helpers.make_copy`` and, on top, the tiny cell: one
    configuration file and entries, nothing edited."""
    copy = helpers.make_copy(str(tmp_path_factory.mktemp("bench_state")))
    shutil.copy(os.path.join(helpers.DATA, "tiny_zaya.json"),
                os.path.join(copy, "benchmarks", "configs"))
    path = os.path.join(copy, "BENCHMARK.json")
    bench = runner.load_json(path)
    bench["configs"].append(
        {"name": "tiny_zaya", "source": "tests", "reduced": [],
         "why": "test", "file": "benchmarks/configs/tiny_zaya.json"})
    bench["workloads"].append(
        {"name": "tiny_state", "config": "tiny_zaya",
         "traffic": "tiny_closed4", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_state")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(bench, fh, indent=1)
    return copy


def test_the_declared_cell_is_data_beside_the_others():
    bench = runner.load_json(os.path.join(helpers.REPO, "BENCHMARK.json"))
    cell, config, mix = runner.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["kind"] == "serve_lm"
    assert cell["traffic"] == "long_gen_closed256"
    assert config["engine"] == {"max_batch": 256, "page_size": 16}
    assert mix["clients"] == 256 and mix["check_requests"] == 4
    assert mix["prompt_len"][1] + mix["new_tokens"][1] <= config["max_len"]
    declared = {m["name"] for m in bench["per_layer"]
                if runner.applies(m, CELL)}
    assert set(NEW_METRICS) <= declared
    # every generic serving metric cell 4 reports, and its expert time
    longcat = {m["name"] for m in bench["per_layer"]
               if runner.applies(m, "longcat_flash_long_gen")}
    assert declared - set(NEW_METRICS) == longcat - {
        "decode_device_ms.mla_attn", "decode_device_ms.ffn",
        "mla_attn_roofline", "moe_experts_roofline",
        "latent_moe_decode_step_roofline", "moe_expert_load_max_over_mean",
        "moe_zero_share"}
    for name in declared:
        assert callable(runner.metric_reader(name))
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 5] == list(NEW_METRICS)
    assert at > names.index("idle_ms_per_step.read")
    assert {m["name"] for m in bench["end_to_end"]
            if runner.applies(m, CELL)} == {
        "serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    # every published number under its key; the cut is depth alone
    entry = next(c for c in bench["configs"] if c["name"] == "zaya1_8b")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types"]
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["num_experts"], config["num_experts_per_tok"],
            config["moe_intermediate_size"], config["router_hidden_size"],
            config["vocab_size"], config["cca_time0"],
            config["cca_time1"]) == (2048, 8, 2, 128, 16, 1, 2048, 256,
                                     262272, 2, 2)
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 10
    assert config["published"]["num_hidden_layers"] == 40
    assert config["held_experts"] == [0, 16]
    assert config["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000,
        "rope_type": "default"}
    for key in ("value_shift", "convolutions", "query_key_mean",
                "norms_and_temperature", "residual_scaling", "router",
                "skip_choice_absent", "serving_dtype", "kv_cache_dtype",
                "max_len"):
        assert key in config["assumed"], key


def test_the_tiny_cell_runs_through_the_programs_constructor(copy):
    rc, result, out = helpers.rehearse(copy, "tiny_state", seed=2**31 + 91,
                                       seconds=2.0)
    assert rc == 0, out
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    assert result["device"]["platform"] == "cpu"
    assert "check served_gap_mean" in out
    assert "compiled inside the window" not in out


def test_a_prefill_that_hands_over_no_state_is_not_correct(copy):
    """The oracle on the served path: with the slot's state left as it
    was at every admission the run ends, and misses a limit."""
    rc, result, out = helpers.rehearse(copy, "tiny_state", seed=2**31 + 92,
                                       seconds=2.0, before=DROP_STATE)
    assert rc == 0, out
    assert result["correct"] is False, out
    assert "FAILED" in out and "check served_gap" in out


def test_a_program_without_the_model_fails_at_once(copy):
    """What the parent commit does with this cell: the driver imports
    the model first of all, and a program that lacks it ends the run
    with an ImportError before a weight is made."""
    cfg_path = os.path.join(copy, "benchmarks", "configs", "tiny_zaya.json")
    saved = open(cfg_path, encoding="utf-8").read()
    cfg = json.loads(saved)
    cfg["model"]["module"] = "bigdl_tpu.models.not_in_this_program"
    try:
        with open(cfg_path, "w", encoding="utf-8") as fh:
            json.dump(cfg, fh)
        rc, result, out = helpers.rehearse(copy, "tiny_state", seconds=1.0)
    finally:
        with open(cfg_path, "w", encoding="utf-8") as fh:
            fh.write(saved)
    assert rc != 0 and result is None
    assert "ModuleNotFoundError" in out
    assert "weights on the device" not in out


# ----------------------------------------------------------- hand counts
def test_operation_and_byte_counts_against_hand_counts():
    cfg = real_config()
    assert f.layers(cfg) == 10 and f.row_values(cfg) == 256
    assert f.channels(cfg) == 1280
    # W_qk, W_v, W_o and the second convolution's 2 x 10 matrices
    assert f.attention_params(cfg) == (
        1280 * 2048 + 256 * 2048 + 2048 * 1024 + 2 * 10 * 128 * 128) \
        == 5570560
    assert f.router_params(cfg) == 256 * 2048 + 2 * 256 * 256 + 16 * 256 \
        == 659456
    assert f.expert_params(cfg) == 3 * 2048 * 2048 == 12582912
    assert f.head_params(cfg) == 262272 * 2048
    assert f.dense_params(cfg) == 10 * (5570560 + 659456) + 262272 * 2048
    # ISSUE 37: a layer 207.6 M = 415 MB, the weights 5.23 GB
    layer = 5570560 + 659456 + 16 * 12582912
    assert 207.5e6 < layer < 207.7e6
    assert 5.22e9 < 2 * (10 * layer + 262272 * 2048) < 5.24e9
    ctx = 256 * 950.0
    # 1 KB a token and layer in bfloat16
    assert f.attn_bytes(cfg, 1.0, 2) == 10 * 1024
    assert f.attn_bytes(cfg, ctx, 2) == 10 * 2 * ctx * 256 * 2
    assert f.attn_flops(cfg, ctx) == 10 * (2 * 2 * 8 * 128) * ctx
    assert f.experts_bytes(cfg, 160, 2) == 160 * 12582912 * 2
    assert 4.02e9 < f.experts_bytes(cfg, 160, 2) < 4.03e9    # ISSUE: 4.03
    assert f.experts_flops(cfg, 2560) == 2 * 12582912 * 2560
    assert f.step_bytes(cfg, ctx, 160, 2) == pytest.approx(
        2 * f.dense_params(cfg) + 10 * 2 * ctx * 256 * 2
        + 160 * 12582912 * 2)
    # rows 2.5, experts 4.0, the head 1.07 and the layers' 0.12 GB
    assert 7.6e9 < f.step_bytes(cfg, ctx, 160, 2) < 7.8e9
    assert f.step_flops(cfg, 256, ctx, 2560) == pytest.approx(
        2 * f.dense_params(cfg) * 256 + 2 * 12582912 * 2560
        + 10 * 4096 * ctx)
    v5e = peaks.peaks_for("TPU v5 lite")
    # bound by the reads: 9.4 ms against 1.9 ms of multiplications
    assert roofline_seconds(
        f.step_flops(cfg, 256, ctx, 2560), f.step_bytes(cfg, ctx, 160, 2),
        v5e) == pytest.approx(f.step_bytes(cfg, ctx, 160, 2) / 819e9)


# ------------------------------------------------------ synthetic runs
def _run(spans, **kw):
    base = dict(config=real_config(), spans=spans, trace={"programs": {}},
                counters={"batch": 256, "weight_itemsize": 2,
                          "kv_itemsize": 2},
                peaks=peaks.peaks_for("TPU v5 lite"), extra={})
    base.update(kw)
    return types.SimpleNamespace(**base)


def _step(held, hit, max_load, ctx):
    return {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.014,
            "attrs": {"bucket": 128, "active": 256, "moe_held": held,
                      "moe_zero": 0, "moe_absent": 0, "moe_hit": hit,
                      "moe_max_load": max_load, "context_tokens": ctx}}


def test_readers_return_nothing_on_a_program_without_the_counts():
    """A trace without the scopes, spans without the counts, or no spans
    at all: every new reader returns None and raises nothing."""
    old = {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.01,
           "attrs": {"bucket": 32, "active": 12}}
    for run in (_run([old]), _run([]), _run([_step(2560, 150, 60, 2e5)])):
        for name in NEW_METRICS:
            assert runner.metric_reader(name)(run) is None, name


def test_the_readers_on_the_sample_traces_shape():
    """The recorded sample trace (another model's ``jit_step``: scopes of
    its own, none of this model's): the readers find their program and
    nothing to read in it."""
    reduced = xplane.reduce(xplane.load_json(os.path.join(
        helpers.BENCH, "lib", "testdata", "small_trace.json")))
    run = _run([_step(2560, 150, 60, 2e5)], trace=reduced)
    for name in NEW_METRICS[:4]:
        assert runner.metric_reader(name)(run) is None, name
    got = runner.metric_reader("cca_step_roofline")(run)
    ms = xplane.program_ms_per_call(reduced, "step")
    assert (got is None) == (ms is None)


def test_roofline_readers_divide_the_least_time_by_the_scope(monkeypatch):
    spans = [_step(2560, 150, 61, 243000), _step(2560, 148, 58, 243256)]
    run = _run(spans)
    cfg, v5e = run.config, run.peaks
    times = {"cca.attn": 4.0, "cca.mix": 1.5, "moe.experts": 6.0,
             "moe.route": 0.5, "kv_write": 0.25, "dense": 2.0,
             "sample": 0.25}
    monkeypatch.setattr(
        hostgaps, "scope_ms_per_call",
        lambda r, program, scopes, scope: times[scope]
        if program == "jit_step" and scopes == f.SCOPES else None)
    monkeypatch.setattr(xplane, "program_ms_per_call",
                        lambda trace, program: 15.0)
    read = runner.metric_reader
    assert read("decode_device_ms.cca_attn")(run) == 4.0
    assert read("decode_device_ms.cca_mix")(run) == 1.5
    attn = np.mean([f.attn_bytes(cfg, c, 2) for c in (243000, 243256)]) \
        / v5e["hbm_bytes_per_s"]
    assert read("cca_attn_roofline")(run) == pytest.approx(
        100 * 1e3 * attn / 4.0)
    moe = np.mean([f.experts_bytes(cfg, h, 2) for h in (150, 148)]) \
        / v5e["hbm_bytes_per_s"]
    assert read("top1_moe_experts_roofline")(run) == pytest.approx(
        100 * 1e3 * moe / 6.0)
    step = np.mean([f.step_bytes(cfg, c, h, 2)
                    for c, h in ((243000, 150), (243256, 148))]) \
        / v5e["hbm_bytes_per_s"]
    got = read("cca_step_roofline")(run)
    assert got == pytest.approx(100 * 1e3 * step / 15.0)
    assert 55.0 < got < 65.0        # 9.1 ms of reads in a 15 ms step
    for name in ("cca_attn_roofline", "top1_moe_experts_roofline"):
        assert 0.0 < read(name)(run) < 100.0


# --------------------------------------------- the reference's control
def test_the_int8_control_and_every_part_separate_from_float32():
    import jax.numpy as jnp

    from benchmarks.reference import zaya1_8b as ref

    cfg = runner.load_json(os.path.join(helpers.DATA, "tiny_zaya.json"))
    sizes = ref.sizes_of(cfg)
    assert sizes["held"] == (0, 8) and sizes["rot"] == 4
    params = ref.init_params(2**31 + 5, sizes, jnp.float32)
    prompt = np.random.default_rng(3).integers(0, 96, size=9)
    # the float32 reference's own greedy continuation scores 0 against
    # itself; what int8, or the reference with a part left out, puts
    # first lies below the best somewhere
    served = []
    for _ in range(10):
        logits = ref.forward_logits(params, sizes, list(prompt) + served)
        served.append(int(np.argmax(np.asarray(logits[-1]))))
    gaps, first = ref.served_gaps(params, sizes, prompt, served)
    assert np.all(gaps == 0.0) and list(first) == served
    l32 = np.asarray(ref.forward_logits(params, sizes,
                                        list(prompt) + served))
    l8 = np.asarray(ref.forward_logits(params, sizes,
                                       list(prompt) + served, "int8"))
    assert np.max(np.abs(l8 - l32)) > 1e-2
    for part in ref.PARTS:
        g, _ = ref.served_gaps(params, sizes, prompt, served, without=part)
        assert np.all(g >= 0.0), part
        lw = np.asarray(ref.forward_logits(
            params, sizes, list(prompt) + served, without=part,
            boundary=len(prompt)))
        assert np.max(np.abs(lw - l32)) > 1e-2, part
