"""The ``serve_lm`` kind of cell under a model whose slots carry a
state-space state that sums over the whole past
(``falcon_h1_ssm_long_gen``): rehearsed on the CPU at a tiny Falcon-H1
configuration added to a temporary copy as new files and entries (it
serves in float32, so its limits catch a program that hands no state
from the prefill to the decode step); the six new readers on synthetic
runs (and reading nothing where nothing is); the operation and byte
counts against hand counts at the published widths."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from benchmarks import run as runner
from benchmarks.lib import flops_hybrid_ssm as f
from benchmarks.lib.flops import roofline_seconds
from benchmarks.lib import hostgaps, peaks, xplane
from benchmarks.tests import helpers

CELL = "falcon_h1_ssm_long_gen"
NEW_METRICS = ("decode_device_ms.ssm", "decode_device_ms.hybrid_attn",
               "decode_device_ms.hybrid_ffn", "ssm_state_roofline",
               "hybrid_attn_roofline", "hybrid_step_roofline")
DROP_STATE = """
from bigdl_tpu.serving import cache, engine
engine.write_slot_state = lambda state, slot, rows: state
"""


def real_config() -> dict:
    return runner.load_json(os.path.join(
        helpers.BENCH, "configs", "falcon_h1_34b.json"))


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``helpers.make_copy`` and, on top, the tiny cell: one
    configuration file and entries, nothing edited."""
    copy = helpers.make_copy(str(tmp_path_factory.mktemp("bench_hybrid")))
    shutil.copy(os.path.join(helpers.DATA, "tiny_falcon_h1.json"),
                os.path.join(copy, "benchmarks", "configs"))
    path = os.path.join(copy, "BENCHMARK.json")
    bench = runner.load_json(path)
    bench["configs"].append(
        {"name": "tiny_falcon_h1", "source": "tests", "reduced": [],
         "why": "test", "file": "benchmarks/configs/tiny_falcon_h1.json"})
    bench["workloads"].append(
        {"name": "tiny_hybrid", "config": "tiny_falcon_h1",
         "traffic": "tiny_closed4", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_hybrid")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(bench, fh, indent=1)
    return copy


def test_the_declared_cell_is_data_beside_the_others():
    bench = runner.load_json(os.path.join(helpers.REPO, "BENCHMARK.json"))
    cell, config, mix = runner.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["kind"] == "serve_lm"
    assert cell["traffic"] == "long_gen_closed128"
    assert config["engine"] == {"max_batch": 128, "page_size": 16}
    assert mix["clients"] == 128 and mix["check_requests"] == 4
    assert mix["prompt_len"][1] + mix["new_tokens"][1] <= config["max_len"]
    declared = {m["name"] for m in bench["per_layer"]
                if runner.applies(m, CELL)}
    assert set(NEW_METRICS) <= declared
    # every generic serving metric cell 7 reports, less its expert time
    zaya = {m["name"] for m in bench["per_layer"]
            if runner.applies(m, "zaya1_cca_long_gen")}
    assert declared - set(NEW_METRICS) == zaya - {
        "decode_device_ms.moe", "decode_device_ms.cca_attn",
        "decode_device_ms.cca_mix", "cca_attn_roofline",
        "top1_moe_experts_roofline", "cca_step_roofline"}
    for name in declared:
        assert callable(runner.metric_reader(name))
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 6] == list(NEW_METRICS)
    assert at > names.index("cca_step_roofline")
    for m in bench["per_layer"][at:at + 6]:
        assert m["workloads"] == [CELL]
    assert {m["name"] for m in bench["end_to_end"]
            if runner.applies(m, CELL)} == {
        "serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    # every published number under its key; the cut is depth alone
    entry = next(c for c in bench["configs"]
                 if c["name"] == "falcon_h1_34b")
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["vocab_size"],
            config["mamba_d_ssm"], config["mamba_n_heads"],
            config["mamba_d_head"], config["mamba_d_state"],
            config["mamba_n_groups"], config["mamba_d_conv"],
            config["mamba_chunk_size"]) == (
        5120, 21504, 20, 4, 128, 261120, 4096, 32, 128, 256, 2, 4, 128)
    assert config["num_hidden_layers"] == 4
    assert config["published"]["num_hidden_layers"] == 72
    assert config["key_multiplier"] == 0.011048543456039804
    assert len(config["ssm_multipliers"]) == 5
    for key in ("state_dtype", "state_layout", "zones", "convolution",
                "gate_and_norm", "attention", "block", "ends", "weights",
                "from_upstream_code_unverified", "serving_dtype",
                "kv_cache_dtype", "max_len"):
        assert key in config["assumed"], key


def test_the_tiny_cell_runs_through_the_programs_constructor(copy):
    rc, result, out = helpers.rehearse(copy, "tiny_hybrid", seed=2**31 + 91,
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
    was at every admission the run ends, and misses a limit.  (The state
    sums over the whole past: a dropped hand-over spoils every token
    after it, not one position's rows.)"""
    rc, result, out = helpers.rehearse(copy, "tiny_hybrid", seed=2**31 + 92,
                                       seconds=2.0, before=DROP_STATE)
    assert rc == 0, out
    assert result["correct"] is False, out
    assert "FAILED" in out and "check served_gap" in out


def test_a_program_without_the_model_fails_at_once(copy):
    """What the parent commit does with this cell: the driver imports
    the model first of all, and a program that lacks it ends the run
    with an ImportError before a weight is made."""
    cfg_path = os.path.join(copy, "benchmarks", "configs",
                            "tiny_falcon_h1.json")
    saved = open(cfg_path, encoding="utf-8").read()
    cfg = json.loads(saved)
    cfg["model"]["module"] = "bigdl_tpu.models.not_in_this_program"
    try:
        with open(cfg_path, "w", encoding="utf-8") as fh:
            json.dump(cfg, fh)
        rc, result, out = helpers.rehearse(copy, "tiny_hybrid", seconds=1.0)
    finally:
        with open(cfg_path, "w", encoding="utf-8") as fh:
            fh.write(saved)
    assert rc != 0 and result is None
    assert "ModuleNotFoundError" in out
    assert "weights on the device" not in out


# ----------------------------------------------------------- hand counts
def test_operation_and_byte_counts_against_hand_counts():
    cfg = real_config()
    assert f.layers(cfg) == 4 and f.row_values(cfg) == 512
    assert f.conv_channels(cfg) == 5120
    assert f.state_values(cfg) == 32 * 128 * 256
    # ISSUE 39: H 4.19 MB and the kept rows 0.06 MB a slot and layer
    assert f.slot_state_bytes(cfg) == 4 * (32 * 128 * 256 + 3 * 5120) * 4 \
        == 17022976
    assert f.mixer_params(cfg) == 5120 * 9248 + 5120 * 4096
    assert f.attention_params(cfg) == 5120 * (2560 + 512 + 512) \
        + 2560 * 5120
    assert f.mlp_params(cfg) == 3 * 5120 * 21504
    layer = f.mixer_params(cfg) + f.attention_params(cfg) + f.mlp_params(cfg)
    assert 430.0e6 < layer < 430.2e6            # ISSUE: 430.1 M = 860 MB
    assert f.head_params(cfg) == 261120 * 5120
    assert f.matrix_params(cfg) == 4 * layer + 261120 * 5120
    state = 2 * 128 * f.slot_state_bytes(cfg)
    assert 4.3e9 < state < 4.4e9                # ISSUE: 4.3 GB in and out
    assert f.slots_of(cfg, state) == 128
    ctx = 128 * 910.0
    # 2 KB a token and layer in bfloat16
    assert f.attn_bytes(cfg, 1.0, 2) == 4 * 2048
    assert 0.93e9 < f.attn_bytes(cfg, ctx, 2) < 0.96e9
    assert f.attn_flops(cfg, ctx) == 4 * (2 * 2 * 20 * 128) * ctx
    assert f.state_flops(cfg, state) == 128 * 4 * (
        5 * 32 * 128 * 256 + 2 * 4 * 5120)
    assert f.step_bytes(cfg, ctx, state, 2) == pytest.approx(
        state + 2 * f.matrix_params(cfg) + f.attn_bytes(cfg, ctx, 2))
    # ISSUE: 11.3 GB, 38 % of it the state
    assert 11.3e9 < f.step_bytes(cfg, ctx, state, 2) < 11.5e9
    assert 0.37 < state / f.step_bytes(cfg, ctx, state, 2) < 0.39
    assert f.step_flops(cfg, ctx, state) == pytest.approx(
        2 * f.matrix_params(cfg) * 128 + f.state_flops(cfg, state)
        + f.attn_flops(cfg, ctx))
    v5e = peaks.peaks_for("TPU v5 lite")
    # bound by the bytes: 13.9 ms against 4.0 ms of multiplications
    assert roofline_seconds(
        f.step_flops(cfg, ctx, state), f.step_bytes(cfg, ctx, state, 2),
        v5e) == pytest.approx(f.step_bytes(cfg, ctx, state, 2) / 819e9)
    assert roofline_seconds(f.state_flops(cfg, state), state, v5e) \
        == pytest.approx(state / 819e9)


# ------------------------------------------------------ synthetic runs
def _run(spans, **kw):
    base = dict(config=real_config(), spans=spans, trace={"programs": {}},
                counters={"batch": 128, "weight_itemsize": 2,
                          "kv_itemsize": 2},
                peaks=peaks.peaks_for("TPU v5 lite"), extra={})
    base.update(kw)
    return types.SimpleNamespace(**base)


def _step(slots, ctx):
    return {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.025,
            "attrs": {"bucket": 128, "active": slots,
                      "state_bytes": 2 * slots * 17022976,
                      "context_tokens": ctx, "attn_rows_copied": ctx}}


def test_readers_return_nothing_on_a_program_without_the_counts():
    """A trace without the scopes, spans without the counts, or no spans
    at all: every new reader returns None and raises nothing."""
    old = {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.01,
           "attrs": {"bucket": 32, "active": 12}}
    for run in (_run([old]), _run([]), _run([_step(128, 1e5)])):
        for name in NEW_METRICS:
            assert runner.metric_reader(name)(run) is None, name


def test_the_readers_on_the_sample_traces_shape():
    """The recorded sample trace (another model's ``jit_step``: scopes of
    its own, none of this model's mixer): the readers find their program
    and nothing to read in it."""
    reduced = xplane.reduce(xplane.load_json(os.path.join(
        helpers.BENCH, "lib", "testdata", "small_trace.json")))
    run = _run([_step(128, 1e5)], trace=reduced)
    for name in NEW_METRICS[:5]:
        assert runner.metric_reader(name)(run) is None, name
    got = runner.metric_reader("hybrid_step_roofline")(run)
    ms = xplane.program_ms_per_call(reduced, "step")
    assert (got is None) == (ms is None)


def test_roofline_readers_divide_the_least_time_by_the_scope(monkeypatch):
    spans = [_step(128, 116000), _step(127, 116400)]
    run = _run(spans)
    cfg, v5e = run.config, run.peaks
    times = {"ssm.proj": 2.5, "ssm.conv": 0.5, "ssm.scan": 7.0,
             "gqa.attn": 2.0, "ffn": 6.0, "kv_write": 0.25, "dense": 5.0,
             "sample": 0.25}
    monkeypatch.setattr(
        hostgaps, "scope_ms_per_call",
        lambda r, program, scopes, scope: times[scope]
        if program == "jit_step" and scopes == f.SCOPES else None)
    monkeypatch.setattr(xplane, "program_ms_per_call",
                        lambda trace, program: 24.0)
    read = runner.metric_reader
    assert read("decode_device_ms.ssm")(run) == 10.0
    assert read("decode_device_ms.hybrid_attn")(run) == 2.0
    assert read("decode_device_ms.hybrid_ffn")(run) == 6.0
    state = np.mean([s["attrs"]["state_bytes"] for s in spans]) \
        / v5e["hbm_bytes_per_s"]
    assert read("ssm_state_roofline")(run) == pytest.approx(
        100 * 1e3 * state / 7.5)
    attn = np.mean([f.attn_bytes(cfg, c, 2) for c in (116000, 116400)]) \
        / v5e["hbm_bytes_per_s"]
    assert read("hybrid_attn_roofline")(run) == pytest.approx(
        100 * 1e3 * attn / 2.0)
    step = np.mean([f.step_bytes(cfg, s["attrs"]["context_tokens"],
                                 s["attrs"]["state_bytes"], 2)
                    for s in spans]) / v5e["hbm_bytes_per_s"]
    got = read("hybrid_step_roofline")(run)
    assert got == pytest.approx(100 * 1e3 * step / 24.0)
    assert 55.0 < got < 60.0        # 13.9 ms of bytes in a 24 ms step
    for name in ("ssm_state_roofline", "hybrid_attn_roofline"):
        assert 0.0 < read(name)(run) < 100.0


# --------------------------------------------- the reference's control
def test_the_int8_control_and_every_part_separate_from_float32():
    import jax.numpy as jnp

    from benchmarks.reference import falcon_h1_34b as ref

    cfg = runner.load_json(os.path.join(helpers.DATA, "tiny_falcon_h1.json"))
    sizes = ref.sizes_of(cfg)
    assert sizes["ssm_heads"] == 6 and sizes["groups"] == 2
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
