"""The ``serve_lm`` kind of cell under a model whose layers differ in
what a slot keeps for them (``ling3_kda_long_gen``: a delta-rule matrix
state in five layers of six, latent rows in pages in the sixth, experts
chosen by groups): rehearsed on the CPU at a tiny Ling configuration
added to a temporary copy as new files and entries (it serves in
float32, so its limits catch a program that hands no state from the
prefill to the decode step); the seven new readers on synthetic runs
(and reading nothing where nothing is); the operation and byte counts
against hand counts at the published widths."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from benchmarks import run as runner
from benchmarks.lib import flops_delta_moe as f
from benchmarks.lib.flops import roofline_seconds
from benchmarks.lib import hostgaps, peaks, xplane
from benchmarks.tests import helpers

CELL = "ling3_kda_long_gen"
NEW_METRICS = ("decode_device_ms.kda", "kda_state_roofline",
               "kda_mla_attn_roofline", "kda_moe_experts_roofline",
               "kda_step_roofline", "kda_moe_expert_load_max_over_mean",
               "kda_moe_group_hit_share")
SHARED = ("decode_device_ms.moe", "decode_device_ms.ffn",
          "decode_device_ms.mla_attn")
DROP_STATE = """
from bigdl_tpu.serving import cache, engine
engine.write_slot_state = lambda state, slot, rows: state
"""
#: a slot's state at the published widths: 6 KDA layers of S (32 x 128 x
#: 128) and the convolution's 3 x 12288 rows, float32
SLOT_BYTES = 6 * (32 * 128 * 128 + 3 * 12288) * 4


def real_config() -> dict:
    return runner.load_json(os.path.join(
        helpers.BENCH, "configs", "ling_3_flash_vl.json"))


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``helpers.make_copy`` and, on top, the tiny cell: one
    configuration file and entries, nothing edited."""
    copy = helpers.make_copy(str(tmp_path_factory.mktemp("bench_kda")))
    shutil.copy(os.path.join(helpers.DATA, "tiny_ling.json"),
                os.path.join(copy, "benchmarks", "configs"))
    path = os.path.join(copy, "BENCHMARK.json")
    bench = runner.load_json(path)
    bench["configs"].append(
        {"name": "tiny_ling", "source": "tests", "reduced": [],
         "why": "test", "file": "benchmarks/configs/tiny_ling.json"})
    bench["workloads"].append(
        {"name": "tiny_kda", "config": "tiny_ling",
         "traffic": "tiny_closed4", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_kda")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(bench, fh, indent=1)
    return copy


def test_the_declared_cell_is_data_beside_the_others():
    bench = runner.load_json(os.path.join(helpers.REPO, "BENCHMARK.json"))
    cell, config, mix = runner.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["kind"] == "serve_lm"
    assert cell["traffic"] == "long_gen_closed256"
    assert bench["workloads"][-1] is cell and len(cell["why"]) <= 200
    assert config["engine"] == {"max_batch": 256, "page_size": 16}
    assert mix["clients"] == 256 and mix["check_requests"] == 4
    assert mix["prompt_len"][1] + mix["new_tokens"][1] <= config["max_len"]
    declared = {m["name"] for m in bench["per_layer"]
                if runner.applies(m, CELL)}
    assert set(NEW_METRICS) | set(SHARED) <= declared
    # every generic serving metric cell 8 reports, the expert and latent
    # times cells 4 and 5 report, and this PR's seven
    falcon = {m["name"] for m in bench["per_layer"]
              if runner.applies(m, "falcon_h1_ssm_long_gen")}
    assert declared - set(NEW_METRICS) - set(SHARED) == falcon - {
        "decode_device_ms.ssm", "decode_device_ms.hybrid_attn",
        "decode_device_ms.hybrid_ffn", "ssm_state_roofline",
        "hybrid_attn_roofline", "hybrid_step_roofline"}
    for name in declared:
        assert callable(runner.metric_reader(name))
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-7:] == list(NEW_METRICS)
    for m in bench["per_layer"][-7:]:
        assert m["workloads"] == [CELL]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for m in bench["per_layer"]:
        if "roofline" in m["name"]:
            assert m["unit"] == "%"
    assert {m["name"] for m in bench["end_to_end"]
            if runner.applies(m, CELL)} == {
        "serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    # every published number under its key; the cuts are the four listed
    entry = bench["configs"][-1]
    assert entry["name"] == "ling_3_flash_vl" and len(entry["why"]) <= 200
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "first_k_dense_replace"]
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["head_dim"],
            config["kv_lora_rank"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["moe_intermediate_size"],
            config["moe_shared_expert_intermediate_size"],
            config["num_experts_per_tok"], config["n_group"],
            config["topk_group"], config["short_conv_kernel_size"],
            config["layer_group_size"], config["kda_lower_bound"]) == (
        2560, 6144, 32, 128, 512, 128, 64, 128, 768, 768, 8, 8, 4, 4, 6, -5)
    assert config["q_lora_rank"] is None
    assert config["published"] == {
        "num_hidden_layers": 42, "num_experts": 512, "vocab_size": 157184,
        "first_k_dense_replace": 2}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["first_k_dense_replace"]) == (
        7, 64, 39296, 1)
    # inside the floors: a whole period behind the dense layer, 8 or
    # more experts, an eighth or more of the vocabulary
    assert config["kept_layers"] == [0, 2, 3, 4, 5, 6, 7]
    assert config["router_experts"] == 512
    assert config["held_experts"] == [0, 64]
    assert config["vocab_size"] * 8 >= 157184 and 39296 % 128 == 0
    for key in ("state_dtype", "state_layout", "kda_gate", "kda_heads",
                "kda_qk", "kda_convolution", "kda_norm", "mla", "router",
                "block", "weights", "from_upstream_code_unverified",
                "serving_dtype", "kv_cache_dtype", "max_len", "kda_chunk"):
        assert key in config["assumed"], key
    for key in ("deployment", "expert_load", "bytes", "left_out",
                "limits_why", "engine_why"):
        assert config[key] and "PLACEHOLDER" not in config[key], key


def test_the_catalogs_numbers_are_under_their_keys():
    """Every number of the catalog's ``config`` is in the file under the
    same key, but the four ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog, encoding="utf-8") as fh:
        row = next(json.loads(line) for line in fh
                   if '"Ling-3.0-flash-VL"' in line)
    config = real_config()
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key


def test_the_tiny_cell_runs_through_the_programs_constructor(copy):
    rc, result, out = helpers.rehearse(copy, "tiny_kda", seed=2**31 + 91,
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
    rc, result, out = helpers.rehearse(copy, "tiny_kda", seed=2**31 + 92,
                                       seconds=2.0, before=DROP_STATE)
    assert rc == 0, out
    assert result["correct"] is False, out
    assert "FAILED" in out and "check served_gap" in out


def test_a_program_without_the_model_fails_at_once(copy):
    """What the parent commit does with this cell: the driver imports
    the model first of all, and a program that lacks it ends the run
    with an ImportError before a weight is made."""
    cfg_path = os.path.join(copy, "benchmarks", "configs", "tiny_ling.json")
    saved = open(cfg_path, encoding="utf-8").read()
    cfg = json.loads(saved)
    cfg["model"]["module"] = "bigdl_tpu.models.not_in_this_program"
    try:
        with open(cfg_path, "w", encoding="utf-8") as fh:
            json.dump(cfg, fh)
        rc, result, out = helpers.rehearse(copy, "tiny_kda", seconds=1.0)
    finally:
        with open(cfg_path, "w", encoding="utf-8") as fh:
            fh.write(saved)
    assert rc != 0 and result is None
    assert "ModuleNotFoundError" in out
    assert "weights on the device" not in out


# ----------------------------------------------------------- hand counts
def test_operation_and_byte_counts_against_hand_counts():
    cfg = real_config()
    assert (f.kda_layers(cfg), f.latent_layers(cfg), f.moe_layers(cfg)) \
        == (6, 1, 6)
    assert f.layer_kinds(cfg)[0] == (False, True)       # KDA, dense
    assert f.layer_kinds(cfg)[4] == (True, False)       # published layer 5
    assert f.state_values(cfg) == 32 * 128 * 128
    assert f.conv_channels(cfg) == 12288
    # ISSUE 44: S 2.10 MB and the kept rows 0.15 MB a slot and layer
    assert f.slot_state_bytes(cfg) == SLOT_BYTES == 13467648
    assert f.kda_params(cfg) == 2560 * (5 * 4096 + 32) + 2560 * 4096
    assert 62.9e6 < f.kda_params(cfg) < 63.1e6          # ISSUE: 63.0 M
    assert f.latent_params(cfg) == (
        2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 32 * 128 * 2560
        + 32 * 2560)
    assert 31.9e6 < f.latent_params(cfg) < 32.1e6       # ISSUE: 32.0 M
    assert f.expert_params(cfg) == 3 * 2560 * 768
    assert f.expert_slots(cfg) == 6 * 64
    outside = f.outside_experts_params(cfg)
    assert outside == pytest.approx(
        6 * f.kda_params(cfg) + f.latent_params(cfg) + 3 * 2560 * 6144
        + 6 * (512 * 2560 + 3 * 2560 * 768) + 39296 * 2560)
    # weights: outside the experts, the 384 held experts, the embedding
    total = outside + 384 * f.expert_params(cfg) + 39296 * 2560
    assert 2.96e9 < total < 2.97e9                      # ISSUE: 5.93 GB
    state = 2 * 256 * SLOT_BYTES
    assert 6.8e9 < state < 7.0e9                        # ISSUE: 6.9 GB
    assert f.slots_of(cfg, state) == 256
    ctx = 256 * 1100.0
    attrs = {"state_bytes": state, "context_tokens": ctx,
             "moe_hit": 6 * 63, "moe_held": 6 * 256}
    assert f.mla_attn_bytes(cfg, 1.0, 2) == 2 * (576 + 512 * 32 * 256)
    assert 0.32e9 < f.mla_attn_bytes(cfg, ctx, 2) < 0.34e9
    assert f.mla_attn_flops(cfg, ctx, 256) == pytest.approx(
        2 * 32 * (576 + 512) * ctx + 2 * 512 * 32 * 256 * 256)
    assert f.moe_experts_bytes(cfg, 6 * 63, 2) == 6 * 63 * 5898240 * 2
    assert 4.4e9 < f.moe_experts_bytes(cfg, 6 * 63, 2) < 4.5e9
    assert f.moe_experts_flops(cfg, 1536) == 2 * 5898240 * 1536
    assert f.state_flops(cfg, state) == 256 * 6 * (
        7 * 32 * 128 * 128 + 2 * 4 * 12288)
    got = f.step_bytes(cfg, attrs, 2)
    assert got == pytest.approx(
        state + 2 * (outside - 512 * 32 * 256)
        + f.moe_experts_bytes(cfg, 6 * 63, 2) + f.mla_attn_bytes(cfg, ctx, 2))
    # ISSUE: 13 GB, the KDA state 53 % of it
    assert 12.6e9 < got < 13.2e9
    assert 0.52 < state / got < 0.55
    v5e = peaks.peaks_for("TPU v5 lite")
    # bound by the bytes: 15.7 ms against a few ms of multiplications
    assert roofline_seconds(f.step_flops(cfg, attrs), got, v5e) \
        == pytest.approx(got / 819e9)
    assert roofline_seconds(f.state_flops(cfg, state), state, v5e) \
        == pytest.approx(state / 819e9)


# ------------------------------------------------------ synthetic runs
def _run(spans, **kw):
    base = dict(config=real_config(), spans=spans, trace={"programs": {}},
                counters={"batch": 256, "weight_itemsize": 2,
                          "kv_itemsize": 2},
                peaks=peaks.peaks_for("TPU v5 lite"), extra={})
    base.update(kw)
    return types.SimpleNamespace(**base)


def _step(slots, ctx, hit=378, held=1540, load=12, share=0.5):
    return {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.025,
            "attrs": {"bucket": 128, "active": slots,
                      "moe_held": held, "moe_zero": 0,
                      "moe_absent": 6 * 8 * slots - held, "moe_hit": hit,
                      "moe_max_load": load, "moe_group_hit_share": share,
                      "context_tokens": ctx, "attn_rows_copied": ctx,
                      "state_bytes": 2 * slots * SLOT_BYTES}}


def test_readers_return_nothing_on_a_program_without_the_counts():
    """A trace without the scopes, spans without the counts, or no spans
    at all: every new reader returns None and raises nothing (what the
    parent commit gives a traced run of another cell)."""
    old = {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.01,
           "attrs": {"bucket": 32, "active": 12}}
    falcon = {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.01,
              "attrs": {"bucket": 32, "active": 12, "state_bytes": 1e9,
                        "context_tokens": 1e5}}
    for run in (_run([old]), _run([]), _run([falcon])):
        for name in NEW_METRICS:
            assert runner.metric_reader(name)(run) is None, name
    # with the counts and no device trace: the two counters read, the
    # five device metrics do not
    run = _run([_step(256, 2.8e5)])
    for name in NEW_METRICS[:5]:
        assert runner.metric_reader(name)(run) is None, name


def test_the_readers_on_the_sample_traces_shape():
    """The recorded sample trace (another model's ``jit_step``: scopes of
    its own, none of this model's mixer): the readers find their program
    and nothing to read in it."""
    reduced = xplane.reduce(xplane.load_json(os.path.join(
        helpers.BENCH, "lib", "testdata", "small_trace.json")))
    run = _run([_step(256, 2.8e5)], trace=reduced)
    for name in NEW_METRICS[:4]:
        assert runner.metric_reader(name)(run) is None, name
    got = runner.metric_reader("kda_step_roofline")(run)
    ms = xplane.program_ms_per_call(reduced, "step")
    assert (got is None) == (ms is None)


def test_the_two_counters_read_the_spans():
    spans = [_step(256, 2.8e5, held=1536, load=12, share=0.5),
             _step(255, 2.8e5, held=1500, load=9, share=0.46)]
    run = _run(spans)
    read = runner.metric_reader
    assert read("kda_moe_group_hit_share")(run) == pytest.approx(48.0)
    assert read("kda_moe_expert_load_max_over_mean")(run) == pytest.approx(
        (12 * 384 / 1536 + 9 * 384 / 1500) / 2)


def test_roofline_readers_divide_the_least_time_by_the_scope(monkeypatch):
    spans = [_step(256, 281600), _step(255, 281000, hit=372, held=1520)]
    run = _run(spans)
    cfg, v5e = run.config, run.peaks
    times = {"kda.proj": 1.5, "kda.conv": 0.5, "kda.state": 10.0,
             "mla.proj": 0.25, "mla.attn": 1.0, "ffn": 1.0, "kv_write": 0.05,
             "moe.route": 0.5, "moe.experts": 7.0, "moe.zero": 0.0,
             "dense": 0.5, "sample": 0.2}
    times.update({g: 0.0 for g in f.GROUPED})
    monkeypatch.setattr(
        hostgaps, "scope_ms_per_call",
        lambda r, program, scopes, scope: times[scope]
        if program == "jit_step" and scopes == f.SCOPES else None)
    monkeypatch.setattr(xplane, "program_ms_per_call",
                        lambda trace, program: 24.0)
    read = runner.metric_reader
    assert read("decode_device_ms.kda")(run) == 12.0
    bw = v5e["hbm_bytes_per_s"]
    state = np.mean([s["attrs"]["state_bytes"] for s in spans]) / bw
    assert read("kda_state_roofline")(run) == pytest.approx(
        100 * 1e3 * state / 10.5)
    attn = np.mean([f.mla_attn_bytes(cfg, s["attrs"]["context_tokens"], 2)
                    for s in spans]) / bw
    assert read("kda_mla_attn_roofline")(run) == pytest.approx(
        100 * 1e3 * attn / 1.0)
    experts = np.mean([f.moe_experts_bytes(cfg, s["attrs"]["moe_hit"], 2)
                       for s in spans]) / bw
    assert read("kda_moe_experts_roofline")(run) == pytest.approx(
        100 * 1e3 * experts / 7.0)
    step = np.mean([f.step_bytes(cfg, s["attrs"], 2) for s in spans]) / bw
    got = read("kda_step_roofline")(run)
    assert got == pytest.approx(100 * 1e3 * step / 24.0)
    assert 62.0 < got < 68.0        # 15.7 ms of bytes in a 24 ms step
    for name in NEW_METRICS[1:5]:
        assert 0.0 < read(name)(run) < 100.0


# --------------------------------------------- the reference's control
def test_the_int8_control_and_every_part_separate_from_float32():
    import jax.numpy as jnp

    from benchmarks.reference import ling_3_flash_vl as ref

    cfg = runner.load_json(os.path.join(helpers.DATA, "tiny_ling.json"))
    sizes = ref.sizes_of(cfg)
    assert sizes["latent"] == (False, False, False, True)
    assert sizes["held"] == (4, 8) and sizes["n_routed"] == 16
    params = ref.init_params(2**31 + 5, sizes, jnp.float32)
    assert params["l1"]["moe"]["w_gate"].shape == (4, 32, 16)
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
