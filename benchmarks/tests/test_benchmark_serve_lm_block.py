"""The ``serve_lm_block`` kind of cell: rehearsed on the CPU at a tiny
SDAR configuration added to a temporary copy as new files and entries;
the oracle failing when the program leaves out a norm, the
renormalisation, the in-block attention or the commit (the tiny
configuration serves in float32: at its widths bfloat16's own noise is
as large as what the renormalisation moves); the six new
readers on synthetic runs; the operation and byte counts against hand
counts."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from benchmarks import run as runner
from benchmarks.lib import flops_block_moe as f
from benchmarks.lib import hostgaps, peaks, xplane
from benchmarks.tests import helpers

CELL = "sdar_moe_block_gen"
NEW_METRICS = ("decode_device_ms.gqa_attn", "decode_device_ms.unmask",
               "block_forwards_per_token", "block_attn_roofline",
               "block_moe_experts_roofline", "block_step_roofline")


def real_config() -> dict:
    return runner.load_json(os.path.join(
        helpers.BENCH, "configs", "sdar_30b_a3b_chat.json"))


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``helpers.make_copy`` and, on top, the tiny block cell: one
    configuration file and entries, nothing edited."""
    copy = helpers.make_copy(str(tmp_path_factory.mktemp("bench_block")))
    shutil.copy(os.path.join(helpers.DATA, "tiny_sdar.json"),
                os.path.join(copy, "benchmarks", "configs"))
    path = os.path.join(copy, "BENCHMARK.json")
    bench = runner.load_json(path)
    bench["configs"].append(
        {"name": "tiny_sdar", "source": "tests", "reduced": [],
         "why": "test", "file": "benchmarks/configs/tiny_sdar.json"})
    bench["workloads"].append(
        {"name": "tiny_block", "config": "tiny_sdar",
         "traffic": "tiny_closed4", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_block")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(bench, fh, indent=1)
    return copy


def test_the_declared_cell_is_data_beside_the_others():
    bench = runner.load_json(os.path.join(helpers.REPO, "BENCHMARK.json"))
    cell, config, mix = runner.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["kind"] == "serve_lm_block"
    assert cell["traffic"] == "long_gen_closed128"
    assert config["engine"]["max_batch"] == mix["clients"] == 128
    assert mix["prompt_len"][1] + mix["new_tokens"][1] <= config["max_len"]
    declared = {m["name"] for m in bench["per_layer"]
                if runner.applies(m, CELL)}
    assert set(NEW_METRICS) <= declared
    # the other models' readers need their configurations' keys
    assert not declared & {"mla_attn_roofline", "moe_experts_roofline",
                           "decode_device_ms.mla_attn", "draft_accept_share",
                           "verify_step_roofline", "window_compiles.serve"}
    for name in declared:
        assert callable(runner.metric_reader(name))
    # appended together, behind what was there (anchored on the names,
    # not on the end of the list: a later PR appends behind them)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 6] == list(NEW_METRICS)
    assert at > names.index("verify_step_roofline")
    reported = {m["name"] for m in bench["end_to_end"]
                if runner.applies(m, CELL)}
    assert reported == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    # every published width under its key; the one cut named
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 48}
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_intermediate_size"], config["num_experts"],
            config["num_experts_per_tok"], config["vocab_size"],
            config["num_hidden_layers"]) == \
        (2048, 32, 4, 128, 768, 128, 8, 151936, 6)
    assert config["held_experts"] == [0, 128]
    assert config["generation"] == {
        "block_length": 4, "denoising_steps": 4,
        "rule": "low_confidence_dynamic", "threshold": 0.9,
        "mask_token_id": 151669}
    for key in ("block_length", "denoising_steps", "rule", "mask_token_id",
                "generation_procedure", "departures"):
        assert key in config["assumed"], key


def test_the_benchmark_s_reference_is_the_program_s_copy():
    with open(os.path.join(helpers.BENCH, "reference",
                           "sdar_30b_a3b_chat.py")) as fh:
        copy = fh.read()
    with open(os.path.join(helpers.REPO, "bigdl_tpu", "models",
                           "sdar_moe_reference.py")) as fh:
        assert fh.read() == copy
    assert "bigdl_tpu" not in copy.replace("bigdl_tpu/", "")


def test_the_tiny_cell_runs_through_the_programs_constructor(copy):
    rc, result, out = helpers.rehearse(copy, "tiny_block",
                                       seed=2**31 + 91, seconds=2.0)
    assert rc == 0, out
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    assert "check token_gap_mean" in out and "check choice_gap_max" in out
    assert "check positions_scored" in out
    assert "check answers_are_the_record_s_prefix" in out
    assert "compiled inside the window" not in out


# what each fault does to the PROGRAM before the run (the rehearsal's
# ``before``): the reference stays whole
NO_HEAD_NORM = """
from bigdl_tpu.models import sdar_moe as m
m.rms_norm = lambda x, w, eps: x
"""
NO_RENORM = """
from bigdl_tpu.nn import experts as e
_init = e.DroplessExperts.__init__
def init(self, *a, **kw):
    kw["renormalise"] = False
    _init(self, *a, **kw)
e.DroplessExperts.__init__ = init
"""
# the block's rows are written one position after the other, each query
# attending up to its own: a causal mask inside the block
CAUSAL_BLOCK = """
import jax.numpy as jnp
from bigdl_tpu.ops import decode_attention as d
_body = d.paged_decode_attention
def causal(q, kp, vp, tables, lengths, **kw):
    s = q.shape[1]
    outs = [_body(q[:, i:i + 1], kp, vp, tables, lengths - (s - 1 - i), **kw)
            for i in range(s)]
    return jnp.concatenate(outs, axis=1)
d.paged_decode_attention = causal
"""
# a committing slot's rows go to the trash page: the cache keeps what its
# last refining pass wrote
NO_COMMIT = """
import jax.numpy as jnp
from bigdl_tpu.models import sdar_moe as m
_logits = m.SDARMoE.block_logits
def kept(self, params, caches, tables, lengths, tokens, masked, active):
    commit = ~jnp.any(masked, axis=1)
    return _logits(self, params, caches,
                   jnp.where(commit[:, None], 0, tables), lengths, tokens,
                   masked, active)
m.SDARMoE.block_logits = kept
"""


@pytest.mark.parametrize("before, row", [
    (NO_HEAD_NORM, "token_gap_mean"), (NO_RENORM, "token_gap_mean"),
    (CAUSAL_BLOCK, "token_gap_mean"), (NO_COMMIT, "token_gap_mean")],
    ids=["no_head_norm", "no_renorm", "causal_block", "no_commit"])
def test_a_part_left_out_of_the_program_fails_the_oracle(copy, before, row):
    rc, result, out = helpers.rehearse(copy, "tiny_block",
                                       seed=2**31 + 92, seconds=2.0,
                                       before=before)
    assert rc == 0, out
    assert result["correct"] is False, out
    assert result["failed"] == 0
    rows = {line.split()[1].rstrip(":"): line for line in out.splitlines()
            if line.startswith("check ")}
    assert rows[row].endswith("FAILED"), out
    assert rows["answers_are_the_record_s_prefix"].endswith("ok")


# ----------------------------------------------------------- hand counts
def test_operation_and_byte_counts_against_hand_counts():
    cfg = real_config()
    assert f.attention_params(cfg) == (
        2 * 2048 * 4096 + 2 * 2048 * 512) == 18874368
    assert f.expert_params(cfg) == 3 * 2048 * 768 == 4718592
    assert f.router_params(cfg) == 2048 * 128
    assert f.head_params(cfg) == 151936 * 2048
    assert (f.layers(cfg), f.block_length(cfg), f.row_values(cfg)) == \
        (6, 4, 512)
    ctx = 128 * 1100.0
    # ISSUE 32: a token's K and V rows are 2 KB a layer, 12 KB over 6
    assert f.attn_bytes(cfg, 1.0, 2) == 6 * 2 * 512 * 2 == 12288
    assert f.attn_bytes(cfg, ctx, 2) == pytest.approx(1.73e9, rel=0.01)
    assert f.attn_flops(cfg, ctx) == 6 * 2 * 2 * 32 * 128 * 4 * ctx
    assert f.experts_bytes(cfg, 6 * 128, 2) == pytest.approx(7.25e9,
                                                            rel=0.01)
    assert f.experts_flops(cfg, 6 * 128 * 4 * 8) == \
        2 * 4718592 * 6 * 128 * 4 * 8
    # weights 8.72 GB with the embedding (0.62 GB), which a step does
    # not read: 8.1 GB of matrices with every expert hit
    whole = f.step_bytes(cfg, 0, 6 * 128, 2)
    assert 8.05e9 < whole < 8.15e9
    assert f.step_bytes(cfg, ctx, 700, 2) == pytest.approx(
        2 * f.dense_params(cfg) + 12288 * ctx + 700 * 4718592 * 2)
    flops = f.step_flops(cfg, 128, ctx, 6 * 128 * 4 * 8)
    assert 0.6e12 < flops < 1.0e12


# ------------------------------------------------------ synthetic runs
def _run(spans, **kw):
    base = dict(config=real_config(), spans=spans, trace={"programs": {}},
                counters={"batch": 128, "weight_itemsize": 2,
                          "kv_itemsize": 2},
                peaks=peaks.peaks_for("TPU v5 lite"), extra={})
    base.update(kw)
    return types.SimpleNamespace(**base)


def _step(passes, commits, emitted, hit, ctx):
    return {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.03,
            "attrs": {"bucket": 128, "active": 128,
                      "block_passes": passes, "block_commits": commits,
                      "positions_unmasked": passes,
                      "tokens_emitted": emitted,
                      "moe_held": 6 * 128 * 4 * 8, "moe_zero": 0,
                      "moe_absent": 0, "moe_hit": hit, "moe_max_load": 60,
                      "context_tokens": ctx}}


def test_readers_return_nothing_on_a_program_without_the_counts():
    old = {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.01,
           "attrs": {"bucket": 128, "active": 128, "moe_held": 128,
                     "moe_hit": 50, "context_tokens": 190000,
                     "draft_verified": 3, "draft_accepted": 0,
                     "tokens_emitted": 128}}
    for spans in ([old], []):
        for name in NEW_METRICS:
            assert runner.metric_reader(name)(_run(spans)) is None, name


def test_readers_on_a_synthetic_window(monkeypatch):
    spans = [_step(100, 28, 101, 760, 140000),
             _step(104, 24, 103, 764, 141000)]
    run = _run(spans)
    cfg, v5e = run.config, run.peaks
    assert runner.metric_reader("block_forwards_per_token")(run) == \
        pytest.approx(256 / 204)
    times = {"gqa.attn": 12.0, "moe.experts": 11.0, "unmask": 1.5}
    monkeypatch.setattr(
        hostgaps, "scope_ms_per_call",
        lambda r, program, scopes, scope: times[scope]
        if program == "jit_step" and scopes == f.SCOPES else None)
    monkeypatch.setattr(xplane, "program_ms_per_call",
                        lambda trace, program: 30.0)
    read = runner.metric_reader
    assert read("decode_device_ms.gqa_attn")(run) == 12.0
    assert read("decode_device_ms.unmask")(run) == 1.5
    attn = np.mean([f.attn_bytes(cfg, c, 2) for c in (140000, 141000)]) \
        / v5e["hbm_bytes_per_s"]
    assert read("block_attn_roofline")(run) == pytest.approx(
        100 * 1e3 * attn / 12.0)
    moe = np.mean([f.experts_bytes(cfg, h, 2) for h in (760, 764)]) \
        / v5e["hbm_bytes_per_s"]
    got = read("block_moe_experts_roofline")(run)
    assert got == pytest.approx(100 * 1e3 * moe / 11.0)
    assert 70.0 < got < 100.0    # 8.8 ms of reads in 11 ms
    step = np.mean([f.step_bytes(cfg, c, h, 2)
                    for c, h in ((140000, 760), (141000, 764))]) \
        / v5e["hbm_bytes_per_s"]
    got = read("block_step_roofline")(run)
    assert got == pytest.approx(100 * 1e3 * step / 30.0)
    assert 30.0 < got < 50.0     # 12 ms of reads in a 30 ms step
