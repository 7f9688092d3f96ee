"""The ``serve_lm_draft`` kind of cell: rehearsed on the CPU at a tiny
JoyAI-LLM-Flash configuration added to a temporary copy as new files and
entries; a draft altered where it is produced and a prediction layer
with one matrix zeroed each fail ``draft_gap_*`` while the served tokens
still pass; the five new readers on synthetic runs; the operation and
byte counts against hand counts."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from benchmarks import run as runner
from benchmarks.lib import flops_draft_moe as d
from benchmarks.lib import flops_latent_moe as f
from benchmarks.lib import hostgaps, peaks, xplane
from benchmarks.tests import helpers

CELL = "joyai_flash_draft_gen"
NEW_METRICS = ("draft_accept_share", "decode_device_ms.mtp",
               "verify_attn_roofline", "verify_moe_experts_roofline",
               "verify_step_roofline")


def real_config() -> dict:
    return runner.load_json(os.path.join(
        helpers.BENCH, "configs", "joyai_llm_flash.json"))


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``helpers.make_copy`` and, on top, the tiny drafting cell: one
    configuration file and entries, nothing edited."""
    copy = helpers.make_copy(str(tmp_path_factory.mktemp("bench_draft")))
    shutil.copy(os.path.join(helpers.DATA, "tiny_joyai.json"),
                os.path.join(copy, "benchmarks", "configs"))
    path = os.path.join(copy, "BENCHMARK.json")
    bench = runner.load_json(path)
    bench["configs"].append(
        {"name": "tiny_joyai", "source": "tests", "reduced": [],
         "why": "test", "file": "benchmarks/configs/tiny_joyai.json"})
    bench["workloads"].append(
        {"name": "tiny_draft", "config": "tiny_joyai",
         "traffic": "tiny_closed4", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_draft")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(bench, fh, indent=1)
    return copy


def test_the_declared_cell_is_data_beside_the_others():
    bench = runner.load_json(os.path.join(helpers.REPO, "BENCHMARK.json"))
    cell, config, mix = runner.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["kind"] == "serve_lm_draft"
    assert (mix["clients"], mix["requests"], mix["check_requests"]) == \
        (256, 512, 4)
    assert config["engine"]["max_batch"] == mix["clients"]
    assert mix["prompt_len"][1] + mix["new_tokens"][1] <= config["max_len"]
    long128 = runner.load_json(os.path.join(
        helpers.BENCH, "traffic", "long_gen_closed128.json"))
    for key in ("prompt_len", "new_tokens", "warm", "temperature"):
        assert mix[key] == long128[key], key
    declared = {m["name"] for m in bench["per_layer"]
                if runner.applies(m, CELL)}
    assert set(NEW_METRICS) <= declared
    # LongCat's readers need its configuration's keys
    assert not declared & {"mla_attn_roofline", "moe_experts_roofline",
                           "latent_moe_decode_step_roofline",
                           "moe_zero_share", "window_compiles.serve"}
    for name in declared:
        assert callable(runner.metric_reader(name))
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(NEW_METRICS)
    # every published number under its key; the two cuts named
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["router_experts"], config["vocab_size"]) == \
        (2048, 7168, 768, 8, 256, 129280)
    assert config["held_experts"] == [0, config["n_routed_experts"]]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "n_routed_experts": 256}


def test_the_tiny_cell_runs_through_the_programs_constructor(copy):
    rc, result, out = helpers.rehearse(copy, "tiny_draft",
                                       seed=2**31 + 91, seconds=2.0)
    assert rc == 0, out
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    assert "check served_gap_mean" in out and "check draft_gap_mean" in out
    assert "check drafts_scored" in out
    assert "compiled inside the window" not in out


# what each fault does before the run (the rehearsal's ``before``)
ALTERED_DRAFT = """
from bigdl_tpu.models import joyai_flash as m
_decode = m.JoyAIFlash.paged_decode
def altered(self, *a, **kw):
    caches, picked, accepted, nxt, counts = _decode(self, *a, **kw)
    return caches, picked, accepted, (nxt + 1) % self.vocab_size, counts
m.JoyAIFlash.paged_decode = altered
"""
# the driver hands program and reference the same tree, so the matrix is
# zeroed where the PROGRAM reads it: the prediction layer loses W_eh
ZEROED_MATRIX = """
from bigdl_tpu.models import joyai_flash as m
_run = m.PredictionLayer.run
def without_join(self, params, emb_next, h, attend, mask):
    import jax
    p = dict(params, proj={"weight": jax.numpy.zeros_like(
        params["proj"]["weight"])})
    return _run(self, p, emb_next, h, attend, mask)
m.PredictionLayer.run = without_join
"""


@pytest.mark.parametrize("before", [ALTERED_DRAFT, ZEROED_MATRIX],
                         ids=["altered_draft", "zeroed_matrix"])
def test_a_wrong_draft_fails_the_draft_gap_and_not_the_served(copy, before):
    rc, result, out = helpers.rehearse(copy, "tiny_draft",
                                       seed=2**31 + 92, seconds=2.0,
                                       before=before)
    assert rc == 0, out
    assert result["correct"] is False, out
    assert result["failed"] == 0
    rows = {line.split()[1].rstrip(":"): line for line in out.splitlines()
            if line.startswith("check ")}
    assert rows["served_gap_mean"].endswith("ok")
    assert rows["served_gap_max"].endswith("ok")
    assert rows["draft_gap_mean"].endswith("FAILED")


# ----------------------------------------------------------- hand counts
def test_operation_and_byte_counts_against_hand_counts():
    cfg = real_config()
    assert f.attention_params(cfg) == (
        2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
        + 32 * 128 * 2048) == 26345472
    assert d.expert_params(cfg) == d.shared_params(cfg) == 3 * 2048 * 768
    assert d.router_params(cfg) == 2048 * 256
    assert d.expert_layer_dense_params(cfg) == 26345472 + 4718592 + 524288
    assert d.dense_layer_params(cfg) == 26345472 + 3 * 2048 * 7168
    assert (d.cached_attentions(cfg), d.expert_layers(cfg)) == (9, 8)
    assert d.head_params(cfg) == 129280 * 2048
    ctx = 256 * 1500.0
    assert d.attn_bytes(cfg, ctx, 2) == 9 * 2 * (ctx * 576 + 512 * 32 * 256)
    assert d.attn_flops(cfg, ctx, 512) == 9 * (
        2 * 32 * (576 + 512) * ctx * 2 + 2 * 512 * 32 * 256 * 512)
    assert d.experts_bytes(cfg, 200, 2) == 200 * 4718592 * 2
    # ISSUE 30: weights 4.14 GB with the embedding (0.53 GB), which a
    # step does not read: 3.6 GB of matrices with every expert hit
    whole = d.step_bytes(cfg, 0, 8 * 32, 2)
    assert 3.55e9 < whole < 3.65e9
    assert d.step_bytes(cfg, ctx, 200, 2) == pytest.approx(
        2 * (d.main_dense_params(cfg) + d.draft_dense_params(cfg))
        + 9 * ctx * 576 * 2 + 200 * 4718592 * 2)
    flops = d.step_flops(cfg, 256, 256, ctx, 8 * 512)
    assert 1.0e12 < flops < 1.6e12


# ------------------------------------------------------ synthetic runs
def _run(spans, **kw):
    base = dict(config=real_config(), spans=spans, trace={"programs": {}},
                counters={"batch": 256, "weight_itemsize": 2,
                          "kv_itemsize": 2},
                peaks=peaks.peaks_for("TPU v5 lite"), extra={})
    base.update(kw)
    return types.SimpleNamespace(**base)


def _step(verified, accepted, held, hit, ctx):
    return {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.04,
            "attrs": {"bucket": 128, "active": 256,
                      "draft_verified": verified,
                      "draft_accepted": accepted,
                      "tokens_emitted": 256 + accepted, "moe_held": held,
                      "moe_zero": 0, "moe_absent": 8 * 4096 - held,
                      "moe_hit": hit, "moe_max_load": 30,
                      "context_tokens": ctx}}


def test_readers_return_nothing_on_a_program_without_the_counts():
    old = {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.01,
           "attrs": {"bucket": 128, "active": 128, "moe_held": 128,
                     "moe_hit": 50, "context_tokens": 190000}}
    for spans in ([old], []):
        for name in NEW_METRICS:
            assert runner.metric_reader(name)(_run(spans)) is None, name


def test_readers_on_a_synthetic_window(monkeypatch):
    spans = [_step(256, 0, 4000, 250, 380000),
             _step(250, 5, 4100, 252, 382000)]
    run = _run(spans)
    cfg, v5e = run.config, run.peaks
    assert runner.metric_reader("draft_accept_share")(run) == \
        pytest.approx(100.0 * 5 / 506)
    times = {"mla.attn": 28.0, "moe.experts": 0.5, "ragged-dot-none": 3.0,
             "ragged-dot-none:": 0.0, "ragged-dot-metadata": 0.25,
             "ragged-dot-metadata:": 0.0, "mtp": 4.5}
    monkeypatch.setattr(
        hostgaps, "scope_ms_per_call",
        lambda r, program, scopes, scope: times[scope]
        if program == "jit_step" and scopes in (f.SCOPES, ("mtp",))
        else None)
    monkeypatch.setattr(xplane, "program_ms_per_call",
                        lambda trace, program: 40.0)
    read = runner.metric_reader
    assert read("decode_device_ms.mtp")(run) == 4.5
    attn = np.mean([d.attn_bytes(cfg, c, 2) for c in (380000, 382000)]) \
        / v5e["hbm_bytes_per_s"]
    assert read("verify_attn_roofline")(run) == pytest.approx(
        100 * 1e3 * attn / 28.0)
    moe = np.mean([d.experts_bytes(cfg, h, 2) for h in (250, 252)]) \
        / v5e["hbm_bytes_per_s"]
    assert read("verify_moe_experts_roofline")(run) == pytest.approx(
        100 * 1e3 * moe / 3.75)
    step = np.mean([d.step_bytes(cfg, c, h, 2)
                    for c, h in ((380000, 250), (382000, 252))]) \
        / v5e["hbm_bytes_per_s"]
    got = read("verify_step_roofline")(run)
    assert got == pytest.approx(100 * 1e3 * step / 40.0)
    assert 15.0 < got < 40.0     # 9-10 ms of reads in a 40 ms step
