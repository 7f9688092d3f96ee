"""The ``serve_lm`` kind of cell under a model that mixes by the gated
delta rule in three layers of four and by full attention in the fourth
(``olmo_hybrid_gdn_long_gen``): rehearsed on the CPU at a tiny
Olmo-Hybrid configuration added to a temporary copy as new files and
entries (it serves in float32, so its limits catch a program that lacks
any one part of the mathematics: each of the reference's ``PARTS`` is
taken out of the PROGRAM in turn, and the run is not ``correct``); the
five new readers on synthetic runs (and reading nothing where nothing
is); the operation and byte counts against hand counts at the published
widths."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from benchmarks import run as runner
from benchmarks.lib import flops_gated_delta as f
from benchmarks.lib.flops import roofline_seconds
from benchmarks.lib import hostgaps, peaks, xplane
from benchmarks.tests import helpers

CELL = "olmo_hybrid_gdn_long_gen"
NEW_METRICS = ("decode_device_ms.gdn", "decode_device_ms.gdn_attn",
               "gdn_state_roofline", "gdn_attn_roofline",
               "gdn_step_roofline")
SHARED = ("decode_device_ms.ffn",)
#: a slot's state at the published widths, by its values: 3 linear
#: layers of S (30 x 96 x 192) and the convolution's 3 x 11520 rows,
#: float32
SLOT_BYTES = 3 * (30 * 96 * 192 + 3 * 11520) * 4

#: the PROGRAM with one part of the mathematics taken out, by the name
#: the reference gives the part (``PARTS``)
FAULTS = {
    "state_carry": """
from bigdl_tpu.serving import cache, engine
engine.write_slot_state = lambda state, slot, rows: state
""",
    "delta": """
import jax.numpy as jnp
from bigdl_tpu.nn import delta

def _advance(self, states, layer, g, k, q, v, beta):
    # S^T k taken as 0: plain gated linear attention, in jax.numpy
    (s,) = states
    h, dk, dv = self.heads, self.key_dim, self.value_dim
    old = s[layer].reshape(-1, dk, h, dv) * jnp.exp(g[..., 0])[:, None, :, None]
    new = old + jnp.swapaxes(k, 1, 2)[..., None] \\
        * (beta[..., None] * v)[:, None]
    o = jnp.einsum("skhv,shk->shv", new, q).reshape(-1, h * dv)
    return (s.at[layer].set(new.reshape(-1, dk, h * dv)),), o

delta.GatedDeltaMixer._advance = _advance
""",
    "neg_eigval": """
from bigdl_tpu.nn import delta
gates = delta.GatedDeltaMixer._gates
delta.GatedDeltaMixer._gates = lambda self, p, a, b, live: (
    lambda g, beta: (g, 0.5 * beta))(*gates(self, p, a, b, live))
""",
    "head_decay": """
from bigdl_tpu.nn import delta
gates = delta.GatedDeltaMixer._gates
delta.GatedDeltaMixer._gates = lambda self, p, a, b, live: (
    lambda g, beta: (0.0 * g, beta))(*gates(self, p, a, b, live))
""",
    "qk_norm": """
from bigdl_tpu.models import olmo_hybrid
olmo_hybrid.rms_norm = lambda x, weight, eps: x
""",
    "out_gate": """
import jax
real = jax.nn.silu
from bigdl_tpu.nn import delta
finish = delta.GatedDeltaMixer._finish

def _finish(self, params, o, z, dtype):
    jax.nn.silu = lambda x: 1.0
    try:
        return finish(self, params, o, z, dtype)
    finally:
        jax.nn.silu = real

delta.GatedDeltaMixer._finish = _finish
""",
}


def real_config() -> dict:
    return runner.load_json(os.path.join(
        helpers.BENCH, "configs", "olmo_hybrid_7b.json"))


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``helpers.make_copy`` and, on top, the tiny cell: one
    configuration file and entries, nothing edited."""
    copy = helpers.make_copy(str(tmp_path_factory.mktemp("bench_gdn")))
    shutil.copy(os.path.join(helpers.DATA, "tiny_olmo_hybrid.json"),
                os.path.join(copy, "benchmarks", "configs"))
    path = os.path.join(copy, "BENCHMARK.json")
    bench = runner.load_json(path)
    bench["configs"].append(
        {"name": "tiny_olmo_hybrid", "source": "tests", "reduced": [],
         "why": "test", "file": "benchmarks/configs/tiny_olmo_hybrid.json"})
    bench["workloads"].append(
        {"name": "tiny_gdn", "config": "tiny_olmo_hybrid",
         "traffic": "tiny_closed4", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_gdn")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(bench, fh, indent=1)
    return copy


def test_the_declared_cell_is_data_beside_the_others():
    bench = runner.load_json(os.path.join(helpers.REPO, "BENCHMARK.json"))
    cell, config, mix = runner.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["kind"] == "serve_lm"
    assert cell["traffic"] == "long_gen_closed256"
    assert len(cell["why"]) <= 200
    assert config["engine"] == {"max_batch": 256, "page_size": 16}
    assert mix["clients"] == 256 and mix["check_requests"] == 4
    assert mix["prompt_len"][1] + mix["new_tokens"][1] <= config["max_len"]
    declared = {m["name"] for m in bench["per_layer"]
                if runner.applies(m, CELL)}
    assert set(NEW_METRICS) | set(SHARED) <= declared
    # every generic serving metric cell 8 reports, the dense MLP's time
    # cells 4, 5 and 9 report, and this PR's five
    falcon = {m["name"] for m in bench["per_layer"]
              if runner.applies(m, "falcon_h1_ssm_long_gen")}
    assert declared - set(NEW_METRICS) - set(SHARED) == falcon - {
        "decode_device_ms.ssm", "decode_device_ms.hybrid_attn",
        "decode_device_ms.hybrid_ffn", "ssm_state_roofline",
        "hybrid_attn_roofline", "hybrid_step_roofline"}
    for name in declared:
        assert callable(runner.metric_reader(name))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["unit"] == ("%" if "roofline" in name else "ms")
    assert {m["name"] for m in bench["end_to_end"]
            if runner.applies(m, CELL)} == {
        "serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    # every published width under its key; the one cut is the depth
    entry = next(c for c in bench["configs"]
                 if c["name"] == "olmo_hybrid_7b")
    assert len(entry["why"]) <= 200
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["linear_num_key_heads"], config["linear_num_value_heads"],
            config["linear_key_head_dim"], config["linear_value_head_dim"],
            config["linear_conv_kernel_dim"], config["vocab_size"]) == (
        3840, 11008, 30, 30, 30, 30, 96, 192, 4, 100352)
    assert config["linear_allow_neg_eigval"] is True
    assert config["rope_parameters"] == {"rope_theta": None}
    assert config["published"] == {"num_hidden_layers": 32}
    assert config["num_hidden_layers"] == 4
    # one whole period, 3 : 1, by published indices
    assert config["kept_layers"] == [0, 1, 2, 3]
    assert [config["layer_types"][i] for i in config["kept_layers"]] == [
        "linear_attention"] * 3 + ["full_attention"]
    assert len(config["layer_types"]) == 32
    for key in ("state_dtype", "state_layout", "gdn_gate", "gdn_heads",
                "gdn_qk", "gdn_convolution", "gdn_norm", "attention",
                "block", "rotary", "weights",
                "from_upstream_code_unverified", "serving_dtype",
                "kv_cache_dtype", "max_len", "gdn_chunk"):
        assert key in config["assumed"], key
    assert "CHECK FIRST" in config["assumed"]["block"]
    assert "CHECK FIRST" in config["assumed"]["rotary"]
    for key in ("deployment", "step_share", "bytes", "left_out",
                "limits_why", "engine_why"):
        assert config[key] and "PROVISIONAL" not in config[key], key


def test_the_catalogs_numbers_are_under_their_keys():
    """Every number of the catalog's ``config`` is in the file under the
    same key, but the one ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog, encoding="utf-8") as fh:
        row = next(json.loads(line) for line in fh
                   if '"Olmo-Hybrid-7B"' in line)
    config = real_config()
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key


def test_the_tiny_cell_runs_through_the_programs_constructor(copy):
    rc, result, out = helpers.rehearse(copy, "tiny_gdn", seed=2**31 + 91,
                                       seconds=2.0)
    assert rc == 0, out
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    assert result["device"]["platform"] == "cpu"
    assert "check served_gap_mean" in out
    assert "compiled inside the window" not in out


@pytest.mark.parametrize("part", sorted(FAULTS))
def test_a_program_that_lacks_a_part_is_not_correct(copy, part):
    """The oracle on the served path: with one part of the mathematics
    taken out of the program (the slot's state left as it was at every
    admission, the delta term, the doubled write strength, the decay,
    the full layers' norm of query and key, the output gate) the run
    ends, and misses a limit."""
    from benchmarks.reference import olmo_hybrid_7b as ref

    assert set(FAULTS) == set(ref.PARTS)
    rc, result, out = helpers.rehearse(copy, "tiny_gdn", seed=2**31 + 92,
                                       seconds=2.0, before=FAULTS[part])
    assert rc == 0, out
    assert result["correct"] is False, out
    assert "FAILED" in out and "check served_gap" in out


def test_a_program_without_the_model_fails_at_once(copy):
    """What the parent commit does with this cell: the driver imports
    the model first of all, and a program that lacks it ends the run
    with an ImportError before a weight is made."""
    cfg_path = os.path.join(copy, "benchmarks", "configs",
                            "tiny_olmo_hybrid.json")
    saved = open(cfg_path, encoding="utf-8").read()
    cfg = json.loads(saved)
    cfg["model"]["module"] = "bigdl_tpu.models.not_in_this_program"
    try:
        with open(cfg_path, "w", encoding="utf-8") as fh:
            json.dump(cfg, fh)
        rc, result, out = helpers.rehearse(copy, "tiny_gdn", seconds=1.0)
    finally:
        with open(cfg_path, "w", encoding="utf-8") as fh:
            fh.write(saved)
    assert rc != 0 and result is None
    assert "ModuleNotFoundError" in out
    assert "weights on the device" not in out


# ----------------------------------------------------------- hand counts
def test_operation_and_byte_counts_against_hand_counts():
    cfg = real_config()
    assert (f.linear_layers(cfg), f.full_layers(cfg)) == (3, 1)
    assert f.layer_kinds(cfg) == [False, False, False, True]
    assert f.state_values(cfg) == 30 * 96 * 192
    assert f.conv_channels(cfg) == 11520
    # ISSUE 48: S 2,211,840 B and the kept rows 138,240 B a slot and layer
    assert f.slot_state_bytes(cfg) == SLOT_BYTES == 3 * (2211840 + 138240)
    assert f.mixer_params(cfg) == 3840 * (2 * 2880 + 2 * 5760 + 60) \
        + 3840 * 5760
    assert 88.6e6 < f.mixer_params(cfg) < 88.8e6        # ISSUE: 88.75 M
    assert f.attention_params(cfg) == 4 * 3840 * 3840   # ISSUE: 58.98 M
    assert f.mlp_params(cfg) == 3 * 3840 * 11008        # ISSUE: 126.81 M
    assert f.row_values(cfg) == 3840 and f.head_dim(cfg) == 128
    matrices = f.matrix_params(cfg)
    assert matrices == pytest.approx(
        3 * f.mixer_params(cfg) + f.attention_params(cfg)
        + 4 * f.mlp_params(cfg) + 100352 * 3840)
    # the layers' 1.665 GB and the head's 0.77 GB in bfloat16
    assert 2.43e9 < 2 * matrices < 2.44e9
    state = 2 * 256 * SLOT_BYTES
    assert 3.60e9 < state < 3.62e9                      # ISSUE: 3.40 + 0.21
    assert f.slots_of(cfg, state) == 256
    ctx = 256 * 900.0
    attrs = {"state_bytes": state, "context_tokens": ctx}
    assert f.attn_bytes(cfg, 1.0, 2) == 2 * 3840 * 2
    assert 3.53e9 < f.attn_bytes(cfg, ctx, 2) < 3.55e9  # ISSUE: 3.54 GB
    assert f.attn_flops(cfg, ctx) == 2 * 2 * 30 * 128 * ctx
    assert f.state_flops(cfg, state) == 256 * 3 * (
        7 * 30 * 96 * 192 + 2 * 4 * 11520)
    got = f.step_bytes(cfg, attrs, 2)
    assert got == pytest.approx(state + 2 * matrices
                                + f.attn_bytes(cfg, ctx, 2))
    # ISSUE: about 9.6 GB, the state and the streamed rows 7 of them
    assert 9.5e9 < got < 9.7e9
    assert 0.73 < (state + f.attn_bytes(cfg, ctx, 2)) / got < 0.76
    v5e = peaks.peaks_for("TPU v5 lite")
    # bound by the bytes: 11.7 ms against 3 ms of multiplications
    assert roofline_seconds(f.step_flops(cfg, attrs), got, v5e) \
        == pytest.approx(got / 819e9)
    assert roofline_seconds(f.state_flops(cfg, state), state, v5e) \
        == pytest.approx(state / 819e9)
    assert roofline_seconds(f.attn_flops(cfg, ctx),
                            f.attn_bytes(cfg, ctx, 2), v5e) \
        == pytest.approx(f.attn_bytes(cfg, ctx, 2) / 819e9)


# ------------------------------------------------------ synthetic runs
def _run(spans, **kw):
    base = dict(config=real_config(), spans=spans, trace={"programs": {}},
                counters={"batch": 256, "weight_itemsize": 2,
                          "kv_itemsize": 2},
                peaks=peaks.peaks_for("TPU v5 lite"), extra={})
    base.update(kw)
    return types.SimpleNamespace(**base)


def _step(slots, ctx):
    return {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.025,
            "attrs": {"bucket": 128, "active": slots,
                      "context_tokens": ctx, "attn_rows_copied": 1.07 * ctx,
                      "state_bytes": 2 * slots * SLOT_BYTES}}


def test_readers_return_nothing_on_a_program_without_the_counts():
    """A trace without the scopes, spans without the counts, or no spans
    at all: every new reader returns None and raises nothing."""
    old = {"name": "serve.decode_step", "start": 0.0, "dur_s": 0.01,
           "attrs": {"bucket": 32, "active": 12}}
    for run in (_run([old]), _run([])):
        for name in NEW_METRICS:
            assert runner.metric_reader(name)(run) is None, name
    # with the counts and no device trace: nothing either
    run = _run([_step(256, 2.3e5)])
    for name in NEW_METRICS:
        assert runner.metric_reader(name)(run) is None, name


def test_the_readers_on_the_sample_traces_shape():
    """The recorded sample trace (another model's ``jit_step``: scopes of
    its own, none of this model's mixer): the readers find their program
    and nothing to read in it."""
    reduced = xplane.reduce(xplane.load_json(os.path.join(
        helpers.BENCH, "lib", "testdata", "small_trace.json")))
    run = _run([_step(256, 2.3e5)], trace=reduced)
    for name in NEW_METRICS[:4]:
        assert runner.metric_reader(name)(run) is None, name
    got = runner.metric_reader("gdn_step_roofline")(run)
    ms = xplane.program_ms_per_call(reduced, "step")
    assert (got is None) == (ms is None)


def test_roofline_readers_divide_the_least_time_by_the_scope(monkeypatch):
    spans = [_step(256, 230400), _step(255, 229000)]
    run = _run(spans)
    cfg, v5e = run.config, run.peaks
    times = {"gdn.proj": 1.2, "gdn.conv": 0.6, "gdn.state": 5.4,
             "attn": 9.0, "ffn": 1.1, "kv_write": 0.05, "dense": 1.4,
             "sample": 0.2}
    monkeypatch.setattr(
        hostgaps, "scope_ms_per_call",
        lambda r, program, scopes, scope: times[scope]
        if program == "jit_step" and scopes == f.SCOPES else None)
    monkeypatch.setattr(xplane, "program_ms_per_call",
                        lambda trace, program: 19.0)
    read = runner.metric_reader
    assert read("decode_device_ms.gdn")(run) == pytest.approx(7.2)
    assert read("decode_device_ms.gdn_attn")(run) == 9.0
    bw = v5e["hbm_bytes_per_s"]
    state = np.mean([s["attrs"]["state_bytes"] for s in spans]) / bw
    assert read("gdn_state_roofline")(run) == pytest.approx(
        100 * 1e3 * state / 6.0)
    attn = np.mean([f.attn_bytes(cfg, s["attrs"]["context_tokens"], 2)
                    for s in spans]) / bw
    assert read("gdn_attn_roofline")(run) == pytest.approx(
        100 * 1e3 * attn / 9.0)
    step = np.mean([f.step_bytes(cfg, s["attrs"], 2) for s in spans]) / bw
    got = read("gdn_step_roofline")(run)
    assert got == pytest.approx(100 * 1e3 * step / 19.0)
    for name in NEW_METRICS[2:]:
        assert 0.0 < read(name)(run) < 100.0
    # a scope that holds nothing reads as nothing, not as a share of 0
    times["attn"] = 0.0
    assert read("gdn_attn_roofline")(run) is None
    assert read("decode_device_ms.gdn_attn")(run) is None


# --------------------------------------------- the reference's control
def test_the_int8_control_and_every_part_separate_from_float32():
    import jax.numpy as jnp

    from benchmarks.reference import olmo_hybrid_7b as ref

    cfg = runner.load_json(os.path.join(helpers.DATA,
                                        "tiny_olmo_hybrid.json"))
    sizes = ref.sizes_of(cfg)
    assert sizes["full"] == (False, False, False, True)
    assert (sizes["lin_heads"], sizes["dk"], sizes["dv"]) == (6, 24, 48)
    params = ref.init_params(2**31 + 5, sizes, jnp.float32)
    assert params["l1"]["gdn"]["w_in"].shape == (2 * 144 + 2 * 288 + 12, 48)
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
