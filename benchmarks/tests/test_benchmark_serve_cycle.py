"""``lib/servecycle.py`` and the eight per-layer metrics of PR 34 on a
hand-made trace and span list: the three children cut
``attribute_serving``'s ``sync`` exactly, every reader reads nothing
from a program without the split (the parent's span log) and a number
with it, and the eight entries are declared with a reader."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import hostgaps, servecycle
from benchmarks.tests.helpers import REPO
from benchmarks.tests.test_benchmark_hostgaps import (a_run, chip, gaps_of,
                                                      rec, span, us)

SERVING_CELLS = ["gpt2xl_gen_heavy", "longcat_flash_long_gen",
                 "joyai_flash_draft_gen", "sdar_moe_block_gen"]
# name -> (unit, better, the end-to-end metric it should move)
METRICS = {
    "serve_dispatch_ms_per_step": ("ms", "lower", "serve_tokens_per_s"),
    "serve_wait_ms_per_step": ("ms", "higher", "serve_tokens_per_s"),
    "serve_read_ms_per_step": ("ms", "lower", "serve_tokens_per_s"),
    "serve_dry_steps.steady": ("%", "lower", "serve_tokens_per_s"),
    "serve_dry_steps.admit": ("%", "lower", "itl_p95_ms"),
    "idle_ms_per_step.dispatch": ("ms", "lower", "serve_tokens_per_s"),
    "idle_ms_per_step.wait": ("ms", "lower", "serve_tokens_per_s"),
    "idle_ms_per_step.read": ("ms", "lower", "serve_tokens_per_s"),
}


def cycle_case(split=True, n_chips=1):
    """``test_benchmark_hostgaps.serving_case``'s chip and parents
    (decode steps at [1000,1100] and [1300,1400] us, a prefill at
    [1150,1200]); with ``split`` their children, and a settled step's
    wait and read after the last emit, outside any parent."""
    mods = [("jit_step(5)", 1000, 100), ("jit_prefill(6)", 1150, 50),
            ("jit_step(5)", 1300, 100), ("jit__unstack(7)", 1500, 5)]
    ops = [("%fusion.1 = f32[] fusion()", s, d) for _, s, d in mods]
    live = [
        rec("serve.prep", 1, 960, 30, step=0),
        rec("serve.decode_step", 2, 990, 120, bucket=32, active=2),
        rec("serve.emit", 3, 1110, 10, step=0),
        rec("serve.admission", 4, 1125, 95, step=1, admitted=1),
        rec("serve.prefill", 5, 1140, 70, step=1),
        rec("serve.prep", 6, 1230, 50, step=1),
        rec("serve.decode_step", 7, 1280, 130, bucket=32, active=3),
        rec("serve.emit", 8, 1410, 30, step=1),
    ]
    if split:
        live += [
            # step 0: dispatched on a busy chip at 991-999, waits for
            # the step before until 1104, reads until 1109
            rec("serve.dispatch", 10, 991, 8, step=0, program="step",
                dry=0),
            rec("serve.wait", 11, 1000, 104, step=0, program="step"),
            rec("serve.read", 12, 1104, 5, step=0, program="step"),
            # the prefill: dispatch 1141-1152, wait to 1206, read to 1209
            rec("serve.dispatch", 13, 1141, 11, step=1, program="prefill",
                dry=1),
            rec("serve.wait", 14, 1152, 54, step=1, program="prefill"),
            rec("serve.read", 15, 1206, 3, step=1, program="prefill"),
            # step 1: on a dry chip, dispatch 1281-1302, wait to 1404,
            # read to 1408
            rec("serve.dispatch", 16, 1281, 21, step=1, program="step",
                dry=1),
            rec("serve.wait", 17, 1302, 102, step=1, program="step"),
            rec("serve.read", 18, 1404, 4, step=1, program="step"),
            # a settle reads the last step between two pumps
            rec("serve.wait", 19, 1450, 20, step=1, program="step"),
            rec("serve.read", 20, 1470, 5, step=1, program="step"),
        ]
    return [chip(i, ops, mods) for i in range(n_chips)], live


def run_of(live):
    """The ``Run`` a reader is given: the window's spans as
    ``harness.program_spans`` hands them on."""
    return a_run("serve_lm", [
        span(r["name"], r["dur_s"], **r["attrs"]) for r in live])


def test_the_children_and_the_rest_add_up_to_sync_to_the_nanosecond():
    chips, live = cycle_case()
    g = gaps_of(chips, live)
    cut = servecycle.cut_sync(g)
    # [1100,1150]: 1100-1104 wait, 1104-1109 read, 1109-1110 the step's
    #   own; 1140-1141 the prefill's own, 1141-1150 dispatch
    # [1200,1300]: 1200-1206 wait, 1206-1209 read, 1209-1210 own;
    #   1280-1281 own, 1281-1300 dispatch
    # [1400,1500]: 1400-1404 wait, 1404-1408 read, 1408-1410 own; the
    #   settled step's wait and read (1450-1475) are not sync's
    assert cut == {"dispatch": (9 + 19) * 1000, "wait": (4 + 6 + 4) * 1000,
                   "read": (5 + 3 + 4) * 1000,
                   "self": (1 + 1 + 1 + 1 + 2) * 1000, "chips": 1}
    assert sum(cut[b] for b in ("dispatch", "wait", "read", "self")) == \
        round(g.idle["sync"] * 1e9) == 60_000
    # the split leaves the accepted buckets as they were
    chips, before = cycle_case(split=False)
    assert gaps_of(chips, before).idle == g.idle


def test_four_chips_cut_like_one():
    one = servecycle.cut_sync(gaps_of(*cycle_case()))
    four = servecycle.cut_sync(gaps_of(*cycle_case(n_chips=4)))
    assert four == {k: 4 * v for k, v in one.items()}
    run = run_of(cycle_case()[1])
    for g in (gaps_of(*cycle_case()), gaps_of(*cycle_case(n_chips=4))):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(hostgaps, "for_run", lambda run: g)
            assert servecycle.idle_ms_per_step(run, "wait") == \
                pytest.approx(14e-3 / 2)


def test_readers_on_a_run_with_the_split(monkeypatch):
    read = bench_run.metric_reader
    chips, live = cycle_case()
    g = gaps_of(chips, live)
    monkeypatch.setattr(hostgaps, "for_run", lambda run: g)
    run = run_of(live)
    near = lambda v: pytest.approx(v, rel=1e-9)
    # two serve.decode_step spans; a settled step's wait and read count
    assert read("serve_dispatch_ms_per_step")(run) == near((8 + 21) / 2e3)
    assert read("serve_wait_ms_per_step")(run) == \
        near((104 + 102 + 20) / 2e3)
    assert read("serve_read_ms_per_step")(run) == near((5 + 4 + 5) / 2e3)
    # step 1 was dispatched dry and its cycle admitted; step 0 was not
    assert read("serve_dry_steps.admit")(run) == 50.0
    assert read("serve_dry_steps.steady")(run) == 0.0
    assert read("idle_ms_per_step.dispatch")(run) == near(28e-3 / 2)
    assert read("idle_ms_per_step.wait")(run) == near(14e-3 / 2)
    assert read("idle_ms_per_step.read")(run) == near(12e-3 / 2)
    parts = sum(read(f"idle_ms_per_step.{b}")(run)
                for b in ("dispatch", "wait", "read"))
    assert parts <= read("idle_ms_per_step.sync")(run) == near(60e-3 / 2)


def test_dry_steps_apart_by_what_the_cycle_admitted():
    spans = [span("serve.admission", 0.04, step=3, offered=2, admitted=1),
             span("serve.admission", 0.001, step=5, offered=1, admitted=0)]
    spans += [span("serve.dispatch", 0.001, step=k, program="step",
                   dry=int(k in (0, 3, 5, 6)))
              for k in range(8)]
    # a prefill's dispatch is no step's
    spans.append(span("serve.dispatch", 0.001, step=3, program="prefill",
                      dry=1))
    run = a_run("serve_lm", spans)
    assert servecycle.dry_share(run, admitting=True) == 100.0 * 1 / 8
    assert servecycle.dry_share(run, admitting=False) == 100.0 * 3 / 8


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_reads_nothing_without_the_split(name, monkeypatch):
    """The parent's span log: the accepted spans and none of the three
    children.  And a run with no spans and no raw trace at all."""
    read = bench_run.metric_reader(name)
    assert read(a_run("serve_lm")) is None
    assert read(a_run("train")) is None
    chips, live = cycle_case(split=False)
    g = gaps_of(chips, live)
    monkeypatch.setattr(hostgaps, "for_run", lambda run: g)
    assert g.idle_ms_per_step("sync") is not None
    assert read(run_of(live)) is None


def test_the_eight_are_declared_with_a_reader():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    declared = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for name, (unit, better, moves) in METRICS.items():
        m = declared[name]
        assert (m["unit"], m["better"], m["moves"]) == (unit, better, moves)
        assert m["source"] == "program_span"
        assert m["layer"] == "serving host loop"
        assert m["workloads"] == SERVING_CELLS and moves in e2e
        assert callable(bench_run.metric_reader(name))
    # the accepted buckets they sit beside keep their entries
    assert {"idle_ms_per_step.sync", "decode_step_wall_ms",
            "serve_host_ms_per_step", "prefill_wall_ms"} <= set(declared)


def test_the_sum_of_the_parts_is_the_step_span():
    """What the acceptance check reads on the chip: dispatch + wait +
    read of the pipelined steps against the mean ``serve.decode_step``."""
    _, live = cycle_case()
    live = [r for r in live if r["id"] not in (19, 20)]   # no settle
    run = run_of(live)
    parts = sum(servecycle.span_ms_per_step(run, c)
                for c in servecycle.CHILDREN)
    steps = [s["dur_s"] for s in run.spans
             if s["name"] == "serve.decode_step"]
    assert us(parts * 1e-3) == pytest.approx(
        us(sum(steps) / len(steps)) - 3)     # 3 us a step of span cost
