"""``lib/stalls.py`` and the three per-layer metrics of PR 50 on
hand-made runs (one 2 s stall in a 45 s window, a profiler's stall left
out, none, a program without the watch, a machine without ``schedstat``,
a stall across the window's end) and on the sample recorded on the chip
(a run of ``gpt2xl_gen_heavy`` stopped for a second inside its window by
``tools/provoke_stall.py``); a traced run of every kind of cell,
rehearsed on the CPU, reports each metric declared for it; the existing
readers of the host's loop (``hostgaps.attribute_*``,
``servecycle.cut_sync``) give what they gave with the watch's spans in
their input; the three entries are declared with a reader each;
``tools/stalls.py`` prints the sample's stall.

(ISSUE 50 asked for two more, ``loop_runq_share.serve|train``, over
``obs.host``'s ``loop_runq_ms``.  The chip's machines keep no
``schedstat``, so their reader found nothing to read in any cell and
the check refused the result line that lacked them: they are not
declared, and have no reader.)"""

import json
import os
import shutil

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import hostgaps, servecycle, stalls
from benchmarks.tests.helpers import BENCH, REPO
from benchmarks.tests.test_benchmark_hostgaps import SAMPLE as HOSTGAPS_SAMPLE
from benchmarks.tools import stalls as stalls_tool

SAMPLE = os.path.join(BENCH, "lib", "testdata", "stalls_sample.json")
SERVING_CELLS = ["gpt2xl_gen_heavy", "longcat_flash_long_gen",
                 "joyai_flash_draft_gen", "sdar_moe_block_gen",
                 "zaya1_cca_long_gen", "falcon_h1_ssm_long_gen",
                 "ling3_kda_long_gen", "olmo_hybrid_gdn_long_gen"]
TRAINING_CELLS = ["resnet50_train_1chip", "resnet50_distri_4chip"]
# name -> (unit, source, layer, moves, cells, loop)
METRICS = {
    "host_stall_share.serve": ("%", "program_span", "serving host loop",
                               "serve_tokens_per_s", SERVING_CELLS),
    "host_stall_share.train": ("%", "program_span", "trainer host loop",
                               "train_samples_per_s", TRAINING_CELLS),
    "host_wake_late_ms.serve": ("ms", "program_counter",
                                "serving host loop", "itl_p95_ms",
                                SERVING_CELLS),
}
WINDOW_S = 45.0
T0 = 5000.0     # the window's start on the spans' clock


# ------------------------------------------------------------ hand-made
def host(second, loop="serve"):
    """The ``obs.host`` span of one second of the window: 50 ticks, 5 ms
    late in all, the loop's thread 2 ms without a core."""
    return {"name": "obs.host", "start": T0 + second, "dur_s": 1.0,
            "attrs": {"loop": loop, "tid": 1, "ticks": 50,
                      "late_ms_sum": 5.0, "late_ms_max": 1.0,
                      "loop_cpu_ms": 400.0, "loop_runq_ms": 2.0,
                      "proc_cpu_ms": 900.0, "nivcsw": 3, "gc_ms": 0.0}}


def stall(at, dur_s, cause="blocked", loop="serve"):
    return {"name": "obs.stall", "start": T0 + at, "dur_s": dur_s,
            "attrs": {"loop": loop, "tid": 1, "stall": 1, "phase":
                      "serve.prep", "span": 7, "step": 3, "cause": cause,
                      "frame": "engine.py:_step", "samples": 20}}


def a_run(spans, loop="serve", seconds=45):
    """A run whose loop beat all through the window: its first span
    starts with the window."""
    marker = {"serve": "serve.decode_step", "train": "iteration"}[loop]
    first = {"name": marker, "start": T0, "dur_s": 0.004, "attrs": {}}
    hosts = [host(s, loop) for s in range(seconds)]
    return bench_run.Run(config={"kind": "anything"}, window_s=WINDOW_S,
                         spans=[first] + hosts + list(spans), counters={},
                         trace={}, e2e={}, extra={})


def read(name, run):
    return bench_run.metric_reader(name)(run)


@pytest.mark.parametrize("loop", ["serve", "train"])
def test_one_two_second_stall_in_the_window(loop):
    run = a_run([stall(20.0, 2.0, loop=loop)], loop)
    other = {"serve": "train", "train": "serve"}[loop]
    assert read(f"host_stall_share.{loop}", run) == \
        pytest.approx(100 * 2.0 / 45.0)       # 4.44 %: the bound's size
    # the other loop's reader finds no span of its own: not its kind of run
    assert read(f"host_stall_share.{other}", run) is None
    late = read("host_wake_late_ms.serve", run)
    assert late == (pytest.approx(5.0 / 50) if loop == "serve" else None)


def test_a_profilers_stall_is_left_out_and_none_reads_zero():
    run = a_run([stall(10.0, 2.0), stall(40.0, 0.6, cause="profiler"),
                 stall(30.0, 0.25, cause="process_stopped")])
    assert read("host_stall_share.serve", run) == \
        pytest.approx(100 * 2.25 / 45.0)
    only_the_session = a_run([stall(40.0, 0.6, cause="profiler")])
    assert read("host_stall_share.serve", only_the_session) == 0.0
    assert read("host_stall_share.serve", a_run([])) == 0.0
    assert stalls.NOT_COUNTED == ("profiler",)


def test_a_program_without_the_watch_reads_nothing():
    """The parent's span log: no ``obs.host``, so no reader of the three
    has anything to read, whatever else the run holds; nor on no spans
    at all."""
    run = a_run([])
    run.spans = [s for s in run.spans if s["name"] != "obs.host"] \
        + [stall(10.0, 2.0)]
    empty = bench_run.Run(config={}, window_s=WINDOW_S, spans=[],
                          counters={}, trace={}, e2e={}, extra={})
    for name in METRICS:
        assert read(name, run) is None, name
        assert read(name, empty) is None, name


def test_without_schedstat_the_three_read_what_they_read():
    """The chip's machines: ``obs.host`` without ``loop_runq_ms`` (and,
    in the sample's day, without ``loop_cpu_ms``).  No reader asks for
    either."""
    run = a_run([stall(20.0, 2.0)])
    for s in run.spans:
        if s["name"] == "obs.host":
            del s["attrs"]["loop_cpu_ms"], s["attrs"]["loop_runq_ms"]
    assert read("host_stall_share.serve", run) == \
        pytest.approx(100 * 2.0 / 45.0)
    assert read("host_wake_late_ms.serve", run) == pytest.approx(0.1)


def test_spans_are_clipped_to_the_window():
    """A stall that begins inside the window and ends after it counts
    up to the window's end, and one wholly after it not at all."""
    run = a_run([stall(44.0, 3.0)])
    assert read("host_stall_share.serve", run) == \
        pytest.approx(100 * 1.0 / 45.0)
    assert read("host_stall_share.serve", a_run([stall(45.5, 1.0)])) == 0.0
    assert stalls.window(run) == (T0, T0 + WINDOW_S)


def test_the_readers_test_no_kind():
    """They read ``loop=``: a serving configuration of any ``kind``."""
    run = a_run([stall(20.0, 2.0)])
    for kind in ("serve", "serve_lm", "serve_lm_block", "train"):
        run.config = {"kind": kind}
        assert read("host_stall_share.serve", run) == \
            pytest.approx(100 * 2.0 / 45.0)


# ------------------------------------------- the sample from the chip
@pytest.fixture(scope="module")
def sample():
    with open(SAMPLE, encoding="utf-8") as fh:
        return json.load(fh)


def sample_run(sample):
    """The sample as ``harness.program_spans`` hands it to a reader."""
    lo, hi = sample["window"]
    spans = [{"name": r["name"], "start": r["wall_time"],
              "dur_s": r["dur_s"], "attrs": r.get("attrs") or {}}
             for r in sample["records"]
             if r["kind"] == "span" and lo <= r["wall_time"] <= hi]
    return bench_run.Run(config={"kind": "serve"}, window_s=hi - lo,
                         spans=spans, counters={}, trace={}, e2e={},
                         extra={})


def test_sample_recorded_on_the_chip(sample):
    """One run of ``gpt2xl_gen_heavy`` stopped for a second, 12 s into
    its window: the watch slept through it too, and says so."""
    run = sample_run(sample)
    stop = max((s for s in run.spans if s["name"] == "obs.stall"),
               key=lambda s: s["dur_s"])
    assert stop["attrs"]["cause"] == "process_stopped"
    assert 0.95 <= stop["dur_s"] <= 1.3 and stop["attrs"]["loop"] == "serve"
    assert stop["attrs"]["watch_late_ms"] >= 900
    share = read("host_stall_share.serve", run)
    counted = sum(s["dur_s"] for s in run.spans if s["name"] == "obs.stall"
                  and s["attrs"]["cause"] != "profiler")
    assert share == pytest.approx(100 * counted / run.window_s, rel=1e-3)
    assert 100 * stop["dur_s"] / run.window_s <= share < 4.0
    hosts = [s for s in run.spans if s["name"] == "obs.host"]
    assert 40 <= len(hosts) <= 46
    # the chip's machines run a sandboxed kernel whose /proc keeps no
    # schedstat: the watch leaves the run-queue wait out, which is why
    # no metric is declared over it
    assert not any("loop_runq_ms" in s["attrs"] for s in hosts)
    assert 0.0 < read("host_wake_late_ms.serve", run) < 50.0
    assert read("host_stall_share.train", run) is None


def test_the_tool_prints_the_samples_stall(sample):
    text = stalls_tool.render(sorted(sample["records"],
                                     key=lambda r: r["wall_time"]))
    assert "of the serve loop:" in text and "process_stopped" in text
    assert "serve loop (pid" in text and "late" in text
    assert stalls_tool.render([]) == "no records"


# ------------------------------------------------ a rehearsed traced run
# the CPU has no device plane, so a traced rehearsal is refused before
# any reader runs: stand in for the reduced trace and the peaks, and let
# the three readers alone read
STAND_IN = f"""
from benchmarks import run as _run
from benchmarks.lib import harness as _harness, peaks as _peaks
_harness.Profile.reduce = lambda self: {{
    "busy_s": 1.0, "window_s": 2.0, "ops": {{}}, "idle_gaps": {{}}}}
_peaks.peaks_for = lambda kind: {{}}
_reader = _run.metric_reader
_run.metric_reader = lambda name: _reader(name) \\
    if name in {list(METRICS)!r} else (lambda run: None)
"""
# the tiny twin of every cell of the benchmark: (the tiny configuration
# of tests/data or None where helpers.make_copy brings the cell, the
# cell it stands for, its loop, devices)
TWINS = {
    "tiny_serve": (None, "gpt2xl_gen_heavy", "serve", 1),
    "tiny_train": (None, "resnet50_train_1chip", "train", 1),
    "tiny_distri": (None, "resnet50_distri_4chip", "train", 4),
    "tiny_lm": ("tiny_longcat", "longcat_flash_long_gen", "serve", 1),
    "tiny_draft": ("tiny_joyai", "joyai_flash_draft_gen", "serve", 1),
    "tiny_block": ("tiny_sdar", "sdar_moe_block_gen", "serve", 1),
    "tiny_state": ("tiny_zaya", "zaya1_cca_long_gen", "serve", 1),
    "tiny_hybrid": ("tiny_falcon_h1", "falcon_h1_ssm_long_gen", "serve", 1),
    "tiny_kda": ("tiny_ling", "ling3_kda_long_gen", "serve", 1),
    "tiny_gdn": ("tiny_olmo_hybrid", "olmo_hybrid_gdn_long_gen", "serve", 1),
}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``helpers.make_copy`` and, on top, a tiny cell for every
    configuration it does not bring: a file and entries, nothing
    edited."""
    from benchmarks.tests import helpers

    copy = helpers.make_copy(str(tmp_path_factory.mktemp("bench_stalls")))
    path = os.path.join(copy, "BENCHMARK.json")
    bench = bench_run.load_json(path)
    for cell, (config, stands_for, _, _) in TWINS.items():
        if config is None:
            continue
        shutil.copy(os.path.join(helpers.DATA, config + ".json"),
                    os.path.join(copy, "benchmarks", "configs"))
        bench["configs"].append(
            {"name": config, "source": "tests", "reduced": [], "why": "test",
             "file": f"benchmarks/configs/{config}.json"})
        bench["workloads"].append(
            {"name": cell, "config": config, "traffic": "tiny_closed4",
             "chips": 1, "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if stands_for in m.get("workloads", ()):
                m["workloads"].append(cell)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(bench, fh, indent=1)
    return copy


@pytest.mark.parametrize("cell", list(TWINS))
def test_a_traced_run_reports_its_loops_metrics(copy, cell):
    """End to end on the CPU, for the tiny twin of every cell: the traced
    run's tracer is on, the loop is minded, ``harness.program_spans``
    hands the watch's spans to the readers, and the result line holds
    every one of the three that is declared for the cell (the check
    refuses a traced run whose line lacks one)."""
    from benchmarks.tests import helpers

    _, stands_for, loop, chips = TWINS[cell]
    rc, result, out = helpers.rehearse(copy, cell, seed=2**31 + 50,
                                       seconds=3.0, trace=1, chips=chips,
                                       before=STAND_IN)
    assert rc == 0 and result["correct"] is True, out
    mine = [name for name, entry in METRICS.items()
            if stands_for in entry[4]]
    assert mine == [name for name in METRICS if name.endswith("." + loop)]
    assert set(result["metrics"]) == set(mine)
    # (a CPU's step may outlast the loop's limit: a share, not a zero)
    assert 0.0 <= result["metrics"][f"host_stall_share.{loop}"]["value"] \
        <= 100.0
    if loop == "serve":
        assert result["metrics"]["host_wake_late_ms.serve"]["value"] >= 0.0


# ------------------------------------ the existing readers are unmoved
def watch_spans(records, watch_tid=99):
    """What the watch would have written beside ``records``, on a line
    of its own: an ``obs.host`` over all of them and an ``obs.stall``
    across the longest span of the loop's thread."""
    lo = min(r["wall_time"] for r in records)
    hi = max(r["wall_time"] + r["dur_s"] for r in records)
    longest = max((r for r in records if r["tid"] == 1),
                  key=lambda r: r["dur_s"])
    top = max(r["id"] for r in records)
    base = {"kind": "span", "parent": None, "tid": watch_tid,
            "host": 0, "pid": records[0].get("pid")}
    return [
        dict(base, name="obs.host", id=top + 1, wall_time=lo,
             dur_s=hi - lo, attrs={"loop": "serve", "tid": 1, "ticks": 50,
                                   "late_ms_sum": 4.0, "late_ms_max": 1.0,
                                   "loop_runq_ms": 1.0}),
        dict(base, name="obs.stall", id=top + 2,
             wall_time=longest["wall_time"], dur_s=longest["dur_s"],
             attrs={"loop": "serve", "tid": 1, "stall": 1,
                    "phase": longest["name"], "span": longest["id"],
                    "cause": "blocked"}),
    ]


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_the_host_loops_buckets_are_unmoved(kind):
    """``hostgaps_sample.json`` with the watch's spans in it gives the
    buckets it gave: they lie on the watch's line, and no reader of the
    loop's line takes a span by anything but its name and line."""
    with open(HOSTGAPS_SAMPLE, encoding="utf-8") as fh:
        part = json.load(fh)[kind]
    before = hostgaps.HostGaps(part, part["records"])
    after = hostgaps.HostGaps(part,
                              part["records"] + watch_spans(part["records"]))
    assert before.idle is not None and after.idle == before.idle
    assert after.offset_ns == before.offset_ns
    assert len(after.spans) == len(before.spans) + 2
    chips = hostgaps.chips_of(part)
    attribute = {"serve": hostgaps.attribute_serving,
                 "train": hostgaps.attribute_training}[kind]
    assert attribute(chips, after.spans) == attribute(chips, before.spans)
    if kind == "serve":
        assert servecycle.cut_sync(after) == servecycle.cut_sync(before)
        tid, _ = hostgaps._loop_thread(
            after.spans, hostgaps.SERVE_DECODE, (hostgaps.SERVE_DECODE,))
        assert tid == 1


# -------------------------------------------------------- declarations
def test_the_three_are_declared_with_a_reader():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    declared = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"][:-3]}
    for name, (unit, source, layer, moves, cells) in METRICS.items():
        m = declared[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (unit, "lower", source, layer, moves)
        assert m["workloads"] == cells and layer in layers
        # every cell listed reports the end-to-end metric it moves
        assert set(cells) <= set(e2e[moves]["workloads"])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert callable(bench_run.metric_reader(name))
    # appended at the end, in the issue's order
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(METRICS)
    # a reader that finds nothing to read on the chip's machines is not
    # declared: the check holds a traced run to every metric of its cell
    assert not [n for n in declared if n.startswith("loop_runq_share")]
    assert not [f for f in os.listdir(os.path.join(BENCH, "metrics"))
                if f.startswith("loop_runq_share")]
