"""``lib/hostgaps.py`` on hand-made traces (the clock offset, each
attribution rule, a gap that straddles spans, the scopes), on the
sample recorded on the chip with its host plane, and every per-layer
metric PR 24 added on a hand-made ``Run``."""

import json
import os
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import hostgaps, xplane
from benchmarks.tests.helpers import BENCH, REPO

SAMPLE = os.path.join(BENCH, "lib", "testdata", "hostgaps_sample.json")
US = 1000                     # the hand-made traces are written in us
OFFSET = 7_000_000_000        # profiler clock = tracer clock + 7 s
WALL0 = 1000.0                # tracer wall time of its clock's zero


# --------------------------------------------------------- trace builders
def rec(name, sid, start_us, dur_us, tid=1, **attrs):
    """A span record of the tracer's log, on the tracer's clock."""
    return {"kind": "span", "name": name, "id": sid, "parent": None,
            "tid": tid, "wall_time": WALL0 + start_us * 1e-6,
            "dur_s": dur_us * 1e-6, "attrs": attrs}


def ann(record, jitter_ns=0):
    """The annotation a live span leaves in the profiler's trace."""
    stats = {"id": record["id"]}
    if "step" in record["attrs"]:
        stats["step"] = record["attrs"]["step"]
    start = round((record["wall_time"] - WALL0) * 1e9) + OFFSET + jitter_ns
    return [record["name"], start, round(record["dur_s"] * 1e9), stats]


def at(us):
    return us * US + OFFSET


def chip(i, ops, mods):
    return {"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Ops", "events": [
            [n, at(s), d * US] + rest for n, s, d, *rest in ops]},
        {"name": "XLA Modules", "events": [
            [n, at(s), d * US] for n, s, d in mods]}]}


def trace_of(chips, live):
    return {"planes": chips + [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [ann(r) for r in live]}]}]}


def gaps_of(chips, live, retro=()):
    return hostgaps.HostGaps(trace_of(chips, live), list(live) + list(retro))


def reduced(planes):
    return xplane.reduce(hostgaps.timing_only({"planes": planes}))


def us(seconds):
    return seconds * 1e6


# ------------------------------------------------------------- the clock
def test_clock_offset_is_the_median_and_places_retroactive_spans():
    live = [rec("step_dispatch", 1, 100, 10, step=1),
            rec("step_dispatch", 2, 300, 10, step=2),
            rec("step_dispatch", 3, 500, 10, step=3)]
    retro = [rec("feed.h2d", 4, 200, 50, tid=2, step=2),
             rec("iteration", 5, -900, 100, step=0)]  # before the session
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ann(live[0], -400), ann(live[1], 0), ann(live[2], 90_000)]}]}
    spans, offset = hostgaps.place_spans({"planes": [host]}, live + retro)
    assert offset == pytest.approx(OFFSET - WALL0 * 1e9, abs=300)
    by_id = {s["id"]: s for s in spans}
    # a live span keeps its annotation's own time ...
    assert by_id[3]["start"] == at(500) + 90_000 and by_id[3]["annotated"]
    # ... the others are put on the profiler's clock by the offset
    assert by_id[4]["start"] == pytest.approx(at(200), abs=300)
    assert by_id[4]["end"] - by_id[4]["start"] == pytest.approx(50 * US,
                                                                abs=2)
    assert by_id[5]["start"] == pytest.approx(at(-900), abs=300)
    assert not by_id[4]["annotated"] and by_id[4]["tid"] == 2
    assert by_id[4]["step"] == 2


def test_no_annotation_no_clock():
    live = [rec("step_dispatch", 1, 100, 10, step=1)]
    none = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert hostgaps.place_spans(none, live) is None
    g = hostgaps.HostGaps(none, live)
    assert g.idle is None and g.idle_ms_per_step("loop") is None


# ------------------------------------------------------ training, by rule
def training_case():
    """One chip, steps 5-7 at [1000,1100], [1200,1300], [1420,1500] us,
    then a small program at [1600,1610]: gaps A [1100,1200], B
    [1300,1420], C [1500,1600]."""
    mods = [("jit_train_step(9)", 1000, 100), ("jit_train_step(9)", 1200,
                                               100),
            ("jit_train_step(9)", 1420, 80), ("jit_copy(3)", 1600, 10)]
    ops = [("%fusion.1 = f32[] fusion()", s, d) for _, s, d in mods]
    live = [
        rec("step_dispatch", 1, 940, 10, step=5),
        rec("step_dispatch", 2, 1150, 10, step=6),
        # gap A: the copy of step 6 still open until 1150 (retro, below),
        # then device_put(7) 1160-1180 inside iteration(6) 1145-1195
        rec("iteration", 3, 1145, 50, step=6),
        rec("device_put", 4, 1160, 20, step=7),
        # gap B: loss_readback 1300-1350, then nothing named until
        # batch_prep 1380-1400 (between two spans: the loop), dispatch
        rec("loss_readback", 5, 1300, 50, step=6),
        rec("batch_prep", 6, 1380, 20, step=8),
        rec("step_dispatch", 7, 1405, 10, step=7),
        # gap C: the loop's last span ends at 1520
        rec("loss_readback", 8, 1500, 20, step=7),
    ]
    retro = [rec("feed.h2d", 20, 1090, 60, tid=2, step=6),
             # a LATER step's copy open all through gap B: not the copy
             # of the step that starts after the gap
             rec("feed.h2d", 21, 1290, 200, tid=2, step=8),
             rec("feed.h2d", 22, 1200, 30, tid=2, step=7),
             rec("feed.gather", 23, 1000, 600, tid=3, step=9)]
    return [chip(0, ops, mods)], live, retro


def test_training_rules_first_match_wins():
    chips, live, retro = training_case()
    g = gaps_of(chips, live, retro)
    idle = g.idle
    assert idle["steps"] == 3
    # A: 1100-1150 h2d (also inside iteration: h2d wins), 1160-1180 feed,
    #    the rest of A (1150-1160, 1180-1200) loop
    # B: 1300-1350 loop, 1350-1380 between two spans -> loop,
    #    1380-1400 feed, 1400-1420 loop (between, then step_dispatch)
    # C: 1500-1520 loop, 1520-1600 after the loop's last span
    assert us(idle["h2d"]) == pytest.approx(50, abs=0.01)
    assert us(idle["feed"]) == pytest.approx(20 + 20, abs=0.01)
    assert us(idle["loop"]) == pytest.approx(30 + 100 + 20, abs=0.01)
    assert us(idle["unattributed"]) == pytest.approx(80, abs=0.01)
    assert g.idle_ms_per_step("h2d") == pytest.approx(0.050 / 3, rel=1e-3)
    assert g.idle_ms_per_step("sync") is None   # a serving bucket


def test_buckets_add_up_to_window_less_busy():
    chips, live, retro = training_case()
    g = gaps_of(chips, live, retro)
    red = reduced(chips)
    total = sum(v for k, v in g.idle.items() if k != "steps")
    assert total == pytest.approx(red["window_s"] - red["busy_s"],
                                  rel=1e-9)


def test_a_starved_chip_does_not_shift_the_steps():
    """The chip waits for its batch while the loop has already
    dispatched the step after: every execution starts after the NEXT
    step's dispatch, and still belongs to its own step."""
    mods = [("jit_sharded_step(1)", 1000 + 200 * k, 60) for k in range(4)]
    ops = [("%fusion.1 = f32[] fusion()", s, d) for _, s, d in mods]
    live, retro = [], []
    for k in range(5):
        step = 10 + k
        # dispatch of step n at 870 + 200k: before execution n-1 starts
        live.append(rec("step_dispatch", 1 + k, 870 + 200 * k, 5,
                        step=step))
        # its batch is ready exactly when its execution starts
        retro.append(rec("feed.h2d", 50 + k, 800 + 200 * k, 200, tid=2,
                         step=step))
    calls = hostgaps._calls(
        hostgaps.chips_of(trace_of([chip(0, ops, mods)], live))[0],
        hostgaps.TRAIN_PROGRAMS)
    spans, _ = hostgaps.place_spans(trace_of([], live), live + retro)
    assert hostgaps._steps_of_calls(calls, spans) == [10, 11, 12, 13]
    # and every gap is the copy's: 3 gaps of 140 us
    g = gaps_of([chip(0, ops, mods)], live, retro)
    assert us(g.idle["h2d"]) == pytest.approx(3 * 140, abs=0.01)


def test_four_chips_read_like_one():
    mods = [("jit_sharded_step(1)", 1000, 100),
            ("jit_sharded_step(1)", 1200, 100)]
    ops = [("%fusion.1 = f32[] fusion()", s, d) for _, s, d in mods]
    live = [rec("step_dispatch", 1, 950, 10, step=1),
            rec("iteration", 2, 1090, 120, step=2),
            rec("step_dispatch", 3, 1150, 10, step=2)]
    g = gaps_of([chip(i, ops, mods) for i in range(4)], live)
    assert g.idle["steps"] == 2
    assert us(g.idle["loop"]) == pytest.approx(100, abs=0.01)


def test_a_window_of_many_small_gaps_is_attributed_in_seconds():
    # the size of a traced ResNet-50 window: a step's few thousand
    # operations each leave a gap of some ns behind them, and the span
    # log holds the whole run's steps.  A pass per gap over the span
    # lists took minutes here and ran a traced run into the time limit
    # of the driver's check (PR 24's first refusal)
    import time

    steps, per_step, step_us, first = 40, 2500, 50_000, 860
    op_us = (step_us - 1000) // per_step
    mods = [("jit_train_step(9)", k * step_us, step_us - 1000)
            for k in range(steps)]
    ops = [[f"%fusion.{j} = f32[] fusion()", at(k * step_us + j * op_us),
            op_us * US - 5] for k in range(steps) for j in range(per_step)]
    one = chip(0, [], mods)
    one["lines"][0]["events"] = ops
    live, retro = [], []
    for n in range(first + steps):
        s0 = (n - first) * step_us
        (live if n >= first else retro).extend([
            rec("iteration", 10 * n, s0 - 3000, step_us - 10, step=n),
            rec("step_dispatch", 10 * n + 1, s0 - 2000, 1500, step=n),
            rec("loss_readback", 10 * n + 2, s0, 30_000, step=n - 1)])
        retro.append(rec("feed.h2d", 10 * n + 3, s0 - 40_000, 14_000,
                         tid=2, step=n))
    t0 = time.perf_counter()
    g = gaps_of([one], live, retro)
    assert time.perf_counter() - t0 < 20
    assert g.idle["steps"] == steps
    total = sum(g.idle[b] for b in ("h2d", "feed", "loop", "unattributed"))
    red = reduced([one])
    assert total == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)
    # every gap lies inside an iteration or between two; those during
    # which the next step's copy was still open go to the copy first
    assert g.idle["unattributed"] < 1e-6 and g.idle["feed"] == 0
    assert 0 < g.idle["h2d"] < g.idle["loop"]


# ------------------------------------------------------- serving, by rule
def serving_case():
    """Decode steps at [1000,1100] and [1300,1400], a prefill at
    [1150,1200]: gaps [1100,1150], [1200,1300], and [1400,1500] before a
    last small program."""
    mods = [("jit_step(5)", 1000, 100), ("jit_prefill(6)", 1150, 50),
            ("jit_step(5)", 1300, 100), ("jit__unstack(7)", 1500, 5)]
    ops = [("%fusion.1 = f32[] fusion()", s, d) for _, s, d in mods]
    live = [
        rec("serve.prep", 1, 960, 30, step=0),
        rec("serve.decode_step", 2, 990, 120, bucket=32, active=2),
        rec("serve.emit", 3, 1110, 10, step=0),
        # between two pumps: 1120-1125
        rec("serve.admission", 4, 1125, 95, step=1),
        rec("serve.prefill", 5, 1140, 70, step=1),
        rec("serve.prep", 6, 1230, 50, step=1),
        rec("serve.decode_step", 7, 1280, 130, bucket=32, active=3),
        rec("serve.emit", 8, 1410, 30, step=1),
    ]
    return [chip(0, ops, mods)], live


def test_serving_rules_first_match_wins():
    chips, live = serving_case()
    idle = gaps_of(chips, live).idle
    assert idle["steps"] == 2
    # [1100,1150]: 1100-1110 sync (decode_step), 1110-1120 emit,
    #   1120-1125 between pumps -> admit, 1125-1140 admission -> admit,
    #   1140-1150 prefill -> sync
    # [1200,1300]: 1200-1210 prefill -> sync, 1210-1220 admission,
    #   1220-1230 between -> admit, 1230-1280 prep, 1280-1300 sync
    # [1400,1500]: 1400-1410 sync, 1410-1440 emit, 1440-1500 nothing
    assert us(idle["sync"]) == pytest.approx(10 + 10 + 10 + 20 + 10,
                                             abs=0.01)
    assert us(idle["emit"]) == pytest.approx(10 + 30, abs=0.01)
    assert us(idle["admit"]) == pytest.approx(5 + 15 + 10 + 10, abs=0.01)
    assert us(idle["prep"]) == pytest.approx(50, abs=0.01)
    assert us(idle["unattributed"]) == pytest.approx(60, abs=0.01)


def test_neither_loop_nothing_to_attribute():
    mods = [("jit_step(5)", 1000, 100)]
    ops = [("%fusion.1 = f32[] fusion()", 1000, 100)]
    live = [rec("validation", 1, 900, 300)]
    g = gaps_of([chip(0, ops, mods)], live)
    assert g.spans and g.idle is None
    assert g.idle_ms_per_step("unattributed") is None


# ----------------------------------------------------------------- scopes
def scoped_chip():
    mods = [("jit_step(5)", 1000, 100), ("jit_step(5)", 1200, 100),
            ("jit_prefill(6)", 1400, 50)]
    path = lambda s: [{"tf_op": f"jit(step)/jit(main)/{s}/op"}]
    ops = []
    for k, base in enumerate((1000, 1200)):
        first = k == 0    # the scope stat rides on the first event only
        ops += [
            ("%copy.1 = bf16[] copy()", base, 20),           # no scope
            ("%fusion.2 = bf16[] fusion()", base + 20, 30,
             *(path("dense") if first else [])),
            ("%while.3 = () while()", base + 50, 30,
             *(path("attn") if first else [])),
            ("%fusion.4 = bf16[] fusion()", base + 55, 10,    # nested
             *(path("attn/softmax") if first else [])),
            ("%scatter.5 = bf16[] scatter()", base + 80, 10,
             *(path("kv_write") if first else [])),
            ("%argmax.6 = s32[] reduce()", base + 90, 5,
             *(path("sample") if first else [])),
        ]
    ops.append(("%fusion.9 = bf16[] fusion()", 1400, 50))
    return chip(0, ops, mods)


def test_scope_seconds_own_time_and_the_rest():
    trace = {"planes": [scoped_chip()]}
    res = hostgaps.scope_seconds(trace, "jit_step", hostgaps.DECODE_SCOPES)
    assert res["calls"] == 2
    assert us(res["dense"]) == pytest.approx(2 * 30)
    assert us(res["attn"]) == pytest.approx(2 * 30)   # while 20 + body 10
    assert us(res["kv_write"]) == pytest.approx(2 * 10)
    # the copy, sample and the 5 us in which nothing ran
    assert us(res["unscoped"]) == pytest.approx(2 * (20 + 5 + 5))
    per_call = sum(res[k] for k in ("dense", "attn", "kv_write",
                                    "unscoped")) / res["calls"]
    assert us(per_call) == pytest.approx(100)   # the module's own time
    assert hostgaps.scope_seconds(trace, "jit_absent", ("dense",)) is None
    g = hostgaps.HostGaps(trace, [])
    assert g.scope_ms_per_call("jit_step", hostgaps.DECODE_SCOPES,
                               "dense") == pytest.approx(0.030)
    # a program none of whose operations carries a scope reads nothing
    assert g.scope_ms_per_call("jit_prefill", hostgaps.DECODE_SCOPES,
                               "dense") is None


def test_scope_of_takes_the_first_known_part():
    assert hostgaps.scope_of("jit(step)/jit(main)/dense/dot_general",
                             ("dense", "attn")) == "dense"
    assert hostgaps.scope_of("jit(step)/jit(main)/attn/dense/x",
                             ("dense", "attn")) == "attn"
    assert hostgaps.scope_of("jit(step)/jit(main)/mul", ("dense",)) is None
    assert hostgaps.scope_of("jit(step)/kv_write/scatter:",
                             ("kv_write",)) == "kv_write"
    assert hostgaps.scope_of("kp:", ("dense",)) is None
    assert hostgaps.scope_of(None, ("dense",)) is None


# ---- the raw file: what ProfileData leaves out, read from the wire
def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def entry(key, value):
    return field(1, key) + field(2, value)


def test_op_paths_reads_tf_op_from_the_events_metadata():
    stat_names = {7: "flops", 300: "tf_op", 9: "source"}
    stat_meta = b"".join(
        field(5, entry(k, field(1, k) + field(2, name)))
        for k, name in stat_names.items())

    def op(mid, name, tf_op=None):
        stats = field(5, field(1, 7) + field(4, 123)) \
            + field(5, field(1, 9) + field(5, "engine.py:150"))
        if tf_op:
            stats += field(5, field(1, 300) + field(5, tf_op))
        return field(4, entry(mid, field(1, mid) + field(2, name)
                              + field(4, "display") + stats))

    device = field(1, 3) + field(2, "/device:TPU:0") + stat_meta \
        + op(1, "%fusion.2 = bf16[] fusion()", "jit(step)/dense/dot:") \
        + op(2, "%copy.15 = bf16[] copy()", "kp:") \
        + op(3, "%fusion.3 = bf16[] fusion()") \
        + field(3, field(2, "XLA Ops") + field(4, field(1, 1)
                                               + field(3, 5000)))
    host = field(2, "/host:CPU") + field(5, entry(1, field(2, "tf_op"))) \
        + field(4, entry(1, field(2, "not a device's")
                         + field(5, field(1, 1) + field(5, "x/y"))))
    raw = field(1, host) + field(1, device)
    assert hostgaps.op_paths(raw) == {
        "%fusion.2 = bf16[] fusion()": "jit(step)/dense/dot:",
        "%copy.15 = bf16[] copy()": "kp:"}
    # a plane without the stat, and a file with no plane at all
    assert hostgaps.op_paths(field(1, field(2, "/device:TPU:1"))) == {}
    assert hostgaps.op_paths(b"") == {}
    assert list(hostgaps._fields(field(3, 300) + field(9, "ab")
                                 + varint(6 << 3 | 1) + bytes(8))) == \
        [(3, 300), (9, b"ab"), (6, bytes(8))]


# ------------------------------------------- the sample from the chip
@pytest.fixture(scope="module")
def sample():
    with open(SAMPLE, encoding="utf-8") as fh:
        return json.load(fh)


@pytest.mark.parametrize("kind,buckets,program", [
    ("serve", ("prep", "emit", "admit", "sync"), "jit_step"),
    ("train", ("h2d", "feed", "loop"), "jit_train_step")])
def test_sample_recorded_on_the_chip(sample, kind, buckets, program):
    part = sample[kind]
    g = hostgaps.HostGaps(part, part["records"])
    assert g.spans is not None
    assert sum(1 for s in g.spans if s["annotated"]) >= 10
    assert any(not s["annotated"] for s in g.spans) or kind == "serve"
    red = reduced(part["planes"])
    assert red["chips"] == 1 and red["programs"][program]["calls"] >= 8
    assert g.idle["steps"] == red["programs"][program]["calls"]
    total = sum(g.idle[b] for b in buckets) + g.idle["unattributed"]
    assert total == pytest.approx(red["window_s"] - red["busy_s"],
                                  rel=1e-6)
    assert all(g.idle[b] >= 0 for b in buckets)
    # the program's loop covers what the chip waited for
    assert g.idle["unattributed"] < 0.1 * total
    assert max(g.idle[b] for b in buckets) > 0


def test_sample_decode_step_by_scope(sample):
    part = sample["serve"]
    res = hostgaps.scope_seconds(part, "jit_step", hostgaps.DECODE_SCOPES)
    assert res["scoped_ops"] > 0
    for scope in hostgaps.DECODE_SCOPES:
        assert res[scope] > 0, scope
    red = reduced(part["planes"])
    whole = sum(res[k] for k in hostgaps.DECODE_SCOPES + ("unscoped",))
    assert whole == pytest.approx(red["programs"]["jit_step"]["seconds"],
                                  rel=0.02)


# ------------------------------------------------ the metrics' readers
def new_metrics():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    names = [m["name"] for m in bench["per_layer"]]
    return bench, names[names.index("loss_wait_ms_per_step"):]


def span(name, dur_s, **attrs):
    return {"name": name, "start": 0.0, "dur_s": dur_s, "attrs": attrs}


def a_run(kind, spans=(), profile=None):
    return bench_run.Run(config={"kind": kind}, spans=list(spans),
                         counters={}, trace={}, e2e={},
                         extra={"profile": profile})


# readers kept beside the declared ones, as ``prefill_device_ms`` is:
# the driver's compile cache hands a traced run the parent's executable
# of ``jit_step``, whose operations carry no scope (PERF.md section 7)
UNDECLARED = ["decode_device_ms.kv_write", "decode_device_ms.attn",
              "decode_device_ms.dense", "decode_device_ms.unscoped"]


def test_every_new_metric_is_declared_with_a_reader_and_reads_nothing():
    bench, names = new_metrics()
    assert len(names) == 14 and not set(names) & set(UNDECLARED)
    names = names + UNDECLARED
    cells = {c["name"] for c in bench["workloads"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    assert layers <= {"trainer host loop", "feed path", "serving host loop",
                      "model math", "kernels", "collectives",
                      "compile cache", "device"}
    for m in bench["per_layer"][-14:]:
        assert set(m["workloads"]) <= cells and m["unit"] == "ms"
    empty = a_run("train")
    for name in names:
        # no spans, no raw trace: nothing to read, and no error
        assert bench_run.metric_reader(name)(empty) is None, name


def test_span_metrics_on_a_hand_made_run():
    read = bench_run.metric_reader
    train = a_run("train", [
        span("step_dispatch", 0.001, step=1), span("step_dispatch", 0.001,
                                                   step=2),
        span("loss_readback", 0.040, step=1), span("loss_readback", 0.030,
                                                   step=2),
        span("feed.h2d", 0.030, step=1), span("feed.h2d", 0.050, step=2),
        span("feed.h2d", 0.034, step=3),
        span("feed.gather", 0.020, step=3), span("feed.gather", 0.024,
                                                 step=4)])
    assert read("loss_wait_ms_per_step")(train) == pytest.approx(35.0)
    assert read("h2d_ms_per_step")(train) == pytest.approx(34.0)
    assert read("gather_ms_per_step")(train) == pytest.approx(22.0)
    assert read("prefill_wall_ms")(train) is None
    assert read("serve_host_ms_per_step")(train) is None
    serve = a_run("serve", [
        span("serve.decode_step", 0.060), span("serve.decode_step", 0.060),
        span("serve.prep", 0.002, step=0), span("serve.prep", 0.003, step=1),
        span("serve.emit", 0.001, step=0), span("serve.emit", 0.001, step=1),
        span("serve.admission", 0.045, step=1),
        span("serve.prefill", 0.040, step=1),
        span("serve.prefill", 0.044, step=1)])
    assert read("prefill_wall_ms")(serve) == pytest.approx(42.0)
    assert read("serve_host_ms_per_step")(serve) == pytest.approx(
        (5 + 2 + 45 - 84) / 2)
    assert read("loss_wait_ms_per_step")(serve) is None
    assert read("h2d_ms_per_step")(serve) is None
    assert read("gather_ms_per_step")(serve) is None


def test_trace_metrics_on_a_hand_made_run(monkeypatch):
    read = bench_run.metric_reader
    chips, live, retro = training_case()
    monkeypatch.setattr(hostgaps, "for_run",
                        lambda run: gaps_of(chips, live, retro))
    train = a_run("train")
    near = lambda v: pytest.approx(v, rel=1e-3)
    assert read("idle_ms_per_step.h2d")(train) == near(0.050 / 3)
    assert read("idle_ms_per_step.feed")(train) == near(0.040 / 3)
    assert read("idle_ms_per_step.loop")(train) == near(0.150 / 3)
    assert read("idle_ms_per_step.unattributed.train")(train) == \
        near(0.080 / 3)
    assert read("idle_ms_per_step.unattributed.serve")(train) is None
    for bucket in ("prep", "emit", "admit", "sync"):
        assert read(f"idle_ms_per_step.{bucket}")(train) is None
    for scope in ("kv_write", "attn", "dense", "unscoped"):
        assert read(f"decode_device_ms.{scope}")(train) is None

    chips, live = serving_case()
    chips = [scoped_chip()]
    monkeypatch.setattr(hostgaps, "for_run", lambda run: gaps_of(chips, live))
    serve = a_run("serve")
    assert read("idle_ms_per_step.prep")(serve) is not None
    assert read("idle_ms_per_step.unattributed.serve")(serve) is not None
    assert read("idle_ms_per_step.unattributed.train")(serve) is None
    assert read("idle_ms_per_step.h2d")(serve) is None
    assert read("decode_device_ms.dense")(serve) == pytest.approx(0.030)
    assert read("decode_device_ms.attn")(serve) == pytest.approx(0.030)
    assert read("decode_device_ms.kv_write")(serve) == pytest.approx(0.010)
    assert read("decode_device_ms.unscoped")(serve) == pytest.approx(0.030)


def test_for_run_without_a_raw_trace(tmp_path):
    assert hostgaps.for_run(a_run("train")) is None
    empty = types.SimpleNamespace(dir=str(tmp_path))
    assert hostgaps.for_run(a_run("train", profile=empty)) is None
    assert hostgaps.idle_ms_per_step(a_run("serve", profile=empty),
                                     "sync") is None
