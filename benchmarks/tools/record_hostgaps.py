"""Record the small sample with a host plane that
``benchmarks/lib/testdata/hostgaps_sample.json`` keeps, and show what a
trace from the chip holds for ``lib/hostgaps.py`` to read.

    chiprun --chips 1 -- python3 benchmarks/tools/record_hostgaps.py

Two short runs of the program itself under ``jax.profiler``, with the
program's span tracer on (``BIGDL_TRACE_DIR``), each through its normal
entry point at a toy size:

* ``serve``: a 2-layer decoder behind ``LMEngine`` (4 slots), eight
  requests pumped on this thread; the sample keeps about a dozen decode
  steps with an admission among them;
* ``train``: a 3-layer MLP under ``LocalOptimizer.optimize()`` fed
  batches of 4 MB, so that the copy to the chip is long enough to see;
  the sample keeps the last steps.

Each sample is the plain form of ``lib/hostgaps.load`` (the chip's
``XLA Ops`` and ``XLA Modules`` lines, the host plane's annotations)
cut to a short window, with the tracer's span records that touch it;
both go into ``chiprun_out/hostgaps_sample/hostgaps_sample.json``.  The
output before that is for reading by hand: the planes and lines of the
trace, the stats an operation's event carries, and where the scope of a
``jax.named_scope`` shows.

    python3 benchmarks/tools/record_hostgaps.py --look <dir> <program>

reads the newest trace under ``<dir>`` (a cell's
``.bench_out/<cell>/profile`` after a ``--trace 1`` run) the same way,
and lists the program's operations by their own device time with the
scope each carries: where the compiler's copies land.
"""

from __future__ import annotations

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "hostgaps_sample")
SCOPES = ("kv_write", "attn", "dense", "sample")
NAME_CHARS = 64


def by_hand(pb: str, program: str):
    """What the raw trace looks like."""
    from jax.profiler import ProfileData

    shown = 0
    for plane in ProfileData.from_file(pb).planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("plane", plane.name, lines[:12],
              f"... {len(lines)} lines" if len(lines) > 12 else "")
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in list(line.events)[:3]:
                    print("  module", ev.name, ev.start_ns, ev.duration_ns,
                          dict(ev.stats))
            if line.name != "XLA Ops":
                continue
            seen = set()
            for ev in line.events:
                if ev.name in seen:
                    continue
                seen.add(ev.name)
                stats = dict(ev.stats)
                text = json.dumps(stats, default=str)
                head = ev.name.split(" = ")[0]
                if shown < 6 or (head.startswith("%copy") and shown < 40) \
                        or any(f"/{s}/" in text for s in SCOPES) \
                        and shown < 60:
                    shown += 1
                    print("  op", ev.name[:100], "|", text[:600])
    sys.stdout.flush()


def cut(trace: dict, records: list, t0: int, t1: int) -> dict:
    """The events that start inside [t0, t1) and the records whose span
    touches it; an operation's scope stat stays with its first event
    that is kept."""
    from benchmarks.lib import hostgaps

    stats = {}
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for ev in line["events"]:
                if len(ev) > 3 and "id" not in ev[3]:
                    stats.setdefault(ev[0], ev[3])
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            seen, events = set(), []
            for ev in line["events"]:
                if not t0 <= ev[1] < t1:
                    continue
                full = ev[0]
                # an operation's event is named by its whole HLO line;
                # the sample keeps its head, which tells operations apart
                ev = [full[:NAME_CHARS]] + list(ev[1:3]) + (
                    [ev[3]] if len(ev) > 3 and "id" in ev[3] else [])
                if full in stats and ev[0] not in seen:
                    seen.add(ev[0])
                    ev.append(stats[full])
                events.append(ev)
            if events:
                lines.append({"name": line["name"], "events": events})
        planes.append({"name": plane["name"], "lines": lines})
    spans, _ = hostgaps.place_spans(trace, records)
    keep = {s["id"] for s in spans if s["end"] > t0 and s["start"] < t1}
    return {"planes": planes,
            "records": [r for r in records if r["id"] in keep]}


def report(name: str, trace: dict, records: list, program: str):
    from benchmarks.lib import hostgaps, xplane

    gaps = hostgaps.HostGaps(trace, records)
    red = xplane.reduce(hostgaps.timing_only(trace))
    print(name, "offset_ns", gaps.offset_ns, "annotated",
          sum(1 for s in gaps.spans or [] if s["annotated"]), "of",
          len(gaps.spans or []))
    print(name, "window_s", red["window_s"], "busy_s", red["busy_s"],
          "idle", red["window_s"] - red["busy_s"])
    print(name, "attributed", json.dumps(gaps.idle))
    print(name, "programs", json.dumps(red["programs"]))
    print(name, "scopes", json.dumps(
        hostgaps.scope_seconds(trace, program, SCOPES)))
    sys.stdout.flush()


def newest_pb(tdir: str) -> str:
    return sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                            recursive=True))[-1]


def record_serve():
    import jax
    import numpy as np

    from benchmarks.lib import hostgaps
    from bigdl_tpu import obs
    from bigdl_tpu.models.transformer import build_transformer_lm
    from bigdl_tpu.serving import LMEngine

    model = build_transformer_lm(512, dim=256, n_head=4, n_layer=2,
                                 max_len=128)
    eng = LMEngine(model, max_batch=4, page_size=16, decode_attn="dense")
    rng = np.random.RandomState(0)
    prompt = lambda n: [int(t) for t in rng.randint(1, 512, size=n)]
    for n in (12, 20):           # every program the window will use
        eng.submit(prompt(n), 4)
    eng.run_until_idle()
    tdir = os.path.join(OUT, "raw_serve")
    jax.profiler.start_trace(tdir)
    try:
        for k in range(8):
            eng.submit(prompt(10 + 2 * k), 12 + k)
        eng.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    tracer = obs.get_tracer()
    tracer.flush()
    records = hostgaps.load_records(tracer.jsonl_path)
    pb = newest_pb(tdir)
    by_hand(pb, "jit_step")
    trace = hostgaps.load(pb, {r["name"] for r in records})
    report("serve (whole)", trace, records, "jit_step")
    steps = hostgaps._calls(hostgaps.chips_of(trace)[0], ("jit_step",))
    mid = len(steps) // 2
    sample = cut(trace, records, steps[mid - 6][1] - 200_000,
                 steps[mid + 6][2] + 200_000)
    report("serve (sample)", sample, sample["records"], "jit_step")
    return sample


def record_train():
    import jax
    import numpy as np

    from benchmarks.lib import hostgaps
    from bigdl_tpu import obs
    from bigdl_tpu.nn import (ClassNLLCriterion, Linear, LogSoftMax, ReLU,
                              Sequential)
    from bigdl_tpu.optim import SGD, Optimizer, Trigger

    rng = np.random.RandomState(1)
    x = rng.randn(8 * 256, 4096).astype(np.float32)
    y = (rng.randint(0, 10, size=len(x)) + 1).astype(np.float32)
    model = Sequential().add(Linear(4096, 2048)).add(ReLU()) \
        .add(Linear(2048, 2048)).add(ReLU()).add(Linear(2048, 10)) \
        .add(LogSoftMax())
    opt = Optimizer(model, (x, y), ClassNLLCriterion(), batch_size=256,
                    distributed=False)
    opt.set_optim_method(SGD(learningrate=0.01))
    opt.set_end_when(Trigger.max_epoch(3))
    n_before = len(hostgaps.load_records(obs.get_tracer().jsonl_path)) \
        if obs.get_tracer().enabled else 0
    tdir = os.path.join(OUT, "raw_train")
    jax.profiler.start_trace(tdir)
    try:
        opt.optimize()
    finally:
        jax.profiler.stop_trace()
    tracer = obs.get_tracer()
    tracer.flush()
    records = hostgaps.load_records(tracer.jsonl_path)[n_before:]
    pb = newest_pb(tdir)
    by_hand(pb, "jit_train_step")
    trace = hostgaps.load(pb, {r["name"] for r in records})
    report("train (whole)", trace, records, "jit_train_step")
    steps = hostgaps._calls(hostgaps.chips_of(trace)[0],
                            ("jit_train_step",))
    sample = cut(trace, records, steps[-9][1] - 200_000,
                 steps[-1][2] + 200_000)
    report("train (sample)", sample, sample["records"], "jit_train_step")
    return sample


def look(tdir: str, program: str) -> int:
    from benchmarks.lib import hostgaps

    pb = newest_pb(tdir)
    by_hand(pb, program)
    trace = hostgaps.load(pb, ())
    paths, total, count = hostgaps.paths_of(trace), {}, {}
    for chip in hostgaps.chips_of(trace):
        for _, m0, m1 in hostgaps._calls(chip, (program,)):
            inside = [op for op in chip["ops"] if m0 <= op[1] < m1]
            for op, own in zip(inside, hostgaps._own_times(inside)):
                total[op[0]] = total.get(op[0], 0) + own
                count[op[0]] = count.get(op[0], 0) + 1
    print(f"{program}: {len(total)} distinct operations; the 40 longest "
          f"by own device time (ms in the trace, events, scope path)")
    for name in sorted(total, key=total.get, reverse=True)[:40]:
        print(f"  {total[name] * 1e-6:10.3f} {count[name]:6d} "
              f"{name.split(' = ')[0][:40]:40s} {paths.get(name)}")
    print(program, "scopes", json.dumps(
        hostgaps.scope_seconds(trace, program, SCOPES)))
    return 0


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--look":
        return look(sys.argv[2], sys.argv[3])
    os.makedirs(OUT, exist_ok=True)
    os.environ["BIGDL_TRACE_DIR"] = os.path.join(OUT, "obs")
    import jax

    print("devices", [(d.platform, d.device_kind, d.id)
                      for d in jax.devices()])
    sample = {"serve": record_serve(), "train": record_train()}
    path = os.path.join(OUT, "hostgaps_sample.json")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(sample, fh, separators=(",", ":"))
    print("wrote", path, os.path.getsize(path), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
