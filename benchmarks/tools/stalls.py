"""Print what the program's stall watch caught in a traced run (PR 50).

    python benchmarks/tools/stalls.py <trace dir> [--stacks N] [--min-ms M]

``<trace dir>`` holds the tracer's ``*.events.jsonl`` (a traced run of
the benchmark leaves it under ``.bench_out/<cell>/obs``).  One block a
pause (``obs.stall``, ``bigdl_tpu/obs/prof.py``): where the loop's
thread stood and the cause, the process's native threads over it
(``obs.stall.threads``: the TPU runtime's and the compiler's threads are
seen here and nowhere else), and every Python thread's stack as the
samples (``obs.stall.sample``) saw it, equal stacks folded and counted
over the samples.  Then a line a minded loop from its ``obs.host``
spans: how late the watch woke, and how long the loop's thread waited
for a core.  Hand-run; no driver calls it.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys
import time


def load(trace_dir: str) -> list:
    """Every record of the directory's ``*.events.jsonl``, by time."""
    records = []
    for path in sorted(glob.glob(os.path.join(trace_dir, "*.events.jsonl"))):
        with open(path, encoding="utf-8") as fh:
            records.extend(json.loads(line) for line in fh if line.strip())
    return sorted(records, key=lambda r: r["wall_time"])


def stalls_of(records: list) -> list:
    """``(span, samples, threads table or None)`` for each ``obs.stall``,
    joined by process, loop and the stall's number."""
    events = collections.defaultdict(list)
    for r in records:
        if r["name"] in ("obs.stall.sample", "obs.stall.threads"):
            a = r["attrs"]
            events[(r.get("pid"), a["loop"], a["stall"], r["name"])].append(r)
    out = []
    for r in records:
        if r["name"] == "obs.stall" and r["kind"] == "span":
            key = (r.get("pid"), r["attrs"]["loop"], r["attrs"]["stall"])
            tables = events.get(key + ("obs.stall.threads",))
            out.append((r, events.get(key + ("obs.stall.sample",), []),
                        tables[0] if tables else None))
    return out


def folded(samples: list, loop: bool) -> list:
    """``(samples seen in, threads, phase, stack)`` of the distinct
    stacks of the loop's thread (``loop``) or of all the others, most
    seen first."""
    seen: dict = {}
    for s in samples:
        for st in s["attrs"]["stacks"]:
            if bool(st["loop"]) != loop:
                continue
            key = (st["phase"], tuple(st["stack"]))
            e = seen.setdefault(key, [0, 0, st["names"]])
            e[0] += 1
            e[1] = max(e[1], st["threads"])
    return sorted(((n, threads, names, phase, stack)
                   for (phase, stack), (n, threads, names) in seen.items()),
                  key=lambda e: (-e[0], -e[1]))


def render_stall(span, samples, table, stacks: int, t0: float) -> list:
    a = span["attrs"]
    at = time.strftime("%H:%M:%S", time.gmtime(span["wall_time"]))
    lines = [
        f"== stall {a['stall']} of the {a['loop']} loop: "
        f"{1e3 * span['dur_s']:.1f} ms, {a['cause']} ==",
        f"  at {at} UTC (+{span['wall_time'] - t0:.3f} s into the log), "
        f"pid {span.get('pid')}, in span {a.get('phase') or '(none)'!r} "
        f"(id {a.get('span')}, step {a.get('step')})",
        f"  loop thread: {a.get('loop_state', '?')} in "
        f"{a.get('frame') or '(not sampled)'} "
        f"({a.get('frames_distinct', 0)} distinct leaf frames); on a core "
        f"{a.get('loop_cpu_ms', 'n/a')} ms, waiting for one "
        f"{a.get('loop_runq_ms', 'n/a')} ms",
        f"  process: {a['proc_cpu_ms']} ms of CPU; busiest other thread "
        f"{a.get('busiest', 'n/a')} ({a.get('busiest_cpu_ms', 'n/a')} ms); "
        f"watch late {a['watch_late_ms']} ms; stolen from the machine "
        f"{a.get('steal_ms', 'n/a')} ms; collector {a['gc_ms']} ms; "
        f"{a['compiles']} compilation(s), {a['compile_ms']} ms; chip idle "
        f"in {a.get('chip_idle', 'n/a')} of {a['samples']} sample(s)"]
    if table is not None:
        lines.append(f"  native threads from {table['attrs']['from_s']} s "
                     "into the stall (tid comm state cpu_ms runq_ms "
                     "python's name):")
        for tid, comm, state, cpu, runq, name in table["attrs"]["rows"]:
            wait = "       n/a" if runq is None else f"{runq:>10.3f}"
            lines.append(f"    {tid:>8} {comm:<16} {state} {cpu:>10.3f} "
                         f"{wait} {name}")
    for title, loop in (("the loop's thread", True),
                        ("the other Python threads", False)):
        rows = folded(samples, loop)
        if rows:
            lines.append(f"  {title}, root first:")
        for n, threads, names, phase, stack in rows[:stacks]:
            who = ", ".join(names) + (", ..." if threads > len(names) else "")
            lines.append(f"    {n}/{len(samples)} samples, {threads} "
                         f"thread(s) [{who}] in {phase}:")
            lines.append("      " + " > ".join(stack))
        if len(rows) > stacks:
            lines.append(f"    ({len(rows) - stacks} more distinct stacks: "
                         "--stacks)")
    return lines


def render_hosts(records: list) -> list:
    by_loop = collections.defaultdict(list)
    for r in records:
        if r["name"] == "obs.host" and r["kind"] == "span":
            by_loop[(r.get("pid"), r["attrs"]["loop"])].append(r)
    lines = []
    for (pid, loop), hosts in sorted(by_loop.items(), key=str):
        secs = sum(h["dur_s"] for h in hosts)
        ticks = sum(h["attrs"]["ticks"] for h in hosts)
        late = sum(h["attrs"]["late_ms_sum"] for h in hosts)
        worst = max(hosts, key=lambda h: h["attrs"]["late_ms_max"])
        line = (f"{loop} loop (pid {pid}): {len(hosts)} obs.host spans over "
                f"{secs:.1f} s, {ticks} ticks, late {late / max(ticks, 1):.3f}"
                f" ms a tick (worst {worst['attrs']['late_ms_max']} ms)")
        runq = [h["attrs"]["loop_runq_ms"] for h in hosts
                if "loop_runq_ms" in h["attrs"]]
        if runq:
            cpu = sum(h["attrs"]["loop_cpu_ms"] for h in hosts)
            line += (f"; loop thread on a core {cpu / 10 / secs:.1f} %, "
                     f"waiting for one {sum(runq) / 10 / secs:.2f} % "
                     f"(worst second {max(runq)} ms)")
        proc = sum(h["attrs"]["proc_cpu_ms"] for h in hosts)
        steal = [h["attrs"]["steal_ms"] for h in hosts
                 if "steal_ms" in h["attrs"]]
        if steal:
            line += (f"; stolen from the machine {sum(steal):.0f} ms "
                     f"(worst second {max(steal):.0f} ms)")
        line += (f"; process {proc / 10 / secs:.0f} % of a core, "
                 f"{sum(h['attrs']['nivcsw'] for h in hosts)} involuntary "
                 f"switches, collector "
                 f"{sum(h['attrs']['gc_ms'] for h in hosts):.1f} ms")
        lines.append(line)
    return lines


def render(records: list, stacks: int = 6, min_ms: float = 0.0) -> str:
    if not records:
        return "no records"
    t0 = records[0]["wall_time"]
    found = [s for s in stalls_of(records)
             if 1e3 * s[0]["dur_s"] >= min_ms]
    lines = [f"{len(found)} stall(s)"]
    for span, samples, table in found:
        lines.append("")
        lines.extend(render_stall(span, samples, table, stacks, t0))
    lines.append("")
    lines.extend(render_hosts(records) or ["no obs.host span: the watch "
                                           "minded no loop"])
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--stacks", type=int, default=6,
                    help="distinct stacks printed a stall and group")
    ap.add_argument("--min-ms", type=float, default=0.0,
                    help="leave out stalls shorter than this")
    args = ap.parse_args(argv)
    print(render(load(args.trace_dir), args.stacks, args.min_ms))
    return 0


if __name__ == "__main__":
    sys.exit(main())
