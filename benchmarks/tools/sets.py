"""Two sets of runs of one cell with the same seeds in both, and each
metric's spread as the contract defines it: the distance between the
first and third quartile (``statistics.quantiles(values, n=4)``) as a
share of the median; the wider of the two sets' spreads is the cell's.

    chiprun --chips 1 -- python3 benchmarks/tools/sets.py \
        --workload resnet50_train_1chip --seeds 1,2,3,4,5,6 --seconds 45

Each run is a process of its own (``benchmarks/run.py``); its result line
is appended to ``chiprun_out/sets/<workload>.jsonl`` as it ends, so a
call that is cut keeps what it had.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out_dir = os.path.join(ROOT, "chiprun_out", "sets")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, args.workload + ".jsonl")
    rows = []
    for k in range(args.sets):
        for seed in seeds:
            t0 = time.time()
            proc = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
                 "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(args.seconds), "--trace", str(args.trace)],
                cwd=ROOT, capture_output=True, text=True)
            lines = proc.stdout.strip().splitlines()
            if proc.returncode != 0 or not lines:
                print(f"set {k} seed {seed}: exit {proc.returncode}\n"
                      + proc.stdout[-1500:] + proc.stderr[-1500:], flush=True)
                continue
            res = json.loads(lines[-1])
            row = {"set": k, "seed": seed, "wall_s": time.time() - t0,
                   "correct": res["correct"], "failed": res["failed"],
                   "attempted": res["attempted"], "device": res["device"],
                   "metrics": {n: m["value"]
                               for n, m in res["metrics"].items()},
                   "checks": [ln for ln in lines if ln.startswith("check ")]}
            if "breakdown" in res:
                row["breakdown"] = res["breakdown"]
            with open(path, "a", encoding="utf-8") as fh:
                fh.write(json.dumps(row) + "\n")
            print(json.dumps({k2: v for k2, v in row.items()
                              if k2 != "checks"}), flush=True)
            if not res["correct"]:
                print("\n".join(row["checks"]), flush=True)
            rows.append(row)
    names = sorted({n for r in rows for n in r["metrics"]})
    for name in names:
        per_set = []
        for k in range(args.sets):
            vals = [r["metrics"][name] for r in rows
                    if r["set"] == k and name in r["metrics"]]
            if len(vals) >= 3:
                per_set.append((statistics.median(vals), spread(vals)))
        if per_set:
            print(f"spread {name}: " + "; ".join(
                f"set {k} median {m:.6g} spread {100 * s:.3f}%"
                for k, (m, s) in enumerate(per_set))
                + f"; widest {100 * max(s for _, s in per_set):.3f}%",
                flush=True)
    print(f"correct in {sum(r['correct'] for r in rows)} of {len(rows)} runs",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
