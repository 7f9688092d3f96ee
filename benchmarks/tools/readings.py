"""The readings a limit of ``correct`` is set from: what sound runs of
the program give over many seeds, and what the control gives, at the
cell's own size, in one process (set-up is paid once).

    chiprun --chips 1 -- python3 benchmarks/tools/readings.py \
        --workload gpt2xl_gen_heavy --seeds 11,12,13 --seconds 25

For a serving cell the engine is built once; each seed swaps in its own
weights (``LMEngine.swap_weights``), runs the mix's ramp and a short
window at the cell's load, and scores the sampled requests against the
float32 reference; the control is the reference in int8 over the same
prompts and tokens.  For a training cell each seed is a whole run of the
driver with a short window; the control is the reference in fp8 put in
the program's place.  One JSON line a seed, and a summary.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def serve_readings(ctx, seeds):
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.drivers import serve
    from benchmarks.lib import harness, traffic

    config, mix = ctx["config"], ctx["traffic"]
    ref = harness.reference_for(config)
    sizes = ref.sizes_of(config)
    dtype = jnp.dtype(config["assumed"]["serving_dtype"])
    params = ref.init_params(seeds[0], sizes, dtype)
    engine = serve.build_engine(config, params, sizes).start()
    profile = harness.Profile(ctx["out_dir"], False)
    rows = []
    try:
        for n, seed in enumerate(seeds):
            if n:
                # one set of weights at a time fits beside the cache
                engine.params = params = None
                gc.collect()
                params = ref.init_params(seed, sizes, dtype)
                engine.swap_weights(params, version=f"seed{seed}")
            plan = traffic.ClosedLoopPlan(mix, seed, sizes["vocab"])
            w = serve.drive(engine, plan, ctx["seconds"], profile,
                            lambda t: None, warm=(n == 0))
            deadline = time.perf_counter() + 60
            while engine.active_count() and time.perf_counter() < deadline:
                time.sleep(0.1)  # requests cut at the window drain
            nums = serve.window_numbers(w["sent"], w["t_open"], w["t_close"])
            sample = serve.pick_sample(nums["finished"],
                                       int(mix["check_requests"]), seed)
            gaps = np.concatenate([
                ref.served_gaps(params, sizes, r.prompt, list(r.tokens))[0]
                for r in sample])
            ctl = serve.control_gaps(ref, params, sizes, sample)
            row = {"seed": seed, "requests": len(sample),
                   "tokens": int(gaps.size),
                   "tokens_per_s": nums["tokens"] / (w["t_close"]
                                                     - w["t_open"]),
                   "failed": len(nums["failed"])}
            for name, g in (("program", gaps), ("control", ctl)):
                row[name] = {"mean": float(np.mean(g)),
                             "p95": float(np.percentile(g, 95)),
                             "max": float(np.max(g)),
                             "nonzero": float(np.mean(g > 0))}
            print(json.dumps(row), flush=True)
            rows.append(row)
    finally:
        engine.close()
    for stat in ("mean", "p95", "max"):
        prog = [r["program"][stat] for r in rows]
        ctl = [r["control"][stat] for r in rows]
        print(f"summary served_gap_{stat}: program largest {max(prog):.6g} "
              f"(smallest {min(prog):.6g}), control smallest "
              f"{min(ctl):.6g} (largest {max(ctl):.6g}), ratio "
              f"{min(ctl) / max(max(prog), 1e-30):.2f}", flush=True)


def train_readings(ctx, seeds):
    from benchmarks.drivers import train

    rows = []
    for seed in seeds:
        c = dict(ctx, seed=seed, control=True)
        out = train.run(c)
        row = {"seed": seed, **out["readings"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
        gc.collect()
    for name in ("loss_gap", "first_gradient_norm_gap",
                 "parameter_change_norm_gap", "first_gradient_difference"):
        prog = [r["program"][name] for r in rows]
        ctl = [r["control"][name] for r in rows]
        print(f"summary {name}: program largest {max(prog):.6g} (smallest "
              f"{min(prog):.6g}), control smallest {min(ctl):.6g} (largest "
              f"{max(ctl):.6g}), ratio "
              f"{min(ctl) / max(max(prog), 1e-30):.2f}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    from benchmarks import run as runner
    from benchmarks.lib import harness

    bench = runner.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, mix = runner.load_cell(bench, args.workload)
    import bigdl_tpu  # noqa: F401
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = runner.require_chips(int(cell["chips"]))
    out_dir = os.path.join(ROOT, ".bench_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    ctx = {"cell": cell, "config": config, "traffic": mix,
           "seconds": args.seconds, "trace": False, "devices": devices,
           "out_dir": out_dir, "compiles": harness.CompileLog(),
           "mark_open": lambda t: None}
    seeds = [int(s) for s in args.seeds.split(",")]
    if config["kind"] == "serve":
        serve_readings(ctx, seeds)
    else:
        train_readings(ctx, seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
