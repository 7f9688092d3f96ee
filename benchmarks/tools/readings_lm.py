"""``tools/readings.py`` for cells of ``"kind": "serve_lm"``: what sound
runs of the program and the int8 control give over many seeds, at the
cell's own size, in one process.

    chiprun --chips 1 -- python3 benchmarks/tools/readings_lm.py \
        --workload longcat_flash_long_gen --seeds 11,12,13 --seconds 25

The engine is built once; each seed swaps in its own weights
(``LMEngine.swap_weights``; the model lets the old tree go first: one
set of weights fits beside the cache), runs the mix's ramp and a short
window at the cell's load, and scores the sampled requests against the
float32 reference; the control is the reference in int8 over the same
prompts and tokens.  One JSON line a seed, and a summary.
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


def readings(ctx, seeds):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.drivers import serve, serve_lm
    from benchmarks.lib import harness, traffic

    config, mix = ctx["config"], ctx["traffic"]
    ref = harness.reference_for(config)
    sizes = ref.sizes_of(config)
    dtype = jnp.dtype(config["assumed"]["serving_dtype"])
    params = ref.init_params(seeds[0], sizes, dtype)
    engine = serve_lm.build_engine(config, params).start()
    profile = harness.Profile(ctx["out_dir"], False)
    rows = []
    try:
        for n, seed in enumerate(seeds):
            if n:
                engine.params = params = None
                engine.model.set_params(None)
                gc.collect()
                params = ref.init_params(seed, sizes, dtype)
                jax.block_until_ready(params)
                engine.model.set_params(params)
                engine.swap_weights(params, version=f"seed{seed}")
            plan = traffic.ClosedLoopPlan(mix, seed, sizes["vocab"])
            w = serve.drive(engine, plan, ctx["seconds"], profile,
                            lambda t: None, warm=(n == 0))
            deadline = time.perf_counter() + 120
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
    if config["kind"] != "serve_lm":
        raise SystemExit(f"{args.workload} is of kind {config['kind']!r}; "
                         "tools/readings.py reads those")
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
    readings(ctx, [int(s) for s in args.seeds.split(",")])
    return 0


if __name__ == "__main__":
    sys.exit(main())
