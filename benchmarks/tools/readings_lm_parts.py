"""``tools/readings_lm.py`` for a ``serve_lm`` cell whose reference can
leave ONE part of its mathematics out (``PARTS`` and ``served_gaps(...,
without=)``: ``reference/zaya1_8b.py``): what sound runs of the program
and the int8 control give over several seeds at the cell's own size,
and what the reference with each part left out reads on the same sound
runs, in one process.

    chiprun --chips 1 -- python3 benchmarks/tools/readings_lm_parts.py \\
        --workload zaya1_cca_long_gen --seeds 11,12,13 --seconds 25

As ``readings_lm.py``: the engine is built once, each seed swaps in its
own weights, runs the mix's ramp and a short window at the cell's load,
and scores the sampled requests against the float32 reference; the
control is the reference in int8 over the same prompts and tokens.  On
the first ``--parts`` seeds the sound run is also scored by the
reference without each part in turn: a limit of the configuration lies
over what the program reads and under what the control and every such
reference read.  One JSON line a seed, and a summary.
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


def stats_of(g) -> dict:
    import numpy as np

    return {"mean": float(np.mean(g)), "p95": float(np.percentile(g, 95)),
            "max": float(np.max(g)), "nonzero": float(np.mean(g > 0))}


def readings(ctx, seeds, parts_seeds: int):
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

            def scored(**kw):
                return np.concatenate([
                    ref.served_gaps(params, sizes, r.prompt, list(r.tokens),
                                    **kw)[0] for r in sample])

            row = {"seed": seed, "requests": len(sample),
                   "tokens_per_s": nums["tokens"] / (w["t_close"]
                                                     - w["t_open"]),
                   "failed": len(nums["failed"]),
                   "preemptions": engine.stats()["preemptions"],
                   "program": stats_of(scored()),
                   "control": stats_of(
                       serve.control_gaps(ref, params, sizes, sample))}
            row["tokens"] = int(sum(len(r.tokens) for r in sample))
            if n < parts_seeds:
                row["without"] = {part: stats_of(scored(without=part))
                                  for part in ref.PARTS}
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
        for part in ref.PARTS:
            got = [r["without"][part][stat] for r in rows if "without" in r]
            if got:
                print(f"summary served_gap_{stat} without {part}: smallest "
                      f"{min(got):.6g} (largest {max(got):.6g})", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--parts", type=int, default=1,
                    help="seeds (from the first) also scored by the "
                         "reference with each part left out")
    args = ap.parse_args(argv)

    from benchmarks import run as runner
    from benchmarks.lib import harness

    bench = runner.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, mix = runner.load_cell(bench, args.workload)
    if config["kind"] != "serve_lm" or not hasattr(
            harness.reference_for(config), "PARTS"):
        raise SystemExit(f"{args.workload}: a serve_lm cell whose reference "
                         "names its PARTS is what this tool reads")
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
    readings(ctx, [int(s) for s in args.seeds.split(",")], args.parts)
    return 0


if __name__ == "__main__":
    sys.exit(main())
