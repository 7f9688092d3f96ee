"""``tools/readings_lm.py`` for cells of ``"kind": "serve_lm_block"``:
what sound runs of the program, the controls and the reference with one
part of the mathematics left out give over several seeds, at the cell's
own size, in one process.

    chiprun --chips 1 -- python3 benchmarks/tools/readings_lm_block.py \\
        --workload sdar_moe_block_gen --seeds 11,12,13 --seconds 25

As ``readings_lm.py``: the engine is built once, each seed swaps in its
own weights, runs the mix's ramp and a short window at the cell's load,
and scores the sampled requests against the float32 reference
(``block_gaps``: the token gap and the choice gap of every generated
position, in the state of the pass that unmasked it).  The controls are
the reference in int8 (W8A8) and with its weights rounded to float8,
over the same prompts, tokens and passes: what each puts first and what
each would unmask, scored by the float32 reference.  On the first seed
(``--variants 1``) the sound run is also scored by the reference with
the per-head norms, the renormalisation, the in-block attention or the
commit left out: each must read over a limit.  One JSON line a seed,
and a summary.
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

CONTROLS = ("int8", "float8")
KINDS = ("token", "choice")


def readings(ctx, seeds, variants: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.drivers import serve, serve_lm, serve_lm_block
    from benchmarks.lib import harness, traffic

    config, mix = ctx["config"], ctx["traffic"]
    ref = harness.reference_for(config)
    sizes = ref.sizes_of(config)
    dtype = jnp.dtype(config["assumed"]["serving_dtype"])
    params = ref.init_params(seeds[0], sizes, dtype)
    engine = serve_lm.build_engine(config, params).start()
    profile = harness.Profile(ctx["out_dir"], False)
    rows = []

    def stats_of(g):
        return {"mean": float(np.mean(g)), "p95": float(np.percentile(g, 95)),
                "max": float(np.max(g)), "nonzero": float(np.mean(g > 0))}

    def both(outs):
        return {k: stats_of(np.concatenate([o[f"{k}_gap"] for o in outs]))
                for k in KINDS}

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
            recorded = serve_lm_block.Recorded(engine)
            before = engine.stats()
            w = serve.drive(recorded, plan, ctx["seconds"], profile,
                            lambda t: None, warm=(n == 0))
            deadline = time.perf_counter() + 120
            while engine.active_count() and time.perf_counter() < deadline:
                time.sleep(0.1)  # requests cut at the window drain
            after = engine.stats()
            nums = serve.window_numbers(w["sent"], w["t_open"], w["t_close"])
            sample = serve.pick_sample(nums["finished"],
                                       int(mix["check_requests"]), seed)
            unmasked = serve_lm_block.unmasked_of(recorded, sample)
            row = {"seed": seed, "requests": len(sample),
                   "tokens_per_s": nums["tokens"] / (w["t_close"]
                                                     - w["t_open"]),
                   "failed": len(nums["failed"]),
                   "slot_passes": after["block_passes"]
                   - before["block_passes"],
                   "slot_commits": after["block_commits"]
                   - before["block_commits"]}
            sound = serve_lm_block.gaps_of(ref, params, sizes, sample,
                                           unmasked)
            row["positions"] = int(sum(o["token_gap"].size for o in sound))
            row["program"] = both(sound)
            for ctl in CONTROLS:
                low = serve_lm_block.gaps_of(ref, params, sizes, sample,
                                             unmasked, ctl)
                row[ctl] = both(serve_lm_block.gaps_of(
                    ref, params, sizes, sample, unmasked, score=low))
            if variants and n == 0:
                for v in ref.VARIANTS[1:]:
                    row[v] = both([ref.block_gaps(
                        params, sizes, rec.prompt, [t for t, _ in u],
                        [s for _, s in u], variant=v)
                        for rec, u in zip(sample, unmasked)])
            print(json.dumps(row), flush=True)
            rows.append(row)
    finally:
        engine.close()
    for what in KINDS:
        for stat in ("mean", "max"):
            prog = [r["program"][what][stat] for r in rows]
            line = (f"summary {what}_gap_{stat}: program largest "
                    f"{max(prog):.6g} (smallest {min(prog):.6g})")
            for ctl in CONTROLS:
                c = [r[ctl][what][stat] for r in rows]
                line += (f"; {ctl} control smallest {min(c):.6g} (largest "
                         f"{max(c):.6g})")
            print(line, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--variants", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    from benchmarks import run as runner
    from benchmarks.lib import harness

    bench = runner.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, mix = runner.load_cell(bench, args.workload)
    if config["kind"] != "serve_lm_block":
        raise SystemExit(f"{args.workload} is of kind {config['kind']!r}")
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
    readings(ctx, [int(s) for s in args.seeds.split(",")],
             bool(args.variants))
    return 0


if __name__ == "__main__":
    sys.exit(main())
