"""Driver for configurations of ``"kind": "serve_lm"``: any decoder the
program can put behind ``LMEngine``, under a closed-loop mix from
``lib/traffic.py``.  The run is ``drivers/serve.py``'s, step for step
(weights from the seed, warm requests, ramp, window, reference), and
its clients, window and oracle are imported from there unchanged; what
differs is that the model is not named here.

**What a ``serve_lm`` configuration brings** (data and a reference, no
driver):

* ``"model": {"module": ..., "build": ...}`` — the program's own
  constructor, called as ``build(config, params=weights)``: it reads the
  configuration file's object in the published spelling and builds the
  model around the weights it is given, drawing none.  The model tells
  ``LMEngine`` what its cache is (``cache_spec``: cached layers, the
  row's width, one buffer or two) and offers ``paged_prefill`` /
  ``paged_decode`` (``bigdl_tpu/serving/engine.py`` says what the
  engine asks of a model);
* ``reference/<name>.py`` with ``sizes_of(config)`` (which holds at
  least ``vocab``), ``init_params(seed, sizes, dtype)`` (the tree the
  constructor takes) and ``served_gaps(params, sizes, prompt, served,
  precision, score)`` with its ``"int8"`` control;
* ``engine`` (options of ``LMEngine``), ``assumed.serving_dtype``,
  ``limits`` as for ``"kind": "serve"``.

The model is imported first of all, before a weight is made: a checkout
whose program lacks it fails at once, with an ``ImportError``.
"""

from __future__ import annotations

import importlib
import time

from benchmarks.drivers.serve import (Clients, drive, pick_sample,  # noqa: F401
                                      score, window_numbers)
from benchmarks.lib import harness, traffic


def build_engine(config: dict, params, **overrides):
    """The configuration's model around ``params``, behind an engine
    with the configuration's options."""
    from bigdl_tpu.serving import LMEngine

    spec = config["model"]
    build = getattr(importlib.import_module(spec["module"]), spec["build"])
    opts = dict(config["engine"])
    opts.update(overrides)
    return LMEngine(build(config, params=params), params=params, **opts)


def run(ctx: dict) -> dict:
    config, mix = ctx["config"], ctx["traffic"]
    # the program's model, before anything else
    importlib.import_module(config["model"]["module"])
    import jax
    import jax.numpy as jnp

    seed, seconds = ctx["seed"], ctx["seconds"]
    ref = harness.reference_for(config)
    sizes = ref.sizes_of(config)
    compiles = ctx["compiles"]
    check = harness.Check()

    dtype = jnp.dtype(config["assumed"]["serving_dtype"])
    t0 = time.perf_counter()
    params = ref.init_params(seed, sizes, dtype)
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    engine = build_engine(config, params).start()
    print(f"weights on the device: {t1 - t0:.1f}s; engine built: "
          f"{time.perf_counter() - t1:.1f}s", flush=True)
    plan = traffic.ClosedLoopPlan(mix, seed, sizes["vocab"])
    profile = harness.Profile(ctx["out_dir"], ctx["trace"])
    try:
        w = drive(engine, plan, seconds, profile, ctx["mark_open"])
    finally:
        engine.close()
    t_open, t_close = w["t_open"], w["t_close"]
    stats_open, stats_close = w["stats_open"], w["stats_close"]
    window_s = t_close - t_open
    mem_peak = harness.memory_peak_bytes(ctx["devices"])
    spans = harness.program_spans(w["wall_open"], w["wall_close"])
    in_window = compiles.between(t_open, t_close)
    for c in in_window:
        print(f"compiled inside the window: {c[1]} ({c[2]:.2f}s)", flush=True)

    nums = window_numbers(w["sent"], t_open, t_close)
    engine_tokens = stats_close["tokens"] - stats_open["tokens"]
    print(f"window {window_s:.3f}s: {nums['tokens']} tokens stamped "
          f"(engine counted {engine_tokens}), {len(nums['finished'])} "
          f"requests finished, {len(nums['failed'])} failed, "
          f"{len(nums['gaps'])} token gaps, {len(nums['ttfts'])} first "
          f"tokens, {stats_close['preemptions']} preemptions so far",
          flush=True)
    e2e = {"serve_tokens_per_s": nums["tokens"] / window_s}
    if nums["gaps"]:
        e2e["itl_p95_ms"] = 1e3 * harness.percentile(nums["gaps"], 95)
    if nums["ttfts"]:
        e2e["ttft_p95_ms"] = 1e3 * harness.percentile(nums["ttfts"], 95)

    # free the program's state before the reference needs the memory
    vocab = sizes["vocab"]
    bad = sum(1 for rec in nums["finished"]
              for t in rec.tokens if not 0 <= int(t) < vocab)
    cache_shape = tuple(engine.cache.kp.shape)
    engine.cache.kp = engine.cache.vp = None
    del engine, w
    sample = pick_sample(nums["finished"], int(mix["check_requests"]), seed)
    t_ref = time.perf_counter()
    check.equal("failed_requests", len(nums["failed"]), 0)
    check.equal("tokens_out_of_vocabulary", bad, 0)
    check.equal("compiles_inside_window", len(in_window), 0)
    score(ref, params, sizes, sample, check, config["limits"])
    print(f"reference took {time.perf_counter() - t_ref:.1f}s", flush=True)

    counters = {
        "window_compiles": len(in_window),
        "steps": stats_close["steps"] - stats_open["steps"],
        "occupancy_sum": (stats_close["occupancy_mean"] * stats_close["steps"]
                          - stats_open["occupancy_mean"]
                          * stats_open["steps"]),
        "engine_tokens": engine_tokens,
        "requests_finished": len(nums["finished"]),
        "cache_hits": compiles.cache_hits,
        "cache_misses": compiles.cache_misses,
        "batch": int(config["engine"]["max_batch"]),
        "page_size": int(config["engine"]["page_size"]),
        "weight_itemsize": dtype.itemsize, "kv_itemsize": dtype.itemsize,
        "cache_row_width": cache_shape[-1],
    }
    return {
        "check": check,
        "attempted": len(nums["finished"]) + len(nums["failed"]),
        "failed": len(nums["failed"]),
        "e2e": e2e,
        "memory_peak_bytes": mem_peak,
        "window_s": window_s,
        "spans": spans,
        "counters": counters,
        "profile": profile,
        "sizes": sizes,
    }
