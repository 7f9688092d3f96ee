"""Driver for configurations of ``"kind": "serve_lm_block"``: a decoder
behind ``LMEngine`` that generates by blocks, so that a decode step
forwards a block of positions a slot, a pass unmasks some of them by
confidence, and a block is committed to the cache when its last mask is
gone (``bigdl_tpu/serving/engine.py``).  The run is
``drivers/serve_lm.py``'s, step for step, with ``drivers/serve.py``'s
clients and window imported unchanged; what it adds:

* **the oracle is the reference's two-pass form**: for the sampled
  requests, every generated position (``ServeRequest.unmasked``: its
  token and the pass of its block that unmasked it) is scored by the
  plain reference in the state of THAT pass (``block_gaps``): the
  **token gap** (the reference's largest logit there minus its logit
  for the served token) and the **choice gap** (how far the reference's
  ranking of that pass's masked positions by confidence disagrees with
  the set the engine unmasked), against ``limits.token_gap_mean_max`` /
  ``token_gap_max_max`` / ``choice_gap_mean_max`` /
  ``choice_gap_max_max`` / ``positions_scored_min``; and the served
  tokens must be the record's first ones (emission is a prefix);
* the counters ``slot_passes``, ``slot_commits`` and
  ``positions_unmasked`` of the window (``LMEngine.stats()`` at both
  edges).

``reference/<name>.py`` brings ``block_gaps`` beside ``sizes_of`` and
``init_params``.  The model is imported first of all, before a weight
is made: a checkout whose program lacks it fails at once, with an
``ImportError``.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from benchmarks.drivers.serve import (drive, pick_sample,  # noqa: F401
                                      window_numbers)
from benchmarks.drivers.serve_lm import build_engine
from benchmarks.drivers.serve_lm_draft import Recorded
from benchmarks.lib import harness, traffic


def unmasked_of(recorded: Recorded, sample) -> list:
    """Each sampled record's generated positions, ``(token, pass)``: a
    client puts its stamped list in place of the request's ``tokens``,
    so the list names the request."""
    by_tokens = {id(req.tokens): req for req in recorded.requests}
    return [list(by_tokens[id(rec.tokens)].unmasked) for rec in sample]


def gaps_of(ref, params, sizes, sample, unmasked, precision="float32",
            score=None):
    """``block_gaps`` of each sampled request (``score``: the rows of
    another precision's run, a request each)."""
    return [ref.block_gaps(
        params, sizes, rec.prompt, [t for t, _ in rows],
        [s for _, s in rows], precision,
        score=None if score is None else score[i]["rows"])
        for i, (rec, rows) in enumerate(zip(sample, unmasked))]


def score_blocks(ref, params, sizes, sample, unmasked, check: harness.Check,
                 limits: dict):
    """The reference over each sampled request, every pass; the numbers
    compared, each against its limit."""
    outs = gaps_of(ref, params, sizes, sample, unmasked)
    agree = prefix = 0
    for rec, rows, out in zip(sample, unmasked, outs):
        served = [t for t, _ in rows][:len(rec.tokens)]
        prefix += int(served == [int(t) for t in rec.tokens])
        agree += int(np.sum(
            out["first"] == np.asarray([t for t, _ in rows],
                                       np.int64)[out["positions"]]))
    token = np.concatenate([o["token_gap"] for o in outs]) if outs \
        else np.zeros((0,))
    choice = np.concatenate([o["choice_gap"] for o in outs]) if outs \
        else np.zeros((0,))
    print(f"reference: {len(sample)} requests, {token.size} positions "
          f"scored in the pass that unmasked them, {agree} hold the "
          f"reference's own token", flush=True)
    check.equal("answers_are_the_record_s_prefix", prefix, len(sample))
    check.at_least("positions_scored", float(token.size),
                   float(limits["positions_scored_min"]))
    if token.size:
        check.at_most("token_gap_mean", float(np.mean(token)),
                      limits["token_gap_mean_max"])
        check.at_most("token_gap_max", float(np.max(token)),
                      limits["token_gap_max_max"])
        check.at_most("choice_gap_mean", float(np.mean(choice)),
                      limits["choice_gap_mean_max"])
        check.at_most("choice_gap_max", float(np.max(choice)),
                      limits["choice_gap_max_max"])
    return token, choice


def longest_gaps(sent, t_open: float, t_close: float, k: int = 6) -> list:
    """The ``k`` longest gaps between consecutive tokens of one request
    that end inside the window, ``(seconds, seconds after the window
    opened)``: a run in which the loop stood still for a while says so
    in its log (a gap is 1 to 5 step periods otherwise)."""
    gaps = [(b - a, b - t_open) for rec in sent if rec.tokens is not None
            for a, b in zip(rec.tokens.stamps, rec.tokens.stamps[1:])
            if t_open <= b <= t_close]
    return sorted(gaps, reverse=True)[:k]


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
    recorded = Recorded(engine)
    try:
        w = drive(recorded, plan, seconds, profile, ctx["mark_open"])
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

    def grew(key):
        return stats_close[key] - stats_open[key]

    nums = window_numbers(w["sent"], t_open, t_close)
    engine_tokens = grew("tokens")
    print(f"window {window_s:.3f}s: {nums['tokens']} tokens stamped "
          f"(engine counted {engine_tokens}), {len(nums['finished'])} "
          f"requests finished, {len(nums['failed'])} failed, "
          f"{len(nums['gaps'])} token gaps, {len(nums['ttfts'])} first "
          f"tokens, {stats_close['preemptions']} preemptions so far; "
          f"{grew('block_passes')} refining passes and "
          f"{grew('block_commits')} commits of a slot, "
          f"{grew('positions_unmasked')} positions unmasked", flush=True)
    print("longest token gaps that end in the window: " + ", ".join(
        f"{gap:.3f}s at {at:.1f}s" for gap, at in longest_gaps(
            w["sent"], t_open, t_close)), flush=True)
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
    sample = pick_sample(nums["finished"], int(mix["check_requests"]), seed)
    unmasked = unmasked_of(recorded, sample)
    del engine, recorded, w
    t_ref = time.perf_counter()
    check.equal("failed_requests", len(nums["failed"]), 0)
    check.equal("tokens_out_of_vocabulary", bad, 0)
    check.equal("compiles_inside_window", len(in_window), 0)
    score_blocks(ref, params, sizes, sample, unmasked, check,
                 config["limits"])
    print(f"reference took {time.perf_counter() - t_ref:.1f}s", flush=True)

    counters = {
        "window_compiles": len(in_window),
        "steps": grew("steps"),
        "occupancy_sum": (stats_close["occupancy_mean"] * stats_close["steps"]
                          - stats_open["occupancy_mean"]
                          * stats_open["steps"]),
        "engine_tokens": engine_tokens,
        "slot_passes": grew("block_passes"),
        "slot_commits": grew("block_commits"),
        "positions_unmasked": grew("positions_unmasked"),
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
