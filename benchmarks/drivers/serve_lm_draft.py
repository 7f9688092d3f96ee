"""Driver for configurations of ``"kind": "serve_lm_draft"``: a decoder
behind ``LMEngine`` that drafts its own next-but-one token, so that a
decode step verifies two positions a slot and yields one or two tokens
(``bigdl_tpu/serving/engine.py``).  The run is ``drivers/serve_lm.py``'s,
step for step, with ``drivers/serve.py``'s clients, window and oracle
imported unchanged; what it adds:

* the served-token oracle cannot see a wrong prediction layer (a wrong
  draft is rejected, and the served tokens stay the main model's: only
  acceptance falls), so **the drafts are checked too**: for the sampled
  requests, every draft the engine verified (``ServeRequest.drafts``:
  the index of the token it was checked against, and the draft) is
  scored by the plain reference's prediction layer over the prompt and
  the served tokens (``draft_gaps``: the reference's best draft logit
  minus its logit for that draft), against ``limits.draft_gap_mean_max``
  / ``draft_gap_max_max`` / ``drafts_scored_min``;
* the counters ``drafts_verified`` and ``drafts_accepted`` of the
  window (``LMEngine.stats()`` at both edges).

``reference/<name>.py`` brings ``draft_gaps`` beside ``sizes_of``,
``init_params`` and ``served_gaps``.  The model is imported first of
all, before a weight is made: a checkout whose program lacks it fails
at once, with an ``ImportError``.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from benchmarks.drivers.serve import (drive, pick_sample,  # noqa: F401
                                      score, window_numbers)
from benchmarks.drivers.serve_lm import build_engine
from benchmarks.lib import harness, traffic


class Recorded:
    """The engine as the clients see it, remembering every request it
    was handed: the clients keep a request's stamped tokens, and the
    drafts are on the request."""

    def __init__(self, engine):
        self._engine = engine
        self.requests: list = []

    def submit(self, *a, **kw):
        req = self._engine.submit(*a, **kw)
        self.requests.append(req)
        return req

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def drafts_of(self, sample) -> list:
        """Each sampled record's verified drafts: a client puts its
        stamped list in place of the request's ``tokens``, so the list
        names the request."""
        by_tokens = {id(req.tokens): req for req in self.requests}
        return [list(by_tokens[id(rec.tokens)].drafts) for rec in sample]


def score_drafts(ref, params, sizes, sample, drafts, check: harness.Check,
                 limits: dict):
    """The reference's prediction layer over each sampled request; the
    numbers compared, each against its limit."""
    gaps = []
    agree = 0
    for rec, recorded in zip(sample, drafts):
        g, first = ref.draft_gaps(params, sizes, rec.prompt,
                                  list(rec.tokens), recorded)
        gaps.append(g)
        agree += int(np.sum(first == np.asarray([d for _, d in recorded],
                                                np.int64)))
    allg = np.concatenate(gaps) if gaps else np.zeros((0,))
    print(f"reference: {allg.size} verified drafts, {agree} are the "
          f"reference's own draft", flush=True)
    check.at_least("drafts_scored", float(allg.size),
                   float(limits["drafts_scored_min"]))
    if allg.size:
        check.at_most("draft_gap_mean", float(np.mean(allg)),
                      limits["draft_gap_mean_max"])
        check.at_most("draft_gap_max", float(np.max(allg)),
                      limits["draft_gap_max_max"])
    return allg


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

    nums = window_numbers(w["sent"], t_open, t_close)
    engine_tokens = stats_close["tokens"] - stats_open["tokens"]
    verified = stats_close["drafts_verified"] - stats_open["drafts_verified"]
    accepted = stats_close["drafts_accepted"] - stats_open["drafts_accepted"]
    print(f"window {window_s:.3f}s: {nums['tokens']} tokens stamped "
          f"(engine counted {engine_tokens}), {len(nums['finished'])} "
          f"requests finished, {len(nums['failed'])} failed, "
          f"{len(nums['gaps'])} token gaps, {len(nums['ttfts'])} first "
          f"tokens, {stats_close['preemptions']} preemptions so far; "
          f"{verified} drafts verified, {accepted} accepted", flush=True)
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
    drafts = recorded.drafts_of(sample)
    del engine, recorded, w
    t_ref = time.perf_counter()
    check.equal("failed_requests", len(nums["failed"]), 0)
    check.equal("tokens_out_of_vocabulary", bad, 0)
    check.equal("compiles_inside_window", len(in_window), 0)
    score(ref, params, sizes, sample, check, config["limits"])
    score_drafts(ref, params, sizes, sample, drafts, check,
                 config["limits"])
    print(f"reference took {time.perf_counter() - t_ref:.1f}s", flush=True)

    counters = {
        "window_compiles": len(in_window),
        "steps": stats_close["steps"] - stats_open["steps"],
        "occupancy_sum": (stats_close["occupancy_mean"] * stats_close["steps"]
                          - stats_open["occupancy_mean"]
                          * stats_open["steps"]),
        "engine_tokens": engine_tokens,
        "drafts_verified": verified,
        "drafts_accepted": accepted,
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
