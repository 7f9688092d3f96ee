"""Driver for configurations of ``"kind": "serve"``: a decoder behind
``LMEngine.start()``, under a closed-loop mix from ``lib/traffic.py``.

One run, in order:

1. weights on the device from the seed (``reference/<config>.py``), the
   model's modules without weights of their own, the engine with its
   default page pool;
2. the mix's ``warm`` requests alone, one for each prefill program;
3. the ramp: every client's first request, cut so that the clients
   leave it out of phase; the window opens when the last has finished,
   so every program the mix reaches has run and every slot is in use;
4. the window: ``--seconds`` of closed-loop traffic.  Every token is
   stamped as the engine emits it (a list that stamps on ``append`` is
   put in place of ``ServeRequest.tokens``), so tokens are counted
   inside the window whether or not their request ends there;
5. after the window: peak memory is read, the engine and its cache are
   freed, and the plain reference scores a seeded sample of the requests
   the window finished (``served_gaps``).
"""

from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from benchmarks.lib import harness, traffic

# a request that emits nothing for this long inside the window is stuck,
# and failed; before the window a program may still be compiling
STALL_S = 120.0
STALL_SETUP_S = 1100.0


class StampedTokens(list):
    """``ServeRequest.tokens`` with the host clock read at every append:
    the engine appends a token where it emits it."""

    def __init__(self, items=()):
        super().__init__(items)
        now = time.perf_counter()
        self.stamps = [now] * len(self)

    def append(self, tok):
        self.stamps.append(time.perf_counter())
        super().append(tok)


class Sent:
    """One request as its client saw it."""

    __slots__ = ("prompt", "new", "t_submit", "tokens", "error", "ramp")

    def __init__(self, prompt, new, ramp):
        self.prompt, self.new, self.ramp = prompt, new, ramp
        self.t_submit = None
        self.tokens = None
        self.error = None


@contextlib.contextmanager
def modules_without_weights():
    """Build the program's model for its ``apply`` functions alone.

    ``TransformerLM()`` draws every weight on the host in float32 and
    puts it on the device: 1.6e9 draws and 6.2 GB at GPT-2 XL, more than
    the chip has beside the engine's weights and cache.  The engine is
    given its weights as ``params=``, so the modules' own are never
    read; while the model is built, the draw and the transfer are
    replaced by nothing.  (PERF.md, Open questions: the program should
    offer this itself.)"""
    from bigdl_tpu import common
    from bigdl_tpu.models import transformer
    from bigdl_tpu.nn import attention, layers

    class NoDraw:
        def astype(self, _):
            return self

    class NoRNG:
        def uniform(self, *a, **k):
            return NoDraw()

        normal = uniform

    saved_rng = common.RandomGenerator.RNG
    saved = [(m, m._to_device) for m in (layers, attention, transformer)]
    common.RandomGenerator.RNG = NoRNG()
    for m, _ in saved:
        m._to_device = lambda x: None
    try:
        yield
    finally:
        common.RandomGenerator.RNG = saved_rng
        for m, fn in saved:
            m._to_device = fn


def build_engine(config: dict, params, sizes: dict, **overrides):
    from bigdl_tpu.models.transformer import build_transformer_lm
    from bigdl_tpu.serving import LMEngine

    with modules_without_weights():
        model = build_transformer_lm(
            sizes["vocab"], dim=sizes["dim"], n_head=sizes["n_head"],
            n_layer=sizes["n_layer"], max_len=sizes["max_len"],
            mlp_ratio=sizes["mlp_ratio"])
    opts = dict(config["engine"])
    opts.update(overrides)
    return LMEngine(model, params=params, **opts)


class Clients:
    """The closed loop: ``plan.clients`` threads, each sending its next
    request when the last has answered, with no think time."""

    def __init__(self, engine, plan):
        self.engine, self.plan = engine, plan
        self.sent: list = []
        self.stop = threading.Event()
        self.stall_s = STALL_SETUP_S
        self.ramp_done = [threading.Event() for _ in range(plan.clients)]
        self._lock = threading.Lock()
        self._threads = [
            threading.Thread(target=self._client, args=(i,), daemon=True,
                             name=f"bench-client-{i}")
            for i in range(plan.clients)]

    def send(self, prompt, new, ramp=False) -> Sent:
        """Submit one request and wait for it, stamping its tokens."""
        rec = Sent(prompt, new, ramp)
        with self._lock:
            self.sent.append(rec)
        rec.t_submit = time.perf_counter()
        try:
            req = self.engine.submit(prompt, new, temperature=0.0)
        except Exception as e:  # noqa: BLE001 — counted as a failed request
            rec.error = f"submit: {e!r}"
            rec.tokens = StampedTokens()
            return rec
        # the engine cannot have prefilled within these microseconds;
        # should it have, the tokens already there are kept
        old = req.tokens
        rec.tokens = req.tokens = StampedTokens(old)
        while not req.done:
            if self.stop.is_set():
                return rec
            last = rec.tokens.stamps[-1] if rec.tokens.stamps \
                else rec.t_submit
            if time.perf_counter() - last > self.stall_s:
                rec.error = f"no token for {self.stall_s:g}s"
                return rec
            if not self.engine._thread.is_alive():
                rec.error = "the engine's loop has died"
                return rec
            req._event.wait(0.1)
        rec.error = req.error
        if rec.error is None and len(rec.tokens) != new:
            rec.error = f"{len(rec.tokens)} tokens for {new} asked"
        return rec

    def _client(self, i: int):
        prompt_len, new = self.plan.ramp[i]
        self.send(self.plan.prompt(i, prompt_len), new, ramp=True)
        self.ramp_done[i].set()
        while not self.stop.is_set():
            idx, (prompt_len, new) = self.plan.next_request()
            self.send(self.plan.prompt(idx, prompt_len), new)

    def start(self):
        for t in self._threads:
            t.start()

    def join(self):
        self.stop.set()
        for t in self._threads:
            if t.ident is not None:
                t.join(timeout=10.0)
        alive = [t.name for t in self._threads if t.is_alive()]
        if alive:
            raise RuntimeError(f"clients did not stop: {alive}")


def window_numbers(sent, t_open: float, t_close: float) -> dict:
    """Everything the end-to-end metrics need, from the stamps."""
    tokens = 0
    gaps, ttfts, finished, failed = [], [], [], []
    for rec in sent:
        stamps = rec.tokens.stamps if rec.tokens is not None else []
        inside = [t_open <= s <= t_close for s in stamps]
        tokens += sum(inside)
        for j in range(1, len(stamps)):
            if inside[j]:
                gaps.append(stamps[j] - stamps[j - 1])
        if stamps and inside[0] and not rec.ramp:
            ttfts.append(stamps[0] - rec.t_submit)
        if rec.error is not None:
            failed.append(rec)
        elif len(stamps) == rec.new and inside[-1]:
            finished.append(rec)
    return {"tokens": tokens, "gaps": gaps, "ttfts": ttfts,
            "finished": finished, "failed": failed}


def pick_sample(finished, k: int, seed: int) -> list:
    """``k`` of the finished requests, drawn from the seed, with the one
    of the longest context among them."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: (len(finished[i].prompt) + finished[i].new,
                                  finished[i].t_submit))
    longest = order[-1]
    rest = [i for i in range(len(finished)) if i != longest]
    rng = traffic.rng_for(seed, 4)
    picked = [longest] + [int(i) for i in
                          rng.permutation(rest)[:max(0, k - 1)]]
    return [finished[i] for i in picked]


def score(ref, params, sizes, sample, check: harness.Check, limits: dict):
    """The reference over each sampled request's prompt and served
    tokens; the numbers compared, each against its limit."""
    gaps = []
    agree = total = 0
    for rec in sample:
        g, first = ref.served_gaps(params, sizes, rec.prompt,
                                   list(rec.tokens))
        gaps.append(g)
        agree += int(np.sum(first == np.asarray(list(rec.tokens))))
        total += len(g)
    allg = np.concatenate(gaps) if gaps else np.zeros((0,))
    print(f"reference: {len(sample)} requests, {total} served tokens, "
          f"{agree} are the reference's own first choice", flush=True)
    check.at_least("served_tokens_scored", float(total),
                   float(limits["served_tokens_scored_min"]))
    if total:
        check.at_most("served_gap_mean", float(np.mean(allg)),
                      limits["served_gap_mean_max"])
        check.at_most("served_gap_max", float(np.max(allg)),
                      limits["served_gap_max_max"])
    return allg


def control_gaps(ref, params, sizes, sample):
    """The control: the reference in int8 over the same prompts and
    served tokens; at each position, how far the token it puts first
    lies below the float32 reference's best."""
    gaps = []
    for rec in sample:
        served = list(rec.tokens)
        _, first8 = ref.served_gaps(params, sizes, rec.prompt, served, "int8")
        g, _ = ref.served_gaps(params, sizes, rec.prompt, served, "float32",
                               score=first8)
        gaps.append(g)
    return np.concatenate(gaps) if gaps else np.zeros((0,))


def drive(engine, plan, seconds: float, profile, mark_open,
          warm: bool = True) -> dict:
    """Warm requests, the ramp, then ``seconds`` of window against a
    started engine; stops the clients (requests in flight are cut, not
    failed) and returns what the window saw."""
    clients = Clients(engine, plan)
    try:
        for k, (prompt_len, new) in enumerate(plan.warm() if warm else []):
            t0 = time.perf_counter()
            rec = clients.send(plan.prompt(-1 - k, prompt_len), new,
                               ramp=True)
            if rec.error:
                raise RuntimeError(f"warm request failed: {rec.error}")
            print(f"warm request ({prompt_len}, {new}): "
                  f"{time.perf_counter() - t0:.1f}s", flush=True)
        t0 = time.perf_counter()
        clients.start()
        for ev in clients.ramp_done:
            while not ev.wait(1.0):
                if not engine._thread.is_alive():
                    raise RuntimeError("the engine's loop died in the ramp")
        print(f"ramp: {time.perf_counter() - t0:.1f}s", flush=True)
        clients.stall_s = STALL_S
        stats_open = engine.stats()
        t_open, wall_open = time.perf_counter(), time.time()
        mark_open(t_open)
        profile.arm(t_open, seconds)
        while True:
            now = time.perf_counter()
            if now >= t_open + seconds:
                break
            if profile.enabled:
                profile.poll(now)
            time.sleep(min(0.05, t_open + seconds - now))
        t_close, wall_close = time.perf_counter(), time.time()
        stats_close = engine.stats()
        profile.stop()
    finally:
        clients.join()
    return {"sent": clients.sent, "t_open": t_open, "t_close": t_close,
            "wall_open": wall_open, "wall_close": wall_close,
            "stats_open": stats_open, "stats_close": stats_close}


def run(ctx: dict) -> dict:
    import jax.numpy as jnp

    config, mix = ctx["config"], ctx["traffic"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    ref = harness.reference_for(config)
    sizes = ref.sizes_of(config)
    compiles = ctx["compiles"]
    check = harness.Check()

    dtype = jnp.dtype(config["assumed"]["serving_dtype"])
    t0 = time.perf_counter()
    params = ref.init_params(seed, sizes, dtype)
    params["ln_f"]["bias"].block_until_ready()
    t1 = time.perf_counter()
    engine = build_engine(config, params, sizes).start()
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
          f"tokens", flush=True)
    e2e = {"serve_tokens_per_s": nums["tokens"] / window_s}
    if nums["gaps"]:
        e2e["itl_p95_ms"] = 1e3 * harness.percentile(nums["gaps"], 95)
    if nums["ttfts"]:
        e2e["ttft_p95_ms"] = 1e3 * harness.percentile(nums["ttfts"], 95)

    # free the program's state before the reference needs the memory
    vocab = sizes["vocab"]
    bad = sum(1 for rec in nums["finished"]
              for t in rec.tokens if not 0 <= int(t) < vocab)
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
