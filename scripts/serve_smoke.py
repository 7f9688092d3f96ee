#!/usr/bin/env python
"""--serve smoke: the continuous-batching serving tier, end to end.

Driven by ``scripts/run-tests.sh --serve``.  Six stages, each a hard
assert:

1. **continuous batching** — a bursty request trace (mixed prompt
   lengths, short and long decodes interleaved, so a batch that
   drained before refilling would sit half empty) on one engine:
   every request completes and freed slots are refilled at step
   boundaries (mean occupancy >= 0.8; a drain-first scheduler reads
   0.48 on this trace).
2. **long decodes on a serving-sized model** — a long-decode trace on
   32-page tables of which it fills 4: every step ships the used-page
   bucket, not the table, and the greedy tokens equal ``generate()``.
3. **concurrent clients over HTTP** — a ResNet classifier (int8 via the
   existing ``quantize()``/folded-BN path) and the LM decoder behind
   one stdlib front-end, hammered by concurrent client threads mixing
   ``/v1/generate`` and ``/v1/classify``; every response must be
   well-formed.
4. **queue-driven autoscale decision** — a burst is parked in the
   request queue while the policy loop scrapes the process's own live
   ``/metrics`` endpoint (the real ``EndpointScraper`` path); the
   ``queue_high`` rule must emit a scale-up decision (dry-run).
5. **report** — ``obs.report`` must render the serving section (now
   incl. the decode ms/step + HBM bytes/token line) in text and carry
   the request-latency histograms + the autoscale decision in
   ``--json``.
6. **bank** — ``SERVE_SMOKE.json`` for BENCH ``extras.serve``.

NOTE: the parent pins JAX_PLATFORMS=cpu for itself — importing
bigdl_tpu pulls jax, which otherwise probes this container's TPU
plugin forever.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

TMP = None  # set in main


def _trace(prompts_seed: int = 7, n: int = 24):
    """The bursty request trace: short/long decodes interleaved so
    a drained-batch scheduler would head-of-line block."""
    import numpy as np

    rs = np.random.RandomState(prompts_seed)
    lens = [20, 3, 16, 2, 12, 4] * (n // 6 + 1)
    return [(rs.randint(0, 48, (3 + i % 5,)).tolist(), lens[i])
            for i in range(n)]


def _reset_measures(eng):
    """Zero the engine's throughput/latency accounting after compile
    warmup so the measured window is pure steady-state decode."""
    eng.completed.clear()
    eng._tokens_total = 0
    eng._occ_sum = eng._steps = 0
    eng._decode_ms_sum = 0.0
    eng._t_first_work = eng._t_last_done = None


def _bursty_run(model):
    from bigdl_tpu.serving import LMEngine

    eng = LMEngine(model, max_batch=4, page_size=8,
                   queue_capacity=64, slo_s=30.0, seed=3)
    # warm every compile OUTSIDE the measured window: one request per
    # prefill bucket, plus one long decode that walks the step through
    # every used-page table bucket the trace will touch (the decode
    # step is compiled per pow2 bucket since ISSUE 13)
    for t0 in (4, 12):
        eng.submit(list(range(1, t0 + 1)), 2)
    eng.submit(list(range(1, 5)), 30)
    eng.run_until_idle(120)
    _reset_measures(eng)
    reqs = [eng.submit(p, m) for p, m in _trace()]
    eng.run_until_idle(180)
    assert all(r.done and len(r.tokens) == m
               for r, (_, m) in zip(reqs, _trace())), "incomplete requests"
    st = eng.stats()
    eng.close()
    return st


# ------------------------------------------------------ long decodes
def _decode_trace(n: int = 16):
    """Long-decode trace: short prompts, 40-56
    generated tokens each, so the step count is decode-dominated and
    slot lengths stay under 64 (= the 4-page bucket at page 16)."""
    import numpy as np

    rs = np.random.RandomState(11)
    decodes = [48, 40, 56, 44, 52, 40, 54, 46] * (n // 8 + 1)
    return [(rs.randint(0, 64, (4 + i % 5,)).tolist(), decodes[i])
            for i in range(n)]


def _decode_run(model):
    from bigdl_tpu.serving import LMEngine

    eng = LMEngine(model, max_batch=8, page_size=16, num_pages=64,
                   queue_capacity=64, slo_s=30.0, seed=7)
    # warmup drives one slot through every decode bucket the trace
    # touches (lengths 4 -> 60: 1-, 2- and 4-page tables) plus the
    # prefill bucket, so the measured window has zero compiles
    eng.submit([1, 2, 3, 4], 56)
    eng.run_until_idle(300)
    _reset_measures(eng)
    reqs = [eng.submit(p, m) for p, m in _decode_trace()]
    eng.run_until_idle(600)
    assert all(r.done and len(r.tokens) == m
               for r, (_, m) in zip(reqs, _decode_trace())), \
        "incomplete requests"
    st = eng.stats()
    eng.close()
    return st, [list(r.tokens) for r in reqs]


def main() -> int:
    global TMP
    import tempfile

    TMP = tempfile.mkdtemp(prefix="bigdl_serve_smoke_")
    os.environ["BIGDL_TRACE_DIR"] = os.path.join(TMP, "trace")
    os.environ["BIGDL_METRICS_DIR"] = os.path.join(TMP, "metrics")
    os.environ["BIGDL_OBS_PORT"] = "0"
    port_file = os.path.join(TMP, "obs_port")
    os.environ["BIGDL_OBS_PORT_FILE"] = port_file

    import numpy as np

    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.engine import Engine

    RandomGenerator.RNG.set_seed(13)
    Engine.init()
    from bigdl_tpu.models.transformer import build_transformer_lm

    model = build_transformer_lm(48, dim=32, n_head=4, n_layer=2,
                                 max_len=64, attn_impl="lax")

    # -- 1: continuous batching on a bursty trace ---------------------
    cont = _bursty_run(model)
    print(f"[serve-smoke] continuous: {cont['tokens_per_s']:.1f} tok/s, "
          f"p99 {cont['e2e_p99_s'] * 1000:.0f}ms, occupancy "
          f"{cont['occupancy_mean'] * 100:.0f}%")
    assert cont["occupancy_mean"] >= 0.8, \
        f"freed slots were not refilled: occupancy " \
        f"{cont['occupancy_mean']:.2f}"
    print("[serve-smoke] continuous batching: every request done, "
          "slots refilled at step boundaries — PASS")

    # -- 2: long decodes on a serving-sized model ---------------------
    RandomGenerator.RNG.set_seed(29)
    # max_len 512 / page 16 = a 32-page table per slot, of which the
    # trace only ever fills 4
    model2 = build_transformer_lm(64, dim=128, n_head=8, n_layer=4,
                                  max_len=512, attn_impl="lax")
    params2 = model2.params()
    dec, dec_toks = _decode_run(model2)
    print(f"[serve-smoke] long decodes: {dec['tokens_per_s']:.0f} tok/s, "
          f"p99 {dec['e2e_p99_s'] * 1000:.0f}ms, "
          f"{dec['decode_ms_mean']:.2f}ms/step, "
          f"{dec['decode_hbm_bytes_per_token'] / 1e6:.2f} MB/token")
    assert dec["last_bucket_pages"] <= 4, dec["last_bucket_pages"]
    p0, m0 = _decode_trace()[0]
    ref0 = list(np.asarray(model2.generate(
        params2, np.asarray(p0)[None, :], m0))[0])
    assert [int(t) for t in p0 + dec_toks[0]] == ref0, \
        "paged decode lost temperature-0 parity vs generate()"
    print("[serve-smoke] long decodes: used-page bucket <= 4 of 32 "
          "pages, tokens equal generate() — PASS")

    # -- 3: concurrent clients vs ResNet + LM over HTTP ---------------
    from bigdl_tpu.models.resnet import build_resnet_cifar
    from bigdl_tpu.serving import (ClassifierEngine, LMEngine,
                                   ServingServer)

    lm = LMEngine(model, max_batch=4, page_size=8, slo_s=30.0,
                  seed=5).start()
    resnet = build_resnet_cifar(depth=8, class_num=10)
    clf = ClassifierEngine(resnet, max_batch=4, int8=True).start()
    assert clf.int8, "classifier must ride the int8 quantize() path"
    srv = ServingServer(lm=lm, classifier=clf, port=0)
    url = f"http://127.0.0.1:{srv.port}"

    def post(path, payload, timeout=120):
        req = urllib.request.Request(
            url + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        return json.loads(urllib.request.urlopen(
            req, timeout=timeout).read())

    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, 48, (3 + i % 4,)).tolist() for i in range(8)]
    images = rs.randn(8, 2, 3, 32, 32).astype(np.float32)
    errors = []

    def client(i):
        try:
            g = post("/v1/generate", {"prompt": prompts[i],
                                      "max_new_tokens": 4 + i % 3})
            assert len(g["tokens"]) == 4 + i % 3, g
            assert g["ttft_s"] is not None and g["e2e_s"] > 0, g
            c = post("/v1/classify", {"inputs": images[i].tolist()})
            assert len(c["classes"]) == 2, c
            assert all(0 <= k < 10 for k in c["classes"]), c
        except Exception as e:  # noqa: BLE001 — joined below
            errors.append(f"client {i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(180)
    assert not errors, "\n".join(errors)
    stats = json.loads(urllib.request.urlopen(
        url + "/stats", timeout=10).read())
    assert stats["lm"]["requests"] >= 8, stats["lm"]
    assert stats["classifier"]["requests"] >= 8, stats["classifier"]
    srv.close()
    clf.close()
    print("[serve-smoke] 8 concurrent HTTP clients vs int8 ResNet-8 + "
          "LM decoder: all responses well-formed — PASS")

    # -- 4: queue-driven autoscale decision off the live /metrics -----
    os.environ.update({
        "BIGDL_AUTOSCALE_QUEUE_HIGH": "8",
        "BIGDL_AUTOSCALE_HYSTERESIS": "1",
        "BIGDL_AUTOSCALE_WARMUP": "0",
        "BIGDL_AUTOSCALE_DRY_RUN": "1",
    })
    from bigdl_tpu.config import refresh_from_env
    from bigdl_tpu.resilience.autoscale import (AutoscaleController,
                                                EndpointScraper,
                                                derive_signals)

    # park a burst in the queue: the engine thread is stopped, so the
    # backlog (and its gauge) is real at scrape time
    lm.close()
    burst_lm = LMEngine(model, max_batch=4, page_size=8,
                        queue_capacity=64, seed=9)
    for i in range(12):
        burst_lm.submit(prompts[i % len(prompts)], 4)
    depth = burst_lm.queue.depth()
    assert depth > 8, f"expected a parked backlog, got depth {depth}"
    scraper = EndpointScraper(port_file=port_file)
    ctl = AutoscaleController(cfg=refresh_from_env().autoscale, world=1,
                              scrape=scraper)
    scraped = scraper()
    assert scraped and scraped[0].get("ok"), scraped
    sig = derive_signals(scraped, {}, 1)
    assert sig.get("queue_depth", 0) > 8, sig
    decision = ctl.evaluate(sig)
    assert decision is not None and decision.direction == "up" \
        and decision.reason == "queue_high", decision
    burst_lm.run_until_idle(120)  # drain so nothing leaks
    burst_lm.close()
    print(f"[serve-smoke] queue depth {sig['queue_depth']:g} scraped "
          f"from the live endpoint -> autoscale decision "
          f"{decision.direction} ({decision.reason}, dry-run) — PASS")

    from bigdl_tpu import obs

    obs.flush()

    # -- 5: the report renders the serving loop -----------------------
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.obs.report",
         os.environ["BIGDL_TRACE_DIR"], "--metrics-dir",
         os.environ["BIGDL_METRICS_DIR"]],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    for needle in ("-- serving --", "latency lm:e2e",
                   "latency classifier:e2e", "tok/s", "decode: ",
                   "MB/token"):
        assert needle in p.stdout, f"report missing {needle!r}:\n{p.stdout}"
    p = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.obs.report",
         os.environ["BIGDL_TRACE_DIR"], "--metrics-dir",
         os.environ["BIGDL_METRICS_DIR"], "--json"],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    rep = json.loads(p.stdout.strip().splitlines()[-1])
    sv = rep["serving"]
    assert sv and sv["latency"]["lm:e2e"]["count"] >= 8, sv
    assert sv["latency"]["lm:ttft"]["p99_s"] is not None, sv
    assert sv["latency"]["classifier:e2e"]["count"] >= 8, sv
    assert sv["tokens_per_second"] and sv["tokens_per_second"] > 0, sv
    assert sv["decode_attn_ms"] and sv["decode_attn_ms"] > 0, sv
    assert sv["decode_hbm_bytes_per_token"] > 0, sv
    decs = rep["autoscale"]["decisions_total"]
    assert decs.get("up:queue_high", 0) >= 1, decs
    print("[serve-smoke] report: serving section + latency histograms "
          "+ the queue-driven decision all present (text + --json) — "
          "PASS")

    # -- 6: bank for BENCH extras.serve -------------------------------
    bank = {
        "continuous": {k: cont[k] for k in
                       ("tokens_per_s", "e2e_p99_s", "e2e_p50_s",
                        "occupancy_mean", "requests", "tokens",
                        "steps")},
        "long_decodes": {k: dec[k] for k in
                         ("tokens_per_s", "e2e_p99_s", "e2e_p50_s",
                          "decode_ms_mean", "decode_hbm_bytes_per_token",
                          "last_bucket_pages", "steps", "tokens")},
        "classifier": {"requests": stats["classifier"]["requests"],
                       "int8": True},
        "autoscale_decision": {"direction": decision.direction,
                               "reason": decision.reason,
                               "queue_depth": sig["queue_depth"]},
        "ts": time.time(),
    }
    out = os.path.join(REPO, "SERVE_SMOKE.json")
    with open(out, "w", encoding="utf-8") as fh:
        json.dump(bank, fh, indent=2)
    print(f"[serve-smoke] banked {out}")
    print("[serve-smoke] PASS")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
