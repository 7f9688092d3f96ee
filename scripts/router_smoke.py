#!/usr/bin/env python
"""Serving data-plane router smoke — chaos matrix + the real engines.

Driven by ``scripts/run-tests.sh --router``.  Two halves:

1. **Chaos matrix** (``bigdl_tpu/sim/serve.py``): the three builtin
   serving scenarios at >= 8 replicas on the virtual clock, with the
   REAL router policies in the loop — placement, the shared
   retry-budget token bucket, the exactly-once handoff ledger:

   * ``preemption_storm`` — half the fleet preempted at once; the
     survivors absorb the dumped queues (claim-gated replays), the
     overflow is shed with explicit 503s, the SLO-burn alert fires
     once and resolves, and not one request is lost or duplicated;
   * ``brownout`` — a 40x-slow replica; retries stay inside the
     budget's amplification ceiling while zombie completions are
     discarded, never double-answered;
   * ``drain_wave`` — replicas drain under a diurnal wave with zero
     dropped, zero duplicated, zero shed requests.

2. **Real engines**: a :class:`Router` over two live
   :class:`LMEngine` replicas — temperature-0 outputs routed (with
   session affinity) must BIT-MATCH the direct ``generate()``
   reference; then one replica drains mid-decode and the checkpointed
   request must replay on the survivor exactly once and still
   bit-match; finally the full HTTP topology (RouterServer ->
   HTTPReplica -> ServingServer) serves a routed request end to end
   and a queue-full admission answers 503 + ``Retry-After``.

Banks ``ROUTER_SMOKE.json`` at the repo root; bench.py folds it into
BENCH ``extras.router`` — the artifact future routing-policy PRs
regress against.
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def run_matrix(args) -> list:
    from bigdl_tpu.sim import SERVE_SCENARIOS, run_serve_scenario

    names = ([s.strip() for s in args.scenarios.split(",") if s.strip()]
             if args.scenarios else list(SERVE_SCENARIOS))
    results = []
    failed = []
    for name in names:
        res = run_serve_scenario(name, seed=args.seed)
        results.append(res)
        print("SMOKE " + res.summary())
        for inv in res.invariants:
            print("   ", inv)
        assert res.replicas >= 8, \
            f"{res.name}: chaos scenarios must run at >= 8 replicas"
        assert res.wall_s <= args.budget_s, \
            (f"scenario {res.name} took {res.wall_s:.1f}s — over the "
             f"{args.budget_s:.0f}s budget")
        if not res.ok:
            failed.append(res.name)
    assert not failed, f"serve scenario invariants FAILED: {failed}"
    # the matrix must exercise every recovery surface at least once
    assert sum(r.handoff_replays for r in results) > 0, \
        "no scenario replayed a handoff"
    assert sum(r.retries for r in results) > 0, \
        "no scenario spent retry budget"
    assert sum(r.shed for r in results) > 0, \
        "no scenario shed load — the budget ceiling went untested"
    assert all(r.lost == 0 and r.duplicates == 0 for r in results)
    return results


def run_real_engines(args) -> dict:
    """Router over two live engines: bit-equality, drain/handoff,
    and the full HTTP topology."""
    import threading

    import numpy as np

    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.models.transformer import build_transformer_lm
    from bigdl_tpu.serving import LMEngine, ServingServer
    from bigdl_tpu.serving.router import (EngineReplica, HTTPReplica,
                                          Router, RouterServer)

    RandomGenerator.RNG.set_seed(13)
    model = build_transformer_lm(48, dim=32, n_head=4, n_layer=2,
                                 max_len=64, attn_impl="lax")
    params = model.params()

    def ref(prompt, n):
        return list(np.asarray(model.generate(
            params, np.asarray(prompt)[None, :], n))[0])

    e1 = LMEngine(model, max_batch=2, page_size=8).start()
    e2 = LMEngine(model, max_batch=2, page_size=8).start()
    router = Router([EngineReplica("r1", e1), EngineReplica("r2", e2)],
                    request_timeout_s=120.0)
    rs = np.random.RandomState(args.seed)
    prompts = [rs.randint(0, 48, (n,)).tolist() for n in (5, 9, 4, 7)]
    for p in prompts:
        out = router.route(p, 8, session="smoke-session")
        assert [int(t) for t in list(p) + out["tokens"]] == ref(p, 8), \
            f"routed output diverged from direct generate() for {p}"
    aff = router.placement.stats()
    assert aff["affinity_hits"] >= len(prompts) - 1, aff
    print(f"SMOKE router bit-equality: {len(prompts)} routed requests "
          f"token-identical to direct generate() "
          f"({aff['affinity_hits']} affinity hits)")

    # drain the session's bound replica mid-decode; the checkpointed
    # request must finish on the survivor, bit-equal, exactly once
    bound = router.placement.lookup("smoke-session")
    long_p = rs.randint(0, 48, (6,)).tolist()
    res = {}
    t = threading.Thread(target=lambda: res.update(
        router.route(long_p, 24, session="smoke-session")))
    t.start()
    time.sleep(0.3)
    drain = router.begin_drain(bound, deadline_s=0.05)
    t.join(60)
    assert res, "drained request never completed"
    assert [int(x) for x in list(long_p) + res["tokens"]] \
        == ref(long_p, 24), "handoff replay diverged"
    assert res["handoffs"] >= 1 and res["replica"] != bound, res
    ledger = router.ledger.stats()
    assert ledger["duplicates"] == 0, ledger
    print(f"SMOKE drain/handoff: {bound} drained mid-decode, request "
          f"replayed on {res['replica']} bit-equal "
          f"({drain['handoffs']} checkpoint(s), 0 duplicates)")
    e1.close()
    e2.close()

    # full HTTP topology: RouterServer -> HTTPReplica -> ServingServer
    e3 = LMEngine(model, max_batch=2, page_size=8).start()
    e4 = LMEngine(model, max_batch=2, page_size=8).start()
    s3, s4 = ServingServer(lm=e3), ServingServer(lm=e4)
    http_router = Router(
        [HTTPReplica("h1", f"127.0.0.1:{s3.port}"),
         HTTPReplica("h2", f"127.0.0.1:{s4.port}")],
        request_timeout_s=120.0)
    front = RouterServer(http_router)
    import urllib.request

    p = prompts[0]
    body = json.dumps({"prompt": p, "max_new_tokens": 8,
                       "session": "http-session"}).encode()
    with urllib.request.urlopen(urllib.request.Request(
            front.url("/v1/generate"), data=body,
            headers={"Content-Type": "application/json"}),
            timeout=120) as r:
        out = json.loads(r.read())
    assert [int(x) for x in list(p) + out["tokens"]] == ref(p, 8), \
        "HTTP-routed output diverged from direct generate()"
    with urllib.request.urlopen(front.url("/healthz"), timeout=10) as r:
        health = json.loads(r.read())
    assert set(health["replicas"].values()) == {"up"}, health
    print(f"SMOKE http topology: RouterServer:{front.port} -> 2x "
          f"ServingServer routed bit-equal, /healthz reports "
          f"{health['replicas']}")

    # queue-full admission at the replica answers 503 + Retry-After
    import urllib.error

    e_small = LMEngine(model, max_batch=1, page_size=8,
                       queue_capacity=1)
    s_small = ServingServer(lm=e_small, request_timeout_s=0.05)
    e_small.submit([1, 2, 3], 4)           # occupies the queue
    code, retry_after = None, None
    try:
        urllib.request.urlopen(urllib.request.Request(
            s_small.url("/v1/generate"),
            data=json.dumps({"prompt": [1], "max_new_tokens": 2}
                            ).encode(),
            headers={"Content-Type": "application/json"}), timeout=10)
    except urllib.error.HTTPError as e:
        code, retry_after = e.code, e.headers.get("Retry-After")
    assert code == 503 and retry_after is not None, \
        f"queue-full admission answered {code} " \
        f"(Retry-After={retry_after!r}), want 503 + Retry-After"
    print(f"SMOKE backpressure: queue-full admission answered 503 "
          f"Retry-After={retry_after}")
    for closer in (front.close, s3.close, s4.close, s_small.close,
                   e3.close, e4.close, e_small.close):
        closer()
    return {
        "bit_equal_requests": len(prompts),
        "affinity_hits": aff["affinity_hits"],
        "drain": {"replica": bound, "handoffs": drain["handoffs"],
                  "replayed_on": res["replica"],
                  "duplicates": ledger["duplicates"]},
        "http_ok": True,
        "queue_full_status": code,
        "retry_after": retry_after,
    }


def main() -> int:
    ap = argparse.ArgumentParser(
        prog="scripts/router_smoke.py",
        description="Serving router chaos matrix + real-engine "
                    "bit-equality smoke (BIGDL_ROUTER_* knobs are the "
                    "env spelling of the router's config).")
    ap.add_argument("--scenarios", default=None,
                    help="comma-separated builtin serve scenarios "
                         "(default: all three)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--budget-s", type=float, default=60.0,
                    help="per-scenario wall-clock budget (default 60)")
    ap.add_argument("--skip-engines", action="store_true",
                    help="chaos matrix only (no jax model build)")
    args = ap.parse_args()

    import tempfile

    smoke_dir = tempfile.mkdtemp(prefix="bigdl_router_smoke_")
    obs_dir = os.path.join(smoke_dir, "obs")
    os.environ["BIGDL_TRACE_DIR"] = obs_dir
    os.environ["BIGDL_METRICS_DIR"] = obs_dir

    t0 = time.monotonic()
    results = run_matrix(args)
    engines = None if args.skip_engines else run_real_engines(args)
    total_wall = time.monotonic() - t0
    print(f"SMOKE router: {len(results)} scenario(s) PASS in "
          f"{total_wall:.1f}s")

    bank = {
        "seed": args.seed,
        "total_wall_s": round(total_wall, 2),
        "scenarios": [r.to_dict() for r in results],
        "engines": engines,
    }
    with open(os.path.join(REPO, "ROUTER_SMOKE.json"), "w",
              encoding="utf-8") as fh:
        json.dump(bank, fh, indent=2, sort_keys=True, default=str)
    print("ROUTER SMOKE PASS (banked ROUTER_SMOKE.json)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
