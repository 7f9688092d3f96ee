#!/usr/bin/env python
"""Request-tracing smoke — p99 attribution on a rigged topology.

Driven by ``scripts/run-tests.sh --reqtrace``.  The scenario: a
:class:`Router` over two live :class:`LMEngine` replicas, one of them
deliberately slow (its single decode slot preloaded with long direct
submissions), with ``BIGDL_REQTRACE_SAMPLE=1.0`` so every request
trace is kept.  Session-affine requests pinned to the slow replica
queue behind the preload; free requests place onto the fast replica.

The assertions are the tentpole's acceptance criteria:

* every routed response is token-identical to the direct
  ``generate()`` reference — tracing moved nothing;
* the report's "request traces" section attributes the slowest decile
  to the *queue* hop (that is where the time actually went), and the
  per-hop attribution sums to within 10% of the measured e2e
  (coverage >= 0.9).

Banks ``REQTRACE_SMOKE.json`` at the repo root; bench.py folds it
into BENCH ``extras.reqtrace``.
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


def main() -> int:
    ap = argparse.ArgumentParser(
        prog="scripts/reqtrace_smoke.py",
        description="End-to-end request tracing smoke: rigged "
                    "slow-replica topology, every trace kept, report "
                    "must attribute the slow decile to the queue hop.")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--slow-requests", type=int, default=3,
                    help="session-affine requests pinned behind the "
                         "slow replica's preload (default 3)")
    ap.add_argument("--fast-requests", type=int, default=8,
                    help="unpinned requests for the fast replica "
                         "(default 8)")
    args = ap.parse_args()

    import tempfile

    smoke_dir = tempfile.mkdtemp(prefix="bigdl_reqtrace_smoke_")
    obs_dir = os.path.join(smoke_dir, "obs")
    os.environ["BIGDL_TRACE_DIR"] = obs_dir
    os.environ["BIGDL_METRICS_DIR"] = obs_dir

    import numpy as np

    from bigdl_tpu import obs
    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.models.transformer import build_transformer_lm
    from bigdl_tpu.obs import reqtrace
    from bigdl_tpu.obs.report import build_report, render_text
    from bigdl_tpu.serving import LMEngine
    from bigdl_tpu.serving.router import EngineReplica, Router

    t0 = time.monotonic()
    RandomGenerator.RNG.set_seed(13)
    model = build_transformer_lm(48, dim=32, n_head=4, n_layer=2,
                                 max_len=64, attn_impl="lax")
    params = model.params()

    def ref(prompt, n):
        return list(np.asarray(model.generate(
            params, np.asarray(prompt)[None, :], n))[0])

    # single decode slot each: a preloaded slow replica really queues
    e1 = LMEngine(model, max_batch=1, page_size=8).start()
    e2 = LMEngine(model, max_batch=1, page_size=8).start()
    engines = {"r1": e1, "r2": e2}
    router = Router([EngineReplica(n, e) for n, e in engines.items()],
                    request_timeout_s=120.0)
    rs = np.random.RandomState(args.seed)

    def route_checked(n_prompt, n_new, session=None):
        p = rs.randint(0, 48, (n_prompt,)).tolist()
        out = router.route(p, n_new, session=session)
        assert [int(t) for t in list(p) + out["tokens"]] \
            == ref(p, n_new), \
            f"traced routed output diverged from generate() for {p}"
        return out

    # warm both replicas UNTRACED (prefill/decode compile must not
    # pollute the measured traces) and bind the session whose replica
    # we will rig slow
    route_checked(5, 8)
    bound = route_checked(5, 8, session="pinned")["replica"]
    slow_eng = engines[bound]
    print(f"SMOKE reqtrace: session pinned to {bound}; rigging it slow")

    # tracing ON for the measured window (read-at-call-time contract:
    # the collector rebuilds from live config on the next route)
    os.environ["BIGDL_REQTRACE_SAMPLE"] = "1.0"

    # rig: a long direct submission occupies the bound replica's only
    # slot, so every pinned request's time goes to the QUEUE hop
    preload = slow_eng.submit(rs.randint(0, 48, (5,)).tolist(), 24)
    parity = 0
    for _ in range(args.slow_requests):
        route_checked(5, 8, session="pinned")
        parity += 1
    for _ in range(args.fast_requests):
        route_checked(5, 8)
        parity += 1
    preload.wait(120)
    col = reqtrace.get_collector()
    sampler = col.stats()
    assert sampler["kept"] >= parity, sampler

    e1.close()
    e2.close()
    obs.flush()

    rep = build_report(obs_dir)
    rt = rep.get("reqtrace")
    assert rt, "report has no request-traces section"
    assert rt["traces"] >= parity, rt
    sd = rt["slow_decile"]
    hop_means = sd["hop_mean_s"]
    worst_hop = max(hop_means, key=hop_means.get)
    assert worst_hop == "queue", \
        (f"slow decile attributed to {worst_hop!r}, expected 'queue' "
         f"(the rigged replica's preloaded slot): {hop_means}")
    coverage = sd["coverage"]
    assert coverage is not None and coverage >= 0.9, \
        f"hop attribution covers {coverage!r} of e2e, want >= 0.9"
    attributed = sum(hop_means.values())
    assert abs(attributed - sd["e2e_mean_s"]) <= 0.1 * sd["e2e_mean_s"], \
        (f"per-hop attribution {attributed:.4f}s deviates more than "
         f"10% from measured e2e {sd['e2e_mean_s']:.4f}s")
    print(f"SMOKE reqtrace: {rt['traces']} kept traces, slow decile "
          f"e2e {sd['e2e_mean_s'] * 1000:.1f}ms -> worst hop "
          f"'{worst_hop}' ({hop_means[worst_hop] * 1000:.1f}ms), "
          f"coverage {coverage * 100:.1f}%")
    print(f"SMOKE reqtrace: {parity} routed requests token-identical "
          f"to direct generate() with tracing on")
    section = [ln for ln in render_text(rep).splitlines()
               if "request traces" in ln]
    assert section, "render_text lost the request-traces section"

    total_wall = time.monotonic() - t0
    bank = {
        "seed": args.seed,
        "total_wall_s": round(total_wall, 2),
        "requests": parity,
        "slow_replica": bound,
        "parity_ok": True,
        "sampler": sampler,
        "report": rt,
    }
    with open(os.path.join(REPO, "REQTRACE_SMOKE.json"), "w",
              encoding="utf-8") as fh:
        json.dump(bank, fh, indent=2, sort_keys=True, default=str)
    print(f"REQTRACE SMOKE PASS in {total_wall:.1f}s "
          "(banked REQTRACE_SMOKE.json)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
