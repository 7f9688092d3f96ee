#!/usr/bin/env python
"""Live weight rollout smoke — watcher, canary, chaos scenario.

Driven by ``scripts/run-tests.sh --rollout``.  Three segments:

1. **Checkpoint watcher against a live engine**: a new version is
   published into a watch directory (model npz + manifest) while a
   long decode is in flight.  The watcher must verify-then-hot-swap
   between decode steps: the in-flight request completes, page tables
   and slots survive, post-swap requests are temperature-0 BIT-EQUAL
   to ``generate()`` on the new weights, and ``stats()``/``/healthz``
   carry the new version + manifest digest.  Then the gate: a publish
   torn mid-write (no manifest yet) is skipped, and a publish
   corrupted post-manifest (``publish:K:corrupt`` fault plan) is
   rejected — counted, never loaded, the engine keeps serving the
   incumbent bit-exactly.

2. **Canary promote/rollback over live engines**: a
   :class:`CanaryController` over four engine replicas.  A good
   version canaries on one replica, holds clean (zero pinned-prompt
   divergence) and promotes fleet-wide; a bad version (different
   weights — wildly divergent tokens) breaches the divergence
   threshold ``for_count`` evaluations in a row and rolls back
   exactly once, draining the canary first so nothing is dropped; the
   cooldown then refuses an immediate re-offer.

3. **Chaos scenario** (``bigdl_tpu/sim/serve.py``): the
   ``weight_rollout`` scenario on the virtual clock — good promote,
   exactly-one-rollback on the bad version, corrupt publish rejected,
   and the rollout invariants (``rollback_exactly_once``,
   ``no_version_skew_after_settle``, ``corrupt_never_loaded``,
   ``zero_dropped_requests``) all green.

Banks ``ROLLOUT_SMOKE.json`` at the repo root; bench.py folds it into
BENCH ``extras.rollout``.
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


def _build(seed: int):
    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.models.transformer import build_transformer_lm

    RandomGenerator.RNG.set_seed(seed)
    model = build_transformer_lm(48, dim=32, n_head=4, n_layer=2,
                                 max_len=64, attn_impl="lax")
    return model, model.params()


def _ref(model, params, prompt, n):
    import numpy as np

    return [int(t) for t in np.asarray(model.generate(
        params, np.asarray(prompt)[None, :], n))[0]]


def _gen(engine, prompt, n, timeout=120.0):
    req = engine.submit(prompt, n, timeout=timeout)
    req.wait(timeout)
    assert not req.error, f"engine request failed: {req.error}"
    return [int(t) for t in prompt] + [int(t) for t in req.tokens]


def run_watcher(args, watch_dir) -> dict:
    """Segment 1: publish -> verify -> hot-swap against a live engine,
    then the torn/corrupt rejection paths."""
    import numpy as np

    from bigdl_tpu.resilience.faults import reset_injector
    from bigdl_tpu.serving import LMEngine, publish_checkpoint
    from bigdl_tpu.serving.rollout import CheckpointWatcher
    from bigdl_tpu.utils.serializer import save_module

    model_a, params_a = _build(13)     # the incumbent ("v0")
    model_b, params_b = _build(17)     # genuinely different weights
    engine = LMEngine(model_a, max_batch=4, page_size=8).start()
    watcher = CheckpointWatcher(engine, watch_dir, poll_s=0.05)
    watcher.start()

    rs = np.random.RandomState(args.seed)
    prompt = rs.randint(0, 48, (6,)).tolist()
    assert _gen(engine, prompt, 8) == _ref(model_a, params_a, prompt, 8)

    # a long decode is in flight while the new version publishes: the
    # swap must not disturb its slot or page table — it completes with
    # every owed token
    inflight = engine.submit(rs.randint(0, 48, (5,)).tolist(), 48,
                             timeout=120.0)
    pages_before = engine.stats()["kv_pages_total"]
    publish_checkpoint(model_b, watch_dir, "v1")
    deadline = time.monotonic() + 30.0
    while engine.weight_version != "v1" and time.monotonic() < deadline:
        time.sleep(0.02)
    assert engine.weight_version == "v1", \
        f"watcher never swapped (still {engine.weight_version})"
    inflight.wait(120.0)
    assert not inflight.error and len(inflight.tokens) == 48, \
        f"in-flight decode did not survive the swap: " \
        f"error={inflight.error} tokens={len(inflight.tokens)}"

    st = engine.stats()
    assert st["weight_version"] == "v1" and st["manifest_sha"], st
    assert st["weight_swaps"] == 1 and engine.swaps == 1
    assert st["kv_pages_total"] == pages_before, \
        "page pool changed across a weight swap"
    assert engine.cache.pages_in_use() == 0, \
        "pages leaked across the swap"
    # post-swap requests are bit-equal to generate() on the NEW weights
    for n in (5, 9, 4):
        p = rs.randint(0, 48, (n,)).tolist()
        assert _gen(engine, p, 8) == _ref(model_b, params_b, p, 8), \
            "post-swap output diverged from generate() on new weights"
    print(f"SMOKE watcher: published v1 hot-swapped mid-decode "
          f"(in-flight finished 48/48 tokens, pages stable, 3 post-swap "
          f"requests bit-equal, sha {st['manifest_sha']})")

    # torn publish: model npz lands, the manifest never does — the
    # watcher must SKIP it (still publishing), not load, not reject
    save_module(model_a, os.path.join(watch_dir, "v2-torn.model"))
    time.sleep(0.3)
    assert engine.weight_version == "v1" and not watcher.rejected, \
        f"manifest-less publish was consumed: {watcher.stats()}"

    # corrupt post-manifest publish: the fault plan flips bytes in the
    # model npz AFTER the manifest records its sha — verify must catch
    # it, count it, and never touch serving state
    os.environ["BIGDL_FAULT_PLAN"] = "publish:1:corrupt"
    reset_injector()
    try:
        publish_checkpoint(model_a, watch_dir, "v3")
        deadline = time.monotonic() + 30.0
        while not watcher.rejected and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        os.environ.pop("BIGDL_FAULT_PLAN", None)
        reset_injector()
    rejects = {os.path.basename(k): v for k, v in
               watcher.rejected.items()}
    assert "v3" in rejects and "checksum" in rejects["v3"], rejects
    assert engine.weight_version == "v1" and engine.swaps == 1, \
        "corrupt publish reached the engine"
    p = rs.randint(0, 48, (7,)).tolist()
    assert _gen(engine, p, 8) == _ref(model_b, params_b, p, 8), \
        "engine output drifted after a rejected publish"
    print(f"SMOKE verify gate: torn publish skipped, corrupt publish "
          f"rejected ({rejects['v3']}) — engine still serving v1 "
          f"bit-exactly")
    watcher.stop()
    out = {"swapped": list(watcher.swapped),
           "rejected": rejects,
           "manifest_sha": st["manifest_sha"],
           "inflight_tokens": len(inflight.tokens)}
    engine.close()
    return out


def run_canary(args) -> dict:
    """Segment 2: the CanaryController over four live engine replicas
    — clean promote, divergence rollback, cooldown."""
    import numpy as np

    from bigdl_tpu.serving import LMEngine
    from bigdl_tpu.serving.rollout import CanaryController

    model_a, params_a = _build(13)
    model_b, params_b = _build(17)
    # "good" = the incumbent weights republished under a new version
    # (pinned-prompt replay is bit-equal); "bad" = different weights
    # (wildly divergent tokens)
    weights = {"v1": params_a, "v2": params_a, "v3": params_b}
    engines = {f"r{i}": LMEngine(model_a, max_batch=2,
                                 page_size=8).start()
               for i in range(4)}
    for eng in engines.values():
        eng.swap_weights(params_a, version="v1")

    def set_version(name, version):
        engines[name].swap_weights(weights[version], version=version)

    def drain_cb(name):
        engines[name].drain(deadline_s=5.0)

    def undrain_cb(name):
        engines[name].draining = False

    rs = np.random.RandomState(args.seed)
    pinned = [rs.randint(0, 48, (n,)).tolist() for n in (5, 7, 4, 6)]

    def measure():
        from bigdl_tpu.serving.rollout import token_divergence

        canary = ctl.canaries[0]
        incumbents = [n for n in engines if n not in ctl.canaries]
        worst = 0.0
        for p in pinned:
            ref = _gen(engines[incumbents[0]], p, 8)
            got = _gen(engines[canary], p, 8)
            worst = max(worst, token_divergence(ref, got))
        return worst

    now = [0.0]
    ctl = CanaryController(
        sorted(engines), set_version=set_version, incumbent="v1",
        measure_divergence=measure, alerts=lambda: [],
        drain=drain_cb, undrain=undrain_cb,
        fraction=0.25, divergence_threshold=0.05, for_count=2,
        hold_evals=3, cooldown_s=30.0, clock=lambda: now[0])

    assert ctl.offer("v2", now=now[0])
    for _ in range(3):
        now[0] += 5.0
        ctl.evaluate(now=now[0])
    assert ctl.state == "idle" and ctl.incumbent == "v2", ctl.stats()
    versions = {n: e.weight_version for n, e in engines.items()}
    assert set(versions.values()) == {"v2"}, versions
    print(f"SMOKE canary promote: v2 held clean 3 rounds, promoted "
          f"fleet-wide ({versions})")

    assert ctl.offer("v3", now=now[0])
    evals = []
    for _ in range(2):
        now[0] += 5.0
        evals.append(ctl.evaluate(now=now[0]))
    assert len(ctl.rollbacks) == 1 \
        and ctl.rollbacks[0]["reason"] == "divergence", ctl.stats()
    versions = {n: e.weight_version for n, e in engines.items()}
    assert set(versions.values()) == {"v2"}, \
        f"rollback left version skew: {versions}"
    assert all(not e.draining for e in engines.values()), \
        "a canary was left draining after rollback"
    # inside the cooldown the same (or any) version is refused
    assert not ctl.offer("v3", now=now[0] + 1.0)
    assert ctl.offer("v2", now=now[0] + 60.0), \
        "offer still refused after the cooldown elapsed"
    worst_div = max(e["divergence"] for e in evals)
    print(f"SMOKE canary rollback: v3 diverged {worst_div:.2f} > 0.05 "
          f"for 2 rounds -> exactly one rollback, fleet back on v2, "
          f"re-offer refused in cooldown")
    for eng in engines.values():
        eng.close()
    return {"promotions": list(ctl.promotions),
            "rollbacks": [dict(r) for r in ctl.rollbacks],
            "worst_divergence": round(worst_div, 4),
            "refused_offers": ctl.refused_offers,
            "versions": versions}


def run_scenario(args) -> dict:
    """Segment 3: the weight_rollout chaos scenario on the virtual
    clock."""
    from bigdl_tpu.sim.serve import run_serve_scenario

    res = run_serve_scenario("weight_rollout", seed=args.seed)
    print("SMOKE " + res.summary())
    for inv in res.invariants:
        print("   ", inv)
    assert res.ok, "weight_rollout scenario invariants FAILED"
    assert res.rollout and res.rollout["rollbacks"] == 1, res.rollout
    assert res.rollout["corrupt_loaded"] == 0
    assert res.lost == 0 and res.duplicates == 0 and res.shed == 0
    return res.to_dict()


def main() -> int:
    ap = argparse.ArgumentParser(
        prog="scripts/rollout_smoke.py",
        description="Live weight rollout smoke: checkpoint watcher "
                    "hot-swap + verify gate, canary promote/rollback, "
                    "and the weight_rollout chaos scenario "
                    "(BIGDL_ROLLOUT_* knobs are the env spelling of "
                    "the rollout config).")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--skip-engines", action="store_true",
                    help="chaos scenario only (no jax model build)")
    args = ap.parse_args()

    import tempfile

    smoke_dir = tempfile.mkdtemp(prefix="bigdl_rollout_smoke_")
    obs_dir = os.path.join(smoke_dir, "obs")
    os.environ["BIGDL_TRACE_DIR"] = obs_dir
    os.environ["BIGDL_METRICS_DIR"] = obs_dir

    t0 = time.monotonic()
    watcher = None
    canary = None
    if not args.skip_engines:
        watcher = run_watcher(args, os.path.join(smoke_dir, "watch"))
        canary = run_canary(args)
    scenario = run_scenario(args)
    total_wall = time.monotonic() - t0
    print(f"SMOKE rollout: all segments PASS in {total_wall:.1f}s")

    bank = {
        "seed": args.seed,
        "total_wall_s": round(total_wall, 2),
        "watcher": watcher,
        "canary": canary,
        "scenario": scenario,
    }
    with open(os.path.join(REPO, "ROLLOUT_SMOKE.json"), "w",
              encoding="utf-8") as fh:
        json.dump(bank, fh, indent=2, sort_keys=True, default=str)
    print("ROLLOUT SMOKE PASS (banked ROLLOUT_SMOKE.json)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
