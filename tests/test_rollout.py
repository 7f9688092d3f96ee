"""Live weight rollout (ISSUE 20): verified hot-swap, canary
auto-rollback, version-exact replay.

The load-bearing contracts:

* ``swap_weights`` is a pointer flip between decode steps — page
  tables, slots and in-flight decodes survive, post-swap requests are
  temperature-0 BIT-EQUAL to ``generate()`` on the new weights (float,
  int8 and TP-sharded engines alike);
* the checkpoint watcher verifies BEFORE touching serving state: torn
  and corrupt publishes are counted and rejected, never loaded;
* drain/handoff replay is version-pinned: an absorber serving a
  different weight version refuses the checkpoint and the request
  re-queues toward a version-exact replica;
* the canary controller is hysteresis-gated: ``for_count`` consecutive
  breaches roll back exactly once, ``hold_evals`` clean rounds
  promote, the cooldown refuses re-offers."""

import os

import numpy as np
import pytest


def _model(seed=13, max_len=64):
    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.models.transformer import build_transformer_lm

    RandomGenerator.RNG.set_seed(seed)
    return build_transformer_lm(48, dim=32, n_head=4, n_layer=2,
                                max_len=max_len, attn_impl="lax")


@pytest.fixture(scope="module")
def lm_model():
    return _model()


@pytest.fixture(scope="module")
def lm_params(lm_model):
    return lm_model.params()


@pytest.fixture(scope="module")
def new_model():
    """A second checkpoint: same architecture, different weights."""
    return _model(seed=17)


@pytest.fixture(scope="module")
def new_params(new_model):
    return new_model.params()


def _ref(model, params, prompt, n):
    return list(np.asarray(model.generate(
        params, np.asarray(prompt)[None, :], n))[0])


def _out(prompt, req):
    return [int(t) for t in list(prompt) + req.tokens]


def _counter_total(name):
    from bigdl_tpu import obs

    snap = obs.get_registry().snapshot()["metrics"]
    fam = snap.get(name)
    return sum(s["value"] for s in fam["samples"]) if fam else 0.0


# ---------------------------------------------------------------- config
class TestRolloutConfig:
    def test_from_env(self, monkeypatch):
        from bigdl_tpu.config import refresh_from_env

        monkeypatch.setenv("BIGDL_ROLLOUT_WATCH", "/tmp/w")
        monkeypatch.setenv("BIGDL_ROLLOUT_POLL", "0.25")
        monkeypatch.setenv("BIGDL_ROLLOUT_CANARY_FRACTION", "0.5")
        monkeypatch.setenv("BIGDL_ROLLOUT_DIVERGENCE", "0.1")
        monkeypatch.setenv("BIGDL_ROLLOUT_FOR", "3")
        monkeypatch.setenv("BIGDL_ROLLOUT_HOLD", "4")
        monkeypatch.setenv("BIGDL_ROLLOUT_COOLDOWN", "7.5")
        cfg = refresh_from_env().rollout
        assert cfg.watch_dir == "/tmp/w"
        assert cfg.poll_s == 0.25
        assert cfg.canary_fraction == 0.5
        assert cfg.divergence_threshold == 0.1
        assert cfg.for_count == 3 and cfg.hold_evals == 4
        assert cfg.cooldown_s == 7.5

    def test_stale_exclude_env(self, monkeypatch):
        from bigdl_tpu.config import refresh_from_env

        assert refresh_from_env().router.stale_exclude is True
        monkeypatch.setenv("BIGDL_ROUTER_STALE_EXCLUDE", "0")
        assert refresh_from_env().router.stale_exclude is False

    def test_token_divergence(self):
        from bigdl_tpu.serving import token_divergence

        assert token_divergence([1, 2, 3], [1, 2, 3]) == 0.0
        assert token_divergence([1, 2, 3, 4], [1, 9, 3, 7]) == 0.5
        assert token_divergence([1, 2], [1, 2, 3, 4]) == 0.5
        assert token_divergence([], []) == 0.0


# ------------------------------------------------------------- hot swap
class TestSwapWeights:
    def test_swap_bit_match_new_weights(self, lm_model, lm_params,
                                        new_model, new_params):
        from bigdl_tpu.serving import LMEngine

        rs = np.random.RandomState(5)
        p1, p2 = rs.randint(0, 48, (5,)), rs.randint(0, 48, (7,))
        eng = LMEngine(lm_model, max_batch=2, page_size=8)
        r1 = eng.submit(p1, 6)
        eng.run_until_idle(120)
        assert _out(p1, r1) == _ref(lm_model, lm_params, p1, 6)

        eng.swap_weights(new_params, version="v1", manifest_sha="abc")
        r2 = eng.submit(p2, 6)
        eng.run_until_idle(120)
        eng.close()
        assert _out(p2, r2) == _ref(new_model, new_params, p2, 6), \
            "post-swap decode is not bit-equal to generate() on the " \
            "new weights"
        st = eng.stats()
        assert st["weight_version"] == "v1"
        assert st["manifest_sha"] == "abc"
        assert st["weight_swaps"] == 1

    def test_mid_stream_swap_preserves_state(self, lm_model, lm_params,
                                             new_params):
        """A request in flight across the swap: its pre-swap tokens
        follow the old-weights trajectory, it completes with every
        owed token, and the page pool survives intact."""
        from bigdl_tpu.serving import LMEngine

        rs = np.random.RandomState(6)
        p = rs.randint(0, 48, (5,)).tolist()
        ref_old = _ref(lm_model, lm_params, p, 12)
        eng = LMEngine(lm_model, max_batch=2, page_size=8)
        pages_total = eng.stats()["kv_pages_total"]
        r = eng.submit(p, 12)
        for _ in range(200):
            if len(r.tokens) >= 4:
                break
            eng.pump(wait_s=0.05)
        pre = [int(t) for t in r.tokens]
        assert len(pre) >= 4
        assert pre == ref_old[len(p):len(p) + len(pre)], \
            "pre-swap tokens diverged from the old-weights trajectory"
        eng.swap_weights(new_params, version="v1")
        eng.run_until_idle(120)
        eng.close()
        assert r.done and not r.error and len(r.tokens) == 12, \
            f"in-flight decode did not survive the swap: {r.error}"
        assert [int(t) for t in r.tokens[:len(pre)]] == pre
        st = eng.stats()
        assert st["kv_pages_total"] == pages_total
        assert eng.cache.pages_in_use() == 0, "pages leaked"

    def test_int8_swap_rebuilds_step(self, lm_model, new_model):
        """The int8 jitted step closes over the quantized twins — a
        swap must requantize AND rebuild the step, so the swapped
        engine decodes exactly like a fresh int8 engine built on the
        new weights."""
        from bigdl_tpu.serving import LMEngine

        p = [3, 1, 4, 1, 5]
        eng = LMEngine(lm_model, max_batch=2, page_size=8, int8=True)
        r0 = eng.submit(p, 8)
        eng.run_until_idle(120)
        assert r0.done and len(r0.tokens) == 8
        eng.swap_weights(new_model.params(), version="v1")
        r1 = eng.submit(p, 8)
        eng.run_until_idle(120)
        eng.close()
        fresh = LMEngine(new_model, max_batch=2, page_size=8, int8=True)
        r2 = fresh.submit(p, 8)
        fresh.run_until_idle(120)
        fresh.close()
        assert [int(t) for t in r1.tokens] == \
            [int(t) for t in r2.tokens], \
            "swapped int8 engine decodes differently from a fresh " \
            "int8 engine on the same weights — stale qparams"
        assert eng.stats()["weight_version"] == "v1"

    def test_tp_swap_bit_match(self, lm_model, new_model, new_params):
        from bigdl_tpu.serving import LMEngine

        rs = np.random.RandomState(7)
        p = rs.randint(0, 48, (6,))
        eng = LMEngine(lm_model, max_batch=2, page_size=8, tp=4)
        eng.swap_weights(new_params, version="v2")
        r = eng.submit(p, 6)
        eng.run_until_idle(120)
        eng.close()
        assert _out(p, r) == _ref(new_model, new_params, p, 6), \
            "TP-sharded post-swap decode diverged from generate()"

    def test_swap_counter_stamped(self, lm_model, new_params):
        from bigdl_tpu.serving import LMEngine

        before = _counter_total("bigdl_serve_weight_swaps_total")
        eng = LMEngine(lm_model, max_batch=2, page_size=8)
        eng.swap_weights(new_params, version="vX")
        eng.close()
        assert _counter_total("bigdl_serve_weight_swaps_total") \
            == before + 1


# -------------------------------------------------------------- watcher
class TestCheckpointWatcher:
    def test_publish_then_poll_swaps(self, tmp_path, lm_model,
                                     new_model, new_params):
        from bigdl_tpu.serving import (LMEngine, publish_checkpoint)
        from bigdl_tpu.serving.rollout import CheckpointWatcher

        eng = LMEngine(lm_model, max_batch=2, page_size=8)
        w = CheckpointWatcher(eng, str(tmp_path))
        assert w.poll_once() is None      # empty dir: nothing to do
        publish_checkpoint(new_model, str(tmp_path), "v1")
        assert w.poll_once() == "v1"
        assert eng.weight_version == "v1" and eng.manifest_sha
        assert w.poll_once() is None      # already seen
        p = [7, 3, 9]
        r = eng.submit(p, 6)
        eng.run_until_idle(120)
        eng.close()
        assert _out(p, r) == _ref(new_model, new_params, p, 6)

    def test_corrupt_publish_rejected(self, tmp_path, lm_model,
                                      new_model):
        from bigdl_tpu.serving import LMEngine, publish_checkpoint
        from bigdl_tpu.serving.rollout import CheckpointWatcher

        eng = LMEngine(lm_model, max_batch=2, page_size=8)
        w = CheckpointWatcher(eng, str(tmp_path))
        prefix = publish_checkpoint(new_model, str(tmp_path), "v1")
        # bit-flip the model npz AFTER the manifest recorded its sha
        with open(prefix + ".model.npz", "r+b") as fh:
            fh.seek(100)
            fh.write(b"\xff\xff\xff\xff")
        assert w.poll_once() is None
        assert eng.weight_version == "v0" and eng.swaps == 0, \
            "corrupt checkpoint reached the engine"
        reasons = {os.path.basename(k): v for k, v in w.rejected.items()}
        assert "checksum" in reasons["v1"], reasons
        assert w.poll_once() is None      # rejected once, not re-tried
        eng.close()

    def test_manifestless_publish_skipped(self, tmp_path, lm_model,
                                          new_model):
        """A publish torn before the manifest landed is *skipped* —
        not rejected (the pair may still be landing), not loaded —
        and picked up once the manifest arrives."""
        from bigdl_tpu.serving import LMEngine
        from bigdl_tpu.serving.rollout import CheckpointWatcher
        from bigdl_tpu.utils.serializer import save_module, write_manifest

        eng = LMEngine(lm_model, max_batch=2, page_size=8)
        w = CheckpointWatcher(eng, str(tmp_path))
        save_module(new_model, str(tmp_path / "v1.model"))
        assert w.poll_once() is None
        assert eng.weight_version == "v0" and not w.rejected
        write_manifest(str(tmp_path / "v1"))
        assert w.poll_once() == "v1"
        eng.close()

    def test_publish_fault_site(self, tmp_path, lm_model, new_model,
                                monkeypatch):
        """The ``publish:K:<action>`` fault plan damages a checkpoint
        post-manifest; verify-before-swap catches it."""
        from bigdl_tpu.resilience.faults import reset_injector
        from bigdl_tpu.serving import LMEngine, publish_checkpoint
        from bigdl_tpu.serving.rollout import CheckpointWatcher

        monkeypatch.setenv("BIGDL_FAULT_PLAN", "publish:1:truncate")
        reset_injector()
        try:
            eng = LMEngine(lm_model, max_batch=2, page_size=8)
            w = CheckpointWatcher(eng, str(tmp_path))
            publish_checkpoint(new_model, str(tmp_path), "v1")
            assert w.poll_once() is None
            assert eng.weight_version == "v0" and w.rejected
            eng.close()
        finally:
            monkeypatch.delenv("BIGDL_FAULT_PLAN")
            reset_injector()

    def test_fault_plan_parses_publish_site(self):
        from bigdl_tpu.resilience.faults import FaultPlan

        plan = FaultPlan.parse("publish:2:corrupt,ckpt:1:truncate")
        sites = sorted(f.site for f in plan.faults)
        assert sites == ["ckpt", "publish"]
        with pytest.raises(ValueError):
            FaultPlan.parse("publish:1:nan")   # step-only action


# --------------------------------------------- version-pinned handoff
class TestHandoffVersionPin:
    def test_record_roundtrip(self):
        from bigdl_tpu.serving import HandoffRecord

        hd = HandoffRecord(prompt=[1, 2], max_new_tokens=3,
                           weight_version="v7")
        assert HandoffRecord.from_dict(hd.to_dict()).weight_version \
            == "v7"
        # pre-rollout checkpoints deserialize with None (accepted
        # anywhere) — backward compatible
        legacy = {"prompt": [1], "max_new_tokens": 2}
        assert HandoffRecord.from_dict(legacy).weight_version is None

    def test_drain_stamps_version(self, lm_model):
        from bigdl_tpu.serving import LMEngine, drain_engine

        eng = LMEngine(lm_model, max_batch=2, page_size=8,
                       weight_version="v3")
        eng.submit([1, 2, 3], 8)
        records = drain_engine(eng, deadline_s=0.0)
        eng.close()
        assert records and all(hd.weight_version == "v3"
                               for hd in records)

    def test_replay_refused_on_version_mismatch(self, lm_model,
                                                lm_params, new_params):
        """The regression this PR pins: a drain checkpoint decoded
        under version A must never continue on a replica serving
        version B.  Replica 'b' (different weights) is the cheapest
        survivor after the drain — the router must refuse it, count
        the mismatch, and land the replay on version-exact 'c'."""
        import threading
        import time as _time

        from bigdl_tpu.serving import LMEngine
        from bigdl_tpu.serving.router import EngineReplica, Router

        ea = LMEngine(lm_model, max_batch=2, page_size=8,
                      weight_version="vA").start()
        eb = LMEngine(lm_model, max_batch=2, page_size=8,
                      weight_version="vA").start()
        ec = LMEngine(lm_model, max_batch=2, page_size=8,
                      weight_version="vA").start()
        eb.swap_weights(new_params, version="vB")
        router = Router([EngineReplica("a", ea), EngineReplica("b", eb),
                         EngineReplica("c", ec)],
                        request_timeout_s=120.0)
        before = _counter_total("bigdl_rollout_version_mismatch_total")
        p = [5, 11, 2, 7, 3, 9]
        res = {}
        t = threading.Thread(target=lambda: res.update(
            router.route(p, 56, session="pin-session")))
        t.start()
        # drain as soon as the request decodes on 'a', with no time to
        # finish (a fixed sleep and a deadline of 50 ms raced the
        # request; and on an idle host the engine's loop kept the lock
        # from the drain until the request was done: LMEngine.start)
        waited = _time.time() + 30.0
        while not ea.active_count() and _time.time() < waited:
            _time.sleep(0.001)
        router.begin_drain("a", deadline_s=0.0)
        t.join(60)
        for eng in (ea, eb, ec):
            eng.close()
        assert res, "drained request never completed"
        assert res["replica"] == "c", \
            f"replay landed on {res['replica']} — version pin ignored"
        assert res["handoffs"] >= 1
        assert [int(x) for x in list(p) + res["tokens"]] \
            == _ref(lm_model, lm_params, p, 56), \
            "version-pinned replay is not bit-equal to generate()"
        assert _counter_total("bigdl_rollout_version_mismatch_total") \
            > before, "the mismatch refusal was not counted"


# ------------------------------------------------------ stale routing
class TestStaleExclusion:
    def _stale_replica(self, name, eng, staleness_s):
        from bigdl_tpu.serving.router import EngineReplica

        class _Stale(EngineReplica):
            def signals(self):
                sig = super().signals()
                sig["staleness_s"] = staleness_s
                return sig

        return _Stale(name, eng)

    def test_skewed_host_excluded(self, lm_model, lm_params):
        """A replica whose host clock skew exceeds BIGDL_STALE_AFTER_S
        is ineligible for placement — and the exclusion is counted."""
        from bigdl_tpu.serving import LMEngine
        from bigdl_tpu.serving.router import EngineReplica, Router

        ea = LMEngine(lm_model, max_batch=2, page_size=8).start()
        eb = LMEngine(lm_model, max_batch=2, page_size=8).start()
        router = Router(
            [self._stale_replica("a", ea, 120.0),
             EngineReplica("b", eb)],
            request_timeout_s=120.0)
        assert router.stale_exclude and router.stale_after_s > 0
        before = _counter_total("bigdl_router_stale_excluded_total")
        views = router.views()
        assert views["a"].stale and not views["a"].eligible
        assert not views["b"].stale
        out = router.route([4, 8, 15], 6)
        assert out["replica"] == "b", \
            "request placed on a clock-skewed replica"
        assert _out([4, 8, 15], type("R", (), {"tokens": out["tokens"]})
                    ) == _ref(lm_model, lm_params, [4, 8, 15], 6)
        assert _counter_total("bigdl_router_stale_excluded_total") \
            > before
        ea.close()
        eb.close()

    def test_exclusion_can_be_disabled(self, lm_model, monkeypatch):
        from bigdl_tpu.serving import LMEngine
        from bigdl_tpu.serving.router import Router

        monkeypatch.setenv("BIGDL_ROUTER_STALE_EXCLUDE", "0")
        eng = LMEngine(lm_model, max_batch=2, page_size=8).start()
        router = Router([self._stale_replica("a", eng, 120.0)],
                        request_timeout_s=120.0)
        assert not router.stale_exclude
        assert router.views()["a"].eligible
        out = router.route([1, 2, 3], 4)
        assert out["replica"] == "a"
        eng.close()


# --------------------------------------------------------------- canary
class _Fleet:
    """Pure-callable harness for CanaryController unit tests."""

    def __init__(self, names, incumbent="v0"):
        self.versions = {n: incumbent for n in names}
        self.drained = []
        self.undrained = []
        self.divergence = 0.0
        self.alerts = []

    def set_version(self, name, version):
        self.versions[name] = version


def _controller(fleet, **kw):
    from bigdl_tpu.serving.rollout import CanaryController

    kw.setdefault("fraction", 0.25)
    kw.setdefault("divergence_threshold", 0.05)
    kw.setdefault("for_count", 2)
    kw.setdefault("hold_evals", 3)
    kw.setdefault("cooldown_s", 30.0)
    return CanaryController(
        sorted(fleet.versions), set_version=fleet.set_version,
        incumbent="v0", measure_divergence=lambda: fleet.divergence,
        alerts=lambda: list(fleet.alerts),
        drain=fleet.drained.append, undrain=fleet.undrained.append,
        clock=lambda: 0.0, **kw)


class TestCanaryController:
    def test_clean_canary_promotes(self):
        fleet = _Fleet([f"r{i}" for i in range(8)])
        ctl = _controller(fleet)
        assert ctl.offer("v1", now=0.0)
        assert ctl.canaries == ["r0", "r1"]     # 0.25 x 8, sorted
        assert ctl.state == "canary"
        canary_only = {n: v for n, v in fleet.versions.items()}
        assert sum(1 for v in canary_only.values() if v == "v1") == 2
        for i in range(3):
            ctl.evaluate(now=float(i))
        assert ctl.state == "idle" and ctl.incumbent == "v1"
        assert set(fleet.versions.values()) == {"v1"}
        assert ctl.promotions == ["v1"] and not ctl.rollbacks
        assert not fleet.drained, "a clean promote drained something"

    def test_divergence_rollback_with_hysteresis(self):
        fleet = _Fleet([f"r{i}" for i in range(8)])
        ctl = _controller(fleet)
        ctl.offer("v1", now=0.0)
        # one breached round, then clean: the streak resets — no
        # rollback from a single noisy window
        fleet.divergence = 0.5
        ctl.evaluate(now=1.0)
        fleet.divergence = 0.0
        ctl.evaluate(now=2.0)
        assert ctl.state == "canary" and not ctl.rollbacks
        # for_count consecutive breaches: exactly one rollback
        fleet.divergence = 0.5
        ctl.evaluate(now=3.0)
        out = ctl.evaluate(now=4.0)
        assert out["state"] == "rollback" \
            and out["rollback"] == "divergence"
        assert len(ctl.rollbacks) == 1
        assert set(fleet.versions.values()) == {"v0"}, \
            f"rollback left skew: {fleet.versions}"
        # the canaries drained before reverting and rejoined after
        assert fleet.drained == ["r0", "r1"]
        assert fleet.undrained == ["r0", "r1"]
        assert ctl.state == "idle"

    def test_slo_burn_rollback(self):
        from bigdl_tpu.serving.rollout import SLO_BURN_ALERT

        fleet = _Fleet([f"r{i}" for i in range(4)])
        ctl = _controller(fleet)
        ctl.offer("v1", now=0.0)
        fleet.alerts = [SLO_BURN_ALERT]
        ctl.evaluate(now=1.0)
        ctl.evaluate(now=2.0)
        assert len(ctl.rollbacks) == 1
        assert ctl.rollbacks[0]["reason"] == "slo_burn"

    def test_cooldown_refuses_offers(self):
        fleet = _Fleet([f"r{i}" for i in range(4)])
        ctl = _controller(fleet)
        ctl.offer("v1", now=0.0)
        fleet.divergence = 1.0
        ctl.evaluate(now=1.0)
        ctl.evaluate(now=2.0)
        assert len(ctl.rollbacks) == 1
        assert not ctl.offer("v2", now=10.0), \
            "offer accepted inside the rollback cooldown"
        assert ctl.refused_offers == 1
        assert ctl.offer("v2", now=40.0)

    def test_offer_refused_while_canarying(self):
        fleet = _Fleet([f"r{i}" for i in range(4)])
        ctl = _controller(fleet)
        assert ctl.offer("v1", now=0.0)
        assert not ctl.offer("v2", now=1.0)

    def test_mixed_signals_reset_clean_streak(self):
        """A breached-but-below-for_count round must also reset the
        promote streak: hold_evals means consecutive CLEAN rounds."""
        fleet = _Fleet([f"r{i}" for i in range(8)])
        ctl = _controller(fleet, hold_evals=2)
        ctl.offer("v1", now=0.0)
        ctl.evaluate(now=1.0)           # clean (streak 1)
        fleet.divergence = 0.5
        ctl.evaluate(now=2.0)           # breach: clean streak resets
        fleet.divergence = 0.0
        ctl.evaluate(now=3.0)           # clean (streak 1 again)
        assert ctl.state == "canary", \
            "promoted despite a breach inside the hold window"
        ctl.evaluate(now=4.0)
        assert ctl.state == "idle" and ctl.incumbent == "v1"


# ------------------------------------------------------------- scenario
class TestWeightRolloutScenario:
    def test_scenario_passes_invariants(self):
        from bigdl_tpu.sim.serve import run_serve_scenario

        res = run_serve_scenario("weight_rollout", seed=0)
        assert res.ok, res.summary()
        names = {r.name for r in res.invariants}
        assert {"rollback_exactly_once", "no_version_skew_after_settle",
                "corrupt_never_loaded",
                "zero_dropped_requests"} <= names
        assert res.rollout["rollbacks"] == 1
        assert res.rollout["promotions"] == ["v1"]
        assert set(res.rollout["versions_at_end"].values()) == {"v1"}
        assert res.rollout["corrupt_rejected"] == 1
        assert res.rollout["corrupt_loaded"] == 0
        assert res.lost == 0 and res.duplicates == 0 and res.shed == 0

    def test_publish_event_validation(self):
        from bigdl_tpu.sim.serve import load_serve_scenario

        with pytest.raises(ValueError, match="version"):
            load_serve_scenario({
                "name": "x", "duration_s": 10.0,
                "events": [{"t": 1.0, "kind": "publish_good"}]})
