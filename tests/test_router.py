"""Fault-tolerant serving data plane (serving/router.py + friends):
placement policy, shared retry budget, exactly-once drain/handoff, the
router core over fake and real replicas, the serving chaos scenarios,
and the 503 + Retry-After backpressure contract.

The load-bearing contract: temperature-0 output routed through the
router — including across a mid-decode drain/handoff onto another
replica — must BIT-MATCH the direct ``TransformerLM.generate()``.
The heavy chaos matrix lives in ``scripts/router_smoke.py``
(``run-tests.sh --router``); tier-1 runs the unit surface plus one
fast scenario — the full matrix is ``-m slow``.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from bigdl_tpu import obs
from bigdl_tpu.resilience.retry import RetryBudget, backoff_delay
from bigdl_tpu.serving.drain import (HANDOFF_ERROR, HandoffLedger,
                                     HandoffRecord)
from bigdl_tpu.serving.placement import (NoReplicaAvailable,
                                         PlacementPolicy, ReplicaView)
from bigdl_tpu.serving.router import (EngineReplica, ReplicaDraining,
                                      ReplicaUnavailable, Router,
                                      RouterShed, _claim_key)
from bigdl_tpu.sim import VirtualClock, run_serve_scenario
from bigdl_tpu.sim.serve import SimServeReplica


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    for var in ("BIGDL_OBS", "BIGDL_TRACE_DIR", "BIGDL_METRICS_DIR",
                "BIGDL_ROUTER_REPLICAS", "BIGDL_ROUTER_AFFINITY_TTL",
                "BIGDL_ROUTER_RETRY_BUDGET", "BIGDL_ROUTER_RETRY_BURST",
                "BIGDL_ROUTER_MAX_RETRIES", "BIGDL_ROUTER_TIMEOUT"):
        monkeypatch.delenv(var, raising=False)
    obs.reset()
    yield
    obs.reset()


# ----------------------------------------------------------- placement
class TestPlacement:
    def _views(self, **depths):
        return {n: ReplicaView(n, queue_depth=float(d))
                for n, d in depths.items()}

    def test_least_loaded_with_kv_weight(self):
        pol = PlacementPolicy(kv_weight=4.0)
        views = {
            "a": ReplicaView("a", queue_depth=2.0, kv_frac=0.0),
            "b": ReplicaView("b", queue_depth=0.0, kv_frac=0.9),
        }
        # b has the empty queue but its KV pool is nearly exhausted:
        # 0 + 4*0.9 = 3.6 > a's 2.0 — admitting onto b buys a preempt
        assert pol.choose(views) == "a"

    def test_affinity_sticks_and_ttl_expires(self):
        vc = VirtualClock()
        pol = PlacementPolicy(affinity_ttl_s=10.0, clock=vc)
        views = self._views(a=0, b=5)
        assert pol.choose(views, session="s") == "a"
        # the bound replica stays chosen even once it is the slower one
        views["a"].queue_depth = 50.0
        assert pol.choose(views, session="s") == "a"
        assert pol.affinity_hits == 1
        vc.advance(11.0)  # TTL expired -> re-place least-loaded
        assert pol.choose(views, session="s") == "b"

    def test_rebind_after_replica_removed(self):
        pol = PlacementPolicy()
        views = self._views(a=0, b=1)
        assert pol.choose(views, session="s") == "a"
        dropped = pol.unbind_replica("a")
        assert dropped == ["s"]
        del views["a"]
        assert pol.choose(views, session="s") == "b"
        assert pol.bindings()["s"] == "b"
        assert pol.rebinds == 0  # unbind cleared it; fresh bind, not a
        #                          rebind of a live binding

    def test_draining_and_down_ineligible(self):
        views = {
            "a": ReplicaView("a", draining=True),
            "b": ReplicaView("b", up=False),
            "c": ReplicaView("c", queue_depth=9.0),
        }
        pol = PlacementPolicy()
        assert pol.choose(views) == "c"
        with pytest.raises(NoReplicaAvailable):
            pol.choose(views, exclude={"c"})

    def test_affinity_to_drained_replica_falls_through(self):
        pol = PlacementPolicy()
        views = self._views(a=0, b=1)
        assert pol.choose(views, session="s") == "a"
        views["a"].draining = True
        assert pol.choose(views, session="s") == "b"
        assert pol.bindings()["s"] == "b"


# -------------------------------------------------------- retry budget
class TestRetryBudget:
    def test_deposit_capped_at_burst(self):
        b = RetryBudget(ratio=0.5, burst=2.0, initial=0.0)
        for _ in range(100):
            b.record_request()
        assert b.tokens() == 2.0

    def test_spend_denied_when_dry(self):
        b = RetryBudget(ratio=0.1, burst=1.0, initial=1.0)
        assert b.try_spend()
        assert not b.try_spend()
        s = b.stats()
        assert s["retries_granted"] == 1 and s["retries_denied"] == 1

    def test_arithmetic_ceiling(self):
        # the invariant the brownout scenario leans on: granted
        # retries can never exceed burst + ratio x requests
        b = RetryBudget(ratio=0.2, burst=4.0)
        granted = 0
        for _ in range(200):
            b.record_request()
            while b.try_spend():   # adversarial: drain after every req
                granted += 1
        assert granted <= 4.0 + 0.2 * 200 + 1e-9
        assert b.stats()["retries_granted"] == granted

    def test_backoff_delay_exponential_with_jitter(self):
        import random

        rng = random.Random(3)
        for attempt, base_delay in ((1, 0.5), (2, 1.0), (3, 2.0)):
            d = backoff_delay(attempt, base=0.5, cap=30.0, jitter=0.1,
                              rng=rng)
            assert base_delay <= d <= base_delay * 1.1
        assert backoff_delay(50, base=0.5, cap=3.0, jitter=0.0) == 3.0


# ------------------------------------------------------ handoff ledger
class TestHandoffLedger:
    def test_claim_exactly_once(self):
        led = HandoffLedger()
        assert led.claim("r1")
        assert not led.claim("r1")   # the losing recovery path

    def test_claim_refused_after_delivery(self):
        led = HandoffLedger()
        assert led.deliver("r1")
        assert not led.claim("r1")

    def test_release_reopens_claim(self):
        led = HandoffLedger()
        assert led.claim("r1")
        led.release("r1")
        assert led.claim("r1")

    def test_deliver_dedup_counts(self):
        led = HandoffLedger()
        assert led.deliver("r1")
        assert not led.deliver("r1")
        assert led.stats()["duplicates"] == 1

    def test_claim_key_distinguishes_handoff_epochs(self):
        # the same request handed off twice (from two drains) builds
        # two distinct claim keys — but the same event surfacing on
        # two recovery paths builds the same one
        hd1 = HandoffRecord(prompt=[1, 2], max_new_tokens=8,
                            request_id="r9", source="a")
        hd1_dup = HandoffRecord(prompt=[1, 2], max_new_tokens=8,
                                request_id="r9", source="a")
        hd2 = HandoffRecord(prompt=[1, 2, 3, 4], max_new_tokens=6,
                            request_id="r9", source="b")
        assert _claim_key(hd1) == _claim_key(hd1_dup)
        assert _claim_key(hd1) != _claim_key(hd2)

    def test_roundtrip_dict(self):
        hd = HandoffRecord(prompt=[1, 2], max_new_tokens=4,
                           temperature=0.0, tokens_done=[7],
                           request_id="x", source="a")
        assert HandoffRecord.from_dict(
            json.loads(json.dumps(hd.to_dict()))) == hd


# ------------------------------------------------- router (fake fleet)
class _FakeReplica:
    """Scriptable replica: each generate() pops the next outcome —
    a token list (success) or an exception to raise."""

    def __init__(self, name, outcomes=None):
        self.name = name
        self.outcomes = list(outcomes or [])
        self.calls = []
        self.drained = False

    def generate(self, prompt, max_new_tokens, *, temperature=0.0,
                 timeout_s=30.0, request_id=None):
        self.calls.append(list(prompt))
        out = self.outcomes.pop(0) if self.outcomes else [0] * 2
        if isinstance(out, Exception):
            raise out
        return {"tokens": list(out), "ttft_s": 0.0, "e2e_s": 0.0}

    def signals(self):
        return {"up": True, "draining": False, "queue_depth": 0.0,
                "kv_frac": 0.0}

    def drain(self, deadline_s=10.0):
        self.drained = True
        return []


def _router(replicas, **kw):
    kw.setdefault("sleep", lambda s: None)
    kw.setdefault("backoff_base_s", 0.0)
    return Router(replicas, **kw)


class TestRouterCore:
    def test_routes_and_returns_tokens(self):
        r = _router([_FakeReplica("a", [[5, 6, 7]])])
        out = r.route([1, 2], 3)
        assert out["tokens"] == [5, 6, 7] and out["replica"] == "a"
        assert out["retries"] == 0 and out["handoffs"] == 0

    def test_retry_lands_on_other_replica(self):
        a = _FakeReplica("a", [ReplicaUnavailable("a: boom")])
        b = _FakeReplica("b", [[9]])
        r = _router([a, b])
        out = r.route([1], 1)
        assert out["replica"] == "b" and out["retries"] == 1
        assert a.calls and b.calls

    def test_budget_exhaustion_sheds_with_retry_after(self):
        a = _FakeReplica("a", [ReplicaUnavailable("x")] * 5)
        b = _FakeReplica("b", [ReplicaUnavailable("x")] * 5)
        r = _router([a, b], retry_budget_ratio=0.0,
                    retry_budget_burst=0.0, max_retries=3,
                    retry_after_s=2.5)
        with pytest.raises(RouterShed) as ei:
            r.route([1], 1)
        assert ei.value.retry_after_s == 2.5
        assert r.budget.stats()["retries_denied"] == 1

    def test_max_retries_exhaustion_sheds(self):
        a = _FakeReplica("a", [ReplicaUnavailable("x")] * 9)
        b = _FakeReplica("b", [ReplicaUnavailable("x")] * 9)
        r = _router([a, b], max_retries=1)
        with pytest.raises(RouterShed):
            r.route([1], 1)

    def test_handoff_replays_elsewhere_with_prefix(self):
        hd = HandoffRecord(prompt=[1, 2, 7, 8], max_new_tokens=2,
                           tokens_done=[7, 8], request_id=None,
                           source="a")
        a = _FakeReplica("a")
        b = _FakeReplica("b", [[9, 10]])
        r = _router([a, b])
        a.outcomes = [ReplicaDraining(
            HandoffRecord(**{**hd.to_dict(), "request_id": None}))]

        def gen(prompt, n, **kw):
            a.calls.append(list(prompt))
            ex = a.outcomes.pop(0)
            ex.handoff.request_id = kw.get("request_id")
            raise ex
        a.generate = gen
        out = r.route([1, 2], 4)
        # generated-so-far prefix + the survivor's continuation
        assert out["tokens"] == [7, 8, 9, 10]
        assert out["handoffs"] == 1 and out["replica"] == "b"
        assert b.calls == [[1, 2, 7, 8]]   # refolded prompt replayed

    def test_dying_mid_handoff_lands_exactly_once(self):
        """The race: a replica dies mid-handoff and the same
        checkpoint surfaces on two recovery paths.  The claim gate
        lets exactly one replay."""
        hd = HandoffRecord(prompt=[1, 2], max_new_tokens=2,
                           request_id="rid-1", source="a")
        a = _FakeReplica("a", [ReplicaDraining(hd)])
        b = _FakeReplica("b", [[3, 4]])
        r = _router([a, b])
        # the drain sweep already claimed this checkpoint...
        assert r.ledger.claim(_claim_key(hd))
        # ...so the per-request path must stand down, not double-land
        with pytest.raises(RouterShed, match="already replayed"):
            r.route([1, 2], 2, request_id="rid-1")
        assert not b.calls

    def test_affinity_rebind_after_remove_replica(self):
        a = _FakeReplica("a", [[1], [1]])
        b = _FakeReplica("b", [[2], [2]])
        r = _router([a, b])
        first = r.route([5], 1, session="s")["replica"]
        dropped = r.remove_replica(first)
        assert dropped == ["s"]
        other = "b" if first == "a" else "a"
        assert r.route([5], 1, session="s")["replica"] == other
        assert r.placement.bindings()["s"] == other

    def test_begin_drain_stops_placement(self):
        a = _FakeReplica("a", [[1]] * 4)
        b = _FakeReplica("b", [[2]] * 4)
        r = _router([a, b])
        summary = r.begin_drain("a")
        assert a.drained and summary["replica"] == "a"
        for _ in range(3):
            assert r.route([1], 1)["replica"] == "b"
        r.undrain("a")
        assert any(r.route([1], 1)["replica"] == "a" for _ in range(2))

    def test_no_replica_sheds(self):
        r = _router([])
        with pytest.raises(RouterShed):
            r.route([1], 1)


# ------------------------------------------------------ serving chaos
class TestServeSim:
    def test_replica_throughput_independent_of_tick(self):
        # slots/service_s regardless of quantum: 4 lanes x 0.25s jobs
        # must finish 16 jobs per virtual second even at 0.5s ticks
        rep = SimServeReplica("r", slots=4)
        for i in range(64):
            assert rep.admit(f"q{i}", 0.25)
        done = []
        for _ in range(4):
            done += rep.tick(0.5)
        assert len(done) == 32

    def test_preempt_dumps_everything(self):
        rep = SimServeReplica("r", slots=2)
        for i in range(6):
            rep.admit(f"q{i}", 1.0)
        rep.tick(0.5)
        dumped = rep.preempt()
        assert len(dumped) == 6 and not rep.up
        # in-flight progress rides the checkpoint (remaining < full)
        assert min(rem for _rid, rem in dumped) == pytest.approx(0.5)
        assert not rep.admit("q9", 1.0)

    def test_drain_refuses_admissions_and_checkpoints(self):
        rep = SimServeReplica("r", slots=2)
        rep.admit("q0", 1.0)
        dumped = rep.drain()
        assert dumped == [("q0", 1.0)] and rep.draining
        assert not rep.admit("q1", 1.0)
        rep.undrain()
        assert rep.admit("q1", 1.0)

    def test_drain_wave_scenario_conserves_requests(self):
        res = run_serve_scenario("drain_wave", seed=7)
        assert res.ok, [str(i) for i in res.invariants if not i.ok]
        assert res.lost == 0 and res.duplicates == 0 and res.shed == 0
        assert res.handoff_replays >= 1 and res.drains >= 3
        assert res.completed == res.requests

    def test_amplification_invariant_catches_violation(self):
        from bigdl_tpu.sim.invariants import check_retry_amplification

        bad = {"amplification": 2.0,
               "budget": {"ratio": 0.2, "burst": 4.0, "requests": 100,
                          "retries_granted": 150, "retries_denied": 0}}
        r = check_retry_amplification(bad, {})
        assert not r.ok and "amplification" in r.detail
        assert "arithmetic" in r.detail  # 150 > 4 + 0.2*100 too

    @pytest.mark.slow
    def test_full_matrix(self):
        from bigdl_tpu.sim import SERVE_SCENARIOS

        for name in SERVE_SCENARIOS:
            res = run_serve_scenario(name, seed=7)
            assert res.ok, (name, [str(i) for i in res.invariants])
            assert res.lost == 0 and res.duplicates == 0


# --------------------------------------------------- real engine tier
def _model():
    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.models.transformer import build_transformer_lm

    RandomGenerator.RNG.set_seed(13)
    return build_transformer_lm(48, dim=32, n_head=4, n_layer=2,
                                max_len=64, attn_impl="lax")


@pytest.fixture(scope="module")
def lm_model():
    return _model()


@pytest.fixture(scope="module")
def lm_params(lm_model):
    return lm_model.params()


def _ref(model, params, prompt, n):
    return list(np.asarray(model.generate(
        params, np.asarray(prompt)[None, :], n))[0])


class TestRouterOverEngines:
    def test_temperature0_bit_equal_through_router(self, lm_model,
                                                   lm_params):
        from bigdl_tpu.serving import LMEngine

        e1 = LMEngine(lm_model, max_batch=2, page_size=8).start()
        e2 = LMEngine(lm_model, max_batch=2, page_size=8).start()
        r = _router([EngineReplica("r1", e1), EngineReplica("r2", e2)],
                    request_timeout_s=120.0)
        try:
            rs = np.random.RandomState(2)
            for n_p, n_new in ((5, 8), (9, 4), (4, 6)):
                p = rs.randint(0, 48, (n_p,)).tolist()
                out = r.route(p, n_new, session="t0")
                assert [int(t) for t in list(p) + out["tokens"]] \
                    == _ref(lm_model, lm_params, p, n_new)
            assert r.placement.stats()["affinity_hits"] >= 2
        finally:
            e1.close()
            e2.close()

    def test_queued_request_hands_off_before_decode_starts(
            self, lm_model, lm_params):
        """Drain edge case: admitted but decode never started (still
        queued behind the batch) — the checkpoint carries zero
        generated tokens and the replay elsewhere is bit-exact."""
        from bigdl_tpu.serving import LMEngine

        e1 = LMEngine(lm_model, max_batch=2, page_size=8)
        e2 = LMEngine(lm_model, max_batch=2, page_size=8)
        p = [1, 2, 3, 4]
        req = e1.submit(p, 6)          # queued; nothing pumped yet
        records = e1.drain(deadline_s=0.0)
        assert len(records) == 1
        hd = records[0]
        assert hd.tokens_done == [] and hd.prompt == p
        assert hd.max_new_tokens == 6
        assert req.error == HANDOFF_ERROR
        # replay the checkpoint on the second engine: bit-equal
        req2 = e2.submit(hd.prompt, hd.max_new_tokens,
                         temperature=hd.temperature)
        e2.run_until_idle(60)
        assert [int(t) for t in list(hd.prompt) + req2.tokens] \
            == _ref(lm_model, lm_params, p, 6)
        e1.close()
        e2.close()

    @pytest.mark.slow
    def test_mid_decode_drain_replays_bit_equal(self, lm_model,
                                                lm_params):
        from bigdl_tpu.serving import LMEngine

        e1 = LMEngine(lm_model, max_batch=2, page_size=8).start()
        e2 = LMEngine(lm_model, max_batch=2, page_size=8).start()
        r = _router([EngineReplica("r1", e1), EngineReplica("r2", e2)],
                    request_timeout_s=120.0)
        try:
            p = [3, 1, 4, 1, 5]
            r.route(p, 2, session="s")   # bind the session
            bound = r.placement.lookup("s")
            res = {}
            t = threading.Thread(target=lambda: res.update(
                r.route(p, 24, session="s")))
            t.start()
            time.sleep(0.3)
            r.begin_drain(bound, deadline_s=0.05)
            t.join(60)
            assert res.get("handoffs", 0) >= 1
            assert res["replica"] != bound
            assert [int(x) for x in list(p) + res["tokens"]] \
                == _ref(lm_model, lm_params, p, 24)
            assert r.ledger.stats()["duplicates"] == 0
        finally:
            e1.close()
            e2.close()

    def test_server_queue_full_answers_503_retry_after(self, lm_model):
        from bigdl_tpu.obs import names
        from bigdl_tpu.obs.metrics import parse_prometheus, sample_value
        from bigdl_tpu.serving import LMEngine, ServingServer

        eng = LMEngine(lm_model, max_batch=1, page_size=8,
                       queue_capacity=1)
        srv = ServingServer(lm=eng, request_timeout_s=0.05)
        try:
            eng.submit([1, 2, 3], 4)    # fills the queue; never pumped
            code, retry_after = None, None
            try:
                urllib.request.urlopen(urllib.request.Request(
                    srv.url("/v1/generate"),
                    data=json.dumps({"prompt": [1],
                                     "max_new_tokens": 2}).encode(),
                    headers={"Content-Type": "application/json"}),
                    timeout=10)
            except urllib.error.HTTPError as e:
                code = e.code
                retry_after = e.headers.get("Retry-After")
            assert code == 503
            assert retry_after is not None and int(retry_after) >= 1
            snap = parse_prometheus(obs.get_registry().to_prometheus())
            assert sample_value(
                snap, names.SERVE_REJECTS_TOTAL) >= 1.0
        finally:
            srv.close()
            eng.close()

    def test_draining_engine_refuses_admissions(self, lm_model):
        from bigdl_tpu.serving import LMEngine

        eng = LMEngine(lm_model, max_batch=1, page_size=8)
        eng.draining = True
        with pytest.raises(RuntimeError, match="draining"):
            eng.submit([1, 2], 2)
        stats = eng.stats()
        assert stats["draining"] is True
        assert "kv_pages_in_use" in stats and "kv_pages_total" in stats
        eng.close()
