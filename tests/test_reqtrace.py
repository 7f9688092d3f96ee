"""End-to-end distributed request tracing (obs/reqtrace.py + the
serving data plane): header propagation across a real HTTP hop, the
tail sampler's keep/drop matrix, exemplar exposition, drain-handoff
trace continuity, and the load-bearing parity contract — tracing on
must not move a single token.
"""

import json
import urllib.request

import numpy as np
import pytest

from bigdl_tpu import obs
from bigdl_tpu.obs.reqtrace import (ReqTraceCollector,
                                    RequestTraceContext, _hash01)


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    for var in ("BIGDL_OBS", "BIGDL_TRACE_DIR", "BIGDL_METRICS_DIR",
                "BIGDL_OBS_PORT", "BIGDL_REQTRACE_SAMPLE",
                "BIGDL_REQTRACE_RING", "BIGDL_SERVE_SLO_MS"):
        monkeypatch.delenv(var, raising=False)
    obs.reset()
    yield
    obs.reset()


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


# ------------------------------------------------------------- context
class TestContext:
    def test_header_roundtrip(self):
        ctx = RequestTraceContext("abc123", parent=7, keep=True)
        back = RequestTraceContext.from_header(ctx.to_header())
        assert back.trace_id == "abc123"
        assert back.parent == 7 and back.keep is True

    def test_minimal_header(self):
        back = RequestTraceContext.from_header("deadbeef::")
        assert back.trace_id == "deadbeef"
        assert back.parent is None and back.keep is False

    @pytest.mark.parametrize("bad", [None, "", "   ", "::", "::k",
                                     ":17:k"])
    def test_malformed_header_is_none_not_error(self, bad):
        assert RequestTraceContext.from_header(bad) is None

    def test_bad_parent_tolerated(self):
        back = RequestTraceContext.from_header("tid:notanint:k")
        assert back.trace_id == "tid"
        assert back.parent is None and back.keep is True


# -------------------------------------------------------- tail sampler
def _finish_kw(reason):
    return {"error": "boom" if reason == "error" else None,
            "retries": 1 if reason == "retry" else 0,
            "preempted": reason == "preempt",
            "slo_violation": reason == "slo",
            "handoff": reason == "handoff"}


class TestTailSampler:
    def _col(self, sample=1e-9, ring_size=8):
        # direct construction: enabled, but the probabilistic path
        # essentially never keeps — only anomalies survive
        return ReqTraceCollector(sample=sample, ring_size=ring_size)

    @pytest.mark.parametrize("reason", ["error", "retry", "preempt",
                                        "slo", "handoff"])
    def test_anomalies_always_kept(self, reason):
        col = self._col()
        ctx = col.new_context()
        col.span(ctx, "req.route", 0.0, 1.0)
        kept, why = col.finish(ctx, request="r1", **_finish_kw(reason))
        assert kept and why == reason
        assert col.find("r1")["reason"] == reason

    def test_forced_keep_flag_kept(self):
        col = self._col()
        ctx = col.new_context()
        ctx.keep = True
        kept, why = col.finish(ctx, request="rf")
        assert kept and why == "forced"

    def test_plain_trace_dropped_at_tiny_sample(self):
        col = self._col()
        ctx = col.new_context()
        col.span(ctx, "req.route", 0.0, 1.0)
        kept, why = col.finish(ctx, request="rd")
        assert not kept and why is None
        assert col.find("rd") is None
        assert col.stats()["dropped"] == 1

    def test_error_outranks_retry(self):
        col = self._col()
        ctx = col.new_context()
        kept, why = col.finish(ctx, error="x", retries=3, handoff=True)
        assert kept and why == "error"

    def test_probabilistic_is_deterministic_by_trace_id(self):
        col = self._col(sample=0.5)
        low = next(f"t{i}" for i in range(200)
                   if _hash01(f"t{i}") < 0.5)
        high = next(f"t{i}" for i in range(200)
                    if _hash01(f"t{i}") >= 0.5)
        assert col.finish(RequestTraceContext(low)) == (True, "sampled")
        assert col.finish(RequestTraceContext(high)) == (False, None)
        # a second process with the same sample rate agrees — no
        # coordination needed fleet-wide
        col2 = self._col(sample=0.5)
        assert col2.finish(RequestTraceContext(low))[0] is True
        assert col2.finish(RequestTraceContext(high))[0] is False

    def test_second_finish_merges_and_counts_once(self):
        col = self._col()
        ctx = col.new_context()
        col.span(ctx, "req.queue", 0.0, 0.5)
        assert col.finish(ctx, request="rm", handoff=True)[0]
        # the replay hop re-opens the SAME trace and lands more spans
        col.span(ctx, "req.decode", 1.0, 2.0)
        assert col.finish(ctx, request="rm", e2e_s=3.0)[0]
        entry = col.find("rm")
        assert [s["name"] for s in entry["spans"]] \
            == ["req.queue", "req.decode"]
        assert entry["e2e_s"] == 3.0
        s = col.stats()
        assert s["sampled"] == {"handoff": 1} and s["dropped"] == 0
        assert s["open"] == 0

    def test_dropped_trace_stays_dropped(self):
        col = self._col()
        ctx = col.new_context()
        assert not col.finish(ctx, request="rx")[0]
        col.span(ctx, "req.decode", 0.0, 1.0)   # after the drop
        assert not col.finish(ctx, request="rx", e2e_s=1.0)[0]
        assert col.find("rx") is None and col.stats()["open"] == 0

    def test_ring_is_bounded(self):
        col = self._col(ring_size=4)
        for i in range(10):
            col.finish(RequestTraceContext(f"e{i}"), request=f"e{i}",
                       error="x")
        assert len(col.completed()) == 4
        assert col.find("e9") is not None    # newest survive
        assert col.find("e0") is None

    def test_disabled_default_is_null_collector(self):
        from bigdl_tpu.obs import reqtrace

        col = reqtrace.get_collector()
        assert col is reqtrace.NULL_COLLECTOR and not col.enabled


# ----------------------------------------------------- engine tracing
class TestEngineTracing:
    def test_parity_and_exact_hop_partition(self, lm_model, lm_params,
                                            monkeypatch):
        from bigdl_tpu.serving import LMEngine

        p = [3, 1, 4, 1, 5]
        ref = _ref(lm_model, lm_params, p, 8)

        # untraced run (collector off, request carries no context)
        eng = LMEngine(lm_model, max_batch=2, page_size=8)
        req = eng.submit(p, 8)
        eng.run_until_idle(60)
        assert req.trace is None
        untraced = [int(t) for t in req.tokens]
        eng.close()
        assert list(p) + untraced == ref

        # traced run: byte-identical tokens, spans partition e2e exactly
        monkeypatch.setenv("BIGDL_REQTRACE_SAMPLE", "1.0")
        obs.reset()
        from bigdl_tpu.obs import reqtrace

        eng = LMEngine(lm_model, max_batch=2, page_size=8)
        req = eng.submit(p, 8)
        eng.run_until_idle(60)
        traced = [int(t) for t in req.tokens]
        eng.close()
        assert traced == untraced
        col = reqtrace.get_collector()
        entry = col.find(req.trace.trace_id)
        assert entry is not None and entry["reason"] == "sampled"
        names = [s["name"] for s in entry["spans"]]
        assert "req.queue" in names and "req.prefill" in names \
            and "req.decode" in names
        hop_sum = sum(s["dur_s"] for s in entry["spans"])
        assert hop_sum == pytest.approx(entry["e2e_s"], abs=1e-6)
        assert col.find(str(req.id)) is not None  # request-id lookup

    def test_exemplar_rides_latency_histogram(self, lm_model,
                                              monkeypatch):
        from bigdl_tpu.obs import names
        from bigdl_tpu.obs.metrics import parse_prometheus
        from bigdl_tpu.serving import LMEngine

        monkeypatch.setenv("BIGDL_REQTRACE_SAMPLE", "1.0")
        obs.reset()
        eng = LMEngine(lm_model, max_batch=2, page_size=8)
        req = eng.submit([1, 2, 3], 4)
        eng.run_until_idle(60)
        eng.close()
        text = obs.get_registry().to_prometheus()
        assert " # {" in text                 # OpenMetrics exemplar
        snap = parse_prometheus(text)
        exemplars = [s for s in snap["samples"]
                     if s["name"].startswith(
                         names.REQUEST_LATENCY_SECONDS)
                     and "exemplar" in s]
        assert exemplars, "no exemplar parsed back"
        ex = exemplars[0]["exemplar"]
        assert ex["labels"]["trace_id"] == req.trace.trace_id
        assert ex["value"] > 0.0


# ------------------------------------------------------- real HTTP hop
class TestHTTPHop:
    def test_trace_propagates_router_to_serving_server(
            self, lm_model, lm_params, monkeypatch):
        monkeypatch.setenv("BIGDL_REQTRACE_SAMPLE", "1.0")
        monkeypatch.setenv("BIGDL_OBS_PORT", "0")
        obs.reset()
        from bigdl_tpu.obs import reqtrace, server
        from bigdl_tpu.serving import LMEngine, ServingServer
        from bigdl_tpu.serving.router import (HTTPReplica, Router,
                                              RouterServer)

        eng = LMEngine(lm_model, max_batch=2, page_size=8).start()
        srv = ServingServer(lm=eng, request_timeout_s=60.0)
        router = Router([HTTPReplica("r1", srv.url(""))],
                        request_timeout_s=60.0)
        front = RouterServer(router, port=0)
        try:
            p = [5, 9, 2, 6]
            body = json.dumps({"prompt": p,
                               "max_new_tokens": 6}).encode()
            with urllib.request.urlopen(urllib.request.Request(
                    front.url("/v1/generate"), data=body,
                    headers={"Content-Type": "application/json"}),
                    timeout=60) as r:
                out = json.loads(r.read())
            # tokens bit-match the direct generate() across the hop
            assert [int(t) for t in list(p) + out["tokens"]] \
                == _ref(lm_model, lm_params, p, 6)
            # the response payload stamps retry count + trace id
            assert out["retries"] == 0 and out["trace"]
            col = reqtrace.get_collector()
            entry = col.find(out["trace"])
            assert entry is not None
            names = [s["name"] for s in entry["spans"]]
            # engine-side hops (crossed the HTTP hop in the header)
            # and router-side hops share the ONE trace id
            assert "req.queue" in names and "req.decode" in names
            assert "req.placement" in names and "req.route" in names
            assert entry["request"] == out["id"]
            # /trace?request=<id> on the obs server serves the entry
            obs_srv = server.ensure_server()
            with urllib.request.urlopen(
                    obs_srv.url(f"/trace?request={out['id']}"),
                    timeout=10) as r:
                served = json.loads(r.read())
            assert served["trace"] == out["trace"]
            assert [s["name"] for s in served["spans"]] == names
        finally:
            front.close()
            srv.close()
            eng.close()


# ----------------------------------------------- drain-handoff replay
class TestDrainHandoffTrace:
    def test_one_trace_id_spans_both_replicas(self, lm_model,
                                              lm_params, monkeypatch):
        # tiny sample rate: only the handoff anomaly forces the keep
        monkeypatch.setenv("BIGDL_REQTRACE_SAMPLE", "0.000000001")
        obs.reset()
        from bigdl_tpu.obs import reqtrace
        from bigdl_tpu.serving import LMEngine
        from bigdl_tpu.serving.drain import HANDOFF_ERROR

        col = reqtrace.get_collector()
        e1 = LMEngine(lm_model, max_batch=2, page_size=8)
        e2 = LMEngine(lm_model, max_batch=2, page_size=8)
        p = [1, 2, 3, 4]
        req = e1.submit(p, 6)            # queued, never pumped
        tid = req.trace.trace_id
        records = e1.drain(deadline_s=0.0)
        assert req.error == HANDOFF_ERROR and len(records) == 1
        hd = records[0]
        # the checkpoint carries the context WITH the force-keep flag
        # (the keep decision crosses the process boundary)
        assert hd.trace is not None
        ctx2 = reqtrace.RequestTraceContext.from_header(hd.trace)
        assert ctx2.trace_id == tid and ctx2.keep is True
        entry = col.find(tid)
        assert entry["reason"] == "handoff"
        assert "req.handoff" in [s["name"] for s in entry["spans"]]

        # replay on the absorbing replica under the SAME trace id
        req2 = e2.submit(hd.prompt, hd.max_new_tokens,
                         temperature=hd.temperature, trace=ctx2)
        e2.run_until_idle(60)
        assert [int(t) for t in list(hd.prompt) + req2.tokens] \
            == _ref(lm_model, lm_params, p, 6)
        entry = col.find(tid)
        names = [s["name"] for s in entry["spans"]]
        assert "req.handoff" in names          # replica A's last hop
        assert "req.queue" in names and "req.decode" in names  # B's
        assert col.stats()["sampled"] == {"handoff": 1}
        e1.close()
        e2.close()
