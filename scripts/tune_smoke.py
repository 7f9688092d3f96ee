#!/usr/bin/env python
"""--tune smoke: the kernel auto-tuner loop, end to end on CPU.

Driven by ``scripts/run-tests.sh --tune``.  Four stages, each a hard
assert:

1. a FRESH process (``BIGDL_TUNER=1``, ``BIGDL_TUNER_MEASURE=1``, CPU
   interpret mode) tunes one attention shape, one conv+BN shape and
   one ``int8_mm`` shape through the real ``impl="auto"``
   dispatchers, measures candidates (wall clock), and must persist a
   well-formed JSON cache under ``BIGDL_TUNER_CACHE`` with one
   decision per site;
2. a SECOND fresh process re-runs the same shapes against the same
   cache and must serve every decision from it: zero cache misses,
   zero wall-clock re-measurements (the chip-unavailable-round
   contract — decisions survive restarts);
3. numerics under the tuner must match the untuned reference exactly
   (whatever impl won, the answer is the same);
4. ``python -m bigdl_tpu.obs.report`` over the run's trace/metrics
   dirs renders the "kernel auto-tuner" section — decision counts by
   site/impl, cache traffic, and the ``tuner.decision`` events — in
   text AND ``--json``.

Exit 0 only when all four hold.
"""

import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = """
import os, sys
sys.path.insert(0, os.environ["BIGDL_REPO"])
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
from bigdl_tpu import obs
from bigdl_tpu.ops import autotune
from bigdl_tpu.ops.attention import _reference_attention
from bigdl_tpu.ops.conv_bn import _reference

# one attention site (concrete arrays -> measurable) ...
out = autotune.prewarm_attention(1, 2, 128, 256, 16, causal=True)
rs = np.random.RandomState(0)
q = jnp.asarray(rs.randn(1, 2, 128, 16).astype(np.float32))
k = jnp.asarray(rs.randn(1, 2, 256, 16).astype(np.float32))
v = jnp.asarray(rs.randn(1, 2, 256, 16).astype(np.float32))
ref = _reference_attention(q, k, v, causal=True, scale=16 ** -0.5)
from bigdl_tpu.ops.attention import dot_product_attention
got = dot_product_attention(q, k, v, causal=True)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-5)

# ... and one conv+BN site (the restored kxk stride-2 regime)
y, s1, s2 = autotune.prewarm_conv_bn(2, 8, 8, 8, 16, 3, stride=2, pad=1)
x = jnp.asarray(rs.randn(2, 8, 8, 8).astype(np.float32))
w = jnp.asarray((rs.randn(16, 8, 3, 3) * 0.1).astype(np.float32))
sh = jnp.asarray(rs.randn(16).astype(np.float32))
from bigdl_tpu.ops.conv_bn import conv_bn_stats
yt, s1t, s2t = conv_bn_stats(x, w, sh, stride=2, pad=1)
yr, s1r, s2r = _reference(x, w, sh, 2, 1)
np.testing.assert_allclose(np.asarray(yt), np.asarray(yr), atol=1e-4,
                           rtol=1e-4)

# ... and the int8_mm site the int8 decode matmuls ride
autotune.prewarm_int8_mm(4, 32, 64)

summ = autotune.summary()
obs.flush()
print("TUNER_SUMMARY " + __import__("json").dumps(summ), flush=True)
"""


def run(script, **env):
    e = dict(os.environ)
    e.update({k: str(v) for k, v in env.items()})
    e["BIGDL_REPO"] = REPO
    e["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-c", script], env=e,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=600)


def _summary(proc):
    for line in (proc.stdout or "").splitlines():
        if line.startswith("TUNER_SUMMARY "):
            return json.loads(line[len("TUNER_SUMMARY "):])
    raise AssertionError(
        f"worker printed no summary\nstdout: {proc.stdout[-2000:]}\n"
        f"stderr: {proc.stderr[-2000:]}")


def main() -> int:
    with tempfile.TemporaryDirectory() as d:
        cache = os.path.join(d, "tuner_cache.json")
        trace = os.path.join(d, "trace")
        metrics = os.path.join(d, "metrics")
        env = dict(BIGDL_TUNER=1, BIGDL_TUNER_CACHE=cache,
                   BIGDL_TUNER_MEASURE=1, BIGDL_TRACE_DIR=trace,
                   BIGDL_METRICS_DIR=metrics)

        # ---- stage 1: cold tune must persist the cache --------------
        p1 = run(_WORKER, **env)
        assert p1.returncode == 0, (p1.stdout[-2000:], p1.stderr[-2000:])
        s1 = _summary(p1)
        assert os.path.exists(cache), "no cache file persisted"
        doc = json.load(open(cache, encoding="utf-8"))
        assert doc["version"] == 1
        sites = {r["site"] for r in doc["decisions"].values()}
        assert sites == {"attn", "conv_bn_kxk", "int8_mm"}, sites
        assert s1["cache"]["misses"] >= 3
        for rec in doc["decisions"].values():
            assert rec["source"] == "measured", rec
            assert rec["measured_s"], rec
        print(f"[tune_smoke] cold run: {len(doc['decisions'])} "
              f"measured decision(s) persisted -> {cache}")

        # ---- stage 2: warm re-run serves everything from cache ------
        p2 = run(_WORKER, **env)
        assert p2.returncode == 0, (p2.stdout[-2000:], p2.stderr[-2000:])
        s2 = _summary(p2)
        assert s2["cache"]["misses"] == 0, s2["cache"]
        assert s2["cache"]["hits"] >= 3, s2["cache"]
        doc2 = json.load(open(cache, encoding="utf-8"))
        assert doc2["decisions"] == doc["decisions"], \
            "warm run mutated the cache"
        print(f"[tune_smoke] warm run: {s2['cache']['hits']} hit(s), "
              "0 misses, 0 re-measurements")

        # ---- stage 3: report renders the tuner section --------------
        e = dict(os.environ, BIGDL_REPO=REPO, JAX_PLATFORMS="cpu")
        rep = subprocess.run(
            [sys.executable, "-m", "bigdl_tpu.obs.report", trace,
             "--metrics-dir", metrics],
            env=e, cwd=REPO, capture_output=True, text=True, timeout=300)
        assert rep.returncode == 0, rep.stderr[-2000:]
        assert "-- kernel auto-tuner --" in rep.stdout, rep.stdout
        assert "attn:" in rep.stdout and "conv_bn_kxk:" in rep.stdout, \
            rep.stdout
        assert "int8_mm:" in rep.stdout, rep.stdout
        assert "wall-clock probe(s)" in rep.stdout
        rep_j = subprocess.run(
            [sys.executable, "-m", "bigdl_tpu.obs.report", trace,
             "--metrics-dir", metrics, "--json"],
            env=e, cwd=REPO, capture_output=True, text=True, timeout=300)
        assert rep_j.returncode == 0, rep_j.stderr[-2000:]
        tn = json.loads(rep_j.stdout)["tuner"]
        assert tn["decisions_total"], tn
        assert tn["measurements"] >= 2, tn
        assert any(ev.get("site") == "attn" for ev in tn["events"]), tn
        print("[tune_smoke] report renders the kernel auto-tuner "
              "section (text + --json)")
    print("[tune_smoke] OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
