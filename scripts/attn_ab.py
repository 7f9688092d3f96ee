"""A/B the TransformerLM train step's attention impl on chip.

Round-5 measured policy (ops/attention.py ``impl="auto"``): XLA's fused
lax attention beats the Pallas flash forward at every length whose
softmax residuals fit, so auto takes lax below T=4096 and flash beyond.
This script reproduces those numbers — and re-evaluates them now that
the flash path has a true blockwise backward — one subprocess per
(T, impl), one at a time, so a hung compile costs only that cell.

``tuned`` is the auto-tuner row (ops/autotune.py): the child enables
``BIGDL_TUNER``, pre-warms the cell's attention shape with concrete
arrays (so candidates are wall-clock measured, fwd+bwd), and runs the
model with ``attn_impl="auto"`` — dispatch then comes from the cached
decision.  All cells share one cache file, and the tuner's
never-lose gate means the tuned row can only match or beat the best
static row; the decisions ride the output line (and bench.py's
``extras.tuner``) so the evidence is banked across
chip-unavailable rounds.

Usage: python scripts/attn_ab.py [impl ...]   (default: pallas lax)
Cells: (T=512,B=16) (T=1024,B=8) (T=2048,B=4) (T=4096,B=2).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELLS = [(512, 16), (1024, 8), (2048, 4), (4096, 2)]
IMPLS = sys.argv[1:] or ["pallas", "lax"]
_VALID = {"auto", "lax", "pallas", "pallas_interpret", "tuned"}
_bad = [i for i in IMPLS if i not in _VALID]
if _bad:
    sys.exit(f"unknown impl {_bad}; choose from {sorted(_VALID)}")


def _run_cell(t: int, b: int, impl: str):
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import lax

    jax.config.update("jax_platforms", "tpu")  # a chip, or an error
    from bigdl_tpu.models.transformer import build_transformer_lm

    attn_impl = impl
    tuner_info = None
    if impl == "tuned":
        os.environ.setdefault("BIGDL_TUNER", "1")
        os.environ.setdefault("BIGDL_TUNER_MEASURE", "1")
        os.environ.setdefault(
            "BIGDL_TUNER_CACHE",
            os.environ.get("ATTN_AB_TUNER_CACHE",
                           "/tmp/bigdl_attn_ab_tuner.json"))
        from bigdl_tpu.ops import autotune

        # pre-warm the cell's shape with concrete arrays so candidates
        # are wall-clock measured; the in-model trace then hits the
        # cache (measurement never runs inside a jit trace)
        autotune.prewarm_attention(b, 8, t, t, 64, causal=True)
        attn_impl = "auto"
        tuner_info = [f"{d['label']}<-{d['source']}"
                      for d in autotune.summary()["decisions"]]
    model = build_transformer_lm(8192, dim=512, n_head=8, n_layer=8,
                                 max_len=t, attn_impl=attn_impl)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randint(0, 8192, (b, t)).astype(np.float32))
    params, state = model.params(), model.state()
    rng = jax.random.key(0)

    def loss_fn(p, x):
        out, _ = model.apply(p, state, x, training=True, rng=rng)
        logp = jax.nn.log_softmax(out.astype(jnp.float32))
        ids = x.astype(jnp.int32)
        tgt = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
        return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))

    def step(p, x):
        loss, g = jax.value_and_grad(loss_fn)(p, x)
        return jax.tree.map(lambda w, gw: w - 1e-3 * gw, p, g), loss

    @jax.jit
    def run(p, x):
        def body(c, _):
            c, loss = step(c, x)
            return c, loss

        _, losses = lax.scan(body, p, None, length=10)
        return losses[-1]

    float(run(params, x))  # compile + warmup
    t0 = time.perf_counter()
    float(run(params, x))
    dt = time.perf_counter() - t0
    rec = {
        "T": t, "batch": b, "impl": impl,
        "tokens_per_sec": round(b * t * 10 / dt, 1),
        "step_ms": round(dt / 10 * 1e3, 2),
    }
    if tuner_info is not None:
        rec["tuner"] = tuner_info
    print(json.dumps(rec), flush=True)


def main():
    child = os.environ.get("ATTN_AB_CHILD")
    if child:
        t, b, impl = child.split(",")
        _run_cell(int(t), int(b), impl)
        return
    if "tuned" in IMPLS and "ATTN_AB_TUNER_CACHE" not in os.environ:
        # one shared decision store across all tuned cells of this run
        os.environ["ATTN_AB_TUNER_CACHE"] = \
            f"/tmp/bigdl_attn_ab_tuner.{os.getpid()}.json"
    for t, b in CELLS:
        for impl in IMPLS:
            t0 = time.time()
            env = dict(os.environ, ATTN_AB_CHILD=f"{t},{b},{impl}")
            try:
                proc = subprocess.run(
                    [sys.executable, os.path.abspath(__file__)],
                    capture_output=True, text=True, timeout=420, env=env)
                out = (proc.stdout or "").strip().splitlines()
                line = out[-1] if out else (proc.stderr or "")[-200:]
            except subprocess.TimeoutExpired:
                line = json.dumps({"T": t, "impl": impl,
                                   "error": "TIMEOUT 420s"})
            print(f"{line}   [{time.time()-t0:.0f}s]", flush=True)


if __name__ == "__main__":
    main()
