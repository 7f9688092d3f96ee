"""chip_smoke.py — the standing proof that the system starts on the chip.

    python chip_smoke.py                  # the chip run: a TPU, or exit 2

One process (a chip belongs to one process at a time) drives the two
main paths through the entry points a user calls, at the full width of
models the repo supports, with random weights made from a seed:

* train     ResNet-50, 224 px, batch 128, bf16 compute, SGD+momentum,
            ``Optimizer(distributed=False)`` -> ``LocalOptimizer.optimize()``
* serve     TransformerLM 8192 x dim 512 x 8 layers behind
            ``LMEngine(...).start()`` + ``ServingServer``, eight concurrent
            ``POST /v1/generate``
* kernels   every ``pallas_call`` in ``bigdl_tpu/ops`` compiled by Mosaic,
            run, and compared with its lax reference at a written tolerance
* multichip the same ResNet-50 under ``DistriOptimizer`` on a
            ``{"data": 4}`` mesh, global batch 512 (needs >= 4 devices;
            with fewer the summary says so in words)

A phase passes or raises: nothing here turns a failure into a warning.
The wall times printed are set-up information (first call = compile,
later calls), not a benchmark: no rate, no utilization.  When every
phase that ran has passed, stdout ends with two JSON lines: the summary
(phases, compile cache, wall times, ``"claim": null``) and then, as the
last line, the result the driver reads, which has exactly these keys:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--rehearse-cpu`` runs the same control flow at toy sizes on the CPU
(kernels in the Pallas interpreter) to debug this script without a chip;
its summary says ``platform: cpu``.  It is never chosen automatically.
``--phases a,b`` runs a subset (the builder's economy on a chip budget).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# sizes: the chip run, and the CPU rehearsal's toy stand-ins
FULL = dict(
    resnet=dict(depth=50, class_num=1000), img=224, batch=128, steps=6,
    lm=dict(vocab_size=8192, dim=512, n_head=8, n_layer=8, max_len=512),
    prompt_lens=(17, 200), new_tokens=32,
    flash=[  # (B, H, Tq, Tk, D): the first two whole-kv, the last
        (1, 8, 4096, 4096, 64),      # streams its kv in superblocks
        (1, 4, 4096, 4096, 128),
        (1, 2, 2048, 32768, 128),
    ],
    grouped=dict(m=1536, k=6144, n=2048, sizes=(3, 0, 5, 1, 2, 9, 0, 4)),
    # the latent cells' row, page and head rows; contexts from 0 to full
    latent=dict(slots=16, heads=64, row=640, page=16, maxp=128, value=512),
    # the block cell's rows: 4 positions x 32 query heads over 4 key
    # heads of 128 lanes; contexts from 0 to full
    shared=dict(slots=16, positions=4, heads=32, kv_heads=4, head_dim=128,
                page=16, maxp=128),
    conv=[  # (N, C, H, O, k, stride): ResNet-50 sites
        (8, 256, 56, 64, 1, 1),
        (8, 128, 28, 128, 3, 1),
        (8, 128, 56, 128, 3, 2),     # stride 2: space-to-depth rewrite
    ],
    multichip=dict(devices=4, batch=512, steps=4),
)
TINY = dict(
    resnet=dict(depth=18, class_num=10), img=32, batch=8, steps=4,
    lm=dict(vocab_size=64, dim=32, n_head=4, n_layer=2, max_len=64),
    prompt_lens=(5, 20), new_tokens=8,
    flash=[(1, 2, 128, 128, 16), (1, 2, 128, 256, 16)],
    grouped=dict(m=256, k=256, n=128, sizes=(37, 0, 90, 5)),
    latent=dict(slots=4, heads=4, row=128, page=8, maxp=20, value=16),
    shared=dict(slots=4, positions=2, heads=4, kv_heads=2, head_dim=128,
                page=8, maxp=20),
    conv=[(2, 16, 8, 16, 1, 1), (2, 8, 8, 16, 3, 1), (2, 8, 8, 16, 3, 2)],
    multichip=dict(devices=4, batch=16, steps=3),
)

# Written tolerances, set before the first chip run.  The error is the
# largest absolute difference over the largest absolute reference value,
# against a float32 reference at matmul precision "highest" on the same
# inputs.  bf16 keeps 8 bits of mantissa (2^-8 = 0.4 % a rounding), so a
# handful of roundings per element bounds every kernel here by a few per
# cent.
TOL = {"bfloat16": 3e-2}


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileLog:
    """Counts XLA compilations by jitted function name, and persistent-
    cache hits and misses, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.compiles: list = []           # (fun_name, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((str(kw.get("fun_name")), float(duration)))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def of(self, name: str) -> list:
        return [s for f, s in self.compiles if name in f]


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"shape {got.shape} != {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values in kernel output")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def on_platform(tree, platform: str) -> bool:
    import jax

    return all(d.platform == platform
               for leaf in jax.tree.leaves(tree) if hasattr(leaf, "devices")
               for d in leaf.devices())


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------


def _resnet_data(n: int, img: int, classes: int, seed: int):
    import numpy as np

    rs = np.random.RandomState(seed)
    x = rs.randn(n, 3, img, img).astype(np.float32)
    y = (rs.randint(0, classes, n) + 1).astype(np.float32)  # 1-based
    return x, y


def _train(opt, steps: int, name: str, step_fn: str, compiles: CompileLog):
    """Run ``opt.optimize()`` for ``steps`` iterations; every step's loss
    comes back through the TrainSummary a user would attach (stamped on
    arrival, so the first call can be told from the later ones).
    ``step_fn`` is the jitted step's name in JAX's compile events.
    Returns (info, trained model)."""
    import numpy as np

    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.visualization import TrainSummary

    class StampedSummary(TrainSummary):
        def __init__(self, log_dir, app_name):
            super().__init__(log_dir, app_name)
            self.seen = []  # (loss, wall clock on arrival)

        def add_scalar(self, tag, value, step):
            if tag == "Loss":
                self.seen.append((float(value), time.time()))
            return super().add_scalar(tag, value, step)

    opt.set_optim_method(SGD(learningrate=0.01, momentum=0.9,
                             dampening=0.0))
    opt.set_compute_dtype("bfloat16")
    opt.set_end_when(Trigger.max_iteration(steps))
    summary = StampedSummary(os.path.join(OUT_DIR, "summary"),
                             f"{name}-{os.getpid()}")
    opt.set_train_summary(summary)
    n_before = len(compiles.of(step_fn))
    t0 = time.time()
    model = opt.optimize()
    wall = time.time() - t0
    summary.close()
    losses = [v for v, _ in summary.seen]
    stamps = [w for _, w in summary.seen]
    if len(losses) != steps:
        raise AssertionError(f"{name}: {len(losses)} losses for {steps} steps")
    if not np.isfinite(losses).all():
        raise AssertionError(f"{name}: non-finite loss in {losses}")
    if max(losses) - min(losses) < 1e-4:
        raise AssertionError(f"{name}: the loss does not move: {losses}")
    step_compiles = compiles.of(step_fn)[n_before:]
    if len(step_compiles) != 1:
        raise AssertionError(
            f"{name}: {step_fn} compiled {len(step_compiles)} times, "
            "expected exactly once")
    later = np.diff(stamps)[1:]  # the loss is read back one step behind
    info = {
        "losses": [round(v, 4) for v in losses],
        "wall_s": round(wall, 1),
        "first_call_s": round(stamps[0] - t0, 1),
        "step_xla_compile_s": round(step_compiles[0], 1),
        "later_calls_s": round(float(np.mean(later)), 3) if len(later) else None,
    }
    log(f"{name}: losses {info['losses']}")
    log(f"{name}: first call {info['first_call_s']}s (XLA compile or cache "
        f"read {info['step_xla_compile_s']}s), later calls "
        f"{info['later_calls_s']}s each, phase wall {info['wall_s']}s")
    return info, model


def phase_train(cfg, platform, compiles) -> dict:
    from bigdl_tpu.models import build_resnet_imagenet
    from bigdl_tpu.nn import ClassNLLCriterion
    from bigdl_tpu.optim import Optimizer
    from bigdl_tpu.optim.optimizer import LocalOptimizer

    x, y = _resnet_data(2 * cfg["batch"], cfg["img"],
                        cfg["resnet"]["class_num"], seed=0)
    model = build_resnet_imagenet(**cfg["resnet"])
    opt = Optimizer(model, (x, y), ClassNLLCriterion(),
                    batch_size=cfg["batch"], distributed=False)
    if type(opt) is not LocalOptimizer:
        raise AssertionError(f"train: Optimizer() built {type(opt).__name__}")
    info, trained = _train(opt, cfg["steps"], "train", "train_step",
                           compiles)
    if not on_platform(trained.params(), platform):
        raise AssertionError("train: returned parameters are not on "
                             f"{platform}")
    info["params_on"] = platform
    return info


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------


def _post(url: str, payload: dict, timeout: float = 900.0) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _round_of_requests(server, prompts, new_tokens: int) -> list:
    """All prompts at once, one client thread each; returns the
    responses in prompt order, raising the first client error."""
    out = [None] * len(prompts)

    def client(i):
        try:
            out[i] = _post(server.url("/v1/generate"),
                           {"prompt": prompts[i],
                            "max_new_tokens": new_tokens,
                            "temperature": 0.0})
        except Exception as e:  # noqa: BLE001 — re-raised below
            out[i] = e

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900.0)
        if t.is_alive():
            raise AssertionError("serve: a client did not finish in 900 s")
    for r in out:
        if isinstance(r, Exception):
            raise r
    return out


def _serve_latent_expert_model(platform) -> dict:
    """The other kind of block behind the same engine: a small
    LongCat-Flash (latent attention over a one-buffer latent cache, the
    double layer, a dropless expert layer holding 8 of 16 experts beside
    8 zero-compute ones), its greedy tokens scored by the plain
    reference's logit gap.  On the chip float32 products run in bfloat16
    passes, so the limit is a bfloat16 one."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import longcat_flash_chat as ref
    from bigdl_tpu.models.longcat_flash import build_longcat_flash
    from bigdl_tpu.serving import LMEngine

    config = dict(
        vocab_size=96, hidden_size=64, num_layers=2, num_attention_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, ffn_hidden_size=128,
        expert_ffn_hidden_size=32, n_routed_experts=8, router_experts=16,
        held_experts=[4, 12], zero_expert_num=8, moe_topk=4,
        routed_scaling_factor=6.0, rms_norm_eps=1e-5, rope_theta=1e7,
        max_len=64, initializer_range=0.1)
    sizes = ref.sizes_of(config)
    params = ref.init_params(7, sizes, jnp.float32)
    engine = LMEngine(build_longcat_flash(config, params=params),
                      params=params, max_batch=4, page_size=8)
    rs = np.random.RandomState(5)
    prompts = [[int(t) for t in rs.randint(0, 96, n)] for n in (5, 9, 14)]
    reqs = [engine.submit(p, 12) for p in prompts]
    engine.run_until_idle(timeout_s=600)
    if not on_platform((engine.params, engine.cache.buffers()), platform):
        raise AssertionError("serve: the latent cache or the expert "
                             f"model's parameters are not on {platform}")
    gaps = np.concatenate([
        ref.served_gaps(params, sizes, p, list(r.tokens))[0]
        for p, r in zip(prompts, reqs)])
    if any(r.error for r in reqs) or gaps.size != 36 \
            or float(gaps.mean()) > 0.05:
        raise AssertionError(
            f"serve: latent expert model: errors "
            f"{[r.error for r in reqs]}, logit gaps {gaps.tolist()}")
    log(f"serve: small LongCat-Flash behind the same engine: 36 greedy "
        f"tokens, mean logit gap to the float32 reference "
        f"{gaps.mean():.4f} (largest {gaps.max():.4f}; limit of the mean "
        f"0.05), one cache buffer {tuple(engine.cache.kp.shape)}")
    engine.close()
    return {"tokens": int(gaps.size), "served_gap_mean": float(gaps.mean()),
            "served_gap_max": float(gaps.max()),
            "cache_buffers": len(engine.cache.buffers()),
            "cache_shape": list(engine.cache.kp.shape)}


def phase_serve(cfg, platform, compiles) -> dict:
    import numpy as np

    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.models.transformer import build_transformer_lm
    from bigdl_tpu.serving import LMEngine, ServingServer

    RandomGenerator.RNG.set_seed(7)
    lm = dict(cfg["lm"])
    vocab = lm.pop("vocab_size")
    model = build_transformer_lm(vocab, **lm)
    rs = np.random.RandomState(11)
    lo, hi = cfg["prompt_lens"]
    lens = [lo, hi] + [int(v) for v in rs.randint(lo, hi + 1, 6)]
    prompts = [[int(t) for t in rs.randint(0, vocab, n)] for n in lens]
    new = cfg["new_tokens"]

    engine = LMEngine(model, max_batch=8, page_size=16).start()
    server = ServingServer(lm=engine, port=0, request_timeout_s=900.0)
    try:
        rounds = []
        for _ in range(2):  # the first compiles every bucket it meets
            t0 = time.time()
            replies = _round_of_requests(server, prompts, new)
            rounds.append(round(time.time() - t0, 2))
            for i, r in enumerate(replies):
                toks = r["tokens"]
                if len(toks) != new or not all(0 <= t < vocab for t in toks):
                    raise AssertionError(
                        f"serve: request {i} returned {len(toks)} tokens, "
                        f"want {new} in [0, {vocab}): {toks}")
        with urllib.request.urlopen(server.url("/stats"), timeout=60) as resp:
            stats = json.loads(resp.read())["lm"]
        if stats["requests"] != 2 * len(prompts):
            raise AssertionError(f"serve: /stats counts {stats['requests']} "
                                 f"requests, sent {2 * len(prompts)}")
        if not (on_platform(engine.params, platform)
                and on_platform((engine.cache.kp, engine.cache.vp),
                                platform)):
            raise AssertionError("serve: parameters or KV cache are not on "
                                 f"{platform}")
        # reported, not asserted: the temperature-0 contract with
        # generate() has only ever been checked on the CPU
        ref = np.asarray(model.generate(
            engine.params, np.asarray(prompts[0])[None, :], new))[0]
        ref = [int(t) for t in ref[len(prompts[0]):]]
        served = [int(t) for t in replies[0]["tokens"]]
        agree = sum(a == b for a, b in zip(served, ref))
    finally:
        server.close()
        engine.close()
    info = {
        "latent_expert_model": _serve_latent_expert_model(platform),
        "requests": len(prompts), "prompt_lens": lens, "new_tokens": new,
        "first_round_s": rounds[0], "second_round_s": rounds[1],
        "decode_steps": stats["steps"],
        "request0_equals_generate": served == ref,
        "request0_tokens_agreeing": f"{agree}/{new}",
        "params_and_cache_on": platform,
    }
    log(f"serve: {len(prompts)} concurrent requests x 2 rounds, every reply "
        f"{new} in-vocabulary tokens; /stats answers")
    log(f"serve: first round {rounds[0]}s (compiles each prefill and decode "
        f"bucket), second round {rounds[1]}s")
    log(f"serve: request 0 tokens equal generate(): {served == ref} "
        f"({agree}/{new} agree) [reported, not asserted]")
    return info


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------


def _run_kernel(name, fn, args, want, tol, rehearsal) -> dict:
    """Lower, check for the Mosaic custom call, compile, run, compare
    every output with ``want`` (a matching pytree)."""
    import jax

    t0 = time.time()
    lowered = jax.jit(fn).lower(*args)
    mosaic = lowered.as_text().count("tpu_custom_call")
    if not rehearsal and mosaic == 0:
        raise AssertionError(
            f"kernels: {name}: no Mosaic custom call in the lowered module "
            "— an interpreter or reference path stood in for the kernel")
    compiled = lowered.compile()
    t_compile = time.time() - t0
    got = jax.block_until_ready(compiled(*args))
    errs = [rel_err(g, w) for g, w in
            zip(jax.tree.leaves(got), jax.tree.leaves(want))]
    worst = max(errs)
    log(f"kernels: {name}: {mosaic} Mosaic call(s), compile "
        f"{t_compile:.1f}s, max error {worst:.2e} (tolerance {tol:.0e})")
    if worst > tol:
        raise AssertionError(
            f"kernels: {name}: error {worst:.3e} over tolerance {tol:.0e} "
            f"(per output: {[f'{e:.2e}' for e in errs]})")
    return {"mosaic_calls": mosaic, "max_err": float(f"{worst:.3e}"),
            "tol": tol, "compile_s": round(t_compile, 1)}


def phase_kernels(cfg, platform, compiles) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops.attention import (_reference_attention,
                                         dot_product_attention,
                                         static_dispatch)
    from bigdl_tpu.ops.conv_bn import _reference as conv_reference
    from bigdl_tpu.ops.conv_bn import conv_bn_stats, kernel_path

    rehearsal = platform == "cpu"
    out = {}
    rs = np.random.RandomState(3)

    def f32(tree):
        return jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    # ---- flash attention, forward and both backward kernels, through
    # the dispatcher's own choice
    for b, h, tq, tk, d in cfg["flash"]:
        dt = jnp.bfloat16
        q, g = (jnp.asarray(rs.randn(b, h, tq, d), dt) for _ in range(2))
        k, v = (jnp.asarray(rs.randn(b, h, tk, d), dt) for _ in range(2))
        off = tk - tq  # the q chunk is the tail of the kv sequence
        impl = "auto"
        if rehearsal:
            impl = "pallas"  # interpreted; auto never picks it on CPU
        else:
            chosen, plan = static_dispatch(q.shape, k.shape, v.shape, dt,
                                           seq_offset=off)
            if chosen != "pallas":
                raise AssertionError(
                    f"kernels: impl='auto' chose {chosen!r} for Tq={tq} "
                    f"Tk={tk} d={d}; this shape is here to reach the kernel")
            log(f"kernels: flash Tq={tq} Tk={tk} d={d}: auto -> pallas, "
                f"plan (bq, bk, bkv, bqs) = {plan}")

        def flash(q, k, v, g, impl=impl, off=off):
            o, vjp = jax.vjp(
                lambda q, k, v: dot_product_attention(
                    q, k, v, causal=True, impl=impl, seq_offset=off),
                q, k, v)
            return (o,) + vjp(g)

        def truth(q, k, v, g, off=off, scale=d ** -0.5):
            o, vjp = jax.vjp(
                lambda q, k, v: _reference_attention(
                    q, k, v, causal=True, scale=scale, seq_offset=off),
                q, k, v)
            return (o,) + vjp(g)

        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(truth)(*f32((q, k, v, g))))
        out[f"flash_tq{tq}_tk{tk}_d{d}_bf16"] = _run_kernel(
            f"flash fwd+dq+dkv Tq={tq} Tk={tk} d={d} bf16 causal",
            flash, (q, k, v, g), want, TOL["bfloat16"], rehearsal)

    # ---- the expert layer's grouped product, against lax.ragged_dot
    from bigdl_tpu.ops.grouped_matmul import grouped_matmul

    c = cfg["grouped"]
    sizes = jnp.asarray(c["sizes"], jnp.int32)
    # rows behind the last group are nobody's: masked on both sides
    mine = (jnp.arange(c["m"]) < int(sizes.sum()))[:, None]
    lhs = jnp.asarray(rs.randn(c["m"], c["k"]), jnp.bfloat16)
    rhs = jnp.asarray(rs.randn(len(c["sizes"]), c["k"], c["n"])
                      * c["k"] ** -0.5, jnp.bfloat16)

    def grouped(lhs, rhs, impl="pallas"):
        return jnp.where(mine, grouped_matmul(
            lhs, rhs, sizes, impl=impl,
            preferred_element_type=jnp.float32), 0.0)

    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(jax.jit(
            lambda a, b: grouped(a, b, impl="ragged"))(*f32((lhs, rhs))))
    out["grouped_matmul_bf16"] = _run_kernel(
        f"grouped product M={c['m']} K={c['k']} N={c['n']} "
        f"groups={len(c['sizes'])} bf16 vs ragged_dot",
        grouped, (lhs, rhs), want, TOL["bfloat16"], rehearsal)

    # ---- latent decode attention over a paged pool, against a gather
    from bigdl_tpu.ops.decode_attention import latent_decode_attention
    from bigdl_tpu.serving.cache import gather_pages

    def contexts(c):
        """Slots whose contexts run from 0 to full, their pages drawn
        from a permuted pool: ``(span, pool pages, tables, lengths)``."""
        span = c["maxp"] * c["page"]
        lens = np.linspace(0, span - 1, c["slots"]).astype(np.int32)
        pool = 1 + c["slots"] * c["maxp"]
        tables = np.zeros((c["slots"], c["maxp"]), np.int32)
        free = rs.permutation(np.arange(1, pool))
        for i, n in enumerate(lens // c["page"] + 1):
            tables[i, :n], free = free[:n], free[n:]
        return span, pool, jnp.asarray(tables), jnp.asarray(lens)

    c = cfg["latent"]
    span, pool, tables, lens = contexts(c)
    q = jnp.asarray(rs.randn(c["slots"], c["heads"], c["row"]),
                    jnp.bfloat16)
    rows = jnp.asarray(rs.randn(2, pool, c["page"], c["row"]), jnp.bfloat16)
    scale = c["row"] ** -0.5

    def latent(q, rows):
        return latent_decode_attention(q, rows, tables, lens, scale=scale,
                                       value_width=c["value"], layer=1)

    def latent_truth(q, rows):
        ctx = gather_pages(rows, tables, 1)        # (slots, span, row)
        s = jnp.einsum("bhc,bkc->bhk", q, ctx) * scale
        s = jnp.where(jnp.arange(span)[None, None] <= lens[:, None, None],
                      s, -jnp.inf)
        return jnp.einsum("bhk,bkc->bhc", jax.nn.softmax(s, axis=-1),
                          ctx[..., :c["value"]])

    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(
            jax.jit(latent_truth)(*f32((q, rows))))
    out["latent_decode_bf16"] = _run_kernel(
        f"latent decode attention slots={c['slots']} heads={c['heads']} "
        f"row={c['row']} pages of {c['page']} x {c['maxp']} bf16 vs gather",
        latent, (q, rows), want, TOL["bfloat16"], rehearsal)

    # ---- per-head K/V decode attention where query rows share a key
    # head (the page-walking kernel), against a gather
    from bigdl_tpu.ops.decode_attention import paged_decode_attention

    c = cfg["shared"]
    span, pool, tables, lens = contexts(c)
    hkv, d, group = c["kv_heads"], c["head_dim"], c["heads"] // c["kv_heads"]
    q = jnp.asarray(rs.randn(c["slots"], c["positions"], c["heads"], d),
                    jnp.bfloat16)
    kp, vp = (jnp.asarray(rs.randn(2, pool, c["page"], hkv * d),
                          jnp.bfloat16) for _ in range(2))

    def shared(q, kp, vp):
        return paged_decode_attention(q, kp, vp, tables, lens,
                                      page_size=c["page"], layer=1)

    def shared_truth(q, kp, vp):
        k = gather_pages(kp, tables, 1).reshape(c["slots"], span, hkv, d)
        v = gather_pages(vp, tables, 1).reshape(c["slots"], span, hkv, d)
        qg = q.reshape(c["slots"], c["positions"], hkv, group, d)
        s = jnp.einsum("bsjgd,bkjd->bsjgk", qg, k) * d ** -0.5
        s = jnp.where(jnp.arange(span) <= lens[:, None, None, None, None],
                      s, -jnp.inf)
        return jnp.einsum("bsjgk,bkjd->bsjgd", jax.nn.softmax(s, axis=-1),
                          v).reshape(q.shape)

    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(
            jax.jit(shared_truth)(*f32((q, kp, vp))))
    out["grouped_decode_bf16"] = _run_kernel(
        f"per-head decode attention slots={c['slots']} "
        f"positions={c['positions']} heads={c['heads']} over {hkv} of {d} "
        f"lanes, pages of {c['page']} x {c['maxp']} bf16 vs gather",
        shared, (q, kp, vp), want, TOL["bfloat16"], rehearsal)

    # ---- conv + BN statistics, forward (the kernel) and its custom vjp
    for n, ci, hw, o, ksz, stride in cfg["conv"]:
        dt = jnp.bfloat16
        pad = (ksz - 1) // 2
        x = jnp.asarray(rs.randn(n, ci, hw, hw), dt)
        w = jnp.asarray(rs.randn(o, ci, ksz, ksz) * (ci * ksz * ksz) ** -0.5,
                        dt)
        shift = jnp.asarray(rs.randn(o) * 0.1, jnp.float32)
        path = kernel_path(x.shape, w.shape, stride=stride, pad=pad)
        if not path.startswith("pallas"):
            raise AssertionError(f"kernels: conv site takes {path}")
        ho = (hw + 2 * pad - ksz) // stride + 1
        gy = jnp.asarray(rs.randn(n, o, ho, ho), dt)
        gs = jnp.asarray(rs.randn(2, o) / (n * ho * ho), jnp.float32)

        def conv(x, w, shift, gy, gs, fwd=None, stride=stride, pad=pad):
            fwd = fwd or (lambda x, w: conv_bn_stats(
                x, w, shift, stride=stride, pad=pad, impl="pallas"))
            outs, vjp = jax.vjp(fwd, x, w)
            return outs + vjp((gy.astype(outs[0].dtype), gs[0], gs[1]))

        def truth(x, w, shift, gy, gs, stride=stride, pad=pad):
            return conv(x, w, shift, gy, gs,
                        fwd=lambda x, w: conv_reference(x, w, shift,
                                                        stride, pad))

        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(truth)(
                *f32((x, w)), shift, gy, gs))
        out[f"conv_bn_{ksz}x{ksz}_s{stride}_c{ci}_o{o}_h{hw}"] = _run_kernel(
            f"conv+BN {ksz}x{ksz}/{stride} C={ci} O={o} {hw}px N={n} bf16 "
            f"({path})", conv, (x, w, shift, gy, gs), want, TOL["bfloat16"],
            rehearsal)
    out["max_err"] = max(v["max_err"] for v in out.values())
    return out


# --------------------------------------------------------------------------
# multichip
# --------------------------------------------------------------------------


def phase_multichip(cfg, platform, compiles) -> dict:
    import jax

    from bigdl_tpu.engine import Engine
    from bigdl_tpu.models import build_resnet_imagenet
    from bigdl_tpu.nn import ClassNLLCriterion
    from bigdl_tpu.optim import DistriOptimizer

    mc = cfg["multichip"]
    n = mc["devices"]
    devices = jax.devices()
    if len(devices) < n:
        words = (f"not run: the process sees {len(devices)} device(s), "
                 f"the phase needs {n}")
        log(f"multichip: {words}")
        return {"skipped": words}
    devices = devices[:n]
    mesh = Engine.build_mesh({"data": n}, devices=devices)
    x, y = _resnet_data(2 * mc["batch"], cfg["img"],
                        cfg["resnet"]["class_num"], seed=1)
    model = build_resnet_imagenet(**cfg["resnet"])
    opt = DistriOptimizer(model, (x, y), ClassNLLCriterion(),
                          batch_size=mc["batch"], mesh=mesh)
    info, trained = _train(opt, mc["steps"], "multichip", "sharded_step",
                           compiles)

    def shard_devices(a):
        return sorted(s.device.id for s in a.addressable_shards)

    want_ids = sorted(d.id for d in devices)
    for leaf in jax.tree.leaves(trained.params()):
        if shard_devices(leaf) != want_ids:
            raise AssertionError(
                f"multichip: a parameter lives on {shard_devices(leaf)}, "
                f"want {want_ids}")
    vel = opt.optim_method.state["velocity"]
    if shard_devices(vel) != want_ids or tuple(vel.sharding.spec) != ("data",):
        raise AssertionError(
            f"multichip: velocity on {shard_devices(vel)} with spec "
            f"{vel.sharding.spec}, want {want_ids} split on 'data'")
    shard_shapes = {tuple(s.data.shape) for s in vel.addressable_shards}
    if shard_shapes != {(vel.shape[0] // n,)}:
        raise AssertionError(f"multichip: velocity shards {shard_shapes}")
    info["velocity"] = {"spec": str(vel.sharding.spec), "devices": want_ids,
                        "shard_len": vel.shape[0] // n}
    stats = [d.memory_stats() for d in devices]
    if all(s and "bytes_in_use" in s for s in stats):
        used = [int(s["bytes_in_use"]) for s in stats]
        if max(used) > 4 * max(1, min(used)):
            raise AssertionError(
                f"multichip: per-device bytes_in_use not of one order: {used}")
        info["bytes_in_use"] = used
        info["peak_bytes_in_use"] = [int(s.get("peak_bytes_in_use", 0))
                                     for s in stats]
    elif platform == "tpu":
        raise AssertionError("multichip: the TPU reports no memory_stats()")
    log(f"multichip: params on devices {want_ids}; velocity "
        f"{info['velocity']}; bytes_in_use {info.get('bytes_in_use')}")
    return info


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------

PHASES = {"train": phase_train, "serve": phase_serve,
          "kernels": phase_kernels, "multichip": phase_multichip}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of " + ",".join(PHASES))
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on the CPU; never the chip run")
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(phases) - set(PHASES))
    if unknown:
        ap.error(f"unknown phase(s) {unknown}")

    t_start = time.time()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    # importing the package places the compile cache before anything
    # compiles; in a directory without the repo this line is the failure
    import bigdl_tpu  # noqa: F401
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"platform: {device['platform']}  device_kind: {device['kind']}  "
        f"devices: {device['count']}")
    want = "cpu" if args.rehearse_cpu else "tpu"
    if dev.platform != want:
        print(f"chip_smoke: the backend is {dev.platform!r}, not {want!r}; "
              "this script proves nothing off the chip "
              "(--rehearse-cpu is the explicit CPU rehearsal)",
              file=sys.stderr)
        return 2
    cfg = TINY if args.rehearse_cpu else FULL

    from bigdl_tpu import native
    from bigdl_tpu.engine import Engine

    cache_dir = jax.config.jax_compilation_cache_dir
    log(f"compile cache: {cache_dir or 'none (CPU runs keep no cache)'}")
    log(f"native.available(): {native.available()}")
    if not native.available():
        raise AssertionError("the native feed library did not build")
    os.makedirs(OUT_DIR, exist_ok=True)
    Engine.init()
    compiles = CompileLog()

    results = {}
    for name, run_phase in PHASES.items():
        if name not in phases:
            continue
        t0 = time.time()
        log(f"--- phase {name}")
        results[name] = run_phase(cfg, dev.platform, compiles)
        results[name]["phase_wall_s"] = round(time.time() - t0, 1)
        log(f"--- phase {name} passed in {results[name]['phase_wall_s']}s")

    summary = {
        "ok": True,
        "device": device,
        "rehearsal": bool(args.rehearse_cpu),
        "phases": results,
        "compile_cache": {"dir": cache_dir, "hits": compiles.cache_hits,
                          "misses": compiles.cache_misses},
        "native_available": True,
        "wall_s": round(time.time() - t_start, 1),
        "claim": None,
    }
    with open(os.path.join(OUT_DIR, f"summary-{os.getpid()}.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    print(json.dumps(summary), flush=True)
    # the driver reads the last line and refuses any other key in it
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
