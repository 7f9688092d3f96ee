"""Fusion-aware kernel auto-tuner — cached cost-model dispatch search.

The hot kernels used to dispatch on hand-picked constants (the
``t * tk >= 4096^2`` lax-vs-Pallas attention policy, budget-derived
conv block sizes), so entire shape regimes never reached the fast path
and the ones that did ran untuned blocks.  Following FADiff's
fusion-aware candidate-search approach (arXiv:2511.22348, PAPERS.md),
this module makes dispatch a measured, cached, regression-gated
decision:

* **candidates** — per call site (flash attention fwd/bwd, 1x1 and kxk
  conv+BN), a small set of ``impl x block-size`` configurations that
  pass the kernels' own symmetric VMEM feasibility models
  (``attention._flash_plan`` / ``conv_bn._kxk_plan``), always
  including the hand-measured static policy;
* **costing** — every XLA candidate is costed with the PR 4 HLO
  ``cost_analysis`` machinery (``obs.runtime.hlo_cost_analysis``, the
  ``instrument_jit`` path): the compiler's own FLOPs/bytes for the
  program it actually builds.  Pallas candidates are opaque custom
  calls to XLA, so they are costed by the kernel's own traffic plan
  (I/O + superblock re-streaming) — documented analytic bytes, same
  units.  The scalar score is a roofline sum
  ``flops/peak + bytes/bandwidth``;
* **measurement** — with ``BIGDL_TUNER_MEASURE=1`` and CONCRETE inputs
  (never inside a jit trace), candidates are additionally timed
  one-shot through a ``jax.jit(value_and_grad)`` probe — the same
  fwd+bwd composite the A/B harnesses (scripts/attn_ab.py,
  scripts/bn_ab.py) measure — and the measured times override the
  model;
* **never lose to the static policy** — the winner is the argmin with
  ties broken toward the static choice, and a measured winner is
  additionally gated through ``obs.regress.check`` (the same verdict
  machinery that gates bench runs against the BENCH_r*.json
  trajectory): a "tuned" config that regresses past the static
  baseline is discarded and the static policy kept, so tuned dispatch
  is >= 1.0x the hand-picked baseline by construction;
* **cache** — decisions persist as JSON under ``BIGDL_TUNER_CACHE``
  keyed on ``(site, shape, dtype, platform)``, so they survive
  restarts and chip-unavailable rounds (bank the evidence once, serve
  it forever).  A corrupt cache file degrades to the static policy —
  it never crashes a run and is never silently clobbered.

Observability: every decision emits a ``tuner.decision`` trace event
and ``bigdl_tuner_decisions_total{site,impl}``; cache traffic rides
``bigdl_tuner_cache_{hits,misses}_total`` and each wall-clock probe
``bigdl_tuner_measurements_total``.  ``obs/report.py`` renders the
"kernel auto-tuner" section from these.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional
from bigdl_tpu.obs import names
from bigdl_tpu.ops._pallas import resolve_interpret

# rough per-platform (peak_flops, peak_hbm_bytes_per_s) for the
# roofline score.  Only the RANKING matters — every candidate of one
# decision is scored with the same constants.
_PEAKS = {
    "tpu": (180e12, 8.0e11),
    "gpu": (1.0e14, 1.0e12),
    "cpu": (2.0e11, 3.0e10),
}

# a MODEL-only (unmeasured) decision may flip the impl away from the
# static policy only when the modeled score beats static's by this
# factor — the analytic model is for ranking, not for close calls; the
# regimes flash exists for (quadratic residual traffic) clear the bar
# by 10-100x, marginal shapes stay on the measured static policy
_MODEL_MARGIN = 0.5

_lock = threading.Lock()
_cache = None
_cache_path = None


# --------------------------------------------------------------------------
# config / obs plumbing
# --------------------------------------------------------------------------


def _cfg():
    from bigdl_tpu.config import refresh_from_env

    return refresh_from_env().tuner


def enabled() -> bool:
    """Is the auto-tuner on (``BIGDL_TUNER=1``)?  Read at call time —
    the fault injector's contract, so tests and late exports work."""
    try:
        return bool(_cfg().enabled)
    except Exception:  # noqa: BLE001 — config must never sink dispatch
        return False


def platform() -> str:
    import jax

    try:
        return jax.default_backend()
    except Exception:  # noqa: BLE001 — backendless host
        return "unknown"


def _counter(name, desc, **labels):
    try:
        from bigdl_tpu import obs

        c = obs.get_registry().counter(name, desc,
                                       labels=tuple(labels) or ())
        (c.labels(**labels) if labels else c).inc()
    except Exception:  # noqa: BLE001 — telemetry never sinks dispatch
        pass


def _event(name, **attrs):
    try:
        from bigdl_tpu import obs

        obs.get_tracer().event(name, **attrs)
    except Exception:  # noqa: BLE001 — telemetry never sinks dispatch
        pass


# --------------------------------------------------------------------------
# decision cache
# --------------------------------------------------------------------------


class TunerCache:
    """JSON decision store.  ``{"version": 1, "decisions": {key: rec}}``.

    Load is tolerant: a corrupt/truncated file flips ``corrupt`` and
    the tuner serves the static policy for every miss (and never
    writes — the evidence stays on disk for the postmortem).  Writes
    are atomic (tmp + rename) so a killed run can't tear the store."""

    VERSION = 1

    def __init__(self, path: Optional[str]):
        self.path = path
        self.decisions: dict = {}
        self.corrupt = False
        self.hits = 0
        self.misses = 0
        if path and os.path.exists(path):
            try:
                with open(path, encoding="utf-8") as fh:
                    doc = json.load(fh)
                if (not isinstance(doc, dict)
                        or doc.get("version") != self.VERSION
                        or not isinstance(doc.get("decisions"), dict)):
                    raise ValueError("bad tuner cache schema")
                self.decisions = doc["decisions"]
            except (OSError, ValueError, json.JSONDecodeError):
                self.corrupt = True

    def get(self, key: str) -> Optional[dict]:
        rec = self.decisions.get(key)
        if rec is not None:
            self.hits += 1
            _counter(names.TUNER_CACHE_HITS_TOTAL,
                     "Tuner decisions served from the cache")
        else:
            self.misses += 1
            _counter(names.TUNER_CACHE_MISSES_TOTAL,
                     "Tuner cache misses (fresh searches)")
        return rec

    def put(self, key: str, rec: dict):
        if self.corrupt:
            return  # never clobber a corrupt store
        self.decisions[key] = rec
        if not self.path:
            return
        try:
            d = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(d, exist_ok=True)
            tmp = f"{self.path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump({"version": self.VERSION,
                           "decisions": self.decisions}, fh)
            os.replace(tmp, self.path)
        except OSError:
            pass  # in-memory decisions still serve this process

    def stats(self) -> dict:
        return {"path": self.path, "entries": len(self.decisions),
                "hits": self.hits, "misses": self.misses,
                "corrupt": self.corrupt}


def get_cache() -> TunerCache:
    """The process cache, rebuilt when ``BIGDL_TUNER_CACHE`` changes
    (read-at-call-time, like the tracer)."""
    global _cache, _cache_path
    path = _cfg().cache_path
    with _lock:
        if _cache is None or path != _cache_path:
            _cache = TunerCache(path)
            _cache_path = path
        return _cache


def reset():
    """Test hook: drop the cache singleton (next access reloads)."""
    global _cache, _cache_path
    with _lock:
        _cache = None
        _cache_path = None


def cache_key(site: str, shape_sig: str, dtype, plat: Optional[str] = None,
              extra: str = "") -> str:
    """Golden key format: ``site|shape|dtype|platform[|extra]`` — the
    (site, shape, dtype, platform) tuple the store is keyed on."""
    import jax.numpy as jnp

    key = f"{site}|{shape_sig}|{jnp.dtype(dtype).name}|{plat or platform()}"
    return f"{key}|{extra}" if extra else key


# --------------------------------------------------------------------------
# costing / measurement
# --------------------------------------------------------------------------


def _score(flops: float, bytes_: float, plat: Optional[str] = None) -> float:
    plat = plat or platform()
    if plat not in _PEAKS:
        raise ValueError(
            f"no roofline peaks for platform {plat!r} (known: "
            f"{sorted(_PEAKS)}); add it to _PEAKS before ranking "
            "candidates on it")
    peak_f, peak_b = _PEAKS[plat]
    return flops / peak_f + bytes_ / peak_b


def _hlo_cost(jitted, args) -> Optional[dict]:
    """HLO ``cost_analysis`` of a jitted candidate via the PR 4 path
    (obs.runtime): the compiler's own FLOPs/bytes.  None when the
    backend can't cost it."""
    try:
        from bigdl_tpu.obs.runtime import abstract_args, hlo_cost_analysis

        return hlo_cost_analysis(jitted, abstract_args(args, {}))
    except Exception:  # noqa: BLE001 — costing is best-effort
        return None


def _concrete(arrays) -> bool:
    """Concrete device/host arrays (measurable), not tracers mid-jit."""
    import jax

    if arrays is None:
        return False
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def _measure(jitted, args, iters: int) -> float:
    """One-shot wall-clock of a compiled candidate (median-free mean
    over ``iters`` after a compile+warmup call)."""
    import jax

    out = jitted(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(max(1, iters)):
        out = jitted(*args)
    jax.block_until_ready(out)
    _counter(names.TUNER_MEASUREMENTS_TOTAL,
             "Wall-clock candidate probes run by the auto-tuner")
    return (time.perf_counter() - t0) / max(1, iters)


def _gate_measured(tuned_label: str, tuned_s: float, static_label: str,
                   static_s: float) -> dict:
    """Regression-gate a measured tuned config against the static
    policy through ``obs.regress.check`` — the same verdict machinery
    (and ``BIGDL_REGRESS_TOLERANCE``) that gates bench runs against
    the BENCH_r*.json trajectory."""
    from bigdl_tpu.obs import regress

    plat = platform()
    fresh = {"source": f"tuned:{tuned_label}", "round": None,
             "platform": plat, "value": None, "step_time_s": tuned_s,
             "step_time_p95_s": None, "compile_count": None}
    base = [{"source": f"static:{static_label}", "round": 0,
             "platform": plat, "value": None, "step_time_s": static_s,
             "step_time_p95_s": None, "compile_count": None}]
    v = regress.check(fresh, base)
    return {"status": v["status"],
            "ratio": v.get("step_time_ratio"),
            "violations": v.get("violations", [])}


def _resolve(site, key, candidates, static_label, analytic, probes,
             arrays):
    """Core search: cache -> (score | measure) -> gate -> cache.

    ``candidates``: {label: decision-payload}; ``analytic``:
    {label: (flops, bytes)}; ``probes``: {label: fn(*arrays)} builders
    for the fwd+bwd measurement/HLO probe (XLA labels only get HLO
    costing)."""
    import jax

    cache = get_cache()
    with _lock:
        rec = cache.get(key)
    if rec is not None:
        _emit(site, rec, "cache")
        return rec

    if cache.corrupt:
        rec = dict(candidates[static_label], site=site, key=key,
                   label=static_label, source="corrupt_cache")
        _emit(site, rec, "corrupt_cache")
        return rec

    cfg = _cfg()
    plat = platform()
    scores = {}
    hlo = {}
    for label, (flops, bytes_) in analytic.items():
        fl, by = flops, bytes_
        if not label.startswith("pallas") and label in probes:
            # XLA candidates: the compiler's own count beats the model
            # (Pallas custom calls are opaque to HloCostAnalysis — the
            # analytic kernel traffic plan stands in)
            try:
                # one jit per DISTINCT candidate, once per cached search
                # — not a per-step re-jit  # graftlint: disable=JX003
                jitted = jax.jit(probes[label])
                cost = _hlo_cost(jitted, arrays) if arrays else None
            except Exception:  # noqa: BLE001
                cost = None
            if cost:
                hlo[label] = cost
                fl = cost.get("flops") or fl
                by = cost.get("bytes_accessed") or by
        scores[label] = _score(fl, by, plat)

    measured = {}
    if cfg.measure and _concrete(arrays):
        for label, probe in probes.items():
            if label not in candidates:
                continue
            try:
                # fresh jit per candidate is the measurement protocol
                # (cold compile excluded by the warmup call)
                measured[label] = _measure(  # graftlint: disable=JX003
                    jax.jit(probe), arrays, cfg.measure_iters)
            except Exception:  # noqa: BLE001 — one broken candidate
                measured.pop(label, None)   # must not sink the search

    gate = None
    if measured and static_label in measured:
        winner = min(measured, key=lambda c: measured[c])
        if measured[winner] >= measured[static_label]:
            winner = static_label  # ties and losses go static
        elif winner != static_label:
            gate = _gate_measured(winner, measured[winner],
                                  static_label, measured[static_label])
            if gate["status"] == "violation":
                winner = static_label
        source = "measured"
    else:
        winner = min(scores, key=lambda c: scores[c]) if scores \
            else static_label
        if winner not in candidates or \
                scores.get(winner, 0) >= scores.get(static_label,
                                                    float("inf")):
            winner = static_label  # model must BEAT static to deviate
        elif (candidates[winner].get("impl")
                != candidates[static_label].get("impl")
                and scores[winner] >= _MODEL_MARGIN
                * scores[static_label]):
            winner = static_label  # impl flips need a decisive margin
        source = "model"

    rec = dict(candidates[winner], site=site, key=key, label=winner,
               source=source, platform=plat, ts=round(time.time(), 3),
               static=static_label,
               scores={c: round(s, 9) for c, s in scores.items()})
    if measured:
        rec["measured_s"] = {c: round(s, 9) for c, s in measured.items()}
    if hlo:
        rec["hlo"] = hlo
    if gate:
        rec["gate"] = gate
    with _lock:
        cache.put(key, rec)
    _emit(site, rec, source)
    return rec


def _emit(site, rec, source):
    _counter(names.TUNER_DECISIONS_TOTAL,
             "Auto-tuner dispatch decisions, by call site and chosen "
             "impl", site=site, impl=rec.get("impl", "?"))
    _event("tuner.decision", site=site, key=rec.get("key"),
           impl=rec.get("impl"), label=rec.get("label"), source=source,
           static=rec.get("static"))


# --------------------------------------------------------------------------
# site: flash attention (fwd/bwd — one decision covers both, the
# custom_vjp ties them)
# --------------------------------------------------------------------------


def decide_attention(q_shape, k_shape, dtype, *, causal: bool,
                     seq_offset: int, static_impl: str, plan,
                     arrays=None) -> Optional[dict]:
    """Dispatch decision for ``dot_product_attention(impl="auto")``.
    Returns ``{"impl": "lax"|"pallas", "blocks": (bq,bk,bkv,bqs)|None}``
    (plus provenance) or None to mean "use the static policy"."""
    try:
        from bigdl_tpu.ops import attention as A

        b, h, tq, d = (int(s) for s in q_shape)
        tk = int(k_shape[-2])
        if not isinstance(seq_offset, int):
            return None  # traced offset: static policy (lax) only
        key = cache_key("attn", f"b{b}h{h}tq{tq}tk{tk}d{d}", dtype,
                        extra=f"c{int(causal)}o{seq_offset}")

        candidates = {"lax": {"impl": "lax", "blocks": None}}
        analytic = {"lax": _attn_cost("lax", None, b, h, tq, tk, d,
                                      dtype, causal)}
        scale = d ** -0.5
        interp = resolve_interpret()

        def _lax_probe(q, k, v):
            import jax
            import jax.numpy as jnp

            def f(q, k, v):
                out = A._reference_attention(q, k, v, causal=causal,
                                             scale=scale,
                                             seq_offset=seq_offset)
                return jnp.sum(out.astype(jnp.float32) ** 2)

            val, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
            return val, grads

        probes = {"lax": _lax_probe}

        # Pallas candidates only where they would run COMPILED (TPU) or
        # where a wall-clock measurement can arbitrate — the analytic
        # model prices Mosaic kernels, not the CPU interpreter, so an
        # unmeasurable non-TPU search must stay on the static policy's
        # side of the impl question
        pallas_ok = (plan is not None
                     and (platform() == "tpu"
                          or (_cfg().measure and _concrete(arrays))))
        if pallas_ok:
            seen = set()
            for bq, bk in ((plan[0], plan[1]), (128, 128), (128, 64),
                           (64, 128), (64, 64)):
                p = A._flash_plan(tq, tk, d, dtype, block_q=bq,
                                  block_k=bk)
                if p is None or p in seen:
                    continue
                seen.add(p)
                label = f"pallas_q{p[0]}k{p[1]}v{p[2]}s{p[3]}"
                candidates[label] = {"impl": "pallas", "blocks": list(p)}
                analytic[label] = _attn_cost("pallas", p, b, h, tq, tk,
                                             d, dtype, causal)
                probes[label] = _flash_probe(A, p, causal, scale,
                                             seq_offset, interp)

        if static_impl == "lax" or plan is None:
            static_label = "lax"
        else:
            static_label = (f"pallas_q{plan[0]}k{plan[1]}"
                            f"v{plan[2]}s{plan[3]}")
        rec = _resolve("attn", key, candidates, static_label, analytic,
                       probes, arrays)
        if rec.get("blocks"):
            rec = dict(rec, blocks=tuple(rec["blocks"]))
        return rec
    except Exception:  # noqa: BLE001 — the tuner must never sink a step
        return None


def _flash_probe(A, plan, causal, scale, seq_offset, interp):
    def probe(q, k, v):
        import jax
        import jax.numpy as jnp

        def f(q, k, v):
            out = A.flash_attention(
                q, k, v, causal=causal, scale=scale, interpret=interp,
                seq_offset=seq_offset, block_q=plan[0], block_k=plan[1],
                block_kv=plan[2], block_qs=plan[3])
            return jnp.sum(out.astype(jnp.float32) ** 2)

        val, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
        return val, grads

    return probe


def _attn_cost(impl, plan, b, h, tq, tk, d, dtype, causal):
    """Analytic (flops, bytes) of the fwd+bwd composite.  The causal
    factor halves the touched tiles; backward recomputes the score
    tiles, hence the 3.5x flops multiplier (1 fwd + 2.5 bwd)."""
    import jax.numpy as jnp

    item = jnp.dtype(dtype).itemsize
    bh = b * h
    cf = 0.5 if causal else 1.0
    flops = 4.0 * bh * tq * tk * d * cf * 3.5
    io = bh * (2 * tq + 2 * tk) * d * item          # q, k, v, out
    if impl == "lax":
        # the (Tq, Tk) f32 score/prob plane makes HBM round trips in
        # both directions (write+read fwd, residual read + dP write
        # bwd) — the quadratic term the flash kernel deletes
        return flops, 3 * io + 4.0 * bh * tq * tk * 4 * cf
    bq, bk, bkv, bqs = plan
    ns_kv = tk // bkv
    ns_q = tq // bqs
    # kv superblocks are refetched per q-block once streaming kicks in
    # (grid index map varies in s), once per bh otherwise; the dkv
    # kernel mirrors that for the q+g streams
    kv_stream = bh * (tq // bq if ns_kv > 1 else 1) * 2 * tk * d * item
    q_stream = bh * (tk // bk if ns_q > 1 else 1) * 2 * tq * d * item
    return flops, 3 * io + 2 * kv_stream + q_stream


# --------------------------------------------------------------------------
# site: fused conv + BN statistics (1x1 / kxk)
# --------------------------------------------------------------------------


def decide_conv_bn(x_shape, w_shape, dtype, *, stride: int, pad: int,
                   arrays=None, interpret: bool = False) -> Optional[dict]:
    """Dispatch decision for ``conv_bn_stats(impl="auto")``.  Returns
    ``{"impl": "pallas"|"xla", "block_o": int}`` (plus provenance) or
    None for "use the static dispatch"."""
    try:
        import jax.numpy as jnp

        from bigdl_tpu.ops import conv_bn as C

        n, c, h, wd = (int(s) for s in x_shape)
        w_shape = tuple(int(s) for s in w_shape)
        o = w_shape[0]
        k = 1 if len(w_shape) == 2 else w_shape[2]
        site = "conv_bn_1x1" if k == 1 else "conv_bn_kxk"
        item = jnp.dtype(dtype).itemsize
        key = cache_key(site,
                        f"n{n}c{c}h{h}w{wd}o{o}k{k}s{stride}p{pad}",
                        dtype)

        static_path = C.kernel_path(x_shape, w_shape, stride=stride,
                                    pad=pad, itemsize=item)
        candidates = {"xla": {"impl": "xla", "block_o": 0}}
        analytic = {"xla": _conv_cost("xla", n, c, h, wd, o, k, stride,
                                      pad, item)}
        probes = {"xla": _conv_probe(C, stride, pad, interpret, "xla", 0)}

        blocks = []
        if static_path.startswith("pallas"):
            if k == 1:
                bo, _ = C._tiles_1x1(o, c, h * wd, item)
            else:
                bo, _, _, _ = C._kxk_plan(c, h, wd, o, k, stride, pad,
                                          item)
            blocks = sorted({bo, max(8, bo // 2)}, reverse=True)
        for bo in blocks:
            label = f"pallas_o{bo}"
            candidates[label] = {"impl": "pallas", "block_o": bo}
            analytic[label] = _conv_cost("pallas", n, c, h, wd, o, k,
                                         stride, pad, item)
            probes[label] = _conv_probe(C, stride, pad, interpret,
                                        "pallas", bo)

        static_label = f"pallas_o{blocks[0]}" if blocks else "xla"
        return _resolve(site, key, candidates, static_label, analytic,
                        probes, arrays)
    except Exception:  # noqa: BLE001 — the tuner must never sink a step
        return None


def _conv_probe(C, stride, pad, interpret, impl, block_o):
    def probe(x, w, shift):
        import jax
        import jax.numpy as jnp

        def f(x, w):
            y, s1, s2 = C._conv_bn_stats_vjp(x, w, shift, stride, pad,
                                             interpret, impl, block_o)
            return (jnp.sum(y.astype(jnp.float32) ** 2)
                    + jnp.sum(s1) + jnp.sum(s2))

        val, grads = jax.value_and_grad(f, argnums=(0, 1))(x, w)
        return val, grads

    return probe


def _conv_cost(impl, n, c, h, wd, o, k, stride, pad, item):
    """Analytic (flops, bytes) of the fused fwd+bwd.  The backward is
    the same analytic XLA conv-grad for both impls; the forward differs
    in whether the output is re-read for the statistics pass (XLA) and
    whether a space-to-depth copy is paid (Pallas stride-2)."""
    ho = (h + 2 * pad - k) // stride + 1
    wo = (wd + 2 * pad - k) // stride + 1
    flops = 2.0 * n * c * k * k * ho * wo * o * 3.0   # fwd + 2x bwd
    x_b = n * c * h * wd * item
    y_b = n * o * ho * wo * item
    w_b = o * c * k * k * item
    common = 3 * (x_b + y_b) + 2 * w_b                # fwd + bwd I/O
    if impl == "xla":
        # the separate statistics pass re-reads the conv output
        return flops, common + y_b
    s2d = 2 * x_b if (stride == 2 and k > 1) else 0   # phase-image copy
    return flops, common + s2d


# --------------------------------------------------------------------------
# site: quantized matmul (int8 decode weights, ROADMAP "widen" item)
# --------------------------------------------------------------------------


def decide_int8_mm(x_shape, w_shape, dtype, *,
                   arrays=None) -> Optional[dict]:
    """Dispatch decision for ``ops.quantized_matmul.int8_matmul
    (impl="auto")``.  Returns ``{"impl": "int8"|"dequant"}`` (plus
    provenance) or None for the static int8 path.

    "int8" is the current implementation (dynamic per-row activation
    quantization + int8 ``dot_general`` with int32 accumulation —
    never-lose static); "dequant" rescales the int8 weight back to f32
    and runs a float matmul — fewer ops on backends whose int8 gemm is
    slow, at 4x the weight-stream bytes.  Both are XLA programs, so
    both ride the HLO ``cost_analysis`` costing when inputs are
    available."""
    try:
        import jax.numpy as jnp

        m = 1
        for s in x_shape[:-1]:
            m *= int(s)
        k = int(x_shape[-1])
        n = int(w_shape[0])
        key = cache_key("int8_mm", f"m{m}k{k}n{n}", dtype)
        flops = 2.0 * m * k * n
        x_b = m * k * 4.0
        out_b = m * n * 4.0
        analytic = {
            # int8: 1-byte weight stream + the dynamic activation
            # quantize round trip (read f32, write+read int8)
            "int8": (flops, n * k + n * 4 + x_b + 2.0 * m * k + out_b),
            # dequant: 1-byte weight read + f32 dequant copy write+read
            "dequant": (flops, n * k + n * 4 + 8.0 * n * k + x_b + out_b),
        }
        candidates = {"int8": {"impl": "int8"},
                      "dequant": {"impl": "dequant"}}
        probes = {lbl: _int8_mm_probe(lbl) for lbl in candidates}
        return _resolve("int8_mm", key, candidates, "int8", analytic,
                        probes, arrays)
    except Exception:  # noqa: BLE001 — the tuner must never sink a step
        return None


def _int8_mm_probe(impl):
    def probe(x, w_q, w_scale):
        import jax.numpy as jnp

        from bigdl_tpu.ops.quantized_matmul import int8_matmul

        y = int8_matmul(x, w_q, w_scale, impl=impl)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    return probe


# --------------------------------------------------------------------------
# pre-warming + reporting
# --------------------------------------------------------------------------


def prewarm_attention(b, h, tq, tk, d, dtype="float32", *,
                      causal=True, seed=0):
    """Offline cache warmer: build concrete inputs and run one
    ``impl="auto"`` dispatch (measuring when BIGDL_TUNER_MEASURE=1).
    Returns the op output so callers can assert numerics."""
    import numpy as np

    import jax.numpy as jnp

    from bigdl_tpu.ops.attention import dot_product_attention

    rs = np.random.RandomState(seed)
    mk = lambda t: jnp.asarray(
        rs.randn(b, h, t, d).astype(np.float32)).astype(dtype)
    return dot_product_attention(mk(tq), mk(tk), mk(tk), causal=causal,
                                 impl="auto")


def prewarm_conv_bn(n, c, h, w, o, k, *, stride=1, pad=0,
                    dtype="float32", seed=0):
    """Offline cache warmer for a fused conv+BN site."""
    import numpy as np

    import jax.numpy as jnp

    from bigdl_tpu.ops.conv_bn import conv_bn_stats

    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(n, c, h, w).astype(np.float32)).astype(dtype)
    wt = jnp.asarray(
        (rs.randn(o, c, k, k) * 0.1).astype(np.float32)).astype(dtype)
    shift = jnp.asarray(rs.randn(o).astype(np.float32))
    return conv_bn_stats(x, wt, shift, stride=stride, pad=pad)


def prewarm_int8_mm(m, k, n, *, dtype="float32", seed=0):
    """Offline cache warmer for the ``int8_mm`` site: quantize a
    random weight per output channel and run one ``impl="auto"``
    matmul on concrete inputs."""
    import numpy as np

    import jax.numpy as jnp

    from bigdl_tpu.ops.quantized_matmul import (int8_matmul,
                                                quantize_per_channel)

    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(m, k).astype(np.float32)).astype(dtype)
    w = jnp.asarray((rs.randn(n, k) * 0.1).astype(np.float32))
    w_q, w_s = quantize_per_channel(w, axis=0)
    return int8_matmul(x, w_q, w_s, impl="auto")


def summary() -> dict:
    """Cache + decision snapshot for ``bench.py`` extras and the A/B
    harnesses' BENCH JSON evidence."""
    cache = get_cache()
    with _lock:
        decisions = [
            {"key": k, "site": r.get("site"), "impl": r.get("impl"),
             "label": r.get("label"), "source": r.get("source"),
             "static": r.get("static"),
             "measured_s": r.get("measured_s"),
             "gate": r.get("gate")}
            for k, r in sorted(cache.decisions.items())]
    return {"enabled": enabled(), "cache": cache.stats(),
            "decisions": decisions}
