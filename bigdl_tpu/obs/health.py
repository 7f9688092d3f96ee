"""Training-health telemetry — per-layer numerics, computed on device.

The host side of the stack became observable in the previous obs PRs
(spans, metrics, multi-host merge, collective bytes); this module makes
the *model numerics* observable — the three numbers an operator of a
long run watches per layer:

* **gradient norm** — exploding/vanishing layers, pre-clip;
* **parameter norm** — weight drift, weight-decay sanity;
* **update-to-weight ratio** — ``||Δw|| / ||w||``, the classic
  learning-rate health signal (~1e-3 is healthy for SGD-family).

Everything is **pure device math appended to the jitted train step**:
per-layer squared norms stacked into ONE small ``(L, 4)`` f32 array
(``[grad_sq, param_sq, update_sq, nonfinite_grad_count]`` per layer)
returned as an extra step output.  The driver fetches it every
``BIGDL_HEALTH_EVERY`` steps — one host transfer per K steps when on,
and when off the step compiles WITHOUT the extra output (identical
signature, zero added transfers).  In the sharded (ZeRO) path the
per-layer partial sums are ``psum``'d across the mesh, so every host
reports **global** norms — the per-layer reconstruction obligation that
sharded weight-update schemes create (arXiv:2004.13336).

On top of the raw stats:

* **non-finite localization** — when the PR 1 non-finite guard trips,
  column 3 (non-finite gradient element count per layer) names the
  offending layer(s); the driver emits a ``health.nonfinite_layers``
  trace event carrying the first offender + the full list, and bumps
  ``bigdl_nonfinite_layers_total{layer}``;
* a **numerics anomaly detector**: a
  loss or global-grad-norm observation above ``rolling median *
  BIGDL_HEALTH_SPIKE_FACTOR`` emits a ``health.anomaly`` trace event
  and bumps ``bigdl_numerics_anomalies_total{kind}``.

A "layer" is one parameter leaf of the model's params pytree, named by
its tree path (e.g. ``"0/weight"``) — the same flatten order
``ravel_pytree`` gives the flat ZeRO vector, so the local (tree) and
sharded (flat) stats agree layer-for-layer.
"""

from __future__ import annotations

import collections
import logging
from typing import List, Optional, Sequence

import numpy as np
from bigdl_tpu.obs import names as mnames

log = logging.getLogger("bigdl_tpu.obs")

# columns of the stacked per-layer stats array
GRAD_SQ, PARAM_SQ, UPDATE_SQ, NONFINITE = 0, 1, 2, 3


def layer_names(params_tree) -> List[str]:
    """Tree-path name per parameter leaf, in ``tree_flatten`` (==
    ``ravel_pytree``) order — the label vocabulary of every per-layer
    metric this module emits."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(params_tree)
    names = []
    for path, _leaf in flat:
        names.append("/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path))
    return names


def layer_sizes(params_tree) -> List[int]:
    """Element count per leaf, same order as :func:`layer_names`."""
    import jax

    return [int(np.size(x)) for x in jax.tree.leaves(params_tree)]


# ------------------------------------------------------------ device math
def tree_layer_stats(grad_tree, params_tree, new_params_tree):
    """LocalOptimizer path: per-leaf ``[grad_sq, param_sq, update_sq,
    nonfinite_count]`` stacked to ``(L, 4)`` f32.  Pure jax — traces
    into the jitted step, no host reads."""
    import jax
    import jax.numpy as jnp

    rows = []
    for g, p, q in zip(jax.tree.leaves(grad_tree),
                       jax.tree.leaves(params_tree),
                       jax.tree.leaves(new_params_tree)):
        gf = g.astype(jnp.float32)
        pf = p.astype(jnp.float32)
        df = q.astype(jnp.float32) - pf
        rows.append(jnp.stack([
            jnp.sum(gf * gf),
            jnp.sum(pf * pf),
            jnp.sum(df * df),
            jnp.sum((~jnp.isfinite(gf)).astype(jnp.float32)),
        ]))
    return jnp.stack(rows)


def flat_shard_stats(gshard, wshard, new_wshard, shard_offset, boundaries,
                     axis, positions=None):
    """DistriOptimizer (ZeRO) path: each device holds a contiguous shard
    of the flat vector starting at ``shard_offset`` (traced).  Layers
    occupy contiguous flat ranges (``ravel_pytree`` concatenates in
    leaves order), so a flat position's layer index is
    ``searchsorted(boundaries, idx)`` with ``boundaries`` the cumulative
    layer end offsets.  Per-layer partial sums via ``segment_sum``, then
    ONE ``(L, 4)`` psum over the data axis makes every host's stats
    **global** — pad positions past the true size land in an extra
    dropped segment.

    ``positions`` (optional, traced int32, same length as the shard)
    overrides the contiguous-shard assumption: the bucketed overlap
    exchange leaves each device owning one chunk of every bucket, so
    the caller hands the per-position flat coordinates over directly."""
    import jax
    import jax.numpy as jnp

    n_layers = int(boundaries.shape[0])
    shard_len = gshard.shape[0]
    idx = positions if positions is not None else \
        jax.lax.iota(jnp.int32, shard_len) + shard_offset
    seg = jnp.searchsorted(boundaries, idx, side="right")

    def seg_sum(v):
        return jax.ops.segment_sum(
            v, seg, num_segments=n_layers + 1)[:n_layers]

    gf = gshard.astype(jnp.float32)
    wf = wshard.astype(jnp.float32)
    df = new_wshard.astype(jnp.float32) - wf
    stats = jnp.stack([
        seg_sum(gf * gf),
        seg_sum(wf * wf),
        seg_sum(df * df),
        seg_sum((~jnp.isfinite(gf)).astype(jnp.float32)),
    ], axis=1)
    return jax.lax.psum(stats, axis)


# ------------------------------------------------------------ host analysis
def nonfinite_layers(stats: np.ndarray,
                     names: Sequence[str]) -> List[str]:
    """Names of layers with any non-finite gradient element, flat-layout
    order (the first entry is the first offender)."""
    arr = np.asarray(stats)
    return [names[i] for i in range(min(len(names), arr.shape[0]))
            if arr[i, NONFINITE] > 0]


def summarize(stats: np.ndarray, names: Sequence[str],
              eps: float = 1e-12) -> dict:
    """Derived per-layer numbers from one fetched ``(L, 4)`` array:
    ``{layer: {grad_norm, param_norm, update_ratio, nonfinite}}`` plus
    the global gradient norm."""
    arr = np.asarray(stats, np.float64)
    layers = {}
    for i, name in enumerate(names[: arr.shape[0]]):
        gsq, psq, usq, nf = arr[i]
        layers[name] = {
            "grad_norm": float(np.sqrt(gsq)),
            "param_norm": float(np.sqrt(psq)),
            "update_ratio": float(np.sqrt(usq) / (np.sqrt(psq) + eps)),
            "nonfinite": int(nf) if np.isfinite(nf) else -1,
        }
    with np.errstate(invalid="ignore"):
        global_grad = float(np.sqrt(arr[:, GRAD_SQ].sum()))
    return {"layers": layers, "global_grad_norm": global_grad}


class HealthMonitor:
    """Driver-side half: owns the fetch cadence, the metric/trace/
    TensorBoard fan-out, non-finite localization, and the anomaly
    detector.  Created by the optimizer only when
    ``config.obs.health_every > 0`` — its absence IS the disabled fast
    path (no fetch sites exist at all)."""

    def __init__(self, names: Sequence[str], every: int, registry=None,
                 tracer=None, summary=None, window: int = 64,
                 spike_factor: float = 10.0):
        from bigdl_tpu import obs
        from bigdl_tpu.obs.trace import NULL_TRACER

        self.names = list(names)
        self.every = max(1, int(every))
        self.registry = registry if registry is not None \
            else obs.get_registry()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.summary = summary
        self.spike_factor = float(spike_factor)
        self.fetches = 0          # device->host health transfers, total
        self.anomalies = 0
        self._loss_window: collections.deque = collections.deque(
            maxlen=max(8, int(window)))
        self._gnorm_window: collections.deque = collections.deque(
            maxlen=max(8, int(window)))
        self.last: Optional[dict] = None
        self._grad_gauge = self.registry.gauge(
            mnames.GRAD_NORM,
            "Per-layer global gradient L2 norm (pre-clip)",
            labels=("layer",))
        self._param_gauge = self.registry.gauge(
            mnames.PARAM_NORM, "Per-layer parameter L2 norm",
            labels=("layer",))
        self._ratio_gauge = self.registry.gauge(
            mnames.UPDATE_RATIO,
            "Per-layer ||update|| / ||param|| ratio", labels=("layer",))
        self._gnorm_hist = self.registry.histogram(
            mnames.GLOBAL_GRAD_NORM,
            "Global (all-layer) gradient L2 norm per health sample",
            buckets=(1e-4, 1e-3, 1e-2, 0.1, 0.3, 1.0, 3.0, 10.0, 30.0,
                     100.0, 1e3, 1e4))
        self._nonfinite_ctr = self.registry.counter(
            mnames.NONFINITE_LAYERS_TOTAL,
            "Non-finite-gradient steps attributed per layer",
            labels=("layer",))
        self._anomaly_ctr = self.registry.counter(
            mnames.NUMERICS_ANOMALIES_TOTAL,
            "Loss / grad-norm spikes vs the rolling median",
            labels=("kind",))

    # ------------------------------------------------------------- cadence
    def wants(self, step: int, ok: bool = True) -> bool:
        """Fetch this step's health array?  Every K steps — and always
        when the non-finite guard tripped (localization is the whole
        point of that fetch)."""
        return (not ok) or step % self.every == 0

    # ------------------------------------------------------------- ingest
    def on_step(self, step: int, stats, ok: bool, loss: float):
        """Called at loss-resolve time with the step's device-resident
        health array.  Fetches it only when :meth:`wants` says so; the
        loss-spike check is free (the loss is already host-side)."""
        self._spike("loss_spike", self._loss_window, step, loss)
        if stats is None or not self.wants(step, ok):
            return None
        arr = np.asarray(stats)   # THE device->host health transfer
        self.fetches += 1
        summ = summarize(arr, self.names)
        self.last = {"step": step, **summ}
        for name, row in summ["layers"].items():
            # a NaN gauge carries no information (the non-finite counter
            # below is the signal for that); keep the last finite value
            for gauge, key in ((self._grad_gauge, "grad_norm"),
                               (self._param_gauge, "param_norm"),
                               (self._ratio_gauge, "update_ratio")):
                if np.isfinite(row[key]):
                    gauge.labels(layer=name).set(row[key])
        g = summ["global_grad_norm"]
        if np.isfinite(g):
            self._gnorm_hist.observe(g)
            self._spike("grad_norm_spike", self._gnorm_window, step, g)
        if self.summary is not None:
            add = getattr(self.summary, "add_health", None)
            if add is not None:
                add(step, summ["layers"])
        if not ok:
            self._report_nonfinite(step, arr, loss)
        return summ

    def _report_nonfinite(self, step: int, arr: np.ndarray, loss: float):
        bad = nonfinite_layers(arr, self.names)
        first = bad[0] if bad else None
        counts = {self.names[i]: int(arr[i, NONFINITE])
                  for i in range(min(len(self.names), arr.shape[0]))
                  if arr[i, NONFINITE] > 0}
        if not bad:
            # grads finite but the loss was not — attribute to the loss
            first = "<loss>"
        log.warning(
            "non-finite localization at step %d: first offender %s "
            "(all: %s)", step, first, bad or "loss only")
        self.tracer.event("health.nonfinite_layers", step=step,
                          first=first, layers=bad, counts=counts,
                          loss=loss)
        for name in (bad or [first]):
            self._nonfinite_ctr.labels(layer=name).inc()

    def _spike(self, kind: str, window: collections.deque, step: int,
               value: float):
        """Rolling-median spike detector (8-observation warmup,
        factor from config, structured event + counter)."""
        if self.spike_factor <= 0 or value is None \
                or not np.isfinite(value):
            return
        v = abs(float(value))
        if len(window) >= 8:
            med = float(np.median(window))
            if med > 0 and v > med * self.spike_factor:
                self.anomalies += 1
                log.warning("numerics anomaly at step %d: %s %.6g vs "
                            "rolling median %.6g (> %gx)", step, kind, v,
                            med, self.spike_factor)
                self.tracer.event("health.anomaly", kind=kind, step=step,
                                  value=v, median=med,
                                  factor=self.spike_factor)
                self._anomaly_ctr.labels(kind=kind).inc()
        window.append(v)


def monitor_from_config(params_tree, tracer=None, summary=None):
    """The optimizer's entry point: a :class:`HealthMonitor` when
    ``BIGDL_HEALTH_EVERY`` > 0, else None (the step then builds without
    the health output — same compiled signature as a health-less
    build)."""
    from bigdl_tpu.config import refresh_from_env

    cfg = refresh_from_env().obs
    if cfg.health_every <= 0:
        return None
    return HealthMonitor(layer_names(params_tree), cfg.health_every,
                         tracer=tracer, summary=summary,
                         window=cfg.health_window,
                         spike_factor=cfg.health_spike_factor)
