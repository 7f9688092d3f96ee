"""TP-sharded decode over the compressed-collective wire.

Decode is memory-bound: one token's matmuls stream every weight byte
per step, so splitting the weights across ``tp`` devices divides the
per-device bytes (and the KV cache, sharded by heads: a head is a
lane range of the token-major cache's rows, serving/cache.py) at the
price of two small cross-device reductions per block — exactly the two
Megatron psums, run here through ``parallel.wire_psum`` so an int8/fp8
wire compresses the only bytes serving puts on the interconnect.

Layout (``SERVE_TP_RULES``): attention wq/wk/wv rows (= heads) and
fc1 rows split over ``model``; wo and fc2 columns split (their products
are partial sums — ``psum`` after); embeddings, LayerNorms and the
vocab head stay replicated, so the sampled token is identical on every
device and leaves the shard_map replicated.  Prefill stays the
replicated single-device path (compute-bound; the engine writes its
K/V into the head-sharded pages through the normal jit path).

The per-step wire footprint is static — ``2 * n_layer`` psums of
``(batch, dim)`` f32 — and is recorded once at build time
(``bigdl_collective_bytes_total{op="serve_tp_psum"}`` plus the
``path="serve"`` wire-savings gauge).
"""

from __future__ import annotations

import numpy as np

# Megatron row/col split for the serving decode step (module paths of
# the TransformerLM params tree); everything unmatched is replicated.
SERVE_TP_RULES = (
    (r"attn/w[qkv]$", ("model", None)),
    (r"attn/b[qkv]$", ("model",)),
    (r"attn/wo$", (None, "model")),
    (r"fc1/weight$", ("model", None)),
    (r"fc1/bias$", ("model",)),
    (r"fc2/weight$", (None, "model")),
)


def _account(n_layer: int, batch: int, dim: int, tp: int, spec):
    """Static per-step byte model of the 2L block reductions; records
    the counters + the path="serve" savings gauge once at build."""
    from bigdl_tpu.obs import collectives as C
    from bigdl_tpu.parallel import wire as W

    elems = batch * dim
    baseline = C.all_reduce_bytes(elems, "float32", tp) * 2 * n_layer
    if spec is None:
        wire_bytes = baseline
        name = "float32"
    elif not spec.scaled:
        wire_bytes = C.all_reduce_bytes(elems, "bfloat16", tp) \
            * 2 * n_layer
        name = "bfloat16"
    else:
        padded, blk = W.psum_layout(elems, spec, tp)
        ex = sum(C.staged_ring_exchange_bytes(
            padded, tp, blk, spec.wire_name).values())
        ex += C.all_gather_bytes(padded, spec.wire_name, tp)
        ex += C.all_gather_bytes(padded // blk, "float32", tp)
        wire_bytes = ex * 2 * n_layer
        name = spec.wire_name
    C.record("serve_tp_psum", name, wire_bytes, axis_size=tp)
    if spec is not None:
        C.record_savings("serve", baseline, wire_bytes)
    return wire_bytes


def build_tp_decode_step(model, *, tp: int, wire=None, page_size: int,
                         max_batch: int, positions: int):
    """The engine's decode step, sharded ``tp`` ways on the first
    ``tp`` local devices.  Same signature as the single-host step:
    ``step(params, kp, vp, tables, lengths, tokens, temps, active,
    key) -> (kp, vp, next_tokens)`` with replicated params/cache
    accepted (GSPMD reshards on first call); a slot's input is its row
    of ``tokens`` (the last step's, or a prefill's since, still on the
    devices).  The attention body sees the LOCAL head shard."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from bigdl_tpu.optim.distri_optimizer import _shard_map
    from bigdl_tpu.parallel import wire as W
    from bigdl_tpu.parallel.tensor_parallel import param_specs
    from bigdl_tpu.models.transformer import paged_decode_math

    del positions  # shapes flow through shard_map; kept for the API
    tp = int(tp)
    devices = jax.devices()
    if tp > len(devices):
        raise ValueError(f"tp={tp} but only {len(devices)} devices")
    mc = model._config
    n_head, dim = int(mc["n_head"]), model.dim
    hidden = int(mc["mlp_ratio"]) * dim
    if n_head % tp or hidden % tp:
        raise ValueError(
            f"tp={tp} must divide n_head={n_head} and the MLP hidden "
            f"{hidden}")
    mesh = Mesh(np.array(devices[:tp]), ("model",))
    spec = W.resolve(wire)
    _account(model.n_layer, max_batch, dim, tp, spec)

    pspecs = param_specs(model.params(), mesh, rules=SERVE_TP_RULES)
    # heads are contiguous in the token-major cache's last dimension
    # (serving/cache.py), so a head shard is a lane range
    cache_spec = P(None, None, None, "model")
    children = model._children
    n_layer = model.n_layer

    def body(params, kp, vp, tables, lengths, tokens, temps, active,
             key_data):
        key = jax.random.wrap_key_data(key_data)

        def psum_fn(x):
            v, _ = W.psum(x, "model", tp, spec)
            return v

        return paged_decode_math(
            children, n_layer, page_size, params, None, kp, vp,
            tables, lengths, tokens, temps, active, key,
            n_head=n_head // tp, psum=psum_fn)

    mapped = _shard_map(
        body, mesh,
        in_specs=(pspecs, cache_spec, cache_spec, P(), P(), P(), P(),
                  P(), P()),
        out_specs=(cache_spec, cache_spec, P()))

    def step(params, kp, vp, tables, lengths, tokens, temps, active, key):
        return mapped(params, kp, vp, tables, lengths, tokens, temps,
                      active, jax.random.key_data(key))

    return jax.jit(step, donate_argnums=(1, 2))


__all__ = ["SERVE_TP_RULES", "build_tp_decode_step"]
