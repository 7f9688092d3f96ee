"""Every ``pallas_call`` in ``bigdl_tpu/ops`` cross-lowered for TPU on the CPU.

``jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs the JAX
side of the Mosaic lowering without a chip.  It catches what the CPU
interpreter never sees: a BlockSpec whose last two block dims are neither
(8, 128)-divisible nor full, a scalar store to VMEM, an unsupported
primitive.  Whether libtpu's Mosaic then accepts the module is
``chip_smoke.py``'s kernel phase, on the chip.  Shapes are the smoke's.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops.attention import flash_attention
from bigdl_tpu.ops.conv_bn import conv_bn_stats
from bigdl_tpu.ops.decode_attention import (latent_decode_attention,
                                            paged_decode_attention)
from bigdl_tpu.serving.cache import pool_shape

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)
FULL = chip_smoke.FULL


def _mosaic_calls(fn, *shapes) -> int:
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    return lowered.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("b,h,tq,tk,d", FULL["flash"])
def test_flash_forward_and_both_backwards_lower(b, h, tq, tk, d):
    def f(q, k, v):
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, interpret=False,
                                seq_offset=tk - tq)
            return jnp.sum(o.astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    dt = jnp.bfloat16
    n = _mosaic_calls(f, ((b, h, tq, d), dt), ((b, h, tk, d), dt),
                      ((b, h, tk, d), dt))
    assert n == 3  # forward, dq, dkv


# GPT-2 XL's widths and the benchmark's engine (benchmarks/configs/
# gpt2_xl.json), cut to 2 layers and a 256-word vocabulary: neither
# changes how a program treats the cache
XL = dict(dim=1600, n_head=25, head_dim=64, n_layer=2, max_len=1024,
          vocab=256, max_batch=12, page_size=16, num_pages=481)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_lowers_at_the_engine_shape(dtype):
    """The gather body at the serving cell's widths, on the stacked
    buffer, lowers for the TPU as plain XLA: no kernel of ours in it."""
    c = XL
    b, h, d, page = c["max_batch"], c["n_head"], c["head_dim"], \
        c["page_size"]

    def f(q, kp, vp, tables, lengths):
        return paged_decode_attention(q, kp, vp, tables, lengths,
                                      page_size=page, layer=1)

    kv = (pool_shape(c["num_pages"], page, h, d, c["n_layer"]), dtype)
    n = _mosaic_calls(f, ((b, h, d), dtype), kv, kv,
                      ((b, 32), jnp.int32), ((b,), jnp.int32))
    assert n == 0


@pytest.mark.parametrize("queries", [1, 2])
def test_latent_decode_is_one_kernel_at_the_engine_shape(queries,
                                                         monkeypatch):
    """The latent body at the latent cells' widths (64 head rows on a
    640-lane bfloat16 row, pages of 16, 128 pages a slot; one length a
    slot, or one a query for a step that verifies a draft), on the
    stacked buffer: ONE Mosaic call, and off the CPU no interpreter
    (the CPU backend is the only one handed it)."""
    b, h, r, page, maxp = 128 * queries, 64, 640, 16, 128
    dt = jnp.bfloat16

    def calls():   # a new function a call: nothing traced is reused
        return _mosaic_calls(
            lambda q, pages, tables, lengths: latent_decode_attention(
                q, pages, tables, lengths, scale=0.1, value_width=512,
                layer=1),
            ((b, h, r), dt), ((2, 1 + b * maxp, page, r), dt),
            ((b, maxp), jnp.int32),
            ((b,) if queries == 1 else (b, h), jnp.int32))

    assert calls() == 0       # CPU: interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "some_new_chip")
    assert calls() == 1


# SDAR-30B-A3B-Chat's attention at the cell's engine size
# (benchmarks/configs/sdar_30b_a3b_chat.json): 128 slots, a block of 4
# positions, 32 query heads over 4 key heads of 128 lanes, pages of 16,
# 128 pages a slot, the stacked bfloat16 pools of six layers
GROUPED = dict(slots=128, block=4, heads=32, kv_heads=4, head_dim=128,
               page=16, maxp=128, layers=6)


def _grouped_shapes(c=GROUPED, dt=jnp.bfloat16):
    b, maxp = c["slots"], c["maxp"]
    pool = (pool_shape(1 + b * maxp, c["page"], c["kv_heads"],
                       c["head_dim"], c["layers"]), dt)
    return (((b, c["block"], c["heads"], c["head_dim"]), dt), pool, pool,
            ((b, maxp), jnp.int32), ((b,), jnp.int32))


def _grouped(q, kp, vp, tables, lengths):
    return paged_decode_attention(q, kp, vp, tables, lengths,
                                  page_size=GROUPED["page"], layer=3)


def test_grouped_decode_is_one_kernel_at_the_engine_shape(monkeypatch):
    """Query rows that share a key head at the block cell's widths, on
    the stacked buffers: ONE Mosaic call and no gather, and off the CPU
    no interpreter."""
    def text():   # a new function a call: nothing traced is reused
        args = [jax.ShapeDtypeStruct(s, d) for s, d in _grouped_shapes()]
        return jax.jit(lambda *a: _grouped(*a)).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    assert "tpu_custom_call" not in text()       # CPU: interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "some_new_chip")
    lowered = text()
    assert lowered.count("tpu_custom_call") == 1
    assert 'kernel_name = "grouped_decode_attention"' in lowered
    assert "stablehlo.gather" not in lowered


@pytest.mark.parametrize("n,c,hw,o,k,stride", FULL["conv"])
def test_conv_bn_lowers_at_resnet50_sites(n, c, hw, o, k, stride):
    def f(x, w, shift):
        def loss(x, w):
            y, s1, s2 = conv_bn_stats(x, w, shift, stride=stride,
                                      pad=(k - 1) // 2, impl="pallas",
                                      interpret=False)
            return (jnp.sum(y.astype(jnp.float32)) + jnp.sum(s1)
                    + jnp.sum(s2))

        return jax.grad(loss, argnums=(0, 1))(x, w)

    dt = jnp.bfloat16
    calls = _mosaic_calls(f, ((n, c, hw, hw), dt), ((o, c, k, k), dt),
                          ((o,), jnp.float32))
    assert calls == 1  # the forward; its vjp is XLA conv grads


def test_no_interpreter_by_default_off_the_cpu(monkeypatch):
    """``interpret=None`` means the interpreter on the CPU backend and
    nowhere else: an accelerator under any name compiles the kernel (or
    fails), it is never handed the interpreter in silence."""
    q = jax.ShapeDtypeStruct((1, 2, 128, 16), jnp.float32)

    def lowered_text():
        return jax.jit(
            lambda q: flash_attention(q, q, q, causal=True)
        ).trace(q).lower(lowering_platforms=("tpu",)).as_text()

    assert "tpu_custom_call" not in lowered_text()  # CPU: interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "some_new_chip")
    flash_attention.clear_cache()
    try:
        assert "tpu_custom_call" in lowered_text()
    finally:
        flash_attention.clear_cache()


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a v5e that is described, not attached.  Asked for
    by the slow tests alone, from inside them (never at import: one
    process at a time may load libtpu)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no usable libtpu here
        pytest.skip(f"no TPU topology without a chip: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.slow
def test_libtpu_mosaic_compiles_every_kernel_without_a_chip(one_chip):
    """The whole way down, still without a chip: libtpu can describe a
    v5e topology on a host that has none, and compiling for it runs
    Mosaic itself (vector layout inference, scoped-VMEM allocation).
    Slow-tagged (it starts libtpu); run it before spending chip minutes
    on a kernel change."""
    sh = one_chip

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in shapes]
        lowered = jax.jit(fn).lower(*args)
        assert "tpu_custom_call" in lowered.as_text()
        lowered.compile()

    dt = jnp.bfloat16
    for b, h, tq, tk, d in FULL["flash"]:
        compile_(
            lambda q, k, v, off=tk - tq: jax.grad(
                lambda q, k, v: jnp.sum(flash_attention(
                    q, k, v, causal=True, interpret=False,
                    seq_offset=off).astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v),
            ((b, h, tq, d), dt), ((b, h, tk, d), dt), ((b, h, tk, d), dt))
    for n, ci, hw, o, k, stride in FULL["conv"]:
        compile_(
            lambda x, w, s, k=k, stride=stride: conv_bn_stats(
                x, w, s, stride=stride, pad=(k - 1) // 2, impl="pallas",
                interpret=False),
            ((n, ci, hw, hw), dt), ((o, ci, k, k), dt), ((o,), jnp.float32))


@pytest.mark.slow
def test_libtpu_mosaic_compiles_the_grouped_kernel_where_the_pools_lie(
        one_chip, monkeypatch):
    """The per-head K/V kernel at the block cell's shapes, compiled by
    libtpu's Mosaic for the described v5e: both stacked pools go into
    the kernel as they are handed in (no instruction of a pool's shape
    but the two parameters: no ``kp[layer]``, no gathered copy), and
    the program's temporaries are the scaled, regrouped queries."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in _grouped_shapes()]
    lowered = jax.jit(_grouped).lower(*args)
    assert lowered.as_text().count("tpu_custom_call") == 1
    compiled = lowered.compile()
    ops = _whole_cache_ops(compiled, args[1])
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"grouped kernel: whole-pool instructions {ops}, temporaries "
          f"{temp / 1e6:.1f} MB")
    assert set(ops) <= {"parameter"} and ops.get("parameter", 0) == 2, ops
    assert temp < 64e6, temp


@pytest.mark.slow
def test_engine_programs_work_on_the_cache_as_it_lies(one_chip):
    """The engine's decode step and both prefill buckets of the serving
    cell, weight-free, compiled for the described v5e with the caches
    donated: the buffer handed in is the buffer worked on.  No
    instruction but the parameters and the in-place scatters has the
    whole cache's shape (a ``copy`` there is a layout conversion: four
    of them were 33.5 of the step's 56 ms, ledger PR 24), and the
    temporaries are small beside the cache (the padded working copy
    was 2.56x of it).  Run it before spending chip minutes on the
    cache."""
    import re

    from bigdl_tpu.models.transformer import build_transformer_lm
    from bigdl_tpu.serving import LMEngine

    sh = one_chip
    c = XL
    dt = jnp.bfloat16
    model = build_transformer_lm(
        c["vocab"], dim=c["dim"], n_head=c["n_head"],
        n_layer=c["n_layer"], max_len=c["max_len"])
    params = jax.tree.map(lambda a: a.astype(dt), model.params())
    eng = LMEngine(model, params=params, max_batch=c["max_batch"],
                   page_size=c["page_size"], num_pages=c["num_pages"])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    def like(a):
        return spec(a.shape, a.dtype)

    b, page = c["max_batch"], c["page_size"]
    weights = jax.tree.map(like, eng.weights())
    kp, vp = like(eng.cache.kp), like(eng.cache.vp)
    key = like(jax.random.key(0))
    programs = {"step": eng._step_fn.lower(
        weights, kp, vp, spec((b, 32), jnp.int32), spec((b,), jnp.int32),
        spec((b,), jnp.int32), spec((b,), jnp.float32),
        spec((b,), jnp.bool_), key)}
    for bucket in (128, 256):
        programs[f"prefill{bucket}"] = eng._prefill_fn(bucket).lower(
            weights, kp, vp, spec((1, bucket), jnp.int32),
            spec((), jnp.int32), spec((bucket // page,), jnp.int32),
            spec((), jnp.float32), key, spec((), jnp.int32),
            spec((b,), jnp.int32))
    eng.close()

    dims = ",".join(str(n) for n in eng.cache.kp.shape)
    whole = re.compile(r"= bf16\[%s\]\{[^}]*\} ([\w-]+)\(" % dims)
    buffer_bytes = 2 * eng.cache.kp.size
    for name, lowered in programs.items():
        compiled = lowered.compile()
        ops = {}
        for line in compiled.as_text().splitlines():
            m = whole.search(line)
            if m is None:
                continue
            op = m.group(1)
            if op == "fusion" and "kv_write/scatter" in line:
                op = "scatter fusion"
            ops[op] = ops.get(op, 0) + 1
        assert set(ops) <= {"parameter", "scatter", "scatter fusion"}, \
            (name, ops)
        # K and V, once a layer, in place
        assert ops["scatter fusion"] == 2 * c["n_layer"], (name, ops)
        temp = compiled.memory_analysis().temp_size_in_bytes
        # activations only (the gathered pages stay in fast memory):
        # under a tenth of one cache buffer, even of this 2-layer one
        assert temp < buffer_bytes // 10, (name, temp, buffer_bytes)
        print(f"{name}: whole-cache instructions {ops}, temporaries "
              f"{temp / 1e6:.1f} MB, one cache buffer "
              f"{buffer_bytes / 1e6:.1f} MB")



def _relayout_probe():
    """``scripts/step_relayout_probe.py`` as a module (a script: loaded
    by its path)."""
    spec = importlib.util.spec_from_file_location(
        "step_relayout_probe",
        os.path.join(REPO, "scripts", "step_relayout_probe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_relayout_probe_tells_a_copy_from_a_row_read():
    """The probe's reading of a compiled program, on a recorded one:
    of five operations that are handed 4 MB or more, the copy of a
    whole table into row order is listed with both layouts, and the
    gather that reads 12 rows of it, the head's product, the cache's
    scatter in place and a kernel are counted apart."""
    text = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: bf16[1024,2048], p1: s32[12]) -> bf16[12,2048] {
  %p0 = bf16[1024,2048]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = s32[12]{0:T(128)} parameter(1)
  ROOT %gather.1 = bf16[12,2048]{1,0:T(8,128)(2,1)} gather(%p0, %p1), offset_dims={1}
}

%fused_computation.2 (p0: bf16[12,2048], p1: bf16[1024,2048]) -> bf16[12,1024] {
  %p0 = bf16[12,2048]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[1024,2048]{0,1:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.1 = bf16[12,1024]{1,0:T(8,128)(2,1)} convolution(%p0, %p1), dim_labels=bf_oi->bf
}

%fused_computation.3 (p0: bf16[2,512,16,256], p1: bf16[12,256]) -> bf16[2,512,16,256] {
  %p0 = bf16[2,512,16,256]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[12,256]{1,0:T(8,128)(2,1)} parameter(1)
  ROOT %scatter.1 = bf16[2,512,16,256]{3,2,1,0:T(8,128)(2,1)} scatter(%p0, %p1, %p1), to_apply=%add
}

ENTRY %main (wte: bf16[1024,2048], head: bf16[1024,2048], kp: bf16[2,512,16,256], ids: s32[12]) -> bf16[12,1024] {
  %wte = bf16[1024,2048]{0,1:T(8,128)(2,1)} parameter(0), metadata={op_name="params['wte']['weight']"}
  %head = bf16[1024,2048]{0,1:T(8,128)(2,1)} parameter(1)
  %kp = bf16[2,512,16,256]{3,2,1,0:T(8,128)(2,1)} parameter(2)
  %ids = s32[12]{0:T(128)} parameter(3)
  %copy.199 = bf16[1024,2048]{1,0:T(8,128)(2,1)} copy(%wte), metadata={op_name="params['wte']['weight']"}
  %fusion.1 = bf16[12,2048]{1,0:T(8,128)(2,1)} fusion(%copy.199, %ids), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(step)/jit(_take)/gather"}
  %fusion.3 = bf16[2,512,16,256]{3,2,1,0:T(8,128)(2,1)} fusion(%kp, %fusion.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/kv_write/scatter"}
  %custom-call.1 = bf16[12,2048]{1,0:T(8,128)(2,1)} custom-call(%fusion.1, %fusion.3), custom_call_target="tpu_custom_call"
  ROOT %fusion.2 = bf16[12,1024]{1,0:T(8,128)(2,1)} fusion(%custom-call.1, %head), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(step)/dense/dot_general"}
}
"""
    table_sized = _relayout_probe().table_sized
    found = table_sized(text, 1024 * 2048 * 2)
    assert {k: v for k, v in found.items() if k != "listed"} == {
        "products": 1, "in_place": 1, "kernels": 1, "row_reads": 1}
    assert found["listed"] == [{
        "op": "copy.199", "kind": "copy",
        "result": ["bf16[1024,2048]{1,0:T(8,128)(2,1)}"],
        "operands": ["bf16[1024,2048]{0,1:T(8,128)(2,1)}"],
        "op_name": "params['wte']['weight']"}]
    # a table twice the size: nothing here touches one
    assert table_sized(text, 1024 * 2048 * 4 + 1) == {
        "listed": [], "products": 0, "in_place": 0, "kernels": 0,
        "row_reads": 0}


@pytest.mark.slow
def test_engine_programs_read_of_the_embedding_the_rows_they_gather(
        one_chip):
    """GPT-2 XL's decode step and a prefill at the cell's widths and
    engine size (two layers, the whole ``(50257, 1600)`` embedding),
    from shapes alone, compiled for the described v5e: handed the tree
    the engine hands them (``TransformerLM.serving_tables``: 1600
    values a row padded to 13 lane tiles) no operation but the head's
    product and the gather touches a table's worth of memory; handed
    the caller's tree, both programs first copy the table, which lies
    ids-along-the-lanes, into row order (``copy.199``: 0.49 of the
    step's 6.83 ms, ledger PR 45)."""
    import functools

    from benchmarks.drivers import serve
    from benchmarks.reference import gpt2_xl as ref
    from bigdl_tpu.models.transformer import build_transformer_lm
    from bigdl_tpu.serving.engine import LMEngine

    sh, dt = one_chip, jnp.bfloat16
    sizes = dict(n_layer=2, dim=1600, n_head=25, max_len=1024, vocab=50257,
                 mlp_ratio=4, init_std=0.02)
    b, page, pages = 12, 16, 481

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    def like(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    shapes = jax.eval_shape(functools.partial(ref.init_params, 1, sizes, dt))
    with serve.modules_without_weights():
        probe = build_transformer_lm(
            sizes["vocab"], dim=sizes["dim"], n_head=sizes["n_head"],
            n_layer=sizes["n_layer"], max_len=sizes["max_len"])
    tables = jax.eval_shape(probe.serving_tables, shapes)
    assert {k: v["weight"].shape for k, v in tables.items()} == {
        "wte": (50257, 1664), "wpe": (1024, 1664)}
    caller = like(shapes)
    served = {**caller, **like(tables)}
    buf = spec((sizes["n_layer"], pages, page, sizes["dim"]), dt)
    eng = _engine_of_shapes(probe, caller, page, sizes["max_len"],
                            (buf, buf))
    key = spec((), jax.random.key(0).dtype)
    ints = spec((b,), jnp.int32)

    def programs(weights):
        return {
            "step": LMEngine._build_step(eng).lower(
                weights, buf, buf, spec((b, 32), jnp.int32), ints, ints,
                spec((b,), jnp.float32), spec((b,), jnp.bool_), key),
            "prefill256": LMEngine._prefill_fn(eng, 256).lower(
                weights, buf, buf, spec((1, 256), jnp.int32),
                spec((), jnp.int32), spec((256 // page,), jnp.int32),
                spec((), jnp.float32), key, spec((), jnp.int32), ints)}

    table_sized = _relayout_probe().table_sized
    table_bytes = 50257 * 1600 * 2
    for name, lowered in programs(served).items():
        found = table_sized(lowered.compile().as_text(), table_bytes)
        print(f"served tree, {name}: {found}")
        assert found["listed"] == [], (name, found)
        assert found["products"] == 1 and found["row_reads"] == 1, found
    for name, lowered in programs(caller).items():
        found = table_sized(lowered.compile().as_text(), table_bytes)
        print(f"caller's tree, {name}: {found}")
        assert [(r["kind"], r["operands"][0][:18], r["result"][0][:18])
                for r in found["listed"]] == [
            ("copy", "bf16[50257,1600]{0", "bf16[50257,1600]{1")], found


def _engine_of_shapes(probe, weights, page, max_len, pools, state=()):
    """The engine's own builders (``LMEngine._build_step``,
    ``_prefill_fn``) over shapes in place of an engine: the kind of step
    ``probe`` declares (``serving/steps.py``), over a cache that holds
    the buffers' specs and nothing else."""
    import types

    from bigdl_tpu.serving import engine, steps

    cache = types.SimpleNamespace(
        buffers=lambda: pools + state, pools=lambda: pools, state=state,
        state_bytes_per_slot=lambda: 0)
    kind, _ = steps.choose(probe, weights, page_size=page, max_len=max_len)
    ops = steps.DeviceOps(engine.sample_step, engine.sample_first,
                          engine.pick_greedy, engine.write_slot_state,
                          engine.keep_inactive)
    return types.SimpleNamespace(
        _kind=kind(probe, probe.cache_spec(weights), cache, page, None, ops),
        _qparams=None, cache=cache, _prefill_fns={})


def _kernel_calls(text: str, kernel: str) -> int:
    """How often a lowered program runs the Mosaic kernel ``kernel``:
    the kernel's jitted program is one private function of the module
    (a model's attentions share one traced program), called once an
    attention."""
    import re

    body = text.index(f'kernel_name = "{kernel}"')
    inside = re.findall(r"func\.func private @([\w.]+)\(", text[:body])[-1]
    return len(re.findall(r"call @%s\(" % re.escape(inside), text))


def _whole_cache_ops(compiled, buf) -> dict:
    """Instructions of a compiled program whose result has the whole
    shape and element type of ``buf`` (a page pool, or the slots'
    state), by kind (the cache write is a ``scatter fusion``)."""
    import re

    dims = ",".join(str(n) for n in buf.shape)
    kind = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(buf.dtype).name]
    whole = re.compile(r"= %s\[%s\]\{[^}]*\} ([\w-]+)\(" % (kind, dims))
    ops = {}
    for line in compiled.as_text().splitlines():
        m = whole.search(line)
        if m is not None:
            op = m.group(1)
            if op == "fusion" and "kv_write/scatter" in line:
                op = "scatter fusion"
            ops[op] = ops.get(op, 0) + 1
    return ops


def _laid_out_bytes(compiled, buf) -> int:
    """Bytes of a parameter of ``buf``'s shape as the compiled program
    lays it out: its tiling ``T(rows, lanes)`` rounds the two minor
    dimensions up."""
    import math
    import re

    dims = ",".join(str(n) for n in buf.shape)
    m = re.search(r"f32\[%s\]\{[\d,]+:T\((\d+),(\d+)\)" % dims,
                  compiled.as_text())
    rows, lanes = int(m.group(1)), int(m.group(2))
    shape = list(buf.shape)
    shape[-2] = -(-shape[-2] // rows) * rows
    shape[-1] = -(-shape[-1] // lanes) * lanes
    return 4 * math.prod(shape)


@pytest.mark.slow
@pytest.mark.parametrize("row_align", [128, 1])
def test_latent_engine_programs_work_on_the_cache_as_it_lies(one_chip,
                                                             row_align,
                                                             monkeypatch):
    """LongCat-Flash's decode step and a prefill at the published
    widths (one double layer, 16 of 512 experts held, 128 slots, the
    default pool of 16385 pages), from shapes alone, compiled for the
    described v5e with the latent cache donated.

    A token's row is 512 + 64 = 576 values, 4.5 tiles of 128 lanes.
    Stored as it is (``row_align=1``) the chip's compiler takes the
    cache in another dimension order than the prefill works in and
    copies the whole of it, in and out, and the decode step's attention
    kernel cannot copy a page of 4.5 tiles at all; padded to 640 lanes
    (the model's default) the buffer handed in is the buffer worked
    on.  That is the evidence the padding was chosen by; run it
    before spending chip minutes on the latent cache."""
    import functools

    from benchmarks.reference import longcat_flash_chat as ref
    from bigdl_tpu.models.longcat_flash import LongCatFlash, PUBLISHED
    from bigdl_tpu.serving.engine import LMEngine

    # the program asks the backend whether to take its kernels: here it
    # is being compiled for the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sh = one_chip
    dt = jnp.bfloat16
    sizes = dict(PUBLISHED, num_layers=1, vocab_size=256)
    slots, page, max_len = 128, 16, 2048
    pages = 1 + slots * (max_len // page)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    # the weights' shapes, from the reference's initialiser (nothing is
    # drawn); the model is built around them and draws none either
    cfg = dict(sizes, router_experts=512, n_routed_experts=16,
               held_experts=[0, 16], max_len=max_len)
    shapes = jax.eval_shape(functools.partial(
        ref.init_params, 1, ref.sizes_of(cfg), dt))
    weights = jax.tree.map(lambda a: spec(a.shape, a.dtype), shapes)
    probe = LongCatFlash(max_len=max_len, held_experts=(0, 16),
                         row_align=row_align, params=weights, **sizes)
    cs = probe.cache_spec(weights)
    assert cs["row_width"] == (640 if row_align == 128 else 576)
    buf = spec((cs["layers"], pages, page, cs["row_width"]), dt)
    eng = _engine_of_shapes(probe, weights, page, max_len, (buf,))
    key = spec((), jax.random.key(0).dtype)
    b = slots
    programs = {
        "step": LMEngine._build_step(eng).lower(
            weights, buf, spec((b, 128), jnp.int32), spec((b,), jnp.int32),
            spec((b,), jnp.int32), spec((b,), jnp.float32),
            spec((b,), jnp.bool_), key),
        "prefill256": LMEngine._prefill_fn(eng, 256).lower(
            weights, buf, spec((1, 256), jnp.int32), spec((), jnp.int32),
            spec((256 // page,), jnp.int32), spec((), jnp.float32), key,
            spec((), jnp.int32), spec((b,), jnp.int32))}
    buffer_bytes = 2 * functools.reduce(lambda a, n: a * n, buf.shape)
    for name, lowered in programs.items():
        # the expert layer's grouped products are the Pallas kernel (two
        # distinct ones: up / gate, and down); the step has the latent
        # kernel beside them (one program, called by both attentions)
        assert lowered.as_text().count("tpu_custom_call") >= (
            3 if name == "step" else 2), name
        if row_align == 1 and name == "step":
            # 576 lanes are 4.5 tiles: a page cannot be copied whole
            with pytest.raises(Exception, match="aligned to tiling"):
                lowered.compile()
            continue
        compiled = lowered.compile()
        ops = _whole_cache_ops(compiled, buf)
        temp = compiled.memory_analysis().temp_size_in_bytes
        print(f"row {cs['row_width']} {name}: whole-cache instructions "
              f"{ops}, temporaries {temp / 1e6:.1f} MB, the cache "
              f"{buffer_bytes / 1e6:.1f} MB")
        if row_align == 1:
            # 576 lanes: the whole cache is copied (why the row is padded)
            assert ops.get("copy", 0) >= 1, (name, ops)
            assert temp > buffer_bytes // 2, (name, temp)
        else:
            # the attention kernel's result is (B, H, value_width): a
            # hit of the cache's shape would be a copy made to feed it
            assert set(ops) <= {"parameter", "scatter",
                                "scatter fusion"}, (name, ops)
            # activations only: the kernel reads the cache where it lies
            assert temp < buffer_bytes // 4, (name, temp, buffer_bytes)


@pytest.mark.slow
def test_the_grouped_product_compiles_at_the_expert_layers_shapes(one_chip):
    """megablox's grouped product at the tilings ``ops/grouped_matmul.py``
    picks for the three expert cells, Mosaic compiling each without a
    chip: LongCat-Flash's experts (16 held, 6144 x 2048 and back) at the
    decode step's 1536 rows, a 256-token prefill's 3072 and a 16-token
    prefill's 192 (padded to whole row tiles); JoyAI's and SDAR's (32
    and 128 groups of 2048 x 768 and back) at a step's 4096 rows and a
    256-token prefill's 2048; ZAYA1's (16 groups of 2048 x 2048 and
    back) at a step's and a 256-token prefill's 256 top-1 rows."""
    from bigdl_tpu.ops.grouped_matmul import _tiling, grouped_matmul

    assert _tiling(6144, 2048) == _tiling(2048, 6144) == (128, 2048, 1024)
    assert _tiling(2048, 768) == (128, 2048, 768)
    assert _tiling(768, 2048) == (128, 768, 2048)
    assert _tiling(2048, 2048) == (128, 2048, 1024)
    assert _tiling(64, 32) is None

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    #: groups, (in, hidden), rows a call
    cells = ((16, (6144, 2048), (1536, 3072, 192)),
             (32, (2048, 768), (4096, 2048)),
             (128, (2048, 768), (4096, 2048)),
             (16, (2048, 2048), (256,)))
    for g, (dim, hidden), calls in cells:
        for m in calls:
            for k, n, out in ((dim, hidden, jnp.bfloat16),
                              (hidden, dim, jnp.float32)):
                lowered = jax.jit(
                    lambda a, b, s, out=out: grouped_matmul(
                        a, b, s, impl="pallas", interpret=False,
                        preferred_element_type=out)).lower(
                    spec((m, k), jnp.bfloat16),
                    spec((g, k, n), jnp.bfloat16), spec((g,), jnp.int32))
                assert "tpu_custom_call" in lowered.as_text()
                lowered.compile()


@pytest.mark.slow
def test_draft_engine_programs_work_on_the_cache_as_it_lies(one_chip,
                                                            monkeypatch):
    """JoyAI-LLM-Flash's verify-and-draft step and a prefill at the
    published widths and the cell's engine size (the dense layer, one
    expert layer and the prediction layer; 32 of 256 experts held, 256
    slots, the default pool of 32769 pages), from shapes alone,
    compiled for the described v5e with the latent cache donated: two
    rows written a slot and two queries a slot on the head axis, and
    still no instruction of the whole cache's size but the scatters."""
    import functools

    from benchmarks.reference import joyai_llm_flash as ref
    from bigdl_tpu.models.joyai_flash import JoyAIFlash, PUBLISHED
    from bigdl_tpu.serving.engine import LMEngine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sh = one_chip
    dt = jnp.bfloat16
    sizes = dict(PUBLISHED, num_hidden_layers=2, vocab_size=1024)
    slots, page, max_len = 256, 16, 2048
    pages = 1 + slots * (max_len // page)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    cfg = dict(sizes, router_experts=256, n_routed_experts=32,
               held_experts=[0, 32], max_len=max_len)
    shapes = jax.eval_shape(functools.partial(
        ref.init_params, 1, ref.sizes_of(cfg), dt))
    weights = jax.tree.map(lambda a: spec(a.shape, a.dtype), shapes)
    probe = JoyAIFlash(max_len=max_len, held_experts=(0, 32),
                       params=weights, **sizes)
    cs = probe.cache_spec(weights)
    assert (cs["row_width"], cs["layers"]) == (640, 3)
    buf = spec((cs["layers"], pages, page, cs["row_width"]), dt)
    eng = _engine_of_shapes(probe, weights, page, max_len, (buf,))
    key = spec((), jax.random.key(0).dtype)
    ints = spec((slots,), jnp.int32)
    flags = spec((slots,), jnp.bool_)
    programs = {
        "step": LMEngine._build_step(eng).lower(
            weights, buf, spec((slots, 128), jnp.int32), ints,
            ints, ints, ints, ints, ints, flags, flags),
        "prefill256": LMEngine._prefill_fn(eng, 256).lower(
            weights, buf, spec((1, 256), jnp.int32), spec((), jnp.int32),
            spec((256 // page,), jnp.int32), spec((), jnp.float32), key,
            spec((), jnp.int32), ints, ints)}
    buffer_bytes = 2 * functools.reduce(lambda a, n: a * n, buf.shape)
    for name, lowered in programs.items():
        assert lowered.as_text().count("tpu_custom_call") >= 2, name
        compiled = lowered.compile()
        ops = _whole_cache_ops(compiled, buf)
        temp = compiled.memory_analysis().temp_size_in_bytes
        print(f"draft {name}: whole-cache instructions {ops}, "
              f"temporaries {temp / 1e6:.1f} MB, the cache "
              f"{buffer_bytes / 1e6:.1f} MB")
        assert set(ops) <= {"parameter", "scatter",
                            "scatter fusion"}, (name, ops)
        assert temp < buffer_bytes // 4, (name, temp, buffer_bytes)


@pytest.mark.slow
def test_block_engine_programs_work_on_the_cache_as_it_lies(one_chip,
                                                            monkeypatch):
    """SDAR-30B-A3B-Chat's block step and a prefill at the published
    widths and the cell's engine size (two layers, all 128 experts, the
    whole vocabulary; 128 slots, the default pool of 16385 pages), from
    shapes alone, compiled for the described v5e with both cache
    buffers donated: eight rows written a slot and buffer (the pending
    tail's and the block's), then ONE ``grouped_decode_attention``
    kernel a layer that reads both buffers where they lie (64 query
    rows a key head, a length each; no gather of a pool), and
    no instruction of a whole buffer's size but the scatters.  The
    step's temporaries are the head's float32 logits and little else
    (the gather body's gathered pages and score plane are gone)."""
    import functools

    from benchmarks.reference import sdar_30b_a3b_chat as ref
    from bigdl_tpu.models.sdar_moe import PUBLISHED, SDARMoE
    from bigdl_tpu.serving.engine import LMEngine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sh = one_chip
    dt = jnp.bfloat16
    sizes = dict(PUBLISHED, num_hidden_layers=2)
    slots, page, max_len, block = 128, 16, 2048, 4
    pages = 1 + slots * (max_len // page)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    cfg = dict(sizes, max_len=max_len)
    shapes = jax.eval_shape(functools.partial(
        ref.init_params, 1, ref.sizes_of(cfg), dt))
    weights = jax.tree.map(lambda a: spec(a.shape, a.dtype), shapes)
    probe = SDARMoE(max_len=max_len, params=weights, **sizes)
    cs = probe.cache_spec(weights)
    assert (cs["row_width"], cs["kv_heads"], cs["heads"], cs["buffers"]) \
        == (512, 4, 32, 2)
    buf = spec((cs["layers"], pages, page, cs["row_width"]), dt)
    eng = _engine_of_shapes(probe, weights, page, max_len, (buf, buf))
    key = spec((), jax.random.key(0).dtype)
    ints = spec((slots,), jnp.int32)
    flags = spec((slots,), jnp.bool_)
    wide = spec((slots, block), jnp.int32)
    wide_flags = spec((slots, block), jnp.bool_)
    programs = {
        "step": LMEngine._build_step(eng).lower(
            weights, buf, buf, spec((slots, 128), jnp.int32), ints,
            wide, wide_flags, ints, ints, wide, flags, flags, flags),
        "prefill256": LMEngine._prefill_fn(eng, 256).lower(
            weights, buf, buf, spec((1, 256), jnp.int32),
            spec((), jnp.int32), spec((256 // page,), jnp.int32),
            spec((), jnp.float32), key, spec((), jnp.int32), wide,
            wide_flags)}
    buffer_bytes = 2 * functools.reduce(lambda a, n: a * n, buf.shape)
    for name, lowered in programs.items():
        text = lowered.as_text()
        assert text.count("tpu_custom_call") >= 2, name
        # the attention kernel is the step's alone (the prefill attends
        # its own prompt densely); once a layer, and no pool is gathered
        if name == "step":
            assert _kernel_calls(text, "grouped_decode_attention") \
                == sizes["num_hidden_layers"]
        else:
            assert "grouped_decode_attention" not in text
        compiled = lowered.compile()
        ops = _whole_cache_ops(compiled, buf)
        temp = compiled.memory_analysis().temp_size_in_bytes
        print(f"block {name}: whole-cache instructions {ops}, "
              f"temporaries {temp / 1e6:.1f} MB, one cache buffer "
              f"{buffer_bytes / 1e6:.1f} MB")
        # (the step's write names a row by one index: the compiler
        # scatters into a flat VIEW of the buffer, in place, and the
        # kernel is handed a ``bitcast`` of it back; no copy)
        assert set(ops) <= {"parameter", "scatter", "scatter fusion",
                            "bitcast"}, (name, ops)
        assert temp < 3 * buffer_bytes, (name, temp, buffer_bytes)
        if name == "step":
            # the float32 logits of 512 positions (311 MB) and little
            # else: the gather body's step held 475 MB
            assert temp < 350e6, temp


# ZAYA1-8B's attention at the cell's engine size
# (benchmarks/configs/zaya1_8b.json): 256 slots, one token a slot, 8
# query heads over 2 key heads of 128 lanes, pages of 16, 128 pages a
# slot, the stacked bfloat16 pools of ten layers
CCA = dict(slots=256, block=1, heads=8, kv_heads=2, head_dim=128, page=16,
           maxp=128, layers=10)


def test_cca_decode_is_one_kernel_at_the_engine_shape(monkeypatch):
    """4 query rows a key head over 256-value rows: the page-walking
    kernel's case (``S x H > H_kv``), ONE Mosaic call and no gather."""
    b = CCA["slots"]
    shapes = _grouped_shapes(CCA)
    shapes = (((b, CCA["heads"], CCA["head_dim"]), jnp.bfloat16),) \
        + shapes[1:]

    def text():   # a new function a call: nothing traced is reused
        args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
        return jax.jit(lambda *a: _grouped(*a)).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    monkeypatch.setattr(jax, "default_backend", lambda: "some_new_chip")
    lowered = text()
    assert lowered.count("tpu_custom_call") == 1
    assert 'kernel_name = "grouped_decode_attention"' in lowered
    assert "stablehlo.gather" not in lowered


@pytest.mark.slow
def test_state_engine_programs_work_on_the_cache_as_it_lies(one_chip,
                                                            monkeypatch):
    """ZAYA1-8B's decode step and a prefill at the published widths and
    the cell's engine size (two layers, all 16 experts, the whole
    vocabulary; 256 slots, the default pool of 32769 pages, the slots'
    three state arrays), from shapes alone, compiled for the described
    v5e with both pools AND the state donated: one row written a slot
    and pool, then ONE ``grouped_decode_attention`` kernel a layer at 4
    query rows a key head, no instruction of a whole pool's size but
    the scatters, and temporaries that are the head's float32 logits
    and little else."""
    import functools

    from benchmarks.reference import zaya1_8b as ref
    from bigdl_tpu.models.zaya import PUBLISHED, Zaya
    from bigdl_tpu.serving.engine import LMEngine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sh = one_chip
    dt = jnp.bfloat16
    sizes = dict(PUBLISHED, num_hidden_layers=2)
    slots, page, max_len = 256, 16, 2048
    pages = 1 + slots * (max_len // page)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    cfg = dict(sizes, max_len=max_len, rope_parameters={"hybrid": dict(
        partial_rotary_factor=0.5, rope_theta=5e6)})
    shapes = jax.eval_shape(functools.partial(
        ref.init_params, 1, ref.sizes_of(cfg), dt))
    weights = jax.tree.map(lambda a: spec(a.shape, a.dtype), shapes)
    probe = Zaya(max_len=max_len, params=weights, **sizes)
    cs, ss = probe.cache_spec(weights), probe.state_spec(weights)
    assert (cs["row_width"], cs["kv_heads"], cs["heads"], cs["buffers"]) \
        == (256, 2, 8, 2)
    assert ss["shapes"] == ((1280,), (1280,), (128,))
    buf = spec((cs["layers"], pages, page, cs["row_width"]), dt)
    state = tuple(spec((ss["layers"], slots) + shp, dt)
                  for shp in ss["shapes"])
    eng = _engine_of_shapes(probe, weights, page, max_len, (buf, buf),
                            state=state)
    key = spec((), jax.random.key(0).dtype)
    ints = spec((slots,), jnp.int32)
    flags = spec((slots,), jnp.bool_)
    programs = {
        "step": LMEngine._build_step(eng).lower(
            weights, buf, buf, *state, spec((slots, 128), jnp.int32), ints,
            ints, spec((slots,), jnp.float32), flags, key),
        "prefill256": LMEngine._prefill_fn(eng, 256).lower(
            weights, buf, buf, *state, spec((1, 256), jnp.int32),
            spec((), jnp.int32), spec((256 // page,), jnp.int32),
            spec((), jnp.float32), key, spec((), jnp.int32), ints)}
    buffer_bytes = 2 * functools.reduce(lambda a, n: a * n, buf.shape)
    for name, lowered in programs.items():
        text = lowered.as_text()
        assert text.count("tpu_custom_call") >= 2, name
        if name == "step":
            assert _kernel_calls(text, "grouped_decode_attention") \
                == sizes["num_hidden_layers"]
        else:
            assert "grouped_decode_attention" not in text
        compiled = lowered.compile()
        ops = _whole_cache_ops(compiled, buf)
        temp = compiled.memory_analysis().temp_size_in_bytes
        print(f"state {name}: whole-cache instructions {ops}, "
              f"temporaries {temp / 1e6:.1f} MB, one cache buffer "
              f"{buffer_bytes / 1e6:.1f} MB")
        assert set(ops) <= {"parameter", "scatter",
                            "scatter fusion"}, (name, ops)
        # 256 x 262272 float32 logits are 269 MB
        assert temp < 700e6, (name, temp)



# Falcon-H1-34B's attention at the cell's engine size
# (benchmarks/configs/falcon_h1_34b.json): 128 slots, one token a slot,
# 20 query heads over 4 key heads of 128 lanes, pages of 16, 128 pages a
# slot, the stacked bfloat16 pools of four layers
HYBRID = dict(slots=128, block=1, heads=20, kv_heads=4, head_dim=128,
              page=16, maxp=128, layers=4)


def test_hybrid_decode_is_one_kernel_at_the_engine_shape(monkeypatch):
    """5 query rows a key head (no multiple of the 8 sublanes) over
    512-value rows: the page-walking kernel's case, ONE Mosaic call and
    no gather."""
    b = HYBRID["slots"]
    shapes = _grouped_shapes(HYBRID)
    shapes = (((b, HYBRID["heads"], HYBRID["head_dim"]), jnp.bfloat16),) \
        + shapes[1:]

    def text():   # a new function a call: nothing traced is reused
        args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
        return jax.jit(lambda *a: _grouped(*a)).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    monkeypatch.setattr(jax, "default_backend", lambda: "some_new_chip")
    lowered = text()
    assert lowered.count("tpu_custom_call") == 1
    assert 'kernel_name = "grouped_decode_attention"' in lowered
    assert "stablehlo.gather" not in lowered


def _hybrid_programs(one_chip, layers, slots=128):
    """Falcon-H1-34B's ``jit_step`` and a prefill of 256 at the
    published widths, ``layers`` layers and the cell's engine size (the
    default pool of 16385 pages, the slots' two float32 state arrays),
    lowered from shapes alone for the described chip; with them the
    pools' and the states' specs."""
    import functools

    from benchmarks.reference import falcon_h1_34b as ref
    from bigdl_tpu.models.falcon_h1 import PUBLISHED, FalconH1
    from bigdl_tpu.serving.engine import LMEngine

    dt = jnp.bfloat16
    sizes = dict(PUBLISHED, num_hidden_layers=layers)
    page, max_len = 16, 2048
    pages = 1 + slots * (max_len // page)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = jax.eval_shape(functools.partial(
        ref.init_params, 1, ref.sizes_of(dict(sizes, max_len=max_len)), dt))
    weights = jax.tree.map(lambda a: spec(a.shape, a.dtype), shapes)
    probe = FalconH1(max_len=max_len, params=weights, **sizes)
    cs, ss = probe.cache_spec(weights), probe.state_spec(weights)
    assert (cs["row_width"], cs["kv_heads"], cs["heads"], cs["buffers"]) \
        == (512, 4, 20, 2)
    assert ss["shapes"] == ((32, 256, 128), (3, 5120))
    assert ss["keeps_inactive"]
    buf = spec((cs["layers"], pages, page, cs["row_width"]), dt)
    state = tuple(spec((ss["layers"], slots) + shp, ss["dtype"])
                  for shp in ss["shapes"])
    eng = _engine_of_shapes(probe, weights, page, max_len, (buf, buf),
                            state=state)
    key = spec((), jax.random.key(0).dtype)
    ints = spec((slots,), jnp.int32)
    flags = spec((slots,), jnp.bool_)
    return {
        "step": LMEngine._build_step(eng).lower(
            weights, buf, buf, *state, spec((slots, 128), jnp.int32), ints,
            ints, spec((slots,), jnp.float32), flags, key),
        "prefill256": LMEngine._prefill_fn(eng, 256).lower(
            weights, buf, buf, *state, spec((1, 256), jnp.int32),
            spec((), jnp.int32), spec((256 // page,), jnp.int32),
            spec((), jnp.float32), key, spec((), jnp.int32), ints)}, buf, state


@pytest.mark.slow
def test_hybrid_engine_programs_work_on_cache_and_state_as_they_lie(
        one_chip, monkeypatch):
    """Falcon-H1-34B's decode step and a prefill (two layers; 128 slots)
    compiled for the described v5e with both pools AND the state
    donated: ONE ``grouped_decode_attention`` kernel a layer at 5 query
    rows a key head, no instruction of a whole pool's size but the
    scatters, NO COPY of the slots' ``H`` (1 GB at two layers) and none
    of its size among the temporaries: the state is updated where it
    lies."""
    import functools

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layers = 2
    programs, buf, state = _hybrid_programs(one_chip, layers)
    h_bytes = 4 * functools.reduce(lambda a, n: a * n, state[0].shape)
    for name, lowered in programs.items():
        text = lowered.as_text()
        if name == "step":
            assert _kernel_calls(text, "grouped_decode_attention") == layers
        else:
            assert "grouped_decode_attention" not in text
        compiled = lowered.compile()
        ops = _whole_cache_ops(compiled, buf)
        held = _whole_cache_ops(compiled, state[0])
        temp = compiled.memory_analysis().temp_size_in_bytes
        print(f"hybrid {name}: whole-cache instructions {ops}, whole-state "
              f"instructions {held}, temporaries {temp / 1e6:.1f} MB, the "
              f"slots' H {h_bytes / 1e6:.1f} MB")
        assert set(ops) <= {"parameter", "scatter",
                            "scatter fusion"}, (name, ops)
        assert "copy" not in held and "copy-start" not in held, (name, held)
        # 128 x 261120 float32 logits are 134 MB; a layer's slice of H
        # would be 537 MB
        assert temp < 500e6, (name, temp)


# (benchmarks/configs/ling_3_flash_vl.json): 256 slots, six KDA layers'
# stacked float32 state, 32 heads of 128 x 128
KDA = dict(layers=6, slots=256, heads=32, dk=128, dv=128)


def test_kda_state_update_is_one_kernel_in_place_at_the_engine_shape():
    """The delta-rule state's decode step at the cell's shape: ONE Mosaic
    call, 16 heads of a slot a grid step, and the stacked state its
    input AND its output (operand 1, behind the prefetched layer)."""
    from bigdl_tpu.ops import delta_state

    c = KDA
    f32 = jnp.float32
    row = ((c["slots"], c["heads"], c["dk"]), f32)
    shapes = (((c["layers"], c["slots"], c["heads"], c["dk"], c["dv"]),
               f32), ((1,), jnp.int32), row, row, row,
              ((c["slots"], c["heads"], c["dv"]), f32),
              ((c["slots"], c["heads"]), f32))
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    lowered = delta_state._program(False).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert lowered.count("tpu_custom_call") == 1
    assert 'kernel_name = "kda_state_update"' in lowered
    assert "output_operand_aliases" in lowered
    assert delta_state._heads_a_block(c["heads"], c["dk"] * c["dv"] * 4) \
        == 16


@pytest.mark.slow
def test_kda_engine_programs_work_on_cache_and_state_as_they_lie(
        one_chip, monkeypatch):
    """Ling-3.0-flash's decode step and a prefill of 256 at the published
    widths and the cell's size (7 layers, 256 slots, 64 held experts a
    layer, 32769 pages of ONE cached layer, six layers of float32 state)
    compiled for the described v5e with the pool AND the state donated:
    ONE ``kda_state_update`` program called twice a KDA layer and one
    ``latent_decode_attention``, NO COPY of the slots' ``S`` (two
    arrays of 1.6 GB) and nothing of its size among the temporaries."""
    import functools
    import json

    from benchmarks.reference import ling_3_flash_vl as ref
    from bigdl_tpu.models.ling_flash import build_ling_flash
    from bigdl_tpu.serving.engine import LMEngine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "ling_3_flash_vl.json"), encoding="utf-8") as fh:
        cfg = json.load(fh)
    dt, slots, page, max_len = jnp.bfloat16, 256, 16, 2048
    pages = 1 + slots * (max_len // page)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = jax.eval_shape(functools.partial(
        ref.init_params, 1, ref.sizes_of(cfg), dt))
    weights = jax.tree.map(lambda a: spec(a.shape, a.dtype), shapes)
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert 5.92e9 < held < 5.95e9
    probe = build_ling_flash(cfg, params=weights)
    cs, ss = probe.cache_spec(weights), probe.state_spec(weights)
    assert (cs["layers"], cs["row_width"], cs["buffers"],
            cs["attn_query_rows"], cs["expert_slots"]) == (1, 640, 1, 32,
                                                           384)
    assert ss["layers"] == 6 and ss["keeps_inactive"]
    # S in two arrays of 16 heads: 1.6 GB each over 6 layers x 256 slots
    assert ss["shapes"] == ((16, 128, 128), (16, 128, 128), (3, 12288))
    buf = spec((1, pages, page, 640), dt)
    state = tuple(spec((6, slots) + shp, ss["dtype"])
                  for shp in ss["shapes"])
    eng = _engine_of_shapes(probe, weights, page, max_len, (buf,),
                            state=state)
    key = spec((), jax.random.key(0).dtype)
    ints = spec((slots,), jnp.int32)
    flags = spec((slots,), jnp.bool_)
    programs = {
        "step": LMEngine._build_step(eng).lower(
            weights, buf, *state, spec((slots, 128), jnp.int32), ints, ints,
            spec((slots,), jnp.float32), flags, key),
        "prefill256": LMEngine._prefill_fn(eng, 256).lower(
            weights, buf, *state, spec((1, 256), jnp.int32),
            spec((), jnp.int32), spec((256 // page,), jnp.int32),
            spec((), jnp.float32), key, spec((), jnp.int32), ints)}
    for name, lowered in programs.items():
        text = lowered.as_text()
        if name == "step":
            assert _kernel_calls(text, "kda_state_update") == 2 * 6
            assert _kernel_calls(text, "latent_decode_attention") == 1
        else:
            assert "kda_state_update" not in text
        compiled = lowered.compile()
        ops = _whole_cache_ops(compiled, buf)
        kept = _whole_cache_ops(compiled, state[0])
        mem = compiled.memory_analysis()
        print(f"kda {name}: whole-cache instructions {ops}, whole-state "
              f"instructions {kept}, arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e6:.1f} MB")
        # a pool of ONE cached layer: its layer axis goes by a bitcast
        assert set(ops) <= {"parameter", "scatter", "scatter fusion",
                            "bitcast"}, (name, ops)
        assert "copy" not in kept and "copy-start" not in kept, (name, kept)
        assert 10.0e9 < mem.argument_size_in_bytes < 10.1e9
        # a layer's slice of S would be 537 MB
        assert mem.temp_size_in_bytes < 400e6, (name, mem.temp_size_in_bytes)


# (benchmarks/configs/olmo_hybrid_7b.json): 256 slots, three linear
# layers' stacked float32 state, 30 heads of 96 x 192 kept (96, 5760)
GDN = dict(layers=3, slots=256, heads=30, dk=96, dv=192)


def test_gdn_state_update_is_one_kernel_in_place_at_the_engine_shape():
    """The gated delta rule's decode step at the cell's shape: ONE Mosaic
    call, 8 slots of 2 heads (3 whole lane tiles) a grid step, and the
    stacked state, its heads along the lanes, its input AND its output
    (operand 1, behind the prefetched layer)."""
    from bigdl_tpu.ops import delta_state

    c = GDN
    f32 = jnp.float32
    row = ((c["slots"], c["heads"], c["dk"]), f32)
    gate = ((c["slots"], c["heads"]), f32)
    shapes = (((c["layers"], c["slots"], c["dk"], c["heads"] * c["dv"]),
               f32), ((1,), jnp.int32), gate, row, row,
              ((c["slots"], c["heads"] * c["dv"]), f32), gate)
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    lowered = delta_state._lane_program(False).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert lowered.count("tpu_custom_call") == 1
    assert 'kernel_name = "gdn_state_update"' in lowered
    assert "output_operand_aliases" in lowered
    assert delta_state._lane_block(c["slots"], c["heads"], c["dk"],
                                   c["dv"]) == (8, 2)
    # what the first kernel's layout cannot offer at this shape
    with pytest.raises(ValueError, match="sublane"):
        delta_state._heads_a_block(c["heads"], c["dk"] * c["dv"] * 4)


def test_one_query_row_a_key_head_streams_at_the_engine_shape(monkeypatch):
    """Olmo-Hybrid's full attention at the cell's size: 30 query heads
    over 30 key heads of 128 lanes, 256 slots, a pool of 32769 pages of
    16 rows of 7,680 B (4 GB a buffer): ONE Mosaic call and no gather,
    chosen by the pool's shape alone; the same call over GPT-2 XL's pool
    (481 pages of 25 heads of 64) is the gather and no kernel."""
    from bigdl_tpu.ops import decode_attention as da

    def text(slots, heads, d, pages):
        bf = jnp.bfloat16
        pool = ((1, pages, 16, heads * d), bf)
        shapes = (((slots, heads, d), bf), pool, pool,
                  ((slots, 32), jnp.int32), ((slots,), jnp.int32))
        args = [jax.ShapeDtypeStruct(s, t) for s, t in shapes]
        return jax.jit(lambda q, kp, vp, tb, ln: da.paged_decode_attention(
            q, kp, vp, tb, ln, page_size=16, layer=0)).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    monkeypatch.setattr(jax, "default_backend", lambda: "some_new_chip")
    lowered = text(256, 30, 128, 1 + 256 * 128)
    assert lowered.count("tpu_custom_call") == 1
    assert 'kernel_name = "single_decode_attention"' in lowered
    assert "stablehlo.gather" not in lowered
    # a block is one whole trip of copies: 8 pages of 122,880 B
    assert da._block_pages(16, 3840, 2, 30) == 8
    small = text(12, 25, 64, 481)
    assert "tpu_custom_call" not in small and "stablehlo.gather" in small


@pytest.mark.slow
def test_gdn_engine_programs_work_on_cache_and_state_as_they_lie(
        one_chip, monkeypatch):
    """Olmo-Hybrid-7B's decode step and a prefill of 256 at the published
    widths and the cell's size (4 layers, 256 slots, 32769 pages of ONE
    cached layer in two buffers, three layers of float32 state) compiled
    for the described v5e with the pools AND the state donated: ONE
    ``gdn_state_update`` program called once a linear layer and one
    ``single_decode_attention``, NO COPY of the slots' ``S`` (one array
    of 1.70 GB, exactly its values' bytes: no padded lane) and under 1
    GB of temporaries."""
    import functools
    import json

    from benchmarks.reference import olmo_hybrid_7b as ref
    from bigdl_tpu.models.olmo_hybrid import build_olmo_hybrid
    from bigdl_tpu.serving.engine import LMEngine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "olmo_hybrid_7b.json"), encoding="utf-8") as fh:
        cfg = json.load(fh)
    dt, slots, page, max_len = jnp.bfloat16, 256, 16, 2048
    pages = 1 + slots * (max_len // page)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = jax.eval_shape(functools.partial(
        ref.init_params, 1, ref.sizes_of(cfg), dt))
    weights = jax.tree.map(lambda a: spec(a.shape, a.dtype), shapes)
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert 3.20e9 < held < 3.21e9
    probe = build_olmo_hybrid(cfg, params=weights)
    cs, ss = probe.cache_spec(weights), probe.state_spec(weights)
    assert (cs["layers"], cs["row_width"], cs["buffers"], cs["heads"],
            cs["kv_heads"], cs["attn_query_rows"]) == (1, 3840, 2, 30, 30,
                                                       30)
    assert ss["layers"] == 3 and ss["keeps_inactive"]
    assert ss["shapes"] == ((96, 5760), (3, 11520))
    buf = spec((1, pages, page, 3840), dt)
    state = tuple(spec((3, slots) + shp, ss["dtype"])
                  for shp in ss["shapes"])
    eng = _engine_of_shapes(probe, weights, page, max_len, (buf, buf),
                            state=state)
    key = spec((), jax.random.key(0).dtype)
    ints = spec((slots,), jnp.int32)
    flags = spec((slots,), jnp.bool_)
    programs = {
        "step": LMEngine._build_step(eng).lower(
            weights, buf, buf, *state, spec((slots, 128), jnp.int32), ints,
            ints, spec((slots,), jnp.float32), flags, key),
        "prefill256": LMEngine._prefill_fn(eng, 256).lower(
            weights, buf, buf, *state, spec((1, 256), jnp.int32),
            spec((), jnp.int32), spec((256 // page,), jnp.int32),
            spec((), jnp.float32), key, spec((), jnp.int32), ints)}
    s_bytes = 3 * slots * 96 * 5760 * 4
    for name, lowered in programs.items():
        text = lowered.as_text()
        if name == "step":
            assert _kernel_calls(text, "gdn_state_update") == 3
            assert _kernel_calls(text, "single_decode_attention") == 1
            assert "stablehlo.gather" not in text.split(
                'kernel_name = "single_decode_attention"')[0].rsplit(
                "func.func private", 1)[-1]
        else:
            assert "gdn_state_update" not in text
        compiled = lowered.compile()
        ops = _whole_cache_ops(compiled, buf)
        kept = _whole_cache_ops(compiled, state[0])
        mem = compiled.memory_analysis()
        laid = _laid_out_bytes(compiled, state[0])
        print(f"gdn {name}: whole-cache instructions {ops}, whole-state "
              f"instructions {kept}, arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e6:.1f} MB, S as laid out "
              f"{laid} B against {s_bytes} B of values")
        # a pool of ONE cached layer: its layer axis goes by a bitcast
        assert set(ops) <= {"parameter", "scatter", "scatter fusion",
                            "bitcast"}, (name, ops)
        assert "copy" not in kept and "copy-start" not in kept, (name, kept)
        # the compiler's layout of S: tiles of (8, 128) on (96, 5760),
        # nothing padded
        assert laid == s_bytes < 1 << 31
        assert 13.0e9 < mem.argument_size_in_bytes < 13.3e9
        assert mem.temp_size_in_bytes < 1e9, (name, mem.temp_size_in_bytes)


# ---------------------------------------------------------------------------
# The serving programs of the models that were there before a model
# whose state sums over the whole past: PR 39 gave the engine a branch
# for it (``state_spec``'s ``keeps_inactive``) and must have changed
# nothing for the others.  The first 16 hex digits of the sha256 of the
# StableHLO of ``jit_step`` and of a two-page ``jit_prefill`` at the
# benchmark's tiny configurations, recorded at PR 38's commit (the same
# text on both trees).  A PR that MEANS to change one of these programs
# records its hash anew and says so.  PR 40 meant to: the three models
# whose step ends in ``sample_step`` and whose prefill in
# ``sample_first`` (the draw under a ``cond``) have new hashes; the two
# that call ``pick_greedy`` themselves kept PR 38's.  PR 41 meant to
# change ONE: SDAR's block step (a pending tail's rows beside the
# block's; its prefill kept PR 38's hash); ZAYA1's step, which runs the
# same grouped kernel with one length a slot, kept its own.  PR 42
# meant to change NONE: it moved the bodies of ``step`` and ``prefill``
# from ``serving/engine.py`` into ``serving/steps.py`` and first pinned
# the sixth model, Falcon-H1 (the one whose ``state_spec`` says
# ``keeps_inactive``), with the hashes PR 41's tree gives.  PR 44 meant
# to change NONE of the six (``nn/experts.py`` and ``nn/latent.py``
# gained options whose defaults trace the programs they traced) and
# pinned the seventh, Ling-3.0-flash, with its own tree's.  PR 45 meant
# to change ALL fourteen, and their arguments: every prefill takes the
# slot and what of the carry it writes the slot's row of, and every
# step takes a fresh slot's input from there and no longer from the
# host; the models' own mathematics is as it was (the served tokens
# equal the parent's: ``tests/test_serving.py``).  PR 46 meant to change
# ONE model's two: ``tiny_gpt``'s programs are handed the embeddings
# with their 32 values a row padded to a lane tile
# (``TransformerLM.serving_tables``) and cut the pad off the gathered
# rows; the six others are handed ``params`` itself and kept PR 45's.
# PR 48 meant to change NONE of the seven (``nn/delta.py``'s step and
# scan run over hooks that trace, for KDA, the operations they traced;
# one query row a key head over GPT-2 XL's pool is still the gather) and
# pinned the eighth, Olmo-Hybrid, with its own tree's.  PR 49 meant to
# change SIX step programs and no prefill: the decode attention kernels
# of the six models whose attention streams pages at these sizes take
# one more scalar operand (which groups of 8 table entries are runs of
# neighbouring pages, ``ops/decode_attention.py`` ``_run_starts``) and
# copy such a group with one descriptor; ``tiny_gpt`` and
# ``tiny_olmo_hybrid`` (one query row a key head over a small pool: the
# gather) and every prefill kept PR 48's.
LOWERED = {
    "tiny_gpt": ("c0f5691a07849545", "ee822d4e8e31c000"),
    "tiny_longcat": ("c98a8aaa0a2a17bc", "cbd0b59cc7e05e3c"),
    "tiny_joyai": ("395012c4249c7b88", "cd0c2e5e5fdb97fe"),
    "tiny_sdar": ("8a3b7b413aaa3d7f", "5e27a46a9de4314b"),
    "tiny_zaya": ("29e6af2a956ff625", "eefa0a1999f540d7"),
    "tiny_falcon_h1": ("affc7f2cc0533b4d", "9fbc61e0ba1b9d4d"),
    "tiny_ling": ("dcd17d0f7fba0007", "2361fdbd91f49a64"),
    "tiny_olmo_hybrid": ("b07a3e7b0950adaf", "95a61bc245efff41"),
}


def _tiny_engine(name):
    """The engine the benchmark's drivers build for a tiny
    configuration of its tests, weights from seed 3."""
    import json

    from benchmarks.drivers import serve, serve_lm
    from benchmarks.lib import harness

    with open(os.path.join(REPO, "benchmarks", "tests", "data",
                           name + ".json"), encoding="utf-8") as fh:
        config = json.load(fh)
    ref = harness.reference_for(config)
    sizes = ref.sizes_of(config)
    params = ref.init_params(
        3, sizes, jnp.dtype(config["assumed"]["serving_dtype"]))
    if config["kind"] == "serve":
        return serve.build_engine(config, params, sizes)
    return serve_lm.build_engine(config, params)


@pytest.fixture(scope="module")
def tiny_engines():
    built = {}

    def get(name):
        if name not in built:
            built[name] = _tiny_engine(name)
        return built[name]

    return get


def _sha(text: str) -> str:
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("program", ["step", "prefill"])
@pytest.mark.parametrize("name", sorted(LOWERED))
def test_the_other_serving_models_programs_lower_unchanged(
        tiny_engines, name, program):
    eng = tiny_engines(name)
    b = eng.max_batch
    ints, flags = jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool)
    if program == "step":
        tables, lengths = eng.cache.device_tables(pages=2)
        # no slot runs: the kind's own host arrays, all zeros
        host = eng._kind.host_args((), jax.random.key(0))
        text = eng._step_fn.lower(
            eng.weights(), *eng.cache.buffers(), tables, lengths,
            *eng._carry, *host).as_text()
    else:
        bucket = 2 * eng.page_size
        # the slot, and what of the carry a prefill writes its row of
        text = eng._prefill_fn(bucket).lower(
            eng.weights(), *eng.cache.buffers(),
            jnp.zeros((1, bucket), jnp.int32), 5,
            jnp.zeros((2,), jnp.int32), 0.0, jax.random.key(1),
            np.int32(1), *eng._carry[:eng._kind.handed]).as_text()
    assert _sha(text) == LOWERED[name][program == "prefill"], (name, program)


# ---------------------------------------------------------------------------
# The seam itself (PR 42): ``LMEngine`` asks one object of
# ``serving/steps.py`` for everything that differs between kinds of
# step.  ``stats()`` has the parent's keys, in the parent's order, under
# every kind, and a request served to its end moves the tallies of its
# engine's kind and no other's.
STATS_KEYS = [
    "requests", "tokens", "steps", "steps_ahead", "admitted",
    "prefills_read_late", "greedy_step_share",
    "tokens_per_step", "drafts_verified", "drafts_accepted",
    "draft_accept_share", "block_passes", "block_tails", "block_commits",
    "positions_unmasked", "tokens_per_forward", "tail_share",
    "attn_pages_a_copy", "settles",
    "busy_s", "tokens_per_s", "occupancy_mean", "queue_depth",
    "kv_pages_in_use", "kv_pages_total", "state_bytes_per_slot",
    "state_rebuilds", "draining", "weight_version", "manifest_sha",
    "weight_swaps", "preemptions", "e2e_p50_s", "e2e_p99_s", "ttft_p50_s",
    "ttft_p99_s", "itl_p50_s", "itl_p95_s", "int8", "tp",
    "last_bucket_pages", "decode_ms_mean", "decode_hbm_bytes_per_token"]
#: the kind a tiny configuration's model declares, whether its slots
#: carry state, and the tallies that kind alone moves (seeded weights
#: accept no draft, so ``drafts_accepted`` may stay 0)
KINDS = {
    "tiny_gpt": ("OneToken", False, set()),
    "tiny_longcat": ("OneToken", False, set()),
    "tiny_zaya": ("OneToken", True, set()),
    "tiny_falcon_h1": ("OneToken", True, set()),
    "tiny_ling": ("OneToken", True, set()),
    "tiny_olmo_hybrid": ("OneToken", True, set()),
    "tiny_joyai": ("Drafting", False, {"drafts_verified",
                                       "draft_accept_share"}),
    "tiny_sdar": ("Block", False, {"block_passes", "block_tails",
                                   "positions_unmasked",
                                   "tokens_per_forward", "tail_share"}),
}


@pytest.mark.parametrize("name", sorted(KINDS))
def test_a_request_served_to_its_end_moves_its_kind_s_tallies_alone(
        tiny_engines, name):
    eng = tiny_engines(name)
    kind, state, own = KINDS[name]
    assert type(eng._kind).__name__ == kind
    before = eng.stats()
    assert list(before) == STATS_KEYS
    req = eng.submit([3, 1, 4, 1, 5], 6)
    eng.run_until_idle(timeout_s=300)
    assert req.error is None and 1 <= len(req.tokens) <= 6
    after = eng.stats()
    assert list(after) == STATS_KEYS
    assert after["requests"] == before["requests"] + 1
    assert after["tokens"] == before["tokens"] + len(req.tokens)
    assert bool(after["state_bytes_per_slot"]) == state
    assert after["block_commits"] == 0 and after["state_rebuilds"] == 0
    tallies = {"drafts_verified", "drafts_accepted", "draft_accept_share",
               "block_passes", "block_tails", "positions_unmasked",
               "tokens_per_forward", "tail_share"}
    moved = {k for k in tallies if after[k] != before[k]}
    may = {"drafts_accepted"} if kind == "Drafting" else set()
    assert own <= moved <= own | may, moved
