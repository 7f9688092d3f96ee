"""Every ``pallas_call`` in ``bigdl_tpu/ops`` cross-lowered for TPU on the CPU.

``jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs the JAX
side of the Mosaic lowering without a chip.  It catches what the CPU
interpreter never sees: a BlockSpec whose last two block dims are neither
(8, 128)-divisible nor full (the refusal that kept the flash-decode
kernel from ever compiling), a scalar store to VMEM, an unsupported
primitive.  Whether libtpu's Mosaic then accepts the module is
``chip_smoke.py``'s kernel phase, on the chip.  Shapes are the smoke's.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.ops.attention import flash_attention
from bigdl_tpu.ops.conv_bn import conv_bn_stats
from bigdl_tpu.ops.decode_attention import paged_decode_attention

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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_lowers_at_the_engine_shape(dtype):
    c = FULL["decode"]
    pool = 1 + c["b"] * c["maxp"]

    def f(q, kp, vp, tables, lengths):
        return paged_decode_attention(q, kp, vp, tables, lengths,
                                      page_size=c["p"], impl="pallas",
                                      interpret=False)

    kv = ((pool, c["h"], c["p"], c["d"]), dtype)
    n = _mosaic_calls(f, ((c["b"], c["h"], c["d"]), dtype), kv, kv,
                      ((c["b"], c["maxp"]), jnp.int32),
                      ((c["b"],), jnp.int32))
    assert n == 1


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


@pytest.mark.slow
def test_libtpu_mosaic_compiles_every_kernel_without_a_chip():
    """The whole way down, still without a chip: libtpu can describe a
    v5e topology on a host that has none, and compiling for it runs
    Mosaic itself (vector layout inference, scoped-VMEM allocation).
    Slow-tagged (it starts libtpu); run it before spending chip minutes
    on a kernel change."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no usable libtpu here
        pytest.skip(f"no TPU topology without a chip: {e}")
    sh = SingleDeviceSharding(topo.devices[0])

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
    c = FULL["decode"]
    pool = 1 + c["b"] * c["maxp"]
    for kv_dt in (jnp.float32, jnp.bfloat16):
        kv = ((pool, c["h"], c["p"], c["d"]), kv_dt)
        compile_(
            lambda q, kp, vp, t, n: paged_decode_attention(
                q, kp, vp, t, n, page_size=c["p"], impl="pallas",
                interpret=False),
            ((c["b"], c["h"], c["d"]), kv_dt), kv, kv,
            ((c["b"], c["maxp"]), jnp.int32), ((c["b"],), jnp.int32))
    for n, ci, hw, o, k, stride in FULL["conv"]:
        compile_(
            lambda x, w, s, k=k, stride=stride: conv_bn_stats(
                x, w, s, stride=stride, pad=(k - 1) // 2, impl="pallas",
                interpret=False),
            ((n, ci, hw, hw), dt), ((o, ci, k, k), dt), ((o,), jnp.float32))
