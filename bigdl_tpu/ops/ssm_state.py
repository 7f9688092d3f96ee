"""The decode step of a selective state-space layer's running state —
one Pallas kernel that reads every slot's state ONCE and writes it ONCE,
where it lies.

``nn/ssm.py`` has the layer.  A decode step advances, for every slot
``s`` and head ``h`` of group ``g(h)``::

    H[s, h] <- decay[s, h] * H[s, h] + B[s, g] (x) xdt[s, h]
    y[s, h] = C[s, g] . H[s, h]                      (the NEW H)

with ``H[s, h]`` a ``d_state x head_dim`` float32 tile.  At a serving
engine's sizes (128 slots x 32 heads x 256 x 128) the state of ONE layer
is 537 MB, more than the layer's weights, so what the update costs is
the passes it makes over it.  Written as ``jax.numpy`` the compiler
reads the old state twice (once for ``y``, once for the update; under
memory pressure it recomputed a layer's update a second time on top),
1.5 to 2.5 times the bytes that must move.  Here a grid step holds a
block of heads of one slot in fast memory, computes both from it, and
the state's buffer is the kernel's input AND output
(``input_output_aliases``): one read, one write, no second buffer.

**The tile is kept ``(d_state, head_dim)``**: ``d_state`` along
sublanes, ``head_dim`` along lanes.  Then ``xdt`` and ``decay`` (a row
a head, as the projections produce them) broadcast along sublanes, and
``y``'s sum over ``d_state`` is a sum of vector registers: no
cross-lane reduction a head.  ``B`` and ``C`` (a row a group) are
turned once a grid step into tiles constant along lanes, by one
transpose each, and shared by the block's heads.

A slot that did not run is given ``decay = 1`` and ``xdt = 0`` by the
caller: ``1 * H + B * 0`` is ``H``, bit for bit.
"""

from __future__ import annotations

import functools

#: bytes of state a grid step holds (its heads' tiles): with the
#: pipeline's two buffers in and two out, 4 MB of fast memory
_BLOCK_BYTES = 1 << 20


def _heads_a_block(per_group: int, tile_bytes: int) -> int:
    """Heads a grid step: the largest divisor of a group's heads whose
    tiles fit ``_BLOCK_BYTES`` (a block's heads share ``B`` and ``C``)."""
    best = 1
    for hb in range(1, per_group + 1):
        if per_group % hb == 0 and hb * tile_bytes <= _BLOCK_BYTES:
            best = hb
    return best


def _kernel(hb: int):
    import jax.numpy as jnp

    def kernel(layer, h_ref, dec_ref, x_ref, b_ref, c_ref, o_ref, y_ref):
        del layer
        n, p = h_ref.shape[-2:]
        # (1, N) rows -> (N, P) tiles constant along lanes
        bt = jnp.broadcast_to(b_ref[0, 0], (p, n)).T
        ct = jnp.broadcast_to(c_ref[0, 0], (p, n)).T
        for j in range(hb):
            new = h_ref[0, 0, j] * dec_ref[0, j:j + 1, :] \
                + bt * x_ref[0, j:j + 1, :]
            o_ref[0, 0, j] = new
            y_ref[0, j:j + 1, :] = jnp.sum(new * ct, axis=0, keepdims=True)

    return kernel


@functools.lru_cache(maxsize=None)
def _program(interpret: bool):
    """The jitted call, the layer a traced argument (one traced program
    for a model's layers)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(h, layer, decay, xdt, b, c):
        _, s, heads, n, p = h.shape
        groups = b.shape[1]
        per = heads // groups
        hb = _heads_a_block(per, n * p * h.dtype.itemsize)
        f32 = jnp.float32
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, heads // hb),
            in_specs=[
                pl.BlockSpec((1, 1, hb, n, p),
                             lambda i, k, lyr: (lyr[0], i, k, 0, 0)),
                pl.BlockSpec((1, hb, p), lambda i, k, lyr: (i, k, 0)),
                pl.BlockSpec((1, hb, p), lambda i, k, lyr: (i, k, 0)),
                pl.BlockSpec((1, 1, 1, n),
                             lambda i, k, lyr: (i, k * hb // per, 0, 0)),
                pl.BlockSpec((1, 1, 1, n),
                             lambda i, k, lyr: (i, k * hb // per, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hb, n, p),
                             lambda i, k, lyr: (lyr[0], i, k, 0, 0)),
                pl.BlockSpec((1, hb, p), lambda i, k, lyr: (i, k, 0)),
            ])
        return pl.pallas_call(
            _kernel(hb),
            out_shape=[jax.ShapeDtypeStruct(h.shape, h.dtype),
                       jax.ShapeDtypeStruct((s, heads, p), f32)],
            grid_spec=grid_spec,
            # the state (operand 1, after the prefetched layer) is
            # updated where it lies
            input_output_aliases={1: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name="ssm_state_update",
        )(layer, h,
          jnp.broadcast_to(decay.astype(f32)[..., None], (s, heads, p)),
          xdt.astype(f32), b.astype(f32)[:, :, None, :],
          c.astype(f32)[:, :, None, :])

    return jax.jit(call)


def state_update(h, layer, decay, xdt, b, c, *, interpret=None):
    """One token a slot (module docstring).  ``h`` ``(layers, slots,
    heads, d_state, head_dim)`` float32, the stacked state, updated at
    ``layer`` (an int or a traced scalar); ``decay`` ``(slots, heads)``,
    ``xdt`` ``(slots, heads, head_dim)`` (``dt * x``), ``b`` and ``c``
    ``(slots, groups, d_state)``.  Returns ``(h', y)`` with ``y``
    ``(slots, heads, head_dim)`` float32 read from the NEW state.  The
    kernel is interpreted on the CPU backend and only there."""
    import jax
    import jax.numpy as jnp

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _program(bool(interpret))(
        h, jnp.asarray(layer, jnp.int32).reshape(1), decay, xdt, b, c)


__all__ = ["state_update"]
