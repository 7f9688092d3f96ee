"""The decode step of a **delta-rule** linear attention's matrix state —
one Pallas kernel that reads every slot's state ONCE and writes it ONCE,
where it lies.

``nn/delta.py`` has the layer (KDA: a delta rule with a decay a
CHANNEL).  A decode step advances, for every slot ``s`` and head ``h``,
a ``d_k x d_v`` float32 tile ``S``::

    S <- Diag(decay[s, h]) S                      decay: d_k values
    u  = S^T k[s, h]                              the READ before the write
    S <- S + k[s, h] (beta[s, h] (v[s, h] - u))^T
    o[s, h] = S^T q[s, h]                         (the NEW S)

``ops/ssm_state.py`` advances a state whose transition is DIAGONAL (each
value scaled, added to).  This one is not: a token reads the decayed
state along its key (a reduction over the ``d_k`` key channels) before
it writes a rank-1 correction, then reads again along its query.
Written as ``jax.numpy`` that is three passes over a state that at 256
slots x 32 heads x 128 x 128 is 537 MB a layer.  Here a grid step holds
a block of heads of one slot in fast memory, makes both reductions and
the update on the tile, and the state's buffer is the kernel's input
AND output (``input_output_aliases``): one read, one write.

**The tile is kept ``(d_k, d_v)``**: ``d_k`` along sublanes, ``d_v``
along lanes.  Then ``S^T k`` and ``S^T q`` are sums of vector registers
(no cross-lane reduction), ``v``, ``beta`` and ``u`` are rows that
broadcast along sublanes, and ``decay``, ``k`` and ``q`` (a row a head,
as the projections produce them) are needed as COLUMNS that broadcast
along lanes: the block's rows of all three are stacked into one ``128 x
d_k`` tile and transposed ONCE a grid step; a head's column is a lane of
the result.

A slot that did not run is given ``decay = 1`` and ``beta = 0`` by the
caller: ``1 * S + k * 0`` is ``S``, bit for bit.
"""

from __future__ import annotations

import functools

#: bytes of state a grid step holds (its heads' tiles): with the
#: pipeline's two buffers in and two out, 4 MB of fast memory
_BLOCK_BYTES = 1 << 20
#: rows of the tile a grid step transposes: three (decay, k, q) a head
_ROWS = 128


def _heads_a_block(heads: int, tile_bytes: int) -> int:
    """Heads a grid step: the largest divisor of ``heads`` whose tiles
    fit ``_BLOCK_BYTES`` and whose three rows a head fit the transposed
    tile, and which keeps a block's rows whole sublane groups (8) unless
    the block is all the heads."""
    best = None
    for hb in range(1, heads + 1):
        if heads % hb or hb * tile_bytes > _BLOCK_BYTES or 3 * hb > _ROWS:
            continue
        if hb % 8 == 0 or hb == heads:
            best = hb
    if best is None:
        raise ValueError(f"{heads} heads of {tile_bytes} B: no block of "
                         "heads is whole sublane groups")
    return best


def _kernel(hb: int):
    import jax.numpy as jnp

    def kernel(layer, s_ref, dec_ref, k_ref, q_ref, v_ref, beta_ref,
               o_ref, y_ref):
        del layer
        dk = s_ref.shape[-2]
        # the block's rows of decay, k and q, one tile, transposed once:
        # column j is head j's decay, hb + j its key, 2 hb + j its query
        rows = jnp.concatenate(
            [dec_ref[0], k_ref[0], q_ref[0],
             jnp.zeros((_ROWS - 3 * hb, dk), jnp.float32)], axis=0)
        cols = rows.T                                   # (d_k, 128)
        for j in range(hb):
            kc = cols[:, hb + j:hb + j + 1]
            s = s_ref[0, 0, j] * cols[:, j:j + 1]
            u = jnp.sum(s * kc, axis=0, keepdims=True)
            new = s + kc * (beta_ref[0, j:j + 1, :]
                            * (v_ref[0, j:j + 1, :] - u))
            o_ref[0, 0, j] = new
            y_ref[0, j:j + 1, :] = jnp.sum(
                new * cols[:, 2 * hb + j:2 * hb + j + 1], axis=0,
                keepdims=True)

    return kernel


@functools.lru_cache(maxsize=None)
def _program(interpret: bool):
    """The jitted call, the layer a traced argument (one traced program
    for a model's layers)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(s, layer, decay, k, q, v, beta):
        _, slots, heads, dk, dv = s.shape
        hb = _heads_a_block(heads, dk * dv * s.dtype.itemsize)
        f32 = jnp.float32

        def state(i, b, lyr):
            return (lyr[0], i, b, 0, 0)

        def row(i, b, lyr):
            return (i, b, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots, heads // hb),
            in_specs=[pl.BlockSpec((1, 1, hb, dk, dv), state)]
            + [pl.BlockSpec((1, hb, dk), row)] * 3
            + [pl.BlockSpec((1, hb, dv), row)] * 2,
            out_specs=[pl.BlockSpec((1, 1, hb, dk, dv), state),
                       pl.BlockSpec((1, hb, dv), row)])
        return pl.pallas_call(
            _kernel(hb),
            out_shape=[jax.ShapeDtypeStruct(s.shape, s.dtype),
                       jax.ShapeDtypeStruct((slots, heads, dv), f32)],
            grid_spec=grid_spec,
            # the state (operand 1, after the prefetched layer) is
            # updated where it lies
            input_output_aliases={1: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name="kda_state_update",
        )(layer, s, decay.astype(f32), k.astype(f32), q.astype(f32),
          v.astype(f32),
          jnp.broadcast_to(beta.astype(f32)[..., None], (slots, heads, dv)))

    return jax.jit(call)


def state_update(s, layer, decay, k, q, v, beta, *, interpret=None):
    """One token a slot (module docstring).  ``s`` ``(layers, slots,
    heads, d_k, d_v)`` float32, the stacked state, updated at ``layer``
    (an int or a traced scalar); ``decay``, ``k`` and ``q`` ``(slots,
    heads, d_k)`` (``decay`` the factor itself, ``exp(g)``), ``v``
    ``(slots, heads, d_v)``, ``beta`` ``(slots, heads)``.  Returns
    ``(s', o)`` with ``o`` ``(slots, heads, d_v)`` float32 read from the
    NEW state.  The kernel is interpreted on the CPU backend and only
    there."""
    import jax
    import jax.numpy as jnp

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _program(bool(interpret))(
        s, jnp.asarray(layer, jnp.int32).reshape(1), decay, k, q, v, beta)


__all__ = ["state_update"]
