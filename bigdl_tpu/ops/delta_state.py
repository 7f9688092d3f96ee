"""The decode step of a **delta-rule** linear attention's matrix state —
a Pallas kernel that reads every slot's state ONCE and writes it ONCE,
where it lies; one kernel a layout of the state, two of them.

``nn/delta.py`` has the layers: KDA, a delta rule with a decay a
CHANNEL (:func:`state_update`, the kernel ``kda_state_update``), and
the gated delta rule, with a decay a HEAD (:func:`head_decay_update`,
``gdn_state_update``; the second half of this docstring).  A decode step
advances, for every slot ``s`` and head ``h``, a ``d_k x d_v`` float32
tile ``S``::

    S <- Diag(decay[s, h]) S                      decay: d_k values, or one
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

**A decay a head, on tiles that are no lane tile**
(:func:`head_decay_update`; ``models/olmo_hybrid.py``: 30 heads of 96 x
192).  The first kernel keeps the state ``(slots, heads, d_k, d_v)`` and
takes a block of HEADS a grid step.  That layout pads a tile whose
``d_v`` is no multiple of 128 lanes (192 is stored as 256: a third more
bytes to hold and to move), and its blocks want 8 heads or all of them,
which 30 heads of 72 KB do not offer.  So this state lies **``(slots,
d_k, heads x d_v)``**: ``d_k`` along sublanes as before, and ALL the
heads' values side by side along the lanes (30 x 192 = 45 lane tiles, no
padded lane).  Everything the recurrence does is then the same work on
every LANE: ``decay``, ``beta``, ``v``, ``u`` and the output are rows
``(slots, heads x d_v)`` (a head's one decay and one ``beta`` repeated
along its lanes by the caller, 23 KB a slot) that broadcast along
sublanes, the two reads are sums over sublanes, and a head's key and
query are columns that broadcast along THAT HEAD'S lanes.  A grid step
takes 8 slots (a row operand's block is 8 sublanes) of the fewest heads
that fill whole lane tiles (2 x 192 = 3 tiles) and walks its lane tiles;
a tile that two heads share (the middle one of each three) takes each
lane's column by a select on the lane index.  What the kernels share:
the recurrence above, to the letter; the columns (:func:`_columns`: the
block's rows of ``k`` and ``q``, padded to 128 lanes by the caller,
stacked into one 128-row tile and transposed ONCE a slot and grid step);
the state the kernel's input AND output; the layer a prefetched scalar;
the idle slot kept bit for bit.
"""

from __future__ import annotations

import functools
import math

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


def _columns(*rows):
    """Rows a head (each part ``(n, width)``) stacked into ONE tile of
    ``_ROWS`` rows and transposed once: ``(width, _ROWS)``, a row's
    values down column ``j`` of the result, the parts in order."""
    import jax.numpy as jnp

    held = sum(r.shape[0] for r in rows)
    return jnp.concatenate(
        [*rows, jnp.zeros((_ROWS - held, rows[0].shape[1]), jnp.float32)],
        axis=0).T


def _kernel(hb: int):
    import jax.numpy as jnp

    def kernel(layer, s_ref, dec_ref, k_ref, q_ref, v_ref, beta_ref,
               o_ref, y_ref):
        del layer
        # the block's rows of decay, k and q, one tile, transposed once:
        # column j is head j's decay, hb + j its key, 2 hb + j its query
        cols = _columns(dec_ref[0], k_ref[0], q_ref[0])  # (d_k, 128)
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


# --------------------------------------------------------------------------
# a decay a head — the state (slots, d_k, heads x d_v), the heads along
# the lanes
# --------------------------------------------------------------------------

#: slots a grid step: a row operand's block is this many sublanes
_SLOTS = 8
_LANES = 128


def _lane_block(slots: int, heads: int, dk: int, dv: int) -> tuple:
    """``(slots, heads)`` a grid step of :func:`head_decay_update`: 8
    slots, or all of them where they are no multiple of 8 (a row
    operand's block is whole sublane groups or the whole axis); the
    fewest heads whose values fill whole lane tiles (2 of 192, 1 of 128,
    8 of 48), doubled while they divide the heads and the block's tiles
    stay under ``_BLOCK_BYTES``; all the heads where those fewest do not
    divide them (the block is then the whole lane axis)."""
    sb = _SLOTS if slots % _SLOTS == 0 else slots
    tile = sb * dk * dv * 4
    hb = _LANES // math.gcd(dv, _LANES)
    if heads % hb:
        hb = heads
    while heads % (2 * hb) == 0 and 2 * hb * tile <= _BLOCK_BYTES:
        hb *= 2
    if dk > _LANES or dk % 8 or 2 * hb > _ROWS or hb * tile > 4 * _BLOCK_BYTES:
        raise ValueError(
            f"{slots} slots x {heads} heads of {dk} x {dv}: a grid step "
            f"of {sb} slots x {hb} heads is {hb * tile / 2 ** 20:.1f} MiB "
            f"(want under 4; slots in multiples of {_SLOTS}), keys of "
            f"whole sublane groups up to {_LANES}, two rows a head in "
            f"{_ROWS}")
    return sb, hb


def _lane_kernel(sb: int, hb: int, dv: int):
    import jax.numpy as jnp
    from jax import lax

    width = hb * dv

    def kernel(layer, s_ref, kq_ref, dec_ref, beta_ref, v_ref, o_ref, y_ref):
        del layer
        dk = s_ref.shape[-2]
        lane = lax.broadcasted_iota(jnp.int32, (dk, _LANES), 1)
        for i in range(sb):
            # column j is the block's head j's key, hb + j its query
            cols = _columns(kq_ref[i, 0])[:dk]              # (d_k, 128)
            for lo in range(0, width, _LANES):
                hi = min(lo + _LANES, width)
                at = slice(lo, hi)

                def along(first):
                    """Each lane of the tile its own head's column: the
                    tile's first head's, then each further head's from
                    its first lane on."""
                    heads = range(lo // dv, (hi - 1) // dv + 1)
                    x = jnp.broadcast_to(
                        cols[:, first + heads[0]:first + heads[0] + 1],
                        (dk, hi - lo))
                    for j in heads[1:]:
                        x = jnp.where(lane[:, :hi - lo] >= j * dv - lo,
                                      cols[:, first + j:first + j + 1], x)
                    return x

                kx = along(0)
                s = s_ref[0, i, :, at] * dec_ref[i:i + 1, at]
                u = jnp.sum(s * kx, axis=0, keepdims=True)
                new = s + kx * (beta_ref[i:i + 1, at]
                                * (v_ref[i:i + 1, at] - u))
                o_ref[0, i, :, at] = new
                y_ref[i:i + 1, at] = jnp.sum(new * along(hb), axis=0,
                                             keepdims=True)

    return kernel


@functools.lru_cache(maxsize=None)
def _lane_program(interpret: bool):
    """The jitted call of the second kernel, the layer a traced
    argument."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(s, layer, decay, k, q, v, beta):
        _, slots, dk, lanes = s.shape
        heads = decay.shape[1]
        dv = lanes // heads
        sb, hb = _lane_block(slots, heads, dk, dv)
        nb, f32 = heads // hb, jnp.float32
        r = -(-2 * hb // 8) * 8

        def rows(x):
            """A block's heads' rows, ``d_k`` padded to a lane tile."""
            return jnp.pad(x.astype(f32), ((0, 0), (0, 0), (0, _LANES - dk))
                           ).reshape(slots, nb, hb, _LANES)

        kq = jnp.concatenate(
            [rows(k), rows(q), jnp.zeros((slots, nb, r - 2 * hb, _LANES), f32)],
            axis=2)

        def row(i, b, lyr):
            return (i, b)

        def state(i, b, lyr):
            return (lyr[0], i, 0, b)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots // sb, nb),
            in_specs=[pl.BlockSpec((1, sb, dk, hb * dv), state),
                      pl.BlockSpec((sb, 1, r, _LANES),
                                   lambda i, b, lyr: (i, b, 0, 0))]
            + [pl.BlockSpec((sb, hb * dv), row)] * 3,
            out_specs=[pl.BlockSpec((1, sb, dk, hb * dv), state),
                       pl.BlockSpec((sb, hb * dv), row)])
        return pl.pallas_call(
            _lane_kernel(sb, hb, dv),
            out_shape=[jax.ShapeDtypeStruct(s.shape, s.dtype),
                       jax.ShapeDtypeStruct((slots, lanes), f32)],
            grid_spec=grid_spec,
            input_output_aliases={1: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name="gdn_state_update",
        )(layer, s, kq, jnp.repeat(decay.astype(f32), dv, axis=1),
          jnp.repeat(beta.astype(f32), dv, axis=1), v.astype(f32))

    return jax.jit(call)


def head_decay_update(s, layer, decay, k, q, v, beta, *, interpret=None):
    """One token a slot under a decay a HEAD (module docstring, second
    half).  ``s`` ``(layers, slots, d_k, heads x d_v)`` float32, the
    stacked state with the heads along the lanes, updated at ``layer``
    (an int or a traced scalar); ``decay`` (the factor itself) and
    ``beta`` ``(slots, heads)``, ``k`` and ``q`` ``(slots, heads,
    d_k)``, ``v`` ``(slots, heads x d_v)``.  Returns ``(s', o)`` with
    ``o`` ``(slots, heads x d_v)`` float32 read from the NEW state.  The
    kernel is interpreted on the CPU backend and only there."""
    import jax.numpy as jnp

    from bigdl_tpu.ops._pallas import resolve_interpret

    return _lane_program(resolve_interpret(interpret))(
        s, jnp.asarray(layer, jnp.int32).reshape(1), decay, k, q, v, beta)


__all__ = ["state_update", "head_decay_update"]
