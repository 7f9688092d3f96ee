"""A selective state-space mixer (Mamba-2) — the layer of the hybrid
decoders that run one beside their attention in every block
(``models/falcon_h1.py``).

With ``n`` the normalised stream (``dim`` wide), ``d_ssm = heads x
head_dim`` inner channels, ``groups`` groups of ``d_state`` values for
``B`` and ``C``, and ``K`` the convolution's kernel::

    p = (W_in (in_multiplier * n)) * m        m: one multiplier a ZONE of p
    [z ; x ; B ; C ; dt] = p                  d_ssm, d_ssm, G*N, G*N, heads
    [x ; B ; C] <- silu(conv_K([x ; B ; C]) + b)    depthwise, causal
    dt_h = softplus(dt_h + dt_bias_h)         A_h = -exp(a_log_h)
    H_h <- exp(dt_h A_h) H_h + dt_h * x_h (x) B_g      g = h // (heads / G)
    y_h = H_h C_g + D_h x_h                   H_h: head_dim x d_state
    y <- rms_g(y * silu(z)) * gain            the norm a GROUP of d_ssm / G
    out = W_out y

``dt``, ``A``, the decays, the recurrence and ``H`` are float32 whatever
the weights' dtype: a slow head keeps ``exp(dt A)`` near 0.999 and adds
increments a thousandth of ``H``'s size, which bfloat16 would drop.

**Two forms of the recurrence that agree to rounding.**
:meth:`Mamba2Mixer.step` advances every slot's ``H`` by ONE token (a
decode step): the convolution over the slot's ``K - 1`` kept rows and
this token's, one update of ``H``.  A slot that did not run is given
``dt = 0``: its decay is ``exp(0) = 1`` and its increment 0, so its
``H`` comes back bit for bit, with no second pass over the state to put
the old values back.  The update of ``H`` and the read of ``y`` are one
Pallas kernel over the slots' stacked state (``ops/ssm_state.py``): one
read and one write of it a step, in place.  :meth:`Mamba2Mixer.scan` runs a whole prompt in
**chunks** of ``chunk`` positions: inside a chunk the masked plane
``exp(a_t - a_s)`` (``a`` the running sum of ``dt A``) times ``C B^T``
weighs every earlier position of the chunk, between chunks the carried
``H`` does.  The prompt is zero-padded to its bucket; positions at or
past ``t0`` take ``dt = 0``, so the state the scan ends on IS the state
after token ``t0 - 1``, and the convolution's kept rows are the last
``K - 1`` REAL rows of ``[x ; B ; C]`` BEFORE the convolution (zeros to
the left of a prompt shorter than that).

What a slot carries for the layer (:meth:`Mamba2Mixer.state_shapes`):
``H`` ``(heads, d_state, head_dim)`` (each head's tile TRANSPOSED:
``d_state`` along sublanes and ``head_dim`` along lanes is how
``ops/ssm_state.py`` updates it without a cross-lane sum) and the
convolution's rows ``(K - 1, d_ssm + 2 G N)``.

``jax.named_scope`` names: ``ssm.proj`` (the two projections),
``ssm.conv`` (the convolution and its rows), ``ssm.scan`` (``dt``, the
update of ``H``, ``y``, the gate and the group norm).
"""

from __future__ import annotations

import numpy as np

from bigdl_tpu.nn.latent import _draw
from bigdl_tpu.nn.module import AbstractModule


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _mm(eq, *ops):
    """A float32 contraction the TPU must not run in bfloat16 passes."""
    import jax.numpy as jnp

    return jnp.einsum(eq, *ops, precision="highest",
                      preferred_element_type=jnp.float32)


class Mamba2Mixer(AbstractModule):
    """The mixer of the module docstring.  ``zone_multipliers`` are the
    five of ``m`` in the order of ``p``'s zones (gate ``z``, ``x``,
    ``B``, ``C``, ``dt``)."""

    param_names = ("w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d",
                   "norm", "w_out")

    def __init__(self, dim: int, heads: int, head_dim: int, d_state: int,
                 groups: int, d_conv: int = 4, chunk: int = 128,
                 eps: float = 1e-5, in_multiplier: float = 1.0,
                 zone_multipliers=(1.0,) * 5, init: bool = True):
        super().__init__()
        if heads % groups:
            raise ValueError(f"{heads} heads over {groups} groups")
        self._config = dict(
            dim=dim, heads=heads, head_dim=head_dim, d_state=d_state,
            groups=groups, d_conv=d_conv, chunk=chunk, eps=eps,
            in_multiplier=in_multiplier,
            zone_multipliers=tuple(zone_multipliers))
        self.dim, self.heads, self.head_dim = dim, heads, head_dim
        self.d_state, self.groups, self.d_conv = d_state, groups, d_conv
        self.chunk, self.eps = chunk, eps
        self.in_multiplier = float(in_multiplier)
        self.d_ssm = heads * head_dim
        #: channels the convolution runs over: ``[x ; B ; C]``
        self.conv_dim = self.d_ssm + 2 * groups * d_state
        #: width of ``p``, and the multiplier of each of its values
        self.zones = (self.d_ssm, self.d_ssm, groups * d_state,
                      groups * d_state, heads)
        self.zone_vector = np.repeat(
            np.asarray(zone_multipliers, np.float32), self.zones)
        for n in self.param_names:
            setattr(self, n, None)
        if init:
            self.reset()

    def reset(self):
        import jax.numpy as jnp

        h = self.heads
        self.w_in = _draw((sum(self.zones), self.dim))
        self.conv_w = _draw((self.d_conv, self.conv_dim), 0.3)
        self.conv_b = jnp.zeros((self.conv_dim,), jnp.float32)
        # dt around 0.01, decays around 0.99
        self.dt_bias = jnp.full((h,), float(np.log(np.expm1(0.01))),
                                jnp.float32)
        self.a_log = jnp.zeros((h,), jnp.float32)
        self.d = jnp.ones((h,), jnp.float32)
        self.norm = jnp.ones((self.d_ssm,), jnp.float32)
        self.w_out = _draw((self.dim, self.d_ssm))
        return self

    def state_shapes(self) -> tuple:
        """What a slot carries for this layer: ``H`` (a head's tile
        ``d_state x head_dim``, as ``ops/ssm_state.py`` wants it), and
        the convolution's last ``K - 1`` rows of ``[x ; B ; C]``."""
        return ((self.heads, self.d_state, self.head_dim),
                (self.d_conv - 1, self.conv_dim))

    # ------------------------------------------------------------ parts
    def project(self, params, n):
        """``n`` (..., dim) -> the gate ``z`` (..., d_ssm), the rows
        ``[x ; B ; C]`` (..., conv_dim) before the convolution, and
        ``dt`` (..., heads) before its bias."""
        import jax.numpy as jnp

        p = jnp.matmul(n * self.in_multiplier, params["w_in"].T) \
            * jnp.asarray(self.zone_vector, n.dtype)
        d = self.d_ssm
        return p[..., :d], p[..., d:d + self.conv_dim], \
            p[..., d + self.conv_dim:]

    def _convolved(self, params, window):
        """``window`` (..., K, conv_dim), the ``K`` rows a position sees,
        oldest first -> ``silu(conv + b)`` (..., conv_dim) float32."""
        import jax
        import jax.numpy as jnp

        w = _f32(params["conv_w"])
        return jax.nn.silu(jnp.sum(_f32(window) * w, axis=-2)
                           + _f32(params["conv_b"]))

    def _split(self, xbc):
        """Convolved rows (..., conv_dim) -> ``x`` (..., heads,
        head_dim), ``B`` and ``C`` (..., groups, d_state)."""
        lead, gn = xbc.shape[:-1], self.groups * self.d_state
        return (xbc[..., :self.d_ssm].reshape(*lead, self.heads,
                                              self.head_dim),
                xbc[..., self.d_ssm:self.d_ssm + gn].reshape(
                    *lead, self.groups, self.d_state),
                xbc[..., self.d_ssm + gn:].reshape(
                    *lead, self.groups, self.d_state))

    def _dt(self, params, dt, live):
        """``softplus(dt + bias)`` (..., heads) float32, 0 where ``live``
        (...,) is false: a decay of exactly 1 and no increment."""
        import jax
        import jax.numpy as jnp

        dt = jax.nn.softplus(_f32(dt) + _f32(params["dt_bias"]))
        return jnp.where(live[..., None], dt, 0.0)

    def _finish(self, params, y, z, dtype):
        """``y`` (..., d_ssm) float32 under the gate ``z``, the group RMS
        norm and the out-projection."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("ssm.scan"):
            lead = y.shape[:-1]
            y = (y * jax.nn.silu(_f32(z))).reshape(*lead, self.groups, -1)
            y = y * jax.lax.rsqrt(
                jnp.mean(jnp.square(y), axis=-1, keepdims=True) + self.eps)
            y = (y.reshape(*lead, self.d_ssm)
                 * _f32(params["norm"])).astype(dtype)
        with jax.named_scope("ssm.proj"):
            return jnp.matmul(y, params["w_out"].T)

    # ---------------------------------------------------- one token a slot
    def step(self, params, n, hs, rows, layer: int, active):
        """One token a slot: ``n`` (S, dim); ``hs`` (layers, S, heads,
        d_state, head_dim) float32 and ``rows`` (layers, S, K - 1,
        conv_dim), the slots' STACKED state, advanced at ``layer``;
        ``active`` (S,) the slots that run -> ``(out (S, dim), hs',
        rows')``; a slot that does not run keeps both (module
        docstring).  ``H`` is read once and written once, where it lies
        (``ops/ssm_state.py``)."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.ops.ssm_state import state_update

        with jax.named_scope("ssm.proj"):
            z, xbc, dt = self.project(params, n)
        with jax.named_scope("ssm.conv"):
            kept = rows[layer]
            window = jnp.concatenate(
                [kept, xbc[:, None].astype(kept.dtype)], axis=1)
            x, b, c = self._split(self._convolved(params, window))
            rows = rows.at[layer].set(
                jnp.where(active[:, None, None], window[:, 1:], kept))
        with jax.named_scope("ssm.scan"):
            dt = self._dt(params, dt, active)
            decay = jnp.exp(dt * -jnp.exp(_f32(params["a_log"])))
            hs, y = state_update(hs, layer, decay, dt[..., None] * x, b, c)
            y = y + _f32(params["d"])[:, None] * x
        out = self._finish(params, y.reshape(n.shape[0], self.d_ssm), z,
                           n.dtype)
        return out, hs, rows

    # ---------------------------------------------------------- a prompt
    def scan(self, params, n, t0):
        """One prompt ``n`` (T, dim), real up to ``t0`` (traced), from a
        zero state, in chunks -> ``(out (T, dim), h, rows)`` with ``h``
        and ``rows`` the state after position ``t0 - 1`` (module
        docstring)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        real = n.shape[0]
        k, per = self.d_conv, self.heads // self.groups
        q = min(self.chunk, real)
        # whole chunks: a tail of zero rows is positions past ``t0``
        n = jnp.pad(n, ((0, -real % q), (0, 0)))
        t = n.shape[0]
        nc = t // q
        with jax.named_scope("ssm.proj"):
            z, xbc, dt = self.project(params, n)
        with jax.named_scope("ssm.conv"):
            padded = jnp.concatenate(
                [jnp.zeros((k - 1, self.conv_dim), xbc.dtype), xbc])
            window = jnp.stack([padded[j:j + t] for j in range(k)], axis=1)
            x, b, c = self._split(self._convolved(params, window))
            # padded[t0 .. t0 + K - 2] are the rows t0 - K + 1 .. t0 - 1
            rows = _f32(lax.dynamic_slice_in_dim(padded, t0, k - 1))
        with jax.named_scope("ssm.scan"):
            dt = self._dt(params, dt, jnp.arange(t) < t0)
            a = dt * -jnp.exp(_f32(params["a_log"]))            # (T, H) <= 0
            a = a.reshape(nc, q, self.heads)
            cs = jnp.cumsum(a, axis=1)                          # inclusive
            xd = (dt[..., None] * x).reshape(nc, q, self.groups, per,
                                             self.head_dim)
            b = b.reshape(nc, q, self.groups, self.d_state)
            c = c.reshape(nc, q, self.groups, self.d_state)
            # inside a chunk: position t reads s <= t under exp(a_t - a_s)
            gap = cs[:, :, None, :] - cs[:, None, :, :]         # (c, t, s, H)
            seen = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]
            plane = jnp.exp(jnp.where(seen, gap, -jnp.inf)) \
                .reshape(nc, q, q, self.groups, per)
            cb = _mm("ctgn,csgn->ctsg", c, b)
            y = _mm("ctsgr,csgrp->ctgrp", plane * cb[..., None], xd)
            # a chunk's own sum, decayed to the chunk's end
            to_end = jnp.exp(cs[:, -1:, :] - cs) \
                .reshape(nc, q, self.groups, per)
            own = _mm("csgrp,csgn->cgrpn", xd * to_end[..., None], b)
            whole = jnp.exp(cs[:, -1, :]).reshape(nc, self.groups, per)

            def carry(h, chunk):
                own_c, whole_c = chunk
                return h * whole_c[..., None, None] + own_c, h

            h, before = lax.scan(
                carry, jnp.zeros(own.shape[1:], jnp.float32), (own, whole))
            # what the chunks before add: C_t H_before under exp(a_t)
            y = y + _mm("ctgn,cgrpn->ctgrp", c, before) \
                * jnp.exp(cs).reshape(nc, q, self.groups, per)[..., None]
            y = y.reshape(t, self.heads, self.head_dim) \
                + _f32(params["d"])[:, None] * x
            # kept as the step keeps it: d_state x head_dim a head
            h = jnp.swapaxes(
                h.reshape(self.heads, self.head_dim, self.d_state), 1, 2)
        out = self._finish(params, y.reshape(t, self.d_ssm), z, n.dtype)
        return out[:real], h, rows

    def update_output_pure(self, params, input, *, training=False, rng=None):
        """``input`` (batch, T, dim) -> (batch, T, dim), every sequence
        on its own from a zero state."""
        import jax.numpy as jnp

        return jnp.stack([self.scan(params, seq, seq.shape[0])[0]
                          for seq in input])

    def __repr__(self):
        return (f"Mamba2Mixer({self.dim} -> {self.heads} x {self.head_dim} "
                f"x {self.d_state})")


__all__ = ["Mamba2Mixer"]
