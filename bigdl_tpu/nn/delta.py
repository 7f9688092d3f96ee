"""**Delta-rule linear attention** — the mixer of the hybrid decoders
that run it in most layers and a full attention in the others: with a
decay a CHANNEL (KDA, :class:`DeltaMixer`, ``models/ling_flash.py``) and
with a decay a HEAD (the gated delta rule, :class:`GatedDeltaMixer`,
``models/olmo_hybrid.py``; what differs is listed at the end, and
nothing else does).

With ``n`` the normalised stream (``dim`` wide), ``H`` heads of ``d_k``
key and ``d_v`` value channels and ``K`` the convolution's kernel::

    [q ; k ; v ; f ; z ; b] = W_in n          H d_k, H d_k, H d_v, H d_k,
                                              H d_v, H
    [q ; k ; v] <- silu(conv_K([q ; k ; v]))  depthwise, causal, no bias
    q, k <- q / |q|, k / |k| a head;  q <- q * d_k ** -0.5
    beta_h = sigmoid(b_h)                     one a head
    g = lower * sigmoid(exp(a_log_h) * (f + dt_bias))     a head AND channel,
                                              in [lower, 0] (lower = -5)
    S_h <- Diag(exp(g_h)) S_h                 S_h: d_k x d_v, float32
    S_h <- S_h + beta_h k_h (v_h - S_h^T k_h)^T
    o_h = S_h^T q_h
    y = W_o (rms(o) * gain * sigmoid(z))      the norm over ``norm_groups``
                                              groups of H d_v / groups

Unlike a state-space mixer's (``nn/ssm.py``) the transition is NOT
diagonal: a token reads the decayed state along its key before it
writes, so the state's values mix along ``d_k`` at every step.  ``g``,
``beta``, the recurrence and ``S`` are float32 whatever the weights'
dtype.

**Two forms of the recurrence that agree to rounding.**
:meth:`DeltaMixer.step` advances every slot's ``S`` by ONE token (a
decode step): the convolution over the slot's ``K - 1`` kept rows and
this token's, then one Pallas kernel over the slots' stacked state
(``ops/delta_state.py``: one read and one write of it, in place).  A
slot that did not run is given ``beta = 0`` and ``g = 0``: ``1 * S + k *
0`` is ``S`` bit for bit, with no second pass over the state.

:meth:`DeltaMixer.scan` runs a whole prompt in **chunks** of ``chunk``
positions from a zero state.  Inside a chunk, with ``G_t`` the running
sum of ``g`` from the chunk's start and ``S_0`` the state the chunk
starts on, the recurrence unrolls to (derived from the three lines
above, and held to them by ``tests/test_ling_flash.py``)::

    S_t = Diag(e^{G_t}) S_0 + sum_{i <= t} Diag(e^{G_t - G_i}) k_i w_i^T
    w_t = beta_t (v_t - S_0^T (k_t e^{G_t}) - sum_{i < t} A_ti w_i)
    A_ti = sum_c k_t[c] k_i[c] e^{G_t[c] - G_i[c]}
    o_t = S_0^T (q_t e^{G_t}) + sum_{i <= t} B_ti w_i     B: A with q_t

so ``(I + Diag(beta) tril(A, -1)) W = Diag(beta) (V - K+ S_0)``: a unit
lower-triangular system a chunk and head.  Its inverse is formed once
for every chunk at a time (a nilpotent matrix's: ``(I - N)(I + N^2)(I +
N^4) ...``, matrix products only), so what runs chunk after chunk is
``W = U - W_k S_0``, ``O = Q+ S_0 + tril(B) W`` and ``S_c = Diag(e^{G_c})
S_0 + K_end^T W``.  **A decay a channel cannot be factored over a whole
chunk**: ``e^{-G_i}`` after 64 steps at the lower bound is ``e^{320}``.
``A`` and ``B`` are therefore formed a SUB-BLOCK of ``sub`` rows at a
time, each against the running sum at its own start: a row's factor is
then at most 1 and a column's at most ``e^{-lower * sub}`` (``e^{80}`` at
16, inside float32; what lies above the diagonal is capped there and
masked).  The prompt is zero-padded to its bucket; positions at or past
``t0`` take ``beta = 0`` and ``g = 0``, so the state the scan ends on IS
the state after token ``t0 - 1``, and the convolution's kept rows are
the last ``K - 1`` REAL rows of ``[q ; k ; v]`` BEFORE the convolution.

What a slot carries for the layer (:meth:`DeltaMixer.state_shapes`):
``S`` ``(H, d_k, d_v)`` and the convolution's rows ``(K - 1, 2 H d_k +
H d_v)``.  ``state_parts=n`` keeps ``S`` as ``n`` arrays of ``H / n``
heads each (the kernel then runs once a part): an engine stacks a
declared shape over layers and slots into ONE buffer, and a serving
engine's buffer of 6 layers x 256 slots x 32 heads is 3.2 GB, past the
2 GiB a 32-bit byte offset reaches.

``jax.named_scope`` names: ``kda.proj`` (the two projections),
``kda.conv`` (the convolution and its rows), ``kda.state`` (a step's
gates, the kernel, the norm and the output gate), ``kda.scan`` (the
same for a prompt, in chunks); ``gdn.*`` under a decay a head.

**The gated delta rule** (:class:`GatedDeltaMixer`; Yang, Kautz,
Hatamizadeh, arXiv:2412.06464) is the recurrence above with ``g`` ONE
number a head, and four things read otherwise::

    [q ; k ; v ; z ; a ; b] = W_in n          H d_k, H d_k, H d_v, H d_v, H, H
    g_h = -exp(a_log_h) softplus(a_h + dt_bias_h)    a head, unbounded
    beta_h = beta_max sigmoid(b_h)            beta_max 2: I - beta k k^T has
                                              the eigenvalue 1 - beta in
                                              (-1, 1) along k
    y = W_o [rms(o_h) * gain * silu(z_h)]_h   a norm a HEAD over its d_v
                                              values, one gain of d_v

and ``d_k != d_v`` (96 x 192).  Both forms of the recurrence are the
ones above: :meth:`DeltaMixer.step` and :meth:`DeltaMixer.scan` run
unchanged, over four hooks.  The gates (``_gates``: ``g`` comes back
``(..., H, 1)`` and broadcasts wherever the scan's algebra has a decay a
channel).  The chunk's pair matrices (``_pairs``): a scalar factors over
a whole chunk, ``A = (K K^T) * exp(G_t - G_i)`` with every exponent of
the kept triangle <= 0, so **no sub-blocks** and no cap.  The state's
layout and kernel (``state_shapes``, ``_advance``, ``_kept``): a slot
keeps ``S`` as ``(d_k, H d_v)``, all heads side by side along the lanes,
because a ``d_v`` of 192 is 1.5 lane tiles and a ``(.., 96, 192)`` array
is stored a third larger than it is (``ops/delta_state.py``
``head_decay_update``, the kernel ``gdn_state_update``).  The norm and
the output gate (``_finish``).
"""

from __future__ import annotations

import numpy as np

from bigdl_tpu.nn.latent import _draw
from bigdl_tpu.nn.module import AbstractModule
from bigdl_tpu.nn.ssm import _f32, _mm

#: added under the root of a head's squared norm
_L2_EPS = 1e-6


def _unit_lower_inverse(n):
    """``(I + n)^-1`` for ``n`` (..., c, c) strictly lower triangular
    (nilpotent: ``n^c = 0``), ``c`` a power of two, by doubling:
    ``(I - n)(I + n^2)(I + n^4) ...``."""
    import jax.numpy as jnp

    c = n.shape[-1]
    inv = jnp.eye(c, dtype=n.dtype) - n
    p = n
    for _ in range(int(np.log2(c)) - 1):
        p = _mm("...ij,...jk->...ik", p, p)
        inv = inv + _mm("...ij,...jk->...ik", inv, p)
    return inv


class DeltaMixer(AbstractModule):
    """The mixer of the module docstring."""

    param_names = ("w_in", "conv_w", "dt_bias", "a_log", "norm", "w_out")
    #: the prefix of the ``jax.named_scope`` names
    scope = "kda"

    def __init__(self, dim: int, heads: int, key_dim: int, value_dim: int,
                 d_conv: int = 4, lower_bound: float = -5.0,
                 norm_groups: int = 1, chunk: int = 64, sub: int = 16,
                 state_parts: int = 1, eps: float = 1e-6,
                 init: bool = True):
        super().__init__()
        if heads % state_parts:
            raise ValueError(f"{heads} heads in {state_parts} parts")
        if chunk % sub or chunk & (chunk - 1):
            raise ValueError(f"chunks of {chunk} in sub-blocks of {sub}: "
                             "a power of two that the sub-block divides")
        if -lower_bound * sub > 85.0:
            raise ValueError(
                f"exp({-lower_bound * sub:g}) a sub-block of {sub} at the "
                f"bound {lower_bound:g} does not fit float32")
        self._config = dict(
            dim=dim, heads=heads, key_dim=key_dim, value_dim=value_dim,
            d_conv=d_conv, lower_bound=lower_bound,
            norm_groups=norm_groups, chunk=chunk, sub=sub,
            state_parts=state_parts, eps=eps)
        self.dim, self.heads = dim, heads
        self.key_dim, self.value_dim, self.d_conv = key_dim, value_dim, d_conv
        self.lower_bound = float(lower_bound)
        self.norm_groups, self.chunk, self.sub = norm_groups, chunk, sub
        self.state_parts, self.eps = state_parts, eps
        self.d_key, self.d_value = heads * key_dim, heads * value_dim
        #: channels the convolution runs over: ``[q ; k ; v]``
        self.conv_dim = 2 * self.d_key + self.d_value
        #: the zones of ``W_in``'s outputs: q, k, v, f, z, b
        self.zones = (self.d_key, self.d_key, self.d_value, self.d_key,
                      self.d_value, heads)
        for n in self.param_names:
            setattr(self, n, None)
        if init:
            self.reset()

    def reset(self):
        import jax.numpy as jnp

        self.w_in = _draw((sum(self.zones), self.dim))
        self.conv_w = _draw((self.d_conv, self.conv_dim), 0.3)
        # decays around 0.97 a step
        self.dt_bias = jnp.full((self.d_key,), -5.0, jnp.float32)
        self.a_log = jnp.zeros((self.heads,), jnp.float32)
        self.norm = jnp.ones((self.d_value,), jnp.float32)
        self.w_out = _draw((self.dim, self.d_value))
        return self

    def state_shapes(self) -> tuple:
        """What a slot carries for this layer: ``S`` (a head's tile
        ``d_k x d_v``, as ``ops/delta_state.py`` wants it) in
        ``state_parts`` arrays of ``H / state_parts`` heads, and behind
        them the convolution's last ``K - 1`` rows of ``[q ; k ; v]``."""
        part = (self.heads // self.state_parts, self.key_dim, self.value_dim)
        return (part,) * self.state_parts \
            + ((self.d_conv - 1, self.conv_dim),)

    # ------------------------------------------------------------ parts
    def project(self, params, n):
        """``n`` (..., dim) -> the rows ``[q ; k ; v]`` (..., conv_dim)
        before the convolution, ``f`` (..., H d_k), the output gate
        ``z`` (..., H d_v) and ``b`` (..., H), each before its
        non-linearity."""
        import jax.numpy as jnp

        p = jnp.matmul(n, params["w_in"].T)
        at = np.cumsum((self.conv_dim,) + self.zones[3:])
        return p[..., :at[0]], p[..., at[0]:at[1]], p[..., at[1]:at[2]], \
            p[..., at[2]:]

    def _convolved(self, params, window):
        """``window`` (..., K, conv_dim), the ``K`` rows a position sees,
        oldest first -> ``q`` and ``k`` (..., H, d_k), each a unit
        vector a head and ``q`` under ``d_k ** -0.5``, and ``v`` (...,
        H, d_v), float32."""
        import jax
        import jax.numpy as jnp

        qkv = jax.nn.silu(jnp.sum(_f32(window) * _f32(params["conv_w"]),
                                  axis=-2))
        lead = qkv.shape[:-1]

        def unit(x):
            x = x.reshape(*lead, self.heads, self.key_dim)
            return x * jax.lax.rsqrt(
                jnp.sum(jnp.square(x), axis=-1, keepdims=True) + _L2_EPS)

        return (unit(qkv[..., :self.d_key]) * self.key_dim ** -0.5,
                unit(qkv[..., self.d_key:2 * self.d_key]),
                qkv[..., 2 * self.d_key:].reshape(*lead, self.heads,
                                                  self.value_dim))

    def _gates(self, params, f, b, live):
        """The log-decay ``g`` (..., H, d_k) in ``[lower, 0]`` and
        ``beta`` (..., H), float32; both 0 where ``live`` (...,) is
        false: a decay of exactly 1 and no write."""
        import jax
        import jax.numpy as jnp

        lead = f.shape[:-1]
        rate = jnp.exp(_f32(params["a_log"]))[:, None]
        g = self.lower_bound * jax.nn.sigmoid(
            rate * (_f32(f) + _f32(params["dt_bias"])).reshape(
                *lead, self.heads, self.key_dim))
        return (jnp.where(live[..., None, None], g, 0.0),
                jnp.where(live[..., None], jax.nn.sigmoid(_f32(b)), 0.0))

    def _finish(self, params, o, z, dtype):
        """``o`` (..., H d_v) float32 under the group RMS norm, its gain
        and the output gate ``z``; left in ``dtype``."""
        import jax
        import jax.numpy as jnp

        lead = o.shape[:-1]
        o = o.reshape(*lead, self.norm_groups, -1)
        o = o * jax.lax.rsqrt(
            jnp.mean(jnp.square(o), axis=-1, keepdims=True) + self.eps)
        return (o.reshape(*lead, self.d_value) * _f32(params["norm"])
                * jax.nn.sigmoid(_f32(z))).astype(dtype)

    # ---------------------------------------------------- one token a slot
    def step(self, params, n, states, rows, layer: int, active):
        """One token a slot: ``n`` (S, dim); ``states``, ``state_parts``
        arrays (layers, S, H / parts, d_k, d_v) float32, and ``rows``
        (layers, S, K - 1, conv_dim), the slots' STACKED state, advanced
        at ``layer``; ``active`` (S,) the slots that run -> ``(out (S,
        dim), states', rows')``; a slot that does not run keeps both
        (module docstring)."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope(self.scope + ".proj"):
            qkv, f, z, b = self.project(params, n)
        with jax.named_scope(self.scope + ".conv"):
            kept = rows[layer]
            window = jnp.concatenate(
                [kept, qkv[:, None].astype(kept.dtype)], axis=1)
            q, k, v = self._convolved(params, window)
            rows = rows.at[layer].set(
                jnp.where(active[:, None, None], window[:, 1:], kept))
        with jax.named_scope(self.scope + ".state"):
            g, beta = self._gates(params, f, b, active)
            states, o = self._advance(states, layer, g, k, q, v, beta)
            y = self._finish(params, o, z, n.dtype)
        with jax.named_scope(self.scope + ".proj"):
            return jnp.matmul(y, params["w_out"].T), states, rows

    def _advance(self, states, layer, g, k, q, v, beta):
        """The slots' stacked state one token on at ``layer``, by the
        kernel of this state's layout -> ``(states', o (S, H d_v))``, the
        read from the NEW state."""
        import jax.numpy as jnp

        from bigdl_tpu.ops.delta_state import state_update

        per = self.heads // self.state_parts
        done = [state_update(part, layer, *(
            a[:, j * per:(j + 1) * per]
            for a in (jnp.exp(g), k, q, v, beta)))
            for j, part in enumerate(states)]
        o = jnp.concatenate([o for _, o in done], axis=1)
        return tuple(part for part, _ in done), \
            o.reshape(k.shape[0], self.d_value)

    def _kept(self, state):
        """A prompt's final ``S`` ``(H, d_k, d_v)`` as a slot keeps it
        (:meth:`state_shapes`, without the convolution's rows)."""
        import jax.numpy as jnp

        return tuple(jnp.split(state, self.state_parts))

    # ---------------------------------------------------------- a prompt
    def _pairs(self, q, k, g_sum):
        """``P[t, i] = sum_c x_t[c] k_i[c] exp(G_t[c] - G_i[c])`` for
        every pair of a chunk, for ``x = k`` (the module docstring's
        ``A``) and ``x = q`` (``B``), a sub-block of rows at a time
        against the running sum at the sub-block's start: ``q``, ``k``
        and ``g_sum`` (chunks, c, H, d_k) -> two (chunks, H, c, c).
        Entries with ``i > t`` are finite and meaningless."""
        import jax.numpy as jnp

        nc, c, h, d = k.shape
        m = c // self.sub
        gs = g_sum.reshape(nc, m, self.sub, h, d)
        # the running sum before each sub-block's first row
        start = jnp.concatenate(
            [jnp.zeros((nc, 1, h, d), jnp.float32), gs[:, :-1, -1]], axis=1)
        rows = jnp.exp(gs - start[:, :, None])
        cols = k[:, None] * jnp.exp(jnp.minimum(
            start[:, :, None] - g_sum[:, None], -self.lower_bound * self.sub))
        return tuple(
            _mm("nmthd,nmihd->nhmti", x.reshape(nc, m, self.sub, h, d) * rows,
                cols).reshape(nc, h, c, c) for x in (k, q))

    def scan(self, params, n, t0):
        """One prompt ``n`` (T, dim), real up to ``t0`` (traced), from a
        zero state, in chunks -> ``(out (T, dim), states, rows)`` with
        ``states`` (``state_parts`` arrays (H / parts, d_k, d_v)) and
        ``rows`` the state after position ``t0 - 1`` (module
        docstring)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        real, c, kk = n.shape[0], self.chunk, self.d_conv
        h, dk, dv = self.heads, self.key_dim, self.value_dim
        # whole chunks: a tail of zero rows is positions past ``t0``
        n = jnp.pad(n, ((0, -real % c), (0, 0)))
        t = n.shape[0]
        nc = t // c
        with jax.named_scope(self.scope + ".proj"):
            qkv, f, z, b = self.project(params, n)
        with jax.named_scope(self.scope + ".conv"):
            padded = jnp.concatenate(
                [jnp.zeros((kk - 1, self.conv_dim), qkv.dtype), qkv])
            window = jnp.stack([padded[j:j + t] for j in range(kk)], axis=1)
            q, k, v = self._convolved(params, window)
            # padded[t0 .. t0 + K - 2] are the rows t0 - K + 1 .. t0 - 1
            rows = _f32(lax.dynamic_slice_in_dim(padded, t0, kk - 1))
        with jax.named_scope(self.scope + ".scan"):
            g, beta = self._gates(params, f, b, jnp.arange(t) < t0)
            q, k = (a.reshape(nc, c, h, dk) for a in (q, k))
            g = g.reshape(nc, c, h, -1)
            v, beta = v.reshape(nc, c, h, dv), beta.reshape(nc, c, h)
            g_sum = jnp.cumsum(g, axis=1)                       # inclusive
            lower = jnp.tril(jnp.ones((c, c), bool))
            a, bq = self._pairs(q, k, g_sum)
            a = jnp.where(lower & ~jnp.eye(c, dtype=bool), a, 0.0)
            bq = jnp.where(lower, bq, 0.0)
            by_row = jnp.swapaxes(beta, 1, 2)                   # (nc, H, c)
            # (I + Diag(beta) A)^-1 Diag(beta)
            solve = _unit_lower_inverse(a * by_row[..., None]) \
                * by_row[:, :, None, :]
            decayed = jnp.exp(g_sum)
            u = _mm("nhti,nihv->nhtv", solve, v)
            wk = _mm("nhti,nihd->nhtd", solve, k * decayed)
            q_in = q * decayed
            k_end = k * jnp.exp(g_sum[:, -1:] - g_sum)
            whole = decayed[:, -1]                              # (nc, H, d_k)

            def carry(s, chunk):
                u_c, wk_c, q_c, bq_c, k_c, whole_c = chunk
                w = u_c - _mm("htd,hdv->htv", wk_c, s)
                o = _mm("thd,hdv->thv", q_c, s) \
                    + _mm("hti,hiv->thv", bq_c, w)
                return s * whole_c[..., None] \
                    + _mm("thd,htv->hdv", k_c, w), o

            state, o = lax.scan(
                carry, jnp.zeros((h, dk, dv), jnp.float32),
                (u, wk, q_in, bq, k_end, whole))
            y = self._finish(params, o.reshape(t, self.d_value), z, n.dtype)
        with jax.named_scope(self.scope + ".proj"):
            return jnp.matmul(y, params["w_out"].T)[:real], \
                self._kept(state), rows

    def update_output_pure(self, params, input, *, training=False, rng=None):
        """``input`` (batch, T, dim) -> (batch, T, dim), every sequence
        on its own from a zero state."""
        import jax.numpy as jnp

        return jnp.stack([self.scan(params, seq, seq.shape[0])[0]
                          for seq in input])

    def __repr__(self):
        return (f"DeltaMixer({self.dim} -> {self.heads} x {self.key_dim} "
                f"x {self.value_dim})")


class GatedDeltaMixer(DeltaMixer):
    """The gated delta rule of the module docstring's last part: a decay
    a head, ``beta`` up to ``beta_max``, a norm a head under ``silu(z)``,
    ``S`` kept ``(d_k, H d_v)``.  No bound on ``g`` and no sub-blocks."""

    scope = "gdn"

    def __init__(self, dim: int, heads: int, key_dim: int, value_dim: int,
                 d_conv: int = 4, beta_max: float = 2.0, chunk: int = 64,
                 eps: float = 1e-6, init: bool = True):
        super().__init__(dim, heads, key_dim, value_dim, d_conv=d_conv,
                         lower_bound=0.0, norm_groups=heads, chunk=chunk,
                         sub=chunk, eps=eps, init=False)
        self._config = dict(
            dim=dim, heads=heads, key_dim=key_dim, value_dim=value_dim,
            d_conv=d_conv, beta_max=beta_max, chunk=chunk, eps=eps)
        self.beta_max = float(beta_max)
        #: the zones of ``W_in``'s outputs: q, k, v, z, a, b
        self.zones = (self.d_key, self.d_key, self.d_value, self.d_value,
                      heads, heads)
        if init:
            self.reset()

    def reset(self):
        import jax.numpy as jnp

        self.w_in = _draw((sum(self.zones), self.dim))
        self.conv_w = _draw((self.d_conv, self.conv_dim), 0.3)
        # decays around 0.97 a step: softplus(-3.5) = 0.03
        self.dt_bias = jnp.full((self.heads,), -3.5, jnp.float32)
        self.a_log = jnp.zeros((self.heads,), jnp.float32)
        self.norm = jnp.ones((self.value_dim,), jnp.float32)
        self.w_out = _draw((self.dim, self.d_value))
        return self

    def state_shapes(self) -> tuple:
        """``S`` with every head's ``d_v`` values side by side along the
        lanes (``ops/delta_state.py``, second half), and the
        convolution's last ``K - 1`` rows of ``[q ; k ; v]``."""
        return ((self.key_dim, self.d_value),
                (self.d_conv - 1, self.conv_dim))

    def project(self, params, n):
        """As :meth:`DeltaMixer.project`, the decay's input ``a`` (...,
        H) where that has ``f``: ``[q ; k ; v]``, ``a``, ``z``, ``b``."""
        import jax.numpy as jnp

        p = jnp.matmul(n, params["w_in"].T)
        at = np.cumsum((self.conv_dim,) + self.zones[3:])
        return p[..., :at[0]], p[..., at[1]:at[2]], p[..., at[0]:at[1]], \
            p[..., at[2]:]

    def _gates(self, params, a, b, live):
        """``g`` (..., H, 1) <= 0 and ``beta`` (..., H) in (0,
        ``beta_max``), float32; both 0 where ``live`` is false."""
        import jax
        import jax.numpy as jnp

        g = -jnp.exp(_f32(params["a_log"])) * jax.nn.softplus(
            _f32(a) + _f32(params["dt_bias"]))
        beta = self.beta_max * jax.nn.sigmoid(_f32(b))
        return (jnp.where(live[..., None], g, 0.0)[..., None],
                jnp.where(live[..., None], beta, 0.0))

    def _finish(self, params, o, z, dtype):
        import jax
        import jax.numpy as jnp

        lead = o.shape[:-1]
        o = o.reshape(*lead, self.heads, self.value_dim)
        o = o * jax.lax.rsqrt(
            jnp.mean(jnp.square(o), axis=-1, keepdims=True) + self.eps) \
            * _f32(params["norm"])
        return (o.reshape(*lead, self.d_value)
                * jax.nn.silu(_f32(z))).astype(dtype)

    def _advance(self, states, layer, g, k, q, v, beta):
        import jax.numpy as jnp

        from bigdl_tpu.ops.delta_state import head_decay_update

        (s,) = states
        s, o = head_decay_update(
            s, layer, jnp.exp(g[..., 0]), k, q,
            v.reshape(v.shape[0], self.d_value), beta)
        return (s,), o

    def _kept(self, state):
        import jax.numpy as jnp

        return (jnp.swapaxes(state, 0, 1).reshape(self.key_dim,
                                                  self.d_value),)

    def _pairs(self, q, k, g_sum):
        """``P[t, i] = (x_t . k_i) exp(G_t - G_i)`` for ``x = k`` and ``x
        = q``: ``g_sum`` (chunks, c, H, 1), one running sum a head, so the
        factor is one plane a head over the whole chunk, 0 above the
        diagonal (the exponent is masked BEFORE it is raised)."""
        import jax.numpy as jnp

        c = k.shape[1]
        gs = jnp.moveaxis(g_sum[..., 0], 2, 1)              # (nc, H, c)
        kept = jnp.tril(jnp.ones((c, c), bool))
        factor = jnp.exp(jnp.where(
            kept, gs[..., :, None] - gs[..., None, :], -jnp.inf))
        return tuple(_mm("nthd,nihd->nhti", x, k) * factor for x in (k, q))

    def __repr__(self):
        return (f"GatedDeltaMixer({self.dim} -> {self.heads} x "
                f"{self.key_dim} x {self.value_dim})")


__all__ = ["DeltaMixer", "GatedDeltaMixer"]
