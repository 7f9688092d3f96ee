"""A **dropless** expert layer for serving, told which experts it holds.

``parallel/moe.py`` is the training layer: GShard top-1 / top-2 with a
capacity factor that drops what overflows.  Serving cannot drop a token
and routes to many experts, most of which live on other chips.  This
layer computes **this chip's share** of

    s = softmax(W_r x)                    (router in float32, no bias)
    chosen = the top_k largest of s + b   (b: a selection bias, a buffer)
    w_e = scale * s_e                     (the bias is not in the weight)
    M(x) = sum over chosen e of w_e E_e(x)
    E_e = gated SiLU MLP for e < n_routed,  E_e(x) = x for the
          n_zero zero-compute experts after them

The router keeps all ``n_routed + n_zero`` outputs and its ``top_k``.
The layer holds the weights of the routed experts ``[lo, hi)`` only,
and returns the sum over a token's chosen experts that are **held** or
**zero-compute**; what the absent ones would have added is left out
(their chips add it in a deployment; here nothing stands in for them).
Zero-compute experts need no weights and are computed where the token
lives, so the sum over all shares counts them once.

How it computes: the ``tokens x top_k`` assignments are sorted by
expert (held experts first, in order; everything else behind them), the
sorted rows go through three grouped matrix products
(``ops/grouped_matmul.py``: a Pallas grouped product on the TPU, chosen
over ``jax.lax.ragged_dot`` by measurement; sized by the rows, not by
rows x experts; an expert without a row is not read) with the held
experts' group sizes,
and are added back to their tokens with their weights; the identity
experts are one weighted add.  The kernel's lane tiles follow from the
experts' own widths (that module's docstring has the rule and the
measured table: a 768-wide expert's matrix goes through whole, a
6144 x 2048 one in 2048 x 1024 blocks); its row tile is 128 whatever
share of the router's experts the layer holds (2, 16 or 32 rows an
expert and step in the three models served through PR 35, 16 rows of
256 top-1 assignments in ``models/zaya.py``: smaller row tiles were
measured and lost).  There is no capacity: the row buffer
holds every assignment, so no token is dropped at any imbalance.

**Another router, and a shared expert** (``models/joyai_flash.py``;
the arguments' defaults are the layer above, bit for bit; a softmax
WITH renormalised weights, no zero-compute and no shared expert, scale
1 and every expert held is ``models/sdar_moe.py``'s layer, run and
guarded by ``tests/test_sdar_moe.py`` and the benchmark's
``sdar_moe_block_gen``):
``score="sigmoid"`` takes ``s = sigmoid(W_r x)`` in place of the
softmax; ``renormalise=True`` divides a token's ``top_k`` weights by
their sum (+ 1e-20) before ``scale``, over ALL of them, held or absent,
so the shares still add up to the uncut layer; ``shared_hidden=n`` adds
``S(x)``, a gated SiLU MLP of that width with weights ``s_gate`` /
``s_up`` / ``s_down`` that every token passes through.  Like a
zero-compute expert it is computed where the token lives, whole, so the
sum over all shares counts it once (a deployment adds the shares'
routed parts to one ``S(x)``).  It is a dense MLP and runs under the
scope ``ffn``, not under ``moe.*``.

**A router the model gives it** (``models/zaya.py``: an MLP on a
narrow down-projection that adds the router row of the layer below, so
the scores are no function of this layer's input alone):
``own_router=False`` builds the layer without ``router`` and ``bias``,
and :meth:`DroplessExperts.apply` then takes ``routed=(idx, w)``, the
chosen experts ``(N, top_k)`` and their float32 weights, as
:meth:`DroplessExperts.route` returns them; everything behind the
choice (held and absent experts, the sort, the grouped products, the
counts) is the layer above.  ``top_k`` 1 is that model's; handing in
``routed=layer.route(params, x)`` is the layer above, bit for bit.

**A choice limited to groups** (``models/ling_flash.py``;
``groups=(n_group, topk_group)``, default none: every route above bit
for bit).  The routed experts lie in ``n_group`` groups of equal size, in
order.  A group's score is the sum of its 2 largest ``s + b``; the
``topk_group`` best groups are kept and the ``top_k`` are taken inside
them (what lies outside them is never chosen, whatever its score);
weights as above.  In a deployment a group is what one chip holds
(``held`` then names one group): a token sends a chip nothing unless
it kept the chip's group.  Such a layer's ``counts`` carry two values
more, behind the five: the real tokens that KEPT a group this chip
holds experts of, and the real tokens (:func:`counts_dict`'s
``group_hit_share`` is the one over the other).

It also counts what it routed (``counts``): assignments to held,
zero-compute and absent experts, how many held experts got a token, and
the largest load of a held expert — the work of a step varies with the
routing, and the serving spans carry these numbers.
"""

from __future__ import annotations

import numpy as np

from bigdl_tpu.nn.module import AbstractModule

#: order of :meth:`DroplessExperts.apply`'s ``counts`` vector
COUNT_NAMES = ("held", "zero", "absent", "hit", "max_load")


def merge_counts(a, b):
    """Counts of two expert layers of one step: sums, and the larger
    ``max_load``."""
    import jax.numpy as jnp

    if a is None:
        return b
    parts = [a[:4] + b[:4], jnp.maximum(a[4:5], b[4:5])]
    if a.shape[0] > 5:      # a group-limited layer's two sums more
        parts.append(a[5:] + b[5:])
    return jnp.concatenate(parts)


class DroplessExperts(AbstractModule):
    """See the module docstring.  ``held=(lo, hi)`` names the routed
    experts whose weights this layer has (default: all of them)."""

    param_names = ("router", "bias", "w_gate", "w_up", "w_down")

    def __init__(self, dim: int, hidden: int, n_routed: int, n_zero: int,
                 top_k: int, scale: float = 1.0, held=None,
                 score: str = "softmax", renormalise: bool = False,
                 shared_hidden: int = 0, own_router: bool = True,
                 groups=None, init: bool = True):
        super().__init__()
        if score not in ("softmax", "sigmoid"):
            raise ValueError(f"score {score!r}: softmax or sigmoid")
        lo, hi = (0, n_routed) if held is None else (int(held[0]),
                                                      int(held[1]))
        if not 0 <= lo < hi <= n_routed:
            raise ValueError(f"held experts [{lo}, {hi}) must lie in "
                             f"[0, {n_routed})")
        if top_k > n_routed + n_zero:
            raise ValueError(f"top_k {top_k} over {n_routed + n_zero} "
                             "experts")
        if groups is not None:
            n_group, topk_group = (int(g) for g in groups)
            if n_zero or n_routed % n_group or not (
                    0 < topk_group <= n_group) or not own_router \
                    or n_routed // n_group < 2 \
                    or topk_group * (n_routed // n_group) < top_k:
                raise ValueError(
                    f"groups {groups}: {n_routed} routed experts (and no "
                    f"zero-compute ones) in {n_group} equal groups of 2 "
                    f"or more, the layer's own router keeping "
                    f"{topk_group} of them with room for top_k {top_k}")
            groups = (n_group, topk_group)
        self._config = dict(dim=dim, hidden=hidden, n_routed=n_routed,
                            n_zero=n_zero, top_k=top_k, scale=scale,
                            held=(lo, hi), score=score,
                            renormalise=renormalise,
                            shared_hidden=shared_hidden,
                            own_router=own_router, groups=groups)
        self.dim, self.hidden = dim, hidden
        self.n_routed, self.n_zero = n_routed, n_zero
        self.top_k, self.scale = top_k, float(scale)
        self.lo, self.hi = lo, hi
        self.score, self.renormalise = score, bool(renormalise)
        self.shared_hidden = int(shared_hidden)
        self.own_router = bool(own_router)
        self.groups = groups
        if not self.own_router:
            self.param_names = tuple(
                n for n in type(self).param_names
                if n not in ("router", "bias"))
        if self.shared_hidden:
            self.param_names = self.param_names + (
                "s_gate", "s_up", "s_down")
        for n in self.param_names:
            setattr(self, n, None)
        if init:
            self.reset()

    @property
    def n_held(self) -> int:
        return self.hi - self.lo

    def reset(self):
        import jax.numpy as jnp

        from bigdl_tpu.nn.latent import _draw

        g = self.n_held
        if self.own_router:
            self.router = _draw((self.n_routed + self.n_zero, self.dim))
            self.bias = jnp.zeros((self.n_routed + self.n_zero,),
                                  jnp.float32)
        # (group, in, out): the grouped product's right-hand side
        self.w_gate = _draw((g, self.dim, self.hidden))
        self.w_up = _draw((g, self.dim, self.hidden))
        self.w_down = _draw((g, self.hidden, self.dim))
        if self.shared_hidden:
            # (out, in), like a dense gated MLP
            self.s_gate = _draw((self.shared_hidden, self.dim))
            self.s_up = _draw((self.shared_hidden, self.dim))
            self.s_down = _draw((self.dim, self.shared_hidden))
        return self

    # ------------------------------------------------------------ parts
    def route(self, params, x):
        """``x`` (N, dim) -> chosen expert ids (N, top_k) and their
        weights ``scale * s_e`` (N, top_k), float32 (``s_e`` over the
        sum of the chosen where the layer renormalises)."""
        return self._choose(params, x)[:2]

    def _choose(self, params, x):
        """:meth:`route`, and behind it the groups each token kept (N,
        n_group) bool, None for a layer without groups."""
        import jax
        import jax.numpy as jnp

        logits = jnp.matmul(x.astype(jnp.float32),
                            params["router"].astype(jnp.float32).T,
                            precision="highest")
        s = jax.nn.softmax(logits, axis=-1) if self.score == "softmax" \
            else jax.nn.sigmoid(logits)
        biased, kept = s + params["bias"].astype(jnp.float32), None
        if self.groups is not None:
            n_group, topk_group = self.groups
            by_group = biased.reshape(x.shape[0], n_group, -1)
            best2, _ = jax.lax.top_k(by_group, 2)
            _, keep = jax.lax.top_k(jnp.sum(best2, axis=-1), topk_group)
            kept = jnp.any(keep[:, :, None] == jnp.arange(n_group), axis=1)
            biased = jnp.where(kept[:, :, None], by_group,
                               -jnp.inf).reshape(biased.shape)
        _, idx = jax.lax.top_k(biased, self.top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if self.renormalise:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx, self.scale * w, kept

    def apply(self, params, state, input, *, mask=None, routed=None,
              training=False, rng=None):
        """``input`` (N, dim) -> ``((y (N, dim), counts (5,) int32: 7
        under ``groups``), state)``.  ``mask`` (N,) marks the rows that are real tokens;
        padding rows are routed nowhere, get 0, and are not counted.
        ``routed`` is another router's ``(idx, w)`` in :meth:`route`'s
        form (a layer without a router of its own needs it)."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.ops.grouped_matmul import grouped_matmul

        x = input
        n, k, g = x.shape[0], self.top_k, self.n_held
        if routed is None and not self.own_router:
            raise ValueError("a layer built with own_router=False is "
                             "handed its routing (routed=(idx, w))")
        with jax.named_scope("moe.route"):
            idx, w, kept = self._choose(params, x) if routed is None \
                else (*routed, None)
            real = jnp.ones((n,), bool) if mask is None else mask
            real = real[:, None]
            is_zero = (idx >= self.n_routed) & real
            is_held = (idx >= self.lo) & (idx < self.hi) & real
            # sort the N*k assignments by held expert; the rest sort
            # behind the last group and belong to none
            local = jnp.where(is_held, idx - self.lo, g).reshape(-1)
            order = jnp.argsort(local, stable=True)
            sizes = jnp.bincount(local, length=g + 1)[:g].astype(jnp.int32)
            token = order // k
            valid = jnp.take(local, order) < g
            w_sorted = jnp.where(valid, jnp.take(w.reshape(-1), order), 0.0)
            held = jnp.sum(sizes)
            counts = jnp.stack([
                held, jnp.sum(is_zero),
                jnp.sum(real) * k - held - jnp.sum(is_zero),
                jnp.sum(sizes > 0), jnp.max(sizes)]).astype(jnp.int32)
            if kept is not None:
                # the groups this chip holds experts of
                per = self.n_routed // self.groups[0]
                mine = kept[:, self.lo // per:-(-self.hi // per)]
                counts = jnp.concatenate([counts, jnp.stack([
                    jnp.sum(jnp.any(mine, axis=1) & real[:, 0]),
                    jnp.sum(real)]).astype(jnp.int32)])
        with jax.named_scope("moe.experts"):
            xs = jnp.take(x, token, axis=0)                  # (N*k, dim)
            h = jax.nn.silu(grouped_matmul(xs, params["w_gate"], sizes)) \
                * grouped_matmul(xs, params["w_up"], sizes)
            ys = grouped_matmul(h, params["w_down"], sizes,
                                preferred_element_type=jnp.float32)
            # rows behind the last group are no expert's: whatever the
            # product left there is dropped, not scaled
            ys = jnp.where(valid[:, None], ys * w_sorted[:, None], 0.0)
            y = jnp.zeros((n, self.dim), jnp.float32).at[token].add(ys)
        if self.n_zero:
            with jax.named_scope("moe.zero"):
                w_zero = jnp.sum(jnp.where(is_zero, w, 0.0), axis=-1)
                y = y + x.astype(jnp.float32) * w_zero[:, None]
        if self.shared_hidden:
            from bigdl_tpu.nn.latent import gated_mlp

            with jax.named_scope("ffn"):
                y = y + gated_mlp(x, params["s_gate"], params["s_up"],
                                  params["s_down"]).astype(jnp.float32)
        return (y.astype(x.dtype), counts), state

    def __repr__(self):
        return (f"DroplessExperts({self.n_routed}+{self.n_zero} experts, "
                f"top {self.top_k}, held [{self.lo}, {self.hi}))")


def counts_dict(counts) -> dict:
    """A ``counts`` vector on the host as ``{name: int}``."""
    vals = np.asarray(counts).reshape(-1)
    out = {name: int(v) for name, v in zip(COUNT_NAMES, vals)}
    if len(vals) > len(COUNT_NAMES):
        # a group-limited layer: the share of the real tokens that kept
        # a group this chip holds experts of
        out["group_hit_share"] = float(vals[5]) / max(int(vals[6]), 1)
    return out


__all__ = ["COUNT_NAMES", "DroplessExperts", "counts_dict", "merge_counts"]
