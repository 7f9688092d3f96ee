"""Grouped matrix product: rows sorted by group, one right-hand matrix a
group — the expert layer's product (``nn/experts.py``).

    out[r] = lhs[r] @ rhs[g]   for the rows r of group g

``lhs`` is ``(M, K)`` with the rows of group 0 first, then group 1's,
and so on; ``group_sizes`` ``(G,)`` int32 says how many each has;
``rhs`` is ``(G, K, N)``.  The sizes may add up to less than ``M``: the
rows behind the last group belong to none and **their output is
unspecified** (the caller masks them).

Two bodies, chosen by measurement (chip run, PR 26: ``M`` 1536 / 3072
rows of which 37 / 67 live, 16 groups of 6144 x 2048 bf16, one product):

* ``"pallas"`` — the megablox grouped product that ships with JAX
  (``jax.experimental.pallas.ops.tpu.megablox.gmm``), tiled (128, up
  to 2048, up to 1024): 0.54-0.60 ms, against the 0.49 ms it takes to
  read sixteen experts' weights at 819 GB/s.  It visits only the
  ``(row tile, group)`` pairs that hold rows, so a group without rows is
  not read;
* ``"ragged"`` — ``jax.lax.ragged_dot``, which the TPU compiler turns
  into a kernel of its own (tiled 512 x 512 x 512): 1.29-1.33 ms.  It
  runs everywhere XLA runs and is what the CPU backend (the tests)
  takes.

``impl="auto"`` takes the kernel on an accelerator when ``K`` and ``N``
are multiples of 128 lanes, and ``ragged`` otherwise.  (A dense masked
product of every token with every held expert reads all sixteen always
and costs as much as three kernels at 128 tokens, 1.64 ms, and 1.98 ms
at 256: it grows with tokens x experts, so it was not taken.)
"""

from __future__ import annotations

from typing import Optional

#: rows a tile; the lane tiles are the largest of these that divide
_TM = 128
_TK = (2048, 1024, 512, 256, 128)
_TN = (1024, 512, 256, 128)


def _tiling(k: int, n: int):
    tk = next((t for t in _TK if k % t == 0), None)
    tn = next((t for t in _TN if n % t == 0), None)
    return None if tk is None or tn is None else (_TM, tk, tn)


def grouped_matmul(lhs, rhs, group_sizes, *, preferred_element_type=None,
                   impl: str = "auto", interpret: Optional[bool] = None):
    """See the module docstring.  ``impl``: "auto", "ragged", "pallas",
    or "pallas_interpret" (testing: the kernel in the Pallas
    interpreter)."""
    import jax
    import jax.numpy as jnp

    out_dtype = preferred_element_type or lhs.dtype
    m, k = lhs.shape
    n = rhs.shape[2]
    tiling = _tiling(k, n)
    if impl == "auto":
        impl = "pallas" if tiling is not None \
            and jax.default_backend() != "cpu" else "ragged"
    if impl == "ragged":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=out_dtype)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"impl must be auto|ragged|pallas, got {impl!r}")
    if tiling is None:
        raise ValueError(f"the grouped kernel needs K and N in multiples "
                         f"of 128, got {k} and {n}")
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from bigdl_tpu.ops._pallas import resolve_interpret

    pad = -m % _TM            # whole row tiles; the padding is no group's
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(lhs, rhs, group_sizes.astype(jnp.int32),
              preferred_element_type=out_dtype, tiling=tiling,
              interpret=resolve_interpret(
                  True if impl == "pallas_interpret" else interpret))
    return out[:m] if pad else out


__all__ = ["grouped_matmul"]
