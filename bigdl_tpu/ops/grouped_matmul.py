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
  (``jax.experimental.pallas.ops.tpu.megablox.gmm``): 0.54-0.60 ms,
  against the 0.49 ms it takes to read sixteen experts' weights at
  819 GB/s.  It visits only the ``(row tile, group)`` pairs that hold
  rows, so a group without rows is not read;
* ``"ragged"`` — ``jax.lax.ragged_dot``, which the TPU compiler turns
  into a kernel of its own (tiled 512 x 512 x 512): 1.29-1.33 ms.  It
  runs everywhere XLA runs and is what the CPU backend (the tests)
  takes.

``impl="auto"`` takes the kernel on an accelerator when ``K`` and ``N``
are multiples of 128 lanes, and ``ragged`` otherwise.  (A dense masked
product of every token with every held expert reads all sixteen always
and costs as much as three kernels at 128 tokens, 1.64 ms, and 1.98 ms
at 256: it grows with tokens x experts, so it was not taken.)

**The kernel's lane tiles follow from the call's widths** (:func:`_tiling`;
PR 35).  One grid step is a ``(row tile, group)`` pair that holds rows,
times the ``K`` and ``N`` tiles; it multiplies a whole ``tm x tk`` by
``tk x tn`` block whatever share of the rows is the group's, and fetches
its ``tk x tn`` block of the group's matrix unless the step before it
used the same one.  ``tk`` and ``tn`` are multiples of 128 that divide
``K`` and ``N`` (the whole width where it fits): the pair that makes
the fewest grid steps a visit among those whose block, twice (the
pipeline keeps two), fits ``_RHS_BUFFERS``; of equals the one with
fewer ``K`` tiles (a group that straddles two row tiles fetches its
matrix again if ``K`` is cut, and only then).  The row tile stays 128
for every shape: the multiplier takes as long over a 128 x 128 block of
the matrix for 16 rows as for 128, so a smaller row tile only adds
visits.

Measured (TPU v5 lite, 2026-09-29, ``scripts/grouped_tiling_probe.py``:
bf16, group sizes as a seeded top-k router gives them, six products a
call, median ms a product; *read* = the hit groups' matrices once at
819 GB/s).  ``(tm, tk, tn)``, with the tiling until PR 35 first::

    16 groups of 6144 x 2048, 1536 rows of which 35 live, read 0.49
      (128, 2048, 1024) 0.515   (16, ..) 0.498    (64, 6144, 512) 0.491
      (128, 3072, 1024) 0.524   (128, 1024, 2048) 0.525
      and back (2048 x 6144): (128, 2048, 1024) 0.529  (16, ..) 0.537
      (128, 2048, 1536) 0.507   (.., 2048, 2048) does not fit
    32 groups of 2048 x 768, 4096 rows of which 506 live, read 0.12
      (128, 2048, 256) 0.302    (128, 2048, 768) 0.296  (16, ..) 0.309
      and back: (128, 256, 1024) 0.349   (128, 768, 2048) 0.333
      (16, 768, 2048) 0.340     (128, 384, 2048) 0.331
    128 groups of 2048 x 768, 4096 rows, all live, read 0.49
      (128, 2048, 256) 0.544    (128, 2048, 768) 0.521  (64, ..) 0.530
      (32, 2048, 768) 0.541     (16, 2048, 768) 0.590
      (128, 1024, 768) 0.558    (16, 1024, 768) 1.013
      and back: (128, 256, 1024) 0.692   (128, 768, 2048) 0.574
      (64, ..) 0.590  (32, ..) 0.609  (16, ..) 0.664
      (128, 768, 1024) 0.583    (128, 384, 2048) 0.604
    the same at a 256-token prefill's 2048 rows
      (128, 2048, 256) 0.517    (128, 2048, 768) 0.487  (16, ..) 0.548
      and back: (128, 256, 1024) 0.622   (128, 768, 2048) 0.553
      (32, 768, 2048) 0.522     (16, ..) 0.555
    16 groups of 2048 x 2048, 256 top-1 rows, all live (largest group
    22), read 0.16  (TPU v5 lite, 2026-09-30, PR 37: the fourth shape)
      (128, 2048, 1024) 0.294   (64, ..) 0.307   (16, ..) 0.304
      (128, 1024, 2048) 0.305   (128, 2048, 512) 0.290
      (128, 1024, 1024) 0.290   (.., 2048, 2048) does not fit
      and back (float32 out): (128, 2048, 1024) 0.274  (16, ..) 0.311
      (128, 1024, 2048) 0.293   (64, 2048, 512) 0.287
      (64, 1024, 1024) 0.295    (16, 2048, 2048) 0.313, wider rows do not fit
      the rule's (128, 2048, 1024) through ``grouped_matmul``: 0.279, 0.282

Runs of one tiling differ by about 3 %.  The wide experts sit at what
the copies take under every tiling tried, so theirs is what it was; the
768-wide ones gain where ``K`` = 768 was cut in three (a sixth of the
time) and little where ``N`` was.  The 2048 x 2048 experts read
0.27-0.31 under every tiling that fits, 0.11-0.15 over their read (16
visits of two grid steps and the group metadata, not the tiles): the
rule's choice stands.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

_log = logging.getLogger(__name__)

#: rows a tile (see above: smaller tiles were measured and lost)
_TM = 128
#: fast memory Mosaic gives a kernel on a v5e (chip-less compiles, PR 35:
#: blocks of 15 MiB in all compile, of 16.5 are refused) ...
_FAST_MEMORY = 16 << 20
#: ... and the part of it the two buffers of the right-hand block may
#: take; beside them stand two ``tm x tk`` and two ``tm x tn`` blocks
#: and the float32 accumulator, 2.5 MiB at most under these tiles
_RHS_BUFFERS = 8 << 20


def _lane_tiles(width: int):
    """Multiples of 128 lanes that divide ``width``, widest first."""
    return [t for t in range(width, 0, -128) if width % t == 0]


def _tiling(k: int, n: int, itemsize: int = 2):
    """``(tm, tk, tn)`` for groups of ``k x n`` matrices, or None where
    ``k`` or ``n`` is not in whole 128-lane tiles (module docstring)."""
    if k % 128 or n % 128:
        return None
    fits = [(tk, tn) for tk in _lane_tiles(k) for tn in _lane_tiles(n)
            if 2 * tk * tn * itemsize <= _RHS_BUFFERS]
    tk, tn = min(fits, key=lambda t: ((k // t[0]) * (n // t[1]), k // t[0]))
    return _TM, tk, tn


@functools.lru_cache(maxsize=None)
def _say(m, g, k, n, tiling):
    """The tiles a shape runs on, said once a distinct shape (the
    callers are traced: once a program, nothing in a step): the tracer's
    event ``grouped_matmul.tiling`` and the same at debug level."""
    from bigdl_tpu import obs

    said = dict(m=m, groups=g, k=k, n=n,
                tm=tiling[0], tk=tiling[1], tn=tiling[2])
    _log.debug("grouped_matmul.tiling %s", said)
    tracer = obs.get_tracer()
    if tracer.enabled:
        tracer.event("grouped_matmul.tiling", **said)


def grouped_matmul(lhs, rhs, group_sizes, *, preferred_element_type=None,
                   impl: str = "auto", interpret: Optional[bool] = None):
    """See the module docstring.  ``impl``: "auto", "ragged", "pallas",
    or "pallas_interpret" (testing: the kernel in the Pallas
    interpreter)."""
    import jax
    import jax.numpy as jnp

    out_dtype = preferred_element_type or lhs.dtype
    m, k = lhs.shape
    g, _, n = rhs.shape
    tiling = _tiling(k, n, rhs.dtype.itemsize)
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

    _say(m, g, k, n, tiling)
    pad = -m % _TM            # whole row tiles; the padding is no group's
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(lhs, rhs, group_sizes.astype(jnp.int32),
              preferred_element_type=out_dtype, tiling=tiling,
              interpret=resolve_interpret(
                  True if impl == "pallas_interpret" else interpret))
    return out[:m] if pad else out


__all__ = ["grouped_matmul"]
