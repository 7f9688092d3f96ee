"""bigdl_tpu — a TPU-native deep-learning framework with the capabilities of
classic BigDL (reference: ugiwgh/BigDL, the Scala/Spark BigDL 0.x line).

Rebuilt idiomatically on JAX/XLA rather than ported:

* ``Tensor[Float]`` on MKL-backed JVM arrays  ->  ``jnp.ndarray`` on TPU HBM
* hand-written per-layer backwards            ->  ``jax.vjp`` / ``jax.grad``
* thread-pool model replicas per executor     ->  one XLA program per chip
* ``AllReduceParameter`` over Spark BlockManager
                                              ->  ``psum_scatter`` +
                                                  owner-shard update +
                                                  ``all_gather`` (ZeRO-1)
                                                  inside one jitted step
* Spark job-per-iteration barrier             ->  implicit synchrony of the
                                                  jitted train step

Reference layout cited throughout as ``«bigdl»/`` =
``spark/dl/src/main/scala/com/intel/analytics/bigdl/`` (see SURVEY.md for the
evidence-status preamble: the reference mount was empty, paths are the
upstream 0.x layout).
"""

import os as _os


def _place_compile_cache():
    """Give JAX's persistent compilation cache a directory, once, before
    anything compiles (package import is the one place the trainers,
    the serving engine and the scripts all pass through; a config
    update starts no backend).

    * ``JAX_COMPILATION_CACHE_DIR`` set: nothing is set here, JAX reads
      the variable itself;
    * platform pinned to CPU (the tests, ``JAX_PLATFORMS=cpu``): no
      cache at all.  The chip tool copies the checkout as it stands,
      and a CPU executable cached on one host may meet another host's
      CPU;
    * otherwise: ``<checkout>/.jax_cache``.  The path is part of the
      cache key, so it is fixed: never a temp dir, a pid or a time.

    Returns the directory set, or None when nothing was set."""
    if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    platforms = jax.config.jax_platforms or ""
    if platforms.split(",")[0].strip() == "cpu":
        return None
    path = _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_place_compile_cache()

from bigdl_tpu.engine import Engine  # noqa: E402
from bigdl_tpu.common import RandomGenerator  # noqa: E402
from bigdl_tpu.config import config, configure  # noqa: E402
from bigdl_tpu.tensor import Tensor  # noqa: E402
from bigdl_tpu import obs  # noqa: E402,F401 — observability layer (obs.get_tracer()…)

__version__ = "0.13.0"
