"""Record the small device trace that ``benchmarks/lib/testdata`` keeps.

    chiprun --chips 1 -- python3 benchmarks/tools/record_trace.py

Runs two tiny jitted programs in a loop under ``jax.profiler``, with a
host sleep between some calls so that the trace holds known idle gaps,
and writes the trace in the compact JSON form ``lib/xplane.py`` reads
(planes -> lines -> [name, start_ns, duration_ns]) to
``chiprun_out/trace_sample/``.  Also prints what the machine looks like
to a benchmark: platform, device kind, whether a CPU backend is there,
the environment variables that steer JAX.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import xplane

    out = os.path.join(ROOT, "chiprun_out", "trace_sample")
    os.makedirs(out, exist_ok=True)
    for k in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS",
              "TPU_VISIBLE_DEVICES", "HOME", "TMPDIR"):
        print(f"env {k}={os.environ.get(k)!r}")
    devs = jax.devices()
    print("devices", [(d.platform, d.device_kind, d.id) for d in devs])
    try:
        print("cpu devices", jax.devices("cpu"))
    except RuntimeError as e:
        print("no cpu backend:", e)
    print("memory_stats", devs[0].memory_stats())

    @jax.jit
    def small_matmul(a):
        return jnp.tanh(a @ a) * 0.5

    @jax.jit
    def small_copy(a):
        return a.T + 1.0

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(small_copy(small_matmul(a)))
    tdir = os.path.join(out, "raw")
    jax.profiler.start_trace(tdir)
    t0 = time.perf_counter()
    for i in range(12):
        a = small_matmul(a)
        a = small_copy(a)
        jax.block_until_ready(a)
        if i % 3 == 2:
            time.sleep(0.002)
    host_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    pb = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True))[-1]
    trace = xplane.load(pb)
    for plane in trace["planes"]:
        print("plane", plane["name"], [(ln["name"], len(ln["events"]))
                                       for ln in plane["lines"]])
    small = xplane.device_only(trace)
    with open(os.path.join(out, "small_trace.json"), "w") as fh:
        json.dump(small, fh)
    red = xplane.reduce(small)
    red["host_loop_s"] = host_s
    print(json.dumps(red, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
