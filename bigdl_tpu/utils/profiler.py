"""Tracing / profiling — jax.profiler + the reference's phase timers.

SURVEY.md §5 "Tracing / profiling": the reference has per-phase wall
timers in DistriOptimizer aggregated via Metrics ("computing time
average / get weights average / …") plus throughput logging; the TPU
rebuild keeps those timer names (optim/metrics.py) and adds real device
traces via ``jax.profiler`` — viewable in TensorBoard or Perfetto.

Usage:

    from bigdl_tpu.utils.profiler import trace

    with trace("/tmp/tb"):               # device + host trace
        optimizer.optimize()

A named region inside a trace is a span of the obs tracer
(``obs.get_tracer().span("my-phase")``, on when ``BIGDL_TRACE_DIR`` is
set): a recording tracer writes each live span into the running
profiler session too, so there is one way to name host time.

Env hook: ``BIGDL_PROFILE=/dir`` makes the optimizers trace their first
20 iterations automatically (compile excluded).
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional

PROFILE_ENV = "BIGDL_PROFILE"
PROFILE_STEPS = 20


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_trace: bool = False):
    """Capture a jax.profiler trace into ``log_dir``."""
    import jax

    jax.profiler.start_trace(log_dir,
                             create_perfetto_trace=create_perfetto_trace)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepProfiler:
    """Optimizer hook: traces steps [skip, skip+steps) of a run when
    ``BIGDL_PROFILE`` is set (skip=1 excludes the compile step)."""

    def __init__(self, log_dir: Optional[str] = None, skip: int = 1,
                 steps: int = PROFILE_STEPS):
        from bigdl_tpu.config import config, refresh_from_env

        refresh_from_env()
        self.log_dir = log_dir or config.profile_dir
        self.skip = skip
        self.steps = steps
        self._n = 0
        self._active = False

    @property
    def enabled(self) -> bool:
        return self.log_dir is not None

    def step(self):
        """Call once per optimizer iteration."""
        if not self.enabled:
            return
        import jax

        if self._n == self.skip:
            jax.profiler.start_trace(self.log_dir)
            self._active = True
        elif self._n == self.skip + self.steps and self._active:
            jax.profiler.stop_trace()
            self._active = False
        self._n += 1

    def stop(self):
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
