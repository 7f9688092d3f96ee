"""Optimizer factory + LocalOptimizer.

Rebuild of «bigdl»/optim/Optimizer.scala and LocalOptimizer.scala
(SURVEY.md §3.2).  The reference's LocalOptimizer runs multi-threaded
model replicas over a core pool with a synchronous gradient sum; on TPU
that intra-node replication "disappears — one XLA program per chip
already saturates the chip" (SURVEY.md §2.4), so LocalOptimizer is a
single jitted train step:

    loss, grads = value_and_grad(model.apply + criterion.loss)
    flat_grad -> [clipping processors] -> optim_method.step

The driver loop around it keeps reference semantics: ``Trigger``-driven
stop/validate/checkpoint, state table with epoch/neval counters, train
summaries, hyper-parameter logging.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
from functools import partial
from typing import Optional, Sequence

import numpy as np
from bigdl_tpu.obs import names

log = logging.getLogger("bigdl_tpu.optim")


def _jnp():
    import jax.numpy as jnp

    return jnp


class _H2DWaiter:
    """Closes the ``feed.h2d`` spans of a traced run off the loop's
    thread.  ``jax.device_put`` returns when the copy is enqueued; this
    thread blocks on the arrays and records, with ``tracer.complete``,
    the put's start until they are ready on every chip.  The loop only
    hands the arrays over, so a traced step is pipelined as an untraced
    one is.  It exists only while the tracer records."""

    def __init__(self, tracer):
        self._tracer = tracer
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._run, name="bigdl-h2d-waiter", daemon=True)
        self._thread.start()

    def watch(self, t_put, arrays, step):
        self._queue.put((t_put, arrays, step))

    def _run(self):
        import jax

        while True:
            item = self._queue.get()
            if item is None:
                return
            t_put, arrays, step = item
            try:
                jax.block_until_ready(arrays)
            except Exception:  # noqa: BLE001 — tracing must not end a run
                log.debug("feed.h2d: batch of step %s not awaited", step,
                          exc_info=True)
                continue
            dur = time.perf_counter() - t_put
            leaves = jax.tree.leaves(arrays)
            self._tracer.complete(
                "feed.h2d", t_put, dur, step=step,
                bytes=int(sum(a.nbytes for a in leaves)),
                chips=len(leaves[0].sharding.device_set) if leaves else 0)

    def close(self):
        """Record what is still in flight, then end the thread."""
        self._queue.put(None)
        self._thread.join(timeout=60.0)


class _GradClipper:
    """Parameter processors («bigdl»/optim/parameters/… SURVEY.md §2.1):
    global L2-norm clipping and constant clipping, applied to the
    gradient pytree inside the jitted step (and to the *sharded* flat
    gradient in DistriOptimizer, matching the reference's sharded
    application — a flat vector is the one-leaf pytree case)."""

    def __init__(self):
        self.l2_norm_clip: Optional[float] = None
        self.const_clip: Optional[tuple] = None

    def __call__(self, grad, global_sq_norm=None):
        import jax

        jnp = _jnp()
        g = grad
        if self.const_clip is not None:
            lo, hi = self.const_clip
            g = jax.tree.map(lambda a: jnp.clip(a, lo, hi), g)
        if self.l2_norm_clip is not None:
            if global_sq_norm is None:
                from bigdl_tpu.optim.optim_method import _global_sq_norm

                sq = _global_sq_norm(g)
            else:
                sq = global_sq_norm
            scale = jnp.minimum(1.0, self.l2_norm_clip / (jnp.sqrt(sq) + 1e-12))
            g = jax.tree.map(lambda a: a * scale, g)
        return g


class BaseOptimizer:
    """Shared builder API (reference: Optimizer's fluent setters)."""

    def __init__(self, model, dataset, criterion, batch_size=32):
        from bigdl_tpu.dataset import to_dataset
        from bigdl_tpu.optim.optim_method import SGD
        from bigdl_tpu.optim.triggers import Trigger
        from bigdl_tpu.optim.metrics import Metrics

        self.model = model
        self.dataset = to_dataset(dataset, batch_size)
        self.criterion = criterion
        self.batch_size = batch_size
        self.optim_method = SGD()
        self.end_when = Trigger.max_epoch(1)
        self.validation_trigger = None
        self.validation_dataset = None
        self.validation_methods = None
        self.checkpoint_path = None
        self.checkpoint_trigger = None
        self.train_summary = None
        self.val_summary = None
        self.metrics = Metrics()
        self._clipper = _GradClipper()
        self.max_retry = 5
        self.checkpoint_keep_last = 0
        # background checkpoint-write failure accounting: the failure is
        # recorded here and SURFACED on the next _checkpoint/optimize
        # call instead of dying as a log line (resilience satellite)
        self.checkpoint_write_failures = 0
        self._ckpt_write_error = None
        # non-finite step guard accounting
        self._nonfinite_consec = 0
        self._fault_injector = None
        # observability session handles; optimize() rebinds them from
        # the live config (NULL tracer / None reservoir / NULL ledger
        # = disabled)
        from bigdl_tpu.obs.goodput import NULL_LEDGER
        from bigdl_tpu.obs.trace import NULL_TRACER

        self._obs_tracer = NULL_TRACER
        self._obs_runtime = None
        self._obs_ledger = NULL_LEDGER
        # per-layer numerics telemetry (obs/health.py); optimize()
        # builds it from the live config, None = disabled
        self._health_monitor = None
        # static per-step collective byte footprint (obs/collectives.py)
        # — DistriOptimizer builds it with the train step; the driver
        # loop commits it once per resolved step
        self._collective_footprint = None
        # mixed-precision compute policy: None = full f32; "bfloat16"
        # runs fwd/bwd in bf16 with f32 master params + f32 grads/update
        # (the TPU-native recipe: MXU at 2x, normalizations stay f32)
        self.compute_dtype = None
        # elastic session (preemption polling + heartbeat liveness);
        # optimize() builds it from the live config, None outside a run
        self._elastic_session = None
        # batches to skip at the next epoch start — set by the resume
        # paths when the loaded checkpoint was written mid-epoch, so the
        # replay sees the exact batch the saved neval expects
        self._pending_fast_forward = 0
        # reference: InternalOptimizerUtil state table.  epoch_neval0 =
        # the neval of the current epoch's first batch, checkpointed so
        # a mid-epoch resume can fast-forward the data iterator to the
        # exact batch the saved neval expects (resilience/elastic.py)
        self.state = {"epoch": 1, "neval": 1, "loss": None, "score": None,
                      "epoch_finished": 0, "nonfinite_skips": 0,
                      "epoch_neval0": 1}

    # ---- fluent setters (camelCase parity aliases at the bottom) --------
    def set_optim_method(self, method):
        self.optim_method = method
        return self

    def set_end_when(self, trigger):
        self.end_when = trigger
        return self

    def set_validation(self, trigger=None, dataset=None, methods=None, batch_size=None):
        from bigdl_tpu.dataset import to_dataset

        self.validation_trigger = trigger
        self.validation_dataset = to_dataset(dataset, batch_size or self.batch_size)
        self.validation_methods = methods
        return self

    def set_checkpoint(self, path, trigger=None, background=None,
                       keep_last=None):
        """``background=True`` writes checkpoints fully async: the
        blocking part snapshots every array to host (the only span on
        the training critical path, stamped as the only
        ``checkpoint_save`` badput), then serialize/fsync/manifest run
        on a background writer thread.  At most one write is in flight;
        the next trigger waits for it.  Default from
        ``BIGDL_CHECKPOINT_ASYNC``; emergency/preemption checkpoints
        ALWAYS write synchronously regardless (the process is exiting —
        there is nothing to overlap, and the checkpoint must be durable
        before the exit code).

        ``keep_last=K`` keeps only the newest K checkpoint pairs on
        disk (GC after each write); default from
        ``config.checkpoint_keep_last``, 0 = unlimited."""
        from bigdl_tpu.config import refresh_from_env
        from bigdl_tpu.optim.triggers import Trigger

        config = refresh_from_env()
        os.makedirs(path, exist_ok=True)
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger or Trigger.every_epoch()
        self.checkpoint_background = (config.checkpoint_async
                                      if background is None
                                      else bool(background))
        self.checkpoint_keep_last = (config.checkpoint_keep_last
                                     if keep_last is None else int(keep_last))
        return self

    def set_train_summary(self, summary):
        self.train_summary = summary
        return self

    def set_val_summary(self, summary):
        self.val_summary = summary
        return self

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float):
        self._clipper.l2_norm_clip = clip_norm
        return self

    def set_constant_gradient_clipping(self, min_value: float, max_value: float):
        self._clipper.const_clip = (min_value, max_value)
        return self

    def disable_gradient_clipping(self):
        self._clipper.l2_norm_clip = None
        self._clipper.const_clip = None
        return self

    def set_compute_dtype(self, dtype):
        """Mixed precision: ``"bfloat16"`` (or a jnp dtype) runs the
        model fwd/bwd in that dtype while master params, gradients, the
        loss, and the optimizer update stay f32.  ``None`` disables."""
        self.compute_dtype = dtype
        return self

    # reference spellings
    setOptimMethod = set_optim_method
    setEndWhen = set_end_when
    setValidation = set_validation
    setCheckpoint = set_checkpoint
    setTrainSummary = set_train_summary
    setValSummary = set_val_summary
    setGradientClippingByL2Norm = set_gradient_clipping_by_l2_norm
    setConstantGradientClipping = set_constant_gradient_clipping

    # ---- shared helpers -------------------------------------------------
    def _summary_resilience(self, step, **counters):
        """Feed resilience counters to the train summary when one is set
        (guarded: user-supplied summary stubs may lack the method)."""
        add = getattr(self.train_summary, "add_resilience", None)
        if add is not None:
            add(step, **counters)

    def _raise_pending_ckpt_error(self):
        """Surface a background checkpoint-write failure recorded by
        ``_flush_checkpoints(raise_errors=False)`` — the next
        ``_checkpoint``/``optimize`` call must fail loudly, not keep
        training against a checkpoint sink that silently stopped
        persisting."""
        err = self._ckpt_write_error
        if err is not None:
            from bigdl_tpu.resilience.retry import CheckpointWriteError

            self._ckpt_write_error = None
            raise CheckpointWriteError(
                f"a background checkpoint write failed earlier "
                f"({self.checkpoint_write_failures} total write "
                f"failures): {err!r}") from err

    def _checkpoint(self):
        if not self.checkpoint_path:
            return
        self._raise_pending_ckpt_error()
        from bigdl_tpu.utils.serializer import (
            save_checkpoint,
            snapshot_checkpoint,
            write_checkpoint,
        )

        tag = f"{self.state['epoch']}_{self.state['neval']}"
        prefix = os.path.join(self.checkpoint_path, f"checkpoint_{tag}")
        extra = self._checkpoint_extra()
        keep = self.checkpoint_keep_last
        if getattr(self, "checkpoint_background", False):
            from concurrent.futures import ThreadPoolExecutor

            if getattr(self, "_ckpt_executor", None) is None:
                self._ckpt_executor = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="bigdl-ckpt")
                self._ckpt_future = None
            self._flush_checkpoints()  # at most one write in flight
            # snapshot-to-host is the ONLY blocking span (and the only
            # checkpoint_save badput); the extra dict — incl. the
            # exactly-once stream offset — was captured above, at
            # snapshot time, with every dispatched step resolved.  The
            # writer thread then owns plain numpy, no device refs.
            snap = snapshot_checkpoint(self.model, self.optim_method,
                                       extra, to_host=True)
            self._ckpt_future = self._ckpt_executor.submit(
                write_checkpoint, snap, prefix, keep, True)
            log.info("checkpoint scheduled at epoch %s iter %s",
                     self.state["epoch"], self.state["neval"])
            return
        save_checkpoint(prefix, self.model, self.optim_method, extra,
                        keep_last=keep)
        log.info("checkpoint saved at epoch %s iter %s", self.state["epoch"],
                 self.state["neval"])

    def _flush_checkpoints(self, raise_errors: bool = True):
        """Wait for an in-flight background checkpoint write — called
        before reads of the checkpoint dir and at the end of
        optimize().  ``raise_errors=False`` records the failure (next
        ``_checkpoint``/``optimize`` call surfaces it) instead of
        raising — used in the exception-path finally, where raising
        would mask the original error."""
        fut = getattr(self, "_ckpt_future", None)
        if fut is not None:
            self._ckpt_future = None
            try:
                fut.result()
            except Exception as e:
                self.checkpoint_write_failures += 1
                self._summary_resilience(
                    self.state["neval"],
                    checkpoint_write_failures=self.checkpoint_write_failures)
                from bigdl_tpu import obs

                obs.get_tracer().event(
                    "resilience.checkpoint_write_failed",
                    step=self.state["neval"], error=type(e).__name__,
                    total=self.checkpoint_write_failures)
                obs.get_registry().counter(
                    names.CHECKPOINT_WRITE_FAILURES_TOTAL,
                    "Background checkpoint writes that raised").inc()
                if raise_errors:
                    raise
                self._ckpt_write_error = e
                log.exception("background checkpoint write failed "
                              "(recorded; surfaces on the next "
                              "checkpoint/optimize call)")

    def _topology(self):
        """The checkpoint topology tag (resilience/elastic.py): how the
        writer's optimizer state is laid out, so restore can tell a
        same-world resume from a resize.  Local training keeps the
        native params pytree — nothing to re-partition."""
        return {"world_size": 1, "shard_layout": "tree",
                "step": self.state["neval"]}

    def _checkpoint_extra(self) -> dict:
        """Everything a resume needs beyond the arrays: trigger/LR
        counters, the epoch's starting neval (mid-epoch fast-forward),
        the writer topology, and — for streaming datasets — the trained
        stream offset/watermark (the exactly-once commit point,
        dataset/stream.py)."""
        extra = {"epoch": self.state["epoch"],
                 "neval": self.state["neval"],
                 "epoch_neval0": self.state.get("epoch_neval0",
                                                self.state["neval"]),
                 "topology": self._topology()}
        stream_state = getattr(self.dataset, "stream_checkpoint_state",
                               None)
        if stream_state is not None:
            extra["stream"] = stream_state()
        return extra

    def _elastic_shutdown(self, step, pvar, mod_state, opt_state):
        """Graceful preemption (resilience/elastic.py): the in-flight
        step already resolved — write back the live device state, write
        a synchronous emergency checkpoint through the hardened
        ``write_checkpoint`` path, and raise :class:`Preempted` (a
        SystemExit carrying EXIT_PREEMPTED).  The optimize() finally
        still flushes obs shards and any background checkpoint."""
        from bigdl_tpu import obs
        from bigdl_tpu.resilience import elastic

        signum = elastic.preemption_signal()
        # the request is being handled NOW: drop the flag so a later
        # optimize() in this process (tests, a supervisor running
        # in-process) doesn't re-preempt on the stale bit
        elastic.clear_preemption()
        log.warning(
            "preemption requested (signal %s) at iter %d — emergency "
            "checkpoint, then exit %d", signum, step,
            elastic.EXIT_PREEMPTED)
        self._write_back(pvar, mod_state)
        self.optim_method.state = opt_state
        tracer = obs.get_tracer()
        prefix = None
        if self.checkpoint_path:
            # serialize against an in-flight background write of the
            # same prefix (records, never raises: nothing may mask the
            # preemption exit)
            self._flush_checkpoints(raise_errors=False)
            tag = f"{self.state['epoch']}_{self.state['neval']}"
            prefix = os.path.join(self.checkpoint_path,
                                  f"checkpoint_{tag}")
            try:
                from bigdl_tpu.utils.serializer import save_checkpoint

                save_checkpoint(prefix, self.model, self.optim_method,
                                extra=self._checkpoint_extra(),
                                keep_last=self.checkpoint_keep_last)
                log.info("emergency checkpoint written: %s", prefix)
                tracer.event("elastic.emergency_checkpoint", step=step,
                             prefix=os.path.basename(prefix))
            except Exception as e:  # noqa: BLE001 — still exit preempted
                log.exception("emergency checkpoint failed; exiting "
                              "preempted without one")
                tracer.event("elastic.emergency_checkpoint_failed",
                             step=step, error=type(e).__name__)
                prefix = None
        obs.get_registry().counter(
            names.PREEMPTIONS_TOTAL,
            "Graceful preemption shutdowns (SIGTERM/SIGINT)").inc()
        tracer.event("elastic.preempted", step=step, signum=signum,
                     checkpoint=prefix and os.path.basename(prefix))
        raise elastic.Preempted(
            f"preempted (signal {signum}) at iter {step}; emergency "
            f"checkpoint: {prefix or 'none'}", step=step,
            checkpoint=prefix)

    def _prepare_batch(self, inp, tgt):
        """Hook: adjust a host batch before device transfer, or return
        None to drop it.  DistriOptimizer overrides to enforce mesh
        divisibility."""
        return inp, tgt

    def _params_tree(self, pvar):
        """Device-resident training params -> the model's params pytree.
        Local training already holds the tree; DistriOptimizer overrides
        to unravel its flat ZeRO vector (on device, no host copy)."""
        return pvar

    def _run_validation(self, pvar=None, mstate=None):
        """Validation on device-resident params (VERDICT r2 #3): the
        trainer passes its live pvar/mstate so no host weight copy
        happens per trigger; the eval forward shards each batch P(data)
        over the trainer's mesh when one exists (reference: distributed
        Evaluator over the executors, SURVEY.md §3.6)."""
        if self.validation_dataset is None or not self.validation_methods:
            return None
        from bigdl_tpu.optim.evaluator import evaluate_dataset

        params = state = None
        if pvar is not None:
            params = self._params_tree(pvar)
            state = mstate
        results = evaluate_dataset(
            self.model, self.validation_dataset, self.validation_methods,
            mesh=getattr(self, "mesh", None), params=params, state=state,
        )
        for method, res in zip(self.validation_methods, results):
            value, _ = res.result()
            log.info("validation %s: %.6f", method.name, value)
            if self.val_summary is not None:
                self.val_summary.add_scalar(method.name, value, self.state["neval"])
        # first method's value is the reference's "score" for Trigger.maxScore
        self.state["score"] = results[0].result()[0]
        # Plateau schedule hook
        sched = getattr(self.optim_method, "learningrate_schedule", None)
        from bigdl_tpu.optim.optim_method import Plateau

        if isinstance(sched, Plateau):
            scale = sched.on_score(self.state["score"], self.optim_method.learningrate)
            if self.optim_method.state is not None:
                jnp = _jnp()
                self.optim_method.state["lr_scale"] = jnp.asarray(scale, jnp.float32)
        return results


class LocalOptimizer(BaseOptimizer):
    """Single-process trainer (reference: «bigdl»/optim/LocalOptimizer.scala).

    The driver loop here is shared with DistriOptimizer (which overrides
    ``_build_train_step``/``_init_opt_state``/``_put_batch`` to shard over
    the mesh) — mirroring how the reference shares Trigger/checkpoint/
    validation logic between its two optimizers.
    """

    def _init_params(self):
        """Device representation of the trainable parameters.  Local:
        the native pytree (no ravel/unravel copies on the hot path).
        DistriOptimizer overrides with the flat vector its ZeRO-1
        reduce-scatter shards.

        The tree is copied: the jitted step donates its input buffers,
        and the model must never be left holding donated (deleted)
        arrays."""
        import jax

        jnp = _jnp()
        return jax.tree.map(lambda a: jnp.array(a, copy=True),
                            self.model.params())

    def _cast_for_compute(self, p, inp):
        """Apply the mixed-precision policy: cast floating params and the
        input to compute_dtype.  The cast sits inside the differentiated
        function, so grads w.r.t. the f32 master params come back f32."""
        if self.compute_dtype is None:
            return p, inp
        import jax

        jnp = _jnp()
        ct = jnp.dtype(self.compute_dtype)
        cast = lambda a: (
            a.astype(ct)
            if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)
            else a
        )
        return jax.tree.map(cast, p), cast(inp)

    def _loss_fn(self):
        """Returns loss_fn: (params, mstate, rng, inp, tgt) ->
        (loss_for_grad, (reported_loss, new_mstate))."""
        model, criterion = self.model, self.criterion

        def loss_fn(p, mstate, rng, inp, tgt):
            import jax

            jnp = _jnp()
            pc, inpc = self._cast_for_compute(p, inp)
            out, new_mstate = model.apply(pc, mstate, inpc, training=True,
                                          rng=rng)
            # the loss always evaluates in f32 (softmax/log numerics)
            out = jax.tree.map(
                lambda a: a.astype(jnp.float32)
                if hasattr(a, "dtype") and jnp.issubdtype(a.dtype,
                                                          jnp.floating)
                else a,
                out,
            )
            loss = criterion.loss(out, tgt) + model.regularization_loss(p)
            return loss, (loss, new_mstate)

        return loss_fn

    def _init_opt_state(self, pvar):
        opt = self.optim_method
        if opt.state is None:
            opt.state = opt.init_state(pvar)
        return opt.state

    def _build_train_step(self):
        import jax

        from bigdl_tpu.config import config

        jnp = _jnp()
        opt = self.optim_method
        clipper = self._clipper
        loss_fn = self._loss_fn()
        guard = config.nonfinite_guard
        # per-layer health telemetry (obs/health.py): pure device math
        # appended to the step ONLY when the monitor exists — disabled
        # runs compile the exact pre-health signature
        health_on = self._health_monitor is not None
        # freeze support (reference module.freeze): zero the gradients
        # of frozen subtrees — static at trace time, no cost unfrozen
        mask = self.model.grad_mask() if self.model.has_frozen() else None

        # params/opt state/model state buffers are donated: the step
        # updates in place on-device instead of allocating fresh HBM
        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def train_step(p, opt_st, mstate, rng, inp, tgt):
            (_, (loss, new_mstate)), grad = jax.value_and_grad(
                loss_fn, has_aux=True
            )(p, mstate, rng, inp, tgt)
            if mask is not None:
                # mask BEFORE the clipper so frozen gradients cannot
                # inflate the global norm and over-shrink live ones
                grad = jax.tree.map(lambda g, s: g * s, grad, mask)
            # health stats see the pre-clip gradient (clipping hides
            # exactly the explosions the telemetry exists to show)
            grad_for_health = grad if health_on else None
            grad = clipper(grad)
            new_p, new_opt = opt.step(grad, p, opt_st)
            if mask is not None:
                # and mask the UPDATE too: optimizer-internal weight
                # decay adds wd*p past the zeroed gradient — frozen
                # parameters must not move at all
                new_p = jax.tree.map(
                    lambda old, new, s: old + s * (new - old),
                    p, new_p, mask)
            ok = jnp.array(True)
            if guard:
                # non-finite step guard: a NaN/inf gradient (or loss)
                # must not be trained on — params/opt state/model state
                # pass through unchanged and the driver counts the skip
                ok = jnp.isfinite(loss)
                for leaf in jax.tree.leaves(grad):
                    ok = ok & jnp.all(jnp.isfinite(leaf))
                keep = lambda new, old: jax.tree.map(
                    lambda a, b: jnp.where(ok, a, b)
                    if hasattr(a, "dtype") else a,
                    new, old)
                new_p = keep(new_p, p)
                new_opt = keep(new_opt, opt_st)
                new_mstate = keep(new_mstate, mstate)
            if health_on:
                from bigdl_tpu.obs import health as _health

                # (L, 4) per-layer [grad_sq, param_sq, update_sq,
                # nonfinite]; new_p is post-guard so a skipped step
                # reports a zero update
                stats = _health.tree_layer_stats(grad_for_health, p,
                                                 new_p)
                return new_p, new_opt, new_mstate, loss, ok, stats
            return new_p, new_opt, new_mstate, loss, ok

        return train_step

    def _put_batch(self, inp, tgt):
        jnp = _jnp()
        return jnp.asarray(inp), jnp.asarray(tgt)

    def optimize(self):
        import jax

        from bigdl_tpu import obs
        from bigdl_tpu.resilience.faults import get_injector

        # a background checkpoint write that failed in a previous
        # optimize() (recorded by the exception-path flush) surfaces
        # here, before any new work trusts the broken sink
        self._raise_pending_ckpt_error()
        inj = get_injector()
        self._fault_injector = inj if inj.active else None
        self._nonfinite_consec = 0
        # observability session: the tracer is NULL (shared no-op
        # context managers) and the runtime reservoir None when obs is
        # off, so the hot loop pays nothing — and nothing here ever
        # reads a device value, so enabling obs adds zero per-step
        # host-device synchronizations either way
        tracer = self._obs_tracer = obs.get_tracer()
        self._h2d_waiter = _H2DWaiter(tracer) if tracer.enabled else None
        # the feed's reused host batches and gather threads, for as long
        # as this call (nothing is allocated before the first batch)
        from bigdl_tpu.native import StagingRing

        self._staging = StagingRing()
        self._obs_runtime = obs.get_runtime() if obs.active() else None
        # goodput ledger (obs/goodput.py): interval stamps ride the
        # span boundaries below — the shared no-op object when obs is
        # off, so the hot loop pays method-call noise at most and never
        # a device read either way
        self._obs_ledger = obs.get_ledger()
        # live telemetry plane (obs/server.py): the /metrics + /healthz
        # endpoint exists only when BIGDL_OBS_PORT is set; unset, this
        # is one config read, no thread, no socket — and the loop below
        # skips the per-step stamp entirely
        from bigdl_tpu.obs import server as _obs_server

        self._obs_server = _obs_server.ensure_server()
        # continuous profiler (obs/prof.py): starts sampling with the
        # training loop when BIGDL_PROF_HZ > 0; off = one config read
        from bigdl_tpu.obs import prof as _obs_prof

        _obs_prof.get_profiler()
        if self._obs_server is not None:
            # the reference Metrics phase timers live in a private
            # registry; expose them on /metrics next to the process one
            _obs_server.register_registry(self.metrics.registry)
        # training-health telemetry: the monitor exists only when
        # BIGDL_HEALTH_EVERY > 0; its absence makes the step build the
        # exact health-less signature with zero extra host transfers
        from bigdl_tpu.obs import health as _health_mod

        self._health_monitor = _health_mod.monitor_from_config(
            self.model.params(), tracer=tracer,
            summary=self.train_summary)
        # elastic session: registers this loop as a preemption listener
        # (SIGTERM now drains gracefully instead of exiting from the
        # handler) and starts the heartbeat monitor on multi-host runs
        from bigdl_tpu.resilience import elastic as _elastic

        self._elastic_session = _elastic.ElasticSession.from_config()
        from bigdl_tpu.utils.profiler import StepProfiler

        profiler = StepProfiler()
        # everything from here on is undone by the finally below, so a
        # failure while the step is being built leaves no preemption
        # listener (and no waiter thread) behind
        try:
            model = self.model
            model.training()

            pvar = self._init_params()
            # copy model/optimizer state before the first (donating) step so
            # the model and any pre-existing opt.state never alias deleted
            # buffers; after that, opt.state tracks the step outputs (only an
            # exception *during* a step can catch it transiently stale)
            copy = lambda t: jax.tree.map(
                lambda a: a.copy() if hasattr(a, "copy") else a, t
            )
            mod_state = copy(model.state())
            opt = self.optim_method
            opt_state = copy(self._init_opt_state(pvar))
            opt.state = opt_state
            # the build itself is traced; the returned step is wrapped so
            # first-call (trace+compile) vs cached-dispatch timing feeds the
            # runtime profile (obs/runtime.py)
            with tracer.span("build_train_step"):
                train_step = self._build_train_step()
            if self._obs_runtime is not None:
                train_step = obs.instrument_jit(
                    train_step, "train_step", stats=self._obs_runtime,
                    tracer=tracer, ledger=self._obs_ledger)

            base_key = jax.random.key(1234)
            wall_start = time.time()
            records_total = 0
            stop = False
            # under a recording tracer the stall watch (obs/prof.py)
            # minds this thread for as long as the loop: its spans'
            # boundaries are the heartbeat, and a validation or a
            # checkpoint is no stall
            minded = _obs_prof.get_watch().add(
                "train", quiet=("validation", "checkpoint",
                                "build_train_step")) \
                if tracer.enabled else None
            try:
                return self._optimize_loop(
                    model, pvar, mod_state, opt, opt_state, train_step,
                    base_key, wall_start, records_total, stop, profiler,
                )
            finally:
                if minded is not None:
                    minded.drop()
        finally:
            # an exception mid-epoch must not leak an active trace — the
            # DistriOptimizer retry path would otherwise hit "profiler
            # already started" on its next attempt
            profiler.stop()
            # unregister the preemption listener + stop the heartbeat
            # thread (a retry attempt builds a fresh session)
            if self._elastic_session is not None:
                self._elastic_session.close()
                self._elastic_session = None
            # a background checkpoint still writing must become durable
            # before optimize() returns or the retry path reads the
            # checkpoint dir; write errors are logged here (raising in
            # a finally would mask an in-flight exception)
            self._flush_checkpoints(raise_errors=False)
            ex = getattr(self, "_ckpt_executor", None)
            if ex is not None:
                # no lingering non-daemon worker thread per optimizer
                ex.shutdown(wait=True)
                self._ckpt_executor = None
            if self._h2d_waiter is not None:
                self._h2d_waiter.close()
                self._h2d_waiter = None
            self._staging.close()
            self._staging = None
            # export the observability artifacts LAST so the snapshot
            # sees the final counter values (incl. any failure recorded
            # by the flush above); off = no-op
            if obs.active():
                obs.flush(extra_registries=[self.metrics.registry])

    def _optimize_loop(self, model, pvar, mod_state, opt, opt_state,
                       train_step, base_key, wall_start, records_total,
                       stop, profiler):
        import jax

        from bigdl_tpu import obs
        from bigdl_tpu.config import config
        from bigdl_tpu.resilience.retry import NonFiniteStepError

        max_nonfinite = config.max_nonfinite_skips
        # double-buffered host->device input (ISSUE 11): batch N+1 is
        # fetched, prepared and device_put right after step N
        # dispatches, so the whole input pipeline overlaps the in-
        # flight device step instead of stalling the loop top (the
        # input_bound badput the goodput ledger measures).  Chaos runs
        # keep the foreground path: the injector poisons host batches
        # at dispatch time, before the transfer.
        double_buffer = (config.input_double_buffer
                         and self._fault_injector is None)
        # session-local obs handles (set up by optimize()): tracer is the
        # shared no-op when disabled, runtime None — zero hot-loop cost
        tracer = self._obs_tracer
        runtime = self._obs_runtime
        monitor = self._health_monitor
        ledger = self._obs_ledger
        # step-advance stamp for /healthz + the supervisor hang
        # watchdog: one tuple rebind per resolved step, and only when
        # the live endpoint exists — the disabled path stays a None
        # check (the exact off-path the noop pin asserts)
        if getattr(self, "_obs_server", None) is not None:
            from bigdl_tpu.obs.server import note_step
        else:
            note_step = None
        # streaming datasets (dataset/stream.py): advance the trained
        # stream frontier once per dispatched batch, so the offset a
        # checkpoint carries covers exactly the batches in the weights
        note_stream = getattr(self.dataset, "note_batch_trained", None)

        # Async-dispatch pipelining: the device loss is read back ONE
        # iteration behind, so the next step is dispatched before the
        # host blocks — the device always has a step queued and the
        # per-step host<->device sync round trip overlaps compute.
        # Loss-reading triggers (Trigger.min_loss) force the exact
        # per-step readback instead.
        # unknown user-supplied callables may read state["loss"], so
        # only triggers that DECLARE needs_loss=False may pipeline —
        # including a Parameters summary trigger, which is evaluated
        # per-iteration against the same state table
        _param_trig = (self.train_summary.get_summary_trigger("Parameters")
                       if self.train_summary is not None else None)
        sync_per_step = any(
            getattr(t, "needs_loss", True)
            for t in (self.end_when, self.validation_trigger,
                      self.checkpoint_trigger, _param_trig)
            if t is not None
        )
        pending = []  # [(n, loss_dev, ok_dev, batch_size, t_dispatch,
        #                 health_dev_or_None, host_batch)]
        # host batches are lent by the ring (native.StagingRing): each
        # goes back when its step's loss has been read, or when no step
        # will see it; until then neither the copy to the chips nor the
        # step has provably finished reading the host array
        ring = self._staging

        def resolve(n, loss_dev, ok_dev, bs, t0, health_dev=None,
                    host_batch=None):
            # the loop's thread waits for the chip here and nowhere else
            with tracer.span("loss_readback", step=n):
                loss_val = float(loss_dev)
                ok_val = bool(ok_dev)
            ring.release(host_batch)
            # in pipelined steady state this spans dispatch -> observed
            # completion (~ device step time + one iteration's host work)
            dt = time.perf_counter() - t0
            self.metrics.add("computing time", dt)
            fp = self._collective_footprint
            if fp is not None:
                # one executed step's static collective bytes -> the
                # bigdl_collective_bytes_total counters (host dict math,
                # children pre-bound at step build)
                fp.commit()
            if runtime is not None:
                # feeds the step-time p50/p95/p99 reservoir; the span is
                # retroactive (complete) because under pipelining this
                # resolves one iteration after its dispatch
                runtime.record_step(dt)
                tracer.complete("computing", t0, dt, step=n)
            # goodput: one productive-step interval (re-tagged rework
            # by the ledger when n is under the resume high-water mark)
            ledger.record("step", t0, dt, step=n)
            if note_step is not None:
                note_step(n)
            self.state["loss"] = loss_val
            if monitor is not None:
                # fetches the (L, 4) health array only every K steps —
                # or unconditionally when the guard tripped, because
                # localization IS the point of that fetch.  Runs before
                # the skip-escalation below so a NonFiniteStepError
                # never races the layer attribution out of the trace.
                monitor.on_step(n, health_dev, ok_val, loss_val)
            if self.train_summary is not None:
                self.train_summary.add_scalar("Loss", loss_val, n)
                self.train_summary.add_scalar(
                    "Throughput",
                    bs / max(1e-9, time.perf_counter() - t0), n)
            if not ok_val:
                # non-finite grads/loss: the guarded step already passed
                # weights/opt-state through unchanged — count the skip,
                # escalate after max_nonfinite consecutive ones
                self.state["nonfinite_skips"] += 1
                self._nonfinite_consec += 1
                log.warning(
                    "non-finite grads/loss at iter %d (loss=%r) — update "
                    "skipped (%d consecutive, %d total)", n, loss_val,
                    self._nonfinite_consec, self.state["nonfinite_skips"])
                self._summary_resilience(
                    n, nonfinite_skips=self.state["nonfinite_skips"])
                # structured resilience telemetry: an instant trace
                # event per skip (not only the cumulative counter)
                tracer.event("resilience.nonfinite_skip", step=n,
                             loss=loss_val,
                             consecutive=self._nonfinite_consec,
                             total=self.state["nonfinite_skips"])
                obs.get_registry().counter(
                    names.NONFINITE_SKIPS_TOTAL,
                    "Train steps skipped by the non-finite guard").inc()
                if self._nonfinite_consec >= max_nonfinite:
                    raise NonFiniteStepError(
                        f"{self._nonfinite_consec} consecutive non-finite "
                        f"training steps (iter {n}): diverged or poisoned "
                        "input — escalating to the retry policy")
            else:
                self._nonfinite_consec = 0
            if n % 20 == 0:
                log.info(
                    "Epoch %d iter %d loss %.5f (%.1f records/s)",
                    self.state["epoch"], n, loss_val,
                    records_total / max(1e-9, time.time() - wall_start),
                )
                log.debug("Metrics: %s", self.metrics.summary())

        def flush_pending():
            while pending:
                resolve(*pending.pop(0))

        h2d = self._h2d_waiter

        def put_batch(inp, tgt, step_tag):
            """``_put_batch`` under its timer and span; a traced run
            also hands the arrays to the waiter that closes
            ``feed.h2d`` when they are ready on the chips."""
            t_put = time.perf_counter() if h2d is not None else 0.0
            with self.metrics.timer("put batch time"), \
                    tracer.span("device_put", step=step_tag):
                put = self._put_batch(inp, tgt)
            if h2d is not None:
                h2d.watch(t_put, put, step_tag)
            return put

        while not stop:
            epoch = self.state["epoch"]
            epoch_start = time.time()
            # background host thread assembles the next minibatch while
            # the chip runs the current step (native.PrefetchIterator)
            from bigdl_tpu.native import PrefetchIterator

            batch_exhausted = False
            # mid-epoch resume (emergency / iteration-trigger
            # checkpoint): the saved neval is this many batches into the
            # epoch — consume them so the replayed data order matches
            # the uninterrupted run exactly (resilience/elastic.py)
            skip, self._pending_fast_forward = \
                self._pending_fast_forward, 0
            # first_step: the step the epoch's first batch trains, for
            # the worker's feed.gather spans
            data_into = getattr(self.dataset, "data_into", None)
            batches = iter(PrefetchIterator(
                self.dataset.data(train=True) if data_into is None
                else data_into(ring.gather, train=True),
                first_step=self.state["neval"] - skip, staging=ring))
            if skip > 0:
                log.info("mid-epoch resume: fast-forwarding %d batches "
                         "to iter %d", skip, self.state["neval"])
                tracer.event("elastic.fast_forward", batches=skip,
                             neval=self.state["neval"])
                for _ in range(skip):
                    try:
                        ring.release(next(batches)[0])
                    except StopIteration:
                        break
            # double-buffer slot: the prefetcher parks the next batch
            # (host arrays + device buffers) here while the current
            # step runs; a discarded staged batch (stop/preemption) is
            # harmless — streams re-read anything yielded-but-untrained,
            # and its host buffer, whose copy may still be under way, is
            # not given back: it goes with the ring
            staged = None
            staged_end = False

            def _prep_and_put(raw_inp, raw_tgt, step_tag):
                """One host batch through prepare + device transfer;
                None = dropped (its stream records are consumed)."""
                with tracer.span("batch_prep", step=step_tag):
                    prepared = self._prepare_batch(raw_inp, raw_tgt)
                if prepared is None:
                    ring.release(raw_inp)
                    if note_stream is not None:
                        log.warning("dropped a streaming batch at "
                                    "iter %d — its records are "
                                    "consumed, not trained", step_tag)
                        note_stream()
                    return None
                p_inp, p_tgt = prepared
                inp_d, tgt_d = put_batch(p_inp, p_tgt, step_tag)
                return p_inp, p_tgt, inp_d, tgt_d, raw_inp

            def _prefetch(step_tag):
                """Double-buffer: pull the NEXT batch through the full
                prepare + device_put pipeline while the just-dispatched
                step is still in flight — traced as ``input_prefetch``
                (overlapped host work), never ``data_wait`` badput."""
                nonlocal staged_end
                t_pre = time.perf_counter()
                out = None
                while out is None:
                    try:
                        raw_inp, raw_tgt = next(batches)
                    except StopIteration:
                        staged_end = True
                        break
                    out = _prep_and_put(raw_inp, raw_tgt, step_tag)
                tracer.complete("input_prefetch", t_pre,
                                time.perf_counter() - t_pre,
                                step=step_tag)
                return out

            while True:
                # reference Metrics phases: the fused XLA step folds the
                # collective phases ("put gradient"/"aggregate"/"send
                # weights") into "computing time"; the host-side phases
                # stay separately visible (SURVEY.md §5 Tracing)
                n = self.state["neval"]
                batch = None
                if staged is not None:
                    # the double-buffered batch is already on device:
                    # the loop top pays ~0 input wait
                    batch, staged = staged, None
                    t_wait = time.perf_counter()
                    dt_wait = 0.0
                elif staged_end:
                    batch_exhausted = True
                    break
                else:
                    t_wait = time.perf_counter()
                    try:
                        inp, tgt = next(batches)
                    except StopIteration:
                        batch_exhausted = True
                        break
                    dt_wait = time.perf_counter() - t_wait
                self.metrics.add("data wait time", dt_wait)
                # elastic boundary: heartbeat + peer-liveness check (may
                # raise the classified-fatal PeerLostError BEFORE the
                # collective that would hang on a dead peer) and the
                # preemption flag a SIGTERM set — the in-flight step is
                # resolved, then emergency checkpoint + Preempted
                es = self._elastic_session
                if es is not None and es.on_iteration(n):
                    flush_pending()
                    self._elastic_shutdown(n, pvar, mod_state, opt_state)
                # trace phases mirror the reference Metrics names + the
                # named_scope phases of the jitted step; tracer is the
                # shared no-op object when observability is off
                tracer.complete("data_wait", t_wait, dt_wait, step=n)
                ledger.record("data_wait", t_wait, dt_wait, step=n)
                # child spans carry the step too: the stall watch's
                # ``obs.stall`` and the merged cross-host timeline both
                # key on it
                with tracer.span("iteration", step=n):
                    if batch is not None:
                        # double-buffered: prepared + transferred while
                        # the previous step was in flight
                        inp, tgt, inp_d, tgt_d, host_batch = batch
                    else:
                        host_batch = inp
                        with tracer.span("batch_prep", step=n):
                            prepared = self._prepare_batch(inp, tgt)
                        if prepared is None:
                            ring.release(host_batch)
                            if note_stream is not None:
                                # a dropped batch still consumed its
                                # stream records: advance the frontier so
                                # the meta queue stays aligned (and say so
                                # — dropping stream records is a
                                # configuration smell)
                                log.warning("dropped a streaming batch at "
                                            "iter %d — its records are "
                                            "consumed, not trained", n)
                                note_stream()
                            continue  # dropped (e.g. sub-mesh partial batch)
                        inp, tgt = prepared
                        if self._fault_injector is not None:
                            # chaos hook: may raise InjectedFault
                            # (transient) or poison this batch to exercise
                            # the non-finite guard
                            action = self._fault_injector.on_step(n)
                            if action == "nan_grad":
                                inp = self._fault_injector.poison_batch(inp)
                        inp_d, tgt_d = put_batch(inp, tgt, n)
                    profiler.step()
                    rng = jax.random.fold_in(base_key, n)
                    t0 = time.perf_counter()
                    # driver-side prep (batch_prep + device_put + rng
                    # fold) feeds the host_bound share of the window
                    # classifier; in pipelined steady state it overlaps
                    # device compute, so it is a share — not a cause
                    ledger.note_host_seconds(t0 - t_wait - dt_wait)
                    with tracer.span("step_dispatch", step=n):
                        out = train_step(
                            pvar, opt_state, mod_state, rng, inp_d, tgt_d
                        )
                    # health-enabled steps carry one extra output (the
                    # per-layer stats array); disabled steps keep the
                    # seed 5-tuple signature
                    pvar, opt_state, mod_state, loss, ok = out[:5]
                    health_dev = out[5] if monitor is not None else None
                    bs = np.asarray(inp).shape[0]
                    records_total += bs
                    if note_stream is not None:
                        note_stream()
                    if double_buffer and not staged_end:
                        # overlap the NEXT batch's fetch/prepare/
                        # device_put with the in-flight device step —
                        # this is the double-buffer: by the time the
                        # loop comes back around, the input is on device
                        staged = _prefetch(n + 1)
                    if sync_per_step:
                        resolve(n, loss, ok, bs, t0, health_dev, host_batch)
                    else:
                        # the step is dispatched; reading back the
                        # PREVIOUS loss now lets the device run two-deep
                        flush_pending()
                        pending.append((n, loss, ok, bs, t0, health_dev,
                                        host_batch))
                    if self.train_summary is not None:
                        # histograms stay on the synchronous path: pvar
                        # here IS step n's output and neval is still n,
                        # so the trigger timing and logged params match
                        # sync mode exactly (reference
                        # setSummaryTrigger("Parameters"))
                        ptrig = self.train_summary.get_summary_trigger(
                            "Parameters")
                        if ptrig is not None and ptrig(self.state):
                            self._write_param_histograms(pvar, n)
                    self.state["neval"] = n + 1
                    opt.state = opt_state
                    if self.validation_trigger is not None and \
                            self.validation_trigger(self.state):
                        flush_pending()
                        # device-resident params: no host weight copy per
                        # validation trigger (VERDICT r2 #3)
                        t_eval = time.perf_counter()
                        with tracer.span("validation", step=n):
                            self._run_validation(pvar, mod_state)
                        ledger.record("eval", t_eval,
                                      time.perf_counter() - t_eval,
                                      step=n)
                        model.training()
                    if self.checkpoint_trigger is not None and \
                            self.checkpoint_trigger(self.state):
                        flush_pending()
                        with tracer.span("checkpoint", step=n):
                            with self.metrics.timer("write back time"):
                                self._write_back(pvar, mod_state)
                            opt.state = opt_state
                            self._checkpoint()
                    if self.end_when(self.state):
                        stop = True
                        break
            flush_pending()
            if batch_exhausted and not stop:
                # epoch finished
                self.state["epoch_finished"] = epoch
                self.state["epoch"] = epoch + 1
                # the next epoch's first batch runs at the current neval
                # (mid-epoch-resume bookkeeping, checkpointed in extra)
                self.state["epoch_neval0"] = self.state["neval"]
                # in place: opt.state must stay the SAME dict object so a
                # Plateau lr_scale poke from the validation below is seen
                # by the next epoch's train_step
                opt_state["epoch"] = opt_state["epoch"] + 1.0
                opt.state = opt_state
                log.info(
                    "Epoch %d done in %.1fs", epoch, time.time() - epoch_start
                )
                # reference: per-phase Metrics averages logged every epoch
                # («bigdl»/optim/Metrics.scala; SURVEY.md §5 Tracing)
                log.info("Metrics: %s", self.metrics.summary())
                if self.validation_trigger is not None and self.validation_trigger(
                    self.state
                ):
                    t_eval = time.perf_counter()
                    with tracer.span("validation", epoch=epoch):
                        self._run_validation(pvar, mod_state)
                    ledger.record("eval", t_eval,
                                  time.perf_counter() - t_eval)
                    model.training()
                if self.checkpoint_trigger is not None and self.checkpoint_trigger(
                    self.state
                ):
                    with tracer.span("checkpoint", epoch=epoch):
                        self._write_back(pvar, mod_state)
                        opt.state = opt_state
                        self._checkpoint()
                if self.end_when(self.state):
                    stop = True
        flush_pending()
        self._write_back(pvar, mod_state)
        opt.state = opt_state
        self.model.evaluate()
        # normal completion: surface any background-checkpoint write
        # error to the caller instead of just logging it
        self._flush_checkpoints()
        return self.model

    def _write_back(self, pvar, mod_state):
        # copy: the next train_step donates pvar/mod_state buffers, and the
        # model must keep valid arrays (validation/checkpoint read them, and
        # the user may hold the model across an interrupted optimize())
        import jax

        jnp = _jnp()
        copy = lambda t: jax.tree.map(lambda a: jnp.array(a, copy=True), t)
        self.model.set_params(copy(pvar))
        self.model.set_state(copy(mod_state))

    def _write_param_histograms(self, pvar, step):
        """Per-layer weight histograms into the TrainSummary (reference:
        TrainSummary with the "Parameters" trigger set)."""
        import jax

        tree = self._params_tree(pvar)
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        for path, leaf in flat:
            tag = "/".join(
                str(getattr(k, "key", getattr(k, "idx", k))) for k in path
            )
            self.train_summary.add_histogram(tag, np.asarray(leaf), step)


def Optimizer(
    model=None,
    training_set=None,
    criterion=None,
    batch_size: int = 32,
    training_rdd=None,
    x=None,
    y=None,
    end_trigger=None,
    optim_method=None,
    distributed: Optional[bool] = None,
):
    """Factory (reference: Optimizer.apply dispatches Local vs Distri on
    the dataset type — SURVEY.md §3.2).  Here: a DistributedDataSet or a
    multi-device default mesh selects DistriOptimizer."""
    import jax

    from bigdl_tpu.dataset import DistributedDataSet, to_dataset

    data = training_set if training_set is not None else training_rdd
    if data is None and x is not None:
        data = (x, y)
    ds = to_dataset(data, batch_size)
    if distributed is None:
        distributed = isinstance(ds, DistributedDataSet) or len(jax.devices()) > 1
        if distributed and not isinstance(ds, DistributedDataSet):
            # auto-promotion on device count alone can surprise on dev
            # boxes with forced host devices — say so (the reference
            # dispatches on dataset type only)
            log.warning(
                "Optimizer: %d devices visible — auto-selecting "
                "DistriOptimizer; pass distributed=False (or a local "
                "dataset on one device) for LocalOptimizer",
                len(jax.devices()),
            )
    if distributed:
        from bigdl_tpu.optim.distri_optimizer import DistriOptimizer

        opt = DistriOptimizer(model, ds, criterion, batch_size)
    else:
        opt = LocalOptimizer(model, ds, criterion, batch_size)
    if optim_method is not None:
        opt.set_optim_method(optim_method)
    if end_trigger is not None:
        opt.set_end_when(end_trigger)
    return opt
