"""Driver for configurations of ``"kind": "train"``: a model trained by
``Optimizer(...).optimize()`` (one chip) or ``DistriOptimizer`` on the
mix's mesh, fed from host arrays through the normal ``DataSet`` path.

``optimize()`` is one call, so the window lives inside it.  The
benchmark hands the optimizer two objects of its own: a train summary
that stamps every step's loss as it arrives, and an end trigger that
opens the window after the mix's ``warm_steps`` losses, lets it run for
``--seconds`` and then ends the run.  The same compiled step with the
same state is driven from the seed through its first steps and on
through the window.  During the first three steps the two objects also
keep what the reference is compared with: each loss, the velocity after
one step (with dampening 0 it is the gradient as the optimizer got it),
and the parameters after three (through the summary's "Parameters"
trigger, the path a user's weight histograms take).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.lib import harness, traffic

FOLLOWED_STEPS = 3
STEP_FUNCTIONS = ("train_step", "sharded_step")


class Recorder:
    """Train summary, end trigger and "Parameters" trigger in one.  The
    optimizer calls ``add_scalar`` when a loss arrives on the host,
    ``parameters_trigger`` and ``end_trigger`` once per dispatched step."""

    def __init__(self, optimizer, warm_steps, seconds, mark_open, profile):
        self.opt = optimizer
        self.warm_steps = int(warm_steps)
        self.seconds = float(seconds)
        self.mark_open = mark_open
        self.profile = profile
        self.losses: list = []
        self.stamps: list = []
        self.t_open = self.wall_open = None
        self.velocity_1 = None
        self.params_3: dict = {}
        self._dispatched = 0
        self._param_calls = 0
        outer = self

        class _End:
            needs_loss = False

            def __call__(self, state):
                return outer._after_dispatch()

        class _Params:
            needs_loss = False

            def __call__(self, state):
                outer._param_calls += 1
                return outer._param_calls == FOLLOWED_STEPS

        self.end_trigger = _End()
        self._params_trigger = _Params()

    # ---- what the optimizer's loop calls on its train summary
    def add_scalar(self, tag, value, step):
        if tag != "Loss":
            return
        now = time.perf_counter()
        self.losses.append(float(value))
        self.stamps.append(now)
        if len(self.losses) == self.warm_steps and self.t_open is None:
            self.t_open, self.wall_open = now, time.time()
            self.mark_open(now)
            self.profile.arm(now, self.seconds)

    def get_summary_trigger(self, name):
        return self._params_trigger if name == "Parameters" else None

    def add_histogram(self, tag, values, step):
        self.params_3[tag] = np.array(values, np.float32)

    def close(self):
        pass

    # ---- the end trigger
    def _after_dispatch(self) -> bool:
        self._dispatched += 1
        if self._dispatched == 1:
            vel = self.opt.optim_method.state["velocity"]
            import jax

            self.velocity_1 = jax.tree.map(np.asarray, vel)
        if self.t_open is None:
            return False
        now = time.perf_counter()
        if self.profile.enabled:
            self.profile.poll(now)
        return now >= self.t_open + self.seconds


def _as_ref_leaves(tree_or_flat, template_tree, ref, config):
    """The program's parameters-shaped value (a tree, or DistriOptimizer's
    flat vector, padded) as the reference's dict of leaves."""
    import jax
    from jax.flatten_util import ravel_pytree

    if isinstance(tree_or_flat, dict):
        tree = tree_or_flat
    else:
        flat0, unravel = ravel_pytree(template_tree)
        tree = unravel(np.asarray(tree_or_flat)[:flat0.size])
    tree = jax.tree.map(np.asarray, tree)
    return ref.from_program_tree(tree, config)


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.dataset.dataset import ArrayDataSet
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.models import build_resnet_imagenet
    from bigdl_tpu.nn import ClassNLLCriterion
    from bigdl_tpu.optim import SGD, DistriOptimizer, Optimizer

    config, mix = ctx["config"], ctx["traffic"]
    seed, seconds, devices = ctx["seed"], ctx["seconds"], ctx["devices"]
    ref = harness.reference_for(config)
    check = harness.Check()
    compiles = ctx["compiles"]

    batch = int(mix["batch"])
    x, y = traffic.train_data(mix, seed, int(config["image_size"]),
                              int(config["num_classes"]))
    params0 = ref.init_params(seed, config)
    model = build_resnet_imagenet(depth=int(config["depth"]),
                                  class_num=int(config["num_classes"]))
    tree0 = ref.to_program_tree(params0, config)
    model.set_params(jax.tree.map(jnp.asarray, tree0))
    dataset = ArrayDataSet(x, y, batch, shuffle=False)
    shards = 1
    if mix.get("mesh"):
        mesh = Engine.build_mesh(dict(mix["mesh"]), devices=list(devices))
        shards = int(np.prod(list(mix["mesh"].values())))
        opt = DistriOptimizer(model, dataset, ClassNLLCriterion(),
                              batch_size=batch, mesh=mesh)
    else:
        opt = Optimizer(model, dataset, ClassNLLCriterion(),
                        batch_size=batch, distributed=False)
    hp = config["optimizer"]
    opt.set_optim_method(SGD(learningrate=hp["learning_rate"],
                             momentum=hp["momentum"], dampening=0.0))
    opt.set_compute_dtype(config["assumed"]["compute_dtype"])
    profile = harness.Profile(ctx["out_dir"], ctx["trace"])
    rec = Recorder(opt, mix["warm_steps"], seconds, ctx["mark_open"],
                   profile)
    opt.set_train_summary(rec)
    opt.set_end_when(rec.end_trigger)
    try:
        trained = opt.optimize()
    finally:
        profile.stop()
    mem_peak = harness.memory_peak_bytes(devices)
    t_open, t_close = rec.t_open, rec.t_open + seconds
    wall_close = rec.wall_open + seconds
    spans = harness.program_spans(rec.wall_open, wall_close)
    in_window = compiles.between(t_open, t_close)
    for c in in_window:
        print(f"compiled inside the window: {c[1]} ({c[2]:.2f}s)", flush=True)
    step_compiles = [c for c in compiles.compiles
                     if any(f in c[1] for f in STEP_FUNCTIONS)]

    inside = [(s, v) for s, v in zip(rec.stamps, rec.losses)
              if t_open < s <= t_close]
    steps = len(inside)
    bad = sum(1 for _, v in inside if not np.isfinite(v))
    print(f"window {seconds:.3f}s: {steps} steps' losses arrived, "
          f"{bad} not finite; first {rec.losses[:FOLLOWED_STEPS]}, last "
          f"{rec.losses[-1]!r}", flush=True)
    e2e = {"train_samples_per_s": steps * batch / seconds}

    # on several chips the trained parameters are one value everywhere
    unequal = 0
    if shards > 1:
        for leaf in jax.tree.leaves(trained.params()):
            datas = [np.asarray(s.data) for s in leaf.addressable_shards]
            unequal += sum(1 for d in datas[1:]
                           if not np.array_equal(d, datas[0]))
        check.equal("parameters_unequal_across_chips", unequal, 0)
        check.equal("chips_holding_parameters",
                    len(jax.tree.leaves(trained.params())[0]
                        .addressable_shards), len(devices))

    # ---- the reference follows the first three steps
    t_ref = time.perf_counter()
    got_grad = _as_ref_leaves(rec.velocity_1, tree0, ref, config)
    paths = ref.program_paths(config)
    p3 = {name: rec.params_3["/".join(path)] for name, path in paths.items()}
    got_delta = {k: p3[k] - params0[k] for k in params0}
    del trained, opt, model
    batches = [(x[i * batch:(i + 1) * batch], y[i * batch:(i + 1) * batch])
               for i in range(FOLLOWED_STEPS)]
    want = ref.follow(params0, batches, config, hp["learning_rate"],
                      hp["momentum"], shards=shards)
    limits = config["limits"]
    loss_gap = max(abs(a - b) for a, b in
                   zip(rec.losses[:FOLLOWED_STEPS], want["losses"]))
    ggaps = ref.norm_gaps(got_grad, want["first_gradient"])
    dgaps = ref.norm_gaps(got_delta, want["parameter_change"])
    worst_g = max(ggaps, key=ggaps.get)
    worst_d = max(dgaps, key=dgaps.get)
    print(f"reference: losses {want['losses']}; worst first-gradient leaf "
          f"{worst_g}, worst parameter-change leaf {worst_d}; took "
          f"{time.perf_counter() - t_ref:.1f}s", flush=True)
    gdiff = ref.difference(got_grad, want["first_gradient"])
    readings = {"program": {
        "loss_gap": loss_gap, "first_gradient_norm_gap": ggaps[worst_g],
        "parameter_change_norm_gap": dgaps[worst_d],
        "first_gradient_difference": gdiff}}
    if ctx.get("control"):
        # tools/readings.py: the reference in fp8 in the program's place
        ctl = ref.follow(params0, batches, config, hp["learning_rate"],
                         hp["momentum"], shards=shards, precision="fp8")
        readings["control"] = {
            "loss_gap": max(abs(a - b) for a, b in
                            zip(ctl["losses"], want["losses"])),
            "first_gradient_norm_gap": max(ref.norm_gaps(
                ctl["first_gradient"], want["first_gradient"]).values()),
            "parameter_change_norm_gap": max(ref.norm_gaps(
                ctl["parameter_change"], want["parameter_change"]).values()),
            "first_gradient_difference": ref.difference(
                ctl["first_gradient"], want["first_gradient"])}
    check.equal("losses_not_finite", bad, 0)
    check.at_least("loss_range_in_window",
                   max(v for _, v in inside) - min(v for _, v in inside)
                   if inside else 0.0, 1e-4)
    check.equal("compiles_of_the_step", len(step_compiles), 1)
    check.equal("compiles_inside_window", len(in_window), 0)
    check.at_most("loss_gap", loss_gap, limits["loss_gap_max"])
    check.at_most("first_gradient_norm_gap", ggaps[worst_g],
                  limits["first_gradient_norm_gap_max"])
    check.at_most("parameter_change_norm_gap", dgaps[worst_d],
                  limits["parameter_change_norm_gap_max"])
    check.at_most("first_gradient_difference", gdiff,
                  limits["first_gradient_difference_max"])

    counters = {
        "window_compiles": len(in_window),
        "loss_stamps": [s for s, _ in inside],
        "steps": steps,
        "cache_hits": compiles.cache_hits,
        "cache_misses": compiles.cache_misses,
    }
    return {
        "check": check, "attempted": steps, "failed": bad, "e2e": e2e,
        "memory_peak_bytes": mem_peak, "window_s": seconds, "spans": spans,
        "counters": counters, "profile": profile, "readings": readings,
    }
