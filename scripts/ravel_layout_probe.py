#!/usr/bin/env python
"""What it costs to cut ResNet-50's leaves out of the flat ZeRO-1 vector
and to pack their gradients back (PR 51): the probe behind
``optim/distri_optimizer.py`` ``unpack_leaf`` / ``pack_leaf``.

    python scripts/ravel_layout_probe.py --compile-only         # no chip
    chiprun --chips 1 -- python scripts/ravel_layout_probe.py --chip
    python scripts/ravel_layout_probe.py --trace .bench_out/resnet50_distri_4chip/profile

``--compile-only`` is the chip-less recipe: libtpu compiles one chip's
train step (``build_resnet_imagenet(50)``, 128 x 3 x 224 x 224, bfloat16
compute over float32 masters, SGD with momentum, no collective) for a
described v5e and the script prints, per form of the step, the sum of the
compiler's ``estimated_cycles`` over the entry computation, the copies of
arrays of rank 3 or more and every operation over the whole flat vector.
The cycles RANK forms; they are no times.  One process at a time may hold
libtpu (``/tmp/libtpu_lockfile``); 40-130 s a form.

``--chip`` times, on one chip, the unpack with its cast and the pack of a
gradient tree alone (median of ``--calls``), today's ``ravel_pytree``
closure against the optimizer's own route and the candidates of ISSUE
51's step 3, and prints the definitions of the relayout operations each
compiled to.

``--trace DIR`` reads the profile a traced run of the benchmark left
(``benchmarks/run.py --trace 1``) and sums, inside ``jit_sharded_step``,
the time of every operation that is named as moving elements (a copy, a
reshape, a convert, a pad, a slice, a transpose, a concatenate: the
profiler names a fusion by its root) and has an operand or a result of
rank 3 or more with 2 to 127 elements behind its second dimension (a
kernel in ``out, in, kh, kw`` order) or ahead of its last two (the same
kernel taps-first): ISSUE 51's step 0.

Exit 2 from ``--chip`` unless the backend is a TPU.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: the six operations ISSUE 51 names from cell 3's ledger breakdown
NAMED = ("reshape.909", "reshape.913", "reshape.916",
         "convert_element_type.2461", "convert_element_type.2489",
         "convert_element_type.2510")
MOVES = re.compile(r"^(copy|reshape|transpose|pad|convert|bitcast|slice|"
                   r"concatenate|dynamic[-_]slice|dynamic[-_]update)")
SHAPE = re.compile(r"\b(?:bf16|f32|f16|s32|u32|s8|u8|pred)\[([0-9,]*)\]")


def small_tail(dims) -> bool:
    """A kernel as the flat vector orders it (few elements behind the
    second dimension) or as the unpack hands it on (taps first)."""
    if len(dims) < 3:
        return False
    tail = 1
    for d in dims[2:]:
        tail *= d
    head = 1
    for d in dims[:-2]:
        head *= d
    return 2 <= tail < 128 or 2 <= head < 128


def kernel_shaped(hlo_line: str) -> bool:
    for m in SHAPE.finditer(hlo_line):
        dims = [int(d) for d in m.group(1).split(",") if d]
        if small_tail(dims):
            return True
    return False


# --------------------------------------------------------------- --trace
def trace_sum(profile_dir: str, program: str, out_path: str | None):
    from benchmarks.lib import xplane

    files = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise SystemExit(f"no .xplane.pb under {profile_dir}")
    trace = xplane.load(files[-1])
    planes = [p for p in trace["planes"]
              if xplane.DEVICE_PLANE.match(p["name"])]
    chips = calls = 0
    table: dict = {}      # op name -> [seconds, definition]
    total = 0.0
    for plane in planes:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        mods = sorted((s, s + d) for nm, s, d in
                      lines.get(xplane.MODULES_LINE, [])
                      if xplane.program_name(nm) == program)
        if not mods:
            continue
        chips += 1
        calls += len(mods)
        i = 0
        for nm, s, d in sorted(lines.get(xplane.OPS_LINE, []),
                               key=lambda ev: ev[1]):
            while i < len(mods) and mods[i][1] <= s:
                i += 1
            if i == len(mods) or s < mods[i][0]:
                continue
            total += d
            rec = table.setdefault(xplane.op_name(nm), [0.0, nm])
            rec[0] += d
    if not chips:
        raise SystemExit(f"the trace holds no execution of {program}")
    steps = calls / chips
    per_step = lambda ns: ns * 1e-6 / calls
    print(f"{files[-1]}: {chips} chips, {steps:.0f} executions of {program} "
          f"a chip, {per_step(total):.3f} ms of operations a step")
    for name in NAMED:
        if name in table:
            print(f"named {name}: {per_step(table[name][0]):.3f} ms a step\n"
                  f"    {table[name][1]}")
    picked = {k: v for k, v in table.items() if kernel_shaped(v[1])}
    # stage 4's activations (128 x 2048 x 7 x 7) are kernel-shaped by
    # that test too, and so are the weight-gradient convolutions, which
    # the trace names ``fusion.N``; an operation that only MOVES
    # elements is named for it (the profiler names a fusion by its root)
    moves = {k: v for k, v in picked.items() if MOVES.match(k)}
    s_all = sum(v[0] for v in picked.values())
    s_moves = sum(v[0] for v in moves.values())
    print(f"operations over a kernel-shaped array: {len(picked)}, "
          f"{per_step(s_all):.3f} ms a step; of them named as moving "
          f"elements (copy, reshape, transpose, pad, convert, slice, "
          f"concatenate): {len(moves)}, {per_step(s_moves):.3f} ms a step")
    for k, v in sorted(picked.items(), key=lambda kv: -kv[1][0])[:40]:
        print(f"  {per_step(v[0]):7.3f} ms  {'move' if k in moves else '    '}"
              f"  {v[1][:260]}")
    print("the step's twenty longest operations:")
    for k, v in sorted(table.items(), key=lambda kv: -kv[1][0])[:20]:
        print(f"  {per_step(v[0]):7.3f} ms  {v[1][:200]}")
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w", encoding="utf-8") as fh:
            json.dump({"program": program, "chips": chips, "steps": steps,
                       "ops_ms_per_step": per_step(total),
                       "kernel_shaped_ms_per_step": per_step(s_all),
                       "kernel_shaped_moves_ms_per_step": per_step(s_moves),
                       "ops": {k: [per_step(v[0]), v[1]]
                               for k, v in table.items()}}, fh)
        print(f"wrote {out_path}")


# ------------------------------------------------------- the forms tried
def _ravel_closure(opt):
    """Today's form before PR 51 (form B): ``ravel_pytree``'s ``unravel``
    inside the differentiated function, gradient with respect to
    ``flat``, cast to the wire behind it."""
    import jax
    from jax.flatten_util import ravel_pytree

    _, unravel = ravel_pytree(opt.model.params())

    def grads(loss_fn, flat_p, rest, pack_dtype):
        with jax.named_scope("computing"):
            (_, aux), grad = jax.value_and_grad(
                lambda f, *r: loss_fn(unravel(f), *r), has_aux=True)(
                    flat_p, *rest)
        return aux, grad if pack_dtype is None else grad.astype(pack_dtype)

    return grads


def _through_unpack(opt):
    """Form C: the optimizer's own unpack, but differentiated THROUGH
    (gradient with respect to ``flat``)."""
    import jax

    def grads(loss_fn, flat_p, rest, pack_dtype):
        (_, aux), grad = jax.value_and_grad(
            lambda f, *r: loss_fn(opt._layout.unpack(f), *r),
            has_aux=True)(flat_p, *rest)
        return aux, grad if pack_dtype is None else grad.astype(pack_dtype)

    return grads


# a leaf's two functions, by candidate: (unpack_leaf(seg, shape),
# pack_leaf(leaf)); "own" is the optimizer's pair
def _own_route():
    from bigdl_tpu.optim import distri_optimizer as D

    return D.unpack_leaf, D.pack_leaf


def _barrier_route():
    """The own route with an ``optimization_barrier`` on each taps-first
    gradient before its transposition (the leaf MATERIALISED)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.optim import distri_optimizer as D

    def pack_leaf(leaf):
        shape = leaf.shape
        if not D.leaf_is_relaid(shape):
            return leaf.reshape(-1)
        r = len(shape)
        t = jnp.transpose(leaf, tuple(range(2, r)) + (0, 1)).reshape(
            -1, shape[0] * shape[1])
        return jax.lax.optimization_barrier(t).T.reshape(-1)

    return D.unpack_leaf, pack_leaf


def _deinterleave_route():
    """A relaid leaf read as rows of taps and de-interleaved by strided
    slices (no transposed matrix is asked for), the gradient interleaved
    back by a stack."""
    import jax.numpy as jnp

    from bigdl_tpu.optim import distri_optimizer as D

    def unpack_leaf(seg, shape):
        if not D.leaf_is_relaid(shape):
            return seg.reshape(shape)
        r = len(shape)
        rows = seg.reshape(shape[0] * shape[1], -1)
        t = jnp.stack([rows[:, k] for k in range(rows.shape[1])]).reshape(
            shape[2:] + shape[:2])
        return jnp.transpose(t, (r - 2, r - 1) + tuple(range(r - 2)))

    def pack_leaf(leaf):
        shape = leaf.shape
        if not D.leaf_is_relaid(shape):
            return leaf.reshape(-1)
        r = len(shape)
        t = jnp.transpose(leaf, tuple(range(2, r)) + (0, 1)).reshape(
            -1, shape[0] * shape[1])
        return jnp.stack([t[k] for k in range(t.shape[0])], axis=1
                         ).reshape(-1)

    return unpack_leaf, pack_leaf


ROUTES = {"own": _own_route, "barrier": _barrier_route,
          "deinterleave": _deinterleave_route}


def unpack_with(layout, unpack_leaf, flat):
    import jax

    return jax.tree.unflatten(layout.treedef, [
        unpack_leaf(jax.lax.slice_in_dim(flat, o, o + z), s)
        for s, o, z in zip(layout.shapes, layout.offsets, layout.sizes)])


def pack_with(pack_leaf, tree, dtype):
    import jax
    import jax.numpy as jnp

    return jnp.concatenate([pack_leaf(x.astype(dtype))
                            for x in jax.tree.leaves(tree)])


def _tree_form(route: str, cast_first: bool = True):
    """Form D with a candidate's leaf functions: the unpack outside the
    differentiated function, the gradient with respect to the tree, the
    pack in the wire's dtype (or, ``cast_first`` False, cast behind)."""
    def make(opt):
        import jax

        unpack_leaf, pack_leaf = ROUTES[route]()
        layout = opt._layout

        def grads(loss_fn, flat_p, rest, pack_dtype):
            p = unpack_with(layout, unpack_leaf, flat_p)
            (_, aux), gtree = jax.value_and_grad(loss_fn, has_aux=True)(
                p, *rest)
            if cast_first or pack_dtype is None:
                return aux, pack_with(pack_leaf, gtree,
                                      pack_dtype or layout.dtype)
            return aux, pack_with(pack_leaf, gtree, layout.dtype).astype(
                pack_dtype)

        return grads

    return make


#: name -> (what it is, how to build the step's gradient function from
#: the optimizer; None: the optimizer's own, as the tree has it)
FORMS = {
    "tree": ("A. cell 1's form: LocalOptimizer, parameters a tree", None),
    "ravel": ("B. ravel_pytree's closure, gradient w.r.t. flat "
              "(before PR 51)", _ravel_closure),
    "through": ("C. own unpack, differentiated through", _through_unpack),
    "own": ("D. DistriOptimizer as it is: own unpack, gradient w.r.t. the "
            "tree, own pack", None),
    "barrier": ("D with an optimization_barrier on each taps-first "
                "gradient before its transposition", _tree_form("barrier")),
    "cast_behind": ("D with the cast to the wire behind the pack",
                    _tree_form("own", cast_first=False)),
    "deinterleave": ("D with rows of taps de-interleaved by strided slices",
                     _tree_form("deinterleave")),
}


# ------------------------------------------------------ building the step
def build(form: str, devices, batch_a_chip: int, depth: int, image: int):
    """The jitted step of ``form`` for ``devices`` (described or real)
    and the shapes it takes; nothing is placed on a device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from bigdl_tpu.models import build_resnet_imagenet
    from bigdl_tpu.nn import ClassNLLCriterion
    from bigdl_tpu.optim import SGD, DistriOptimizer
    from bigdl_tpu.optim.optimizer import LocalOptimizer

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        model = build_resnet_imagenet(depth=depth, class_num=1000)
    n = len(devices)
    batch = batch_a_chip * n
    sgd = SGD(learningrate=0.1, momentum=0.9, dampening=0.0)
    sds = lambda a, sh: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                             sharding=sh)
    key = jax.eval_shape(lambda: jax.random.fold_in(jax.random.PRNGKey(0), 1))
    images = jax.ShapeDtypeStruct((batch, 3, image, image), jnp.float32)
    labels = jax.ShapeDtypeStruct((batch,), jnp.float32)
    if form == "tree":
        one = SingleDeviceSharding(devices[0])
        opt = LocalOptimizer(model, None, ClassNLLCriterion(), batch)
        opt.set_optim_method(sgd)
        opt.set_compute_dtype("bfloat16")
        with jax.default_device(cpu):
            params = model.params()
            state = jax.eval_shape(sgd.init_state, params)
        put = lambda t: jax.tree.map(lambda a: sds(a, one), t)
        return opt._build_train_step(), (
            put(params), put(state), put(model.state()), sds(key, one),
            sds(images, one), sds(labels, one)), opt
    mesh = Mesh(np.array(devices), ("data",))
    opt = DistriOptimizer(model, None, ClassNLLCriterion(), batch_size=batch,
                          mesh=mesh, wire_dtype="bfloat16")
    opt.set_optim_method(sgd)
    opt.set_compute_dtype("bfloat16")
    with jax.default_device(cpu):
        flat = opt._init_params()
        # _init_opt_state places its vectors on the mesh; a described
        # device holds nothing, and only shapes are wanted here
        real_put, jax.device_put = jax.device_put, lambda x, *a, **k: x
        try:
            opt_state = opt._init_opt_state(flat)
        finally:
            jax.device_put = real_put
    make = FORMS[form][1]
    if make is not None:
        opt._value_and_flat_grad = make(opt)
    step = opt._build_step_impl(masked=False)
    rep = NamedSharding(mesh, P())
    split = NamedSharding(mesh, P("data"))
    args = (sds(flat, rep),
            {k: sds(v, split if v.ndim == 1 else rep)
             for k, v in opt_state.items()},
            jax.tree.map(lambda a: sds(a, rep), model.state()),
            sds(key, rep), sds(images, split), sds(labels, split))
    return step, args, opt


# ---------------------------------------------------------- --compile-only
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\(")
CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')


def entry_instructions(hlo_text: str):
    """(name, result type, opcode, estimated cycles, line, holds a
    convolution) of the entry computation's instructions; a fusion holds
    one if the computation it calls does."""
    convolves, current = set(), None
    for line in hlo_text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            current = re.match(r"^(?:ENTRY )?%?([\w.\-]+)", line).group(1)
        elif current and " convolution(" in line:
            convolves.add(current)
    out, inside = [], False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            inside = True
            continue
        if inside and line.startswith("}"):
            break
        if not inside:
            continue
        m = INSTRUCTION.match(line)
        if not m:
            continue
        c = CYCLES.search(line)
        called = re.search(r"calls=%?([\w.\-]+)", line)
        out.append((m.group(1), m.group(2), m.group(3),
                    int(c.group(1)) if c else 0, line.strip(),
                    m.group(3) == "convolution"
                    or bool(called and called.group(1) in convolves)))
    return out


def describe(text: str, flat_elems: int, show: int):
    """What ``--compile-only`` prints of one compiled program."""
    ins = entry_instructions(text)
    total = sum(i[3] for i in ins)
    print(f"  sum of estimated_cycles over the entry computation: "
          f"{total / 1e6:.2f} M in {len(ins)} instructions")
    moves = [i for i in ins if not i[5] and kernel_shaped(i[4])
             and i[2] not in ("parameter", "get-tuple-element", "tuple",
                              "bitcast", "copy-start", "copy-done")]
    by_opcode: dict = {}
    for i in ins:
        key = "convolution" if i[5] else i[2]
        by_opcode[key] = by_opcode.get(key, 0) + i[3]
    print("  by opcode: " + ", ".join(
        f"{k} {v / 1e6:.2f} M" for k, v in
        sorted(by_opcode.items(), key=lambda kv: -kv[1])[:8]))
    print(f"  copies, reshapes and fusions over kernel-shaped arrays of "
          f"rank >= 3 that are no convolution: {len(moves)}, "
          f"{sum(i[3] for i in moves) / 1e6:.2f} M")
    for i in sorted(moves, key=lambda i: -i[3])[:show]:
        print(f"    {i[3] / 1e6:6.2f} M  {i[4][:230]}")
    whole = [i for i in ins if re.search(
        rf"\[({flat_elems}|{flat_elems + (-flat_elems) % 4})\]", i[4])
        and i[2] not in ("parameter", "get-tuple-element", "tuple",
                         "bitcast")]
    print(f"  operations over the whole flat vector: {len(whole)}, "
          f"{sum(i[3] for i in whole) / 1e6:.2f} M")
    for i in sorted(whole, key=lambda i: -i[3])[:show]:
        print(f"    {i[3] / 1e6:6.2f} M  {i[4][:230]}")
    print("  longest:")
    for i in sorted(ins, key=lambda i: -i[3])[:show]:
        print(f"    {i[3] / 1e6:6.2f} M  {i[4][:200]}")
    return total


def compile_only(args) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sums = {}
    for form in args.forms:
        chips = 1 if form == "tree" else args.chips
        step, shapes, opt = build(form, list(topo.devices[:chips]),
                                  args.batch, args.depth, args.image)
        t0 = time.perf_counter()
        compiled = step.lower(*shapes).compile()
        text = compiled.as_text()
        print(f"{form}: {FORMS[form][0]}; {chips} described chip(s), "
              f"compiled in {time.perf_counter() - t0:.0f} s", flush=True)
        flat_elems = getattr(opt, "_flat_elems", 0)
        sums[form] = describe(text, flat_elems, args.show)
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump, f"{form}_{chips}chip.hlo.txt"),
                      "w", encoding="utf-8") as fh:
                fh.write(text)
    print("sums of estimated_cycles (they rank, they do not time): "
          + ", ".join(f"{k} {v / 1e6:.1f} M" for k, v in sums.items()))
    return 0


# ------------------------------------------------------------------ --chip
def _time_calls(fn, arg, calls):
    """Seconds of ``calls`` warm calls of ``jit(fn)(arg)``, each waited
    for, and the compiled program's text."""
    import jax

    jitted = jax.jit(fn)
    text = jitted.lower(arg).compile().as_text()
    jax.block_until_ready(jitted(arg))
    times = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(arg))
        times.append(time.perf_counter() - t0)
    return times, text


def chip(args) -> int:
    """Unpack + cast and the pack alone, each form, on one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    print(f"platform {dev.platform}, kind {dev.device_kind}, "
          f"{len(jax.devices())} device(s)", flush=True)
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print("ravel_layout_probe --chip needs a TPU", file=sys.stderr)
        return 2
    from jax.flatten_util import ravel_pytree

    from bigdl_tpu.models import build_resnet_imagenet
    from bigdl_tpu.optim import distri_optimizer as D

    model = build_resnet_imagenet(depth=args.depth, class_num=1000)
    params = model.params()
    layout = D.FlatLayout(params)
    print(f"layout {layout.said()}", flush=True)
    flat0, unravel = ravel_pytree(params)
    flat = jax.random.normal(jax.random.PRNGKey(args.seed), flat0.shape,
                             jnp.float32)
    bf = jnp.bfloat16
    # a consumer that reads every kernel the way a convolution does (so
    # that the unpack has to deliver the chip's layout) and every other
    # leaf once
    image = jnp.ones((8, 1, 1, 1), bf)

    def use(tree):
        total = jnp.float32(0)
        for x in jax.tree.leaves(tree):
            if x.ndim == 4 and x.shape[2] * x.shape[3] > 1:
                inp = jnp.broadcast_to(image, (8, x.shape[1], 8, 8))
                y = jax.lax.conv_general_dilated(
                    inp, x, (1, 1), "SAME",
                    dimension_numbers=("NCHW", "OIHW", "NCHW"))
                total = total + jnp.sum(y.astype(jnp.float32))
            else:
                total = total + jnp.sum(x.astype(jnp.float32))
        return total

    cast = lambda t: jax.tree.map(lambda a: a.astype(bf), t)
    # a kernel's gradient leaves its convolution taps-first, so the
    # packs start from taps-first arrays behind a logical transpose
    def taps_first(x):
        if not D.leaf_is_relaid(x.shape):
            return x
        return jnp.transpose(x, tuple(range(2, x.ndim)) + (0, 1))

    def as_leaves(tree):
        return jax.tree.map(
            lambda x, shape: x if x.shape == shape else jnp.transpose(
                x, (x.ndim - 2, x.ndim - 1) + tuple(range(x.ndim - 2))),
            tree, jax.tree.unflatten(layout.treedef, layout.shapes))

    unpacks = {"ravel": lambda f: use(cast(unravel(f)))}
    packs = {"ravel": lambda t: ravel_pytree(jax.tree.map(
        lambda a: a.astype(jnp.float32), as_leaves(t)))[0].astype(bf)}
    for name, route in ROUTES.items():
        unpack_leaf, pack_leaf = route()
        unpacks[name] = lambda f, u=unpack_leaf: use(
            cast(unpack_with(layout, u, f)))
        packs[name] = lambda t, p=pack_leaf: pack_with(p, as_leaves(t), bf)
    gtree = jax.jit(lambda f: jax.tree.map(taps_first, cast(unravel(f))))(
        flat)
    results = {}
    for kind, table, arg in (("unpack+cast", unpacks, flat),
                             ("pack", packs, gtree)):
        for name, fn in table.items():
            times, text = _time_calls(fn, arg, args.calls)
            ms = 1e3 * statistics.median(times)
            results[f"{kind}.{name}"] = ms
            print(f"{kind:12s} {name:8s} median of {args.calls}: "
                  f"{ms:8.3f} ms (least {1e3 * min(times):.3f})", flush=True)
            if dev.platform == "tpu":
                ins = entry_instructions(text)
                for i in sorted((i for i in ins if kernel_shaped(i[4])
                                 and not i[5]),
                                key=lambda i: -i[3])[:6]:
                    print(f"      {i[3] / 1e6:6.2f} M  {i[4][:200]}")
    a = np.asarray(jax.jit(packs["own"])(gtree))
    b = np.asarray(jax.jit(packs["ravel"])(gtree))
    print(f"own pack equals ravel's to the last bit: "
          f"{bool((a.view(np.uint16) == b.view(np.uint16)).all())}")
    out = os.path.join(ROOT, "chiprun_out", "ravel_layout")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "chip.json"), "w", encoding="utf-8") as fh:
        json.dump({"device": dev.device_kind, "ms": results,
                   "layout": layout.said()}, fh)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--compile-only", action="store_true")
    mode.add_argument("--chip", action="store_true")
    mode.add_argument("--trace", metavar="DIR")
    ap.add_argument("--forms", nargs="+", default=["tree", "ravel", "own"],
                    choices=sorted(FORMS))
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="--compile-only: described chips the sharded "
                         "forms are compiled for (4: with the collectives)")
    ap.add_argument("--batch", type=int, default=128, help="a chip")
    ap.add_argument("--depth", type=int, default=50)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--show", type=int, default=8)
    ap.add_argument("--dump", default=None,
                    help="--compile-only: write each program's text here")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--program", default="jit_sharded_step")
    ap.add_argument("--out", default=None,
                    help="--trace: also write every operation's time and "
                         "definition here (JSON)")
    args = ap.parse_args(argv)
    if args.trace:
        trace_sum(args.trace, args.program, args.out)
        return 0
    if args.compile_only:
        return compile_only(args)
    return chip(args)


if __name__ == "__main__":
    sys.exit(main())
