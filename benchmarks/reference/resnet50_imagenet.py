"""Plain reference for the ``resnet50_imagenet`` configuration: the
bottleneck ResNet-50 of He et al. 2015 (Table 1), training mode, as
straightforward ``jax.numpy`` / ``lax`` in float32 with precision
``highest``, SGD with momentum beside it.  It imports nothing of the
program.

Departures from the paper, all the program's (configuration file,
``assumed``): the stride of a stage's first block sits on its 3x3
convolution; the last batch norm of every block starts at gamma 0.
Batch norm is the two-pass form (biased variance, eps 1e-5) over the
rows it is given; across chips the program normalizes each chip's rows
by themselves, so ``follow`` is given the number of shards and does the
same.  Each block is recomputed in the backward pass
(``jax.checkpoint``) so that a float32 batch of 128 fits beside nothing.

Parameters live in a flat dict, ``"<unit>.<leaf>"`` -> array:
``stem.w``, ``stem_bn.scale``, ``s<stage>b<block>.c1.w`` ...
``.bn3.bias``, ``.sc.w``, ``.scbn.scale``, ``fc.w`` (classes, 2048),
``fc.b``.  ``program_paths`` maps them onto the nested ``Sequential``
indices the program's ``build_resnet_imagenet`` gives its parameters.

``precision="fp8"`` is the control of "How correct is decided": what
the program holds in bfloat16 (every convolution's and the classifier's
inputs and weights, and the activations between operations) is rounded
to float8 (e4m3) in the forward pass, the nearest precision below the
configuration's bfloat16 compute.
"""

from __future__ import annotations

import functools

import numpy as np

BN_EPS = 1e-5


def stages_of(config: dict):
    return list(zip(config["stage_widths"], config["stage_blocks"],
                    [1, 2, 2, 2]))


def _units(config: dict):
    """(name, kind, shape) of every parameter, in a fixed order."""
    exp = int(config["bottleneck_expansion"])
    out = [("stem.w", "conv", (64, 3, 7, 7)), ("stem_bn", "bn", 64)]
    cin = 64
    for s, (width, blocks, _) in enumerate(stages_of(config)):
        for b in range(blocks):
            u = f"s{s}b{b}"
            out += [(u + ".c1.w", "conv", (width, cin, 1, 1)),
                    (u + ".bn1", "bn", width),
                    (u + ".c2.w", "conv", (width, width, 3, 3)),
                    (u + ".bn2", "bn", width),
                    (u + ".c3.w", "conv", (width * exp, width, 1, 1)),
                    (u + ".bn3", "bn0", width * exp)]
            if b == 0:
                out += [(u + ".sc.w", "conv", (width * exp, cin, 1, 1)),
                        (u + ".scbn", "bn", width * exp)]
            cin = width * exp
    out += [("fc", "fc", (int(config["num_classes"]), cin))]
    return out


def init_params(seed: int, config: dict) -> dict:
    """Weights from the seed, on the host (25.6 M numbers): He-normal
    convolutions, batch norms at 1 / 0 (the last of a block at 0 / 0),
    classifier N(0, 1/fan_in) with a small random bias."""
    rng = np.random.default_rng([int(seed), 7])
    p = {}
    for name, kind, shape in _units(config):
        if kind == "conv":
            fan = shape[1] * shape[2] * shape[3]
            p[name] = (rng.standard_normal(shape, dtype=np.float32)
                       * np.float32(np.sqrt(2.0 / fan)))
        elif kind in ("bn", "bn0"):
            p[name + ".scale"] = np.full((shape,), 1.0 if kind == "bn"
                                         else 0.0, np.float32)
            p[name + ".bias"] = np.zeros((shape,), np.float32)
        else:
            p[name + ".w"] = (rng.standard_normal(shape, dtype=np.float32)
                              * np.float32(np.sqrt(1.0 / shape[1])))
            p[name + ".b"] = (rng.standard_normal((shape[0],),
                                                  dtype=np.float32)
                              * np.float32(0.01))
    return p


def program_paths(config: dict) -> dict:
    """reference leaf name -> path of string keys in the program's
    parameter tree (``Sequential`` children are numbered as added)."""
    paths = {"stem.w": ("0", "weight"),
             "stem_bn.scale": ("1", "weight"), "stem_bn.bias": ("1", "bias")}
    idx = 4  # conv, bn, relu, max pool come first
    for s, (_, blocks, _) in enumerate(stages_of(config)):
        for b in range(blocks):
            u, main = f"s{s}b{b}", (str(idx), "0", "0")
            for conv, bn, at in (("c1", "bn1", 0), ("c2", "bn2", 3),
                                 ("c3", "bn3", 6)):
                paths[f"{u}.{conv}.w"] = main + (str(at), "weight")
                paths[f"{u}.{bn}.scale"] = main + (str(at + 1), "weight")
                paths[f"{u}.{bn}.bias"] = main + (str(at + 1), "bias")
            if b == 0:
                short = (str(idx), "0", "1")
                paths[f"{u}.sc.w"] = short + ("0", "weight")
                paths[f"{u}.scbn.scale"] = short + ("1", "weight")
                paths[f"{u}.scbn.bias"] = short + ("1", "bias")
            idx += 1
    fc = str(idx + 2)  # average pool, reshape, then the classifier
    paths["fc.w"] = (fc, "weight")
    paths["fc.b"] = (fc, "bias")
    return paths


def to_program_tree(params: dict, config: dict) -> dict:
    tree: dict = {}
    for name, path in program_paths(config).items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = params[name]
    return tree


def from_program_tree(tree: dict, config: dict) -> dict:
    out = {}
    for name, path in program_paths(config).items():
        node = tree
        for key in path:
            node = node[key]
        out[name] = node
    return out


def _round(a, precision):
    """The operand as the precision holds it.  The rounding is passed
    straight through in the backward pass: unscaled float8 would flush
    most cotangents to zero, which no fp8 recipe does."""
    import jax

    if precision == "fp8":
        # reduce_precision is an operation of its own: a pair of
        # converts the compiler may drop (xla_allow_excess_precision)
        rounded = jax.lax.reduce_precision(a, exponent_bits=4,
                                           mantissa_bits=3)
        return a + jax.lax.stop_gradient(rounded - a)
    return a


def _conv(x, w, stride, pad, precision):
    from jax import lax

    return lax.conv_general_dilated(
        _round(x, precision), _round(w, precision), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)


def _relu(x):
    """max(x, 0).  JAX differentiates the maximum as 1/2 at a tie; with
    the last gamma of a block at 0, half of a block's pre-activations
    are exactly 0 at the start, so the convention at 0 is part of the
    semantics (``jax.nn.relu`` takes 0 there and gives other first
    gradients)."""
    import jax.numpy as jnp

    return jnp.maximum(x, 0.0)


def _bn(x, scale, bias):
    import jax
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + BN_EPS)
            * scale[None, :, None, None] + bias[None, :, None, None])


def loss(params: dict, x, labels, config: dict, precision: str = "float32"):
    """Mean negative log-likelihood of 0-based ``labels`` under the
    network in training mode, over the rows given."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    p = params

    def keep(t):
        # an activation as the precision holds it between two operations
        return _round(t, precision)

    def bn(h, name):
        return keep(_bn(h, p[name + ".scale"], p[name + ".bias"]))

    def block(h, u, stride, first):
        y = _relu(bn(_conv(h, p[u + ".c1.w"], 1, 0, precision), u + ".bn1"))
        y = _relu(bn(_conv(y, p[u + ".c2.w"], stride, 1, precision),
                     u + ".bn2"))
        y = bn(_conv(y, p[u + ".c3.w"], 1, 0, precision), u + ".bn3")
        if first:
            h = bn(_conv(h, p[u + ".sc.w"], stride, 0, precision),
                   u + ".scbn")
        return keep(_relu(y + h))

    h = _relu(bn(_conv(x, p["stem.w"], 2, 3, precision), "stem_bn"))
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for s, (_, blocks, stride) in enumerate(stages_of(config)):
        for b in range(blocks):
            h = jax.checkpoint(
                functools.partial(block, u=f"s{s}b{b}",
                                  stride=stride if b == 0 else 1,
                                  first=b == 0))(h)
    h = jnp.mean(h, axis=(2, 3))
    logits = jnp.matmul(_round(h, precision), _round(p["fc.w"], precision).T,
                        precision="highest") + p["fc.b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


@functools.lru_cache(maxsize=None)
def _grad_fn(config_key: str, precision: str):
    import json

    import jax

    config = json.loads(config_key)
    return jax.jit(jax.value_and_grad(
        lambda p, x, y: loss(p, x, y, config, precision)))


def follow(params0: dict, batches, config: dict, lr: float, momentum: float,
           shards: int = 1, precision: str = "float32") -> dict:
    """The first ``len(batches)`` steps of SGD with momentum (dampening
    0) from ``params0``; each batch is (images, 1-based labels) as the
    trainer is fed them.  A batch is split into ``shards`` equal blocks
    of rows; each block is normalized by itself and the gradients are
    averaged, as data-parallel chips do.

    Returns the loss of each step, the first step's gradient and the
    change of the parameters after the last, as dicts of numpy arrays."""
    import json

    import jax
    import jax.numpy as jnp

    key = json.dumps({k: config[k] for k in
                      ("stage_widths", "stage_blocks", "bottleneck_expansion",
                       "num_classes")}, sort_keys=True)
    grad_fn = _grad_fn(key, precision)
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params0.items()}
    vel = {k: jnp.zeros_like(v) for k, v in p.items()}
    losses, first_grad = [], None
    for x, y in batches:
        rows = x.shape[0] // shards
        total, grads = 0.0, None
        for s in range(shards):
            xs = jnp.asarray(x[s * rows:(s + 1) * rows], jnp.float32)
            ys = jnp.asarray(np.asarray(y[s * rows:(s + 1) * rows])
                             .astype(np.int32) - 1)
            val, g = grad_fn(p, xs, ys)
            total += float(val) / shards
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        grads = jax.tree.map(lambda a: a / shards, grads)
        if first_grad is None:
            first_grad = {k: np.asarray(v) for k, v in grads.items()}
        vel = {k: momentum * vel[k] + grads[k] for k in p}
        p = {k: p[k] - lr * vel[k] for k in p}
        losses.append(total)
    delta = {k: np.asarray(p[k]) - np.asarray(params0[k], np.float32)
             for k in p}
    return {"losses": losses, "first_gradient": first_grad,
            "parameter_change": delta}


def norm_gaps(got: dict, want: dict) -> dict:
    """Per leaf: |norm(got) - norm(want)| over the larger of the
    reference's norm of that leaf and of its median leaf (some gradients
    are all but zero).  Returns the gaps by leaf name."""
    want_norms = {k: float(np.linalg.norm(np.asarray(v, np.float64)))
                  for k, v in want.items()}
    floor = float(np.median(list(want_norms.values())))
    gaps = {}
    for k, wn in want_norms.items():
        gn = float(np.linalg.norm(np.asarray(got[k], np.float64)))
        gaps[k] = abs(gn - wn) / max(wn, floor, 1e-30)
    return gaps


def difference(got: dict, want: dict) -> float:
    """Norm of the difference of all leaves together over the
    reference's norm: where the norms of ``norm_gaps`` forgive noise that
    averages out, this does not."""
    num = sum(float(np.sum(np.square(np.asarray(got[k], np.float64)
                                     - np.asarray(want[k], np.float64))))
              for k in want)
    den = sum(float(np.sum(np.square(np.asarray(v, np.float64))))
              for v in want.values())
    return float(np.sqrt(num / max(den, 1e-300)))
