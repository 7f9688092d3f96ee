"""Model-zoo specs (reference: «test»/models/*Spec.scala — shape checks
on small inputs + convergence smokes)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.models import (
    build_alexnet, build_autoencoder, build_inception_v1, build_lenet5,
    build_ptb_lm, build_resnet_cifar, build_resnet_imagenet, build_vgg16,
    build_vgg_cifar, imagenet_recipe_optim,
)


def _count_params(model):
    return sum(int(np.prod(w.shape)) for w in model.get_weights())


def test_lenet_shape():
    m = build_lenet5()
    out = m.forward(jnp.ones((2, 28, 28)))
    assert out.shape == (2, 10)


def test_resnet_cifar_shape_and_params():
    m = build_resnet_cifar(depth=20)
    m.evaluate()
    out = m.forward(jnp.ones((2, 3, 32, 32)))
    assert out.shape == (2, 10)
    n = _count_params(m)
    # ResNet-20 CIFAR is ~0.27M params
    assert 0.25e6 < n < 0.3e6, n


def test_resnet50_imagenet_param_count():
    m = build_resnet_imagenet(depth=50)
    n = _count_params(m)
    # canonical ResNet-50: 25.56M
    assert 25.0e6 < n < 26.2e6, n


def test_resnet50_forward_tiny():
    m = build_resnet_imagenet(depth=50, class_num=10)
    m.evaluate()
    out = m.forward(jnp.ones((1, 3, 64, 64)))  # global pool handles size
    assert out.shape == (1, 10)


def test_resnet18_basic_blocks():
    m = build_resnet_imagenet(depth=18, class_num=10)
    m.evaluate()
    out = m.forward(jnp.ones((1, 3, 64, 64)))
    assert out.shape == (1, 10)


def test_vgg16_param_count():
    m = build_vgg16()
    n = _count_params(m)
    # canonical VGG-16: 138.36M
    assert 138e6 < n < 139e6, n


def test_vgg_cifar_shape():
    m = build_vgg_cifar()
    m.evaluate()
    out = m.forward(jnp.ones((2, 3, 32, 32)))
    assert out.shape == (2, 10)


def test_alexnet_shape():
    m = build_alexnet(class_num=100)
    m.evaluate()
    out = m.forward(jnp.ones((1, 3, 227, 227)))
    assert out.shape == (1, 100)


def test_inception_v1_shape_and_params():
    m = build_inception_v1(class_num=1000)
    m.evaluate()
    out = m.forward(jnp.ones((1, 3, 224, 224)))
    assert out.shape == (1, 1000)
    n = _count_params(m)
    # GoogLeNet main tower ~ 6-7M params
    assert 5e6 < n < 8e6, n


def test_inception_v2_shape_and_params():
    from bigdl_tpu.models import build_inception_v2

    m = build_inception_v2(class_num=1000)
    m.evaluate()
    out = m.forward(jnp.ones((1, 3, 224, 224)))
    assert out.shape == (1, 1000)
    n = _count_params(m)
    # BN-Inception ~ 11M params
    assert 10e6 < n < 13e6, n


def test_inception_v2_train_step_decreases_loss():
    from bigdl_tpu.models.inception import inception_layer_v2
    from bigdl_tpu.nn import (
        ClassNLLCriterion, Linear, LogSoftMax, Reshape, Sequential,
        SpatialAveragePooling,
    )
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.optim.optimizer import LocalOptimizer

    # a tiny v2 tower (one regular + one grid-reduction module) so the
    # double-3x3/stride-2/pool-pass-through paths all run fwd+bwd
    model = (
        Sequential()
        .add(inception_layer_v2(3, ([8], [8, 8], [8, 8], ("avg", 8)), "a/"))
        .add(inception_layer_v2(32, ([0], [8, 8], [8, 8], ("max", 0)), "b/"))
        .add(SpatialAveragePooling(8, 8, 1, 1))
        .add(Reshape([48]))
        .add(Linear(48, 4))
        .add(LogSoftMax())
    )
    rs = np.random.RandomState(0)
    x = rs.rand(32, 3, 16, 16).astype(np.float32)
    y = (rs.randint(0, 4, 32) + 1).astype(np.float32)
    opt = LocalOptimizer(model, (x, y), ClassNLLCriterion(), batch_size=16)
    opt.set_optim_method(SGD(learningrate=0.1))
    opt.set_end_when(Trigger.max_epoch(4))
    opt.optimize()
    assert opt.state["loss"] < np.log(4)  # below chance-level NLL


def test_autoencoder_trains():
    from bigdl_tpu.models.autoencoder import train_autoencoder

    model, opt = train_autoencoder(max_epoch=2, batch_size=64)
    assert opt.state["loss"] < 0.1


def test_ptb_lm_shape_and_perplexity_drops():
    from bigdl_tpu.models.rnn import train_ptb

    model, opt, ppl = train_ptb(vocab_size=50, batch_size=16, num_steps=10,
                                max_epoch=2, hidden_size=64,
                                learning_rate=1.0)
    # random baseline perplexity = vocab_size (50); Markov structure is
    # learnable well below that
    assert ppl < 40, f"perplexity {ppl}"


def test_imagenet_recipe_schedule():
    opt = imagenet_recipe_optim(batch_size=256, iterations_per_epoch=10,
                                n_epochs=90, warmup_epochs=5)
    state = opt.init_state(jnp.zeros(4))
    # during warmup lr climbs from 0.1 toward base (0.1 * 256/256 = 0.1,
    # so flat here); after epoch 30 boundary it decays 10x
    state["neval"] = jnp.asarray(31.0 * 10)
    lr_after_30 = float(opt.current_rate(state))
    state["neval"] = jnp.asarray(61.0 * 10)
    lr_after_60 = float(opt.current_rate(state))
    assert abs(lr_after_30 - 0.01) < 1e-6
    assert abs(lr_after_60 - 0.001) < 1e-6


def test_module_level_evaluate_and_predict():
    """Reference parity: model.evaluate(data, methods) and
    model.predict/predictClass as MODULE methods (SURVEY §3.6)."""
    import numpy as np
    from bigdl_tpu.nn import Linear, LogSoftMax, Sequential
    from bigdl_tpu.optim import Top1Accuracy

    rs = np.random.RandomState(0)
    x = rs.randn(40, 6).astype(np.float32)
    y = (rs.randint(0, 3, 40) + 1).astype(np.float32)
    m = Sequential().add(Linear(6, 3)).add(LogSoftMax())

    # no-arg evaluate keeps the mode-switch contract
    assert m.evaluate() is m
    assert not m.is_training

    (acc,) = m.evaluate((x, y), [Top1Accuracy()])
    value, count = acc.result()
    assert count == 40
    preds = m.predict(x, batch_size=16)
    assert preds.shape == (40, 3)
    classes = m.predict_class(x)
    assert classes.min() >= 1 and classes.max() <= 3
    # predictions and the accuracy agree
    assert value == np.mean(classes == y)


def test_ncf_forward_and_learns():
    from bigdl_tpu.models import build_ncf
    from bigdl_tpu.nn import ClassNLLCriterion
    from bigdl_tpu.optim import Adam, Trigger
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from examples.recommendation.ncf_train import (
        synthetic_interactions, training_pairs,
    )

    pos = synthetic_interactions(50, 80, per_user=10)
    x, y = training_pairs(pos, 80, neg_per_pos=2)
    m = build_ncf(50, 80, class_num=2)
    out = m.forward(jnp.asarray(x[:8]))
    assert out.shape == (8, 2)
    opt = LocalOptimizer(m, (x, y), ClassNLLCriterion(), batch_size=128)
    opt.set_optim_method(Adam(learningrate=1e-2))
    opt.set_end_when(Trigger.max_epoch(3))
    opt.optimize()
    assert opt.state["loss"] < 0.63  # below the all-negative prior NLL


def test_remat_container_matches_plain():
    """Remat(module) must be numerically IDENTICAL (fwd + grads) to the
    plain module — only the memory/recompute schedule differs."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.nn import Linear, ReLU, Remat, Sequential

    RandomGenerator.RNG.set_seed(3)
    inner = Sequential().add(Linear(8, 16)).add(ReLU()).add(Linear(16, 8))
    wrapped = Remat(inner)
    x = jnp.asarray(np.random.RandomState(0).randn(4, 8), jnp.float32)

    p_plain = inner.params()
    p_wrap = wrapped.params()

    def loss_plain(p, x):
        out, _ = inner.apply(p, inner.state(), x)
        return jnp.sum(out ** 2)

    def loss_wrap(p, x):
        out, _ = wrapped.apply(p, wrapped.state(), x)
        return jnp.sum(out ** 2)

    l1, g1 = jax.value_and_grad(loss_plain)(p_plain, x)
    l2, g2 = jax.value_and_grad(loss_wrap)(p_wrap, x)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(g1["0"]["weight"]), np.asarray(g2["0"]["0"]["weight"]),
        rtol=1e-6)


def test_transformer_remat_matches_plain():
    """remat=True changes the backward schedule, not the math: same
    loss and same gradients as the stored-activation path."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.models.transformer import build_transformer_lm

    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 64, (2, 16)).astype(np.float32))
    tgt = rs.randint(0, 64, (2, 16))

    grads = {}
    losses = {}
    rng = jax.random.key(17)
    for remat in (False, True):
        RandomGenerator.RNG.set_seed(9)
        # training=True with dropout exercises the riskiest remat
        # interaction: a traced PRNG key closed over jax.checkpoint —
        # identical fold_in keys on both paths give identical masks
        model = build_transformer_lm(64, dim=32, n_head=2, n_layer=2,
                                     max_len=16, dropout=0.1, remat=remat)
        params = model.params()

        def loss_fn(p):
            logits, _ = model.apply(p, model.state(), ids,
                                    training=True, rng=rng)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(
                logp, jnp.asarray(tgt)[:, :, None], 2))

        l, g = jax.value_and_grad(loss_fn)(params)
        losses[remat] = float(l)
        grads[remat] = g
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-6)
    flat_a = jax.tree_util.tree_leaves_with_path(grads[False])
    flat_b = jax.tree_util.tree_leaves_with_path(grads[True])
    key = lambda kv: jax.tree_util.keystr(kv[0])
    for (ka, a), (kb, b) in zip(sorted(flat_a, key=key),
                                sorted(flat_b, key=key)):
        assert jax.tree_util.keystr(ka) == jax.tree_util.keystr(kb)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_transformer_generate_matches_incremental_forward():
    """The KV-cache scan decode must produce exactly the tokens a naive
    loop (full forward over the growing prefix, argmax of the last
    logits) produces."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.models.transformer import build_transformer_lm

    RandomGenerator.RNG.set_seed(13)
    model = build_transformer_lm(48, dim=32, n_head=4, n_layer=2,
                                 max_len=24, attn_impl="lax")
    params = model.params()
    rs = np.random.RandomState(0)
    prompt = rs.randint(0, 48, (2, 5))

    got = np.asarray(model.generate(params, prompt, 8))
    assert got.shape == (2, 13)
    np.testing.assert_array_equal(got[:, :5], prompt)

    # prefill IS the training forward: block.prefill output must equal
    # apply() on the prompt exactly (same projection + attention path)
    x = jnp.take(params["wte"]["weight"],
                 jnp.asarray(prompt, jnp.int32), axis=0)
    x = x + params["wpe"]["weight"][:5][None]
    xa = x
    for i in range(model.n_layer):
        blk = model._children[f"h{i}"]
        x, _, _ = blk.prefill(params[f"h{i}"], x)
        xa, _ = blk.apply(params[f"h{i}"], {}, xa)
    np.testing.assert_allclose(np.asarray(x), np.asarray(xa),
                               rtol=1e-6, atol=1e-6)

    # naive reference: grow the sequence one full forward at a time
    seq = prompt.copy()
    for _ in range(8):
        logits, _ = model.apply(
            params, model.state(), jnp.asarray(seq.astype(np.float32)))
        nxt = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(got, seq)


def test_transformer_generate_sampling_reproducible():
    import jax

    from bigdl_tpu.common import RandomGenerator
    from bigdl_tpu.models.transformer import build_transformer_lm

    RandomGenerator.RNG.set_seed(13)
    model = build_transformer_lm(32, dim=16, n_head=2, n_layer=1,
                                 max_len=16)
    params = model.params()
    prompt = np.random.RandomState(1).randint(0, 32, (1, 3))
    a = np.asarray(model.generate(params, prompt, 6, temperature=0.8,
                                  rng=jax.random.key(5)))
    b = np.asarray(model.generate(params, prompt, 6, temperature=0.8,
                                  rng=jax.random.key(5)))
    np.testing.assert_array_equal(a, b)
    import pytest as _pytest

    with _pytest.raises(ValueError, match="rng"):
        model.generate(params, prompt, 2, temperature=0.5)
    with _pytest.raises(ValueError, match="max_len"):
        model.generate(params, prompt, 100)
