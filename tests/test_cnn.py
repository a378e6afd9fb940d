"""The CNN's 3x3 SAME convolution (models/cnn.py): its two lowerings
compute the same function under the participant vmap, and the CPU keeps
the im2col one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CIFAR_CNN, MNIST_CNN
from repro.fl import ImageClassifierPool
from repro.models import cnn

PARTICIPANTS = 4
BATCH = 8


def _participants(cfg):
    """Four participants' models, each with its own filters and biases,
    and a batch of images and labels each."""
    def one(key):
        init, noise = jax.random.split(key)
        params = cnn.init_params(init, cfg)
        leaves, tree = jax.tree.flatten(params)
        keys = jax.random.split(noise, len(leaves))
        return jax.tree.unflatten(tree, [
            p + 0.05 * jax.random.normal(k, p.shape)
            for p, k in zip(leaves, keys)])

    params = jax.jit(jax.vmap(one))(
        jax.random.split(jax.random.PRNGKey(7), PARTICIPANTS))
    rng = np.random.default_rng(9)
    images = rng.uniform(0, 1, (PARTICIPANTS, BATCH, cfg.image_size,
                                cfg.image_size, cfg.channels))
    labels = rng.integers(0, cfg.num_classes, (PARTICIPANTS, BATCH))
    return params, jnp.asarray(images, jnp.float32), jnp.asarray(labels)


def _vmapped(cfg, value_and_grad):
    def one(params, images, labels):
        f = jax.value_and_grad(cnn.loss_fn) if value_and_grad else cnn.loss_fn
        return f(params, cfg, images, labels)
    return jax.jit(jax.vmap(one))


@pytest.mark.parametrize("value_and_grad", [False, True],
                         ids=["loss", "value_and_grad"])
@pytest.mark.parametrize("cfg", [MNIST_CNN, CIFAR_CNN], ids=lambda c: c.name)
def test_xla_conv_matches_im2col_under_vmap(cfg, value_and_grad,
                                            monkeypatch):
    args = _participants(cfg)
    outs = {}
    for conv in (cnn._conv_im2col, cnn._conv_xla):
        monkeypatch.setattr(cnn, "_conv", conv)
        outs[conv.__name__] = jax.device_get(
            _vmapped(cfg, value_and_grad)(*args))
    ref, got = outs["_conv_im2col"], outs["_conv_xla"]
    for r, g in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        r, g = np.asarray(r), np.asarray(g)
        assert r.shape == g.shape
        np.testing.assert_allclose(g, r, rtol=1e-5,
                                   atol=1e-5 * np.abs(r).max())


def _cpu_training_step_hlo():
    cfg = MNIST_CNN
    shards = [np.arange(i * BATCH, (i + 1) * BATCH)
              for i in range(PARTICIPANTS)]
    pool = ImageClassifierPool(
        cfg, np.zeros((PARTICIPANTS * BATCH, 28, 28, 1), np.float32),
        np.zeros(PARTICIPANTS * BATCH, np.int32), shards,
        local_iters=2, batch_size=4)
    params = cnn.init_params(jax.random.PRNGKey(0), cfg)
    ids = np.arange(PARTICIPANTS, dtype=np.int32)
    inputs = jax.tree.map(jnp.asarray, pool.epoch_inputs(ids))
    return jax.jit(pool.epoch_train_fn()).lower(
        params, inputs, jnp.asarray(ids), jnp.uint32(1)).compile().as_text()


def _has_3x3_convolution(hlo: str) -> bool:
    return any(" convolution(" in line and "window={size=3x3" in line
               for line in hlo.splitlines())


def test_cpu_training_step_keeps_im2col(monkeypatch):
    assert not _has_3x3_convolution(_cpu_training_step_hlo())
    # the same step with XLA's convolution forced shows what is looked for
    monkeypatch.setattr(cnn, "_conv", cnn._conv_xla)
    assert _has_3x3_convolution(_cpu_training_step_hlo())
