"""The paper's MLP as the local model (``model_kind`` ``"mlp"``): MNIST_MLP,
two hidden ReLU layers of ``hidden`` units, on seeded class-conditional
images dealt out over the satellites at random (an IID partition).

Test data: a second model kind, added as files alone.  The reference part
imports nothing of the program; ``build`` hands the program the same seeded
data and builds its ``ImageClassifierPool`` and ``Evaluator``.
"""
import math

import numpy as np


def images(seed, n, cfg):
    """Seeded images in [0, 1]: a fixed prototype per class plus noise."""
    size, ch, ncls = cfg["image_size"], cfg["channels"], cfg["num_classes"]
    rng = np.random.default_rng(seed)
    protos = np.random.default_rng(1234).standard_normal((ncls, size, size,
                                                          ch))
    labels = rng.integers(0, ncls, size=n)
    x = (cfg["separation"] * protos[labels]
         + rng.standard_normal((n, size, size, ch)))
    x = (x - x.min()) / (x.max() - x.min() + 1e-9)
    return x.astype(np.float32), labels.astype(np.int32)


def _num_sats(cfg):
    c = cfg["constellation"]
    return c["num_orbits"] * c["sats_per_orbit"]


def data(seed, cfg):
    if cfg["partition"] != "iid":
        raise ValueError(f"partition {cfg['partition']!r}")
    x, y = images(seed, cfg["train_samples"], cfg)
    perm = np.random.default_rng(seed).permutation(len(y))
    shards = [np.sort(s) for s in np.array_split(perm, _num_sats(cfg))]
    return (x, y), shards


def shard_rows(seed, cfg):
    return cfg["train_samples"] // _num_sats(cfg)


def init_params(seed, cfg):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    d_in = cfg["image_size"] ** 2 * cfg["channels"]
    hid, ncls = cfg["hidden"], cfg["num_classes"]

    def tn(k, shape):
        std = 1.0 / math.sqrt(shape[0])
        return jax.random.truncated_normal(k, -2.0, 2.0, shape) * std

    return {"w1": tn(ks[0], (d_in, hid)), "b1": jnp.zeros((hid,)),
            "w2": tn(ks[1], (hid, hid)), "b2": jnp.zeros((hid,)),
            "w3": tn(ks[2], (hid, ncls)), "b3": jnp.zeros((ncls,))}


def xent(params, x, y, p):
    import jax
    import jax.numpy as jnp
    h = x.reshape(x.shape[0], -1)
    for k in ("1", "2"):
        h = jax.nn.relu(jnp.dot(h, params["w" + k], precision=p)
                        + params["b" + k])
    z = (jnp.dot(h, params["w3"], precision=p)
         + params["b3"]).astype(jnp.float32)
    gold = jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
    return (jax.nn.logsumexp(z, axis=-1) - gold).mean()


# jitted local training by (lr, batch, local_iters, precision)
_TRAIN = {}


def train_participants(w, inputs_sel, ids, epoch_seed, cfg, local_iters,
                       precision="highest"):
    """``local_iters`` SGD steps per participant, minibatches drawn with key
    ``PRNGKey(epoch_seed * 9973 + id)`` (uint32), as the program's pool."""
    import jax
    import jax.numpy as jnp
    imgs, labs = inputs_sel
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    p = (jax.lax.Precision.HIGHEST if precision == "highest"
         else jax.lax.Precision.DEFAULT)
    lr, bs = cfg["lr"], cfg["batch_size"]

    def one(w, x, y, sid, epoch_seed):
        key = jax.random.PRNGKey(epoch_seed * jnp.uint32(9973)
                                 + sid.astype(jnp.uint32))
        params = jax.tree.map(lambda a: a.astype(dtype), w)
        x = x.astype(dtype)

        def step(params, k):
            idx = jax.random.randint(k, (bs,), 0, x.shape[0])
            loss, g = jax.value_and_grad(xent)(params, x[idx], y[idx], p)
            return jax.tree.map(lambda a, b: a - lr * b, params, g), loss

        params, losses = jax.lax.scan(step, params,
                                      jax.random.split(key, local_iters))
        return (jax.tree.map(lambda a: a.astype(jnp.float32), params),
                losses.astype(jnp.float32).mean())

    key = (lr, bs, local_iters, precision)
    fn = _TRAIN.get(key)
    if fn is None:
        fn = _TRAIN[key] = jax.jit(jax.vmap(one, in_axes=(None, 0, 0, 0,
                                                          None)))
    return fn(w, jnp.asarray(imgs), jnp.asarray(labs),
              jnp.asarray(ids, jnp.int32), jnp.uint32(epoch_seed))


def build(cfg, seed, local_iters):
    import jax
    from repro.configs.paper_models import SmallNetConfig
    from repro.fl import Evaluator, ImageClassifierPool
    from repro.models import cnn

    model = SmallNetConfig(cfg["name"], cfg["model_kind"], cfg["image_size"],
                           cfg["channels"], cfg["num_classes"], cfg["hidden"])
    (x, y), shards = data(seed, cfg)
    pool = ImageClassifierPool(model, x, y, shards, local_iters=local_iters,
                               batch_size=int(cfg["batch_size"]),
                               lr=float(cfg["lr"]))
    w0 = jax.device_get(cnn.init_params(jax.random.PRNGKey(seed), model))
    ti, tl = images(seed + 99, cfg["test_samples"], cfg)
    return pool, Evaluator(model, ti, tl), w0
