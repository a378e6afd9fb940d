"""The paper's CNN as the local model (``model_kind`` ``"cnn"``): MNIST_CNN
and CIFAR_CNN on seeded class-conditional images, dealt out over the
satellites by the paper's non-IID partition.

The reference part (``data`` to ``train_participants``) imports nothing of
the program: plain numpy and ``jax.numpy``, following the configuration
file alone.  The program part, ``build``, builds the same run with the
program's public constructors and imports ``repro`` inside its body only.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

# ---- reference: data --------------------------------------------------------


def _prototypes(rng, num_classes, size, channels, smooth=3):
    protos = rng.standard_normal((num_classes, size, size, channels))
    for _ in range(smooth):
        protos = (protos
                  + np.roll(protos, 1, 1) + np.roll(protos, -1, 1)
                  + np.roll(protos, 1, 2) + np.roll(protos, -1, 2)) / 5.0
    protos -= protos.mean(axis=(1, 2, 3), keepdims=True)
    protos /= protos.std(axis=(1, 2, 3), keepdims=True) + 1e-9
    return protos


def images(seed: int, n: int, size: int, channels: int, separation: float,
           num_classes: int = 10):
    """Seeded class-conditional images in [0, 1] and their labels."""
    rng = np.random.default_rng(seed)
    protos = _prototypes(np.random.default_rng(1234), num_classes, size,
                         channels)
    labels = rng.integers(0, num_classes, size=n)
    noise = rng.standard_normal((n, size, size, channels))
    noise = (noise + np.roll(noise, 1, 1) + np.roll(noise, 1, 2)) / 3.0
    x = separation * protos[labels] + noise
    x = (x - x.min()) / (x.max() - x.min() + 1e-9)
    return x.astype(np.float32), labels.astype(np.int32)


def shards_of(labels, cfg: Dict, seed: int) -> List[np.ndarray]:
    """The paper's partition of the training set over satellites: those of
    the first two orbits hold classes 0-3, the others classes 4-9, each
    class pool dealt out in order."""
    if cfg["partition"] != "paper_noniid":
        raise ValueError(f"partition {cfg['partition']!r}")
    c = cfg["constellation"]
    N = c["sats_per_orbit"]
    orbit_of_sat = np.arange(c["num_orbits"] * N) // N
    rng = np.random.default_rng(seed)
    low = np.flatnonzero(labels < 4)
    high = np.flatnonzero(labels >= 4)
    rng.shuffle(low)
    rng.shuffle(high)
    out = [None] * len(orbit_of_sat)
    for sats, pool in ((np.flatnonzero(orbit_of_sat < 2), low),
                       (np.flatnonzero(orbit_of_sat >= 2), high)):
        for s, chunk in zip(sats, np.array_split(pool, len(sats))):
            out[int(s)] = np.sort(chunk)
    return out


def data(seed: int, cfg: Dict):
    """The training set, ``(images, labels)``, and each satellite's shard."""
    x, y = images(seed, cfg["train_samples"], cfg["image_size"],
                  cfg["channels"], cfg["separation"])
    return (x, y), shards_of(y, cfg, seed)


def shard_rows(seed: int, cfg: Dict) -> int:
    """Samples per satellite once shards are cut to the smallest."""
    labels = np.random.default_rng(seed).integers(
        0, cfg["num_classes"], size=cfg["train_samples"])
    return min(len(s) for s in shards_of(labels, cfg, seed))


# ---- reference: model -------------------------------------------------------

def init_params(seed: int, cfg: Dict):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    c1, c2 = cfg["conv_channels"]
    ch, hid, ncls = cfg["channels"], cfg["hidden"], cfg["num_classes"]
    flat = (cfg["image_size"] // 4) ** 2 * c2

    def tn(k, shape, fan_in):
        std = 1.0 / math.sqrt(fan_in)
        return jax.random.truncated_normal(k, -2.0, 2.0, shape) * std

    return {"conv1": tn(ks[0], (3, 3, ch, c1), 9 * ch),
            "bc1": jnp.zeros((c1,)),
            "conv2": tn(ks[1], (3, 3, c1, c2), 9 * c1),
            "bc2": jnp.zeros((c2,)),
            "w1": tn(ks[2], (flat, hid), flat),
            "b1": jnp.zeros((hid,)),
            "w2": tn(ks[3], (hid, ncls), hid),
            "b2": jnp.zeros((ncls,))}


def _precision(precision: str):
    import jax
    return {"highest": jax.lax.Precision.HIGHEST,
            "bfloat16": jax.lax.Precision.DEFAULT}[precision]


def logits(params, x, precision: str = "highest"):
    """Conv 3x3 SAME + ReLU + 2x2 max-pool, twice, then two dense layers."""
    import jax
    import jax.numpy as jnp
    p = _precision(precision)

    def conv(x, w, b):
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=p)
        return jax.nn.relu(y + b)

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    x = pool(conv(x, params["conv1"], params["bc1"]))
    x = pool(conv(x, params["conv2"], params["bc2"]))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, params["w1"], precision=p) + params["b1"])
    return jnp.dot(x, params["w2"], precision=p) + params["b2"]


def xent(params, x, y, precision: str = "highest"):
    import jax
    import jax.numpy as jnp
    z = logits(params, x, precision).astype(jnp.float32)
    gold = jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
    return (jax.nn.logsumexp(z, axis=-1) - gold).mean()


# jitted local training by (lr, batch, local_iters, precision)
_TRAIN: Dict = {}


def train_participants(w, inputs_sel, ids, epoch_seed: int, cfg: Dict,
                       local_iters: int, precision: str = "highest"):
    """Every participant's ``local_iters`` SGD steps from ``w``: rows of
    trained parameters (a pytree with a leading participant axis) and each
    participant's mean step loss.  Participant ``i`` trains on
    ``inputs_sel`` = ``(imgs[i], labs[i])`` with key ``PRNGKey(epoch_seed *
    9973 + ids[i])`` (uint32 arithmetic), drawing ``batch_size`` samples per
    step.  ``precision`` is ``"highest"`` (float32 at HIGHEST) or
    ``"bfloat16"`` (the control: bfloat16 parameters and inputs)."""
    import jax
    import jax.numpy as jnp
    imgs, labs = inputs_sel
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    lr, bs = cfg["lr"], cfg["batch_size"]

    def one(w, x, y, sid, epoch_seed):
        key = jax.random.PRNGKey(epoch_seed * jnp.uint32(9973)
                                 + sid.astype(jnp.uint32))
        n = x.shape[0]
        params = jax.tree.map(lambda a: a.astype(dtype), w)
        x = x.astype(dtype)

        def step(params, k):
            idx = jax.random.randint(k, (bs,), 0, n)
            loss, g = jax.value_and_grad(xent)(params, x[idx], y[idx],
                                               precision)
            return jax.tree.map(lambda a, b: a - lr * b, params, g), loss

        params, losses = jax.lax.scan(step, params,
                                      jax.random.split(key, local_iters))
        return (jax.tree.map(lambda a: a.astype(jnp.float32), params),
                losses.astype(jnp.float32).mean())

    # w and the seed are arguments, not constants, so one compiled program
    # (and its persistent-cache entry) serves every round and seed; the
    # jitted function is kept, so a round trained in chunks traces once
    key = (lr, bs, local_iters, precision)
    fn = _TRAIN.get(key)
    if fn is None:
        fn = _TRAIN[key] = jax.jit(jax.vmap(one, in_axes=(None, 0, 0, 0,
                                                          None)))
    return fn(w, jnp.asarray(imgs), jnp.asarray(labs),
              jnp.asarray(ids, jnp.int32), jnp.uint32(epoch_seed))


# ---- program ----------------------------------------------------------------

def build(cfg: Dict, seed: int, local_iters: int):
    """The program's pool, evaluator and initial model (host arrays) for
    run seed ``seed``, as the configuration states them."""
    import jax
    from repro.configs.paper_models import SmallNetConfig
    from repro.core.constellation import WalkerDelta
    from repro.data import class_conditional_images, paper_noniid_partition
    from repro.fl import Evaluator, ImageClassifierPool
    from repro.models import cnn

    model = SmallNetConfig(cfg["name"], cfg["model_kind"], cfg["image_size"],
                           cfg["channels"], cfg["num_classes"], cfg["hidden"],
                           tuple(cfg["conv_channels"]))

    def images_of(s, n):
        return class_conditional_images(
            s, n, num_classes=cfg["num_classes"], size=cfg["image_size"],
            channels=cfg["channels"], separation=cfg["separation"])
    imgs, labs = images_of(seed, cfg["train_samples"])
    # the test set's seed is the launcher's: the run seed + 99
    ti, tl = images_of(seed + 99, cfg["test_samples"])
    if cfg["partition"] != "paper_noniid":
        raise ValueError(f"partition {cfg['partition']!r}")
    orbits = WalkerDelta(**cfg["constellation"]).orbit_ids()
    shards = paper_noniid_partition(labs, orbits, seed)
    pool = ImageClassifierPool(model, imgs, labs, shards,
                               local_iters=local_iters,
                               batch_size=int(cfg["batch_size"]),
                               lr=float(cfg["lr"]))
    w0 = jax.device_get(cnn.init_params(jax.random.PRNGKey(seed), model))
    return pool, Evaluator(model, ti, tl), w0
