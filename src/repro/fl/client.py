"""FL client pool: on-board local training (paper eq. 3).

Each satellite trains the received global model for J local SGD iterations on
its own shard.  ``ImageClassifierPool`` is the paper's workload (CNN/MLP on
image classification); ``LMPool`` trains transformer LMs (our LLM-scale
federated examples).  Training is jitted once and reused across satellites.

Both pools expose three result forms:

* ``epoch_train_fn`` / ``epoch_inputs`` — the fused-epoch protocol
  (DESIGN.md §6): a *traceable* training function the simulator inlines
  into its single donated epoch program, plus the host-side gather of the
  participants' data shards for one call.
* ``train_many_stacked`` — one jitted vmap over the whole participant set,
  returning a device-resident ``ModelBank`` (stacked ``(C, N)`` float32,
  see DESIGN.md §2) and *lazy* device losses (``np.asarray`` only at
  history-record time, so timing math overlaps training dispatch).
  Participant counts are padded up to ``modelbank.bucket_rows`` buckets
  (powers of two up to 8, then multiples of 8 with at most 8 buckets an
  octave: 40 participants train as 40 rows, at most 12.5% padding) so a
  changing number of participants hits at most O(log S) traces instead of
  one per distinct count.
* ``train_many`` — legacy form materializing per-satellite host pytrees
  (one ``device_get``); kept for callers that need pytrees.

Datasets stay host-side in both pools: only the participants' shards are
put on device per call (the whole (S, m, ...) tensor must not live in HBM
for mega-constellation S).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_models import SmallNetConfig
from repro.core.modelbank import FlatSpec, ModelBank, pad_bucket_ids
from repro.models import cnn
from repro.optim import sgd, apply_updates

# participant-count bucketing (padded rows trained and discarded) so a
# changing participant set retraces the jitted vmap O(log S) times
_pad_ids = pad_bucket_ids


def _empty_bank(params) -> Tuple[ModelBank, np.ndarray]:
    """Zero-participant result (legacy pools returned ([], []))."""
    spec = FlatSpec.of(params)
    return (ModelBank(spec, jnp.zeros((0, spec.num_params), jnp.float32)),
            np.zeros(0))


@dataclasses.dataclass
class ImageClassifierPool:
    cfg: SmallNetConfig
    images: np.ndarray                 # (N, H, W, C)
    labels: np.ndarray                 # (N,)
    shards: List[np.ndarray]           # per-satellite index arrays
    local_iters: int = 30              # J
    batch_size: int = 32               # b
    lr: float = 0.01                   # eta (Table I)

    def __post_init__(self):
        opt = sgd(self.lr)
        self._true_sizes = [len(s) for s in self.shards]
        m = min(self._true_sizes)                     # equalize for vmap
        # host-side (S, m) index grid: participants' shards are gathered and
        # put on device per call (the full dataset never lives in HBM)
        self._sel = np.stack([s[:m] for s in self.shards])

        def _train_one(params, imgs, labs, key):
            state = opt.init(params)
            n = imgs.shape[0]

            def step(carry, k):
                params, state = carry
                idx = jax.random.randint(k, (self.batch_size,), 0, n)
                loss, grads = jax.value_and_grad(cnn.loss_fn)(
                    params, self.cfg, imgs[idx], labs[idx])
                upd, state = opt.update(grads, state, params)
                return (apply_updates(params, upd), state), loss

            keys = jax.random.split(key, self.local_iters)
            (params, _), losses = jax.lax.scan(step, (params, state), keys)
            return params, losses.mean()

        self._train_one = _train_one
        # one jitted vmap over the whole constellation — params broadcast
        self._train_many = jax.jit(jax.vmap(_train_one, in_axes=(None, 0, 0, 0)))

    @property
    def num_clients(self) -> int:
        return len(self.shards)

    def data_size(self, sat: int) -> int:
        return int(self._true_sizes[sat])

    def epoch_inputs(self, ids_np: np.ndarray):
        """Host gather of the padded participants' shards for one call."""
        sel = self._sel[ids_np]
        return (self.images[sel], self.labels[sel])

    def epoch_train_fn(self):
        """Traceable (params, inputs, ids, seed) -> (stacked_params, losses)
        for the fused epoch program (keys derived exactly as the stacked
        path does, so the two paths stay bit-comparable)."""
        train_one = self._train_one

        def _fn(params, inputs, ids, seed):
            imgs, labs = inputs
            keys = jax.vmap(lambda s: jax.random.PRNGKey(
                seed * jnp.uint32(9973) + s.astype(jnp.uint32)))(ids)
            return jax.vmap(train_one,
                            in_axes=(None, 0, 0, 0))(params, imgs, labs, keys)
        return _fn

    def train_many_stacked(self, sat_ids: Sequence[int], params, seed: int):
        """Train the given satellites from the same global model in one
        batched call.  Returns (ModelBank of per-sat models — stacked (C, N)
        on device, no host copy — and *lazy* device losses (C,))."""
        ids_np, n = _pad_ids(sat_ids)
        if n == 0:
            return _empty_bank(params)
        ids = jnp.asarray(ids_np)
        keys = jax.vmap(lambda s: jax.random.PRNGKey(
            (np.uint32(seed) * np.uint32(9973)) + s.astype(jnp.uint32)))(ids)
        imgs, labs = self.epoch_inputs(ids_np)
        stacked, losses = self._train_many(params, jnp.asarray(imgs),
                                           jnp.asarray(labs), keys)
        bank = ModelBank.from_stacked_tree(stacked)
        return ModelBank(bank.spec, bank.stack[:n]), losses[:n]

    def train_many(self, sat_ids: Sequence[int], params, seed: int):
        """Legacy form: (list of per-sat host param pytrees, losses)."""
        bank, losses = self.train_many_stacked(sat_ids, params, seed)
        return bank.to_pytrees(), losses

    def train(self, sat: int, params, seed: int):
        outs, losses = self.train_many([sat], params, seed)
        return outs[0], float(losses[0])


@dataclasses.dataclass
class Evaluator:
    cfg: SmallNetConfig
    images: np.ndarray
    labels: np.ndarray

    def __post_init__(self):
        self._acc = jax.jit(functools.partial(cnn.accuracy, cfg=self.cfg))
        # device the evaluation set once, not per epoch
        self._imgs = jnp.asarray(self.images)
        self._labs = jnp.asarray(self.labels)

    def eval_async(self, params):
        """Lazy device scalar — the simulator blocks on it only when the
        history row is finalized, so evaluation overlaps the next epoch's
        host work."""
        return self._acc(params, images=self._imgs, labels=self._labs)

    def __call__(self, params) -> float:
        return float(self.eval_async(params))


@dataclasses.dataclass
class LMPool:
    """Federated LM pretraining pool (tokens partitioned across satellites).

    Shards are truncated to a common sequence count so the whole participant
    set trains in one jitted vmap (like ``ImageClassifierPool``) — the
    per-satellite loop of the seed retraced ``_train`` whenever a shard's
    token count differed.

    ``size_mode`` picks what ``data_size`` (the D_n of eqs. 13/14) reports:
    ``"on_board"`` (default) keeps the paper's reading — the full shard a
    satellite holds — while ``"trained"`` reports the truncated per-call
    sequence count the vmap actually trained on, making aggregation weights
    proportional to gradient contributions instead of data held
    (DESIGN.md §3 records the trade-off).
    """
    model_cfg: object                  # ModelConfig
    tokens: np.ndarray                 # (N_seqs, seq_len)
    shards: List[np.ndarray]
    local_iters: int = 4
    batch_size: int = 4
    lr: float = 1e-3
    size_mode: str = "on_board"        # "on_board" (paper D_n) | "trained"

    def __post_init__(self):
        if self.size_mode not in ("on_board", "trained"):
            raise ValueError(
                f"size_mode must be 'on_board' or 'trained', "
                f"got {self.size_mode!r}")
        from repro.models import registry as R
        from repro.optim import adamw
        opt = adamw(self.lr)
        cfg = self.model_cfg
        self._true_sizes = [len(s) for s in self.shards]
        m = min(self._true_sizes)                     # equalize for vmap
        self._sel = np.stack([s[:m] for s in self.shards])  # (S, m)
        # tokens stay host-side: only the participants' shards are put on
        # device per call (an LLM-scale corpus must not live in HBM)

        def _train_one(params, toks, key):
            state = opt.init(params)
            n = toks.shape[0]

            def step(carry, k):
                params, state = carry
                idx = jax.random.randint(k, (self.batch_size,), 0, n)
                (loss, _), grads = jax.value_and_grad(
                    R.train_loss, has_aux=True)(params, cfg, {"tokens": toks[idx]})
                upd, state = opt.update(grads, state, params)
                return (apply_updates(params, upd), state), loss

            keys = jax.random.split(key, self.local_iters)
            (params, _), losses = jax.lax.scan(step, (params, state), keys)
            return params, losses.mean()

        self._train_one = _train_one
        self._train_many = jax.jit(jax.vmap(_train_one, in_axes=(None, 0, 0)))

    @property
    def num_clients(self) -> int:
        return len(self.shards)

    def data_size(self, sat: int) -> int:
        if self.size_mode == "trained":
            return int(self._sel.shape[1])     # truncated common length
        return int(self._true_sizes[sat])      # full on-board shard (D_n)

    def epoch_inputs(self, ids_np: np.ndarray):
        return self.tokens[self._sel[ids_np]]

    def epoch_train_fn(self):
        train_one = self._train_one

        def _fn(params, toks, ids, seed):
            keys = jax.vmap(lambda s: jax.random.PRNGKey(
                seed * jnp.uint32(7919) + s.astype(jnp.uint32)))(ids)
            return jax.vmap(train_one,
                            in_axes=(None, 0, 0))(params, toks, keys)
        return _fn

    def train_many_stacked(self, sat_ids: Sequence[int], params, seed: int):
        """One batched call over the participant set -> (ModelBank, lazy
        device losses)."""
        ids_np, n = _pad_ids(sat_ids)
        if n == 0:
            return _empty_bank(params)
        ids = jnp.asarray(ids_np)
        keys = jax.vmap(lambda s: jax.random.PRNGKey(
            np.uint32(seed) * np.uint32(7919) + s.astype(jnp.uint32)))(ids)
        toks = jnp.asarray(self.epoch_inputs(ids_np))
        stacked, losses = self._train_many(params, toks, keys)
        bank = ModelBank.from_stacked_tree(stacked)
        return ModelBank(bank.spec, bank.stack[:n]), losses[:n]

    def train_many(self, sat_ids: Sequence[int], params, seed: int):
        bank, losses = self.train_many_stacked(sat_ids, params, seed)
        return bank.to_pytrees(), losses

    def train(self, sat: int, params, seed: int):
        outs, losses = self.train_many([sat], params, seed)
        return outs[0], float(losses[0])
