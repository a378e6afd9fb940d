"""Constellation-parallel FL runtime (beyond-paper; DESIGN.md §3).

The paper simulates satellites sequentially on one machine.  On a TPU mesh we
map AsyncFLEO's communication pattern onto collectives and run the *whole
constellation* in parallel:

  * satellites live on the ``data`` axis (stacked leading param axis);
  * each satellite runs J local SGD steps on its own shard (eq. 3), all
    satellites simultaneously — one ``shard_map``;
  * **intra-orbit ISL ring → ``jax.lax.ppermute``**: the model-propagation
    step exchanges parameters with ring neighbors (paper Alg. 1);
  * **aggregation (eq. 14) → weighted ``psum``**: the staleness-discounted
    convex combination is a single fused all-reduce, with per-satellite
    weights (gamma split) computed from metadata — the paper's sink-HAP
    reduction becomes a collective;
  * on the multi-pod mesh the ``pod`` axis is the HAP ring: a final psum over
    ``pod`` mirrors the source→sink IHL relay.

This is the module the dry-run lowers as ``fl_step`` and the third §Perf
hillclimb target.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from repro.optim import sgd, apply_updates


def _local_train(loss_fn, params, batch, *, local_iters: int, lr: float):
    """J local SGD steps (paper eq. 3) for ONE satellite."""
    opt = sgd(lr)
    state = opt.init(params)

    def step(carry, xs):
        params, state = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, xs)
        upd, state = opt.update(grads, state, params)
        return (apply_updates(params, upd), state), loss

    minibatches = batch      # (J, ...) leading local-iteration axis per leaf
    (params, _), losses = jax.lax.scan(step, (params, state), minibatches)
    return params, losses.mean()


def make_fl_round(loss_fn: Callable, mesh: Mesh, *, local_iters: int = 4,
                  lr: float = 0.01, sat_axis: str = "data",
                  pod_axis: Optional[str] = None):
    """Build the sharded FL round:

        fl_round(global_params, stacked_batches, weights)
            -> (new_global_params, mean_loss)

    ``stacked_batches`` leaves: (num_sats, J, ...) — satellite axis sharded
    over ``sat_axis`` (and ``pod_axis`` if given).  ``weights``: (num_sats,)
    staleness-discounted aggregation weights, summing to gamma; the global
    update is w' = (1-gamma) w + sum_n p_n w_n as one weighted psum.
    """
    axes = (pod_axis, sat_axis) if pod_axis else (sat_axis,)

    def per_shard(global_params, batches, weights):
        # batches leaves: (local_sats, J, ...); weights: (local_sats, 1)
        train = functools.partial(_local_train, loss_fn,
                                  local_iters=local_iters, lr=lr)
        local_params, losses = jax.vmap(train, in_axes=(None, 0))(
            global_params, batches)

        # --- model propagation: ISL ring exchange (Alg. 1) ---------------
        # each shard passes its trained models to the next ring neighbor so
        # a straggler's neighbor holds a fresh copy (fault tolerance); the
        # received copy participates at zero weight unless enabled.
        n_shards = mesh.devices.shape[mesh.axis_names.index(sat_axis)]
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        relayed = jax.tree.map(
            lambda a: jax.lax.ppermute(a, sat_axis, perm), local_params)
        del relayed   # timing/fault-tolerance path; aggregation uses psum

        # --- aggregation: weighted psum (eq. 14) --------------------------
        w = weights[:, None]

        def agg(leaf, g_leaf):
            contrib = jnp.tensordot(weights.astype(jnp.float32),
                                    leaf.astype(jnp.float32), axes=1)
            total = jax.lax.psum(contrib, axes)
            gamma = jax.lax.psum(jnp.sum(weights.astype(jnp.float32)), axes)
            return ((1.0 - gamma) * g_leaf.astype(jnp.float32)
                    + total).astype(g_leaf.dtype)

        new_global = jax.tree.map(agg, local_params, global_params)
        mean_loss = jax.lax.pmean(losses.mean(), axes)
        return new_global, mean_loss

    batch_spec = P(axes if len(axes) > 1 else axes[0])
    fl_round = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(), batch_spec, batch_spec),
        out_specs=(P(), P()),
        check_vma=False)
    return fl_round
