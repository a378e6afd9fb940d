"""Expert-parallel MoE with explicit all-to-all (beyond-paper, shard_map).

The GSPMD path (models/moe.py) lets XLA choose the collective schedule for
the sort-based dispatch.  This module is the hand-scheduled production
alternative: tokens are sequence-sharded over the ``model`` axis, each rank
owns E/n_ranks experts, and dispatch/return are two explicit
``jax.lax.all_to_all`` collectives — the schedule used by Switch/GShard-class
systems and the pattern AsyncFLEO's ring-of-stars maps onto when satellites
hold expert shards (DESIGN.md §3).

Layout inside shard_map (per (data, model) device):
  x_loc   : (T_loc, d)        tokens of my sequence shard
  we*_loc : (E_loc, d, f)     my experts
  send    : (n_ranks, C, d)   capacity-C buckets per destination rank
  recv    = all_to_all(send)  tokens routed to my experts from every rank
  y       = expert matmuls    (n_ranks*C tokens through E_loc experts)
  return  = all_to_all(y)     back to the token owners, combined by gate.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


def ep_capacity(tokens_local: int, top_k: int, n_ranks: int,
                factor: float) -> int:
    c = int(math.ceil(tokens_local * top_k * factor / n_ranks))
    return max(8, -(-c // 8) * 8)


def moe_ffn_ep_local(p_local, cfg: ModelConfig, x_loc, *, axis_name: str,
                     n_ranks: int, capacity_factor: float = None):
    """Body to run inside shard_map.  x_loc: (T_loc, d) this rank's tokens;
    p_local leaves are the LOCAL expert shards (E_loc, d, f); the router is
    replicated.  Returns (out (T_loc, d), aux)."""
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity_factor
    T_loc, d = x_loc.shape
    E, k = cfg.num_experts, cfg.top_k
    E_loc = E // n_ranks
    dt = x_loc.dtype

    logits = (x_loc @ p_local["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, k)                       # (T_loc, k)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0) / (T_loc * k)
    aux = E * jnp.sum(me * ce)
    aux = jax.lax.pmean(aux, axis_name)

    C = ep_capacity(T_loc, k, n_ranks, capacity_factor)
    flat_ids = ids.reshape(-1)                                # (T_loc*k,)
    dest_rank = flat_ids // E_loc
    # position within destination-rank bucket via stable sort by rank
    sort_idx = jnp.argsort(dest_rank, stable=True)
    sorted_rank = dest_rank[sort_idx]
    start = jnp.searchsorted(sorted_rank, jnp.arange(n_ranks), side="left")
    pos = jnp.arange(T_loc * k) - start[sorted_rank]
    tok = sort_idx // k
    valid = pos < C
    slot = jnp.where(valid, sorted_rank * C + pos, n_ranks * C)

    send_x = jnp.zeros((n_ranks * C + 1, d), dt).at[slot].set(x_loc[tok])
    send_eid = jnp.full((n_ranks * C + 1,), 0, jnp.int32).at[slot].set(
        flat_ids[sort_idx] % E_loc)
    send_x = send_x[:-1].reshape(n_ranks, C, d)
    send_eid = send_eid[:-1].reshape(n_ranks, C)

    # ---- dispatch: tokens travel to their experts' rank -------------------
    recv_x = jax.lax.all_to_all(send_x, axis_name, 0, 0, tiled=False)
    recv_eid = jax.lax.all_to_all(send_eid, axis_name, 0, 0, tiled=False)
    rx = recv_x.reshape(n_ranks * C, d)
    reid = recv_eid.reshape(n_ranks * C)

    # local per-expert compute via one-hot masking over E_loc (E_loc is
    # small per rank; (E_loc, nC, d) buffers stay VMEM/HBM friendly)
    onehot = jax.nn.one_hot(reid, E_loc, dtype=dt)            # (nC, E_loc)
    xe = jnp.einsum("td,te->etd", rx, onehot)                 # (E_loc, nC, d)
    a = jnp.einsum("etd,edf->etf", xe, p_local["we1"].astype(dt))
    b = jnp.einsum("etd,edf->etf", xe, p_local["we3"].astype(dt))
    ye = jnp.einsum("etf,efd->etd", jax.nn.silu(a) * b, p_local["we2"].astype(dt))
    y = jnp.einsum("etd,te->td", ye, onehot)                  # (nC, d)

    # ---- return trip ------------------------------------------------------
    y_send = y.reshape(n_ranks, C, d)
    y_back = jax.lax.all_to_all(y_send, axis_name, 0, 0, tiled=False)
    y_flat = y_back.reshape(n_ranks * C, d)

    gate_sorted = gate.reshape(-1)[sort_idx].astype(dt)
    contrib = y_flat[jnp.where(valid, slot, 0)] * jnp.where(valid, gate_sorted,
                                                            0.0)[:, None]
    out = jnp.zeros((T_loc, d), dt).at[tok].add(contrib)

    if "shared" in p_local:
        out = out + L.mlp(p_local["shared"], x_loc)
    return out, aux


def make_ep_moe_layer(cfg: ModelConfig, mesh, *, axis_name: str = "model",
                      capacity_factor: float = None):
    """Returns moe(params, x (B,S,d)) -> (out, aux) wrapping shard_map.

    params: full (unsharded-view) moe params; shard_map slices experts onto
    ranks via in_specs; x is sequence-sharded over ``axis_name`` inside."""
    from jax.sharding import PartitionSpec as P
    n_ranks = mesh.devices.shape[mesh.axis_names.index(axis_name)]

    body = functools.partial(moe_ffn_ep_local, cfg=cfg, axis_name=axis_name,
                             n_ranks=n_ranks, capacity_factor=capacity_factor)

    def local_fn(p_local, x_loc):
        B_loc, S_loc, d = x_loc.shape
        out, aux = body(p_local, x_loc=x_loc.reshape(B_loc * S_loc, d))
        return out.reshape(B_loc, S_loc, d), aux

    expert_spec = P(axis_name)
    p_specs = {
        "router": P(),
        "we1": expert_spec, "we3": expert_spec, "we2": expert_spec,
    }

    def moe(params, x):
        p_specs_full = dict(p_specs)
        if "shared" in params:
            p_specs_full["shared"] = jax.tree.map(lambda _: P(), params["shared"])
        mapped = jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(p_specs_full, P("data", axis_name, None)),
            out_specs=(P("data", axis_name, None), P()),
            check_vma=False)
        return mapped(params, x)

    return moe
