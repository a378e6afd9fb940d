"""Constellation-parallel shard_map runtime + sharding-rule resolution."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.fl.sharded import make_fl_round
from repro.launch import make_host_mesh
from repro.launch.mesh import make_mesh
from repro.launch.sharding import (BASE_RULES, FSDP_RULES, classify_leaf,
                                   partition_spec, tree_shardings)


def test_fl_round_runs_and_aggregates():
    mesh = make_host_mesh(data=1)
    num_sats, J = 4, 3

    def loss_fn(params, batch):
        x, y = batch
        pred = x @ params["w"]
        return jnp.mean((pred - y) ** 2)

    fl_round = make_fl_round(loss_fn, mesh, local_iters=J, lr=0.1)
    params = {"w": jnp.zeros((5, 1))}
    rng = np.random.default_rng(0)
    w_true = rng.standard_normal((5, 1)).astype(np.float32)
    xs = rng.standard_normal((num_sats, J, 16, 5)).astype(np.float32)
    ys = xs @ w_true
    weights = jnp.full((num_sats,), 1.0 / num_sats)

    w1, loss1 = fl_round(params, (jnp.asarray(xs), jnp.asarray(ys)), weights)
    w2, loss2 = fl_round(w1, (jnp.asarray(xs), jnp.asarray(ys)), weights)
    assert float(loss2) < float(loss1)          # global model improves
    # gamma=1 -> result is average of locally trained models (no prev term)
    assert np.isfinite(np.asarray(w2["w"])).all()


def test_fl_round_partial_gamma_keeps_prev():
    mesh = make_host_mesh(data=1)

    def loss_fn(params, batch):
        return jnp.mean((params["w"] - batch) ** 2)

    fl_round = make_fl_round(loss_fn, mesh, local_iters=2, lr=0.0)  # lr=0: no move
    params = {"w": jnp.full((3,), 7.0)}
    batch = jnp.zeros((2, 2, 3))
    weights = jnp.full((2,), 0.25)               # gamma = 0.5
    w1, _ = fl_round(params, batch, weights)
    np.testing.assert_allclose(np.asarray(w1["w"]), 7.0, rtol=1e-6)


# --------------------------------------------------------------------------
# sharding rules
# --------------------------------------------------------------------------

def test_classify_known_leaves():
    assert classify_leaf("wq", 3) == ("embed", "heads", "head")
    assert classify_leaf("wq", 4) == (None, "embed", "heads", "head")   # stacked
    # routed-expert weights are we* — MUST not collide with stacked dense w1
    assert classify_leaf("we1", 3) == ("expert", "embed", "moe_mlp")
    assert classify_leaf("we1", 4) == (None, "expert", "embed", "moe_mlp")
    assert classify_leaf("w1", 3) == (None, "embed", "mlp")   # stacked dense
    assert classify_leaf("embedding", 2) == ("vocab", "embed")
    assert classify_leaf("unknown_leaf", 2) == (None, None)


def test_partition_spec_divisibility_fallback():
    mesh = make_mesh((1, 1), ("data", "model"))
    # model axis size 1: everything divides; use a fake 16-wide mesh check via
    # direct sizes by constructing the spec logic with a wider mesh if devices
    # allow — here we assert the no-crash property and correct axis names.
    spec = partition_spec((32, 14, 64), ("embed", "heads", "head"),
                          mesh, BASE_RULES)
    assert isinstance(spec, P)


def test_tree_shardings_cover_params():
    from repro.configs import ARCHS
    from repro.launch.specs import param_specs
    mesh = make_host_mesh()
    cfg = ARCHS["qwen3-4b"].reduced()
    specs = param_specs(cfg)
    sh = tree_shardings(specs, mesh, BASE_RULES)
    n_leaves = len(jax.tree_util.tree_leaves(specs))
    n_sh = len(jax.tree_util.tree_leaves(
        sh, is_leaf=lambda x: hasattr(x, "spec")))
    assert n_leaves == n_sh


def test_fsdp_rules_shard_embed_dim():
    """On a mesh with a >1 'data' axis, FSDP rules shard the embed dim."""
    if len(jax.devices()) < 2:
        mesh = make_mesh((1, 1), ("data", "model"))
        spec = partition_spec((256, 512), ("embed", "mlp"), mesh, FSDP_RULES)
        assert isinstance(spec, P)      # single device: still resolves
    else:
        mesh = make_mesh((2, 1), ("data", "model"))
        spec = partition_spec((256, 512), ("embed", "mlp"), mesh, FSDP_RULES)
        assert spec[0] == "data"
