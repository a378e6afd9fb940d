"""Fused epoch-step program (core/epoch_step.py, DESIGN.md §6).

Covers: three-way simulator parity (legacy pytrees / stacked ModelBank /
fused one-dispatch program), the one-donated-dispatch-per-epoch contract,
the stale+new-orbit two-dispatch fallback, the no-participant guard, and
lazy (non-blocking) losses/evaluation.  The multi-device NamedSharding /
shard_map path runs in a subprocess (device count is locked at first jax
init).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FLSimulation, SimConfig
from repro.core.epoch_step import (EpochStepProgram, carry_capacity,
                                   make_epoch_program, next_pow2)
from repro.core.modelbank import FlatSpec, ModelBank, flatten_tree
from repro.fl import get_strategy

W0 = {"w": np.zeros((6,), np.float32), "b": np.ones((3,), np.float32)}


class TinyFusedTrainer:
    """Deterministic trainer exposing all three protocols with identical
    math: model * 0.9 + per-(sat, seed) offset."""

    def __init__(self, w0):
        self.spec = FlatSpec.of(w0)

    def data_size(self, sat):
        return 100 + (sat % 5) * 10

    # fused protocol ------------------------------------------------------
    def epoch_inputs(self, ids_np):
        return None

    def epoch_train_fn(self):
        def _fn(params, inputs, ids, seed):
            flat = flatten_tree(params)
            offs = ((ids * 37 + seed.astype(jnp.int32)) % 11
                    - 5).astype(jnp.float32) * 0.01
            stack = flat[None, :] * 0.9 + offs[:, None]
            return stack, jnp.zeros(ids.shape[0])
        return _fn

    # stacked protocol ----------------------------------------------------
    def train_many_stacked(self, sats, params, seed):
        flat = self.spec.flatten(params)
        offs = jnp.asarray([(s * 37 + seed) % 11 - 5 for s in sats],
                           jnp.float32) * 0.01
        stack = flat[None, :] * 0.9 + offs[:, None]
        return ModelBank(self.spec, stack), np.zeros(len(sats))

    # legacy protocol -----------------------------------------------------
    def train_many(self, sats, params, seed):
        bank, losses = self.train_many_stacked(sats, params, seed)
        return bank.to_pytrees(), losses


def _run(mode, name, trainer_cls=TinyFusedTrainer, evaluator=None,
         max_epochs=4, **simkw):
    sim = SimConfig(duration_s=86400.0, train_time_s=300.0,
                    use_model_bank=mode != "legacy",
                    use_fused_step=mode == "fused", **simkw)
    fls = FLSimulation(get_strategy(name), trainer_cls(W0), evaluator, sim)
    hist = fls.run(W0, max_epochs=max_epochs)
    rows = [(r.epoch, round(r.time_s, 6), r.num_models,
             round(r.gamma, 6), r.stale_groups) for r in hist]
    return fls, rows


# ---- three-way simulator parity -------------------------------------------

@pytest.mark.parametrize("name", ["asyncfleo-twohap", "fedhap", "fedsat",
                                  "fedspace"])
def test_fused_history_matches_stacked_and_legacy(name):
    rows = {m: _run(m, name)[1] for m in ("legacy", "stacked", "fused")}
    assert rows["legacy"] == rows["stacked"] == rows["fused"]


@pytest.mark.parametrize("name", ["asyncfleo-twohap", "fedsat"])
def test_fused_history_parity_with_stragglers(name):
    """A tight window forces late arrivals -> carried stale models."""
    rows = {m: _run(m, name, agg_timeout_s=120.0)[1]
            for m in ("legacy", "stacked", "fused")}
    assert rows["legacy"] == rows["stacked"] == rows["fused"]


def test_fused_final_models_match():
    evals = {}
    for mode in ("legacy", "stacked", "fused"):
        seen = []

        def ev(params, seen=seen):
            seen.append(np.concatenate(
                [np.ravel(np.asarray(params["w"])),
                 np.ravel(np.asarray(params["b"]))]))
            return 0.0
        _run(mode, "asyncfleo-twohap", evaluator=ev, agg_timeout_s=120.0)
        evals[mode] = seen
    assert len(evals["legacy"]) == len(evals["fused"]) > 0
    for a, b in zip(evals["legacy"], evals["fused"]):
        np.testing.assert_allclose(a, b, atol=1e-5)
    for a, b in zip(evals["stacked"], evals["fused"]):
        np.testing.assert_allclose(a, b, atol=1e-5)


# ---- the one-donated-dispatch-per-epoch contract --------------------------

def test_one_dispatch_per_epoch():
    fls, rows = _run("fused", "asyncfleo-twohap")
    prog = fls._fused_prog
    assert prog is not None
    assert prog.dispatches == len(rows)      # exactly one program per epoch
    assert prog.fallback_dispatches == 0


def test_program_donates_and_matches_manual():
    spec = FlatSpec.of(W0)
    trainer = TinyFusedTrainer(W0)
    prog = EpochStepProgram(spec, trainer.epoch_train_fn())
    N = spec.num_params
    C, cap = 4, 4
    # reference host copy from a SEPARATE flatten: fetching the donated
    # buffer to host first would cache an _npy_value and keep it alive
    w_host = np.asarray(spec.flatten(W0))
    w = spec.flatten(W0)
    carry = jnp.asarray(np.linspace(0, 1, cap * N,
                                    dtype=np.float32).reshape(cap, N))
    ids = np.arange(C, dtype=np.int32)
    wv = np.array([0.1, 0.2, 0.0, 0.05], np.float32)
    wc = np.array([0.03, 0.0, 0.0, 0.0], np.float32)
    # two new orbits: rows {0,1} -> orbit 0 (half weight each), row 2 ->
    # orbit 1; row 3 owned by no orbit (dump segment kpad=2)
    kpad = 2
    dw_row = np.array([0.5, 0.5, 1.0, 0.0], np.float32)
    dw_seg = np.array([0, 0, 1, kpad], np.int32)
    dwc = np.zeros((kpad, cap), np.float32)
    ref = jnp.zeros(N)

    new_w, stack, dists, losses = prog.step(
        w, carry, None, ids, 7, wv, wc, 0.6, dw_row, dw_seg, kpad,
        0, dwc, ref)
    assert prog.dispatches == 1
    # donation: the global-model input buffer was consumed
    assert w.is_deleted()
    # manual reference
    offs = ((ids * 37 + 7) % 11 - 5).astype(np.float32) * 0.01
    stack_ref = w_host[None, :] * 0.9 + offs[:, None]
    np.testing.assert_allclose(np.asarray(stack), stack_ref, atol=1e-6)
    w_ref = 0.6 * w_host + wv @ stack_ref + wc @ np.asarray(carry)
    np.testing.assert_allclose(np.asarray(new_w), w_ref, atol=1e-5)
    # dense equivalent of the (dw_row, dw_seg) distance inputs
    dw = np.array([[0.5, 0.5, 0.0, 0.0], [0.0, 0.0, 1.0, 0.0]], np.float32)
    d_ref = np.linalg.norm(dw @ stack_ref, axis=1)
    np.testing.assert_allclose(np.asarray(dists)[:2], d_ref, rtol=1e-5)
    # the blocked-einsum layout (orbit k owns rows [k*2, k*2+2)) must give
    # the same distances as the dense one-hot path
    w2 = spec.flatten(W0)
    _nw, _st, dists_b, _l = prog.step(
        w2, carry, None, ids, 7, wv, wc, 0.6, dw_row, dw_seg, kpad,
        2, dwc, ref)
    np.testing.assert_allclose(np.asarray(dists_b)[:2], d_ref, rtol=1e-5)


def test_fused_kernel_routing_parity():
    """``use_agg_kernel`` routes the fused program's aggregation
    contraction through the Pallas fed_agg kernel (interpret mode on CPU);
    history and final weights must match the XLA contraction, and the
    kernel-routed program must be cached separately."""
    import dataclasses as dc
    rows, finals = {}, {}
    for flag in (False, True):
        spec = dc.replace(get_strategy("asyncfleo-twohap"),
                          use_agg_kernel=flag)
        sim = SimConfig(duration_s=86400.0, train_time_s=300.0,
                        use_model_bank=True, use_fused_step=True)
        fls = FLSimulation(spec, TinyFusedTrainer(W0), None, sim)
        hist = fls.run(W0, max_epochs=3)
        rows[flag] = [(r.epoch, round(r.time_s, 6), r.num_models)
                      for r in hist]
        finals[flag] = np.asarray(fls._w_flat)
        assert fls._fused_prog.use_kernel is flag
        assert fls._fused_prog.dispatches == len(hist)
    assert rows[False] == rows[True]
    np.testing.assert_allclose(finals[False], finals[True], atol=1e-5)


def test_program_cached_on_trainer():
    trainer = TinyFusedTrainer(W0)
    p1 = make_epoch_program(trainer, W0)
    p2 = make_epoch_program(trainer, W0)
    assert p1 is p2                       # compiled program reused across runs


def test_carry_capacity_buckets():
    assert carry_capacity(0) == carry_capacity(1) == carry_capacity(4) == 4
    assert carry_capacity(5) == 8
    assert next_pow2(1) == 1 and next_pow2(3) == 4


# ---- stale + new-orbit fallback -------------------------------------------

def _staged_downlink(fls, visible_epochs):
    """Patch _downlink so epoch e only reaches the sats in
    visible_epochs[min(e, len-1)] (the rest wait)."""
    state = {"calls": 0}
    S = fls.constellation.num_sats

    def fake(t0, bits, source):
        idx = min(state["calls"], len(visible_epochs) - 1)
        state["calls"] += 1
        recv = np.full(S, np.inf)
        vis = list(visible_epochs[idx])
        # spread receive times so arrivals straddle the collection window
        recv[vis] = t0 + 60.0 + 90.0 * np.arange(len(vis))
        return recv
    fls._downlink = fake


def test_fallback_parity_new_orbit_with_stale():
    """A model from a never-seen orbit is pending as a STALE straggler
    when fresh models arrive: group membership (and hence the weight
    vector) depends on this epoch's distances, so the fused path must
    split into two dispatches — and still match the stacked path."""
    spec = FlatSpec.of(W0)
    straggler = (np.asarray(spec.flatten(W0)) + 0.7)[None, :]
    rows, evals, progs = {}, {}, {}
    for mode in ("stacked", "fused"):
        seen = []

        def ev(params, seen=seen):
            seen.append(np.asarray(params["w"]).copy())
            return 0.0
        sim = SimConfig(duration_s=86400.0, train_time_s=300.0,
                        use_model_bank=True,
                        use_fused_step=mode == "fused")
        fls = FLSimulation(get_strategy("asyncfleo-twohap"),
                           TinyFusedTrainer(W0), ev, sim)
        # sat 8 belongs to orbit 1, which the grouping has never seen; its
        # model arrives immediately but was trained "before epoch 0"
        fls._pend_meta = [(1.0, 8, -1)]
        fls._pend_dev = jnp.asarray(straggler.astype(np.float32))
        _staged_downlink(fls, [range(0, 8)])   # only orbit 0 trains
        hist = fls.run(W0, max_epochs=2)
        rows[mode] = [(r.epoch, round(r.time_s, 6), r.num_models,
                       round(r.gamma, 6), r.stale_groups) for r in hist]
        evals[mode] = seen
        progs[mode] = fls._fused_prog
    assert rows["stacked"] == rows["fused"]
    assert any(r[4] > 0 for r in rows["fused"])     # a stale-only group
    for a, b in zip(evals["stacked"], evals["fused"]):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert progs["fused"].fallback_dispatches >= 1


# ---- no-participant / never-trained guards --------------------------------

@pytest.mark.parametrize("mode", ["stacked", "fused"])
def test_pending_without_training_regression(mode):
    """_pend_meta populated while no participant ever trained: the stacked
    path used to reach _combine with base=None (spec never set) and crash;
    now the base falls back to the pytree's own FlatSpec."""
    sim = SimConfig(duration_s=86400.0, train_time_s=300.0,
                    use_model_bank=True, use_fused_step=mode == "fused")
    fls = FLSimulation(get_strategy("asyncfleo-twohap"),
                       TinyFusedTrainer(W0), None, sim)
    spec = FlatSpec.of(W0)
    row = np.asarray(spec.flatten(W0))[None, :] + 1.0
    fls._pend_meta = [(10.0, 3, 0)]
    fls._pend_dev = jnp.asarray(row.astype(np.float32))
    _staged_downlink(fls, [()])              # nobody is ever visible
    hist = fls.run(W0, max_epochs=2)
    assert len(hist) == 1                    # straggler-only aggregation
    assert hist[0].num_models == 1


# ---- lazy losses / lazy evaluation ----------------------------------------

def test_stacked_losses_are_lazy_device_values():
    from repro.fl.client import ImageClassifierPool
    from repro.configs.paper_models import SmallNetConfig
    from repro.models import cnn
    rng = np.random.default_rng(0)
    images = rng.standard_normal((64, 8, 8, 1)).astype(np.float32)
    labels = np.asarray(rng.integers(0, 3, 64))
    shards = [np.arange(i * 16, (i + 1) * 16) for i in range(4)]
    cfg = SmallNetConfig("t", "mlp", image_size=8, channels=1,
                         num_classes=3, hidden=8)
    pool = ImageClassifierPool(cfg, images, labels, shards, local_iters=2)
    # dataset stays host-side (satellite shards are gathered per call)
    assert isinstance(pool._sel, np.ndarray)
    assert not hasattr(pool, "_imgs")
    w0 = cnn.init_params(jax.random.PRNGKey(0), cfg)
    bank, losses = pool.train_many_stacked([0, 2], w0, seed=1)
    assert isinstance(losses, jax.Array)     # no np.asarray block
    assert np.isfinite(np.asarray(losses)).all()
    # fused protocol present and consistent with the stacked call
    fn = pool.epoch_train_fn()
    ids = np.array([0, 2], np.int32)
    stacked, l2 = fn(w0, jax.tree.map(jnp.asarray, pool.epoch_inputs(ids)),
                     jnp.asarray(ids), jnp.uint32(1))
    np.testing.assert_allclose(np.asarray(l2), np.asarray(losses),
                               atol=1e-6)


def test_history_accuracy_finalized_to_float():
    class Ev:
        def eval_async(self, params):
            return jnp.mean(params["w"])     # device scalar

        def __call__(self, params):
            return float(self.eval_async(params))

    sim = SimConfig(duration_s=86400.0, train_time_s=300.0,
                    use_model_bank=True, use_fused_step=True)
    fls = FLSimulation(get_strategy("asyncfleo-twohap"),
                       TinyFusedTrainer(W0), Ev(), sim)
    hist = fls.run(W0, max_epochs=2)
    assert len(hist) >= 1
    assert all(isinstance(r.accuracy, float) for r in hist)


# ---- multi-device sharding (subprocess: device count locks at jax init) ---

MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.epoch_step import EpochStepProgram, bank_sharding
    from repro.core.modelbank import FlatSpec, flatten_tree
    from repro.launch.mesh import make_host_mesh

    assert len(jax.devices()) == 4
    w0 = {"w": np.arange(24, dtype=np.float32).reshape(4, 6),
          "b": np.ones(8, np.float32)}
    spec = FlatSpec.of(w0)

    def train_fn(params, inputs, ids, seed):
        flat = flatten_tree(params)
        offs = ((ids * 37 + seed.astype(jnp.int32)) % 11
                - 5).astype(jnp.float32) * 0.01
        stack = flat[None, :] * 0.9 + offs[:, None] + inputs[:, None]
        return stack, offs

    mesh = make_host_mesh(data=4, model=1)
    C, cap, K = 8, 4, 2
    ids = np.arange(C, dtype=np.int32)
    inputs = np.linspace(0.0, 1.0, C).astype(np.float32)
    wv = np.linspace(0.1, 0.2, C).astype(np.float32)
    wc = np.zeros(cap, np.float32)
    carry = jnp.zeros((cap, spec.num_params), jnp.float32)
    dw_row = np.full(C, 0.25, np.float32)
    dw_seg = np.array([0] * 4 + [1] * 4, np.int32)
    dwc = np.zeros((K, cap), np.float32)
    ref = jnp.zeros(spec.num_params)

    outs = {}
    for name, m in (("single", None), ("mesh", mesh)):
        prog = EpochStepProgram(spec, train_fn, mesh=m)
        w = spec.flatten(w0)
        new_w, stack, dists, losses = prog.step(
            w, carry, jnp.asarray(inputs), ids, 7, wv, wc, 0.5,
            dw_row, dw_seg, K, 0, dwc, ref)
        outs[name] = (np.asarray(new_w), np.asarray(stack),
                      np.asarray(dists))
        if name == "mesh":
            # the bank's NamedSharding spec is actually applied
            assert stack.sharding.is_equivalent_to(bank_sharding(mesh),
                                                   stack.ndim), \
                stack.sharding
            assert w.is_deleted()             # donation holds under the mesh
    for a, b in zip(outs["single"], outs["mesh"]):
        np.testing.assert_allclose(a, b, atol=1e-5)

    # the simulator's explicit input copy lands in the layout the mesh
    # program compiles for, so the call does not reshard it
    from repro.core.epoch_step import put_inputs
    put = put_inputs(inputs, mesh, C)
    compiled = prog._step.lower(
        spec.flatten(w0), carry, put, jnp.asarray(ids), np.uint32(7),
        jnp.asarray(wv), jnp.asarray(wc), np.float32(0.5),
        jnp.asarray(dw_row), jnp.asarray(dw_seg), K, 0, jnp.asarray(dwc),
        ref).compile()
    assert put.sharding.spec == ("data",), put.sharding
    assert compiled.input_shardings[0][2].is_equivalent_to(put.sharding,
                                                           put.ndim)

    # end-to-end: a full simulation on the data mesh matches the
    # single-device run epoch for epoch
    from test_epoch_step import TinyFusedTrainer, W0
    from repro.core import FLSimulation, SimConfig
    from repro.fl import get_strategy
    from repro.launch.mesh import make_data_mesh

    rows = {}
    for label, mesh_arg in (("single", None), ("mesh", make_data_mesh())):
        sim = SimConfig(duration_s=86400.0, train_time_s=300.0,
                        use_model_bank=True, use_fused_step=True,
                        mesh=mesh_arg)
        fls = FLSimulation(get_strategy("asyncfleo-twohap"),
                           TinyFusedTrainer(W0), None, sim)
        hist = fls.run(W0, max_epochs=3)
        rows[label] = [(r.epoch, round(r.time_s, 6), r.num_models,
                        round(r.gamma, 6)) for r in hist]
        assert fls._fused_prog.dispatches == len(hist)
    assert rows["single"] == rows["mesh"]
    print("SHARDED-OK")
""")


def test_epoch_program_multi_device_sharding():
    here = os.path.dirname(__file__)
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(here, "..", "src"), here]))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", MULTI_DEVICE_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARDED-OK" in proc.stdout
