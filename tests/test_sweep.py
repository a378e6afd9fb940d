"""Differential tests for the batched scenario engine (DESIGN.md §13).

The headline contract: for any scenario batch, the batched engine's
per-scenario histories, final weights and logical dispatch counts are
BIT-IDENTICAL to running each scenario sequentially through the
event-driven runtime.  ``assert_batched_parity`` is the one shared
checker — the hypothesis property in ``test_property.py`` drives it
with randomly drawn axes; the cases here pin named regressions and the
engine's own machinery (grid/draw compiler, percentile reduction,
dispatch economy, error propagation, determinism).
"""
import numpy as np
import pytest

from repro.sweep import (ConvergingTrainer, DispatchBatcher,
                         MeanDistanceEvaluator, ScenarioSpec, draw,
                         draw_spec, grid, make_model, percentile_bands,
                         reduce_results, run_scenarios)

# small-but-real default: 8 sats over 2 orbits, a 4 h horizon
BASE = ScenarioSpec(num_orbits=2, sats_per_orbit=4, duration_s=4 * 3600.0,
                    dt_s=60.0, train_time_s=300.0)
W0 = make_model()


def _hist_key(hist):
    return [(r.epoch, r.time_s, r.accuracy, r.num_models, r.gamma,
             r.stale_groups) for r in hist]


def assert_batched_parity(specs, max_epochs=3, target=0.9, mode="exact",
                          batcher=None, trainer_factory=None):
    """Run ``specs`` sequentially and batched; assert bit-identical
    per-scenario histories, weights and dispatch counts.  Returns
    (sequential, batched, batcher) for callers that inspect more;
    ``trainer_factory`` builds the batched run's trainer."""
    seq = run_scenarios(specs, W0, batched=False, max_epochs=max_epochs,
                        target_accuracy=target)
    batcher = batcher or DispatchBatcher(mode=mode)
    bat = run_scenarios(specs, W0, batched=True, max_epochs=max_epochs,
                        target_accuracy=target, batcher=batcher,
                        trainer_factory=trainer_factory)
    for s, b in zip(seq, bat):
        assert _hist_key(s.history) == _hist_key(b.history), s.spec
        assert np.array_equal(s.final_weights, b.final_weights), s.spec
        assert (s.dispatches, s.fallback_dispatches) == \
            (b.dispatches, b.fallback_dispatches), s.spec
        assert s.convergence_delay_s == b.convergence_delay_s, s.spec
        assert s.stats == b.stats, s.spec
    return seq, bat, batcher


# ---- scenario compiler -----------------------------------------------------

def test_grid_is_sorted_cartesian_product():
    specs = grid(BASE, seed=[0, 1], strategy=["asyncfleo-gs", "fedisl"])
    assert len(specs) == 4
    # axes sorted by name: seed outer, strategy inner
    assert [(s.seed, s.strategy) for s in specs] == [
        (0, "asyncfleo-gs"), (0, "fedisl"),
        (1, "asyncfleo-gs"), (1, "fedisl")]
    assert all(s.num_orbits == 2 for s in specs)   # base preserved


def test_grid_rejects_unknown_axis():
    with pytest.raises(ValueError, match="unknown scenario axes"):
        grid(BASE, not_a_field=[1])
    with pytest.raises(ValueError, match="no values"):
        grid(BASE, seed=[])


def test_draw_is_seed_deterministic():
    axes = {"seed": [0, 1, 2, 3], "rate_bps": [16e6, 1e5],
            "strategy": ["asyncfleo-gs", "fedasync"]}
    a = draw(6, axes, seed=7, base=BASE)
    b = draw(6, axes, seed=7, base=BASE)
    assert a == b
    assert draw(6, axes, seed=8, base=BASE) != a
    assert all(s.rate_bps in axes["rate_bps"] for s in a)
    spec = draw_spec(axes, seed=7, n=6)
    assert spec["kind"] == "draw" and spec["n"] == 6
    assert list(spec["axes"]) == sorted(axes)      # JSON-stable order


def test_draw_rejects_bad_n():
    with pytest.raises(ValueError, match="n >= 1"):
        draw(0, {"seed": [1]})


# ---- percentile reduction --------------------------------------------------

def test_percentile_bands_values_and_failures():
    bands = percentile_bands([10.0, 20.0, 30.0, None])
    assert bands["n"] == 4 and bands["n_failed"] == 1
    assert bands["p50"] == 20.0
    assert bands["p10"] == pytest.approx(12.0)
    assert bands["p90"] == pytest.approx(28.0)


def test_percentile_bands_all_failed():
    bands = percentile_bands([None, None])
    assert bands["n"] == 2 and bands["n_failed"] == 2
    assert bands["p10"] is bands["p50"] is bands["p90"] is None


# ---- differential parity ---------------------------------------------------

def test_parity_seed_batch():
    specs = grid(BASE, seed=[0, 1, 2, 3])
    _, _, batcher = assert_batched_parity(specs)
    # homogeneous scenarios share every dispatch: one program per epoch
    assert batcher.physical_dispatches < 4 * batcher.max_group
    assert batcher.max_group == 4


def test_parity_heterogeneous_axes():
    """Mixed strategies (incl. sync barrier + pipelined), geometries,
    link rates and staleness functions in ONE batch."""
    axes = {
        "seed": [0, 3],
        "num_orbits": [2, 3],
        "rate_bps": [16e6, 1e5],
        "strategy": ["asyncfleo-gs", "fedisl", "asyncfleo-pipelined"],
        "staleness_fn": ["eq13", "poly"],
    }
    specs = draw(6, axes, seed=11, base=BASE)
    assert_batched_parity(specs)


def test_parity_fedasync_per_arrival():
    # per-arrival EMA commits: many more (solo-sized) dispatches
    specs = grid(BASE, seed=[0, 1], strategy=["fedasync"])
    _, bat, _ = assert_batched_parity(specs, max_epochs=6)
    assert all(r.epochs > 0 for r in bat)


def test_parity_trainer_without_batch_key_runs_solo():
    """A trainer with no scenario_batch_key must still be correct —
    every dispatch routes solo through its own program."""
    class KeylessTrainer(ConvergingTrainer):
        def __init__(self, w0):
            super().__init__(w0)
            del self.scenario_batch_key

    specs = grid(BASE, seed=[0, 1])
    seq = run_scenarios(specs, W0, batched=False, max_epochs=3,
                        target_accuracy=0.9,
                        trainer_factory=lambda w0: KeylessTrainer(w0))
    batcher = DispatchBatcher()
    bat = run_scenarios(specs, W0, batched=True, max_epochs=3,
                        target_accuracy=0.9,
                        trainer_factory=lambda w0: KeylessTrainer(w0),
                        batcher=batcher)
    for s, b in zip(seq, bat):
        assert _hist_key(s.history) == _hist_key(b.history)
        assert np.array_equal(s.final_weights, b.final_weights)
    assert batcher.batched_dispatches == 0          # nothing grouped
    assert batcher.solo_dispatches == batcher.physical_dispatches > 0


def test_batched_run_is_deterministic():
    specs = draw(5, {"seed": [0, 1, 2], "strategy":
                     ["asyncfleo-gs", "fedisl"]}, seed=3, base=BASE)
    a = run_scenarios(specs, W0, batched=True, max_epochs=3,
                      target_accuracy=0.9)
    b = run_scenarios(specs, W0, batched=True, max_epochs=3,
                      target_accuracy=0.9)
    for ra, rb in zip(a, b):
        assert _hist_key(ra.history) == _hist_key(rb.history)
        assert np.array_equal(ra.final_weights, rb.final_weights)
        assert ra.dispatches == rb.dispatches


def test_vmap_mode_is_close_not_required_exact():
    """The opt-in vmap mode trades bit-exactness for one batched GEMM:
    results must stay allclose to sequential (documented non-exact)."""
    specs = grid(BASE, seed=[0, 1, 2])
    seq = run_scenarios(specs, W0, batched=False, max_epochs=3,
                        target_accuracy=0.9)
    bat = run_scenarios(specs, W0, batched=True, mode="vmap",
                        max_epochs=3, target_accuracy=0.9)
    for s, b in zip(seq, bat):
        assert len(s.history) == len(b.history)
        np.testing.assert_allclose(s.final_weights, b.final_weights,
                                   atol=1e-4)


# ---- dispatch economy ------------------------------------------------------

def test_dispatch_economy_small():
    specs = grid(BASE, seed=list(range(6)))
    _, bat, batcher = assert_batched_parity(specs)
    logical = sum(r.dispatches + r.fallback_dispatches for r in bat)
    assert batcher.physical_dispatches < logical
    summary = batcher.summary()
    assert summary["physical_dispatches"] == batcher.physical_dispatches
    assert summary["mode"] == "exact"


@pytest.mark.slow
def test_dispatch_economy_64_scenarios():
    """The acceptance-criteria sweep: 64 scenarios complete in fewer
    physical fused dispatches than 64 sequential runs, counted by the
    shared fused program's own counters, with per-scenario parity
    intact."""
    specs = grid(BASE, seed=list(range(32)),
                 strategy=["asyncfleo-gs", "fedisl"])
    assert len(specs) == 64
    shared = ConvergingTrainer(W0)
    batcher = DispatchBatcher()
    _, bat, _ = assert_batched_parity(specs, max_epochs=3,
                                      batcher=batcher,
                                      trainer_factory=lambda _w0: shared)
    logical = sum(r.dispatches + r.fallback_dispatches for r in bat)
    progs = shared._epoch_programs.values()
    physical = sum(p.dispatches + p.fallback_dispatches
                   + p.batched_dispatches for p in progs)
    # the programs saw every physical program launch, and batching won
    assert physical == batcher.physical_dispatches
    assert batcher.physical_dispatches < logical
    assert batcher.max_group >= 32


# ---- failure handling ------------------------------------------------------

def test_worker_error_propagates():
    class ExplodingEvaluator(MeanDistanceEvaluator):
        def __call__(self, params):
            raise RuntimeError("boom")

    specs = grid(BASE, seed=[0, 1])
    with pytest.raises(RuntimeError, match="scenario"):
        run_scenarios(specs, W0, batched=True, max_epochs=2,
                      target_accuracy=0.9,
                      evaluator_factory=ExplodingEvaluator)


# ---- seed-determinism regression (sched_bench-equivalent runs) -------------

def _bench_equivalent_run(seed: int):
    """One sched_bench-style traced run (paper constellation, the PR 3
    head-to-head config at a shorter horizon), as `_run_policy` builds
    it; returns (history keys, stats, trace span count, weights)."""
    from repro.core import FLSimulation, SimConfig
    from repro.fl.strategies import get_strategy
    from repro.obs import Tracer
    from repro.sched import EventDrivenRuntime

    tracer = Tracer()
    sim = SimConfig(duration_s=86400.0, dt_s=30.0, train_time_s=300.0,
                    event_driven=True, seed=seed, tracer=tracer)
    fls = FLSimulation(get_strategy("asyncfleo-gs"), ConvergingTrainer(W0),
                       MeanDistanceEvaluator(), sim)
    rt = EventDrivenRuntime(fls)
    hist = rt.run(W0, max_epochs=4, target_accuracy=0.9)
    return (_hist_key(hist), dict(rt.stats), len(tracer.spans),
            np.asarray(fls._w_flat))


def test_seed_determinism_regression():
    """Two sched_bench-equivalent runs with the same seed produce
    identical histories, stats and trace span counts — the determinism
    the sweep engine (and every band row) rides on."""
    h1, s1, n1, w1 = _bench_equivalent_run(seed=0)
    h2, s2, n2, w2 = _bench_equivalent_run(seed=0)
    assert h1 == h2
    assert s1 == s2
    assert n1 == n2
    assert np.array_equal(w1, w2)


def test_parity_trainer_with_epoch_inputs():
    """Trainers whose ``epoch_inputs`` carries per-participant arrays
    batch too: the batcher stacks every batch leaf along the scenario
    axis and parity must still be exact."""
    import jax.numpy as jnp

    class InputsTrainer(ConvergingTrainer):
        def __init__(self, w0):
            super().__init__(w0)
            self.scenario_batch_key = ("inputs-converging",)

        def epoch_inputs(self, ids_np):
            return jnp.asarray(np.asarray(ids_np, np.float32) % 3.0)

        def epoch_train_fn(self):
            rate, jitter = self._rate, self._jitter

            def _fn(params, inputs, ids, seed):
                from repro.core.modelbank import flatten_tree
                flat = flatten_tree(params)
                phase = ((ids * 37 + seed.astype(jnp.int32)) % 13
                         - 6).astype(jnp.float32) * jitter
                stack = (flat[None, :] * (1.0 - rate) + rate
                         + phase[:, None] + inputs[:, None] * 1e-4)
                return stack, jnp.zeros(ids.shape[0])
            return _fn

        def train_many_stacked(self, sats, params, seed):
            from repro.core.modelbank import ModelBank, pad_bucket_ids
            ids, n = pad_bucket_ids(list(sats))
            fn = self.epoch_train_fn()
            stack, _ = fn(params, self.epoch_inputs(ids),
                          jnp.asarray(ids), jnp.uint32(np.uint32(seed)))
            return ModelBank(self.spec, stack[:n]), np.zeros(n)

    specs = grid(BASE, seed=[0, 1, 2])
    seq = run_scenarios(specs, W0, batched=False, max_epochs=3,
                        target_accuracy=0.9,
                        trainer_factory=lambda w0: InputsTrainer(w0))
    batcher = DispatchBatcher()
    bat = run_scenarios(specs, W0, batched=True, max_epochs=3,
                        target_accuracy=0.9,
                        trainer_factory=lambda w0: InputsTrainer(w0),
                        batcher=batcher)
    for s, b in zip(seq, bat):
        assert _hist_key(s.history) == _hist_key(b.history)
        assert np.array_equal(s.final_weights, b.final_weights)
    assert batcher.batched_dispatches > 0    # inputs batched, not solo'd


def test_parity_strategy_knob_overrides():
    """ScenarioSpec's ps_channels / max_in_flight / staleness_fn
    overrides reach the StrategySpec and stay parity-exact."""
    specs = [
        ScenarioSpec(num_orbits=2, sats_per_orbit=4, duration_s=4 * 3600.0,
                     dt_s=60.0, train_time_s=300.0, seed=1,
                     strategy="asyncfleo-pipelined", ps_channels=1,
                     max_in_flight=2, staleness_fn="hinge",
                     rate_bps=1e5),
        ScenarioSpec(num_orbits=2, sats_per_orbit=4, duration_s=4 * 3600.0,
                     dt_s=60.0, train_time_s=300.0, seed=2,
                     strategy="asyncfleo-gs", ps_channels=2),
    ]
    assert_batched_parity(specs)
