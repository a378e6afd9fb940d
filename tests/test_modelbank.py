"""Stacked (ModelBank) vs legacy pytree parity for the server hot path.

Every aggregation and grouping entry point must produce allclose results
whether models arrive as host pytrees or as one device-resident (C, N)
stack — including the strict_paper_eq14 and stale-only-group branches and
the segmented (multi-matrix) simulator path.
"""
import numpy as np
import pytest

from repro.core.aggregation import (SatelliteMeta, asyncfleo_aggregate,
                                    combine_stacked, dedup, dedup_indices,
                                    fedavg, weighted_sum)
from repro.core.grouping import GroupingState, partial_global_model
from repro.core.modelbank import FlatSpec, ModelBank


def _models(vals):
    rng = np.random.default_rng(0)
    out = []
    for v in vals:
        out.append({"w": np.full((3, 4), v, np.float32),
                    "b": np.full((5,), -v, np.float32),
                    "nested": {"k": (v * rng.standard_normal(7)).astype(np.float32)}})
    return out


def _meta(sid, size=100.0, epoch=0, ts=0.0):
    return SatelliteMeta(sid, size, (0.0, 0.0), ts, epoch)


def _assert_tree_close(a, b, atol=1e-5):
    import jax
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=atol)


# ---- FlatSpec / ModelBank roundtrips --------------------------------------

def test_flatspec_roundtrip():
    m = _models([1.5])[0]
    spec = FlatSpec.of(m)
    flat = spec.flatten(m)
    assert flat.shape == (spec.num_params,)
    _assert_tree_close(spec.unflatten(flat), m, atol=0)
    _assert_tree_close(spec.unflatten_host(flat), m, atol=0)


def test_modelbank_roundtrip_and_select():
    models = _models([0.0, 1.0, 2.0, 3.0])
    bank = ModelBank.from_pytrees(models)
    assert len(bank) == 4
    back = bank.to_pytrees()
    for m, b in zip(models, back):
        _assert_tree_close(m, b, atol=0)
    sub = bank.select([3, 1])
    _assert_tree_close(sub.pytree(0), models[3], atol=0)
    _assert_tree_close(sub.pytree(1), models[1], atol=0)


def test_spec_cache_reuse():
    a, b = _models([1.0, 2.0])
    assert FlatSpec.of(a) is FlatSpec.of(b)


# ---- aggregation parity ----------------------------------------------------

def test_fedavg_parity():
    models = _models([0.0, 1.0, 5.0])
    sizes = [100, 300, 50]
    bank = ModelBank.from_pytrees(models)
    legacy = fedavg(models, sizes)
    stacked = bank.spec.unflatten(fedavg(bank, sizes))
    _assert_tree_close(legacy, stacked)


def test_weighted_sum_parity_with_base():
    models = _models([2.0, -1.0])
    base = _models([7.0])[0]
    bank = ModelBank.from_pytrees(models)
    legacy = weighted_sum(models, [0.3, 0.4], base=base, base_weight=0.3)
    stacked = bank.spec.unflatten(
        weighted_sum(bank, [0.3, 0.4], base=base, base_weight=0.3))
    _assert_tree_close(legacy, stacked)


def test_weighted_sum_kernel_parity():
    models = _models([2.0, -1.0, 0.5])
    base = _models([7.0])[0]
    bank = ModelBank.from_pytrees(models)
    legacy = weighted_sum(models, [0.3, 0.4, 0.1], base=base, base_weight=0.2)
    stacked = bank.spec.unflatten(
        weighted_sum(bank, [0.3, 0.4, 0.1], base=base, base_weight=0.2,
                     use_kernel=True))
    _assert_tree_close(legacy, stacked)


@pytest.mark.parametrize("strict", [False, True])
def test_asyncfleo_parity_mixed_freshness(strict):
    models = _models([1.0, 3.0, -2.0, 0.5, 4.0])
    metas = [_meta(0, 100, epoch=5), _meta(1, 200, epoch=5),
             _meta(2, 150, epoch=2), _meta(3, 50, epoch=1),
             _meta(4, 120, epoch=5)]
    groups = {0: [0, 2], 1: [1, 4], 2: [3]}   # group 2 is stale-only
    w_prev = _models([0.25])[0]
    bank = ModelBank.from_pytrees(models)
    legacy, info_l = asyncfleo_aggregate(w_prev, groups, models, metas, beta=5,
                                         strict_paper_eq14=strict)
    flat, info_s = asyncfleo_aggregate(w_prev, groups, bank, metas, beta=5,
                                       strict_paper_eq14=strict)
    assert info_l == info_s
    assert info_l["stale_groups"] == 1
    _assert_tree_close(legacy, bank.spec.unflatten(flat))


def test_asyncfleo_parity_stale_only():
    models = _models([2.0, -1.0])
    metas = [_meta(0, 100, epoch=1), _meta(1, 50, epoch=2)]
    w_prev = _models([5.0])[0]
    bank = ModelBank.from_pytrees(models)
    legacy, info_l = asyncfleo_aggregate(w_prev, {0: [0, 1]}, models, metas,
                                         beta=6)
    flat, info_s = asyncfleo_aggregate(w_prev, {0: [0, 1]}, bank, metas,
                                       beta=6)
    assert info_l == info_s
    assert 0.0 < info_l["gamma"] < 1.0
    _assert_tree_close(legacy, bank.spec.unflatten(flat))


def test_dedup_parity():
    models = _models([1.0, 2.0, 3.0])
    metas = [_meta(7, ts=1.0), _meta(7, ts=5.0), _meta(8, ts=2.0)]
    bank = ModelBank.from_pytrees(models)
    m_l, t_l = dedup(models, metas)
    b_s, t_s = dedup(bank, metas)
    assert [m.sat_id for m in t_l] == [m.sat_id for m in t_s]
    assert dedup_indices(metas) == [1, 2]
    for i in range(len(m_l)):
        _assert_tree_close(m_l[i], b_s.pytree(i), atol=0)


def test_combine_stacked_kernel_parity():
    models = _models([1.0, -2.0, 0.5])
    weights = np.array([0.2, 0.3, 0.1], np.float32)
    base = _models([4.0])[0]
    bank = ModelBank.from_pytrees(models)
    bflat = bank.spec.flatten(base)
    xla = combine_stacked([(bank.stack, weights)], bflat, 0.4)
    pallas = combine_stacked([(bank.stack, weights)], bflat, 0.4,
                             use_kernel=True)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(pallas), atol=1e-5)
    # split across two segments, kernel-chained
    a, b = ModelBank.from_pytrees(models[:1]), ModelBank.from_pytrees(models[1:])
    pallas2 = combine_stacked([(a.stack, weights[:1]), (b.stack, weights[1:])],
                              bflat, 0.4, use_kernel=True)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(pallas2),
                               atol=1e-5)


def test_pad_ids_empty():
    from repro.fl.client import _pad_ids
    ids, n = _pad_ids([])
    assert n == 0 and len(ids) == 0


_COUNTS = np.arange(1, 10_001)


def _buckets():
    from repro.core.modelbank import bucket_rows
    return np.array([bucket_rows(int(n)) for n in _COUNTS])


def _per_octave(b):
    # octave k holds the counts in (2^(k-1), 2^k]
    octave = np.array([int(n - 1).bit_length() for n in _COUNTS])
    return max(len(set(b[octave == k])) for k in set(octave))


_BUCKET_RULES = {
    "at_least_n": lambda b: np.all(b >= _COUNTS),
    "pow2_up_to_8": lambda b: list(b[:8]) == [1, 2, 4, 4, 8, 8, 8, 8],
    "multiple_of_8_above_8": lambda b: np.all(b[8:] % 8 == 0),
    "pad_under_8_rows_to_64": lambda b: np.all((b - _COUNTS)[:64] < 8),
    "pad_at_most_one_eighth_above_64":
        lambda b: np.all((b - _COUNTS)[64:] * 8 <= _COUNTS[64:]),
    "at_most_8_buckets_an_octave": lambda b: _per_octave(b) <= 8,
    "paper_40_and_10k": lambda b: b[39] == 40 and b[9_999] == 10_240,
}


@pytest.mark.parametrize("rule", sorted(_BUCKET_RULES))
def test_bucket_rows_rule(rule):
    """The participant-row bucket over n = 1..10,000: never below n, powers
    of two up to 8, multiples of 8 above (the 4-chip split), little
    padding, and few buckets, so few traces."""
    assert _BUCKET_RULES[rule](_buckets())


def test_pad_bucket_ids_repeats_the_first_id():
    from repro.core.modelbank import pad_bucket_ids
    ids, n = pad_bucket_ids([5, 3, 9] + list(range(10, 50)))
    assert n == 43 and len(ids) == 48
    assert list(ids[:n]) == [5, 3, 9] + list(range(10, 50))
    assert set(ids[n:].tolist()) == {5}


def test_combine_stacked_segments_match_single_bank():
    """Models split over two device matrices combine identically to one."""
    models = _models([1.0, -2.0, 0.5, 3.0])
    weights = np.array([0.1, 0.2, 0.3, 0.15])
    base = _models([4.0])[0]
    bank = ModelBank.from_pytrees(models)
    whole = weighted_sum(bank, weights, base=base, base_weight=0.25)
    a = ModelBank.from_pytrees(models[:2])
    b = ModelBank.from_pytrees(models[2:])
    split = combine_stacked([(a.stack, weights[:2]), (b.stack, weights[2:])],
                            bank.spec.flatten(base), 0.25)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(split),
                               atol=1e-5)


# ---- grouping parity -------------------------------------------------------

def test_partial_global_model_parity():
    models = _models([0.0, 1.0, 4.0])
    sizes = [1.0, 3.0, 2.0]
    bank = ModelBank.from_pytrees(models)
    legacy = partial_global_model(models, sizes)
    flat = partial_global_model(bank, sizes)
    _assert_tree_close(legacy, bank.spec.unflatten(flat))


def test_observe_orbit_parity():
    w0 = _models([0.0])[0]
    models = _models([0.1, 0.2, 5.0, 5.2, 9.0, 9.1])
    sizes = [1.0] * 6
    orbit_rows = {0: [0, 1], 1: [2, 3], 2: [4, 5]}
    bank = ModelBank.from_pytrees(models)

    gs_l = GroupingState(num_groups=2)
    gs_l.set_reference(w0)
    gs_s = GroupingState(num_groups=2)
    gs_s.set_reference(w0)
    for orbit, rows in orbit_rows.items():
        gl = gs_l.observe_orbit(orbit, [models[j] for j in rows],
                                [sizes[j] for j in rows])
        st = gs_s.observe_orbit(orbit, bank.select(rows),
                                [sizes[j] for j in rows])
        assert gl == st
    for o, d in gs_l.distances.items():
        assert gs_s.distances[o] == pytest.approx(d, rel=1e-5)
    assert gs_l.groups == gs_s.groups


def test_observe_orbits_batched_matches_sequential():
    w0 = _models([0.0])[0]
    models = _models([0.1, 0.2, 5.0, 5.2, 9.0, 9.1])
    bank = ModelBank.from_pytrees(models)
    sizes = [1.0, 2.0, 1.0, 1.0, 3.0, 1.0]
    orbit_rows = {0: [0, 1], 1: [2, 3], 2: [4, 5]}

    gs_seq = GroupingState(num_groups=2)
    gs_seq.set_reference(w0)
    seq = {o: gs_seq.observe_orbit(o, [models[j] for j in rows],
                                   [sizes[j] for j in rows])
           for o, rows in orbit_rows.items()}
    gs_b = GroupingState(num_groups=2)
    gs_b.set_reference(w0)
    batched = gs_b.observe_orbits(orbit_rows, bank, sizes)
    assert seq == batched
    assert gs_seq.groups == gs_b.groups

    # multi-segment form (models split across two matrices) agrees too
    gs_m = GroupingState(num_groups=2)
    gs_m.set_reference(w0)
    a = ModelBank.from_pytrees(models[:4])
    b = ModelBank.from_pytrees(models[4:])
    rows_a = [0, 1, 2, 3, -1, -1]
    rows_b = [-1, -1, -1, -1, 0, 1]
    multi = gs_m.observe_orbits_multi(orbit_rows,
                                      [(a.stack, rows_a), (b.stack, rows_b)],
                                      sizes)
    assert multi == seq
    assert gs_m.groups == gs_seq.groups


# ---- simulator end-to-end parity ------------------------------------------

class _TinyTrainer:
    """Deterministic stacked/legacy trainer: model + per-sat offset."""

    def __init__(self, w0):
        self.spec = FlatSpec.of(w0)

    def data_size(self, sat):
        return 100 + (sat % 5) * 10

    def train_many_stacked(self, sats, params, seed):
        import jax.numpy as jnp
        flat = self.spec.flatten(params)
        offs = jnp.asarray([(s * 37 + seed) % 11 - 5 for s in sats],
                           jnp.float32) * 0.01
        stack = flat[None, :] * 0.9 + offs[:, None]
        return ModelBank(self.spec, stack), np.zeros(len(sats))

    def train_many(self, sats, params, seed):
        bank, losses = self.train_many_stacked(sats, params, seed)
        return bank.to_pytrees(), losses


@pytest.mark.parametrize("name", ["asyncfleo-twohap", "fedhap", "fedsat",
                                  "fedspace"])
def test_simulation_stacked_matches_legacy(name):
    from repro.core import FLSimulation, SimConfig
    from repro.fl import get_strategy

    w0 = {"w": np.zeros((6,), np.float32), "b": np.ones((3,), np.float32)}
    histories = {}
    for use_bank in (False, True):
        sim = SimConfig(duration_s=86400.0, train_time_s=300.0,
                        use_model_bank=use_bank)
        fls = FLSimulation(get_strategy(name), _TinyTrainer(w0),
                           None, sim)
        hist = fls.run(w0, max_epochs=3)
        histories[use_bank] = [(r.epoch, round(r.time_s, 6), r.num_models,
                                round(r.gamma, 6), r.stale_groups)
                               for r in hist]
    assert histories[False] == histories[True]


def test_simulation_stacked_final_model_matches_legacy():
    from repro.core import FLSimulation, SimConfig
    from repro.fl import get_strategy

    w0 = {"w": np.full((6,), 0.5, np.float32), "b": np.ones((3,), np.float32)}
    evals = {}
    for use_bank in (False, True):
        seen = []

        def evaluator(params, seen=seen):
            seen.append(np.concatenate(
                [np.ravel(np.asarray(l)) for l in
                 (params["w"], params["b"])]))
            return 0.0

        sim = SimConfig(duration_s=86400.0, train_time_s=300.0,
                        use_model_bank=use_bank)
        fls = FLSimulation(get_strategy("asyncfleo-twohap"),
                           _TinyTrainer(w0), evaluator, sim)
        fls.run(w0, max_epochs=3)
        evals[use_bank] = seen
    assert len(evals[False]) == len(evals[True]) > 0
    for a, b in zip(evals[False], evals[True]):
        np.testing.assert_allclose(a, b, atol=1e-5)
