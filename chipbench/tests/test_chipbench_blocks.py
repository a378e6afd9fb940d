"""The reference in bounded memory, on the CPU at ``test_chipbench_correct``'s
size: a round trained in chunks of participants, each folded
at once into the size-weighted sums of its blocks, gives the lap that the
whole round in one call gives, for MNIST_CNN under AsyncFLEO and FedAsync
and for the test-only MNIST_MLP kind; folding alone is exact to float64
rounding; and no more trained models are alive at once than the chunk
that ``reference.CHUNK_BYTES`` holds.
"""
import os
import shutil
import sys
import weakref

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import harness, reference  # noqa: E402

KINDS_DATA = os.path.join(ROOT, "chipbench", "tests", "data", "kinds")
SEED = 2 ** 33 + 17
CASES = {"mnist-asyncfleo": ("paper-mnist.asyncfleo", 6400.0),
         "mnist-fedasync": ("paper-mnist.fedasync", 1500.0),
         "mlp-asyncfleo": (None, 6400.0)}


def _mlp_root(tmp):
    """A root holding the MLP kind's files and the commit rules."""
    bench_dir = os.path.join(tmp, "chipbench")
    for sub in ("models", "configs", "mixes"):
        shutil.copytree(os.path.join(KINDS_DATA, sub),
                        os.path.join(bench_dir, sub))
    shutil.copytree(os.path.join(ROOT, "chipbench", "rules"),
                    os.path.join(bench_dir, "rules"))
    return tmp


def _case(name, tmp_factory):
    """(config, spec, run seed, root) at the test size."""
    cell_name, horizon = CASES[name]
    if cell_name is None:
        import json
        root = _mlp_root(str(tmp_factory.mktemp("mlp")))
        cfg = json.load(open(os.path.join(root, "chipbench", "configs",
                                          "paper-mnist-mlp.json")))
        spec = json.load(open(os.path.join(root, "chipbench", "mixes",
                                           "asyncfleo-mlp.json")))["spec"]
    else:
        root = ROOT
        cell = harness.load_cell(cell_name)
        cfg, spec = cell.config, cell.mix["spec"]
    cfg = dict(cfg, horizon_s=horizon, local_iters=2)
    return cfg, spec, harness.run_seed(SEED, cfg, root), root


@pytest.fixture(scope="module")
def laps(tmp_path_factory):
    """Per case: the whole-round lap and the lap in chunks of one."""
    out = {}
    for name in CASES:
        cfg, spec, rs, root = _case(name, tmp_path_factory)
        whole = reference.lap(cfg, spec, rs, local_iters=2, root=root)
        one = reference.lap(cfg, spec, rs, local_iters=2, root=root,
                            chunk=1)
        out[name] = (cfg, spec, root, whole, one)
    return out


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-300))


@pytest.mark.parametrize("name", list(CASES))
def test_chunks_of_one_give_the_whole_round_lap(laps, name):
    cfg, _spec, _root, whole, one = laps[name]
    assert one.schedule == whole.schedule
    assert (one.participants, one.used, one.sizes) == (
        whole.participants, whole.used, whole.sizes)
    assert len(one.commits) == len(whole.commits) >= reference.MIN_COMMITS
    for a, b in zip(one.commits, whole.commits):
        assert (a.t_agg, a.models, a.weights, a.base_weight, a.gamma,
                a.stale_groups, a.groups) == (
            b.t_agg, b.models, b.weights, b.base_weight, b.gamma,
            b.stale_groups, b.groups)
        for k, v in b.change.items():
            assert abs(a.change[k] - v) <= 1e-6 * v, k
    assert _rel(one.commits[0].w, whole.commits[0].w) < 1e-6
    assert set(one.dists) == set(whole.dists)
    for o, d in whole.dists.items():
        assert abs(one.dists[o] - d) <= 1e-6 * d
    assert set(one.blocks) == set(whole.blocks)
    for key, b in whole.blocks.items():
        assert sorted(one.blocks[key].sats) == sorted(b.sats)
        assert _rel(one.blocks[key].sum, b.sum) < 1e-6
    for k, v in whole.rows.items():
        assert _rel(one.rows[k], v) < 1e-6
    if "fedasync" in name:
        assert all(len(b.sats) == 1 for b in whole.blocks.values())
    else:
        N = cfg["constellation"]["sats_per_orbit"]
        assert all(s // N == key[0] for key, b in whole.blocks.items()
                   for s in b.sats)


@pytest.mark.parametrize("name", list(CASES))
def test_folding_is_exact(laps, name):
    """The same rows folded in one call and one row at a time, and eq. 14
    and the distances from the sums against the rows one by one."""
    cfg, spec, root, whole, _one = laps[name]
    N = cfg["constellation"]["sats_per_orbit"]
    by_orbit = reference.load_rule(spec["agg_mode"], root).BLOCKS == "orbit"
    rows = {k: np.asarray(v) for k, v in whole.rows.items()}
    flat = np.stack([reference.flatten({k: v[i] for k, v in rows.items()})
                     for i in range(len(whole.participants))])
    keys = [reference.block_key(s, s in whole.used, N, by_orbit)
            for s in whole.participants]
    single = {}
    for i, s in enumerate(whole.participants):
        reference.fold(single, [keys[i]], [s], flat[i:i + 1], whole.sizes)
    at_once = reference.first_blocks(rows, whole.participants, whole.used,
                                     whole.sizes, N, by_orbit)
    assert set(single) == set(at_once) == set(whole.blocks)
    for key, b in at_once.items():
        assert _rel(single[key].sum, b.sum) < 1e-12
        assert _rel(whole.blocks[key].sum, b.sum) < 1e-12

    first = whole.commits[0]
    row = {s: i for i, s in enumerate(whole.participants)}
    w0 = reference.flatten(whole.w0)
    by_rows = reference.contract([first.weights[s] for s in whole.used],
                                 flat[[row[s] for s in whole.used]],
                                 first.base_weight, w0, "highest")
    assert _rel(first.w, by_rows) < 1e-12
    if whole.dists:
        want = reference.orbit_distances(
            whole.used, flat[[row[s] for s in whole.used]], whole.sizes, w0,
            N)
        for o, d in whole.dists.items():
            assert abs(want[o] - d) <= 1e-12 * d


class _Counting:
    """A model kind that counts the trained models it has handed out and
    that are still alive."""

    def __init__(self, inner):
        self.inner = inner
        self.alive = 0
        self.peak = 0
        self.calls = []

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def train_participants(self, w, inputs_sel, ids, *args, **kwargs):
        n = len(ids)
        self.calls.append(n)
        self.peak = max(self.peak, self.alive + n)
        tree, losses = self.inner.train_participants(w, inputs_sel, ids,
                                                     *args, **kwargs)
        out = {k: np.array(v) for k, v in tree.items()}
        del tree
        self.alive += n
        left = [len(out)]

        def gone():
            left[0] -= 1
            if left[0] == 0:
                self.alive -= n
        for v in out.values():
            weakref.finalize(v, gone)
        return out, losses


def test_no_more_models_alive_than_a_chunk(monkeypatch, tmp_path_factory):
    cfg, spec, rs, root = _case("mnist-asyncfleo", tmp_path_factory)
    counting = _Counting(reference.load_kind(cfg["model_kind"], root))
    monkeypatch.setattr(reference, "load_kind", lambda *_a: counting)
    # room for three rows of the model's size: chunks of three
    monkeypatch.setattr(reference, "CHUNK_BYTES", 3 * 12 * cfg["params"])
    lap = reference.lap(cfg, spec, rs, local_iters=2, root=root)
    assert len(lap.participants) > 3
    assert max(counting.calls) == 3
    assert sum(counting.calls) >= len(lap.participants)
    assert counting.peak == 3
    assert counting.alive == 0
