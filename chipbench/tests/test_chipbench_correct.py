"""The comparison that decides ``correct``, driven on the CPU at a size a
test run can hold: the paper deployment and MNIST_CNN at their widths, with
a short lap (three AsyncFLEO commits, a few dozen FedAsync ones) and 2 local
iterations.

A sound window is correct.  Each fault planted in the timed path underneath
a warmed run makes it incorrect: the commit step returning its state
unchanged, half of the models aggregated (renormalised over the rest), a
trained row altered where it is produced, the schedule altered (every
uplink one grid step late), stale models weighted as fresh, and carried
models never re-entering a commit.  The control, the reference one
precision step below the configuration (bfloat16 local training), is
incorrect too.  The cells run on one chip, so there is no exchange between
chips to leave out.
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import check, harness, reference  # noqa: E402
from chipbench import run as bench_run  # noqa: E402

SEED = 2 ** 33 + 17
PEAK = {"bf16_flops_per_s": 197e12}


HORIZON = {"paper-mnist.asyncfleo": 6400.0, "paper-mnist.fedasync": 1500.0}


def _small(name):
    cell = harness.load_cell(name)
    cell.config = dict(cell.config, horizon_s=HORIZON[name], local_iters=2)
    return cell


def _warm(name):
    cell = _small(name)
    bench = harness.Bench(cell, SEED).build()
    bench.warm()
    ref = reference.lap(cell.config, cell.mix["spec"], bench.seed,
                        local_iters=2)
    return cell, bench, ref


@pytest.fixture(scope="module")
def warmed():
    return _warm("paper-mnist.asyncfleo")


@pytest.fixture(scope="module")
def fedasync():
    return _warm("paper-mnist.fedasync")


def _verdict(cell, bench, ref):
    import jax
    result, _lines = bench_run.measure(bench, 0.0, False, jax.devices()[0],
                                       PEAK)
    assert result["attempted"] >= 1
    ok, checks, _ = bench_run.verify(cell, check.host_capture(bench.capture),
                                     bench.seed, bench.local_iters, ref=ref)
    return ok, {k: v["value"] for k, v in checks.items()}


def test_sound_window_is_correct(warmed):
    ok, nums = _verdict(*warmed)
    assert ok, nums
    assert nums["sched_diff"] == 0
    assert len(warmed[2].commits) == 3


@pytest.fixture
def step(warmed):
    """The warmed run's jitted commit step, restored after the test."""
    disp = warmed[1].prog._step
    inner = disp.fn
    yield disp, inner
    disp.fn = inner


def test_unchanged_state_is_incorrect(warmed, step):
    disp, inner = step

    def unchanged(w, *args):
        keep = w + 0.0
        _new_w, stack, dists, losses = inner(w, *args)
        return keep, stack, dists, losses
    disp.fn = unchanged
    ok, nums = _verdict(*warmed)
    assert not ok
    assert nums["model_change_gap"] > warmed[0].config["limits"][
        "model_change_gap"]


def test_half_batch_is_incorrect(warmed, step):
    import jax.numpy as jnp
    disp, inner = step

    def half(w, carry, inputs, ids, seed, wv_bank, *rest):
        wv = np.asarray(wv_bank, np.float64)
        live = np.flatnonzero(wv)
        cut = wv.copy()
        cut[live[len(live) // 2:]] = 0.0
        cut *= wv.sum() / cut.sum()
        return inner(w, carry, inputs, ids, seed,
                     jnp.asarray(cut, jnp.float32), *rest)
    disp.fn = half
    ok, nums = _verdict(*warmed)
    assert not ok
    assert nums["eq14_gap"] > warmed[0].config["limits"]["eq14_gap"]


def test_altered_row_is_incorrect(warmed, step):
    disp, inner = step

    def altered(*args):
        new_w, stack, dists, losses = inner(*args)
        return new_w, stack.at[0].multiply(1.5), dists, losses
    disp.fn = altered
    ok, nums = _verdict(*warmed)
    assert not ok


def test_altered_schedule_is_incorrect(warmed, monkeypatch):
    from repro.sched.contacts import ContactPlan
    cell = warmed[0]
    orig = ContactPlan.uplink_times

    def late(self, *args, **kwargs):
        t, ps = orig(self, *args, **kwargs)
        return t + cell.config["dt_s"], ps
    monkeypatch.setattr(ContactPlan, "uplink_times", late)
    ok, nums = _verdict(*warmed)
    assert not ok
    assert nums["commit_time_err_s"] > cell.config["limits"][
        "commit_time_err_s"]


def test_bf16_control_is_incorrect(warmed):
    cell, bench, ref = warmed
    ctl = reference.lap(cell.config, cell.mix["spec"], bench.seed,
                        local_iters=2, precision="bfloat16")
    per_orbit = cell.config["constellation"]["sats_per_orbit"]
    nums = check.compare(check.control_capture(ctl, per_orbit), ref,
                         per_orbit)
    ok, _lines = check.judge(nums, cell.config["limits"])
    assert not ok


def test_fedasync_window_is_correct(fedasync):
    ok, nums = _verdict(*fedasync)
    assert ok, nums
    assert "dist_gap" not in nums
    ref = fedasync[2]
    assert any(ep < k for k, c in enumerate(ref.commits)
               for (_s, ep) in c.models)


def test_stale_undiscounted_is_incorrect(fedasync, monkeypatch):
    from repro.core import aggregation
    orig = aggregation.epoch_weight_vector

    def undiscounted(agg_mode, metas, beta, groups, **kw):
        fresh = [dataclasses.replace(m, epoch=beta) for m in metas]
        return orig(agg_mode, fresh, beta, groups, **kw)
    monkeypatch.setattr(aggregation, "epoch_weight_vector", undiscounted)
    ok, nums = _verdict(*fedasync)
    assert not ok
    assert nums["weight_gap"] > fedasync[0].config["limits"]["weight_gap"]


def test_carried_dropped_is_incorrect(fedasync, monkeypatch):
    from repro.core.simulator import FLSimulation

    def never(self, t_agg):
        return [], list(range(len(self._pend_meta)))
    monkeypatch.setattr(FLSimulation, "_carried_split", never)
    ok, nums = _verdict(*fedasync)
    assert not ok
    assert nums["sched_diff"] > 0
