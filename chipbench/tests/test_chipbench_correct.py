"""The comparison that decides ``correct``, driven on the CPU at a size a
test run can hold: the paper deployment and MNIST_CNN at their widths, with
a short lap (three AsyncFLEO commits, a few dozen FedAsync ones) and 2 local
iterations.

A sound window is correct.  Each fault planted in the timed path underneath
a warmed run makes it incorrect: the commit step returning its state
unchanged, half of the models aggregated (renormalised over the rest), a
trained row altered where it is produced, a participant trained on another
satellite's shard, the schedule altered (every uplink one grid step late),
stale models weighted as fresh, and carried models never re-entering a
commit.  The control, the reference one precision step below the
configuration (bfloat16 local training), is incorrect too.  The cells run
on one chip, so there is no exchange between chips to leave out here
(``test_chipbench_mesh.py`` runs a four-chip test cell).

The step's second output read as block sums (form 2) gives the numbers
the bank gives (form 1), and a configuration that declares block sums is
checked without its rows.
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


def _window(bench):
    import jax
    result, _lines = bench_run.measure(bench, 0.0, False, jax.devices()[0],
                                       PEAK)
    assert result["attempted"] >= 1


def _verdict(cell, bench, ref):
    _window(bench)
    ok, checks, _ = bench_run.verify(
        cell, check.host_capture(bench.capture, ref.w0), bench.seed,
        bench.local_iters, ref=ref)
    return ok, {k: v["value"] for k, v in checks.items()}


def test_sound_window_is_correct(warmed):
    ok, nums = _verdict(*warmed)
    assert ok, nums
    assert nums["sched_diff"] == 0
    assert len(warmed[2].commits) == 3
    assert list(nums) == [k for k in check.NUMBERS
                          if k != "block_change_gap"]
    assert warmed[1].mesh is None and warmed[1].prog.mesh is None


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


@pytest.fixture(scope="module")
def form2(warmed):
    """The warmed cell's configuration declaring block sums, and its
    reference.  Its ``block_change_gap`` limit stands in for the chip
    readings a real configuration sets it from: ``row_change_gap``'s, the
    same measure over single rows."""
    cell, bench, _ref = warmed
    lim = dict(cell.config["limits"])
    lim["block_change_gap"] = lim.pop("row_change_gap")
    cfg = dict(cell.config, step_output="block_sums", limits=lim)
    return cfg, reference.lap(cfg, cell.mix["spec"], bench.seed,
                              local_iters=2)


def test_wrong_shard_is_incorrect(warmed, form2, monkeypatch):
    """The first participant's inputs taken from the shard of the
    satellite at the far end of the numbering, where they are gathered."""
    cell, bench, ref = warmed
    pool = bench.pool
    inner = pool.epoch_inputs
    S = len(ref.sizes)
    s0 = ref.used[0]
    far = S - 1 if s0 < S // 2 else 0

    def wrong(ids_np):
        ids = np.array(ids_np, copy=True)
        ids[ids == s0] = far
        return inner(ids)
    monkeypatch.setattr(pool, "epoch_inputs", wrong)
    ok, nums = _verdict(*warmed)
    assert not ok
    assert nums["row_change_gap"] > cell.config["limits"]["row_change_gap"]

    # the same fault, the program returning block sums: its block fails
    cfg, ref2 = form2
    prog = check.host_capture(dataclasses.replace(
        bench.capture, form="block_sums",
        stack=_block_sums(bench.capture, "float32")), ref2.w0)
    ok, checks, _lines = bench_run.verify(
        dataclasses.replace(cell, config=cfg), prog, bench.seed, 2, ref=ref2)
    assert not ok
    assert checks["block_change_gap"]["value"] > cfg["limits"][
        "block_change_gap"]


def _block_sums(cap, dtype):
    """The captured bank folded into form 2, as a program that keeps no
    bank would return it: float64 on the host, or float32 on the device."""
    import jax.numpy as jnp
    ids = np.asarray(cap.ids)
    layout = check.block_layout(ids, np.asarray(cap.wv_bank), cap.per_orbit,
                                cap.by_orbit)
    if dtype == "float64":
        bank = np.asarray(cap.stack, np.float64)
        return np.stack([sum(float(cap.size_of(int(ids[r]))) * bank[r]
                             for r in rows) for _key, rows in layout])
    w = np.zeros((len(layout), len(ids)), np.float32)
    for b, (_key, rows) in enumerate(layout):
        for r in rows:
            w[b, r] = cap.size_of(int(ids[r]))
    return jnp.dot(jnp.asarray(w), cap.stack, precision="highest")


def test_block_sums_read_as_the_bank(warmed, form2):
    cell, bench, ref = warmed
    _window(bench)
    cap = bench.capture
    per_orbit = cell.config["constellation"]["sats_per_orbit"]
    bank = check.compare(check.host_capture(cap, ref.w0), ref, per_orbit)
    sums = check.compare(check.host_capture(dataclasses.replace(
        cap, form="block_sums", stack=_block_sums(cap, "float64")), ref.w0),
        ref, per_orbit)
    assert set(bank) - set(sums) == {"row_change_gap"}
    assert set(sums) - set(bank) == {"block_change_gap"}
    for k, v in bank.items():
        if k != "row_change_gap":
            assert abs(sums[k] - v) <= 1e-12 * max(abs(v), 1e-300), k

    # a configuration that declares block sums: its reference keeps no
    # rows, the check compares no row, and the device's float32 sums pass
    cfg, ref2 = form2
    cell2 = dataclasses.replace(cell, config=cfg)
    assert ref2.rows is None
    prog = check.host_capture(dataclasses.replace(
        cap, form="block_sums", stack=_block_sums(cap, "float32")), ref2.w0)
    ok, checks, lines = bench_run.verify(cell2, prog, bench.seed, 2,
                                         ref=ref2)
    assert ok, lines
    assert "row_change_gap" not in checks
    assert checks["block_change_gap"]["value"] < cfg["limits"][
        "block_change_gap"]
    assert any(line.startswith("row_change_gap not compared")
               for line in lines)
    assert checks["eq14_gap"]["value"] < cfg["limits"]["eq14_gap"]

    # the first block's trained change doubled where the sums are made:
    # no row is read, and the block's own comparison fails
    ids = np.asarray(cap.ids)
    _key, rows = check.block_layout(ids, np.asarray(cap.wv_bank),
                                    cap.per_orbit, cap.by_orbit)[0]
    base = (sum(float(cap.size_of(int(ids[r]))) for r in rows)
            * reference.flatten(ref2.w0))
    doubled = _block_sums(cap, "float64")
    doubled[0] = base + 2.0 * (doubled[0] - base)
    prog = check.host_capture(dataclasses.replace(
        cap, form="block_sums", stack=doubled), ref2.w0)
    ok, checks, _lines = bench_run.verify(cell2, prog, bench.seed, 2,
                                          ref=ref2)
    assert not ok
    assert checks["block_change_gap"]["value"] > cfg["limits"][
        "block_change_gap"]

    # block sums declared, the bank returned: nothing to read
    wrong = check.host_capture(dataclasses.replace(cap, form="block_sums"),
                               ref.w0)
    assert wrong["blocks"] is None
    assert check.compare(wrong, ref2, per_orbit)["eq14_gap"] == float("inf")
