"""The comparison that decides ``correct``: the window's last lap, as the
program ran it, against the plain reference (``reference.py``).

The reference follows the whole lap's schedule, and its models for at
least three commits and on to the first commit that holds a stale model.
Each number compared has a limit of its own, stated in the configuration
file under ``limits`` and set from measured readings (``PERF.md``).  The
numbers:

sched_diff          round 0's participants that differ; over the whole
                    lap, commits made or missed unlike the reference, each
                    commit's models (satellite, epoch trained from) that
                    differ or sit at another place in its order, and models
                    counted per commit unlike the reference's; over the
                    commits followed with models, stale-only groups unlike
                    the reference's; the first commit's orbits with a
                    grouping distance that differ.  Exact: limit 0.
commit_time_err_s   the largest |program - reference| commit instant over
                    the whole lap, seconds.
weight_gap          the largest gap between the program's and the
                    reference's aggregation weight of a model, the old
                    model's weight (1 - gamma) or gamma, over the commits
                    followed with models.
row_change_gap      each first-round participant's trained change from w0,
                    by the worst (participant, leaf): |norm(program) -
                    norm(ref)| over the larger of the reference's norm of
                    that leaf and of its median leaf.  Leaves whose
                    reference change is under a thousandth of the median
                    leaf's do not count.  Only where the program returns
                    its bank (output form 1, below).
block_change_gap    where no row is compared (output form 2): the same
                    measure on each block of the first round (see below),
                    the change of sum of size_i x_i from sum of size_i w0,
                    the worst (block, leaf).  Its limit comes from the
                    configuration's own chip readings (``control.py``).
model_change_gap    the same measure on the global model after each commit
                    followed, the worst commit.
eq14_gap            the first commit's new global model against the float64
                    eq. 14 contraction of the program's own block sums with
                    the reference's weights: max |gap| / max |ref|.
dist_gap            the first commit's grouping distances against float64
                    from the program's own block sums, max |gap| / max |ref|
                    (rules that group only).

The per-participant losses and the new models' test accuracy are not
compared: neither the control nor any fault reads three times what sound
runs read (``PERF.md``).

The fused step's second output, as captured at a lap's first commit, comes
in one of two forms, which the configuration's ``step_output`` names:

``"bank"`` (form 1, the default)
    the ``(C, N)`` bank, one trained row per padded participant.
    ``host_capture`` reads it one float32 row at a time and folds the real
    rows into float64 block sums, weighted by the program's data sizes.
``"block_sums"`` (form 2)
    the ``(B, N)`` block sums themselves, sum of size_i x_i, for a program
    that keeps no bank.

Either way the blocks follow from the step's own arguments
(``block_layout``): a real row is the first row of its id (padding repeats
an id); its block is (its orbit, or itself where the commit rule's
``BLOCKS`` is ``"model"``; whether its aggregation weight ``wv_bank`` is
non-zero), and the blocks run in order of the orbit or satellite, the
taken block before the carried one.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from chipbench import reference as ref_mod

NUMBERS = ("sched_diff", "commit_time_err_s", "weight_gap", "row_change_gap",
           "block_change_gap", "model_change_gap", "eq14_gap", "dist_gap")
FORMS = ("bank", "block_sums")

_take_row = None


def _rows(arr, index):
    """Rows ``index`` of a (device) array, one at a time, as float64."""
    global _take_row
    if isinstance(arr, np.ndarray):
        for i in index:
            yield np.asarray(arr[i], np.float64)
        return
    import jax
    if _take_row is None:
        _take_row = jax.jit(lambda a, i: jax.lax.dynamic_index_in_dim(
            a, i, keepdims=False))
    for i in index:
        yield np.asarray(_take_row(arr, np.int32(i)), np.float64)


def block_layout(ids, wv_bank, per_orbit: int, by_orbit: bool
                 ) -> List[Tuple[tuple, List[int]]]:
    """The step's blocks, from its arguments: ``(block_key, rows)`` in the
    order form 2 returns them (see the module docstring)."""
    seen, out = set(), {}
    for r, s in enumerate(np.asarray(ids).tolist()):
        if s in seen:
            continue
        seen.add(s)
        key = ref_mod.block_key(s, float(wv_bank[r]) != 0.0, per_orbit,
                                by_orbit)
        out.setdefault(key, []).append(r)
    return sorted(out.items(), key=lambda kv: (kv[0][0], not kv[0][1]))


def _orbit_dists(cap, ids, per_orbit: int) -> Dict[int, float]:
    """The program's distance outputs keyed by orbit: output ``i`` covers
    the bank rows of block ``i`` (blocked layout) or of segment ``i``."""
    w, seg = np.asarray(cap.dw_row), np.asarray(cap.dw_seg)
    dists = np.asarray(cap.dists, np.float64)
    m = cap.blocked_m
    out = {}
    for i in range(cap.kpad):
        rows = (np.arange(i * m, (i + 1) * m) if m
                else np.flatnonzero(seg == i))
        rows = [r for r in rows if w[r] > 0]
        if not rows:
            continue
        orbits = {int(ids[r]) // per_orbit for r in rows}
        if len(orbits) != 1:
            return {}
        out[orbits.pop()] = float(dists[i])
    return out


def host_capture(cap, w0: Dict) -> Dict:
    """The captured lap on the host: the first commit's blocks (float64
    sums, ``block_layout``), its rows' per-leaf change norms where the step
    returns its bank, the distances by orbit, and each commit's per-leaf
    change norms (the first commit's model whole).  ``w0`` is the
    reference's initial model, against which every change is taken."""
    w0_flat = ref_mod.flatten(w0)
    slices = ref_mod.leaf_slices(w0)
    commits = []
    for k, c in enumerate(cap.commits):
        c = dict(c, change=None)
        w = c.pop("w")
        c["w"] = None
        if w is not None:
            import jax
            flat = ref_mod.flatten(jax.tree.map(np.asarray, w))
            c["change"] = ref_mod.change_norms(flat, w0_flat, slices)
            c["w"] = flat if k == 0 else None
        commits.append(c)
    out = {"form": cap.form, "participants": list(cap.participants),
           "commits": commits, "row_norms": None, "blocks": None,
           "dists": {}}
    if cap.stack is None:
        return out
    ids = np.asarray(cap.ids)
    layout = block_layout(ids, np.asarray(cap.wv_bank), cap.per_orbit,
                          cap.by_orbit)
    out["dists"] = _orbit_dists(cap, ids, cap.per_orbit)
    shape = tuple(cap.stack.shape)
    blocks = {}
    if cap.form == "bank" and shape == (len(ids), w0_flat.size):
        out["row_norms"] = {}
        for key, rows in layout:
            acc = np.zeros(w0_flat.size)
            sats = [int(ids[r]) for r in rows]
            for s, x in zip(sats, _rows(cap.stack, rows)):
                out["row_norms"][s] = ref_mod.change_norms(x, w0_flat,
                                                           slices)
                acc += float(cap.size_of(s)) * x
            blocks[key] = {"sats": sats, "sum": acc}
    elif cap.form == "block_sums" and shape == (len(layout), w0_flat.size):
        for (key, rows), x in zip(layout, _rows(cap.stack,
                                                range(len(layout)))):
            blocks[key] = {"sats": [int(ids[r]) for r in rows], "sum": x}
    else:
        return out                    # a malformed output: nothing to read
    out["blocks"] = blocks
    return out


def _norm_gap(dp: Dict[str, float], dr: Dict[str, float]) -> float:
    """Worst-leaf gap between the program's and the reference's norm of a
    change (see the module docstring)."""
    med = float(np.median(list(dr.values())))
    worst = 0.0
    for k in dr:
        if dr[k] < 1e-3 * med:
            continue
        worst = max(worst, abs(dp[k] - dr[k]) / max(dr[k], med))
    return float(worst)


def _block_gap(got: Dict, want: Dict, sizes: Dict[int, int], w0_flat,
               slices) -> float:
    """``block_change_gap``: the program's first-round blocks against the
    reference's, both float64 sums of size_i x_i; a block missing on
    either side, or holding other models, reads inf."""
    if not got or set(got) != set(want):
        return float("inf")
    worst = 0.0
    for key, b in want.items():
        if sorted(got[key]["sats"]) != sorted(b.sats):
            return float("inf")
        base = float(sum(sizes[s] for s in b.sats)) * w0_flat
        worst = max(worst, _norm_gap(
            ref_mod.change_norms(got[key]["sum"], base, slices),
            ref_mod.change_norms(b.sum, base, slices)))
    return worst


def _schedule(prog: Dict, ref: "ref_mod.Lap") -> Tuple[float, float, float]:
    """(sched_diff without the distances, commit_time_err_s, weight_gap):
    the schedule over the whole lap, the weights over the commits the
    reference followed with models."""
    diff = len(set(prog["participants"]) ^ set(ref.participants))
    pcs = prog["commits"]
    diff += abs(len(pcs) - len(ref.schedule))
    t_err, w_gap = 0.0, 0.0
    for pc, sl in zip(pcs, ref.schedule):
        pm = [tuple(m) for m in pc["models"]]
        rm = [(s, ep) for (_t, s, ep, _r) in sl.models]
        diff += sum(1 for a, b in zip(pm, rm) if a != b)
        diff += abs(len(pm) - len(rm))
        diff += abs(pc["num_models"] - len(rm))
        t_err = max(t_err, abs(pc["t_agg"] - sl.t_agg))
    for pc, rc in zip(pcs, ref.commits):
        diff += abs(pc["stale_groups"] - rc.stale_groups)
        for s in set(pc["weights"]) | set(rc.weights):
            w_gap = max(w_gap, abs(pc["weights"].get(s, 0.0)
                                   - rc.weights.get(s, 0.0)))
        w_gap = max(w_gap, abs(pc["base_weight"] - rc.base_weight),
                    abs(pc["gamma"] - rc.gamma))
    if len(pcs) < len(ref.commits):
        w_gap = float("inf")
    return float(diff), float(t_err), float(w_gap)


def compare(prog: Dict, ref: "ref_mod.Lap", per_orbit: int
            ) -> Dict[str, float]:
    """Every number compared, for one run."""
    nums: Dict[str, float] = {}
    diff, nums["commit_time_err_s"], nums["weight_gap"] = _schedule(prog,
                                                                    ref)
    slices = ref_mod.leaf_slices(ref.w0)
    w0 = ref_mod.flatten(ref.w0)
    if prog["row_norms"] is not None:
        worst = 0.0 if ref.rows is not None else float("inf")
        for i, s in enumerate(ref.participants if ref.rows is not None
                              else ()):
            if s not in prog["row_norms"]:
                continue
            r_flat = ref_mod.flatten({k: v[i] for k, v in ref.rows.items()})
            worst = max(worst, _norm_gap(
                prog["row_norms"][s],
                ref_mod.change_norms(r_flat, w0, slices)))
        nums["row_change_gap"] = worst
    else:
        nums["block_change_gap"] = _block_gap(prog["blocks"], ref.blocks,
                                              ref.sizes, w0, slices)
    pairs = list(zip(prog["commits"], ref.commits))
    nums["model_change_gap"] = (
        max(_norm_gap(pc["change"], rc.change) for pc, rc in pairs)
        if len(pairs) == len(ref.commits)
        and all(pc.get("change") for pc, _rc in pairs) else float("inf"))

    # the first commit's server math, on the program's own block sums with
    # the reference's weights (the sums themselves are held by the gaps
    # above); its taken blocks must hold the models the reference takes
    first = ref.commits[0]
    taken = sorted((k, b) for k, b in (prog["blocks"] or {}).items()
                   if k[1])
    if (prog["commits"] and prog["commits"][0].get("w") is not None
            and sorted(s for _k, b in taken for s in b["sats"])
            == sorted(ref.used)):
        cs = [sum(first.weights[s] for s in b["sats"])
              / sum(ref.sizes[s] for s in b["sats"]) for _k, b in taken]
        want = ref_mod.contract(cs, [b["sum"] for _k, b in taken],
                                first.base_weight, w0, "highest")
        w1 = prog["commits"][0]["w"]
        nums["eq14_gap"] = float(np.max(np.abs(w1 - want))
                                 / np.max(np.abs(want)))
        if ref.dists:
            got = prog["dists"]
            diff += len(set(got) ^ set(ref.dists))
            want_d = ref_mod.block_distances(
                [(b["sats"][0] // per_orbit, ref_mod.Block(
                    b["sats"], float(sum(ref.sizes[s] for s in b["sats"])),
                    b["sum"])) for _k, b in taken], w0)
            keys = sorted(set(got) & set(want_d))
            if keys:
                g = np.array([got[o] for o in keys])
                wd = np.array([want_d[o] for o in keys])
                nums["dist_gap"] = float(np.max(np.abs(g - wd))
                                         / np.max(np.abs(wd)))
            else:
                nums["dist_gap"] = float("inf")
    else:
        nums["eq14_gap"] = float("inf")
        if ref.dists:
            nums["dist_gap"] = float("inf")
    nums["sched_diff"] = diff
    return {k: nums[k] for k in NUMBERS if k in nums}


def control_capture(ctl: "ref_mod.Lap", per_orbit: int) -> Dict:
    """A reference computed one precision step lower (or with a fault
    planted), shaped as a program capture (its blocks, and its rows where
    the configuration's program returns its bank) so that ``compare``
    holds it against the reference."""
    w0 = ref_mod.flatten(ctl.w0)
    slices = ref_mod.leaf_slices(ctl.w0)
    row_norms = None
    if ctl.rows is not None:
        row_norms = {s: ref_mod.change_norms(
            ref_mod.flatten({k: v[i] for k, v in ctl.rows.items()}), w0,
            slices) for i, s in enumerate(ctl.participants)}
    commits = [{"t_agg": sl.t_agg,
                "models": [(s, ep) for (_t, s, ep, _r) in sl.models],
                "num_models": len(sl.models), "weights": {},
                "base_weight": 1.0, "gamma": 1.0, "stale_groups": 0,
                "change": None, "w": None} for sl in ctl.schedule]
    for pc, c in zip(commits, ctl.commits):
        pc.update(weights=dict(c.weights), base_weight=c.base_weight,
                  gamma=c.gamma, stale_groups=c.stale_groups,
                  change=c.change, w=c.w)
    return {"form": "bank" if row_norms else "block_sums",
            "participants": list(ctl.participants),
            "commits": commits, "row_norms": row_norms,
            "blocks": {k: {"sats": list(b.sats), "sum": b.sum}
                       for k, b in ctl.blocks.items()},
            "dists": dict(ctl.dists)}


def judge(nums: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, List[str]]:
    """``correct`` and one line per number: its value beside its limit.
    A number without a limit, or that is not finite, fails."""
    ok = True
    lines = []
    for k, v in nums.items():
        lim = limits.get(k)
        good = lim is not None and np.isfinite(v) and v <= lim
        ok &= bool(good)
        lines.append(f"{k} {v!r} limit {lim!r}")
    return ok, lines
