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
                    leaf's do not count.
model_change_gap    the same measure on the global model after each commit
                    followed, the worst commit.
eq14_gap            the first commit's new global model against the float64
                    eq. 14 contraction of the program's own trained rows
                    with the reference's weights: max |gap| / max |ref|.
dist_gap            the first commit's grouping distances against float64
                    from the program's own rows, max |gap| / max |ref|
                    (rules that group only).

The per-participant losses and the new models' test accuracy are not
compared: neither the control nor any fault reads three times what sound
runs read (``PERF.md``).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from chipbench import reference as ref_mod

NUMBERS = ("sched_diff", "commit_time_err_s", "weight_gap", "row_change_gap",
           "model_change_gap", "eq14_gap", "dist_gap")


def host_capture(cap) -> Dict:
    """The captured lap pulled to the host (float64)."""
    import jax
    commits = []
    for c in cap.commits:
        c = dict(c)
        if c["w"] is not None:
            c["w"] = ref_mod.flatten(jax.tree.map(np.asarray, c["w"]))
        commits.append(c)
    return {
        "ids": np.asarray(cap.ids),
        "stack": np.asarray(cap.stack, np.float64),
        "dists": np.asarray(cap.dists, np.float64),
        "dw_row": np.asarray(cap.dw_row, np.float64),
        "dw_seg": np.asarray(cap.dw_seg),
        "kpad": cap.kpad, "blocked_m": cap.blocked_m,
        "participants": list(cap.participants), "commits": commits,
    }


def _change_gap(p_flat, r_flat, w0_flat, slices) -> float:
    """Worst-leaf gap between the program's and the reference's norm of
    the change from w0 (see the module docstring)."""
    dp = {k: np.linalg.norm(p_flat[sl] - w0_flat[sl])
          for k, sl in slices.items()}
    dr = {k: np.linalg.norm(r_flat[sl] - w0_flat[sl])
          for k, sl in slices.items()}
    med = float(np.median(list(dr.values())))
    worst = 0.0
    for k in slices:
        if dr[k] < 1e-3 * med:
            continue
        worst = max(worst, abs(dp[k] - dr[k]) / max(dr[k], med))
    return float(worst)


def _orbit_dists(prog: Dict, per_orbit: int) -> Dict[int, float]:
    """The program's distance outputs keyed by orbit: output ``i`` covers
    the bank rows of block ``i`` (blocked layout) or of segment ``i``."""
    ids, w, seg = prog["ids"], prog["dw_row"], prog["dw_seg"]
    m = prog["blocked_m"]
    out = {}
    for i in range(prog["kpad"]):
        rows = (np.arange(i * m, (i + 1) * m) if m
                else np.flatnonzero(seg == i))
        rows = [r for r in rows if w[r] > 0]
        if not rows:
            continue
        orbits = {int(ids[r]) // per_orbit for r in rows}
        if len(orbits) != 1:
            return {}
        out[orbits.pop()] = float(prog["dists"][i])
    return out


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
    row_of_p = {}
    for k, s in enumerate(prog["ids"][:len(prog["participants"])]):
        row_of_p.setdefault(int(s), k)
    row_of_r = {s: k for k, s in enumerate(ref.participants)}
    worst = 0.0
    for s in ref.participants:
        if s not in row_of_p:
            continue
        r_flat = ref_mod.flatten({k: v[row_of_r[s]]
                                  for k, v in ref.rows.items()})
        worst = max(worst, _change_gap(prog["stack"][row_of_p[s]], r_flat,
                                       w0, slices))
    nums["row_change_gap"] = worst
    pairs = list(zip(prog["commits"], ref.commits))
    nums["model_change_gap"] = (
        max(_change_gap(pc["w"], rc.w, w0, slices) for pc, rc in pairs)
        if len(pairs) == len(ref.commits) else float("inf"))

    # the first commit's server math, on the program's own rows with the
    # reference's weights (the rows themselves are held by the gaps above)
    used_r = ref.used
    first = ref.commits[0]
    if prog["commits"] and all(s in row_of_p for s in used_r):
        rows = prog["stack"][[row_of_p[s] for s in used_r]]
        want = ref_mod.contract([first.weights[s] for s in used_r], rows,
                                first.base_weight, w0, "highest")
        w1 = prog["commits"][0]["w"]
        nums["eq14_gap"] = float(np.max(np.abs(w1 - want))
                                 / np.max(np.abs(want)))
        if ref.dists:
            got = _orbit_dists(prog, per_orbit)
            diff += len(set(got) ^ set(ref.dists))
            want_d = ref_mod.orbit_distances(used_r, rows, ref.sizes, w0,
                                             per_orbit)
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
    planted), shaped as a program capture so that ``compare`` holds it
    against the reference."""
    n = len(ctl.participants)
    stack = np.stack([ref_mod.flatten({k: v[i] for k, v in ctl.rows.items()})
                      for i in range(n)])
    ids = np.asarray(ctl.participants)
    orbits = sorted(ctl.dists)
    dw_row = np.zeros(n)
    dw_seg = np.full(n, len(orbits))
    for i, s in enumerate(ids):
        if int(s) in ctl.used and s // per_orbit in orbits:
            dw_row[i] = 1.0
            dw_seg[i] = orbits.index(s // per_orbit)
    commits = [{"t_agg": sl.t_agg,
                "models": [(s, ep) for (_t, s, ep, _r) in sl.models],
                "num_models": len(sl.models), "weights": {},
                "base_weight": 1.0, "gamma": 1.0, "stale_groups": 0,
                "w": None} for sl in ctl.schedule]
    for pc, c in zip(commits, ctl.commits):
        pc.update(weights=dict(c.weights), base_weight=c.base_weight,
                  gamma=c.gamma, stale_groups=c.stale_groups, w=c.w)
    return {"ids": ids, "stack": stack,
            "dists": np.array([ctl.dists[o] for o in orbits]),
            "dw_row": dw_row, "dw_seg": dw_seg, "kpad": len(orbits),
            "blocked_m": 0, "participants": list(ctl.participants),
            "commits": commits}


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
