"""Readings that set the limits of ``check.py``; not run by the benchmark.

    python3 chipbench/control.py --workload <cell> --seeds 12 --controls 3

In one process, on the chip and at the cell's own size:

* the program: for each seed, set-up and the first commits of a lap, as
  many as the reference follows with models; their numbers against the
  reference (the lower readings).  The whole lap's schedule is read by the
  benchmark's own runs;
* the control: the reference put in the program's place, one precision step
  below what the configuration states: local training in bfloat16
  (``control:bf16-training``), and the server's eq. 14 contraction and
  grouping distances at ``HIGH`` (``control:high-server``);
* faults planted in the reference put in the program's place: the first
  commit aggregates half of its models, renormalised (``fault:half-batch``);
  one participant's trained row altered (``fault:altered-row``); the first
  commit one grid step late and one model short
  (``fault:altered-schedule``); the commit returning the global model
  unchanged (``fault:unchanged``); stale models weighted as fresh
  (``fault:stale-undiscounted``); carried models never re-entering
  (``fault:carried-dropped``).

Prints one JSON line per reading.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def program_reading(cell, seed):
    """The program's first commits against the reference, for one seed."""
    from chipbench import check, harness, reference
    cfg, spec = cell.config, cell.mix["spec"]
    bench = harness.Bench(cell, seed).build()
    rs, iters = bench.seed, bench.local_iters
    ref = reference.lap(cfg, spec, rs, local_iters=iters)
    bench.lap(max_commits=len(ref.commits))
    prog = check.host_capture(bench.capture)
    del bench
    part = dataclasses.replace(ref, schedule=ref.schedule[:len(ref.commits)])
    return check.compare(prog, part,
                         cfg["constellation"]["sats_per_orbit"]), ref


def _recommit(ref, cfg, k, models, weights, base_weight,
              precision="highest"):
    """``ref`` with commit ``k`` aggregating ``models`` (rows of the first
    round, by satellite) with ``weights``; the global model after it
    follows from those rows."""
    from chipbench import reference
    N = cfg["constellation"]["sats_per_orbit"]
    row = {s: i for i, s in enumerate(ref.participants)}
    flat = np.stack([reference.flatten({n: v[row[s]]
                                        for n, v in ref.rows.items()})
                     for s in models])
    w0 = reference.flatten(ref.w0)
    prev = w0 if k == 0 else ref.commits[k - 1].w
    w = reference.contract(weights, flat, base_weight, prev, precision)
    commits = list(ref.commits)
    commits[k] = dataclasses.replace(
        commits[k], w=w, base_weight=float(base_weight),
        weights={s: float(v) for s, v in zip(models, weights)})
    dists = ref.dists
    if k == 0 and ref.dists:
        dists = reference.orbit_distances(list(models), flat, ref.sizes, w0,
                                          N, precision)
    return dataclasses.replace(ref, commits=commits, dists=dists)


def control_readings(cell, ref, rs, *, local_iters):
    """Controls and faults for one seed (run seed ``rs``), each held
    against ``ref``."""
    from chipbench import check, reference
    cfg, spec = cell.config, cell.mix["spec"]
    N = cfg["constellation"]["sats_per_orbit"]
    rule = reference.load_rule(spec["agg_mode"])
    first = ref.commits[0]
    used = list(ref.used)
    out = {}

    def held(name, fake):
        out[name] = check.compare(check.control_capture(fake, N), ref, N)

    held("control:bf16-training", reference.lap(
        cfg, spec, rs, local_iters=local_iters, precision="bfloat16"))
    held("control:high-server", reference.lap(
        cfg, spec, rs, local_iters=local_iters, server_precision="high"))
    half = used[:max(1, len(used) // 2)]
    hw = np.array([first.weights[s] for s in half])
    hw = hw / hw.sum() * (1.0 - first.base_weight)
    held("fault:half-batch", _recommit(ref, cfg, 0, half, hw,
                                       first.base_weight))

    rows = {k: np.array(v, copy=True) for k, v in ref.rows.items()}
    i = ref.participants.index(used[0])
    for k, v in rows.items():
        v[i] = ref.w0[k] + 2.0 * (v[i] - ref.w0[k])
    altered = dataclasses.replace(ref, rows=rows)
    held("fault:altered-row", _recommit(
        altered, cfg, 0, used, [first.weights[s] for s in used],
        first.base_weight))

    sl = ref.schedule[0]
    late = dataclasses.replace(sl, t_agg=sl.t_agg + cfg["dt_s"],
                               models=sl.models[:-1])
    held("fault:altered-schedule", dataclasses.replace(
        ref, schedule=[late] + ref.schedule[1:]))

    commits = list(ref.commits)
    commits[0] = dataclasses.replace(commits[0], w=reference.flatten(ref.w0))
    held("fault:unchanged", dataclasses.replace(ref, commits=commits))

    commits = list(ref.commits)
    for k, c in enumerate(commits):
        if any(ep < k for (_s, ep) in c.models):
            sizes = [(s, ref.sizes[s], k) for (s, _ep) in c.models]
            ws, base_w, gamma, _sg = rule.weights(sizes, k, c.groups)
            commits[k] = dataclasses.replace(
                c, weights={s: float(v) for (s, _e), v in zip(c.models, ws)},
                base_weight=float(base_w), gamma=float(gamma),
                stale_groups=0)
    held("fault:stale-undiscounted", dataclasses.replace(ref,
                                                         commits=commits))

    slots = [dataclasses.replace(sl, models=[m for m in sl.models
                                             if m[2] >= k])
             for k, sl in enumerate(ref.schedule)]
    held("fault:carried-dropped", dataclasses.replace(ref, schedule=slots))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_000_000_001)
    args = ap.parse_args(argv)
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    harness.check_device(cell.chips, harness.load_peaks())
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        nums, ref = program_reading(cell, seed)
        print(json.dumps({"reading": "program", "seed": seed,
                          "commits": len(ref.commits),
                          "seconds": time.perf_counter() - t0, **nums}),
              flush=True)
        if i < args.controls:
            t0 = time.perf_counter()
            for name, n in control_readings(
                    cell, ref, harness.run_seed(seed, cell.config),
                    local_iters=cell.config["local_iters"]).items():
                print(json.dumps({"reading": name, "seed": seed, **n}),
                      flush=True)
            print(json.dumps({"reading": "control seconds",
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
