"""Head-to-head convergence-delay benchmark under the event runtime.

The paper's headline (Table II / Fig. 6) is not an accuracy number but a
*delay* number: time-to-target-accuracy under asynchronous aggregation vs
the synchronous barrier.  This benchmark finally makes that comparison
runnable: the SAME constellation, contact plan and (deterministic,
fused-protocol) trainer run under each strategy's trigger policy in the
event-driven runtime (`sched/runtime.py`), and the simulated convergence
delay to a target accuracy is read off the shared history format with
``convergence_time``.

Per policy it records: simulated convergence delay (seconds), epochs to
target, fused dispatch counts, event counts, pipeline telemetry
(rounds opened / peak rounds in flight / cross-round straggler
adoptions), and host wall time; plus the compiled contact-plan summary
for the scenario.  The ``async_pipelined`` row runs the SAME AsyncFLEO
policy with up to 3 overlapping rounds in flight (DESIGN.md §8), so the
pipelined-vs-single-round delta is pure scheduling.  Results go to
``BENCH_sched.json`` (CI uploads it next to ``BENCH_epoch.json``; the
field-by-field schema is documented in ``benchmarks/README.md``).

``--fail-if-not-lower`` exits nonzero unless the AsyncFLEO policy's
convergence delay is strictly lower than the sync GS-FedAvg baseline's —
the acceptance gate for the paper's ordering — the pipelined row's is no
higher than single-round async, AND async still strictly beats sync in
the most bandwidth-constrained contention cell (``ps_channels=1`` at the
lowest swept rate): the ordering is a genuinely different claim once a
PS can no longer absorb every transfer at once.

The **contention sweep** (on by default, ``--skip-contention-sweep`` to
disable) re-runs the async / pipelined / sync head-to-head under finite
per-PS link capacity (DESIGN.md §9): every ``ps_channels`` in {1, 4, ∞}
crossed with a nominal and a bandwidth-constrained ``rate_bps``.  The
interesting row is the pipelined one — overlapping rounds share the
same PS pools, so the single-round-vs-pipelined delta shrinks (or
inverts) as channels get scarce, which the infinite-parallelism model
could never show.  ``--ps-channels`` additionally applies a channel
count to the four MAIN policy rows.

The **fault sweep** (on by default, ``--skip-fault-sweep`` to disable)
re-runs the AsyncFLEO row under injected faults (DESIGN.md §10): every
transfer-dropout probability in {0, 5%, 20%} crossed with a per-sat
compute-rate spread in {0, 1.0} and a staleness function in
{eq13, poly} — 12 cells, each carrying the retry telemetry
(transfers failed / retried / dropped after max retries) and the
realized compute-rate spread.  Under ``--fail-if-not-lower`` the
all-off cell (dropout 0, spread 0, eq13; ``fault_model=None``) must
match the main async row EXACTLY (the §10 off-switch parity pin), and
every dropout=20% cell must still reach the target accuracy.

Two §11 robustness cells ride along with the fault sweep: the
**defaults-parity row** re-runs the main async scenario with an
explicit ``FaultModel()`` (burst / outage / energy / adaptive-backoff
axes all at their defaults) and the gate requires it to match the
``fault_model=None`` row on every deterministic key, and the **outage
smoke cell** (``outage_smoke``) runs pipelined AsyncFLEO on the
two-HAP ring with one HAP dark for a contiguous 30% of the horizon —
the gate requires ring failover + lazy arrival reroutes to carry it to
the target anyway.

``--cnn-sats 200`` appends the accuracy-aware convergence-delay study:
the async / pipelined / sync head-to-head re-run with REAL federated CNN
training (non-IID class-conditional shards) at S >= 200, where the
measured delay includes genuine accuracy dynamics instead of the
deterministic proxy.

Usage:  PYTHONPATH=src python benchmarks/sched_bench.py [--target 0.9]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import FLSimulation, SimConfig, convergence_time
from repro.core.constellation import WalkerDelta
from repro.core.links import LinkModel
from repro.fl.strategies import get_strategy
from repro.obs import (Tracer, add_runtime_tracks, export_chrome,
                       export_jsonl, validate_chrome_trace)
from repro.obs.trace import SPAN_ROUND
from repro.sched import EventDrivenRuntime

# async vs sync on the same constellation with the SAME PS placement
# (a single ground station, the Razmi-style GS-FL setup), plus the
# FedAsync per-arrival baseline for reference and the pipelined runtime
# (up to 3 overlapping rounds in flight, DESIGN.md §8) head-to-head
# against single-round async
POLICY_ROWS = (
    ("async_asyncfleo", "asyncfleo-gs"),
    ("async_pipelined", "asyncfleo-pipelined"),
    ("sync_gs_fedavg", "fedisl"),
    ("fedasync_per_arrival", "fedasync"),
)

# the bandwidth-constrained contention sweep (DESIGN.md §9): the same
# head-to-head under finite per-PS link capacity.  16 Mb/s is the paper's
# Table I evaluation rate (transfers are near-free there: the sweep's
# control); 3 kb/s makes one model transfer ~88 s, so a single-channel PS
# needs ~1 h of airtime to drain a 40-satellite round — the serialized
# transfers dominate the round and the 1.26x pipelining win inverts,
# while 4 channels (FedHAP-style collaborating capacity) restore it
CONTENTION_ROWS = POLICY_ROWS[:3]
CONTENTION_RATES = (16e6, 3e3)
CONTENTION_CHANNELS = (1, 4, None)         # None = infinite parallelism

# the robustness sweep (DESIGN.md §10): AsyncFLEO under injected faults.
# dropout x compute-rate spread x staleness function; the all-off cell
# (0, 0, eq13) runs with fault_model=None and must match the main async
# row EXACTLY — that equality is the off-switch parity pin the
# --fail-if-not-lower gate enforces
FAULT_DROPOUTS = (0.0, 0.05, 0.2)
FAULT_SPREADS = (0.0, 1.0)
FAULT_STALENESS = ("eq13", "poly")


# the deterministic fused-protocol testbed (trainer/evaluator/model) moved
# to `repro.sweep.testbed` so the batched sweep engine and this benchmark
# share ONE definition; re-exported here because tests and the CNN study
# import them from this module
from repro.sweep.testbed import (ConvergingTrainer, MeanDistanceEvaluator,
                                 make_model)


def program_counters(prog) -> Dict:
    """The fused epoch program's dispatch and trace counters."""
    return {k: int(getattr(prog, k)) for k in
            ("dispatches", "fallback_dispatches", "batched_dispatches",
             "traces")}


def _run_policy(name: str, strategy: str, w0, target: float,
                max_epochs: int, duration_s: float,
                ps_channels: Optional[int] = None,
                link: Optional[LinkModel] = None,
                fault=None, staleness_fn: str = "eq13",
                spec_kw: Optional[Dict] = None, tracer=None):
    """One benched run; returns (row, fls, rt, hist) so callers that
    need the live objects (the trace smoke cell) share the exact setup
    the plain rows use."""
    spec = get_strategy(strategy)
    if spec_kw:
        spec = dataclasses.replace(spec, **spec_kw)
    if ps_channels is not None:
        spec = dataclasses.replace(spec, ps_channels=ps_channels)
    if staleness_fn != "eq13":
        spec = dataclasses.replace(spec, staleness_fn=staleness_fn)
    sim = SimConfig(duration_s=duration_s, dt_s=30.0, train_time_s=300.0,
                    use_model_bank=True, use_fused_step=True,
                    event_driven=True, link=link, fault_model=fault,
                    tracer=tracer)
    fls = FLSimulation(spec, ConvergingTrainer(w0),
                       MeanDistanceEvaluator(), sim)
    rt = EventDrivenRuntime(fls)
    t0 = time.perf_counter()
    hist = rt.run(w0, max_epochs=max_epochs, target_accuracy=target)
    wall = time.perf_counter() - t0
    conv = convergence_time(hist, target)
    row = {
        "policy": name,
        "strategy": strategy,
        "trigger_policy": rt.policy.name,
        "target_accuracy": target,
        "convergence_delay_s": conv,
        "epochs_to_target": (len(hist) if conv is not None else None),
        "final_accuracy": float(hist[-1].accuracy) if hist else None,
        "aggregations": len(hist),
        "fused_dispatches": fls._fused_prog.dispatches,
        "fallback_dispatches": fls._fused_prog.fallback_dispatches,
        "event_counts": dict(rt.events.counts),
        "sched_stats": dict(rt.stats),
        "max_in_flight": rt.max_in_flight,
        "handoff_policy": rt.handoff.name,
        "ps_channels": ps_channels,
        "rate_bps": float((link or LinkModel()).rate_bps),
        "contention": rt.contention_stats(),
        "staleness_fn": staleness_fn,
        # fault/heterogeneity config + realized compute spread; the retry
        # telemetry (transfers_failed / transfer_retries / dropped_*) is
        # in sched_stats above
        "fault": None if fault is None else {
            "loss_prob": fault.loss_prob,
            "max_retries": fault.max_retries,
            "retry_backoff_s": fault.retry_backoff_s,
            "compute_rate_spread": fault.compute_rate_spread,
            "eclipse_fraction": fault.eclipse_fraction,
            "seed": fault.seed,
            "train_scale_min": (1.0 if fls._train_scale is None
                                else float(fls._train_scale.min())),
            "train_scale_max": (1.0 if fls._train_scale is None
                                else float(fls._train_scale.max())),
            # §11 degradation-and-recovery config (the realized outage /
            # energy / backoff telemetry is in sched_stats above)
            "burst_len_s": fault.burst_len_s,
            "loss_prob_bad": fault.loss_prob_bad,
            "loss_prob_good": fault.loss_prob_good,
            "ps_outages": (None if fault.ps_outages is None
                           else [list(iv) for iv in fault.ps_outages]),
            "ps_outage_fraction": fault.ps_outage_fraction,
            "battery_j": fault.battery_j,
            "adaptive_backoff": fault.adaptive_backoff,
        },
        "wall_s": wall,
        # reproducibility (DESIGN.md §12): the RNG seed this row trained
        # under, and the fused program's own counters (the row's trainer
        # is fresh, so they count this run alone)
        "seed": int(sim.seed),
        "profile": program_counters(fls._fused_prog),
        "plan": fls.plan.summary(),
    }
    return row, fls, rt, hist


def bench_policy(name: str, strategy: str, w0, target: float,
                 max_epochs: int, duration_s: float,
                 ps_channels: Optional[int] = None,
                 link: Optional[LinkModel] = None,
                 fault=None, staleness_fn: str = "eq13",
                 spec_kw: Optional[Dict] = None) -> Dict:
    row, _fls, _rt, _hist = _run_policy(
        name, strategy, w0, target, max_epochs, duration_s,
        ps_channels=ps_channels, link=link, fault=fault,
        staleness_fn=staleness_fn, spec_kw=spec_kw)
    return row


def trace_smoke(w0, target: float, max_epochs: int, duration_s: float,
                trace_out: str) -> Dict:
    """The observability smoke cell (DESIGN.md §12): run the pipelined
    AsyncFLEO row twice — once traced, once with ``tracer=None`` — and
    gate three claims before writing the trace artifact:

    1. **null-tracer bit-parity**: the traced run's history rows and
       final flat weights are bit-identical to the untraced run's;
    2. the exported Chrome trace-event JSON passes the schema validator
       (loads in Perfetto);
    3. the trace carries >= 1 ``round`` span per committed epoch.

    Writes ``trace_out`` (Chrome JSON, the CI artifact) plus the same
    buffer as JSONL next to it.  Raises SystemExit on any gate failure.
    """
    tracer = Tracer()
    _rowt, fls_t, rt_t, hist_t = _run_policy(
        "async_pipelined_traced", "asyncfleo-pipelined", w0, target,
        max_epochs, duration_s, tracer=tracer)
    _rowu, fls_u, _rt_u, hist_u = _run_policy(
        "async_pipelined", "asyncfleo-pipelined", w0, target,
        max_epochs, duration_s)

    def _rows(h):
        return [(r.epoch, r.time_s, r.accuracy, r.num_models, r.gamma)
                for r in h]

    if _rows(hist_t) != _rows(hist_u):
        raise SystemExit("tracer=None parity broken: traced history "
                         "differs from the untraced run")
    wt = np.asarray(fls_t._w_flat)
    wu = np.asarray(fls_u._w_flat)
    if wt.tobytes() != wu.tobytes():
        raise SystemExit("tracer=None parity broken: traced final "
                         "weights differ bitwise from the untraced run")

    add_runtime_tracks(tracer, rt_t)          # per-PS occupancy/outages
    obj = export_chrome(tracer, trace_out)
    errs = validate_chrome_trace(obj)
    if errs:
        raise SystemExit("exported trace failed Chrome-trace schema "
                         "validation: " + "; ".join(errs[:5]))
    round_spans = sum(1 for s in tracer.spans if s.name == SPAN_ROUND)
    if round_spans < len(hist_t):
        raise SystemExit(
            f"trace coverage broken: {round_spans} round spans for "
            f"{len(hist_t)} committed epochs")
    jsonl_out = trace_out.rsplit(".", 1)[0] + ".jsonl"
    lines = export_jsonl(tracer, jsonl_out)
    print(f"[trace] parity ok  {len(obj['traceEvents'])} events  "
          f"{round_spans} round spans / {len(hist_t)} epochs  "
          f"-> {trace_out} (+{jsonl_out}, {lines} lines)")
    return {"trace_path": trace_out, "jsonl_path": jsonl_out,
            "trace_events": len(obj["traceEvents"]),
            "round_spans": round_spans, "aggregations": len(hist_t),
            "tracer_null_parity": True}


def contention_sweep(w0, target: float, max_epochs: int,
                     duration_s: float) -> Dict:
    """The async / pipelined / sync head-to-head under finite per-PS link
    capacity: one cell per (rate_bps, ps_channels) with per-cell speedup
    ratios.  ``ps_channels=None`` cells are the infinite-parallelism
    control — bit-identical to the main rows at the same rate."""
    cells = []
    for rate in CONTENTION_RATES:
        link = LinkModel(rate_bps=rate)
        for k in CONTENTION_CHANNELS:
            cell = {"rate_bps": float(rate), "ps_channels": k, "rows": []}
            for name, strategy in CONTENTION_ROWS:
                r = bench_policy(name, strategy, w0, target, max_epochs,
                                 duration_s, ps_channels=k, link=link)
                cell["rows"].append(r)
            by = {r["policy"]: r["convergence_delay_s"]
                  for r in cell["rows"]}
            a, p, s = (by["async_asyncfleo"], by["async_pipelined"],
                       by["sync_gs_fedavg"])
            cell["async_vs_sync_speedup"] = (s / a if a and s else None)
            cell["pipelined_vs_async_speedup"] = (a / p if a and p else None)
            k_str = "inf" if k is None else str(k)
            print(f"[contention rate={rate:9.0f} k={k_str:>3s}] "
                  f"async {_h(a)} h  pipelined {_h(p)} h  sync {_h(s)} h  "
                  f"async/sync {cell['async_vs_sync_speedup'] or float('nan'):.1f}x  "
                  f"pipe/async {cell['pipelined_vs_async_speedup'] or float('nan'):.2f}x")
            cells.append(cell)
    return {"rates_bps": [float(r) for r in CONTENTION_RATES],
            "channels": list(CONTENTION_CHANNELS), "cells": cells}


def fault_sweep(w0, target: float, max_epochs: int, duration_s: float,
                ps_channels: Optional[int] = None) -> Dict:
    """AsyncFLEO convergence delay under injected faults: every dropout
    probability crossed with a compute-rate spread and a staleness
    function (12 cells).  Lossy cells retry with exponential backoff
    (max_retries=3, 120 s base), so moderate dropout costs delay rather
    than updates; the telemetry in each row's ``sched_stats`` records
    how many transfers failed / retried / dropped."""
    from repro.sched import FaultModel
    cells = []
    for drop in FAULT_DROPOUTS:
        for spread in FAULT_SPREADS:
            for sfn in FAULT_STALENESS:
                off = drop == 0.0 and spread == 0.0
                fm = None if off else FaultModel(
                    loss_prob=drop, compute_rate_spread=spread)
                r = bench_policy("async_asyncfleo", "asyncfleo-gs", w0,
                                 target, max_epochs, duration_s,
                                 ps_channels=ps_channels, fault=fm,
                                 staleness_fn=sfn)
                cell = {"dropout": drop, "compute_rate_spread": spread,
                        "staleness_fn": sfn, "row": r}
                st = r["sched_stats"]
                print(f"[fault drop={drop:4.2f} spread={spread:3.1f} "
                      f"{sfn:8s}] conv {_h(r['convergence_delay_s'])} h  "
                      f"failed {st['transfers_failed']:3d}  "
                      f"retried {st['transfer_retries']:3d}  "
                      f"dropped {st['dropped_after_max_retries']:3d}")
                cells.append(cell)
    return {"dropouts": list(FAULT_DROPOUTS),
            "compute_rate_spreads": list(FAULT_SPREADS),
            "staleness_fns": list(FAULT_STALENESS), "cells": cells}


def outage_smoke(w0, target: float, max_epochs: int,
                 duration_s: float) -> Dict:
    """The §11 PS-outage smoke cell: pipelined AsyncFLEO on the two-HAP
    ring with one HAP dark for a contiguous 30% of the horizon
    (explicit ``ps_outages``).  Ring failover + lazy arrival reroutes
    must carry the run to the target anyway — ``--fail-if-not-lower``
    gates on it converging.  The ``sched_stats`` telemetry
    (``sink_failovers`` / ``rerouted_arrivals`` / ``dropped_outage``)
    records how much recovery work that took."""
    from repro.sched import FaultModel
    # the dark window opens ~33 min in — right on top of the active
    # rounds (with the ring handoff, every other in-flight round is
    # sunk at PS 0 by then), not parked in the idle tail of the horizon
    dark = (0, 2000.0, 2000.0 + 0.3 * duration_s)
    fm = FaultModel(ps_outages=(dark,))
    r = bench_policy("async_pipelined_outage", "asyncfleo-twohap", w0,
                     target, max_epochs, duration_s, fault=fm,
                     spec_kw=dict(max_in_flight=3))
    st = r["sched_stats"]
    print(f"[outage ps=0 dark {dark[1] / 3600.0:.1f}-{dark[2] / 3600.0:.1f} h]"
          f" conv {_h(r['convergence_delay_s'])} h  "
          f"failovers {st['sink_failovers']:2d}  "
          f"rerouted {st['rerouted_arrivals']:3d}  "
          f"dropped {st['dropped_outage']:3d}")
    return {"ps_outages": [list(dark)], "row": r}


def scale_smoke(target: float, max_epochs: int, num_sats: int,
                num_ps: int, duration_s: float = 86400.0,
                dt_s: float = 30.0) -> Dict:
    """Mega-constellation scale cell (DESIGN.md §14): a Starlink-class
    S=10^4 shell over a P>=4 ``hapring`` of parameter servers compiles
    its contact plan through the SPARSE segment timeline (the dense
    (T, S, P) grid + (T, S, 3) positions would be gigabytes) and
    completes a ``max_epochs``-epoch event-driven run.  The row reports
    compile and run wall seconds separately; CI gates the total against
    an explicit budget (``--scale-budget-s``) so scale cannot rot."""
    spo = 250 if num_sats % 250 == 0 and num_sats >= 250 else num_sats
    cst = WalkerDelta(num_orbits=num_sats // spo, sats_per_orbit=spo,
                      altitude_m=550e3, inclination_deg=53.0)
    spec = dataclasses.replace(get_strategy("asyncfleo-gs"),
                               ps_scenario=f"hapring:{num_ps}")
    w0 = make_model()
    sim = SimConfig(duration_s=duration_s, dt_s=dt_s, train_time_s=300.0,
                    use_model_bank=True, use_fused_step=True,
                    event_driven=True, visibility="sparse")
    t0 = time.perf_counter()
    fls = FLSimulation(spec, ConvergingTrainer(w0),
                       MeanDistanceEvaluator(), sim, constellation=cst)
    rt = EventDrivenRuntime(fls)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    hist = rt.run(w0, max_epochs=max_epochs, target_accuracy=target)
    run_s = time.perf_counter() - t0
    row = {
        "num_sats": num_sats,
        "num_ps": num_ps,
        "duration_s": duration_s,
        "dt_s": dt_s,
        "visibility": "sparse",
        "epochs": len(hist),
        "final_accuracy": float(hist[-1].accuracy) if hist else None,
        "fused_dispatches": fls._fused_prog.dispatches,
        "event_counts": dict(rt.events.counts),
        "plan": fls.plan.summary(),
        "compile_wall_s": compile_s,
        "run_wall_s": run_s,
        "wall_s": compile_s + run_s,
    }
    print(f"scale smoke S={num_sats} P={num_ps}: compile {compile_s:.1f} s, "
          f"{len(hist)} epochs in {run_s:.1f} s, "
          f"{row['plan']['num_windows']} windows")
    return row


def _h(delay_s) -> str:
    return (f"{delay_s / 3600.0:6.2f}" if delay_s is not None
            else "  none")


def cnn_study(num_sats: int, target: float, max_epochs: int,
              duration_s: float) -> Dict:
    """Accuracy-aware convergence-delay study with the REAL CNN pools at
    S >= 200: the deterministic-trainer rows above isolate pure
    scheduling delay, this one re-runs the async / pipelined / sync
    head-to-head with actual federated CNN training on class-conditional
    image shards, so the measured delay includes genuine accuracy
    dynamics (staleness-discounted stale rounds really do contribute
    less).  Opt-in via ``--cnn-sats`` (minutes of wall time, not CI)."""
    import jax

    from repro.configs import MNIST_CNN
    from repro.core.constellation import WalkerDelta
    from repro.data import class_conditional_images, paper_noniid_partition
    from repro.fl import Evaluator, ImageClassifierPool
    from repro.models import cnn

    assert num_sats % 8 == 0, "num_sats must be a multiple of 8 (orbits)"
    const = WalkerDelta(num_orbits=num_sats // 8, sats_per_orbit=8,
                        altitude_m=2000e3, inclination_deg=80.0)
    cfg = dataclasses.replace(MNIST_CNN, conv_channels=(4, 8), hidden=32)
    imgs, labs = class_conditional_images(0, 3000, separation=1.2)
    ti, tl = class_conditional_images(99, 500, separation=1.2)
    shards = paper_noniid_partition(labs, const.orbit_ids(), 0)
    pool = ImageClassifierPool(cfg, imgs, labs, shards, local_iters=20,
                               lr=0.05)
    ev = Evaluator(cfg, ti, tl)
    w0 = jax.device_get(cnn.init_params(jax.random.PRNGKey(0), cfg))

    out = {"num_sats": num_sats, "target_accuracy": target, "rows": []}
    for name, strategy in (("async_asyncfleo", "asyncfleo-gs"),
                           ("async_pipelined", "asyncfleo-pipelined"),
                           ("sync_gs_fedavg", "fedisl")):
        sim = SimConfig(duration_s=duration_s, dt_s=30.0, train_time_s=300.0,
                        use_model_bank=True, use_fused_step=True,
                        event_driven=True)
        fls = FLSimulation(get_strategy(strategy), pool, ev, sim,
                           constellation=const)
        rt = EventDrivenRuntime(fls)
        # staleness-discounted pipelined rounds contribute smaller steps,
        # so the pipeline gets a proportionally larger epoch budget (it
        # fits them in less simulated time — that trade is the point)
        budget = max_epochs * (2 if strategy == "asyncfleo-pipelined"
                               else 1)
        t0 = time.perf_counter()
        hist = rt.run(w0, max_epochs=budget, target_accuracy=target)
        wall = time.perf_counter() - t0
        conv = convergence_time(hist, target)
        row = {
            "policy": name,
            "strategy": strategy,
            "convergence_delay_s": conv,
            "epochs_to_target": (len(hist) if conv is not None else None),
            "final_accuracy": float(hist[-1].accuracy) if hist else None,
            "aggregations": len(hist),
            "sched_stats": dict(rt.stats),
            "wall_s": wall,
        }
        out["rows"].append(row)
        conv_h = conv / 3600.0 if conv is not None else float("nan")
        acc = (row["final_accuracy"] if row["final_accuracy"] is not None
               else float("nan"))
        print(f"[cnn S={num_sats}] {name:18s}: "
              f"conv_delay {conv_h:8.2f} h"
              f"  aggs {len(hist)}  final_acc {acc:.3f}"
              f"  wall {wall:.1f} s")
    return out


def policy_sweep(w0, target: float, max_epochs: int, duration_s: float,
                 n_scenarios: int, ps_channels: Optional[int] = None) -> Dict:
    """Percentile-band Monte-Carlo sweep (DESIGN.md §13): the async /
    pipelined / sync head-to-head over ``n_scenarios`` seeds per policy,
    all 3 x n scenarios multiplexed through ONE DispatchBatcher so the
    whole sweep costs a handful of physical device programs.  Emits one
    band cell per policy (p10/p50/p90 over convergence delay, epochs to
    target, final accuracy, aggregations, plus the draw spec) and the
    sweep-wide dispatch economy (logical = what the same scenarios cost
    sequentially, a parity invariant; physical = programs actually
    launched, counted by the batcher).  Under
    ``--fail-if-not-lower`` the async<sync and pipelined<=async gates
    move onto the p50 band, and physical < logical is itself a gate."""
    from repro.sweep import (DispatchBatcher, ScenarioSpec, grid,
                             reduce_results, run_scenarios)
    seeds = list(range(n_scenarios))
    rows = POLICY_ROWS[:3]
    base = ScenarioSpec(duration_s=duration_s, dt_s=30.0,
                        train_time_s=300.0, ps_channels=ps_channels)
    specs = grid(base, strategy=[s for _, s in rows], seed=seeds)
    batcher = DispatchBatcher(mode="exact")
    t0 = time.perf_counter()
    results = run_scenarios(specs, w0, batched=True, max_epochs=max_epochs,
                            target_accuracy=target, batcher=batcher)
    wall = time.perf_counter() - t0
    by_strategy: Dict[str, list] = {}
    for spec, res in zip(specs, results):
        by_strategy.setdefault(spec.strategy, []).append(res)
    cells = []
    for name, strategy in rows:
        rs = by_strategy[strategy]
        bands = reduce_results(rs)
        cells.append({
            "policy": name, "strategy": strategy,
            "n_scenarios": len(rs),
            "draw": {"kind": "grid", "axes": {"seed": seeds}},
            "bands": bands,
            "logical_dispatches": sum(r.dispatches + r.fallback_dispatches
                                      for r in rs),
        })
        band = bands["convergence_delay_s"]
        print(f"[sweep n={len(rs)}] {name:18s}: conv_delay p50 "
              f"{_h(band['p50'])} (p10 {_h(band['p10'])}, "
              f"p90 {_h(band['p90'])}, {band['n_failed']} failed)")
    logical = sum(r.dispatches + r.fallback_dispatches for r in results)
    print(f"[sweep] dispatch economy: {batcher.physical_dispatches} "
          f"physical vs {logical} logical "
          f"(max group {batcher.max_group})")
    return {
        "n_scenarios": len(specs), "target": target,
        "cells": cells,
        "dispatch_economy": {
            "logical_dispatches": logical,
            "physical_dispatches": batcher.physical_dispatches,
            "batcher": batcher.summary(),
        },
        "wall_s": wall,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--target", type=float, default=0.9)
    ap.add_argument("--max-epochs", type=int, default=30)
    ap.add_argument("--days", type=float, default=3.0)
    ap.add_argument("--out", default="BENCH_sched.json")
    ap.add_argument("--fail-if-not-lower", action="store_true",
                    help="exit 1 unless AsyncFLEO's convergence delay is "
                         "strictly lower than the sync GS-FedAvg baseline, "
                         "the pipelined runtime's is no higher than "
                         "single-round async, and async still strictly "
                         "beats sync in the ps_channels=1 cell at the "
                         "lowest swept rate (unless the sweep is skipped)")
    ap.add_argument("--ps-channels", type=int, default=None,
                    help="finite per-PS link capacity for the MAIN policy "
                         "rows (StrategySpec.ps_channels; <=0 or omitted "
                         "= infinite parallelism)")
    ap.add_argument("--skip-contention-sweep", action="store_true",
                    help="skip the (rate_bps x ps_channels) contention "
                         "sweep cells")
    ap.add_argument("--skip-fault-sweep", action="store_true",
                    help="skip the (dropout x compute spread x staleness "
                         "fn) robustness sweep cells")
    ap.add_argument("--trace-out", default=None,
                    help="emit a Perfetto-loadable Chrome trace of the "
                         "pipelined async row to this path (plus JSONL "
                         "next to it) and gate tracer=None bit-parity, "
                         "trace schema validity, and >=1 round span per "
                         "committed epoch (DESIGN.md §12)")
    ap.add_argument("--cnn-sats", type=int, default=0,
                    help="also run the accuracy-aware CNN study at this "
                         "constellation size (>= 200 for the ROADMAP item; "
                         "0 = skip)")
    ap.add_argument("--cnn-target", type=float, default=0.55,
                    help="target test accuracy for the CNN study")
    ap.add_argument("--cnn-max-epochs", type=int, default=10)
    ap.add_argument("--scale-sats", type=int, default=0,
                    help="run the mega-constellation scale smoke cell at "
                         "this constellation size over a hapring of "
                         "--scale-ps parameter servers with sparse "
                         "contact compilation (DESIGN.md §14); 0 = skip")
    ap.add_argument("--scale-ps", type=int, default=4,
                    help="parameter servers in the scale cell's hapring")
    ap.add_argument("--scale-epochs", type=int, default=2,
                    help="event-driven epochs the scale cell must commit")
    ap.add_argument("--scale-budget-s", type=float, default=0.0,
                    help="explicit wall-clock budget for the scale cell "
                         "(compile + run); exceeded => exit 1, so scale "
                         "cannot rot (0 = report only, no gate)")
    ap.add_argument("--scale-only", action="store_true",
                    help="run ONLY the scale smoke cell (the CI scale "
                         "step: everything else lives in the main "
                         "benchmark invocation)")
    ap.add_argument("--sweep", type=int, default=0,
                    help="run the batched Monte-Carlo policy sweep with "
                         "this many seeds per policy cell (DESIGN.md "
                         "§13): p10/p50/p90 band rows land in the "
                         "report's 'sweep' section and, under "
                         "--fail-if-not-lower, the async<sync and "
                         "pipelined<=async gates move onto the p50 band "
                         "plus a physical<logical dispatch-economy gate; "
                         "0 = skip (single-seed gates)")
    args = ap.parse_args()

    if args.scale_only:
        if not args.scale_sats:
            raise SystemExit("--scale-only requires --scale-sats")
        row = scale_smoke(args.target, args.scale_epochs,
                          args.scale_sats, args.scale_ps)
        report = {"scale_smoke": row}
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {args.out}")
        if row["epochs"] < args.scale_epochs:
            raise SystemExit(
                f"scale smoke committed only {row['epochs']} epochs "
                f"(expected {args.scale_epochs})")
        if args.scale_budget_s and row["wall_s"] > args.scale_budget_s:
            raise SystemExit(
                f"scale smoke wall clock {row['wall_s']:.1f} s exceeded "
                f"the {args.scale_budget_s:.0f} s budget "
                f"(S={args.scale_sats}, P={args.scale_ps})")
        return

    w0 = make_model()
    main_channels = (args.ps_channels if args.ps_channels
                     and args.ps_channels > 0 else None)
    report = {"target": args.target, "ps_channels": main_channels,
              "policies": []}
    for name, strategy in POLICY_ROWS:
        # per-arrival aggregations are single-model EMA steps, so FedAsync
        # needs ~participants-per-round more of them per unit of progress
        budget = (args.max_epochs * 20 if strategy == "fedasync"
                  else args.max_epochs)
        r = bench_policy(name, strategy, w0, args.target, budget,
                         args.days * 86400.0, ps_channels=main_channels)
        conv = r["convergence_delay_s"]
        print(f"{name:22s} ({strategy:13s}): conv_delay "
              f"{conv / 3600.0 if conv else float('nan'):8.2f} h  "
              f"epochs {r['epochs_to_target']}  "
              f"dispatches {r['fused_dispatches']}  wall {r['wall_s']:.2f} s")
        report["policies"].append(r)

    by_name = {r["policy"]: r for r in report["policies"]}
    a = by_name["async_asyncfleo"]["convergence_delay_s"]
    p = by_name["async_pipelined"]["convergence_delay_s"]
    s = by_name["sync_gs_fedavg"]["convergence_delay_s"]
    report["async_vs_sync_speedup"] = (s / a if a and s else None)
    report["pipelined_vs_async_speedup"] = (a / p if a and p else None)
    if report["async_vs_sync_speedup"]:
        print(f"async/sync convergence-delay speedup: "
              f"{report['async_vs_sync_speedup']:.1f}x")
    if report["pipelined_vs_async_speedup"]:
        print(f"pipelined/single-round async speedup: "
              f"{report['pipelined_vs_async_speedup']:.2f}x")

    if args.trace_out:
        report["trace_smoke"] = trace_smoke(
            w0, args.target, args.max_epochs, args.days * 86400.0,
            args.trace_out)

    if not args.skip_contention_sweep:
        report["contention_sweep"] = contention_sweep(
            w0, args.target, args.max_epochs, args.days * 86400.0)

    if not args.skip_fault_sweep:
        report["fault_sweep"] = fault_sweep(
            w0, args.target, args.max_epochs, args.days * 86400.0,
            ps_channels=main_channels)
        # §11 defaults bit-parity row: an EXPLICIT FaultModel() — every
        # new axis at its default — must reproduce the fault=None main
        # async row exactly (gated below)
        from repro.sched import FaultModel
        report["fault_defaults_parity"] = bench_policy(
            "async_fault_defaults", "asyncfleo-gs", w0, args.target,
            args.max_epochs, args.days * 86400.0,
            ps_channels=main_channels, fault=FaultModel())
        report["outage_smoke"] = outage_smoke(
            w0, args.target, args.max_epochs, args.days * 86400.0)

    if args.sweep:
        report["sweep"] = policy_sweep(
            w0, args.target, args.max_epochs, args.days * 86400.0,
            args.sweep, ps_channels=main_channels)

    if args.cnn_sats:
        report["cnn_study"] = cnn_study(args.cnn_sats, args.cnn_target,
                                        args.cnn_max_epochs,
                                        args.days * 86400.0)

    if args.scale_sats:
        report["scale_smoke"] = scale_smoke(
            args.target, args.scale_epochs, args.scale_sats, args.scale_ps)

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")

    if args.scale_sats:
        row = report["scale_smoke"]
        if row["epochs"] < args.scale_epochs:
            raise SystemExit(
                f"scale smoke committed only {row['epochs']} epochs "
                f"(expected {args.scale_epochs})")
        if args.scale_budget_s and row["wall_s"] > args.scale_budget_s:
            raise SystemExit(
                f"scale smoke wall clock {row['wall_s']:.1f} s exceeded "
                f"the {args.scale_budget_s:.0f} s budget")

    if args.fail_if_not_lower:
        if args.sweep:
            # distributional gates (DESIGN.md §13): with band rows
            # available, the async<sync and pipelined<=async orderings
            # gate on the MEDIAN over the seed draw instead of one seed
            bands = {c["policy"]: c["bands"]["convergence_delay_s"]
                     for c in report["sweep"]["cells"]}
            a50 = bands["async_asyncfleo"]["p50"]
            p50 = bands["async_pipelined"]["p50"]
            s50 = bands["sync_gs_fedavg"]["p50"]
            if a50 is None or s50 is None or not a50 < s50:
                raise SystemExit(
                    f"p50 async convergence delay ({a50}) not strictly "
                    f"lower than p50 sync ({s50}) over "
                    f"{report['sweep']['n_scenarios']} scenarios")
            if p50 is None or not p50 <= a50:
                raise SystemExit(
                    f"p50 pipelined convergence delay ({p50}) worse "
                    f"than p50 single-round async ({a50})")
            econ = report["sweep"]["dispatch_economy"]
            if not econ["physical_dispatches"] < econ["logical_dispatches"]:
                raise SystemExit(
                    f"sweep dispatch economy broken: "
                    f"{econ['physical_dispatches']} physical programs "
                    f"for {econ['logical_dispatches']} logical "
                    f"dispatches (batching bought nothing)")
        elif a is None or s is None or not a < s:
            raise SystemExit(
                f"async convergence delay ({a}) not strictly lower than "
                f"sync ({s})")
        if not args.sweep and (p is None or not p <= a):
            raise SystemExit(
                f"pipelined convergence delay ({p}) worse than "
                f"single-round async ({a})")
        if not args.skip_contention_sweep:
            # the paper-relevant NEW ordering: async must beat sync even
            # when a single-channel PS serializes every transfer at the
            # bandwidth-constrained rate (DESIGN.md §9)
            cell = next(c for c in report["contention_sweep"]["cells"]
                        if c["ps_channels"] == 1
                        and c["rate_bps"] == min(CONTENTION_RATES))
            by = {r["policy"]: r["convergence_delay_s"]
                  for r in cell["rows"]}
            ac, sc = by["async_asyncfleo"], by["sync_gs_fedavg"]
            if ac is None or sc is None or not ac < sc:
                raise SystemExit(
                    f"contended async convergence delay ({ac}) not "
                    f"strictly lower than contended sync ({sc}) at "
                    f"ps_channels=1, rate={min(CONTENTION_RATES)} bps")
        if not args.skip_fault_sweep:
            # off-switch parity pin (DESIGN.md §10): the all-off fault
            # cell must reproduce the main async row EXACTLY — the fault
            # layer with fault_model=None is bit-identical to not having
            # the layer at all
            null = next(c["row"] for c in report["fault_sweep"]["cells"]
                        if c["dropout"] == 0.0
                        and c["compute_rate_spread"] == 0.0
                        and c["staleness_fn"] == "eq13")
            ref = by_name["async_asyncfleo"]
            keys = ("convergence_delay_s", "epochs_to_target",
                    "final_accuracy", "aggregations", "fused_dispatches")
            drift = [k for k in keys if null[k] != ref[k]]
            if drift:
                raise SystemExit(
                    f"fault off-switch parity broken: null fault cell "
                    f"differs from the main async row on {drift}")
            # and the robustness claim: async still converges with one
            # transfer in five dropped (retry/backoff absorbs the loss)
            bad = [c for c in report["fault_sweep"]["cells"]
                   if c["dropout"] == max(FAULT_DROPOUTS)
                   and c["row"]["convergence_delay_s"] is None]
            if bad:
                raise SystemExit(
                    f"{len(bad)} dropout={max(FAULT_DROPOUTS)} fault "
                    f"cells failed to reach the target accuracy")
            # §11 defaults bit-parity gate: the explicit-FaultModel()
            # row (burst / outage / energy / adaptive-backoff axes all
            # at their defaults) must match the fault=None main async
            # row on every deterministic key — the new axes' off
            # switches are bit-exact, not just approximately quiet
            null_fm = report["fault_defaults_parity"]
            drift = [k for k in keys if null_fm[k] != ref[k]]
            if drift:
                raise SystemExit(
                    f"§11 defaults parity broken: explicit FaultModel() "
                    f"row differs from the main async row on {drift}")
            # §11 outage smoke gate: pipelined async must still reach
            # the target with one ring HAP dark for a contiguous 30% of
            # the horizon (ring failover + arrival reroutes)
            if report["outage_smoke"]["row"]["convergence_delay_s"] is None:
                raise SystemExit(
                    "outage smoke cell failed: pipelined async did not "
                    "reach the target with one PS dark 30% of the horizon")


if __name__ == "__main__":
    main()
