"""Strategy comparison driver (paper Table II / Fig. 6, configurable).

    PYTHONPATH=src python examples/fl_constellation_sim.py \
        --schemes asyncfleo-hap fedhap --epochs 8 --iid

Runs the simulation for each scheme on the same data and prints
accuracy-vs-simulated-time CSV curves — the paper's Fig. 6.

``--event-driven`` swaps the epoch loop for the event-driven async
scheduler (`repro.sched`): the same constellation is compiled into a
contact plan, each scheme runs under its trigger policy (AsyncFLEO idle
window / sync barrier / FedAsync per-arrival, see DESIGN.md §7), and the
compiled plan's window statistics are printed alongside the curves:

    PYTHONPATH=src python examples/fl_constellation_sim.py \
        --schemes asyncfleo-hap fedasync fedisl --event-driven

``--max-in-flight N`` (N > 1) additionally pipelines every scheme's
rounds — up to N overlapping rounds in flight per the DESIGN.md §8
round model (the ``asyncfleo-pipelined`` scheme ships with depth 3 and
the contact-plan handoff built in):

    PYTHONPATH=src python examples/fl_constellation_sim.py \
        --schemes asyncfleo-pipelined asyncfleo-gs --event-driven

The fault / heterogeneity flags (DESIGN.md §10) inject failures into
every scheme: ``--dropout`` makes each uplink transfer fail with that
probability (retried with exponential backoff; forces --event-driven),
``--compute-spread`` stretches each satellite's training time by a
seeded per-sat multiplier in [1, 1+spread], ``--eclipse-fraction``
blacks out each satellite for that fraction of a phase-shifted orbital
period, and ``--staleness-fn`` swaps eq. 13's staleness discount for a
FedAsync-family alternative:

    PYTHONPATH=src python examples/fl_constellation_sim.py \
        --schemes asyncfleo-gs fedisl --event-driven \
        --dropout 0.2 --compute-spread 1.0 --staleness-fn poly
"""
import argparse
import dataclasses
import sys

import jax

sys.path.insert(0, "src")

from repro.compile_cache import configure_compile_cache
from repro.configs import MNIST_CNN
from repro.core import (FLSimulation, SimConfig, convergence_time,
                        paper_constellation)
from repro.data import (class_conditional_images, iid_partition,
                        paper_noniid_partition)
from repro.fl import Evaluator, ImageClassifierPool, get_strategy, STRATEGIES
from repro.models import cnn


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--schemes", nargs="+", default=["asyncfleo-hap", "fedhap"],
                    choices=sorted(STRATEGIES))
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--iid", action="store_true")
    ap.add_argument("--target", type=float, default=0.75)
    ap.add_argument("--days", type=float, default=3.0)
    ap.add_argument("--event-driven", action="store_true",
                    help="drive each scheme with the async event scheduler "
                         "(contact plan + trigger policies) instead of the "
                         "epoch loop")
    ap.add_argument("--max-in-flight", type=int, default=0,
                    help="override every scheme's pipeline depth (rounds "
                         "in flight, DESIGN.md §8); 0 keeps each "
                         "strategy's own setting, >1 implies "
                         "--event-driven")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="per-transfer loss probability (retried with "
                         "exponential backoff, DESIGN.md §10); >0 implies "
                         "--event-driven")
    ap.add_argument("--compute-spread", type=float, default=0.0,
                    help="per-sat compute heterogeneity: training time "
                         "stretched by a seeded multiplier in "
                         "[1, 1+spread]")
    ap.add_argument("--eclipse-fraction", type=float, default=0.0,
                    help="fraction of each (phase-shifted) orbital period "
                         "a satellite is unavailable")
    ap.add_argument("--staleness-fn", default="eq13",
                    choices=["eq13", "constant", "hinge", "poly"],
                    help="staleness discount: the paper's eq. 13 or a "
                         "FedAsync-family alternative")
    args = ap.parse_args()
    if args.max_in_flight > 1 or args.dropout > 0.0:
        args.event_driven = True

    fault = None
    if args.dropout or args.compute_spread or args.eclipse_fraction:
        from repro.sched import FaultModel
        fault = FaultModel(loss_prob=args.dropout,
                           compute_rate_spread=args.compute_spread,
                           eclipse_fraction=args.eclipse_fraction)

    cfg = dataclasses.replace(MNIST_CNN, conv_channels=(8, 16))
    const = paper_constellation()
    imgs, labs = class_conditional_images(0, 4000, separation=0.8)
    ti, tl = class_conditional_images(99, 1000, separation=0.8)
    shards = (iid_partition(labs, const.num_sats, 0) if args.iid
              else paper_noniid_partition(labs, const.orbit_ids(), 0))
    pool = ImageClassifierPool(cfg, imgs, labs, shards, local_iters=30)
    ev = Evaluator(cfg, ti, tl)
    w0 = jax.device_get(cnn.init_params(jax.random.PRNGKey(0), cfg))

    print("scheme,epoch,sim_time_h,accuracy,num_models,gamma")
    summary = []
    for name in args.schemes:
        spec = get_strategy(name)
        if args.max_in_flight:
            spec = dataclasses.replace(spec,
                                       max_in_flight=args.max_in_flight)
        if args.staleness_fn != "eq13":
            spec = dataclasses.replace(spec,
                                       staleness_fn=args.staleness_fn)
        sim = FLSimulation(spec, pool, ev,
                           SimConfig(duration_s=args.days * 86400.0,
                                     event_driven=args.event_driven,
                                     fault_model=fault))
        if args.event_driven:
            s = sim.plan.summary()
            print(f"# {name}: contact plan — {s['num_windows']} windows, "
                  f"coverage {s['coverage_fraction']:.3f}, "
                  f"mean window {s['mean_window_s']:.0f}s")
        if args.event_driven and fault is not None:
            # drive the runtime directly so the retry telemetry is visible
            from repro.sched import EventDrivenRuntime
            rt = EventDrivenRuntime(sim)
            hist = rt.run(w0, max_epochs=args.epochs)
            st = rt.stats
            print(f"# {name}: faults — transfers failed "
                  f"{st['transfers_failed']}, retried "
                  f"{st['transfer_retries']}, dropped "
                  f"{st['dropped_after_max_retries'] + st['dropped_unreachable']}")
        else:
            hist = sim.run(w0, max_epochs=args.epochs)
        for r in hist:
            print(f"{name},{r.epoch},{r.time_s/3600:.3f},{r.accuracy:.4f},"
                  f"{r.num_models},{r.gamma:.3f}")
        conv = convergence_time(hist, args.target)
        summary.append((name, max(r.accuracy for r in hist),
                        conv / 3600 if conv else None))
    print("\n# scheme,best_acc,conv_time_h(target=%.2f)" % args.target)
    for name, acc, conv in summary:
        print(f"# {name},{acc:.4f},{conv if conv else 'n/a'}")


if __name__ == "__main__":
    main()
