"""FL training launcher — the production entrypoint for the paper's system.

    PYTHONPATH=src python -m repro.launch.fl_train \
        --strategy asyncfleo-hap --epochs 8 --target 0.8 \
        [--iid] [--dataset mnist|cifar] [--model cnn|mlp] \
        [--checkpoint out/server.npz] [--resume out/server.npz]

Runs the discrete-event constellation simulation with real JAX training on
the paper's models at their published widths, and checkpoints the trained
PS state (global model + epoch + grouping).  :func:`build_run` is the
per-run setup; ``chip_smoke.py`` drives the same run on the chip.
"""
from __future__ import annotations

import argparse
import os
import sys

import jax

from repro.checkpoint import load_server_state, save_server_state
from repro.compile_cache import configure_compile_cache
from repro.configs import CIFAR_CNN, CIFAR_MLP, MNIST_CNN, MNIST_MLP
from repro.core import FLSimulation, SimConfig, convergence_time, paper_constellation
from repro.data import class_conditional_images, iid_partition, paper_noniid_partition
from repro.fl import Evaluator, ImageClassifierPool, STRATEGIES, get_strategy
from repro.models import cnn


def build_run(cfg, spec, sim: SimConfig, *, iid: bool = False,
              local_iters: int = 30, separation: float = 0.8,
              seed: int = 0):
    """One run of the paper's setup: the S=40 paper constellation, seeded
    class-conditional images at ``cfg``'s shape (4000 train, 1000 test),
    the IID or paper non-IID partition, an ``ImageClassifierPool`` and its
    evaluator.  Returns (FLSimulation, initial params on the host)."""
    const = paper_constellation()
    imgs, labs = class_conditional_images(seed, 4000, size=cfg.image_size,
                                          channels=cfg.channels,
                                          separation=separation)
    ti, tl = class_conditional_images(seed + 99, 1000, size=cfg.image_size,
                                      channels=cfg.channels,
                                      separation=separation)
    shards = (iid_partition(labs, const.num_sats, seed) if iid
              else paper_noniid_partition(labs, const.orbit_ids(), seed))
    pool = ImageClassifierPool(cfg, imgs, labs, shards,
                               local_iters=local_iters)
    w0 = jax.device_get(cnn.init_params(jax.random.PRNGKey(seed), cfg))
    return FLSimulation(spec, pool, Evaluator(cfg, ti, tl), sim, const), w0


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--strategy", default="asyncfleo-hap",
                    choices=sorted(STRATEGIES))
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--target", type=float, default=None)
    ap.add_argument("--iid", action="store_true")
    ap.add_argument("--dataset", default="mnist", choices=["mnist", "cifar"])
    ap.add_argument("--model", default="cnn", choices=["cnn", "mlp"])
    ap.add_argument("--local-iters", type=int, default=30)
    ap.add_argument("--days", type=float, default=3.0)
    ap.add_argument("--separation", type=float, default=0.8)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--resume", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = {("mnist", "cnn"): MNIST_CNN, ("mnist", "mlp"): MNIST_MLP,
           ("cifar", "cnn"): CIFAR_CNN, ("cifar", "mlp"): CIFAR_MLP}[
        (args.dataset, args.model)]
    sim, w0 = build_run(cfg, get_strategy(args.strategy),
                        SimConfig(duration_s=args.days * 86400.0,
                                  seed=args.seed),
                        iid=args.iid, local_iters=args.local_iters,
                        separation=args.separation, seed=args.seed)
    if args.resume:
        w0, side = load_server_state(args.resume)
        print(f"resumed from {args.resume} at epoch {side['epoch']}")

    print(f"strategy={args.strategy} sats={sim.constellation.num_sats} "
          f"iid={args.iid} dataset={args.dataset}/{args.model}")
    hist = sim.run(w0, max_epochs=args.epochs, target_accuracy=args.target)
    for r in hist:
        print(f"epoch {r.epoch:3d}  sim {r.time_s/3600:6.2f} h  "
              f"acc {r.accuracy:.4f}  models {r.num_models:2d}  "
              f"gamma {r.gamma:.2f}")
    if args.checkpoint and hist:
        os.makedirs(os.path.dirname(os.path.abspath(args.checkpoint)),
                    exist_ok=True)
        save_server_state(args.checkpoint,
                          global_model=jax.device_get(sim.global_model()),
                          epoch=hist[-1].epoch,
                          grouping=sim.grouping.groups)
        print(f"server state -> {args.checkpoint}")
    if args.target:
        conv = convergence_time(hist, args.target)
        print(f"convergence to {args.target}: "
              f"{conv/3600:.2f} h" if conv else "not reached")
    return 0


if __name__ == "__main__":
    sys.exit(main())
