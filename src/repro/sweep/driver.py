"""The batched sweep driver: N scenarios, a handful of device programs.

``run_scenarios(specs)`` builds one full simulation per
:class:`~repro.sweep.scenario.ScenarioSpec` (strategy table lookup +
overrides, WalkerDelta geometry or the paper constellation, LinkModel at
the swept rate, seeded SimConfig), then runs them either

* **sequentially** (``batched=False``) — the exact pre-existing
  event-driven runtime path, one scenario after another; or
* **batched** (the default) — every scenario's runtime on its own worker
  thread with all fused epoch dispatches multiplexed through one shared
  :class:`~repro.sweep.batch.DispatchBatcher` on the calling thread.

The two paths are bit-identical per scenario (histories, weights,
logical dispatch counts) under ``mode="exact"`` — the differential
contract ``tests/test_sweep.py`` pins.  Results come back in spec order.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.core import FLSimulation, SimConfig, convergence_time
from repro.core.constellation import WalkerDelta
from repro.core.links import LinkModel
from repro.fl.strategies import get_strategy
from repro.sched import EventDrivenRuntime
from repro.sweep.batch import DispatchBatcher
from repro.sweep.scenario import ScenarioSpec
from repro.sweep.testbed import (ConvergingTrainer, MeanDistanceEvaluator,
                                 make_model)


@dataclasses.dataclass
class ScenarioResult:
    spec: ScenarioSpec
    history: list                       # EpochRecord rows
    final_weights: np.ndarray           # forced flat weights
    dispatches: int                     # logical fused dispatches
    fallback_dispatches: int
    convergence_delay_s: Optional[float]
    final_accuracy: Optional[float]
    epochs: int
    stats: Dict


def _build(spec: ScenarioSpec, w0, trainer, evaluator, dispatcher,
           const_cache: Dict):
    strat = get_strategy(spec.strategy)
    kw = {}
    if spec.staleness_fn is not None:
        kw["staleness_fn"] = spec.staleness_fn
    if spec.ps_channels is not None:
        kw["ps_channels"] = spec.ps_channels
    if spec.max_in_flight is not None:
        kw["max_in_flight"] = spec.max_in_flight
    if kw:
        strat = dataclasses.replace(strat, **kw)
    const = None
    if spec.num_orbits is not None:
        gkey = spec.geometry_key()
        const = const_cache.get(gkey)
        if const is None:
            const = const_cache[gkey] = WalkerDelta(
                num_orbits=spec.num_orbits,
                sats_per_orbit=spec.sats_per_orbit or 8,
                altitude_m=spec.altitude_m,
                inclination_deg=spec.inclination_deg)
    sim = SimConfig(duration_s=spec.duration_s, dt_s=spec.dt_s,
                    train_time_s=spec.train_time_s,
                    agg_timeout_s=spec.agg_timeout_s, seed=spec.seed,
                    link=LinkModel(rate_bps=spec.rate_bps),
                    event_driven=True, dispatcher=dispatcher)
    fls = FLSimulation(strat, trainer, evaluator, sim, constellation=const)
    return fls, EventDrivenRuntime(fls)


def run_scenarios(specs: Sequence[ScenarioSpec], w0=None, *,
                  batched: bool = True, mode: str = "exact",
                  max_epochs: int = 30,
                  target_accuracy: Optional[float] = None,
                  trainer_factory: Optional[Callable] = None,
                  evaluator_factory: Optional[Callable] = None,
                  batcher: Optional[DispatchBatcher] = None
                  ) -> List[ScenarioResult]:
    """Run every scenario; return :class:`ScenarioResult` in spec order.

    ``trainer_factory(w0)`` / ``evaluator_factory()`` default to ONE
    shared ``ConvergingTrainer`` / ``MeanDistanceEvaluator`` — sharing
    the (stateless) trainer shares its jitted program cache across
    scenarios, and its ``scenario_batch_key`` is what lets the batcher
    group them.  Pass ``batcher`` to inspect physical-dispatch telemetry
    after the run (``batcher.summary()``).
    """
    w0 = w0 if w0 is not None else make_model()
    if trainer_factory is None:
        shared = ConvergingTrainer(w0)
        trainer_factory = lambda _w0: shared        # noqa: E731
    if evaluator_factory is None:
        evaluator_factory = MeanDistanceEvaluator
    if batcher is None and batched:
        batcher = DispatchBatcher(mode=mode)
    const_cache: Dict = {}
    builds = [_build(s, w0, trainer_factory(w0), evaluator_factory(),
                     batcher if batched else None, const_cache)
              for s in specs]
    # pre-warm the shared program cache on this thread so concurrent
    # _init_run calls hit the cache instead of racing to populate it
    from repro.core.epoch_step import make_epoch_program
    for fls, _rt in builds:
        make_epoch_program(fls.trainer, w0, mesh=fls.sim.mesh,
                           use_kernel=fls.spec.use_agg_kernel)

    histories: List = [None] * len(specs)
    errors: List = [None] * len(specs)
    counts: List = [None] * len(specs)  # sequential per-scenario deltas

    def _finish(i: int) -> ScenarioResult:
        fls, rt = builds[i]
        hist = histories[i] or []
        conv = (convergence_time(hist, target_accuracy)
                if target_accuracy is not None else None)
        if counts[i] is not None:
            disp, fb = counts[i]
        else:                           # batched: the proxy counts
            prog = fls._fused_prog      # per-scenario logical dispatches
            disp = int(getattr(prog, "dispatches", 0))
            fb = int(getattr(prog, "fallback_dispatches", 0))
        return ScenarioResult(
            spec=specs[i], history=hist,
            final_weights=np.asarray(fls._w_flat),
            dispatches=disp, fallback_dispatches=fb,
            convergence_delay_s=conv,
            final_accuracy=(float(hist[-1].accuracy) if hist else None),
            epochs=len(hist), stats=dict(rt.stats))

    if not batched:
        # a shared trainer shares one program (and its counters) across
        # scenarios, so per-scenario dispatch counts are deltas
        for i, (fls, rt) in enumerate(builds):
            prog = make_epoch_program(fls.trainer, w0, mesh=fls.sim.mesh,
                                      use_kernel=fls.spec.use_agg_kernel)
            d0 = ((prog.dispatches, prog.fallback_dispatches)
                  if prog is not None else (0, 0))
            histories[i] = rt.run(w0, max_epochs=max_epochs,
                                  target_accuracy=target_accuracy)
            counts[i] = (((prog.dispatches - d0[0]),
                          (prog.fallback_dispatches - d0[1]))
                         if prog is not None else (0, 0))
        return [_finish(i) for i in range(len(specs))]

    def _worker(i: int) -> None:
        try:
            histories[i] = builds[i][1].run(
                w0, max_epochs=max_epochs,
                target_accuracy=target_accuracy)
        except BaseException as e:      # surfaced after drain
            errors[i] = e
        finally:
            batcher.finish()

    threads = []
    for i in range(len(specs)):
        batcher.register()
        t = threading.Thread(target=_worker, args=(i,),
                             name=f"scenario-{i}", daemon=True)
        threads.append(t)
    for t in threads:
        t.start()
    batcher.drain()
    for t in threads:
        t.join()
    for i, err in enumerate(errors):
        if err is not None:
            raise RuntimeError(
                f"scenario {i} ({specs[i]!r}) failed") from err
    return [_finish(i) for i in range(len(specs))]
