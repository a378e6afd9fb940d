"""Multiplexing per-scenario epoch dispatches into shared device programs.

The event-driven runtime is host logic — contact plans, priority queues,
channel reservations differ per scenario and stay per-scenario.  What IS
shared is the device work: every committed epoch funnels through
``EpochStepProgram.step``.  The sweep engine therefore runs each
scenario's full runtime on its own worker thread and intercepts that one
choke point with a ``BatchedProgram`` proxy: instead of dispatching, the
worker enqueues a *dispatch request* and blocks.  When every live
scenario is either blocked on a request or finished, the driver thread
flushes: requests with identical static signatures (same program spec,
participant count, carry rows, kpad/blocked_m, fallback split, batch
structure and the trainer's ``scenario_batch_key``) become ONE physical
``batched_step`` dispatch; singletons and unbatchable programs (mesh /
Pallas kernel / no batch key) run solo through their own ``step`` —
trivially bit-exact.  Each scenario gets back lazy ``out[j]`` slices, so
nothing blocks at flush time; workers force values only where the
sequential runtime already would (evaluator, recorded stats).

Deadlock-freedom: workers block only inside ``submit``; the driver
flushes exactly when no worker can make progress without it; every lazy
value a worker forces after waking was enqueued by that flush.

Parity contract (DESIGN.md §13, pinned by tests/test_sweep.py and the
hypothesis property): per-scenario histories, weights and *logical*
dispatch counts from a batched run are bit-identical to running each
scenario sequentially — ``mode="exact"`` dispatches the same per-scenario
HLO, just unrolled into one program.  ``mode="vmap"`` trades that for one
batched GEMM (not bit-exact; opt-in).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _normalize_step_args(w_flat, carry, inputs, ids_np, seed, wv_bank,
                         wv_carry, base_w, dw_row, dw_seg, kpad, blocked_m,
                         dw_carry, ref):
    """Exactly ``EpochStepProgram.step``'s host->device conversions, done
    once at enqueue time so grouping and stacking see committed arrays.
    The result re-passes through ``step`` unchanged (every conversion is
    idempotent), so solo execution stays bit-identical."""
    return (w_flat, carry, inputs,
            jnp.asarray(ids_np, jnp.int32), np.uint32(seed),
            jnp.asarray(np.asarray(wv_bank, np.float32)),
            jnp.asarray(np.asarray(wv_carry, np.float32)),
            np.float32(base_w),
            jnp.asarray(np.asarray(dw_row, np.float32)),
            jnp.asarray(np.asarray(dw_seg, np.int32)),
            int(kpad), int(blocked_m),
            jnp.asarray(np.asarray(dw_carry, np.float32)),
            ref)


def _inputs_sig(inputs) -> Optional[Tuple]:
    if inputs is None:
        return None
    leaves, treedef = jax.tree.flatten(inputs)
    return (treedef,
            tuple((tuple(l.shape), str(getattr(l, "dtype", type(l))))
                  for l in leaves))


@dataclasses.dataclass
class _Request:
    """One scenario's pending epoch dispatch."""
    prog: Any                          # the scenario's own EpochStepProgram
    args: Tuple                        # normalized step-order args (14)
    fallback: bool
    sig: Tuple                         # grouping signature
    event: threading.Event = dataclasses.field(default_factory=threading.Event)
    out: Optional[Tuple] = None
    error: Optional[BaseException] = None


class BatchedProgram:
    """Drop-in ``EpochStepProgram`` facade handed to one scenario's
    simulator/runtime: same ``spec``/``step`` surface, same
    *logical* dispatch counters (``dispatches``/``fallback_dispatches``
    advance exactly as a sequential run's would — a parity invariant),
    but ``step`` routes through the shared :class:`DispatchBatcher`."""

    def __init__(self, batcher: "DispatchBatcher", inner, key=None):
        self._batcher = batcher
        self._inner = inner
        self._key = key
        self.dispatches = 0
        self.fallback_dispatches = 0

    @property
    def spec(self):
        return self._inner.spec

    def _batchable(self) -> bool:
        return (self._key is not None and self._inner.mesh is None
                and not self._inner.use_kernel)

    def step(self, w_flat, carry, inputs, ids_np, seed, wv_bank, wv_carry,
             base_w, dw_row, dw_seg, kpad, blocked_m, dw_carry, ref,
             *, fallback: bool = False):
        if fallback:
            self.fallback_dispatches += 1
        else:
            self.dispatches += 1
        args = _normalize_step_args(w_flat, carry, inputs, ids_np, seed,
                                    wv_bank, wv_carry, base_w, dw_row,
                                    dw_seg, kpad, blocked_m, dw_carry, ref)
        sig = (self._key if self._batchable() else None,
               self._inner.spec, int(args[1].shape[0]),
               int(args[3].shape[0]), int(kpad), int(blocked_m),
               bool(fallback), _inputs_sig(inputs))
        return self._batcher.submit(
            _Request(self._inner, args, bool(fallback), sig))


class DispatchBatcher:
    """The barrier + flush engine shared by one sweep's scenarios.

    Lifecycle: the driver ``register()``s each scenario before starting
    its worker thread, then loops in ``drain()`` on the main thread;
    workers go through ``wrap()``ed programs whose ``step`` calls
    ``submit()`` and blocks; ``finish()`` retires a worker.  All jit
    execution happens on the driver thread inside ``drain`` — workers
    only build arrays and force already-enqueued values.
    """

    def __init__(self, mode: str = "exact"):
        if mode not in ("exact", "vmap"):
            raise ValueError(f"unknown scenario batch mode {mode!r}")
        self.mode = mode
        self._cv = threading.Condition()
        self._pending: List[_Request] = []
        self._live = 0                 # registered, not yet finished
        self._running = 0              # live and not blocked in submit()
        # telemetry — physical accounting (logical lives on the proxies)
        self.flushes = 0
        self.physical_dispatches = 0   # programs actually launched
        self.batched_dispatches = 0    # ... of which multi-scenario
        self.solo_dispatches = 0       # ... of which single-scenario
        self.max_group = 0

    # ---- worker side -------------------------------------------------------

    def register(self) -> None:
        with self._cv:
            self._live += 1
            self._running += 1

    def wrap(self, prog, key=None):
        """Proxy ``prog`` for one scenario; ``key`` is the trainer's
        ``scenario_batch_key`` (None -> every dispatch runs solo)."""
        if prog is None:
            return None
        return BatchedProgram(self, prog, key=key)

    def submit(self, req: _Request):
        with self._cv:
            self._pending.append(req)
            self._running -= 1
            self._cv.notify_all()
        req.event.wait()
        with self._cv:
            self._running += 1
        if req.error is not None:
            raise req.error
        return req.out

    def finish(self) -> None:
        with self._cv:
            self._live -= 1
            self._running -= 1
            self._cv.notify_all()

    # ---- driver side -------------------------------------------------------

    def drain(self) -> None:
        """Run on the driver thread until every registered scenario has
        finished: wait for the barrier (no runnable worker), flush."""
        while True:
            with self._cv:
                self._cv.wait_for(
                    lambda: self._running == 0 and (self._pending
                                                    or self._live == 0))
                if not self._pending and self._live == 0:
                    return
                batch, self._pending = self._pending, []
            self._flush(batch)

    def _flush(self, batch: List[_Request]) -> None:
        groups: dict = {}
        for req in batch:
            groups.setdefault(req.sig, []).append(req)
        self.flushes += 1
        for reqs in groups.values():
            try:
                self._execute(reqs)
            except BaseException as e:   # propagate into every blocked worker
                for r in reqs:
                    r.error = e
            finally:
                for r in reqs:
                    r.event.set()

    def _execute(self, reqs: List[_Request]) -> None:
        if len(reqs) == 1 or reqs[0].sig[0] is None:
            # singleton or unbatchable: the scenario's own program, its
            # own step() — bit-exact by construction
            for r in reqs:
                r.out = r.prog.step(*r.args, fallback=r.fallback)
                self.physical_dispatches += 1
                self.solo_dispatches += 1
            self.max_group = max(self.max_group, 1)
            return
        prog = reqs[0].prog            # batch_key certifies equivalence
        cols = list(zip(*(r.args for r in reqs)))
        if cols[2][0] is None:
            inputs = None
        else:
            inputs = jax.tree.map(lambda *ls: jnp.stack(ls), *cols[2])
        kpad, blocked_m = reqs[0].args[10], reqs[0].args[11]
        out = prog.batched_step(
            jnp.stack(cols[0]), jnp.stack(cols[1]), inputs,
            jnp.stack(cols[3]),
            jnp.asarray(np.asarray(cols[4], np.uint32)),
            jnp.stack(cols[5]), jnp.stack(cols[6]),
            jnp.asarray(np.asarray(cols[7], np.float32)),
            jnp.stack(cols[8]), jnp.stack(cols[9]), kpad, blocked_m,
            jnp.stack(cols[12]), jnp.stack(cols[13]),
            mode=self.mode)
        for j, r in enumerate(reqs):
            r.out = tuple(o[j] for o in out)
        self.physical_dispatches += 1
        self.batched_dispatches += 1
        self.max_group = max(self.max_group, len(reqs))

    def summary(self) -> dict:
        return {"flushes": self.flushes,
                "physical_dispatches": self.physical_dispatches,
                "batched_dispatches": self.batched_dispatches,
                "solo_dispatches": self.solo_dispatches,
                "max_group": self.max_group,
                "mode": self.mode}
