"""Discrete-event FL simulation over LEO trajectories (paper §V).

The simulator advances *simulated* time (seconds over a 3-day horizon) while
running *real* JAX training for every satellite's local model.  Per global
epoch beta:

  1. downlink  — Alg. 1 timing gives each satellite its receive time of
     w^beta (ring-of-stars + ISL relay for strategies that have ISL; plain
     next-visibility otherwise);
  2. train     — each satellite trains for J local iterations (real SGD),
     finishing ``train_time_s`` later in simulated time;
  3. uplink    — arrival time of each local model at the sink PS;
  4. aggregate — strategy-dependent trigger and rule (AsyncFLEO grouping +
     staleness discounting; FedAvg barrier; per-arrival; fixed interval);
  5. evaluate  — test accuracy of the new global model at the trigger time.

Three trainer paths, fastest first (DESIGN.md §2/§6):

* **fused** — trainers exposing the fused-epoch protocol
  (``epoch_train_fn`` + ``epoch_inputs``) run steps 2-4 as ONE donated
  jitted device program per epoch (``core/epoch_step.py``): propagation
  timing and all per-model weight metadata math happen on host *before*
  the dispatch, training/grouping-distances/aggregation happen inside it.
  Losses stay lazy device arrays the simulator never forces, and accuracy
  values are blocked on only when the history is finalized.  Carried
  stragglers live in a small device matrix re-gathered per epoch (never
  donated twice).
* **stacked** — trainers with ``train_many_stacked`` keep local models as
  one device-resident (C, N) stack through grouping and aggregation but
  issue separate (still fused per-segment) dispatches.
* **legacy** — pytree trainers (e.g. test stubs) take the seed's
  host-pytree path.

The output is a history of (sim_time_s, epoch, accuracy, ...) rows, from
which convergence time (time to reach a target accuracy) is read — the
paper's Table II / Fig. 6 quantities.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg
from repro.core.aggregation import SatelliteMeta
from repro.core.constellation import (WalkerDelta, make_ps_nodes,
                                      paper_constellation)
from repro.core.grouping import (GroupingState, segment_partial_inputs,
                                 segment_weight_matrix)
from repro.core.links import LinkModel, model_bits
from repro.core.modelbank import FlatSpec, gather_rows, pad_bucket_ids
from repro.core.propagation import PropagationModel
from repro.core.topology import RingOfStars
from repro.core.visibility import VisibilityTimeline
from repro.fl.strategies import StrategySpec
from repro.obs.span import span, tracing


@dataclasses.dataclass
class SimConfig:
    duration_s: float = 3 * 86400.0
    dt_s: float = 10.0
    train_time_s: float = 600.0        # on-board local-training wall time
    agg_timeout_s: float = 1500.0      # async collection window per epoch
    min_models: int = 2                # never aggregate on fewer arrivals
    eval_fn: Optional[object] = None   # params -> accuracy
    seed: int = 0
    sync_stall_s: float = 86400.0      # cap a sync round at this (stragglers)
    link: Optional[LinkModel] = None   # None -> paper Table I RF (16 Mb/s)
    use_model_bank: bool = True        # stacked path when trainer supports it
    use_fused_step: bool = True        # one donated program/epoch (DESIGN §6)
    mesh: Optional[object] = None      # jax Mesh with a "data" axis, or None
    event_driven: bool = False         # run() delegates to sched.runtime
    # pluggable fault/heterogeneity layer (sched/faults.FaultModel,
    # DESIGN.md §10): per-sat compute-rate multipliers, eclipse
    # availability windows, lossy sat->PS transfers with bounded
    # retry/backoff.  None attaches NO fault state at all — bit-identical
    # to the fault-free simulator (the parity contract)
    fault_model: Optional[object] = None
    # observability (obs/, DESIGN.md §12): a `obs.Tracer` records the
    # event runtime's round lifecycle in simulated seconds.  Strictly
    # read-only — None (the default) attaches nothing, bit-identical
    tracer: Optional[object] = None
    # scenario-batched sweeps (sweep/batch.DispatchBatcher, DESIGN.md
    # §13): when set, `_init_run` wraps the fused program in the
    # batcher's proxy so this simulation's epoch dispatches multiplex
    # into shared device programs with the sweep's other scenarios.
    # None (the default) attaches nothing — the sequential path is
    # untouched (the batched-vs-sequential parity contract)
    dispatcher: Optional[object] = None
    # contact-plan geometry backend (DESIGN.md §14): "dense" precomputes
    # the (T, S, P) visibility grid; "sparse" compiles per-(sat, PS)
    # window segments coarse-to-fine and answers every query by bisect —
    # O(windows) memory, required at mega-constellation scale.  Sparse is
    # pinned bit-identical to dense (windows, queries, runtime histories)
    # but cannot host fault grid-masks (eclipse/outage masks mutate the
    # dense grid in place), so those combinations raise at construction
    visibility: str = "dense"


# FLSimulation.segment_seconds keys (host wall seconds; DESIGN.md §12):
# contact-plan timing, stacked/legacy training, the fused step (its input
# gather, the inputs' host-to-device copy and the jit dispatch), the
# weight math, grouping (its blocking distance read), the straggler
# carry, and evaluation (its blocking accuracy reads)
SEGMENTS = ("timing", "train", "step", "agg", "group", "carry", "eval",
            "input_gather", "input_put", "dispatch", "eval_read",
            "dist_read")


@dataclasses.dataclass
class EpochRecord:
    epoch: int
    time_s: float
    accuracy: float
    num_models: int
    gamma: float
    stale_groups: int


def split_min_models(arrivals, t_agg: float, min_models: int):
    """(t_agg, used, late) partition of SORTED arrivals at ``t_agg`` with
    the ``min_models`` backstop: when fewer than ``min_models`` arrivals
    land inside the window, the first ``min_models`` are aggregated anyway
    and ``t_agg`` moves to the last of them.

    ``used`` is always a *prefix* of the sorted arrivals and ``late`` the
    exact remainder, so ``used + late == arrivals`` holds on every branch
    — in particular, arrivals *tied* at the backstop's ``t_agg`` beyond
    the ``min_models`` slice are carried as late, never dropped (the
    conservation property tests/test_property.py pins).  ONE shared
    implementation for `FLSimulation._trigger` and the per-group
    `sched/policies.AsyncFLEOPolicy.split` — neither may fork it.
    """
    used = [a for a in arrivals if a[0] <= t_agg]
    if len(used) < min_models:
        used = arrivals[:min_models]
        t_agg = used[-1][0] if used else t_agg
    return t_agg, used, arrivals[len(used):]


class FLSimulation:
    def __init__(self, spec: StrategySpec, trainer, evaluator,
                 sim: SimConfig, constellation: Optional[WalkerDelta] = None):
        self.spec = spec
        self.trainer = trainer
        self.evaluator = evaluator
        self.sim = sim
        self.constellation = constellation or paper_constellation()
        self.nodes = make_ps_nodes(spec.ps_scenario)
        visibility = getattr(sim, "visibility", "dense")
        if visibility == "sparse":
            from repro.core.visibility import SparseVisibilityTimeline
            self.timeline = SparseVisibilityTimeline(
                self.constellation, self.nodes, sim.duration_s, sim.dt_s)
        elif visibility == "dense":
            self.timeline = VisibilityTimeline(
                self.constellation, self.nodes, sim.duration_s, sim.dt_s)
        else:
            raise ValueError(f"visibility must be dense|sparse: {visibility}")
        # fault/heterogeneity layer (DESIGN.md §10): eclipse windows mask
        # the visibility grid BEFORE anything derives state from it, so
        # contact windows, downlink stars, relay seeds and uplinks all
        # route around dark satellites with no special cases; the per-sat
        # training-time scale is applied in _train_times (None = scalar
        # math, bit-identical to the fault-free path)
        self.fault = getattr(sim, "fault_model", None)
        self._train_scale = None
        self._outages = None
        if self.fault is not None:
            S = self.constellation.num_sats
            self._train_scale = self.fault.train_time_scale(S)
            mask = self.fault.availability_mask(self.timeline.times, S)
            if mask is not None:
                if visibility == "sparse":
                    raise ValueError(
                        "sparse visibility cannot host eclipse/outage "
                        "grid-masks — use visibility='dense' with this "
                        "fault model")
                self.timeline.grid &= mask[:, :, None]
            # PS outage windows (DESIGN.md §11) mask the PS axis the same
            # way — a dark parameter server has no satellite contacts —
            # and the compiled OutageSchedule drives the event runtime's
            # ring-failover recovery.  No outage config -> no schedule,
            # no grid mutation at all (the off-switch contract)
            omask = self.fault.outage_mask(self.timeline.times,
                                           len(self.nodes), sim.duration_s)
            if omask is not None:
                from repro.sched.faults import OutageSchedule
                if visibility == "sparse":
                    raise ValueError(
                        "sparse visibility cannot host eclipse/outage "
                        "grid-masks — use visibility='dense' with this "
                        "fault model")
                self.timeline.grid &= omask[:, None, :]
                self._outages = OutageSchedule(
                    self.fault.outage_intervals(len(self.nodes),
                                                sim.duration_s),
                    len(self.nodes))
        self.topo = RingOfStars(self.constellation, self.nodes, self.timeline)
        self.prop = PropagationModel(self.topo, sim.link or LinkModel())
        # the compiled contact plan owns the downlink/uplink timing rules
        # (including the use_isl switch) and is shared with the
        # event-driven runtime; lazy import keeps core <-> sched acyclic
        from repro.sched.contacts import ContactPlan, ContentionModel
        self.plan = ContactPlan(self.constellation, self.nodes,
                                self.timeline, self.topo, self.prop,
                                use_isl=spec.use_isl)
        if getattr(spec, "ps_channels", None) is not None:
            # finite per-PS link capacity (DESIGN.md §9): every sat<->PS
            # model transfer serializes over spec.ps_channels parallel
            # channels; None keeps infinite parallelism with NO contention
            # state at all (the parity default)
            self.plan.contention = ContentionModel(len(self.nodes),
                                                   int(spec.ps_channels))
        self.grouping = GroupingState(num_groups=spec.num_groups)
        self.orbit_ids = self.constellation.orbit_ids()
        # persistent per-satellite bookkeeping
        self.last_epoch_included: Dict[int, int] = {}
        # legacy path: (arrival_t, sat, host pytree, trained_from_epoch)
        self.pending: List[tuple] = []
        # stacked + fused paths: stragglers live in a small DEVICE matrix
        # (O(late) rows, not O(S)) so nothing blocks — they re-enter
        # aggregation as one fused term
        self._pend_dev = None                            # (L, N) device
        self._pend_meta: List[tuple] = []      # (arrival_t, sat, epoch)
        self._spec = None              # FlatSpec of the stacked/fused path
        self._w_flat = None            # its global model, flat on device
        self._fused_prog = None        # EpochStepProgram (fused path)
        # fused path: distances of newly seen orbits are fetched lazily —
        # (new_orbits, device dists, block map, block size), resolved at
        # the next grouping read so the next epoch's host timing overlaps
        # the device stream instead of draining it
        self._dist_pending = None
        # wall-time attribution per host-side section (bench breakdown);
        # the last five nest inside "step", "eval" and "group"
        self.segment_seconds: Dict[str, float] = {
            k: 0.0 for k in SEGMENTS}

    @contextlib.contextmanager
    def _seg(self, key: str, **args):
        """Time a host section into ``segment_seconds[key]``, inside the
        profiler span ``asyncfleo.<key>`` that carries ``args``."""
        t0 = time.perf_counter()
        try:
            with span(key, **args):
                yield
        finally:
            self.segment_seconds[key] += time.perf_counter() - t0

    # ------------------------------------------------------------------

    def _downlink(self, t0: float, bits: float, source: int) -> np.ndarray:
        # timing rules live on the compiled contact plan (sched/contacts.py)
        return self.plan.downlink_times(t0, bits, source)

    def _uplink_many(self, sats, t_done, bits: float, sink: int):
        return self.plan.uplink_times(sats, t_done, bits, sink)

    def _train_times(self, participants):
        """Per-participant local-training durations.  Homogeneous fleets
        get the scalar ``train_time_s`` (bit-identical to the fault-free
        arithmetic); under a FaultModel compute-rate spread each
        satellite's duration is stretched by its multiplier, which is how
        heterogeneity reaches every TRAIN_DONE instant of both drivers."""
        if self._train_scale is None:
            return self.sim.train_time_s
        return (self.sim.train_time_s
                * self._train_scale[np.asarray(participants, np.int64)])

    def _combine(self, segments, weights, base_flat, base_weight: float):
        """Map metas-indexed ``weights`` onto per-segment weight vectors and
        run the fused stacked combination (host bookkeeping + one
        contraction per segment)."""
        terms = []
        for stack, rows in segments:
            if stack is None or stack.shape[0] == 0:
                continue
            terms.append((stack,
                          agg.scatter_weights(rows, weights, stack.shape[0])))
        out = agg.combine_stacked(terms, base_flat, base_weight,
                                  use_kernel=self.spec.use_agg_kernel)
        return base_flat if out is None else out

    # ---- shared per-epoch host metadata ------------------------------

    def _trigger(self, arrivals, t: float):
        """Aggregation trigger: (t_agg, used, late) from sorted arrivals.
        ``used`` is a prefix of ``arrivals`` and ``late`` the exact
        remainder (``used + late == arrivals`` — no drops, even on tied
        arrival times)."""
        sim, spec = self.sim, self.spec
        if spec.sync:
            # barrier: last expected arrival, capped by the straggler
            # stall AND the simulation horizon — a barrier round must not
            # commit an epoch past the end of the simulation
            t_agg = min(arrivals[-1][0] if arrivals else t,
                        t + sim.sync_stall_s, sim.duration_s)
            used = [a for a in arrivals if a[0] <= t_agg]
            return t_agg, used, arrivals[len(used):]
        t_first = arrivals[0][0] if arrivals else t
        t_agg = min(t_first + sim.agg_timeout_s, sim.duration_s)
        return split_min_models(arrivals, t_agg, sim.min_models)

    def _mode_weights(self, metas: List[SatelliteMeta], beta: int,
                      groups: Optional[Dict[int, List[int]]]):
        """Per-model weight vector + base weight for the epoch update
        (:func:`repro.core.aggregation.epoch_weight_vector`)."""
        return agg.epoch_weight_vector(
            self.spec.agg_mode, metas, beta, groups,
            strict_paper_eq14=self.spec.strict_paper_eq14,
            staleness_fn=getattr(self.spec, "staleness_fn", "eq13"))

    @staticmethod
    def _blocked_layout(new_orbits, orbit_indices, bank_rows, n_rows: int,
                        kpad: int):
        """Detect whether every new orbit's bank rows sit in one distinct
        contiguous block of ``n_rows // kpad`` rows (the common
        full-participation layout).  Returns (block size m, orbit-index ->
        block map); (0, {}) when the layout is irregular and the program
        must fall back to the dense one-hot GEMM.  The blocked einsum is
        O(C*N) instead of O(K*C*N) — see DESIGN.md §6."""
        if not kpad or not n_rows or n_rows % kpad:
            return 0, {}
        mb = n_rows // kpad
        block_of: Dict[int, int] = {}
        used = set()
        homeless = []
        for k, o in enumerate(new_orbits):
            blocks = {bank_rows[j] // mb for j in orbit_indices[o]
                      if bank_rows[j] >= 0}
            if len(blocks) > 1:
                return 0, {}
            if blocks:
                b = blocks.pop()
                if b in used:
                    return 0, {}
                block_of[k] = b
                used.add(b)
            else:
                homeless.append(k)      # carry-only orbit: any free block
        free = (b for b in range(kpad) if b not in used)
        for k in homeless:
            b = next(free, None)
            if b is None:
                return 0, {}
            block_of[k] = b
        return mb, block_of

    def _resolve_pending_dists(self) -> None:
        """Fetch + record the previous epoch's new-orbit distances.  MUST
        run before any grouping-state read (``group_of`` / ``groups``)."""
        pend = self._dist_pending
        if pend is None:
            return
        self._dist_pending = None
        new_orbits, dists, block_of, blocked_m = pend
        with self._seg("group"):
            with self._seg("dist_read"):
                ds_full = np.asarray(dists)      # tiny (kpad,) transfer
            if blocked_m:
                ds = ds_full[[block_of[k] for k in range(len(new_orbits))]]
            else:
                ds = ds_full[:len(new_orbits)]
            self.grouping.assign_distances(new_orbits, ds)

    def _carried_split(self, t_agg: float):
        """Indices of pending stragglers that arrived (<= t_agg) vs kept."""
        c_idx = [i for i, (ta, _s, _ep) in enumerate(self._pend_meta)
                 if ta <= t_agg]
        k_idx = [i for i in range(len(self._pend_meta)) if i not in c_idx]
        return c_idx, k_idx

    # ---- fused path (one donated program per epoch, DESIGN.md §6) ----

    def _arrival_times(self, participants, recv, bits, sink):
        """Participant timing for one round: padded bank ids, per-row
        training-done times, raw per-row sink arrival times, and the
        sorted finite (t_arr, sat, row) arrival triples.  ONE shared
        implementation for the epoch loop and the event runtime — their
        parity contract (tests/test_sched.py) depends on identical
        timing math, so neither may fork this."""
        ids_np, _n = pad_bucket_ids(participants)
        t_done = recv[participants] + self._train_times(participants)
        t_arr, _haps = self._uplink_many(participants, t_done, bits, sink)
        arrivals = [(float(t_arr[k]), s, k)
                    for k, s in enumerate(participants)
                    if np.isfinite(t_arr[k])]
        arrivals.sort(key=lambda a: a[0])
        return ids_np, t_done, t_arr, arrivals

    def _fused_epoch(self, prog, beta, participants, recv, t, bits, sink):
        """One epoch-loop iteration on the fused path: propagation timing
        and the `_trigger` split happen here, everything after the trigger
        is the shared `_fused_commit` (which the event-driven runtime calls
        directly with policy-chosen trigger instants)."""
        # all host work happens BEFORE the dispatch: propagation timing,
        # trigger, straggler bookkeeping, weight-vector metadata math
        arrivals = []
        ids_np = np.zeros(0, np.int32)
        if participants:
            with self._seg("timing"):
                ids_np, _td, _ta, arrivals = self._arrival_times(
                    participants, recv, bits, sink)
        if not arrivals and not self._pend_meta:
            return None
        t_agg, used, late = self._trigger(arrivals, t)
        return self._fused_commit(prog, beta, ids_np, participants, t_agg,
                                  used, late)

    def _fused_commit(self, prog, beta, ids_np, participants, t_agg, used,
                      late, train_epoch: Optional[int] = None):
        """Post-trigger tail of a fused epoch: metas/carry bookkeeping,
        grouping metadata, weight vectors, the ONE donated dispatch, and
        the straggler carry-over.  ``used``/``late`` are (t_arr, sat, bank
        row) triples split at ``t_agg`` — by `_trigger` on the epoch loop,
        by a trigger policy in the event runtime (`sched/runtime.py`).

        ``train_epoch`` names the round the commit belongs to: the global
        epoch counter when the round's downlink left the source (defaults
        to ``beta``, the epoch-loop case where rounds never overlap).
        With the pipelined runtime (DESIGN.md §8) a round may commit
        after later-opened rounds advanced ``beta``; its models — used
        AND late-carried — are stamped with ``train_epoch``, so eq. 13's
        staleness discount and Alg. 2's fresh/stale selection see the
        model version the round actually started from."""
        from repro.core.epoch_step import (carry_capacity, next_pow2,
                                           put_inputs)

        sim, spec = self.sim, self.spec
        if train_epoch is None:
            train_epoch = beta
        # the RNG seed stays keyed on the commit-time counter: commits are
        # serialized so beta is unique per training dispatch, while two
        # overlapping pipelined rounds can share a train_epoch (and must
        # NOT draw identical minibatch streams)
        seed = sim.seed * 1000 + beta
        self._spec = prog.spec
        N = prog.spec.num_params
        c_idx, k_idx = self._carried_split(t_agg)

        metas = [SatelliteMeta(s, self.trainer.data_size(s),
                               loc=(0.0, 0.0), ts=ta, epoch=train_epoch)
                 for (ta, s, _k) in used]
        metas += [SatelliteMeta(s, self.trainer.data_size(s),
                                loc=(0.0, 0.0), ts=ta, epoch=ep)
                  for (ta, s, ep) in (self._pend_meta[i] for i in c_idx)]
        bank_rows = [k for (_, _, k) in used] + [-1] * len(c_idx)
        carry_rows = [-1] * len(used) + list(range(len(c_idx)))
        keep = agg.dedup_indices(metas)
        if len(keep) < len(metas):
            metas = [metas[i] for i in keep]
            bank_rows = [bank_rows[i] for i in keep]
            carry_rows = [carry_rows[i] for i in keep]

        # carried stragglers: a small padded device matrix (pad rows repeat
        # row 0 and carry zero weight); rebuilt from _pend_dev every epoch
        # so the program may freely consume (donate) its buffer
        cap = carry_capacity(len(c_idx))
        if c_idx:
            gids = np.asarray(c_idx + [c_idx[0]] * (cap - len(c_idx)),
                              np.int32)
            carry = gather_rows(self._pend_dev, gids)
        else:
            carry = jnp.zeros((cap, N), jnp.float32)

        # groups + new-orbit partial-model inputs (host metadata): the
        # program computes distances as an O(C*N) segment-sum, so the host
        # ships per-row weights + segment ids, not a (K, C) matrix
        groups = None
        new_orbits: List[int] = []
        orbit_indices: Dict[int, List[int]] = {}
        kpad, blocked_m = 0, 0
        block_of: Dict[int, int] = {}
        dw_row = np.zeros(len(ids_np), np.float32)
        dw_seg = np.zeros(len(ids_np), np.int32)
        dw_carry = np.zeros((0, cap), np.float32)
        fallback = False
        if spec.agg_mode == "asyncfleo" and not spec.grouping:
            groups = {0: list(range(len(metas)))}
        elif spec.agg_mode == "asyncfleo":
            self._resolve_pending_dists()        # state read follows
            for i, meta in enumerate(metas):
                orbit_indices.setdefault(
                    int(self.orbit_ids[meta.sat_id]), []).append(i)
            known = {o: self.grouping.group_of(o) for o in orbit_indices}
            new_orbits = [o for o, g in known.items() if g is None]
            if new_orbits:
                sizes = [m.size for m in metas]
                totals = {o: float(sum(sizes[j] for j in orbit_indices[o]))
                          for o in new_orbits}
                kpad = next_pow2(len(new_orbits))
                dw_row, dw_seg = segment_partial_inputs(
                    new_orbits, orbit_indices, bank_rows, sizes, totals,
                    len(ids_np), kpad)
                carry_w = segment_weight_matrix(
                    new_orbits, orbit_indices, carry_rows, sizes, totals,
                    cap)
                blocked_m, block_of = self._blocked_layout(
                    new_orbits, orbit_indices, bank_rows, len(ids_np),
                    kpad)
                dw_carry = np.zeros((kpad, cap), np.float32)
                if blocked_m:
                    for k in range(len(new_orbits)):
                        dw_carry[block_of[k]] = carry_w[k]
                else:
                    dw_carry[:len(new_orbits)] = carry_w
            any_stale = any(not m.is_fresh(beta) for m in metas)
            # group membership only moves weights through which *stale*
            # models survive selection; with everything fresh the weights
            # are group-independent, so provisional singleton groups keep
            # the epoch at one dispatch.  A new orbit arriving while stale
            # models are pending is the one case where the weight vector
            # depends on this epoch's distances -> two dispatches.
            fallback = bool(new_orbits) and any_stale
            groups = {}
            provisional = -1
            for o, idxs in orbit_indices.items():
                gi = known[o]
                if gi is None:
                    gi = provisional
                    provisional -= 1
                groups.setdefault(gi, []).extend(idxs)

        if not participants:
            # nothing trained this epoch: no program — one eager fused
            # combine over the carried-stragglers matrix only
            return self._fused_no_train(beta, metas, carry, carry_rows,
                                        c_idx, k_idx, new_orbits,
                                        orbit_indices, groups, t_agg)

        with self._seg("agg"):
            if fallback:
                wv_bank = np.zeros(len(ids_np), np.float32)
                wv_carry = np.zeros(cap, np.float32)
                base_w, info = 1.0, None
            else:
                ws, base_w, info = self._mode_weights(metas, beta, groups)
                wv_bank = agg.scatter_weights(bank_rows, ws, len(ids_np))
                wv_carry = agg.scatter_weights(carry_rows, ws, cap)

        with self._seg("step"):
            with self._seg("input_gather"):
                inputs = self.trainer.epoch_inputs(ids_np)
            # the inputs' host-to-device copy, made explicit and in the
            # program's own layout: the jit call would make the same copy
            # of the host arrays itself
            nbytes = (sum(getattr(x, "nbytes", 0)
                          for x in jax.tree.leaves(inputs))
                      if tracing() else None)
            with self._seg("input_put", bytes=nbytes):
                inputs = put_inputs(inputs, self.sim.mesh, len(ids_np))
            with self._seg("dispatch", participants=len(participants),
                           rows=len(ids_np), carried=len(c_idx),
                           carry_rows=cap, params=N, fallback=fallback):
                new_w, stack, dists, losses = prog.step(
                    self._w_flat, carry, inputs, ids_np, seed,
                    wv_bank, wv_carry, base_w, dw_row, dw_seg, kpad,
                    blocked_m, dw_carry, self.grouping._ref_device(),
                    fallback=fallback)

        if new_orbits:
            # don't block here: the fetch resolves at the next grouping
            # read, letting the next epoch's host work overlap the stream
            self._dist_pending = (new_orbits, dists, block_of, blocked_m)

        if fallback:
            self._resolve_pending_dists()        # weights need the groups
            with self._seg("agg"):
                groups = {}
                for o, idxs in orbit_indices.items():
                    gi = self.grouping.group_of(o)
                    groups.setdefault(gi, []).extend(idxs)
                ws, base_w, info = self._mode_weights(metas, beta, groups)
                out = agg.combine_stacked(
                    [(stack, agg.scatter_weights(bank_rows, ws,
                                                 len(ids_np))),
                     (carry if c_idx else None,
                      agg.scatter_weights(carry_rows, ws, cap))],
                    new_w, base_w, use_kernel=spec.use_agg_kernel)
                new_w = out if out is not None else new_w

        # retire carried stragglers, enqueue this epoch's late rows —
        # all lazy device gathers, nothing blocks
        with self._seg("carry"):
            kept_meta = [self._pend_meta[i] for i in k_idx]
            kept_dev = (gather_rows(self._pend_dev,
                                    np.asarray(k_idx, np.int32))
                        if k_idx else None)
            if late:
                late_ids = np.asarray([k for (_, _, k) in late], np.int32)
                late_dev = gather_rows(stack, late_ids)
                kept_dev = (late_dev if kept_dev is None
                            else jnp.concatenate([kept_dev, late_dev]))
                kept_meta += [(ta, s, train_epoch) for (ta, s, _k) in late]
            self._pend_dev, self._pend_meta = kept_dev, kept_meta

        self._w_flat = new_w
        return t_agg, metas, info, losses

    def _fused_no_train(self, beta, metas, carry, carry_rows, c_idx, k_idx,
                        new_orbits, orbit_indices, groups, t_agg):
        """Fused-path epoch with no participants: carried stragglers only."""
        spec = self.spec
        if new_orbits:
            with self._seg("group"):
                sizes = [m.size for m in metas]
                totals = {o: float(sum(sizes[j] for j in orbit_indices[o]))
                          for o in new_orbits}
                dw = segment_weight_matrix(new_orbits, orbit_indices,
                                           carry_rows, sizes, totals,
                                           carry.shape[0])
                pm = jnp.asarray(dw) @ carry
                ds = jnp.linalg.norm(
                    pm - self.grouping._ref_device()[None, :], axis=1)
                with self._seg("dist_read"):
                    ds = np.asarray(ds)
                self.grouping.assign_distances(new_orbits, ds)
            if spec.agg_mode == "asyncfleo" and spec.grouping:
                groups = {}
                for o, idxs in orbit_indices.items():
                    groups.setdefault(self.grouping.group_of(o),
                                      []).extend(idxs)
        with self._seg("agg"):
            ws, base_w, info = self._mode_weights(metas, beta, groups)
            out = agg.combine_stacked(
                [(carry, agg.scatter_weights(carry_rows, ws,
                                             carry.shape[0]))],
                self._w_flat, base_w, use_kernel=spec.use_agg_kernel)
            if out is not None:
                self._w_flat = out
        kept_meta = [self._pend_meta[i] for i in k_idx]
        kept_dev = (gather_rows(self._pend_dev, np.asarray(k_idx, np.int32))
                    if k_idx else None)
        self._pend_dev, self._pend_meta = kept_dev, kept_meta
        return t_agg, metas, info, None

    # ---- stacked path (device-resident bank, chained dispatches) -----

    def _stacked_epoch(self, beta, participants, recv, t, bits, sink,
                       w_tree):
        sim, spec = self.sim, self.spec
        bank = None
        arrivals = []
        if participants:
            with self._seg("train"):
                bank, _losses = self.trainer.train_many_stacked(
                    participants, w_tree, seed=sim.seed * 1000 + beta)
                self._spec = bank.spec
            with self._seg("timing"):
                t_done = recv[participants] + self._train_times(participants)
                t_arr_vec, _haps = self._uplink_many(participants, t_done,
                                                     bits, sink)
            arrivals = [(float(t_arr_vec[k]), s, k)
                        for k, s in enumerate(participants)
                        if np.isfinite(t_arr_vec[k])]
            arrivals.sort(key=lambda a: a[0])
        if not arrivals and not self._pend_meta:
            return None
        t_agg, used, late = self._trigger(arrivals, t)
        c_idx, k_idx = self._carried_split(t_agg)

        metas = [SatelliteMeta(s, self.trainer.data_size(s),
                               loc=(0.0, 0.0), ts=ta, epoch=beta)
                 for (ta, s, _k) in used]
        metas += [SatelliteMeta(s, self.trainer.data_size(s),
                                loc=(0.0, 0.0), ts=ta, epoch=ep)
                  for (ta, s, ep) in (self._pend_meta[i] for i in c_idx)]
        # row bookkeeping instead of row gathers: metas index j maps to a
        # row of the intact epoch bank or the carried matrix
        bank_rows = [k for (_, _, k) in used] + [-1] * len(c_idx)
        carry_rows = [-1] * len(used) + list(range(len(c_idx)))
        with self._seg("carry"):
            carry_seg = (gather_rows(self._pend_dev,
                                     np.asarray(c_idx, np.int32))
                         if c_idx else None)
            # retire carried stragglers, enqueue this epoch's late rows —
            # all lazy device gathers, O(late) rows; the old path staged
            # them in a host matrix (a (late, N) device->host->device
            # round-trip per epoch that an accelerator host can't hide)
            keep_dev = (gather_rows(self._pend_dev,
                                    np.asarray(k_idx, np.int32))
                        if k_idx else None)
            keep_meta = [self._pend_meta[i] for i in k_idx]
            if late:
                late_ids = np.asarray([k for (_, _, k) in late], np.int32)
                late_dev = gather_rows(bank.stack, late_ids)
                keep_dev = (late_dev if keep_dev is None else
                            jnp.concatenate([keep_dev, late_dev]))
                keep_meta += [(ta, s, beta) for (ta, s, _k) in late]
            self._pend_dev, self._pend_meta = keep_dev, keep_meta

        keep = agg.dedup_indices(metas)
        if len(keep) < len(metas):
            metas = [metas[i] for i in keep]
            bank_rows = [bank_rows[i] for i in keep]
            carry_rows = [carry_rows[i] for i in keep]
        carry_dev = (carry_seg
                     if carry_seg is not None
                     and any(r >= 0 for r in carry_rows) else None)
        segments = [(bank.stack if bank is not None else None, bank_rows),
                    (carry_dev, carry_rows)]

        # guard: a trainer that never ran leaves _spec unset — fall back
        # to the pytree base's own structure instead of crashing
        if self._spec is None:
            self._spec = FlatSpec.of(w_tree)
        if self._w_flat is None:
            self._w_flat = self._spec.flatten(w_tree)

        groups: Optional[Dict[int, List[int]]] = None
        if spec.agg_mode == "asyncfleo":
            if not spec.grouping:                    # ablation: one group
                groups = {0: list(range(len(metas)))}
            else:
                with self._seg("group"):
                    # batched: all new-orbit partial models + distances in
                    # fused per-segment contractions over the bank
                    orbit_indices: Dict[int, List[int]] = {}
                    for i, meta in enumerate(metas):
                        orbit_indices.setdefault(
                            int(self.orbit_ids[meta.sat_id]), []).append(i)
                    orbit_group = self.grouping.observe_orbits_multi(
                        orbit_indices, segments, [m.size for m in metas])
                    groups = {}
                    for i, meta in enumerate(metas):
                        gi = orbit_group[int(self.orbit_ids[meta.sat_id])]
                        groups.setdefault(gi, []).append(i)

        with self._seg("agg"):
            # per-model weights are host metadata math; the tensor update
            # is a couple of fused per-segment contractions (epoch bank +
            # carried stragglers), no row copies
            ws, base_w, info = self._mode_weights(metas, beta, groups)
            w_new = self._combine(segments, ws, self._w_flat, base_w)
            self._w_flat = (w_new if getattr(w_new, "ndim", None) == 1
                            else self._spec.flatten(w_new))
        return t_agg, metas, info, None

    # ---- legacy path (host pytrees, the seed's semantics) ------------

    def _legacy_epoch(self, beta, participants, recv, t, bits, sink,
                      w_tree):
        sim, spec = self.sim, self.spec
        arrivals = []
        if participants:
            with self._seg("train"):
                trained, _losses = self.trainer.train_many(
                    participants, w_tree, seed=sim.seed * 1000 + beta)
            with self._seg("timing"):
                t_done = recv[participants] + self._train_times(participants)
                t_arr_vec, _haps = self._uplink_many(participants, t_done,
                                                     bits, sink)
            arrivals = [(float(t_arr_vec[k]), s, p)
                        for k, (s, p)
                        in enumerate(zip(participants, trained))
                        if np.isfinite(t_arr_vec[k])]
            arrivals.sort(key=lambda a: a[0])
        if not arrivals and not self.pending:
            return None
        t_agg, used, late = self._trigger(arrivals, t)

        metas = [SatelliteMeta(s, self.trainer.data_size(s),
                               loc=(0.0, 0.0), ts=ta, epoch=beta)
                 for (ta, s, _p) in used]
        carried = [(ta, s, p, ep) for (ta, s, p, ep) in self.pending
                   if ta <= t_agg]
        self.pending = [x for x in self.pending if x[0] > t_agg]
        self.pending.extend((ta, s, p, beta) for (ta, s, p) in late)
        metas += [SatelliteMeta(s, self.trainer.data_size(s),
                                loc=(0.0, 0.0), ts=ta, epoch=ep)
                  for (ta, s, _p, ep) in carried]
        models = ([p for (_, _, p) in used]
                  + [p for (_, _, p, _) in carried])
        models, metas = agg.dedup(models, metas)
        base = w_tree

        info = {"gamma": 1.0, "stale_groups": 0}
        with self._seg("agg"):
            if spec.agg_mode == "fedavg":
                w_new = agg.fedavg(models, [m.size for m in metas],
                                   use_kernel=spec.use_agg_kernel)
            elif spec.agg_mode == "per_arrival":
                w_new = base
                for m_i, meta in zip(models, metas):
                    alpha = 0.5 / (1.0 + max(beta - meta.epoch, 0))
                    w_new = agg.weighted_sum([m_i], [alpha], base=w_new,
                                             base_weight=1.0 - alpha)
            elif spec.agg_mode == "interval":
                total = sum(m.size for m in metas)
                raw = np.array([m.size / (1.0 + max(beta - m.epoch, 0))
                                for m in metas])
                gam = float(np.clip(raw.sum() / max(total, 1e-9), 0.2, 1.0))
                w_new = agg.weighted_sum(models, gam * raw / raw.sum(),
                                         base=base, base_weight=1.0 - gam)
                info["gamma"] = gam
            else:                                    # asyncfleo (Alg. 2)
                groups: Dict[int, List[int]] = {}
                if not spec.grouping:                # ablation: one group
                    groups[0] = list(range(len(metas)))
                else:
                    for i, meta in enumerate(metas):
                        orbit = int(self.orbit_ids[meta.sat_id])
                        gi = self.grouping.group_of(orbit)
                        if gi is None:     # first sighting: distance to w0
                            same_orbit = [j for j, mm in enumerate(metas)
                                          if int(self.orbit_ids[mm.sat_id])
                                          == orbit]
                            gi = self.grouping.observe_orbit(
                                orbit, [models[j] for j in same_orbit],
                                [metas[j].size for j in same_orbit])
                        groups.setdefault(gi, [])
                        if i not in groups[gi]:
                            groups[gi].append(i)
                w_new, info = agg.asyncfleo_aggregate(
                    base, groups, models, metas, beta,
                    strict_paper_eq14=spec.strict_paper_eq14,
                    use_kernel=spec.use_agg_kernel)
        return t_agg, metas, info, w_new

    # ------------------------------------------------------------------

    def _init_run(self, w0):
        """Shared run-state reset for the epoch loop and the event-driven
        runtime.  Returns (model bits, fused program or None, stacked?)."""
        bits = model_bits(w0)
        self.grouping.set_reference(w0)
        if self.plan.contention is not None:
            self.plan.contention.reset()   # channel pools are per-run state
        stacked = self.sim.use_model_bank and hasattr(self.trainer,
                                                      "train_many_stacked")
        fused = None
        if stacked and self.sim.use_fused_step:
            from repro.core.epoch_step import make_epoch_program
            fused = make_epoch_program(self.trainer, w0, mesh=self.sim.mesh,
                                       use_kernel=self.spec.use_agg_kernel)
            if fused is not None:
                dispatcher = getattr(self.sim, "dispatcher", None)
                if dispatcher is not None:
                    # scenario-batched sweep (DESIGN.md §13): route this
                    # run's dispatches through the shared batcher; the
                    # proxy keeps step()'s exact surface and counters
                    fused = dispatcher.wrap(
                        fused, key=getattr(self.trainer,
                                           "scenario_batch_key", None))
        self._fused_prog = fused
        self._w_flat = None               # flat device view (stacked/fused)
        self._dist_pending = None
        if stacked:
            self._spec = self._spec or FlatSpec.of(w0)
            self._w_flat = self._spec.flatten(w0)
        return bits, fused, stacked

    def global_model(self):
        """The global model the last ``run`` ended with: a pytree on the
        device (the model-bank paths keep it as one flat vector)."""
        if self._w_flat is None:
            raise ValueError("only a model-bank run keeps the global model")
        return self._spec.unflatten(self._w_flat)

    def _record_epoch(self, history: List[EpochRecord], beta: int,
                      t_agg: float, metas, info, lazy_eval: bool, w_tree):
        """Evaluate + append one epoch's history row (shared by the epoch
        loop and the event runtime so the records stay bit-identical).
        Returns the recorded accuracy (a lazy device scalar when
        ``lazy_eval``)."""
        for meta in metas:
            self.last_epoch_included[meta.sat_id] = beta
        with self._seg("eval"):
            if self.evaluator is None:
                acc = float("nan")
            elif lazy_eval:
                acc = self.evaluator.eval_async(w_tree)  # lazy device
            else:
                with self._seg("eval_read"):
                    acc = float(self.evaluator(w_tree))
        history.append(EpochRecord(beta, t_agg, acc, len(metas),
                                   float(info.get("gamma", 1.0)),
                                   int(info.get("stale_groups", 0))))
        return acc

    def run(self, w0, max_epochs: int = 30,
            target_accuracy: Optional[float] = None) -> List[EpochRecord]:
        sim, spec = self.sim, self.spec
        if sim.event_driven:
            # the event-driven async runtime replaces the epoch loop as
            # the top-level driver (DESIGN.md §7)
            from repro.sched.runtime import EventDrivenRuntime
            return EventDrivenRuntime(self).run(
                w0, max_epochs, target_accuracy=target_accuracy)
        if self.fault is not None and self.fault.has_loss:
            raise ValueError(
                "FaultModel transfer loss (loss_prob > 0 or burst_len_s "
                "> 0) requires the event-driven runtime "
                "(SimConfig.event_driven=True): the epoch loop cannot "
                "express TRANSFER_FAILED retry chains")
        if self.fault is not None and (self.fault.has_outages
                                       or self.fault.has_energy):
            raise ValueError(
                "FaultModel PS outages / energy budgets require the "
                "event-driven runtime (SimConfig.event_driven=True): the "
                "epoch loop cannot express ring failover or deferred "
                "uplinks (DESIGN.md §11)")
        bits, fused, stacked = self._init_run(w0)
        w_tree = w0                       # pytree view (trainer/evaluator)
        t = 0.0
        source = 0
        history: List[EpochRecord] = []
        S = self.constellation.num_sats
        lazy_eval = (target_accuracy is None
                     and hasattr(self.evaluator, "eval_async"))

        for beta in range(max_epochs):
            if t >= sim.duration_s:
                break
            sink = self.topo.sink_of(source)
            with self._seg("timing"):
                recv = self._downlink(t, bits, source)
            participants = [s for s in range(S) if np.isfinite(recv[s])]

            if fused is not None:
                out = self._fused_epoch(fused, beta, participants, recv, t,
                                        bits, sink)
            elif stacked:
                out = self._stacked_epoch(beta, participants, recv, t,
                                          bits, sink, w_tree)
            else:
                out = self._legacy_epoch(beta, participants, recv, t,
                                         bits, sink, w_tree)
            if out is None:
                break
            t_agg, metas, info, extra = out
            if fused is not None:
                # the fused path trains from w_flat directly: the pytree
                # view only feeds the evaluator
                if self.evaluator is not None:
                    w_tree = self._spec.unflatten(self._w_flat)  # lazy
            elif stacked:
                w_tree = self._spec.unflatten(self._w_flat)  # device, lazy
            else:
                w_tree = extra
            if spec.agg_mode == "interval":
                t_agg = max(t_agg, t + spec.interval_s)

            acc = self._record_epoch(history, beta, t_agg, metas, info,
                                     lazy_eval, w_tree)
            t = t_agg
            source, sink = sink, source            # §IV-B3 role swap
            if target_accuracy is not None and acc >= target_accuracy:
                break
        self._resolve_pending_dists()        # leave grouping state complete
        self._read_accuracies(history)
        return history

    def _read_accuracies(self, history: List[EpochRecord]) -> None:
        """Block once, at finalize time, on every accuracy still lazy."""
        with self._seg("eval"), self._seg("eval_read"):
            for rec in history:
                rec.accuracy = float(rec.accuracy)


def convergence_time(history: List[EpochRecord], target: float) -> Optional[float]:
    for rec in history:
        if rec.accuracy >= target:
            return rec.time_s
    return None
