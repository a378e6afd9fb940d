"""Satellite grouping by model-weight divergence (paper §IV-C1, Fig. 5).

The PS cannot see data (FL), so data-distribution similarity is inferred from
model weights: per orbit, a *partial global model* S'_o = data-size-weighted
average of that orbit's received local models; its Euclidean distance to the
*initial* global model w0 (largest divergence happens in epoch 1, giving the
sharpest differentiation) places the orbit on a 1-D axis; orbits with similar
distances form a group.  Later epochs assign new orbits to the group whose
members' mean distance is closest.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.modelbank import HIGHEST, ModelBank


@functools.partial(jax.jit, static_argnames=("k",))
def _blocked_distances(stack, w, ref, k):
    """Distances of k equal contiguous-block partial models to ref — one
    fused O(C*N) batched contraction (weights normalized per block)."""
    c, n = stack.shape
    pm = jnp.einsum("kc,kcn->kn", w.reshape(k, c // k),
                    stack.reshape(k, c // k, n), precision=HIGHEST)
    return jnp.linalg.norm(pm - ref[None, :], axis=1)


@jax.jit
def _dense_distances(weight_matrix, stack, ref):
    """General case: per-orbit weight rows -> partial models -> distances,
    one fused (K,C)x(C,N) contraction."""
    return jnp.linalg.norm(
        jnp.dot(weight_matrix, stack, precision=HIGHEST) - ref[None, :],
        axis=1)


def flatten_model(model) -> np.ndarray:
    if getattr(model, "ndim", None) == 1:         # already a flat vector
        return np.asarray(model, dtype=np.float32)
    return np.concatenate([np.asarray(l, dtype=np.float32).ravel()
                           for l in jax.tree_util.tree_leaves(model)])


def model_distance(model, ref_flat: np.ndarray) -> float:
    """|| flat(model) - flat(w0) ||_2."""
    return float(np.linalg.norm(flatten_model(model) - ref_flat))


def partial_global_model(models, sizes: Sequence[float]):
    """Data-size-weighted average of one orbit's local models (Fig. 5a).
    With a ``ModelBank`` this is one fused (1,C)x(C,N) device contraction
    returning the flat (N,) partial model; pytree lists keep host math."""
    total = float(sum(sizes))
    if isinstance(models, ModelBank):
        ws = jnp.asarray(np.asarray(sizes, np.float32) / total)
        return jnp.dot(ws, models.stack, precision=HIGHEST)
    ws = [s / total for s in sizes]
    return jax.tree.map(
        lambda *leaves: sum(w * np.asarray(l, dtype=np.float32)
                            for w, l in zip(ws, leaves)),
        *models)


def group_by_gaps(distances: Dict[int, float], num_groups: int = 3) -> List[List[int]]:
    """1-D clustering: sort orbit distances, split at the (num_groups-1)
    largest gaps.  Deterministic; matches the paper's 'similar Euclidean
    distances are grouped together'."""
    orbits = sorted(distances, key=lambda o: distances[o])
    if len(orbits) <= num_groups:
        return [[o] for o in orbits]
    vals = np.array([distances[o] for o in orbits])
    gaps = np.diff(vals)
    cuts = np.sort(np.argsort(gaps)[::-1][: num_groups - 1])
    groups, start = [], 0
    for c in cuts:
        groups.append(orbits[start:c + 1])
        start = c + 1
    groups.append(orbits[start:])
    return groups


def segment_partial_inputs(new_orbits: Sequence[int],
                           orbit_indices: Dict[int, List[int]],
                           rows: Sequence[int], sizes: Sequence[float],
                           totals: Dict[int, float], n_rows: int,
                           dump: int):
    """Per-row (weight, segment id) arrays for the fused epoch program's
    O(C*N) partial-model segment-sum: row ``r`` gets orbit k's
    size-normalized weight when model j with ``rows[j] == r`` belongs to
    ``new_orbits[k]``; unowned rows get weight 0 and segment ``dump``.
    Each bank row feeds at most one orbit, which is what makes the
    segment-sum equivalent to the dense (K, n_rows) matrix product."""
    w = np.zeros(n_rows, np.float32)
    seg = np.full(n_rows, dump, np.int32)
    for k, orbit in enumerate(new_orbits):
        for j in orbit_indices[orbit]:
            r = rows[j]
            if r >= 0:
                w[r] = sizes[j] / totals[orbit]
                seg[r] = k
    return w, seg


def segment_weight_matrix(new_orbits: Sequence[int],
                          orbit_indices: Dict[int, List[int]],
                          rows: Sequence[int], sizes: Sequence[float],
                          totals: Dict[int, float],
                          n_rows: int) -> np.ndarray:
    """(K, n_rows) per-orbit partial-model weight rows for ONE segment:
    row k holds the size-normalized weights of orbit k's models that live
    in this segment (``rows[j]`` is model j's row there, -1 elsewhere).
    Host metadata math — shared by ``observe_orbits_multi`` and the fused
    epoch program, which takes the matrices as inputs and returns the
    distances (DESIGN.md §6)."""
    from repro.core.aggregation import scatter_weights
    return np.stack([scatter_weights(
        [rows[j] for j in orbit_indices[orbit]],
        [sizes[j] / totals[orbit] for j in orbit_indices[orbit]],
        n_rows) for orbit in new_orbits]) if new_orbits else \
        np.zeros((0, n_rows), np.float32)


@dataclasses.dataclass
class GroupingState:
    """Incremental grouping maintained by the sink HAP."""
    ref_flat: Optional[np.ndarray] = None          # flat(w0), host copy
    distances: Dict[int, float] = dataclasses.field(default_factory=dict)
    groups: List[List[int]] = dataclasses.field(default_factory=list)
    num_groups: int = 3
    use_dist_kernel: bool = False      # route distances through pairwise_dist
    _ref_dev: Optional[object] = dataclasses.field(default=None, repr=False)

    def set_reference(self, w0) -> None:
        self.ref_flat = flatten_model(w0)
        self._ref_dev = jnp.asarray(self.ref_flat)

    def _ref_device(self):
        """Device copy of ref_flat — derived lazily so a GroupingState
        built with the public ``ref_flat`` field (legacy style) still works
        on the stacked paths."""
        if self._ref_dev is None:
            assert self.ref_flat is not None, "set_reference(w0) first"
            self._ref_dev = jnp.asarray(self.ref_flat)
        return self._ref_dev

    def group_of(self, orbit: int) -> Optional[int]:
        for gi, g in enumerate(self.groups):
            if orbit in g:
                return gi
        return None

    def observe_orbit(self, orbit: int, models, sizes: Sequence[float]) -> int:
        """Ingest an orbit's freshly received models; returns its group id.
        First sighting computes the partial-model distance; known orbits keep
        their stored group (paper: 'directly assigned to the associated
        group').  ``models`` may be a pytree list or a ``ModelBank`` — the
        stacked path fuses the partial model and its distance-to-w0 into
        device calls (only the scalar distance reaches host)."""
        gi = self.group_of(orbit)
        if gi is not None:
            return gi
        assert self.ref_flat is not None, "set_reference(w0) first"
        pm = partial_global_model(models, sizes)
        if isinstance(models, ModelBank):
            if self.use_dist_kernel:
                from repro.kernels.pairwise_dist.ops import dist_to_ref
                d = float(dist_to_ref(pm[None], self._ref_device())[0])
            else:
                d = float(jnp.linalg.norm(pm - self._ref_device()))
        else:
            d = model_distance(pm, self.ref_flat)
        self.distances[orbit] = d
        if len(self.groups) < self.num_groups:
            # still building the grouping (paper: first epoch(s)) — recluster
            # over every orbit distance seen so far so early arrivals don't
            # freeze a degenerate single group.
            self.groups = group_by_gaps(self.distances, self.num_groups)
            return self.group_of(orbit)                     # type: ignore
        # grouping established: assign to nearest group by mean distance
        means = [np.mean([self.distances[o] for o in g if o in self.distances])
                 if any(o in self.distances for o in g) else np.inf
                 for g in self.groups]
        gi = int(np.argmin([abs(d - m) for m in means]))
        self.groups[gi].append(orbit)
        return gi

    def observe_orbits(self, orbit_indices: Dict[int, List[int]],
                       bank: ModelBank,
                       sizes: Sequence[float]) -> Dict[int, int]:
        """Batched ``observe_orbit`` over a whole epoch's arrivals.

        ``orbit_indices``: orbit id -> row indices into ``bank``;
        ``sizes``: per-row data sizes.  All partial global models of *new*
        orbits are computed in ONE fused segment-sum over the stacked
        (C, N) bank and all distances-to-w0 in one norm call — only the
        per-orbit scalar distances reach host.  Returns orbit -> group id.
        """
        out: Dict[int, int] = {}
        new_orbits = []
        for orbit in orbit_indices:
            gi = self.group_of(orbit)
            if gi is not None:
                out[orbit] = gi
            else:
                new_orbits.append(orbit)
        if not new_orbits:
            return out
        assert self.ref_flat is not None, "set_reference(w0) first"
        # per-model weight vectors are host metadata math; the tensor work
        # is one fused device call either way
        counts = [len(orbit_indices[o]) for o in new_orbits]
        idx_all = np.concatenate([orbit_indices[o] for o in new_orbits])
        if (len(set(counts)) == 1 and len(idx_all) == len(bank)
                and np.array_equal(idx_all, np.arange(len(bank)))):
            # common layout (constellation order, equal orbits): O(C*N)
            # blocked reduction instead of the O(K*C*N) dense contraction
            w = np.zeros(len(bank), dtype=np.float32)
            for orbit in new_orbits:
                idxs = orbit_indices[orbit]
                total = float(sum(sizes[j] for j in idxs))
                for j in idxs:
                    w[j] = sizes[j] / total
            ds = np.asarray(_blocked_distances(bank.stack, jnp.asarray(w),
                                               self._ref_device(),
                                               len(new_orbits)))
        else:
            W = np.zeros((len(new_orbits), len(bank)), dtype=np.float32)
            for k, orbit in enumerate(new_orbits):
                idxs = orbit_indices[orbit]
                total = float(sum(sizes[j] for j in idxs))
                for j in idxs:
                    W[k, j] = sizes[j] / total
            ds = np.asarray(_dense_distances(jnp.asarray(W), bank.stack,
                                             self._ref_device()))
        self._assign_new(new_orbits, ds, out)
        return out

    def observe_orbits_multi(self, orbit_indices: Dict[int, List[int]],
                             segments, sizes: Sequence[float]) -> Dict[int, int]:
        """``observe_orbits`` over models split across device matrices.

        ``segments``: list of (stack (C_s, N) or None, rows) where
        ``rows[j]`` is model j's row in that stack (-1 elsewhere) — e.g. the
        epoch's training bank plus a small carried-stragglers matrix.  Each
        segment contributes one fused (K,C_s)x(C_s,N) term to the partial
        models; no rows are gathered or concatenated.
        """
        out: Dict[int, int] = {}
        new_orbits = [o for o in orbit_indices if self.group_of(o) is None]
        for o in orbit_indices:
            if o not in new_orbits:
                out[o] = self.group_of(o)                       # type: ignore
        if not new_orbits:
            return out
        assert self.ref_flat is not None, "set_reference(w0) first"
        totals = {o: float(sum(sizes[j] for j in orbit_indices[o]))
                  for o in new_orbits}
        pm = None
        for stack, rows in segments:
            if stack is None or stack.shape[0] == 0:
                continue
            W = segment_weight_matrix(new_orbits, orbit_indices, rows,
                                      sizes, totals, stack.shape[0])
            if not W.any():
                continue
            term = jnp.dot(jnp.asarray(W), stack, precision=HIGHEST)
            pm = term if pm is None else pm + term
        if pm is None:
            return out
        ds = np.asarray(jnp.linalg.norm(pm - self._ref_device()[None, :],
                                        axis=1))
        self._assign_new(new_orbits, ds, out)
        return out

    def assign_distances(self, new_orbits: Sequence[int],
                         ds: Sequence[float]) -> Dict[int, int]:
        """Record externally computed distances-to-w0 (e.g. the fused epoch
        program's output) for new orbits and assign their groups — the same
        sequential replay ``observe_orbits*`` uses."""
        out: Dict[int, int] = {}
        self._assign_new(list(new_orbits), np.asarray(ds), out)
        return out

    def _assign_new(self, new_orbits, ds, out: Dict[int, int]) -> None:
        """Replay the exact sequential observe_orbit assignment logic
        (distances enter one at a time so intermediate reclusters match)."""
        for orbit, d in zip(new_orbits, ds):
            self.distances[orbit] = float(d)
            if len(self.groups) < self.num_groups:
                self.groups = group_by_gaps(self.distances, self.num_groups)
                out[orbit] = self.group_of(orbit)               # type: ignore
                continue
            means = [np.mean([self.distances[o] for o in g
                              if o in self.distances])
                     if any(o in self.distances for o in g) else np.inf
                     for g in self.groups]
            gi = int(np.argmin([abs(float(d) - m) for m in means]))
            self.groups[gi].append(orbit)
            out[orbit] = gi

    def regroup(self) -> None:
        """Re-run the gap clustering over all seen orbits (end of an epoch
        where new orbits appeared)."""
        if self.distances:
            self.groups = group_by_gaps(self.distances, self.num_groups)

    def all_grouped(self, num_orbits: int) -> bool:
        return sum(len(g) for g in self.groups) >= num_orbits
