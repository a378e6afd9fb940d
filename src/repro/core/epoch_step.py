"""One fused, buffer-donated device program per simulated epoch.

PR 1 made the server tensor work device-resident, but the epoch hot path
still issued a *chain* of small dispatches — the training vmap, a flatten,
per-segment grouping contractions, per-segment aggregation contractions, an
unflatten — with host sync points in between (``np.asarray(losses)``, the
blocking evaluator).  On CPU that chain is dominated by dispatch overhead;
on accelerators it wastes the async queue.

``EpochStepProgram`` fuses the whole epoch into ONE jitted XLA program
(DESIGN.md §6):

    in :  w_flat (N,) [donated], carry (L, N) stragglers, per-participant
          batch inputs, participant ids, epoch seed, aggregation weight
          vectors over bank/carry rows, base weight, new-orbit partial-
          model row weights + segment ids, grouping reference (N,)
    out:  new_w_flat (N,), bank stack (C, N), new-orbit distances (K,),
          per-participant losses (C,)

Inside the program: ``w_flat`` is unflattened (on device), the pool's
training vmap runs over the participant axis, the trained stack is formed,
the new global model is one ``base_w * w + wv_bank @ stack +
wv_carry @ carry`` contraction, and grouping distances for new orbits are
``|| segment_sum(w_row * rows) - ref ||`` over the same stack — a
segment-sum rather than a dense (K, C) GEMM because each bank row feeds at
most one new orbit (O(C*N), not O(K*C*N); at S=1000 with 125 fresh orbits
that is a 125x FLOP difference).  Because every per-model
weight is host *metadata* math (eqs. 13/14 need sizes/staleness, not
tensors), the weight vectors are program inputs — the one case where they
depend on a tensor result (a *new* orbit arriving while *stale* models are
pending, so group membership depends on this epoch's distances) falls back
to two dispatches (train+distances, then the contraction), counted in
``fallback_dispatches``.

``donate_argnums`` donates the global model buffer so XLA writes the new
global model into it in place — the simulator never touches the donated
buffer again.  The carried-stragglers matrix is NOT donated: it has no
same-shape output for XLA to reuse (donating it only triggers the
"unusable donation" warning), and keeping it alive lets the rare
two-dispatch fallback contract over it without a re-gather.

Mesh-awareness: with a ``jax.sharding.Mesh`` carrying a ``"data"`` axis,
the (C, N) bank and the participant batch shard their leading axis over
"data" (``NamedSharding``), and the bank contraction runs as an explicit
``shard_map`` psum so multi-device hosts scale the participant dimension.
A single-device (identity) mesh — or ``mesh=None`` — leaves every shape
and result bit-identical to the unsharded path, keeping CPU tests
unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.modelbank import HIGHEST, FlatSpec
from repro.obs.span import span

# The fused program's parts, as named scopes in its HLO's op_name metadata
# (DESIGN.md §12): local training (the unflatten of the global model and
# the pool's training vmap), forming the flat (C, N) bank, eq. 14, and
# the new orbits' partial models and grouping distances.
SCOPE_TRAIN = "local_train"
SCOPE_FLATTEN = "flatten"
SCOPE_AGGREGATE = "aggregate"
SCOPE_GROUP_DIST = "group_dist"
SCOPES = (SCOPE_TRAIN, SCOPE_FLATTEN, SCOPE_AGGREGATE, SCOPE_GROUP_DIST)

# Straggler matrices are padded up to at least this many rows so the fused
# program keeps one trace across the common 0..4-straggler epochs.
CARRY_MIN_ROWS = 4


def _dot(a, b):
    return jnp.dot(a, b, precision=HIGHEST)


def next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def carry_capacity(n: int) -> int:
    """Row capacity for a carried-stragglers matrix of ``n`` live rows."""
    return max(CARRY_MIN_ROWS, next_pow2(max(n, 1)))


def _data_axis_size(mesh: Optional[Mesh]) -> int:
    if mesh is None or "data" not in mesh.axis_names:
        return 1
    return int(dict(zip(mesh.axis_names, mesh.devices.shape))["data"])


def bank_sharding(mesh: Mesh) -> NamedSharding:
    """The (C, N) bank layout: participants over "data", params replicated
    (the shared rule lives in ``launch/sharding.py``)."""
    from repro.launch.sharding import bank_sharding as _bs
    return _bs(mesh)


def sharded_contract(w: jnp.ndarray, stack: jnp.ndarray,
                     mesh: Mesh) -> jnp.ndarray:
    """(C,) @ (C, N) with the C axis sharded over "data": each device
    contracts its local rows, one psum combines the partials."""
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P("data"), P("data", None)),
                       out_specs=P(None), check_vma=False)
    def _contract(w_loc, s_loc):
        return jax.lax.psum(_dot(w_loc, s_loc), "data")

    return _contract(w, stack)


def _batch_leaf_sharding(leaf, mesh: Mesh,
                         ndata: int) -> Optional[NamedSharding]:
    """A batch leaf's leading (participant) axis over "data", where it
    divides; None for a leaf that stays whole."""
    if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] % ndata == 0:
        return NamedSharding(mesh, P("data", *([None] * (leaf.ndim - 1))))
    return None


def _constrain_batch(inputs, mesh: Mesh, ndata: int):
    """Shard every batch leaf's leading (participant) axis over "data"."""
    def _c(leaf):
        sharding = _batch_leaf_sharding(leaf, mesh, ndata)
        return (leaf if sharding is None
                else jax.lax.with_sharding_constraint(leaf, sharding))
    return jax.tree.map(_c, inputs)


def put_inputs(inputs, mesh: Optional[Mesh], rows: int):
    """Copy one epoch's gathered host inputs to the device(s) in the
    layout the fused program takes them, so that its call copies nothing
    again: where the program shards the batch (a data mesh whose axis
    divides the ``rows`` padded participants), each batch leaf split over
    "data" (``_constrain_batch``) and every other leaf replicated; else
    on the default device, where the call would have put host arrays."""
    ndata = _data_axis_size(mesh)
    if ndata <= 1 or rows % ndata:
        return jax.device_put(inputs)
    whole = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda leaf: jax.device_put(
            leaf, _batch_leaf_sharding(leaf, mesh, ndata) or whole),
        inputs)


@dataclasses.dataclass
class EpochStepProgram:
    """The per-epoch fused program for one (FlatSpec, trainer) pair.

    ``train_fn(params, inputs, ids, seed) -> (stacked_models, losses)`` must
    be traceable; ``stacked_models`` is either a pytree whose leaves carry a
    leading participant axis (a vmap output) or already a flat (C, N) stack.
    """
    spec: FlatSpec
    train_fn: Callable[..., Tuple[Any, jnp.ndarray]]
    mesh: Optional[Mesh] = None
    donate: bool = True
    use_kernel: bool = False           # fed_agg Pallas contraction (below)

    dispatches: int = 0                # fused one-dispatch epochs
    fallback_dispatches: int = 0       # epochs that needed train+agg split
    batched_dispatches: int = 0        # scenario-batched physical dispatches
    traces: int = 0                    # JAX traces of the fused program

    def __post_init__(self):
        donate = (0,) if self.donate else ()
        self._step = jax.jit(self._trace, donate_argnums=donate,
                             static_argnums=(10, 11))
        self._batched_fns = {}         # (mode,) -> jitted scenario-batched fn

    # ---- traced body -------------------------------------------------------

    def _trace(self, *args):
        """The jitted step's Python body: it runs only when JAX traces the
        program (a new static signature), never at steady state."""
        self.traces += 1
        w_flat, carry, ids, kpad, blocked_m = (args[0], args[1], args[3],
                                               args[10], args[11])
        with span("fused_trace", carry_rows=int(carry.shape[0]),
                  rows=int(ids.shape[0]), kpad=int(kpad),
                  blocked_m=int(blocked_m), params=int(w_flat.shape[0])):
            return self._body(*args)

    def _body(self, w_flat, carry, inputs, ids, seed,
              wv_bank, wv_carry, base_w, dw_row, dw_seg, kpad,
              blocked_m, dw_carry, ref):
        # the named scopes land in each HLO instruction's op_name metadata
        # and nowhere else: a device trace charges every op to its part
        mesh, ndata = self.mesh, _data_axis_size(self.mesh)
        sharded = ndata > 1 and int(ids.shape[0]) % ndata == 0
        with jax.named_scope(SCOPE_TRAIN):
            if sharded:
                inputs = _constrain_batch(inputs, mesh, ndata)
            params = self.spec.unflatten(w_flat)
            stacked, losses = self.train_fn(params, inputs, ids, seed)
        with jax.named_scope(SCOPE_FLATTEN):
            stack = (stacked if getattr(stacked, "ndim", None) == 2
                     else self.spec.flatten_stacked(stacked))
            if sharded:
                stack = jax.lax.with_sharding_constraint(
                    stack, bank_sharding(mesh))
        with jax.named_scope(SCOPE_AGGREGATE):
            new_w = self._aggregate(w_flat, stack, carry, wv_bank, wv_carry,
                                    base_w, sharded)
        with jax.named_scope(SCOPE_GROUP_DIST):
            dists = self._group_dists(stack, carry, dw_row, dw_seg, kpad,
                                      blocked_m, dw_carry, ref)
        return new_w, stack, dists, losses

    def _aggregate(self, w_flat, stack, carry, wv_bank, wv_carry, base_w,
                   sharded):
        """Eq. 14: the new global model from the base, bank and carry."""
        if sharded:
            # the shard_map psum keeps the XLA contraction — the Pallas
            # kernel is single-device (per-shard pallas_call under
            # shard_map is future work; the flag is ignored here)
            bank_term = sharded_contract(wv_bank, stack, self.mesh)
            new_w = base_w * w_flat + bank_term + _dot(wv_carry, carry)
        elif self.use_kernel:
            # route eq. 14 through the fed_agg Pallas kernel, inlined into
            # the fused program: the bank pass folds in the (donated) base
            # model, the carry pass accumulates onto its output
            from repro.kernels.fed_agg import ops as agg_ops
            new_w = agg_ops.fed_agg(stack, wv_bank, w_flat, base_w)
            new_w = agg_ops.fed_agg(carry, wv_carry, new_w, 1.0)
        else:
            new_w = (base_w * w_flat + _dot(wv_bank, stack)
                     + _dot(wv_carry, carry))
        return new_w

    @staticmethod
    def _group_dists(stack, carry, dw_row, dw_seg, kpad, blocked_m,
                     dw_carry, ref):
        """New orbits' partial models and their distances to ``ref``."""
        if kpad:
            c, n = stack.shape
            if blocked_m:
                # new orbits own contiguous equal row blocks (the common
                # full-participation layout): one O(C*N) blocked einsum
                pm = jnp.einsum("km,kmn->kn",
                                dw_row.reshape(kpad, blocked_m),
                                stack.reshape(kpad, blocked_m, n),
                                precision=HIGHEST)
            else:
                # general layout: one-hot the segment ids into a dense
                # (kpad+1, C) weight matrix on device and GEMM (the +1
                # dump row also keeps XLA CPU off its pathological
                # 1-row-dot fusion)
                w_mat = (jax.nn.one_hot(dw_seg, kpad + 1,
                                        dtype=jnp.float32).T
                         * dw_row[None, :])
                pm = _dot(w_mat, stack)[:kpad]
            pm = pm + _dot(dw_carry, carry)
            return jnp.linalg.norm(pm - ref[None, :], axis=1)
        return jnp.zeros((0,), jnp.float32)

    # ---- scenario batch axis (DESIGN.md §13) -------------------------------

    def _unrolled(self, w_stack, carry, inputs, ids, seeds,
                  wv_bank, wv_carry, base_w, dw_row, dw_seg, kpad,
                  blocked_m, dw_carry, ref):
        """B per-scenario epochs as ONE program, bit-exact per scenario.

        A traced Python loop (unrolled at jit time) over the scenario axis:
        each iteration is *the same* ``_trace`` computation graph the solo
        path jits, so XLA sees B independent copies of the identical HLO and
        every per-scenario output is bitwise what the sequential run
        produces.  ``jax.vmap`` would be one batched GEMM instead of B —
        faster, but its batched ``dot_general`` reduces in a different
        order, so it is NOT bit-exact (~1e-6 on new_w on CPU); that is the
        opt-in ``mode="vmap"`` below, never the parity default.
        """
        self.traces += 1
        outs = []
        with span("fused_trace", scenarios=int(w_stack.shape[0]),
                  carry_rows=int(carry.shape[1]), rows=int(ids.shape[1]),
                  kpad=int(kpad), blocked_m=int(blocked_m),
                  params=int(w_stack.shape[1])):
            for i in range(w_stack.shape[0]):
                inp = (None if inputs is None
                       else jax.tree.map(lambda l: l[i], inputs))
                outs.append(self._body(
                    w_stack[i], carry[i], inp, ids[i], seeds[i],
                    wv_bank[i], wv_carry[i], base_w[i], dw_row[i],
                    dw_seg[i], kpad, blocked_m, dw_carry[i], ref[i]))
        return tuple(jnp.stack(parts) for parts in zip(*outs))

    def batched_step(self, w_stack, carry, inputs, ids, seeds,
                     wv_bank, wv_carry, base_w, dw_row, dw_seg, kpad: int,
                     blocked_m: int, dw_carry, ref, *, mode: str = "exact"):
        """Dispatch B scenarios' epochs as one physical program.

        Every array carries a leading scenario axis B (batch leaves of
        ``inputs`` too; ``inputs=None`` stays None); ``kpad``/``blocked_m``
        are static and shared — the DispatchBatcher only groups requests
        with identical static signatures.  The stacked ``w_stack`` is
        donated (it is a fresh buffer the batcher built; the per-scenario
        flats it was stacked from stay alive).  Returns lazy
        (B, ...)-leading outputs; callers slice per scenario.
        """
        if self.mesh is not None or self.use_kernel:
            raise ValueError("scenario batching supports the plain XLA "
                             "path only (mesh=None, use_kernel=False); "
                             "route mesh/kernel programs solo")
        if mode not in ("exact", "vmap"):
            raise ValueError(f"unknown scenario batch mode {mode!r}")
        key = (mode, inputs is None)
        fn = self._batched_fns.get(key)
        if fn is None:
            donate = (0,) if self.donate else ()
            if mode == "exact":
                fn = jax.jit(self._unrolled, donate_argnums=donate,
                             static_argnums=(10, 11))
            else:
                in_axes = (0, 0, (None if inputs is None else 0), 0, 0,
                           0, 0, 0, 0, 0, None, None, 0, 0)
                fn = jax.jit(jax.vmap(self._trace, in_axes=in_axes),
                             donate_argnums=donate, static_argnums=(10, 11))
            self._batched_fns[key] = fn
        self.batched_dispatches += 1
        return fn(w_stack, carry, inputs, ids, seeds, wv_bank, wv_carry,
                  base_w, dw_row, dw_seg, int(kpad), int(blocked_m),
                  dw_carry, ref)

    # ---- dispatch ----------------------------------------------------------

    def step(self, w_flat, carry, inputs, ids_np: np.ndarray, seed: int,
             wv_bank: np.ndarray, wv_carry: np.ndarray, base_w: float,
             dw_row: np.ndarray, dw_seg: np.ndarray, kpad: int,
             blocked_m: int, dw_carry: np.ndarray, ref,
             *, fallback: bool = False):
        """Dispatch one epoch.  All returned values are lazy device arrays —
        nothing here blocks; callers block only on what they record.

        ``w_flat`` is consumed (donated): pass a buffer you will not
        reuse.  ``wv_*`` / ``dw_*`` / ``base_w`` are host metadata (numpy);
        ``ids_np`` is the padded participant id vector.  ``dw_row``/
        ``dw_seg`` give each bank row its partial-model weight and its
        new-orbit segment (``kpad`` = dump id, static; pow2-bucketed so
        trace count stays O(log orbits)); ``blocked_m`` > 0 (static)
        asserts segment k owns exactly rows [k*m, (k+1)*m) and selects the
        blocked einsum.  The returned distances carry ``kpad`` entries of
        which the first K are real.
        """
        if fallback:
            self.fallback_dispatches += 1
        else:
            self.dispatches += 1
        return self._step(
            w_flat, carry, inputs,
            jnp.asarray(ids_np, jnp.int32), np.uint32(seed),
            jnp.asarray(np.asarray(wv_bank, np.float32)),
            jnp.asarray(np.asarray(wv_carry, np.float32)),
            np.float32(base_w),
            jnp.asarray(np.asarray(dw_row, np.float32)),
            jnp.asarray(np.asarray(dw_seg, np.int32)),
            int(kpad), int(blocked_m),
            jnp.asarray(np.asarray(dw_carry, np.float32)),
            ref)


def make_epoch_program(trainer, params, mesh: Optional[Mesh] = None,
                       *, donate: bool = True,
                       use_kernel: bool = False) -> Optional[EpochStepProgram]:
    """Build (or reuse) the fused program for a trainer exposing the
    fused-epoch protocol (``epoch_train_fn`` + ``epoch_inputs``); None
    otherwise.  Programs are cached on the trainer so repeated simulations
    with the same trainer share jit traces and compiled executables."""
    fn = getattr(trainer, "epoch_train_fn", None)
    if fn is None or not hasattr(trainer, "epoch_inputs"):
        return None
    spec = FlatSpec.of(params)
    cache = getattr(trainer, "_epoch_programs", None)
    if cache is None:
        cache = {}
        try:
            trainer._epoch_programs = cache
        except AttributeError:        # trainer forbids attributes: no reuse
            pass
    key = (spec, mesh, donate, use_kernel)   # Mesh is hashable; id() could
    prog = cache.get(key)                    # collide
    if prog is None:
        prog = cache[key] = EpochStepProgram(spec, fn(), mesh=mesh,
                                             donate=donate,
                                             use_kernel=use_kernel)
    return prog
