"""Device-resident stacked model storage (the ``ModelBank``).

The AsyncFLEO server path (grouping + staleness-discounted aggregation,
paper §IV-C) only ever needs models as *vectors*: Euclidean distances for
grouping (Fig. 5) and convex combinations for aggregation (eqs. 4/13/14).
The seed implementation nevertheless shuttled every trained model to host as
a pytree and back — O(S) full copies plus Python per-leaf loops per epoch.

``ModelBank`` keeps the whole client population as one stacked ``(C, N)``
float32 array on device from ``train_many`` output all the way through
grouping and aggregation.  A ``FlatSpec`` — built once per model structure
and cached — records how the pytree flattens into the ``N`` axis, so
pytrees only materialize when a caller explicitly asks (``to_pytrees`` /
``unflatten``), e.g. to feed the evaluator one global model per epoch.

Layout convention (see DESIGN.md §2): row ``c`` is client ``c``'s model;
columns are ``jax.tree_util.tree_leaves`` order, each leaf raveled
C-contiguously, concatenated.  All rows are float32.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Every contraction over the bank (eq. 14, grouping partial models) runs at
# full f32 precision: XLA's TPU default is one bf16 pass, which moved the
# grouping distances ~3e-3 relative on a v5e chip.  The CPU ignores it.
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Cached flatten/unflatten recipe for one model structure."""
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]

    @property
    def num_params(self) -> int:
        return int(sum(self.sizes))

    # ---- construction ------------------------------------------------------

    @staticmethod
    def of(model) -> "FlatSpec":
        """Spec for ``model``'s structure (cached by treedef+shapes)."""
        leaves, treedef = jax.tree_util.tree_flatten(model)
        shapes = tuple(tuple(np.shape(l)) for l in leaves)
        key = (treedef, shapes)
        spec = _SPEC_CACHE.get(key)
        if spec is None:
            sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
            spec = FlatSpec(treedef, shapes, sizes)
            _SPEC_CACHE[key] = spec
        return spec

    # ---- flatten -----------------------------------------------------------

    def flatten(self, model) -> jnp.ndarray:
        """Pytree -> (N,) float32 device vector (one fused jitted call —
        per-leaf eager dispatch would cost ~0.1 ms x leaves per call)."""
        return _flatten_jit(self)(model)

    def flatten_stacked(self, stacked_model) -> jnp.ndarray:
        """Pytree whose leaves carry a shared leading axis C -> (C, N)."""
        leaves = jax.tree_util.tree_leaves(stacked_model)
        c = leaves[0].shape[0]
        return jnp.concatenate(
            [jnp.reshape(l, (c, -1)).astype(jnp.float32) for l in leaves],
            axis=1)

    # ---- unflatten ---------------------------------------------------------

    def unflatten(self, flat):
        """(N,) vector -> pytree of device arrays (no host copy)."""
        return _unflatten_jit(self)(jnp.asarray(flat))

    def unflatten_host(self, flat):
        """(N,) vector -> pytree of host numpy arrays (one device_get)."""
        flat = np.asarray(jax.device_get(flat))
        parts, off = [], 0
        for size, shape in zip(self.sizes, self.shapes):
            parts.append(flat[off:off + size].reshape(shape))
            off += size
        return jax.tree_util.tree_unflatten(self.treedef, parts)


_SPEC_CACHE: Dict[Any, FlatSpec] = {}
_UNFLATTEN_JIT: Dict[FlatSpec, Any] = {}


@jax.jit
def gather_rows(stack, idx):
    """Jitted row gather — noticeably faster than the eager `stack[idx]`
    dispatch path on CPU backends, and shape-cached like any jit."""
    return stack[idx]


def pad_bucket_ids(ids: Sequence[int]) -> Tuple[np.ndarray, int]:
    """Pad an index list to the next power-of-two bucket by repeating the
    first id, returning (padded int32 ids, true count).  Bucketing keeps
    jitted vmaps and row gathers at O(log S) distinct shapes as participant
    counts change; padded rows are computed and discarded (<2x bound)."""
    arr = np.asarray(list(ids), dtype=np.int32)
    n = len(arr)
    if n == 0:
        return arr, 0
    b = 1 << max(n - 1, 0).bit_length()
    if b > n:
        arr = np.concatenate([arr, np.full(b - n, arr[0], dtype=np.int32)])
    return arr, n


def flat_base(spec: FlatSpec, base):
    """Base model as a flat (N,) float32 device vector (None passes
    through); shared by the XLA and Pallas aggregation entry points."""
    if base is None:
        return None
    if getattr(base, "ndim", None) == 1:
        return jnp.asarray(base, jnp.float32)
    return spec.flatten(base)


@jax.jit
def flatten_tree(model):
    """Pytree -> (N,) float32 vector in the §2 layout.  Jitted when called
    eagerly; inlines when traced inside a larger program (the fused epoch
    step and custom ``epoch_train_fn`` implementations use it that way —
    structure-generic, jax.jit re-specializes per pytree structure)."""
    leaves = jax.tree_util.tree_leaves(model)
    return jnp.concatenate(
        [jnp.ravel(l).astype(jnp.float32) for l in leaves])


_flatten_tree = flatten_tree          # former private name


def _flatten_jit(spec: FlatSpec):
    del spec                     # flatten needs no spec; jit caches by tree
    return flatten_tree


def _unflatten_jit(spec: FlatSpec):
    fn = _UNFLATTEN_JIT.get(spec)
    if fn is None:
        def _unflatten(flat):
            parts, off = [], 0
            for size, shape in zip(spec.sizes, spec.shapes):
                parts.append(jnp.reshape(flat[off:off + size], shape))
                off += size
            return jax.tree_util.tree_unflatten(spec.treedef, parts)
        fn = _UNFLATTEN_JIT[spec] = jax.jit(_unflatten)
    return fn


@dataclasses.dataclass
class ModelBank:
    """C models held as one device-resident (C, N) float32 stack."""
    spec: FlatSpec
    stack: jnp.ndarray                 # (C, N) float32

    # ---- construction ------------------------------------------------------

    @classmethod
    def from_pytrees(cls, models: Sequence) -> "ModelBank":
        spec = FlatSpec.of(models[0])
        return cls(spec, jnp.stack([spec.flatten(m) for m in models]))

    @classmethod
    def from_stacked_tree(cls, stacked_model) -> "ModelBank":
        """From a vmap output: pytree with shared leading client axis."""
        one = jax.tree_util.tree_map(lambda l: l[0], stacked_model)
        spec = FlatSpec.of(one)
        return cls(spec, spec.flatten_stacked(stacked_model))

    @classmethod
    def from_rows(cls, spec: FlatSpec, rows: Sequence) -> "ModelBank":
        """From per-client (N,) flat vectors (device or host)."""
        return cls(spec, jnp.stack([jnp.asarray(r) for r in rows]))

    # ---- views -------------------------------------------------------------

    def __len__(self) -> int:
        return int(self.stack.shape[0])

    @property
    def num_params(self) -> int:
        return int(self.stack.shape[1])

    def select(self, idx: Sequence[int]) -> "ModelBank":
        """Sub-bank of the given rows (device gather; no host copy)."""
        return ModelBank(self.spec,
                         gather_rows(self.stack,
                                     np.asarray(list(idx), dtype=np.int32)))

    def row(self, i: int) -> jnp.ndarray:
        return self.stack[i]

    # ---- explicit materialization -----------------------------------------

    def to_pytrees(self) -> List:
        """Materialize per-client host pytrees (single device_get)."""
        host = np.asarray(jax.device_get(self.stack))
        out = []
        for c in range(host.shape[0]):
            parts, off = [], 0
            for size, shape in zip(self.spec.sizes, self.spec.shapes):
                parts.append(host[c, off:off + size].reshape(shape))
                off += size
            out.append(jax.tree_util.tree_unflatten(self.spec.treedef, parts))
        return out

    def pytree(self, i: int):
        """Materialize one client's pytree (device arrays)."""
        return self.spec.unflatten(self.stack[i])
