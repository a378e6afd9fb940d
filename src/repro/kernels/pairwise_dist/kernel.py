"""Pallas kernel: pairwise squared-L2 distances between M flattened models.

    D[i,j] = ||X[i] - X[j]||^2 = n_i + n_j - 2 * X X^T

The parameter dimension N is huge (models have 1e5..1e9 entries) while M is
tiny (orbits / satellites), so the kernel streams N in VMEM-sized tiles and
accumulates the (M, M) Gram matrix and the per-row squared norms in VMEM
scratch, finalizing D on the last grid step — one HBM pass, no (M, N)
temporaries materialized twice like the broadcast-subtract oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_N = 4096


def _pdist_kernel(x_ref, out_ref, gram_acc, norm_acc):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        gram_acc[...] = jnp.zeros_like(gram_acc)
        norm_acc[...] = jnp.zeros_like(norm_acc)

    xb = x_ref[...].astype(jnp.float32)                     # (M, BLOCK_N)
    gram_acc[...] += jnp.dot(xb, xb.T, precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    norm_acc[...] += jnp.sum(xb * xb, axis=1, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _finalize():
        n = norm_acc[...]
        d = n + n.T - 2.0 * gram_acc[...]
        out_ref[...] = jnp.maximum(d, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def pairwise_dist_sq(x, *, interpret: bool = True, block_n: int = BLOCK_N):
    """x: (M, N) -> (M, M) squared distances."""
    M, N = x.shape
    pad = (-N) % block_n
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))   # zero pad leaves distances intact
    grid = ((N + pad) // block_n,)
    return pl.pallas_call(
        _pdist_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((M, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((M, M), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, M), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((M, M), jnp.float32),
            pltpu.VMEM((M, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)
