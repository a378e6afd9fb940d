"""Pallas kernel: fused staleness-discounted aggregation (paper eq. 14).

    out = base_weight * w_prev + sum_c gamma_c * W[c]

W is the stack of C client models flattened to (C, N).  The grid tiles N;
each step loads a (C, BLOCK_N) VMEM tile of W, the matching (BLOCK_N,) tile
of w_prev, and reduces over clients with a (1,C)x(C,BLOCK_N) dot — MXU work,
one HBM pass over the client stack, no intermediate (C, N) temporaries like
the naive tree_map sum would make.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 2048


def _agg_kernel(w_ref, gamma_ref, base_ref, bw_ref, out_ref):
    # w_ref: (C, BLOCK_N) VMEM; gamma_ref: (1, C); base_ref/out_ref: (1, BLOCK_N)
    mixed = jnp.dot(gamma_ref[...], w_ref[...],
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)        # (1, BLOCK_N)
    out_ref[...] = bw_ref[0, 0] * base_ref[...] + mixed


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def fed_agg_flat(stack, gamma, base, base_weight, *, interpret: bool = True,
                 block_n: int = BLOCK_N):
    """stack: (C, N) f32, gamma: (C,), base: (N,), base_weight: scalar."""
    C, N = stack.shape
    pad = (-N) % block_n
    if pad:
        stack = jnp.pad(stack, ((0, 0), (0, pad)))
        base = jnp.pad(base, (0, pad))
    Np = N + pad
    grid = (Np // block_n,)
    out = pl.pallas_call(
        _agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((C, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.float32),
        interpret=interpret,
    )(stack.astype(jnp.float32), gamma[None].astype(jnp.float32),
      base[None].astype(jnp.float32),
      jnp.asarray(base_weight, jnp.float32)[None, None])
    return out[0, :N]
