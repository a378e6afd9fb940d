"""Pallas kernel: chunked linear recurrence (RWKV6 / Mamba2-SSD).

Grid = (B*H, T/Lc); the chunk axis is innermost and sequential, carrying the
(K, V) recurrent state in VMEM scratch across chunks of the same batch-head
(re-seeded from the state0 input at chunk 0).  Per chunk: two (Lc,K)x(K,V)
matmuls + one (Lc,K)x(K,Lc) masked matmul — MXU work — with the decay
exponentials computed in f32 on the VPU.  Mosaic has no cumsum: the
in-chunk prefix sums of the log decays are triangular matmuls and the
state's per-row decay a diagonal one, at HIGHEST precision so they stay
exact to f32 rounding.  See models/scan_ops.py for the math and the
stabilization/clamp discussion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _chunk_kernel(r_ref, k_ref, v_ref, ld_ref, s0_ref, u_ref,
                  y_ref, sfin_ref, state, *, include_current: bool, Lc: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _seed():
        state[...] = s0_ref[0]

    S = state[...]                                        # (K, V) f32
    r = r_ref[0].astype(jnp.float32)                      # (Lc, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)                      # (Lc, V)
    ld = ld_ref[0].astype(jnp.float32)                    # (Lc, K)

    rows = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 1)
    L = _dot(jnp.where(rows >= cols, 1.0, 0.0), ld)      # inclusive cumsum
    keep = (rows >= cols) if include_current else (rows > cols)
    M = L if include_current else _dot(jnp.where(keep, 1.0, 0.0), ld)
    L_end = jnp.sum(ld, axis=0, keepdims=True)           # (1, K)

    q_t = r * jnp.exp(M)
    k_t = k * jnp.exp(-L)
    y_cross = jnp.dot(q_t, S, preferred_element_type=jnp.float32)
    A = jnp.dot(q_t, k_t.T, preferred_element_type=jnp.float32)
    A = jnp.where(keep, A, 0.0)
    y = y_cross + jnp.dot(A, v, preferred_element_type=jnp.float32)
    if not include_current:
        u = u_ref[0].astype(jnp.float32)                  # (1, K)
        diag = jnp.sum(r * u * k, axis=1, keepdims=True)
        y = y + diag * v

    k_carry = k * jnp.exp(L_end - L)
    K = S.shape[0]
    decay = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
                      == jax.lax.broadcasted_iota(jnp.int32, (K, K), 1),
                      jnp.exp(L_end), 0.0)                # diag(exp(L_end))
    S_new = (_dot(decay, S)
             + jnp.dot(k_carry.T, v, preferred_element_type=jnp.float32))
    state[...] = S_new
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit_state():
        sfin_ref[0] = S_new


@functools.partial(jax.jit, static_argnames=("include_current", "chunk",
                                             "interpret"))
def chunk_scan_flat(r, k, v, ld, s0, u, *, include_current: bool,
                    chunk: int, interpret: bool = True):
    """Flattened-batch-head form.
    r, k, ld: (BH, T, K); v: (BH, T, V); s0: (BH, K, V); u: (BH, K).
    ``u`` enters the kernel as (BH, 1, K) so its block's last two dims equal
    the array's, as Mosaic's (8, 128) tiling rule requires.
    Returns (y (BH, T, V), s_fin (BH, K, V))."""
    BH, T, K = r.shape
    V = v.shape[-1]
    Lc = chunk
    assert T % Lc == 0, (T, Lc)
    grid = (BH, T // Lc)
    kernel = functools.partial(_chunk_kernel, include_current=include_current,
                               Lc=Lc)
    y, s_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Lc, K), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, Lc, K), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, Lc, V), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, Lc, K), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, K, V), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, K), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Lc, V), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, K, V), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, V), v.dtype),
            jax.ShapeDtypeStruct((BH, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
    )(r, k, v, ld, s0, u[:, None, :])
    return y, s_fin
