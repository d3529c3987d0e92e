"""Pallas TPU kernel: fused batched Cholesky factor + solve + sample.

BPMF never needs the precision inverse (paper Sec 3.1): the sampler needs

    x = Lambda^-1 b + L^-T z           with Lambda = L L^T.

This kernel fuses, per VMEM-resident batch tile of K x K matrices:
  1. right-looking Cholesky (column loop, vectorized over the batch tile),
  2. forward substitution  L y = b,
  3. one back substitution L^T x = (y + z)  — mean and noise share it.

K is small (64 padded), so a whole (BB, K, K) tile lives in VMEM and each
stage is a lax.fori_loop over columns — no HBM traffic between the three
stages, which is the point of fusing them. The loops pick rows and columns
with iota masks and lane/sublane reductions, not dynamic slices, which
Mosaic does not lower.

The batch axis is one flat leading dimension; callers with stacked batches
— the serving fold-in's (S draws, B users) solve — flatten them into a
single (S*B) launch through the `kernels.ops.chol_solve_sample` wrapper,
which also pads the batch to the tile size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _chol_solve_kernel(prec_ref, rhs_ref, z_ref, out_ref):
    a = prec_ref[...].astype(jnp.float32)          # (B, K, K)
    b = rhs_ref[...].astype(jnp.float32)[:, None, :]   # (B, 1, K)
    z = z_ref[...].astype(jnp.float32)[:, None, :]     # (B, 1, K)
    bb, k, _ = a.shape
    # Column j of a (B, K, K) array is a lane reduction under a lane mask and
    # comes out along sublanes, (B, K, 1); row j is a sublane reduction and
    # comes out along lanes, (B, 1, K). Each is used in the orientation it
    # comes out in, so no loop step slices or relayouts a vector.
    sub = jax.lax.broadcasted_iota(jnp.int32, (1, k, 1), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, k), 2)

    def col(x, j):
        return jnp.sum(jnp.where(lane == j, x, 0.0), axis=2, keepdims=True)

    def row(x, j):
        return jnp.sum(jnp.where(sub == j, x, 0.0), axis=1, keepdims=True)

    # --- Cholesky, column by column. Invariant: cols >= j of l are zero. ---
    def chol_col(j, l):
        s = jnp.sum(l * row(l, j), axis=2, keepdims=True)      # (B, K, 1)
        c = col(a, j) - s
        dj = jnp.sqrt(jnp.maximum(row(c, j), 1e-20))           # (B, 1, 1)
        newcol = jnp.where(sub >= j, c / dj, 0.0)
        return jnp.where(lane == j, newcol, l)

    l = jax.lax.fori_loop(0, k, chol_col, jnp.zeros_like(a))

    # --- forward substitution: L y = b (row j of L against solved y) ---
    def fwd(j, y):
        lrow = row(l, j)                                       # (B, 1, K)
        ljj = col(lrow, j)
        yj = (col(b, j) - jnp.sum(jnp.where(lane < j, lrow, 0.0) * y,
                                  axis=2, keepdims=True)) / ljj
        return jnp.where(lane == j, yj, y)

    y = jax.lax.fori_loop(0, k, fwd, jnp.zeros_like(b))
    y = y + z                                       # mean + noise share L^-T

    # --- back substitution: L^T x = y, sweeping rows of L from the last:
    # x_j = r_j / L[j, j], then r_i -= L[j, i] x_j for every i < j ---
    def bwd(t, carry):
        x, r = carry
        j = k - 1 - t
        lrow = row(l, j)
        xj = col(r, j) / col(lrow, j)
        return jnp.where(lane == j, xj, x), r - lrow * xj

    x, _ = jax.lax.fori_loop(0, k, bwd, (jnp.zeros_like(b), y))
    out_ref[...] = x[:, 0, :]


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def chol_solve_sample_pallas(
    prec: jax.Array,
    rhs: jax.Array,
    z: jax.Array,
    *,
    block_b: int = 16,
    interpret: bool = False,
) -> jax.Array:
    """prec: (B, K, K), rhs/z: (B, K) -> x (B, K). B % block_b == 0."""
    bsz, k, _ = prec.shape
    assert bsz % block_b == 0, (bsz, block_b)
    grid = (bsz // block_b,)
    return pl.pallas_call(
        _chol_solve_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, k, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, k), jnp.float32),
        interpret=interpret,
    )(prec, rhs, z)
