"""Pallas TPU kernel: batched Cholesky factor + solve + sample, batch on lanes.

BPMF never needs the precision inverse (paper Sec 3.1): the sampler needs

    x = L^-T (L^-1 b + z)           with Lambda = L L^T,

the posterior mean Lambda^-1 b plus the noise L^-T z, through one shared
back substitution.

Layout. One grid step holds `block` systems, batch minor: the precisions
as (K, K, block) and b, z, x as (K, block), one system per lane. Element
(i, j) of every system of the tile is then one lane vector, and column j
of L is the slab `l[j]`, (K, block), rows on the sublanes. Every step of
the factorization and of the substitutions is a slab multiply-add over
whole vregs: no masked reductions over a tile, and no half-empty vregs
where K = 64 is half of the 128 lanes. The wrapper hands the kernel the
(C, K, K) precisions transposed to (K, K, C) in XLA, which lays out the
producing fusion batch-minor where it can, and transposes x back.

Factorization: left-looking, with an inner loop over the earlier columns p,

    acc = A[:, j] - sum_{p<j} L[:, p] L[j, p];   L[:, j] = acc / sqrt(acc[j]),

on the rows from j's sublane group down (the rows above j of L[:, j] are
zero). The group is a static loop, so each slab slice is static; the
columns within a group and the earlier columns are `fori_loop`s, which
keeps the traced kernel small: the sweep sends it a dozen batch shapes,
and each call site traces and lowers it anew. Column j of A is read as
its row j (A is symmetric).
Forward substitution L y = b runs column by column (r -= L[:, j] y_j); the
back substitution L^T x = y + z takes dot products of the columns of L
with the solved part of x.

A batch that does not fill the last tile is padded inside the kernel with
identity systems and zero right-hand sides, so no padded copy is made in
HBM; pad lanes are never written back. `lane_stats()` counts, as each call
is traced, the systems and the lanes the kernel is sent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8
# Systems per grid step, and the inner loop's unroll. At K = 64 the
# double-buffered (K, K, 256) input and the (K, K, 256) factor take 12 MiB
# of VMEM.
BLOCK = 256
UNROLL = 4

_systems = 0
_lanes = 0


def lane_stats() -> tuple[int, int]:
    """(systems, lanes) sent through the kernel by the calls traced so far.

    `chol_solve_sample_pallas` adds a call's batch and that batch padded to
    whole tiles when the call is traced: once per call site of each
    compiled program (a call inside a loop body, such as the sweep's row
    chunks, counts once), once per call when run eagerly. systems / lanes
    is the share of the kernel's lanes that hold a real system.
    """
    return _systems, _lanes


def _chol_solve_kernel(prec_ref, rhs_ref, z_ref, out_ref, l_ref, r_ref, x_ref,
                       *, k: int, n_valid: int, block: int, unroll: int):
    # lanes past the batch hold identity systems with zero b and z
    lane = (pl.program_id(0) * block
            + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1))
    valid = lane < n_valid                                     # (1, block)

    # --- left-looking Cholesky: l[j] = L[:, j], on rows >= lo, the first
    # row of j's sublane group (static, so every slab slice is static) ---
    for lo in range(0, k, SUBLANES):
        rows = jax.lax.broadcasted_iota(jnp.int32, (k - lo, block), 0) + lo

        def column(j, carry, lo=lo, rows=rows):
            def step(p, acc):
                return acc - l_ref[p, lo:, :] * l_ref[p, pl.ds(j, 1), :]

            def steps(q, acc):
                for t in range(unroll):
                    acc = step(q * unroll + t, acc)
                return acc

            a_j = jnp.where(valid, prec_ref[j, lo:, :].astype(jnp.float32),
                            jnp.where(rows == j, 1.0, 0.0))
            acc = jax.lax.fori_loop(0, j // unroll, steps, a_j)
            acc = jax.lax.fori_loop(j // unroll * unroll, j, step, acc)
            l_ref[j, lo:, :] = acc
            d = l_ref[j, pl.ds(j, 1), :]               # A_jj - sum_p L_jp^2
            l_ref[j, lo:, :] = jnp.where(rows >= j, acc * (1.0 / jnp.sqrt(d)),
                                         0.0)
            if lo:
                l_ref[j, :lo, :] = jnp.zeros((lo, block), jnp.float32)
            return carry

        jax.lax.fori_loop(lo, min(lo + SUBLANES, k), column, 0)

    # --- forward substitution L y = b: r holds b less the solved part;
    # y_j = r_j / L[j, j], then r -= L[:, j] y_j ---
    r_ref[...] = jnp.where(valid, rhs_ref[...].astype(jnp.float32), 0.0)

    def fwd(j, carry):
        yj = r_ref[pl.ds(j, 1), :] / l_ref[j, pl.ds(j, 1), :]
        r_ref[...] = r_ref[...] - l_ref[j] * yj
        x_ref[pl.ds(j, 1), :] = yj
        return carry

    jax.lax.fori_loop(0, k, fwd, 0)

    # --- back substitution L^T x = y + z, from the last row up:
    # x_j = (c_j - L[:, j] . x) / L[j, j], unsolved entries of x still 0 ---
    r_ref[...] = x_ref[...] + jnp.where(valid, z_ref[...].astype(jnp.float32),
                                        0.0)
    x_ref[...] = jnp.zeros((k, block), jnp.float32)

    def bwd(t, carry):
        j = k - 1 - t
        dot = jnp.sum(l_ref[j] * x_ref[...], axis=0, keepdims=True)
        x_ref[pl.ds(j, 1), :] = ((r_ref[pl.ds(j, 1), :] - dot)
                                 / l_ref[j, pl.ds(j, 1), :])
        return carry

    jax.lax.fori_loop(0, k, bwd, 0)
    out_ref[...] = x_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "unroll", "interpret"))
def _solve(prec, rhs, z, *, block: int, unroll: int, interpret: bool):
    c, k, _ = prec.shape
    kernel = functools.partial(_chol_solve_kernel, k=k, n_valid=c,
                               block=block, unroll=unroll)
    x = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(c, block),),
        in_specs=[
            pl.BlockSpec((k, k, block), lambda i: (0, 0, i)),
            pl.BlockSpec((k, block), lambda i: (0, i)),
            pl.BlockSpec((k, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((k, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, k, block), jnp.float32),
                        pltpu.VMEM((k, block), jnp.float32),
                        pltpu.VMEM((k, block), jnp.float32)],
        interpret=interpret,
        name="chol_solve_sample",
    )(prec.transpose(1, 2, 0), rhs.T, z.T)
    return x.T


def chol_solve_sample_pallas(prec: jax.Array, rhs: jax.Array, z: jax.Array, *,
                             interpret: bool = False) -> jax.Array:
    """prec: (C, K, K) symmetric positive definite, rhs/z: (C, K) -> x (C, K),
    for any C >= 1."""
    global _systems, _lanes
    c = prec.shape[0]
    _systems += c
    _lanes += -(-c // BLOCK) * BLOCK
    return _solve(prec, rhs, z, block=BLOCK, unroll=UNROLL, interpret=interpret)
