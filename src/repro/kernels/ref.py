"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def masked_syrk_ref(vm: jax.Array, rv: jax.Array) -> tuple[jax.Array, jax.Array]:
    """vm: (R, W, K) pre-masked gathered factors; rv: (R, W) masked ratings.

    Returns (prec (R,K,K) = vm^T vm, rhs (R,K) = rv @ vm) per row.
    """
    prec = jnp.einsum("rwk,rwl->rkl", vm, vm, preferred_element_type=jnp.float32)
    rhs = jnp.einsum("rwk,rw->rk", vm, rv)
    return prec, rhs


def chol_solve_sample_ref(prec: jax.Array, rhs: jax.Array, z: jax.Array) -> jax.Array:
    """x = Lambda^-1 rhs + L^-T z with Lambda = L L^T (batched)."""
    chol = jnp.linalg.cholesky(prec)
    y = jax.lax.linalg.triangular_solve(chol, rhs[..., None], left_side=True, lower=True)
    x = jax.lax.linalg.triangular_solve(
        chol, y + z[..., None], left_side=True, lower=True, transpose_a=True
    )
    return x[..., 0]


def topn_scores_ref(
    u: jax.Array, v: jax.Array, topk: int
) -> tuple[jax.Array, jax.Array]:
    """Monolithic U @ V^T then jax.lax.top_k — the bit-for-bit oracle."""
    scores = jax.lax.dot_general(
        u, v,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return jax.lax.top_k(scores, topk)
