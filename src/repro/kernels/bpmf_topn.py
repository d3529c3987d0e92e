"""Pallas TPU kernel: tiled U @ V^T scoring with streaming top-k.

The BPMF serving hot loop scores a user batch against the full item catalogue
and keeps only the N best items per user:

    scores = U_batch @ V^T            (B, N) — never materialised
    top-k over the item axis          (B, TOPK) values + indices

Materialising (B, N) for millions of items blows HBM and wastes bandwidth on
scores that are immediately discarded. Instead the grid tiles the item axis:
each step computes one (B_blk, N_blk) score tile on the MXU and folds it into
a running (B_blk, TOPK) candidate list held in the output refs, so only the
candidates ever leave VMEM. The item axis is the fastest-varying grid
dimension (sequential on TPU), which makes the in-place merge race-free.

Each merge runs `topk` rounds of max-and-lowest-index selection over the
running list and the fresh tile (`_select_topk`): Mosaic lowers a max, a
min and a select, not `lax.top_k`. Taking the lowest item id among equal
scores reproduces `jax.lax.top_k` over the full score row bit-for-bit,
ties included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _select_topk(cand, topk: int):
    """`topk` rounds of max-and-lowest-index selection over candidate
    (value, item) arrays of shape (BB, *). Equal to `lax.top_k` over the
    same candidates, ties included: every round takes the largest value and,
    among equal values, the lowest item id, then retires that item."""
    bb = cand[0][0].shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (bb, topk), 1)
    big = jnp.iinfo(jnp.int32).max

    def round_(t, carry):
        cand, out_v, out_i = carry
        best = functools.reduce(
            jnp.maximum, [jnp.max(v, axis=1, keepdims=True) for v, _ in cand])
        pick = functools.reduce(jnp.minimum, [
            jnp.min(jnp.where(v == best, i, big), axis=1, keepdims=True)
            for v, i in cand])
        cand = tuple(
            (jnp.where(i == pick, -jnp.inf, v), jnp.where(i == pick, big, i))
            for v, i in cand)
        out_v = jnp.where(col == t, best, out_v)
        out_i = jnp.where(col == t, pick, out_i)
        return cand, out_v, out_i

    init = (tuple(cand), jnp.zeros((bb, topk), jnp.float32),
            jnp.zeros((bb, topk), jnp.int32))
    _, out_v, out_i = jax.lax.fori_loop(0, topk, round_, init)
    return out_v, out_i


def _topn_kernel(u_ref, v_ref, val_ref, idx_ref, *, topk: int, n_valid: int,
                 block_n: int):
    j = pl.program_id(1)
    u = u_ref[...]                                 # (BB, K)
    v = v_ref[...]                                 # (BN, K)
    scores = jax.lax.dot_general(
        u, v,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                              # (BB, BN)
    cols = j * block_n + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(cols < n_valid, scores, -jnp.inf)

    @pl.when(j == 0)
    def _init():  # an empty running list: no entry outranks a real score
        val_ref[...] = jnp.full(val_ref.shape, -jnp.inf, jnp.float32)
        idx_ref[...] = jnp.full(idx_ref.shape, jnp.iinfo(jnp.int32).max,
                                jnp.int32)

    run = (val_ref[...], idx_ref[...])
    val_ref[...], idx_ref[...] = _select_topk([run, (scores, cols)], topk)


_trace_count = 0


def trace_count() -> int:
    """How many times the top-N kernel has been (re)traced this process.

    The body of `topn_scores_pallas` bumps the counter at trace time only,
    so the count moves exactly when the jit cache misses — a new
    (shape, static-arg) combination. Serving publishes with unchanged
    (S, N, K) must leave it flat (tests/test_publish.py asserts this);
    compare before/after a swap to prove executable reuse.
    """
    return _trace_count


@functools.partial(
    jax.jit,
    static_argnames=("topk", "n_valid", "block_b", "block_n", "interpret"),
)
def topn_scores_pallas(
    u: jax.Array,
    v: jax.Array,
    *,
    topk: int,
    n_valid: int,
    block_b: int = 8,
    block_n: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """u: (B, K), v: (N, K) -> (values (B, topk) f32, indices (B, topk) i32).

    B must divide by block_b and N by block_n; rows of v at index >= n_valid
    are padding and never selected (ops.py pads). topk <= block_n so the
    first tile alone can seed the candidate list.
    """
    global _trace_count
    _trace_count += 1  # executes at trace time only: one bump per jit miss
    b, k = u.shape
    n = v.shape[0]
    assert b % block_b == 0 and n % block_n == 0, (b, n, block_b, block_n)
    assert topk <= block_n, (topk, block_n)
    assert topk <= n_valid <= n, (topk, n_valid, n)
    grid = (b // block_b, n // block_n)
    kernel = functools.partial(
        _topn_kernel, topk=topk, n_valid=n_valid, block_n=block_n
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, k), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, topk), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, topk), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, topk), jnp.float32),
            jax.ShapeDtypeStruct((b, topk), jnp.int32),
        ],
        interpret=interpret,
    )(u, v)
