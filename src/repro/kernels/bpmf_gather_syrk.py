"""Pallas TPU kernel: FUSED gather + masked syrk for BPMF.

The training sweep's hot loop is, per bucket row r with counterpart ids
idx[r, :] and ratings val[r, :]:

    prec_r = sum_w  V[idx[r,w]] V[idx[r,w]]^T * mask[r,w]
    rhs_r  = sum_w  V[idx[r,w]] * val[r,w] * mask[r,w]

followed by a per-item segment reduction over rows (long-tail items are
split across rows). The two-step path (`bpmf_syrk.py`) makes the gathered
(R, W, K) factor block round-trip through HBM (gather write + kernel read).
This kernel gathers the rows itself:

  * V stays in HBM/ANY space; rows are gathered *inside* the kernel with
    double-buffered per-row DMA into a (2, BR, BW, L) VMEM scratch — the
    W axis is tiled, and tile t+1's row DMAs are issued before tile t is
    consumed, so the gather streams HBM exactly once. Mosaic slices an
    HBM row only at full lane tiles, so the wrapper pads V's K axis to a
    multiple of 128 lanes (L); the kernel computes on the first K lanes.
    The row indices arrive through SMEM, where scalar reads are legal.
  * Per row, the masked outer-product sum is one 2-D MXU product
    (BW, K)^T (BW, K) with fp32 accumulation. With ``bf16`` the gathered
    rows are rounded to bf16 before the product (fp32 accumulate); the
    gather itself stays fp32, because a bf16 HBM row is not a whole tile.
  * Row-level statistics leave through VMEM output blocks, one (BR, K, K)
    block per grid step. The segment reduction is XLA's sorted
    `segment_sum` in the wrapper (`kernels.ops.gather_syrk_seg`), skipped
    for the common identity-segment bucket where it is a no-op.

A leading stacked-draw axis (V of shape (S, N, L), e.g. the serving
fold-in's S retained draws) becomes the slow grid dimension: the same plan
block is swept against every draw's factors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _gather_syrk_kernel(
    idx_ref,                      # SMEM (BR, W) int32 row block
    val_ref, msk_ref,             # VMEM (BR, W) row blocks
    v_ref,                        # ANY: (N, L) or (S, N, L) — gathered in-kernel
    prec_ref, rhs_ref,            # VMEM out blocks: (BR, K, K), (BR, K)
    gather_buf,                   # VMEM scratch: (2, BR, BW, L)
    dma_sem,                      # DMA semaphores: (2,)
    *, width: int, block_w: int, k: int, stacked: bool, bf16: bool,
):
    s = pl.program_id(0) if stacked else None
    br = val_ref.shape[0]
    n_wt = width // block_w

    def row_dma(slot, wt, t):
        """Async copy of one gathered V row into the tile's scratch slot."""
        r = t // block_w
        w = t % block_w
        j = idx_ref[r, wt * block_w + w]
        src = (v_ref.at[s, pl.ds(j, 1), :] if stacked
               else v_ref.at[pl.ds(j, 1), :])
        return pltpu.make_async_copy(
            src, gather_buf.at[slot, r, pl.ds(w, 1), :], dma_sem.at[slot]
        )

    def tile_start(slot, wt):
        jax.lax.fori_loop(
            0, br * block_w, lambda t, c: (row_dma(slot, wt, t).start(), c)[1], 0
        )

    def tile_wait(slot, wt):
        jax.lax.fori_loop(
            0, br * block_w, lambda t, c: (row_dma(slot, wt, t).wait(), c)[1], 0
        )

    # double-buffered W tiles: issue tile t+1's row DMAs before consuming t
    tile_start(0, 0)
    acc_p = [jnp.zeros((k, k), jnp.float32) for _ in range(br)]
    acc_r = [jnp.zeros((1, k), jnp.float32) for _ in range(br)]
    for wt in range(n_wt):  # static unroll: width // block_w is small
        if wt + 1 < n_wt:
            tile_start((wt + 1) % 2, wt + 1)
        tile_wait(wt % 2, wt)
        cols = slice(wt * block_w, (wt + 1) * block_w)
        m = msk_ref[:, cols]                                   # (BR, BW)
        rv = val_ref[:, cols] * m
        m_t = m.T                                              # (BW, BR)
        for r in range(br):  # one 2-D MXU product per row of the block
            g = gather_buf[wt % 2, r][:, :k]                   # (BW, K) f32
            if bf16:
                g = g.astype(jnp.bfloat16)
            gm = g * m_t[:, r:r + 1].astype(g.dtype)
            acc_p[r] += jax.lax.dot_general(
                gm, g, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_r[r] += jax.lax.dot_general(
                rv[r:r + 1], g.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
    for r in range(br):
        prec_ref[r] = acc_p[r]
        rhs_ref[r:r + 1, :] = acc_r[r]


@functools.partial(
    jax.jit, static_argnames=("k", "block_rows", "block_w", "bf16", "interpret"),
)
def gather_syrk_pallas(
    indices: jax.Array,   # (R, W) int32 — rows of v to gather
    values: jax.Array,    # (R, W) f32
    mask: jax.Array,      # (R, W) f32 (0/1)
    v: jax.Array,         # (N, L) or (S, N, L) f32, L a multiple of 128
    *,
    k: int,               # the real factor rank: v[..., :k] is used
    block_rows: int = 8,
    block_w: int = 128,
    bf16: bool = False,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused gather→syrk. Returns per-ROW statistics

        prec (..., R, K, K), rhs (..., R, K)

    with a leading draw axis iff ``v`` carried one. Callers pad and reduce
    through `kernels.ops.gather_syrk_seg`.
    """
    r, w = indices.shape
    stacked = v.ndim == 3
    lanes = v.shape[-1]
    assert r % block_rows == 0 and w % block_w == 0, (r, w, block_rows, block_w)
    assert lanes % LANES == 0 and k <= lanes, (lanes, k)
    kernel = functools.partial(
        _gather_syrk_kernel, width=w, block_w=block_w, k=k,
        stacked=stacked, bf16=bf16,
    )
    n_blocks = r // block_rows
    if stacked:
        n_draws = v.shape[0]
        grid = (n_draws, n_blocks)
        row_block = lambda s, i: (i, 0)  # noqa: E731
        out_specs = [
            pl.BlockSpec((None, block_rows, k, k), lambda s, i: (s, i, 0, 0)),
            pl.BlockSpec((None, block_rows, k), lambda s, i: (s, i, 0)),
        ]
        lead = (n_draws,)
    else:
        grid = (n_blocks,)
        row_block = lambda i: (i, 0)  # noqa: E731
        out_specs = [
            pl.BlockSpec((block_rows, k, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
        ]
        lead = ()
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, w), row_block, memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, w), row_block),
            pl.BlockSpec((block_rows, w), row_block),
            pl.BlockSpec(memory_space=pl.ANY),   # v: gathered in-kernel
        ],
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct(lead + (r, k, k), jnp.float32),
            jax.ShapeDtypeStruct(lead + (r, k), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, block_rows, block_w, lanes), v.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(indices, values, mask, v)
