"""Pallas TPU kernel: masked batched syrk for the BPMF precision matrices.

The hot loop of the BPMF item update is, per bucket row,

    prec_r = sum_w vm[r, w, :] vm[r, w, :]^T        (K x K outer-product sum)
    rhs_r  = sum_w rv[r, w] * vm[r, w, :]

i.e. a batch of (W x K)^T (W x K) products — exactly the MXU's shape. The
kernel tiles rows into VMEM blocks and (for wide buckets) blocks the W axis
with in-VMEM accumulation, so the gathered factor block streams HBM->VMEM
once. K is padded to the 64/128 lane width by the caller (ops.py).

Grid: (rows / BR, W / BW); the W axis is the fastest-varying (sequential on
TPU), so output tiles accumulate in place across W steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _syrk_kernel(vm_ref, rv_ref, prec_ref, rhs_ref):
    j = pl.program_id(1)
    br = vm_ref.shape[0]
    rv = rv_ref[...]                     # (BR, BW)
    prec, rhs = [], []
    for r in range(br):  # one 2-D MXU product per row of the block
        vm = vm_ref[r]                   # (BW, K)
        prec.append(jax.lax.dot_general(
            vm, vm, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ))
        rhs.append(jax.lax.dot_general(
            rv[r:r + 1].astype(vm.dtype), vm, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ))

    @pl.when(j == 0)
    def _init():
        for r in range(br):
            prec_ref[r] = prec[r]
            rhs_ref[r:r + 1, :] = rhs[r]

    @pl.when(j > 0)
    def _acc():
        for r in range(br):
            prec_ref[r] += prec[r]
            rhs_ref[r:r + 1, :] += rhs[r]


@functools.partial(jax.jit, static_argnames=("block_rows", "block_w", "interpret"))
def masked_syrk_pallas(
    vm: jax.Array,
    rv: jax.Array,
    *,
    block_rows: int = 8,
    block_w: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """vm: (R, W, K) f32, rv: (R, W) f32 -> (prec (R,K,K), rhs (R,K)).

    R must divide by block_rows and W by block_w (ops.py pads).
    """
    r, w, k = vm.shape
    assert r % block_rows == 0 and w % block_w == 0, (r, w, block_rows, block_w)
    grid = (r // block_rows, w // block_w)
    return pl.pallas_call(
        _syrk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, block_w, k), lambda i, j: (i, j, 0)),
            pl.BlockSpec((block_rows, block_w), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, k, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((block_rows, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, k, k), jnp.float32),
            jax.ShapeDtypeStruct((r, k), jnp.float32),
        ],
        interpret=interpret,
    )(vm, rv)
