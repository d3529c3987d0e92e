"""Public jit'd wrappers around the Pallas kernels.

Handles shape padding to kernel tile multiples and selects interpret mode on
non-TPU backends. On a TPU backend every kernel is compiled by Mosaic and
none runs interpreted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bpmf_syrk import masked_syrk_pallas
from repro.kernels.chol_solve import chol_solve_sample_pallas
from repro.kernels.flash_attention import flash_attention_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _block_w_for(w: int) -> int:
    """W-tile size for a bucket of width w: 8-lane aligned (the balanced
    planner emits non-pow2 widths; the kernels always see lane-aligned
    tiles — the pad columns carry mask 0 and contribute exact zeros)."""
    return min(128, max(8, -(-w // 8) * 8))


def masked_syrk(vm: jax.Array, rv: jax.Array, *, interpret: bool | None = None):
    """(..., R, W, K) x (..., R, W) -> (prec (...,R,K,K), rhs (...,R,K)).

    Pads W/R/K to tiles. Extra leading axes (e.g. the fold-in's stacked-draw
    axis S) are flattened into the row axis — every row is independent, so
    the kernel sees one (S*R, W, K) launch instead of S separate ones.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    if vm.ndim > 3:
        lead = vm.shape[:-2]
        prec, rhs = masked_syrk(
            vm.reshape((-1,) + vm.shape[-2:]), rv.reshape((-1, rv.shape[-1])),
            interpret=interpret,
        )
        return (prec.reshape(lead + prec.shape[1:]),
                rhs.reshape(lead + rhs.shape[1:]))
    r, w, k = vm.shape
    block_rows = 8
    block_w = _block_w_for(w)
    vm_p = _pad_to(_pad_to(_pad_to(vm, 0, block_rows), 1, block_w), 2, 8)
    rv_p = _pad_to(_pad_to(rv, 0, block_rows), 1, block_w)
    prec, rhs = masked_syrk_pallas(
        vm_p, rv_p, block_rows=block_rows, block_w=block_w, interpret=interpret
    )
    return prec[:r, :k, :k], rhs[:r, :k]


def chol_solve_sample(prec: jax.Array, rhs: jax.Array, z: jax.Array,
                      *, interpret: bool | None = None):
    """Batched x = L^-T (L^-1 rhs + z) with Lambda = L L^T, through the
    batch-on-lanes Pallas kernel (`kernels.chol_solve`).

    Any leading axes — (B,), or the fold-in's stacked (S, B) — are flattened
    into one kernel batch: an (S, B, K, K) precision stack becomes a single
    (S*B) launch. A batch that does not fill the kernel's last tile is
    padded inside the kernel with identity systems and zero right-hand
    sides, so no padded copy is made in HBM. The K axis is NOT padded (a
    zero-padded precision matrix is singular); BPMF uses K=64.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    k = prec.shape[-1]
    out = chol_solve_sample_pallas(
        prec.reshape(-1, k, k), rhs.reshape(-1, k), z.reshape(-1, k),
        interpret=interpret,
    )
    return out.reshape(rhs.shape)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool = True, window: int = 0, softcap: float = 0.0,
    scale: float | None = None, interpret: bool | None = None,
):
    """(BH, S, D) flash attention; pads S to tile multiples, masks the pad."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = min(128, max(16, sq))
    bk = min(128, max(16, sk))
    q_p = _pad_to(q, 1, bq)
    k_p = _pad_to(k, 1, bk)
    v_p = _pad_to(v, 1, bk)
    # padded KV columns are masked inside the kernel only by causal/window;
    # rely on causal (qpos < padded kpos) for the tail. For non-causal use,
    # pad K with -inf-producing zeros is insufficient -> explicitly guard:
    if not causal and k_p.shape[1] != sk:
        raise ValueError("non-causal flash path requires S_k % block == 0")
    out = flash_attention_pallas(
        q_p, k_p, v_p, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=bq, block_k=bk, interpret=interpret,
    )
    return out[:, :sq]


def topn_scores(u: jax.Array, v: jax.Array, topk: int,
                *, interpret: bool | None = None):
    """Batched top-k of U @ V^T without materialising the (B, N) score matrix.

    u: (B, K) user factors, v: (N, K) item factors -> (values (B, topk),
    indices (B, topk)). Pads B/N to tile multiples; padded items are masked
    to -inf inside the kernel so they are never recommended. Matches
    `jax.lax.top_k` over the full score row bit-for-bit (stable ties) when
    B is a tile multiple; a padded batch can flip last-bit score rounding
    (XLA picks a different gemm micro-kernel per M) but never the selection.
    """
    from repro.kernels.bpmf_topn import topn_scores_pallas

    interpret = (not _on_tpu()) if interpret is None else interpret
    b, k = u.shape
    n = v.shape[0]
    if not 0 < topk <= n:
        raise ValueError(f"topk must be in [1, {n}], got {topk}")
    block_b = 8
    block_n = 128
    while block_n < topk:
        block_n *= 2
    u_p = _pad_to(u, 0, block_b)
    v_p = _pad_to(v, 0, block_n)
    vals, idx = topn_scores_pallas(
        u_p, v_p, topk=topk, n_valid=n,
        block_b=block_b, block_n=block_n, interpret=interpret,
    )
    return vals[:b], idx[:b]


def _gather_syrk_rows_jnp(indices, values, mask, v, *, bf16_gather):
    """Row-level (prec, rhs) on the jnp path (the off-TPU engine).

    Same contraction order as the kernel: gather → masked MXU-style
    dot_general with fp32 accumulation.
    """
    if bf16_gather:
        v = v.astype(jnp.bfloat16)
    g = v[:, indices] if v.ndim == 3 else v[indices]   # (..., R, W, K)
    gm = g * mask[..., None].astype(g.dtype)
    rv = values * mask
    nb = g.ndim - 2                                    # batch dims: (...,) + R
    batch = tuple(range(nb))
    prec_rows = jax.lax.dot_general(
        gm, g, (((nb,), (nb,)), (batch, batch)),
        preferred_element_type=jnp.float32,
    )
    rhs_rows = jax.lax.dot_general(
        gm.astype(jnp.float32),
        jnp.broadcast_to(rv, gm.shape[:-1])[..., None],
        (((nb,), (nb,)), (batch, batch)),
        preferred_element_type=jnp.float32,
    )[..., 0]
    return prec_rows, rhs_rows


def _gather_syrk_rows_pallas(indices, values, mask, v, *, bf16_gather,
                             interpret):
    """Row-level (prec, rhs) from the Pallas kernel, padded to its tiles."""
    from repro.kernels.bpmf_gather_syrk import LANES, gather_syrk_pallas

    r, w = indices.shape
    block_rows = 8
    block_w = _block_w_for(w)
    # pad rows/columns carry mask 0 and contribute exact zeros
    indices = _pad_to(_pad_to(indices, 0, block_rows), 1, block_w)
    values = _pad_to(_pad_to(values, 0, block_rows), 1, block_w)
    mask = _pad_to(_pad_to(mask, 0, block_rows), 1, block_w)
    # an HBM row is DMA-able only as whole 128-lane tiles
    k = v.shape[-1]
    v = _pad_to(v.astype(jnp.float32), v.ndim - 1, LANES)
    prec_rows, rhs_rows = gather_syrk_pallas(
        indices, values, mask, v, k=k, block_rows=block_rows,
        block_w=block_w, bf16=bf16_gather, interpret=interpret,
    )
    return prec_rows[..., :r, :, :], rhs_rows[..., :r, :]


def gather_syrk_seg(
    indices: jax.Array,    # (R, W) int32
    values: jax.Array,     # (R, W) f32
    mask: jax.Array,       # (R, W) f32
    seg_ids: jax.Array,    # (R,) int32 — NONDECREASING dense 0..n_segments-1
    n_segments: int,
    v: jax.Array,          # (N, K) counterpart factors, or (S, N, K) stacked
    *,
    bf16_gather: bool = False,
    identity_segments: bool = False,
    interpret: bool | None = None,
):
    """Fused gather→syrk→segment-reduce: per-SEGMENT (prec, rhs) directly.

    The sweep's fused engine. On TPU the row-level statistics come from the
    Pallas kernel (V gathered row by row from ANY space inside the kernel,
    so the gathered (R, W, K) block never touches HBM); elsewhere from a
    jnp path with identical contraction order. Pass ``interpret=True`` to
    force the real kernel in interpret mode (the equivalence tests);
    None/False off-TPU both mean the jnp path — a compiled Mosaic kernel
    does not exist there. Either way XLA's sorted segment reduction follows
    (skipped when every row is its own segment — the common narrow-bucket
    case). Rows must be segment-sorted — the bucket/grid planner invariant;
    `bf16_gather` rounds the gathered factors to bf16 and keeps fp32
    accumulation (tolerance documented in docs/architecture.md).

    Returns prec (..., n_segments, K, K), rhs (..., n_segments, K), with the
    leading stacked-draw axis present iff ``v`` carried one.
    """
    # one shared definition of the segment reduction (lazy import: gibbs
    # imports this module lazily too, so neither import is circular)
    from repro.core.gibbs import segment_reduce_rows

    if interpret is True or _on_tpu():
        rows = _gather_syrk_rows_pallas(indices, values, mask, v,
                                        bf16_gather=bf16_gather,
                                        interpret=bool(interpret))
    else:
        rows = _gather_syrk_rows_jnp(indices, values, mask, v,
                                     bf16_gather=bf16_gather)
    return tuple(
        segment_reduce_rows(x, seg_ids, n_segments, stacked=v.ndim == 3,
                            identity=identity_segments)
        for x in rows
    )


def gather_syrk(indices: jax.Array, values: jax.Array, mask: jax.Array,
                v: jax.Array, *, interpret: bool | None = None):
    """Row-level fused gather+syrk (no segment reduction): each row is its
    own segment. Kept for callers that need per-row statistics; the sweep
    engines use `gather_syrk_seg`.
    """
    r = indices.shape[0]
    seg = jnp.arange(r, dtype=jnp.int32)
    return gather_syrk_seg(
        indices, values, mask, seg, r, v,
        identity_segments=True, interpret=interpret,
    )
