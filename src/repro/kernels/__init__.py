"""Pallas TPU kernels for the BPMF hot spots.

The paper optimizes the per-item update (outer-product accumulation + a
Cholesky-based solve, Sec 3.1); these are the corresponding TPU kernels:

  bpmf_syrk.py        masked batched syrk (precision-matrix accumulation)
  bpmf_gather_syrk.py fused gather+syrk — V stays in HBM, gathered in-kernel
                      (halves the update sweep's dominant traffic)
  chol_solve.py       fused batched Cholesky factor + solve + sample
  bpmf_topn.py        tiled U @ V^T scoring + streaming top-k (BPMF serving)

Each kernel ships three layers:
  <name>.py  -- pl.pallas_call + explicit BlockSpec VMEM tiling
  ops.py     -- jit'd public wrapper (padding, backend dispatch)
  ref.py     -- pure-jnp oracle used by the allclose test sweeps
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
