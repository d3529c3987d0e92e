"""Paper Fig 4: single-node BPMF throughput (updates to U and V per second).

The paper compares TBB / OpenMP / ExaSHARK / GraphLab on 12 cores. On one
CPU device the corresponding axis is the *update engine*:

  naive     per-item python-loop Cholesky updates (the "35 lines of C++"
            baseline before any optimization)
  bucketed  degree-bucketed batched syrk + batched Cholesky (our TPU-style
            engine — the work-stealing analogue)
  kernel    same, routed through the Pallas kernels in interpret mode
            (correctness path; interpret mode is not a speed claim)

Also reports the plan's padding efficiency (= the static load balance the
paper achieves dynamically) and the Fig 2-style degree histogram.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import csv_row, time_fn
from repro.core import GibbsSampler
from repro.core.gibbs import update_factors
from repro.data import chembl_like, train_test_split


def naive_update(key, v, indptr, indices, values, hyper, alpha):
    """Per-item loop — the unoptimized reference engine."""
    m = len(indptr) - 1
    k = v.shape[1]
    out = np.zeros((m, k), np.float32)
    vn = np.asarray(v)
    lam = np.asarray(hyper.lam)
    mu = np.asarray(hyper.mu)
    rng = np.random.default_rng(0)
    for i in range(m):
        sl = slice(indptr[i], indptr[i + 1])
        vj = vn[indices[sl]]
        prec = lam + alpha * vj.T @ vj
        rhs = lam @ mu + alpha * vj.T @ values[sl]
        l = np.linalg.cholesky(prec)
        mean = np.linalg.solve(prec, rhs)
        out[i] = mean + np.linalg.solve(l.T, rng.normal(size=k))
    return out


def main(smoke: bool = False) -> list[str]:
    rows = []
    ratings, _, _ = chembl_like(scale=0.004, seed=0)
    train, _ = train_test_split(ratings, 0.05, seed=1)
    k = 32

    deg = train.degrees(0)
    hist, edges = np.histogram(deg[deg > 0], bins=[1, 2, 4, 8, 16, 32, 64, 128, 1024])
    print("# Fig2-style degree histogram (ChEMBL-like):",
          dict(zip(edges[:-1].tolist(), hist.tolist())))

    # balanced planner (the work-stealing analogue): widths fit to the
    # degree profile, per entity set
    s = GibbsSampler(train, None, k=k, alpha=1.5, widths="balanced")
    print("# plan:", s.user_plan_host.stats())
    state = s.init(0)
    n_items = s.m + s.n

    # bucketed engine (jit, jnp path)
    t = time_fn(s.sweep, state, warmup=1, iters=1 if smoke else 3)
    rows.append(csv_row("fig4_bucketed_updates_per_s", t * 1e6, f"{n_items / t:.0f}"))

    if not smoke:
        # kernel path (interpret mode — correctness, not speed)
        sk = GibbsSampler(train, None, k=k, alpha=1.5, widths="balanced",
                          use_kernel=True)
        t_k = time_fn(sk.sweep, sk.init(0), warmup=1, iters=1)
        rows.append(csv_row("fig4_kernel_interpret_updates_per_s", t_k * 1e6, f"{n_items / t_k:.0f}"))

        # naive python engine on a subsample (extrapolated)
        sub = 200
        from repro.data.sparse import csr_from_coo
        c = train.centered()
        indptr, indices, values = csr_from_coo(c.rows, c.cols, c.vals, s.m)
        import time as _t
        t0 = _t.perf_counter()
        naive_update(None, np.asarray(state.v), indptr[: sub + 1], indices, values,
                     state.hyper_u, 1.5)
        t_n = (_t.perf_counter() - t0) * (s.m / sub) * 2  # both U and V sweeps
        rows.append(csv_row("fig4_naive_updates_per_s", t_n * 1e6, f"{n_items / t_n:.0f}"))

    eff = s.user_plan_host.padding_efficiency
    rows.append(csv_row("fig4_plan_padding_efficiency", 0.0, f"{eff:.3f}"))
    # the load-balance gate this figure now reports against: the balanced
    # planner must clear 0.7 on the chembl-like profile (the pow2 ladder
    # sat at 0.290)
    assert eff > 0.7, f"balanced plan padding_efficiency {eff:.3f} <= 0.7"

    # Fig 3-style study: bucket-width ladders trade MXU lane fill against
    # per-bucket launch count (the paper's rank-one-vs-Cholesky threshold,
    # restated as a static planning knob). "balanced" = the degree-fit DP.
    from repro.core.buckets import plan_buckets
    from repro.data.sparse import csr_from_coo

    c = train.centered()
    indptr, indices, values = csr_from_coo(c.rows, c.cols, c.vals, s.m)
    for widths in ("balanced", (4, 16, 64), (8, 32, 128, 512), (16, 128),
                   (32,), (256,)):
        p = plan_buckets(indptr, indices, values, s.m, s.n, widths)
        tag = widths if isinstance(widths, str) else "x".join(map(str, widths))
        rows.append(csv_row(
            f"fig4_widths_{tag}", 0.0,
            f"lane_eff={p.padding_efficiency:.3f};rows={sum(b.rows for b in p.buckets)}",
        ))
    return rows


if __name__ == "__main__":
    for r in main():
        print(r)
