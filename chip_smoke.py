#!/usr/bin/env python3
"""Smoke run of BPMF train -> retain -> serve on a TPU, at full ChEMBL width.

    python chip_smoke.py             # one chip: the main path
    python chip_smoke.py --chips 4   # four chips: the multi-chip paths only

Data is ChEMBL-shaped (483,500 compounds x 5,775 targets, ~1.02M ratings),
generated from a fixed seed; the rank is K=64. On one chip:

  1. `GibbsSampler(engine="fused")` runs 4 sweeps (burn-in 2) and retains
     the post-burn-in draws into a `SampleStore` under artifacts/chip_smoke/;
  2. one `engine="einsum"` sweep from the same state is compared with the
     fused sweep;
  3. a `RecommendFrontend` over that store serves warm users top-10 with
     seen-item exclusion, checked against `lax.top_k` over dense scores;
  4. one cold-start user is served through the fold-in, checked against a
     dense float64 posterior; the fused (kernel) fold-in is checked too.

With --chips 4 it runs only `DistributedBPMF` in ring, allgather and async
modes on a 4-device mesh, and the 4-host `ClusterCoordinator` against the
single-host `TopNRecommender`. The distributed sweep solves its systems as
the single-chip sweep does (`gibbs._sweep_solver`: the batch-on-lanes Pallas
kernel on a TPU), so async's first-sweep v must still be bit-equal to
ring's, and ring and allgather must agree within their tolerance.

The lowered sweep and top-N step must contain `tpu_custom_call` (no kernel
runs interpreted). Any mismatch, or a first device that is not a TPU, exits
non-zero. The last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "artifacts" / "chip_smoke"
SEED = 0
SCALE = 1.0     # chembl_like scale: the dataset's full size
K = 64
ALPHA = 4.0          # the launchers' observation precision
TOPK = 10
# Agreement bound between two fp32 implementations of the same quantity
# (kernel vs XLA, or vs a float64 reference): fp32 rounding through a K=64
# Cholesky, far below the O(1) error of a wrong row or a wrong item.
TOL = 2e-2

sys.path.insert(0, str(ROOT / "src"))


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    log(f"ok: {what}")


def timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def load_data():
    from repro.data import chembl_like, train_test_split

    t0 = time.perf_counter()
    ratings, _, _ = chembl_like(scale=SCALE, seed=SEED)
    train, test = train_test_split(ratings, 0.1, seed=SEED + 1)
    log(f"data: {train.shape[0]} x {train.shape[1]}, {train.nnz} train / "
        f"{test.nnz} test ratings ({time.perf_counter() - t0:.1f} s)")
    return train, test


def seen_lists(train):
    """Per-user rated items (CSR over users)."""
    import numpy as np

    from repro.data.sparse import csr_from_coo

    ptr, idx, vals = csr_from_coo(train.rows, train.cols, train.vals,
                                  train.shape[0])
    return ptr, idx, vals, np.diff(ptr)


def compare_topn(name, vals, items, ref_scores, excluded):
    """Served top-N vs lax.top_k over dense reference scores (-inf where
    excluded): the same ranked values, and every served item a legal pick
    whose reference score is its served score."""
    import jax
    import numpy as np

    ref_v, ref_i = jax.lax.top_k(ref_scores, TOPK)
    ref_v, ref_i, scores = map(np.asarray, (ref_v, ref_i, ref_scores))
    live = np.isfinite(ref_v)          # rows with fewer legal items pad out
    check(bool((items[~live] == -1).all()), f"{name}: padding where no item is left")
    rows = np.arange(items.shape[0])[:, None]
    served_ref = scores[rows, items][live]
    dv = float(np.abs(vals[live] - ref_v[live]).max())
    ds = float(np.abs(vals[live] - served_ref).max())
    same = float((items == ref_i)[live].mean())
    log(f"{name}: |served - lax.top_k| max {dv:.3g}, |served - ref score of "
        f"served item| max {ds:.3g}, identical items {same:.3f}")
    check(not any(np.isin(items[r], excluded[r]).any()
                  for r in range(items.shape[0])),
          f"{name}: no excluded item served")
    check(dv <= TOL and ds <= TOL, f"{name}: top-{TOPK} matches lax.top_k")


def one_chip() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import SampleStore
    from repro.core import GibbsSampler
    from repro.data.sparse import SparseRatings
    from repro.kernels import ops
    from repro.serve import RecommendFrontend, fold_in

    train, test = load_data()
    shutil.rmtree(OUT, ignore_errors=True)
    root = OUT / "samples"

    # --- train: fused engine, 4 sweeps with burn-in 2, draws retained ---
    t0 = time.perf_counter()
    sampler = GibbsSampler(train, test, k=K, alpha=ALPHA, burn_in=2,
                           engine="fused")
    log(f"plan: {time.perf_counter() - t0:.1f} s; user buckets "
        f"{[(b.width, b.indices.shape[0]) for b in sampler.user_buckets]}")
    state0 = sampler.init(SEED)
    check("tpu_custom_call" in sampler._sweep.lower(state0, *sampler._plan_args).as_text(),
          "lowered fused sweep contains tpu_custom_call")
    store = SampleStore(root, keep=4)
    t0 = time.perf_counter()
    state = jax.block_until_ready(sampler.run(4, seed=SEED, store=store))
    t_run = time.perf_counter() - t0
    fused_next, t_fused = timed(lambda: sampler.sweep(state))
    log(f"fused: 4 sweeps incl. compile and retain {t_run:.2f} s, steady "
        f"sweep {t_fused:.3f} s, sample rmse {sampler.sample_rmse(state):.4f}")
    check(len(store.steps()) == 2, "2 post-burn-in draws retained")

    # --- one einsum sweep from the same state, vs the fused sweep ---
    einsum = GibbsSampler(train, test, k=K, alpha=ALPHA, burn_in=2,
                          engine="einsum")
    ein_next, t_ein_first = timed(lambda: einsum.sweep(state))
    _, t_ein = timed(lambda: einsum.sweep(state))
    log(f"einsum: first sweep incl. compile {t_ein_first:.2f} s, steady "
        f"sweep {t_ein:.3f} s")
    for name in ("u", "v"):
        a = np.asarray(getattr(fused_next, name))
        b = np.asarray(getattr(ein_next, name))
        d = float(np.abs(a - b).max())
        log(f"fused vs einsum {name}: max |diff| {d:.3g} (max |{name}| "
            f"{float(np.abs(b).max()):.3g})")
        check(np.isfinite(a).all() and d <= TOL,
              f"fused sweep {name} matches einsum sweep")
    del einsum, fused_next, ein_next

    # --- serve: frontend over the retained draws ---
    ptr, idx, vals, deg = seen_lists(train)
    fe = RecommendFrontend(root, seen=train, max_batch=16)
    ens = fe.ensemble
    u_flat, v_flat = ens.scoring_matrices()
    step = jax.jit(lambda rows, v: ops.topn_scores(rows, v, TOPK))
    check("tpu_custom_call" in step.lower(u_flat[:16], v_flat).as_text(),
          "lowered top-N step contains tpu_custom_call")

    rng = np.random.default_rng(SEED)
    rated = np.flatnonzero(deg > 0)
    users = np.concatenate([[int(np.argmax(deg))],
                            rng.choice(rated, 15, replace=False)])

    def serve_warm():
        for u in users:
            fe.submit(int(u), topk=TOPK)
        res = sorted(fe.flush(), key=lambda r: r.ticket)
        return (np.stack([r.scores for r in res]),
                np.stack([r.items for r in res]))

    (w_vals, w_items), t_w_first = timed(serve_warm)
    _, t_w = timed(serve_warm)
    log(f"warm top-{TOPK}, batch of {len(users)} (max degree "
        f"{int(deg.max())}): first {t_w_first:.2f} s, steady {t_w:.4f} s")
    excluded = [idx[ptr[u]:ptr[u + 1]] for u in users]
    ref = jnp.dot(u_flat[users], v_flat.T,
                  precision=jax.lax.Precision.HIGHEST) + ens.global_mean
    mask = np.zeros(ref.shape, bool)
    for r, e in enumerate(excluded):
        mask[r, e] = True
    compare_topn("warm", w_vals, w_items,
                 jnp.where(jnp.asarray(mask), -jnp.inf, ref), excluded)

    # --- cold start: a trained user's ratings, folded in as a new user ---
    cu = int(np.argmin(np.abs(deg - 30)))   # a user with ~30 ratings
    c_items, c_vals = idx[ptr[cu]:ptr[cu + 1]], vals[ptr[cu]:ptr[cu + 1]]

    def serve_cold():
        fe.submit_ratings(c_items, c_vals, topk=TOPK)
        (res,) = fe.flush()
        return res.scores[None], res.items[None]

    (c_sc, c_it), t_c_first = timed(serve_cold)
    _, t_c = timed(serve_cold)
    log(f"cold-start ({len(c_items)} ratings): first {t_c_first:.2f} s, "
        f"steady {t_c:.4f} s")
    # dense float64 conditional posterior means, one per retained draw
    v_all = np.asarray(ens.v, np.float64)
    r_c = c_vals.astype(np.float64) - ens.global_mean
    u_ref = []
    for s, smp in enumerate(ens.samples):
        vi = v_all[s, c_items]
        lam = np.asarray(smp.hyper_u_lam, np.float64)
        prec = lam + ens.alpha * vi.T @ vi
        rhs = lam @ np.asarray(smp.hyper_u_mu, np.float64) + ens.alpha * vi.T @ r_c
        u_ref.append(np.linalg.solve(prec, rhs))
    u_ref = np.stack(u_ref)                                   # (S, K)
    c_ref = np.einsum("sk,snk->n", u_ref, v_all) / len(u_ref) + ens.global_mean
    c_ref[c_items] = -np.inf
    compare_topn("cold-start", c_sc, c_it,
                 jnp.asarray(c_ref[None], jnp.float32), [c_items])
    cold = SparseRatings(rows=np.zeros(len(c_items), np.int32), cols=c_items,
                         vals=c_vals, shape=(1, ens.n_items))
    u_fused = np.asarray(fold_in(None, cold, ens, sample=False,
                                 engine="fused"))[:, 0]
    d = float(np.abs(u_fused - u_ref).max())
    log(f"fused fold-in vs float64 posterior mean: max |diff| {d:.3g}")
    check(d <= TOL, "fused (stacked-draw kernel) fold-in matches reference")
    fe.close()


def four_chips(devs) -> None:
    import jax
    import numpy as np

    from repro.checkpoint import SampleStore
    from repro.core.distributed import AXIS, DistributedBPMF
    from repro.launch.serve import run_cluster

    train, test = load_data()
    shutil.rmtree(OUT, ignore_errors=True)
    mesh = jax.make_mesh((4,), (AXIS,), devices=devs[:4])
    first = {}
    for mode in ("ring", "allgather", "async"):
        t0 = time.perf_counter()
        d = DistributedBPMF(train, test, k=K, alpha=ALPHA, mode=mode,
                            width="auto", mesh=mesh)
        t_plan = time.perf_counter() - t0
        st0 = d.init(SEED)
        s1, t_first = timed(lambda: d.sweep(st0))
        s2, t_steady = timed(lambda: d.sweep(s1))
        for name in ("u", "v"):
            devices = [{sh.device for sh in getattr(st, name).addressable_shards}
                       for st in (st0, s1)]
            check(all(len(d) == 4 for d in devices),
                  f"{mode}: {name} shards on 4 distinct devices")
        log(f"{mode}: plan {t_plan:.1f} s, first sweep incl. compile "
            f"{t_first:.2f} s, steady sweep {t_steady:.3f} s, rmse "
            f"{d.rmse(s2):.4f}")
        first[mode] = (d, s1, s2)

    ring, r1, r2 = first["ring"]
    allg, a1, _ = first["allgather"]
    asyn, y1, _ = first["async"]
    for (x, y), name in zip(zip(ring.gather_factors(r1),
                                allg.gather_factors(a1)), ("u", "v")):
        d = float(np.abs(x - y).max())
        log(f"ring vs allgather first-sweep {name}: max |diff| {d:.3g}")
        check(d <= 2e-3, f"ring and allgather agree on {name}")
    _, v_ring = ring.gather_factors(r1)
    _, v_async = asyn.gather_factors(y1, coupled=False)
    check(np.array_equal(v_ring, v_async),
          "async first-sweep v bit-equal to ring's")

    # retain the ring chain's two draws, then serve them through the tier
    store = SampleStore(OUT / "samples", keep=4)
    for s in (r1, r2):
        u, v = ring.gather_factors(s)
        store.retain(int(s.step), {
            "u": u, "v": v,
            "hyper_u_mu": np.asarray(s.hyper_u.mu),
            "hyper_u_lam": np.asarray(s.hyper_u.lam),
            "hyper_v_mu": np.asarray(s.hyper_v.mu),
            "hyper_v_lam": np.asarray(s.hyper_v.lam),
            "global_mean": np.float32(ring.global_mean),
            "alpha": np.float32(ALPHA),
        })
    store.wait()
    del first, ring, allg, asyn
    m = run_cluster(hosts=4, samples=str(OUT / "samples"), requests=256,
                    topk=TOPK, max_batch=8, publishes=2, seed=SEED)
    check(m["bit_identical"], "4-host tier bit-identical to single host")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: the main path on one chip; 4: the distributed "
                         "sampler and the multi-host serving tier only")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found {dev.platform}")
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but only "
                         f"{len(devs)} device(s)")
    log(f"device: {dev.device_kind} x {len(devs)}; compile cache "
        f"{enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(devs)
    else:
        one_chip()
    stats = dev.memory_stats() or {}
    log(f"total {time.perf_counter() - t0:.1f} s; peak HBM in use "
        f"{stats.get('peak_bytes_in_use', 'not reported')} bytes")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
