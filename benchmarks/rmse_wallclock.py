"""RMSE-vs-wallclock: minibatch SGLD vs exact fused-Gibbs training.

    PYTHONPATH=src python benchmarks/rmse_wallclock.py [--smoke]

The headline evidence for the SGLD engine (core/sgld.py): exact Gibbs
pays O(|ratings| * K^2) per sweep, SGLD pays O(|minibatch| * K) per step,
so as the dataset grows the exact engine's FLOOR cost — the wallclock of
one full sweep, before which it produces nothing at all — moves right
linearly while SGLD's progress rate stays fixed. Three sections, all
written to BENCH_rmse_wallclock.json (curves included) and summarized
into the committed BENCH_history.jsonl by `run.py --smoke`:

  default profile   a synthetic split the model genuinely learns (the
                    chembl_like scales the other suites use for THROUGHPUT
                    don't separate any trainer from the predict-the-mean
                    baseline, which would make accuracy curves vacuous).
                    Gate: SGLD's converged posterior-mean RMSE within
                    ACCURACY_GAP of fused Gibbs' (accuracy parity — the
                    minibatch noise and finite step size cost ~nothing).
  big profile       >=4x the ratings at serving-scale K, where exact
                    sweeps are the bottleneck. Gate: at the equal-wallclock
                    budget T1 = the time fused Gibbs needs to complete its
                    FIRST sweep (the exact engine's floor cost — budgets
                    below it get no exact estimate whatsoever), SGLD's
                    best RMSE is STRICTLY better than Gibbs'. The summary
                    also reports t_cross, the largest budget at which SGLD
                    still leads — the window [0, t_cross] where the
                    minibatch engine dominates, which widens as |ratings|
                    grows. At CPU-smoke scale exact Gibbs wins at large
                    budgets (its per-rating fused kernel is extremely
                    efficient); the decoupling claim is about the floor,
                    not the asymptote.
  flat iterations   fixed (m, n) and minibatch while nnz grows 1x -> 4x:
                    SGLD per-step wallclock must stay flat
                    (< FLAT_RATIO growth) while the Gibbs sweep time is
                    measured alongside to show the O(|ratings|) contrast.

Timing protocol: one throwaway compiled step before each run, then
cumulative wallclock over chain steps only — RMSE evaluation happens off
the clock. Curve points carry the posterior-mean RMSE once the
accumulator has draws (post burn-in), the current-sample RMSE before.
"""
from __future__ import annotations

import argparse
import math
import time

import jax
import numpy as np

try:
    from benchmarks.common import csv_row, time_fn, write_bench_json
except ModuleNotFoundError:  # invoked as a file: python benchmarks/<name>.py
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks.common import csv_row, time_fn, write_bench_json

from repro.core import GibbsSampler, SGLDSampler
from repro.data import synthetic_lowrank, train_test_split

ALPHA = 4.0
ACCURACY_GAP = 0.05    # default profile: sgld within this of fused Gibbs
FLAT_RATIO = 1.35      # flat-iteration gate: t_step(4x nnz) / t_step(1x)


def _rmse(sampler, state) -> float:
    if int(state.pred_count) == 0:     # pre-burn-in: rmse() would return
        return sampler.sample_rmse(state)   # the predict-the-mean baseline
    r = sampler.rmse(state)
    return sampler.sample_rmse(state) if math.isnan(r) else r


def _curve(sampler, n_steps: int, eval_every: int, seed: int = 0):
    """[(cumulative wall seconds, rmse)] with eval off the clock."""
    state = sampler.init(seed)
    jax.block_until_ready(sampler.sweep(state).u)   # compile, excluded
    state = sampler.init(seed)
    t_cum, pts = 0.0, []
    for i in range(n_steps):
        t0 = time.perf_counter()
        state = sampler.sweep(state)
        jax.block_until_ready(state.u)
        t_cum += time.perf_counter() - t0
        if (i + 1) % eval_every == 0 or i == n_steps - 1:
            pts.append((t_cum, _rmse(sampler, state)))
    return pts


def _best_by(pts, budget: float) -> float:
    """Best RMSE achieved within the wallclock budget (inf if none yet)."""
    vals = [r for t, r in pts if t <= budget]
    return min(vals) if vals else float("inf")


def _t_cross(g_pts, s_pts) -> float:
    """Largest budget at which SGLD's best-so-far still beats Gibbs'."""
    budgets = sorted({t for t, _ in g_pts} | {t for t, _ in s_pts})
    lead = [t for t in budgets if _best_by(s_pts, t) < _best_by(g_pts, t)]
    return max(lead) if lead else 0.0


def _profile(tag, shape, *, k, gibbs_sweeps, gibbs_burn, sgld_steps,
             sgld_burn, eval_every, sgld_kwargs):
    m, n, nnz = shape
    ratings, _, _ = synthetic_lowrank(
        m, n, 8, nnz, noise=0.25, popularity_exponent=1.2, seed=0
    )
    train, test = train_test_split(ratings, 0.1, seed=1)
    print(f"# {tag}: m={train.shape[0]} n={train.shape[1]} nnz={train.nnz}"
          f" k={k}")

    g = GibbsSampler(train, test, k=k, alpha=ALPHA, burn_in=gibbs_burn,
                     engine="fused")
    g_pts = _curve(g, gibbs_sweeps, 1)
    s = SGLDSampler(train, test, k=k, alpha=ALPHA, burn_in=sgld_burn,
                    temp_warmup=sgld_burn, hyper_every=5, accum_every=5,
                    **sgld_kwargs)
    s_pts = _curve(s, sgld_steps, eval_every)

    g_total, g_final = g_pts[-1]
    s_total, s_final = s_pts[-1]
    # equal-wallclock budget: the exact engine's floor cost (first sweep)
    t1, g1 = g_pts[0]
    rows = [
        csv_row(f"rw_{tag}_gibbs_fused", g_total * 1e6 / gibbs_sweeps,
                f"final_rmse={g_final:.4f} total_s={g_total:.2f}"),
        csv_row(f"rw_{tag}_sgld", s_total * 1e6 / sgld_steps,
                f"final_rmse={s_final:.4f} total_s={s_total:.2f}"),
        csv_row(f"rw_{tag}_at_first_sweep", t1 * 1e6,
                f"gibbs={g1:.4f} sgld={_best_by(s_pts, t1):.4f} "
                f"t_cross_s={_t_cross(g_pts, s_pts):.2f}"),
    ]
    summary = {
        "gibbs_curve": [[round(t, 4), round(r, 5)] for t, r in g_pts],
        "sgld_curve": [[round(t, 4), round(r, 5)] for t, r in s_pts],
        "gibbs_final": g_final, "sgld_final": s_final,
        "first_sweep_s": t1, "gibbs_first_sweep": g1,
        "sgld_at_first_sweep": _best_by(s_pts, t1),
        "t_cross_s": _t_cross(g_pts, s_pts),
    }
    return rows, summary


def _flat_study(*, m, n, base_nnz, minibatch, iters):
    """Per-step wallclock vs rating count at fixed (m, n, minibatch)."""
    rows, steps = [], {}
    for mult in (1, 2, 4):
        ratings, _, _ = synthetic_lowrank(
            m, n, 8, base_nnz * mult, noise=0.3, seed=0
        )
        s = SGLDSampler(ratings, None, k=16, alpha=ALPHA,
                        minibatch=minibatch)
        t_s = time_fn(s.sweep, s.init(0), warmup=1, iters=iters)
        g = GibbsSampler(ratings, None, k=16, alpha=ALPHA, engine="fused")
        t_g = time_fn(g.sweep, g.init(0), warmup=1, iters=iters)
        steps[mult] = (t_s, t_g)
        rows.append(csv_row(
            f"rw_flat_{mult}x", t_s * 1e6,
            f"nnz={ratings.nnz} gibbs_sweep_us={t_g * 1e6:.1f}"
        ))
    ratio = steps[4][0] / steps[1][0]
    gibbs_ratio = steps[4][1] / steps[1][1]
    rows.append(csv_row(
        "rw_flat_ratio_4x_over_1x", 0.0,
        f"sgld={ratio:.2f} gibbs={gibbs_ratio:.2f}"
    ))
    return rows, {"sgld_step_ratio": ratio, "gibbs_sweep_ratio": gibbs_ratio}


def main(smoke: bool = False) -> list[str]:
    # the SGLD recipe for accuracy curves: aggressive preconditioned-SGD
    # warmup (temperature annealed over burn-in, trust-region clip 6) with
    # a 1/t step decay reaching sampling-size steps by warmup's end
    recipe = dict(step_size=1.0, step_decay=1.0, step_t0=50.0, clip=6.0)
    if smoke:
        default = dict(shape=(1000, 300, 20000), k=16, gibbs_sweeps=16,
                       gibbs_burn=5, sgld_steps=500, sgld_burn=250,
                       eval_every=20,
                       sgld_kwargs=dict(minibatch=2048, **recipe))
        big = dict(shape=(8000, 1200, 2000000), k=64, gibbs_sweeps=5,
                   gibbs_burn=2, sgld_steps=800, sgld_burn=400,
                   eval_every=25,
                   sgld_kwargs=dict(minibatch=16384, **recipe))
        flat = dict(m=1000, n=300, base_nnz=15000, minibatch=2048, iters=3)
    else:
        default = dict(shape=(2000, 400, 60000), k=32, gibbs_sweeps=40,
                       gibbs_burn=6, sgld_steps=1200, sgld_burn=400,
                       eval_every=25,
                       sgld_kwargs=dict(minibatch=4096, **recipe))
        big = dict(shape=(12000, 1500, 3000000), k=64, gibbs_sweeps=8,
                   gibbs_burn=3, sgld_steps=1200, sgld_burn=600,
                   eval_every=50,
                   sgld_kwargs=dict(minibatch=16384, **recipe))
        flat = dict(m=3000, n=500, base_nnz=60000, minibatch=4096, iters=5)

    rows, extra = [], {}
    d_rows, d_sum = _profile("default", **default)
    rows += d_rows
    extra["default"] = d_sum
    b_rows, b_sum = _profile("big", **big)
    rows += b_rows
    extra["big"] = b_sum
    f_rows, f_sum = _flat_study(**flat)
    rows += f_rows
    extra["flat"] = f_sum

    # acceptance gates (warn, never raise: benchmarks report, CI gates on
    # the committed history trajectory)
    gap = d_sum["sgld_final"] - d_sum["gibbs_final"]
    gates = {
        "accuracy_gap": round(gap, 4),
        "accuracy_ok": bool(gap <= ACCURACY_GAP),
        "big_equal_wallclock_ok": bool(
            b_sum["sgld_at_first_sweep"] < b_sum["gibbs_first_sweep"]
        ),
        "flat_ok": bool(f_sum["sgld_step_ratio"] < FLAT_RATIO),
    }
    extra["gates"] = gates
    rows.append(csv_row(
        "rw_gates", 0.0,
        f"accuracy_gap={gap:+.4f}(<= {ACCURACY_GAP}: {gates['accuracy_ok']}) "
        f"big_equal_wallclock={gates['big_equal_wallclock_ok']} "
        f"flat={gates['flat_ok']}"
    ))
    for name, ok in (("accuracy", gates["accuracy_ok"]),
                     ("big_equal_wallclock", gates["big_equal_wallclock_ok"]),
                     ("flat_iteration", gates["flat_ok"])):
        if not ok:
            print(f"# WARNING: rmse_wallclock gate '{name}' failed")

    path = write_bench_json("rmse_wallclock", rows, extra=extra)
    print(f"# wrote {path}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes/steps for CI smoke runs")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for row in main(smoke=args.smoke):
        print(row)
