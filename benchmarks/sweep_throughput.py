"""Gibbs training-sweep throughput: updates/sec per sweep engine.

    PYTHONPATH=src python benchmarks/sweep_throughput.py [--smoke]

The paper's headline numbers are *training* throughput (Fig 4 multicore
updates/sec). This benchmark pins the repo's own trajectory for the
single-host sweep across the three engine generations:

  reference   the seed data flow: einsum row statistics, per-bucket
              segment_sum + two full-size scatter-add passes, and the
              LAPACK-style 3-triangular-solve sampler.
  einsum      the restructured flow (default engine): identical statistics
              written once into their seg_item_ids slots (no full-size zero
              buffers, one scatter per output) and the batch-vectorized
              substitution solver.
  fused       the restructured flow with statistics from the fused
              gather→syrk→segment-reduce engine (`ops.gather_syrk_seg`:
              the Pallas kernel on TPU, the fused-semantics jnp path here).

Updates/sec counts one resampled entity (user or movie) per sweep, the
paper's Fig 4 metric. Engines are also cross-checked: one sweep from a
shared key must produce the same samples to fp32 tolerance.

Emits machine-readable BENCH_sweep.json (suite rows + speedup summary) so
the perf trajectory finally has data; `--smoke` shrinks shapes for the CI
job. The two-step Pallas `kernel` engine is measured by fig4 in interpret
mode (a correctness path, not a speed claim) and is skipped here.
"""
from __future__ import annotations

import argparse

import numpy as np

try:
    from benchmarks.common import csv_row, time_fn, write_bench_json
except ModuleNotFoundError:  # invoked as a file: python benchmarks/<name>.py
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks.common import csv_row, time_fn, write_bench_json

from repro.core import GibbsSampler
from repro.data import chembl_like, train_test_split

ENGINES = ("reference", "einsum", "fused")
TARGET_SPEEDUP = 1.5   # acceptance floor: restructured/fused vs reference


def measure_engine(train, widths, engine, k, iters):
    s = GibbsSampler(train, None, k=k, alpha=1.5, widths=widths, engine=engine)
    state = s.init(0)
    sweep = s.sweep           # the sampler's own jitted sweep (run() path)
    t = time_fn(sweep, state, warmup=1, iters=iters)
    n_updates = s.m + s.n
    out = sweep(state)
    return t, n_updates / t, (np.asarray(out.u), np.asarray(out.v))


def main(smoke: bool = False) -> list[str]:
    # k=32 everywhere: at toy K the XLA batched solve never leaves its
    # vectorized small-matrix path and the engine comparison is meaningless
    if smoke:
        scale, k, iters = 0.004, 32, 2
        profiles = [(8, 32, 128, 512)]
    else:
        scale, k, iters = 0.004, 32, 5
        profiles = [(8, 32, 128, 512), (16, 128), (32,)]
    ratings, _, _ = chembl_like(scale=scale, seed=0)
    train, _ = train_test_split(ratings, 0.05, seed=1)
    print(f"# m={train.shape[0]} n={train.shape[1]} nnz={train.nnz} k={k}"
          f"{' (smoke)' if smoke else ''}")

    rows = []
    speedups = {}
    for widths in profiles:
        tag = "x".join(map(str, widths))
        times = {}
        samples = {}
        for engine in ENGINES:
            t, ups, uv = measure_engine(train, widths, engine, k, iters)
            times[engine] = t
            samples[engine] = uv
            rows.append(csv_row(
                f"sweep_{tag}_{engine}", t * 1e6, f"updates_per_s={ups:.0f}"
            ))
        # engine equivalence from the shared key (fp32 tolerance)
        dev = max(
            float(np.abs(samples[e][i] - samples["reference"][i]).max())
            for e in ENGINES[1:] for i in (0, 1)
        )
        rows.append(csv_row(f"sweep_{tag}_max_sample_dev", 0.0, f"{dev:.2e}"))
        for engine in ENGINES[1:]:
            sp = times["reference"] / times[engine]
            speedups[f"{tag}_{engine}"] = round(sp, 3)
            rows.append(csv_row(
                f"sweep_{tag}_{engine}_speedup", 0.0, f"{sp:.2f}x"
            ))
        if widths == (8, 32, 128, 512):
            for engine in ENGINES[1:]:
                if times["reference"] / times[engine] < TARGET_SPEEDUP:
                    print(f"# WARNING: {engine} speedup below the "
                          f"{TARGET_SPEEDUP}x acceptance target at {tag}")
            if dev > 5e-3:
                print(f"# WARNING: engine sample deviation {dev:.2e} above "
                      "fp32 tolerance")

    path = write_bench_json("sweep", rows, extra={"speedups": speedups})
    print(f"# wrote {path}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI smoke runs")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for row in main(smoke=args.smoke):
        print(row)
