"""Paper Fig 5: distributed strong scaling, sync vs async communication.

Measured in a subprocess per device count (jax pins the host device count at
first init). For each P in {1, 2, 4, 8}: updates/sec of the ring (pipelined,
GASPI analogue), the all-gather (bulk-synchronous, MPI_bcast analogue), and
the stale-tolerant fused "async" sampler on the ChEMBL-like benchmark, plus
parallel efficiency vs P=1 and an RMSE-parity gate for async at P=4.

Wall-clock on a single shared CPU is a *scheduling* proxy — the structural
comparison (collective bytes, overlap) is in fig6_overlap.py; both views
together reproduce the paper's Fig 5/6 story.
"""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

from benchmarks.common import csv_row

SRC = str(Path(__file__).resolve().parents[1] / "src")

_WORKER = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={p}'
os.environ['JAX_PLATFORMS'] = 'cpu'  # simulated host devices, never the chip
import sys, json, time
sys.path.insert(0, {src!r})
import jax
import jax.numpy as jnp
from repro.data import chembl_like, train_test_split
from repro.core.distributed import DistributedBPMF

def timed_sweeps(s, iters):
    st = s.init(0)
    st = s.sweep(st); jax.block_until_ready(st.u)   # compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        st = s.sweep(st)
        jax.block_until_ready(st.u)
        times.append(time.perf_counter() - t0)
    times.sort()
    return st, times[len(times) // 2]   # median: robust to scheduler noise

ratings, _, _ = chembl_like(scale=0.002, seed=0)
train, test = train_test_split(ratings, 0.05, seed=1)
out = {{}}
for mode in ("ring", "allgather", "async"):
    s = DistributedBPMF(train, test, k=32, alpha=1.5, mode=mode, width=32)
    st, dt = timed_sweeps(s, {iters})
    # per-phase split by ablation: rebuild the same program with every
    # collective replaced by a shape-preserving local stub (ppermute ->
    # identity, all_gather -> broadcast, psum -> x * P). The stub trace is
    # per-instance (each sampler jits its own closure), so compute_s is
    # the same sharded sweep minus communication; exchange_s is the rest.
    # Numerically wrong, timing-valid — an ablation, not a chain.
    real = (jax.lax.ppermute, jax.lax.all_gather, jax.lax.psum)
    n_sh = s.n_shards
    jax.lax.ppermute = lambda x, *a, **kw: x
    jax.lax.all_gather = lambda x, *a, **kw: jnp.broadcast_to(
        x, (n_sh,) + x.shape)
    jax.lax.psum = lambda x, *a, **kw: x * n_sh
    try:
        s2 = DistributedBPMF(train, test, k=32, alpha=1.5, mode=mode,
                             width=32)
        _, compute = timed_sweeps(s2, {iters})
    finally:
        jax.lax.ppermute, jax.lax.all_gather, jax.lax.psum = real
    # run on to a common sweep count before scoring: the stale-by-one
    # async chain needs ~2x the burn-in in sweeps, so RMSE parity is a
    # plateau property, not a sweep-4 property
    for _ in range(10 - 1 - {iters}):
        st = s.sweep(st)
    out[mode] = {{"sweep_s": dt, "compute_s": min(compute, dt),
                  "exchange_s": max(dt - compute, 0.0),
                  "rmse": s.rmse(st),
                  "items": train.shape[0] + train.shape[1]}}
print(json.dumps(out))
"""


def run_p(p: int, iters: int = 3) -> dict:
    code = _WORKER.format(p=p, src=SRC, iters=str(iters))
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900,
    )
    if res.returncode != 0:
        raise RuntimeError(res.stderr[-2000:])
    return json.loads(res.stdout.strip().splitlines()[-1])


def main(smoke: bool = False) -> list[str]:
    import os

    rows = []
    base = {}
    rmse_p4 = {}
    # parallel efficiency is relative to the cores that physically exist:
    # on an n-core host, P > n forced host devices time-slice, so the
    # ideal is base * min(P, n), not base * P. (The seed's flat ~0.5
    # "efficiency" at any P was a recompile artifact — the timed window
    # was compile time, constant in P — not real scaling.)
    cores = os.cpu_count() or 1
    for p in (1, 4) if smoke else (1, 2, 4, 8):
        out = run_p(p, iters=1 if smoke else 3)
        for mode in ("ring", "allgather", "async"):
            d = out[mode]
            ups = d["items"] / d["sweep_s"]
            if p == 1:
                base[mode] = ups
            eff = ups / (base[mode] * min(p, cores))
            if p == 4:
                rmse_p4[mode] = d["rmse"]
            rows.append(csv_row(
                f"fig5_{mode}_p{p}", d["sweep_s"] * 1e6,
                f"updates_per_s={ups:.0f};efficiency={eff:.2f};"
                f"rmse={d['rmse']:.3f};compute_s={d['compute_s']:.4f};"
                f"exchange_s={d['exchange_s']:.4f}",
            ))
    # RMSE-parity gate (paper Sec 5.2): the stale-by-one async chain must
    # land on the same plateau as the exact ring sampler at p=4
    gap = abs(rmse_p4["async"] - rmse_p4["ring"])
    rows.append(csv_row("fig5_async_rmse_parity_p4", 0.0,
                        f"|async-ring|={gap:.4f}"))
    assert gap < 0.05, (
        f"async RMSE diverged from ring at p=4: {rmse_p4['async']:.4f} vs "
        f"{rmse_p4['ring']:.4f}"
    )
    return rows


if __name__ == "__main__":
    for r in main():
        print(r)
