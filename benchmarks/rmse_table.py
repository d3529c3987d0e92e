"""Paper Sec 5.2 claim: every parallel version reaches the same RMSE.

Runs the four samplers (single-host jnp, single-host Pallas-kernel path,
distributed ring, distributed all-gather — the latter two in an 8-device
subprocess) on the same ChEMBL-like split and reports test RMSE, plus the
ALS baseline (the paper's Sec 6 comparison: BPMF needs no regularization
tuning; ALS gets an untuned lambda).
"""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

from benchmarks.common import csv_row
from repro.core import ALS, GibbsSampler
from repro.data import chembl_like, train_test_split

SRC = str(Path(__file__).resolve().parents[1] / "src")
N_SWEEPS = 20


def main() -> list[str]:
    rows = []
    ratings, _, _ = chembl_like(scale=0.003, seed=0)
    train, test = train_test_split(ratings, 0.1, seed=1)

    s = GibbsSampler(train, test, k=32, alpha=4.0, burn_in=6)
    st = s.run(N_SWEEPS, seed=0)
    rows.append(csv_row("rmse_gibbs_single", 0.0, f"{s.rmse(st):.4f}"))

    sk = GibbsSampler(train, test, k=32, alpha=4.0, burn_in=6, use_kernel=True)
    stk = sk.run(N_SWEEPS, seed=0)
    rows.append(csv_row("rmse_gibbs_pallas", 0.0, f"{sk.rmse(stk):.4f}"))

    code = f"""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    os.environ['JAX_PLATFORMS'] = 'cpu'  # simulated host devices, never the chip
    import sys, json
    sys.path.insert(0, {SRC!r})
    from repro.data import chembl_like, train_test_split
    from repro.core.distributed import DistributedBPMF
    ratings, _, _ = chembl_like(scale=0.003, seed=0)
    train, test = train_test_split(ratings, 0.1, seed=1)
    out = {{}}
    for mode in ("ring", "allgather"):
        s = DistributedBPMF(train, test, k=32, alpha=4.0, mode=mode)
        st = s.run({N_SWEEPS}, seed=0)
        out[mode] = s.rmse(st)
    print(json.dumps(out))
    """
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=1200,
    )
    if res.returncode != 0:
        raise RuntimeError(res.stderr[-2000:])
    dist = json.loads(res.stdout.strip().splitlines()[-1])
    rows.append(csv_row("rmse_gibbs_ring_8dev", 0.0, f"{dist['ring']:.4f}"))
    rows.append(csv_row("rmse_gibbs_allgather_8dev", 0.0, f"{dist['allgather']:.4f}"))

    als = ALS(train, test, k=32, lam_reg=0.3)
    sta = als.run(12)
    rows.append(csv_row("rmse_als_untuned", 0.0, f"{als.rmse(sta):.4f}"))
    return rows


if __name__ == "__main__":
    for r in main():
        print(r)
