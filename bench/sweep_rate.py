#!/usr/bin/env python3
"""Find the highest open-loop rate a serving cell sustains.

    python bench/sweep_rate.py --workload <serving cell> --seed <n> \
        --seconds 20 --rates 1000 1100 1200 1300 1400 1500

Sets the cell up once (as `bench/run.py` does), then serves the cell's mix
at each rate in turn, lowest first, for `--seconds` and prints one JSON line
per rate: p50 and p99 latency from the due time, the rate completed, the
median latency of the first and the last fifth of the requests, and whether
the rate was sustained (`sustained`). A rate is sustained when no request
went unanswered, the last fifth's median latency is at most `GROWTH` times
the first fifth's (a backlog that grows shows as a last fifth slower than
the first), and the median latency is at most `SATURATION` times that of the
lowest rate swept. The last line names the highest rate sustained with every
lower rate sustained too, and four fifths of it: the rate the cell's traffic
file then fixes as a number. This script is not part of a run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import numpy as np  # noqa: E402

import harness  # noqa: E402
import run  # noqa: E402

# A rate is sustained when its last fifth's median latency is at most
# GROWTH x its first fifth's, and its median at most SATURATION x the lowest
# rate's median.
GROWTH = 1.15
SATURATION = 1.5


def sustained(row: dict, base_p50_ms: float) -> bool:
    """The rule above, on one rate's line."""
    return (row["failed"] == 0
            and row["last_fifth_p50_ms"] <= GROWTH * row["first_fifth_p50_ms"]
            and row["p50_ms"] <= SATURATION * base_p50_ms)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    cell = harness.load_cell(args.workload)
    harness.src_path()
    devices = harness.require_devices(cell.chips)
    harness.enable_compile_cache()
    ctx = run.RunContext(cell=cell, seed=args.seed, seconds=args.seconds,
                         trace=False, control=False, devices=devices,
                         peaks=harness.peaks(devices[0].device_kind),
                         rec=harness.Recorder(), compiles=harness.CompileCounter(),
                         limits={})
    drv = harness.driver(cell.traffic["kind"])
    ss = np.random.SeedSequence([args.seed, 3]).generate_state(4)
    rng = np.random.default_rng(ss[:2])
    server = drv.Server(ctx, cell.config, cell.traffic, rng,
                        draw_seed=int(ss[2] & 0x7FFFFFFF))
    server.warm_up(np.random.default_rng(ss[3]))
    ctx.setup_done()
    run.log(f"sweep: set-up {ctx.setup_s:.1f} s")
    base_p50 = None
    highest = None
    for rate in sorted(args.rates):
        sched = drv.make_schedule(cell.config, cell.traffic, args.seconds, rate,
                                  rng, server.item_p, server.v_true)
        c0, n_spans = ctx.compiles.count, len(ctx.rec.spans)
        lat, _, _, _, _ = drv.open_loop(ctx, server, sched)
        lat_ms = 1e3 * lat
        fifth = max(1, sched.n // 5)
        done = lat[np.isfinite(lat)]
        span = float(np.max(sched.due + np.where(np.isfinite(lat), lat, 0.0)))
        row = {
            "rate": rate, "requests": sched.n, "failed": int(sched.n - len(done)),
            "completed_per_s": len(done) / span,
            "p50_ms": float(np.percentile(lat_ms, 50)),
            "p99_ms": float(np.percentile(lat_ms, 99)),
            "first_fifth_p50_ms": float(np.median(lat_ms[:fifth])),
            "last_fifth_p50_ms": float(np.median(lat_ms[-fifth:])),
            "window_compiles": ctx.compiles.count - c0,
            "cold_per_batch": np.bincount(
                [a["cold"] for n, _, _, a in ctx.rec.spans[n_spans:]
                 if n == "flush"]).tolist(),
        }
        base_p50 = row["p50_ms"] if base_p50 is None else base_p50
        row["sustained"] = sustained(row, base_p50)
        print(json.dumps(row), flush=True)
        if not row["sustained"]:
            break
        highest = rate
    server.fe.close()
    print(json.dumps({"highest_sustained_per_s": highest,
                      "four_fifths_per_s": None if highest is None else 0.8 * highest}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
