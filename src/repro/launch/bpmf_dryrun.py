import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""BPMF production-mesh dry-run: lower + compile the distributed sweep at the
paper's full benchmark scales on 256 and 512 chips, for both communication
modes. Plans enter as ShapeDtypeStructs — the planner's shapes are derived
from real degree statistics of the (synthetic, full-scale) dataset, but no
plan arrays are materialized.

    python -m repro.launch.bpmf_dryrun [--dataset chembl|ml20m] [--mode ring|allgather|both]
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.distributed import AXIS, DistState, make_sweep
from repro.core.hyper import HyperParams, default_prior
from repro.launch.hlo_analysis import HloCostModel, roofline_terms
from repro.launch.mesh import TARGET_KIND, chip_peaks

ART_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

DATASETS = {
    # (n_users, n_items, nnz) at full paper scale
    "chembl": (483_500, 5_775, 1_023_952),
    "ml20m": (138_493, 27_278, 20_000_000),
}


def plan_shape(m: int, n: int, nnz: int, p: int, width: int) -> tuple[int, int, int]:
    """(m_loc, n_loc, rows) estimate for the (P,P) grid plan of the U update.

    rows per block ~ items-with-ratings-in-block + chunk splits; we provision
    the max block at 3x the mean (power-law skew headroom; the host planner
    reports the true max at run time).
    """
    m_loc = -(-m // p)
    n_loc = -(-n // p)
    mean_rows = max(1.0, nnz / (p * p) / 1.0)  # ~1 row per (item, block) touch
    rows = int(np.ceil(3.0 * mean_rows)) + 4
    return m_loc, n_loc, rows


def run_cell(dataset: str, mode: str, multi_pod: bool, k: int = 64, width: int = 32) -> dict:
    m, n, nnz = DATASETS[dataset]
    p = 512 if multi_pod else 256
    mesh = jax.make_mesh((p,), (AXIS,), devices=jax.devices()[:p])
    rec = {
        "arch": f"bpmf-{dataset}-{mode}",
        "shape": f"K{k}_sweep",
        "kind": "bpmf",
        "mesh": f"{p}x1",
        "n_devices": p,
        "ok": False,
    }
    t0 = time.time()
    try:
        m_loc, n_loc_v, ru = plan_shape(m, n, nnz, p, width)
        _, m_loc_u, rv = plan_shape(n, m, nnz, p, width)

        f32, i32 = jnp.float32, jnp.int32
        sds = jax.ShapeDtypeStruct

        def plan_sds(rows):
            return (
                sds((p, p, rows, width), i32),
                sds((p, p, rows, width), f32),
                sds((p, p, rows, width), f32),
                sds((p, p, rows), i32),
                sds((p, p, rows), i32),      # seg_dense
                sds((p, p, rows), i32),      # seg_map
            )

        if mode == "allgather":
            def plan_sds(rows):  # noqa: F811 — flattened layout
                return (
                    sds((p, p * rows, width), i32),
                    sds((p, p * rows, width), f32),
                    sds((p, p * rows, width), f32),
                    sds((p, p * rows), i32),
                    sds((p, p * rows), i32),  # seg_dense
                    sds((p, p * rows), i32),  # seg_map
                )

        state_sds = DistState(
            u=sds((p, m_loc, k), f32),
            v=sds((p, n_loc_v, k), f32),
            hyper_u=HyperParams(sds((k,), f32), sds((k, k), f32)),
            hyper_v=HyperParams(sds((k,), f32), sds((k, k), f32)),
            key=sds((2,), jnp.uint32),
            step=sds((), i32),
        )
        u_plans = plan_sds(ru)
        v_plans = plan_sds(rv)
        ids_u = sds((p, m_loc), i32)
        ids_v = sds((p, n_loc_v), i32)

        sweep = make_sweep(mesh, mode, alpha=1.5, prior=default_prior(k))
        shard = lambda spec: NamedSharding(mesh, spec)
        state_sh = DistState(
            u=shard(P(AXIS)), v=shard(P(AXIS)),
            hyper_u=HyperParams(shard(P()), shard(P())),
            hyper_v=HyperParams(shard(P()), shard(P())),
            key=shard(P()), step=shard(P()),
        )
        plan_sh = tuple(shard(P(AXIS)) for _ in range(6))
        jitted = jax.jit(
            sweep,
            in_shardings=(state_sh, plan_sh, plan_sh, shard(P(AXIS)), shard(P(AXIS))),
            out_shardings=state_sh,
            donate_argnums=(0,),
        )
        with mesh:
            lowered = jitted.lower(state_sds, u_plans, v_plans, ids_u, ids_v)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        print(mem)
        print({k_: v for k_, v in (compiled.cost_analysis() or {}).items()
               if k_ in ("flops", "bytes accessed")})
        cost = HloCostModel(compiled.as_text()).analyze()
        # useful flops: per item update 2*deg*W... analytic: syrk 2*nnz*W_eff*K^2/W... use
        # 2 * nnz * K^2 (outer products) + (M+N) * (2/3 K^3 + 4K^2) (cholesky+solves)
        model_flops = 2.0 * nnz * k * k + (m + n) * (2 / 3 * k**3 + 4 * k * k)
        peaks = chip_peaks(TARGET_KIND)
        terms = roofline_terms(
            flops=float(cost["flops"]),
            hbm_bytes=float(cost["hbm_bytes"]),
            collective_bytes_per_device=float(cost["collective_total_bytes"]),
            n_devices=p,
            peak_flops=peaks.flops_bf16,
            hbm_bw=peaks.hbm_bw,
            ici_bw=peaks.ici_bw,
        )
        rec.update(
            ok=True,
            lower_s=round(t_lower, 1),
            compile_s=round(time.time() - t0 - t_lower, 1),
            memory=dict(
                argument_bytes=mem.argument_size_in_bytes,
                output_bytes=mem.output_size_in_bytes,
                temp_bytes=mem.temp_size_in_bytes,
            ),
            per_device_flops=float(cost["flops"]),
            per_device_hbm_bytes=float(cost["hbm_bytes"]),
            collective_bytes=cost["collective_bytes"],
            collective_counts=cost["collective_counts"],
            collective_total_bytes=cost["collective_total_bytes"],
            wire_bytes=cost.get("wire_bytes"),
            model_flops=model_flops,
            useful_flops_ratio=model_flops / max(float(cost["flops"]) * p, 1.0),
            roofline=terms,
        )
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    rec["total_s"] = round(time.time() - t0, 1)
    ART_DIR.mkdir(parents=True, exist_ok=True)
    out = ART_DIR / f"bpmf-{dataset}-{mode}__K{k}__{'multi' if multi_pod else 'single'}.json"
    out.write_text(json.dumps(rec, indent=2))
    status = "OK" if rec["ok"] else f"FAIL ({rec.get('error', '')[:120]})"
    print(f"[bpmf-dryrun] {dataset} {mode} {rec['mesh']}: {status} ({rec['total_s']}s)")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="both", choices=["chembl", "ml20m", "both"])
    ap.add_argument("--mode", default="both", choices=["ring", "allgather", "both"])
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    args = ap.parse_args()
    datasets = ["chembl", "ml20m"] if args.dataset == "both" else [args.dataset]
    modes = ["ring", "allgather"] if args.mode == "both" else [args.mode]
    meshes = [False, True] if args.mesh == "both" else [args.mesh == "multi"]
    fails = 0
    for d in datasets:
        for mo in modes:
            for mp in meshes:
                fails += 0 if run_cell(d, mo, mp)["ok"] else 1
    raise SystemExit(1 if fails else 0)


if __name__ == "__main__":
    main()
