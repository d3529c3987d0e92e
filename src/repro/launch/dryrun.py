import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, with 512 placeholder host devices standing in for the
TPU slice. Proves the distribution config is coherent: sharding mismatches,
compile-time OOM, or unsupported collectives fail loudly here.

Usage:
    python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k --mesh single
    python -m repro.launch.dryrun --arch all --mesh both        # full sweep
    python -m repro.launch.dryrun --list                        # cell list

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>.json and feed
benchmarks/roofline.py and EXPERIMENTS.md.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import REGISTRY, get_config
from repro.launch.hlo_analysis import HloCostModel, roofline_terms
from repro.launch.mesh import TARGET_KIND, chip_peaks, make_production_mesh
from repro.launch.train import (
    make_train_step,
    shardings_of,
    train_state_pspecs,
    train_state_shapes,
)
from repro.models import (
    build_model,
    cache_pspecs,
    input_pspecs,
    input_specs,
    shape_by_name,
    supported_shapes,
)
from repro.optim import AdamWConfig

ART_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _cache_shapes(model, shape):
    return jax.eval_shape(lambda: model.init_cache(shape.global_batch, shape.seq_len))


def lower_cell(cfg, shape, mesh):
    """Build the jitted step for one cell and return (lowered, n_devices)."""
    from repro.models.layers import active_mesh

    with active_mesh(mesh):
        return _lower_cell_inner(cfg, shape, mesh)


def _lower_cell_inner(cfg, shape, mesh):
    model = build_model(cfg)
    ispecs = input_specs(cfg, shape)
    ips = input_pspecs(cfg, shape, mesh)
    in_batch_shardings = {k: NamedSharding(mesh, ips[k]) for k in ispecs}

    if shape.kind == "train":
        opt_cfg = AdamWConfig(moment_dtype=cfg.moment_dtype)
        step_fn = make_train_step(cfg, opt_cfg)
        state_sds = train_state_shapes(cfg, opt_cfg)
        state_ps = train_state_pspecs(cfg, state_sds, mesh)
        state_sh = shardings_of(state_ps, mesh)
        jitted = jax.jit(
            step_fn,
            in_shardings=(state_sh, in_batch_shardings),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        with mesh:
            return jitted.lower(state_sds, ispecs)

    params_sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    from repro.models import param_pspecs

    params_sh = shardings_of(param_pspecs(cfg, params_sds, mesh), mesh)

    if shape.kind == "prefill":
        jitted = jax.jit(
            model.prefill_fn, in_shardings=(params_sh, in_batch_shardings)
        )
        with mesh:
            return jitted.lower(params_sds, ispecs)

    # decode: one new token against a seq_len cache
    cache_sds = _cache_shapes(model, shape)
    cache_sh = shardings_of(cache_pspecs(cfg, shape, mesh), mesh)
    jitted = jax.jit(
        model.decode_fn,
        in_shardings=(params_sh, cache_sh, in_batch_shardings),
        out_shardings=(cache_sh, None),
        donate_argnums=(1,),
    )
    with mesh:
        return jitted.lower(params_sds, cache_sds, ispecs)


def run_cell(arch: str, shape_name: str, mesh_name: str, save_hlo: bool = False,
             variant: str = "base") -> dict:
    from repro.configs.variants import apply_variant

    cfg = apply_variant(get_config(arch), variant)
    shape = shape_by_name(shape_name)
    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_dev = mesh.size
    rec = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "variant": variant,
        "mesh": f"{'2x16x16' if multi else '16x16'}",
        "n_devices": n_dev,
        "ok": False,
    }
    t0 = time.time()
    try:
        lowered = lower_cell(cfg, shape, mesh)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        print(mem)  # proves it fits
        ca = compiled.cost_analysis() or {}
        print({k: v for k, v in ca.items() if k in ("flops", "bytes accessed")})
        hlo = compiled.as_text()
        # XLA's cost_analysis counts while bodies once; our HLO walker applies
        # known_trip_count multipliers (see hlo_analysis.py).
        cost = HloCostModel(hlo).analyze()

        flops = float(cost["flops"])
        hbm_bytes = float(cost["hbm_bytes"])
        # tokens processed per step
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        from repro.models.api import model_flops_per_step

        model_flops = model_flops_per_step(cfg, shape)
        peaks = chip_peaks(TARGET_KIND)
        terms = roofline_terms(
            flops=flops,
            hbm_bytes=hbm_bytes,
            collective_bytes_per_device=float(cost["collective_total_bytes"]),
            n_devices=n_dev,
            peak_flops=peaks.flops_bf16,
            hbm_bw=peaks.hbm_bw,
            ici_bw=peaks.ici_bw,
        )
        rec.update(
            ok=True,
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory=dict(
                argument_bytes=mem.argument_size_in_bytes,
                output_bytes=mem.output_size_in_bytes,
                temp_bytes=mem.temp_size_in_bytes,
                alias_bytes=mem.alias_size_in_bytes,
                code_bytes=mem.generated_code_size_in_bytes,
            ),
            per_device_flops=flops,
            per_device_hbm_bytes=hbm_bytes,
            xla_cost_analysis=dict(
                flops=float(ca.get("flops", 0.0)),
                bytes_accessed=float(ca.get("bytes accessed", 0.0)),
            ),
            collective_bytes=cost["collective_bytes"],
            collective_counts=cost["collective_counts"],
            collective_total_bytes=cost["collective_total_bytes"],
            wire_bytes=cost.get("wire_bytes"),
            wire_total_bytes=cost.get("wire_total_bytes"),
            cost_warnings=cost["warnings"],
            model_flops=model_flops,
            useful_flops_ratio=(model_flops / (flops * n_dev)) if flops else 0.0,
            tokens_per_step=tokens,
            roofline=terms,
        )
        if save_hlo:
            import gzip

            hp = ART_DIR / f"{arch}__{shape_name}__{rec['mesh']}.hlo.gz"
            hp.parent.mkdir(parents=True, exist_ok=True)
            with gzip.open(hp, "wt") as f:
                f.write(hlo)
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 1)
    ART_DIR.mkdir(parents=True, exist_ok=True)
    suffix = "" if variant == "base" else f"__{variant}"
    out = ART_DIR / f"{arch}__{shape_name}__{'multi' if multi else 'single'}{suffix}.json"
    out.write_text(json.dumps(rec, indent=2))
    status = "OK" if rec["ok"] else f"FAIL ({rec.get('error', '')[:120]})"
    print(f"[dryrun] {arch} {shape_name} {rec['mesh']}: {status} ({rec['total_s']}s)")
    return rec


def all_cells():
    cells = []
    for arch, cfg in REGISTRY.items():
        for shape in supported_shapes(cfg):
            cells.append((arch, shape.name))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--variant", default="base", choices=["base", "opt"])
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        for arch, shape in all_cells():
            print(arch, shape)
        return

    cells = all_cells()
    if args.arch != "all":
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape != "all":
        cells = [c for c in cells if c[1] == args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_fail = 0
    suffix = "" if args.variant == "base" else f"__{args.variant}"
    for arch, shape in cells:
        for mesh_name in meshes:
            out = ART_DIR / f"{arch}__{shape}__{mesh_name}{suffix}.json"
            if args.skip_done and out.exists() and json.loads(out.read_text()).get("ok"):
                print(f"[dryrun] skip {arch} {shape} {mesh_name} (done)")
                continue
            rec = run_cell(arch, shape, mesh_name, save_hlo=args.save_hlo,
                           variant=args.variant)
            n_fail += 0 if rec["ok"] else 1
    print(f"[dryrun] sweep complete, {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
