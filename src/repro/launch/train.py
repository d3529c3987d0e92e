"""Train-step construction: loss + grad + AdamW, with mesh-aware shardings.

Also the BPMF training launcher. Plain training retains post-burn-in draws
durably:

    PYTHONPATH=src python -m repro.launch.train --bpmf --samples samples/ \
        --sweeps 24 --k 16

and --co-serve additionally runs a live RecommendFrontend in the same
process, fed by the asynchronous sample-publication channel
(serve/publish.py) — the trainer pushes each retained draw to serving
while the next sweep runs, the overlap the paper makes between computation
and communication (Sec 4), applied to the train -> serve hand-off:

    PYTHONPATH=src python -m repro.launch.train --bpmf --co-serve --sweeps 24

The co-serve path shares its driver with `repro.launch.serve --bpmf
--co-train` (the two entry points are the trainer's and the server's view
of the same overlapped process).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import build_model, param_pspecs
from repro.models.layers import ModelConfig
from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_schedule
from repro.optim.adamw import AdamWState


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState
    step: jax.Array


def init_train_state(cfg: ModelConfig, key: jax.Array, opt_cfg: AdamWConfig) -> TrainState:
    model = build_model(cfg)
    params = model.init(key)
    return TrainState(
        params=params, opt=adamw_init(params, opt_cfg), step=jnp.zeros((), jnp.int32)
    )


def train_state_shapes(cfg: ModelConfig, opt_cfg: AdamWConfig):
    """ShapeDtypeStructs of the train state — no allocation (dry-run path)."""
    return jax.eval_shape(
        lambda: init_train_state(cfg, jax.random.PRNGKey(0), opt_cfg)
    )


def train_state_pspecs(cfg: ModelConfig, state_shapes: TrainState, mesh) -> TrainState:
    return TrainState(
        params=param_pspecs(cfg, state_shapes.params, mesh),
        opt=AdamWState(
            m=param_pspecs(cfg, state_shapes.opt.m, mesh),
            v=param_pspecs(cfg, state_shapes.opt.v, mesh),
            step=P(),
        ),
        step=P(),
    )


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig, *, total_steps: int = 100_000):
    model = build_model(cfg)

    def train_step(state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        def loss_of(p):
            return model.loss_fn(p, batch)

        (loss, metrics), grads = jax.value_and_grad(loss_of, has_aux=True)(state.params)
        lr = cosine_schedule(
            state.step, peak_lr=opt_cfg.lr, warmup_steps=min(2000, total_steps // 10),
            total_steps=total_steps,
        )
        new_params, new_opt, om = adamw_update(grads, state.opt, state.params, opt_cfg, lr=lr)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["loss"] = loss
        metrics["lr"] = lr
        return TrainState(params=new_params, opt=new_opt, step=state.step + 1), metrics

    return train_step


def shardings_of(pspecs: Any, mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# BPMF training CLI (train -> retain; optionally train-while-serve)
# ---------------------------------------------------------------------------
def bpmf_train_main(args) -> None:
    if args.co_serve:
        from repro.launch.serve import run_train_and_serve

        run_train_and_serve(
            scale=args.scale, sweeps=args.sweeps, k=args.k,
            burn_in=args.burn_in, window=args.keep, samples=args.samples,
            seed=args.seed,
        )
        return

    import tempfile

    from repro.checkpoint import SampleStore
    from repro.core import GibbsSampler
    from repro.data import movielens_like, train_test_split

    root = args.samples or tempfile.mkdtemp(prefix="bpmf_samples_")
    ratings, _, _ = movielens_like(scale=args.scale, seed=args.seed)
    train, test = train_test_split(ratings, 0.1, seed=args.seed + 1)
    print(f"training {train.shape[0]} x {train.shape[1]} ({train.nnz} ratings), "
          f"k={args.k}, {args.sweeps} sweeps (burn-in {args.burn_in}) -> {root}")

    if args.mode != "single":
        # multi-device path over all local devices; sgld rides the same
        # grid partition and exchange modes as the Gibbs trainer
        from repro.core.distributed import DistributedBPMF
        from repro.core.sgld import DistributedSGLD

        width = "auto" if args.plan == "balanced" else 32
        if args.engine == "sgld":
            d = DistributedSGLD(train, test, k=args.k, alpha=4.0,
                                mode=args.mode, width=width,
                                minibatch=args.minibatch,
                                step_size=args.step_size)
        else:
            d = DistributedBPMF(train, test, k=args.k, alpha=4.0,
                                mode=args.mode, width=width,
                                engine="fused" if args.engine == "fused" else "einsum")
        state = d.run(args.sweeps, seed=args.seed, verbose=True)
        print(f"test rmse {d.rmse(state):.4f} "
              f"({d.n_shards} shards, engine={args.engine or 'einsum'}, "
              f"mode={args.mode}, plan={args.plan})")
        return

    widths = "balanced" if args.plan == "balanced" else (8, 32, 128)
    if args.engine == "sgld":
        from repro.core.sgld import SGLDSampler

        sampler = SGLDSampler(train, test, k=args.k, alpha=4.0,
                              burn_in=args.burn_in, widths=widths,
                              minibatch=args.minibatch,
                              step_size=args.step_size)
    else:
        sampler = GibbsSampler(train, test, k=args.k, alpha=4.0,
                               burn_in=args.burn_in, widths=widths,
                               engine=args.engine)
    store = SampleStore(root, keep=args.keep)
    state = sampler.run(args.sweeps, seed=args.seed, store=store,
                        thin=args.thin, verbose=True)
    print(f"test rmse {sampler.rmse(state):.4f}; retained "
          f"{len(store.steps())} draws; serve them with: "
          f"python -m repro.launch.serve --bpmf --samples {root}")


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--bpmf", action="store_true",
                    help="train BPMF (the only CLI mode; LM training is a "
                         "library — see make_train_step)")
    ap.add_argument("--samples", default=None,
                    help="SampleStore directory for retained draws "
                         "(default: a fresh temp dir)")
    ap.add_argument("--sweeps", type=int, default=40)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--burn-in", type=int, default=6)
    ap.add_argument("--keep", type=int, default=4,
                    help="retained-draw window (store keep / channel window)")
    ap.add_argument("--scale", type=float, default=0.01,
                    help="movielens_like dataset scale")
    ap.add_argument("--seed", type=int, default=0)
    from repro.core.gibbs import TRAIN_ENGINES

    ap.add_argument("--engine", default=None, choices=list(TRAIN_ENGINES),
                    help="trainer engine, one of: "
                         "'reference' (seed Gibbs data flow, equivalence "
                         "oracle), 'einsum' (restructured Gibbs, the "
                         "default), 'kernel' (two-step Pallas Gibbs), "
                         "'fused' (gather-syrk kernel Gibbs), 'sgld' "
                         "(minibatch SG-MCMC: per-step cost set by "
                         "--minibatch, not dataset size; --sweeps then "
                         "counts SGLD steps)")
    ap.add_argument("--minibatch", type=int, default=4096,
                    help="sgld engine: padded-lane budget per half-step "
                         "(per shard when --mode is distributed)")
    ap.add_argument("--step-size", type=float, default=0.3,
                    help="sgld engine: peak Langevin step size (decays "
                         "polynomially; see optim.schedule.sgld_step_schedule)")
    ap.add_argument("--thin", type=int, default=1,
                    help="retain every thin-th post-burn-in draw (sgld "
                         "publishes far more often than Gibbs — thin keeps "
                         "store/channel traffic bounded)")
    ap.add_argument("--plan", default="balanced",
                    choices=["balanced", "pow2"],
                    help="bucket planner: 'balanced' fits variable widths to "
                         "the degree profile (work-stealing-equivalent load "
                         "balance); 'pow2' is the legacy fixed ladder")
    ap.add_argument("--mode", default="single",
                    choices=["single", "ring", "allgather", "async"],
                    help="'single' = one-device GibbsSampler; otherwise a "
                         "DistributedBPMF exchange mode ('async' = "
                         "stale-tolerant fused ring pipeline)")
    ap.add_argument("--co-serve", action="store_true",
                    help="serve live recommendations from this process while "
                         "training, via the async publication channel")
    args = ap.parse_args()
    if not args.bpmf:
        raise SystemExit("only --bpmf has a CLI; LM training is library-only")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    bpmf_train_main(args)


if __name__ == "__main__":
    main()
