"""Traffic kind `train_sweeps_mesh`: back-to-back sweeps of the distributed
sampler over a 1-D mesh of the cell's chips.

Set-up generates the configuration's ratings from the seed and builds
`DistributedBPMF` over the run's devices as `launch/train.py --mode <mode>`
builds it, with the configuration's `mesh` group (`shards`, `mode`,
`width`, `engine`); the planner's time is `plan_s`, and the grid plans'
rated and sent slots (`plan_stats()`, where the program counts them) go
to the per-layer metrics. It drives the sampler from the seed through its
first `check_sweeps` sweeps with the window's own call, keeping host copies
of the initial and of each checked state in global entity order. The
window follows `train_sweeps`' rule: sweeps dispatched back to back,
`ahead_seconds` of them (at least one, at most `ahead_max`) queued behind
the one waited for; once `--seconds` have passed none more is sent, every
sent sweep is waited for, and all of them count over all of that time.
`train_updates_per_s` counts as `train_sweeps` does (`work.sweep_updates`),
so one chip and a mesh compare directly. After the window the float64
reference (`reference_mesh.py`) checks every checked sweep's hyper draws
and the draws of entities sampled from every degree stratum, on both
half-sweeps.

Traffic parameters (bench/traffic/<mix>.json): those of `train_sweeps`.
"""
from __future__ import annotations

import collections
import time

import numpy as np

import datagen
import reference as ref
import reference_mesh
import work


def _program_sampler(cfg: dict, data: datagen.Dataset, devices):
    """The system under test, built as `launch/train.py` builds it."""
    import jax
    from repro.core.distributed import AXIS, DistributedBPMF
    from repro.data.sparse import SparseRatings

    def to_program(r: datagen.Ratings) -> SparseRatings:
        return SparseRatings(rows=r.rows, cols=r.cols, vals=r.vals, shape=r.shape)

    mesh_cfg = cfg["mesh"]
    if len(devices) != int(mesh_cfg["shards"]):
        raise ValueError(f"the mesh needs {mesh_cfg['shards']} devices, "
                         f"the run has {len(devices)}")
    mesh = jax.make_mesh((len(devices),), (AXIS,), devices=devices)
    return DistributedBPMF(to_program(data.train), to_program(data.test),
                           mesh=mesh, k=cfg["k"], alpha=cfg["alpha"],
                           width=mesh_cfg["width"], mode=mesh_cfg["mode"],
                           engine=mesh_cfg["engine"])


def _global(x, ids: np.ndarray) -> np.ndarray:
    """(P, n_loc, K) sharded factors -> (N, K) in global entity order, by
    the program's entity-to-shard map `ids` ((P, n_loc), -1 for padding)."""
    x = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    real = ids.reshape(-1) >= 0
    out = np.zeros((int(real.sum()), x.shape[-1]))
    out[ids.reshape(-1)[real]] = x[real]
    return out


def _host_state(d, state) -> dict:
    """The draws and hypers of a state, on the host, in global order."""
    u_ids, v_ids = d.u_part.ids, d.v_part.ids
    v_eval = getattr(state, "v_eval", None)
    return {
        "u": _global(state.u, u_ids), "v": _global(state.v, v_ids),
        "v_eval": None if v_eval is None else _global(v_eval, v_ids),
        "mu_u": np.asarray(state.hyper_u.mu), "lam_u": np.asarray(state.hyper_u.lam),
        "mu_v": np.asarray(state.hyper_v.mu), "lam_v": np.asarray(state.hyper_v.lam),
    }


def run(ctx):
    import jax

    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    rec = ctx.rec
    init_seed = int(np.random.SeedSequence([ctx.seed, 1]).generate_state(1)[0]
                    & 0x7FFFFFFF)

    with rec.span("data"):
        data = datagen.generate(cfg, ctx.seed)
    with rec.span("plan"):
        sampler = _program_sampler(cfg, data, ctx.devices)
    rec.counters["plan_s"] = rec.durations("plan")[0]
    if hasattr(sampler, "plan_stats"):
        ctx.layer["plan_stats"] = sampler.plan_stats()

    n_check = int(traffic["check_sweeps"])
    state = sampler.init(init_seed)
    init = _host_state(sampler, state)
    states = []
    for _ in range(n_check):
        with rec.span("warmup_sweep"):
            state = jax.block_until_ready(sampler.sweep(state))
        states.append(_host_state(sampler, state))
    ctx.setup_done()

    ahead = int(float(traffic["ahead_seconds"]) / rec.durations("warmup_sweep")[-1])
    ahead = min(max(ahead, 1), int(traffic["ahead_max"]))
    sweeps = 0
    seconds = ctx.window_seconds(traffic)
    pending = collections.deque()
    done = []
    with ctx.window() as win:
        while time.perf_counter() - win.t0 < seconds:
            with rec.span("sweep"):
                state = sampler.sweep(state)
            pending.append(state.step)
            sweeps += 1
            if len(pending) > ahead:
                with rec.span("wait"):
                    jax.block_until_ready(pending.popleft())
                done.append(time.perf_counter())
        while pending:
            with rec.span("wait"):
                jax.block_until_ready(pending.popleft())
            done.append(time.perf_counter())
    elapsed = win.t1 - win.t0

    n_train = data.train.nnz
    du, di = datagen.degrees(data.train, 0), datagen.degrees(data.train, 1)
    ctx.layer["sweeps"] = sweeps
    ctx.layer["sweep_seconds"] = elapsed
    ctx.layer["sweep_flops"] = work.sweep_flops(
        n_train, int((du > 0).sum()), int((di > 0).sum()), cfg["k"])
    ctx.read_device()
    del sampler, state
    sides = strata(data.train, int(traffic["per_stratum"]), ctx.seed)
    program, control = reference_mesh.check(cfg, sides, init, states, init_seed,
                                            ctx.limits, control=ctx.control)
    gaps = np.diff(done)
    notes = ["plan stats: " + str(ctx.layer.get("plan_stats")),
             "warm-up sweeps (s): " + ", ".join(f"{t:.3f}" for t in rec.durations("warmup_sweep")),
             f"window: {sweeps} sweeps in {elapsed:.3f} s, {ahead} queued ahead; "
             "between completions (s): " + ", ".join(f"{t:.3f}" for t in gaps)]
    if control:
        notes += [f"program reads {c.name} = {c.value!r}" for c in program]
    return {
        "attempted": sweeps, "failed": 0, "compares": control or program,
        "notes": notes,
        "metrics": {"train_updates_per_s": work.sweep_updates(n_train) * sweeps / elapsed},
    }


def strata(train: datagen.Ratings, per_stratum: int, seed: int) -> dict:
    """The entities to check on each side, `per_stratum` from every degree
    stratum plus the unrated and the heaviest (drawn as `train_sweeps`
    draws them), with the centred ratings grouped by entity."""
    m, n = train.shape
    rng = np.random.default_rng([seed, 2])
    centered = train.vals.astype(np.float64) - float(np.mean(train.vals, dtype=np.float64))
    u_side = datagen.csr(train.rows, train.cols, centered, m)
    v_side = datagen.csr(train.cols, train.rows, centered, n)
    users = ref.strata_sample(np.diff(u_side[0]), per_stratum, rng)
    items = ref.strata_sample(np.diff(v_side[0]), per_stratum, rng)
    return {"u": (users, *u_side), "v": (items, *v_side)}
