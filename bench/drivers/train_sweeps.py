"""Traffic kind `train_sweeps`: back-to-back Gibbs sweeps.

Set-up generates the configuration's ratings from the seed, builds the
sampler (the planner's time is `plan_s`), and drives it from the seed
through its first `check_sweeps` sweeps with the window's own call,
keeping host copies of each state. The window then dispatches sweeps
back to back, keeping `ahead_seconds` of sweeps (by the last warm-up
sweep's time; at least one, at most `ahead_max`, whose outputs are all
held on the device) queued behind the one it waits for, so that a
stall of the host does not leave the chip idle. Once `--seconds` have
passed it dispatches no more, waits for every sweep it sent, and reads the
clock after that wait: all of those sweeps count, over all of that time.
After the window the float64 reference checks every checked sweep's hyper
draws and the draws of entities sampled from every degree stratum, on both
half-sweeps.

Traffic parameters (bench/traffic/<mix>.json): `check_sweeps`,
`per_stratum` (entities per degree stratum), `ahead_seconds`,
`ahead_max`, `trace_seconds`.
"""
from __future__ import annotations

import collections
import time

import numpy as np

import datagen
import reference as ref
import work
from harness import Compare


def _program_sampler(cfg: dict, data: datagen.Dataset):
    """The system under test, built as `launch/train.py` builds it."""
    from repro.core import GibbsSampler
    from repro.data.sparse import SparseRatings

    def to_program(r: datagen.Ratings) -> SparseRatings:
        return SparseRatings(rows=r.rows, cols=r.cols, vals=r.vals, shape=r.shape)

    kw = {} if cfg.get("engine") is None else {"engine": cfg["engine"]}
    return GibbsSampler(to_program(data.train), to_program(data.test), k=cfg["k"],
                        alpha=cfg["alpha"], widths=cfg["widths"], **kw)


def _host_state(state) -> dict:
    """The draws and hypers of a state, on the host."""
    return {
        "u": np.asarray(state.u, np.float64), "v": np.asarray(state.v, np.float64),
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
        sampler = _program_sampler(cfg, data)
    rec.counters["plan_s"] = rec.durations("plan")[0]

    n_check = int(traffic["check_sweeps"])
    state = sampler.init(init_seed)
    states = []
    for _ in range(n_check):
        with rec.span("warmup_sweep"):
            state = jax.block_until_ready(sampler.sweep(state))
        states.append(_host_state(state))
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
    program, control = check(cfg, traffic, data, states, init_seed, ctx.seed,
                             ctx.limits, control=ctx.control)
    gaps = np.diff(done)
    notes = ["warm-up sweeps (s): " + ", ".join(f"{t:.3f}" for t in rec.durations("warmup_sweep")),
             f"window: {sweeps} sweeps in {elapsed:.3f} s, {ahead} queued ahead; "
             "between completions (s): " + ", ".join(f"{t:.3f}" for t in gaps)]
    if control:
        notes += [f"program reads {c.name} = {c.value!r}" for c in program]
    return {
        "attempted": sweeps, "failed": 0, "compares": control or program,
        "notes": notes,
        "metrics": {"train_updates_per_s": work.sweep_updates(n_train) * sweeps / elapsed},
    }


def check(cfg: dict, traffic: dict, data: datagen.Dataset, states: list[dict],
          init_seed: int, seed: int, limits: dict,
          control: bool = False) -> tuple[list[Compare], list[Compare] | None]:
    """Reference of each checked sweep, half by half. The first sweep's
    items see the reference's own initial U; every later half sees the
    program's state it was given. Returns the program's gaps and, with
    `control`, the float8 control's gaps on the same inputs."""
    k, alpha = int(cfg["k"]), float(cfg["alpha"])
    m, n = data.train.shape
    rng = np.random.default_rng([seed, 2])
    train = data.train
    mean = float(np.mean(train.vals, dtype=np.float64))
    centered = train.vals.astype(np.float64) - mean
    u_side = datagen.csr(train.rows, train.cols, centered, m)
    v_side = datagen.csr(train.cols, train.rows, centered, n)
    users = ref.strata_sample(np.diff(u_side[0]), int(traffic["per_stratum"]), rng)
    items = ref.strata_sample(np.diff(v_side[0]), int(traffic["per_stratum"]), rng)

    u_prev, v_prev, key = ref.init_factors(init_seed, m, n, k)
    gaps = {"program": [0.0, 0.0], "control": [0.0, 0.0]}
    for st in states:
        keys = ref.sweep_keys(key)
        key = keys.next
        for side, rows, csr_side, x_prev, other, hk, zk, n_side in (
            ("v", items, v_side, v_prev, u_prev, keys.hyper_v, keys.v, n),
            ("u", users, u_side, u_prev, st["v"], keys.hyper_u, keys.u, m),
        ):
            var = ref.nw_variates(hk, k, n_side)
            mu_r, lam_r = ref.normal_wishart(x_prev, var)
            z = ref.normal_rows(zk, n_side, k, rows)
            want = ref.conditional_draws(other, *csr_side, rows, mu_r, lam_r,
                                         alpha, z)
            outputs = {"program": (st[f"mu_{side}"], st[f"lam_{side}"], st[side][rows])}
            if control:
                mu_c, lam_c = ref.normal_wishart(x_prev, var, control=True)
                outputs["control"] = (mu_c, lam_c, ref.conditional_draws(
                    other, *csr_side, rows, mu_c, lam_c, alpha, z, control=True))
            for who, (mu_p, lam_p, got) in outputs.items():
                g = gaps[who]
                g[0] = max(g[0], ref.draw_gap(got, want))
                g[1] = max(g[1], ref.hyper_gap(mu_p, lam_p, mu_r, lam_r))
        u_prev, v_prev = st["u"], st["v"]

    def compares(g):
        return [Compare("draw_gap", g[0], limits["draw_gap"]),
                Compare("hyper_gap", g[1], limits["hyper_gap"])]

    return compares(gaps["program"]), compares(gaps["control"]) if control else None

