"""Minibatch SGLD trainers: per-step cost decoupled from dataset size.

Exact Gibbs (core.gibbs / core.distributed) touches every rating each
sweep, so training cost grows linearly with the dataset no matter how fast
the per-rating kernels get. Stochastic gradient Langevin dynamics (Welling
& Teh 2011; distributed for matrix factorization by Ahn et al., arXiv
1503.01596) replaces the exact conditional draw with a noisy gradient step

    x <- x + (eps/2) G (grad log p(x | rest))  +  sqrt(eps G) z,   z ~ N(0, I)

whose likelihood gradient is estimated from a minibatch of rating-plan
rows and rescaled by the inverse inclusion probability, so each step costs
O(|minibatch|) regardless of |ratings|. Crucially the samplers here are
NOT a fork of the data layout: minibatch rows are subsampled from the SAME
bucketed plans (`core.buckets`) and grid plans (`core.partition`) the
Gibbs engines sweep, so the planner, the distributed exchange
(ring/allgather/async), and the serving hand-off all carry over.

Three deliberate choices, each load-bearing:

* Sampling is uniform-with-replacement over PLAN ROWS (`jax.random.randint`),
  not a permutation — drawing s row ids is O(s), while a permutation is
  O(rows) and would silently reintroduce the dataset-size term this engine
  exists to remove. A row of width w carries up to w ratings of one
  entity; scaling each sampled row's gradient by rows/s makes the
  estimator exactly unbiased for the full-plan gradient (padding rows are
  masked to zero, identical to the Gibbs treatment).
* The per-entity preconditioner takes its SHAPE from the degree profile
  the balanced planner fits widths to — G_i = 1 / (lam_bar + alpha d_i
  sig2_bar) — but calibrates the two amplitudes online: lam_bar is the
  mean diagonal of the current hyper precision and sig2_bar the current
  per-coordinate second moment of the counterpart factors. Factor
  coordinates live at scale ~1/sqrt(K), so a fixed 1/(1 + alpha d) gain
  would understate the prior curvature by ~K and diverge. As in pSGLD,
  the state-dependent-preconditioner drift term is ignored.
* Hyperparameters keep their EXACT Normal-Wishart Gibbs draw each step
  (sufficient statistics are O(entities), not O(ratings)) — the mixed
  Gibbs/SGLD scheme of Ahn et al. Half-steps alternate exactly like the
  Gibbs sweep: movies from (minibatch, U), users from (minibatch, V).

`SGLDSampler` subclasses `GibbsSampler`, inheriting plans, the
posterior-predictive accumulator, and the serving hand-off (`run(store=...,
publish=...)` retains and publishes draws through the identical
SAMPLE_KEYS schema). `DistributedSGLD` subclasses `DistributedBPMF`,
riding the same block partition and all three exchange modes; async mode
keeps the stale-by-one `v_eval` semantics.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import (
    AXIS,
    DIST_MODES,
    DistributedBPMF,
    DistState,
    _per_item_noise,
    _stats,
)
from repro.core.gibbs import DeviceBucket, GibbsSampler, factor_stats
from repro.core.hyper import HyperParams, NWPrior, sample_normal_wishart
from repro.data.sparse import SparseRatings

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# shared numerics (single-host and distributed phases both route through
# these; the exactness unit tests pin them against dense numpy)
# ---------------------------------------------------------------------------

def sgld_step_schedule(
    step, *, peak: float, decay: float = 0.33, t0: float = 200.0,
    floor: float = 0.0,
):
    """Polynomial SGLD step-size decay: eps_t = peak * (t0 / (t0 + t))^decay.

    The Welling & Teh (2011) a(b+t)^-gamma family, reparameterized so
    `peak` IS eps_0 (no coupled a/b algebra when tuning). `decay` < 1
    keeps the step sum divergent (the chain keeps exploring) while the
    discretization bias shrinks; `floor` optionally pins a terminal step
    size for infinite-horizon serving runs where a fully decayed chain
    would stop mixing.
    """
    step = jnp.asarray(step, jnp.float32)
    eps = peak * (t0 / (t0 + step)) ** decay
    return jnp.maximum(eps, floor)


def row_grads(factors, counterpart, idx, val, msk, items):
    """Per-row likelihood gradient contributions for the row's owning entity.

    For plan rows (idx (s, w) counterpart ids, val/msk (s, w)) owned by
    entities `items` (s,), returns (s, K) rows of
        g_row = sum_w msk * (r - u_item . v_j) * v_j
    i.e. d/du of -0.5 * sum (r - u.v)^2 restricted to the row's ratings.
    The caller scatter-adds rows into their entities and scales by alpha
    and the inverse inclusion probability.
    """
    vg = counterpart[idx]                               # (s, w, K)
    ug = factors[items]                                 # (s, K)
    pred = jnp.einsum("sk,swk->sw", ug, vg)
    resid = (val - pred) * msk
    return jnp.einsum("sw,swk->sk", resid, vg)


def minibatch_likelihood_grad(
    key: jax.Array,
    factors: jax.Array,
    counterpart: jax.Array,
    buckets: Sequence[DeviceBucket],
    n_rows: Sequence[int],
    scales: Sequence[float],
) -> jax.Array:
    """Unbiased minibatch estimate of the full-plan likelihood gradient.

    Per bucket b, draws n_rows[b] row ids uniformly with replacement
    (O(n_rows), dataset-size independent) and scales the summed row
    gradients by scales[b] = rows_b / n_rows[b]. A bucket whose quota
    covers every row short-circuits to the exact sum over arange(rows) —
    so a large enough minibatch degrades gracefully to full-gradient
    Langevin, which is what the exactness tests pin.
    """
    g = jnp.zeros_like(factors)
    for b, (bucket, s_b, scale) in enumerate(zip(buckets, n_rows, scales)):
        r_total = bucket.indices.shape[0]
        if s_b >= r_total:
            rows = jnp.arange(r_total)
        else:
            kb = jax.random.fold_in(key, b)
            rows = jax.random.randint(kb, (s_b,), 0, r_total)
        items = bucket.seg_item_ids[bucket.seg_ids[rows]]
        g_rows = row_grads(
            factors, counterpart,
            bucket.indices[rows], bucket.values[rows], bucket.mask[rows],
            items,
        )
        g = g.at[items].add(scale * g_rows)
    return g


def precond_gain(degrees, alpha, lam_bar, sig2_bar):
    """Per-entity SGLD gain G_i = 1 / (lam_bar + alpha * d_i * sig2_bar).

    `degrees` is the planner's per-entity rating-count profile; `lam_bar`
    (mean diagonal of the hyper precision) and `sig2_bar` (per-coordinate
    second moment of the counterpart factors) calibrate the prior and
    likelihood curvature scales online. G_i approximates the inverse
    per-coordinate posterior precision, so the effective per-coordinate
    step eps * G_i * P_i stays ~eps across the degree spectrum.
    """
    return 1.0 / (lam_bar + alpha * degrees * sig2_bar)


def langevin_update(key, factors, grad, gain, eps, temperature, clip=3.0):
    """x + (eps/2) G grad + sqrt(eps G T) z, gain per entity (broadcast over K).

    The drift is clipped elementwise to `clip` times the T=1 noise scale
    sqrt(eps G) — a scale-free trust region. Inverse-inclusion scaling
    makes rare wide-row draws kick popular entities by multiples of the
    factor scale (variance ~ scale * row energy), and un-clipped those
    kicks feed back through the residuals into a runaway. At equilibrium
    the typical drift is ~sqrt(eps) noise-scales, far inside the clip, so
    the stationary distribution is untouched; only transient and
    outlier-minibatch kicks are bounded. clip=None disables.
    """
    z = jax.random.normal(key, factors.shape, factors.dtype)
    step = eps * gain[:, None]
    drift = 0.5 * step * grad
    if clip is not None:
        # tied to the T=1 noise scale, NOT the tempered one — a cooled
        # chain (temperature < 1, e.g. during warmup) must keep its drift
        lim = clip * jnp.sqrt(step)
        drift = jnp.clip(drift, -lim, lim)
    return factors + drift + jnp.sqrt(step * temperature) * z


def _lam_bar(hyper: HyperParams) -> jax.Array:
    k = hyper.lam.shape[-1]
    return jnp.trace(hyper.lam) / k


def effective_temperature(step, temperature: float, temp_warmup: int):
    """Annealed temperature: ramps 0 -> `temperature` linearly over the
    first `temp_warmup` steps (0 disables — constant temperature).

    During the ramp the chain is preconditioned minibatch SGD with damped
    injected noise — the stochastic-optimization phase of Welling & Teh's
    SGLD picture — which descends to the posterior bulk far faster than
    the full-temperature chain (the injected noise otherwise dominates
    the early drift signal). Annealed steps land inside burn-in, which is
    discarded anyway; only the T = `temperature` regime is sampled from."""
    if temp_warmup <= 0:
        return temperature
    ramp = jnp.minimum(1.0, step.astype(jnp.float32) / temp_warmup)
    return temperature * ramp


def data_init_scale(vals: np.ndarray, k: int) -> float:
    """Init-factor std matched to the data: k * s^4 ~= var(ratings), so
    u.v predictions start at the ratings' scale instead of ~0.

    The Gibbs engines don't care (one exact sweep snaps factors to the
    conditional posterior regardless of init), but SGLD bootstraps
    through a feedback loop — small factors -> large hyper precision ->
    tiny preconditioned gain -> factors grow slowly — that a 0.1-scale
    init turns into hundreds of wasted steps on well-populated data.
    Floored at the Gibbs 0.1 so degenerate/empty data keeps the old
    behavior."""
    var = float(np.var(vals)) if len(vals) else 0.0
    return max(0.1, (max(var, 1e-8) / k) ** 0.25)


def alloc_minibatch(plan_host, lanes_budget: int):
    """Split a lane budget across a plan's buckets, proportional to each
    bucket's share of total padded lanes (rows * width): wide buckets get
    fewer rows so every bucket contributes ~equal compute. Returns
    (rows_per_bucket, inverse_inclusion_scales); a bucket capped at its
    own row count gets scale 1.0 (exact)."""
    rows = np.array([b.indices.shape[0] for b in plan_host.buckets], np.float64)
    lanes = rows * np.array([b.width for b in plan_host.buckets], np.float64)
    total = lanes.sum()
    n_rows, scales = [], []
    for b, r, l in zip(plan_host.buckets, rows, lanes):
        s = int(min(r, max(1.0, round(lanes_budget * l / total / b.width))))
        n_rows.append(s)
        scales.append(float(r) / s)
    return tuple(n_rows), tuple(scales)


# ---------------------------------------------------------------------------
# single-host sampler
# ---------------------------------------------------------------------------

class SGLDSampler(GibbsSampler):
    """Single-host minibatch SGLD over the same bucketed plans as Gibbs.

    `minibatch` is a PADDED-LANE budget per half-step: each bucket samples
    ~minibatch * share_of_lanes / width rows, so the per-step gather and
    einsum cost tracks the budget, not the dataset (sum s_b * w_b ~=
    minibatch). Steps are ~|ratings| / minibatch cheaper than a Gibbs
    sweep; run correspondingly more of them (`burn_in` and `thin` are in
    steps). Everything downstream of the chain — posterior-predictive
    RMSE, SampleStore retention, PublicationChannel publishes — is
    inherited unchanged from GibbsSampler.
    """

    verbose_every = 50

    def __init__(
        self,
        ratings: SparseRatings,
        test: SparseRatings | None = None,
        *,
        k: int = 64,
        alpha: float = 1.5,
        burn_in: int = 200,
        widths="balanced",
        minibatch: int = 4096,
        step_size: float = 0.3,
        step_decay: float = 0.33,
        step_t0: float = 100.0,
        temperature: float = 1.0,
        temp_warmup: int = 0,
        precondition: bool = True,
        clip: float | None = 3.0,
        hyper_every: int = 1,
        accum_every: int = 1,
        dtype=jnp.float32,
    ):
        self.minibatch = int(minibatch)
        self.step_size = float(step_size)
        self.step_decay = float(step_decay)
        self.step_t0 = float(step_t0)
        self.temperature = float(temperature)
        self.temp_warmup = int(temp_warmup)
        self.precondition = bool(precondition)
        self.clip = None if clip is None else float(clip)
        # Per-step costs the minibatch does NOT bound, thinned under
        # lax.cond so skipped steps pay nothing: the exact NW hyper draw
        # is O(entities * K^2) (sufficient-stats syrk) and the
        # posterior-predictive accumulation is O(|test| * K). Both are
        # slowly-mixing relative to the factor chain, so drawing hypers /
        # accumulating every few steps is standard MCMC thinning, not an
        # approximation of the stationary distribution.
        self.hyper_every = int(hyper_every)
        self.accum_every = int(accum_every)
        super().__init__(
            ratings, test, k=k, alpha=alpha, burn_in=burn_in, widths=widths,
            engine="einsum", dtype=dtype,
        )
        self.user_rows, self.user_scales = alloc_minibatch(
            self.user_plan_host, self.minibatch
        )
        self.item_rows, self.item_scales = alloc_minibatch(
            self.item_plan_host, self.minibatch
        )
        # the planner's degree profile, reused as the preconditioner shape
        self.deg_u = jnp.asarray(ratings.degrees(0).astype(np.float32))
        self.deg_v = jnp.asarray(ratings.degrees(1).astype(np.float32))
        self.init_scale = data_init_scale(ratings.vals, self.k)

    def init(self, seed: int = 0):
        state = super().init(seed)
        s = self.init_scale / 0.1
        return state._replace(u=state.u * s, v=state.v * s)

    def _gain(self, degrees, hyper, counterpart):
        if not self.precondition:
            return jnp.ones_like(degrees)
        # per-coordinate second moment of the counterpart = the trace of
        # its sum_xxt / (n k), but computed in O(n k) — no syrk needed
        sig2 = jnp.mean(counterpart * counterpart)
        return precond_gain(degrees, self.alpha, _lam_bar(hyper), sig2)

    # --- one SGLD step (two preconditioned Langevin half-steps) ---
    def _sweep_impl(self, state, plans):
        item_buckets, user_buckets = plans
        key, k_hv, k_hu, k_sv, k_su, k_nv, k_nu = jax.random.split(state.key, 7)
        eps = sgld_step_schedule(
            state.step, peak=self.step_size, decay=self.step_decay,
            t0=self.step_t0,
        )
        temp = effective_temperature(
            state.step, self.temperature, self.temp_warmup
        )

        # exact Normal-Wishart hyper draws from the previous factors (the
        # mixed scheme: sufficient stats are O(entities), never
        # O(ratings)); thinned every hyper_every steps behind a cond so
        # the O(entities * K^2) stats syrk is skipped entirely in between
        def draw_hypers(_):
            sv = factor_stats(state.v)
            su = factor_stats(state.u)
            return (
                sample_normal_wishart(k_hv, sv.sum_x, sv.sum_xxt, sv.n, self.prior),
                sample_normal_wishart(k_hu, su.sum_x, su.sum_xxt, su.n, self.prior),
            )

        hyper_v, hyper_u = jax.lax.cond(
            jnp.mod(state.step, self.hyper_every) == 0,
            draw_hypers, lambda _: (state.hyper_v, state.hyper_u), None,
        )

        # movies half-step: minibatch gradient of V given U
        g_lik = minibatch_likelihood_grad(
            k_sv, state.v, state.u, item_buckets,
            self.item_rows, self.item_scales,
        )
        grad_v = self.alpha * g_lik - (state.v - hyper_v.mu) @ hyper_v.lam
        v_new = langevin_update(
            k_nv, state.v, grad_v,
            self._gain(self.deg_v, hyper_v, state.u), eps, temp,
            clip=self.clip,
        )

        # users half-step: minibatch gradient of U given the new V
        g_lik = minibatch_likelihood_grad(
            k_su, state.u, v_new, user_buckets,
            self.user_rows, self.user_scales,
        )
        grad_u = self.alpha * g_lik - (state.u - hyper_u.mu) @ hyper_u.lam
        u_new = langevin_update(
            k_nu, state.u, grad_u,
            self._gain(self.deg_u, hyper_u, v_new), eps, temp,
            clip=self.clip,
        )

        # posterior-predictive accumulation, thinned: the O(|test| * K)
        # einsum runs only on accumulated steps (cond, not where — the
        # skipped branch must cost nothing for per-step cost to stay
        # decoupled from |test|)
        collect = (state.step >= self.burn_in) & (
            jnp.mod(state.step - self.burn_in, self.accum_every) == 0
        )

        def accum(carry):
            ps, pc = carry
            preds = (
                jnp.einsum("nk,nk->n", u_new[self.test_rows], v_new[self.test_cols])
                + self.global_mean
            )
            return ps + preds, pc + 1

        pred_sum, pred_count = jax.lax.cond(
            collect, accum, lambda c: c, (state.pred_sum, state.pred_count)
        )

        return state._replace(
            u=u_new, v=v_new, hyper_u=hyper_u, hyper_v=hyper_v,
            key=key, step=state.step + 1,
            pred_sum=pred_sum, pred_count=pred_count,
        )


# ---------------------------------------------------------------------------
# distributed sampler: same grid partition + exchange modes as Gibbs
# ---------------------------------------------------------------------------

class SGLDConfig(NamedTuple):
    step_size: float
    step_decay: float
    step_t0: float
    temperature: float
    temp_warmup: int
    u_rows: int          # sampled rows per (shard, block) in the user phase
    v_rows: int
    precondition: bool
    clip: float | None


def _sgld_grad_block(factors_pad, counter_blk, idx, val, msk, seg, n_loc,
                     key, s_rows):
    """Scaled minibatch gradient of local items against one counterpart
    block. `factors_pad` is the local factor block with a zero pad slot
    appended (seg == n_loc rows are plan padding; their msk is zero, so
    they contribute nothing — sampling them merely wastes a lane, the
    same deal the Gibbs engines accept)."""
    r_total = idx.shape[0]
    if s_rows < r_total:
        rows = jax.random.randint(key, (s_rows,), 0, r_total)
        scale = r_total / s_rows
        idx, val, msk, seg = idx[rows], val[rows], msk[rows], seg[rows]
    else:
        scale = 1.0
    k = counter_blk.shape[-1]
    g_rows = row_grads(factors_pad, counter_blk, idx, val, msk, seg)
    g = jnp.zeros((n_loc + 1, k), jnp.float32).at[seg].add(g_rows)
    return scale * g[:n_loc]


def _pad_slot(factors_loc):
    k = factors_loc.shape[-1]
    return jnp.concatenate(
        [factors_loc, jnp.zeros((1, k), factors_loc.dtype)]
    )


def _sgld_phase_ring(key_sel, counter_blk, plans, factors_loc, n_shards,
                     s_rows):
    """Accumulate the minibatch likelihood gradient over the P ring steps.

    Identical overlap structure to the Gibbs ring phase: the ppermute of
    step s+1 has no data dependence on step s's gradient block, so the
    collective hides behind the compute. Selection keys fold (shard, ring
    step) into the phase key — distinct blocks draw independent rows.
    """
    idx_all, val_all, msk_all, seg_all = plans[:4]
    n_loc = factors_loc.shape[0]
    k = factors_loc.shape[-1]
    pid = jax.lax.axis_index(AXIS)
    f_pad = _pad_slot(factors_loc)
    fwd = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def step(carry, s):
        blk, g = carry
        src = jnp.mod(pid - s, n_shards)
        take = lambda a: jnp.take(a, src, axis=0)
        kb = jax.random.fold_in(jax.random.fold_in(key_sel, pid), s)
        dg = _sgld_grad_block(
            f_pad, blk, take(idx_all), take(val_all), take(msk_all),
            take(seg_all), n_loc, kb, s_rows,
        )
        blk = jax.lax.ppermute(blk, AXIS, fwd)
        return (blk, g + dg), None

    g0 = jnp.zeros((n_loc, k), jnp.float32)
    (_, g), _ = jax.lax.scan(step, (counter_blk, g0), jnp.arange(n_shards))
    return g


def _sgld_phase_allgather(key_sel, counter_blk, plan_full, factors_loc,
                          n_shards, s_rows):
    """Sync baseline: gather the whole counterpart, one flat-plan draw."""
    full = jax.lax.all_gather(counter_blk, AXIS)
    full = full.reshape(-1, full.shape[-1])
    idx, val, msk, seg = plan_full[:4]
    n_loc = factors_loc.shape[0]
    pid = jax.lax.axis_index(AXIS)
    kb = jax.random.fold_in(key_sel, pid)
    return _sgld_grad_block(
        _pad_slot(factors_loc), full, idx, val, msk, seg, n_loc, kb,
        n_shards * s_rows,
    )


def _sgld_phase_async(kv_sel, ku_sel, u_blk, v_blk, v_plans, u_plans,
                      v_loc, u_loc, n_shards, v_rows, u_rows):
    """Both half-step gradients fused into ONE stale-tolerant ring scan.

    As in the Gibbs async mode, each step issues the next blocks'
    ppermutes before either gradient consumes its held operand, and the
    user gradient reads the PREVIOUS step's v (the carry) — stale by
    exactly one SGLD step, far inside the staleness Gibbs itself
    tolerates. The caller pairs the returned u with v_eval = the stale v.
    """
    n_v = v_loc.shape[0]
    n_u = u_loc.shape[0]
    k = u_blk.shape[-1]
    pid = jax.lax.axis_index(AXIS)
    fwd = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    vp = _pad_slot(v_loc)
    up = _pad_slot(u_loc)

    def step(carry, s):
        ub, vb, gv, gu = carry
        src = jnp.mod(pid - s, n_shards)
        take = lambda plans: tuple(jnp.take(a, src, axis=0) for a in plans[:4])
        ub_next = jax.lax.ppermute(ub, AXIS, fwd)
        vb_next = jax.lax.ppermute(vb, AXIS, fwd)
        kbv = jax.random.fold_in(jax.random.fold_in(kv_sel, pid), s)
        kbu = jax.random.fold_in(jax.random.fold_in(ku_sel, pid), s)
        dgv = _sgld_grad_block(vp, ub, *take(v_plans), n_v, kbv, v_rows)
        dgu = _sgld_grad_block(up, vb, *take(u_plans), n_u, kbu, u_rows)
        return (ub_next, vb_next, gv + dgv, gu + dgu), None

    init = (
        u_blk, v_blk,
        jnp.zeros((n_v, k), jnp.float32), jnp.zeros((n_u, k), jnp.float32),
    )
    (_, _, gv, gu), _ = jax.lax.scan(step, init, jnp.arange(n_shards))
    return gv, gu


def _sgld_finish(k_noise, factors, g_lik, item_ids, hyper, alpha, gain,
                 eps, temperature, clip=3.0):
    """Gradient + prior + per-item noise -> preconditioned Langevin step.

    Noise is keyed by GLOBAL item id (`_per_item_noise`), so like the
    Gibbs modes the update is layout-independent; pad slots (id < 0) are
    zeroed after the step. The drift carries the same noise-std trust
    region as `langevin_update` (see there for why)."""
    grad = alpha * g_lik - (factors - hyper.mu) @ hyper.lam
    z = _per_item_noise(k_noise, item_ids, factors.shape[-1])
    step = eps * gain[:, None]
    drift = 0.5 * step * grad
    if clip is not None:
        lim = clip * jnp.sqrt(step)
        drift = jnp.clip(drift, -lim, lim)
    new = factors + drift + jnp.sqrt(step * temperature) * z
    return jnp.where(item_ids[:, None] >= 0, new, 0.0)


def make_sgld_sweep(mesh: Mesh, mode: str, alpha: float, prior: NWPrior,
                    cfg: SGLDConfig):
    """shard_map'd SGLD step over grid plans: peer of distributed.make_sweep.

    Plans are the same 6-tuples the Gibbs sweep takes (only idx/val/msk/seg
    are consumed — gradients need no dense-segment relabeling); the two
    extra operands are the per-shard degree vectors feeding the
    preconditioner."""
    if mode not in DIST_MODES:
        raise ValueError(f"mode must be one of {DIST_MODES}, got {mode!r}")
    n_shards = mesh.shape[AXIS]

    def sweep(state: DistState, u_plans, v_plans, u_ids, v_ids, u_deg, v_deg):
        key, k_hv, k_hu, k_sv, k_su, k_nv, k_nu = jax.random.split(state.key, 7)
        u_plans = tuple(a[0] for a in u_plans)
        v_plans = tuple(a[0] for a in v_plans)
        u_ids, v_ids = u_ids[0], v_ids[0]
        u_deg, v_deg = u_deg[0], v_deg[0]
        eps = sgld_step_schedule(
            state.step, peak=cfg.step_size, decay=cfg.step_decay,
            t0=cfg.step_t0,
        )
        temp = effective_temperature(
            state.step, cfg.temperature, cfg.temp_warmup
        )

        # exact hyper draws from psum'd sufficient stats (previous factors)
        sv = _stats(state.v[0], v_ids >= 0)
        hyper_v = sample_normal_wishart(k_hv, *sv, prior)
        su = _stats(state.u[0], u_ids >= 0)
        hyper_u = sample_normal_wishart(k_hu, *su, prior)

        def gain(deg, hyper, counter_stats):
            if not cfg.precondition:
                return jnp.ones_like(deg)
            _, sum_xxt, n = counter_stats
            sig2 = jnp.trace(sum_xxt) / (n * state.u.shape[-1])
            return precond_gain(deg, alpha, _lam_bar(hyper), sig2)

        g_v = gain(v_deg, hyper_v, su)
        g_u = gain(u_deg, hyper_u, sv)

        if mode == "async":
            glv, glu = _sgld_phase_async(
                k_sv, k_su, state.u[0], state.v[0], v_plans, u_plans,
                state.v[0], state.u[0], n_shards, cfg.v_rows, cfg.u_rows,
            )
            v_new = _sgld_finish(k_nv, state.v[0], glv, v_ids, hyper_v,
                                 alpha, g_v, eps, temp, clip=cfg.clip)
            u_new = _sgld_finish(k_nu, state.u[0], glu, u_ids, hyper_u,
                                 alpha, g_u, eps, temp, clip=cfg.clip)
            return DistState(
                u=u_new[None], v=v_new[None],
                hyper_u=hyper_u, hyper_v=hyper_v,
                key=key, step=state.step + 1,
                v_eval=state.v,   # u_new's gradient read this v
            )

        if mode == "ring":
            glv = _sgld_phase_ring(k_sv, state.u[0], v_plans, state.v[0],
                                   n_shards, cfg.v_rows)
        else:
            glv = _sgld_phase_allgather(k_sv, state.u[0], v_plans,
                                        state.v[0], n_shards, cfg.v_rows)
        v_new = _sgld_finish(k_nv, state.v[0], glv, v_ids, hyper_v, alpha,
                             g_v, eps, temp, clip=cfg.clip)

        if mode == "ring":
            glu = _sgld_phase_ring(k_su, v_new, u_plans, state.u[0],
                                   n_shards, cfg.u_rows)
        else:
            glu = _sgld_phase_allgather(k_su, v_new, u_plans, state.u[0],
                                        n_shards, cfg.u_rows)
        u_new = _sgld_finish(k_nu, state.u[0], glu, u_ids, hyper_u, alpha,
                             g_u, eps, temp, clip=cfg.clip)

        return DistState(
            u=u_new[None], v=v_new[None], hyper_u=hyper_u, hyper_v=hyper_v,
            key=key, step=state.step + 1,
        )

    state_spec = DistState(
        u=P(AXIS), v=P(AXIS),
        hyper_u=HyperParams(P(), P()), hyper_v=HyperParams(P(), P()),
        key=P(), step=P(),
        v_eval=P(AXIS) if mode == "async" else None,
    )
    plans_in = tuple(P(AXIS) for _ in range(6))
    return jax.shard_map(
        sweep,
        mesh=mesh,
        in_specs=(state_spec, plans_in, plans_in, P(AXIS), P(AXIS),
                  P(AXIS), P(AXIS)),
        out_specs=state_spec,
        check_vma=False,
    )


class DistributedSGLD(DistributedBPMF):
    """Multi-device minibatch SGLD over the Gibbs grid partition.

    Rides the exact plans, LPT entity sharding, and exchange modes of
    DistributedBPMF — only the per-block work changes (a sampled gradient
    block instead of a full syrk) and the finish step is a preconditioned
    Langevin update instead of a Cholesky draw. `minibatch` is the padded
    lane budget per shard per half-step, split evenly across the P blocks
    a shard visits (ring/async) or drawn at once from the flattened plan
    (allgather).
    """

    verbose_every = 50

    def __init__(
        self,
        ratings: SparseRatings,
        test: SparseRatings | None = None,
        *,
        mesh: Mesh | None = None,
        k: int = 32,
        alpha: float = 1.5,
        width: int | str = 32,
        mode: str = "ring",
        minibatch: int = 4096,
        step_size: float = 0.3,
        step_decay: float = 0.33,
        step_t0: float = 100.0,
        temperature: float = 1.0,
        temp_warmup: int = 0,
        precondition: bool = True,
        clip: float | None = 3.0,
        seed: int = 0,
    ):
        self.minibatch = int(minibatch)
        self.step_size = float(step_size)
        self.step_decay = float(step_decay)
        self.step_t0 = float(step_t0)
        self.temperature = float(temperature)
        self.temp_warmup = int(temp_warmup)
        self.precondition = bool(precondition)
        self.clip = None if clip is None else float(clip)
        self._degrees = (
            np.asarray(ratings.degrees(0), np.float32),
            np.asarray(ratings.degrees(1), np.float32),
        )
        self.init_scale = data_init_scale(ratings.vals, k)
        super().__init__(
            ratings, test, mesh=mesh, k=k, alpha=alpha, width=width,
            mode=mode, engine="einsum", seed=seed,
        )

    def init(self, seed: int = 0):
        state = super().init(seed)
        s = self.init_scale / 0.1
        u, v = state.u * s, state.v * s
        return state._replace(
            u=u, v=v, v_eval=v if self.mode == "async" else None
        )

    def _shard_degrees(self, degrees, part):
        """Per-entity degrees in plan layout (P, n_loc); pad slots get 0,
        so their gain is the finite 1/lam_bar and the finish mask zeroes
        them regardless."""
        ids = part.ids
        d = np.where(ids >= 0, degrees[np.maximum(ids, 0)], 0.0)
        sh = NamedSharding(self.mesh, P(AXIS))
        return jax.device_put(jnp.asarray(d, jnp.float32), sh)

    def _build_sweep(self):
        self.u_ring, self.u_ids = self._device_plans(self.u_plan)
        self.v_ring, self.v_ids = self._device_plans(self.v_plan)
        if self.mode == "allgather":
            self.u_flat = self._flat_plans(self.u_plan)
            self.v_flat = self._flat_plans(self.v_plan)
        self.u_deg = self._shard_degrees(self._degrees[0], self.u_part)
        self.v_deg = self._shard_degrees(self._degrees[1], self.v_part)

        def rows_per_block(plan):
            _, _, r, w = plan.indices.shape
            return int(min(r, max(1, round(
                self.minibatch / (self.n_shards * w)
            ))))

        cfg = SGLDConfig(
            step_size=self.step_size, step_decay=self.step_decay,
            step_t0=self.step_t0, temperature=self.temperature,
            temp_warmup=self.temp_warmup,
            u_rows=rows_per_block(self.u_plan),
            v_rows=rows_per_block(self.v_plan),
            precondition=self.precondition, clip=self.clip,
        )
        mapped = make_sgld_sweep(self.mesh, self.mode, self.alpha,
                                 self.prior, cfg)
        u_plans = self.u_flat if self.mode == "allgather" else self.u_ring
        v_plans = self.v_flat if self.mode == "allgather" else self.v_ring

        self._plan_args = (u_plans, v_plans, self.u_ids, self.v_ids,
                           self.u_deg, self.v_deg)
        return jax.jit(mapped)
