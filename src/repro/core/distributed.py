"""Distributed BPMF: ring-pipelined (async) and all-gather (sync) samplers.

The paper's central result (Sec 4.3, Fig 5-6) is that one-sided asynchronous
communication (GASPI) hides ~85% of communication behind computation while
bulk-synchronous exchange hides none. The TPU-idiomatic equivalent:

  "allgather"     : all_gather the counterpart factor matrix, then sweep —
                    all communication up front, none overlapped.
  "ring"          : the counterpart matrix stays sharded; each of P pipeline
                    steps computes partial precision contributions against
                    the currently-held block while lax.ppermute forwards it —
                    the permute of step s+1 has no data dependence on the
                    syrk of step s, so XLA's latency-hiding scheduler runs
                    them concurrently (the "both" region of the paper's
                    Fig 6). Phases stay sequential: the user phase waits for
                    the full v draw.
  "async"         : the stale-tolerant pipeline (paper Sec 4.3). BOTH phases
                    ride ONE ring scan: each step issues the next blocks'
                    ppermutes before either accumulate consumes its held
                    operand, then accumulates movie stats against the held u
                    block and user stats against the held v block. The user
                    update therefore reads the PREVIOUS sweep's v — stale by
                    exactly one draw, the bounded staleness Gibbs tolerates
                    (arXiv 2004.02561, 1503.01596): the chain decouples into
                    two interleaved samplers whose draws are each exactly
                    conditional, so the stationary distribution is unchanged
                    and only burn-in lengthens (~2x in sweeps, repaid >2x in
                    wall clock at moderate P). Halves the scan count per
                    sweep and removes the inter-phase barrier.

All modes share plans, keys, and per-item noise (folded from global item
ids), so they produce bit-comparable samples — the accuracy-parity claim of
Sec 5.2 is testable exactly: an async sweep's v draw is bit-identical to the
ring sweep's from the same state (the movie phase consumes identical
inputs); only the u draw sees the one-sweep-older v.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.gibbs import (
    _sweep_solver,
    chunk_rows,
    sample_mvn_precision,
    scopes_in_cache_key,
)
from repro.core.hyper import (
    HyperParams,
    NWPrior,
    default_prior,
    init_hyper,
    sample_normal_wishart,
)
from repro.core.partition import GridPlan, build_grid_plan, partition_entities
from repro.data.sparse import SparseRatings

AXIS = "items"


class DistState(NamedTuple):
    u: jax.Array          # (P, m_loc, K) user factors, sharded over AXIS
    v: jax.Array          # (P, n_loc, K)
    hyper_u: HyperParams
    hyper_v: HyperParams
    key: jax.Array
    step: jax.Array
    # async mode only (None otherwise): the v the u draw was conditioned
    # on — one sweep stale. The stale-by-one sweep interleaves two valid
    # Gibbs chains, so (u, v) at the same step are draws from DIFFERENT
    # chains whose latent rotations drift apart; predictions must pair u
    # with v_eval, the jointly-coupled sample.
    v_eval: jax.Array | None = None


# stats engines the distributed sweep supports: the einsum reference and
# the fused gather-syrk kernel (core.gibbs.ENGINES documents the family)
DIST_ENGINES = ("einsum", "fused")

# exchange modes: see the module docstring
DIST_MODES = ("ring", "allgather", "async")


def _per_item_noise(key: jax.Array, item_ids: jax.Array, k: int) -> jax.Array:
    """Noise keyed by global item id — layout-independent determinism.

    The whole id vector is folded into per-item keys in one vmapped
    threefry call, then the noise drawn in one vmapped normal
    (`jax.random.fold_in` itself accepts only scalars); under jit the pair
    fuses into a single launch. Bit-identical to folding each id
    separately — pinned by a regression test, since the ring/allgather
    parity argument depends on these exact bits.
    """
    ids = jnp.maximum(item_ids, 0)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(ids)
    return jax.vmap(lambda kk: jax.random.normal(kk, (k,), jnp.float32))(keys)


def _accumulate_block(prec, rhs, counter_blk, idx, val, msk, seg, seg_dense,
                      seg_map, *, engine="einsum"):
    """Add the statistics of local items against one counterpart block into
    the (n_loc, K, K) / (n_loc, K) accumulators.

    einsum: gathered block + row-level einsums, scatter-added by row
    segment (the equivalence-tested reference). fused: `ops.gather_syrk_seg`
    — the counterpart block is gathered in-kernel against the dense
    per-block segment ids and the per-segment outputs scatter once through
    seg_map. Slot n_loc collects the padding and is dropped. Adding into the
    accumulators in place keeps one (n_loc, K, K) buffer live, not two.
    """
    with jax.named_scope("bpmf.stats"):
        if engine == "fused":
            from repro.kernels import ops as kops

            prec_seg, rhs_seg = kops.gather_syrk_seg(
                idx, val, msk, seg_dense, idx.shape[0], counter_blk
            )
            return (prec.at[seg_map].add(prec_seg, mode="drop"),
                    rhs.at[seg_map].add(rhs_seg, mode="drop"))
        vg = counter_blk[idx]                            # (R, W, K)
        vm = vg * msk[..., None]
        prec_rows = jnp.einsum("rwk,rwl->rkl", vm, vm,
                               preferred_element_type=jnp.float32)
        rhs_rows = jnp.einsum("rwk,rw->rk", vm, val * msk)
        return (prec.at[seg].add(prec_rows, mode="drop"),
                rhs.at[seg].add(rhs_rows, mode="drop"))


def _forward(blk, n_shards):
    """Send the held block one step round the ring."""
    with jax.named_scope("bpmf.exchange"):
        return jax.lax.ppermute(
            blk, AXIS, [(i, (i + 1) % n_shards) for i in range(n_shards)]
        )


def _phase_ring(key, counter_blk, plans, item_ids, hyper, alpha, n_shards,
                engine, solver):
    """One ring half-sweep: resample local items given sharded counterpart.

    plans: (P, R, W) arrays (this shard's slice of the grid plan) keyed by
    source block id. At ring step s, this device holds block
    (pid - s) mod P; the matching plan slice is selected dynamically.
    """
    idx_all, val_all, msk_all, seg_all, segd_all, segm_all = plans
    n_loc = item_ids.shape[0]
    k = counter_blk.shape[-1]
    pid = jax.lax.axis_index(AXIS)

    def step(carry, s):
        blk, prec, rhs = carry
        src = jnp.mod(pid - s, n_shards)
        take = lambda a: jnp.take(a, src, axis=0)
        prec, rhs = _accumulate_block(
            prec, rhs, blk, take(idx_all), take(val_all), take(msk_all),
            take(seg_all), take(segd_all), take(segm_all), engine=engine,
        )
        # forward the block; independent of this step's accumulate -> overlap
        return (_forward(blk, n_shards), prec, rhs), None

    prec0 = jnp.zeros((n_loc, k, k), jnp.float32)
    rhs0 = jnp.zeros((n_loc, k), jnp.float32)
    (blk, prec, rhs), _ = jax.lax.scan(
        step, (counter_blk, prec0, rhs0), jnp.arange(n_shards)
    )
    return _finish_phase(key, prec, rhs, item_ids, hyper, alpha, solver)


def _finish_phase(key, prec, rhs, item_ids, hyper, alpha, solver):
    """Raw accumulated stats -> posterior draw for this shard's items.

    Solved in fixed-size item chunks (the last one clamped to end at n_loc,
    re-solving a few items identically) so the Cholesky factors of a full
    ChEMBL shard never coexist with its statistics. Each chunk is solved as
    the single-chip sweep solves a bucket: `solver` is
    `gibbs._sweep_solver`'s choice, the batch-on-lanes Pallas kernel on a
    TPU and the substitution solver elsewhere.
    """
    n, k = rhs.shape
    c = min(n, chunk_rows(k, k))
    with jax.named_scope("bpmf.prior"):
        z = _per_item_noise(key, item_ids, k)
        lam, lam_mu = hyper.lam[None], (hyper.lam @ hyper.mu)[None]

    def solve_chunk(i, new):
        start = jnp.minimum(i * c, n - c)
        part = lambda a: jax.lax.dynamic_slice_in_dim(a, start, c)
        with jax.named_scope("bpmf.prior"):
            prec_c = lam + alpha * part(prec)
            rhs_c = lam_mu + alpha * part(rhs)
        x = sample_mvn_precision(None, prec_c, rhs_c, z=part(z), solver=solver)
        with jax.named_scope("bpmf.prior"):
            return jax.lax.dynamic_update_slice_in_dim(new, x, start, 0)

    new = jax.lax.fori_loop(0, -(-n // c), solve_chunk, jnp.zeros_like(rhs))
    with jax.named_scope("bpmf.prior"):
        return jnp.where(item_ids[:, None] >= 0, new, 0.0)


def _phase_ring_async(k_v, k_u, u_blk, v_blk, v_plans, u_plans, v_ids, u_ids,
                      hyper_v, hyper_u, alpha, n_shards, engine, solver):
    """Both Gibbs phases fused into ONE stale-tolerant ring scan.

    Each step first issues the ppermutes that deliver step s+1's blocks —
    they read only the held (u, v) blocks, never this step's accumulates, so
    the collectives are in flight for the entire accumulate pair — then
    accumulates movie stats against the held u block and user stats against
    the held v block. v comes from the carry (previous sweep's draw): the
    user update is stale by exactly one sweep. One scan of P steps replaces
    ring mode's two, and the user phase no longer waits on the full v draw.

    The movie accumulation consumes inputs bit-identical to ring mode's, in
    the same order, so from equal states the v draw matches ring
    bit-for-bit (pinned by a parity test).
    """
    n_v = v_ids.shape[0]
    n_u = u_ids.shape[0]
    k = u_blk.shape[-1]
    pid = jax.lax.axis_index(AXIS)

    def step(carry, s):
        ub, vb, pv, rv, pu, ru = carry
        src = jnp.mod(pid - s, n_shards)
        take = lambda plans: tuple(jnp.take(a, src, axis=0) for a in plans)
        # next blocks, issued before either accumulate touches the held ones
        ub_next = _forward(ub, n_shards)
        vb_next = _forward(vb, n_shards)
        pv, rv = _accumulate_block(pv, rv, ub, *take(v_plans), engine=engine)
        pu, ru = _accumulate_block(pu, ru, vb, *take(u_plans), engine=engine)
        return (ub_next, vb_next, pv, rv, pu, ru), None

    init = (
        u_blk, v_blk,
        jnp.zeros((n_v, k, k), jnp.float32), jnp.zeros((n_v, k), jnp.float32),
        jnp.zeros((n_u, k, k), jnp.float32), jnp.zeros((n_u, k), jnp.float32),
    )
    (_, _, pv, rv, pu, ru), _ = jax.lax.scan(step, init, jnp.arange(n_shards))
    v_new = _finish_phase(k_v, pv, rv, v_ids, hyper_v, alpha, solver)
    u_new = _finish_phase(k_u, pu, ru, u_ids, hyper_u, alpha, solver)
    return v_new, u_new


def _phase_allgather(key, counter_blk, plan_full, item_ids, hyper, alpha,
                     engine, solver):
    """Sync baseline: gather the whole counterpart, then sweep locally."""
    with jax.named_scope("bpmf.exchange"):
        full = jax.lax.all_gather(counter_blk, AXIS)      # (P, n_loc, K)
    full = full.reshape(-1, full.shape[-1])
    n_loc = item_ids.shape[0]
    k = full.shape[-1]
    prec, rhs = _accumulate_block(
        jnp.zeros((n_loc, k, k), jnp.float32), jnp.zeros((n_loc, k), jnp.float32),
        full, *plan_full, engine=engine,
    )
    return _finish_phase(key, prec, rhs, item_ids, hyper, alpha, solver)


def _stats(x, valid):
    """Sufficient statistics of the valid rows of every shard's factors:
    local sums under `bpmf.hyper`, their sums over the mesh under
    `bpmf.exchange`."""
    with jax.named_scope("bpmf.hyper"):
        xm = jnp.where(valid[:, None], x, 0.0)
        local = (xm.sum(0),
                 jnp.einsum("nk,nl->kl", xm, xm, preferred_element_type=jnp.float32),
                 valid.sum())
    with jax.named_scope("bpmf.exchange"):
        return tuple(jax.lax.psum(a, AXIS) for a in local)


def _hyper_draw(key, x, valid, prior):
    """Normal-Wishart draw from the factors' statistics over the mesh."""
    stats = _stats(x, valid)
    with jax.named_scope("bpmf.hyper"):
        return sample_normal_wishart(key, *stats, prior)


def make_sweep(mesh: Mesh, mode: str, alpha: float, prior: NWPrior,
               engine: str = "einsum"):
    """shard_map'd full Gibbs sweep (both phases + fused hyper stats).

    Standalone so the production-mesh dry-run can lower it against
    ShapeDtypeStruct plans without building a real plan. `engine` picks the
    per-block stats path (DIST_ENGINES); plans are 6-tuples
    (idx, val, msk, seg, seg_dense, seg_map). The systems are solved by
    `gibbs._sweep_solver(engine)`, as in the single-chip sweep.
    """
    if engine not in DIST_ENGINES:
        raise ValueError(f"engine must be one of {DIST_ENGINES}, got {engine!r}")
    if mode not in DIST_MODES:
        raise ValueError(f"mode must be one of {DIST_MODES}, got {mode!r}")
    n_shards = mesh.shape[AXIS]
    solver = _sweep_solver(engine)

    def sweep(state: DistState, u_plans, v_plans, u_ids, v_ids):
        key, k_hv, k_v, k_hu, k_u = jax.random.split(state.key, 5)
        # strip the sharded leading axis (local block views)
        u_plans = tuple(a[0] for a in u_plans)
        v_plans = tuple(a[0] for a in v_plans)
        u_ids = u_ids[0]
        v_ids = v_ids[0]

        # both hyper draws read the PREVIOUS sweep's factors in every mode
        # (sync modes too: su below uses state.u, not u_new) — so async can
        # hoist them above its fused scan without changing a single bit
        hyper_v = _hyper_draw(k_hv, state.v[0], v_ids >= 0, prior)
        if mode == "async":
            hyper_u = _hyper_draw(k_hu, state.u[0], u_ids >= 0, prior)
            v_new, u_new = _phase_ring_async(
                k_v, k_u, state.u[0], state.v[0], v_plans, u_plans,
                v_ids, u_ids, hyper_v, hyper_u, alpha, n_shards, engine, solver,
            )
            return DistState(
                u=u_new[None], v=v_new[None],
                hyper_u=hyper_u, hyper_v=hyper_v,
                key=key, step=state.step + 1,
                v_eval=state.v,   # u_new conditioned on this v
            )

        # movies phase
        if mode == "ring":
            v_new = _phase_ring(k_v, state.u[0], v_plans, v_ids, hyper_v,
                                alpha, n_shards, engine, solver)
        else:
            v_new = _phase_allgather(k_v, state.u[0], v_plans, v_ids, hyper_v,
                                     alpha, engine, solver)

        hyper_u = _hyper_draw(k_hu, state.u[0], u_ids >= 0, prior)
        if mode == "ring":
            u_new = _phase_ring(k_u, v_new, u_plans, u_ids, hyper_u,
                                alpha, n_shards, engine, solver)
        else:
            u_new = _phase_allgather(k_u, v_new, u_plans, u_ids, hyper_u,
                                     alpha, engine, solver)

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
        in_specs=(state_spec, plans_in, plans_in, P(AXIS), P(AXIS)),
        out_specs=state_spec,
        check_vma=False,
    )


class DistributedBPMF:
    """Multi-device BPMF over a 1-D mesh, paper Sec 4 faithful."""

    def __init__(
        self,
        ratings: SparseRatings,
        test: SparseRatings | None = None,
        *,
        mesh: Mesh | None = None,
        k: int = 32,
        alpha: float = 1.5,
        width: int | str = 32,       # "auto": degree-aware grid width
        mode: str = "ring",          # ring | allgather | async (DIST_MODES)
        engine: str = "einsum",      # einsum | fused (DIST_ENGINES)
        seed: int = 0,
    ):
        if mode not in DIST_MODES:
            raise ValueError(f"mode must be one of {DIST_MODES}, got {mode!r}")
        if mesh is None:
            n = len(jax.devices())
            mesh = jax.make_mesh((n,), (AXIS,))
        self.mesh = mesh
        self.n_shards = mesh.shape[AXIS]
        self.k = k
        self.alpha = alpha
        self.mode = mode
        self.engine = engine
        self.global_mean = ratings.mean()
        self.test = test
        centered = ratings.centered()

        p = self.n_shards
        self.u_part = partition_entities(centered.degrees(0), p)
        self.v_part = partition_entities(centered.degrees(1), p)
        # user-update plan: rows = users, counterpart = movies
        self.u_plan = build_grid_plan(centered, self.u_part, self.v_part, width=width)
        self.v_plan = build_grid_plan(
            centered.transpose(), self.v_part, self.u_part, width=width
        )
        self.prior = default_prior(k)
        self._plan_stats = {side: {
            "rated": plan.mask.sum(axis=(1, 2, 3)).astype(np.int64).tolist(),
            "sent": [int(np.prod(plan.mask.shape[1:]))] * p,
        } for side, plan in (("u", self.u_plan), ("v", self.v_plan))}
        self._sweep = self._build_sweep()

    def plan_stats(self) -> dict:
        """Per shard, the rated slots and the slots sent to the statistics
        pass of the user ("u") and item ("v") grid plans:
        `{"u": {"rated": [...], "sent": [...]}, "v": ...}`, one entry per
        shard. Counted on the host when the plans are built; rated / sent is
        the plans' fill."""
        return {side: {k: list(v) for k, v in d.items()}
                for side, d in self._plan_stats.items()}

    # ------------------------------------------------------------------
    def _device_plans(self, plan: GridPlan):
        """Grid plan arrays, sharded over dim 0 (the owning shard)."""
        sh = NamedSharding(self.mesh, P(AXIS))
        to_dev = lambda a: jax.device_put(jnp.asarray(a), sh)
        ring = (
            to_dev(plan.indices),
            to_dev(plan.values),
            to_dev(plan.mask),
            to_dev(plan.seg),
            to_dev(plan.seg_dense),
            to_dev(plan.seg_map),
        )
        ids = to_dev(plan.item_ids)
        return ring, ids

    def _flat_plans(self, plan: GridPlan):
        """Per-shard flattened plan vs the FULL counterpart (allgather mode).

        Block-local indices are rebased to gathered-global offsets q*n_loc+i.
        The per-block dense segment ids are rebased the same way (cumulative
        per-block segment counts), so the flattened seg_dense stays dense
        and nondecreasing — the fused engine's invariant.
        """
        p, _, r, w = plan.indices.shape
        offs = (np.arange(p) * plan.n_counter_loc)[None, :, None, None]
        idx = plan.indices + offs.astype(np.int32)

        # flatten dense segments across the q blocks of each shard row
        n_dense = plan.seg_dense[:, :, -1] + 1            # (P, P) segs per block
        seg_dense = np.zeros((p, p * r), np.int32)
        seg_map = np.full((p, p * r), plan.n_loc, np.int32)
        for pp in range(p):
            off = 0
            pos = 0
            for q in range(p):
                d = int(n_dense[pp, q])
                seg_dense[pp, q * r:(q + 1) * r] = plan.seg_dense[pp, q] + off
                seg_map[pp, pos:pos + d] = plan.seg_map[pp, q, :d]
                off += d
                pos += d

        sh = NamedSharding(self.mesh, P(AXIS))
        to_dev = lambda a: jax.device_put(jnp.asarray(a), sh)
        return (
            to_dev(idx.reshape(p, p * r, w)),
            to_dev(plan.values.reshape(p, p * r, w)),
            to_dev(plan.mask.reshape(p, p * r, w)),
            to_dev(plan.seg.reshape(p, p * r)),
            to_dev(seg_dense),
            to_dev(seg_map),
        )

    def _build_sweep(self):
        self.u_ring, self.u_ids = self._device_plans(self.u_plan)
        self.v_ring, self.v_ids = self._device_plans(self.v_plan)
        if self.mode == "allgather":
            self.u_flat = self._flat_plans(self.u_plan)
            self.v_flat = self._flat_plans(self.v_plan)

        mapped = make_sweep(self.mesh, self.mode, self.alpha, self.prior,
                            engine=self.engine)
        # ring and async share the per-block grid plans; only allgather
        # needs the flattened full-counterpart layout
        u_plans = self.u_flat if self.mode == "allgather" else self.u_ring
        v_plans = self.v_flat if self.mode == "allgather" else self.v_ring

        # plans ride in as arguments, not as constants baked into the program
        self._plan_args = (u_plans, v_plans, self.u_ids, self.v_ids)
        return jax.jit(mapped)

    # ------------------------------------------------------------------
    def init(self, seed: int = 0) -> DistState:
        key = jax.random.PRNGKey(seed)
        ku, kv, key = jax.random.split(key, 3)
        p = self.n_shards
        sh = NamedSharding(self.mesh, P(AXIS))
        # replicate the small leaves explicitly: the sweep's outputs carry
        # these shardings, so an init state laid out any other way makes the
        # SECOND sweep recompile — the whole first-sweeps timing window used
        # to be compile time
        rep = NamedSharding(self.mesh, P())
        u = 0.1 * jax.random.normal(ku, (p, self.u_part.n_loc, self.k), jnp.float32)
        v = 0.1 * jax.random.normal(kv, (p, self.v_part.n_loc, self.k), jnp.float32)
        v_dev = jax.device_put(v, sh)
        return DistState(
            u=jax.device_put(u, sh),
            v=v_dev,
            hyper_u=jax.device_put(init_hyper(self.k), rep),
            hyper_v=jax.device_put(init_hyper(self.k), rep),
            key=jax.device_put(key, rep),
            step=jax.device_put(jnp.zeros((), jnp.int32), rep),
            v_eval=v_dev if self.mode == "async" else None,
        )

    def sweep(self, state: DistState) -> DistState:
        with scopes_in_cache_key():
            return self._sweep(state, *self._plan_args)

    def gather_factors(self, state: DistState, *, coupled: bool = True):
        """(M, K), (N, K) in global entity order (host-side, for eval).

        In async mode the u draw conditioned on the PREVIOUS sweep's v, so
        the jointly-coupled posterior sample — the one predictions must
        use — is (u, v_eval). The fresh-but-uncoupled v (what the next
        sweep consumes, and what ring's first sweep matches bit-for-bit)
        is returned with coupled=False.
        """
        v_src = state.v if (state.v_eval is None or not coupled) else state.v_eval
        u = np.asarray(state.u).reshape(-1, self.k)
        v = np.asarray(v_src).reshape(-1, self.k)
        m = self.u_part.shard.shape[0]
        n = self.v_part.shard.shape[0]
        uo = np.zeros((m, self.k), np.float32)
        vo = np.zeros((n, self.k), np.float32)
        uo[self.u_part.ids[self.u_part.ids >= 0]] = u[
            (self.u_part.ids >= 0).reshape(-1)
        ]
        vo[self.v_part.ids[self.v_part.ids >= 0]] = v[
            (self.v_part.ids >= 0).reshape(-1)
        ]
        return uo, vo

    def rmse(self, state: DistState) -> float:
        if self.test is None:
            return float("nan")
        u, v = self.gather_factors(state)
        pred = np.einsum("nk,nk->n", u[self.test.rows], v[self.test.cols]) + self.global_mean
        return float(np.sqrt(np.mean((pred - self.test.vals) ** 2)))

    # run() bounds the async dispatch queue: XLA's CPU collectives
    # rendezvous per run id, and a deep enough pipeline of un-synced
    # collective programs lets the per-device threads skew until three
    # ranks wait on a rendezvous the fourth never joins (observed as a
    # hard hang past ~300 queued SGLD steps on forced host devices).
    # Draining every sync_every dispatches keeps the threads aligned at
    # negligible cost (a Gibbs sweep dwarfs the round trip; SGLD steps
    # lose ~nothing at depth 16 vs unbounded).
    sync_every = 16
    verbose_every = 5

    def run(self, n_sweeps: int, seed: int = 0, verbose: bool = False) -> DistState:
        state = self.init(seed)
        for i in range(n_sweeps):
            state = self.sweep(state)
            if i % self.sync_every == self.sync_every - 1:
                jax.block_until_ready(state.u)
            if verbose and (i % self.verbose_every == 0 or i == n_sweeps - 1):
                print(f"sweep {i:3d} rmse {self.rmse(state):.4f}")
        jax.block_until_ready(state.u)
        return state
