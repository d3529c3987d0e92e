"""Single-host BPMF Gibbs sampler over bucketed plans.

Algorithm 1 of the paper: per sweep, sample movie hyperparameters from V,
update every movie from (R, U); sample user hyperparameters from U, update
every user from (R, V); then predict the test points. The per-item update is

    Lambda_i = Lambda_hyper + alpha * sum_j v_j v_j^T     (j in ratings of i)
    b_i      = Lambda_hyper mu_hyper + alpha * sum_j r_ij v_j
    u_i      ~ N(Lambda_i^-1 b_i, Lambda_i^-1)

computed bucket-by-bucket as batched masked syrk (MXU) + batched Cholesky
sample — full inverses are never formed (paper Sec 3.1). The sufficient
statistics for the *next* hyperparameter draw are fused into the sweep.
"""
from __future__ import annotations

import contextlib
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax._src import config as jax_config

from repro.core.buckets import Bucket, BucketPlan, WidthsSpec, plan_buckets
from repro.core.hyper import (
    HyperParams,
    default_prior,
    init_hyper,
    sample_normal_wishart,
)
from repro.data.sparse import SparseRatings, csr_from_coo


# Sweep engines, selecting how per-segment rating statistics are computed
# and how the posterior systems are solved (docs/architecture.md §4):
#   reference  seed data flow kept verbatim: einsum row stats, per-bucket
#              segment_sum + full-size scatter-adds, LAPACK-style 3-solve
#              sampling. The equivalence oracle and benchmark baseline.
#   einsum     restructured flow (default): same einsum statistics, but
#              per-segment outputs written once into their seg_item_ids
#              slots; solved by the Pallas chol_solve_sample kernel on a
#              TPU, by the batched substitution solver elsewhere.
#   kernel     restructured flow through the two-step Pallas kernels
#              (masked_syrk + chol_solve_sample; interpret mode off-TPU).
#   fused      restructured flow through the fused gather→syrk→segment-
#              reduce kernel: V gathered in-kernel, no row-level
#              intermediate, optional bf16 gather.
ENGINES = ("reference", "einsum", "kernel", "fused")

# The full trainer family launch/train.py exposes: the four Gibbs sweep
# engines above plus the minibatch SGLD trainer (core.sgld.SGLDSampler /
# DistributedSGLD), which is a different sampler, not a sweep
# implementation — resolve_engine therefore rejects it with a pointer.
SGLD = "sgld"
TRAIN_ENGINES = ENGINES + (SGLD,)


def resolve_engine(engine: str | None, use_kernel: bool = False) -> str:
    """Map the (engine, legacy use_kernel flag) pair onto an ENGINES name."""
    if engine is None:
        return "kernel" if use_kernel else "einsum"
    if engine == SGLD:
        raise ValueError(
            f"engine must be one of {ENGINES}, got {engine!r}: 'sgld' is "
            "the minibatch SG-MCMC trainer, not a Gibbs sweep engine — use "
            "core.sgld.SGLDSampler / DistributedSGLD "
            "(launch.train --engine sgld)"
        )
    if engine not in ENGINES:
        raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
    return engine


@contextlib.contextmanager
def scopes_in_cache_key():
    """Compile what runs inside under a persistent-cache key that holds its
    name scopes (`bpmf.*`, `serve.foldin`).

    A profile attributes device time by those scopes, read from the HLO
    proto of the executable that ran. JAX's cache key leaves such metadata
    out by default, so an executable cached from the same computation under
    other scopes, or none, would be loaded and profiled in its place. Source
    locations are left out of the metadata here, so that the key does not
    depend on file paths or call stacks.
    """
    with jax_config.compilation_cache_include_metadata_in_key(True), \
            jax_config.traceback_in_locations_limit(0):
        yield


class FactorStats(NamedTuple):
    """Sufficient statistics of a factor matrix, fused into the sweep."""

    sum_x: jax.Array    # (K,)
    sum_xxt: jax.Array  # (K, K)
    n: jax.Array        # scalar


class BPMFState(NamedTuple):
    u: jax.Array              # (M, K)
    v: jax.Array              # (N, K)
    hyper_u: HyperParams
    hyper_v: HyperParams
    key: jax.Array
    step: jax.Array
    # Posterior-predictive accumulators over test points (after burn-in).
    pred_sum: jax.Array       # (n_test,)
    pred_count: jax.Array     # scalar


class DeviceBucket(NamedTuple):
    """Device-resident copy of a host Bucket (jnp arrays)."""

    width: int
    indices: jax.Array
    values: jax.Array
    mask: jax.Array
    seg_ids: jax.Array
    n_segments: int
    seg_item_ids: jax.Array
    # host-verified: seg_ids == arange(rows), i.e. every row is its own
    # segment and the per-bucket reduction is the identity (all buckets
    # except the widest, which splits long-tail items across rows)
    identity_segments: bool = False


# A pytree whose leaves are the arrays only: the sizes and the identity flag
# are static, so a plan passed into jit as an argument keeps static shapes.
jax.tree_util.register_pytree_node(
    DeviceBucket,
    lambda b: ((b.indices, b.values, b.mask, b.seg_ids, b.seg_item_ids),
               (b.width, b.n_segments, b.identity_segments)),
    lambda aux, arrs: DeviceBucket(aux[0], *arrs[:4], aux[1], arrs[4], aux[2]),
)


def device_plan(
    plan: BucketPlan | Sequence[Bucket],
) -> tuple[DeviceBucket, ...]:
    """Move a host plan (or a bare bucket sequence, e.g. one the fold-in
    cache padded) onto the device."""
    if isinstance(plan, BucketPlan):
        plan = plan.buckets
    return tuple(
        DeviceBucket(
            width=b.width,
            indices=jnp.asarray(b.indices),
            values=jnp.asarray(b.values),
            mask=jnp.asarray(b.mask),
            seg_ids=jnp.asarray(b.seg_ids),
            n_segments=b.n_segments,
            seg_item_ids=jnp.asarray(b.seg_item_ids),
            identity_segments=bool(
                b.indices.shape[0] == b.n_segments
                and np.array_equal(
                    np.asarray(b.seg_ids), np.arange(b.n_segments)
                )
            ),
        )
        for b in plan
    )


def segment_reduce_rows(
    rows: jax.Array, seg_ids: jax.Array, n_segments: int, *,
    stacked: bool = False, sorted_ids: bool = True, identity: bool = False,
) -> jax.Array:
    """Row-level statistics -> per-segment sums. The one definition of the
    bucket segment reduction, shared by every engine (`bucket_stats` here
    and the fused jnp path in `kernels.ops`): identity skips the reduction
    outright (every row its own segment), `stacked` rotates a leading draw
    axis out of the way (segment_sum reduces the leading axis), and
    `sorted_ids` asserts the planner's nondecreasing-rows invariant to XLA.
    """
    if identity:
        return rows
    if stacked:
        perm = (1, 0) + tuple(range(2, rows.ndim))
        return jax.ops.segment_sum(
            rows.transpose(perm), seg_ids, n_segments,
            indices_are_sorted=sorted_ids,
        ).transpose(perm)
    return jax.ops.segment_sum(
        rows, seg_ids, n_segments, indices_are_sorted=sorted_ids
    )


def bucket_stats(
    counterpart: jax.Array, bucket: DeviceBucket, *,
    use_kernel: bool = False, engine: str | None = None,
    bf16_gather: bool = False, interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-segment (sum v v^T, sum r v) for one bucket.

    counterpart is either one factor matrix (N, K) — the training sweep —
    or a stack of S retained draws (S, N, K) — the serving fold-in, where
    the same bucket plan (indices, ratings, mask are draw-independent) is
    applied against every draw's factors in one batched contraction.
    Returns (prec (..., n_segments, K, K), rhs (..., n_segments, K)) with
    the leading draw axis present iff counterpart carried one.

    `engine` selects the implementation (see ENGINES); the fused engine
    routes both forms through `kernels.ops.gather_syrk_seg`, so the
    stacked-draw fold-in rides the same kernel as the training sweep.
    """
    with jax.named_scope("bpmf.stats"):
        engine = resolve_engine(engine, use_kernel)

        if engine == "fused":
            from repro.kernels import ops as kops

            return kops.gather_syrk_seg(
                bucket.indices, bucket.values, bucket.mask,
                bucket.seg_ids, bucket.n_segments, counterpart,
                bf16_gather=bf16_gather,
                identity_segments=bucket.identity_segments,
                interpret=interpret,
            )

        # identity reduction is exact (a permutation-free relabeling), so the
        # restructured einsum engine skips it; the reference engine keeps the
        # seed computation verbatim
        skip_reduce = engine == "einsum" and bucket.identity_segments
        sorted_ids = engine != "reference"

        def reduce(rows, rotate):
            return segment_reduce_rows(
                rows, bucket.seg_ids, bucket.n_segments, stacked=rotate,
                sorted_ids=sorted_ids, identity=skip_reduce,
            )

        rv = bucket.values * bucket.mask
        if counterpart.ndim == 2:
            vg = counterpart[bucket.indices]                # (rows, w, K)
            vm = vg * bucket.mask[..., None]
            if engine == "kernel":
                from repro.kernels import ops as kops

                prec_rows, rhs_rows = kops.masked_syrk(vm, rv)
            else:
                # Rows that go straight to the solve take the mask on one
                # operand only (m * m = m: the same products), so on a TPU
                # the contraction reads the gathered block in the layout the
                # gather wrote, with no transposed copy of it. Rows that feed
                # the segment sum keep the symmetric form: there the block is
                # transposed either way, once for both operands.
                other = vg if skip_reduce else vm
                prec_rows = jnp.einsum(
                    "rwk,rwl->rkl", vm, other, preferred_element_type=jnp.float32
                )
                rhs_rows = jnp.einsum("rwk,rw->rk", other, rv)
            return reduce(prec_rows, False), reduce(rhs_rows, False)

        # stacked draws: one gather + one contraction covering all S draws
        vg = counterpart[:, bucket.indices]                 # (S, rows, w, K)
        vm = vg * bucket.mask[..., None]
        if engine == "kernel":
            from repro.kernels import ops as kops

            prec_rows, rhs_rows = kops.masked_syrk(
                vm, jnp.broadcast_to(rv, vm.shape[:-1])
            )
        else:
            prec_rows = jnp.einsum(
                "srwk,srwl->srkl", vm, vm, preferred_element_type=jnp.float32
            )
            rhs_rows = jnp.einsum("srwk,rw->srk", vm, rv)
        return reduce(prec_rows, True), reduce(rhs_rows, True)


def chol_subst_solve(chol: jax.Array, rhs: jax.Array, z: jax.Array) -> jax.Array:
    """x = L^-T (L^-1 rhs + z) via batch-vectorized substitution.

    XLA's batched `triangular_solve` dispatches per batch element on CPU
    and dominates the sweep (it is the seed path's real bottleneck, not the
    syrk). This runs the two substitutions as K fixed-shape steps over the
    whole batch — full-width dot products are exact because not-yet-solved
    entries are still zero — and merges the mean and noise solves into one
    backward pass. Works for any leading batch axes.
    """
    k = chol.shape[-1]

    def fwd(i, y):
        row = jax.lax.dynamic_slice_in_dim(chol, i, 1, axis=-2)[..., 0, :]
        d = jax.lax.dynamic_slice_in_dim(row, i, 1, axis=-1)[..., 0]
        yi = (
            jax.lax.dynamic_slice_in_dim(rhs, i, 1, axis=-1)[..., 0]
            - jnp.sum(row * y, -1)
        ) / d
        return jax.lax.dynamic_update_slice_in_dim(y, yi[..., None], i, axis=-1)

    c = jax.lax.fori_loop(0, k, fwd, jnp.zeros_like(rhs)) + z

    def bwd(j, x):
        i = k - 1 - j
        col = jax.lax.dynamic_slice_in_dim(chol, i, 1, axis=-1)[..., 0]
        d = jax.lax.dynamic_slice_in_dim(col, i, 1, axis=-1)[..., 0]
        xi = (
            jax.lax.dynamic_slice_in_dim(c, i, 1, axis=-1)[..., 0]
            - jnp.sum(col * x, -1)
        ) / d
        return jax.lax.dynamic_update_slice_in_dim(x, xi[..., None], i, axis=-1)

    return jax.lax.fori_loop(0, k, bwd, jnp.zeros_like(rhs))


def sample_mvn_precision(
    key: jax.Array | None, prec: jax.Array, rhs: jax.Array,
    *, z: jax.Array | None = None, use_kernel: bool = False,
    solver: str | None = None,
) -> jax.Array:
    """x ~ N(prec^-1 rhs, prec^-1), batched over any leading axes.

    Cholesky-only (no inverse): with prec = L L^T,
      mean = L^-T (L^-1 rhs),  x = mean + L^-T z.
    key=None returns the posterior mean (the z = 0 limb of the same solve)
    — the serving fold-in's deterministic mode. An explicit `z` (same shape
    as rhs) overrides the key: the batched fold-in pre-draws its noise with
    the per-draw key sequence of the original per-sample loop, so fused and
    looped sampling consume identical random bits.

    solver: "subst" (default) — XLA's batched Cholesky and the
    batch-vectorized substitution, the path off the TPU; "lapack" — the
    seed 3-triangular-solve formulation (retained for the reference
    engine); "kernel" — the batch-on-lanes Pallas kernel
    (`kernels.chol_solve`), which the training sweep takes on a TPU. All
    three compute the same lower factor and agree to fp32 rounding.
    """
    if solver is None:
        solver = "kernel" if use_kernel else "subst"
    with jax.named_scope("bpmf.solve"):
        if z is None:
            z = (
                jnp.zeros_like(rhs)
                if key is None
                else jax.random.normal(key, rhs.shape, rhs.dtype)
            )
        if solver == "kernel":
            from repro.kernels import ops as kops

            return kops.chol_solve_sample(prec, rhs, z)
        chol = jnp.linalg.cholesky(prec)
        if solver == "subst":
            return chol_subst_solve(chol, rhs, z)
        y = jax.lax.linalg.triangular_solve(
            chol, rhs[..., None], left_side=True, lower=True
        )
        mean = jax.lax.linalg.triangular_solve(
            chol, y, left_side=True, lower=True, transpose_a=True
        )
        noise = jax.lax.linalg.triangular_solve(
            chol, z[..., None], left_side=True, lower=True, transpose_a=True
        )
        return (mean + noise)[..., 0]


# Largest per-chunk intermediate of a bucket update, in bytes. A full-size
# ChEMBL user bucket holds 150k rows: its (rows, K, K) statistics and their
# Cholesky factors are 2.5 GB each at K=64, so big buckets are swept in row
# chunks (a sequential lax.map / scan) to keep the sweep inside one chip's HBM.
CHUNK_BYTES = 1 << 28


def chunk_rows(width: int, k: int) -> int:
    """Power-of-two rows per chunk: gathered (C, W, K) and (C, K, K) stay
    under CHUNK_BYTES each."""
    per_row = 4 * k * max(width, k)
    return max(8, 1 << int(np.log2(max(CHUNK_BYTES // per_row, 1))))


def _bucket_update(counterpart, b: DeviceBucket, sample, *, engine,
                   bf16_gather):
    """Posterior draws (n_segments, K) for one bucket's items.

    `sample(prec, rhs, item_ids)` turns per-segment statistics into draws.
    A bucket longer than one chunk is swept chunk by chunk: identity-segment
    buckets statistics-and-sample each chunk of rows; a bucket that splits
    items across rows accumulates its per-segment statistics over the chunks,
    then samples once.
    """
    k = counterpart.shape[-1]
    rows = b.indices.shape[0]
    c = chunk_rows(b.width, k)

    def stats(bucket):
        return bucket_stats(counterpart, bucket, engine=engine,
                            bf16_gather=bf16_gather)

    if rows <= c:
        return sample(*stats(b), b.seg_item_ids)
    n_chunks = -(-rows // c)
    pad = n_chunks * c - rows

    def chunked(x, mode="constant"):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1), mode=mode)
        return x.reshape((n_chunks, c) + x.shape[1:])

    # pad rows carry mask 0; seg_ids repeat the last segment (sorted)
    parts = (chunked(b.indices), chunked(b.values), chunked(b.mask),
             chunked(b.seg_ids, "edge"))

    def chunk_bucket(idx, val, msk, seg, n_segments, identity):
        return DeviceBucket(b.width, idx, val, msk, seg, n_segments,
                            b.seg_item_ids, identity)

    if b.identity_segments:
        ids = chunked(b.seg_item_ids)

        def one(args):
            *arrs, item_ids = args
            local = jnp.arange(c, dtype=jnp.int32)
            return sample(*stats(chunk_bucket(*arrs[:3], local, c, True)),
                          item_ids)

        x = jax.lax.map(one, (*parts[:3], ids))
        return x.reshape(n_chunks * c, k)[:rows]

    def acc(carry, arrs):
        p, r = stats(chunk_bucket(*arrs, b.n_segments, False))
        return (carry[0] + p, carry[1] + r), None

    init = (jnp.zeros((b.n_segments, k, k), jnp.float32),
            jnp.zeros((b.n_segments, k), jnp.float32))
    (prec, rhs), _ = jax.lax.scan(acc, init, parts)
    return sample(prec, rhs, b.seg_item_ids)


def _sweep_solver(engine: str) -> str:
    """The restructured engines' solve: the batch-on-lanes Pallas kernel on
    a TPU (and for the kernel engine everywhere); elsewhere the substitution
    solver, the CPU path and the kernel's equivalence oracle."""
    if engine == "kernel" or jax.default_backend() == "tpu":
        return "kernel"
    return "subst"


def update_factors(
    key: jax.Array,
    counterpart: jax.Array,
    buckets: Sequence[DeviceBucket],
    n_items: int,
    hyper: HyperParams,
    alpha: float,
    *,
    use_kernel: bool = False,
    engine: str | None = None,
    bf16_gather: bool = False,
) -> tuple[jax.Array, FactorStats]:
    """One half-sweep: resample every item factor given the counterpart matrix.

    Also returns the sufficient statistics of the *new* factor matrix (fused
    aggregation, paper Sec 3.1).

    The restructured flow (every engine except "reference") solves bucket by
    bucket: each bucket's per-segment statistics get the hyper-prior added
    and are sampled with the noise rows of their items, and the draws are
    written into their seg_item_ids rows of a prior draw for every item —
    the bucket plan partitions items, so indices are unique and items with
    no ratings keep the prior draw, as in the seed flow. No (n_items, K, K)
    buffer exists: a full-size user side would need 7.9 GB for it at K=64.
    The systems are solved by `_sweep_solver(engine)`.
    """
    engine = resolve_engine(engine, use_kernel)
    k = counterpart.shape[-1]
    dtype = counterpart.dtype

    if engine == "reference":
        prec_all = jnp.zeros((n_items, k, k), dtype)
        rhs_all = jnp.zeros((n_items, k), dtype)
        for b in buckets:
            prec, rhs = bucket_stats(counterpart, b, engine="reference")
            with jax.named_scope("bpmf.prior"):
                prec_all = prec_all.at[b.seg_item_ids].add(prec)
                rhs_all = rhs_all.at[b.seg_item_ids].add(rhs)
        with jax.named_scope("bpmf.prior"):
            prec_all = hyper.lam[None] + alpha * prec_all
            rhs_all = (hyper.lam @ hyper.mu)[None] + alpha * rhs_all
        new = sample_mvn_precision(key, prec_all, rhs_all, solver="lapack")
    else:
        with jax.named_scope("bpmf.prior"):
            lam = hyper.lam.astype(dtype)
            lam_mu = (hyper.lam @ hyper.mu).astype(dtype)
            z = jax.random.normal(key, (n_items, k), dtype)
            # items with no ratings keep the prior: one shared Cholesky factor
            new = chol_subst_solve(
                jnp.linalg.cholesky(lam), jnp.broadcast_to(lam_mu, (n_items, k)), z
            )
        solver = _sweep_solver(engine)

        def sample(prec, rhs, item_ids):
            with jax.named_scope("bpmf.prior"):
                prec = lam + (alpha * prec).astype(dtype)
                rhs = lam_mu + (alpha * rhs).astype(dtype)
                z_items = z[item_ids]
            return sample_mvn_precision(
                None, prec, rhs, z=z_items, solver=solver
            )

        for b in buckets:
            x = _bucket_update(counterpart, b, sample, engine=engine,
                               bf16_gather=bf16_gather)
            with jax.named_scope("bpmf.prior"):
                new = new.at[b.seg_item_ids].set(x, unique_indices=True)

    with jax.named_scope("bpmf.hyper"):
        stats = FactorStats(
            sum_x=new.sum(0),
            sum_xxt=jnp.einsum("nk,nl->kl", new, new,
                               preferred_element_type=jnp.float32),
            n=jnp.asarray(n_items, dtype),
        )
    return new, stats


def factor_stats(x: jax.Array) -> FactorStats:
    return FactorStats(
        sum_x=x.sum(0),
        sum_xxt=jnp.einsum("nk,nl->kl", x, x, preferred_element_type=jnp.float32),
        n=jnp.asarray(x.shape[0], x.dtype),
    )


class GibbsSampler:
    """Single-host BPMF sampler. `jit`-compiled sweep over bucketed plans.

    `engine` selects the sweep implementation (see ENGINES): the
    restructured einsum flow by default, "fused" for the gather-syrk
    kernel path, "kernel" for the two-step Pallas path (the legacy
    `use_kernel=True`), "reference" for the seed flow. `bf16_gather`
    (fused engine) gathers counterpart factors at half width with fp32
    accumulation.

    `widths` picks the bucket planner: the default "balanced" fits a
    degree-aware width ladder to each plan's own degree histogram
    (`core.buckets.balanced_widths` — the static work-stealing analogue;
    the user and item plans resolve independently), or pass an explicit
    tuple for a fixed ladder. The sampled chain is plan-independent up to
    fp32 reduction order — every ladder draws the same per-item noise.
    """

    # verbose run() progress cadence; SGLD steps are ~100x cheaper than
    # Gibbs sweeps, so its subclass prints far less often
    verbose_every = 5

    def __init__(
        self,
        ratings: SparseRatings,
        test: SparseRatings | None = None,
        *,
        k: int = 64,
        alpha: float = 1.5,
        burn_in: int = 8,
        widths: WidthsSpec = "balanced",
        use_kernel: bool = False,
        engine: str | None = None,
        bf16_gather: bool = False,
        dtype=jnp.float32,
    ):
        self.m, self.n = ratings.shape
        self.k = k
        self.alpha = alpha
        self.burn_in = burn_in
        self.engine = resolve_engine(engine, use_kernel)
        self.use_kernel = self.engine == "kernel"
        self.bf16_gather = bf16_gather
        self.dtype = dtype
        self.global_mean = ratings.mean()
        centered = ratings.centered()

        # Movie-major and user-major plans.
        uptr, uidx, uval = csr_from_coo(
            centered.rows, centered.cols, centered.vals, self.m
        )
        self.user_plan_host = plan_buckets(uptr, uidx, uval, self.m, self.n, widths)
        t = centered.transpose()
        vptr, vidx, vval = csr_from_coo(t.rows, t.cols, t.vals, self.n)
        self.item_plan_host = plan_buckets(vptr, vidx, vval, self.n, self.m, widths)
        self.user_buckets = device_plan(self.user_plan_host)
        self.item_buckets = device_plan(self.item_plan_host)

        if test is not None:
            self.test_rows = jnp.asarray(test.rows.astype(np.int32))
            self.test_cols = jnp.asarray(test.cols.astype(np.int32))
            self.test_vals = jnp.asarray(test.vals.astype(np.float32))
        else:
            self.test_rows = jnp.zeros((0,), jnp.int32)
            self.test_cols = jnp.zeros((0,), jnp.int32)
            self.test_vals = jnp.zeros((0,), jnp.float32)

        self.prior = default_prior(k, dtype)
        # the plans ride in as arguments: closed over, they would be baked
        # into the program as constants (tens of MB at full ChEMBL size)
        self._plan_args = ((self.item_buckets, self.user_buckets),)
        self._sweep = jax.jit(self._sweep_impl)

    def init(self, seed: int = 0) -> BPMFState:
        key = jax.random.PRNGKey(seed)
        ku, kv, key = jax.random.split(key, 3)
        return BPMFState(
            u=0.1 * jax.random.normal(ku, (self.m, self.k), self.dtype),
            v=0.1 * jax.random.normal(kv, (self.n, self.k), self.dtype),
            hyper_u=init_hyper(self.k, self.dtype),
            hyper_v=init_hyper(self.k, self.dtype),
            key=key,
            step=jnp.asarray(0, jnp.int32),
            pred_sum=jnp.zeros_like(self.test_vals),
            pred_count=jnp.asarray(0, jnp.int32),
        )

    # --- one full Gibbs sweep (Algorithm 1 body) ---
    def _sweep_impl(self, state: BPMFState, plans) -> BPMFState:
        item_buckets, user_buckets = plans
        key, k_hv, k_v, k_hu, k_u = jax.random.split(state.key, 5)

        # Movies phase: hyper from V stats, then update V given U.
        with jax.named_scope("bpmf.hyper"):
            sv = factor_stats(state.v)
            hyper_v = sample_normal_wishart(k_hv, sv.sum_x, sv.sum_xxt, sv.n,
                                            self.prior)
        v_new, _ = update_factors(
            k_v, state.u, item_buckets, self.n, hyper_v, self.alpha,
            engine=self.engine, bf16_gather=self.bf16_gather,
        )

        # Users phase: hyper from U stats, then update U given new V.
        with jax.named_scope("bpmf.hyper"):
            su = factor_stats(state.u)
            hyper_u = sample_normal_wishart(k_hu, su.sum_x, su.sum_xxt, su.n,
                                            self.prior)
        u_new, _ = update_factors(
            k_u, v_new, user_buckets, self.m, hyper_u, self.alpha,
            engine=self.engine, bf16_gather=self.bf16_gather,
        )

        # Posterior-predictive accumulation after burn-in.
        with jax.named_scope("bpmf.predict"):
            preds = (
                jnp.einsum("nk,nk->n", u_new[self.test_rows], v_new[self.test_cols])
                + self.global_mean
            )
            collect = state.step >= self.burn_in
            pred_sum = jnp.where(collect, state.pred_sum + preds, state.pred_sum)
            pred_count = state.pred_count + jnp.where(collect, 1, 0)

        return BPMFState(
            u=u_new,
            v=v_new,
            hyper_u=hyper_u,
            hyper_v=hyper_v,
            key=key,
            step=state.step + 1,
            pred_sum=pred_sum,
            pred_count=pred_count,
        )

    def sweep(self, state: BPMFState) -> BPMFState:
        with scopes_in_cache_key():
            return self._sweep(state, *self._plan_args)

    def rmse(self, state: BPMFState) -> float:
        """Posterior-mean RMSE over the test set (paper's accuracy metric)."""
        if self.test_vals.shape[0] == 0:
            return float("nan")
        count = jnp.maximum(state.pred_count, 1)
        pred = state.pred_sum / count
        return float(jnp.sqrt(jnp.mean((pred - self.test_vals) ** 2)))

    def sample_rmse(self, state: BPMFState) -> float:
        """RMSE of the current single sample (no posterior averaging)."""
        if self.test_vals.shape[0] == 0:
            return float("nan")
        preds = (
            jnp.einsum(
                "nk,nk->n", state.u[self.test_rows], state.v[self.test_cols]
            )
            + self.global_mean
        )
        return float(jnp.sqrt(jnp.mean((preds - self.test_vals) ** 2)))

    def sample_dict(self, state: BPMFState, *, host: bool = True) -> dict:
        """The current draw in the flat SAMPLE_KEYS schema both publication
        paths consume. host=True copies arrays off-device (the durable
        SampleStore write); host=False hands the device arrays through
        as-is (the in-memory PublicationChannel publish — the subscriber
        stacks them without a host round trip)."""
        conv = np.asarray if host else (lambda x: x)
        return {
            "u": conv(state.u),
            "v": conv(state.v),
            "hyper_u_mu": conv(state.hyper_u.mu),
            "hyper_u_lam": conv(state.hyper_u.lam),
            "hyper_v_mu": conv(state.hyper_v.mu),
            "hyper_v_lam": conv(state.hyper_v.lam),
            "global_mean": np.asarray(self.global_mean, np.float32),
            "alpha": np.asarray(self.alpha, np.float32),
        }

    def retain_sample(self, state: BPMFState, store) -> None:
        """Persist the current draw into a checkpoint.SampleStore."""
        store.retain(int(state.step), self.sample_dict(state))

    def run(
        self,
        n_sweeps: int,
        seed: int = 0,
        verbose: bool = False,
        *,
        store=None,
        publish=None,
        thin: int = 1,
    ) -> BPMFState:
        """Run the chain; every `thin`-th post-burn-in draw is handed off to
        serving on up to two paths:

        * `store` (a checkpoint.SampleStore): the durable write — survives
          restarts, feeds cold server starts.
        * `publish` (a serve.publish.PublicationChannel): the asynchronous
          in-memory push to a co-running server — the draw is live before
          (and regardless of whether) the store's async write hits disk.
          The channel is left open; callers close() it when the co-running
          server should see end-of-stream.

        Both writes overlap the next sweep (the store's executor thread, the
        channel's subscriber threads) — publication never stalls the chain,
        which is the paper's async-communication discipline applied to the
        train -> serve hand-off.
        """
        if thin < 1:
            raise ValueError(f"thin must be >= 1, got {thin}")
        state = self.init(seed)
        for i in range(n_sweeps):
            state = self.sweep(state)
            if i >= self.burn_in and (i - self.burn_in) % thin == 0:
                if store is not None:
                    self.retain_sample(state, store)
                if publish is not None:
                    publish.publish(
                        int(state.step), self.sample_dict(state, host=False)
                    )
            if verbose and (i % self.verbose_every == 0 or i == n_sweeps - 1):
                print(f"sweep {i:3d}  sample-rmse {self.sample_rmse(state):.4f}")
        if store is not None:
            store.wait()
        return state
