"""Cold-start fold-in: batched conditional posteriors for unseen users.

A user who arrives after training has no row in any retained U_s, but the
BPMF model still defines their conditional posterior given each draw's item
factors and user hyperparameters:

    Lambda_b^s = Lambda_u^s + alpha * sum_j v_j^s v_j^s^T   (j rated by b)
    rhs_b^s    = Lambda_u^s mu_u^s + alpha * sum_j r_bj v_j^s
    u_b^s      ~ N((Lambda_b^s)^-1 rhs_b^s, (Lambda_b^s)^-1)

— exactly the per-item update of the training sweep (posterior propagation
in the sense of Qin et al. 2017: the retained draws carry the training
posterior, and the new user's factor is inferred conditionally without
touching the chain).

The serving formulation is *batched over draws and users at once*: the
bucket plan (gather indices, ratings, mask) is draw-independent, so one
gather + contraction per bucket covers all S draws, the per-draw hypers are
broadcast from the ensemble's stacked (S, K, K) / (S, K) device arrays, and
the S*B conditional systems are factored and solved in one
`sample_mvn_precision` call over an (S, B, K, K) precision stack — one
compiled executable per plan shape instead of a Python loop of S separate
solves. `fold_in_loop` keeps the original per-draw loop as the reference
implementation (equivalence-tested; the fused path matches it bit-for-bit
through the statistics and to fp32 rounding through the batched triangular
solves).

`FoldInPlanCache` removes the other steady-state cost: recompiling. A
batch's bucket plan is still built per request (contents are new data),
but its *shapes* are keyed on a quantized rating-count profile — the
(width, rows, segments) shape of the plan with every count rounded up to
a power of two, plus the padded batch size — so repeated cold-start
batches with similar degree shapes map onto one set of padded array
shapes and therefore reuse every compiled executable (`trace_count()`
stays flat; tested). Padding is exact: mask-zero rows and zero-sum
segments contribute nothing.
"""
from __future__ import annotations

import functools
import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.buckets import (
    DEFAULT_WIDTHS,
    balanced_widths,
    pad_bucket,
    plan_buckets,
)
from repro.core.gibbs import (
    DeviceBucket,
    bucket_stats,
    device_plan,
    sample_mvn_precision,
    scopes_in_cache_key,
)
from repro.data.sparse import SparseRatings, csr_from_coo
from repro.serve.ensemble import PosteriorEnsemble

_trace_count = 0


def trace_count() -> int:
    """How many times the fused fold-in solve has been traced (compiled).

    Same discipline as kernels.bpmf_topn.trace_count: the counter bumps at
    trace time only, so a flat count across repeated cold-start batches
    proves the plan cache mapped them onto already-compiled executables.
    """
    return _trace_count


class FoldInPlanCache:
    """Quantized plan schemas for cold-start batches, keyed on rating counts.

    The expensive parts of serving a cold batch are shape-dependent: every
    distinct set of bucket array shapes costs a fresh trace + compile of the
    fused solve. Raw batches almost never repeat shapes exactly — degree
    profiles drift request to request — so the cache quantizes: a batch's
    rating-count profile (per-bucket rows and segments, and the batch size)
    is rounded up to powers of two, and batches that land on the same
    quantized schema share one set of padded shapes and therefore every
    compiled executable.

    An entry is the immutable quantized schema itself (per-batch array
    *contents* are new data and are rebuilt each request); what the hit path
    buys is shape stability — `trace_count()` flat across same-profile
    batches — plus the hit/miss accounting serving dashboards want. Entries
    are LRU-bounded. Thread-safe: the frontend may flush from several
    threads.

    The cache is ensemble-shape-agnostic except for the item-axis width
    (item ids must index the same catalogue), so same-shape publishes keep
    every entry; `RecommendFrontend` clears it only when the ensemble's
    shapes actually change.
    """

    def __init__(
        self,
        widths: tuple[int, ...] = DEFAULT_WIDTHS,
        *,
        max_entries: int = 64,
        quantum: int = 8,
    ):
        self.widths = tuple(sorted(widths))
        self.quantum = int(quantum)
        self.max_entries = int(max_entries)
        self.hits = 0
        self.misses = 0
        self._entries: OrderedDict[tuple, None] = OrderedDict()
        self._lock = threading.Lock()

    @classmethod
    def balanced(
        cls,
        degrees: np.ndarray,
        *,
        max_buckets: int = 8,
        lane: int = 1,
        max_width: int = 512,
        max_entries: int = 64,
        quantum: int = 8,
    ) -> "FoldInPlanCache":
        """A cache whose width ladder is fit ONCE to a reference degree
        profile (typically the training users') by the balanced planner,
        then frozen. Per-request plans bin into these fixed — possibly
        non-pow2 — widths, so quantized-profile keys stay trace-flat
        exactly as with the pow2 ladder, while the padding tracks the
        workload's real degree shape. The ladder must not be refit per
        batch: that would make the width axis of the schema key
        data-dependent and retrace on every profile drift.
        """
        widths = balanced_widths(
            np.asarray(degrees), max_buckets=max_buckets,
            lane=lane, max_width=max_width,
        )
        return cls(widths, max_entries=max_entries, quantum=quantum)

    @staticmethod
    def _quantize(n: int, quantum: int) -> int:
        """Smallest power of two >= n, floored at `quantum` (tile-friendly)."""
        return max(quantum, 1 << (max(int(n), 1) - 1).bit_length())

    def schema(
        self,
        profile: tuple[tuple[int, int, int], ...],
        n_new: int,
        n_items: int,
    ) -> tuple[int, tuple[tuple[int, int, int], ...]]:
        """Quantized (padded_batch, ((width, rows, segments), ...)) for a
        batch whose exact plan shape is `profile` — the (width, rows,
        segments) triples of the plan's buckets, in bucket order, so the
        quantized targets stay aligned with the plan by construction.
        Records hit/miss."""
        q = self.quantum
        padded_batch = self._quantize(n_new, q)
        buckets = tuple(
            (w, self._quantize(rows, q), self._quantize(segs, q))
            for w, rows, segs in profile
        )
        key = (n_items, padded_batch, buckets)
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                self.hits += 1
            else:
                self._entries[key] = None
                self.misses += 1
                while len(self._entries) > self.max_entries:
                    self._entries.popitem(last=False)
        return padded_batch, buckets

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def stats(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "entries": len(self._entries),
            }


@functools.partial(
    jax.jit, static_argnames=("plan_key", "n_new", "engine")
)
def _fused_fold_in(
    v: jax.Array,           # (S, N, K) stacked item factors
    lam: jax.Array,         # (S, K, K) stacked user hyper precisions
    mu: jax.Array,          # (S, K)    stacked user hyper means
    alpha: float,
    arrays: tuple,          # per bucket: (indices, values, mask, seg_ids, seg_item_ids)
    z: jax.Array | None,    # (S, n_new, K) pre-drawn noise, or None for the mean
    *,
    plan_key: tuple,        # per bucket: (width, n_segments, identity) — static
    n_new: int,
    engine: str,
) -> jax.Array:
    """One batched (S*B) assembly + Cholesky solve for the whole fold-in."""
    global _trace_count
    _trace_count += 1  # executes at trace time only: one bump per jit miss
    with jax.named_scope("serve.foldin"):
        s, _, k = v.shape
        prec = jnp.zeros((s, n_new, k, k), v.dtype)
        rhs = jnp.zeros((s, n_new, k), v.dtype)
        for (width, n_segments, identity), (
            idx, vals, mask, seg_ids, seg_item_ids
        ) in zip(plan_key, arrays):
            b = DeviceBucket(
                width=width, indices=idx, values=vals, mask=mask,
                seg_ids=seg_ids, n_segments=n_segments, seg_item_ids=seg_item_ids,
                identity_segments=identity,
            )
            # stacked-draw bucket stats: the fused engine rides the same
            # gather-syrk kernel as the training sweep (leading S axis)
            p, r = bucket_stats(v, b, engine=engine)  # (S, segs, ...)
            prec = prec.at[:, seg_item_ids].add(p)
            rhs = rhs.at[:, seg_item_ids].add(r)
        prec = lam[:, None] + alpha * prec
        rhs = jnp.einsum("skl,sl->sk", lam, mu)[:, None] + alpha * rhs
        solver = "kernel" if engine == "kernel" else "subst"
        return sample_mvn_precision(None, prec, rhs, z=z, solver=solver)


def _check_fold_in_args(
    key: jax.Array | None, ratings: SparseRatings,
    ensemble: PosteriorEnsemble, sample: bool,
) -> None:
    if sample and key is None:
        raise ValueError(
            "fold_in(sample=True) draws conditional samples and needs a PRNG "
            "key; pass a key, or sample=False for the deterministic "
            "posterior mean"
        )
    n_items = ratings.shape[1]
    if n_items != ensemble.n_items:
        raise ValueError(
            f"ratings cover {n_items} items, ensemble has {ensemble.n_items}"
        )
    # out-of-range item ids would otherwise be silently clamped by the gather
    ratings.validate()


def _presample_noise(
    key: jax.Array, s: int, n_new: int, k: int
) -> jax.Array:
    """(S, n_new, K) noise via the per-draw key-split sequence of the
    original loop — fused and looped sampling consume identical bits."""
    zs = []
    for _ in range(s):
        key, sub = jax.random.split(key)
        zs.append(jax.random.normal(sub, (n_new, k), jnp.float32))
    return jnp.stack(zs)


def fold_in(
    key: jax.Array | None,
    ratings: SparseRatings,
    ensemble: PosteriorEnsemble,
    *,
    sample: bool = True,
    widths: tuple[int, ...] = DEFAULT_WIDTHS,
    use_kernel: bool = False,
    engine: str | None = None,
    plan_cache: FoldInPlanCache | None = None,
) -> jax.Array:
    """Factor posteriors for a batch of new users from their ratings alone.

    ratings: (n_new, n_items) sparse — row b holds new user b's ratings on
    the *training* item index space, on the raw rating scale (the training
    global mean is subtracted here). Returns (S, n_new, K) per-draw factors:
    conditional draws when sample=True (a PRNG key is required), conditional
    posterior means (z = 0, key may be None) when False. Feed them to
    PosteriorEnsemble.score_factors or TopNRecommender.recommend_factors.

    The whole batch is solved fused: rating statistics are computed once per
    bucket for all S draws, broadcast against the ensemble's stacked user
    hypers, and the S*n_new conditional systems share one batched Cholesky
    solve. A user with zero ratings gets their hyper-prior posterior
    N(mu_u^s, (Lambda_u^s)^-1) — the zero-statistics limb of the same solve.

    plan_cache: a FoldInPlanCache quantizes the plan shapes so repeated
    batches with similar rating-count profiles reuse compiled executables
    (the serving hot path; `widths` is taken from the cache). Without one,
    the plan is built at exact shapes (bit-parity with `fold_in_loop`).

    engine: sweep engine for the bucket statistics and solve
    (core.gibbs.ENGINES) — "fused" routes the stacked-draw statistics
    through the same gather-syrk kernel as the training sweep.
    """
    from repro.core.gibbs import resolve_engine

    engine = resolve_engine(engine, use_kernel)
    _check_fold_in_args(key, ratings, ensemble, sample)
    n_new = ratings.shape[0]
    s, k = ensemble.n_samples, ensemble.k

    z = _presample_noise(key, s, n_new, k) if sample else None

    if ratings.nnz == 0:
        # zero-rating batch: nothing to plan — the prior-only solve below.
        # Still quantize the batch axis when a cache is attached, or every
        # distinct empty-batch size would trace a fresh executable.
        arrays: tuple = ()
        plan_key: tuple = ()
        padded_batch = (
            plan_cache._quantize(n_new, plan_cache.quantum)
            if plan_cache is not None else n_new
        )
    else:
        # host planning: CSR, bucket plan, schema padding, copies to the device
        with jax.profiler.TraceAnnotation("serve.foldin.plan"):
            centered = (ratings.vals - ensemble.global_mean).astype(np.float32)
            indptr, idx, vals = csr_from_coo(
                ratings.rows, ratings.cols, centered, n_new
            )
            if plan_cache is not None:
                widths = plan_cache.widths
            plan = plan_buckets(
                indptr, idx, vals, n_new, ensemble.n_items, widths
            )
            buckets = plan.buckets
            if plan_cache is not None:
                padded_batch, targets = plan_cache.schema(
                    tuple((b.width, b.rows, b.n_segments) for b in buckets),
                    n_new, ensemble.n_items,
                )
                buckets = tuple(
                    pad_bucket(b, rows, segs)
                    for b, (_, rows, segs) in zip(buckets, targets)
                )
            else:
                padded_batch = n_new
            db = device_plan(buckets)
            # under a plan cache the static key must be a function of the
            # quantized SCHEMA alone: identity_segments is computed from the
            # padded seg_ids contents, which can differ between two batches
            # that share a schema (e.g. padding by one row makes seg_ids
            # exactly arange) — letting it through would retrace on a cache
            # hit and break the trace-flat contract
            plan_key = tuple(
                (b.width, b.n_segments,
                 False if plan_cache is not None else b.identity_segments)
                for b in db
            )
            arrays = tuple(
                (b.indices, b.values, b.mask, b.seg_ids, b.seg_item_ids)
                for b in db
            )

    if z is not None and padded_batch != n_new:
        z = jnp.concatenate(
            [z, jnp.zeros((s, padded_batch - n_new, k), z.dtype)], axis=1
        )

    with scopes_in_cache_key():
        out = _fused_fold_in(
            ensemble.v, ensemble.hyper_u_lam, ensemble.hyper_u_mu,
            ensemble.alpha, arrays, z,
            plan_key=plan_key, n_new=padded_batch, engine=engine,
        )
    return out[:, :n_new]  # drop batch padding (padded rows solve the prior)


def _ratings_stats(v: jax.Array, buckets, n_new: int,
                   use_kernel: bool) -> tuple[jax.Array, jax.Array]:
    """Accumulate (sum v v^T, sum r v) per new user via the bucket plan."""
    k = v.shape[-1]
    prec = jnp.zeros((n_new, k, k), v.dtype)
    rhs = jnp.zeros((n_new, k), v.dtype)
    for b in buckets:
        p, r = bucket_stats(v, b, use_kernel=use_kernel)
        prec = prec.at[b.seg_item_ids].add(p)
        rhs = rhs.at[b.seg_item_ids].add(r)
    return prec, rhs


def fold_in_loop(
    key: jax.Array | None,
    ratings: SparseRatings,
    ensemble: PosteriorEnsemble,
    *,
    sample: bool = True,
    widths: tuple[int, ...] = DEFAULT_WIDTHS,
    use_kernel: bool = False,
) -> jax.Array:
    """The original per-retained-draw fold-in: S separate solves in a Python
    loop. Kept as the reference implementation the fused `fold_in` is
    equivalence-tested against. Not the serving path.
    """
    _check_fold_in_args(key, ratings, ensemble, sample)
    n_new = ratings.shape[0]
    centered = (ratings.vals - ensemble.global_mean).astype(np.float32)
    indptr, idx, vals = csr_from_coo(ratings.rows, ratings.cols, centered, n_new)
    plan = plan_buckets(indptr, idx, vals, n_new, ensemble.n_items, widths)
    buckets = device_plan(plan)
    alpha = ensemble.alpha

    out = []
    for s, smp in enumerate(ensemble.samples):
        v = ensemble.v[s]
        lam = jnp.asarray(smp.hyper_u_lam)
        mu = jnp.asarray(smp.hyper_u_mu)
        prec, rhs = _ratings_stats(v, buckets, n_new, use_kernel)
        prec = lam[None] + alpha * prec
        rhs = (lam @ mu)[None] + alpha * rhs
        if sample:
            key, sub = jax.random.split(key)
        else:
            sub = None  # posterior mean: the z = 0 limb of the same solve
        out.append(sample_mvn_precision(sub, prec, rhs, use_kernel=use_kernel))
    return jnp.stack(out)  # (S, n_new, K)
