"""Sweep-engine equivalence: the restructured/fused engines must produce
the same samples as the reference engine from a shared key — single-host,
distributed ring (subprocess: jax pins the device count at first init),
and the stacked-draw serving fold-in."""
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import GibbsSampler
from repro.core.gibbs import (
    chol_subst_solve,
    resolve_engine,
    sample_mvn_precision,
    update_factors,
)
from repro.data import synthetic_lowrank, train_test_split

SRC = str(Path(__file__).resolve().parents[1] / "src")


@pytest.fixture(scope="module")
def small_data():
    ratings, _, _ = synthetic_lowrank(200, 150, k_true=6, nnz=6000, noise=0.3, seed=2)
    return train_test_split(ratings, 0.1, seed=3)


# ---------------------------------------------------------------------------
# engine flag resolution
# ---------------------------------------------------------------------------
def test_resolve_engine():
    assert resolve_engine(None) == "einsum"
    assert resolve_engine(None, use_kernel=True) == "kernel"
    assert resolve_engine("fused") == "fused"
    with pytest.raises(ValueError):
        resolve_engine("warp")
    # 'sgld' is a valid --engine choice but not a sweep implementation:
    # the error must list the sweep engines AND point at the SGLD samplers
    with pytest.raises(ValueError, match="SGLDSampler"):
        resolve_engine("sgld")


# ---------------------------------------------------------------------------
# solver equivalence
# ---------------------------------------------------------------------------
def test_subst_solver_matches_lapack():
    rng = np.random.default_rng(0)
    b, k = 37, 24
    a = rng.normal(size=(b, k, k)).astype(np.float32)
    prec = jnp.asarray(a @ a.transpose(0, 2, 1) + 3 * np.eye(k, dtype=np.float32))
    rhs = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32))
    z = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32))
    x_l = sample_mvn_precision(None, prec, rhs, z=z, solver="lapack")
    x_s = sample_mvn_precision(None, prec, rhs, z=z, solver="subst")
    np.testing.assert_allclose(x_s, x_l, rtol=1e-4, atol=1e-4)
    # leading batch axes flatten-free (the fold-in's (S, B) stack)
    x2 = chol_subst_solve(
        jnp.linalg.cholesky(prec.reshape(1, b, k, k)),
        rhs.reshape(1, b, k), z.reshape(1, b, k),
    )
    np.testing.assert_allclose(x2[0], x_s, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# single-host: update_factors and full sweeps agree across engines
# ---------------------------------------------------------------------------
def test_update_factors_engines_match(small_data):
    train, _ = small_data
    s = GibbsSampler(train, None, k=16, alpha=8.0, widths=(8, 32, 128))
    state = s.init(0)
    key = jax.random.PRNGKey(42)
    out = {}
    for engine in ("reference", "einsum", "fused"):
        new, stats = update_factors(
            key, state.u, s.item_buckets, s.n, state.hyper_v, 8.0,
            engine=engine,
        )
        out[engine] = np.asarray(new)
        assert np.isfinite(out[engine]).all()
    np.testing.assert_allclose(out["einsum"], out["reference"], atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(out["fused"], out["reference"], atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("engine", ["einsum", "fused"])
def test_update_factors_row_chunks_match_whole_buckets(small_data, engine,
                                                       monkeypatch):
    """Buckets longer than one chunk are swept in row chunks (the HBM bound
    at full dataset size); the draws must not depend on the chunking, for
    identity-segment buckets and for the bucket that splits items."""
    import repro.core.gibbs as gibbs

    train, _ = small_data
    s = GibbsSampler(train, None, k=16, alpha=8.0, widths=(8, 32))
    state = s.init(0)
    key = jax.random.PRNGKey(7)
    buckets = s.user_buckets
    assert not all(b.identity_segments for b in buckets)

    def run():
        new, _ = update_factors(key, state.v, buckets, s.m, state.hyper_u,
                                8.0, engine=engine)
        return np.asarray(new)

    whole = run()
    monkeypatch.setattr(gibbs, "CHUNK_BYTES", 4 * 16 * 32 * 8)  # 8-row chunks
    assert all(b.indices.shape[0] > gibbs.chunk_rows(b.width, 16)
               for b in buckets)
    np.testing.assert_allclose(run(), whole, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("engine", ["einsum", "fused", "kernel"])
def test_gibbs_sweeps_identical_across_engines(small_data, engine):
    """Two full sweeps from one seed: every engine draws the same samples
    (shared z bits; only solve rounding differs)."""
    train, test = small_data
    ref = GibbsSampler(train, test, k=16, alpha=10.0, widths=(8, 32, 128),
                       engine="reference")
    alt = GibbsSampler(train, test, k=16, alpha=10.0, widths=(8, 32, 128),
                       engine=engine)
    st_r, st_a = ref.init(0), alt.init(0)
    for _ in range(2):
        st_r, st_a = ref.sweep(st_r), alt.sweep(st_a)
    np.testing.assert_allclose(np.asarray(st_a.u), np.asarray(st_r.u),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(st_a.v), np.asarray(st_r.v),
                               atol=2e-3, rtol=2e-3)


def test_sweep_kernel_solve_matches_subst(small_data, monkeypatch):
    """On a TPU the restructured engines solve through the batch-on-lanes
    Pallas kernel (`gibbs._sweep_solver`); here it runs in interpret mode.
    A sweep draws the same factors as with the substitution solver, to f32
    rounding, on a plan whose widest user bucket splits users across rows,
    and lane_stats() counts every system the plan holds."""
    import repro.core.gibbs as gibbs
    from repro.kernels import chol_solve

    train, test = small_data
    kw = dict(k=16, alpha=10.0, widths=(8, 32))
    subst = GibbsSampler(train, test, **kw)
    assert gibbs._sweep_solver(subst.engine) == "subst"
    st_s = subst.sweep(subst.init(0))

    monkeypatch.setattr(gibbs, "_sweep_solver", lambda engine: "kernel")
    kern = GibbsSampler(train, test, **kw)
    buckets = kern.item_buckets + kern.user_buckets
    assert not all(b.identity_segments for b in buckets)
    before = chol_solve.lane_stats()
    st_k = kern.sweep(kern.init(0))
    systems, lanes = (a - b for a, b in zip(chol_solve.lane_stats(), before))
    assert systems == sum(b.n_segments for b in buckets)
    tile = chol_solve.BLOCK
    assert lanes == sum(-(-b.n_segments // tile) * tile for b in buckets)
    for got, want in ((st_k.u, st_s.u), (st_k.v, st_s.v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_bf16_gather_engine_close_but_looser(small_data):
    train, _ = small_data
    f32 = GibbsSampler(train, None, k=16, alpha=10.0, widths=(8, 32),
                       engine="fused")
    bf16 = GibbsSampler(train, None, k=16, alpha=10.0, widths=(8, 32),
                        engine="fused", bf16_gather=True)
    st_f, st_b = f32.init(0), bf16.init(0)
    st_f, st_b = f32.sweep(st_f), bf16.sweep(st_b)
    # same chain to bf16-rounding tolerance (documented accuracy contract)
    np.testing.assert_allclose(np.asarray(st_b.u), np.asarray(st_f.u),
                               atol=0.05, rtol=0.05)
    assert np.abs(np.asarray(st_b.u) - np.asarray(st_f.u)).max() > 0


# ---------------------------------------------------------------------------
# distributed ring: fused engine matches einsum bit-for-bit per mode
# ---------------------------------------------------------------------------
def test_distributed_ring_engines_match():
    """Ring-mode fused vs einsum parity on 4 simulated devices. Kept small
    enough for tier-1 (two configs, tiny data); the full ring-vs-allgather
    cross-product lives in tests/test_distributed.py's slow suite."""
    prog = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
        f"import sys\nsys.path.insert(0, {SRC!r})\n"
        + textwrap.dedent("""
        import numpy as np
        from repro.core.distributed import DistributedBPMF
        from repro.data import synthetic_lowrank, train_test_split

        ratings, _, _ = synthetic_lowrank(100, 60, k_true=4, nnz=1500,
                                          noise=0.3, seed=3)
        train, test = train_test_split(ratings, 0.1, seed=4)
        outs = {}
        for engine in ('einsum', 'fused'):
            s = DistributedBPMF(train, test, k=8, alpha=10.0,
                                mode='ring', engine=engine)
            outs[engine] = s.gather_factors(s.run(2, seed=7))
        u1, v1 = outs['einsum']
        u2, v2 = outs['fused']
        np.testing.assert_allclose(u2, u1, atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(v2, v1, atol=2e-4, rtol=2e-4)
        print('dist engines ok')
        """)
    )
    res = subprocess.run([sys.executable, "-c", prog],
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "dist engines ok" in res.stdout


@pytest.mark.slow
def test_distributed_allgather_engines_match():
    """Allgather-mode fused vs einsum parity + cross-mode agreement (the
    heavier cross-product, slow-marked per the distributed-test convention)."""
    prog = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
        f"import sys\nsys.path.insert(0, {SRC!r})\n"
        + textwrap.dedent("""
        import numpy as np
        from repro.core.distributed import DistributedBPMF
        from repro.data import synthetic_lowrank, train_test_split

        ratings, _, _ = synthetic_lowrank(150, 100, k_true=4, nnz=3000,
                                          noise=0.3, seed=3)
        train, test = train_test_split(ratings, 0.1, seed=4)
        outs = {}
        for mode in ('ring', 'allgather'):
            for engine in ('einsum', 'fused'):
                s = DistributedBPMF(train, test, k=8, alpha=10.0,
                                    mode=mode, engine=engine)
                outs[(mode, engine)] = s.gather_factors(s.run(3, seed=7))
        for mode in ('ring', 'allgather'):
            u1, v1 = outs[(mode, 'einsum')]
            u2, v2 = outs[(mode, 'fused')]
            np.testing.assert_allclose(u2, u1, atol=2e-4, rtol=2e-4)
            np.testing.assert_allclose(v2, v1, atol=2e-4, rtol=2e-4)
        # and the ring still matches the sync baseline across engines
        np.testing.assert_allclose(outs[('ring', 'fused')][0],
                                   outs[('allgather', 'einsum')][0],
                                   atol=2e-3, rtol=2e-3)
        print('dist engines ok')
        """)
    )
    res = subprocess.run([sys.executable, "-c", prog],
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "dist engines ok" in res.stdout


def test_per_item_noise_batched_bits_pinned():
    """Regression: the batched fold-in of the id vector produces the exact
    bits of folding each id separately (layout-independent determinism)."""
    from repro.core.distributed import _per_item_noise

    key = jax.random.PRNGKey(11)
    ids = jnp.asarray([5, 0, -1, 17, 3, 3], jnp.int32)
    got = np.asarray(_per_item_noise(key, ids, 8))
    want = np.stack([
        np.asarray(jax.random.normal(
            jax.random.fold_in(key, int(max(i, 0))), (8,), jnp.float32))
        for i in np.asarray(ids)
    ])
    assert np.array_equal(got, want)  # bit-exact, not allclose


# ---------------------------------------------------------------------------
# stacked-draw fold-in rides the fused kernel
# ---------------------------------------------------------------------------
def _toy_ensemble(rng, s=3, m=40, n=60, k=8):
    from repro.serve import PosteriorEnsemble

    def spd():
        a = rng.normal(size=(k, k)).astype(np.float32) / np.sqrt(k)
        return a @ a.T + 2.0 * np.eye(k, dtype=np.float32)

    return PosteriorEnsemble.from_arrays(
        rng.normal(size=(s, m, k)).astype(np.float32),
        rng.normal(size=(s, n, k)).astype(np.float32),
        hyper_u_mu=rng.normal(size=(s, k)).astype(np.float32) * 0.1,
        hyper_u_lam=np.stack([spd() for _ in range(s)]),
        hyper_v_mu=np.zeros((s, k), np.float32),
        hyper_v_lam=np.stack([np.eye(k, dtype=np.float32)] * s),
        global_mean=3.5,
        alpha=2.0,
        steps=list(range(s)),
    )


def _toy_batch(rng, n_new, n_items):
    from repro.data.sparse import SparseRatings

    rows, cols, vals = [], [], []
    for u in range(n_new):
        d = int(rng.integers(1, 9))
        rows.extend([u] * d)
        cols.extend(rng.choice(n_items, d, replace=False).tolist())
        vals.extend(rng.normal(3.5, 1.0, d).tolist())
    return SparseRatings(
        rows=np.asarray(rows, np.int32), cols=np.asarray(cols, np.int32),
        vals=np.asarray(vals, np.float32), shape=(n_new, n_items),
    )


@pytest.mark.parametrize("sample", [False, True])
def test_fold_in_fused_engine_matches_loop(sample):
    from repro.serve import fold_in, fold_in_loop

    rng = np.random.default_rng(0)
    ens = _toy_ensemble(rng)
    ratings = _toy_batch(rng, 7, ens.n_items)
    key = jax.random.PRNGKey(5) if sample else None
    out_loop = fold_in_loop(key, ratings, ens, sample=sample)
    out_ein = fold_in(key, ratings, ens, sample=sample, engine="einsum")
    out_fus = fold_in(key, ratings, ens, sample=sample, engine="fused")
    assert out_fus.shape == (ens.n_samples, 7, ens.k)
    np.testing.assert_allclose(np.asarray(out_ein), np.asarray(out_loop),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(out_fus), np.asarray(out_loop),
                               atol=2e-4, rtol=2e-4)


def test_fold_in_fused_engine_with_plan_cache_padding():
    """pad_bucket keeps seg_ids nondecreasing (pad rows -> last segment), so
    the fused engine accepts quantized/padded plans unchanged."""
    from repro.core.buckets import pad_bucket, plan_buckets
    from repro.data.sparse import csr_from_coo
    from repro.serve import FoldInPlanCache, fold_in

    rng = np.random.default_rng(1)
    ens = _toy_ensemble(rng)
    ratings = _toy_batch(rng, 5, ens.n_items)
    cache = FoldInPlanCache()
    out_exact = fold_in(None, ratings, ens, sample=False, engine="fused")
    out_padded = fold_in(None, ratings, ens, sample=False, engine="fused",
                         plan_cache=cache)
    np.testing.assert_allclose(np.asarray(out_padded), np.asarray(out_exact),
                               atol=1e-5, rtol=1e-5)

    # padding invariant directly
    indptr, idx, vals = csr_from_coo(ratings.rows, ratings.cols,
                                     ratings.vals, 5)
    plan = plan_buckets(indptr, idx, vals, 5, ens.n_items, (4, 16))
    for b in plan.buckets:
        pb = pad_bucket(b, b.rows + 3, b.n_segments + 2)
        assert (np.diff(pb.seg_ids) >= 0).all()
        assert pb.seg_ids[-1] == pb.n_segments - 1


def test_plan_cache_trace_flat_across_identity_flip():
    """Regression: two batches sharing a quantized schema must reuse one
    compiled executable even when padding makes one batch's seg_ids exactly
    arange (identity) and not the other's — the static plan key is derived
    from the schema, never from padded array contents."""
    from repro.serve import FoldInPlanCache, fold_in
    from repro.serve import foldin as foldin_mod

    rng = np.random.default_rng(4)
    ens = _toy_ensemble(rng)
    cache = FoldInPlanCache(widths=(4,), quantum=8)

    def one_rating_batch(n_new, seed):
        r = np.random.default_rng(seed)
        from repro.data.sparse import SparseRatings
        return SparseRatings(
            rows=np.arange(n_new, dtype=np.int32),
            cols=r.choice(ens.n_items, n_new, replace=False).astype(np.int32),
            vals=np.full(n_new, 3.0, np.float32),
            shape=(n_new, ens.n_items),
        )

    # 6 users -> pads 2 rows onto segment 7 (seg_ids != arange);
    # 7 users -> pads 1 row onto segment 7 (seg_ids == arange). Same schema.
    out6 = fold_in(None, one_rating_batch(6, 0), ens, sample=False,
                   plan_cache=cache)
    traces = foldin_mod.trace_count()
    out7 = fold_in(None, one_rating_batch(7, 1), ens, sample=False,
                   plan_cache=cache)
    assert foldin_mod.trace_count() == traces, "schema hit must not retrace"
    assert cache.hits == 1 and cache.misses == 1
    assert out6.shape[1] == 6 and out7.shape[1] == 7
