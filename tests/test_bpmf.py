"""BPMF core: sampler correctness, bucket planning, hyperprior sampling."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import ALS, GibbsSampler, default_prior, plan_buckets
from repro.core.buckets import workload_model
from repro.core.hyper import sample_normal_wishart, sample_wishart
from repro.data import synthetic_lowrank, train_test_split
from repro.data.sparse import csr_from_coo


@pytest.fixture(scope="module")
def small_data():
    ratings, u, v = synthetic_lowrank(250, 180, k_true=8, nnz=8000, noise=0.3, seed=1)
    return train_test_split(ratings, 0.1, seed=2)


def test_gibbs_converges_to_noise_floor(small_data):
    train, test = small_data
    s = GibbsSampler(train, test, k=16, alpha=1.0 / 0.09, burn_in=8, widths=(8, 32, 128))
    state = s.run(30, seed=0)
    rmse = s.rmse(state)
    assert np.isfinite(rmse)
    # noise floor is 0.3; posterior mean should approach it
    assert rmse < 0.55, rmse


def test_bpmf_beats_or_matches_als(small_data):
    """Paper Sec 5.2: all versions reach the same accuracy; BPMF is robust
    without per-dataset regularization tuning (ALS given an untuned lambda)."""
    train, test = small_data
    s = GibbsSampler(train, test, k=16, alpha=1.0 / 0.09, burn_in=8, widths=(8, 32, 128))
    st_g = s.run(30, seed=0)
    als = ALS(train, test, k=16, lam_reg=0.3, widths=(8, 32, 128))  # untuned lambda
    st_a = als.run(12)
    assert s.rmse(st_g) <= als.rmse(st_a) + 0.02


def test_gibbs_kernel_path_matches_jnp(small_data):
    """use_kernel=True routes through the Pallas syrk + chol kernels."""
    train, test = small_data
    s_ref = GibbsSampler(train, test, k=16, alpha=10.0, widths=(8, 32))
    s_ker = GibbsSampler(train, test, k=16, alpha=10.0, widths=(8, 32), use_kernel=True)
    st_r = s_ref.init(0)
    st_k = s_ker.init(0)
    st_r = s_ref.sweep(st_r)
    st_k = s_ker.sweep(st_k)
    np.testing.assert_allclose(np.asarray(st_r.u), np.asarray(st_k.u), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(st_r.v), np.asarray(st_k.v), atol=2e-3, rtol=2e-3)


@settings(max_examples=25, deadline=None)
@given(
    n_items=st.integers(5, 60),
    n_counter=st.integers(5, 40),
    nnz=st.integers(1, 300),
    seed=st.integers(0, 10_000),
)
def test_bucket_plan_preserves_every_rating(n_items, n_counter, nnz, seed):
    """Property: the padded bucket plan is a lossless re-layout of the CSR."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n_items, nnz).astype(np.int32)
    cols = rng.integers(0, n_counter, nnz).astype(np.int32)
    vals = rng.normal(size=nnz).astype(np.float32)
    indptr, idx, v = csr_from_coo(rows, cols, vals, n_items)
    plan = plan_buckets(indptr, idx, v, n_items, n_counter, widths=(4, 16, 64))

    # reconstruct multiset of (item, counterpart, value) triples
    got = []
    for b in plan.buckets:
        for r in range(b.rows):
            item = b.seg_item_ids[b.seg_ids[r]]
            for w in range(b.width):
                if b.mask[r, w]:
                    got.append((int(item), int(b.indices[r, w]), float(b.values[r, w])))
    want = sorted(zip(rows.tolist(), cols.tolist(), vals.astype(float).tolist()))
    assert sorted(got) == [tuple(x) for x in want]
    assert plan.nnz == nnz
    assert 0 < plan.padding_efficiency <= 1.0


def test_bucket_plan_empty_items_field():
    """Regression: BucketPlan.empty_items is Optional with a None default —
    constructing a plan without naming it must not trip dataclass machinery,
    and a fully-rated matrix yields an empty (not None) array."""
    from repro.core.buckets import BucketPlan

    plan = BucketPlan(n_items=3, n_counterparts=2, buckets=(), nnz=0, padded=0)
    assert plan.empty_items is None

    # every item rated -> empty_items present but zero-length
    rows = np.array([0, 1, 2, 0], np.int32)
    cols = np.array([0, 1, 0, 1], np.int32)
    vals = np.ones(4, np.float32)
    indptr, idx, v = csr_from_coo(rows, cols, vals, 3)
    full = plan_buckets(indptr, idx, v, 3, 2, widths=(4, 16))
    assert full.empty_items is not None and full.empty_items.size == 0

    # item 1 unrated -> reported as empty
    rows = np.array([0, 2], np.int32)
    indptr, idx, v = csr_from_coo(rows, cols[:2], vals[:2], 3)
    gappy = plan_buckets(indptr, idx, v, 3, 2, widths=(4, 16))
    assert gappy.empty_items.tolist() == [1]


def test_workload_model_monotone():
    d = np.array([0, 1, 10, 1000, 100000])
    c = workload_model(d)
    assert np.all(np.diff(c) > 0)


def test_wishart_sampler_moments():
    """E[Wishart(nu, S)] = nu * S."""
    key = jax.random.PRNGKey(0)
    k = 4
    a = np.random.default_rng(0).normal(size=(k, k))
    s = a @ a.T + np.eye(k)
    chol = jnp.linalg.cholesky(jnp.asarray(s, jnp.float32))
    nu = jnp.asarray(12.0)
    samples = jax.vmap(lambda kk: sample_wishart(kk, nu, chol))(
        jax.random.split(key, 3000)
    )
    mean = np.asarray(samples.mean(0))
    np.testing.assert_allclose(mean, 12.0 * s, rtol=0.15)


def test_normal_wishart_posterior_concentrates():
    """With many observations the NW posterior mean tracks the sample mean."""
    rng = np.random.default_rng(1)
    k = 6
    x = rng.normal(loc=1.7, scale=0.5, size=(5000, k)).astype(np.float32)
    prior = default_prior(k)
    sum_x = jnp.asarray(x.sum(0))
    sum_xxt = jnp.asarray(x.T @ x)
    hp = sample_normal_wishart(jax.random.PRNGKey(2), sum_x, sum_xxt, x.shape[0], prior)
    np.testing.assert_allclose(np.asarray(hp.mu), x.mean(0), atol=0.05)
    # precision should approximate 1/var = 4
    prec_diag = np.diag(np.asarray(hp.lam))
    np.testing.assert_allclose(prec_diag, 4.0, rtol=0.3)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(10, 120),
    p=st.integers(2, 8),
    seed=st.integers(0, 500),
)
def test_lpt_partition_properties(n, p, seed):
    """Property: LPT assignment is a permutation-complete, load-bounded
    partition under the paper's workload model."""
    from repro.core.partition import partition_entities

    rng = np.random.default_rng(seed)
    degrees = rng.zipf(1.5, size=n).clip(0, 10_000)
    part = partition_entities(degrees, p)
    # completeness: every entity exactly once
    ids = part.ids[part.ids >= 0]
    assert sorted(ids.tolist()) == list(range(n))
    # local slots are dense per shard
    for sh in range(p):
        members = np.where(part.shard == sh)[0]
        assert sorted(part.local[members].tolist()) == list(range(len(members)))
    # LPT bound: max load <= mean + max single item (classic guarantee)
    cost = workload_model(degrees)
    loads = np.zeros(p)
    np.add.at(loads, part.shard, cost)
    assert loads.max() <= loads.mean() + cost.max() + 1e-9

