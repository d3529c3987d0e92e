"""Data pipeline: dataset generators, train/test split, CSR conversion."""
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.data import chembl_like, movielens_like, synthetic_lowrank, train_test_split
from repro.data.sparse import SparseRatings, csr_from_coo


def test_chembl_like_shape_and_skew():
    ratings, _, _ = chembl_like(scale=0.01, seed=0)
    ratings.validate()
    deg = ratings.degrees(1)
    # power-law skew like the paper's Fig 2: top 1% of items >> median
    top = np.sort(deg)[-max(1, len(deg) // 100):].mean()
    assert top > 8 * max(np.median(deg), 1)


def test_movielens_like_scale():
    ratings, _, _ = movielens_like(scale=0.002, seed=1)
    ratings.validate()
    n, m = ratings.shape
    target = min(int(20_000_000 * 0.002), n * m // 2)
    assert ratings.nnz >= 0.9 * target  # rejection sampling may stall near cap


def test_split_disjoint_and_complete():
    ratings, _, _ = synthetic_lowrank(100, 80, 4, 2000, seed=2)
    tr, te = train_test_split(ratings, 0.2, seed=3)
    assert tr.nnz + te.nnz == ratings.nnz
    keys = lambda r: set(zip(r.rows.tolist(), r.cols.tolist()))
    assert not (keys(tr) & keys(te))


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 50), m=st.integers(1, 40), nnz=st.integers(0, 200),
       seed=st.integers(0, 999))
def test_csr_roundtrip(n, m, nnz, seed):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n, nnz).astype(np.int32)
    cols = rng.integers(0, m, nnz).astype(np.int32)
    vals = rng.normal(size=nnz).astype(np.float32)
    indptr, idx, v = csr_from_coo(rows, cols, vals, n)
    assert indptr[-1] == nnz
    got = []
    for i in range(n):
        for j in range(indptr[i], indptr[i + 1]):
            got.append((i, int(idx[j]), float(v[j])))
    assert sorted(got) == sorted(zip(rows.tolist(), cols.tolist(), vals.astype(float).tolist()))

