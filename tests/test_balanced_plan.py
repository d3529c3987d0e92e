"""Balanced (work-stealing-equivalent) planner: width-ladder fitting, plan
invariants under non-pow2 widths, and the padding-efficiency gate the paper's
load-balance claim rides on."""
import numpy as np
import jax
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import GibbsSampler, plan_buckets
from repro.core.buckets import (
    BALANCED,
    DEFAULT_WIDTHS,
    SUBLANE,
    balanced_widths,
    pad_bucket,
    resolve_widths,
)
from repro.data import (
    chembl_like,
    movielens_like,
    synthetic_lowrank,
    train_test_split,
)
from repro.data.sparse import csr_from_coo


def _sent(w, lane):
    """The width the chip is sent under `lane` (None: the sublane rule)."""
    step = lane or (SUBLANE if w >= SUBLANE else 1)
    return -(-w // step) * step


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(5, 400),
    zipf_a=st.floats(1.2, 3.0),
    max_buckets=st.integers(1, 10),
    lane=st.sampled_from([None, 1, 4, 8]),
    seed=st.integers(0, 5000),
)
def test_balanced_widths_properties(n, zipf_a, max_buckets, lane, seed):
    """Property: the fitted ladder is sorted, unique, lane-aligned, within
    the bucket budget, and wide enough for every in-range degree."""
    rng = np.random.default_rng(seed)
    degrees = rng.zipf(zipf_a, size=n).astype(np.int64)
    widths = balanced_widths(
        degrees, max_buckets=max_buckets, lane=lane, max_width=512
    )
    assert len(widths) >= 1
    assert len(widths) <= max_buckets
    assert list(widths) == sorted(set(widths))
    assert all(w == _sent(w, lane) for w in widths)
    in_range = degrees[(degrees > 0) & (degrees <= 512)]
    if in_range.size:
        assert widths[-1] >= in_range.max() or 512 in widths
    if (degrees > 512).any():
        # oversize mass forces a max-width split bucket
        assert widths[-1] == _sent(512, lane)


def test_balanced_widths_degenerate_inputs():
    assert balanced_widths(np.array([], np.int64)) == (1,)
    assert balanced_widths(np.zeros(10, np.int64)) == (1,)
    # all oversize: only the split bucket
    assert balanced_widths(np.array([9000, 4000]), max_width=512) == (512,)
    # a budget of one bucket: the split bucket takes the in-range items too
    assert balanced_widths(np.array([3, 9000]), max_buckets=1) == (512,)
    with pytest.raises(ValueError):
        balanced_widths(np.array([1, 2, 3]), max_buckets=0)


def test_resolve_widths_rejects_unknown_string():
    with pytest.raises(ValueError, match="balanced"):
        resolve_widths("lpt", np.array([1, 2, 3]))
    assert resolve_widths(BALANCED, np.array([3, 3, 3])) == (3,)
    assert resolve_widths((32, 8), np.array([1])) == (8, 32)


def _chembl_csr():
    ratings, _, _ = chembl_like(scale=0.004, seed=0)
    train, _ = train_test_split(ratings, 0.05, seed=1)
    c = train.centered()
    m, n = train.shape
    indptr, idx, vals = csr_from_coo(c.rows, c.cols, c.vals, m)
    return indptr, idx, vals, m, n


def test_chembl_padding_efficiency_gate():
    """The acceptance gate of the planner rewrite: > 0.7 on the chembl-like
    profile, where the pow2 ladder managed 0.290 (the seed's number)."""
    indptr, idx, vals, m, n = _chembl_csr()
    balanced = plan_buckets(indptr, idx, vals, m, n, widths=BALANCED)
    pow2 = plan_buckets(indptr, idx, vals, m, n, widths=DEFAULT_WIDTHS)
    assert balanced.padding_efficiency > 0.7, balanced.stats()
    assert balanced.padding_efficiency > pow2.padding_efficiency
    assert pow2.padding_efficiency < 0.35  # the problem being fixed is real


def test_balanced_plan_is_lossless_and_pad_keeps_invariants():
    """Every rating survives the non-pow2 re-layout, and pad_bucket keeps
    seg_ids dense-nondecreasing (the fused kernel's reduction invariant)."""
    indptr, idx, vals, m, n = _chembl_csr()
    plan = plan_buckets(indptr, idx, vals, m, n, widths=BALANCED)
    assert plan.nnz == int(np.diff(indptr).sum())
    assert sum(float(b.mask.sum()) for b in plan.buckets) == plan.nnz
    # each row padded to its bucket's width and no further: the DP's cost
    assert plan.widths == balanced_widths(np.diff(indptr))
    assert plan.padded == _sent_slots(np.diff(indptr), plan.widths)
    for b in plan.buckets:
        padded = pad_bucket(b, b.rows + 5, b.n_segments + 3)
        s = padded.seg_ids
        assert (np.diff(s) >= 0).all()
        assert s.max() == padded.n_segments - 1
        assert padded.mask[b.rows:].sum() == 0  # pad rows contribute nothing
        # unpadded prefix untouched
        np.testing.assert_array_equal(padded.seg_ids[: b.rows], b.seg_ids)
        np.testing.assert_array_equal(padded.values[: b.rows], b.values)


def test_split_item_segment_sum_recombination():
    """A heavy item split across rows of the widest bucket must recombine,
    via the per-bucket segment sum, to the exact unsplit statistics."""
    rng = np.random.default_rng(3)
    deg = 23                      # > widest width below -> 3 split rows
    n_counter = 40
    cols = rng.choice(n_counter, deg, replace=False).astype(np.int32)
    vals = rng.normal(size=deg).astype(np.float32)
    indptr = np.array([0, deg], np.int64)
    plan = plan_buckets(indptr, cols, vals, 1, n_counter, widths=(3, 9))
    (b,) = plan.buckets
    assert b.width == 9 and b.rows == 3 and b.n_segments == 1

    k = 5
    v = rng.normal(size=(n_counter, k)).astype(np.float32)
    g = v[b.indices] * b.mask[..., None]               # (rows, w, k)
    prec_rows = np.einsum("rwk,rwl->rkl", g, g)
    rhs_rows = np.einsum("rwk,rw->rk", g, b.values * b.mask)
    prec = np.zeros((1, k, k), np.float32)
    rhs = np.zeros((1, k), np.float32)
    np.add.at(prec, b.seg_ids, prec_rows)
    np.add.at(rhs, b.seg_ids, rhs_rows)

    vj = v[cols]
    np.testing.assert_allclose(prec[0], vj.T @ vj, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(rhs[0], vj.T @ vals, rtol=1e-5, atol=1e-5)


def test_balanced_sweep_matches_pow2_sweep():
    """The Gibbs chain is plan-independent: a sweep under the balanced
    ladder must match the pow2-ladder sweep up to fp32 accumulation-order
    rounding (the noise is drawn per item, not per plan slot)."""
    ratings, _, _ = chembl_like(scale=0.004, seed=0)
    train, test = train_test_split(ratings, 0.1, seed=2)
    s_bal = GibbsSampler(train, test, k=8, alpha=2.0, widths=BALANCED)
    s_pow = GibbsSampler(train, test, k=8, alpha=2.0, widths=(8, 32, 128, 512))
    st_b = s_bal.sweep(s_bal.init(0))
    st_p = s_pow.sweep(s_pow.init(0))
    np.testing.assert_allclose(
        np.asarray(st_b.u), np.asarray(st_p.u), rtol=2e-3, atol=2e-3
    )
    np.testing.assert_allclose(
        np.asarray(st_b.v), np.asarray(st_p.v), rtol=2e-3, atol=2e-3
    )


def _degree_profiles():
    """Degree profiles shaped like the two deployments: ChEMBL's one- and
    two-rating compounds over a few thousand targets, and MovieLens' ~130
    ratings a user and ~660 a movie."""
    chembl, _, _ = chembl_like(scale=0.02, seed=0)
    ml20m, _, _ = movielens_like(scale=0.01, seed=0)
    out = {}
    for name, r in (("chembl", chembl), ("ml20m", ml20m)):
        out[f"{name}-users"] = np.bincount(r.rows, minlength=r.shape[0])
        out[f"{name}-items"] = np.bincount(r.cols, minlength=r.shape[1])
    return out


PROFILES = ("chembl-users", "chembl-items", "ml20m-users", "ml20m-items")


@pytest.fixture(scope="module")
def profiles():
    return _degree_profiles()


def _sent_slots(degrees, widths):
    """Slots the plan sends for `degrees` under `widths`: each item's row
    width, times its row count where the widest bucket splits it."""
    w = np.asarray(widths)
    d = degrees[degrees > 0]
    fit = w[np.clip(np.searchsorted(w, d), 0, len(w) - 1)]
    return int((np.maximum(1, -(-d // fit)) * fit).sum())


@pytest.mark.parametrize("profile", PROFILES)
def test_default_ladder_aligns_wide_widths_only(profiles, profile):
    """Every width of 8 or more is a multiple of the sublane tile; narrower
    widths are sent exact (each is a degree of the profile); lane=8 still
    aligns the narrow ones too."""
    degrees = profiles[profile]
    exact = balanced_widths(degrees, lane=1)
    assert any(w % SUBLANE for w in exact if w >= SUBLANE)  # rule matters
    widths = balanced_widths(degrees)
    assert all(w % SUBLANE == 0 for w in widths if w >= SUBLANE), widths
    assert set(w for w in widths if w < SUBLANE) <= set(degrees.tolist())
    assert all(w % 8 == 0 for w in balanced_widths(degrees, lane=8))


@pytest.mark.parametrize("profile", PROFILES)
def test_default_ladder_is_optimal_under_rounding(profiles, profile):
    """The DP charges each row the width the chip is sent, so its ladder
    sends the fewest slots of any aligned ladder within the budget (checked
    exhaustively for three buckets), and never more than the exact ladder
    rounded up after the fact."""
    from itertools import combinations

    degrees = profiles[profile]
    widths = balanced_widths(degrees, max_buckets=3)
    in_range = degrees[(degrees > 0) & (degrees <= 512)]
    cands = sorted({_sent(int(x), None) for x in in_range})
    split = {512} if (degrees > 512).any() else set()
    budget = 3 - len(split)
    best = min(
        _sent_slots(degrees, sorted(set(c) | {cands[-1]} | split))
        for n in range(budget)
        for c in combinations(cands[:-1], n)
    )
    assert _sent_slots(degrees, widths) == best

    rounded = sorted({_sent(w, None) for w in balanced_widths(degrees, lane=1)})
    assert _sent_slots(degrees, balanced_widths(degrees)) <= _sent_slots(
        degrees, rounded
    )


@pytest.fixture(scope="module")
def small_data():
    ratings, _, _ = synthetic_lowrank(200, 150, k_true=6, nnz=6000,
                                      noise=0.3, seed=2)
    return train_test_split(ratings, 0.1, seed=3)


def test_aligned_ladder_sweep_matches_exact_ladder(small_data):
    """The pad slots the sublane rule adds carry index 0, value 0 and mask
    0: two sweeps on the aligned ladder match two on the explicit exact
    ladder to fp32 summation order."""
    train, test = small_data
    degrees = np.bincount(train.rows, minlength=train.shape[0])
    exact = balanced_widths(degrees, lane=1)
    assert any(w % SUBLANE for w in exact if w >= SUBLANE)
    s_al = GibbsSampler(train, test, k=16, alpha=10.0, widths=BALANCED)
    s_ex = GibbsSampler(train, test, k=16, alpha=10.0, widths=exact)
    assert s_al.user_plan_host.widths == balanced_widths(degrees)
    assert s_al.user_plan_host.padded > s_ex.user_plan_host.padded
    st_a, st_e = s_al.init(0), s_ex.init(0)
    for _ in range(2):
        st_a, st_e = s_al.sweep(st_a), s_ex.sweep(st_e)
    np.testing.assert_allclose(np.asarray(st_a.u), np.asarray(st_e.u),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_a.v), np.asarray(st_e.v),
                               rtol=1e-5, atol=1e-5)
