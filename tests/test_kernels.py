"""Per-kernel allclose sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.gibbs import sample_mvn_precision
from repro.kernels import chol_solve, ops, ref


# ---------------------------------------------------------------------------
# masked syrk
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("r,w,k", [
    (8, 16, 8), (16, 32, 16), (8, 256, 64), (5, 33, 24), (1, 8, 64), (24, 128, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_syrk_shapes(r, w, k, dtype):
    rng = np.random.default_rng(r * 1000 + w + k)
    vm = jnp.asarray(rng.normal(size=(r, w, k)), dtype)
    rv = jnp.asarray(rng.normal(size=(r, w)), dtype)
    p1, b1 = ops.masked_syrk(vm, rv)
    p2, b2 = ref.masked_syrk_ref(vm, rv)
    np.testing.assert_allclose(p1, p2, rtol=2e-5, atol=3e-4)
    np.testing.assert_allclose(b1, b2, rtol=2e-5, atol=3e-4)


@settings(max_examples=15, deadline=None)
@given(
    r=st.integers(1, 20), w=st.integers(1, 80), k=st.integers(1, 48),
    seed=st.integers(0, 1000),
)
def test_syrk_property(r, w, k, seed):
    rng = np.random.default_rng(seed)
    vm = jnp.asarray(rng.normal(size=(r, w, k)), jnp.float32)
    rv = jnp.asarray(rng.normal(size=(r, w)), jnp.float32)
    p1, b1 = ops.masked_syrk(vm, rv)
    p2, b2 = ref.masked_syrk_ref(vm, rv)
    np.testing.assert_allclose(p1, p2, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(b1, b2, rtol=1e-4, atol=1e-3)
    # precision matrices are symmetric PSD by construction
    np.testing.assert_allclose(p1, np.swapaxes(np.asarray(p1), 1, 2), atol=1e-5)


# ---------------------------------------------------------------------------
# fused cholesky-solve-sample
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,k", [
    (16, 16), (32, 64), (7, 24), (1, 8), (64, 32),
    # batches that do not fill the kernel's tile, at the sweep's K
    (1, 64), (7, 64), (129, 64), (300, 64),
])
def test_chol_solve_shapes(b, k):
    rng = np.random.default_rng(b + k)
    a = rng.normal(size=(b, k, k))
    prec = jnp.asarray(a @ np.transpose(a, (0, 2, 1)) + (k * 0.1 + 0.5) * np.eye(k), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(b, k)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(b, k)), jnp.float32)
    x1 = ops.chol_solve_sample(prec, rhs, z)
    x2 = ref.chol_solve_sample_ref(prec, rhs, z)
    np.testing.assert_allclose(x1, x2, rtol=2e-3, atol=2e-3)
    # the sweep's off-TPU solver, on the same z
    x3 = sample_mvn_precision(None, prec, rhs, z=z, solver="subst")
    np.testing.assert_allclose(x1, x3, rtol=2e-3, atol=2e-3)


def test_chol_solve_zero_noise_solves_system():
    """With z = 0 the kernel output solves Lambda x = rhs exactly."""
    rng = np.random.default_rng(5)
    b, k = 8, 32
    a = rng.normal(size=(b, k, k))
    prec = jnp.asarray(a @ np.transpose(a, (0, 2, 1)) + 4.0 * np.eye(k), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(b, k)), jnp.float32)
    x = ops.chol_solve_sample(prec, rhs, jnp.zeros_like(rhs))
    recon = jnp.einsum("bij,bj->bi", prec, x)
    np.testing.assert_allclose(recon, rhs, rtol=3e-3, atol=3e-3)


def test_chol_solve_low_rank_systems_match_float64():
    """The sweep's typical system: a compound with three ratings, the
    prior's diagonal plus a rank-3 term, solved against float64."""
    rng = np.random.default_rng(11)
    b, k = 256, 64
    v = rng.normal(size=(b, 3, k))
    prec = np.einsum("bwk,bwl->bkl", v, v) + 0.7 * np.eye(k)
    rhs = rng.normal(size=(b, k))
    z = rng.normal(size=(b, k))
    chol = np.linalg.cholesky(prec)
    want = np.stack([np.linalg.solve(chol[i].T, np.linalg.solve(chol[i], rhs[i]) + z[i])
                     for i in range(b)])
    got = np.asarray(ops.chol_solve_sample(*(jnp.asarray(x, jnp.float32)
                                             for x in (prec, rhs, z))))
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-5


def test_chol_solve_lane_stats_count_systems_and_tiles():
    """lane_stats() adds each call's systems and its lanes, the batch padded
    to whole tiles; a stacked (S, B) batch is one launch."""
    rng = np.random.default_rng(3)
    k = 8
    prec = jnp.broadcast_to(2.0 * jnp.eye(k), (2, 150, k, k))   # L = sqrt(2) I
    rhs = jnp.asarray(rng.normal(size=(2, 150, k)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(2, 150, k)), jnp.float32)
    before = chol_solve.lane_stats()
    x1 = ops.chol_solve_sample(prec[0], rhs[0], z[0])
    x2 = ops.chol_solve_sample(prec, rhs, z)
    systems, lanes = (a - b for a, b in zip(chol_solve.lane_stats(), before))
    tile = chol_solve.BLOCK
    assert (systems, lanes) == (150 + 300, tile * (-(-150 // tile) + -(-300 // tile)))
    want = rhs / 2 + z / np.sqrt(2.0)
    np.testing.assert_allclose(x1, want[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(x2, want, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# fused gather+syrk+segment-reduce (V stays in HBM; rows gathered in-kernel)
# ---------------------------------------------------------------------------
def _sorted_segments(rng, r, n_seg):
    """Nondecreasing dense segment ids with ragged boundaries: every segment
    gets at least one row, the rest are assigned at random."""
    assert r >= n_seg
    extra = np.sort(rng.integers(0, n_seg, r - n_seg))
    return np.sort(np.concatenate([np.arange(n_seg), extra])).astype(np.int32)


def _seg_ref(idx, val, msk, seg, n_seg, v):
    """numpy oracle: einsum row stats + segment scatter-add."""
    vm = np.asarray(v)[..., np.asarray(idx), :] * np.asarray(msk)[..., None]
    prec_rows = np.einsum("...rwk,...rwl->...rkl", vm, vm)
    rhs_rows = np.einsum("...rwk,...rw->...rk", vm, np.asarray(val * msk))
    shape = vm.shape[:-3] + (n_seg,)
    p = np.zeros(shape + vm.shape[-1:] * 2, np.float32)
    b = np.zeros(shape + vm.shape[-1:], np.float32)
    if vm.ndim == 3:
        np.add.at(p, seg, prec_rows)
        np.add.at(b, seg, rhs_rows)
    else:
        for s in range(vm.shape[0]):
            np.add.at(p[s], seg, prec_rows[s])
            np.add.at(b[s], seg, rhs_rows[s])
    return p, b


@pytest.mark.parametrize("r,w,n,k,n_seg", [
    (8, 16, 40, 8, 5),       # aligned rows, ragged segments
    (16, 32, 100, 16, 16),   # identity segments
    (13, 8, 20, 24, 9),      # rows need padding
    (24, 256, 60, 16, 11),   # multiple W tiles (double-buffered DMA path)
])
@pytest.mark.parametrize("interpret", [True, None])
def test_gather_syrk_seg_matches_reference(r, w, n, k, n_seg, interpret):
    """interpret=True runs the real Pallas kernel; None the jnp fused path."""
    rng = np.random.default_rng(r * 100 + w + n_seg)
    idx = jnp.asarray(rng.integers(0, n, (r, w)), jnp.int32)
    val = jnp.asarray(rng.normal(size=(r, w)), jnp.float32)
    msk = jnp.asarray((rng.random((r, w)) > 0.3).astype(np.float32))
    seg = _sorted_segments(rng, r, n_seg)
    v = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    p1, b1 = ops.gather_syrk_seg(
        idx, val, msk, jnp.asarray(seg), n_seg, v, interpret=interpret
    )
    p2, b2 = _seg_ref(idx, val, msk, seg, n_seg, v)
    assert p1.shape == (n_seg, k, k) and b1.shape == (n_seg, k)
    np.testing.assert_allclose(p1, p2, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(b1, b2, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("interpret", [True, None])
def test_gather_syrk_seg_stacked_draws(interpret):
    """The leading stacked-draw axis (serving fold-in) rides the same kernel."""
    rng = np.random.default_rng(7)
    s, r, w, n, k, n_seg = 3, 11, 16, 30, 8, 6
    idx = jnp.asarray(rng.integers(0, n, (r, w)), jnp.int32)
    val = jnp.asarray(rng.normal(size=(r, w)), jnp.float32)
    msk = jnp.asarray((rng.random((r, w)) > 0.4).astype(np.float32))
    seg = _sorted_segments(rng, r, n_seg)
    v = jnp.asarray(rng.normal(size=(s, n, k)), jnp.float32)
    p1, b1 = ops.gather_syrk_seg(
        idx, val, msk, jnp.asarray(seg), n_seg, v, interpret=interpret
    )
    p2, b2 = _seg_ref(idx, val, msk, seg, n_seg, v)
    assert p1.shape == (s, n_seg, k, k)
    np.testing.assert_allclose(p1, p2, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(b1, b2, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("interpret", [True, None])
def test_gather_syrk_seg_bf16_gather_tolerance(interpret):
    """bf16 gather keeps fp32 accumulation: ~1e-2 relative, not 1e-4."""
    rng = np.random.default_rng(3)
    r, w, n, k, n_seg = 16, 32, 50, 16, 10
    idx = jnp.asarray(rng.integers(0, n, (r, w)), jnp.int32)
    val = jnp.asarray(rng.normal(size=(r, w)), jnp.float32)
    msk = jnp.asarray((rng.random((r, w)) > 0.3).astype(np.float32))
    seg = _sorted_segments(rng, r, n_seg)
    v = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    p1, b1 = ops.gather_syrk_seg(
        idx, val, msk, jnp.asarray(seg), n_seg, v,
        bf16_gather=True, interpret=interpret,
    )
    p2, b2 = _seg_ref(idx, val, msk, seg, n_seg, v)
    np.testing.assert_allclose(p1, p2, rtol=3e-2, atol=3e-1)
    np.testing.assert_allclose(b1, b2, rtol=3e-2, atol=3e-1)
    # and the fp32 path is strictly tighter on the same inputs
    p3, _ = ops.gather_syrk_seg(
        idx, val, msk, jnp.asarray(seg), n_seg, v, interpret=interpret
    )
    assert np.abs(np.asarray(p3) - p2).max() < np.abs(np.asarray(p1) - p2).max()


@pytest.mark.parametrize("r,w,n,k", [(8, 16, 40, 8), (16, 32, 100, 16), (5, 8, 20, 24)])
def test_gather_syrk_fused_matches_two_step(r, w, n, k):
    rng = np.random.default_rng(r + w + n)
    idx = jnp.asarray(rng.integers(0, n, (r, w)), jnp.int32)
    val = jnp.asarray(rng.normal(size=(r, w)), jnp.float32)
    msk = jnp.asarray((rng.random((r, w)) > 0.3).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    p1, b1 = ops.gather_syrk(idx, val, msk, v, interpret=True)
    vm = v[idx] * msk[..., None]
    p2, b2 = ref.masked_syrk_ref(vm, val * msk)
    np.testing.assert_allclose(p1, p2, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(b1, b2, rtol=1e-4, atol=1e-3)
