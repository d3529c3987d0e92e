"""Plain float64 references for what the timed paths produce.

Nothing here imports the program. The training reference follows the
sampler's published algorithm (Salakhutdinov & Mnih 2008, paper Algorithm 1)
and its documented random streams: the same `jax.random` keys give the same
standard normal and gamma variates, and every other operation is float64
NumPy. The serving reference scores dense and solves the fold-in in
float64. Each reference has a control that computes the same thing from
inputs rounded to float8 (e4m3) with float32 accumulation: on the TPU the
program's float32 products run at XLA's default precision, one bfloat16
pass, so the step below what it computes is an 8-bit product. The control
has to fail the comparison.
"""
from __future__ import annotations

from dataclasses import dataclass

import ml_dtypes
import numpy as np

BETA0 = 2.0     # Normal-Wishart prior: mu0 = 0, beta0 = 2, W0 = I, nu0 = K
INIT_SCALE = 0.1


def low(x: np.ndarray) -> np.ndarray:
    """Round to float8 e4m3 and back to float32: the control's inputs."""
    return np.asarray(x, np.float32).astype(ml_dtypes.float8_e4m3fn).astype(np.float32)


def gram(x: np.ndarray, control: bool = False) -> np.ndarray:
    """x^T x in float64, or of float8 inputs accumulated in float32."""
    if not control:
        return x.T @ x
    xl = low(x)
    return (xl.T @ xl).astype(np.float64)


def matvec_t(x: np.ndarray, r: np.ndarray, control: bool = False) -> np.ndarray:
    if not control:
        return x.T @ r
    return (low(x).T @ low(r)).astype(np.float64)


# --------------------------------------------------------------------------
# random streams (jax.random, on the default device)
# --------------------------------------------------------------------------
def init_factors(seed: int, m: int, n: int, k: int):
    """The sampler's initial state: U, V ~ 0.1 N(0, 1) and the chain key."""
    import jax

    key = jax.random.PRNGKey(seed)
    ku, kv, key = jax.random.split(key, 3)
    u = INIT_SCALE * np.asarray(jax.random.normal(ku, (m, k), np.float32), np.float64)
    v = INIT_SCALE * np.asarray(jax.random.normal(kv, (n, k), np.float32), np.float64)
    return u, v, key


@dataclass(frozen=True)
class SweepKeys:
    next: object
    hyper_v: object
    v: object
    hyper_u: object
    u: object


def sweep_keys(key) -> SweepKeys:
    import jax

    return SweepKeys(*jax.random.split(key, 5))


def normal_rows(key, n: int, k: int, rows: np.ndarray) -> np.ndarray:
    """Rows of the (n, k) standard normal block the half-sweep draws."""
    import jax

    z = jax.random.normal(key, (n, k), np.float32)
    return np.asarray(z[np.asarray(rows)], np.float64)


def nw_variates(key, k: int, n: int):
    """Bartlett chi-square and normal variates, and the mean's normal."""
    import jax

    kw, km = jax.random.split(key)
    kn, kc = jax.random.split(kw)
    dfs = np.float32(k + n) - np.arange(k, dtype=np.float32)
    chi2 = 2.0 * np.asarray(jax.random.gamma(kc, dfs / 2.0, dtype=np.float32), np.float64)
    normal = np.asarray(jax.random.normal(kn, (k, k), np.float32), np.float64)
    z = np.asarray(jax.random.normal(km, (k,), np.float32), np.float64)
    return chi2, normal, z


# --------------------------------------------------------------------------
# the sampler's conditionals
# --------------------------------------------------------------------------
def normal_wishart(x: np.ndarray, variates, control: bool = False):
    """(mu, Lambda) drawn from the Normal-Wishart posterior given factors x."""
    chi2, normal, z = variates
    n, k = x.shape
    sum_x = low(x).sum(0, dtype=np.float64) if control else x.sum(0)
    xbar = sum_x / n
    n_s = gram(x, control) - n * np.outer(xbar, xbar)
    beta = BETA0 + n
    mu_star = n * xbar / beta
    w_inv = np.eye(k) + n_s + (BETA0 * n / beta) * np.outer(xbar, xbar)
    w = np.linalg.inv(0.5 * (w_inv + w_inv.T))
    a = np.tril(normal, -1) + np.diag(np.sqrt(chi2))
    la = np.linalg.cholesky(0.5 * (w + w.T)) @ a
    lam = la @ la.T
    lam = 0.5 * (lam + lam.T)
    chol = np.linalg.cholesky(beta * lam + 1e-6 * np.eye(k))
    mu = mu_star + np.linalg.solve(chol.T, z)
    return mu, lam


def conditional_draws(counterpart: np.ndarray, indptr: np.ndarray,
                      cols: np.ndarray, vals: np.ndarray, rows: np.ndarray,
                      mu: np.ndarray, lam: np.ndarray, alpha: float,
                      z: np.ndarray, control: bool = False) -> np.ndarray:
    """x_i = L^-T (L^-1 b_i + z_i) with L L^T = Lambda + alpha sum v v^T and
    b_i = Lambda mu + alpha sum r v over entity i's ratings, for `rows`."""
    out = np.empty((len(rows), counterpart.shape[1]))
    lam_mu = lam @ mu
    for t, i in enumerate(rows):
        sl = slice(indptr[i], indptr[i + 1])
        c = counterpart[cols[sl]]
        prec = lam + alpha * gram(c, control)
        rhs = lam_mu + alpha * matvec_t(c, vals[sl], control)
        chol = np.linalg.cholesky(prec)
        y = np.linalg.solve(chol, rhs) + z[t]
        out[t] = np.linalg.solve(chol.T, y)
    return out


def strata_sample(degrees: np.ndarray, per_stratum: int,
                  rng: np.random.Generator) -> np.ndarray:
    """Entities from every degree stratum [2^j, 2^(j+1)), the unrated ones
    and the heaviest: every bucket width a planner could choose."""
    picks = [np.array([int(np.argmax(degrees))])]
    edges = [0, 1]
    while edges[-1] <= degrees.max():
        edges.append(edges[-1] * 2)
    for lo, hi in zip(edges[:-1], edges[1:]):
        ids = np.flatnonzero((degrees >= lo) & (degrees < max(hi, lo + 1)))
        if len(ids):
            picks.append(rng.choice(ids, min(per_stratum, len(ids)), replace=False))
    return np.unique(np.concatenate(picks))


# --------------------------------------------------------------------------
# gaps
# --------------------------------------------------------------------------
def draw_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Worst entity's ||got - want|| / ||want||."""
    num = np.linalg.norm(np.asarray(got, np.float64) - want, axis=1)
    return float(np.max(num / np.maximum(np.linalg.norm(want, axis=1), 1e-30)))


def hyper_gap(mu, lam, mu_ref, lam_ref) -> float:
    """Lambda's relative Frobenius gap, and mu's gap in units of the
    spread the hyper describes (sqrt of trace Lambda^-1)."""
    lam = np.asarray(lam, np.float64)
    g_lam = np.linalg.norm(lam - lam_ref) / np.linalg.norm(lam_ref)
    scale = np.sqrt(np.trace(np.linalg.inv(lam_ref)))
    g_mu = np.linalg.norm(np.asarray(mu, np.float64) - mu_ref) / scale
    return float(max(g_lam, g_mu))


def topn_gap(items: np.ndarray, scores_ref: np.ndarray, excluded: np.ndarray,
             topk: int) -> float:
    """How far the served list falls short of the reference's: the widest
    gap, position by position, between the reference's p-th best score and
    the reference score of the p-th served item. A served item that is
    excluded, repeated or out of range, or a short list, reads infinite."""
    items = np.asarray(items)
    n = len(scores_ref)
    legal = np.ones(n, bool)
    legal[np.asarray(excluded, np.int64)] = False
    want = min(topk, int(legal.sum()))
    got = items[items >= 0]
    if (len(got) != want or len(np.unique(got)) != len(got)
            or (got >= n).any() or not legal[got].all()):
        return float("inf")
    best = np.sort(np.where(legal, scores_ref, -np.inf))[::-1][:want]
    return float(np.max(best - scores_ref[got], initial=0.0))


def foldin_means(v: np.ndarray, lam: np.ndarray, mu: np.ndarray, alpha: float,
                 items: np.ndarray, centered: np.ndarray,
                 control: bool = False) -> np.ndarray:
    """Per-draw conditional posterior means of a new user, (S, K)."""
    out = []
    for s in range(v.shape[0]):
        c = v[s, items]
        prec = lam[s] + alpha * gram(c, control)
        rhs = lam[s] @ mu[s] + alpha * matvec_t(c, centered, control)
        out.append(np.linalg.solve(prec, rhs))
    return np.stack(out)


def flat_draws(x: np.ndarray) -> np.ndarray:
    """(S, R, K) per-draw factors -> (R, S*K) rows, draw-major along K."""
    s, r, k = x.shape
    return np.ascontiguousarray(x.transpose(1, 0, 2).reshape(r, s * k))


def scores(u_draws: np.ndarray, v_flat: np.ndarray, global_mean: float,
           control: bool = False) -> np.ndarray:
    """Posterior-mean scores over the catalogue, mean_s u_s . v_s + mean,
    for users u_draws (B, S, K) against v_flat (N, S*K) -> (B, N)."""
    b, s, k = u_draws.shape
    rows = u_draws.reshape(b, s * k)
    if control:
        per = (low(rows) @ low(v_flat).T).astype(np.float64)
    else:
        per = rows @ v_flat.T
    return per / s + global_mean
