"""Seeded rating matrices with the shapes and skew of a configuration.

A vectorised copy of the generator the program keeps in
`src/repro/data/datasets.py` (`synthetic_lowrank`): a ground-truth low-rank
model plus Gaussian noise, sampled with power-law popularity over items and
a milder power law over users, duplicates (user, item) pairs dropped. The
program's copy deduplicates through a Python `set`, which is slow at 20M
ratings; this one draws by inverse CDF and deduplicates with `np.unique`.
The draws differ from the program's for one seed, the distributions do not.

Which pairs are rated, and which of them are held out for the test split,
depend on the configuration alone (`generator.pattern_seed`); the values
depend on the run's seed. Every seed thus gives the sampler the same plan
shapes and the same held-out pairs, so its compiled sweep is the same
program for every seed and a run finds it in the compile cache. Values are
multiples of `QUANTUM`, and the training values sum to exactly zero: their
float32 mean, which the program bakes into its sweep as the global mean,
is 0.0 on every seed.

Everything here is host NumPy and depends only on the configuration and the
seed, so the benchmark owns it: a later change to the program cannot move it.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# Oversampling factor and round cap of the program's generator.
OVERSAMPLE = 1.4
MAX_ROUNDS = 8
# Rating values are whole multiples of this step (2**-7, far below the
# noise), so sums of up to 2**24 steps are exact in float32.
QUANTUM = 2.0 ** -7


@dataclass(frozen=True)
class Ratings:
    """COO ratings: user, item, value per rating, and the matrix shape."""

    rows: np.ndarray   # (nnz,) int32
    cols: np.ndarray   # (nnz,) int32
    vals: np.ndarray   # (nnz,) float32
    shape: tuple[int, int]

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    def take(self, idx: np.ndarray) -> "Ratings":
        return Ratings(self.rows[idx], self.cols[idx], self.vals[idx], self.shape)


@dataclass(frozen=True)
class Dataset:
    train: Ratings
    test: Ratings
    u_true: np.ndarray   # (n_users, k_true) float32
    v_true: np.ndarray   # (n_items, k_true) float32
    item_p: np.ndarray   # (n_items,) float64 item popularity
    user_p: np.ndarray   # (n_users,) float64 user activity


def power_law(n: int, exponent: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** (-exponent)
    return p / p.sum()


def _draw(rng: np.random.Generator, cdf: np.ndarray, m: int) -> np.ndarray:
    idx = np.searchsorted(cdf, rng.random(m), side="right")
    return np.minimum(idx, len(cdf) - 1).astype(np.int64)


def sample_pairs(rng: np.random.Generator, user_p: np.ndarray,
                 item_p: np.ndarray, target: int) -> tuple[np.ndarray, np.ndarray]:
    """`target` distinct (user, item) pairs drawn by popularity, sorted.

    Each round draws enough pairs to cover what is missing at the share of
    fresh pairs the previous round saw; the surplus is dropped at random.
    """
    n_items = len(item_p)
    ucdf, icdf = np.cumsum(user_p), np.cumsum(item_p)
    keys = np.zeros(0, np.int64)
    fresh_share = 1.0 / OVERSAMPLE
    for _ in range(MAX_ROUNDS):
        if len(keys) >= target:
            break
        m = int((target - len(keys)) / max(fresh_share, 0.05) * 1.05) + 16
        before = len(keys)
        drawn = _draw(rng, ucdf, m) * n_items + _draw(rng, icdf, m)
        keys = np.unique(np.concatenate([keys, drawn]))
        fresh_share = (len(keys) - before) / m
    if len(keys) > target:
        keys = keys[np.sort(rng.choice(len(keys), target, replace=False))]
    return (keys // n_items).astype(np.int32), (keys % n_items).astype(np.int32)


def generate(cfg: dict, seed: int) -> Dataset:
    """The configuration's ratings, split into train and test: the pattern
    from the configuration, the values from `seed`.

    `cfg` holds `n_users`, `n_items`, `nnz`, `test_frac` and a `generator`
    group: `k_true`, `noise`, `item_exponent`, `user_exponent`, `clip` and
    `pattern_seed` (0 when absent).
    """
    g = cfg["generator"]
    n_users, n_items = int(cfg["n_users"]), int(cfg["n_items"])
    item_p = power_law(n_items, float(g["item_exponent"]))
    user_p = power_law(n_users, float(g["user_exponent"]))
    target = min(int(cfg["nnz"]), n_users * n_items // 2)
    pattern = np.random.default_rng(
        np.random.SeedSequence([int(g.get("pattern_seed", 0)), 0x9A77]))
    rows, cols = sample_pairs(pattern, user_p, item_p, target)
    perm = pattern.permutation(len(rows))
    n_test = int(len(rows) * float(cfg["test_frac"]))
    train_at, test_at = np.sort(perm[n_test:]), np.sort(perm[:n_test])

    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x5EED]))
    k_true = int(g["k_true"])
    scale = 1.0 / np.sqrt(k_true)
    u_true = rng.normal(0.0, scale, (n_users, k_true)).astype(np.float32)
    v_true = rng.normal(0.0, scale, (n_items, k_true)).astype(np.float32)
    vals = np.empty(len(rows), np.float32)
    step = 1 << 21
    for lo in range(0, len(rows), step):
        r, c = rows[lo:lo + step], cols[lo:lo + step]
        vals[lo:lo + step] = np.einsum("nk,nk->n", u_true[r], v_true[c])
    vals += rng.normal(0.0, float(g["noise"]), len(vals)).astype(np.float32)
    if g.get("clip") is not None:
        np.clip(vals, *g["clip"], out=vals)
    vals = zero_sum_steps(vals, train_at, rng)
    ratings = Ratings(rows, cols, vals, (n_users, n_items))
    train = ratings.take(train_at)
    if float(train.vals.mean()) != 0.0:
        raise RuntimeError("training values do not average to exactly 0.0 in "
                           "float32; the sweep would compile anew for this seed")
    return Dataset(train=train, test=ratings.take(test_at),
                   u_true=u_true, v_true=v_true, item_p=item_p, user_p=user_p)


def zero_sum_steps(vals: np.ndarray, train_at: np.ndarray,
                   rng: np.random.Generator) -> np.ndarray:
    """`vals` rounded to whole `QUANTUM` steps, with the training values
    shifted by whole steps so that they sum to exactly zero: each by the
    same number of steps, and a random few by one step more."""
    steps = np.rint(vals.astype(np.float64) / QUANTUM).astype(np.int64)
    n = len(train_at)
    if n:
        base, extra = divmod(-int(steps[train_at].sum()), n)
        steps[train_at] += base
        steps[train_at[rng.choice(n, extra, replace=False)]] += 1
    return (steps * QUANTUM).astype(np.float32)


def degrees(r: Ratings, axis: int) -> np.ndarray:
    idx = r.rows if axis == 0 else r.cols
    return np.bincount(idx, minlength=r.shape[axis]).astype(np.int64)


def csr(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, n_rows: int):
    """(indptr, cols, vals) grouped by row, stable within a row."""
    order = np.argsort(rows, kind="stable")
    indptr = np.zeros(n_rows + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n_rows), out=indptr[1:])
    return indptr, cols[order], vals[order]
