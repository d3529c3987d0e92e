"""Work that the algorithm needs, counted from the data and the shapes.

Nothing here reads the program's plans: padded lanes, chunks and buckets
change with the program, the work a sweep or a request needs does not.
"""
from __future__ import annotations


def sweep_stats_flops(n_train: int, k: int) -> float:
    """Both half-sweeps' statistics: each rating adds v v^T (2K^2 FLOPs)
    to its user's precision and u u^T to its item's."""
    return 2.0 * n_train * 2.0 * k * k


def sweep_solve_flops(n_rated: int, k: int) -> float:
    """Per rated entity: a K x K Cholesky (K^3/3) and two triangular
    solves (K^2 each)."""
    return n_rated * (k ** 3 / 3.0 + 2.0 * k * k)


def sweep_flops(n_train: int, n_rated_users: int, n_rated_items: int,
                k: int) -> float:
    return (sweep_stats_flops(n_train, k)
            + sweep_solve_flops(n_rated_users + n_rated_items, k))


def sweep_updates(n_train: int) -> int:
    """Rating updates per sweep: every rating is read once per half-sweep."""
    return 2 * n_train


def topn_flops(batch: int, width: int, n_items: int) -> float:
    """Scores of a batch against the catalogue: (B, S*K) x (S*K, N)."""
    return 2.0 * batch * width * n_items


def topn_bytes(batch: int, width: int, n_items: int, itemsize: int = 4) -> float:
    """V' (N, S*K) read once per call, plus the batch's scoring rows."""
    return float(itemsize) * width * (n_items + batch)


def foldin_flops(n_ratings: int, n_users: int, draws: int, k: int) -> float:
    """Cold-start fold-in over S draws: statistics per rating, then one
    Cholesky and two solves per user and draw."""
    return draws * (n_ratings * 2.0 * k * k
                    + n_users * (k ** 3 / 3.0 + 2.0 * k * k))
