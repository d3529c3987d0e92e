"""The open-loop schedule: a fixed amount of work from every seed, in
another order."""
import numpy as np

import harness
from conftest import tiny_cell


def schedule(seed, rate=200.0, seconds=5.0):
    drv = harness.driver("serve_open_loop")
    cell = tiny_cell("ml20m-serve-mixed")
    g = cell.config["generator"]
    rng = np.random.default_rng(seed)
    v_true = rng.normal(0, 0.25, (cell.config["n_items"], g["k_true"]))
    item_p = np.full(cell.config["n_items"], 1.0 / cell.config["n_items"])
    return drv.make_schedule(cell.config, cell.traffic, seconds, rate, rng,
                             item_p, v_true), cell


def test_same_work_every_seed():
    a, cell = schedule(1)
    b, _ = schedule(2)
    assert a.n == b.n == 1000
    cold_a = sorted(len(x) for x in a.cold_items if x is not None)
    cold_b = sorted(len(x) for x in b.cold_items if x is not None)
    assert len(cold_a) == round(cell.traffic["cold_share"] * a.n)
    assert cold_a == cold_b                      # one multiset of sizes
    assert not np.array_equal(a.user, b.user)   # in another order
    assert np.all(np.diff(a.due) >= 0) and 0 <= a.due[0] and a.due[-1] < 5.0


def test_cold_sizes_heavy_tailed():
    drv = harness.driver("serve_open_loop")
    spec = {"median": 10, "sigma": 1.2, "min": 1, "max": 256}
    s = drv.cold_sizes(10000, spec)
    assert s.min() == 1 and s.max() == 256
    assert abs(np.median(s) - 10) <= 1
    assert np.mean(s > 100) > 0.01


def test_users_follow_zipf():
    a, cell = schedule(3, rate=2000.0)
    warm = a.user[a.user >= 0]
    _, counts = np.unique(warm, return_counts=True)
    counts = np.sort(counts)[::-1]
    # Zipf(1): the most requested user far above the median one
    assert counts[0] > 10 * np.median(counts)
    assert warm.max() < cell.config["n_users"]


def test_sweep_rule_finds_the_knee():
    """The rate sweep's rule on rows like a sweep on the chip: a last fifth
    24 % slower than the first is a growing backlog; 9 % is not."""
    import sweep_rate

    base = 24.4
    held = {"failed": 0, "p50_ms": 29.5, "first_fifth_p50_ms": 28.3,
            "last_fifth_p50_ms": 30.9}
    growing = {"failed": 0, "p50_ms": 40.7, "first_fifth_p50_ms": 33.0,
               "last_fifth_p50_ms": 41.0}
    assert sweep_rate.sustained(held, base)
    assert not sweep_rate.sustained(growing, base)
    assert not sweep_rate.sustained(dict(held, failed=3), base)
    assert not sweep_rate.sustained(dict(held, p50_ms=40.0), base)
