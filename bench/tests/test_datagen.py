"""The harness's generator gives each configuration's shape, ratings and
skew from a seed."""
import json

import numpy as np
import pytest

import datagen
from conftest import BENCH


def cfg(name, **over):
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return dict(c, **over)


@pytest.mark.parametrize("name,scale", [("chembl-k64", 0.02), ("ml20m-k64", 0.01)])
def test_shape_ratings_split(name, scale):
    full = cfg(name)
    c = cfg(name, n_users=int(full["n_users"] * scale),
            n_items=int(full["n_items"] * scale), nnz=int(full["nnz"] * scale * scale * 4))
    d = datagen.generate(c, 2**31 + 5)
    total = d.train.nnz + d.test.nnz
    assert total == min(c["nnz"], c["n_users"] * c["n_items"] // 2)
    assert d.test.nnz == int(total * c["test_frac"])
    for r in (d.train, d.test):
        assert r.shape == (c["n_users"], c["n_items"])
        assert r.rows.max() < c["n_users"] and r.cols.max() < c["n_items"]
    keys = np.concatenate([d.train.rows.astype(np.int64) * c["n_items"] + d.train.cols,
                           d.test.rows.astype(np.int64) * c["n_items"] + d.test.cols])
    assert len(np.unique(keys)) == total          # no pair twice
    if c["generator"]["clip"] is not None:
        lo, hi = c["generator"]["clip"]
        assert d.train.vals.min() >= lo and d.train.vals.max() <= hi


def test_same_seed_same_data_other_seed_other_data():
    """One seed gives one dataset; another seed gives other values on the
    configuration's own pattern of rated and held-out pairs."""
    c = cfg("chembl-k64", n_users=3000, n_items=200, nnz=20000)
    a, b = datagen.generate(c, 7), datagen.generate(c, 7)
    other = datagen.generate(c, 2**31 + 8)
    assert np.array_equal(a.train.rows, b.train.rows)
    assert np.array_equal(a.train.vals, b.train.vals)
    for x, y in ((a.train, other.train), (a.test, other.test)):
        assert np.array_equal(x.rows, y.rows) and np.array_equal(x.cols, y.cols)
        assert not np.array_equal(x.vals, y.vals)
    moved = dict(c, generator=dict(c["generator"], pattern_seed=1))
    assert not np.array_equal(datagen.generate(moved, 7).train.cols, a.train.cols)


@pytest.mark.parametrize("name", ["chembl-k64", "ml20m-k64"])
def test_training_mean_exactly_zero(name):
    """Values are whole steps of QUANTUM and the training values average to
    exactly 0.0 in float32, the global mean the program bakes in."""
    c = cfg(name, n_users=5000, n_items=400, nnz=40000)
    for seed in (1, 2**31 + 3, 2**33 + 5):
        d = datagen.generate(c, seed)
        assert float(d.train.vals.mean()) == 0.0
        steps = d.train.vals.astype(np.float64) / datagen.QUANTUM
        assert np.array_equal(steps, np.rint(steps))


def test_one_program_for_every_seed():
    """The sampler's jitted sweep lowers to the same program for two seeds,
    so a seed new to the compile cache still finds its sweep there."""
    import hashlib

    import harness

    drv = harness.driver("train_sweeps")
    c = cfg("chembl-k64", n_users=4000, n_items=150, nnz=15000, k=8)
    texts = []
    for seed in (2**31 + 101, 2**31 + 102):
        s = drv._program_sampler(c, datagen.generate(c, seed))
        low = s._sweep.lower(s.init(1), *s._plan_args).as_text()
        texts.append(hashlib.sha1(low.encode()).hexdigest())
    assert texts[0] == texts[1]


def test_power_law_skew():
    """Item degrees follow the configuration's popularity exponent: the
    most popular item is rated far more often than the median one, and
    degree falls with popularity rank."""
    c = cfg("chembl-k64", n_users=20000, n_items=500, nnz=60000)
    d = datagen.generate(c, 3)
    deg = datagen.degrees(d.train, 1)
    assert deg[0] > 50 * np.median(deg)
    head, tail = deg[:10].mean(), deg[-100:].mean()
    ratio = (np.arange(1, 11) ** -1.2).mean() / (np.arange(401, 501) ** -1.2).mean()
    assert 0.3 * ratio < head / max(tail, 1e-9) < 3 * ratio


def test_csr_groups_rows():
    rows = np.array([2, 0, 2, 1], np.int32)
    cols = np.array([5, 6, 7, 8], np.int32)
    ptr, c, v = datagen.csr(rows, cols, cols.astype(np.float64), 3)
    assert ptr.tolist() == [0, 1, 2, 4]
    assert c.tolist() == [6, 8, 5, 7]
