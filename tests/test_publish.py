"""Async sample publication: channel ordering, atomic frontend swaps, and
compiled-executable reuse across same-shape publishes."""
import threading
import time

import numpy as np
import pytest

from repro.checkpoint import SampleStore
from repro.core import GibbsSampler
from repro.data import synthetic_lowrank, train_test_split
from repro.kernels import bpmf_topn
from repro.serve import (
    PosteriorEnsemble,
    PublicationChannel,
    RecommendFrontend,
    TopNRecommender,
)

M, N, K = 24, 16, 4


def make_sample(step: int, *, u=None, v=None) -> dict:
    """A schema-complete draw; u/v default to deterministic per-step values."""
    rng = np.random.default_rng(step)
    return {
        "u": (rng.normal(size=(M, K)).astype(np.float32) if u is None else u),
        "v": (rng.normal(size=(N, K)).astype(np.float32) if v is None else v),
        "hyper_u_mu": np.zeros(K, np.float32),
        "hyper_u_lam": np.eye(K, dtype=np.float32),
        "hyper_v_mu": np.zeros(K, np.float32),
        "hyper_v_lam": np.eye(K, dtype=np.float32),
        "global_mean": np.float32(0.0),
        "alpha": np.float32(2.0),
    }


def epoch_coded_sample(step: int) -> dict:
    """A draw whose top-1 score *is* its step: u rows are all-ones/K, v is
    zero except item (step % N) which scores exactly `step`. Any mix of u
    and v from different epochs (a torn swap) would score a wrong value."""
    u = np.full((M, K), 1.0 / K, np.float32)
    v = np.zeros((N, K), np.float32)
    v[step % N] = float(step)
    return make_sample(step, u=u, v=v)


# ---------------------------------------------------------------------------
# channel semantics
# ---------------------------------------------------------------------------
def test_channel_windows_and_orders_draws():
    ch = PublicationChannel(window=3)
    assert ch.snapshot() is None and ch.epoch is None and ch.seq == 0
    for step in (10, 12, 11, 14):
        assert ch.publish(step, make_sample(step))
    snap = ch.snapshot()
    assert snap.epoch == 14 and snap.seq == 4
    assert [d.step for d in snap.draws] == [11, 12, 14]  # windowed, sorted


def test_channel_epoch_monotone_under_out_of_order_publishes():
    ch = PublicationChannel(window=4)
    ch.publish(9, make_sample(9))
    assert ch.epoch == 9
    # a straggler draw lands in the window but cannot move the epoch back
    assert ch.publish(7, make_sample(7)) is True
    assert ch.epoch == 9
    assert [d.step for d in ch.snapshot().draws] == [7, 9]
    # duplicates and draws older than a full window are dropped
    assert ch.publish(9, make_sample(9)) is False
    ch.publish(10, make_sample(10))
    ch.publish(11, make_sample(11))
    assert ch.publish(3, make_sample(3)) is False
    assert ch.epoch == 11 and ch.seq == 4


def test_channel_wait_and_close():
    ch = PublicationChannel(window=2)
    assert ch.wait(timeout=0.01) is None
    got = []
    t = threading.Thread(target=lambda: got.append(ch.wait(timeout=5.0)))
    t.start()
    ch.publish(1, make_sample(1))
    t.join(timeout=5.0)
    assert got and got[0].epoch == 1
    assert ch.wait(newer_than=1, timeout=0.01) is None  # nothing newer yet
    ch.close()
    assert ch.wait(newer_than=1, timeout=5.0) is None   # closed: no block
    with pytest.raises(RuntimeError):
        ch.publish(2, make_sample(2))


def test_channel_push_callback_fires_per_publish():
    ch = PublicationChannel(window=2)
    seen = []
    unsubscribe = ch.subscribe(lambda snap: seen.append(snap.epoch))
    ch.publish(1, make_sample(1))
    ch.publish(2, make_sample(2))
    unsubscribe()
    ch.publish(3, make_sample(3))
    assert seen == [1, 2]


def test_channel_rejects_incomplete_sample():
    ch = PublicationChannel()
    bad = make_sample(1)
    del bad["alpha"]
    with pytest.raises(ValueError, match="alpha"):
        ch.publish(1, bad)


# ---------------------------------------------------------------------------
# trainer integration: publish alongside the durable store
# ---------------------------------------------------------------------------
def test_gibbs_run_publishes_alongside_store(tmp_path):
    ratings, _, _ = synthetic_lowrank(40, 24, k_true=3, nnz=600, noise=0.3, seed=0)
    train, test = train_test_split(ratings, 0.1, seed=1)
    store = SampleStore(tmp_path / "samples", keep=8)
    ch = PublicationChannel(window=8)
    sampler = GibbsSampler(train, test, k=4, alpha=2.0, burn_in=3, widths=(8, 32))
    sampler.run(8, seed=0, store=store, publish=ch)

    assert ch.epoch == store.epoch()
    snap = ch.snapshot()
    assert [d.step for d in snap.draws] == store.steps()
    durable = store.load(store.epoch())
    published = snap.draws[-1]
    np.testing.assert_array_equal(np.asarray(published.u), durable.u)
    np.testing.assert_array_equal(np.asarray(published.v), durable.v)
    assert published.alpha == pytest.approx(durable.alpha)


def test_sgld_run_publishes_alongside_store(tmp_path):
    """SGLD parity with the Gibbs publish test: the minibatch trainer emits
    draws through the identical store/channel hand-off, at its much higher
    step rate (thin keeps the traffic bounded), and the channel's epoch
    tracks the store's."""
    from repro.core import SGLDSampler

    ratings, _, _ = synthetic_lowrank(40, 24, k_true=3, nnz=600, noise=0.3, seed=0)
    train, test = train_test_split(ratings, 0.1, seed=1)
    store = SampleStore(tmp_path / "samples", keep=8)
    ch = PublicationChannel(window=8)
    sampler = SGLDSampler(train, test, k=4, alpha=2.0, burn_in=20,
                          minibatch=256, step_size=0.3, widths=(8, 32))
    sampler.run(60, seed=0, store=store, publish=ch, thin=10)

    assert ch.epoch == store.epoch()
    snap = ch.snapshot()
    assert [d.step for d in snap.draws] == store.steps()
    durable = store.load(store.epoch())
    published = snap.draws[-1]
    np.testing.assert_array_equal(np.asarray(published.u), durable.u)
    np.testing.assert_array_equal(np.asarray(published.v), durable.v)


def test_store_retention_under_high_rate_publishes(tmp_path):
    """SGLD-rate retention: hundreds of retains against a small keep window
    must leave exactly the last `keep` epochs on disk, in order, with the
    newest loadable — the async writer can't tear or leak under burst."""
    store = SampleStore(tmp_path / "samples", keep=4)
    for step in range(1, 201):
        store.retain(step, epoch_coded_sample(step))
    store.wait()
    assert store.epoch() == 200
    assert store.steps() == list(range(197, 201))
    got = store.load(200)
    assert float(got.v[200 % N].max()) == pytest.approx(200.0)


def test_frontend_stays_consistent_under_publish_burst():
    """A tight synchronous burst of publishes (the SGLD cadence, no sleeps)
    with refresh interleaved: served epochs stay monotone and every result
    is internally consistent (no torn u/v mix), even though most publishes
    are superseded before the frontend ever sees them."""
    ch = PublicationChannel(window=1)  # S pinned at 1: exact-score checks
    ch.publish(1, epoch_coded_sample(1))
    fe = RecommendFrontend(channel=ch, subscribe=False, max_batch=4)
    served = []
    step = 2
    for burst in range(30):
        for _ in range(7):  # frontend refreshes once per 7 publishes
            ch.publish(step, epoch_coded_sample(step))
            step += 1
        fe.refresh()
        fe.submit(0, topk=1)
        (res,) = fe.flush()
        served.append(res.epoch)
        assert res.items[0] == res.epoch % N, res
        assert res.scores[0] == pytest.approx(float(res.epoch)), res
    assert served == sorted(served)
    assert served[-1] == ch.epoch == step - 1  # every refresh caught up


# ---------------------------------------------------------------------------
# frontend adoption: epochs, monotonicity, no disk required
# ---------------------------------------------------------------------------
def test_frontend_serves_from_channel_without_disk():
    ch = PublicationChannel(window=2)
    ch.publish(5, epoch_coded_sample(5))
    fe = RecommendFrontend(channel=ch, subscribe=False, max_batch=4)
    assert fe.store is None and fe.epoch == 5
    fe.submit(0, topk=1)
    (res,) = fe.flush()
    assert res.epoch == 5
    assert res.items[0] == 5 % N and res.scores[0] == pytest.approx(5.0)


def test_frontend_requires_some_sample_source():
    with pytest.raises(ValueError, match="sample_root"):
        RecommendFrontend()
    ch = PublicationChannel()
    with pytest.raises(TimeoutError):
        RecommendFrontend(channel=ch, subscribe=False, wait_first_publish_s=0.05)
    # a closed-before-first-publish channel means the trainer died/finished
    # early — reported distinctly, not as a phantom timeout
    ch.close()
    with pytest.raises(RuntimeError, match="closed before the first publish"):
        RecommendFrontend(channel=ch, subscribe=False, wait_first_publish_s=5.0)


def test_frontend_epoch_monotone_and_stale_publish_ignored():
    ch = PublicationChannel(window=4)
    ch.publish(10, epoch_coded_sample(10))
    fe = RecommendFrontend(channel=ch, subscribe=False, max_batch=4,
                           max_samples=1)
    assert fe.epoch == 10
    # a straggler publish must not move the served epoch backwards
    ch.publish(8, epoch_coded_sample(8))
    assert fe.refresh() is False and fe.epoch == 10
    ch.publish(12, epoch_coded_sample(12))
    assert fe.refresh() is True and fe.epoch == 12
    fe.submit(1, topk=1)
    (res,) = fe.flush()
    assert res.epoch == 12 and res.items[0] == 12 % N


def test_frontend_prefers_channel_over_store(tmp_path):
    root = tmp_path / "samples"
    store = SampleStore(root, keep=4)
    store.retain(1, epoch_coded_sample(1))
    store.wait()
    ch = PublicationChannel(window=1)
    fe = RecommendFrontend(root, channel=ch, subscribe=False, max_batch=4)
    assert fe.epoch == 1  # cold start from disk
    ch.publish(6, epoch_coded_sample(6))
    assert fe.refresh() is True and fe.epoch == 6  # push wins over the poll
    fe.submit(2, topk=1)
    (res,) = fe.flush()
    assert res.items[0] == 6 % N and res.scores[0] == pytest.approx(6.0)


def _draws(steps):
    from repro.checkpoint import as_retained_sample

    return tuple(as_retained_sample(s, epoch_coded_sample(s)) for s in steps)


# ---------------------------------------------------------------------------
# executable reuse: same-shape publish must not retrace the top-N kernel
# ---------------------------------------------------------------------------
def test_same_shape_publish_zero_topn_recompiles():
    ch = PublicationChannel(window=2)
    ch.publish(1, epoch_coded_sample(1))
    ch.publish(2, epoch_coded_sample(2))  # window full: S pinned at 2
    fe = RecommendFrontend(channel=ch, subscribe=False, max_batch=4)
    fe.submit(0, topk=3)
    fe.flush()  # compile at the serving shape

    traces_before = bpmf_topn.trace_count()
    for step in (3, 4, 5):
        ch.publish(step, epoch_coded_sample(step))
        assert fe.refresh() is True
        fe.submit(0, topk=3)
        (res,) = fe.flush()
        assert res.epoch == step and res.items[0] == step % N
    assert bpmf_topn.trace_count() == traces_before  # swaps, no retraces
    assert fe.swaps >= 4 and fe.rebinds >= 3


def test_rebind_rejects_shape_change_and_rebuild_still_works():
    rec = TopNRecommender(PosteriorEnsemble(_draws((1, 2))))
    e3 = PosteriorEnsemble(_draws((1, 2, 3)))  # S changed: 2 -> 3
    with pytest.raises(ValueError, match="shape changed"):
        rec.rebind(e3)
    # the frontend path falls back to a full rebuild on shape change
    ch = PublicationChannel(window=3)
    for s in (1, 2):
        ch.publish(s, epoch_coded_sample(s))
    fe = RecommendFrontend(channel=ch, subscribe=False, max_batch=4)
    ch.publish(3, epoch_coded_sample(3))  # window grows: S 2 -> 3
    assert fe.refresh() is True
    assert fe.swaps == 2 and fe.rebinds == 0
    fe.submit(0, topk=1)
    (res,) = fe.flush()
    assert res.epoch == 3


def test_ensemble_from_arrays_matches_draw_construction():
    """from_arrays (stacked device arrays, the embedding API) must build the
    same servable ensemble as stacking RetainedSamples."""
    import jax.numpy as jnp

    draws = _draws((3, 5))
    want = PosteriorEnsemble(draws)
    got = PosteriorEnsemble.from_arrays(
        jnp.stack([jnp.asarray(d.u) for d in draws]),
        jnp.stack([jnp.asarray(d.v) for d in draws]),
        hyper_u_mu=jnp.stack([jnp.asarray(d.hyper_u_mu) for d in draws]),
        hyper_u_lam=jnp.stack([jnp.asarray(d.hyper_u_lam) for d in draws]),
        hyper_v_mu=jnp.stack([jnp.asarray(d.hyper_v_mu) for d in draws]),
        hyper_v_lam=jnp.stack([jnp.asarray(d.hyper_v_lam) for d in draws]),
        global_mean=want.global_mean, alpha=want.alpha, steps=(3, 5),
    )
    assert got.epoch == want.epoch == 5
    assert got.shape_key() == want.shape_key()
    users = np.asarray([0, 1], np.int32)
    items = np.asarray([3 % N, 5 % N], np.int32)
    np.testing.assert_allclose(
        np.asarray(got.score(users, items)[0]),
        np.asarray(want.score(users, items)[0]),
    )
    assert [s.step for s in got.samples] == [3, 5]  # fold_in metadata intact

    with pytest.raises(ValueError, match="ascending"):
        PosteriorEnsemble.from_arrays(
            got.u, got.v,
            hyper_u_mu=jnp.zeros((2, K)), hyper_u_lam=jnp.stack([jnp.eye(K)] * 2),
            hyper_v_mu=jnp.zeros((2, K)), hyper_v_lam=jnp.stack([jnp.eye(K)] * 2),
            global_mean=0.0, alpha=2.0, steps=(5, 3),
        )


def test_frontend_channel_with_empty_store_waits_for_first_publish(tmp_path):
    """Co-train first boot: the durable sample dir exists but is still empty
    (trainer in burn-in); a channel-attached frontend must block for the
    first publish, not crash on the empty directory."""
    ch = PublicationChannel(window=2)
    t = threading.Thread(
        target=lambda: (time.sleep(0.05), ch.publish(4, epoch_coded_sample(4)))
    )
    t.start()
    fe = RecommendFrontend(tmp_path / "empty", channel=ch, subscribe=False,
                           wait_first_publish_s=10.0)
    t.join()
    assert fe.epoch == 4
    # store-only with an empty dir still fails fast, as before
    with pytest.raises(FileNotFoundError):
        RecommendFrontend(tmp_path / "empty2")


def test_rebind_scores_new_factors_through_old_layout():
    one = TopNRecommender(PosteriorEnsemble(_draws((4,))))
    rebound = one.rebind(PosteriorEnsemble(_draws((7,))))
    vals, idx = rebound.recommend(np.asarray([0], np.int32), 1)
    assert idx[0][0] == 7 % N and vals[0][0] == pytest.approx(7.0)
    # the original recommender still serves its own epoch untouched
    vals, idx = one.recommend(np.asarray([0], np.int32), 1)
    assert idx[0][0] == 4 % N and vals[0][0] == pytest.approx(4.0)


def test_subscriber_hammer_publishes_while_draining():
    """Two publisher threads hammer the channel while the frontend's
    subscriber thread drains it — the locked `_epoch` read in the loop and
    the locked write in `_swap` must agree: served epochs stay monotone and
    the final publish is always adopted (no lost-wakeup on a stale read)."""
    ch = PublicationChannel(window=1)
    ch.publish(1, epoch_coded_sample(1))
    fe = RecommendFrontend(channel=ch, subscribe=True, max_batch=4)
    barrier = threading.Barrier(2)

    def publisher(steps):
        barrier.wait()
        for step in steps:
            ch.publish(step, epoch_coded_sample(step))
            time.sleep(0.0005)

    threads = [
        threading.Thread(target=publisher, args=(range(2, 120, 2),)),
        threading.Thread(target=publisher, args=(range(3, 120, 2),)),
    ]
    for t in threads:
        t.start()
    epochs = []
    try:
        while any(t.is_alive() for t in threads):
            fe.submit(0, topk=1)
            for res in fe.flush():
                epochs.append(res.epoch)
                assert res.items[0] == res.epoch % N, res
    finally:
        for t in threads:
            t.join(timeout=20.0)
        ch.close()
    # the drain path must catch the last published epoch: condition-wait on
    # the swap (woken by every adoption), no sleep/poll race
    assert fe.wait_epoch(ch.epoch, timeout=20.0)
    fe.close()
    assert fe.epoch == ch.epoch == 119
    assert epochs == sorted(epochs)
    assert fe.swaps >= 2


# ---------------------------------------------------------------------------
# the seen-item index across shape-changing swaps
# ---------------------------------------------------------------------------
def _sized_sample(step: int, m: int, n: int) -> dict:
    rng = np.random.default_rng(step)
    k = K
    return {
        "u": rng.normal(size=(m, k)).astype(np.float32),
        "v": rng.normal(size=(n, k)).astype(np.float32),
        "hyper_u_mu": np.zeros(k, np.float32),
        "hyper_u_lam": np.eye(k, dtype=np.float32),
        "hyper_v_mu": np.zeros(k, np.float32),
        "hyper_v_lam": np.eye(k, dtype=np.float32),
        "global_mean": np.float32(0.0),
        "alpha": np.float32(2.0),
    }


def _boot_ratings():
    from repro.data.sparse import SparseRatings

    rows = np.repeat(np.arange(M, dtype=np.int32), 3)
    rng = np.random.default_rng(7)
    cols = rng.integers(0, N, rows.size).astype(np.int32)
    return SparseRatings(rows=rows, cols=cols,
                         vals=np.ones(rows.size, np.float32), shape=(M, N))


def test_seen_index_follows_grown_axes_on_swap():
    """The exclusion index is built against boot-time ratings; a swap that
    grows the user/item axes must rebuild it padded to the new shape (new
    users get empty exclusion rows) instead of silently under-excluding
    (or crashing the seen lookup for users past the boot axis)."""
    train = _boot_ratings()
    ch = PublicationChannel(window=1)
    ch.publish(1, _sized_sample(1, M, N))
    fe = RecommendFrontend(channel=ch, subscribe=False, seen=train,
                           max_batch=4)
    assert fe.seen.shape == (M, N)

    ch.publish(2, _sized_sample(2, M + 6, N + 3))  # trainer grew both axes
    assert fe.refresh() is True
    assert fe.seen.shape == (M + 6, N + 3)
    # an existing user still gets their boot-time exclusions
    fe.submit(0, topk=5)
    # a user beyond the boot axis is servable with an empty exclusion row
    fe.submit(M + 2, topk=5)
    results = fe.flush()
    assert len(results) == 2
    seen0 = set(train.cols[train.rows == 0].tolist())
    assert not seen0.intersection(results[0].items.tolist())


def test_seen_index_rejects_shrunk_ensemble():
    """An ensemble smaller than the ratings matrix cannot be served with
    exclusions intact — adopting it must fail loudly, not under-exclude."""
    train = _boot_ratings()
    ch = PublicationChannel(window=1)
    ch.publish(1, _sized_sample(1, M, N))
    fe = RecommendFrontend(channel=ch, subscribe=False, seen=train,
                           max_batch=4)
    ch.publish(2, _sized_sample(2, M - 4, N))
    with pytest.raises(ValueError, match="under-exclude"):
        fe.refresh()


def test_subscriber_survives_rejected_publish():
    """A rejected adoption (shrunk ensemble vs the seen index) must not
    kill the subscriber thread: the bad epoch is recorded and skipped, and
    the next acceptable publish is still adopted."""
    train = _boot_ratings()
    ch = PublicationChannel(window=1)
    ch.publish(1, _sized_sample(1, M, N))
    fe = RecommendFrontend(channel=ch, subscribe=True, seen=train,
                           max_batch=4)
    try:
        ch.publish(2, _sized_sample(2, M - 4, N))   # rejected: shrunk axes
        # the subscriber notifies the swap condition on a rejection too —
        # wait on it rather than polling the deque
        with fe._lock:
            assert fe._swap_cond.wait_for(lambda: len(fe.adopt_errors) > 0,
                                          timeout=20.0)
        assert fe.epoch == 1
        ch.publish(3, _sized_sample(3, M, N))        # good again
        assert fe.wait_epoch(3, timeout=20.0)  # the loop lived on
    finally:
        ch.close()
        fe.close()


# ---------------------------------------------------------------------------
# no torn ensemble: concurrent recommend() during a stream of publishes
# ---------------------------------------------------------------------------
def test_no_torn_ensemble_during_concurrent_publishes():
    """Each epoch-coded draw scores exactly its own step for every user; a
    torn swap (u from one epoch, v from another, or epoch label mismatching
    the factors) would surface as a score != the result's reported epoch."""
    ch = PublicationChannel(window=1)  # S pinned at 1: every swap rebinds
    ch.publish(1, epoch_coded_sample(1))
    fe = RecommendFrontend(channel=ch, subscribe=True, max_batch=4)

    stop = threading.Event()

    def publisher():
        step = 2
        while not stop.is_set() and step < 200:
            ch.publish(step, epoch_coded_sample(step))
            step += 1
            time.sleep(0.002)
        ch.close()

    served = []

    def serve_batch():
        for u in range(3):
            fe.submit(u, topk=1)
        for res in fe.flush():
            served.append(res)
            # consistency: reported epoch, item, and score all agree
            assert res.items[0] == res.epoch % N, res
            assert res.scores[0] == pytest.approx(float(res.epoch)), res

    pub = threading.Thread(target=publisher)
    try:
        # the first batch compiles the scoring step; serve it before the
        # publish stream starts so the stream is not spent compiling
        serve_batch()
        pub.start()
        t_end = time.monotonic() + 3.0
        while time.monotonic() < t_end and not ch.closed:
            serve_batch()
    finally:
        stop.set()
        if pub.ident is not None:
            pub.join(timeout=10.0)
        fe.close()

    epochs = [r.epoch for r in served]
    assert len(served) >= 10
    assert epochs == sorted(epochs)      # served freshness never regressed
    assert len(set(epochs)) >= 2         # and at least one live swap happened
