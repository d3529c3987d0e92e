"""Multi-host serving tier: merge contract, single-host parity, channel
fan-out, and the all-shards-staged epoch barrier (serve/cluster.py)."""
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st
from repro.checkpoint import as_retained_sample
from repro.kernels import bpmf_topn
from repro.serve import (
    ClusterCoordinator,
    PosteriorEnsemble,
    PublicationChannel,
    TopNRecommender,
)
from repro.serve.cluster import _merge_topk, shard_bounds

M, N, K = 40, 57, 4


def make_sample(step: int, *, n_items: int = N, u=None, v=None) -> dict:
    rng = np.random.default_rng(step)
    return {
        "u": (rng.normal(size=(M, K)).astype(np.float32) if u is None else u),
        "v": (rng.normal(size=(n_items, K)).astype(np.float32) if v is None else v),
        "hyper_u_mu": np.zeros(K, np.float32),
        "hyper_u_lam": np.eye(K, dtype=np.float32),
        "hyper_v_mu": np.zeros(K, np.float32),
        "hyper_v_lam": np.eye(K, dtype=np.float32),
        "global_mean": np.float32(0.0),
        "alpha": np.float32(2.0),
    }


def epoch_coded_sample(step: int) -> dict:
    """Top-1 score == step for every user; item = step % N. Any cross-shard
    tear (one shard's epoch mixed with another's) surfaces as a score that
    disagrees with the served epoch."""
    u = np.full((M, K), 1.0 / K, np.float32)
    v = np.zeros((N, K), np.float32)
    v[step % N] = float(step)
    return make_sample(step, u=u, v=v)


def _ensemble(steps, sample_fn=make_sample) -> PosteriorEnsemble:
    return PosteriorEnsemble(tuple(
        as_retained_sample(s, sample_fn(s)) for s in steps
    ))


# ---------------------------------------------------------------------------
# the merge contract: bit-equality with one unsharded lax.top_k
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_shards", [1, 2, 3, 4])
@pytest.mark.parametrize("n_items,topk", [
    (57, 8),     # shards wider than topk
    (21, 8),     # EVERY shard narrower than topk (ragged k_eff < topk)
    (130, 50),   # odd split with a ragged final shard
])
def test_merge_topk_matches_unsharded_reference(n_shards, n_items, topk):
    """Per-shard lax.top_k candidates, concatenated in ascending range
    order and merged, must reproduce one monolithic lax.top_k bit-for-bit —
    including tie resolution to the lowest global item index."""
    rng = np.random.default_rng(n_shards * 1000 + n_items)
    scores = rng.normal(size=(6, n_items)).astype(np.float32)
    # plant cross-shard ties: identical score values far apart on the item
    # axis, so stable ordering is observable
    scores[:, n_items - 1] = scores[:, 0]
    scores[:, n_items // 2] = scores[:, 1]
    scores = jnp.asarray(scores)
    topk = min(topk, n_items)

    want_v, want_i = jax.lax.top_k(scores, topk)

    bounds = shard_bounds(n_items, n_shards)
    vals, idx = [], []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        k_eff = min(topk, int(hi - lo))
        v, pos = jax.lax.top_k(scores[:, lo:hi], k_eff)
        vals.append(v)
        idx.append(pos + np.int32(lo))
    got_v, got_i = _merge_topk(
        jnp.concatenate(vals, 1), jnp.concatenate(idx, 1), topk
    )
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_shard_bounds_cover_and_balance():
    b = shard_bounds(10, 4)
    assert b[0] == 0 and b[-1] == 10
    widths = np.diff(b)
    assert widths.min() >= 2 and widths.max() <= 3


# ---------------------------------------------------------------------------
# property tests: the contracts hold for ARBITRARY shapes, not just the
# parametrized grid above (skipped individually when hypothesis is absent)
# ---------------------------------------------------------------------------
@settings(max_examples=60, deadline=None)
@given(st.data())
def test_merge_topk_bit_equal_under_arbitrary_splits(data):
    """Property form of the merge contract: for ANY item count, ANY topk,
    ANY split (uneven, ragged, empty shards included) and duplicate-heavy
    scores, merging per-shard top_k candidates reproduces one monolithic
    lax.top_k bit-for-bit — stability means every tie resolves to the
    lowest global item index, exactly as unsharded top_k would."""
    n_items = data.draw(st.integers(min_value=1, max_value=48), label="n_items")
    topk = data.draw(st.integers(min_value=1, max_value=n_items), label="topk")
    # a tiny value alphabet forces heavy cross-shard score collisions
    flat = data.draw(
        st.lists(st.integers(min_value=-3, max_value=3),
                 min_size=3 * n_items, max_size=3 * n_items),
        label="scores",
    )
    scores = jnp.asarray(np.asarray(flat, np.float32).reshape(3, n_items))
    cuts = data.draw(
        st.lists(st.integers(min_value=0, max_value=n_items), max_size=5),
        label="cuts",
    )
    bounds = [0, *sorted(cuts), n_items]

    want_v, want_i = jax.lax.top_k(scores, topk)
    vals, idx = [], []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        if hi == lo:
            continue  # empty shard: contributes no candidates
        k_eff = min(topk, hi - lo)
        v, pos = jax.lax.top_k(scores[:, lo:hi], k_eff)
        vals.append(v)
        idx.append(pos + np.int32(lo))
    got_v, got_i = _merge_topk(
        jnp.concatenate(vals, 1), jnp.concatenate(idx, 1), topk
    )
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


@settings(max_examples=100, deadline=None)
@given(st.integers(min_value=1, max_value=2000),
       st.integers(min_value=1, max_value=32))
def test_shard_bounds_properties(n_items, n_shards):
    """Coverage (first bound 0, last n_items, widths sum exactly — so the
    half-open ranges tile the catalogue disjointly), monotonicity, and
    balance (widths within one row) for arbitrary layouts, including more
    shards than items (empty shards allowed, never negative)."""
    b = shard_bounds(n_items, n_shards)
    assert len(b) == n_shards + 1
    assert b[0] == 0 and b[-1] == n_items
    widths = np.diff(b)
    assert (widths >= 0).all()
    assert widths.sum() == n_items          # covers exactly once
    assert widths.max() - widths.min() <= 1  # balanced to within one row


# ---------------------------------------------------------------------------
# parity: the tier IS the single-host recommender, shard count irrelevant
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def ensemble():
    return _ensemble((1, 2, 3))


def test_cluster_bit_identical_to_single_host(ensemble):
    users = np.arange(12, dtype=np.int32)
    single = TopNRecommender(ensemble)
    v1, i1 = single.recommend(users, 9)
    for h in (1, 2, 3, 4):
        cluster = ClusterCoordinator(ensemble, n_hosts=h)
        v2, i2 = cluster.recommend(users, 9)
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_array_equal(v1, v2)


def test_cluster_exclusions_and_foldin_rows_match_single_host(ensemble):
    users = np.arange(8, dtype=np.int32)
    exclude = [np.arange(r, r + 4, dtype=np.int32) for r in range(8)]
    single = TopNRecommender(ensemble)
    cluster = ClusterCoordinator(ensemble, n_hosts=3)

    rows = single.u_flat[users]
    a_v, a_i = single.recommend_rows(rows, 6, exclude=exclude, fetch_hint=16)
    b_v, b_i = cluster.recommend_rows(rows, 6, exclude=exclude, fetch_hint=16)
    np.testing.assert_array_equal(a_i, b_i)
    np.testing.assert_array_equal(a_v, b_v)

    rng = np.random.default_rng(0)
    u_draws = jnp.asarray(rng.normal(size=(ensemble.n_samples, 5, K)),
                          jnp.float32)
    a_v, a_i = single.recommend_factors(u_draws, 4)
    b_v, b_i = cluster.recommend_factors(u_draws, 4)
    np.testing.assert_array_equal(a_i, b_i)
    np.testing.assert_array_equal(a_v, b_v)


def test_topn_recommender_is_the_single_host_special_case(ensemble):
    """The historical TopNRecommender surface maps straight onto the tier."""
    rec = TopNRecommender(ensemble, n_shards=3)
    assert isinstance(rec, ClusterCoordinator)
    assert rec.n_shards == rec.n_hosts == 3
    assert [v.shape[0] for v in rec.v_shards] == [19, 19, 19]
    np.testing.assert_array_equal(rec.shard_offsets, [0, 19, 38])
    assert rec.u_flat.shape == (M, ensemble.n_samples * K)
    rebound = rec.rebind(_ensemble((4, 5, 6)))
    assert isinstance(rebound, TopNRecommender) and rebound.n_shards == 3
    with pytest.raises(ValueError, match="shape changed"):
        rec.rebind(_ensemble((1, 2)))


# ---------------------------------------------------------------------------
# epoch barrier: no epoch is served before ALL shards staged it
# ---------------------------------------------------------------------------
def test_partial_staging_does_not_advance_epoch():
    cluster = ClusterCoordinator(_ensemble((1,), epoch_coded_sample),
                                 n_hosts=3)
    nxt = _ensemble((2,), epoch_coded_sample)
    # stage hosts one at a time: the epoch must only move on the last one
    for host in cluster.hosts[:-1]:
        with cluster._lock:
            host.staged = host.stage(nxt)
            assert cluster._commit_locked(None) is False
        assert cluster.epoch == 1
    with cluster._lock:
        cluster.hosts[-1].staged = cluster.hosts[-1].stage(nxt)
        assert cluster._commit_locked(None) is True
    assert cluster.epoch == 2
    assert all(h.staged is None for h in cluster.hosts)
    assert all(h.live.ensemble.epoch == 2 for h in cluster.hosts)


def test_hosts_staggered_across_publishes_skip_to_common_epoch():
    """Host A staged epoch 2, host B jumped to 3: the barrier holds (2 is
    never served torn), then both land on 3 and it commits."""
    cluster = ClusterCoordinator(_ensemble((1,), epoch_coded_sample),
                                 n_hosts=2)
    e2 = _ensemble((2,), epoch_coded_sample)
    e3 = _ensemble((3,), epoch_coded_sample)
    a, b = cluster.hosts
    with cluster._lock:
        a.staged = a.stage(e2)
        b.staged = b.stage(e3)
        assert cluster._commit_locked(None) is False   # mixed epochs: hold
    assert cluster.epoch == 1
    with cluster._lock:
        a.staged = a.stage(e3)
        assert cluster._commit_locked(None) is True
    assert cluster.epoch == 3  # epoch 2 skipped, never served


def test_channel_fanout_commits_and_serves_consistently():
    """Publishes fan out to every host's subscriber loop; a request issued
    at any moment scores a single epoch across all shards (epoch-coded
    draws make a torn cross-shard mix observable), and the compiled top-N
    kernel is never retraced by same-shape publishes."""
    ch = PublicationChannel(window=1)
    ch.publish(1, epoch_coded_sample(1))
    cluster = ClusterCoordinator(
        PosteriorEnsemble(ch.snapshot().draws), n_hosts=2, channel=ch,
    )
    users = np.arange(4, dtype=np.int32)
    cluster.recommend(users, 1)  # compile at the serving shape
    traces_before = bpmf_topn.trace_count()

    def publisher():
        for step in range(2, 30):
            ch.publish(step, epoch_coded_sample(step))
            time.sleep(0.002)
        ch.close()

    pub = threading.Thread(target=publisher)
    pub.start()
    served_epochs = []
    try:
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            epoch = cluster.epoch
            vals, idx = cluster.recommend(users, 1)
            # every row scored one consistent cross-shard ensemble: the
            # winning item/score pair is some published epoch's signature,
            # no older than the epoch observed before the request
            got = float(vals[0][0])
            assert got == pytest.approx(round(got)), got
            assert idx[0][0] == int(round(got)) % N
            assert got >= epoch
            served_epochs.append(epoch)
            if ch.closed and cluster.epoch >= 29:
                break
    finally:
        pub.join(timeout=20.0)
        # the last publishes may still be mid-adoption: condition-wait for
        # the final barrier instead of polling
        assert cluster.wait_epoch(29, timeout=20.0)
        cluster.close()

    assert cluster.epoch == 29
    assert served_epochs == sorted(served_epochs)
    assert cluster.commits >= 2
    assert bpmf_topn.trace_count() == traces_before  # zero retraces


def test_shape_change_publish_reshards_all_hosts():
    ch = PublicationChannel(window=2)
    ch.publish(1, epoch_coded_sample(1))
    cluster = ClusterCoordinator(
        PosteriorEnsemble(ch.snapshot().draws), n_hosts=2, channel=ch,
    )
    assert cluster.ensemble.shape_key()[0] == 1
    ch.publish(2, epoch_coded_sample(2))  # window grows: S 1 -> 2
    assert cluster.wait_epoch(2, timeout=20.0)  # condition wait, no polling
    cluster.close()
    assert cluster.epoch == 2
    assert cluster.reshards == 1 and cluster.commits == 0
    assert cluster.ensemble.shape_key()[0] == 2
    # bounds still cover the catalogue after the reshard
    assert cluster.hosts[0].live.lo == 0
    assert cluster.hosts[-1].live.hi == N
    vals, idx = cluster.recommend(np.arange(3, dtype=np.int32), 1)
    assert idx[0][0] == 2 % N


def test_adopt_survives_stage_reshard_race():
    """host.stage() raising (live shapes changed by a concurrent reshard
    between the shape check and staging) must not kill the host loop: the
    adoption re-runs as a reshard and the publish is still served."""
    big_n = N + 7
    ch = PublicationChannel(window=1)
    ch.publish(2, epoch_coded_sample(2))
    snap = ch.snapshot()
    cluster = ClusterCoordinator(_ensemble((1,), epoch_coded_sample),
                                 n_hosts=2)
    # simulate the race: a reshard to a different item axis already hit
    # this host's live binding while snap's adoption was in flight
    bigger = PosteriorEnsemble((as_retained_sample(
        1, make_sample(1, n_items=big_n)),))
    cluster.hosts[0].live = cluster.hosts[0].build(bigger, 0, big_n)
    cluster._adopt(cluster.hosts[0], snap)  # must not raise
    assert cluster.epoch == 2
    assert all(h.live.ensemble.epoch == 2 for h in cluster.hosts)
    vals, idx = cluster.recommend(np.arange(3, dtype=np.int32), 1)
    assert idx[0][0] == 2 % N


def test_colocated_hosts_share_one_u_table():
    """The single-host special case must not pay the tier's replica cost:
    every colocated shard aliases one U scoring table."""
    ens = _ensemble((1, 2, 3))
    rec = TopNRecommender(ens, n_shards=4)
    u0 = rec.hosts[0].live.u_replica
    assert all(h.live.u_replica is u0 for h in rec.hosts)
    # the routed tier shares it too while hosts are device-less
    cluster = ClusterCoordinator(ens, n_hosts=4)
    u0 = cluster.hosts[0].live.u_replica
    assert all(h.live.u_replica is u0 for h in cluster.hosts)


def test_frontend_routes_through_cluster_tier():
    """RecommendFrontend(n_hosts=) serves through the coordinator and its
    publish swaps preserve the tier layout (rebind returns the same class
    with the same host count)."""
    from repro.serve import RecommendFrontend

    ch = PublicationChannel(window=1)
    ch.publish(5, epoch_coded_sample(5))
    fe = RecommendFrontend(channel=ch, subscribe=False, max_batch=4,
                           n_hosts=2)
    assert isinstance(fe._recommender, ClusterCoordinator)
    assert not isinstance(fe._recommender, TopNRecommender)
    assert fe._recommender.n_hosts == 2
    fe.submit(0, topk=1)
    (res,) = fe.flush()
    assert res.items[0] == 5 % N and res.scores[0] == pytest.approx(5.0)

    ch.publish(6, epoch_coded_sample(6))
    assert fe.refresh() is True and fe.rebinds == 1
    assert fe._recommender.n_hosts == 2
    fe.submit(1, topk=1)
    (res,) = fe.flush()
    assert res.epoch == 6 and res.items[0] == 6 % N


def test_cluster_freshness_clock_records_barrier_latency():
    ch = PublicationChannel(window=1)
    ch.publish(1, epoch_coded_sample(1))
    cluster = ClusterCoordinator(
        PosteriorEnsemble(ch.snapshot().draws), n_hosts=2, channel=ch,
    )
    for step in (2, 3):
        ch.publish(step, epoch_coded_sample(step))
        assert cluster.wait_epoch(step, timeout=20.0)
    cluster.close()
    fresh = cluster.freshness_percentiles()
    assert cluster.commits == 2
    assert len(cluster.publish_to_fresh_s) == 2
    assert 0 < fresh["p50"] <= fresh["max"] < 20.0


def test_chip_peaks_keyed_by_device_kind():
    """Roofline peaks come from a table keyed by `device_kind`; a kind that
    is not in the table is an error, never a silent default."""
    from repro.launch.mesh import TARGET_KIND, chip_peaks

    v5e = chip_peaks(TARGET_KIND)
    assert (v5e.flops_bf16, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("cpu")


def test_serving_hosts_share_devices_only_on_cpu(monkeypatch):
    """Forced host devices exist only on the CPU backend, where hosts may
    cycle over fewer devices; on an accelerator, fewer devices than hosts
    is an error rather than two hosts silently sharing one chip."""
    import jax

    from repro.launch import mesh

    n = len(jax.devices())
    hosts = mesh.serving_host_devices(n_hosts=n + 1)
    assert len(hosts) == n + 1 and hosts[n] == hosts[0]
    monkeypatch.setattr(mesh.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="serving hosts need"):
        mesh.serving_host_devices(n_hosts=n + 1)
    assert len(mesh.serving_host_devices(n_hosts=n)) == n
