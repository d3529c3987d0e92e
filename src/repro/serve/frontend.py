"""Request-batching serving front end for BPMF recommendations.

Serving traffic arrives as single-user requests; the kernel wants batches.
The frontend queues requests (thread-safe), then `flush()` drains the queue
in micro-batches of up to `max_batch`, one kernel invocation per batch —
the same amortisation the LM serving path gets from batched decode steps.
Cold-start requests (raw ratings instead of a user id) ride the same queue:
each flush folds them in against the current ensemble and scores them
through the same top-N kernel as trained users.

The item-factor cache is keyed by *sample epoch* — the newest retained
Gibbs step — and is refreshed on one of two paths:

* Push (preferred, trainer co-running): the frontend subscribes to a
  `serve.publish.PublicationChannel`; each retained draw the trainer
  publishes wakes the subscriber thread, which stacks the window into a
  PosteriorEnsemble *in memory* and swaps it in without touching disk.
  When the ensemble shapes (S, N, K) are unchanged — the steady state —
  the swap rebinds the existing recommender's shard layout and reuses
  every compiled top-N executable: a publish costs a buffer swap, not a
  recompile.
* Poll (fallback, no trainer attached): `refresh()` compares the
  SampleStore's newest step against the cached epoch and only on change
  reloads the ensemble from disk and re-shards V' across the mesh devices.

Both paths swap atomically and double-buffered: the previous epoch's
recommender is kept intact until the successor is fully built, and
`flush()` captures (recommender, epoch) under the lock, so in-flight
requests always score against one consistent ensemble — never a torn mix
of old and new factors — whichever thread published.
"""
from __future__ import annotations

import collections
import threading
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np
import jax

from repro.checkpoint.samples import SampleStore
from repro.data.sparse import SparseRatings
from repro.serve.cluster import ClusterCoordinator
from repro.serve.ensemble import PosteriorEnsemble
from repro.serve.foldin import FoldInPlanCache, fold_in
from repro.serve.publish import ChannelSnapshot, PublicationChannel
from repro.serve.topn import SeenIndex, TopNRecommender


@dataclass(frozen=True)
class RecommendResult:
    ticket: int
    items: np.ndarray    # (topk,) int32, -1 padded
    scores: np.ndarray   # (topk,) f32 posterior-mean scores
    epoch: int           # sample epoch that served the request
    latency_s: float     # enqueue -> result


@dataclass
class _Pending:
    ticket: int
    topk: int
    t_enqueue: float
    user_id: int | None = None
    item_ids: np.ndarray | None = None   # cold-start payload
    ratings: np.ndarray | None = None


class RecommendFrontend:
    def __init__(
        self,
        sample_root: str | Path | None = None,
        *,
        channel: PublicationChannel | None = None,
        subscribe: bool = True,
        wait_first_publish_s: float = 60.0,
        seen: SparseRatings | None = None,
        max_batch: int = 32,
        max_samples: int | None = None,
        devices=None,
        mesh=None,
        n_hosts: int | None = None,
        replicas: int = 1,
        interpret: bool | None = None,
    ):
        """seen: training ratings used to exclude already-rated items.
        devices / mesh: where to shard the item factors — a mesh contributes
        its "data"-axis devices (launch/mesh.py), default all local devices.
        n_hosts: serve through the multi-host tier (serve/cluster.py) with
        this many shard hosts — one per device when enough exist — instead
        of the colocated single-host recommender.
        replicas: per-shard replication factor for the tier (n_hosts only):
        each item shard gets `replicas` owners and the coordinator routes
        around dead or stale ones (serve/cluster.py failure semantics).

        channel: a PublicationChannel a co-running trainer publishes into;
        with subscribe=True (default) a daemon thread adopts each publish as
        it lands, otherwise call refresh() to adopt on your own schedule.
        At least one of sample_root / channel is required; with only a
        channel the constructor blocks up to `wait_first_publish_s` for the
        trainer's first retained draw.
        """
        if sample_root is None and channel is None:
            raise ValueError("need a sample_root, a channel, or both")
        self.store = SampleStore(sample_root) if sample_root is not None else None
        self.channel = channel
        self.seen = SeenIndex(seen) if seen is not None else None
        self.max_batch = max_batch
        self.max_samples = max_samples
        if mesh is not None and devices is None:
            devices = list(mesh.devices.flatten())
        self.devices = devices if devices is not None else jax.devices()
        self.n_hosts = n_hosts
        self.replicas = replicas
        self.interpret = interpret
        self._lock = threading.Lock()
        # notified (under _lock) by every _swap — the condition wait_epoch()
        # blocks on, so tests and drain loops need no sleep/poll
        self._swap_cond = threading.Condition(self._lock)
        self._adopt_lock = threading.Lock()  # one ensemble build at a time
        # cold-start plan cache: batches with similar rating-count profiles
        # share padded plan shapes, so the fused fold-in solve never
        # recompiles on the steady-state cold path (serve/foldin.py)
        self.foldin_cache = FoldInPlanCache()
        self._queue: list[_Pending] = []
        self._ticket = 0
        self._epoch: int | None = None
        self._recommender: TopNRecommender | None = None
        # bounded: a long-lived server must not grow one float per request
        self.latencies_s: collections.deque[float] = collections.deque(maxlen=65536)
        # publish-path stats: swap count
        self.swaps = 0
        self.rebinds = 0  # swaps that reused the compiled executables
        # publishes the subscriber rejected (e.g. an ensemble smaller than
        # the seen-item index) — kept so a rejection is observable without
        # killing the subscriber thread
        self.adopt_errors: collections.deque[Exception] = collections.deque(maxlen=64)
        self._subscriber: threading.Thread | None = None
        self._stop = threading.Event()

        # initial ensemble: disk when the store has retained draws (restart /
        # no-trainer case); otherwise block for the trainer's first publish —
        # a co-train first boot hands the server an still-empty sample dir
        if self.store is not None and self.store.epoch() is not None:
            self.refresh()
        elif channel is not None:
            snap = channel.wait(timeout=wait_first_publish_s)
            if snap is None:
                if channel.closed:
                    # not a timeout: the trainer ended (or died) before
                    # publishing anything — report that, don't mask it
                    raise RuntimeError(
                        "publication channel closed before the first publish "
                        "(trainer failed or finished during burn-in?)"
                    )
                raise TimeoutError(
                    f"no sample published within {wait_first_publish_s}s "
                    "and no retained samples to fall back to"
                )
            self._adopt_snapshot(snap)
        else:
            raise FileNotFoundError(
                f"no retained samples in {self.store.store.root}"
            )
        if channel is not None and subscribe:
            self._subscriber = threading.Thread(
                target=self._subscriber_loop, name="publish-subscriber", daemon=True
            )
            self._subscriber.start()

    # ------------------------------------------------------------------
    @property
    def epoch(self) -> int:
        with self._lock:
            assert self._epoch is not None
            return self._epoch

    @property
    def ensemble(self) -> PosteriorEnsemble:
        with self._lock:
            rec = self._recommender
        return rec.ensemble

    def refresh(self) -> bool:
        """Adopt the newest published or retained epoch; True on a swap.

        Checks the attached PublicationChannel first (in-memory adopt, no
        disk); falls back to polling the SampleStore directory — the only
        path when no trainer is co-running. The served-epoch reads here are
        prechecks on one locked snapshot; _swap() re-checks monotonicity
        under its lock.
        """
        with self._lock:
            served = self._epoch
            have_recommender = self._recommender is not None
        if self.channel is not None:
            snap = self.channel.snapshot()
            if snap is not None and (served is None or snap.epoch > served):
                return self._adopt_snapshot(snap)
        if self.store is None:
            return False
        newest = self.store.epoch()
        if newest is None:
            raise FileNotFoundError(f"no retained samples in {self.store.store.root}")
        if served is not None and newest <= served:
            return False
        try:
            ensemble = PosteriorEnsemble.load(
                self.store.store.root, max_samples=self.max_samples
            )
        except (FileNotFoundError, ValueError):
            # lost the race against the trainer's prune wholesale — keep
            # serving the cached epoch and let the next poll retry
            if have_recommender:
                return False
            raise
        return self._swap(ensemble)

    # ------------------------------------------------------------------
    # publish-path adoption: in-memory ensemble build + atomic swap
    # ------------------------------------------------------------------
    def _adopt_snapshot(self, snap: ChannelSnapshot) -> bool:
        """Build an ensemble from a channel snapshot and swap it in. The
        epoch precheck is only an optimisation — _swap() re-checks under
        its lock, which is what preserves monotonicity under races."""
        with self._lock:
            served = self._epoch
        if served is not None and snap.epoch <= served:
            return False
        draws = snap.draws
        if self.max_samples is not None:
            draws = draws[-self.max_samples:]
        ensemble = PosteriorEnsemble(draws)
        return self._swap(ensemble)

    def _swap(self, ensemble: PosteriorEnsemble) -> bool:
        """Atomically publish a fully-built successor recommender.

        Double-buffered: the old recommender keeps serving until the new one
        exists; rebind() reuses its compiled executables when shapes are
        unchanged, else a full build (which retraces on first use).

        Every adoption path (channel snapshot, disk reload) funnels through
        here, and the monotonicity check runs under _adopt_lock — so a slow
        disk refresh() racing the subscriber thread can never regress the
        served epoch, and only one successor is built at a time.
        """
        with self._adopt_lock:
            if self._epoch is not None and ensemble.epoch <= self._epoch:
                return False  # lost the race to a newer adopt
            old = self._recommender
            rebound = False
            if old is not None:
                try:
                    recommender = old.rebind(ensemble)
                    rebound = True
                except ValueError:
                    # shape change: fold-in plan schemas key on the item
                    # axis, so drop them with the executables they fed.
                    # Same-shape rebinds keep every cache entry — a publish
                    # must not cost the cold path its compiled solves.
                    self.foldin_cache.clear()
                    recommender = self._build_recommender(ensemble)
            else:
                recommender = self._build_recommender(ensemble)
            with self._lock:
                self._epoch = ensemble.epoch
                self._recommender = recommender
                self.swaps += 1
                self.rebinds += int(rebound)
                self._swap_cond.notify_all()
        return True

    def wait_epoch(self, epoch: int, timeout: float | None = None) -> bool:
        """Block until the served epoch reaches `epoch`; True on success,
        False on timeout. Condition-based (woken by every swap) — the
        synchronization seam threaded tests use instead of sleep/poll."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            while self._epoch is None or self._epoch < epoch:
                remaining = None
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return False
                self._swap_cond.wait(remaining)
            return True

    def _build_recommender(self, ensemble: PosteriorEnsemble):
        """Fresh recommender for `ensemble` (boot, or a shape-changing
        swap). Resyncs the seen-item index first: an exclusion index built
        against the boot-time ratings silently under-excludes once the
        user/item axes grow, so a mismatched shape rebuilds it padded to
        the ensemble's axes (new users/items get empty exclusion rows) and
        an ensemble *smaller* than the ratings is rejected outright."""
        if self.seen is not None:
            want = (ensemble.n_users, ensemble.n_items)
            if self.seen.shape != want:
                self.seen = self.seen.resized(want)  # ValueError on shrink
        if self.n_hosts is not None:
            devices = None
            if self.devices is not None and len(self.devices) >= self.n_hosts:
                devices = list(self.devices)[: self.n_hosts]
            return ClusterCoordinator(
                ensemble, n_hosts=self.n_hosts, replicas=self.replicas,
                devices=devices, interpret=self.interpret,
            )
        return TopNRecommender(
            ensemble, devices=self.devices, interpret=self.interpret
        )

    def _subscriber_loop(self) -> None:
        """Daemon: sleep on the channel, adopt each newer snapshot on
        arrival — the push path; serving threads never wait on a rebuild.

        A publish whose adoption is *rejected* (ValueError — e.g. an
        ensemble shrunk below the seen-item index) is recorded in
        `adopt_errors` and skipped: the loop keeps serving the current
        epoch and stays alive for future publishes, rather than dying and
        silently freezing the served epoch forever.
        """
        rejected: int | None = None  # newest rejected epoch; skip until newer

        def adopt(snap) -> None:
            nonlocal rejected
            try:
                self._adopt_snapshot(snap)
            except ValueError as e:
                with self._lock:
                    # recorded under the lock + notified so tests and
                    # operators can condition-wait on a rejection instead
                    # of polling the deque
                    self.adopt_errors.append(e)
                    self._swap_cond.notify_all()
                rejected = snap.epoch

        while not self._stop.is_set():
            with self._lock:
                # locked read: _swap writes _epoch under this lock, and an
                # unlocked read here could see a torn/stale value while a
                # swap is mid-publish (the hammer test in tests/test_publish
                # drives this race)
                epoch = self._epoch
            floor = epoch if rejected is None else max(epoch, rejected)
            snap = self.channel.wait(newer_than=floor, timeout=0.25)
            if snap is None:
                if self.channel.closed:
                    # a final publish can land between our timed-out wait()
                    # and the closed check — drain it before exiting, or the
                    # last epoch would never be adopted (co-train drain loops
                    # block on fe.epoch catching up to channel.epoch)
                    final = self.channel.snapshot()
                    if final is not None and final.epoch > floor:
                        adopt(final)
                    return
                continue  # timeout heartbeat: re-check _stop
            adopt(snap)

    def close(self) -> None:
        """Stop the subscriber thread (the channel itself stays usable)."""
        self._stop.set()
        if self._subscriber is not None:
            self._subscriber.join(timeout=5.0)
            self._subscriber = None

    # ------------------------------------------------------------------
    def submit(self, user_id: int, topk: int = 10) -> int:
        """Queue a trained-user request; returns a ticket matched by flush()."""
        with self._lock:
            # snapshot the ensemble under the lock (the discipline flush()
            # uses): an unlocked read could race a concurrent publish swap
            # and validate against a torn view
            n_users = self._recommender.ensemble.n_users
            if not 0 <= user_id < n_users:
                # reject at enqueue (like submit_ratings): an out-of-range id
                # would otherwise clamp to another user's recommendations, or
                # crash the whole micro-batch in the seen-item lookup
                raise ValueError(
                    f"user id must be in [0, {n_users}), got {user_id}"
                )
            self._ticket += 1
            self._queue.append(_Pending(
                ticket=self._ticket, topk=topk, t_enqueue=time.perf_counter(),
                user_id=int(user_id),
            ))
            return self._ticket

    def submit_ratings(
        self, item_ids: np.ndarray, ratings: np.ndarray, topk: int = 10
    ) -> int:
        """Queue a cold-start request: the user's ratings, not a user id."""
        item_ids = np.asarray(item_ids, np.int32)
        ratings = np.asarray(ratings, np.float32)
        assert item_ids.shape == ratings.shape
        with self._lock:
            # same snapshot-under-lock discipline as submit(): the item-axis
            # bound must come from the recommender a concurrent publish
            # cannot be half-way through swapping
            n_items = self._recommender.ensemble.n_items
            if item_ids.size and not (
                0 <= item_ids.min() and item_ids.max() < n_items
            ):
                # reject here, not at flush: one bad request must not poison
                # the whole micro-batch it would be folded in with
                raise ValueError(
                    f"item ids must be in [0, {n_items}), got "
                    f"[{item_ids.min()}, {item_ids.max()}]"
                )
            self._ticket += 1
            self._queue.append(_Pending(
                ticket=self._ticket, topk=topk, t_enqueue=time.perf_counter(),
                item_ids=item_ids, ratings=ratings,
            ))
            return self._ticket

    @property
    def pending(self) -> int:
        with self._lock:
            return len(self._queue)

    # ------------------------------------------------------------------
    def flush(self) -> list[RecommendResult]:
        """Drain the queue in micro-batches; returns results ticket-matched."""
        with self._lock:
            batch_all, self._queue = self._queue, []
            rec = self._recommender
            epoch = self._epoch
        results: list[RecommendResult] = []
        for lo in range(0, len(batch_all), self.max_batch):
            results.extend(self._run_batch(batch_all[lo: lo + self.max_batch],
                                           rec, epoch))
        self.latencies_s.extend(r.latency_s for r in results)
        return results

    def _run_batch(self, batch: list[_Pending], rec: TopNRecommender,
                   epoch: int) -> list[RecommendResult]:
        if not batch:
            return []
        # one host span per micro-batch, its warm and cold halves inside; a
        # profiler (jax.profiler.trace) puts them on the device trace's clock
        with jax.profiler.TraceAnnotation("serve.batch"):
            topk = max(p.topk for p in batch)
            warm = [p for p in batch if p.user_id is not None]
            cold = [p for p in batch if p.user_id is None]
            out: dict[int, tuple[np.ndarray, np.ndarray]] = {}

            if warm:
                with jax.profiler.TraceAnnotation("serve.warm"):
                    ids = np.asarray([p.user_id for p in warm], np.int32)
                    vals, idx = rec.recommend(ids, topk, seen=self.seen)
                for r, p in enumerate(warm):
                    out[p.ticket] = (vals[r], idx[r])

            if cold:
                with jax.profiler.TraceAnnotation("serve.cold"):
                    vals, idx = self._run_cold(cold, rec, topk)
                for r, p in enumerate(cold):
                    out[p.ticket] = (vals[r], idx[r])

        t_done = time.perf_counter()
        return [
            RecommendResult(
                ticket=p.ticket,
                items=out[p.ticket][1][: p.topk],
                scores=out[p.ticket][0][: p.topk],
                epoch=epoch,
                latency_s=t_done - p.t_enqueue,
            )
            for p in batch
        ]

    def _run_cold(self, cold: list[_Pending], rec: TopNRecommender,
                  topk: int) -> tuple[np.ndarray, np.ndarray]:
        """Fold in the cold-start requests and score them."""
        rows = np.concatenate([
            np.full(len(p.item_ids), r, np.int32) for r, p in enumerate(cold)
        ])
        cols = np.concatenate([p.item_ids for p in cold])
        vals_r = np.concatenate([p.ratings for p in cold])
        ratings = SparseRatings(
            rows=rows, cols=cols, vals=vals_r,
            shape=(len(cold), rec.ensemble.n_items),
        )
        # deterministic fold-in (conditional posterior means): serving
        # the same ratings twice must return the same recommendations.
        # The plan cache quantizes the batch's rating-count profile so
        # the fused (S*B) solve recompiles only on new shape families.
        u_draws = fold_in(None, ratings, rec.ensemble, sample=False,
                          plan_cache=self.foldin_cache)  # repro-lint: disable=guarded-field (never rebound; cache is internally locked)
        # explicit candidate-count pin (topk + batch max degree,
        # power-of-two quantized) — the same fetch the exclusion lists
        # imply, but stated independently of them, so the kernel shape
        # stays pinned even for requests with nothing to exclude
        hint = topk + max(len(p.item_ids) for p in cold)
        hint = 1 << (hint - 1).bit_length()
        return rec.recommend_factors(
            u_draws, topk, exclude=[p.item_ids for p in cold],
            fetch_hint=hint,
        )

    # ------------------------------------------------------------------
    def latency_percentiles(self) -> dict[str, float]:
        """p50/p99 over every request served so far (seconds)."""
        if not self.latencies_s:
            return {"p50": float("nan"), "p99": float("nan")}
        lat = np.asarray(self.latencies_s)
        return {"p50": float(np.percentile(lat, 50)),
                "p99": float(np.percentile(lat, 99))}
