"""Multi-host top-N serving tier: scatter/gather over resident item shards,
with per-shard replication and health-routed failover.

The single-host recommender (serve/topn.py) stops scaling at one host's
HBM: V' for the full catalogue must fit beside the U table. This module is
the pod-scale tier ROADMAP names — the same decomposition "A
High-Performance Implementation of Bayesian Matrix Factorization with
Limited Communication" (Vander Aa et al., 2020) uses for BMF at scale:

* Each **ShardHost** owns a *resident* row-range of V' (its item shard)
  plus a *routed replica* of the U scoring table, so a warm-user request
  ships only user ids to every host — each host gathers the rows from its
  own replica and streams its shard through the `bpmf_topn` kernel.
  Cold-start rows (fold-in factors, computed once at the coordinator) are
  scattered to the hosts instead.

* The **ClusterCoordinator** gathers one candidate list per *shard* — each
  `(B, min(fetch, shard_rows))`, so the exchange is bounded by
  O(shards * fetch) values + indices regardless of catalogue size — and
  merges them with the same stable `_merge_topk` the kernel applies across
  item tiles: shards hold disjoint ascending index ranges and are
  concatenated in range order, so ties still resolve to the lowest global
  item index, bit-for-bit what one unsharded `lax.top_k` would pick.

* **Replication & failover** (`replicas=R`): every shard is owned by R
  hosts holding identical bindings, and requests are routed to the first
  healthy, epoch-current replica (serve/faults.py's `HostHealth` tracks
  heartbeats, adopt/serve error escalation, and explicit kills). A host
  that dies mid-request is routed around within the request; a shard whose
  owners are *all* dead is rebuilt from the committed ensemble on a
  surviving host's device (`reassignments` counts these) — served results
  stay bit-identical to a healthy tier at the committed epoch whenever at
  least one replica per shard is live, because every replica (original,
  surviving, or rebuilt) is a pure function of the same ensemble.

* Freshness rides the PublicationChannel's subscriber list (serve/publish):
  `attach()` fans each publish out to one subscriber loop per host. Each
  host *stages* its successor binding (a zero-retrace rebind: same shapes,
  same compiled executables), and the coordinator *commits* an epoch once a
  **quorum** — one healthy staged replica per shard — has staged it: a
  request can never score shard 0 against epoch E and shard 1 against E-1
  (no torn cross-shard ensembles), and a dead or hung host no longer wedges
  the barrier (it is simply absent from the quorum; with `replicas=1` its
  shard is reassigned and the replacement stages). Replicas that stage the
  committed epoch late flip in place — identical data, no second commit.
  A host that falls behind makes only *its* shard lean on the other
  replicas; epochs it skipped are never served.

* **Fault seams** (serve/faults.py): when a `FaultPlan` is injected, the
  coordinator fires named hook points — "adopt" (subscriber picked up a
  publish), "stage" (building the successor binding), "commit" (before the
  barrier), "gather" (collecting a host's candidates) — so chaos schedules
  (kill / hang / delay / drop) are reproducible from a seed instead of
  sleeps. tests/test_chaos.py is the suite built on them.

`TopNRecommender` is the single-host special case of this tier: it
subclasses the coordinator with all shards colocated in-process, so the
shard assignment, fetch quantization, exclusion filtering, and merge
contract exist exactly once.

Runnable without hardware: `launch/serve.py --hosts N [--replicas R]`
simulates N hosts via `XLA_FLAGS=--xla_force_host_platform_device_count`,
one simulated host per device with its own subscriber thread.
"""
from __future__ import annotations

import collections
import math
import threading
import time
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.serve.ensemble import PosteriorEnsemble
from repro.serve.faults import (
    DEAD,
    HEALTHY,
    Clock,
    FaultDrop,
    FaultPlan,
    HostHealth,
    HostKilled,
    assert_holds,
)
from repro.serve.publish import ChannelSnapshot, PublicationChannel


def shard_bounds(n_items: int, n_shards: int) -> np.ndarray:
    """Item-axis shard assignment shared by every tier layout: n_shards+1
    ascending bounds, balanced to within one row. The single-host
    recommender and the cluster use the same bounds, so their per-shard
    kernel shapes (and jit cache entries) coincide."""
    return np.linspace(0, n_items, n_shards + 1).astype(int)


def _merge_topk(vals: jax.Array, idx: jax.Array, topk: int
                ) -> tuple[jax.Array, jax.Array]:
    """Merge per-shard candidates (B, C) keeping lax.top_k's stable order.

    Shards hold disjoint, ascending index ranges and are concatenated in
    range order, so position-stable top_k again resolves ties to the lowest
    global item index.
    """
    v, pos = jax.lax.top_k(vals, topk)
    return v, jnp.take_along_axis(idx, pos, axis=1)


class _Binding(NamedTuple):
    """One host's immutable serving state for one epoch. Requests capture a
    binding snapshot under the coordinator lock and score entirely against
    it — commits and reshards replace bindings, never mutate them."""

    ensemble: PosteriorEnsemble
    u_replica: jax.Array   # (M, S*K) routed replica of the U scoring table
    v_shard: jax.Array     # (hi-lo, S*K) resident item shard
    lo: int                # global index of the shard's first item
    hi: int


class ShardHost:
    """One serving host: device placement + the live/staged binding pair.

    `stage()` builds the successor binding off the serving path (the
    expensive part: slicing V' and placing both tables on the host's
    device); the coordinator performs the cheap barrier-side flip under
    its lock once a quorum of hosts has staged the same epoch.

    `shard` is the item shard this host owns; with `replicas=R` several
    hosts share one shard (identical bindings — any of them can serve it).

    routed=False is the colocated (single-host recommender) layout: hosts
    share one coordinator-side U table instead of each holding a routed
    device replica, and the coordinator gathers scoring rows once — the
    tier's replica memory cost is only paid where hosts are real.
    """

    def __init__(self, host_id: int, ensemble: PosteriorEnsemble,
                 lo: int, hi: int, *, device=None, interpret: bool | None = None,
                 routed: bool = True, flats=None, shard: int | None = None):
        self.host_id = host_id
        self.shard = host_id if shard is None else shard
        self.device = device
        self.interpret = interpret
        self.routed = routed
        self.live = self.build(ensemble, lo, hi, flats=flats)
        self.staged: _Binding | None = None

    def build(self, ensemble: PosteriorEnsemble, lo: int, hi: int,
              *, flats=None) -> _Binding:
        """Materialise a binding: resident V' rows [lo, hi) + the U table,
        device-placed when this host has a pinned device. `flats` shares
        one scoring_matrices() result across hosts (construction/reshard —
        colocated hosts then alias a single U array); staging computes its
        own, modelling per-host independence on a real pod."""
        u_flat, v_flat = flats if flats is not None else ensemble.scoring_matrices()
        chunk = v_flat[lo:hi]
        if self.device is not None:
            chunk = jax.device_put(chunk, self.device)
            if self.routed:
                u_flat = jax.device_put(u_flat, self.device)
        return _Binding(ensemble, u_flat, chunk, int(lo), int(hi))

    def stage(self, ensemble: PosteriorEnsemble) -> _Binding:
        """Build (but do not serve) the successor for a same-shape publish.
        Same bounds + same shapes -> every kernel invocation lands on the
        jit cache entries the live binding already compiled (zero retrace).
        """
        live = self.live  # snapshot: a concurrent reshard swaps the attr
        if ensemble.shape_key() != live.ensemble.shape_key():
            raise ValueError(
                f"shape changed: {ensemble.shape_key()} vs "
                f"{live.ensemble.shape_key()} — reshard, don't stage"
            )
        return self.build(ensemble, live.lo, live.hi)

    def candidates(self, binding: _Binding, fetch: int, *,
                   rows: jax.Array | None = None,
                   user_ids: np.ndarray | None = None
                   ) -> tuple[jax.Array, jax.Array]:
        """This host's (B, k_eff) candidate list against `binding`'s shard.

        Warm requests route user ids and gather from the local U replica;
        cold/fold-in requests scatter precomputed scoring rows instead.
        k_eff < fetch on a shard smaller than the fetch width (the ragged
        final shard) — the merge pads nothing, it just sees fewer columns.
        """
        if rows is None:
            rows = binding.u_replica[user_ids]
        k_eff = min(fetch, binding.hi - binding.lo)
        vals, idx = ops.topn_scores(rows, binding.v_shard, k_eff,
                                    interpret=self.interpret)
        return vals, idx + np.int32(binding.lo)


class ClusterCoordinator:
    """Scatter/gather top-N over ShardHosts, with a quorum epoch barrier,
    per-shard replication, and health-routed failover.

    The serving API matches TopNRecommender exactly (`recommend`,
    `recommend_rows`, `recommend_factors`, `rebind`) — the frontend and the
    launchers treat the two interchangeably; TopNRecommender *is* this
    class with every host colocated.

    `attach(channel)` subscribes one loop per host to a PublicationChannel:
    publishes fan out to all hosts, each stages its shard independently,
    and `epoch` advances once one healthy replica per shard staged it.

    `replicas=R` gives every item shard R owners (n_shards =
    ceil(n_hosts / R); host i owns shard i mod n_shards). `faults` injects
    a chaos schedule (serve/faults.py); `clock` is the injected time
    source shared with the health tracker.
    """

    # the tier routes user ids and each host gathers from its own U
    # replica; TopNRecommender overrides this to False — colocated shards
    # share one U table and the coordinator gathers rows once
    routed = True

    def __init__(
        self,
        ensemble: PosteriorEnsemble,
        *,
        n_hosts: int = 1,
        replicas: int = 1,
        devices=None,
        mesh=None,
        interpret: bool | None = None,
        channel: PublicationChannel | None = None,
        max_samples: int | None = None,
        faults: FaultPlan | None = None,
        clock: Clock | None = None,
        heartbeat_timeout: float = 5.0,
        max_host_errors: int = 3,
    ):
        if mesh is not None and devices is None:
            from repro.launch.mesh import serving_host_devices
            devices = serving_host_devices(mesh=mesh)
        if devices is not None:
            n_hosts = len(devices)
        self.interpret = interpret
        self.devices = devices
        self.max_samples = max_samples
        self.replicas = max(1, int(replicas))
        n_hosts = max(1, n_hosts)
        self._n_shards = max(1, min(math.ceil(n_hosts / self.replicas),
                                    ensemble.n_items))
        self._layout_hosts = n_hosts
        self.faults = faults
        if clock is None:
            clock = faults.clock if faults is not None else Clock()
        self.clock = clock
        self.health = HostHealth(clock=clock,
                                 heartbeat_timeout=heartbeat_timeout,
                                 max_errors=max_host_errors)
        bounds = shard_bounds(ensemble.n_items, self._n_shards)
        flats = ensemble.scoring_matrices()  # one U/V' build shared by all
        self.hosts = []
        self._owners: list[list[ShardHost]] = [[] for _ in range(self._n_shards)]
        for i in range(n_hosts):
            s = i % self._n_shards
            host = ShardHost(
                i, ensemble, bounds[s], bounds[s + 1],
                device=(devices[i % len(devices)] if devices is not None else None),
                interpret=interpret, routed=self.routed, flats=flats,
                shard=s,
            )
            self.hosts.append(host)
            self._owners[s].append(host)
            self.health.register(i)
        self._next_host_id = n_hosts
        # candidates from hosts pinned to distinct devices need an explicit
        # device->host gather before the merge; colocated shards merge on
        # device with no round trip
        self._multi_device = devices is not None and len(set(devices)) > 1
        self.ensemble = ensemble
        self._epoch = ensemble.epoch
        self._lock = threading.Lock()
        self._epoch_cond = threading.Condition(self._lock)
        self._build_lock = threading.Lock()
        self._pending: tuple[int, PosteriorEnsemble] | None = None  # (seq, ens)
        # barrier-path stats: committed epochs, coordinated reshards, shard
        # reassignments after host loss, gather-path failovers, and
        # publish -> all-shards-fresh latency (the cross-host freshness clock)
        self.commits = 0
        self.reshards = 0
        self.reassignments = 0
        self.gather_failovers = 0
        self.publish_to_fresh_s: collections.deque[float] = collections.deque(maxlen=4096)
        # adopt failures recorded instead of killing a host loop (the
        # frontend keeps the same deque one level up)
        self.adopt_errors: collections.deque[Exception] = collections.deque(maxlen=64)
        self.channel: PublicationChannel | None = None
        self._threads: list[threading.Thread] = []
        self._stop = threading.Event()
        if channel is not None:
            self.attach(channel)

    # -- layout ---------------------------------------------------------
    @property
    def n_hosts(self) -> int:
        with self._lock:
            return len(self.hosts)

    @property
    def n_shards(self) -> int:
        return self._n_shards

    @property
    def epoch(self) -> int:
        with self._lock:
            return self._epoch

    def wait_epoch(self, epoch: int, timeout: float | None = None) -> bool:
        """Block until the committed epoch reaches `epoch`; True on success,
        False on timeout. Condition-based (woken by commits and reshards) —
        the synchronization seam tests use instead of sleep/poll loops."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            while self._epoch < epoch:
                remaining = None
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return False
                self._epoch_cond.wait(remaining)
            return True

    def _layout_kwargs(self) -> dict:
        return dict(n_hosts=self._layout_hosts, replicas=self.replicas,
                    devices=self.devices, interpret=self.interpret,
                    max_samples=self.max_samples)

    def rebind(self, ensemble: PosteriorEnsemble):
        """A new coordinator serving `ensemble` through this one's compiled
        executables: same shard bounds, same device placement, and — because
        every jit in the scoring path keys on shapes this layout pins — zero
        retraces of the top-N kernel (kernels.bpmf_topn.trace_count is flat
        across a rebind; tested). The publish hot path: a same-shape sample
        publication costs one V' re-shard + buffer swap, not a recompile.

        Self is left untouched and fully servable — callers swap the
        returned instance in atomically (RecommendFrontend holds requests'
        view stable by capturing the old instance under its lock).

        Raises ValueError when the ensemble's (S, M, N, K) changed; the
        caller falls back to a full rebuild (which will retrace).
        """
        with self._lock:
            current_key = self.ensemble.shape_key()
        if ensemble.shape_key() != current_key:
            raise ValueError(
                f"shape changed: {ensemble.shape_key()} vs "
                f"{current_key} — rebuild, don't rebind"
            )
        return type(self)(ensemble, **self._layout_kwargs())

    # -- fault seam -----------------------------------------------------
    def _fault(self, seam: str, host_id: int) -> None:
        """Hook point for the injected chaos schedule. kill marks the host
        dead and raises; hang blocks until released (heartbeats stop —
        the health tracker escalates); delay sleeps on the injected clock;
        drop raises FaultDrop for the caller to swallow."""
        if self.faults is None:
            return
        ev = self.faults.fire(seam, host_id)
        if ev is None:
            return
        if ev.action == "kill":
            self.health.kill(host_id)
            raise HostKilled(f"host {host_id} killed at seam {seam!r}")
        if ev.action == "hang":
            self.faults.hang(host_id)
        elif ev.action == "delay":
            self.clock.sleep(ev.delay_s)
        elif ev.action == "drop":
            raise FaultDrop(f"{seam!r} dropped for host {host_id}")

    # -- serving (scatter/gather with failover routing) ------------------
    def _snapshot(self) -> tuple[int, PosteriorEnsemble,
                                 list[tuple[ShardHost, _Binding]]]:
        """Atomic view for one request: epoch + one (host, binding) pick per
        shard, routed around unhealthy replicas. A commit or reshard that
        lands mid-request replaces bindings but never mutates these — the
        request finishes on one epoch."""
        with self._lock:
            picks = [self._select_shard_locked(s) for s in range(self._n_shards)]
            return self._epoch, self.ensemble, picks

    def _select_shard_locked(self, s: int, exclude: set[int] = frozenset()
                             ) -> tuple[ShardHost, _Binding]:
        """Pick the replica serving shard `s`: the first HEALTHY owner whose
        live binding is at the committed epoch; a SUSPECT owner (stale
        heartbeat) only as a fallback; a freshly rebuilt replica when no
        owner survives at the committed epoch. Caller holds self._lock."""
        assert_holds(self._lock)
        fallback = None
        for h in self._owners[s]:
            if h.host_id in exclude:
                continue
            state = self.health.state(h.host_id)
            if state == DEAD:
                continue
            if h.live.ensemble.epoch != self._epoch:
                continue  # stale replica: routed around until it catches up
            if state == HEALTHY:
                return h, h.live
            if fallback is None:
                fallback = (h, h.live)
        if fallback is not None:
            return fallback
        return self._reassign_locked(s)

    def _reassign_locked(self, s: int) -> tuple[ShardHost, _Binding]:
        """Failover path: every owner of shard `s` is dead (or stale past
        recovery) — rebuild the shard from the *committed* ensemble on a
        surviving host's device. The rebuilt binding is a pure function of
        the same ensemble every committed binding came from, so serving
        stays bit-identical and epoch monotonicity is untouched. When a
        channel is attached the replacement gets its own subscriber loop,
        so it stages future epochs like any other owner."""
        assert_holds(self._lock)
        bounds = shard_bounds(self.ensemble.n_items, self._n_shards)
        donor = next(
            (h for h in self.hosts
             if self.health.serveable(h.host_id) and h.device is not None),
            None,
        )
        host = ShardHost(
            self._next_host_id, self.ensemble, bounds[s], bounds[s + 1],
            device=(donor.device if donor is not None else None),
            interpret=self.interpret, routed=self.routed, shard=s,
        )
        self._next_host_id += 1
        self.hosts.append(host)
        self._owners[s].append(host)
        self.health.register(host.host_id)
        self.reassignments += 1
        if (self.channel is not None and self._threads
                and not self._stop.is_set()):
            t = threading.Thread(
                target=self._host_loop, args=(host,),
                name=f"shard-host-{host.host_id}", daemon=True,
            )
            self._threads.append(t)
            t.start()
        return host, host.live

    def _gather_merge(self, picks: list[tuple[ShardHost, _Binding]],
                      fetch: int, *, rows=None, user_ids=None
                      ) -> tuple[jax.Array, jax.Array]:
        vals, idx = [], []
        for s, (host, binding) in enumerate(picks):
            tried: set[int] = set()
            while True:
                try:
                    self._fault("gather", host.host_id)
                    v, i = host.candidates(binding, fetch, rows=rows,
                                           user_ids=user_ids)
                    break
                except HostKilled:
                    # the host died mid-request: fail over to another
                    # replica of the same shard (identical binding), or a
                    # rebuilt one — the request still completes
                    tried.add(host.host_id)
                except FaultDrop as e:
                    # the response was lost: escalate (repeated drops kill
                    # the host) and re-route this request
                    self.health.error(host.host_id, e)
                    tried.add(host.host_id)
                with self._lock:
                    self.gather_failovers += 1
                    host, binding = self._select_shard_locked(s, exclude=tried)
            vals.append(v)
            idx.append(i)
        if len(vals) == 1:
            return vals[0], idx[0]
        if self._multi_device:
            # the cross-host exchange: each host ships only its (B, k_eff)
            # candidate list to the coordinator — O(shards * fetch) values +
            # indices regardless of catalogue size. device_get is the
            # explicit gather (candidates live on per-host devices); the
            # merge itself runs at the coordinator.
            vals = np.concatenate([np.asarray(v) for v in vals], axis=1)
            idx = np.concatenate([np.asarray(i) for i in idx], axis=1)
            return _merge_topk(jnp.asarray(vals), jnp.asarray(idx), fetch)
        # colocated shards: merge on device, no host round trip
        return _merge_topk(jnp.concatenate(vals, 1), jnp.concatenate(idx, 1),
                           fetch)

    def _topk_rows(self, rows: jax.Array, topk: int
                   ) -> tuple[jax.Array, jax.Array]:
        """Kernel top-k of rows @ V'^T across all item shards."""
        _, ens, picks = self._snapshot()
        return self._gather_merge(picks, min(topk, ens.n_items), rows=rows)

    def _serve(self, topk: int, *, rows=None, user_ids=None,
               exclude: list[np.ndarray] | None = None,
               fetch_hint: int | None = None) -> tuple[np.ndarray, np.ndarray]:
        _, ens, picks = self._snapshot()
        if user_ids is not None and not self.routed:
            # colocated layout: one coordinator-side gather from the shared
            # U table instead of a per-host replica gather
            rows = picks[0][1].u_replica[np.asarray(user_ids, np.int32)]
            user_ids = None
        b = rows.shape[0] if rows is not None else len(user_ids)
        fetch = topk
        if exclude is not None:
            assert len(exclude) == b, (len(exclude), b)
            fetch = topk + max((len(e) for e in exclude), default=0)
        if fetch_hint is not None:
            # honored with or without exclusions: a hint pins the kernel
            # shape even for exclusion-free (e.g. cold-start) batches, whose
            # drifting topk would otherwise thrash the jit cache
            fetch = max(fetch, fetch_hint)
        # round up to a power of two unconditionally: every serving caller
        # (with exclusions, with a hint, or bare) folds onto O(log n_items)
        # kernel shapes instead of one compile per distinct topk
        fetch = 1 << (fetch - 1).bit_length()
        fetch = min(fetch, ens.n_items)
        vals, idx = self._gather_merge(picks, fetch, rows=rows,
                                       user_ids=user_ids)
        # the host waits here for the device's candidates
        with jax.profiler.TraceAnnotation("serve.fetch"):
            vals = np.asarray(vals) + ens.global_mean
            idx = np.asarray(idx)
        if exclude is None:
            return vals[:, :topk], idx[:, :topk]
        with jax.profiler.TraceAnnotation("serve.exclude"):
            out_v = np.full((b, topk), -np.inf, np.float32)
            out_i = np.full((b, topk), -1, np.int32)
            for r in range(b):
                keep = ~np.isin(idx[r], exclude[r])
                kept_v, kept_i = vals[r][keep][:topk], idx[r][keep][:topk]
                out_v[r, : len(kept_v)] = kept_v
                out_i[r, : len(kept_i)] = kept_i
        return out_v, out_i

    def recommend_rows(
        self,
        rows: jax.Array,
        topk: int,
        *,
        exclude: list[np.ndarray] | None = None,
        fetch_hint: int | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Top-N for explicit scoring rows (B, S*K), scattered to every host.

        exclude: optional per-row arrays of item ids to drop (seen items).
        fetch_hint: a batch-independent upper bound on topk + exclusions
        (e.g. topk + SeenIndex.max_degree) — pins the candidate count so the
        serving hot path compiles exactly one kernel shape per topk.
        Returns host arrays (values (B, topk), indices (B, topk)); rows with
        fewer than topk candidates left are padded with (-inf, -1).
        """
        return self._serve(topk, rows=rows, exclude=exclude,
                           fetch_hint=fetch_hint)

    def recommend(
        self,
        user_ids: np.ndarray,
        topk: int,
        *,
        seen=None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Top-N for trained users: only the ids are routed — each host
        gathers the scoring rows from its own U replica. `seen` excludes
        each user's already-rated items; pass a prebuilt SeenIndex on the
        serving hot path (a raw SparseRatings is indexed from scratch on
        every call)."""
        from repro.serve.topn import SeenIndex  # lazy: topn subclasses us

        user_ids = np.asarray(user_ids, np.int32)
        exclude = None
        fetch_hint = None
        if seen is not None:
            if not isinstance(seen, SeenIndex):
                seen = SeenIndex(seen)
            exclude = [seen[int(u)] for u in user_ids]
            fetch_hint = topk + seen.max_degree
        return self._serve(topk, user_ids=user_ids, exclude=exclude,
                           fetch_hint=fetch_hint)

    def recommend_factors(
        self,
        u_draws: jax.Array,
        topk: int,
        *,
        exclude: list[np.ndarray] | None = None,
        fetch_hint: int | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Top-N for fold-in users given their per-draw factors (S, B, K).

        fetch_hint pins the candidate count across cold batches (the
        frontend passes topk + batch max degree, power-of-two quantized) so
        varying per-batch rated counts reuse one compiled kernel shape."""
        _, ens, _ = self._snapshot()
        rows = ens.user_scoring_rows(u_draws)
        return self._serve(topk, rows=rows, exclude=exclude,
                           fetch_hint=fetch_hint)

    # -- freshness: channel fan-out + quorum-staged barrier ---------------
    def attach(self, channel: PublicationChannel) -> None:
        """Fan the channel's publishes out to every host: one subscriber
        loop per host (the in-process stand-in for a per-process subscriber
        on a real pod), each staging its own shard as publishes land."""
        if self.channel is not None:
            raise RuntimeError("already attached to a channel")
        self.channel = channel
        with self._lock:
            threads = [
                threading.Thread(
                    target=self._host_loop, args=(host,),
                    name=f"shard-host-{host.host_id}", daemon=True,
                )
                for host in self.hosts
            ]
            self._threads = threads
        for t in threads:
            t.start()

    def close(self) -> None:
        """Stop the per-host subscriber loops (the channel stays usable).
        Hung hosts are released first so their threads can exit."""
        self._stop.set()
        if self.faults is not None:
            self.faults.release()
        with self._lock:
            threads = list(self._threads)
        for t in threads:
            t.join(timeout=5.0)
        with self._lock:
            self._threads = []

    def _host_loop(self, host: ShardHost) -> None:
        last_staged = self.epoch
        while not self._stop.is_set():
            self.health.beat(host.host_id)
            snap = self.channel.wait(newer_than=last_staged, timeout=0.25)
            if snap is None:
                if self.channel.closed:
                    # drain: a final publish can land between a timed-out
                    # wait and the closed check (same discipline as the
                    # frontend's subscriber loop)
                    final = self.channel.snapshot()
                    if final is not None and final.epoch > last_staged:
                        self._adopt_in_loop(host, final)
                    return
                continue
            last_staged = max(last_staged, snap.epoch)
            if not self._adopt_in_loop(host, snap):
                return  # the host died; its replicas carry the shard

    def _adopt_in_loop(self, host: ShardHost, snap: ChannelSnapshot) -> bool:
        """Adoption with the loop's failure policy: a kill ends the loop
        (False); any other failure is recorded and escalated, and the loop
        lives on to try the next publish — a bad epoch must not freeze the
        host forever, and an unexpected exception must not silently wedge
        the quorum."""
        try:
            self._adopt(host, snap)
            return True
        except HostKilled:
            return False
        except Exception as e:  # noqa: BLE001 — recorded, host escalated
            self.adopt_errors.append(e)
            self.health.error(host.host_id, e)
            return True

    def _ensemble_for(self, snap: ChannelSnapshot) -> PosteriorEnsemble:
        """Stack the snapshot's draw window once per publish; host loops
        share the decoded ensemble, then do their own (per-device) staging
        work outside any lock."""
        with self._build_lock:
            if self._pending is not None and self._pending[0] == snap.seq:
                return self._pending[1]
            draws = snap.draws
            if self.max_samples is not None:
                draws = draws[-self.max_samples:]
            ensemble = PosteriorEnsemble(draws)
            self._pending = (snap.seq, ensemble)
            return ensemble

    def _adopt(self, host: ShardHost, snap: ChannelSnapshot) -> None:
        try:
            self._fault("adopt", host.host_id)
            ensemble = self._ensemble_for(snap)
            # optimistic shape precheck — deliberately lock-free: staging
            # revalidates (ValueError below) and _reshard re-checks epoch
            # and shape under the lock, so a stale read here only costs one
            # detour, never a torn commit
            if ensemble.shape_key() != self.ensemble.shape_key():  # repro-lint: disable=guarded-field (revalidated under lock)
                self._reshard(ensemble)
                return
            self._fault("stage", host.host_id)
            try:
                binding = host.stage(ensemble)  # heavy part: off the lock
            except ValueError:
                # raced a reshard: another host's thread changed the live
                # shapes between our shape check and staging. Re-run as a
                # reshard — _reshard re-checks epoch and shape under the
                # lock, so a reshard that already superseded this publish
                # is a no-op (and the host loop survives either way).
                self._reshard(ensemble)
                return
            # the commit seam fires *before* the lock: a hang here stalls
            # this host's commit, never the coordinator's critical section
            self._fault("commit", host.host_id)
        except FaultDrop:
            return  # the publish never reached this host; it catches up later
        with self._lock:
            if ensemble.epoch <= self._epoch:
                if (ensemble.epoch == self._epoch
                        and host.live.ensemble.epoch < self._epoch):
                    # late replica of the already-committed epoch: flip in
                    # place — byte-identical to every committed binding, so
                    # no second commit and no epoch movement
                    host.live = binding
                    host.staged = None
                return  # lost the race to a newer commit / reshard
            host.staged = binding
            self._commit_locked(snap.t_publish)

    def _commit_locked(self, t_publish: float | None) -> bool:
        """Flip staged hosts iff a quorum — one serveable replica per shard
        — has staged the same strictly-newer epoch (the no-torn-cross-shard
        barrier; dead hosts are excluded, so a lost host cannot wedge it).
        The highest fully-covered epoch wins; hosts staged on an older
        epoch have it discarded (it was never served), hosts staged on a
        newer one keep theirs for the next barrier. Caller holds self._lock.
        """
        assert_holds(self._lock)
        for s in range(self._n_shards):
            # a shard whose owners all died can never clear the barrier:
            # rebuild it on a surviving host now — with a channel attached
            # the replacement subscribes and stages the pending epoch
            if not any(self.health.serveable(h.host_id)
                       for h in self._owners[s]):
                self._reassign_locked(s)
        staged_epochs = sorted(
            {h.staged.ensemble.epoch for h in self.hosts
             if h.staged is not None and self.health.serveable(h.host_id)},
            reverse=True,
        )
        for epoch in staged_epochs:
            if epoch <= self._epoch:
                break
            covered = {
                h.shard for h in self.hosts
                if h.staged is not None and self.health.serveable(h.host_id)
                and h.staged.ensemble.epoch == epoch
            }
            if len(covered) != self._n_shards:
                continue  # some shard's replicas are all mid-flight: hold
            committed = next(
                h.staged.ensemble for h in self.hosts
                if h.staged is not None and h.staged.ensemble.epoch == epoch
            )
            for h in self.hosts:
                if h.staged is None:
                    continue
                if h.staged.ensemble.epoch == epoch:
                    h.live, h.staged = h.staged, None
                elif h.staged.ensemble.epoch < epoch:
                    h.staged = None  # superseded; that epoch is never served
            self._epoch = epoch
            self.ensemble = committed
            self.commits += 1
            if t_publish is not None:
                self.publish_to_fresh_s.append(time.perf_counter() - t_publish)
            self._epoch_cond.notify_all()
            return True
        return False

    def _reshard(self, ensemble: PosteriorEnsemble) -> None:
        """Coordinated shape-change adoption: new shard bounds, every host
        rebuilt in one critical section (on a real pod this is a resharding
        deployment, not a rolling rebind). First host thread to see the new
        shape does the work; the rest observe the advanced epoch and skip.
        In-flight requests hold the old bindings and finish untorn."""
        with self._lock:
            if ensemble.epoch <= self._epoch:
                return
            bounds = shard_bounds(ensemble.n_items, self._n_shards)
            # a reshard IS the stop-the-world path: every host must flip to
            # the new shard bounds in one critical section or a request
            # could gather torn cross-shard state. The device build happens
            # under the lock by design (rare: shape changes only).
            flats = ensemble.scoring_matrices()  # repro-lint: disable=sync-under-lock (intentional stop-the-world)
            for h in self.hosts:
                h.live = h.build(ensemble, bounds[h.shard],
                                 bounds[h.shard + 1], flats=flats)
                h.staged = None
            self._epoch = ensemble.epoch
            self.ensemble = ensemble
            self.reshards += 1
            self._epoch_cond.notify_all()

    # -- observability ---------------------------------------------------
    def freshness_percentiles(self) -> dict[str, float]:
        """p50/max publish -> all-shards-fresh latency (seconds)."""
        # snapshot under the lock: a commit appending to the deque while
        # np.asarray iterates it would raise "deque mutated during iteration"
        with self._lock:
            lat = list(self.publish_to_fresh_s)
        if not lat:
            return {"p50": float("nan"), "max": float("nan")}
        arr = np.asarray(lat)
        return {"p50": float(np.percentile(arr, 50)), "max": float(arr.max())}

    def stats(self) -> dict:
        """One observability snapshot: committed epoch, per-host health and
        binding state, and per-shard commit-quorum status (who owns it, who
        is serveable, who has staged what). The failure-mode dashboard the
        chaos suite reads."""
        health = self.health.snapshot()
        with self._lock:
            hosts = {}
            for h in self.hosts:
                rec = dict(health.get(
                    h.host_id,
                    {"state": HEALTHY, "errors": 0, "last_beat_age_s": None},
                ))
                rec["shard"] = h.shard
                rec["live_epoch"] = h.live.ensemble.epoch
                rec["staged_epoch"] = (None if h.staged is None
                                       else h.staged.ensemble.epoch)
                hosts[h.host_id] = rec
            quorum = {}
            for s in range(self._n_shards):
                owners = self._owners[s]
                quorum[s] = {
                    "owners": [h.host_id for h in owners],
                    "serveable": [h.host_id for h in owners
                                  if health.get(h.host_id, {}).get("state")
                                  != DEAD],
                    "staged": {h.host_id: h.staged.ensemble.epoch
                               for h in owners if h.staged is not None},
                }
            return {
                "epoch": self._epoch,
                "replicas": self.replicas,
                "n_shards": self._n_shards,
                "n_hosts": len(self.hosts),
                "commits": self.commits,
                "reshards": self.reshards,
                "reassignments": self.reassignments,
                "gather_failovers": self.gather_failovers,
                "adopt_errors": len(self.adopt_errors),
                "hosts": hosts,
                "quorum": quorum,
            }
