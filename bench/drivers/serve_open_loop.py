"""Traffic kind `serve_open_loop`: top-N requests at a fixed open-loop rate.

Set-up makes the configuration's retained draws on the device from the seed
(one jitted call), publishes them through a `PublicationChannel` and lets a
`RecommendFrontend` adopt them, then warms every batch shape the mix can
produce. The window replays a schedule drawn from the seed: arrivals at
`rate_per_s` (a Poisson process with the count fixed at rate x seconds),
users by Zipf popularity over a seeded permutation, and a fixed share of
cold-start requests whose rating counts are a fixed heavy-tailed set in
seeded order. One loop submits every request that is due (at most
`max_batch`) and flushes; a request's latency runs from its due time to the
end of the flush that answered it. After the window the float64 reference
scores a seeded sample of the answered requests, the largest cold-start
ones among them, and compares the served lists.

Traffic parameters (bench/traffic/<mix>.json): `rate_per_s`, `zipf_s`,
`cold_share`, `cold_ratings` (`median`, `sigma`, `min`, `max`), `topk`,
`max_batch`, `check_warm`, `check_cold`, `drain_seconds`, `trace_seconds`.
"""
from __future__ import annotations

import collections
import itertools
import statistics
import time
from dataclasses import dataclass

import numpy as np

import datagen
import reference as ref
import work
from harness import Compare


@dataclass
class Schedule:
    due: np.ndarray          # (R,) seconds after the window opens
    user: np.ndarray         # (R,) user id, -1 for a cold-start request
    cold_items: list         # per request: item ids (cold) or None
    cold_vals: list          # per request: ratings (cold) or None

    @property
    def n(self) -> int:
        return len(self.due)


def cold_sizes(n: int, spec: dict) -> np.ndarray:
    """A fixed heavy-tailed multiset: quantiles of a log-normal of the
    given median and sigma, rounded and clipped."""
    q = (np.arange(n) + 0.5) / max(n, 1)
    z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
    sizes = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(sizes), spec["min"], spec["max"]).astype(np.int64)


def cold_payload(rng, n_ratings: int, item_p: np.ndarray, v_true: np.ndarray,
                 gen: dict):
    """One new user's ratings: items by popularity, values from the
    configuration's ground-truth model."""
    items = rng.choice(len(item_p), n_ratings, replace=False, p=item_p)
    u = rng.normal(0.0, 1.0 / np.sqrt(v_true.shape[1]), v_true.shape[1])
    vals = v_true[items] @ u + rng.normal(0.0, gen["noise"], n_ratings)
    if gen.get("clip") is not None:
        vals = np.clip(vals, *gen["clip"])
    return items.astype(np.int32), vals.astype(np.float32)


def make_schedule(cfg: dict, traffic: dict, seconds: float, rate: float,
                  rng: np.random.Generator, item_p, v_true) -> Schedule:
    n = max(1, int(round(rate * seconds)))
    due = np.sort(rng.uniform(0.0, seconds, n))
    n_users = int(cfg["n_users"])
    perm = rng.permutation(n_users)
    zipf = datagen.power_law(n_users, float(traffic["zipf_s"]))
    users = perm[np.minimum(np.searchsorted(np.cumsum(zipf), rng.random(n)),
                            n_users - 1)]
    n_cold = int(round(float(traffic["cold_share"]) * n))
    cold_at = rng.choice(n, n_cold, replace=False)
    users[cold_at] = -1
    sizes = rng.permutation(cold_sizes(n_cold, traffic["cold_ratings"]))
    items: list = [None] * n
    vals: list = [None] * n
    for i, size in zip(sorted(cold_at), sizes):
        items[i], vals[i] = cold_payload(rng, int(size), item_p, v_true,
                                         cfg["generator"])
    return Schedule(due, users.astype(np.int64), items, vals)


def make_draws(cfg: dict, seed: int):
    """The retained draws (S, M, K) / (S, N, K) and per-draw hypers, made on
    the device in one jitted call from the seed, in float32."""
    import jax
    import jax.numpy as jnp

    s, m, n, k = (int(cfg["serving"]["draws"]), int(cfg["n_users"]),
                  int(cfg["n_items"]), int(cfg["k"]))
    scale = float(cfg["serving"]["draw_scale"])

    @jax.jit
    def draws(key):
        ku, kv, km = jax.random.split(key, 3)
        u = scale * jax.random.normal(ku, (s, m, k), jnp.float32)
        v = scale * jax.random.normal(kv, (s, n, k), jnp.float32)
        mu = 0.1 * scale * jax.random.normal(km, (s, k), jnp.float32)
        lam = jnp.broadcast_to(jnp.eye(k, dtype=jnp.float32) / scale ** 2, (s, k, k))
        return u, v, mu, lam

    return draws(jax.random.PRNGKey(seed))


class Server:
    """The system under test and what the harness keeps beside it."""

    def __init__(self, ctx, cfg: dict, traffic: dict, rng: np.random.Generator,
                 draw_seed: int):
        import jax
        from repro.serve import RecommendFrontend
        from repro.serve.publish import PublicationChannel

        rec = ctx.rec
        self.cfg, self.traffic, self.rec = cfg, traffic, rec
        self.topk = int(traffic["topk"])
        self.max_batch = int(traffic["max_batch"])
        gm, alpha = float(cfg["serving"]["global_mean"]), float(cfg["alpha"])
        with rec.span("draws"):
            u, v, mu, lam = jax.block_until_ready(make_draws(cfg, draw_seed))
        with rec.span("adopt"):
            channel = PublicationChannel(window=u.shape[0])
            for s in range(u.shape[0]):
                channel.publish(s + 1, {
                    "u": u[s], "v": v[s], "hyper_u_mu": mu[s], "hyper_u_lam": lam[s],
                    "hyper_v_mu": mu[s], "hyper_v_lam": lam[s],
                    "global_mean": np.float32(gm), "alpha": np.float32(alpha)})
            self.fe = RecommendFrontend(channel=channel, subscribe=False,
                                        max_batch=self.max_batch)
            channel.close()
        self.draws = (u, v, mu, lam)
        self.gm, self.alpha = gm, alpha
        self.n_items = int(cfg["n_items"])
        g = dict(cfg["generator"])
        with rec.span("catalogue"):
            drng = np.random.default_rng(rng.integers(2 ** 63))
            self.v_true = drng.normal(0.0, 1.0 / np.sqrt(g["k_true"]),
                                      (self.n_items, int(g["k_true"])))
            self.item_p = datagen.power_law(self.n_items, float(g["item_exponent"]))

    # -- one batch ------------------------------------------------------
    def serve(self, sched: Schedule, idx: list[int]):
        """Submit requests `idx` of the schedule and flush: the answers in
        request order, and the flush's span."""
        tickets = {}
        n_cold = 0
        for i in idx:
            if sched.user[i] >= 0:
                t = self.fe.submit(int(sched.user[i]), topk=self.topk)
            else:
                t = self.fe.submit_ratings(sched.cold_items[i], sched.cold_vals[i],
                                           topk=self.topk)
                n_cold += 1
            tickets[t] = i
        with self.rec.span("flush", cold=n_cold, warm=len(idx) - n_cold):
            results = self.fe.flush()
        return [(tickets[r.ticket], r) for r in results], n_cold

    # -- warm-up --------------------------------------------------------
    def warm_up(self, rng: np.random.Generator) -> None:
        """Every batch shape the mix produces with at most `cold_cover`
        cold-start requests in a batch: warm batches of every size; one cold
        batch per fold-in plan schema (the bucket widths present and each
        bucket's row count, both as the plan cache quantizes them); and cold
        batches of every size with every candidate width (top-k plus the
        batch's largest rating count, to a power of two)."""
        from repro.serve.foldin import FoldInPlanCache

        n_users = int(self.cfg["n_users"])
        q = self.fe.foldin_cache.quantum
        widths = self.fe.foldin_cache.widths
        cover = int(self.traffic["cold_cover"])
        cmax = int(self.traffic["cold_ratings"]["max"])
        lows = [1] + [w + 1 for w in widths[:-1]]
        ranges = [(lo, min(w, cmax)) for lo, w in zip(lows, widths) if lo <= cmax]

        for w in range(1, self.max_batch + 1):
            users = rng.integers(0, n_users, w)
            self.serve(Schedule(np.zeros(w), users, [None] * w, [None] * w),
                       list(range(w)))
        for r in range(1, len(ranges) + 1):
            for subset in itertools.combinations(range(len(ranges)), r):
                # one request per bucket; q + 1 spread so no bucket passes q
                # rows; then each bucket in turn past q rows
                layouts = [{i: 1 for i in subset}]
                spread = {i: (q + 1) // r + (j < (q + 1) % r)
                          for j, i in enumerate(subset)}
                if max(spread.values()) <= q:
                    layouts.append(spread)
                layouts += [{i: q + 1 if i == wide else 1 for i in subset}
                            for wide in subset]
                for rows in layouts:
                    if sum(rows.values()) <= cover:
                        self._serve_cold(rng, [
                            rng.integers(ranges[i][0], ranges[i][1] + 1)
                            for i, n in rows.items() for _ in range(n)])
        fetches = sorted({FoldInPlanCache._quantize(self.topk + s, 1)
                          for s in range(1, cmax + 1)})
        for c in range(1, cover + 1):
            for f in fetches:
                # the largest request sets the candidate width
                top = min(max(f // 2 - self.topk + 1, 1), cmax)
                self._serve_cold(rng, [1] * (c - 1) + [top])

    def _serve_cold(self, rng, sizes) -> None:
        """One batch of cold-start requests with these rating counts."""
        items, vals = zip(*(cold_payload(rng, int(sz), self.item_p, self.v_true,
                                         self.cfg["generator"]) for sz in sizes))
        c = len(sizes)
        self.serve(Schedule(np.zeros(c), np.full(c, -1), list(items), list(vals)),
                   list(range(c)))


def run(ctx):
    cfg, traffic, rec = ctx.cell.config, ctx.cell.traffic, ctx.rec
    ss = np.random.SeedSequence([ctx.seed, 3]).generate_state(4)
    rng = np.random.default_rng(ss[:2])
    server = Server(ctx, cfg, traffic, rng, draw_seed=int(ss[2] & 0x7FFFFFFF))
    seconds = ctx.window_seconds(traffic)
    rate = float(traffic["rate_per_s"])
    with rec.span("schedule"):
        sched = make_schedule(cfg, traffic, seconds, rate, rng,
                              server.item_p, server.v_true)
    with rec.span("warm_up"):
        server.warm_up(np.random.default_rng(ss[3]))
    ctx.setup_done()

    lat, answers, lateness, topn_calls, flops = open_loop(ctx, server, sched)
    ctx.read_device()
    check_idx = sample_checks(sched, traffic, np.random.default_rng([ctx.seed, 4]))
    host = host_copies(server, sched, check_idx)
    server.fe.close()
    del server
    failed = int(np.isinf(lat).sum())
    program, control = check(host, sched, check_idx, answers, ctx.limits,
                             int(traffic["topk"]), control=ctx.control)
    compares = control or program
    ctx.layer.update(served=sched.n - failed, topn_calls=topn_calls,
                     served_flops=flops)
    lat_ms = np.nan_to_num(1e3 * lat, posinf=1e30)   # unanswered: past every limit
    return {
        "attempted": sched.n, "failed": failed, "compares": compares,
        "metrics": {"serve_p99_ms": float(np.percentile(lat_ms, 99)),
                    "serve_p50_ms": float(np.percentile(lat_ms, 50))},
        "notes": [f"requests {sched.n} at {rate} /s over {seconds} s, "
                  f"{int((sched.user < 0).sum())} cold-start; failed {failed}",
                  "generator lateness (wake-up after an idle wait, ms): "
                  + (f"p50 {1e3 * statistics.median(lateness):.3f}, "
                     f"max {1e3 * max(lateness):.3f}" if lateness else "none")]
        + ([f"program reads {c.name} = {c.value!r}" for c in program] if control else []),
    }


def open_loop(ctx, server: Server, sched: Schedule):
    """Serve the schedule; latency per request from its due time (inf for
    one never answered before the drain deadline)."""
    traffic = server.traffic
    lat = np.full(sched.n, np.inf)
    answers = {}
    lateness: list[float] = []
    topn_calls: list[tuple[int, int, int]] = []
    flops = 0.0
    s, k = (int(server.cfg["serving"]["draws"]), int(server.cfg["k"]))
    pending: collections.deque[int] = collections.deque()
    nxt = 0
    deadline = float(traffic["drain_seconds"]) + sched.due[-1]
    with ctx.window() as win:
        t0 = win.t0
        while True:
            now = time.perf_counter() - t0
            while nxt < sched.n and sched.due[nxt] <= now:
                pending.append(nxt)
                nxt += 1
            if pending:
                idx = [pending.popleft()
                       for _ in range(min(server.max_batch, len(pending)))]
                out, n_cold = server.serve(sched, idx)
                done = time.perf_counter() - t0
                for i, r in out:
                    lat[i] = done - sched.due[i]
                    answers[i] = (r.items, r.scores)
                n_warm = len(idx) - n_cold
                for b in (n_warm, n_cold):
                    if b:
                        topn_calls.append((b, s * k, server.n_items))
                        flops += work.topn_flops(b, s * k, server.n_items)
                if n_cold:
                    nr = sum(len(sched.cold_items[i]) for i in idx if sched.user[i] < 0)
                    flops += work.foldin_flops(nr, n_cold, s, k)
            elif nxt < sched.n:
                time.sleep(max(0.0, sched.due[nxt] - now))
                lateness.append(time.perf_counter() - t0 - sched.due[nxt])
            else:
                break
            if now > deadline:
                break
    return lat, answers, lateness, topn_calls, flops


def sample_checks(sched: Schedule, traffic: dict, rng) -> np.ndarray:
    """A seeded sample of requests to check: warm ones at random, and the
    cold-start ones with the most ratings plus a random share of the rest."""
    warm = np.flatnonzero(sched.user >= 0)
    cold = np.flatnonzero(sched.user < 0)
    n_w = min(len(warm), int(traffic["check_warm"]))
    n_c = min(len(cold), int(traffic["check_cold"]))
    by_size = cold[np.argsort([-len(sched.cold_items[i]) for i in cold], kind="stable")]
    heavy = by_size[: n_c // 2]
    rest = np.setdiff1d(cold, heavy)
    picks = [rng.choice(warm, n_w, replace=False), heavy,
             rng.choice(rest, min(len(rest), n_c - len(heavy)), replace=False)]
    return np.sort(np.concatenate(picks)).astype(np.int64)


def host_copies(server: Server, sched: Schedule, idx: np.ndarray) -> dict:
    """What the reference needs, copied off the device before it is freed:
    the draws of the checked users, all item draws, the user hypers."""
    u, v, mu, lam = server.draws
    users = np.unique(sched.user[idx][sched.user[idx] >= 0])
    return {
        "users": users,
        "u": np.asarray(u[:, users], np.float64),                 # (S, U, K)
        "v_flat": ref.flat_draws(np.asarray(v, np.float64)),      # (N, S*K)
        "v": np.asarray(v, np.float64),                           # (S, N, K)
        "mu": np.asarray(mu, np.float64), "lam": np.asarray(lam, np.float64),
        "gm": server.gm, "alpha": server.alpha,
    }


def check(host: dict, sched: Schedule, idx: np.ndarray, answers: dict,
          limits: dict, topk: int, control: bool = False
          ) -> tuple[list[Compare], list[Compare] | None]:
    """Reference scores of each checked request; the served list's
    `topn_gap` and the served scores' `score_gap` against them. With
    `control`, also the gaps of the float8 control's lists."""
    gaps = {"program": [0.0, 0.0], "control": [0.0, 0.0]}
    pos = {int(x): j for j, x in enumerate(host["users"])}
    for i in idx:
        if i not in answers:
            continue   # never answered: counted in `failed`
        if sched.user[i] >= 0:
            u_draws = host["u"][:, pos[int(sched.user[i])]][None]      # (1, S, K)
            excluded = np.zeros(0, np.int64)
        else:
            items, vals = sched.cold_items[i], sched.cold_vals[i]
            centered = vals.astype(np.float64) - host["gm"]
            u_draws = ref.foldin_means(host["v"], host["lam"], host["mu"],
                                       host["alpha"], items, centered)[None]
            excluded = items
        want = ref.scores(u_draws, host["v_flat"], host["gm"])[0]
        outputs = {"program": answers[i]}
        if control:
            if sched.user[i] < 0:
                u_draws = ref.foldin_means(host["v"], host["lam"], host["mu"],
                                           host["alpha"], items, centered,
                                           control=True)[None]
            mine = ref.scores(u_draws, host["v_flat"], host["gm"], control=True)[0]
            mine[excluded] = -np.inf
            top = np.argsort(-mine, kind="stable")[:topk]
            outputs["control"] = (top, mine[top])
        for who, (got_items, got_scores) in outputs.items():
            got_items = np.asarray(got_items)
            g = gaps[who]
            g[0] = max(g[0], ref.topn_gap(got_items, want, excluded, topk))
            ok = got_items >= 0
            if ok.any():
                g[1] = max(g[1], float(np.max(np.abs(
                    np.asarray(got_scores, np.float64)[ok] - want[got_items[ok]]))))

    def compares(g):
        return [Compare("topn_gap", g[0], limits["topn_gap"]),
                Compare("score_gap", g[1], limits["score_gap"])]

    return compares(gaps["program"]), compares(gaps["control"]) if control else None
