"""Serving launcher: LM decode serving and BPMF recommendation serving.

LM mode (batched prefill + decode for any architecture):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --reduced \
        --batch 4 --prompt-len 64 --max-new 32

BPMF mode (posterior-predictive top-N from retained Gibbs samples):

    PYTHONPATH=src python -m repro.launch.serve --bpmf --samples /path/to/dir \
        --requests 256 --max-batch 32 --topk 10

BPMF serving drives the request-batching frontend (repro.serve): requests
are micro-batched, scored by the Pallas streaming top-k kernel against the
item-factor cache (keyed by sample epoch, sharded over the host mesh), and
the run reports queries/sec plus p50/p99 latency. Without --samples it
trains a small synthetic model first so the command works standalone.

Co-train mode (train-while-serve, the paper's async overlap applied to the
train -> serve hand-off):

    PYTHONPATH=src python -m repro.launch.serve --bpmf --co-train \
        --sweeps 24 --topk 10

runs the GibbsSampler and the RecommendFrontend in one process, connected
by a serve.publish.PublicationChannel: each retained post-burn-in draw is
pushed to the live frontend (no disk poll), which swaps its ensemble
atomically — reusing the compiled top-N kernel whenever (S, N, K) shapes
are unchanged — while request traffic keeps flowing. Reports publish
-> first-fresh-recommendation latency alongside the usual qps numbers.
The same driver backs `python -m repro.launch.train --bpmf --co-serve`.

Multi-host tier mode (the pod-scale scatter/gather layer, simulated):

    PYTHONPATH=src python -m repro.launch.serve --bpmf --hosts 2 \
        --requests 256 --topk 10

simulates N serving hosts without hardware: the process re-execs itself
under `XLA_FLAGS=--xla_force_host_platform_device_count=N` when fewer
devices exist, pins one ShardHost (resident V' item shard + routed U
replica, serve/cluster.py) per device with its own channel-subscriber
thread, and drives traffic while a publisher thread pushes fresh epochs
mid-stream. Verifies the tier serves top-N bit-identical to the
single-host TopNRecommender on the same ensemble and that served epochs
stay monotone across publishes (the quorum epoch barrier), then reports
qps, commit count, and publish -> all-shards-fresh latency. Add
`--replicas 2` to give every item shard two owner hosts: the run then
also kills one host and verifies serving stays bit-identical and every
publish still commits (failure semantics in docs/serving.md §6).
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced as reduce_cfg
from repro.models import build_model


def build_serving(cfg, max_new: int):
    model = build_model(cfg)
    prefill = jax.jit(lambda p, b: model.prefill_fn(p, b, headroom=max_new + 8))
    decode = jax.jit(model.decode_fn)
    return model, prefill, decode


def train_demo_samples(root: str, *, seed: int = 0) -> "SparseRatings":
    """Train a small synthetic BPMF model and retain samples under `root`.

    Returns the training ratings (the serve-side seen-item filter).
    """
    from repro.checkpoint import SampleStore
    from repro.core import GibbsSampler
    from repro.data import movielens_like, train_test_split

    ratings, _, _ = movielens_like(scale=0.002, seed=seed)
    train, test = train_test_split(ratings, 0.1, seed=seed + 1)
    sampler = GibbsSampler(train, test, k=16, alpha=4.0, burn_in=6,
                           widths=(8, 32, 128))
    store = SampleStore(root, keep=8)
    sampler.run(14, seed=seed, store=store)
    return train


def run_train_and_serve(
    *,
    scale: float = 0.01,
    sweeps: int = 60,
    k: int = 16,
    burn_in: int = 6,
    window: int = 4,
    samples: str | None = None,
    topk: int = 10,
    max_batch: int = 8,
    seed: int = 0,
    verbose: bool = True,
) -> dict:
    """Train and serve in one process with overlapped sample publication.

    A trainer thread runs the Gibbs chain, publishing every retained draw
    into a PublicationChannel (and, when `samples` is given, also writing it
    durably through the SampleStore — push and durable paths side by side).
    The main thread serves continuous top-N traffic the whole time; the
    frontend's subscriber thread adopts each publish as it lands. Returns a
    metrics dict (also printed): requests served, draws published, ensemble
    swaps, rebinds (swaps that reused the compiled top-N executables), and
    publish -> first-fresh-recommendation latency percentiles.
    """
    import threading

    from repro.checkpoint import SampleStore
    from repro.core import GibbsSampler
    from repro.data import movielens_like, train_test_split
    from repro.serve import PublicationChannel, RecommendFrontend

    if sweeps <= burn_in:
        raise ValueError(
            f"need sweeps > burn_in to publish anything ({sweeps} <= {burn_in})"
        )
    ratings, _, _ = movielens_like(scale=scale, seed=seed)
    train, test = train_test_split(ratings, 0.1, seed=seed + 1)
    sampler = GibbsSampler(train, test, k=k, alpha=4.0, burn_in=burn_in,
                           widths=(8, 32, 128))
    channel = PublicationChannel(window=window)
    store = SampleStore(samples, keep=window) if samples else None
    if verbose:
        print(f"co-train: {train.shape[0]} x {train.shape[1]} ratings matrix, "
              f"{sweeps} sweeps (burn-in {burn_in}), k={k}, window={window}"
              + (f", durable store {samples}" if samples else ""))

    trainer_error: list[BaseException] = []

    def train_loop():
        try:
            sampler.run(sweeps, seed=seed, store=store, publish=channel)
        except BaseException as e:  # noqa: BLE001 — surfaced after join
            trainer_error.append(e)
        finally:
            channel.close()  # always unblocks the serving loop's drain

    trainer = threading.Thread(target=train_loop, name="gibbs-trainer")
    trainer.start()
    try:
        fe = RecommendFrontend(channel=channel, seen=train, max_batch=max_batch)
    except Exception:
        trainer.join()  # surface the root cause, not the closed channel
        if trainer_error:
            raise trainer_error[0]
        raise

    rng = np.random.default_rng(seed)
    served = 0
    fresh_lat: list[float] = []        # publish -> first fresh recommendation
    seen_epochs: list[int] = []
    t0 = time.perf_counter()
    while True:
        drained = channel.closed and fe.epoch >= (channel.epoch or 0)
        for u in rng.integers(0, train.shape[0], max_batch):
            fe.submit(int(u), topk=topk)
        results = fe.flush()
        served += len(results)
        t_now = time.perf_counter()
        for r in results:
            if not seen_epochs or r.epoch > seen_epochs[-1]:
                seen_epochs.append(r.epoch)
                t_pub = channel.publish_time(r.epoch)
                if t_pub is not None and len(seen_epochs) > 1:
                    fresh_lat.append(t_now - t_pub)
        if drained:
            break
    dt = time.perf_counter() - t0
    trainer.join()
    fe.close()
    if trainer_error:
        raise trainer_error[0]

    lat = fe.latency_percentiles()
    metrics = {
        "served": served,
        "qps": served / dt,
        "published": channel.seq,
        "epochs_served": len(seen_epochs),
        "swaps": fe.swaps,
        "rebinds": fe.rebinds,
        "request_p50_ms": lat["p50"] * 1e3,
        "request_p99_ms": lat["p99"] * 1e3,
        "fresh_p50_ms": float(np.median(fresh_lat) * 1e3) if fresh_lat else float("nan"),
        "fresh_max_ms": float(np.max(fresh_lat) * 1e3) if fresh_lat else float("nan"),
    }
    if verbose:
        print(f"served {served} requests in {dt:.2f}s -> {metrics['qps']:,.0f} qps "
              f"while {channel.seq} draws were published; served "
              f"{len(seen_epochs)} distinct epochs "
              f"({fe.swaps} swaps, {fe.rebinds} rebinds without recompile)")
        print(f"request p50 {metrics['request_p50_ms']:.2f} ms  "
              f"p99 {metrics['request_p99_ms']:.2f} ms;  publish->fresh "
              f"p50 {metrics['fresh_p50_ms']:.1f} ms  "
              f"max {metrics['fresh_max_ms']:.1f} ms")
    return metrics


def _ensure_host_devices(n_hosts: int) -> None:
    """Re-exec under XLA_FLAGS=--xla_force_host_platform_device_count=N
    when the CPU backend has fewer devices than simulated hosts requested.
    Device count is fixed once the backend initialises, so this must
    replace the process; the guard env var prevents an exec loop. Forced
    host devices exist only on the CPU: on an accelerator backend, fewer
    devices than hosts is an error."""
    n_dev = len(jax.devices())
    if n_dev >= n_hosts:
        return
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"--hosts {n_hosts} needs {n_hosts} devices but only {n_dev} "
            f"{backend} device(s) exist"
        )
    if os.environ.get("_REPRO_SERVE_HOSTS_REEXEC") == "1":
        raise RuntimeError(
            f"--hosts {n_hosts} needs {n_hosts} devices but only "
            f"{n_dev} exist even after forcing host devices"
        )
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_hosts}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["_REPRO_SERVE_HOSTS_REEXEC"] = "1"
    os.execvpe(sys.executable,
               [sys.executable, "-m", "repro.launch.serve", *sys.argv[1:]],
               env)


def run_cluster(
    *,
    hosts: int = 2,
    replicas: int = 1,
    samples: str | None = None,
    requests: int = 256,
    topk: int = 10,
    max_batch: int = 8,
    publishes: int = 4,
    seed: int = 0,
    verbose: bool = True,
) -> dict:
    """Drive the multi-host serving tier against live traffic + publishes.

    Builds an N-host ClusterCoordinator (one simulated host per device)
    and a single-host TopNRecommender over the same ensemble, checks the
    tier's top-N is bit-identical, then serves `requests` warm-user batches
    while a publisher thread pushes `publishes` fresh same-shape epochs —
    asserting served epochs never regress (the quorum epoch barrier).

    With --replicas R > 1 each item shard gets R owner hosts, and the run
    additionally kills one host before the publish stream: serving must
    stay bit-identical (requests route to the surviving replica) and every
    publish must still commit (the dead host is excluded from the quorum).
    Returns a metrics dict (also printed).
    """
    import threading

    import numpy as np

    from repro.checkpoint import SampleStore
    from repro.serve import (
        ClusterCoordinator,
        PosteriorEnsemble,
        PublicationChannel,
        TopNRecommender,
    )

    root = samples
    if root is None:
        root = tempfile.mkdtemp(prefix="bpmf_samples_")
        if verbose:
            print(f"no --samples given; training a demo model into {root}")
        train_demo_samples(root, seed=seed)
    ensemble = PosteriorEnsemble.load(root)
    devices = jax.devices()[:hosts]
    if verbose:
        print(f"cluster: {hosts} simulated hosts over {[str(d) for d in devices]}, "
              f"ensemble S={ensemble.n_samples} {ensemble.n_users}x"
              f"{ensemble.n_items} k={ensemble.k} epoch={ensemble.epoch}")

    single = TopNRecommender(ensemble)
    channel = PublicationChannel(window=ensemble.n_samples)
    for s in ensemble.samples:
        channel.publish(s.step, {
            "u": s.u, "v": s.v,
            "hyper_u_mu": s.hyper_u_mu, "hyper_u_lam": s.hyper_u_lam,
            "hyper_v_mu": s.hyper_v_mu, "hyper_v_lam": s.hyper_v_lam,
            "global_mean": np.float32(s.global_mean),
            "alpha": np.float32(s.alpha),
        })
    cluster = ClusterCoordinator(ensemble, devices=devices, channel=channel,
                                 replicas=replicas)

    # --- acceptance gate: the tier must match the single host bit-for-bit
    rng = np.random.default_rng(seed)
    probe = rng.integers(0, ensemble.n_users, max_batch).astype(np.int32)
    v1, i1 = single.recommend(probe, topk)
    v2, i2 = cluster.recommend(probe, topk)
    identical = bool(np.array_equal(i1, i2) and np.array_equal(v1, v2))
    if not identical:
        raise AssertionError(
            f"cluster top-N diverged from single-host: items equal="
            f"{np.array_equal(i1, i2)} values equal={np.array_equal(v1, v2)}"
        )
    if verbose:
        print(f"parity: {hosts}-host tier bit-identical to single-host "
              f"TopNRecommender over {max_batch} probe users (topk={topk})")

    # --- degraded mode: kill one host, the tier must not notice
    if replicas > 1:
        cluster.health.kill(cluster.hosts[0].host_id)
        v3, i3 = cluster.recommend(probe, topk)
        if not (np.array_equal(i1, i3) and np.array_equal(v1, v3)):
            raise AssertionError(
                "degraded tier (1 host down) diverged from single-host"
            )
        if verbose:
            print(f"degraded parity: host 0 killed, replicas={replicas} — "
                  "still bit-identical; publishes must commit past the dead "
                  "host (quorum barrier)")

    # --- serve while a publisher pushes fresh epochs mid-stream
    base = ensemble.samples[-1]

    def publisher():
        p_rng = np.random.default_rng(seed + 1)
        for i in range(publishes):
            time.sleep(0.05)
            step = ensemble.epoch + 1 + i
            channel.publish(step, {
                "u": base.u + 0.01 * p_rng.normal(size=np.shape(base.u)).astype(np.float32),
                "v": base.v + 0.01 * p_rng.normal(size=np.shape(base.v)).astype(np.float32),
                "hyper_u_mu": base.hyper_u_mu, "hyper_u_lam": base.hyper_u_lam,
                "hyper_v_mu": base.hyper_v_mu, "hyper_v_lam": base.hyper_v_lam,
                "global_mean": np.float32(base.global_mean),
                "alpha": np.float32(base.alpha),
            })
        channel.close()

    pub = threading.Thread(target=publisher, name="cluster-publisher")
    pub.start()
    served = 0
    epochs_seen: list[int] = []
    t0 = time.perf_counter()
    deadline = t0 + 300.0  # a wedged barrier must fail loudly, not hang CI
    while True:
        if time.perf_counter() > deadline:
            raise TimeoutError(
                f"cluster stuck at epoch {cluster.epoch} < {channel.epoch}"
            )
        drained = channel.closed and cluster.epoch >= (channel.epoch or 0)
        users = rng.integers(0, ensemble.n_users, max_batch).astype(np.int32)
        epoch = cluster.epoch
        cluster.recommend(users, topk)
        served += len(users)
        if not epochs_seen or epoch != epochs_seen[-1]:
            epochs_seen.append(epoch)
        if drained and served >= requests:
            break
    dt = time.perf_counter() - t0
    pub.join()
    cluster.close()
    assert epochs_seen == sorted(epochs_seen), (
        f"served epochs regressed: {epochs_seen}"
    )

    fresh = cluster.freshness_percentiles()
    metrics = {
        "hosts": hosts,
        "replicas": replicas,
        "served": served,
        "qps": served / dt,
        "bit_identical": identical,
        "commits": cluster.commits,
        "reassignments": cluster.reassignments,
        "epochs_served": len(epochs_seen),
        "fresh_p50_ms": fresh["p50"] * 1e3,
        "fresh_max_ms": fresh["max"] * 1e3,
    }
    if verbose:
        print(f"served {served} requests in {dt:.2f}s -> {metrics['qps']:,.0f} qps "
              f"across {len(epochs_seen)} monotone epochs "
              f"({cluster.commits} barrier commits)")
        print(f"publish -> all-shards-fresh p50 {metrics['fresh_p50_ms']:.1f} ms  "
              f"max {metrics['fresh_max_ms']:.1f} ms")
    return metrics


def bpmf_main(args) -> None:
    from repro.launch.mesh import make_host_mesh
    from repro.serve import RecommendFrontend

    if args.co_train:
        run_train_and_serve(
            sweeps=args.sweeps, samples=args.samples, topk=args.topk,
            window=args.keep, max_batch=args.max_batch,
        )
        return

    seen = None
    root = args.samples
    if root is None:
        root = tempfile.mkdtemp(prefix="bpmf_samples_")
        print(f"no --samples given; training a demo model into {root}")
        seen = train_demo_samples(root)

    mesh = make_host_mesh()
    fe = RecommendFrontend(root, seen=seen, max_batch=args.max_batch, mesh=mesh)
    ens = fe.ensemble
    print(f"ensemble: {ens.n_samples} samples, {ens.n_users} users x "
          f"{ens.n_items} items, k={ens.k}, epoch={fe.epoch} "
          f"({len(mesh.devices.flatten())} device(s))")

    rng = np.random.default_rng(0)
    users = rng.integers(0, ens.n_users, args.requests)
    # warm the kernel cache at the *serving* batch shape (jit specialises on
    # the padded batch size, so a batch-of-1 warm-up would leave the first
    # timed flush paying compilation)
    for u in users[: args.max_batch]:
        fe.submit(int(u), topk=args.topk)
    fe.flush()
    fe.latencies_s.clear()
    t0 = time.perf_counter()
    served = 0
    for u in users:
        fe.submit(int(u), topk=args.topk)
        if fe.pending >= args.max_batch:
            served += len(fe.flush())
    served += len(fe.flush())
    dt = time.perf_counter() - t0
    lat = fe.latency_percentiles()
    print(f"served {served} requests in {dt:.3f}s -> {served/dt:,.0f} qps  "
          f"p50 {lat['p50']*1e3:.2f} ms  p99 {lat['p99']*1e3:.2f} ms")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--bpmf", action="store_true",
                    help="serve BPMF recommendations instead of an LM")
    ap.add_argument("--samples", default=None,
                    help="SampleStore directory of retained Gibbs draws")
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--co-train", action="store_true",
                    help="train and serve in one process; retained draws are "
                         "pushed to the live frontend (no disk poll)")
    ap.add_argument("--hosts", type=int, default=0,
                    help="serve through the multi-host tier with N hosts, one "
                         "per device (on the CPU backend, re-execs under "
                         "--xla_force_host_platform_device_count when needed)")
    ap.add_argument("--publishes", type=int, default=4,
                    help="--hosts mode: fresh epochs pushed mid-stream")
    ap.add_argument("--replicas", type=int, default=1,
                    help="--hosts mode: owners per item shard; with R > 1 "
                         "the run kills one host and verifies serving stays "
                         "bit-identical and publishes still commit")
    ap.add_argument("--sweeps", type=int, default=60,
                    help="co-train: total Gibbs sweeps")
    ap.add_argument("--keep", type=int, default=4,
                    help="co-train: publication window / ensemble size")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.bpmf and args.hosts > 0:
        _ensure_host_devices(args.hosts)
        run_cluster(
            hosts=args.hosts, replicas=args.replicas, samples=args.samples,
            requests=args.requests, topk=args.topk,
            max_batch=min(args.max_batch, 8), publishes=args.publishes,
        )
        return
    if args.bpmf:
        bpmf_main(args)
        return

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    model, prefill, decode = build_serving(cfg, args.max_new)
    params = model.init(jax.random.PRNGKey(0))

    key, k_tok, k_frames, k_patch = jax.random.split(jax.random.PRNGKey(1), 4)
    batch = {"tokens": jax.random.randint(
        k_tok, (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32)}
    if cfg.family == "audio":
        batch["frames"] = 0.1 * jax.random.normal(
            k_frames, (args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        batch["patch_embeds"] = 0.1 * jax.random.normal(
            k_patch, (args.batch, cfg.n_patches, cfg.d_model), jnp.float32)
        s_total = cfg.n_patches + args.prompt_len
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(s_total, dtype=jnp.int32), (args.batch, 3, s_total))

    t0 = time.time()
    out = prefill(params, batch)
    jax.block_until_ready(out["logits"])
    t_prefill = time.time() - t0

    cache = out["cache"]
    tok = jnp.argmax(out["logits"], -1)[:, None]
    pos0 = (cfg.n_patches if cfg.family == "vlm" else 0) + args.prompt_len
    toks = [tok]
    t0 = time.time()
    for t in range(args.max_new - 1):
        dbatch = {"tokens": tok}
        if cfg.family == "vlm":
            dbatch["positions"] = jnp.full((args.batch, 3, 1), pos0 + t, jnp.int32)
        cache, logits = decode(params, cache, dbatch)
        if args.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits / args.temperature)[:, None]
        else:
            tok = jnp.argmax(logits, -1)[:, None]
        toks.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    gen = np.asarray(jnp.concatenate(toks, axis=1))

    n_tok = args.batch * (args.max_new - 1)
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len}")
    print(f"prefill: {t_prefill*1e3:.0f} ms   decode: {n_tok/max(t_decode,1e-9):,.0f} tok/s")
    print("sample:", gen[0][:16], "...")


if __name__ == "__main__":
    main()
