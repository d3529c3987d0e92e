"""Distributed BPMF + grad compression. Multi-device tests run in
subprocesses (jax pins the device count at first init)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_sub(code: str, devices: int = 8) -> str:
    prog = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        f"import sys\nsys.path.insert(0, {SRC!r})\n" + textwrap.dedent(code)
    )
    res = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True, timeout=600
    )
    assert res.returncode == 0, res.stderr[-4000:]
    return res.stdout


@pytest.mark.slow
def test_ring_equals_allgather_and_converges():
    out = run_sub("""
    import numpy as np, json
    from repro.data import synthetic_lowrank, train_test_split
    from repro.core.distributed import DistributedBPMF

    ratings, _, _ = synthetic_lowrank(300, 200, k_true=8, nnz=9000, noise=0.3, seed=3)
    train, test = train_test_split(ratings, 0.1, seed=4)
    ring = DistributedBPMF(train, test, k=16, alpha=11.0, mode="ring")
    s1 = ring.run(10, seed=7)
    sync = DistributedBPMF(train, test, k=16, alpha=11.0, mode="allgather")
    s2 = sync.run(10, seed=7)
    u1, v1 = ring.gather_factors(s1)
    u2, v2 = sync.gather_factors(s2)
    np.testing.assert_allclose(u1, u2, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(v1, v2, rtol=2e-3, atol=2e-3)
    print(json.dumps({"ring": ring.rmse(s1), "sync": sync.rmse(s2)}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert abs(res["ring"] - res["sync"]) < 1e-4
    assert res["ring"] < 0.7


@pytest.mark.slow
def test_distributed_matches_partition_invariants():
    out = run_sub("""
    import numpy as np, json
    from repro.data import synthetic_lowrank
    from repro.core.partition import partition_entities, build_grid_plan

    ratings, _, _ = synthetic_lowrank(200, 150, k_true=4, nnz=4000, noise=0.3, seed=5)
    up = partition_entities(ratings.degrees(0), 8)
    vp = partition_entities(ratings.degrees(1), 8)
    # every entity appears exactly once
    ids = up.ids[up.ids >= 0]
    assert sorted(ids.tolist()) == list(range(200))
    plan = build_grid_plan(ratings, up, vp, width=16)
    assert plan.mask.sum() == ratings.nnz
    # balance: LPT keeps per-shard cost within 30% of the mean
    from repro.core.buckets import workload_model
    cost = workload_model(ratings.degrees(0))
    loads = np.zeros(8)
    np.add.at(loads, up.shard, cost)
    assert loads.max() / loads.mean() < 1.3
    print(json.dumps(plan.stats()))
    """)
    stats = json.loads(out.strip().splitlines()[-1])
    assert stats["lane_efficiency"] > 0.03


def test_partition_heap_lpt_matches_argmin_reference():
    """Bit-equality regression for the O(N log P) heap rewrite of
    partition_entities: the heap pops the same (load, shard) minimum the
    old O(N*P) np.argmin scan found (argmin breaks load ties by lowest
    shard id; the (load, p) tuple order does the same), so assignments —
    not just balance — must be identical."""
    from repro.core.buckets import workload_model
    from repro.core.partition import partition_entities

    def argmin_reference(degrees, n_shards):
        cost = workload_model(degrees)
        order = np.argsort(-cost, kind="stable")
        loads = np.zeros(n_shards)
        shard = np.zeros(len(degrees), np.int32)
        for e in order:
            p = int(np.argmin(loads))
            shard[e] = p
            loads[p] += cost[e]
        return shard

    rng = np.random.default_rng(11)
    for n, p in [(1, 1), (7, 8), (200, 3), (500, 8), (333, 5)]:
        degrees = rng.zipf(1.7, size=n).astype(np.int64)
        degrees[rng.random(n) < 0.2] = 0  # ties: zero-degree entities
        got = partition_entities(degrees, p)
        np.testing.assert_array_equal(got.shard, argmin_reference(degrees, p))


def test_grid_plan_auto_width_no_worse_than_fixed():
    """width="auto" must keep the plan lossless and never pick a lane
    layout worse than the fixed default on a skewed profile."""
    from repro.data import chembl_like
    from repro.core.partition import partition_entities, build_grid_plan

    ratings, _, _ = chembl_like(scale=0.002, seed=0)
    up = partition_entities(ratings.degrees(0), 4)
    vp = partition_entities(ratings.degrees(1), 4)
    auto = build_grid_plan(ratings, up, vp, width="auto")
    fixed = build_grid_plan(ratings, up, vp, width=32)
    assert auto.mask.sum() == ratings.nnz
    assert auto.stats()["lane_efficiency"] >= fixed.stats()["lane_efficiency"]


def test_distributed_rejects_unknown_mode():
    from repro.data import synthetic_lowrank, train_test_split
    from repro.core.distributed import DistributedBPMF

    ratings, _, _ = synthetic_lowrank(40, 30, k_true=2, nnz=300, noise=0.3, seed=0)
    train, test = train_test_split(ratings, 0.1, seed=1)
    with pytest.raises(ValueError, match="async"):
        DistributedBPMF(train, test, k=4, mode="gossip")


@pytest.mark.slow
def test_async_first_sweep_v_bitwise_and_rmse_parity():
    """The stale-by-one async sweep is bit-comparable at burn-in: sweep 1
    consumes fresh u for the v-phase (staleness only enters via u reading
    last sweep's v), so from equal init states async and ring must produce
    the SAME v draw bit-for-bit — and after burn-in both chains land on
    the same RMSE plateau."""
    out = run_sub("""
    import numpy as np, json
    from repro.data import synthetic_lowrank, train_test_split
    from repro.core.distributed import DistributedBPMF

    ratings, _, _ = synthetic_lowrank(300, 200, k_true=8, nnz=9000, noise=0.3, seed=3)
    train, test = train_test_split(ratings, 0.1, seed=4)
    ring = DistributedBPMF(train, test, k=16, alpha=11.0, mode="ring")
    asyn = DistributedBPMF(train, test, k=16, alpha=11.0, mode="async")
    s1 = ring.sweep(ring.init(7))
    s2 = asyn.sweep(asyn.init(7))
    _, v1 = ring.gather_factors(s1)
    _, v2 = asyn.gather_factors(s2, coupled=False)   # fresh v, not the eval pair
    assert np.array_equal(np.asarray(v1), np.asarray(v2)), "first-sweep v diverged"
    # rmse() pairs u with v_eval (the v it conditioned on): the
    # same-index (u, v) pair mixes the two interleaved chains and
    # plateaus visibly high — pin that the coupled pair does not
    for _ in range(19):
        s1 = ring.sweep(s1)
        s2 = asyn.sweep(s2)
    print(json.dumps({"ring": ring.rmse(s1), "async": asyn.rmse(s2)}))
    """, devices=4)
    res = json.loads(out.strip().splitlines()[-1])
    assert abs(res["ring"] - res["async"]) < 0.05
    assert res["async"] < 0.7


@pytest.mark.slow
def test_int8_compressed_psum_error_feedback():
    out = run_sub("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.optim.compress import compress_init, compressed_psum, CompressState

    mesh = jax.make_mesh((8,), ("pod",))
    g_global = np.random.default_rng(0).normal(size=(8, 64, 32)).astype(np.float32)

    def f(g, err):
        out, st = compressed_psum({"w": g[0]}, CompressState(error={"w": err[0]}), "pod")
        return out["w"][None], st.error["w"][None]

    m = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                  out_specs=(P("pod"), P("pod")), check_vma=False)
    errs = np.zeros_like(g_global)
    # accumulate over rounds: error feedback keeps the running sum unbiased
    total_true = g_global.sum(0)
    out, errs2 = jax.jit(m)(jnp.asarray(g_global), jnp.asarray(errs))
    got = np.asarray(out)[0]
    rel = np.abs(got - total_true).max() / np.abs(total_true).max()
    assert rel < 0.05, rel
    # second round with carried error: residual shrinks the bias
    out2, _ = jax.jit(m)(jnp.asarray(g_global), errs2)
    print("ok", rel)
    """)
    assert "ok" in out


def test_compress_roundtrip_single_device():
    import jax.numpy as jnp
    from repro.optim.compress import int8_compress, int8_decompress

    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(128, 64)), jnp.float32)
    e = jnp.zeros_like(g)
    q, scale, new_e = int8_compress(g, e)
    deq = int8_decompress(q, scale)
    np.testing.assert_allclose(np.asarray(deq + new_e), np.asarray(g), atol=1e-5)
    assert np.abs(np.asarray(new_e)).max() <= float(scale) / 2 + 1e-6


@pytest.mark.slow
def test_moe_ep_shard_map_matches_grouped():
    """The shard_map EP dispatch (§Perf iteration 5) must be numerically
    faithful to the single-device grouped dispatch."""
    out = run_sub("""
    import numpy as np, dataclasses
    import jax, jax.numpy as jnp
    from repro.models.layers import ModelConfig, init_moe, moe_block, active_mesh

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32, n_heads=4,
                      n_kv_heads=2, d_ff=0, vocab_size=64, n_experts=8,
                      n_experts_active=2, moe_d_ff=16, capacity_factor=8.0,
                      dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 32), jnp.float32)
    o_ref, a_ref = moe_block(params, x, dataclasses.replace(cfg, moe_group_dispatch=True))
    cfg_ep = dataclasses.replace(cfg, moe_ep_shard_map=True)
    with mesh, active_mesh(mesh):
        o_ep, a_ep = jax.jit(lambda p, xx: moe_block(p, xx, cfg_ep))(params, x)
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_ep), rtol=3e-3, atol=3e-3)
    # aux: local-mean estimator vs global — close but not identical
    np.testing.assert_allclose(float(a_ref), float(a_ep), rtol=5e-2)
    print("ep ok")
    """)
    assert "ep ok" in out


def test_finish_phase_chunked_solve_matches_whole(monkeypatch):
    """A shard's items are solved in fixed-size chunks (the last clamped to
    end at n_loc, re-solving some items) so a full-size shard's Cholesky
    factors never coexist with its statistics; the draws must not depend
    on the chunking, and pad slots (id -1) stay zero."""
    import jax
    import jax.numpy as jnp

    import repro.core.gibbs as gibbs
    from repro.core.distributed import _finish_phase
    from repro.core.hyper import init_hyper

    rng = np.random.default_rng(0)
    n, k = 37, 8
    a = rng.normal(size=(n, k, 3 * k)).astype(np.float32)
    prec = jnp.asarray(a @ a.transpose(0, 2, 1))
    rhs = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    ids = jnp.asarray(np.where(np.arange(n) < 33, np.arange(n), -1), jnp.int32)

    def finish():  # the same key both times: the draws must match exactly
        return np.asarray(_finish_phase(jax.random.PRNGKey(3), prec, rhs, ids,
                                        init_hyper(k), 2.0))

    whole = finish()
    monkeypatch.setattr(gibbs, "CHUNK_BYTES", 4 * k * k * 8)   # 8-item chunks
    assert gibbs.chunk_rows(k, k) == 8
    chunked = finish()
    np.testing.assert_allclose(chunked, whole, rtol=1e-5, atol=1e-5)
    assert np.isfinite(whole).all() and not whole[33:].any()
