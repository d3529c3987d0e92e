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
                                        init_hyper(k), 2.0,
                                        gibbs._sweep_solver("einsum")))

    whole = finish()
    monkeypatch.setattr(gibbs, "CHUNK_BYTES", 4 * k * k * 8)   # 8-item chunks
    assert gibbs.chunk_rows(k, k) == 8
    chunked = finish()
    np.testing.assert_allclose(chunked, whole, rtol=1e-5, atol=1e-5)
    assert np.isfinite(whole).all() and not whole[33:].any()


def test_finish_phase_kernel_route_matches_subst():
    """The distributed sweep solves through the single-chip sweep's solver
    choice: `_sweep_solver`'s kernel route (the Pallas kernel, interpreted
    off the TPU) and its substitution route give the same draws on one
    chunk, pad slots zero."""
    import jax
    import jax.numpy as jnp

    from repro.core.distributed import _finish_phase
    from repro.core.gibbs import _sweep_solver
    from repro.core.hyper import HyperParams

    rng = np.random.default_rng(1)
    n, k = 45, 16
    a = rng.normal(size=(n, k, 2 * k)).astype(np.float32)
    prec = jnp.asarray(a @ a.transpose(0, 2, 1))
    rhs = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    ids = jnp.asarray(np.where(np.arange(n) < 41, np.arange(n) * 3, -1), jnp.int32)
    b = rng.normal(size=(k, k)).astype(np.float32)
    hyper = HyperParams(mu=jnp.asarray(rng.normal(size=k).astype(np.float32)),
                        lam=jnp.asarray(b @ b.T / k + np.eye(k, dtype=np.float32)))
    assert (_sweep_solver("kernel"), _sweep_solver("einsum")) == ("kernel", "subst")

    def finish(solver):
        return np.asarray(_finish_phase(jax.random.PRNGKey(5), prec, rhs, ids,
                                        hyper, 2.5, _sweep_solver(solver)))

    kernel, subst = finish("kernel"), finish("einsum")
    np.testing.assert_allclose(kernel, subst, rtol=2e-4, atol=2e-5)
    assert np.isfinite(kernel).all() and not kernel[41:].any()


# Float64 plain reference of a distributed sweep, run inside the test's
# subprocess: dense posteriors per entity, the Normal-Wishart draw from the
# previous sweep's factors, noise folded from each entity's global id, and
# for `async` a user half conditioned on the previous sweep's v.
_REFERENCE = """
import jax
import numpy as np

def nw(key, x):
    n, k = x.shape
    kw, km = jax.random.split(key)
    kn, kc = jax.random.split(kw)
    dfs = np.float32(k + n) - np.arange(k, dtype=np.float32)
    chi2 = 2.0 * np.asarray(jax.random.gamma(kc, dfs / 2.0, dtype=np.float32), np.float64)
    normal = np.asarray(jax.random.normal(kn, (k, k), np.float32), np.float64)
    z = np.asarray(jax.random.normal(km, (k,), np.float32), np.float64)
    xbar = x.mean(0)
    beta = 2.0 + n
    w_inv = np.eye(k) + x.T @ x - n * np.outer(xbar, xbar) + (2.0 * n / beta) * np.outer(xbar, xbar)
    w = np.linalg.inv(w_inv)
    la = np.linalg.cholesky(0.5 * (w + w.T)) @ (np.tril(normal, -1) + np.diag(np.sqrt(chi2)))
    lam = la @ la.T
    chol = np.linalg.cholesky(beta * lam + 1e-6 * np.eye(k))
    return n * xbar / beta + np.linalg.solve(chol.T, z), lam

def draws(key, rows, cols, vals, n_rows, counter, mu, lam, alpha):
    k = counter.shape[1]
    out = np.empty((n_rows, k))
    for i in range(n_rows):
        sel = rows == i
        c = counter[cols[sel]]
        chol = np.linalg.cholesky(lam + alpha * c.T @ c)
        z = np.asarray(jax.random.normal(jax.random.fold_in(key, i), (k,), np.float32), np.float64)
        y = np.linalg.solve(chol, lam @ mu + alpha * c.T @ vals[sel]) + z
        out[i] = np.linalg.solve(chol.T, y)
    return out

def sweep(key, u, v, rows, cols, vals, alpha, stale):
    key, k_hv, k_v, k_hu, k_u = jax.random.split(key, 5)
    m, n = u.shape[0], v.shape[0]
    v_new = draws(k_v, cols, rows, vals, n, u, *nw(k_hv, v), alpha)
    u_new = draws(k_u, rows, cols, vals, m, v if stale else v_new, *nw(k_hu, u), alpha)
    return key, u_new, v_new
"""


@pytest.mark.parametrize("mode", ["ring", "async"])
def test_two_sweeps_match_float64_reference(mode):
    """Two sweeps on a 4-device mesh against a float64 reference of the
    sampler's conditionals and random streams. Each sweep starts the
    reference from the program's previous state; the u half of `async`
    reads the previous sweep's v, and its state carries that v as
    `v_eval`."""
    out = run_sub(_REFERENCE + f"""
import json
from repro.data import synthetic_lowrank
from repro.core.distributed import DistributedBPMF

ratings, _, _ = synthetic_lowrank(70, 45, k_true=3, nnz=700, noise=0.3, seed=8)
d = DistributedBPMF(ratings, k=6, alpha=3.0, mode={mode!r}, width="auto")
rows, cols = ratings.rows, ratings.cols
vals = ratings.vals.astype(np.float64) - ratings.vals.astype(np.float64).mean()
state = d.init(11)
key = jax.random.split(jax.random.PRNGKey(11), 3)[2]
gaps = []
for _ in range(2):
    u, v = d.gather_factors(state, coupled=False)
    state = d.sweep(state)
    key, u_ref, v_ref = sweep(key, u.astype(np.float64), v.astype(np.float64),
                              rows, cols, vals, 3.0, {mode == "async"})
    u_got, v_got = d.gather_factors(state, coupled=False)
    for got, want in ((u_got, u_ref), (v_got, v_ref)):
        gaps.append(float(np.abs(got - want).max() / np.abs(want).max()))
    if {mode == "async"}:
        _, v_eval = d.gather_factors(state)
        assert np.array_equal(v_eval, v), "v_eval is not the v the u half read"
print(json.dumps(gaps))
""", devices=4)
    gaps = json.loads(out.strip().splitlines()[-1])
    assert len(gaps) == 4 and max(gaps) < 1e-4, gaps


def test_lowered_sweep_carries_its_scopes():
    """The compiled distributed sweep names its layers with the single-chip
    scopes, and every collective sits under `bpmf.exchange`."""
    out = run_sub(r"""
import json, re
from repro.data import synthetic_lowrank
from repro.core.distributed import DistributedBPMF

ratings, _, _ = synthetic_lowrank(60, 40, k_true=3, nnz=500, noise=0.3, seed=2)
found = {}
for mode in ("async", "allgather"):
    d = DistributedBPMF(ratings, k=4, mode=mode, width="auto")
    text = d._sweep.lower(d.init(0), *d._plan_args).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    coll = re.findall(r'= \S+ (?:collective-permute|all-gather|all-reduce)'
                      r'(?:-start)?\(.*?op_name="([^"]+)"', text)
    found[mode] = {"scopes": sorted({p for n in names for p in n.split("/")}),
                   "collectives": coll}
print(json.dumps(found))
""", devices=4)
    found = json.loads(out.strip().splitlines()[-1])
    for mode, f in found.items():
        assert {"bpmf.stats", "bpmf.solve", "bpmf.prior", "bpmf.hyper",
                "bpmf.exchange"} <= set(f["scopes"]), (mode, f["scopes"])
        assert f["collectives"], mode
        assert all("bpmf.exchange" in n for n in f["collectives"]), f["collectives"]
