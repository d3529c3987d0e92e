"""The training cells added beside `chembl-train`: `ml20m-train` (the
existing `train_sweeps` driver on `ml20m-k64`) and `chembl-async4-train`
(the distributed sampler over a 4-device mesh, `train_sweeps_mesh`).

The mesh runs go through `run.run_cell` at a tiny size in a subprocess of
their own, on 4 host devices that XLA simulates (the device count is fixed
when JAX starts). XLA's CPU collectives hang when the device threads drift
apart, so the tiny traffic keeps the queue of dispatched sweeps (`ahead_max`)
under `DistributedBPMF.sync_every`, and every subprocess has a time limit.
"""
import json
import subprocess
import sys

import pytest

import harness
import tracereduce
from conftest import BENCH, CPU_PEAKS

CELL = "chembl-async4-train"
TINY_MESH = {"n_users": 600, "n_items": 80, "nnz": 5000, "k": 8}
TINY_MESH_TRAFFIC = {"per_stratum": 4, "ahead_max": 4}
LIMIT_S = 300


def run_mesh(prelude: str = "", seed: int = 2**31 + 11, control: bool = False) -> dict:
    """One tiny run of the mesh cell on 4 simulated devices, after `prelude`
    (code that may break the program first)."""
    code = f"""
import os, sys, json, dataclasses
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {str(BENCH)!r})
import harness
harness.src_path()
import jax
import run
{prelude}
cell = harness.load_cell({CELL!r})
cell = dataclasses.replace(cell, config=dict(cell.config, **{TINY_MESH!r}),
                           traffic=dict(cell.traffic, **{TINY_MESH_TRAFFIC!r}))
out = run.run_cell(cell, jax.devices()[:4], {CPU_PEAKS!r}, seed={seed},
                   seconds=1.0, control={control})
print(json.dumps(out))
"""
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=LIMIT_S)
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_mesh_sound_run_is_correct():
    """On the CPU the program and the float64 reference agree to rounding:
    the reference follows the distributed sampler's key schedule, its
    per-entity noise and the stale v of its user half."""
    out = run_mesh()
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compared"]["draw_gap"]["value"] < 1e-4
    assert out["compared"]["hyper_gap"]["value"] < 1e-4
    assert out["metrics"]["train_updates_per_s"]["value"] > 0
    assert out["device"]["count"] == 4


FRESH_V = """
import repro.core.distributed as dist

def fresh(k_v, k_u, u_blk, v_blk, v_plans, u_plans, v_ids, u_ids, hyper_v,
          hyper_u, alpha, n_shards, *rest):
    v_new = dist._phase_ring(k_v, u_blk, v_plans, v_ids, hyper_v, alpha,
                             n_shards, *rest)
    u_new = dist._phase_ring(k_u, v_new, u_plans, u_ids, hyper_u, alpha,
                             n_shards, *rest)
    return v_new, u_new

dist._phase_ring_async = fresh
"""


def test_mesh_user_half_on_the_fresh_v_is_not_correct():
    """The async user half conditioned on the v the same sweep drew (ring's
    semantics), while the state still names the previous v as `v_eval`: a
    reference that copied the program's exchange would pass it."""
    assert not run_mesh(FRESH_V)["correct"]


ONE_DRAW = """
import numpy as np
from repro.core.distributed import DistributedBPMF

real = DistributedBPMF.sweep

def altered(self, state):
    out = real(self, state)
    plan, part = self.u_plan, self.u_part
    deg = np.zeros(part.shard.shape[0])
    for p in range(self.n_shards):
        ids = np.append(part.ids[p], -1)[plan.seg[p].reshape(-1)]
        np.add.at(deg, ids[ids >= 0], plan.mask[p].sum(-1).reshape(-1)[ids >= 0])
    g = int(np.argmax(deg))
    u = np.array(out.u)
    u[part.shard[g], part.local[g]] += 0.5
    return out._replace(u=jax.device_put(u, out.u.sharding))

DistributedBPMF.sweep = altered
"""


def test_mesh_one_draw_altered_is_not_correct():
    """The heaviest user's draw moved where the sweep produces it."""
    assert not run_mesh(ONE_DRAW)["correct"]


def test_mesh_control_fails_the_limits():
    out = run_mesh(control=True)
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("control", [False, True])
def test_ml20m_train_sound_and_control(tiny, control):
    """`ml20m-train` is the `train_sweeps` driver on `ml20m-k64`: sound at
    a tiny size, and its float8 control fails its limits."""
    out = tiny("ml20m-train", control=control)
    assert out["correct"] is not control, out["compared"]
    if not control:
        assert out["compared"]["draw_gap"]["value"] < 1e-4


def test_new_cells_are_found_by_name():
    """The new configuration, traffic, driver, readers and limits are files
    the harness finds by the names in BENCHMARK.json."""
    mesh = harness.load_cell(CELL)
    assert mesh.chips == 4 and mesh.config["mesh"]["shards"] == 4
    assert mesh.config["mesh"]["mode"] == "async"
    assert mesh.traffic["kind"] == "train_sweeps_mesh"
    assert hasattr(harness.driver(mesh.traffic["kind"]), "run")
    names = {m["name"] for m in mesh.per_layer}
    assert {"exchange_exposed.train", "shard_skew.train", "grid_fill.train",
            "plan_s", "sweep_mfu", "device_idle.train", "solve_ms.train",
            "stats_ms.train"} <= names
    assert [m["name"] for m in mesh.end_to_end] == ["setup_s", "train_updates_per_s"]
    ml = harness.load_cell("ml20m-train")
    assert ml.chips == 1 and ml.traffic["kind"] == "train_sweeps"
    assert "exchange_exposed.train" not in {m["name"] for m in ml.per_layer}
    for cell in (CELL, "ml20m-train"):
        limits = json.loads((BENCH / "limits" / f"{cell}.json").read_text())
        assert set(limits) == {"draw_gap", "hyper_gap"}
    for m in names:
        assert hasattr(harness.metric_reader(m), "read")


def _trace():
    """Two chips over a 10 s window: chip 0 computes 0-6 s and exchanges
    5-8 s (2 s exposed); chip 1 computes 0-4 s and exchanges 4-9 s (5 s
    exposed)."""
    return tracereduce.Trace(devices={
        "/device:TPU:0": [("fusion.1", 0.0, 6.0), ("collective-permute-done.2", 5.0, 8.0)],
        "/device:TPU:1": [("fusion.1", 0.0, 4.0), ("all-reduce.3", 4.0, 9.0)],
    })


def test_exchange_readers():
    info = {"trace": _trace(), "window": (0.0, 10.0), "layer": {"sweeps": 2}}
    exposed = harness.metric_reader("exchange_exposed.train").read(info)
    assert exposed == pytest.approx(1e3 * (2.0 + 5.0) / 2 / 2)
    skew = harness.metric_reader("shard_skew.train").read(info)
    assert skew == pytest.approx(100.0 * (6.0 / 5.0 - 1.0))
    quiet = {"trace": tracereduce.Trace(devices={
        "/device:TPU:0": [("fusion.1", 0.0, 1.0)]}), "window": (0.0, 10.0),
        "layer": {"sweeps": 2}}
    assert harness.metric_reader("exchange_exposed.train").read(quiet) is None
    assert harness.metric_reader("shard_skew.train").read(quiet) is None
    none = {"trace": None, "window": None, "layer": {}}
    for m in ("exchange_exposed.train", "shard_skew.train", "grid_fill.train"):
        assert harness.metric_reader(m).read(none) is None


def test_grid_fill_reads_the_plan_counter():
    stats = {"u": {"rated": [3, 5], "sent": [8, 8]}, "v": {"rated": [4, 4], "sent": [8, 8]}}
    fill = harness.metric_reader("grid_fill.train").read({"layer": {"plan_stats": stats}})
    assert fill == pytest.approx(100.0 * 16 / 32)
