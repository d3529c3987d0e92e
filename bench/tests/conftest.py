"""Helpers for the benchmark's own tests (CPU, tiny sizes):
`python -m pytest bench/tests`."""
from __future__ import annotations

import dataclasses
import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import harness  # noqa: E402

harness.src_path()

TINY = {
    "chembl-k64": {"n_users": 600, "n_items": 80, "nnz": 5000, "k": 8},
    "ml20m-k64": {"n_users": 900, "n_items": 300, "nnz": 12000, "k": 8,
                  "serving": {"draws": 2, "draw_scale": 0.3, "global_mean": 0.0,
                              "exclude_seen": False}},
}
TINY_TRAFFIC = {
    "train_sweeps": {"per_stratum": 4},
    "serve_open_loop": {"rate_per_s": 30, "cold_cover": 2, "check_warm": 20,
                        "check_cold": 10, "drain_seconds": 20},
}
CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_cell(name: str) -> harness.Cell:
    """A cell of BENCHMARK.json at a size the CPU runs in seconds."""
    cell = harness.load_cell(name)
    cfg = dict(cell.config, **TINY[cell.config["name"]])
    traffic = dict(cell.traffic, **TINY_TRAFFIC[cell.traffic["kind"]])
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def run_tiny(name: str, seed: int = 2**31 + 11, seconds: float = 1.0,
             control: bool = False) -> dict:
    import jax

    import run

    cell = tiny_cell(name)
    return run.run_cell(cell, jax.devices()[: cell.chips], CPU_PEAKS, seed=seed,
                        seconds=seconds, control=control)


@pytest.fixture
def tiny():
    return run_tiny
