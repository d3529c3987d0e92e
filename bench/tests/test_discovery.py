"""A new configuration, traffic mix or per-layer metric is a new file that
the harness finds by name; no existing file of the harness changes."""
import json
import shutil

import harness
from conftest import BENCH


def test_new_files_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    cfg = json.loads((bench / "configs" / "chembl-k64.json").read_text())
    cfg["name"] = "chembl-k32"
    cfg["k"] = 32
    (bench / "configs" / "chembl-k32.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "train-sweeps.json").read_text())
    mix["name"] = "train-sweeps-long"
    mix["check_sweeps"] = 2
    (bench / "traffic" / "train-sweeps-long.json").write_text(json.dumps(mix))
    (bench / "metrics" / "sweeps_done.py").write_text(
        "def read(info):\n    return info['layer'].get('sweeps')\n")
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"], "run_seconds": 10,
        "configs": [{"name": "chembl-k32", "source": "https://arxiv.org/abs/1705.10633",
                     "file": "bench/configs/chembl-k32.json", "reduced": [], "why": "w"}],
        "workloads": [{"name": "chembl-k32-long", "config": "chembl-k32",
                       "traffic": "train-sweeps-long", "chips": 1, "why": "w"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": "sweeps_done", "unit": "sweeps", "better": "higher",
                       "source": "host_clock", "layer": "sweep", "moves": "setup_s"}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("chembl-k32-long", root=tmp_path, bench=bench)
    assert cell.config["k"] == 32 and cell.traffic["check_sweeps"] == 2
    assert [m["name"] for m in cell.per_layer] == ["sweeps_done"]
    drv = harness.driver(cell.traffic["kind"], bench=bench)
    assert hasattr(drv, "run")
    reader = harness.metric_reader("sweeps_done", bench=bench)
    assert reader.read({"layer": {"sweeps": 4}}) == 4
    after = {p: p.read_bytes() for p in before}
    assert after == before            # nothing that was there changed


def test_metric_without_workloads_follows_its_end_to_end_metric():
    m = {"name": "x", "moves": "serve_p50_ms"}
    assert harness.reports(m, "any-serving-cell", {"serve_p50_ms", "setup_s"})
    assert not harness.reports(m, "a-training-cell", {"train_updates_per_s", "setup_s"})
    assert harness.reports(dict(m, workloads=["a"]), "a", set())
