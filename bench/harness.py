"""What every cell shares: the spec, the device check, the compile cache,
spans and counters, and the per-layer metric readers.

The harness is driven by data. A cell of `BENCHMARK.json` names a
configuration (its `file`), a traffic mix (`bench/traffic/<traffic>.json`,
whose `kind` names a driver module `bench/drivers/<kind>.py`) and is read by
the per-layer metrics whose reader is `bench/metrics/<metric>.py`. A new
configuration, mix or metric is a new file; no existing file changes.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPEC_FILE = "BENCHMARK.json"
PEAKS_FILE = BENCH / "peaks.json"


class DeviceError(RuntimeError):
    """No accelerator, too few chips, or a chip with no known peaks."""


def load_spec(root: Path = ROOT) -> dict:
    path = root / SPEC_FILE
    if not path.is_file():
        raise FileNotFoundError(f"no {SPEC_FILE} at {root}")
    return json.loads(path.read_text())


@dataclass(frozen=True)
class Cell:
    """One workload of the spec, with its configuration and traffic loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def reports(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    """Whether `cell` reports `metric`: listed under its `workloads`, or,
    without that key, every cell that reports the metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_names


def load_cell(name: str, root: Path = ROOT, bench: Path = BENCH) -> Cell:
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = tuple(m for m in spec["end_to_end"] if reports(m, name, set()))
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in spec["per_layer"]
                      if reports(m, name, e2e_names))
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def load_module(path: Path, name: str) -> ModuleType:
    """Import a file of the harness by path (its name may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str, bench: Path = BENCH) -> ModuleType:
    return load_module(bench / "drivers" / f"{kind}.py", f"bench_driver_{kind}")


def metric_reader(name: str, bench: Path = BENCH) -> ModuleType:
    return load_module(bench / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


def peaks(kind: str, path: Path = PEAKS_FILE) -> dict:
    """Published peaks of one chip of `kind`; an unknown kind is an error."""
    table = json.loads(path.read_text())["chips"]
    if kind not in table:
        raise DeviceError(f"no peaks for device kind {kind!r}; known: {sorted(table)}")
    return table[kind]


def require_devices(chips: int):
    """The first `chips` accelerator devices, or DeviceError. Never the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise DeviceError(f"needs a TPU, found {devs[0].platform}")
    if len(devs) < chips:
        raise DeviceError(f"the cell needs {chips} chips, found {len(devs)}")
    peaks(devs[0].device_kind)
    return devs[:chips]


def device_record(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at one fixed path: `$JAX_COMPILATION_CACHE_DIR`
    when set, else `.jax_cache/` in the checkout. Every program is kept,
    however fast it compiled, so a warm run loads and never compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compilations (persistent-cache loads included) from
    JAX's monitoring events: every jit-cache miss that reaches the backend."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


@dataclass
class Recorder:
    """The harness's host spans (perf_counter seconds) and counters. With
    `trace` on, each span is also a `jax.profiler.TraceAnnotation`, so it
    lands in the profiler's host plane on the device trace's clock."""

    trace: bool = False
    spans: list[tuple[str, float, float, dict]] = field(default_factory=list)
    counters: dict = field(default_factory=dict)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        ann = contextlib.nullcontext()
        if self.trace:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ann:
            yield attrs
        self.spans.append((name, t0, time.perf_counter(), attrs))

    def durations(self, name: str) -> list[float]:
        return [t1 - t0 for n, t0, t1, _ in self.spans if n == name]


@dataclass(frozen=True)
class Compare:
    """One number the correctness check compares, with its limit: the run
    is correct only when value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def finite(x: float) -> float:
    """JSON has no infinity: a failed comparison reads as 1e30."""
    return x if x == x and abs(x) < 1e30 else 1e30


def src_path() -> None:
    """Put the program (`src/`) on the import path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
