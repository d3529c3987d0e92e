#!/usr/bin/env python3
"""Run one benchmark cell once on the chip(s) of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in BENCHMARK.json, sets it up from the seed (data,
plans, compile or cache load, warm-up), measures for `--seconds`, checks
what the timed path produced against a float64 reference, and prints one
JSON object as the last line of stdout. `--trace 0` reports the cell's
end-to-end metrics; `--trace 1` traces the window with the JAX profiler and
reports its per-layer metrics. Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell needs.

`--control 1` puts the control, the reference with its inputs rounded to
float8 (e4m3), in the program's place in the check (its readings set the
upper end of each limit); the benchmark's own runs never pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import tracereduce as tracing  # noqa: E402


@dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0


@dataclass
class RunContext:
    """What a driver gets: the cell, the run's arguments, the recorder, and
    the hooks that mark set-up's end and the measured window."""

    cell: harness.Cell
    seed: int
    seconds: float
    trace: bool
    control: bool
    devices: list
    peaks: dict
    rec: harness.Recorder
    compiles: harness.CompileCounter
    limits: dict
    t_start: float = T_START
    setup_s: float | None = None
    layer: dict = field(default_factory=dict)
    device: dict = field(default_factory=dict)
    captured: object = None
    win: Window = field(default_factory=Window)

    def setup_done(self) -> None:
        """Set-up ends: its garbage is collected and what survives is moved
        out of the collector's reach (`gc.freeze`), so that no full
        collection inside the window walks set-up's objects."""
        gc.collect()
        gc.freeze()
        self.setup_s = time.perf_counter() - self.t_start

    def window_seconds(self, traffic: dict) -> float:
        """The window's length: `--seconds`, or in a traced run at most
        the mix's `trace_seconds`."""
        if self.trace:
            return min(self.seconds, float(traffic.get("trace_seconds", self.seconds)))
        return self.seconds

    @contextlib.contextmanager
    def window(self):
        counts0 = (self.compiles.count, program_traces())
        with tracing.capture(self.trace) as cap:
            with self.rec.span("window"):
                self.win.t0 = time.perf_counter()
                yield self.win
                self.win.t1 = time.perf_counter()
        if cap.trace is not None:
            names = {s[0] for s in self.rec.spans}
            cap.trace.host = [h for h in cap.trace.host if h[0] in names]
        self.captured = cap
        self.rec.counters["window_compiles"] = self.compiles.count - counts0[0]
        self.rec.counters["window_program_traces"] = program_traces() - counts0[1]

    def read_device(self) -> None:
        """Peak memory after the window, before any reference runs."""
        self.device = harness.device_record(self.devices)


def program_traces() -> int:
    """The program's own retrace counters (top-N kernel, fold-in solve)."""
    total = 0
    for mod in ("repro.kernels.bpmf_topn", "repro.serve.foldin"):
        m = sys.modules.get(mod)
        if m is not None:
            total += m.trace_count()
    return total


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def per_layer(ctx: RunContext) -> tuple[dict, dict | None]:
    """Each per-layer metric of the cell from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    tr = ctx.captured.trace if ctx.captured is not None else None
    window = None
    if tr is not None and tr.devices:
        window = tracing.window_of(tr)
    info = {"trace": tr, "window": window, "rec": ctx.rec, "layer": ctx.layer,
            "peaks": ctx.peaks, "chips": ctx.cell.chips,
            "win": (ctx.win.t0, ctx.win.t1)}
    out = {}
    for m in ctx.cell.per_layer:
        value = harness.metric_reader(m["name"]).read(info)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    bd = None
    if window is not None:
        busy = tracing.busy_seconds(tr, window)
        ctx.device["busy_s"] = busy
        ctx.device["window_s"] = window[1] - window[0]
        bd = tracing.breakdown(tr, window)
    return out, bd


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--control", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    try:
        cell = harness.load_cell(args.workload)
    except (FileNotFoundError, KeyError) as e:
        log(f"bench: {e}")
        return 2
    if not (harness.ROOT / "src" / "repro").is_dir():
        log(f"bench: the program is missing: no src/repro under {harness.ROOT}")
        return 2
    harness.src_path()
    try:
        devices = harness.require_devices(cell.chips)
    except harness.DeviceError as e:
        log(f"bench: {e}")
        return 3
    cache = harness.enable_compile_cache()
    log(f"bench: {cell.name} on {devices[0].device_kind} x {len(devices)}, "
        f"seed {args.seed}, compile cache {cache}")
    out = run_cell(cell, devices, harness.peaks(devices[0].device_kind),
                   seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                   control=bool(args.control), t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


def run_cell(cell: harness.Cell, devices, peaks: dict, *, seed: int,
             seconds: float, trace: bool = False, control: bool = False,
             t_start: float | None = None) -> dict:
    """Set up, measure and check one cell on `devices`; the result object.
    Set-up counts from `t_start` (the process's start in a run)."""
    limits = json.loads((harness.BENCH / "limits" / f"{cell.name}.json").read_text())
    ctx = RunContext(cell=cell, seed=seed, seconds=seconds, trace=trace,
                     control=control, devices=devices, peaks=peaks,
                     rec=harness.Recorder(trace=trace),
                     compiles=harness.CompileCounter(), limits=limits,
                     t_start=time.perf_counter() if t_start is None else t_start)
    res = harness.driver(cell.traffic["kind"]).run(ctx)
    return report(ctx, res)


def report(ctx: RunContext, res: dict) -> dict:
    rec = ctx.rec
    setup = {n: round(sum(rec.durations(n)), 3)
             for n in dict.fromkeys(s[0] for s in rec.spans) if n not in ("window",)}
    log(f"bench: set-up {ctx.setup_s:.3f} s; host spans (s) {json.dumps(setup)}")
    log(f"bench: compilations inside the window: {rec.counters.get('window_compiles')} "
        f"(program retraces {rec.counters.get('window_program_traces')})")
    for line in res.get("notes", []):
        log(f"bench: {line}")
    if ctx.trace:
        metrics, bd = per_layer(ctx)
        tracing.discard(ctx.captured)
    else:
        metrics, bd = {}, None
        values = dict(res["metrics"], setup_s=ctx.setup_s)
        for m in ctx.cell.end_to_end:
            metrics[m["name"]] = {"value": harness.finite(values[m["name"]]),
                                  "unit": m["unit"]}
    compares = res["compares"]
    correct = all(c.ok for c in compares) and res["failed"] == 0
    out = {"correct": correct, "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics, "device": ctx.device}
    if bd is not None:
        out["breakdown"] = bd
    out["compared"] = {c.name: {"value": harness.finite(c.value), "limit": c.limit}
                       for c in compares}
    for c in compares:
        log(f"compared {c.name} = {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    return out


if __name__ == "__main__":
    sys.exit(main())
