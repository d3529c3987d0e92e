"""Profiler trace of a window, and its reduction to numbers.

`capture` records the JAX profiler's trace of a window; `extract` reads the
`.xplane.pb` it writes into a plain `Trace`: per device, the operations that
ran (name, start, end in seconds), and the harness's own host spans on the
same clock. Everything else here works on a `Trace`, so a recorded trace
(saved with `Trace.save`) is reduced exactly as a live one.
"""
from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
import shutil
import tempfile
from collections import defaultdict
from dataclasses import dataclass, field

# The line of a TPU device plane that holds one event per operation.
OPS_LINE = "XLA Ops"
COLLECTIVE_MARKS = ("collective-permute", "all-gather", "all-reduce",
                    "reduce-scatter", "all-to-all")

Interval = tuple[float, float]


@dataclass
class Trace:
    """Device operations and host spans, in seconds on one clock."""

    devices: dict[str, list[tuple[str, float, float]]] = field(default_factory=dict)
    host: list[tuple[str, float, float]] = field(default_factory=list)

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"devices": self.devices, "host": self.host}, f)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls({k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                   [tuple(e) for e in d["host"]])

    def span(self, name: str) -> Interval | None:
        """The first host span of that name."""
        for n, t0, t1 in self.host:
            if n == name:
                return t0, t1
        return None


@dataclass
class Captured:
    trace: Trace | None = None
    path: str | None = None
    logdir: str | None = None


@contextlib.contextmanager
def capture(enabled: bool):
    """Trace the body with the JAX profiler (host spans from
    `TraceAnnotation` only, no Python tracer); yields a holder whose
    `trace` is filled in on exit."""
    holder = Captured()
    if not enabled:
        yield holder
        return
    import jax

    holder.logdir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(holder.logdir, profiler_options=opts)
    try:
        yield holder
    finally:
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(holder.logdir, "**", "*.xplane.pb"),
                          recursive=True)
        holder.path = files[0] if files else None
        holder.trace = extract(holder.path) if files else Trace()


def discard(holder: Captured) -> None:
    if holder.logdir:
        shutil.rmtree(holder.logdir, ignore_errors=True)


def extract(path: str, host_names: set[str] | None = None) -> Trace:
    """Read an `.xplane.pb`: each TPU plane's operation line, and the host
    plane's spans (those named in `host_names`, or all with a duration)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            line = lines.get(OPS_LINE)
            if line is None:
                continue
            tr.devices[plane.name] = [
                (short_name(e.name), e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9)
                for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    if host_names is not None and e.name not in host_names:
                        continue
                    tr.host.append((e.name, e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9))
    tr.host.sort(key=lambda x: x[1])
    return tr


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------
def union(intervals, window: Interval | None = None) -> list[Interval]:
    """Merged, sorted, clipped to `window`."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if window is not None:
            a, b = max(a, window[0]), min(b, window[1])
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(merged: list[Interval]) -> float:
    return sum(b - a for a, b in merged)


def minus(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """Parts of merged intervals `a` not covered by merged intervals `b`."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def is_collective(name: str) -> bool:
    return any(m in name for m in COLLECTIVE_MARKS)


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------
def window_of(tr: Trace, span: str = "window") -> Interval:
    """The harness's window span, or the extent of all device operations."""
    w = tr.span(span)
    if w is not None:
        return w
    evs = [e for ops in tr.devices.values() for e in ops]
    return min(e[1] for e in evs), max(e[2] for e in evs)


def busy_seconds(tr: Trace, window: Interval) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not tr.devices:
        return 0.0
    return sum(length(union([(a, b) for _, a, b in ops], window))
               for ops in tr.devices.values()) / len(tr.devices)


def exposed_collective_seconds(tr: Trace, window: Interval) -> float:
    """Seconds in which a collective ran and no compute did, averaged over
    the devices."""
    if not tr.devices:
        return 0.0
    total = 0.0
    for ops in tr.devices.values():
        coll = union([(a, b) for n, a, b in ops if is_collective(n)], window)
        comp = union([(a, b) for n, a, b in ops if not is_collective(n)], window)
        total += length(minus(coll, comp))
    return total / len(tr.devices)


def self_times(ops, window: Interval) -> list[tuple[str, float]]:
    """Each operation's time inside the window less the time of the
    operations nested in it (a loop's body ops count for themselves, the
    loop keeps only its own overhead)."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    own = [max(0.0, min(b, window[1]) - max(a, window[0])) for _, a, b in evs]
    stack: list[int] = []
    for i, (_, a, b) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= evs[stack[-1]][2]:
            own[stack[-1]] -= own[i]
            stack.append(i)
        elif not stack:
            stack.append(i)
    return [(e[0], max(t, 0.0)) for e, t in zip(evs, own)]


def op_seconds(tr: Trace, window: Interval) -> dict[str, float]:
    """Self seconds per operation name inside the window, summed over
    devices and averaged per device."""
    totals: dict[str, float] = defaultdict(float)
    n = max(len(tr.devices), 1)
    for ops in tr.devices.values():
        for name, secs in self_times(ops, window):
            if secs > 0:
                totals[name] += secs / n
    return dict(totals)


def kernel_seconds(tr: Trace, window: Interval, prefix: str) -> tuple[float, int]:
    """Summed device seconds (per device) and event count of a kernel."""
    n = max(len(tr.devices), 1)
    secs, count = 0.0, 0
    for ops in tr.devices.values():
        for name, a, b in ops:
            if name.startswith(prefix):
                a, b = max(a, window[0]), min(b, window[1])
                if b > a:
                    secs += (b - a) / n
                    count += 1
    return secs, count


def short_name(text: str) -> str:
    """An operation event's HLO text -> its instruction name, with the
    target of a custom call: `%custom-call.38 = ... custom_call_target=
    "Cholesky"` -> `custom-call.38:Cholesky`; a Pallas kernel keeps its
    own name (`topn_scores_pallas.1`)."""
    if not text.startswith("%"):
        return text
    head = text[1:].split(" ", 1)[0]
    m = re.search(r'custom_call_target="([^"]+)"', text)
    if m and m.group(1) != "tpu_custom_call":
        head = f"{head}:{m.group(1)}"
    return head


def base_name(op: str) -> str:
    """`fusion.123` -> `fusion`, `custom-call.38:Cholesky` ->
    `custom-call:Cholesky`: instances of one operation kind together."""
    m = re.match(r"^(.*?)\.\d+(:.*)?$", op)
    return m.group(1) + (m.group(2) or "") if m else op


def idle_gaps(tr: Trace, window: Interval, top: int = 10) -> list[list]:
    """The longest gaps in which the first device ran nothing, each named
    by the innermost harness span open at its middle."""
    if not tr.devices:
        return []
    first = sorted(tr.devices)[0]
    busy = union([(a, b) for _, a, b in tr.devices[first]], window)
    gaps = minus([window], busy)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    out = []
    for a, b in gaps[:top]:
        mid = 0.5 * (a + b)
        label = "no span"
        best = float("inf")
        for name, s0, s1 in tr.host:
            if s0 <= mid <= s1 and s1 - s0 < best and name != "window":
                label, best = name, s1 - s0
        out.append([label, b - a])
    return out


def breakdown(tr: Trace, window: Interval, top: int = 10) -> dict:
    by_kind: dict[str, float] = defaultdict(float)
    for name, secs in op_seconds(tr, window).items():
        by_kind[base_name(name)] += secs
    ops = sorted(by_kind.items(), key=lambda kv: kv[1], reverse=True)[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": idle_gaps(tr, window, top)}
