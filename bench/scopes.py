"""The program's own trace points in a traced window, and their reduction.

The program names its layers itself: `jax.named_scope` on the compiled
sweep and fold-in (`bpmf.stats`, `bpmf.solve`, `bpmf.prior`, `bpmf.hyper`,
`bpmf.predict`, `serve.foldin`), and `jax.profiler.TraceAnnotation` host
spans in the serving flush (`serve.batch`, `serve.warm`, `serve.cold`,
`serve.foldin.plan`, `serve.fetch`, `serve.exclude`). `tracereduce.extract`
keeps neither: it cuts each operation's HLO text down to its name, and the
window keeps only the harness's own spans. This module reads the window's
profile again into a `ScopedTrace`: a `tracereduce.Trace` that also holds
each device operation's scope path and the program's `serve.*` spans.

A device operation's event names its instruction and its program, not its
scope: on a v5e the event's HLO text carries no `metadata` and its stats
no `tf_op`. The profile's `/host:metadata` plane holds each program's HLO
proto, whose instructions carry the `op_name` that `jax.named_scope` writes
(`jit(_sweep_impl)/bpmf.hyper/.../cholesky`). `extract` joins the two on
the event's `program_id` stat and instruction name. It decodes the profile
with a minimal copy of the fields of `xplane.proto` and `hlo.proto` it
reads, so that it needs nothing beyond `protobuf`.

A reader gets the reduced `Trace`; the profile it came from is still on
disk while the readers run, and `scoped(info)` finds its path through the
`tracereduce.Captured` that holds that `Trace`. A program without these
scopes and spans gives a `ScopedTrace` with none, and each reader that needs
them returns None.
"""
from __future__ import annotations

import functools
import gzip
import json
import statistics
import sys
from collections import defaultdict
from dataclasses import dataclass, field

import tracereduce
from tracereduce import Interval

PROGRAM_SPANS = "serve."


@dataclass
class ScopedTrace(tracereduce.Trace):
    """A `Trace` whose device operations each carry their name-scope path
    (`scopes[device][i]` belongs to `devices[device][i]`; "" where the
    operation has none)."""

    scopes: dict[str, list[str]] = field(default_factory=dict)

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"devices": self.devices, "host": self.host,
                       "scopes": self.scopes}, f)

    @classmethod
    def load(cls, path: str) -> "ScopedTrace":
        """A saved `ScopedTrace`, or a plain saved `Trace` (no scopes)."""
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls({k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                   [tuple(e) for e in d["host"]], d.get("scopes", {}))


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_SPANS)


# (message, ((field, number, type, repeated, message type), ...)); types are
# FieldDescriptorProto's: 3 int64, 4 uint64, 9 string, 11 message, 12 bytes
_PROTO = (
    ("XSpace", (("planes", 1, 11, True, "XPlane"),)),
    ("XPlane", (("name", 2, 9, False, ""), ("lines", 3, 11, True, "XLine"),
                ("event_metadata", 4, 11, True, "EventMetadataEntry"),
                ("stat_metadata", 5, 11, True, "StatMetadataEntry"))),
    ("EventMetadataEntry", (("key", 1, 3, False, ""),
                            ("value", 2, 11, False, "XEventMetadata"))),
    ("StatMetadataEntry", (("key", 1, 3, False, ""),
                           ("value", 2, 11, False, "XStatMetadata"))),
    ("XLine", (("name", 2, 9, False, ""), ("timestamp_ns", 3, 3, False, ""),
               ("events", 4, 11, True, "XEvent"))),
    ("XEvent", (("metadata_id", 1, 3, False, ""), ("offset_ps", 2, 3, False, ""),
                ("duration_ps", 3, 3, False, ""))),
    ("XEventMetadata", (("name", 2, 9, False, ""), ("display_name", 4, 9, False, ""),
                        ("stats", 5, 11, True, "XStat"))),
    ("XStatMetadata", (("name", 2, 9, False, ""),)),
    ("XStat", (("metadata_id", 1, 3, False, ""), ("uint64_value", 3, 4, False, ""),
               ("bytes_value", 6, 12, False, ""))),
    ("HloProto", (("hlo_module", 1, 11, False, "HloModuleProto"),)),
    ("HloModuleProto", (("computations", 3, 11, True, "HloComputationProto"),)),
    ("HloComputationProto", (("instructions", 2, 11, True, "HloInstructionProto"),)),
    ("HloInstructionProto", (("name", 1, 9, False, ""),
                             ("metadata", 7, 11, False, "OpMetadata"))),
    ("OpMetadata", (("op_name", 2, 9, False, ""),)),
)


@functools.cache
def messages() -> dict:
    """Message classes for the fields of the profile and of the HLO proto
    that `extract` reads; every other field is skipped when parsing."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    fd = descriptor_pb2.FileDescriptorProto(name="bench_scopes.proto",
                                            package="bench_scopes")
    for name, fields in _PROTO:
        m = fd.message_type.add(name=name)
        for fname, number, ftype, repeated, mtype in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=3 if repeated else 1)
            if mtype:
                f.type_name = f".bench_scopes.{mtype}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {name: message_factory.GetMessageClass(
                pool.FindMessageTypeByName(f"bench_scopes.{name}"))
            for name, _ in _PROTO}


def _stat_names(plane) -> dict[int, str]:
    return {e.key: e.value.name for e in plane.stat_metadata}


def program_scopes(space) -> dict[int, dict[str, str]]:
    """Per program id, each instruction's `op_name`, from the HLO protos of
    the profile's `/host:metadata` plane."""
    hlo = messages()["HloProto"]
    out: dict[int, dict[str, str]] = {}
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        names = _stat_names(plane)
        for e in plane.event_metadata:
            for st in e.value.stats:
                if names.get(st.metadata_id) == "Hlo Proto":
                    module = hlo.FromString(st.bytes_value).hlo_module
                    out[e.key & 0xFFFFFFFFFFFFFFFF] = {
                        i.name: i.metadata.op_name
                        for c in module.computations for i in c.instructions}
    return out


def extract(path: str, host: list[tuple[str, float, float]] = ()) -> ScopedTrace:
    """Read an `.xplane.pb`: each TPU plane's operations with their scope
    paths, the program's spans, and the given (harness) spans. Times are
    whole nanoseconds, as `tracereduce.extract` reads them."""
    with open(path, "rb") as f:
        space = messages()["XSpace"].FromString(f.read())
    programs = program_scopes(space)
    tr = ScopedTrace(host=list(host))
    for plane in space.planes:
        is_device = plane.name.startswith("/device:TPU:")
        if not (is_device or plane.name.startswith("/host:CPU")):
            continue
        names = _stat_names(plane)
        meta = {}
        for e in plane.event_metadata:
            md = e.value
            pid = next((st.uint64_value for st in md.stats
                        if names.get(st.metadata_id) == "program_id"), None)
            meta[e.key] = (md.name, programs.get(pid, {}).get(md.display_name, ""))
        ops, paths = [], []
        for line in plane.lines:
            if is_device and line.name != tracereduce.OPS_LINE:
                continue
            for ev in line.events:
                text, scope = meta.get(ev.metadata_id, ("", ""))
                t0 = line.timestamp_ns + ev.offset_ps // 1000
                span = (t0 * 1e-9, (t0 + ev.duration_ps // 1000) * 1e-9)
                if is_device:
                    ops.append((tracereduce.short_name(text), *span))
                    paths.append(scope)
                elif ev.duration_ps // 1000 > 0 and is_program_span(text):
                    tr.host.append((text, *span))
        if is_device and ops:
            tr.devices[plane.name] = ops
            tr.scopes[plane.name] = paths
    tr.host.sort(key=lambda x: x[1])
    return tr


def profile_path(trace: tracereduce.Trace) -> str | None:
    """Where the profile reduced into `trace` lies: the `path` of the
    `Captured` that holds it, a local of one of the callers (the run's
    context holds it as `captured`)."""
    frame = sys._getframe(1)
    while frame is not None:
        for v in frame.f_locals.values():
            cap = v if isinstance(v, tracereduce.Captured) else \
                getattr(v, "__dict__", {}).get("captured")
            if isinstance(cap, tracereduce.Captured) and cap.trace is trace:
                return cap.path
        frame = frame.f_back
    return None


_last: tuple[object, ScopedTrace] | None = None


def scoped(info: dict) -> ScopedTrace | None:
    """The reader's trace with scopes and program spans: as given when it
    is a `ScopedTrace` already (a recorded one), else read once from the
    run's profile and kept for the run's other readers."""
    global _last
    tr = info.get("trace")
    if tr is None or isinstance(tr, ScopedTrace):
        return tr
    if _last is not None and _last[0] is tr:
        return _last[1]
    path = profile_path(tr)
    if path is None:
        return None
    _last = (tr, extract(path, tr.host))
    return _last[1]


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------
def scope_seconds(tr: ScopedTrace, window: Interval) -> dict[str, float]:
    """Self seconds inside the window under each scope component (an
    operation counts once for every scope it is nested in), per device."""
    totals: dict[str, float] = defaultdict(float)
    n = max(len(tr.devices), 1)
    for dev, ops in tr.devices.items():
        paths = tr.scopes.get(dev)
        if not paths:
            continue
        labelled = [(p, a, b) for p, (_, a, b) in zip(paths, ops)]
        for path, secs in tracereduce.self_times(labelled, window):
            if secs > 0:
                for part in set(path.split("/")) - {""}:
                    totals[part] += secs / n
    return dict(totals)


def spans(tr: tracereduce.Trace, name: str, window: Interval) -> list[Interval]:
    """Host spans of that name lying inside the window."""
    return [(a, b) for n, a, b in tr.host
            if n == name and a >= window[0] and b <= window[1]]


def self_seconds(tr: tracereduce.Trace, name: str, child: str,
                 window: Interval) -> list[float]:
    """Each span `name` inside the window, less the part of it that spans
    `child` cover."""
    kids = spans(tr, child, window)
    return [tracereduce.length(tracereduce.minus(
                [(a, b)], tracereduce.union(kids, (a, b))))
            for a, b in spans(tr, name, window)]


def ms_per_sweep(info: dict, scope: str) -> float | None:
    """Device self time under `scope` per sweep of the window, in ms."""
    sweeps = info["layer"].get("sweeps")
    tr = scoped(info)
    if not sweeps or tr is None or info["window"] is None:
        return None
    secs = scope_seconds(tr, info["window"]).get(scope)
    return None if secs is None else 1e3 * secs / sweeps


def median_ms(values: list[float]) -> float | None:
    return 1e3 * statistics.median(values) if values else None
