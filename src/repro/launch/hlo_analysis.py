"""HLO-text cost model with loop-trip-count multiplication.

XLA's `compiled.cost_analysis()` counts every while-loop body ONCE — for a
sweep that scans over solve chunks or ring steps that understates FLOPs by
the trip count and hides per-step collectives entirely. This module walks
the optimized (post-SPMD-partitioning) HLO text instead:

  - dot flops = 2 * result_elems * contracted_elems, multiplied through the
    call graph (while bodies x known_trip_count from backend_config, fusions,
    calls);
  - HBM traffic at fusion granularity: each non-trivial op contributes
    (operand bytes + result bytes), matching how fused kernels actually touch
    HBM; fusion-internal ops are skipped for bytes but traversed for flops;
  - collective bytes = operand sizes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute, with loop multipliers.

Shapes in the partitioned module are per-device shards, so all totals are
per-device; the roofline divides by per-chip peaks directly.

Known approximations: elementwise / transcendental flops are ignored
(dot-dominated workloads); conditional branches are summed; custom-call
flops (LAPACK cholesky etc. on the CPU backend) are ignored.
"""
from __future__ import annotations

import dataclasses
import json
import re

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# Ops that are views/bookkeeping — no HBM traffic of their own.
_FREE_OPS = {
    "bitcast", "tuple", "get-tuple-element", "parameter", "constant",
    "after-all", "iota", "reshape", "broadcast", "copy-start", "copy-done",
    "partition-id", "replica-id", "add-dependency", "opt-barrier",
}

# Elementwise / layout ops the TPU compiler fuses into producers/consumers.
# The CPU backend leaves them as standalone ops (1000+ converts in a bf16
# model); counting their traffic would model a machine with no fusion at all.
# Their inputs/outputs are still charged at the surrounding dot/fusion/
# reduce boundaries.
_FUSED_AWAY_OPS = {
    "convert", "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "compare", "select", "and", "or", "not", "xor", "negate", "abs", "sign",
    "tanh", "exponential", "log", "sqrt", "rsqrt", "power", "cosine", "sine",
    "floor", "ceil", "round-nearest-even", "round-nearest-afz", "clamp",
    "is-finite", "exponential-minus-one", "log-plus-one", "logistic", "atan2",
    "remainder", "shift-left", "shift-right-logical", "shift-right-arithmetic",
    "reduce-precision", "real", "imag", "slice", "reverse", "transpose",
    "copy", "pad",
}

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*(?:e[0-9a-z]+)?)\[([0-9,]*)\]")
_NAME_RE = re.compile(r"%([\w.\-]+)")
_OPLINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^=]*?\))|(?:\S+))\s+([\w\-]+)\("
)
_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CALLS_RE = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")
_COND_BRANCH_RE = re.compile(r"(?:branch_computations|true_computation|false_computation)=\{?([^,}]+)\}?")
_LHS_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def _shape_elems(dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n


def _all_shape_bytes(segment: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(segment):
        if dt in _DTYPE_BYTES:
            total += _shape_elems(dims) * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class _Op:
    name: str
    opcode: str
    result_seg: str
    operand_seg: str
    attr_seg: str


class HloCostModel:
    def __init__(self, hlo_text: str):
        self.computations: dict[str, list[_Op]] = {}
        self.entry: str | None = None
        self._shapes: dict[tuple[str, str], str] = {}  # (comp, op) -> result seg
        self._parse(hlo_text)
        self._memo: dict[str, tuple[float, float, dict, dict]] = {}
        self.warnings: list[str] = []

    # ---------------- parsing ----------------
    _COMMENT_RE = re.compile(r"/\*.*?\*/")

    def _parse(self, text: str) -> None:
        comp = None
        for raw in text.splitlines():
            line = self._COMMENT_RE.sub("", raw).rstrip()
            hdr = _COMP_HDR_RE.match(line)
            if hdr:
                comp = hdr.group(2)
                self.computations[comp] = []
                if hdr.group(1):
                    self.entry = comp
                continue
            if comp is None or "=" not in line:
                continue
            m = _OPLINE_RE.match(line)
            if not m:
                continue
            name, result_seg, opcode = m.group(1), m.group(2), m.group(3)
            rest = line[m.end() - 1 :]
            depth, end = 0, len(rest)
            for i, ch in enumerate(rest):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            operand_seg = rest[: end + 1]
            attr_seg = rest[end + 1 :]
            self.computations[comp].append(
                _Op(name, opcode, result_seg, operand_seg, attr_seg)
            )
            self._shapes[(comp, name)] = result_seg

    # ---------------- cost walking ----------------
    @staticmethod
    def _group_size(attr_seg: str) -> int:
        m = _GROUPS_LIST_RE.search(attr_seg)
        if m:
            return len([x for x in m.group(1).split(",") if x.strip()])
        m = _GROUPS_IOTA_RE.search(attr_seg)
        if m:
            return int(m.group(2))  # [n_groups, group_size]
        return 2

    @staticmethod
    def _wire_factor(op: str, g: int) -> float:
        """Bytes on the wire per device, as a multiple of operand bytes."""
        if g <= 1:
            return 0.0
        return {
            "all-gather": g - 1.0,               # operand is the local shard
            "reduce-scatter": (g - 1.0) / g,     # operand is the full buffer
            "all-reduce": 2.0 * (g - 1.0) / g,   # ring: reduce + broadcast
            "all-to-all": (g - 1.0) / g,
            "collective-permute": 1.0,
        }.get(op, 1.0)

    def _dot_flops(self, comp: str, op: _Op) -> float:
        result_elems = 0
        for dt, dims in _SHAPE_RE.findall(op.result_seg):
            if dt in _DTYPE_BYTES:
                result_elems += _shape_elems(dims)
        cm = _LHS_CDIMS_RE.search(op.attr_seg)
        contract = 1
        if cm:
            idxs = [int(x) for x in cm.group(1).split(",") if x.strip()]
            opnames = _NAME_RE.findall(op.operand_seg)
            if opnames:
                lhs_seg = self._shapes.get((comp, opnames[0]), "")
                sm = _SHAPE_RE.search(lhs_seg)
                if sm:
                    dims = [int(x) for x in sm.group(2).split(",") if x.strip()]
                    for i in idxs:
                        if i < len(dims):
                            contract *= dims[i]
        return 2.0 * result_elems * contract

    def _operand_bytes(self, comp: str, op: _Op) -> int:
        total = 0
        for nm in _NAME_RE.findall(op.operand_seg):
            seg = self._shapes.get((comp, nm))
            if seg:
                total += _all_shape_bytes(seg)
        return total

    def _fusion_bytes(self, comp: str, op: _Op, called: str | None) -> int:
        """Fusion-boundary traffic with slice-awareness.

        A fusion that merely dynamic-slices a big operand (one chunk out of
        a scanned stack) reads only the slice, not the buffer. For each
        operand whose every consumer inside the fused computation is a
        dynamic-slice / slice / gather, charge the sliced result size
        instead.
        """
        result = _all_shape_bytes(op.result_seg)
        opnames = _NAME_RE.findall(op.operand_seg)
        full = [
            _all_shape_bytes(self._shapes.get((comp, nm), "")) for nm in opnames
        ]
        charged = list(full)
        if called and called in self.computations:
            body = self.computations[called]
            params: dict[int, str] = {}
            for o in body:
                if o.opcode == "parameter":
                    m = re.match(r"\((\d+)\)", o.operand_seg.strip())
                    if m:
                        params[int(m.group(1))] = o.name
            for i in range(len(opnames)):
                pname = params.get(i)
                if pname is None or full[i] < (1 << 20):
                    continue  # only worth it for big buffers
                slice_bytes = 0
                ok = True
                for o in body:
                    if o.opcode == "parameter":
                        continue
                    if f"%{pname}" in o.operand_seg or f"({pname}" in o.operand_seg:
                        if o.opcode in ("dynamic-slice", "slice", "gather"):
                            slice_bytes = max(
                                slice_bytes, _all_shape_bytes(o.result_seg)
                            )
                        else:
                            ok = False
                            break
                if ok and slice_bytes:
                    charged[i] = slice_bytes
        total = result + sum(charged)
        name_l = op.name.lower()
        if any(h in name_l for h in self._INPLACE_HINTS):
            if result in full:
                total -= 2 * result  # aliased in/out buffer
        return max(total, 0)

    _INPLACE_HINTS = ("dynamic-update-slice", "scatter")

    def _inplace_aware_bytes(self, comp: str, op: _Op) -> int:
        """Operand+result traffic, modeling in-place buffer aliasing.

        dynamic-update-slice / scatter (standalone or as the root of a
        fusion) update a buffer in place on TPU: the big aliased operand is
        neither fully read nor fully rewritten — only the update region
        moves. We subtract the aliased pair (one operand whose size equals
        the result) and charge the remaining operands (the update payload).
        """
        result = _all_shape_bytes(op.result_seg)
        operands = []
        for nm in _NAME_RE.findall(op.operand_seg):
            seg = self._shapes.get((comp, nm))
            if seg:
                operands.append(_all_shape_bytes(seg))
        total = result + sum(operands)
        name_l = op.name.lower()
        if op.opcode in self._INPLACE_HINTS or any(
            h in name_l for h in self._INPLACE_HINTS
        ):
            if result in operands:
                total -= 2 * result  # aliased in/out buffer
        return max(total, 0)

    def _analyze_comp(self, comp: str):
        """Returns (flops, hbm_bytes, coll_bytes, coll_counts, wire_bytes)."""
        if comp in self._memo:
            return self._memo[comp]
        zero = {k: 0.0 for k in COLLECTIVE_OPS}
        self._memo[comp] = (0.0, 0.0, dict(zero), dict(zero), dict(zero))  # cycle guard
        flops = 0.0
        hbm = 0.0
        coll_b = dict(zero)
        coll_n = dict(zero)
        coll_w = dict(zero)

        def merge(mult, bf, bb, bc, bn, bw):
            nonlocal flops, hbm
            flops += mult * bf
            hbm += mult * bb
            for k in COLLECTIVE_OPS:
                coll_b[k] += mult * bc.get(k, 0.0)
                coll_n[k] += mult * bn.get(k, 0.0)
                coll_w[k] += mult * bw.get(k, 0.0)

        for op in self.computations.get(comp, ()):
            base = op.opcode.replace("-start", "").replace("-done", "")
            if op.opcode == "dot":
                flops += self._dot_flops(comp, op)
            if base in COLLECTIVE_OPS:
                if op.opcode.endswith("-done"):
                    continue  # paired with -start
                b = self._operand_bytes(comp, op) or _all_shape_bytes(op.result_seg)
                coll_b[base] += b
                coll_n[base] += 1
                coll_w[base] += b * self._wire_factor(base, self._group_size(op.attr_seg))
                hbm += self._operand_bytes(comp, op) + _all_shape_bytes(op.result_seg)
                continue
            if op.opcode == "while":
                bm = re.search(r"body=%?([\w.\-]+)", op.attr_seg)
                tm = _TRIP_RE.search(op.attr_seg)
                trips = int(tm.group(1)) if tm else 1
                if not tm:
                    self.warnings.append(f"while {op.name}: unknown trip count, using 1")
                if bm:
                    merge(trips, *self._analyze_comp(bm.group(1)))
                continue
            if op.opcode == "fusion":
                cm = re.search(r"calls=%?([\w.\-]+)", op.attr_seg)
                if cm:
                    bf, _, _, _, _ = self._analyze_comp(cm.group(1))
                    flops += bf  # fusion internals: flops yes, bytes no
                hbm += self._fusion_bytes(comp, op, cm.group(1) if cm else None)
                continue
            if op.opcode in ("call", "async-start"):
                cm = re.search(r"(?:to_apply|calls)=%?([\w.\-]+)", op.attr_seg)
                if cm:
                    merge(1, *self._analyze_comp(cm.group(1)))
                continue
            if op.opcode == "conditional":
                for cm in re.findall(r"%([\w.\-]+)", op.attr_seg):
                    if cm in self.computations:
                        merge(1, *self._analyze_comp(cm))
                continue
            if op.opcode in _FREE_OPS or op.opcode in _FUSED_AWAY_OPS:
                continue
            # generic compute op: operands + result traffic
            hbm += self._inplace_aware_bytes(comp, op)
        out = (flops, hbm, coll_b, coll_n, coll_w)
        self._memo[comp] = out
        return out

    def analyze(self) -> dict:
        if not self.entry:
            raise ValueError("no ENTRY computation found")
        flops, hbm, coll_b, coll_n, coll_w = self._analyze_comp(self.entry)
        return {
            "flops": flops,
            "hbm_bytes": hbm,
            "collective_bytes": {k: int(v) for k, v in coll_b.items()},
            "collective_counts": {k: int(v) for k, v in coll_n.items()},
            "collective_total_bytes": int(sum(coll_b.values())),
            "wire_bytes": {k: int(v) for k, v in coll_w.items()},
            "wire_total_bytes": int(sum(coll_w.values())),
            "warnings": self.warnings[:20],
        }


def parse_collectives(hlo_text: str):
    """Back-compat helper: loop-aware collective stats."""
    model = HloCostModel(hlo_text)
    res = model.analyze()

    @dataclasses.dataclass
    class CollectiveStats:
        bytes_by_op: dict
        count_by_op: dict

        @property
        def total_bytes(self) -> int:
            return sum(self.bytes_by_op.values())

    return CollectiveStats(res["collective_bytes"], res["collective_counts"])


def roofline_terms(
    *,
    flops: float,
    hbm_bytes: float,
    collective_bytes_per_device: float,
    n_devices: int,
    peak_flops: float,
    hbm_bw: float,
    ici_bw: float,
) -> dict:
    """All inputs are per-device (the partitioned module's shard shapes)."""
    compute_s = flops / peak_flops
    memory_s = hbm_bytes / hbm_bw
    collective_s = collective_bytes_per_device / ici_bw
    terms = {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
    }
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    terms.update(
        dominant=dom,
        step_lower_bound_s=bound,
        roofline_fraction=(compute_s / bound) if bound > 0 else 0.0,
        global_flops=flops * n_devices,
        global_collective_bytes=collective_bytes_per_device * n_devices,
    )
    return terms
