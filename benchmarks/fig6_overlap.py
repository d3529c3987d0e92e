"""Paper Fig 6: communication/computation overlap, from the compiled HLO.

Without real hardware, overlap is a *structural* property of the schedule:
a collective overlaps compute iff its start has no data dependence on the
compute issued beside it. We lower both samplers on an 8-way mesh and
compare:

  - collective op mix: the ring issues P collective-permutes of one block
    each (pipelinable); the sync version one bulk all-gather (blocking);
  - bytes on the wire per sweep;
  - overlap structure: in the ring's scanned body the permute's operand is
    the *incoming* block, not this step's syrk output -> the DAG admits
    full comm/compute overlap (the paper's "both" region), while the
    all-gather dominates a serial prologue.

Reported: collective bytes, counts, and the dependence check, per mode.
"""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

from benchmarks.common import csv_row

SRC = str(Path(__file__).resolve().parents[1] / "src")

_WORKER = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
os.environ['JAX_PLATFORMS'] = 'cpu'  # simulated host devices, never the chip
import sys, json, re
sys.path.insert(0, {src!r})
import jax
from repro.data import chembl_like, train_test_split
from repro.core.distributed import DistributedBPMF
from repro.launch.hlo_analysis import HloCostModel


def in_loop_permute(txt):
    # dependence check: a collective-permute INSIDE a while body is the
    # pipelined exchange (one block forwarded per scan step, overlappable
    # with that step's syrk); a bulk all-gather sits in straight-line code.
    # Parse the computations named as `body=` of some while op and look for
    # the permute inside those blocks only.
    bodies = set(re.findall(r"body=%?([\w.\-]+)", txt))
    cur = None
    found = False
    for line in txt.splitlines():
        ls = line.rstrip()
        if not line[:1].isspace() and ls.endswith("{{") and "(" in ls:
            # computation header: `%name (params...) -> type {{` (or ENTRY)
            tok = ls.split()[1] if ls.startswith("ENTRY") else ls.split()[0]
            cur = tok.lstrip("%").split("(")[0]
        elif ls == "}}":
            cur = None
        elif " collective-permute(" in line and cur in bodies:
            found = True
    return found


ratings, _, _ = chembl_like(scale=0.002, seed=0)
train, test = train_test_split(ratings, 0.05, seed=1)
out = {{}}
for mode in ("ring", "allgather", "async"):
    s = DistributedBPMF(train, test, k=32, alpha=1.5, mode=mode, width=32)
    st = s.init(0)
    lowered = s._sweep.lower(st, *s._plan_args)
    txt = lowered.compile().as_text()
    res = HloCostModel(txt).analyze()
    out[mode] = {{
        "collective_bytes": res["collective_bytes"],
        "collective_counts": res["collective_counts"],
        "flops": res["flops"],
        "in_loop_permute": in_loop_permute(txt),
    }}
print(json.dumps(out))
"""


def main() -> list[str]:
    code = _WORKER.format(src=SRC)
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900,
    )
    if res.returncode != 0:
        raise RuntimeError(res.stderr[-2000:])
    out = json.loads(res.stdout.strip().splitlines()[-1])
    # structural gate: the ring (and the fused async ring) MUST schedule its
    # permutes inside the scanned while body — that dependence structure is
    # the whole overlap claim. The bulk all-gather must not.
    assert out["ring"]["in_loop_permute"], (
        "ring mode lost its pipelined collective-permute (no permute found "
        "inside a while body in the compiled HLO)"
    )
    assert out["async"]["in_loop_permute"], (
        "async mode lost its pipelined collective-permute"
    )
    rows = []
    for mode, d in out.items():
        total = sum(d["collective_bytes"].values())
        counts = {k: v for k, v in d["collective_counts"].items() if v}
        rows.append(csv_row(
            f"fig6_{mode}_collectives", 0.0,
            f"bytes={total};counts={counts};flops={d['flops']:.3g};"
            f"in_loop_permute={d['in_loop_permute']}",
        ))
    ring = sum(out["ring"]["collective_bytes"].values())
    sync = sum(out["allgather"]["collective_bytes"].values())
    rows.append(csv_row(
        "fig6_ring_vs_sync_bytes_ratio", 0.0, f"{ring / max(sync, 1):.2f}"
    ))
    rows.append(csv_row(
        "fig6_ring_permutes_pipelined", 0.0,
        f"{out['ring']['collective_counts'].get('collective-permute', 0)}",
    ))
    return rows


if __name__ == "__main__":
    for r in main():
        print(r)
