"""Sharding-layout pass: init-vs-step layout drift and donation hazards.

* **state-sharding** — the PR 6 bug class: `DistributedBPMF.init()` once
  assembled the sweep state without explicit shardings, so the state the
  first jitted sweep *returned* carried different layouts than the state
  `init()` produced — and the second sweep silently recompiled, putting
  XLA compile time inside a timed window.  The pass finds the state
  types that flow through ``shard_map`` (constructor calls returned by the
  mapped function), then flags any field of such a constructor inside an
  ``init*`` function whose value is not layout-pinned: accepted forms are
  ``jax.device_put(...)`` / ``with_sharding_constraint(...)`` calls, local
  names bound to one, ``None``, and conditionals over those.  Spec-tree
  constructions (``DistState(u=P(AXIS), ...)``) live outside ``init*``
  functions and are not touched.

* **donated-reuse** — a jitted callable built with ``donate_argnums`` /
  ``donate_argnames`` invalidates the donated operand buffers at the call;
  reading such an argument after the call is use-after-free that XLA only
  sometimes rejects.  The pass tracks names bound to donating ``jax.jit``
  results and flags loads of a donated argument on lines after the call
  within the same function.  Only direct calls of the jitted name count —
  ``jitted.lower(...)`` does not execute and donates nothing.
"""
from __future__ import annotations

import ast

from repro.analysis.common import Finding, SourceFile, call_name, scope_of

RULES = ("state-sharding", "donated-reuse")

_PIN_CALLS = frozenset({
    "device_put", "device_put_replicated", "device_put_sharded",
    "with_sharding_constraint",
})


def _leaf(name: str | None) -> str | None:
    return name.rsplit(".", 1)[-1] if name else None


def _is_pin_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and _leaf(call_name(node)) in _PIN_CALLS)


# ---------------------------------------------------------------------------
# state-sharding
# ---------------------------------------------------------------------------
def _mapped_functions(sf: SourceFile) -> list[ast.AST]:
    """Function bodies passed as the first argument of *shard_map calls —
    Lambda nodes inline, Names resolved to same-file defs."""
    by_name: dict[str, ast.AST] = {}
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, node)
    out: list[ast.AST] = []
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        leaf = _leaf(call_name(node))
        if leaf is None or not leaf.lstrip("_").startswith("shard_map"):
            continue
        if not node.args:
            continue
        fn = node.args[0]
        if isinstance(fn, ast.Lambda):
            out.append(fn)
        elif isinstance(fn, ast.Name) and fn.id in by_name:
            out.append(by_name[fn.id])
    return out


def _state_types(mapped: list[ast.AST]) -> set[str]:
    """Capitalized constructor names the mapped functions return — the
    pytree state types whose layout must match between init and step."""
    types: set[str] = set()
    for fn in mapped:
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Return) and node.value is not None):
                continue
            vals = (node.value.elts
                    if isinstance(node.value, (ast.Tuple, ast.List))
                    else [node.value])
            for val in vals:
                if (isinstance(val, ast.Call)
                        and isinstance(val.func, ast.Name)
                        and val.func.id[:1].isupper()):
                    types.add(val.func.id)
    return types


def _local_assigns(func: ast.AST) -> dict[str, list[ast.AST]]:
    out: dict[str, list[ast.AST]] = {}
    for node in ast.walk(func):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    out.setdefault(tgt.id, []).append(node.value)
    return out


def _pinned(value: ast.AST, assigns: dict[str, list[ast.AST]],
            depth: int = 3) -> bool | None:
    """True: value carries an explicit sharding.  False: provably does not.
    None: can't tell (parameters, attributes, imports) — stay silent."""
    if depth <= 0:
        return None
    if _is_pin_call(value):
        return True
    if isinstance(value, ast.Constant) and value.value is None:
        return True  # absent optional field, no buffer to mislay
    if isinstance(value, ast.IfExp):
        a = _pinned(value.body, assigns, depth - 1)
        b = _pinned(value.orelse, assigns, depth - 1)
        if a is True and b is True:
            return True
        if a is False or b is False:
            return False
        return None
    if isinstance(value, ast.Name):
        srcs = assigns.get(value.id)
        if not srcs:
            return None  # parameter / closure / import: unknown provenance
        verdicts = [_pinned(s, assigns, depth - 1) for s in srcs]
        if all(v is True for v in verdicts):
            return True
        if any(v is False for v in verdicts):
            return False
        return None
    if isinstance(value, (ast.Call, ast.BinOp, ast.UnaryOp)):
        return False  # computed on the fly, layout left to XLA's default
    return None


def _check_state_sharding(sf: SourceFile) -> list[Finding]:
    mapped = _mapped_functions(sf)
    if not mapped:
        return []
    types = _state_types(mapped)
    if not types:
        return []
    mapped_ids = {id(m) for m in mapped}

    findings: list[Finding] = []
    for func in ast.walk(sf.tree):
        if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not func.name.startswith("init"):
            continue
        # an init nested inside the mapped body is traced, not host-side
        cur = sf.parent(func)
        inside_mapped = False
        while cur is not None:
            if id(cur) in mapped_ids:
                inside_mapped = True
                break
            cur = sf.parent(cur)
        if inside_mapped:
            continue
        assigns = _local_assigns(func)
        for node in ast.walk(func):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in types):
                continue
            fields = [(kw.arg, kw.value) for kw in node.keywords if kw.arg]
            fields += [(f"<arg{i}>", a) for i, a in enumerate(node.args)]
            for fname, fval in fields:
                if _pinned(fval, assigns) is False:
                    findings.append(Finding(
                        sf.rel, fval.lineno, fval.col_offset,
                        "state-sharding",
                        f"field {fname!r} of shard_map state "
                        f"{node.func.id!r} is built in {func.name}() without "
                        "an explicit sharding (device_put / "
                        "with_sharding_constraint) — init and step layouts "
                        "diverge and the second step silently recompiles",
                        scope_of(sf, fval)))
    return findings


# ---------------------------------------------------------------------------
# donated-reuse
# ---------------------------------------------------------------------------
def _donating_jit(node: ast.AST) -> tuple[tuple[int, ...], tuple[str, ...]]:
    """(donated positions, donated names) when `node` is a jax.jit call with
    donation configured; empty tuples otherwise."""
    if not (isinstance(node, ast.Call) and _leaf(call_name(node)) == "jit"):
        return (), ()
    nums: tuple[int, ...] = ()
    names: tuple[str, ...] = ()
    for kw in node.keywords:
        if kw.arg == "donate_argnums":
            if isinstance(kw.value, ast.Constant) and isinstance(
                    kw.value.value, int):
                nums = (kw.value.value,)
            elif isinstance(kw.value, (ast.Tuple, ast.List)):
                vals = [e.value for e in kw.value.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, int)]
                nums = tuple(vals)
        elif kw.arg == "donate_argnames":
            if isinstance(kw.value, ast.Constant) and isinstance(
                    kw.value.value, str):
                names = (kw.value.value,)
            elif isinstance(kw.value, (ast.Tuple, ast.List)):
                names = tuple(e.value for e in kw.value.elts
                              if isinstance(e, ast.Constant)
                              and isinstance(e.value, str))
    return nums, names


def _check_donated_reuse(sf: SourceFile) -> list[Finding]:
    findings: list[Finding] = []
    for func in ast.walk(sf.tree):
        if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        donors: dict[str, tuple[tuple[int, ...], tuple[str, ...]]] = {}
        for node in ast.walk(func):
            if not isinstance(node, ast.Assign):
                continue
            nums, names = _donating_jit(node.value)
            if not nums and not names:
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    donors[tgt.id] = (nums, names)
        if not donors:
            continue
        for node in ast.walk(func):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in donors):
                continue
            nums, names = donors[node.func.id]
            donated: list[str] = []
            for pos in nums:
                if pos < len(node.args) and isinstance(node.args[pos],
                                                       ast.Name):
                    donated.append(node.args[pos].id)
            for kw in node.keywords:
                if kw.arg in names and isinstance(kw.value, ast.Name):
                    donated.append(kw.value.id)
            if not donated:
                continue
            call_line = node.end_lineno or node.lineno
            # `state = step(state)` rebinds the name to the *result*; later
            # loads see the fresh buffer, not the donated one
            rebound = {
                tgt.id
                for sub in ast.walk(func) if isinstance(sub, ast.Assign)
                for tgt in sub.targets
                if isinstance(tgt, ast.Name) and tgt.lineno >= node.lineno
            }
            donated = [d for d in donated if d not in rebound]
            for later in ast.walk(func):
                if (isinstance(later, ast.Name)
                        and isinstance(later.ctx, ast.Load)
                        and later.id in donated
                        and later.lineno > call_line):
                    findings.append(Finding(
                        sf.rel, later.lineno, later.col_offset,
                        "donated-reuse",
                        f"{later.id!r} was donated to {node.func.id}() on "
                        f"line {node.lineno} and read again here — the "
                        "buffer may already be reused by XLA",
                        scope_of(sf, later)))
                    break  # one finding per donated call is enough
    return findings


def run(sf: SourceFile) -> list[Finding]:
    return _check_state_sharding(sf) + _check_donated_reuse(sf)
