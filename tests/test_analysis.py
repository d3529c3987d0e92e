"""repro-lint (`python -m repro.analysis`): per-rule fixture snippets
(positive, negative, suppression), baseline round-trip, CLI exit codes,
and the meta-test that the analyzer runs clean on this repo's live tree
against the checked-in baseline."""
import json
import textwrap
from pathlib import Path

import pytest

from repro.analysis import ALL_RULES, RULE_DOCS, analyze_source, main
from repro.analysis import baseline as baseline_mod
from repro.analysis.pallas import RULES as PALLAS_RULES

ROOT = Path(__file__).resolve().parents[1]


def src(code: str) -> str:
    return textwrap.dedent(code).lstrip("\n")


def rules_of(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# pass 1: lock discipline
# ---------------------------------------------------------------------------
GUARDED = src("""
    import threading

    class Coord:
        def __init__(self):
            self._lock = threading.Lock()
            self.epoch = 0

        def commit(self):
            with self._lock:
                self.epoch += 1

        def peek(self):
            return self.epoch
""")


def test_guarded_field_positive():
    (f,) = analyze_source(GUARDED, rules=["guarded-field"])
    assert f.rule == "guarded-field"
    assert f.scope == "Coord.peek"
    assert "'self.epoch'" in f.message and "_lock" in f.message


def test_guarded_field_locked_read_is_clean():
    ok = GUARDED.replace(
        "    def peek(self):\n        return self.epoch",
        "    def peek(self):\n        with self._lock:\n"
        "            return self.epoch",
    )
    assert ok != GUARDED
    assert analyze_source(ok, rules=["guarded-field"]) == []


def test_guarded_field_constructor_exempt():
    # the unlocked write in __init__ must neither flag nor poison inference
    code = GUARDED + src("""
        class Boot:
            def __init__(self):
                self._lock = threading.Lock()
                self.x = 0
                self.x = 1
    """)
    (f,) = analyze_source(code, rules=["guarded-field"])
    assert f.scope == "Coord.peek"


def test_guarded_field_mutator_call_counts_as_write():
    code = src("""
        import threading

        class Q:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []

            def push(self, v):
                with self._lock:
                    self.items.append(v)

            def drain(self):
                return list(self.items)
    """)
    (f,) = analyze_source(code, rules=["guarded-field"])
    assert f.scope == "Q.drain"


def test_guarded_field_condition_alias_holds_the_lock():
    code = src("""
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._cond = threading.Condition(self._lock)
                self.n = 0

            def bump(self):
                with self._lock:
                    self.n += 1

            def wait_n(self):
                with self._cond:
                    return self.n
    """)
    assert analyze_source(code, rules=["guarded-field"]) == []


def test_guarded_field_nested_def_resets_held():
    # a thread target defined under `with lock` runs later, without it
    code = src("""
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def go(self):
                with self._lock:
                    self.n = 1
                    def worker():
                        return self.n
                    return worker
    """)
    (f,) = analyze_source(code, rules=["guarded-field"])
    # findings are keyed to the defining method's scope
    assert f.scope == "C.go"
    assert "read of 'self.n'" in f.message


LOCKED_CALL = src("""
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()

        def _pick_locked(self):
            return 1

        def good(self):
            with self._lock:
                return self._pick_locked()

        def also_good_locked(self):
            return self._pick_locked()

        def bad(self):
            return self._pick_locked()
""")


def test_locked_call_positive_and_convention_negative():
    (f,) = analyze_source(LOCKED_CALL, rules=["locked-call"])
    assert f.scope == "C.bad"
    assert "_pick_locked" in f.message


def test_lock_reacquire_flags_plain_lock_only():
    code = src("""
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def _step_locked(self):
                with self._lock:
                    return 1

        class B:
            def __init__(self):
                self._lock = threading.RLock()

            def _step_locked(self):
                with self._lock:
                    return 1
    """)
    (f,) = analyze_source(code, rules=["lock-reacquire"])
    assert f.scope == "A._step_locked"
    assert "deadlock" in f.message


# ---------------------------------------------------------------------------
# pass 2: retrace hazards
# ---------------------------------------------------------------------------
def test_traced_branch_positive_decorator_form():
    code = src("""
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """)
    (f,) = analyze_source(code, rules=["traced-branch"])
    assert "branches in Python" in f.message and "'x'" in f.message


def test_traced_branch_static_and_shape_exemptions():
    code = src("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("mode",))
        def f(x, mode, y=None):
            if mode == "fast":            # static: exempt
                return x
            if x.shape[0] > 2:            # shape projection: exempt
                pass
            if y is None:                 # trace-time None check: exempt
                return x
            for _ in range(len(x)):       # len(): exempt
                pass
            return x + y
    """)
    assert analyze_source(code, rules=["traced-branch"]) == []


def test_traced_branch_container_annotation_exempt():
    # pytree STRUCTURE is part of the jit cache key (serve/foldin.py)
    code = src("""
        import jax

        @jax.jit
        def f(arrays: tuple, x):
            for a in arrays:
                x = x + a
            for b in x:
                pass
            return x
    """)
    (f,) = analyze_source(code, rules=["traced-branch"])
    assert "'x'" in f.message


def test_shape_leak_positive_and_fstring():
    code = src("""
        import jax

        @jax.jit
        def f(x):
            n = int(x)
            name = f"val={x}"
            safe = int(x.shape[0])
            return n, name, safe
    """)
    found = analyze_source(code, rules=["shape-leak"])
    assert rules_of(found) == ["shape-leak", "shape-leak"]
    assert "int(...)" in found[0].message
    assert "f-string" in found[1].message


def test_static_args_typo_and_unhashable_call_site():
    code = src("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("shap",))
        def f(x, shape):
            return x

        def caller(x):
            return f(x, shape=[1, 2])
    """)
    found = analyze_source(code, rules=["static-args"])
    # the typo'd name is reported; the call site is not (the typo'd name
    # is what got pinned, right or wrong)
    assert any("'shap' is not a parameter" in f.message for f in found)


def test_static_args_unhashable_value():
    code = src("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("widths",))
        def f(x, widths):
            return x

        def caller(x):
            return f(x, widths=[8, 16])
    """)
    found = analyze_source(code, rules=["static-args"])
    assert len(found) == 1 and "unhashable" in found[0].message


def test_static_args_non_literal_argnums():
    code = src("""
        import jax

        NUMS = (1,)

        @jax.jit(static_argnums=NUMS)
        def f(x, n):
            return x
    """)
    (f,) = analyze_source(code, rules=["static-args"])
    assert "literal" in f.message


def test_bound_method_jit_assignment_is_recognized():
    code = src("""
        import jax

        class Sweeper:
            def __init__(self):
                self._sweep = jax.jit(self._sweep_impl)

            def _sweep_impl(self, state):
                if state:
                    return state
                return state
    """)
    (f,) = analyze_source(code, rules=["traced-branch"])
    assert "'state'" in f.message


# ---------------------------------------------------------------------------
# pass 3: device sync under a coordinator lock
# ---------------------------------------------------------------------------
def test_sync_under_lock_positive_and_negative():
    code = src("""
        import threading
        import jax.numpy as jnp

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def bad(self, x):
                with self._lock:
                    return jnp.asarray(x)

            def good(self, x):
                y = jnp.asarray(x)
                with self._lock:
                    return y
    """)
    (f,) = analyze_source(code, rules=["sync-under-lock"])
    assert f.scope == "C.bad"


def test_sync_under_lock_tree_util_allowlisted():
    code = src("""
        import threading
        import jax

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def ok(self, x):
                with self._lock:
                    return jax.tree_util.tree_map(lambda a: a, x)
    """)
    assert analyze_source(code, rules=["sync-under-lock"]) == []


# ---------------------------------------------------------------------------
# pass 4: PRNG key discipline
# ---------------------------------------------------------------------------
def test_prng_reuse_positive():
    code = src("""
        import jax

        def draw(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.normal(key, (3,))
            return a + b
    """)
    (f,) = analyze_source(code, rules=["prng-reuse"])
    assert "'key'" in f.message and f.line == 5


def test_prng_split_between_uses_is_clean():
    code = src("""
        import jax

        def draw(key):
            key, sub = jax.random.split(key)
            a = jax.random.normal(sub, (3,))
            key, sub = jax.random.split(key)
            b = jax.random.normal(sub, (3,))
            return a + b
    """)
    assert analyze_source(code, rules=["prng-reuse"]) == []


def test_prng_sibling_branches_do_not_taint_each_other():
    code = src("""
        import jax

        def draw(key, fast):
            if fast:
                return jax.random.normal(key, (3,))
            else:
                return jax.random.uniform(key, (3,))
    """)
    assert analyze_source(code, rules=["prng-reuse"]) == []


def test_prng_early_return_arm_excluded_from_merge():
    # the core/distributed.py sweep shape: the async arm consumes the
    # keys and returns; the sync path below is mutually exclusive with it
    code = src("""
        import jax

        def sweep(key, mode):
            k1, k2 = jax.random.split(key)
            if mode == "async":
                return jax.random.normal(k1, (3,)) + jax.random.normal(k2, (3,))
            a = jax.random.normal(k1, (3,))
            return a + jax.random.normal(k2, (3,))
    """)
    assert analyze_source(code, rules=["prng-reuse"]) == []


def test_prng_fallthrough_arm_still_taints():
    code = src("""
        import jax

        def sweep(key, warm):
            if warm:
                a = jax.random.normal(key, (3,))
            return jax.random.normal(key, (3,))
    """)
    (f,) = analyze_source(code, rules=["prng-reuse"])
    assert f.line == 6


def test_prng_loop_carried_reuse():
    code = src("""
        import jax

        def draws(key, n):
            out = []
            for _ in range(n):
                out.append(jax.random.normal(key, (3,)))
            return out
    """)
    (f,) = analyze_source(code, rules=["prng-reuse"])
    assert f.line == 6


def test_prng_per_iteration_split_ledger_is_clean():
    code = src("""
        import jax

        def draws(key, n):
            out = []
            for _ in range(n):
                key, sub = jax.random.split(key)
                out.append(jax.random.normal(sub, (3,)))
            return out
    """)
    assert analyze_source(code, rules=["prng-reuse"]) == []


def test_prng_fold_in_and_validators_do_not_consume():
    code = src("""
        import jax

        def fan_out(key, ids):
            _check_args(key, ids)
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(ids)
            return jax.random.normal(key, (3,)), keys
    """)
    assert analyze_source(code, rules=["prng-reuse"]) == []


def test_prng_randint_selection_counts_as_consumption():
    # the SGLD minibatch pattern: row selection via jax.random.randint is
    # a draw like any other — reusing its key for the noise must flag
    code = src("""
        import jax

        def step(key, factors):
            rows = jax.random.randint(key, (4,), 0, 10)
            return rows, jax.random.normal(key, factors.shape)
    """)
    (f,) = analyze_source(code, rules=["prng-reuse"])
    assert "'key'" in f.message and f.line == 5


def test_prng_per_bucket_fold_in_chain_is_clean():
    # core/sgld.py's bucket loop: fold_in derives an independent stream
    # per bucket without consuming the parent key
    code = src("""
        import jax

        def minibatch(key, buckets):
            out = []
            for b in range(len(buckets)):
                kb = jax.random.fold_in(key, b)
                out.append(jax.random.randint(kb, (4,), 0, 10))
            return out
    """)
    assert analyze_source(code, rules=["prng-reuse"]) == []


def test_prng_stateful_numpy_generator_not_tracked():
    code = src("""
        import numpy as np

        def fixture():
            rng = np.random.default_rng(0)
            a = make(rng)
            b = make(rng)
            return a, b
    """)
    assert analyze_source(code, rules=["prng-reuse"]) == []


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------
def test_suppression_comment_silences_one_rule():
    flagged = GUARDED.replace(
        "        return self.epoch",
        "        return self.epoch  # repro-lint: disable=guarded-field (snapshot read)",
    )
    assert flagged != GUARDED
    assert analyze_source(flagged) == []
    # a different rule on the same line is NOT silenced
    wrong = GUARDED.replace(
        "        return self.epoch",
        "        return self.epoch  # repro-lint: disable=prng-reuse",
    )
    assert rules_of(analyze_source(wrong)) == ["guarded-field"]


# ---------------------------------------------------------------------------
# baseline round-trip + CLI
# ---------------------------------------------------------------------------
def test_baseline_round_trip(tmp_path):
    target = tmp_path / "mod.py"
    target.write_text(GUARDED)
    base_file = tmp_path / "base.json"

    args = [str(target), "--root", str(tmp_path), "--baseline", str(base_file)]
    assert main(args) == 1                      # finding, no baseline yet
    assert main([*args, "--write-baseline"]) == 0
    assert main(args) == 0                      # grandfathered

    data = json.loads(base_file.read_text())
    assert data["version"] == baseline_mod.BASELINE_VERSION
    (key,) = data["findings"]
    assert key.startswith("mod.py::guarded-field::Coord.peek::")

    # baseline keys survive line churn but not edits to the flagged line
    target.write_text("# a new leading comment\n" + GUARDED)
    assert main(args) == 0
    target.write_text(GUARDED.replace("return self.epoch",
                                      "return self.epoch + 1"))
    assert main(args) == 1


def test_cli_exit_codes(tmp_path, capsys):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert main([str(clean), "--root", str(tmp_path)]) == 0
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ALL_RULES:
        assert rule in out
    assert main([str(clean), "--rules", "no-such-rule"]) == 2
    assert main([str(tmp_path / "missing.py")]) == 2

    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    assert main([str(bad), "--root", str(tmp_path)]) == 2


def test_cli_json_format(tmp_path, capsys):
    target = tmp_path / "mod.py"
    target.write_text(GUARDED)
    rc = main([str(target), "--root", str(tmp_path), "--format", "json"])
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["summary"] == {"guarded-field": 1}
    (finding,) = payload["findings"]
    assert finding["path"] == "mod.py"
    assert finding["rule"] == "guarded-field"


def test_rule_docs_cover_every_rule():
    assert set(RULE_DOCS) == set(ALL_RULES)


# ---------------------------------------------------------------------------
# meta: the live tree is clean modulo the checked-in baseline
# ---------------------------------------------------------------------------
def test_analyzer_clean_on_live_tree():
    """`python -m repro.analysis src tests` must exit 0 against the
    checked-in baseline — the same invocation the CI lint job gates on.
    A failure here means a new finding: fix it, suppress it in-line with a
    justification, or (last resort) regenerate the baseline."""
    rc = main([
        str(ROOT / "src"), str(ROOT / "tests"),
        "--root", str(ROOT),
        "--baseline", str(ROOT / baseline_mod.DEFAULT_BASELINE),
    ])
    assert rc == 0


# ---------------------------------------------------------------------------
# pass 5: collective discipline (SPMD)
# ---------------------------------------------------------------------------
RING = src("""
    import jax

    AXIS = "items"

    def exchange(blk, n_shards):
        fwd = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        return jax.lax.ppermute(blk, AXIS, fwd)
""")


def test_ppermute_ring_comprehension_is_clean():
    assert analyze_source(RING, rules=["ppermute-perm"]) == []


def test_ppermute_missing_wraparound_flagged():
    bad = RING.replace("(i + 1) % n_shards", "i + 1")
    assert bad != RING
    (f,) = analyze_source(bad, rules=["ppermute-perm"])
    assert f.rule == "ppermute-perm" and "wraparound" in f.message


def test_ppermute_wrong_ring_modulus_flagged():
    bad = RING.replace("% n_shards", "% (n_shards - 1)")
    assert bad != RING
    (f,) = analyze_source(bad, rules=["ppermute-perm"])
    assert "not a bijection" in f.message


def test_ppermute_literal_duplicate_dest_flagged():
    code = src("""
        import jax

        def exchange(blk):
            return jax.lax.ppermute(blk, "x", [(0, 1), (1, 1)])
    """)
    (f,) = analyze_source(code, rules=["ppermute-perm"])
    assert "destination" in f.message


def test_ppermute_dynamic_perm_is_skipped():
    code = src("""
        import jax

        def exchange(blk, perm):
            return jax.lax.ppermute(blk, "x", perm)
    """)
    assert analyze_source(code, rules=["ppermute-perm"]) == []


def test_collective_branch_one_armed_psum_flagged():
    code = src("""
        import jax

        def step(pred, x):
            return jax.lax.cond(
                pred,
                lambda v: jax.lax.psum(v, "items"),
                lambda v: v,
                x,
            )
    """)
    (f,) = analyze_source(code, rules=["collective-branch"])
    assert f.rule == "collective-branch" and "deadlock" in f.message


def test_collective_branch_balanced_arms_clean():
    code = src("""
        import jax

        def step(pred, x):
            return jax.lax.cond(
                pred,
                lambda v: jax.lax.psum(v * 2, "items"),
                lambda v: jax.lax.psum(v, "items"),
                x,
            )
    """)
    assert analyze_source(code, rules=["collective-branch"]) == []


def test_collective_branch_expands_same_file_helpers():
    # the collective hides two calls deep in a named arm: _stats -> psum
    code = src("""
        import jax

        def _stats(v):
            return jax.lax.psum(v, "items")

        def _draw(v):
            return _stats(v) + 1.0

        def step(pred, x):
            return jax.lax.cond(pred, _draw, lambda v: v, x)
    """)
    (f,) = analyze_source(code, rules=["collective-branch"])
    assert "psum" in f.message


def test_collective_branch_unresolvable_arm_skipped():
    code = src("""
        import jax
        from elsewhere import mystery_fn

        def step(pred, x):
            return jax.lax.cond(
                pred, mystery_fn, lambda v: jax.lax.psum(v, "i"), x)
    """)
    assert analyze_source(code, rules=["collective-branch"]) == []


def test_collective_axis_undeclared_flagged():
    code = src("""
        import jax

        AXIS = "items"

        def make(n):
            mesh = jax.make_mesh((n,), (AXIS,))
            return mesh

        def stats(x):
            return jax.lax.psum(x, "rows")
    """)
    (f,) = analyze_source(code, rules=["collective-axis"])
    assert "'rows'" in f.message and "items" in f.message


def test_collective_axis_resolves_module_constants():
    code = src("""
        import jax
        from jax.sharding import PartitionSpec as P

        AXIS = "items"
        SPEC = P(AXIS)

        def stats(x):
            return jax.lax.psum(x, AXIS)
    """)
    assert analyze_source(code, rules=["collective-axis"]) == []


def test_collective_axis_silent_without_declarations():
    # a helper module that takes axis_name from callers declares nothing:
    # the contract lives at the call sites, not here
    code = src("""
        import jax

        def compressed_psum(x, axis_name):
            return jax.lax.psum(x, axis_name)

        def hardcoded(x):
            return jax.lax.psum(x, "pod")
    """)
    assert analyze_source(code, rules=["collective-axis"]) == []


# ---------------------------------------------------------------------------
# pass 6: sharding layout
# ---------------------------------------------------------------------------
STATE_INIT = src("""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map

    AXIS = "items"

    class DistState(tuple):
        pass

    def make_sweep(mesh):
        def sweep(state, plans):
            return DistState(u=state.u, key=state.key)
        state_spec = DistState(u=P(AXIS), key=P())
        return shard_map(sweep, mesh=mesh, in_specs=(state_spec, P(AXIS)),
                         out_specs=state_spec)

    def init(mesh, key):
        sh = NamedSharding(mesh, P(AXIS))
        rep = NamedSharding(mesh, P())
        u = 0.1 * jax.random.normal(key, (8, 4))
        u_dev = jax.device_put(u, sh)
        return DistState(u=u_dev, key=jax.device_put(key, rep))
""")


def test_state_sharding_pinned_init_is_clean():
    assert analyze_source(STATE_INIT, rules=["state-sharding"]) == []


def test_state_sharding_bare_field_flagged():
    bad = STATE_INIT.replace("u=u_dev,", "u=u,")
    assert bad != STATE_INIT
    (f,) = analyze_source(bad, rules=["state-sharding"])
    assert f.rule == "state-sharding"
    assert "'u'" in f.message and "recompile" in f.message


def test_state_sharding_direct_call_field_flagged():
    bad = STATE_INIT.replace(
        "key=jax.device_put(key, rep)", "key=jax.random.split(key)")
    assert bad != STATE_INIT
    (f,) = analyze_source(bad, rules=["state-sharding"])
    assert "'key'" in f.message


def test_state_sharding_spec_tree_outside_init_exempt():
    # `state_spec = DistState(u=P(AXIS), ...)` in make_sweep stays silent:
    # only init* functions assemble device state
    found = analyze_source(STATE_INIT, rules=["state-sharding"])
    assert found == []
    optional = STATE_INIT.replace(
        "key=jax.device_put(key, rep))",
        "key=jax.device_put(key, rep) if mesh else None)")
    assert analyze_source(optional, rules=["state-sharding"]) == []


def test_state_sharding_catches_pr6_mutant_in_live_init():
    """Seeded mutant: delete the explicit shardings in DistributedBPMF.init()
    (the PR 6 silent-recompile bug) and the pass must catch it."""
    live = (ROOT / "src" / "repro" / "core" / "distributed.py").read_text()
    assert "u=jax.device_put(u, sh)," in live
    assert analyze_source(live, rules=["state-sharding"]) == []
    mutant = live.replace("u=jax.device_put(u, sh),", "u=u,")
    found = analyze_source(mutant, rules=["state-sharding"])
    assert [f.rule for f in found] == ["state-sharding"]
    assert "'u'" in found[0].message


def test_donated_reuse_flagged():
    code = src("""
        import jax
        import jax.numpy as jnp

        def run(f, state):
            step = jax.jit(f, donate_argnums=(0,))
            out = step(state)
            return out, jnp.sum(state)
    """)
    (f,) = analyze_source(code, rules=["donated-reuse"])
    assert f.rule == "donated-reuse" and "'state'" in f.message


def test_donated_reuse_rebind_idiom_clean():
    code = src("""
        import jax

        def run(f, state, n):
            step = jax.jit(f, donate_argnums=(0,))
            for _ in range(n):
                state = step(state)
            return state
    """)
    assert analyze_source(code, rules=["donated-reuse"]) == []


def test_donated_reuse_argnames_and_undonated_clean():
    code = src("""
        import jax
        import jax.numpy as jnp

        def run(f, state, other):
            step = jax.jit(f, donate_argnames=("state",))
            out = step(state=state, other=other)
            return out, jnp.sum(other)
    """)
    assert analyze_source(code, rules=["donated-reuse"]) == []
    bad = code.replace("jnp.sum(other)", "jnp.sum(state)")
    (f,) = analyze_source(bad, rules=["donated-reuse"])
    assert "'state'" in f.message


# ---------------------------------------------------------------------------
# pass 7: Pallas lowerability / kernel structure
# ---------------------------------------------------------------------------
PALLAS = src("""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def _kernel(x_ref, o_ref):
        x = x_ref[...]
        o_ref[...] = jnp.maximum(x, 0.0)

    def relu(x, block):
        n, k = x.shape
        assert n % block == 0, (n, block)
        grid = (n // block,)
        return pl.pallas_call(
            _kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((block, k), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((block, k), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, k), x.dtype),
        )(x)
""")


def test_pallas_clean_kernel_is_clean():
    assert analyze_source(PALLAS) == []


def test_pallas_lowering_top_k_flagged():
    bad = PALLAS.replace("jnp.maximum(x, 0.0)",
                         "jax.lax.top_k(x, 4)[0]")
    assert bad != PALLAS
    (f,) = analyze_source(bad, rules=["pallas-lowering"])
    assert f.rule == "pallas-lowering" and "top_k" in f.message


def test_pallas_lowering_sort_flagged_only_inside_kernel():
    bad = PALLAS.replace("jnp.maximum(x, 0.0)", "jnp.sort(x, axis=-1)")
    (f,) = analyze_source(bad, rules=["pallas-lowering"])
    assert "sort" in f.message
    # the same op in the host-side wrapper is fine
    host = PALLAS.replace("return pl.pallas_call(",
                          "x = jnp.sort(x, axis=-1)\n    return pl.pallas_call(")
    assert analyze_source(host, rules=["pallas-lowering"]) == []


def test_pallas_lowering_catches_mutant_in_live_topn_kernel():
    """The live top-N kernel lints clean with no suppression comment, and
    a seeded mutant that puts the interpret-only lax.top_k back into its
    merge surfaces."""
    live = (ROOT / "src" / "repro" / "kernels" / "bpmf_topn.py").read_text()
    assert "repro-lint: disable" not in live
    assert analyze_source(live, rules=list(PALLAS_RULES)) == []
    mutant = live.replace(
        "val_ref[...], idx_ref[...] = _select_topk([run, (scores, cols)], topk)",
        "val_ref[...], idx_ref[...] = jax.lax.top_k(scores, topk)",
    )
    assert mutant != live
    (f,) = analyze_source(mutant, rules=["pallas-lowering"])
    assert f.rule == "pallas-lowering" and "top_k" in f.message


def test_pallas_anyspace_direct_access_flagged():
    code = src("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _kernel(v_ref, o_ref):
            o_ref[...] = v_ref[0] * 2.0

        def scale(v, n, k):
            return pl.pallas_call(
                _kernel,
                grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
                out_specs=pl.BlockSpec((n, k), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((n, k), v.dtype),
            )(v)
    """)
    (f,) = analyze_source(code, rules=["pallas-anyspace"])
    assert f.rule == "pallas-anyspace" and "'v_ref'" in f.message
    # .at[...] DMA slicing of the same ref is the sanctioned access path
    dma = code.replace("v_ref[0] * 2.0", "v_ref.at[0].shape[0] * 2.0")
    assert analyze_source(dma, rules=["pallas-anyspace"]) == []


def test_pallas_anyspace_vmem_refs_untouched():
    assert analyze_source(PALLAS, rules=["pallas-anyspace"]) == []


def test_pallas_anyspace_catches_mutant_in_live_gather_syrk():
    """The live gather-syrk kernel lints clean with no suppression comment
    (V is touched only through `.at[...]` DMA slices), and a seeded mutant
    that reads the ANY-space ref directly surfaces."""
    live = (ROOT / "src" / "repro" / "kernels"
            / "bpmf_gather_syrk.py").read_text()
    assert "repro-lint: disable" not in live
    assert analyze_source(live, rules=list(PALLAS_RULES)) == []
    mutant = live.replace(
        "g = gather_buf[wt % 2, r][:, :k]",
        "g = v_ref[pl.ds(0, block_w), :k]",
    )
    assert mutant != live
    (f,) = analyze_source(mutant, rules=["pallas-anyspace"])
    assert f.rule == "pallas-anyspace" and "'v_ref'" in f.message


def test_pallas_out_init_accumulate_into_garbage_flagged():
    bad = PALLAS.replace("o_ref[...] = jnp.maximum(x, 0.0)",
                         "o_ref[...] += x")
    assert bad != PALLAS
    (f,) = analyze_source(bad, rules=["pallas-out-init"])
    assert f.rule == "pallas-out-init" and "read before" in f.message


def test_pallas_out_init_store_before_read_clean():
    ok = PALLAS.replace(
        "o_ref[...] = jnp.maximum(x, 0.0)",
        "o_ref[...] = jnp.zeros_like(x)\n    o_ref[...] += x")
    assert analyze_source(ok, rules=["pallas-out-init"]) == []


def test_pallas_out_init_when_guarded_init_clean():
    code = src("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _kernel(x_ref, o_ref):
            j = pl.program_id(0)

            @pl.when(j == 0)
            def _first():
                o_ref[...] = jnp.zeros_like(x_ref)

            @pl.when(j > 0)
            def _rest():
                o_ref[...] += x_ref[...]

        def accum(x, block, n, k):
            assert n % block == 0
            return pl.pallas_call(
                _kernel,
                grid=(n // block,),
                in_specs=[pl.BlockSpec((block, k), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((block, k), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((block, k), x.dtype),
            )(x)
    """)
    assert analyze_source(code, rules=["pallas-out-init"]) == []


def test_pallas_out_init_aliased_output_clean():
    code = src("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _kernel(x_ref, z_ref, o_ref):
            o_ref[...] += x_ref[...]

        def accum(x, z, n, k):
            return pl.pallas_call(
                _kernel,
                grid=(1,),
                in_specs=[pl.BlockSpec((n, k), lambda i: (0, 0)),
                          pl.BlockSpec((n, k), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((n, k), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((n, k), x.dtype),
                input_output_aliases={1: 0},
            )(x, z)
    """)
    assert analyze_source(code, rules=["pallas-out-init"]) == []


def test_pallas_blockspec_arity_mismatch_flagged():
    bad = PALLAS.replace("grid = (n // block,)", "grid = (n // block, 1)")
    found = analyze_source(bad, rules=["pallas-blockspec"])
    assert len(found) == 2  # both index_maps take 1 arg against a rank-2 grid
    assert all("rank" in f.message for f in found)


def test_pallas_blockspec_element_offset_flagged():
    bad = PALLAS.replace("lambda i: (i, 0)", "lambda i: (i * block, 0)")
    found = analyze_source(bad, rules=["pallas-blockspec"])
    assert len(found) == 2
    assert all("block units" in f.message for f in found)


def test_pallas_blockspec_missing_divisibility_check_flagged():
    bad = PALLAS.replace("assert n % block == 0, (n, block)\n    ", "")
    assert bad != PALLAS
    (f,) = analyze_source(bad, rules=["pallas-blockspec"])
    assert "divisibility" in f.message and "n // block" in f.message


# ---------------------------------------------------------------------------
# suppression anchoring: statement spans, not physical lines
# ---------------------------------------------------------------------------
def test_suppression_on_first_line_of_multiline_call():
    code = src("""
        import jax

        AXIS = "items"

        def make(n):
            return jax.make_mesh((n,), (AXIS,))

        def stats(x):
            return jax.lax.psum(  # repro-lint: disable=collective-axis (cross-mesh)
                x,
                "rows",
            )
    """)
    assert analyze_source(code, rules=["collective-axis"]) == []
    # the undirected comment does not leak onto the next statement
    two = code + src("""
        def more(x):
            return jax.lax.psum(x, "cols")
    """)
    (f,) = analyze_source(two, rules=["collective-axis"])
    assert "'cols'" in f.message


def test_suppression_on_decorator_line_covers_header():
    code = src("""
        import jax

        NUMS = (1,)

        @jax.jit(
            static_argnums=NUMS,
        )
        def f(x, n):
            return x
    """)
    (f,) = analyze_source(code, rules=["static-args"])
    assert "literal" in f.message
    quiet = code.replace("@jax.jit(",
                         "@jax.jit(  # repro-lint: disable=static-args")
    assert analyze_source(quiet, rules=["static-args"]) == []


def test_suppression_on_def_line_does_not_cover_body():
    code = src("""
        import jax

        def draw(key):  # repro-lint: disable=prng-reuse
            a = jax.random.normal(key)
            b = jax.random.normal(key)
            return a + b
    """)
    (f,) = analyze_source(code, rules=["prng-reuse"])
    assert f.rule == "prng-reuse"


# ---------------------------------------------------------------------------
# CLI: --changed-only and --out
# ---------------------------------------------------------------------------
def _git(cwd, *args):
    import subprocess
    subprocess.run(
        ["git", "-c", "user.email=lint@test", "-c", "user.name=lint", *args],
        cwd=cwd, check=True, capture_output=True)


def test_cli_changed_only_scopes_to_git_diff(tmp_path):
    import shutil
    if shutil.which("git") is None:
        pytest.skip("git unavailable")
    _git(tmp_path, "init", "-q")
    committed = tmp_path / "committed.py"
    committed.write_text(GUARDED)           # has a finding, but is committed
    _git(tmp_path, "add", "committed.py")
    _git(tmp_path, "commit", "-q", "-m", "seed")

    fresh = tmp_path / "fresh.py"
    fresh.write_text("x = 1\n")             # untracked, clean

    args = [str(tmp_path), "--root", str(tmp_path)]
    assert main(args) == 1                  # full run still sees committed.py
    assert main([*args, "--changed-only"]) == 0   # diff scope skips it

    fresh.write_text(GUARDED)               # untracked file gains a finding
    assert main([*args, "--changed-only"]) == 1


def test_cli_changed_only_outside_git_is_usage_error(tmp_path, monkeypatch):
    target = tmp_path / "mod.py"
    target.write_text("x = 1\n")
    monkeypatch.setenv("GIT_CEILING_DIRECTORIES", str(tmp_path.parent))
    monkeypatch.setenv("GIT_DIR", str(tmp_path / "no-such-gitdir"))
    rc = main([str(target), "--root", str(tmp_path), "--changed-only"])
    assert rc == 2


def test_cli_out_writes_json_artifact(tmp_path, capsys):
    target = tmp_path / "mod.py"
    target.write_text(GUARDED)
    report = tmp_path / "lint-report.json"
    rc = main([str(target), "--root", str(tmp_path), "--out", str(report)])
    assert rc == 1
    payload = json.loads(report.read_text())
    assert payload["summary"] == {"guarded-field": 1}
    (finding,) = payload["findings"]
    assert finding["rule"] == "guarded-field"


def test_every_pass_rule_is_documented_and_reachable():
    """RULE_DOCS, ALL_RULES, and the pass modules' RULES tuples must agree —
    an undocumented rule (or a documented rule no pass implements) is a
    registry bug."""
    from repro.analysis.cli import PASSES

    implemented = set()
    for mod in PASSES:
        implemented.update(mod.RULES)
    assert implemented == set(RULE_DOCS) == set(ALL_RULES)
