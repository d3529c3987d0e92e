"""The program's own trace points: host spans in the serving flush
(`jax.profiler.TraceAnnotation`) and name scopes in the compiled sweep and
fold-in (`jax.named_scope`), as a profiler and the compiled HLO see them."""
import glob
import hashlib
import os
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core import GibbsSampler
from repro.core.buckets import plan_buckets
from repro.core.gibbs import device_plan, scopes_in_cache_key
from repro.data import synthetic_lowrank, train_test_split
from repro.data.sparse import csr_from_coo
from repro.kernels import bpmf_topn
from repro.serve import PublicationChannel, RecommendFrontend
from repro.serve import foldin as foldin_mod

M, N, K = 40, 96, 8
# span -> the spans it may sit directly inside
PARENTS = {
    "serve.batch": (),
    "serve.warm": ("serve.batch",),
    "serve.cold": ("serve.batch",),
    "serve.foldin.plan": ("serve.cold",),
    "serve.fetch": ("serve.warm", "serve.cold"),
    "serve.exclude": ("serve.cold",),
}
SWEEP_SCOPES = ("bpmf.stats", "bpmf.solve", "bpmf.hyper", "bpmf.prior",
                "bpmf.predict")


def _frontend():
    rng = np.random.default_rng(3)
    channel = PublicationChannel(window=2)
    for s in range(2):
        a = rng.normal(size=(K, K)).astype(np.float32) / np.sqrt(K)
        channel.publish(s, {
            "u": rng.normal(size=(M, K)).astype(np.float32),
            "v": rng.normal(size=(N, K)).astype(np.float32),
            "hyper_u_mu": np.zeros(K, np.float32),
            "hyper_u_lam": a @ a.T + 2.0 * np.eye(K, dtype=np.float32),
            "hyper_v_mu": np.zeros(K, np.float32),
            "hyper_v_lam": np.eye(K, dtype=np.float32),
            "global_mean": np.float32(3.0), "alpha": np.float32(2.0)})
    return RecommendFrontend(channel=channel, subscribe=False, max_batch=8)


def _mixed_flush(fe, seed):
    """One micro-batch: three warm users and two cold-start users with the
    same rating counts on every call (one fold-in plan schema)."""
    rng = np.random.default_rng(seed)
    for u in (1, 7, 30):
        fe.submit(u, topk=5)
    for d in (4, 11):
        fe.submit_ratings(rng.choice(N, d, replace=False).astype(np.int32),
                          rng.normal(3.0, 1.0, d).astype(np.float32), topk=5)
    assert len(fe.flush()) == 5


def _host_spans(logdir):
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name.startswith("serve.")]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def _innermost_parent(span, spans):
    name, a, b = span
    around = [s for s in spans if s is not span and s[1] <= a and b <= s[2]
              and s[2] - s[1] > b - a]
    return min(around, key=lambda s: s[2] - s[1])[0] if around else None


@pytest.fixture(scope="module")
def traced_flushes(tmp_path_factory):
    """Two same-profile warm + cold flushes under the profiler, after one
    flush outside it that compiled every shape."""
    fe = _frontend()
    _mixed_flush(fe, seed=0)
    traces = (foldin_mod.trace_count(), bpmf_topn.trace_count())
    logdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(logdir):
        _mixed_flush(fe, seed=1)
        _mixed_flush(fe, seed=2)
    after = (foldin_mod.trace_count(), bpmf_topn.trace_count())
    fe.close()
    return _host_spans(logdir), traces, after


def test_flush_emits_the_six_spans_nested(traced_flushes):
    spans, _, _ = traced_flushes
    names = [s[0] for s in spans]
    assert set(names) == set(PARENTS)
    # one batch, one warm and one cold half, one plan per flush; a fetch in
    # each half; exclusion only on the cold half
    for name, count in (("serve.batch", 2), ("serve.warm", 2), ("serve.cold", 2),
                        ("serve.foldin.plan", 2), ("serve.fetch", 4),
                        ("serve.exclude", 2)):
        assert names.count(name) == count, name
    for span in spans:
        parent = _innermost_parent(span, spans)
        if PARENTS[span[0]]:
            assert parent in PARENTS[span[0]], (span[0], parent)
        else:
            assert parent is None


def test_foldin_and_topn_do_not_retrace_under_the_profiler(traced_flushes):
    _, before, after = traced_flushes
    assert after == before


def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]+)"', hlo_text))


def _scopes(op_names):
    return {part for name in op_names for part in name.split("/")}


@pytest.mark.parametrize("engine", [None, "reference"])
def test_sweep_carries_its_scopes(engine):
    ratings, _, _ = synthetic_lowrank(60, 40, k_true=4, nnz=900, noise=0.3,
                                      seed=2)
    train, test = train_test_split(ratings, 0.1, seed=2)
    kw = {} if engine is None else {"engine": engine}
    s = GibbsSampler(train, test, k=8, widths=(4, 16), **kw)
    text = s._sweep.lower(s.init(0), *s._plan_args).compile().as_text()
    assert set(SWEEP_SCOPES) <= _scopes(_op_names(text))


def test_fused_fold_in_carries_its_scope():
    rng = np.random.default_rng(5)
    s, n_new = 3, 4
    rows = np.repeat(np.arange(n_new), 5).astype(np.int32)
    cols = rng.integers(0, N, rows.size).astype(np.int32)
    indptr, idx, vals = csr_from_coo(rows, cols,
                                     rng.normal(size=rows.size).astype(np.float32),
                                     n_new)
    db = device_plan(plan_buckets(indptr, idx, vals, n_new, N, (4, 8)))
    lam = jnp.broadcast_to(jnp.eye(K), (s, K, K))
    text = foldin_mod._fused_fold_in.lower(
        jnp.ones((s, N, K)), lam, jnp.zeros((s, K)), 2.0,
        tuple((b.indices, b.values, b.mask, b.seg_ids, b.seg_item_ids) for b in db),
        None, plan_key=tuple((b.width, b.n_segments, b.identity_segments) for b in db),
        n_new=n_new, engine="einsum",
    ).compile().as_text()
    names = _op_names(text)
    assert "serve.foldin" in _scopes(names)
    # the shared statistics and solve read under the fold-in's scope
    for inner in ("bpmf.stats", "bpmf.solve"):
        assert any(f"serve.foldin/{inner}/" in n for n in names), inner


def _computation_key(scope):
    """The computation's part of JAX's persistent-cache key, for one small
    function with or without a name scope, lowered and hashed as the cache
    would (under whatever key settings are in force)."""
    from jax._src import cache_key

    def f(x):
        if scope:
            with jax.named_scope(scope):
                return jnp.sin(x @ x).sum()
        return jnp.sin(x @ x).sum()

    h = hashlib.sha256()
    ir = jax.jit(f).lower(jnp.ones((8, 8))).compiler_ir("stablehlo")
    cache_key._hash_computation(h, ir, cache_key.IgnoreCallbacks.NO)
    return h.hexdigest()


def test_scopes_enter_the_cache_key_only_where_asked():
    # JAX's default: an executable cached without the scope would be reused
    assert _computation_key("bpmf.stats") == _computation_key(None)
    with scopes_in_cache_key():
        keyed = _computation_key("bpmf.stats")
        assert keyed != _computation_key(None)
        assert keyed != _computation_key("bpmf.solve")

        def deeper(n):   # another call stack: no source location in the key
            return deeper(n - 1) if n else _computation_key("bpmf.stats")

        assert deeper(3) == keyed
