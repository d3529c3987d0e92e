"""The program's scopes and spans in a trace (bench/scopes.py) and the five
readers built on them: hand-made traces with known answers, a profile
recorded here on the CPU, and traces recorded on a v5e (tests/data/)."""
import glob
import os
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import scopes
import tracereduce as tr

DATA = Path(__file__).resolve().parent / "data"
TRAIN_METRICS = ("solve_ms.train", "stats_ms.train")
SERVE_METRICS = ("foldin_ms.serve", "foldin_plan_ms.serve", "host_ms.serve")


def read(metric, trace, layer=None):
    window = tr.window_of(trace) if trace.devices else None
    return harness.metric_reader(metric).read(
        {"trace": trace, "window": window, "layer": layer or {}})


def _space(path, pid):
    """A profile in the v5e's layout: the op event names its instruction
    (`display_name`) and program (`program_id` stat); the program's HLO
    proto, in the metadata plane, holds the instruction's `op_name`."""
    m = scopes.messages()
    hlo = m["HloProto"]()
    comp = hlo.hlo_module.computations.add()
    for name, op_name in (("custom-call.38", "jit(f)/while/body/bpmf.solve/cholesky"),
                          ("fusion.2", "jit(f)/bpmf.hyper/dot_general")):
        ins = comp.instructions.add(name=name)
        ins.metadata.op_name = op_name
    space = m["XSpace"]()
    meta = space.planes.add(name="/host:metadata")
    meta.stat_metadata.add(key=1).value.name = "Hlo Proto"
    prog = meta.event_metadata.add(key=pid - 2**64)       # int64 key
    prog.value.name = f"jit_f({pid})"
    prog.value.stats.add(metadata_id=1, bytes_value=hlo.SerializeToString())
    tpu = space.planes.add(name="/device:TPU:0")
    tpu.stat_metadata.add(key=9).value.name = "program_id"
    for key, text, name, program in (
            (7, '%custom-call.38 = f32[4670,64,64]{2,1,0:T(8,128)} custom-call('
                'f32[4670,64,64]{2,1,0:T(8,128)} %add_multiply_fusion.1), '
                'custom_call_target="Cholesky"', "custom-call.38", pid),
            (8, "%fusion.2 = f32[64,64]{1,0} fusion(f32[9,64] %p), kind=kOutput",
             "fusion.2", pid),
            (6, "%fusion.2 = f32[64,64]{1,0} fusion(f32[9,64] %p), kind=kOutput",
             "fusion.2", 5)):                            # another program's
        md = tpu.event_metadata.add(key=key).value
        md.name, md.display_name = text, name
        md.stats.add(metadata_id=9, uint64_value=program)
    tpu.lines.add(name="XLA Modules", timestamp_ns=0).events.add(
        metadata_id=7, offset_ps=0, duration_ps=10**6)
    ops = tpu.lines.add(name=tr.OPS_LINE, timestamp_ns=1000)
    for key, offset in ((7, 2500), (8, 9000), (6, 12000)):
        ops.events.add(metadata_id=key, offset_ps=offset, duration_ps=3999)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_extract_joins_each_op_to_its_programs_scope(tmp_path):
    path = str(tmp_path / "chip.xplane.pb")
    _space(path, pid=12094040262672820895)
    got = scopes.extract(path)
    # whole nanoseconds, as tracereduce.extract reads them
    assert got.devices == {"/device:TPU:0": [
        ("custom-call.38:Cholesky", pytest.approx(1002e-9), pytest.approx(1005e-9)),
        ("fusion.2", pytest.approx(1009e-9), pytest.approx(1012e-9)),
        ("fusion.2", pytest.approx(1012e-9), pytest.approx(1015e-9))]}
    assert got.scopes == {"/device:TPU:0": [
        "jit(f)/while/body/bpmf.solve/cholesky", "jit(f)/bpmf.hyper/dot_general",
        ""]}                                  # its program's proto is absent
    assert scopes.is_program_span("serve.foldin.plan")
    assert not scopes.is_program_span("flush")


def hand_made():
    """Two devices; a loop holds a solve and a statistics pass; the fold-in's
    statistics nest under its own scope. One warm and one mixed batch."""
    ops = [("while.1", 0.0, 10.0), ("custom-call.2:Cholesky", 1.0, 4.0),
           ("fusion.3", 4.0, 5.0), ("fusion.4", 6.0, 7.0), ("copy.5", 12.0, 13.0)]
    paths = ["jit(f)/while", "jit(f)/while/body/bpmf.solve/cholesky",
             "jit(f)/while/body/bpmf.stats/dot_general",
             "jit(g)/serve.foldin/bpmf.stats/mul", ""]
    host = [("window", 0.0, 20.0),
            ("serve.batch", 1.0, 9.0), ("serve.warm", 1.0, 4.0),
            ("serve.fetch", 3.0, 4.0), ("serve.cold", 5.0, 9.0),
            ("serve.foldin.plan", 5.0, 5.5), ("serve.fetch", 8.0, 9.0),
            ("serve.batch", 10.0, 13.0), ("serve.warm", 10.0, 13.0),
            ("serve.fetch", 11.0, 13.0),
            ("serve.batch", 19.0, 21.0)]          # runs past the window
    return scopes.ScopedTrace({"/device:TPU:0": ops, "/device:TPU:1": ops},
                              host, {"/device:TPU:0": paths, "/device:TPU:1": paths})


def test_scope_seconds_are_self_times_per_component():
    secs = scopes.scope_seconds(hand_made(), (0.0, 20.0))
    assert secs["bpmf.solve"] == pytest.approx(3.0)
    assert secs["bpmf.stats"] == pytest.approx(2.0)     # sweep 1 + fold-in 1
    assert secs["serve.foldin"] == pytest.approx(1.0)
    assert secs["while"] == pytest.approx(10.0 - 3.0 - 1.0 - 1.0 + 3.0 + 1.0)
    assert secs["jit(f)"] == pytest.approx(10.0 - 1.0)  # the loop and its body
    assert "" not in secs
    # clipped to the window
    assert scopes.scope_seconds(hand_made(), (2.0, 3.0)) == {
        "jit(f)": pytest.approx(1.0), "while": pytest.approx(1.0),
        "body": pytest.approx(1.0), "bpmf.solve": pytest.approx(1.0),
        "cholesky": pytest.approx(1.0)}


def test_spans_and_self_seconds():
    t = hand_made()
    w = (0.0, 20.0)
    assert scopes.spans(t, "serve.batch", w) == [(1.0, 9.0), (10.0, 13.0)]
    assert scopes.self_seconds(t, "serve.batch", "serve.fetch", w) == [
        pytest.approx(6.0), pytest.approx(1.0)]


def test_train_readers():
    t = hand_made()
    assert read("solve_ms.train", t, {"sweeps": 2}) == pytest.approx(1500.0)
    assert read("stats_ms.train", t, {"sweeps": 2}) == pytest.approx(1000.0)
    assert read("solve_ms.train", t, {}) is None        # no sweep counted


def test_serve_readers():
    t = hand_made()
    assert read("foldin_ms.serve", t) == pytest.approx(1000.0)      # 1 s, 1 cold
    assert read("foldin_plan_ms.serve", t) == pytest.approx(500.0)
    assert read("host_ms.serve", t) == pytest.approx(3500.0)        # median 6, 1


def test_readers_silent_without_program_scopes_and_spans():
    """A program with neither (the parent commit's) gives no reading."""
    t = hand_made()
    bare = scopes.ScopedTrace(t.devices, [h for h in t.host if h[0] == "window"])
    for metric in TRAIN_METRICS:
        assert read(metric, bare, {"sweeps": 2}) is None
    for metric in SERVE_METRICS:
        assert read(metric, bare) is None


def test_profile_found_through_the_run_context():
    cap = tr.Captured(trace=tr.Trace(), path="/some/run.xplane.pb")
    ctx = SimpleNamespace(captured=cap)   # noqa: F841 — found by the walk

    def reader():
        return scopes.profile_path(cap.trace)

    assert reader() == "/some/run.xplane.pb"
    assert scopes.profile_path(tr.Trace()) is None


def test_extract_keeps_program_spans_and_given_ones(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("bpmf.stats"):
            return (x @ x).sum()

    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("serve.batch"):
            with jax.profiler.TraceAnnotation("serve.fetch"):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("not.the.program"):
            f(x).block_until_ready()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    got = scopes.extract(path, host=[("window", 0.0, 1e12)])
    assert [h[0] for h in got.host] == ["window", "serve.batch", "serve.fetch"]
    assert got.devices == {} and got.scopes == {}     # no TPU plane here
    b, fetch = got.host[1], got.host[2]
    assert b[1] <= fetch[1] and fetch[2] <= b[2]
    # the CPU profile holds the programs' HLO protos too
    with open(path, "rb") as fh:
        space = scopes.messages()["XSpace"].FromString(fh.read())
    op_names = {n for prog in scopes.program_scopes(space).values()
                for n in prog.values()}
    assert any("bpmf.stats" in n.split("/") for n in op_names)


@pytest.fixture(scope="module")
def old_chip():
    return (tr.Trace.load(str(DATA / "v5e_serve_trace.json.gz")),
            scopes.ScopedTrace.load(str(DATA / "v5e_serve_trace.json.gz")))


def test_recorded_parent_trace_reads_as_before(old_chip):
    plain, scoped = old_chip
    assert scoped.scopes == {}
    w = tr.window_of(plain)
    assert tr.window_of(scoped) == w
    assert tr.busy_seconds(scoped, w) == tr.busy_seconds(plain, w)
    assert tr.kernel_seconds(scoped, w, "topn_scores_pallas") == \
        tr.kernel_seconds(plain, w, "topn_scores_pallas")
    assert tr.breakdown(scoped, w) == tr.breakdown(plain, w)
    layer = {"served": 1}
    for metric in ("device_idle.serve",):
        assert read(metric, scoped, layer) == read(metric, plain, layer)
    for metric in SERVE_METRICS:
        assert read(metric, scoped) is None


def test_saved_scoped_trace_round_trips(tmp_path):
    t = hand_made()
    t.save(str(tmp_path / "t.json.gz"))
    back = scopes.ScopedTrace.load(str(tmp_path / "t.json.gz"))
    assert back == t


# ---------------------------------------------------------------------------
# recorded on a v5e, cut from traced runs of the program with its scopes and
# spans (the reduced form `ScopedTrace.save` writes)
# ---------------------------------------------------------------------------
def recorded(name):
    return scopes.ScopedTrace.load(str(DATA / name))


def test_recorded_sweep_reads_by_scope():
    """50 ms of `chembl-train` from a sweep's start: the hyper draw, the
    prior, the predictive accumulation, statistics and the solve."""
    t = recorded("v5e_train_trace_scopes.json.gz")
    w = tr.window_of(t)
    secs = scopes.scope_seconds(t, w)
    assert {k for k in secs if k.startswith("bpmf.")} == {
        "bpmf.stats", "bpmf.solve", "bpmf.prior", "bpmf.hyper", "bpmf.predict"}
    assert read("solve_ms.train", t, {"sweeps": 1}) == pytest.approx(13.190897, rel=1e-6)
    assert read("stats_ms.train", t, {"sweeps": 1}) == pytest.approx(0.349532, rel=1e-6)
    # the cut's busy time lies under a bpmf.* scope but for copies
    (dev,) = t.devices
    labelled = [(i, a, b) for i, (_, a, b) in enumerate(t.devices[dev])]
    under = sum(s for i, s in tr.self_times(labelled, w)
                if any(c.startswith("bpmf.") for c in t.scopes[dev][i].split("/")))
    assert under / tr.busy_seconds(t, w) == pytest.approx(0.960495, rel=1e-5)
    for metric in SERVE_METRICS:
        assert read(metric, t) is None


def test_recorded_serving_window_reads_by_span():
    """0.25 s of `ml20m-serve-mixed`: its device ops ran executables cached
    before the scopes were in the cache key, so they carry no `serve.foldin`
    and the fold-in's device time reads nothing; the spans read."""
    t = recorded("v5e_serve_trace_spans.json.gz")
    w = tr.window_of(t)
    assert read("foldin_plan_ms.serve", t) == pytest.approx(1.900401, rel=1e-6)
    assert read("host_ms.serve", t) == pytest.approx(13.831689, rel=1e-6)
    assert read("foldin_ms.serve", t) is None
    assert read("device_idle.serve", t, {"served": 1}) == pytest.approx(62.171458, rel=1e-6)
    # with the program's spans the breakdown's idle gaps name them
    gaps = tr.breakdown(t, w)["idle_gaps"]
    assert {n for n, _ in gaps} <= {"serve.foldin.plan", "serve.batch"}
    assert gaps[0] == ["serve.foldin.plan", pytest.approx(0.005145111, rel=1e-6)]


def test_recorded_flush_reads_the_fold_in_scope():
    """One warm + cold flush at a test size on a v5e: all six spans, and the
    fold-in's device ops under `serve.foldin` with the shared scopes."""
    t = recorded("v5e_flush_scopes.json.gz")
    w = tr.window_of(t)
    assert {n for n, *_ in t.host} == {"window", "serve.batch", "serve.warm",
                                       "serve.cold", "serve.foldin.plan",
                                       "serve.fetch", "serve.exclude"}
    secs = scopes.scope_seconds(t, w)
    # the shared statistics and solve nest inside the fold-in's scope
    assert 0 < secs["bpmf.stats"] + secs["bpmf.solve"] < secs["serve.foldin"]
    assert read("foldin_ms.serve", t) == pytest.approx(0.037444, rel=1e-6)
    assert read("foldin_plan_ms.serve", t) == pytest.approx(3.15035, rel=1e-6)
    assert read("host_ms.serve", t) == pytest.approx(15.324578, rel=1e-6)
