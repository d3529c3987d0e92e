"""The trace -> metrics reduction: hand-made events with known answers,
and a small trace recorded on a v5e (tests/data/v5e_serve_trace.json.gz,
the reduced form `tracereduce.Trace.save` writes)."""
from pathlib import Path

import pytest

import tracereduce as tr

DATA = Path(__file__).resolve().parent / "data"


def hand_made():
    return tr.Trace(
        devices={
            "/device:TPU:0": [("fusion.1", 1.0, 2.0), ("fusion.2", 1.5, 2.5),
                              ("collective-permute.3", 2.0, 4.0),
                              ("topn_scores_pallas.1", 5.0, 5.5)],
            "/device:TPU:1": [("fusion.1", 1.0, 3.0), ("all-reduce.7", 3.0, 3.5),
                              ("topn_scores_pallas.1", 6.0, 7.0)],
        },
        host=[("window", 0.0, 10.0), ("flush", 4.2, 4.9), ("sweep", 0.5, 2.2)],
    )


def test_union_and_minus():
    assert tr.union([(3, 4), (1, 2), (1.5, 2.5)]) == [(1, 2.5), (3, 4)]
    assert tr.union([(0, 5)], window=(1, 2)) == [(1, 2)]
    assert tr.minus([(0, 10)], [(1, 2), (4, 5)]) == [(0, 1), (2, 4), (5, 10)]
    assert tr.minus([(1, 2)], [(0, 3)]) == []


def test_busy_and_idle():
    t = hand_made()
    w = tr.window_of(t)
    assert w == (0.0, 10.0)
    # device 0 busy [1, 4] + [5, 5.5] = 3.5 s; device 1 [1, 3.5] + [6, 7] = 3.5 s
    assert tr.busy_seconds(t, w) == pytest.approx(3.5)
    assert tr.busy_seconds(t, (2.0, 3.0)) == pytest.approx(1.0)


def test_collective_exposed():
    t = hand_made()
    # device 0: permute [2, 4] under compute until 2.5 -> 1.5 s exposed;
    # device 1: all-reduce [3, 3.5] after compute ends at 3 -> 0.5 s
    assert tr.exposed_collective_seconds(t, (0.0, 10.0)) == pytest.approx(1.0)


def test_kernel_sums():
    secs, count = tr.kernel_seconds(hand_made(), (0.0, 10.0), "topn_scores_pallas")
    assert count == 2 and secs == pytest.approx((0.5 + 1.0) / 2)


def test_breakdown_ops_and_gaps():
    t = hand_made()
    bd = tr.breakdown(t, (0.0, 10.0))
    ops = dict(bd["device_ops"])
    # top-level events per device, averaged over 2 devices; fusion.2 is
    # not nested (it outlasts fusion.1) so it counts whole
    assert ops["fusion"] == pytest.approx((1.0 + 1.0 + 2.0) / 2)
    assert ops["collective-permute"] == pytest.approx(2.0 / 2)
    gaps = bd["idle_gaps"]
    # device 0 idle: [0, 1], [4, 5], [5.5, 10]; the longest is 4.5 s with
    # no span open at its middle, [4, 5] lies under the flush span
    assert gaps[0] == ["no span", pytest.approx(4.5)]
    assert ["flush", pytest.approx(1.0)] in gaps
    assert ["sweep", pytest.approx(1.0)] in gaps


def test_short_names_from_chip_hlo_text():
    assert tr.short_name('%fusion.18 = f32[16,8]{0,1:T(8,128)S(1)} fusion(f32[16,8,64]'
                         '{0,1,2:T(8,128)S(1)} %get-tuple-element.295), kind=kLoop') == "fusion.18"
    assert tr.short_name('%custom-call.38 = f32[4670,64,64]{2,1,0:T(8,128)} custom-call('
                         'f32[4670,64,64]{2,1,0:T(8,128)} %add_multiply_fusion.1), '
                         'custom_call_target="Cholesky"') == "custom-call.38:Cholesky"
    assert tr.short_name('%topn_scores_pallas.1 = (f32[8,16]{1,0:T(8,128)}, s32[8,16]) '
                         'custom-call(f32[8,1024] %u.1), custom_call_target='
                         '"tpu_custom_call"') == "topn_scores_pallas.1"
    assert tr.base_name("custom-call.38:Cholesky") == "custom-call:Cholesky"
    assert tr.base_name("topn_scores_pallas.1") == "topn_scores_pallas"
    assert tr.base_name("copy-done") == "copy-done"


@pytest.fixture(scope="module")
def chip():
    """0.25 s of a serving window on one v5e: warm and cold-start flushes."""
    return tr.Trace.load(str(DATA / "v5e_serve_trace.json.gz"))


def test_recorded_busy_idle_partition_the_window(chip):
    w = tr.window_of(chip)
    busy = tr.busy_seconds(chip, w)
    (ops,) = chip.devices.values()
    merged = tr.union([(a, b) for _, a, b in ops], w)
    gaps = tr.minus([w], merged)
    assert busy == pytest.approx(0.046387009, rel=1e-6)
    assert busy + tr.length(gaps) == pytest.approx(w[1] - w[0], rel=1e-9)
    # the operation line is properly nested: self times add up to busy
    self_total = sum(t for _, t in tr.self_times(ops, w))
    assert self_total == pytest.approx(busy, rel=1e-6)


def test_recorded_kernel_sum(chip):
    w = tr.window_of(chip)
    secs, count = tr.kernel_seconds(chip, w, "topn_scores_pallas")
    (ops,) = chip.devices.values()
    direct = [min(b, w[1]) - max(a, w[0]) for n, a, b in ops
              if n.startswith("topn_scores_pallas") and b > w[0] and a < w[1]]
    assert count == len(direct) == 32
    assert secs == pytest.approx(sum(direct)) == pytest.approx(0.02938006, rel=1e-6)
    assert tr.exposed_collective_seconds(chip, w) == 0.0   # one chip: none


def test_recorded_breakdown(chip):
    w = tr.window_of(chip)
    bd = tr.breakdown(chip, w)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0] == "topn_scores_pallas"
    assert [n for n, _ in bd["device_ops"]][:3] == ["topn_scores_pallas", "pad",
                                                    "custom-call:Cholesky"]
    secs = [s for _, s in bd["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    assert {n for n, _ in bd["idle_gaps"]} <= {"flush", "no span"}
    assert "flush" in {n for n, _ in bd["idle_gaps"]}
