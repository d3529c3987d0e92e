"""A run names its device, and refuses the CPU and unknown chips."""
import json

import pytest

import harness


def test_cpu_is_refused():
    with pytest.raises(harness.DeviceError, match="needs a TPU"):
        harness.require_devices(1)


def test_unknown_kind_is_an_error():
    with pytest.raises(harness.DeviceError, match="no peaks"):
        harness.peaks("TPU v99")


def test_known_kind_has_peaks():
    p = harness.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_device_record_names_the_device():
    import jax

    rec = harness.device_record(jax.devices()[:1])
    assert rec["platform"] == "cpu" and rec["count"] == 1 and "kind" in rec


def test_run_exits_nonzero_without_a_tpu(capsys):
    import run

    spec = harness.load_spec()
    assert run.main(["--workload", spec["workloads"][0]["name"], "--seed", "1",
                     "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_run_exits_nonzero_without_a_spec(tmp_path, capsys, monkeypatch):
    import run

    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness.load_spec, "__defaults__", (tmp_path,))
    monkeypatch.setattr(harness.load_cell, "__defaults__", (tmp_path, tmp_path / "bench"))
    assert run.main(["--workload", "x", "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""
