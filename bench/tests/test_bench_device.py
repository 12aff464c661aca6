"""The command measures the chip or nothing."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = harness.load_spec()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no fallback" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_fewer_chips_than_the_cell_asks_is_refused(monkeypatch):
    class Tpu:
        platform = "tpu"
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [Tpu()])
    with pytest.raises(harness.NoChip):
        harness.require_chips(4)
    assert harness.require_chips(1)


def test_a_device_missing_from_the_peaks_is_an_error():
    peaks = harness.Files().peaks()
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peaks["source"]
    with pytest.raises(KeyError):
        harness.peak_for(peaks, "TPU v9 imaginary")
