"""The command refuses a CPU backend and a device missing from the peaks
table, and prints no result."""

import json
import os
import subprocess
import sys
import types

import pytest

import run


def test_cpu_backend_fails_the_command():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(run.BENCH, "run.py"), "--workload",
         "granite_3_2b.chat", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, env=env, cwd=run.ROOT,
        timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def _fake(monkeypatch, kind, n=1):
    import jax
    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * n)


def test_unknown_device_kind_fails(monkeypatch):
    _fake(monkeypatch, "TPU v9 imaginary")
    with pytest.raises(run.DeviceError, match="not in bench/peaks.json"):
        run.check_device(1)


def test_too_few_chips_fails(monkeypatch):
    _fake(monkeypatch, "TPU v5 lite", n=1)
    with pytest.raises(run.DeviceError, match="needs 4 chips"):
        run.check_device(4)


def test_known_device_reads_its_peaks(monkeypatch):
    _fake(monkeypatch, "TPU v5 lite")
    pk = run.check_device(1)["peaks"]
    assert pk["flops_bf16_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with open(os.path.join(run.BENCH, "peaks.json")) as f:
        assert "cloud.google.com" in json.load(f)["source"]
