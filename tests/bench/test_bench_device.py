"""A run measures TPU chips whose peaks the table knows, or refuses."""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench_testlib import ROOT

from bench.run import Refused, check_devices

PEAKS = json.loads((ROOT / "bench" / "peaks.json").read_text())


def dev(platform="tpu", kind="TPU v5 lite"):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_peaks_table_names_its_source():
    assert "TPU v5e" in PEAKS["source"]
    v5e = PEAKS["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9


def test_known_chip_gives_its_peaks():
    assert check_devices([dev()], 1, PEAKS["devices"])["bf16_flops_per_s"] == 197e12


@pytest.mark.parametrize("devices,chips,why", [
    ([dev("cpu", "cpu")], 1, "not a TPU"),
    ([], 1, "not a TPU"),
    ([dev(kind="TPU v99")], 1, "not in bench/peaks.json"),
    ([dev()], 4, "asks for 4 chips"),
])
def test_refusals(devices, chips, why):
    with pytest.raises(Refused, match=why):
        check_devices(devices, chips, PEAKS["devices"])


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "trial.mamba2.resume",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_on_cpu_exits_nonzero_without_a_result():
    out = _run(ROOT)
    assert out.returncode != 0
    assert "not a TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths has
    no system under test: the run fails and prints no result."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {"PYTHONPATH": ""}
    out = _run(tmp_path, env)
    assert out.returncode != 0
    assert "No module named 'repro'" in out.stderr
    assert out.stdout.strip() == ""
