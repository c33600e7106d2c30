"""The benchmark runs only on a chip it knows: the CPU, too few chips and
an unknown device kind are refused, and ``run.py`` then exits non-zero
with no result line. So does a checkout that holds only the benchmark."""

import json
import os
import pathlib
import runpy
import shutil
import subprocess
import sys
import types

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
RUN = runpy.run_path(str(REPO / "bench" / "run.py"))
PEAKS = json.loads((REPO / "bench" / "peaks.json").read_text())


def _patch_devices(monkeypatch, devices):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: devices)


def _dev(platform="tpu", kind="TPU v5 lite"):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("devices,chips", [
    ([_dev("cpu", "cpu")], 1),
    ([_dev(kind="TPU v9 imaginary")], 1),
    ([_dev()], 4),
], ids=["cpu", "unknown-kind", "too-few-chips"])
def test_refused(monkeypatch, devices, chips):
    _patch_devices(monkeypatch, devices)
    with pytest.raises(RUN["NoChip"]):
        RUN["find_device"](types.SimpleNamespace(chips=chips), PEAKS)


def test_known_kind_passes(monkeypatch):
    _patch_devices(monkeypatch, [_dev()])
    devices, kind = RUN["find_device"](types.SimpleNamespace(chips=1), PEAKS)
    assert kind == "TPU v5 lite" and len(devices) == 1


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dqn.lockstep", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_tpu_exits_nonzero():
    p = _run(REPO)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_run_with_only_the_benchmark_exits_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()
