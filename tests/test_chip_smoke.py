"""``chip_smoke.py`` refuses to report a result it did not get on a TPU: on
the CPU, with the sum-tree backend forced, and away from the repository it
exits non-zero and prints no JSON line."""

from __future__ import annotations

import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


REASONS = {"cpu": "no TPU: JAX runs on cpu",
           "forced-backend": "REPRO_SUMTREE_BACKEND=interpret forces",
           "alone": "No module named 'repro'"}


@pytest.mark.parametrize("case", list(REASONS))
def test_chip_smoke_fails_without_a_tpu_run(case, tmp_path):
    script, cwd = ROOT / "chip_smoke.py", ROOT
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "REPRO_SUMTREE_BACKEND")}
    env["JAX_PLATFORMS"] = "cpu"
    if case == "forced-backend":
        env["REPRO_SUMTREE_BACKEND"] = "interpret"
    if case == "alone":
        script = pathlib.Path(shutil.copy(script, tmp_path))
        cwd = tmp_path
    r = subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert REASONS[case] in r.stderr
