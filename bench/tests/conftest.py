"""Fixtures of the harness's own tests: a tiny copy of the benchmark (the
program's reduced presets) and a runner for one cell on the CPU."""

from __future__ import annotations

import pathlib
import runpy
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session")
def tiny_checkout(tmp_path_factory):
    from bench.tests import tiny
    return tiny.make_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(scope="session")
def run_cell(tiny_checkout):
    run = runpy.run_path(str(tiny_checkout / "bench" / "run.py"))

    def go(workload, seed=7, seconds=1.0, trace=False, checkout=None, calibrate=()):
        return run["run_cell"](workload, seed, seconds, trace,
                               checkout=checkout or tiny_checkout,
                               require_chip=False, calibrate=calibrate)
    return go
