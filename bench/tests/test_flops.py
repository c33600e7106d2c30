"""Model operation counts against hand counts for both networks."""

import json
import pathlib

import pytest

from bench import flops

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_dqn_dueling_mlp():
    c = _cfg("apex_dqn_chainworld_mlp")
    # 66 -> 512 -> 512, value 512 -> 512 -> 1, advantage 512 -> 512 -> 4
    f = 2 * (66 * 512 + 512 * 512) + 2 * (512 * 512 + 512) + 2 * (512 * 512 + 512 * 4)
    assert f == 1645568
    assert flops.dqn_forward(c) == f
    assert flops.per_learner_sample(c) == 5 * f
    assert flops.per_env_step(c) == pytest.approx(f * 51 / 50)


def test_dpg_actor_critic():
    c = _cfg("apex_dpg_pointmass")
    p = 2 * (6 * 300 + 300 * 200 + 200 * 2)
    q = 2 * (8 * 400 + 400 * 300 + 300 * 1)
    assert (p, q) == (124400, 247000)
    assert flops.dpg_forwards(c) == (p, q)
    assert flops.per_learner_sample(c) == 4 * p + 6 * q
    assert flops.per_env_step(c) == pytest.approx((p + 2 * q) * 51 / 50)
