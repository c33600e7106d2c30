"""Faults planted in each cell's timed path turn ``correct`` false; the
path left whole turns it true. Each fault is patched into the program
before the cell builds its programs, so the timed path itself carries it:

* ``unchanged``: the learner step returns its state unchanged;
* ``half_batch``: the learner leaves out half of the batch and takes the
  mean over the rest;
* ``stale_node``: the sum tree's write leaves one internal node stale;
* ``uniform_sampling``: sampling ignores the priorities;
* ``altered_row``: the rollout's actions are altered where they are made.
"""

import jax
import jax.numpy as jnp
import pytest

CELLS = ("dqn.lockstep", "dqn.async", "dpg.async")
FAULTS = ("unchanged", "half_batch", "stale_node", "uniform_sampling", "altered_row")


def _plant(monkeypatch, fault):
    from repro.core import sumtree
    from repro.runtime import phases
    learn, write, sample, act = (phases.learn_phase, sumtree.write,
                                 sumtree.sample_with_mass, phases.act_phase)
    if fault == "unchanged":
        def bad(cfg, agent, opt, lsl, items, w, axis_name=None):
            new, prios, m = learn(cfg, agent, opt, lsl, items, w, axis_name)
            return lsl._replace(learner_step=new.learner_step), prios, m
        monkeypatch.setattr(phases, "learn_phase", bad)
    elif fault == "half_batch":
        def bad(cfg, agent, opt, lsl, items, w, axis_name=None):
            h = w.shape[0] // 2
            new, prios, m = learn(cfg, agent, opt, lsl,
                                  jax.tree.map(lambda x: x[:h], items), w[:h], axis_name)
            return new, jnp.concatenate([prios, prios]), m
        monkeypatch.setattr(phases, "learn_phase", bad)
    elif fault == "stale_node":
        def bad(tree, idx, values):
            return write(tree, idx, values).at[2].set(tree[2])
        monkeypatch.setattr(sumtree, "write", bad)
    elif fault == "uniform_sampling":
        def bad(tree, u):
            cap = tree.shape[0] // 2
            idx = (u / jnp.maximum(tree[1], 1e-30) * cap).astype(jnp.int32) % cap
            return idx, tree[cap:][idx]
        monkeypatch.setattr(sumtree, "sample_with_mass", bad)
    elif fault == "altered_row":
        def bad(*args, **kw):
            sl, block, m = act(*args, **kw)
            a = block.items["action"]
            a = (a + 1) % 4 if a.dtype == jnp.int32 else jnp.clip(a + 0.5, -1.0, 1.0)
            return sl, block._replace(items=dict(block.items, action=a)), m
        monkeypatch.setattr(phases, "act_phase", bad)


@pytest.mark.parametrize("cell", CELLS)
def test_whole_path_is_correct(run_cell, cell):
    result, lines = run_cell(cell)
    assert result["correct"], lines


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_caught(run_cell, monkeypatch, cell, fault):
    _plant(monkeypatch, fault)
    result, lines = run_cell(cell)
    assert not result["correct"], lines
