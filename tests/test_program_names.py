"""Every device program of the runtime and the lockstep loop carries a
stable module name, and naming it (plus the phase scopes in its op
metadata) changes nothing else: each program's optimised HLO equals that of
the anonymous ``jax.jit(lambda ...)`` (or the fabric's generic ``part`` and
``merge``) it replaced, once names and metadata are stripped. The
references call the phase bodies without their ``jax.named_scope``
(``__wrapped__``), as the lambdas did."""

import re

import jax
import jax.numpy as jnp
import pytest
from _apex_helpers import item_example, make_block, tiny_preset
from jax._src.lib import xla_client

from repro.core import apex, replay as replay_lib, sampling
from repro.runtime import fabric, phases, runner, service


def _hlo(jitted, *args) -> tuple[str, str]:
    """(module name, optimised HLO text without names and metadata)."""
    (module,) = jitted.lower(*args).compile().runtime_executable().hlo_modules()
    opts = xla_client._xla.HloPrintOptions()
    opts.print_metadata = False
    opts.canonicalize_instruction_names = True
    opts.print_program_shape = False
    text = module.to_string(opts)
    return module.name, re.sub(r"^HloModule \S+", "HloModule", text, flags=re.M)


@pytest.fixture(scope="module")
def programs():
    """name -> (named program, the lambda it replaced, arguments)."""
    preset = tiny_preset()
    cfg, env, agent = preset.apex, preset.env, preset.agent
    opt = preset.make_optimizer()
    rcfg, batch = cfg.replay, cfg.batch_size
    st = replay_lib.init(rcfg, item_example(env))
    block = make_block(cfg, env, agent)
    key = jax.random.key(0)
    idx = jnp.arange(batch, dtype=jnp.int32)
    prios = jnp.ones((batch,), jnp.float32)
    fns = service.make_shard_fns(cfg, batch)

    act, learn = phases.act_phase.__wrapped__, phases.learn_phase.__wrapped__
    add, wb = phases.replay_add.__wrapped__, phases.priority_writeback.__wrapped__
    out = {
        "replay_add": (fns.add, jax.jit(lambda st, block: add(cfg, st, block),
                                        donate_argnums=(0,)), (st, block)),
        "replay_sample": (fns.sample, jax.jit(
            lambda st, rng: replay_lib.sample(rcfg, st, rng, batch)), (st, key)),
        "replay_writeback": (fns.writeback, jax.jit(
            lambda st, idx, prios, step, rng: wb(cfg, st, idx, prios, step, rng),
            donate_argnums=(0,)), (st, idx, prios, 10, key)),
        "replay_can_sample": (fns.can_sample, jax.jit(
            lambda st: replay_lib.can_sample(rcfg, st)), (st,)),
        "replay_rng_split": (fns.split, jax.jit(lambda k: jax.random.split(k)), (key,)),
    }

    shards, cap = 2, rcfg.capacity

    def part(indices, priorities):
        sids = indices // cap
        order = jnp.argsort(sids, stable=True)
        counts = jnp.sum(sids[:, None] == jnp.arange(shards)[None, :], axis=0)
        return (indices - sids * cap)[order], priorities[order], counts

    def merge(subs):
        leaf = jnp.stack([b.leaf_mass for b in subs])
        totals = jnp.stack([b.total_mass for b in subs])
        sizes = jnp.stack([b.size for b in subs])
        w = sampling.merged_is_weights(leaf, totals, sizes, rcfg.beta).reshape(-1)
        gidx = jnp.concatenate([b.indices + k * cap for k, b in enumerate(subs)])
        items = jax.tree.map(lambda *xs: jnp.concatenate(xs), *[b.items for b in subs])
        return gidx, items, w

    sub = jax.eval_shape(lambda st, k: replay_lib.sample(rcfg, st, k, batch // 2), st, key)
    out["replay_route_writeback"] = (fabric._partition_fn(shards, cap), jax.jit(part),
                                     (idx, prios))
    out["replay_merge"] = (fabric._merge_fn(rcfg.beta, cap), jax.jit(merge), ((sub, sub),))

    rollout, learner_step, learner_step_many = runner.make_runner_fns(cfg, env, agent, opt)
    aslice = phases.initial_actor_slice(cfg, env, 0, 0)
    params = agent.init(key, aslice.obs[:1])
    lslice = phases.LearnerSlice(params=params, target_params=params,
                                 opt_state=opt.init(params),
                                 learner_step=jnp.zeros((), jnp.int32))
    items, w = phases.learner_batch_example(cfg, item_example(env))
    items_k = jax.tree.map(lambda a: jnp.stack([a, a]), items)

    def learn_scan(lsl, items_k, w_k):
        def body(l, xw):
            l, p, _ = learn(cfg, agent, opt, l, xw[0], xw[1], None)
            return l, p
        return jax.lax.scan(body, lsl, (items_k, w_k))

    out["actor_rollout"] = (rollout, jax.jit(
        lambda p, sl, sid: act(cfg, env, agent, p, sl, sid)), (params, aslice, jnp.int32(0)))
    out["learner_step"] = (learner_step, jax.jit(
        lambda lsl, it, w: learn(cfg, agent, opt, lsl, it, w, None)), (lslice, items, w))
    out["learner_step_many"] = (learner_step_many, jax.jit(learn_scan),
                                (lslice, items_k, jnp.stack([w, w])))

    init_fn, step_fn = apex.make_train_fn(cfg, env, agent, opt)
    state = jax.eval_shape(init_fn, key)
    out["apex_init"] = (init_fn, jax.jit(
        lambda rng: apex.init_state(cfg, env, agent, opt, rng, 0)), (key,))
    out["apex_step"] = (step_fn, jax.jit(
        lambda st: apex.train_iteration(cfg, env, agent, opt, st, 0, None)), (state,))
    return out


NAMES = ["replay_add", "replay_sample", "replay_writeback", "replay_can_sample",
         "replay_rng_split", "replay_route_writeback", "replay_merge",
         "actor_rollout", "learner_step", "learner_step_many", "apex_init", "apex_step"]


@pytest.mark.parametrize("name", NAMES)
def test_named_program_hlo_equals_the_lambda(programs, name):
    named, lam, args = programs[name]
    got_name, got = _hlo(named, *args)
    assert got_name == f"jit_{name}"
    assert got == _hlo(lam, *args)[1]


def test_stripped_hlo_still_tells_programs_apart(programs):
    """The comparison is not blind: the add without its donation differs."""
    named, _, args = programs["replay_add"]
    undonated = jax.jit(named.__wrapped__)
    assert _hlo(undonated, *args)[1] != _hlo(named, *args)[1]


def test_lockstep_ops_carry_their_phase_scope(programs):
    """Inside the fused step every phase's ops carry its scope."""
    step_fn, _, (state,) = programs["apex_step"]
    text = step_fn.lower(state).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("act", "replay_add", "replay_sample", "learn", "replay_writeback"):
        assert any(scope in n.split("/") for n in names), scope
