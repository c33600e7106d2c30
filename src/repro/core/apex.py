"""The Ape-X loop on a TPU mesh: SPMD actor/learner alternation.

Paper architecture (Fig. 1): many actors feed a shared prioritized replay; a
single learner samples, updates, and writes back priorities; actors refresh
parameters periodically. TPU-native realization (DESIGN.md §2):

* Actor lanes — every ``data``-axis shard steps a vector of environments with
  its slice of the global eps-ladder; the *whole* global lane vector plays the
  role of the paper's N actors (eps_i = eps^(1 + i/(N-1)*alpha) over global
  lane ids).
* Sharded replay — each shard owns ``capacity/num_shards`` slots. Experience
  never crosses shards; the learner's gradient psum and two scalars per
  sampling round (global size, global max-IS-weight) are the only collectives.
* Staleness — actors act with a parameter copy refreshed every
  ``param_sync_period`` iterations (paper: every 400 frames), making the
  off-policy gap explicit and testable.
* Alternation — acting and learning run bulk-synchronously;
  ``learner_steps_per_iter`` and ``rollout_len`` set the paper's generate :
  consume ratio (~12.5K : 9.7K transitions/s in §4.1).

Everything below is per-shard pure functions plus a ``shard_map`` wrapper.
The phase bodies themselves (rollout, update, priority write-back) live in
``repro.runtime.phases`` and are shared with the decoupled async runtime
(``repro.runtime.runner``); this module composes them bulk-synchronously.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import priority as prio, replay as replay_lib, sampling
from repro.envs.synthetic import batch_reset
from repro.runtime import phases


@dataclasses.dataclass(frozen=True)
class ApexConfig:
    replay: replay_lib.ReplayConfig
    lanes_per_shard: int = 32          # vectorized envs per shard
    num_shards: int = 1                # data-axis size (for the global ladder)
    rollout_len: int = 16              # T env steps per actor phase
    n_step: int = 3                    # paper: n = 3
    batch_size: int = 64               # learner batch per shard
    learner_steps_per_iter: int = 1
    param_sync_period: int = 1         # iterations between actor param refresh
    target_update_period: int = 100    # learner steps (paper Atari: 2500)
    evict_interval: int = 100          # learner steps between evictions (paper: 100)
    evict_num: int = 0                 # victims per prioritized eviction (DPG mode)
    eviction: str = "fifo"             # "fifo" | "prioritized"
    replicate_k: int = 1               # Fig. 6 ablation: add each transition k times
    eps_mode: str = "ladder"           # "ladder" | "fixed_set" (Fig. 7 ablation)
    eps_base: float = prio.EPSILON_BASE
    eps_alpha: float = prio.EPSILON_ALPHA
    compress_obs: bool = False         # store obs via the uint8 codec (the
                                       # paper's PNG-compression analogue)

    @property
    def num_actors(self) -> int:
        return self.lanes_per_shard * self.num_shards

    @property
    def window(self) -> int:
        return self.rollout_len - self.n_step + 1


class ApexState(NamedTuple):
    # replicated across shards
    params: Any
    target_params: Any
    opt_state: Any
    actor_params: Any          # the stale copy actors act with
    iteration: jax.Array
    learner_step: jax.Array
    # per-shard
    replay: replay_lib.ReplayState
    env_state: Any             # (lanes, ...)
    obs: jax.Array             # (lanes, ...)
    ep_return: jax.Array       # (lanes,) running episode return
    rng: jax.Array
    frames: jax.Array          # env steps on this shard


REPLICATED_FIELDS = ("params", "target_params", "opt_state", "actor_params",
                     "iteration", "learner_step")


def lane_epsilons(cfg: ApexConfig, shard_id: jax.Array) -> jax.Array:
    """This shard's slice of the global exploration ladder."""
    return phases.lane_epsilons(cfg, shard_id)


def init_state(cfg: ApexConfig, env, agent, optimizer, rng: jax.Array,
               shard_id: jax.Array | int = 0) -> ApexState:
    rng = jax.random.fold_in(rng, jnp.asarray(shard_id))
    p_rng, e_rng, s_rng = jax.random.split(rng, 3)
    env_state, obs = batch_reset(env, e_rng, cfg.lanes_per_shard)
    params = agent.init(p_rng, obs[:1])
    item = _item_example(env, obs, cfg.compress_obs)
    return ApexState(
        params=params,
        target_params=jax.tree.map(jnp.copy, params),
        opt_state=optimizer.init(params),
        actor_params=jax.tree.map(jnp.copy, params),
        iteration=jnp.zeros((), jnp.int32),
        learner_step=jnp.zeros((), jnp.int32),
        replay=replay_lib.init(cfg.replay, item),
        env_state=env_state,
        obs=obs,
        ep_return=jnp.zeros((cfg.lanes_per_shard,), jnp.float32),
        rng=s_rng,
        frames=jnp.zeros((), jnp.int32),
    )


def _item_example(env, obs: jax.Array, compress: bool = False) -> dict:
    return phases.item_example(env, obs, compress)


# ---------------------------------------------------------------------------
# Actor phase
# ---------------------------------------------------------------------------

def actor_phase(cfg: ApexConfig, env, agent, state: ApexState,
                shard_id: jax.Array | int = 0) -> tuple[ApexState, dict]:
    """Roll out T steps per lane, build n-step transitions from the trajectory,
    compute initial priorities from the buffered Q-values, bulk-add to the
    shard's replay slots (Alg. 1, vectorized). Thin wrapper over the shared
    ``runtime.phases.act_phase`` + ``replay_add`` pair."""
    aslice = phases.ActorSlice(
        env_state=state.env_state, obs=state.obs, ep_return=state.ep_return,
        rng=state.rng, frames=state.frames)
    aslice, block, metrics = phases.act_phase(
        cfg, env, agent, state.actor_params, aslice, shard_id)
    new_replay = phases.replay_add(cfg, state.replay, block)
    state = state._replace(
        replay=new_replay, env_state=aslice.env_state, obs=aslice.obs,
        ep_return=aslice.ep_return, rng=aslice.rng, frames=aslice.frames)
    return state, metrics


# ---------------------------------------------------------------------------
# Learner phase
# ---------------------------------------------------------------------------

def _global_is_weights(cfg: ApexConfig, batch: replay_lib.SampleBatch,
                       size: jax.Array, axis_name: str | None) -> jax.Array:
    """IS weights for the *actual* global sampling distribution.

    With equal per-shard quotas, P(i) = leaf_i / (shard_total * num_shards);
    correcting with the global N and global max keeps the estimate unbiased
    even when shard masses drift apart. Two scalar collectives total. The
    formula itself lives in ``repro.core.sampling`` and is shared with the
    async fabric's host-side merge (``sampling.merged_is_weights``).
    """
    if axis_name is None:
        return batch.is_weights
    return sampling.collective_is_weights(
        batch.leaf_mass, batch.total_mass, size, cfg.num_shards,
        cfg.replay.beta, axis_name)


def learner_phase(cfg: ApexConfig, agent, optimizer, state: ApexState,
                  axis_name: str | None = None) -> tuple[ApexState, dict]:
    """Sample prioritized batches, apply the off-policy update, write back
    fresh priorities, periodically update the target net and evict (Alg. 2)."""
    rcfg = cfg.replay

    def one_step(st: ApexState, rng: jax.Array) -> tuple[ApexState, dict]:
        ready = replay_lib.can_sample(rcfg, st.replay)
        if axis_name is not None:
            # learner starts only when every shard passed min-fill (paper: a
            # single global threshold of 50000 transitions).
            ready = jax.lax.pmin(ready.astype(jnp.int32), axis_name) > 0

        def do_update(st: ApexState) -> tuple[ApexState, dict]:
            s_rng, e_rng = jax.random.split(rng)
            batch = phases.replay_sample(cfg, st.replay, s_rng, cfg.batch_size)
            weights = _global_is_weights(cfg, batch, st.replay.size, axis_name)
            lslice = phases.LearnerSlice(
                params=st.params, target_params=st.target_params,
                opt_state=st.opt_state, learner_step=st.learner_step)
            lslice, new_prios, metrics = phases.learn_phase(
                cfg, agent, optimizer, lslice, batch.items, weights, axis_name)
            rep = phases.priority_writeback(
                cfg, st.replay, batch.indices, new_prios,
                lslice.learner_step, e_rng)
            st = st._replace(params=lslice.params, opt_state=lslice.opt_state,
                             target_params=lslice.target_params, replay=rep,
                             learner_step=lslice.learner_step)
            return st, {**metrics, "updated": jnp.ones((), jnp.float32)}

        def skip(st: ApexState) -> tuple[ApexState, dict]:
            zero = {k: jnp.zeros((), jnp.float32) for k in _metric_keys(agent)}
            return st, {**zero, "updated": jnp.zeros((), jnp.float32)}

        return jax.lax.cond(ready, do_update, skip, st)

    if cfg.learner_steps_per_iter == 0:   # actor-only mode (ablations)
        zero = {k: jnp.zeros((), jnp.float32) for k in _metric_keys(agent)}
        return state, {**zero, "updated": jnp.zeros((), jnp.float32)}
    rng, sub = jax.random.split(state.rng)
    step_rngs = jax.random.split(sub, cfg.learner_steps_per_iter)
    state = state._replace(rng=rng)
    state, metrics = jax.lax.scan(
        lambda st, r: one_step(st, r), state, step_rngs)
    return state, jax.tree.map(lambda m: m[-1], metrics)


def _metric_keys(agent) -> tuple[str, ...]:
    from repro.core.agents import DPGAgent
    if isinstance(agent, DPGAgent):
        return ("critic_loss", "policy_loss", "mean_q")
    return ("loss", "mean_q", "mean_abs_td")


# ---------------------------------------------------------------------------
# Full iteration + distribution wrappers
# ---------------------------------------------------------------------------

def train_iteration(cfg: ApexConfig, env, agent, optimizer, state: ApexState,
                    shard_id: jax.Array | int = 0,
                    axis_name: str | None = None) -> tuple[ApexState, dict]:
    # Periodic actor parameter refresh (paper: every 400 frames).
    sync = (state.iteration % cfg.param_sync_period) == 0
    actor_params = jax.tree.map(
        lambda p, a: jnp.where(sync, p, a), state.params, state.actor_params)
    state = state._replace(actor_params=actor_params)

    state, actor_metrics = actor_phase(cfg, env, agent, state, shard_id)
    state, learner_metrics = learner_phase(cfg, agent, optimizer, state, axis_name)
    state = state._replace(iteration=state.iteration + 1)
    return state, {**actor_metrics, **learner_metrics,
                   "replay_size": state.replay.size.astype(jnp.float32),
                   "frames": state.frames.astype(jnp.float32)}


def make_train_fn(cfg: ApexConfig, env, agent, optimizer, mesh=None,
                  data_axis: str = "data"):
    """Build (init_fn, step_fn).

    Without a mesh: single-shard jitted loop (tests/examples). With a mesh:
    ``shard_map`` over the data axis — replicated learner state, per-shard
    replay/envs; collectives are the gradient pmean + the IS/min-fill scalars.
    Either way the two programs are named ``jit_apex_init`` and
    ``jit_apex_step``.
    """
    if mesh is None:
        def apex_init(rng):
            return init_state(cfg, env, agent, optimizer, rng, 0)

        def apex_step(st):
            return train_iteration(cfg, env, agent, optimizer, st, 0, None)

        return jax.jit(apex_init), jax.jit(apex_step)

    shard_map = functools.partial(jax.shard_map, check_vma=False)

    def apex_init(rng):
        sid = jax.lax.axis_index(data_axis)
        st = init_state(cfg, env, agent, optimizer, rng, sid)
        return _add_leading(st)

    def apex_step(st):
        sid = jax.lax.axis_index(data_axis)
        st = _strip_leading(st)
        st, metrics = train_iteration(cfg, env, agent, optimizer, st, sid, data_axis)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, data_axis), metrics)
        return _add_leading(st), metrics

    def state_specs():
        def spec_for(field, leaf_spec):
            return leaf_spec
        reps = {f: P() for f in REPLICATED_FIELDS}
        return ApexState(**reps, **{
            f: P(data_axis) for f in ApexState._fields if f not in reps})

    specs = state_specs()
    init_fn = jax.jit(shard_map(
        apex_init, mesh=mesh, in_specs=P(), out_specs=specs))
    step_fn = jax.jit(shard_map(
        apex_step, mesh=mesh, in_specs=(specs,),
        out_specs=(specs, P())))
    return init_fn, step_fn


def _add_leading(st: ApexState) -> ApexState:
    """Re-attach the per-shard leading axis expected by shard_map out_specs."""
    return ApexState(**{
        f: (getattr(st, f) if f in REPLICATED_FIELDS
            else jax.tree.map(lambda x: x[None], getattr(st, f)))
        for f in ApexState._fields})


def _strip_leading(st: ApexState) -> ApexState:
    return ApexState(**{
        f: (getattr(st, f) if f in REPLICATED_FIELDS
            else jax.tree.map(lambda x: x[0], getattr(st, f)))
        for f in ApexState._fields})
