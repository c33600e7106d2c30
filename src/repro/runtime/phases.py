"""Pure per-phase functions shared by the synchronous driver and the async
runtime.

The paper decouples acting from learning (§3): actors generate experience at
their own pace, the learner consumes prioritized samples at its own pace, and
the replay memory sits between them. To make that decoupling real in code,
the Ape-X iteration is split here into five pure, independently jittable
functions:

* ``act_phase``        — roll out T env steps per lane and emit a
                         ``TransitionBlock`` (items + actor-side initial
                         priorities). Touches no replay state.
* ``replay_add``       — insert a block into a replay shard (FIFO or
                         alloc-into-free-slots, per config).
* ``replay_sample``    — draw a prioritized batch from a replay shard.
* ``learn_phase``      — one prioritized update from an already-sampled
                         batch: loss/grads, optimizer step, periodic target
                         sync. Returns fresh priorities; touches no replay
                         state.
* ``priority_writeback`` — write learner priorities back into the replay
                         shard and run the periodic eviction policy.

Each phase runs under a ``jax.named_scope`` of its own (``act``,
``replay_add``, ``replay_sample``, ``learn``, ``replay_writeback``), so
every device op of a program that fuses several phases carries its phase
in its op metadata, and a profiler trace can split such a program's
device time by phase.

``repro.core.apex`` composes them bulk-synchronously inside one jitted step;
``repro.runtime.runner`` composes them across actor / replay-service /
learner threads. Both paths share these exact functions, so the async
runtime's numerics per phase match the lockstep driver's.

``cfg`` everywhere is an ``apex.ApexConfig`` (accessed structurally to avoid
an import cycle with ``repro.core.apex``).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import codec, nstep, priority as prio, replay as replay_lib
from repro.envs.synthetic import batch_reset, batch_step
from repro.optim import optimizers as optim


class ActorSlice(NamedTuple):
    """Per-actor mutable state: everything an actor thread owns exclusively."""
    env_state: Any
    obs: jax.Array             # (lanes, ...)
    ep_return: jax.Array       # (lanes,) running episode return
    rng: jax.Array
    frames: jax.Array          # env steps taken by this slice


class TransitionBlock(NamedTuple):
    """A flat block of n-step transitions plus actor-computed priorities —
    the unit of actor → replay traffic (paper Alg. 1 l.10-11, batched)."""
    items: Any                 # pytree of (B, ...) arrays
    priorities: jax.Array      # (B,)


class LearnerSlice(NamedTuple):
    """Learner-owned state: online/target params, optimizer, step count."""
    params: Any
    target_params: Any
    opt_state: Any
    learner_step: jax.Array


def lane_epsilons(cfg, shard_id: jax.Array) -> jax.Array:
    """This shard's slice of the global exploration ladder (paper §3)."""
    if cfg.eps_mode == "ladder":
        table = prio.epsilon_ladder(cfg.num_actors, cfg.eps_base, cfg.eps_alpha)
    elif cfg.eps_mode == "fixed_set":
        table = prio.fixed_epsilon_set(cfg.num_actors)
    else:
        raise ValueError(cfg.eps_mode)
    gids = shard_id * cfg.lanes_per_shard + jnp.arange(cfg.lanes_per_shard)
    return table[gids]


def item_example(env, obs: jax.Array, compress: bool = False) -> dict:
    """Replay item layout: the paper stores both endpoint states per
    transition ("costs more RAM, but simplifies the code" — Appendix F)."""
    ob = obs[0]
    if compress:
        ob = codec.encode(ob[None])._asdict()
        ob = {k: v[0] for k, v in ob.items()}
    if hasattr(env, "num_actions"):
        action = jnp.zeros((), jnp.int32)
    else:
        action = jnp.zeros((env.action_dim,), jnp.float32)
    return {
        "obs": ob, "action": action,
        "returns": jnp.zeros((), jnp.float32),
        "discount_n": jnp.zeros((), jnp.float32),
        "next_obs": ob,
    }


def initial_actor_slice(cfg, env, seed: int, actor_id: int) -> ActorSlice:
    """The canonical starting slice for global actor ``actor_id`` of a run
    seeded with ``seed``. Every actor host derives its slice through this
    one function — runner threads and remote actor processes alike — so the
    exploration ladder cannot fork across the process boundary."""
    _, e_rng = jax.random.split(jax.random.key(seed))
    a_rng = jax.random.fold_in(e_rng, actor_id)
    env_state, obs = batch_reset(env, a_rng, cfg.lanes_per_shard)
    return ActorSlice(
        env_state=env_state, obs=obs,
        ep_return=jnp.zeros((cfg.lanes_per_shard,), jnp.float32),
        rng=jax.random.fold_in(a_rng, 1),
        frames=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Act phase
# ---------------------------------------------------------------------------

@jax.named_scope("act")
def act_phase(cfg, env, agent, actor_params: Any, aslice: ActorSlice,
              shard_id: jax.Array | int = 0,
              ) -> tuple[ActorSlice, TransitionBlock, dict]:
    """Roll out T steps per lane, build n-step transitions from the
    trajectory, and compute initial priorities from the buffered Q-values
    (Alg. 1, vectorized). Pure: emits a ``TransitionBlock`` instead of
    writing to replay, so actors need no access to the replay shard."""
    eps = lane_epsilons(cfg, jnp.asarray(shard_id))
    rng, rollout_rng, last_rng = jax.random.split(aslice.rng, 3)
    step_rngs = jax.random.split(rollout_rng, cfg.rollout_len)

    def step_fn(carry, rng_t):
        env_state, obs, ep_ret = carry
        a, aux = agent.act(actor_params, rng_t, obs, eps)
        env_state, out = batch_step(env, env_state, a)
        done = out.discount == 0.0
        ep_ret_next = jnp.where(done, 0.0, ep_ret + out.reward)
        completed = jnp.where(done, ep_ret + out.reward, jnp.nan)
        emit = dict(obs=obs, action=a, aux=aux, reward=out.reward,
                    discount=out.discount, completed=completed)
        return (env_state, out.obs, ep_ret_next), emit

    (env_state, last_obs, ep_ret), traj = jax.lax.scan(
        step_fn, (aslice.env_state, aslice.obs, aslice.ep_return), step_rngs)
    # time-major (T, lanes, ...) -> lane-major (lanes, T, ...)
    traj = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), traj)

    # Bootstrap aux at the final state S_T (one extra policy eval).
    _, last_aux = agent.act(actor_params, last_rng, last_obs, eps)

    n, W = cfg.n_step, cfg.window
    returns, discount_n = nstep.from_trajectory(traj["reward"], traj["discount"], n)

    full_obs = jnp.concatenate([traj["obs"], last_obs[:, None]], axis=1)  # (lanes, T+1, ...)
    full_aux = jax.tree.map(
        lambda a, b: jnp.concatenate([a, b[:, None]], axis=1), traj["aux"], last_aux)

    first_aux = jax.tree.map(lambda x: x[:, :W], full_aux)
    last_aux_w = jax.tree.map(lambda x: x[:, n:], full_aux)
    action_w = traj["action"][:, :W]
    priorities = agent.initial_priorities(
        *jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]),
                      (first_aux, action_w, returns, discount_n, last_aux_w)))

    flat = lambda x: x.reshape((-1,) + x.shape[2:])
    enc = ((lambda o: dict(codec.encode(o)._asdict())) if cfg.compress_obs
           else (lambda o: o))
    items = {
        "obs": enc(flat(full_obs[:, :W])),
        "action": flat(action_w),
        "returns": flat(returns),
        "discount_n": flat(discount_n),
        "next_obs": enc(flat(full_obs[:, n:])),
    }
    if cfg.replicate_k > 1:  # Fig. 6 recency-vs-diversity ablation
        items = jax.tree.map(
            lambda x: jnp.tile(x, (cfg.replicate_k,) + (1,) * (x.ndim - 1)), items)
        priorities = jnp.tile(priorities, cfg.replicate_k)

    completed = traj["completed"]
    n_done = jnp.sum(~jnp.isnan(completed))
    mean_ep_return = jnp.where(
        n_done > 0, jnp.nansum(completed) / jnp.maximum(n_done, 1), jnp.nan)
    metrics = {"mean_ep_return": mean_ep_return, "episodes": n_done,
               "mean_initial_priority": priorities.mean()}

    aslice = ActorSlice(
        env_state=env_state, obs=last_obs, ep_return=ep_ret, rng=rng,
        frames=aslice.frames + cfg.lanes_per_shard * cfg.rollout_len)
    return aslice, TransitionBlock(items, priorities), metrics


def learner_batch_example(cfg, item: Any) -> tuple[Any, jax.Array]:
    """Storage-shaped garbage ``(items, is_weights)`` at the learner batch
    size — the canonical input for warming ``learn_phase`` jit caches before
    a clock starts (the runner and the sample-plane benches share it so the
    warm-up cannot drift from the real batch layout)."""
    items = jax.tree.map(
        lambda a: jnp.zeros((cfg.batch_size,) + jnp.shape(a),
                            jnp.asarray(a).dtype), item)
    return items, jnp.ones((cfg.batch_size,), jnp.float32)


@jax.named_scope("replay_add")
def replay_add(cfg, replay_state: replay_lib.ReplayState,
               block: TransitionBlock) -> replay_lib.ReplayState:
    """Insert a transition block into a replay shard (the replay side of
    Alg. 1 l.10-11): circular FIFO for the Atari regime, alloc-into-free
    slots for the DPG/prioritized-eviction regime."""
    add = replay_lib.add_fifo if cfg.eviction == "fifo" else replay_lib.add_alloc
    return add(cfg.replay, replay_state, block.items, block.priorities)


@jax.named_scope("replay_sample")
def replay_sample(cfg, replay_state: replay_lib.ReplayState, rng: jax.Array,
                  batch_size: int) -> replay_lib.SampleBatch:
    """A prioritized batch of ``batch_size`` from a replay shard (Alg. 2
    l.4)."""
    return replay_lib.sample(cfg.replay, replay_state, rng, batch_size)


# ---------------------------------------------------------------------------
# Learn phase
# ---------------------------------------------------------------------------

@jax.named_scope("learn")
def learn_phase(cfg, agent, optimizer, lslice: LearnerSlice, items: Any,
                weights: jax.Array, axis_name: str | None = None,
                ) -> tuple[LearnerSlice, jax.Array, dict]:
    """One prioritized update from an already-sampled batch (Alg. 2 l.5-7):
    decode, loss/grads, optimizer step, periodic target sync. Returns the
    fresh |TD| priorities for write-back; touches no replay state."""
    if cfg.compress_obs:  # decode fuses into the learner forward
        items = dict(items)
        items["obs"] = codec.decode(codec.EncodedObs(**items["obs"]))
        items["next_obs"] = codec.decode(codec.EncodedObs(**items["next_obs"]))
    params, opt_state, new_prios, metrics = agent.update(
        lslice.params, lslice.target_params, lslice.opt_state, optimizer,
        items, weights, axis_name)
    step = lslice.learner_step + 1
    target = optim.periodic_target_update(
        params, lslice.target_params, step, cfg.target_update_period)
    lslice = LearnerSlice(params=params, target_params=target,
                          opt_state=opt_state, learner_step=step)
    return lslice, new_prios, metrics


@jax.named_scope("replay_writeback")
def priority_writeback(cfg, replay_state: replay_lib.ReplayState,
                       indices: jax.Array, priorities: jax.Array,
                       learner_step: jax.Array, rng: jax.Array,
                       ) -> replay_lib.ReplayState:
    """Write fresh learner priorities back into the shard (Alg. 2 l.8) and
    run the periodic eviction policy (paper: every 100 learning steps).
    ``learner_step`` is the post-update step count."""
    rcfg = cfg.replay
    rep = replay_lib.set_priorities(rcfg, replay_state, indices, priorities)
    if cfg.eviction == "fifo":
        rep = jax.lax.cond(
            learner_step % cfg.evict_interval == 0,
            lambda r: replay_lib.evict_fifo(rcfg, r), lambda r: r, rep)
    else:
        evict_num = cfg.evict_num or cfg.batch_size
        rep = jax.lax.cond(
            (learner_step % cfg.evict_interval == 0) & (rep.size > rcfg.soft_cap),
            lambda r: replay_lib.evict_prioritized(rcfg, r, rng, evict_num),
            lambda r: r, rep)
    return rep
