"""Plain reference of the Ape-X iteration, written apart from the program.

It imports nothing of ``repro``. It reads the program's state only as data
(arrays by field name) and rebuilds every output from the configuration file
of the cell, in straightforward ``jax.numpy``: the two environments, the
dueling Q-network and the DPG actor and critic, the rollout and its n-step
rows, the replay's adds, sum tree, proportional sampling, importance
weights, priority write-back and both evictions, and the learner's loss,
gradient and optimizer step.

Precision. The configurations state float32 parameters and activations with
matrix products at JAX's default precision, which is what the program runs.
The reference computes at that precision (``dt=float32``). The control runs
the same code at ``dt=bfloat16``: parameters, activations, environment
state, optimizer state, leaves and tree sums all rounded to bfloat16.

The random key schedule is part of the semantics (which key draws which
exploration coin, slip, noise and stratum), so the reference splits keys in
the same order as the program does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
MIN_PRIORITY = 1e-4


def cast(tree, dt):
    return jax.tree.map(
        lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree)


# ---------------------------------------------------------------------------
# Environments
# ---------------------------------------------------------------------------

def chain_obs(env: dict, pos, t):
    onehot = (jnp.arange(env["length"]) == pos).astype(jnp.uint8) * 255
    timer = (t * (255 // env["max_steps"])).astype(jnp.uint8)
    return jnp.concatenate([onehot, timer[None], jnp.full((1,), 255, jnp.uint8)])


def chain_step(env: dict, state: dict, action, dt):
    """ChainWorld: a chain of ``length`` cells; +1 at the far end, a small
    reward for the distractor action, slips to a random action."""
    rng, slip_rng, a_rng, _ = jax.random.split(state["rng"], 4)
    slipped = jax.random.uniform(slip_rng) < env["slip_prob"]
    action = jnp.where(slipped,
                       jax.random.randint(a_rng, (), 0, env["num_actions"]),
                       action)
    delta = jnp.where(action == 0, -1, jnp.where(action == 1, 1, 0))
    pos = jnp.clip(state["pos"] + delta, 0, env["length"] - 1)
    t = state["t"] + 1
    reached = pos == env["length"] - 1
    terminal = reached | (t >= env["max_steps"])
    reward = (reached.astype(F32)
              + (action == 3).astype(F32) * env["distractor_reward"])
    discount = jnp.where(terminal, 0.0, env["gamma"]).astype(F32)
    nxt = {"pos": jnp.where(terminal, 0, pos), "t": jnp.where(terminal, 0, t),
           "rng": rng}
    return nxt, chain_obs(env, nxt["pos"], nxt["t"]), reward.astype(dt), discount


def point_reset(rng, dt):
    rng, p_rng, t_rng = jax.random.split(rng, 3)
    return {"pos": jax.random.uniform(p_rng, (2,), minval=-1.0, maxval=1.0).astype(dt),
            "vel": jnp.zeros((2,), dt),
            "target": jax.random.uniform(t_rng, (2,), minval=-1.0, maxval=1.0).astype(dt),
            "t": jnp.zeros((), jnp.int32), "rng": rng}


def point_obs(s):
    return jnp.concatenate([s["pos"], s["vel"], s["target"]])


def point_step(env: dict, s: dict, action, dt):
    """PointMass: a 2-D point driven by acceleration toward a random target;
    reward is minus the distance; resets at ``max_steps``."""
    rng, reset_rng = jax.random.split(s["rng"])
    a = jnp.clip(action, -1.0, 1.0).astype(dt)
    vel = ((1.0 - env["drag"]) * s["vel"] + env["dt"] * a).astype(dt)
    pos = jnp.clip(s["pos"] + env["dt"] * vel, -1.5, 1.5).astype(dt)
    t = s["t"] + 1
    reward = -jnp.linalg.norm(pos - s["target"])
    timeout = t >= env["max_steps"]
    discount = jnp.where(timeout, 0.0, env["gamma"]).astype(F32)
    fresh = point_reset(reset_rng, dt)
    cur = {"pos": pos, "vel": vel, "target": s["target"], "t": t, "rng": rng}
    nxt = {k: jnp.where(timeout, fresh[k], cur[k]) for k in cur}
    return nxt, point_obs(nxt), reward.astype(dt), discount


# ---------------------------------------------------------------------------
# Networks (parameter dicts as the program lays them out)
# ---------------------------------------------------------------------------

def _dense(p, x, dt):
    return x @ p["w"].astype(dt) + p["b"].astype(dt)


def prep_obs(obs, dt):
    if obs.dtype == jnp.uint8:
        return (obs.astype(F32) / 255.0).astype(dt)
    return obs.astype(dt)


def dueling_q(params, obs, dt):
    """Dueling Q-network with an MLP torso: q = v + a - mean(a)."""
    x = prep_obs(obs, dt)
    i = 0
    while f"fc{i}" in params:
        x = jax.nn.relu(_dense(params[f"fc{i}"], x, dt))
        i += 1
    v = _dense(params["val2"], jax.nn.relu(_dense(params["val1"], x, dt)), dt)
    a = _dense(params["adv2"], jax.nn.relu(_dense(params["adv1"], x, dt)), dt)
    return v + a - a.mean(axis=-1, keepdims=True)


def dpg_policy(params, obs, dt):
    x = prep_obs(obs, dt)
    x = jnp.tanh(_dense(params["fc0"], x, dt))
    x = jax.nn.relu(_dense(params["fc1"], x, dt))
    return jnp.tanh(_dense(params["out"], x, dt))


def dpg_critic(params, obs, action, dt):
    x = jnp.concatenate([prep_obs(obs, dt), action.astype(dt)], axis=-1)
    x = jnp.tanh(_dense(params["fc0"], x, dt))
    x = jax.nn.relu(_dense(params["fc1"], x, dt))
    return _dense(params["out"], x, dt)[..., 0]


# ---------------------------------------------------------------------------
# Acting
# ---------------------------------------------------------------------------

def epsilon_ladder(n: int, base: float, alpha: float):
    if n == 1:
        return jnp.array([base], F32)
    i = jnp.arange(n, dtype=F32)
    return jnp.power(base, 1.0 + i / (n - 1) * alpha)


def lane_eps(cfg: dict, lanes: int, shard_id):
    table = epsilon_ladder(cfg["num_actors"], cfg["eps_base"], cfg["eps_alpha"])
    return table[shard_id * lanes + jnp.arange(lanes)]


def _act(cfg, params, rng, obs, eps, dt):
    """One exploration step: returns (greedy-or-mean action, the explored
    action, the explore mask and what initial priorities need)."""
    if cfg["agent"] == "dqn":
        q = dueling_q(params, obs, dt)
        explore_rng, action_rng = jax.random.split(rng)
        greedy = jnp.argmax(q, axis=-1).astype(jnp.int32)
        random = jax.random.randint(action_rng, (obs.shape[0],), 0,
                                    cfg["env"]["num_actions"]).astype(jnp.int32)
        explore = jax.random.uniform(explore_rng, (obs.shape[0],)) < eps
        return {"q": q, "greedy": greedy, "random": random, "explore": explore,
                "action": jnp.where(explore, random, greedy)}
    pi = dpg_policy(params["actor"], obs, dt)
    noise = cfg["sigma"] * jax.random.normal(rng, pi.shape, F32).astype(dt)
    a = jnp.clip(pi + noise, -1.0, 1.0)
    a = jnp.where(eps[:, None] > 0, a, pi)
    return {"pi": pi, "action": a}


def _aux_values(cfg, params, obs, action, act, dt):
    if cfg["agent"] == "dqn":
        return {"q": act["q"]}
    return {"q_sa": dpg_critic(params["critic"], obs, action, dt),
            "q_pi": dpg_critic(params["critic"], obs, act["pi"], dt)}


def rollout(cfg: dict, params, slice_: dict, shard_id, lanes: int, dt,
            forced_actions=None, alter_actions: bool = False):
    """Roll out ``rollout_len`` steps on every lane and build the n-step rows.

    ``slice_`` holds ``env_state`` (a field dict), ``obs`` and ``rng``.
    ``forced_actions`` ((lanes, W) or None) replays the program's stored
    actions for the first W steps, so that one tie broken the other way
    cannot fork a lane; every forced action is scored against the
    reference's own policy (``act_gap``). Returns the rows, their initial
    priorities, the policy gaps and the slice after the rollout.
    """
    env = cfg["env"]
    step_env = chain_step if cfg["agent"] == "dqn" else point_step
    T, n = cfg["rollout_len"], cfg["n_step"]
    W = T - n + 1
    params = cast(params, dt)
    eps = lane_eps(cfg, lanes, shard_id)
    rng, rollout_rng, last_rng = jax.random.split(slice_["rng"], 3)
    step_rngs = jax.random.split(rollout_rng, T)
    env_state = slice_["env_state"]
    if cfg["agent"] == "dpg":
        env_state = cast(env_state, dt)
    obs = slice_["obs"] if cfg["agent"] == "dqn" else slice_["obs"].astype(dt)
    if forced_actions is None:
        forced = jnp.zeros((T, lanes) + (() if cfg["agent"] == "dqn"
                                          else (env["action_dim"],)),
                           jnp.int32 if cfg["agent"] == "dqn" else dt)
        use = jnp.zeros((T,), bool)
    else:
        pad = jnp.zeros((T - W, lanes) + forced_actions.shape[2:], forced_actions.dtype)
        forced = jnp.concatenate([jnp.swapaxes(forced_actions, 0, 1), pad])
        use = jnp.arange(T) < W

    def step(carry, xs):
        env_state, obs = carry
        rng_t, f_t, use_t = xs
        act = _act(cfg, params, rng_t, obs, eps, dt)
        a = act["action"]
        if alter_actions and cfg["agent"] == "dqn":
            a = (a + 1) % env["num_actions"]
        elif alter_actions:
            a = jnp.clip(a + 0.5, -1.0, 1.0)
        if cfg["agent"] == "dqn":
            a_used = jnp.where(use_t, f_t, a)
            q = act["q"]
            gap = jnp.max(q, -1) - jnp.take_along_axis(q, a_used[:, None], -1)[:, 0]
            spread = jnp.max(q, -1) - jnp.min(q, -1)
            # an exploring step must take exactly the key's random action
            bad = act["explore"] & (a_used != act["random"])
            gap = jnp.where(act["explore"], jnp.where(bad, jnp.inf, 0.0), gap)
        else:
            a_used = jnp.where(use_t, f_t.astype(dt), a)
            gap = jnp.max(jnp.abs(a_used.astype(F32) - act["action"].astype(F32)), -1)
            spread = jnp.ones_like(gap)
        aux = _aux_values(cfg, params, obs, a_used, act, dt)
        env_state, nobs, reward, discount = jax.vmap(
            lambda s, ac: step_env(env, s, ac, dt))(env_state, a_used)
        emit = {"obs": obs, "action": a_used, "aux": aux, "reward": reward,
                "discount": discount, "gap": gap.astype(F32),
                "spread": spread.astype(F32), "use": jnp.broadcast_to(use_t, gap.shape)}
        return (env_state, nobs), emit

    (env_state, last_obs), traj = jax.lax.scan(
        step, (env_state, obs), (step_rngs, forced, use))
    traj = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), traj)  # (lanes, T, ...)
    last = _act(cfg, params, last_rng, last_obs, eps, dt)
    last_aux = _aux_values(cfg, params, last_obs, last["action"], last, dt)

    returns = jnp.zeros((lanes, W), dt)
    disc = jnp.ones((lanes, W), F32)
    for k in range(n):
        returns = returns + disc.astype(dt) * traj["reward"][:, k:k + W]
        disc = disc * traj["discount"][:, k:k + W]
    full_obs = jnp.concatenate([traj["obs"], last_obs[:, None]], axis=1)
    full_aux = jax.tree.map(lambda a, b: jnp.concatenate([a, b[:, None]], 1),
                            traj["aux"], last_aux)
    first = jax.tree.map(lambda x: x[:, :W], full_aux)
    later = jax.tree.map(lambda x: x[:, n:], full_aux)
    action_w = traj["action"][:, :W]
    if cfg["agent"] == "dqn":
        q_sa = jnp.take_along_axis(first["q"], action_w[..., None], -1)[..., 0]
        boot = later["q"].max(-1)
    else:
        q_sa, boot = first["q_sa"], later["q_pi"]
    prios = jnp.abs(returns + disc.astype(dt) * boot - q_sa)
    flat = lambda x: x.reshape((-1,) + x.shape[2:])
    rows = {"obs": flat(full_obs[:, :W]), "action": flat(action_w),
            "returns": flat(returns), "discount_n": flat(disc),
            "next_obs": flat(full_obs[:, n:])}
    policy = {"gap": traj["gap"], "spread": traj["spread"], "use": traj["use"]}
    after = {"env_state": env_state, "obs": last_obs, "rng": rng}
    return rows, flat(prios), policy, after


# ---------------------------------------------------------------------------
# Replay: leaves, sum tree, adds, sampling, write-back, eviction
# ---------------------------------------------------------------------------

def to_leaf(p, alpha, dt=F32):
    return jnp.power(jnp.maximum(jnp.abs(p), MIN_PRIORITY).astype(dt), alpha)


def tree_levels(leaves):
    """Pairwise sums, level by level, each node the sum of its two
    children (strided adds, which the compiler cannot reassociate): a flat
    ``(2C,)`` array, index 0 unused, node i's children at 2i and 2i+1."""
    levels = [leaves]
    while levels[-1].shape[0] > 1:
        levels.append(levels[-1][0::2] + levels[-1][1::2])
    return jnp.concatenate([jnp.zeros((1,), leaves.dtype)]
                           + [lv for lv in reversed(levels)])


def tree_write(tree, idx, values):
    """Set leaves ``idx`` (out-of-range lanes dropped) and recompute every
    touched ancestor as the sum of its two children; the other nodes keep
    their values."""
    cap = tree.shape[0] // 2
    ok = (idx >= 0) & (idx < cap)
    tree = tree.at[jnp.where(ok, idx + cap, 2 * cap)].set(values.astype(tree.dtype), mode="drop")
    node = jnp.clip(idx, 0, cap - 1) + cap
    for _ in range(cap.bit_length() - 1):
        node = node // 2
        tree = tree.at[node].set(tree[2 * node] + tree[2 * node + 1])
    return tree


def leaves_of(rep):
    cap = rep["tree"].shape[0] // 2
    return rep["tree"][cap:]


def descend(tree, u):
    """Inverse-CDF walk from the root: left while ``u`` is below the left
    child's mass, else subtract it and go right."""
    cap = tree.shape[0] // 2
    node = jnp.ones(u.shape, jnp.int32)
    u = u.astype(tree.dtype)
    for _ in range(cap.bit_length() - 1):
        left = 2 * node
        lm = tree[left]
        go_left = u < lm
        node = jnp.where(go_left, left, left + 1)
        u = jnp.where(go_left, u, u - lm)
    return jnp.clip(node - cap, 0, cap - 1)


def stratified_u(rng, batch: int, total):
    jitter = jax.random.uniform(rng, (batch,))
    u = (jnp.arange(batch, dtype=F32) + jitter) * (total.astype(F32) / batch)
    return jnp.minimum(u, total.astype(F32) * (1.0 - 1e-6))


def is_weights(leaf, scaled_total, n_items, beta):
    p = leaf.astype(F32) / jnp.maximum(scaled_total.astype(F32), 1e-30)
    n = jnp.maximum(n_items.astype(F32), 1.0)
    w = jnp.power(n * jnp.maximum(p, 1e-30), -beta)
    return w


def sample(rcfg: dict, rep: dict, rng, batch: int, uniform: bool = False):
    """Stratified proportional sampling over the replay's sum tree, with
    max-normalized IS weights. The replay dict holds ``tree``; the captured
    program tree is the input the program sampled from, and its agreement
    with its own leaves is checked apart (``tree_gap``)."""
    tree = rep["tree"]
    total = tree[1]
    u = stratified_u(rng, batch, total)
    cap = tree.shape[0] // 2
    if uniform:  # planted fault: ignore the priorities
        idx = (u / jnp.maximum(total, 1e-30) * cap).astype(jnp.int32) % cap
    else:
        idx = descend(tree, u)
    leaf = tree[cap:][idx]
    w = is_weights(leaf, total, rep["size"], rcfg["beta"])
    return {"indices": idx, "items": jax.tree.map(lambda b: b[idx], rep["storage"]),
            "is_weights": w / jnp.maximum(jnp.max(w), 1e-30), "leaf_mass": leaf,
            "total_mass": total, "size": rep["size"]}


def merged_weights(leaf, totals, sizes, beta):
    """IS weights of a learner batch merged from equal per-shard quotas."""
    s = leaf.shape[0]
    w = is_weights(leaf, (totals * s)[:, None], jnp.sum(sizes), beta)
    return (w / jnp.maximum(jnp.max(w), 1e-30)).reshape(-1)


def add_rows(rcfg: dict, rep: dict, rows, prios, mode: str, dt=F32):
    """Store a block: circular FIFO slots, or the lowest free slots (leaf 0)
    with the overflow dropped."""
    cap = rep["tree"].shape[0] // 2
    b = prios.shape[0]
    if mode == "fifo":
        idx = (rep["write_pos"] + jnp.arange(b, dtype=jnp.int32)) % cap
    else:
        free = leaves_of(rep) <= 0
        idx = jnp.nonzero(free, size=b, fill_value=cap)[0].astype(jnp.int32)
    n_new = (idx < cap).sum().astype(jnp.int32)
    tree = tree_write(rep["tree"].astype(dt), idx, to_leaf(prios, rcfg["alpha"], dt))
    storage = jax.tree.map(lambda buf, x: buf.at[idx].set(x.astype(buf.dtype),
                                                          mode="drop"),
                           rep["storage"], rows)
    out = dict(rep, tree=tree, storage=storage,
               size=jnp.minimum(rep["size"] + n_new, cap),
               total_added=rep["total_added"] + n_new)
    if mode == "fifo":
        out["write_pos"] = (rep["write_pos"] + n_new) % cap
    return out, idx


def set_priorities(rcfg: dict, rep: dict, idx, prios, dt=F32):
    tree = rep["tree"].astype(dt)
    old = leaves_of(rep)[idx]
    new = jnp.where(old > 0, to_leaf(prios, rcfg["alpha"], dt), 0.0).astype(dt)
    return dict(rep, tree=tree_write(tree, idx, new))


def evict_fifo(rcfg: dict, rep: dict):
    cap = rep["tree"].shape[0] // 2
    excess = jnp.maximum(rep["size"] - rcfg["soft_capacity"], 0)
    oldest = (rep["write_pos"] - rep["size"]) % cap
    age = (jnp.arange(cap, dtype=jnp.int32) - oldest) % cap
    leaves = jnp.where(age < excess, 0.0, leaves_of(rep)).astype(rep["tree"].dtype)
    return dict(rep, tree=tree_levels(leaves), size=rep["size"] - excess)


def evict_prioritized(rcfg: dict, rep: dict, rng, num: int):
    leaves = leaves_of(rep)
    live = leaves > 0
    mass = jnp.where(live, jnp.power(jnp.maximum(leaves.astype(F32), 1e-30),
                                     rcfg["evict_alpha"] / rcfg["alpha"]), 0.0)
    etree = tree_levels(mass)
    victims = descend(etree, stratified_u(rng, num, etree[1]))
    freed = jnp.zeros((leaves.shape[0],), bool).at[victims].set(True) & live
    return dict(rep, tree=tree_write(rep["tree"], victims, jnp.zeros((num,), leaves.dtype)),
                size=jnp.maximum(rep["size"] - freed.sum().astype(jnp.int32), 0))


def writeback(cfg: dict, rcfg: dict, rep: dict, idx, prios, step, rng,
              evict_num: int, dt=F32):
    """Priority write-back, then the periodic eviction of the cell's mode."""
    rep = set_priorities(rcfg, rep, idx, prios, dt)
    due = step % cfg["evict_interval"] == 0
    if cfg["eviction"] == "fifo":
        ev = evict_fifo(rcfg, rep)
    else:
        due = due & (rep["size"] > rcfg["soft_capacity"])
        ev = evict_prioritized(rcfg, rep, rng, evict_num)
    return jax.tree.map(lambda a, b: jnp.where(due, a, b), ev, rep)


# ---------------------------------------------------------------------------
# Learning
# ---------------------------------------------------------------------------

def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(F32)))
                        for x in jax.tree.leaves(tree)))


def dqn_loss(params, target, items, w, dt):
    q = dueling_q(params, items["obs"], dt)
    q_sa = jnp.take_along_axis(q, items["action"][:, None], -1)[:, 0]
    a_star = jnp.argmax(dueling_q(params, items["next_obs"], dt), -1)
    boot = jnp.take_along_axis(dueling_q(target, items["next_obs"], dt),
                               a_star[:, None], -1)[:, 0]
    g = items["returns"].astype(dt) + items["discount_n"].astype(dt) * jax.lax.stop_gradient(boot)
    td = g - q_sa
    return 0.5 * jnp.mean(w.astype(dt) * jnp.square(td)), jnp.abs(jax.lax.stop_gradient(td))


def dpg_losses(params, target, items, w, dt):
    def critic_loss(cp):
        q_sa = dpg_critic(cp, items["obs"], items["action"], dt)
        a_next = dpg_policy(target["actor"], items["next_obs"], dt)
        boot = dpg_critic(target["critic"], items["next_obs"], a_next, dt)
        g = items["returns"].astype(dt) + items["discount_n"].astype(dt) * jax.lax.stop_gradient(boot)
        td = g - q_sa
        return 0.5 * jnp.mean(w.astype(dt) * jnp.square(td)), jnp.abs(jax.lax.stop_gradient(td))

    def policy_loss(ap):
        a = dpg_policy(ap, items["obs"], dt)
        dq_da = jax.grad(lambda x: jnp.sum(w.astype(dt) * dpg_critic(
            params["critic"], items["obs"], x, dt)))(a)
        dq_da = jnp.clip(dq_da, -1.0, 1.0)
        return -jnp.sum(jax.lax.stop_gradient(dq_da) * a) / max(items["obs"].shape[0], 1)

    (c_loss, td), c_grads = jax.value_and_grad(critic_loss, has_aux=True)(params["critic"])
    a_grads = jax.grad(policy_loss)(params["actor"])
    return c_loss, td, {"actor": a_grads, "critic": c_grads}


def optimizer_step(opt: dict, grads, state, dt):
    """Centered RMSProp (fields ``mean_sq``, ``mean``) or Adam (``step``,
    ``mu``, ``nu``); returns (updates, new state)."""
    g = cast(grads, dt)
    if opt["kind"] == "centered_rmsprop":
        d, lr, eps = opt["decay"], opt["learning_rate"], opt["eps"]
        ms = jax.tree.map(lambda s, x: d * s.astype(dt) + (1 - d) * x * x, state["mean_sq"], g)
        m = jax.tree.map(lambda s, x: d * s.astype(dt) + (1 - d) * x, state["mean"], g)
        upd = jax.tree.map(lambda x, s, mm: -lr * x / jnp.sqrt(s - mm * mm + eps), g, ms, m)
        return upd, {"mean_sq": ms, "mean": m}
    b1, b2, lr, eps = opt["b1"], opt["b2"], opt["learning_rate"], opt["eps"]
    step = state["step"] + 1
    mu = jax.tree.map(lambda s, x: b1 * s.astype(dt) + (1 - b1) * x, state["mu"], g)
    nu = jax.tree.map(lambda s, x: b2 * s.astype(dt) + (1 - b2) * x * x, state["nu"], g)
    bc1 = 1 - b1 ** step.astype(F32)
    bc2 = 1 - b2 ** step.astype(F32)
    upd = jax.tree.map(lambda mm, v: -lr * (mm / bc1.astype(dt)) / (jnp.sqrt(v / bc2.astype(dt)) + eps), mu, nu)
    return upd, {"step": step, "mu": mu, "nu": nu}


def learn(cfg: dict, lrn: dict, items, w, dt, half_batch: bool = False,
          unchanged: bool = False):
    """One learner update from a sampled batch. ``lrn`` holds ``params``,
    ``target_params``, ``opt_state`` (a field dict) and ``learner_step``.
    Returns (new learner dict, fresh priorities |TD|, loss, gradients)."""
    params = cast(lrn["params"], dt)
    target = cast(lrn["target_params"], dt)
    if half_batch:  # planted fault: the second half of the batch left out
        h = w.shape[0] // 2
        items = jax.tree.map(lambda x: x[:h], items)
        w = w[:h]
    if cfg["agent"] == "dqn":
        (loss, td), grads = jax.value_and_grad(
            lambda p: dqn_loss(p, target, items, w, dt), has_aux=True)(params)
        norm = _global_norm(grads)
        scale = jnp.minimum(1.0, cfg["grad_clip"] / jnp.maximum(norm, 1e-12))
        grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)
    else:
        loss, td, grads = dpg_losses(params, target, items, w, dt)
    if half_batch:
        td = jnp.concatenate([td, td])
    upd, opt_state = optimizer_step(cfg["optimizer"], grads, lrn["opt_state"], dt)
    new_params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, upd)
    step = lrn["learner_step"] + 1
    copy = step % cfg["target_update_period"] == 0
    new_target = jax.tree.map(lambda p, t: jnp.where(copy, p.astype(t.dtype), t),
                              new_params, target)
    out = {"params": new_params, "target_params": new_target,
           "opt_state": opt_state, "learner_step": step}
    if unchanged:  # planted fault: the step returns its state unchanged
        out = dict(lrn, learner_step=step)
    return out, td, loss, grads
