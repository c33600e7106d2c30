"""Model operations per unit of work, counted from the configuration's
shapes (never from HLO). A multiply-add is 2 operations; a backward pass
through a layer costs twice its forward (gradients of weights and of
inputs); a backward to the inputs alone costs once its forward.

DQN (dueling MLP): F = one forward of one observation.
* acting: F per env step, plus one bootstrap forward per lane per rollout;
* learning, per sampled transition: forward and backward on ``obs`` (3F),
  the online forward on ``next_obs`` for the greedy action (F) and the
  target forward on ``next_obs`` (F): 5F.

DPG: P = one policy forward, C = one critic forward.
* acting: P and two critic forwards (the taken and the greedy action) per
  env step, plus the same once per lane per rollout for the bootstrap;
* learning, per sampled transition: the critic's forward and backward
  (3C), the target policy and target critic on ``next_obs`` (P + C); the
  policy's forward (P), the critic's forward and its backward to the
  action (2C), and the policy's backward (2P): 4P + 6C.
"""

from __future__ import annotations


def _mlp(sizes) -> int:
    return 2 * sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))


def dqn_forward(c: dict) -> int:
    obs = c["env"]["length"] + 2
    hid = list(c["mlp_hidden"])
    torso = _mlp([obs] + hid)
    feat, head, acts = hid[-1], c["head_hidden"], c["env"]["num_actions"]
    return torso + _mlp([feat, head, 1]) + _mlp([feat, head, acts])


def dpg_forwards(c: dict) -> tuple[int, int]:
    obs, act = 6, c["env"]["action_dim"]
    return (_mlp([obs] + list(c["actor_hidden"]) + [act]),
            _mlp([obs + act] + list(c["critic_hidden"]) + [1]))


def per_env_step(c: dict) -> float:
    extra = (c["rollout_len"] + 1) / c["rollout_len"]
    if c["agent"] == "dqn":
        return dqn_forward(c) * extra
    p, q = dpg_forwards(c)
    return (p + 2 * q) * extra


def per_learner_sample(c: dict) -> float:
    if c["agent"] == "dqn":
        return 5 * dqn_forward(c)
    p, q = dpg_forwards(c)
    return 4 * p + 6 * q
