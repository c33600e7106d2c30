"""Lockstep runtime: ``apex.make_train_fn``'s step, dispatched the way
``launch/train.py run_apex`` dispatches it.

Set-up builds the state on the device in one jitted call from the seed,
then runs the step until the replay is past its soft cap (a number of
iterations fixed by the configuration, the same for every seed), which
compiles and warms the one step program. The window dispatches the step
back to back and waits on the host only every ``sync_every`` iterations, as
``run_apex`` does when it logs.

The traced run dispatches the same way from the profiler's start to the
span's end, so the device's idle share and the step's MFU are read from
the timed dispatch pattern; the span opens and closes at sync points, so it
covers whole iterations. Before the profiler starts and after the span the
traced run waits on every iteration and times it (``iter_ms``), which the
profile never sees.

Two window iterations are captured for the check: their input state is
copied on the device before the step runs, and their output right after it
is dispatched, so the capture survives a step that donates its input. One
is drawn from the seed; the other is the first iteration whose learner
steps reach an eviction.
"""

from __future__ import annotations

import math
import time

import numpy as np

from bench import compare, harness
from bench import reference as ref


def _geometry(cell):
    c = cell.config
    lanes = c["num_actors"]
    window = c["rollout_len"] - c["n_step"] + 1
    return lanes, window


def run(cell, seed: int, seconds: float, trace: bool, clock: dict) -> dict:
    import jax
    from repro.core import apex

    preset = harness.build_preset(cell.config)
    cfg = preset.apex
    optimizer = preset.make_optimizer()
    init_fn, step_fn = apex.make_train_fn(cfg, preset.env, preset.agent, optimizer)
    state = jax.block_until_ready(init_fn(jax.random.key(seed)))
    clock["build"] = time.perf_counter()

    lanes, window = _geometry(cell)
    fill_iters = math.ceil(cfg.replay.soft_cap / (lanes * window)) + 1
    sync_every = int(cell.traffic["sync_every"])
    for i in range(fill_iters):
        state, metrics = step_fn(state)
        if (i + 1) % sync_every == 0:
            float(metrics["loss"])
    jax.block_until_ready((state, metrics))
    if int(state.replay.size) < cfg.replay.soft_cap or float(metrics["updated"]) != 1.0:
        raise RuntimeError("warm-up did not reach the soft cap with the learner updating")
    # compile the device copies of the captured structures before the window
    jax.block_until_ready((harness.device_copy(state), harness.device_copy(metrics)))
    clock["fill"] = time.perf_counter()

    steps_per_iter = cfg.learner_steps_per_iter
    step0 = int(state.learner_step)
    added0 = int(state.replay.total_added)
    live0 = int(state.replay.size)
    drawn = 2 + seed % 5
    evicting = next(i for i in range(10 ** 6)
                    if (step0 + steps_per_iter * (i + 1)) // cfg.evict_interval
                    > (step0 + steps_per_iter * i) // cfg.evict_interval)
    want = {drawn: "drawn", evicting: "evicting"}
    captures = {}
    prof = harness.ProfileWindow(trace, clock["trace_dir"], seconds)
    counter = clock["compile_counter"]

    counter.active = True
    t0 = time.perf_counter()
    iter_ms = []
    i = 0
    since_sync = 0
    skip = set()  # iterations that waited on the profiler's start or stop

    def mark():
        jax.block_until_ready(state)  # the span holds whole iterations
        return i

    while time.perf_counter() - t0 < seconds or i <= max(want):
        # the profiler changes state only where the host has just waited,
        # so the span opens and closes on the window's own sync points
        if since_sync == 0 and prof.tick(time.perf_counter() - t0, mark=mark):
            skip.update((i, i + 1))
        timed = trace and prof.state in ("before", "done")
        t_it = time.perf_counter()
        if i in want:
            cin = harness.device_copy(state)
        state, metrics = step_fn(state)
        if i in want:
            captures[want[i]] = {"iteration": i, "state_in": cin,
                                 "state_out": harness.device_copy(state),
                                 "metrics": harness.device_copy(metrics)}
        i += 1
        since_sync += 1
        if timed:
            jax.block_until_ready(state)
            if i - 1 not in skip:
                iter_ms.append(1e3 * (time.perf_counter() - t_it))
            since_sync = 0
        elif since_sync == sync_every:
            float(metrics["loss"])  # the wait run_apex takes when it logs
            since_sync = 0
    in_flight = since_sync
    jax.block_until_ready(state)
    t1 = time.perf_counter()
    prof.finish(mark)
    counter.active = False
    traced = None
    if trace and len(prof.marks) == 2:
        (ta, ia), (tb, ib) = prof.marks
        traced = {"seconds": tb - ta, "iterations": ib - ia,
                  "env_steps": (ib - ia) * lanes * cell.config["rollout_len"],
                  "learner_samples": (ib - ia) * steps_per_iter * cell.config["batch_size"]}

    step1 = int(state.learner_step)
    added1 = int(state.replay.total_added)
    live1 = int(state.replay.size)
    window_s = t1 - t0
    c = cell.config
    return {
        "window_s": window_s,
        "generated": added1 - added0,
        "consumed": (step1 - step0) * c["batch_size"],
        "env_steps": i * lanes * c["rollout_len"],
        "learner_samples": (step1 - step0) * c["batch_size"],
        "iterations": i,
        "in_flight_at_deadline": in_flight,
        "iter_ms": iter_ms,
        "traced": traced,
        "trace_path": getattr(prof, "path", None),
        "fill": {"live_start": live0, "live_end": live1,
                 "soft_cap": cfg.replay.soft_cap,
                 "evictions_in_window": (step1 // cfg.evict_interval
                                         - step0 // cfg.evict_interval)},
        "captures": captures,
        "_state": state,
    }


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------

def _state_dict(st) -> dict:
    """The program's ``ApexState`` as plain data for the reference."""
    return {
        "params": st.params, "target_params": st.target_params,
        "opt_state": st.opt_state._asdict(), "actor_params": st.actor_params,
        "iteration": st.iteration, "learner_step": st.learner_step,
        "replay": {"storage": dict(st.replay.storage), "tree": st.replay.tree,
                   "write_pos": st.replay.write_pos, "size": st.replay.size,
                   "total_added": st.replay.total_added},
        "slice": {"env_state": st.env_state._asdict(), "obs": st.obs, "rng": st.rng},
    }


class RefIteration:
    """One lockstep iteration from an input state, by the reference:
    parameter sync, rollout, FIFO add, then ``learner_steps_per_iter``
    learner steps with write-back and eviction, each stage a jitted call.

    ``forced`` holds the observed actions of the new rows. ``teacher`` holds
    the observed leaves after the iteration: wherever the reference writes
    a leaf, its own value is kept as the last one it wrote to that slot
    (``prio_gap`` compares those with the observed leaves) and, where it
    agrees with the observed leaf to 1e-3, the observed value is carried on,
    so that a rounding-level difference cannot move a stratum boundary of a
    later sample: each later stage then runs on the inputs the program had.

    The sum tree holds some two million leaves in float32, so a stratum
    point can lie within rounding of a slot boundary, and a leaf the
    reference could not carry (a new row written back later in the same
    iteration) moves such points by rounding. Where the reference samples a
    slot the program left unwritten and one within ``FOLLOW`` slots of it
    was written by the program and not sampled by the reference, the
    reference follows the program to that neighbour (the same stratum,
    across a boundary). Every such step is counted in ``followed``, which
    ``sample_miss`` adds to the samples that stay apart.
    """

    FOLLOW = 4

    def __init__(self, c: dict, dt, fault=None):
        import jax
        import jax.numpy as jnp
        self.c, self.dt, self.fault = c, dt, fault
        self.rc = {"alpha": c["alpha"], "beta": c["beta"],
                   "evict_alpha": c["evict_alpha"], "soft_capacity": c["soft_capacity"]}

        def act_add(s, forced):
            sync = s["iteration"] % c["param_sync_period"] == 0
            actor_params = jax.tree.map(lambda p, a: jnp.where(sync, p, a),
                                        s["params"], s["actor_params"])
            rows, prios, policy, after = ref.rollout(
                c, actor_params, s["slice"], 0, c["num_actors"], dt, forced,
                alter_actions=(fault == "altered_row"))
            rep, new_idx = ref.add_rows(self.rc, s["replay"], rows, prios, "fifo", dt)
            _, sub = jax.random.split(after["rng"])
            return rep, new_idx, policy, jax.random.split(sub, c["learner_steps_per_iter"])

        def follow(rep, batch, prog):
            idx = batch["indices"]
            cap = prog.shape[0]
            in_batch = jnp.zeros((cap,), bool).at[idx].set(True)
            offs = jnp.array([d * sg for d in range(1, self.FOLLOW + 1) for sg in (-1, 1)],
                             jnp.int32)
            cand = jnp.clip(idx[:, None] + offs[None, :], 0, cap - 1)
            ok = prog[cand] & ~in_batch[cand]
            take = ~prog[idx] & ok.any(axis=1)
            idx = jnp.where(take, cand[jnp.arange(idx.shape[0]), jnp.argmax(ok, axis=1)], idx)
            leaf = ref.leaves_of(rep)[idx]
            w = ref.is_weights(leaf, rep["tree"][1], rep["size"], c["beta"])
            batch = dict(batch, indices=idx, leaf_mass=leaf,
                         items=jax.tree.map(lambda b: b[idx], rep["storage"]),
                         is_weights=w / jnp.maximum(jnp.max(w), 1e-30))
            return batch, take.sum()

        def learn_step(lrn, rep, step_rng, prog):
            s_rng, e_rng = jax.random.split(step_rng)
            batch = ref.sample(self.rc, rep, s_rng, c["batch_size"],
                               uniform=(fault == "uniform_sampling"))
            followed = jnp.zeros((), jnp.int32)
            if prog is not None:
                batch, followed = follow(rep, batch, prog)
            lrn, td, loss, grads = ref.learn(
                c, lrn, batch["items"], batch["is_weights"], dt,
                half_batch=(fault == "half_batch"), unchanged=(fault == "unchanged"))
            rep = ref.writeback(c, self.rc, rep, batch["indices"], td, lrn["learner_step"],
                                e_rng, c["evict_num"] or c["batch_size"], dt)
            return lrn, rep, batch["indices"], loss, grads, followed

        def program_written(tree_in, rep, new_idx, teacher):
            """Slots the program wrote in the iteration: old slots whose leaf
            changed, new rows whose leaf is not their initial priority."""
            cap = teacher.shape[0]
            init = ref.leaves_of(rep).astype(jnp.float32)
            is_new = jnp.zeros((cap,), bool).at[new_idx].set(True)
            return jnp.where(is_new, jnp.abs(teacher - init) > 1e-3 * jnp.abs(init),
                             teacher != tree_in[cap:].astype(jnp.float32))

        def carry(rep, idx, teacher, last, written):
            mine = ref.leaves_of(rep)[idx]
            last = last.at[idx].set(mine.astype(jnp.float32))
            written = written.at[idx].set(True)
            theirs = teacher[idx]
            m32 = mine.astype(jnp.float32)
            ok = jnp.abs(m32 - theirs) <= 1e-3 * jnp.abs(m32)
            tree = ref.tree_write(rep["tree"], idx,
                                  jnp.where(ok, theirs.astype(mine.dtype), mine))
            return dict(rep, tree=tree), last, written

        self._act_add = jax.jit(act_add)
        self._learn_step = jax.jit(learn_step)
        self._program_written = jax.jit(program_written)
        self._carry = jax.jit(carry)

    def __call__(self, s: dict, forced=None, teacher=None) -> dict:
        import jax.numpy as jnp
        c = self.c
        rep, new_idx, policy, step_rngs = self._act_add(s, forced)
        slots = rep["tree"].shape[0] // 2
        last = jnp.zeros((slots,), jnp.float32)
        written = jnp.zeros((slots,), bool)
        prog = None
        if teacher is not None:
            prog = self._program_written(s["replay"]["tree"], rep, new_idx, teacher)
            rep, last, written = self._carry(rep, new_idx, teacher, last, written)
        lrn = {"params": s["params"], "target_params": s["target_params"],
               "opt_state": s["opt_state"], "learner_step": s["learner_step"]}
        sampled, loss, grads0, followed = [], None, None, 0
        for k in range(c["learner_steps_per_iter"]):
            lrn, rep, idx, loss, grads, n = self._learn_step(lrn, rep, step_rngs[k], prog)
            followed += int(n)
            grads0 = grads if grads0 is None else grads0
            if teacher is not None:
                rep, last, written = self._carry(rep, idx, teacher, last, written)
            sampled.append(idx)
        tree = rep["tree"].astype(jnp.float32)
        if self.fault == "stale_node":  # planted fault: one internal node not updated
            tree = tree.at[2].set(s["replay"]["tree"][2])
        return {"params": lrn["params"], "target_params": lrn["target_params"],
                "learner_step": lrn["learner_step"],
                "replay": dict(rep, tree=tree),
                "new_idx": new_idx, "sampled": jnp.concatenate(sampled),
                "loss": loss, "grads": grads0, "policy": policy,
                "last_written": last, "written": written, "followed": followed}


def _observed(st_out, metrics) -> dict:
    d = _state_dict(st_out)
    return {"params": d["params"], "target_params": d["target_params"],
            "learner_step": d["learner_step"], "replay": d["replay"],
            "loss": metrics["loss"]}


def numbers(c: dict, s_in: dict, obs: dict, r: dict) -> dict:
    """Compare one iteration's observed outputs ``obs`` with the reference
    ``r`` computed from the same input state ``s_in``."""
    import jax.numpy as jnp
    lanes = c["num_actors"]
    W = c["rollout_len"] - c["n_step"] + 1
    pol = r["policy"]
    use = np.asarray(pol["use"])
    gap, spread = np.asarray(pol["gap"], np.float64), np.asarray(pol["spread"], np.float64)
    med = max(float(np.median(spread[use])), 1e-30)
    explore_bad = np.isinf(gap[use]).sum()
    act_gap = float(np.max(np.where(np.isinf(gap[use]), 0.0, gap[use])) / med) + 10.0 * explore_bad

    idx = np.asarray(r["new_idx"])
    full = (np.arange(lanes * W) % W) <= W - c["n_step"]
    obs_rows = {k: np.asarray(v)[idx] for k, v in obs["replay"]["storage"].items()}
    ref_rows = {k: np.asarray(v)[idx] for k, v in r["replay"]["storage"].items()}
    rows = compare.row_gap(obs_rows, ref_rows, full)

    leaves_in = np.asarray(ref.leaves_of(s_in["replay"]))
    leaves_obs = np.asarray(ref.leaves_of(obs["replay"]))
    leaves_ref = np.asarray(ref.leaves_of(r["replay"]))
    old = np.ones(leaves_in.shape[0], bool)
    old[idx] = False
    changed = (leaves_obs != leaves_in) & old
    s_ref = np.zeros_like(old)
    s_ref[np.asarray(r["sampled"])] = True
    s_ref &= old
    evicted_ref = (leaves_ref == 0) & (leaves_in > 0) & old
    missed = (s_ref & ~changed).sum() + (changed & ~s_ref & ~evicted_ref).sum()
    sample_miss = float((missed + r["followed"]) / max(s_ref.sum(), 1))

    # every leaf the reference wrote, its own last value before any carry
    written = np.asarray(r["written"]) & (leaves_ref > 0) & (leaves_obs > 0)
    med_leaf = float(np.median(leaves_ref[leaves_ref > 0]))
    mine = np.asarray(r["last_written"], np.float64)[written]
    theirs = leaves_obs[written].astype(np.float64)
    prio_gap = (float(np.max(np.abs(mine - theirs) / np.maximum(np.abs(mine), med_leaf)))
                if mine.size else 1.0)

    counts = float(int(obs["replay"]["size"]) != int(r["replay"]["size"])
                   or int(obs["replay"]["write_pos"]) != int(r["replay"]["write_pos"])
                   or int(obs["learner_step"]) != int(r["learner_step"]))
    upd = max(compare.update_gap(s_in["params"], obs["params"], r["params"], r["grads"]),
              compare.rel_gap(jnp.concatenate([x.ravel() for x in _leaves(obs["target_params"])]),
                              jnp.concatenate([x.ravel() for x in _leaves(r["target_params"])])))
    return {
        "act_gap": act_gap,
        "rows_gap": max(rows, counts),
        "tree_gap": compare.tree_gap(obs["replay"]["tree"]),
        "leaf_gap": compare.leaf_gap(leaves_obs, leaves_ref),
        "prio_gap": prio_gap,
        "sample_miss": sample_miss,
        "update_gap": upd,
        "loss_gap": compare.rel_gap(obs["loss"], r["loss"], scale=abs(float(r["loss"]))),
    }


def _leaves(tree):
    import jax
    return jax.tree.leaves(tree)


def check(cell, record: dict, mode: str = "program") -> dict:
    """Numbers for every captured iteration, the worst of each kept.
    ``mode`` "program" compares the program; "control" puts the reference
    at bfloat16 in its place; any other mode is a fault planted in the
    float32 reference put in its place."""
    import jax.numpy as jnp
    c = cell.config
    out = {}
    dt = jnp.bfloat16 if mode == "control" else jnp.float32
    fault = None if mode in ("program", "control") else mode
    as_program = RefIteration(c, dt, fault)
    reference = RefIteration(c, jnp.float32)
    W = c["rollout_len"] - c["n_step"] + 1
    for cap in record["captures"].values():
        s_in = _state_dict(cap["state_in"])
        if mode == "program":
            obs = _observed(cap["state_out"], cap["metrics"])
        else:
            o = as_program(s_in)
            obs = {k: o[k] for k in ("params", "target_params", "learner_step", "replay", "loss")}
        cap_slots = s_in["replay"]["tree"].shape[0] // 2
        new = (int(s_in["replay"]["write_pos"]) + np.arange(c["num_actors"] * W)) % cap_slots
        forced = jnp.asarray(np.asarray(obs["replay"]["storage"]["action"])[new].reshape(c["num_actors"], W))
        r = reference(s_in, forced, ref.leaves_of(obs["replay"]).astype(jnp.float32))
        for k, v in numbers(c, s_in, obs, r).items():
            out[k] = max(out.get(k, 0.0), v)
    return out
