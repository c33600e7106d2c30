"""Decoupled runtime: ``repro.runtime.run_async`` with its actor threads,
replay shards and learner thread, and learner steps out of reach.

``run_async`` itself warms the rollout and the learner step. The window
opens, inside ``on_handles``, once the learner has taken ``warm_steps``
steps, so that every replay op (add, sample, merge, write-back with its
eviction branch) has compiled. It reads the fabric's counters and the
learner's step at both ends, sleeps on the main thread in between, and then
sets the run's stop event; teardown lies outside the window and outside
``setup_s``.

The window's rates come from the program's public counters:
``fabric.snapshot().transitions_added`` (rows offered to the shards' add
ops) and the learner's step. After teardown every shard's own
``total_added`` is summed and held against the offered count: a row
offered and not stored (an add that drops rows) makes the run incorrect,
so the offered count is the stored count.

For the check, the benchmark wraps the calls into each layer (its own
instrumentation, installed through the run's handles): shard 0's add,
sample and write-back ops, the learner's batch and write-back, and actor
thread 0's hand-off of a block to the fabric. For one call of each, drawn
from the seed among the first few of the window, it copies the inputs on
the device before the call and the outputs right after, so a later op that
donates them cannot touch the capture; every other call passes straight
through. The actor capture reads the rollout's input slice and parameters
from the actor loop's frame at the hand-off (``sl`` and ``snap`` of the
loop); if they are not there, the check fails rather than pass unchecked.
These wrappers reach private fields of the runtime (``ReplayShard._fns``,
the actor loop's locals), and the window's ends read two private
instruments (``_c_add_blocked``, ``_hists``): see PERF.md, Open questions.
"""

from __future__ import annotations

import dataclasses
import math
import sys
import threading
import time

import numpy as np

from bench import compare, harness
from bench import reference as ref


def _shard_cfg(c: dict, shards: int) -> dict:
    """The per-shard replay geometry the fabric derives from the config."""
    return {"capacity": c["replay_capacity"] // shards,
            "soft_capacity": max(1, math.ceil(c["soft_capacity"] / shards)),
            "min_fill": max(1, math.ceil(c["min_fill"] / shards)),
            "alpha": c["alpha"], "beta": c["beta"], "evict_alpha": c["evict_alpha"],
            "evict_num": max(1, (c["evict_num"] or c["batch_size"]) // shards)}


class _Recorder:
    """Wraps the calls into each layer and keeps the drawn captures."""

    KINDS = ("actor", "add", "sample", "learn", "writeback")

    def __init__(self, handles, evict_interval: int):
        self.h = handles
        self.evict_interval = evict_interval
        self.active = False
        self.errors: list[str] = []
        self._lock = threading.Lock()
        self._last_sub: dict = {}
        self.reset(2)

    def reset(self, k: int) -> None:
        """Forget the captures and draw the ``k``-th call of each kind."""
        self.k = k
        self.caps: dict = {}
        self._n = {kind: 0 for kind in self.KINDS}
        self._after_pending = False
        self._wb_pending = False
        self._actor_prev = None
        self._actor_block = None

    def complete(self) -> bool:
        return (all(self.caps.get(kind) for kind in self.KINDS)
                and "after" in self.caps["learn"])

    def _tick(self, kind) -> int:
        with self._lock:
            self._n[kind] += 1
            return self._n[kind]

    def install(self):
        fabric = self.h.fabric
        sh0 = fabric.shards[0]
        fns = sh0._fns
        orig_add, orig_sample, orig_wb = fns.add, fns.sample, fns.writeback

        def add(st, block):
            if not self.active or "add" in self.caps or self._tick("add") != self.k:
                return orig_add(st, block)
            cin, cblock = harness.device_copy(st), harness.device_copy(block)
            out = orig_add(st, block)
            self.caps["add"] = {"in": cin, "block": cblock, "out": harness.device_copy(out)}
            return out

        def sample(st, rng):
            if not self.active or "sample" in self.caps:
                return orig_sample(st, rng)
            n = self._tick("sample")
            if n != self.k:
                return orig_sample(st, rng)
            cin, crng = harness.device_copy(st), harness.device_copy(rng)
            out = orig_sample(st, rng)
            self.caps["sample"] = {"in": cin, "rng": crng, "out": harness.device_copy(out)}
            return out

        def writeback(st, idx, prios, step, rng):
            if not self.active:
                return orig_wb(st, idx, prios, step, rng)
            n = self._tick("writeback")
            name = ("writeback" if n == self.k and "writeback" not in self.caps
                    else "writeback_evict" if step % self.evict_interval == 0
                    and "writeback_evict" not in self.caps else None)
            if name is None:
                return orig_wb(st, idx, prios, step, rng)
            cin = harness.device_copy((st, idx, prios, rng))
            out = orig_wb(st, idx, prios, step, rng)
            self.caps[name] = {"in": cin[0], "idx": cin[1], "prios": cin[2],
                               "rng": cin[3], "step": int(step),
                               "out": harness.device_copy(out)}
            return out

        sh0._fns = fns._replace(add=add, sample=sample, writeback=writeback)

        for k, sh in enumerate(fabric.shards):
            def shard_get(timeout=None, _orig=sh.get_batch, _k=k):
                b = _orig(timeout=timeout)
                if b is not None:
                    self._last_sub[_k] = b
                return b
            sh.get_batch = shard_get

        src = self.h.source
        orig_get, orig_write = src.get_batch, src.write_back
        box = self.h.learner_box

        def get_batch(timeout=None):
            if self._after_pending:
                self.caps["learn"]["after"] = harness.device_copy(box["live"][1])
                self._after_pending = False
            before = box["live"][1]
            b = orig_get(timeout=timeout)
            if b is None or not self.active or "learn" in self.caps:
                return b
            if self._tick("learn") == self.k:
                subs = [self._last_sub[i] for i in range(len(fabric.shards))]
                self.caps["learn"] = {"before": harness.device_copy(before),
                                      "batch": harness.device_copy(tuple(b)),
                                      "subs": harness.device_copy(subs)}
                self._wb_pending = True
            return b

        def write_back(indices, priorities, trace_id=0):
            if self._wb_pending:
                self.caps["learn"]["prios"] = harness.device_copy(
                    (indices, priorities))
                self._wb_pending = False
                self._after_pending = True
            return orig_write(indices, priorities, trace_id=trace_id)

        src.get_batch, src.write_back = get_batch, write_back

        orig_fadd = fabric.add

        def fabric_add(block, timeout=None, trace_id=0):
            if self.active and "actor" not in self.caps:
                self._on_actor_add(block, sys._getframe(1).f_locals)
            return orig_fadd(block, timeout=timeout, trace_id=trace_id)

        fabric.add = fabric_add

    def _on_actor_add(self, block, loc):
        if loc.get("t") != 0:
            return
        if block is self._actor_block:  # a retry under backpressure
            return
        self._actor_block = block
        if "sl" not in loc or "snap" not in loc:
            self.errors.append("actor loop frame has no 'sl'/'snap': rollout not captured")
            self.caps["actor"] = None
            return
        n = self._tick("actor")
        if n == self.k - 1:
            self._actor_prev = harness.device_copy(loc["sl"])
        elif n == self.k:
            self.caps["actor"] = {"slice": self._actor_prev,
                                  "params": harness.device_copy(loc["snap"].params),
                                  "block": harness.device_copy(tuple(block)),
                                  "thread": 0}


def run(cell, seed: int, seconds: float, trace: bool, clock: dict) -> dict:
    import jax
    from repro.runtime import AsyncConfig, run_async

    preset = harness.build_preset(cell.config)
    tr = cell.traffic
    threads, shards = int(tr["actor_threads"]), int(tr["replay_shards"])
    lanes = cell.config["num_actors"] // threads
    cfg = dataclasses.replace(preset.apex, lanes_per_shard=lanes)
    acfg = AsyncConfig(actor_threads=threads, replay_shards=shards,
                       total_learner_steps=2 ** 62, seed=seed)
    record: dict = {}
    k = 2 + seed % 5
    prof = harness.ProfileWindow(trace, clock["trace_dir"], seconds)
    counter = clock["compile_counter"]
    warm_steps = int(tr["warm_steps"])

    def on_handles(h):
        rec = _Recorder(h, cfg.evict_interval)
        rec.install()
        box, fabric = h.learner_box, h.fabric
        deadline = time.perf_counter() + float(tr["warm_timeout_s"])
        # A rehearsal capture of every kind before the window compiles the
        # device copies of each captured structure, so none compiles inside.
        rec.active = True
        while (box["live"][0] < warm_steps or not rec.complete()) or any(
                s.updates_applied < 1 for s in fabric.shard_snapshots()):
            if h.stop.is_set() or time.perf_counter() > deadline:
                record["error"] = "warm-up did not finish: " + "; ".join(rec.errors)
                h.stop.set()
                return
            time.sleep(0.01)
        rec.active = False
        rec.reset(k)
        clock["fill"] = time.perf_counter()

        def ends():
            snap = fabric.snapshot()
            shard = [{"blocks_added": s.snapshot().blocks_added,
                      "backpressure": s._c_add_blocked.value,
                      "sample_hist": list(s._hists["sample"]._counts)}
                     for s in fabric.shards]
            return {"steps": box["live"][0], "transitions_added": snap.transitions_added,
                    "replay_size": _live_rows(fabric), "shards": shard}

        start = ends()
        counter.active = True
        rec.active = True
        t0 = time.perf_counter()
        mark = lambda: (box["live"][0], fabric.snapshot().transitions_added)  # noqa: E731
        if trace:
            for due in prof.due():
                time.sleep(max(0.0, due - (time.perf_counter() - t0)))
                prof.tick(time.perf_counter() - t0, mark)
        time.sleep(max(0.0, seconds - (time.perf_counter() - t0)))
        end = ends()
        t1 = time.perf_counter()
        prof.finish(mark)
        record["trace_path"] = getattr(prof, "path", None)
        if trace and len(prof.marks) == 2:
            (ta, (sa, oa)), (tb, (sb, ob)) = prof.marks
            W = cfg.rollout_len - cfg.n_step + 1
            record["traced"] = {"seconds": tb - ta,
                                "env_steps": (ob - oa) // W * cfg.rollout_len,
                                "learner_samples": (sb - sa) * cfg.batch_size}
        counter.active = False
        rec.active = False
        record.update(window_s=t1 - t0, start=start, end=end, recorder=rec, fabric=fabric)
        h.stop.set()

    res = run_async(cfg, acfg, preset.env, preset.agent, preset.make_optimizer(),
                    rng=jax.random.key(seed), on_handles=on_handles)
    if "error" in record:
        raise RuntimeError(record["error"])
    s, e = record["start"], record["end"]
    c = cell.config
    W = c["rollout_len"] - c["n_step"] + 1
    gen = e["transitions_added"] - s["transitions_added"]
    steps = e["steps"] - s["steps"]
    rec = record["recorder"]
    # every row offered over the run was stored: the shards' own counts
    owners = record["fabric"].shards
    stored = sum(int(sh.replay_state.total_added) for sh in owners)
    offered = sum(sh.snapshot().transitions_added for sh in owners)
    return {
        "window_s": record["window_s"],
        "generated": gen,
        "consumed": steps * c["batch_size"],
        "env_steps": (e["transitions_added"] - s["transitions_added"]) // W * c["rollout_len"],
        "learner_samples": steps * c["batch_size"],
        "trace_path": record["trace_path"],
        "traced": record.get("traced"),
        "shards_start": s["shards"], "shards_end": e["shards"],
        "dropped": offered - stored,
        "fill": {"live_start": s["replay_size"], "live_end": e["replay_size"],
                 "soft_cap": c["soft_capacity"],
                 "eviction_step_in_window": "writeback_evict" in rec.caps,
                 "eviction": c["eviction"],
                 "final_live": int(res.stats["replay_size"])},
        "captures": rec.caps, "capture_errors": rec.errors,
        "threads": threads, "shards": shards, "lanes": lanes,
    }


def _live_rows(fabric) -> int:
    """Live rows across the shards, as their owners last refreshed them."""
    return int(sum(s.snapshot().replay_size for s in fabric.shards))


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------

def _rep(st) -> dict:
    return {"storage": dict(st.storage), "tree": st.tree,
            "write_pos": st.write_pos, "size": st.size, "total_added": st.total_added}


def check(cell, record: dict, mode: str = "program") -> dict:
    """One number per layer, from each capture. ``mode`` as in the
    lockstep check: the program, the bfloat16 control, or a fault planted
    in the float32 reference put in the program's place."""
    import jax
    import jax.numpy as jnp
    c = cell.config
    S, lanes = record["shards"], record["lanes"]
    rc = _shard_cfg(c, S)
    f32 = jnp.float32
    dt = jnp.bfloat16 if mode == "control" else f32
    fault = None if mode in ("program", "control") else mode
    caps = record["captures"]
    out = {}
    missing = [k for k in ("actor", "add", "sample", "learn", "writeback")
               if not caps.get(k)] + (["learn.after"] if caps.get("learn") and "after" not in caps["learn"] else [])
    out["captured"] = float(len(missing))
    out["dropped"] = float(record["dropped"])

    # actor: the rollout that made a block
    a = caps.get("actor")
    if a:
        sl = a["slice"]
        slice_ = {"env_state": sl.env_state._asdict(), "obs": sl.obs, "rng": sl.rng}
        items, prios = a["block"]
        if mode == "program":
            rows, p = items, prios
        else:
            rows, p, _, _ = jax.jit(lambda pr, s: ref.rollout(
                c, pr, s, a["thread"], lanes, dt,
                alter_actions=(fault == "altered_row")))(a["params"], slice_)
        W = c["rollout_len"] - c["n_step"] + 1
        forced = jnp.asarray(rows["action"]).reshape((lanes, W) + jnp.shape(rows["action"])[1:])
        r_rows, r_p, pol, _ = jax.jit(lambda pr, s, f: ref.rollout(
            c, pr, s, a["thread"], lanes, f32, forced_actions=f))(a["params"], slice_, forced)
        use = np.asarray(pol["use"])
        gap = np.asarray(pol["gap"], np.float64)[use]
        med = max(float(np.median(np.asarray(pol["spread"], np.float64)[use])), 1e-30)
        bad = np.isinf(gap).sum()
        out["act_gap"] = float(np.max(np.where(np.isinf(gap), 0.0, gap)) / med) + 10.0 * bad
        full = (np.arange(lanes * W) % W) <= W - c["n_step"]
        out["rows_gap"] = compare.row_gap(rows, r_rows, full)
        out["prio_gap"] = compare.rel_gap(p, r_p)

    # add: the block into shard 0
    ad = caps.get("add")
    if ad:
        rep_in = _rep(ad["in"])
        items, prios = ad["block"]
        r_rep, r_idx = jax.jit(lambda r, it, p: ref.add_rows(rc, r, it, p, c["eviction"]))(rep_in, items, prios)
        if mode == "program":
            o_rep, o_tree = _rep(ad["out"]), ad["out"].tree
        else:
            o_rep, _ = jax.jit(lambda r, it, p: ref.add_rows(rc, r, it, p, c["eviction"], dt))(rep_in, items, prios)
            o_tree = _tree(o_rep["tree"], fault)
        idx = np.asarray(r_idx)
        idx = idx[idx < rc["capacity"]]
        gaps = [compare.leaf_gap(ref.leaves_of(o_rep).astype(f32), ref.leaves_of(r_rep))]
        for key in r_rep["storage"]:
            gaps.append(compare.exact_mismatch(np.asarray(o_rep["storage"][key])[idx],
                                               np.asarray(r_rep["storage"][key])[idx]))
        for key in ("size", "write_pos", "total_added"):
            gaps.append(compare.exact_mismatch(o_rep[key], r_rep[key]))
        out["add_gap"] = max(gaps)
        out["tree_gap"] = max(out.get("tree_gap", 0.0), compare.tree_gap(o_tree))

    # sample: one prefetched sub-batch of shard 0
    sm = caps.get("sample")
    if sm:
        rep_in = _rep(sm["in"])
        sub = c["batch_size"] // S
        r = jax.jit(lambda rp, k: ref.sample(rc, rp, k, sub))(rep_in, sm["rng"])
        if mode == "program":
            o = sm["out"]._asdict()
        else:
            rp = dict(rep_in, tree=_own_tree(rep_in, dt))
            o = jax.jit(lambda rp, k: ref.sample(rc, rp, k, sub, uniform=(fault == "uniform_sampling")))(rp, sm["rng"])
        idx_o, idx_r = np.asarray(o["indices"]), np.asarray(r["indices"])
        gaps = [float(np.mean(idx_o != idx_r)),
                compare.rel_gap(o["leaf_mass"], r["leaf_mass"]),
                compare.rel_gap(o["is_weights"], r["is_weights"]),
                compare.rel_gap(o["total_mass"], r["total_mass"], scale=abs(float(r["total_mass"]))),
                compare.exact_mismatch(o["size"], r["size"])]
        gaps += [compare.exact_mismatch(o["items"][key], r["items"][key]) for key in r["items"]]
        out["sample_gap"] = max(gaps)
        stale = (_tree(_own_tree(rep_in, dt), fault) if mode != "program"
                 else sm["in"].tree)
        out["tree_gap"] = max(out.get("tree_gap", 0.0), compare.tree_gap(stale))

    # learner: one step on one merged batch
    ln = caps.get("learn")
    if ln and "after" in ln:
        before = ln["before"]
        lrn = {"params": before.params, "target_params": before.target_params,
               "opt_state": before.opt_state._asdict(), "learner_step": before.learner_step}
        idx_b, items_b, w_b = ln["batch"]
        r_lrn, r_td, _, grads = jax.jit(lambda l, it, w: ref.learn(c, l, it, w, f32))(lrn, items_b, w_b)
        if mode == "program":
            o_params, o_target = ln["after"].params, ln["after"].target_params
            o_idx, o_td = ln["prios"]
        else:
            o_lrn, o_td, _, _ = jax.jit(lambda l, it, w: ref.learn(
                c, l, it, w, dt, half_batch=(fault == "half_batch"),
                unchanged=(fault == "unchanged")))(lrn, items_b, w_b)
            o_params, o_target, o_idx = o_lrn["params"], o_lrn["target_params"], idx_b
        out["update_gap"] = max(
            compare.update_gap(lrn["params"], o_params, r_lrn["params"], grads),
            compare.update_gap(lrn["target_params"], o_target, r_lrn["target_params"], grads))
        out["td_gap"] = max(compare.rel_gap(o_td, r_td), compare.exact_mismatch(o_idx, idx_b))
        # the merge of the shards' sub-batches into this learner batch
        subs = ln["subs"]
        if S > 1:
            leaf = jnp.stack([s.leaf_mass for s in subs])
            totals = jnp.stack([s.total_mass for s in subs])
            sizes = jnp.stack([s.size for s in subs])
            w = ref.merged_weights(leaf, totals, sizes, c["beta"])
            if mode != "program":
                w_b = ref.merged_weights(leaf.astype(dt), totals.astype(dt), sizes, c["beta"])
            gidx = np.concatenate([np.asarray(s.indices) + k * rc["capacity"] for k, s in enumerate(subs)])
            out["merge_gap"] = max(compare.rel_gap(w_b, w), compare.exact_mismatch(idx_b, gidx))
        else:
            out["merge_gap"] = max(compare.exact_mismatch(idx_b, subs[0].indices),
                                   compare.rel_gap(w_b, subs[0].is_weights))

    # write-back (and the evicting one, when the window reaches it)
    for name in ("writeback", "writeback_evict"):
        wb = caps.get(name)
        if not wb:
            continue
        rep_in = _rep(wb["in"])
        fn = lambda r, i, p, k, d: ref.writeback(c, rc, r, i, p, wb["step"], k, rc["evict_num"], d)
        r_rep = jax.jit(lambda r, i, p, k: fn(r, i, p, k, f32))(rep_in, wb["idx"], wb["prios"], wb["rng"])
        if mode == "program":
            o_rep, o_tree = _rep(wb["out"]), wb["out"].tree
        else:
            o_rep = jax.jit(lambda r, i, p, k: fn(r, i, p, k, dt))(rep_in, wb["idx"], wb["prios"], wb["rng"])
            o_tree = _tree(o_rep["tree"], fault)
        g = max(compare.leaf_gap(ref.leaves_of(o_rep).astype(f32), ref.leaves_of(r_rep)),
                compare.exact_mismatch(o_rep["size"], r_rep["size"]))
        out["writeback_gap"] = max(out.get("writeback_gap", 0.0), g)
        out["tree_gap"] = max(out.get("tree_gap", 0.0), compare.tree_gap(o_tree))
    return out


def _own_tree(rep, dt):
    """The tree a reference in the program's place would hold: its own
    sums of the leaves, at its own precision (for the control)."""
    import jax.numpy as jnp
    if dt == jnp.float32:
        return rep["tree"]
    return ref.tree_levels(ref.leaves_of(rep).astype(dt))


def _tree(tree, fault):
    """A planted stale node: node 2 keeps an old value (off by 1%)."""
    if fault == "stale_node":
        tree = tree.at[2].add(tree[2] * 0.01)
    return tree
