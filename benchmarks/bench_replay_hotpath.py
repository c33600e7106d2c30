"""Replay mutation hot path — incremental sum-tree updates vs full rebuilds.

The paper fixed the replay server's contention by batching all request types
(§Contention / Alg. 2); our TPU-native analogue is making each batched
mutation cheap. Schaul et al.'s prioritized replay is O(log C) per update by
design, and this PR's ``sumtree.update`` restores that bound for batched
writes: O(B * log C) incremental propagation instead of the O(C) full
level-rebuild ``sumtree.write`` used to pay. This bench gates the win and
tracks the satellites around it:

* ``write_speedup_incremental_vs_rebuild`` — THE GATE (``--check``): at the
  acceptance geometry (capacity 2^17, B = 64 write-back lanes) the
  incremental write must be >= 3x faster than the rebuild-based write.
* ``ingest_fused`` rows — THE SECOND GATE (``--check``): one dispatch for
  the whole add (priority init + storage scatter + tree repair in one
  jitted ``add_fifo``, whose tree write runs the update kernel on TPU) must
  be >= 1.3x the three-dispatch alloc→store→``sumtree.write`` chain.
* ``sample_fused`` rows — ``sample_with_mass`` is backend-dispatched per
  path: on XLA it *is* the descent + leaf gather (bitwise, and within
  noise of it — the earlier committed 0.69x row was the fused lowering
  running on the wrong backend), on the Pallas backends the descent emits
  the mass for free.
* ``add_alloc`` row — free-slot compaction via masked cumsum (the O(C log C)
  argsort is timed inline as the reference it replaced).
* ``evict_fifo`` row — direct kill-mask + rebuild (the permuted index
  materialization it replaced is timed inline as reference).
* ``writeback_donated`` rows — a ShardFns-style jitted priority write-back
  with and without ``ReplayState`` donation (donation lets XLA update the
  storage pytree in place instead of copying it every call).

Absolute wall numbers are CPU-container artifacts; the ratios are the
reproducible claims. Results land in ``BENCH_replay_hotpath.json``
(``benchmarks/artifacts/`` + committed repo-root twin).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.common import emit, write_artifact  # noqa: E402
from repro.core import priority as prio  # noqa: E402
from repro.core import replay as replay_lib, sumtree  # noqa: E402
from repro.runtime import make_shard_fns, phases  # noqa: E402
from repro.core import apex  # noqa: E402


def timeit(fn, *args, iters=50, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e6 * (time.perf_counter() - t0) / iters


def _alloc_argsort_idx(leaves_live: jax.Array, batch: int) -> jax.Array:
    """The free-slot selection ``add_alloc`` used before this PR: a full
    O(C log C) argsort pulling free slots to the front. Kept here as the
    timing reference for the masked-cumsum compaction."""
    return jnp.argsort(leaves_live, stable=True)[:batch]


# the compaction inside add_alloc (O(C)) — the live code, not a copy
_alloc_cumsum_idx = replay_lib.free_slot_idx


def _evict_permuted(tree: jax.Array, write_pos, size, soft_cap: int):
    """Pre-PR evict_fifo body: materialize the FIFO-ordered index permutation
    and push all C lanes through a tree write."""
    cap = sumtree.capacity(tree)
    excess = jnp.maximum(size - soft_cap, 0)
    oldest = (write_pos - size) % cap
    offs = jnp.arange(cap, dtype=jnp.int32)
    idx = (oldest + offs) % cap
    kill = offs < excess
    old = sumtree.leaves(tree)[idx]
    return sumtree.write_rebuild(tree, idx, jnp.where(kill, 0.0, old))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: fewer timing iterations")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless incremental write >= --min-speedup "
                         "x the rebuild write at the acceptance geometry")
    ap.add_argument("--cap", type=int, default=1 << 17,
                    help="sum-tree capacity (acceptance: 2^17)")
    ap.add_argument("--batch", type=int, default=64,
                    help="write-back batch B (acceptance: 64)")
    ap.add_argument("--min-speedup", type=float, default=3.0)
    ap.add_argument("--min-ingest-speedup", type=float, default=1.3,
                    help="gate: fused one-dispatch ingest vs the "
                         "three-dispatch alloc→store→write chain")
    ap.add_argument("--json", default=None,
                    help="stable artifact path for the JSON result set")
    args = ap.parse_args()
    cap, batch = args.cap, args.batch
    iters = 20 if args.smoke else 100

    leaves = jax.random.uniform(jax.random.key(0), (cap,)) + 0.01
    tree = sumtree.rebuild(leaves)
    idx = jax.random.randint(jax.random.key(1), (batch,), 0, cap)
    vals = jax.random.uniform(jax.random.key(2), (batch,)) + 0.01
    u = jax.random.uniform(jax.random.key(3), (batch,)) * sumtree.total(tree)

    rows = {}

    def row(name, us, derived):
        emit(f"replay_hotpath/{name}", us, derived)
        rows[name] = {"us": us, "derived": str(derived)}

    # -- the gate: incremental vs rebuild write ---------------------------
    # Timed as the replay shard actually runs them: a chain of writes
    # threading the tree through. The incremental path donates the incoming
    # tree (as ``ShardFns`` donates the whole ``ReplayState``), so each of
    # the log2(C) levels updates in place; the rebuild reference is the
    # pre-PR hot path — no donation, full level reconstruction per call.
    wr_rebuild = jax.jit(sumtree.write_rebuild)
    wr_incr = jax.jit(sumtree.update, donate_argnums=(0,))

    def chain(fn, iters):
        t = jnp.array(tree)  # private copy: the chain may donate it away
        for _ in range(2):
            t = fn(t, idx, vals)
        jax.block_until_ready(t)
        t0 = time.perf_counter()
        for _ in range(iters):
            t = fn(t, idx, vals)
        jax.block_until_ready(t)
        return 1e6 * (time.perf_counter() - t0) / iters

    us_rebuild = chain(wr_rebuild, iters)
    us_incr = chain(wr_incr, iters)
    speedup = us_rebuild / max(us_incr, 1e-9)
    row(f"write_rebuild_cap{cap}_b{batch}", us_rebuild, "o_c")
    row(f"write_incremental_cap{cap}_b{batch}", us_incr, "o_b_logc_donated")
    row("write_speedup_incremental_vs_rebuild", us_incr, f"{speedup:.2f}")

    # -- sample+mass: backend-dispatched per path -------------------------
    # ``sample_with_mass`` now picks its form per backend: the explicit
    # two-gather graph on XLA (CPU/GPU — the fused lowering regressed to
    # 0.69x there), the mass-emitting descent kernel on pallas/interpret.
    # Both rows therefore time the *dispatched* op against the explicit
    # two-gather reference; on CPU they are the same graph and the ratio
    # must sit at ~1.0x. Interleaved min-of-rounds keeps the rows stable
    # against CPU frequency drift.
    two_gather = jax.jit(
        lambda t, v: (sumtree.sample(t, v),
                      sumtree.leaves(t)[sumtree.sample(t, v)]))
    fused = jax.jit(sumtree.sample_with_mass)
    pairs = [(timeit(two_gather, tree, u, iters=iters),
              timeit(fused, tree, u, iters=iters)) for _ in range(5)]
    us_two = min(p[0] for p in pairs)
    us_fused = min(p[1] for p in pairs)
    row(f"sample_two_gather_cap{cap}_b{batch}", us_two, "descent+gather")
    row(f"sample_dispatched_cap{cap}_b{batch}", us_fused,
        f"{us_two / max(us_fused, 1e-9):.2f}x_{sumtree.backend()}")

    # -- add_alloc free-slot compaction -----------------------------------
    live = leaves > jnp.median(leaves)  # ~half the slots free
    argsort_idx = jax.jit(_alloc_argsort_idx, static_argnums=1)
    cumsum_idx = jax.jit(_alloc_cumsum_idx, static_argnums=1)
    us_sort = timeit(lambda lv: argsort_idx(lv, batch), live, iters=iters)
    us_cs = timeit(lambda lv: cumsum_idx(lv, batch), live, iters=iters)
    row(f"alloc_argsort_cap{cap}", us_sort, "o_c_logc_reference")
    row(f"alloc_cumsum_cap{cap}", us_cs,
        f"{us_sort / max(us_cs, 1e-9):.2f}x")

    # -- evict_fifo: kill mask vs permuted index write --------------------
    soft = (cap // 8) * 7
    rcfg = replay_lib.ReplayConfig(capacity=cap, min_fill=1)
    state = replay_lib.ReplayState(
        storage={}, tree=tree,
        write_pos=jnp.asarray(0, jnp.int32),
        size=jnp.asarray(cap, jnp.int32),
        total_added=jnp.asarray(cap, jnp.int32))
    ev_new = jax.jit(lambda st: replay_lib.evict_fifo(rcfg, st).tree)
    ev_old = jax.jit(lambda t: _evict_permuted(
        t, jnp.asarray(0, jnp.int32), jnp.asarray(cap, jnp.int32), soft))
    us_ev_new = timeit(ev_new, state, iters=max(4, iters // 4))
    us_ev_old = timeit(ev_old, tree, iters=max(4, iters // 4))
    row(f"evict_fifo_permuted_cap{cap}", us_ev_old, "reference")
    row(f"evict_fifo_masked_cap{cap}", us_ev_new,
        f"{us_ev_old / max(us_ev_new, 1e-9):.2f}x")

    # -- ShardFns add: donated vs copying ---------------------------------
    # The add op scatters a transition block into the storage pytree; with
    # the ``ReplayState`` donated, XLA updates the (multi-MB) storage
    # buffers in place, while the non-donated reference must copy every
    # buffer it writes each call. (Priority write-back leaves storage
    # untouched — unchanged pytree leaves alias through jit — so ``add`` is
    # where donation pays.)
    add_cap, obs_dim, add_lanes = 4096, 64, 128
    wcfg = apex.ApexConfig(
        replay=replay_lib.ReplayConfig(capacity=add_cap, min_fill=1),
        lanes_per_shard=8, rollout_len=8, n_step=3, batch_size=batch,
        evict_interval=10_000)
    item = {"obs": jnp.zeros((obs_dim,), jnp.float32),
            "action": jnp.zeros((), jnp.int32),
            "returns": jnp.zeros(()), "discount_n": jnp.zeros(()),
            "next_obs": jnp.zeros((obs_dim,), jnp.float32)}
    block = phases.TransitionBlock(
        items={"obs": jnp.ones((add_lanes, obs_dim), jnp.float32),
               "action": jnp.zeros((add_lanes,), jnp.int32),
               "returns": jnp.ones((add_lanes,)),
               "discount_n": jnp.full((add_lanes,), 0.99),
               "next_obs": jnp.ones((add_lanes, obs_dim), jnp.float32)},
        priorities=jax.random.uniform(jax.random.key(4), (add_lanes,)) + 0.01)

    fns = make_shard_fns(wcfg, batch)  # donated state (this PR)
    plain_add = jax.jit(lambda st, b: phases.replay_add(wcfg, st, b))

    def run_add(fn):
        st = replay_lib.init(wcfg.replay, item)
        for _ in range(iters):
            st = fn(st, block)
        return jax.block_until_ready(st.tree)

    run_add(fns.add), run_add(plain_add)  # compile both before the clock
    t0 = time.perf_counter(); run_add(fns.add)
    us_don = 1e6 * (time.perf_counter() - t0) / iters
    t0 = time.perf_counter(); run_add(plain_add)
    us_cp = 1e6 * (time.perf_counter() - t0) / iters
    row(f"add_copying_cap{add_cap}_obs{obs_dim}", us_cp, "reference")
    row(f"add_donated_cap{add_cap}_obs{obs_dim}", us_don,
        f"{us_cp / max(us_don, 1e-9):.2f}x")

    # -- one-dispatch ingest vs the alloc→store→write chain ---------------
    # The second gate. Reference is the replaced chain *as it ran*: three
    # separate device dispatches — (1) index/mask/leaf prep, (2) storage
    # scatter, (3) tree write — composed eagerly like every other
    # reference row here (no cross-call donation: a chain of independent
    # jits cannot update the storage pytree in place, so each scatter
    # copies the buffers it touches). The other side is the live code:
    # ``add_fifo`` jitted as one program with the state donated (its tree
    # write is the update kernel on TPU). One dispatch + in-place storage
    # is the claim; the donation-only share of the win is tracked
    # separately by the ``add_donated`` row above.
    rcfg_add = wcfg.replay
    offs = jnp.arange(add_lanes, dtype=jnp.int32)

    @jax.jit
    def ing_prep(tr, pos, pr):
        idx = (pos + offs) % add_cap
        applied = offs < add_lanes
        leaf = jnp.where(applied, prio.to_leaf(pr, rcfg_add.alpha),
                         sumtree.leaves(tr)[idx])
        return idx, applied, leaf

    @jax.jit
    def ing_store(storage, items, idx, applied):
        def scat(buf, x):
            m = applied.reshape(applied.shape + (1,) * (buf.ndim - 1))
            return buf.at[idx].set(jnp.where(m, x.astype(buf.dtype),
                                             buf[idx]))
        return jax.tree.map(scat, storage, items)

    ing_write = jax.jit(sumtree.update)
    ing_fused = jax.jit(
        lambda st, it, pr: replay_lib.add_fifo(rcfg_add, st, it, pr),
        donate_argnums=(0,))

    def run_three(n):
        st = replay_lib.init(rcfg_add, item)
        storage, tr = st.storage, st.tree
        pos = jnp.asarray(0, jnp.int32)
        t0 = time.perf_counter()
        for _ in range(n):
            idx2, app2, leaf2 = ing_prep(tr, pos, block.priorities)
            storage = ing_store(storage, block.items, idx2, app2)
            tr = ing_write(tr, idx2, leaf2)
        jax.block_until_ready(tr)
        return 1e6 * (time.perf_counter() - t0) / n

    def run_fused(n):
        st = replay_lib.init(rcfg_add, item)
        t0 = time.perf_counter()
        for _ in range(n):
            st = ing_fused(st, block.items, block.priorities)
        jax.block_until_ready(st.tree)
        return 1e6 * (time.perf_counter() - t0) / n

    run_three(2), run_fused(2)  # compile both before the clock
    ing_pairs = [(run_three(iters), run_fused(iters)) for _ in range(3)]
    us_three = min(p[0] for p in ing_pairs)
    us_fused_add = min(p[1] for p in ing_pairs)
    ingest_speedup = us_three / max(us_fused_add, 1e-9)
    row(f"ingest_three_dispatch_cap{add_cap}_lanes{add_lanes}", us_three,
        "reference")
    row(f"ingest_fused_cap{add_cap}_lanes{add_lanes}", us_fused_add,
        f"{ingest_speedup:.2f}x_{sumtree.hot_backend(add_cap)}")
    row("ingest_speedup_fused_vs_three_dispatch", us_fused_add,
        f"{ingest_speedup:.2f}")

    write_artifact("replay_hotpath", {
        "bench": "replay_hotpath",
        "unix_time": time.time(),
        "cpu_count": os.cpu_count(),
        "backend": jax.default_backend(),
        "smoke": args.smoke,
        "cap": cap,
        "batch": batch,
        "write_speedup_incremental_vs_rebuild": speedup,
        "min_speedup": args.min_speedup,
        "ingest_speedup_fused_vs_three_dispatch": ingest_speedup,
        "min_ingest_speedup": args.min_ingest_speedup,
        "rows": rows,
    }, args.json)

    if args.check:
        failed = False
        if speedup < args.min_speedup:
            print(f"FAIL: incremental write only {speedup:.2f}x the "
                  f"full-rebuild write at cap={cap} B={batch} (need >= "
                  f"{args.min_speedup:.1f}x)", file=sys.stderr)
            failed = True
        if ingest_speedup < args.min_ingest_speedup:
            print(f"FAIL: one-dispatch ingest only {ingest_speedup:.2f}x the "
                  f"three-dispatch chain at cap={add_cap} "
                  f"lanes={add_lanes} (need >= "
                  f"{args.min_ingest_speedup:.1f}x)", file=sys.stderr)
            failed = True
        if failed:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
