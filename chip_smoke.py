"""Smoke run of the Ape-X main path on one TPU chip, at the paper's widths.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # four chips: the shard_map driver only

The model is ``configs/apex_dqn.full()``: a dueling MLP 512-512 with a 512
head, batch 512 split across shards, a 2^21-slot replay, n = 3; weights are
random, made from a seed. Each phase prints one ``[phase] k=v`` line:

* ``device`` — platform, device kind and count;
* ``kernel-parity`` — the sum-tree sample and update kernels and both
  replay adds (whose tree write is the update kernel) at C = 2^15 with the
  batch shapes the runs below send, once on the Pallas path and once on the
  XLA path: outputs compared bit for bit, and each op's wall time per call
  (``<op>_us=pallas/xla``, the mean of ``TIMED_CALLS`` waited-for calls);
* ``lockstep`` — ``--mode apex-dqn --full`` (2^17 slots, the XLA path);
* ``decoupled`` — ``--runtime async --replay-shards 4`` (2^15 slots per
  shard, the Pallas path) with actor threads, batched inference and staging;
* ``actor-procs`` — the same with two actor processes on the CPU streaming
  through the replay gateway;
* ``dpg`` — ``--mode apex-dpg --full --runtime async`` (adds into free slots,
  prioritized eviction compiled into the write-back);
* ``compile`` — seconds spent compiling or loading from the cache.

The runs go through ``repro.launch.train.main``, the code the CLI runs. The
last line of output is one JSON object naming the device. The script exits
non-zero, and prints no such line, when JAX finds no TPU, when the sum-tree
backend is forced, or when any phase fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

SEED = 0
TIMED_CALLS = 300


class SmokeError(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def report(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def bitwise_equal(a, b) -> bool:
    import jax
    import numpy as np
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.shape == y.shape and x.dtype == y.dtype
        and np.array_equal(np.asarray(x).reshape(-1).view(np.uint8),
                           np.asarray(y).reshape(-1).view(np.uint8))
        for x, y in zip(la, lb))


def per_call_us(fn, *args, thread: bool) -> float:
    """Mean wall time of one ``fn(*args)`` call, dispatch included, each
    call waited for. With ``thread`` the first argument is donated and
    threaded through the calls, as a replay shard runs its adds and
    write-backs; otherwise every call reads the same inputs."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda *a: fn(*a), donate_argnums=(0,) if thread else ())
    first, rest = args[0], args[1:]
    if thread:  # a private copy: donation consumes it
        first = jax.tree.map(jnp.copy, first)
    for i in range(TIMED_CALLS + 1):
        if i == 1:  # the first call compiles
            t0 = time.perf_counter()
        out = jax.block_until_ready(f(first, *rest))
        if thread:
            first = out
    return 1e6 * (time.perf_counter() - t0) / TIMED_CALLS


def on_both_paths(fn, *args, thread: bool = False):
    """``fn(*args)`` jitted on the auto backend, then on ``xla``: both
    outputs and both per-call times in microseconds. A fresh wrapper per
    run: the backend is chosen when the function is traced."""
    import jax
    from repro.core import sumtree
    outs, us = [], []
    for forced in (None, "xla"):
        sumtree.set_backend(forced)
        try:
            outs.append(jax.block_until_ready(
                jax.jit(lambda *a: fn(*a))(*args)))
            us.append(per_call_us(fn, *args, thread=thread))
        finally:
            sumtree.set_backend(None)
    return outs, us


def kernel_parity(replay_shards: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import apex_dqn
    from repro.core import priority as prio, replay as replay_lib, sumtree
    from repro.runtime import phases
    from repro.runtime.fabric import shard_replay_config

    preset = apex_dqn.full()
    cfg = preset.apex
    rcfg = shard_replay_config(cfg.replay, replay_shards)
    cap = rcfg.capacity
    backend = sumtree.hot_backend(cap)
    check(backend == "pallas", f"capacity {cap}: backend {backend}, "
          "expected pallas")
    sub_batch = cfg.batch_size // replay_shards
    block = cfg.lanes_per_shard * cfg.window

    k = jax.random.split(jax.random.key(SEED), 8)
    live = jax.random.uniform(k[0], (cap,)) < 0.9
    raw = jax.random.uniform(k[1], (cap,), minval=0.0, maxval=3.0)
    tree = sumtree.rebuild(jnp.where(live, prio.to_leaf(raw, rcfg.alpha), 0.0))
    # write-back values are a function of the slot, as a learner's are
    # (duplicate samples of one slot carry one TD error)
    fresh = prio.to_leaf(jax.random.uniform(k[2], (cap,), maxval=3.0),
                         rcfg.alpha)
    times = {}
    for b in (sub_batch, cfg.batch_size):
        u = sumtree.stratified_uniforms(k[3], b, sumtree.total(tree))
        (got, want), times[f"sample_b{b}"] = on_both_paths(
            sumtree.sample_with_mass, tree, u)
        check(bitwise_equal(got, want), f"sample B={b}: Pallas != XLA")
        idx = want[0]
        (got, want), times[f"update_b{b}"] = on_both_paths(
            sumtree.write, tree, idx, fresh[idx], thread=True)
        check(bitwise_equal(got, want), f"update B={b}: Pallas != XLA")

    env = preset.env
    obs = jnp.zeros((1,) + env.obs_shape, env.obs_dtype)
    example = phases.item_example(env, obs)

    def fill(key, a, rows):
        shape = (rows,) + jnp.shape(a)
        dt = jnp.asarray(a).dtype
        if dt == jnp.uint8:
            return jax.random.randint(key, shape, 0, 256).astype(dt)
        if jnp.issubdtype(dt, jnp.integer):
            return jax.random.randint(key, shape, 0, env.num_actions, dt)
        return jax.random.normal(key, shape, dt)

    leaves, treedef = jax.tree.flatten(example)
    keys = jax.random.split(k[4], 2 * len(leaves))
    storage = jax.tree.unflatten(
        treedef, [fill(kk, a, cap) for kk, a in zip(keys, leaves)])
    items = jax.tree.unflatten(
        treedef, [fill(kk, a, block) for kk, a in zip(keys[len(leaves):],
                                                       leaves)])
    state = replay_lib.ReplayState(
        storage=storage, tree=tree,
        write_pos=jnp.asarray(cap - block // 2, jnp.int32),   # wraps
        size=jnp.sum(live).astype(jnp.int32),
        total_added=jnp.zeros((), jnp.int32))
    prios = jax.random.uniform(k[5], (block,), minval=-3.0, maxval=3.0)
    valid = jax.random.uniform(k[6], (block,)) < 0.9
    for name, add in (("fifo", replay_lib.add_fifo),
                      ("alloc", replay_lib.add_alloc)):
        (got, want), times[f"ingest_{name}"] = on_both_paths(
            lambda s, it, p, v: add(rcfg, s, it, p, v),
            state, items, prios, valid, thread=True)
        check(bitwise_equal(got, want), f"ingest ({name}): Pallas != XLA")
    # per-call microseconds, Pallas / XLA, each call waited for
    us = {f"{op}_us": f"{p}/{x}" for op, (p, x) in times.items()}
    report("kernel-parity", capacity=cap, backend=backend,
           sample_update_batches=f"{sub_batch},{cfg.batch_size}",
           ingest_block=block, ingest_modes="fifo,alloc", result="bitwise",
           calls=TIMED_CALLS, **us)


def lockstep(train) -> None:
    import numpy as np
    from repro.configs import apex_dqn
    from repro.core import sumtree

    cfg = apex_dqn.full().apex
    iters = 4
    t0 = time.perf_counter()
    state, metrics = train.main(["--mode", "apex-dqn", "--full",
                                 "--iterations", str(iters),
                                 "--log-every", str(iters)])
    loss = float(metrics["loss"])
    frames = int(state.frames)
    backend = sumtree.hot_backend(cfg.replay.capacity)
    check(backend == "xla", f"capacity {cfg.replay.capacity}: backend "
          f"{backend}, expected xla")
    check(np.isfinite(loss), f"loss {loss}")
    check(float(metrics["updated"]) == 1.0, "learner never updated")
    check(frames == iters * cfg.lanes_per_shard * cfg.rollout_len,
          f"frames {frames}")
    report("lockstep", capacity=cfg.replay.capacity, backend=backend,
           iterations=iters, frames=frames, loss=loss,
           replay_size=int(state.replay.size),
           seconds=time.perf_counter() - t0)


def decoupled(train, phase: str, extra: list[str], steps: int,
              replay_shards: int) -> None:
    from repro.configs import apex_dqn
    from repro.core import sumtree

    cap = apex_dqn.full().apex.replay.capacity // replay_shards
    t0 = time.perf_counter()
    res = train.main(["--mode", "apex-dqn", "--full", "--runtime", "async",
                      "--replay-shards", str(replay_shards),
                      "--ingest-staging", "--sample-staging",
                      "--iterations", str(steps)] + extra)
    s = res.stats
    backend = sumtree.hot_backend(cap)
    check(backend == "pallas", f"capacity {cap}: backend {backend}, "
          "expected pallas")
    check(int(s["learner_steps"]) == steps,
          f"learner_steps {s['learner_steps']} != {steps}")
    kv = dict(capacity=cap, backend=backend,
              learner_steps=int(s["learner_steps"]),
              generate_tps=s["actor_tps"], consume_tps=s["learner_tps"],
              blocks_staged=res.service_stats.blocks_staged,
              replay_size=int(s["replay_size"]))
    g = res.gateway_stats
    if g is not None:
        procs = int(s["actor_procs"])
        check(g.blocks_in > 0, "no block arrived through the gateway")
        check(int(s["actor_proc_exits"]) == 0,
              f"actor_proc_exits {s['actor_proc_exits']}")
        check(int(s["actor_restarts"]) == 0,
              f"actor_restarts {s['actor_restarts']}")
        check(g.cpu_clients == procs,
              f"{g.cpu_clients} of {procs} actor processes ran on the CPU")
        kv.update(actor_procs=procs, gateway_blocks_in=g.blocks_in,
                  actor_proc_exits=int(s["actor_proc_exits"]),
                  actor_restarts=int(s["actor_restarts"]),
                  children_platform="cpu")
    report(phase, **kv, seconds=time.perf_counter() - t0)


def dpg(train) -> None:
    from repro.configs import apex_dpg
    from repro.core import sumtree

    cfg = apex_dpg.full().apex
    steps = 20
    t0 = time.perf_counter()
    res = train.main(["--mode", "apex-dpg", "--full", "--runtime", "async",
                      "--iterations", str(steps)])
    s = res.stats
    check(int(s["learner_steps"]) == steps,
          f"learner_steps {s['learner_steps']} != {steps}")
    report("dpg", capacity=cfg.replay.capacity,
           backend=sumtree.hot_backend(cfg.replay.capacity),
           eviction=cfg.eviction, learner_steps=int(s["learner_steps"]),
           generate_tps=s["actor_tps"], consume_tps=s["learner_tps"],
           replay_size=int(s["replay_size"]),
           seconds=time.perf_counter() - t0)


def four_chips() -> None:
    """The lockstep ``shard_map`` driver over four chips, and one shard of
    the same configuration on one chip."""
    import jax
    import numpy as np
    from repro.configs import apex_dqn
    from repro.core import apex
    from repro.launch.mesh import make_mesh

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, need 4")
    preset = apex_dqn.full(num_shards=4)
    cfg = preset.apex
    iters = 4
    per_shard = iters * cfg.lanes_per_shard * cfg.rollout_len
    runs = {}
    for name, mesh in (("mesh-4", make_mesh((4,), ("data",))),
                       ("one-shard", None)):
        t0 = time.perf_counter()
        init_fn, step_fn = apex.make_train_fn(
            cfg, preset.env, preset.agent, preset.make_optimizer(), mesh=mesh)
        state = init_fn(jax.random.key(SEED))
        for _ in range(iters):
            state, metrics = step_fn(state)
        loss = float(metrics["loss"])
        frames = np.asarray(state.frames).reshape(-1)
        check(np.isfinite(loss), f"{name}: loss {loss}")
        check(float(metrics["updated"]) == 1.0, f"{name}: learner idle")
        check((frames == per_shard).all(), f"{name}: frames {frames}")
        devices = {
            field: sorted({s.device.id for s in leaf.addressable_shards})
            for field, leaf in (("replay", state.replay.tree),
                                ("env", state.obs))}
        want = 4 if mesh is not None else 1
        for field, ids in devices.items():
            check(len(ids) == want, f"{name}: {field} state on devices {ids}")
        runs[name] = dict(loss=loss, frames=frames.tolist())
        report(name, shards=len(frames), capacity=cfg.replay.capacity,
               frames=",".join(map(str, frames)), loss=loss,
               replay_devices=",".join(map(str, devices["replay"])),
               env_devices=",".join(map(str, devices["env"])),
               seconds=time.perf_counter() - t0)
    check(runs["mesh-4"]["frames"][0] == runs["one-shard"]["frames"][0],
          "shard 0 advanced differently on one chip")


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip shard_map driver and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)
    forced = os.environ.get("REPRO_SUMTREE_BACKEND")
    if forced:
        print(f"REPRO_SUMTREE_BACKEND={forced} forces the sum-tree backend; "
              "the smoke checks the automatic choice", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    import jax
    from jax import monitoring
    from repro.launch import train
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX runs on {dev.platform}", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    compile_s = [0.0]
    cache_hits = [0]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_hits[0] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    t0 = time.perf_counter()
    report("device", platform=dev.platform, kind=repr(dev.device_kind),
           count=len(devices), compile_cache=cache_dir)
    if args.four_chips:
        four_chips()
    else:
        kernel_parity(replay_shards=4)
        lockstep(train)
        decoupled(train, "decoupled", ["--actor-threads", "2",
                                       "--inference-batching"],
                  steps=30, replay_shards=4)
        decoupled(train, "actor-procs", ["--actor-threads", "0",
                                         "--actor-procs", "2"],
                  steps=30, replay_shards=4)
        dpg(train)
    report("compile", backend_compile_s=compile_s[0],
           cache_hits=cache_hits[0], wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
