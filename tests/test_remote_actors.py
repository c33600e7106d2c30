"""Remote actor ingest: RemoteActorLoop against a live gateway (in-thread),
the acceptance 2-actor-process run through ``AsyncConfig.actor_procs``, and
the lax.scan learner-batching satellite."""

import dataclasses
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _apex_helpers import item_example, tiny_preset

from repro.net import (RemoteActorLoop, RemoteActorSpec, ReplayGateway,
                       initial_slice)
from repro.runtime import (AsyncConfig, ParamStore, ReplayFabric, phases,
                           run_async)


# --- client loop (in-thread: fast, no subprocess) ----------------------------

def test_remote_loop_streams_blocks_and_pulls_params():
    preset = tiny_preset()
    cfg, env, agent = preset.apex, preset.env, preset.agent
    fabric = ReplayFabric(cfg, item_example(env), num_shards=2).start()
    params = agent.init(jax.random.key(0), item_example(env)["obs"][None])
    store = ParamStore(params)
    gw = ReplayGateway(fabric, store).start()
    try:
        spec = RemoteActorSpec(cfg=cfg, env=env, agent=agent, host=gw.host,
                               port=gw.port, actor_id=0, seed=3,
                               max_rollouts=6)
        stats = RemoteActorLoop(spec).run()
        assert stats["rollouts"] == 6
        assert stats["pushed"] == 6
        assert stats["param_version"] == 0      # pulled the initial snapshot
        # cfg.param_sync_period=2: pulls at rollouts 0 (initial), 2, 4
        assert stats["param_pulls"] == 3
        deadline = time.monotonic() + 10.0
        while (fabric.snapshot().blocks_added < 6
               and time.monotonic() < deadline):
            time.sleep(0.01)
        snap = fabric.snapshot()
        assert snap.blocks_added == 6
        assert snap.transitions_added == stats["transitions"]
        per_shard = [s.blocks_added for s in fabric.shard_snapshots()]
        assert per_shard == [3, 3]              # round robin reached both
    finally:
        gw.stop()
        fabric.stop()
    assert gw.error is None and fabric.error is None
    gsnap = gw.snapshot()
    assert gsnap.client_rollouts == 6           # BYE counters merged
    assert gsnap.cpu_clients == 1               # HELLO named its platform


def test_remote_loop_blocks_on_full_inflight_window():
    """A stalled fabric holds ACKs back; the client's bounded window must
    make it wait (the socket analogue of actor_blocked), then drain once
    the fabric recovers."""
    preset = tiny_preset()
    cfg, env, agent = preset.apex, preset.env, preset.agent

    class StallFabric:
        def __init__(self):
            self.release = threading.Event()
            self.blocks = []

        def add(self, block, timeout=None, trace_id=0):
            if not self.release.is_set():
                time.sleep(0.01)
                return False
            self.blocks.append(block)
            return True

    fabric = StallFabric()
    params = agent.init(jax.random.key(0), item_example(env)["obs"][None])
    gw = ReplayGateway(fabric, ParamStore(params),
                       add_timeout_s=0.001).start()
    try:
        spec = RemoteActorSpec(cfg=cfg, env=env, agent=agent, host=gw.host,
                               port=gw.port, actor_id=0, seed=0,
                               max_inflight=2, max_rollouts=5, poll_s=0.01,
                               param_sync_period=1000)  # isolate the window
        loop = RemoteActorLoop(spec)
        box = {}

        def run():
            box["stats"] = loop.run()

        th = threading.Thread(target=run, daemon=True)
        th.start()
        deadline = time.monotonic() + 60.0
        while loop.stats["blocked"] == 0 and time.monotonic() < deadline:
            time.sleep(0.01)     # client compiling, then filling the window
        assert loop.stats["blocked"] > 0   # parked: 2 in flight, no ACKs
        assert th.is_alive()
        assert loop.stats["pushed"] == 2   # window held the third block back
        fabric.release.set()
        th.join(timeout=30.0)
        assert not th.is_alive()
        assert box["stats"]["rollouts"] == 5
        assert len(fabric.blocks) == 5
    finally:
        gw.stop()
    assert gw.error is None


def test_initial_slice_matches_runner_derivation():
    """Thread actor t and remote actor with actor_id=t must start from the
    same slice — one exploration ladder across the process boundary."""
    preset = tiny_preset()
    cfg = dataclasses.replace(preset.apex, num_shards=3)
    seed = 11
    _, e_rng = jax.random.split(jax.random.key(seed))
    for t in range(3):
        a_rng = jax.random.fold_in(e_rng, t)
        from repro.envs.synthetic import batch_reset
        env_state, obs = batch_reset(preset.env, a_rng, cfg.lanes_per_shard)
        want = phases.ActorSlice(
            env_state=env_state, obs=obs,
            ep_return=jnp.zeros((cfg.lanes_per_shard,), jnp.float32),
            rng=jax.random.fold_in(a_rng, 1),
            frames=jnp.zeros((), jnp.int32))
        got = initial_slice(cfg, preset.env, seed, t)

        def cmp(a, b):
            if jax.dtypes.issubdtype(jnp.asarray(a).dtype,
                                     jax.dtypes.prng_key):
                a, b = jax.random.key_data(a), jax.random.key_data(b)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        jax.tree.map(cmp, want, got)


# --- acceptance: 2 actor processes through run_async -------------------------

def test_run_async_two_actor_procs_end_to_end():
    """Acceptance: a 2-actor-process run via actor_procs reaches the replay
    min-fill gate and completes learner steps, with priority write-backs
    landing on the correct shard. The CI matrix sets REPRO_TEST_TRANSPORT
    to pin the byte path (strict shm — no silent tcp fallback) instead of
    the default auto negotiation."""
    preset = tiny_preset()
    transport = os.environ.get("REPRO_TEST_TRANSPORT") or "auto"
    acfg = AsyncConfig(actor_threads=0, actor_procs=2, replay_shards=2,
                       total_learner_steps=8, max_seconds=240.0, seed=3,
                       transport=transport)
    res = run_async(preset.apex, acfg, preset.env, preset.agent,
                    preset.make_optimizer())
    s = res.stats
    assert s["learner_steps"] == 8
    assert int(res.learner.learner_step) == 8
    assert s["actor_transitions"] > 0           # min-fill was reached
    assert s["replay_size"] > 0
    assert res.gateway_stats is not None
    assert res.gateway_stats.connections == 2
    assert res.gateway_stats.cpu_clients == 2   # children keep off the chip
    if transport == "shm":
        assert res.gateway_stats.shm_connections == 2
    assert res.gateway_stats.blocks_in > 0
    assert res.gateway_stats.transitions_in == s["actor_transitions"]
    assert len(res.shard_stats) == 2
    for shard in res.shard_stats:
        assert shard.blocks_added > 0           # round robin reached both
        assert shard.updates_applied == 8       # write-backs hit each owner
    assert res.service_stats.transitions_added == s["actor_transitions"]
    assert s["param_version"] >= 1


def test_async_config_rejects_zero_actors():
    preset = tiny_preset()
    with pytest.raises(ValueError, match="at least one actor"):
        run_async(preset.apex, AsyncConfig(actor_threads=0, actor_procs=0),
                  preset.env, preset.agent, preset.make_optimizer())


# --- learner batching (lax.scan satellite) -----------------------------------

def test_learner_batching_consumes_k_per_jitted_call():
    preset = tiny_preset()
    acfg = AsyncConfig(actor_threads=2, total_learner_steps=8,
                       learn_batches_per_step=3, max_seconds=120.0, seed=5)
    res = run_async(preset.apex, acfg, preset.env, preset.agent,
                    preset.make_optimizer())
    s = res.stats
    assert s["learner_steps"] == 9              # first multiple of 3 >= 8
    assert int(res.learner.learner_step) == 9
    # one write-back application per consumed batch: the eviction clock is
    # unchanged by k-batching
    assert res.service_stats.updates_applied == 9
    assert s["learner_transitions"] == 9 * preset.apex.batch_size
    assert s["param_version"] >= 1


def test_learner_batching_matches_single_batch_numerics():
    """k updates through the scanned learner == k sequential learn_phase
    calls on the same batches."""
    preset = tiny_preset()
    cfg, env, agent = preset.apex, preset.env, preset.agent
    opt = preset.make_optimizer()
    params = agent.init(jax.random.key(1), item_example(env)["obs"][None])
    lslice = phases.LearnerSlice(
        params=params, target_params=jax.tree.map(jnp.copy, params),
        opt_state=opt.init(params), learner_step=jnp.zeros((), jnp.int32))

    from _apex_helpers import make_block
    k, bsz = 3, cfg.batch_size
    blocks = [make_block(cfg, env, agent, seed=s) for s in range(k)]
    items = [jax.tree.map(lambda x: x[:bsz], b.items) for b in blocks]
    weights = [jnp.linspace(0.5, 1.0, bsz) for _ in range(k)]

    ref = lslice
    ref_prios = []
    for i in range(k):
        ref, prios, _ = phases.learn_phase(cfg, agent, opt, ref, items[i],
                                           weights[i])
        ref_prios.append(prios)

    def scan_fn(lsl, items_k, w_k):
        def body(l, xw):
            l, prios, _ = phases.learn_phase(cfg, agent, opt, l, xw[0], xw[1])
            return l, prios
        return jax.lax.scan(body, lsl, (items_k, w_k))

    items_k = jax.tree.map(lambda *xs: jnp.stack(xs), *items)
    got, got_prios = jax.jit(scan_fn)(lslice, items_k, jnp.stack(weights))
    assert int(got.learner_step) == k
    np.testing.assert_allclose(np.asarray(got_prios),
                               np.asarray(jnp.stack(ref_prios)),
                               rtol=1e-5, atol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        got.params, ref.params)
