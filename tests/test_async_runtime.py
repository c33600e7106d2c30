"""Async runtime: shared phases, snapshot store, replay-service queue
behaviour (backpressure + starvation), ingest staging, and an end-to-end
decoupled run."""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from _apex_helpers import init_actor, item_example, make_block, tiny_preset

from repro.core import apex, replay as replay_lib
from repro.runtime import (AsyncConfig, ParamStore, ReplayService, phases,
                           run_async)
from repro.runtime.sources import BlockStager

# CI matrix leg: REPRO_TEST_INGEST_STAGING=1 re-runs the end-to-end test
# with the pipelined ingest stager attached (pass-through puts on CPU, so
# it exercises the stage-ahead ordering, not the DMA).
INGEST_STAGING = bool(os.environ.get("REPRO_TEST_INGEST_STAGING"))
# CI matrix leg: REPRO_TEST_METRICS_DIR=<dir> re-runs the end-to-end test
# with the telemetry plane enabled (JSONL sink + full-rate tracing), and
# CI uploads the resulting metrics/spans JSONL as a workflow artifact.
METRICS_DIR = os.environ.get("REPRO_TEST_METRICS_DIR") or None
# CI matrix leg: REPRO_TEST_INFERENCE_MODE=slots re-runs the end-to-end
# test with the shared inference engine in slot-scheduled continuous-
# batching mode (wave also accepted; empty = per-thread dispatch).
INFERENCE_MODE = os.environ.get("REPRO_TEST_INFERENCE_MODE") or None


# --- shared phases ----------------------------------------------------------

def test_act_phase_block_shape_and_frames():
    preset = tiny_preset()
    cfg, env, agent = preset.apex, preset.env, preset.agent
    aslice, obs = init_actor(cfg, env, jax.random.key(0))
    params = agent.init(jax.random.key(1), obs[:1])
    new_slice, block, metrics = phases.act_phase(cfg, env, agent, params,
                                                 aslice, 0)
    n_transitions = cfg.lanes_per_shard * cfg.window
    assert block.priorities.shape == (n_transitions,)
    assert block.items["obs"].shape[0] == n_transitions
    assert int(new_slice.frames) == cfg.lanes_per_shard * cfg.rollout_len
    assert bool(jnp.all(block.priorities >= 0))
    assert "mean_ep_return" in metrics


def test_sync_driver_composes_shared_phases():
    """apex.actor_phase == act_phase + replay_add on identical state, so the
    lockstep driver and the async runtime can never drift apart."""
    preset = tiny_preset()
    cfg, env, agent = preset.apex, preset.env, preset.agent
    opt = preset.make_optimizer()
    state = apex.init_state(cfg, env, agent, opt, jax.random.key(0))

    via_driver, _ = apex.actor_phase(cfg, env, agent, state, 0)

    aslice = phases.ActorSlice(env_state=state.env_state, obs=state.obs,
                               ep_return=state.ep_return, rng=state.rng,
                               frames=state.frames)
    aslice2, block, _ = phases.act_phase(cfg, env, agent, state.actor_params,
                                         aslice, 0)
    replay2 = phases.replay_add(cfg, state.replay, block)

    np.testing.assert_allclose(np.asarray(via_driver.replay.tree),
                               np.asarray(replay2.tree), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(via_driver.obs),
                                  np.asarray(aslice2.obs))
    assert int(via_driver.frames) == int(aslice2.frames)


def test_learn_phase_steps_and_priorities():
    preset = tiny_preset()
    cfg, env, agent = preset.apex, preset.env, preset.agent
    opt = preset.make_optimizer()
    aslice, obs = init_actor(cfg, env, jax.random.key(0))
    params = agent.init(jax.random.key(1), obs[:1])
    lslice = phases.LearnerSlice(
        params=params, target_params=jax.tree.map(jnp.copy, params),
        opt_state=opt.init(params), learner_step=jnp.zeros((), jnp.int32))
    _, block, _ = phases.act_phase(cfg, env, agent, params, aslice, 0)
    items = jax.tree.map(lambda x: x[:cfg.batch_size], block.items)
    w = jnp.ones((cfg.batch_size,), jnp.float32)
    new_lslice, prios, metrics = phases.learn_phase(cfg, agent, opt, lslice,
                                                    items, w)
    assert int(new_lslice.learner_step) == 1
    assert prios.shape == (cfg.batch_size,)
    assert bool(jnp.all(jnp.isfinite(prios)))
    assert bool(jnp.isfinite(metrics["loss"]))
    # params actually moved
    diff = jax.tree_util.tree_reduce(
        lambda a, l: a + float(jnp.abs(l).sum()),
        jax.tree.map(lambda a, b: a - b, new_lslice.params, lslice.params), 0.0)
    assert diff > 0


# --- param store ------------------------------------------------------------

def test_param_store_versioning():
    store = ParamStore({"w": jnp.zeros((2,))})
    assert store.version == 0
    v1 = store.publish({"w": jnp.ones((2,))})
    assert v1 == 1 and store.version == 1
    snap = store.get()
    assert snap.version == 1
    assert float(snap.params["w"][0]) == 1.0


def test_param_store_concurrent_reads_never_torn():
    """Readers must always see a snapshot whose version matches its payload."""
    store = ParamStore(jnp.zeros((4,)) + 0.0)
    stop = threading.Event()
    errors = []

    def reader():
        while not stop.is_set():
            snap = store.get()
            if float(snap.params[0]) != float(snap.version):
                errors.append((snap.version, float(snap.params[0])))

    threads = [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    for v in range(1, 200):
        store.publish(jnp.zeros((4,)) + float(v))
    stop.set()
    for t in threads:
        t.join()
    assert not errors


# --- replay service queue paths ---------------------------------------------

def empty_replay(cfg, env):
    return replay_lib.init(cfg.replay, item_example(env))


def test_actor_backpressure_when_service_stalled():
    """With the owner thread not running, the bounded add queue fills and
    further adds report backpressure instead of growing memory."""
    preset = tiny_preset()
    cfg, env, agent = preset.apex, preset.env, preset.agent
    service = ReplayService(cfg, empty_replay(cfg, env),
                            add_queue_depth=2)  # never started
    block = make_block(cfg, env, agent)
    assert service.add(block, timeout=0.01)
    assert service.add(block, timeout=0.01)
    t0 = time.monotonic()
    assert not service.add(block, timeout=0.05)   # actor would block here
    assert time.monotonic() - t0 >= 0.04          # it genuinely waited


def test_learner_starved_until_min_fill():
    """Before min-fill the sample queue stays empty (learner-starved path);
    after enough adds the service starts serving batches."""
    preset = tiny_preset(min_fill=64)
    cfg, env, agent = preset.apex, preset.env, preset.agent
    service = ReplayService(cfg, empty_replay(cfg, env)).start()
    try:
        assert service.get_batch(timeout=0.05) is None   # starved: empty replay
        block = make_block(cfg, env, agent)              # 24 transitions
        n_blocks = 64 // int(block.priorities.shape[0]) + 1
        for _ in range(n_blocks):
            assert service.add(block, timeout=1.0)
        batch = None
        deadline = time.monotonic() + 5.0
        while batch is None and time.monotonic() < deadline:
            batch = service.get_batch(timeout=0.1)
        assert batch is not None, "service never served once min-fill passed"
        assert batch.items["obs"].shape[0] == cfg.batch_size
        assert bool(jnp.all(batch.is_weights > 0))
    finally:
        service.stop()
    assert service.stats.transitions_added >= 64
    assert service.stats.batches_sampled >= 1


def test_priority_writeback_applied_on_drain():
    """Write-backs queued before stop() are applied during the drain."""
    preset = tiny_preset(min_fill=8)
    cfg, env, agent = preset.apex, preset.env, preset.agent
    service = ReplayService(cfg, empty_replay(cfg, env)).start()
    block = make_block(cfg, env, agent)
    assert service.add(block, timeout=1.0)
    batch = None
    deadline = time.monotonic() + 5.0
    while batch is None and time.monotonic() < deadline:
        batch = service.get_batch(timeout=0.1)
    assert batch is not None
    service.write_back(batch.indices,
                       jnp.full((cfg.batch_size,), 7.0, jnp.float32))
    service.stop()
    assert service.stats.updates_applied == 1
    assert service.learner_steps == 1


# --- ingest staging ----------------------------------------------------------

def test_block_stager_put_path_bit_identical():
    """Forcing the put path on a CPU host must still be value-preserving:
    staged leaves land on the device bitwise-equal, already-resident leaves
    pass through untouched, and the default stager passes through on CPU."""
    preset = tiny_preset()
    block = make_block(preset.apex, preset.env, preset.agent)
    host = jax.tree.map(np.asarray, block)   # gateway-style numpy leaves
    stager = BlockStager(passthrough=False)
    staged = stager.stage(host)
    assert stager.blocks_staged == 1
    for got, want in zip(jax.tree.leaves(staged), jax.tree.leaves(block)):
        assert isinstance(got, jax.Array)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # device-resident leaves are not re-put (no redundant copy/dispatch)
    again = stager.stage(staged)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(staged)):
        assert a is b
    default = BlockStager()                  # auto-detect: CPU passes through
    assert default.passthrough
    assert default.stage(host) is host
    assert default.blocks_staged == 0


def test_staged_shard_matches_unstaged_and_reports_h2d():
    """A shard with a (forced-put) ingest stager must produce the exact
    replay state of an unstaged shard over the same add stream, while the
    h2d_us / blocks_staged counters populate."""
    preset = tiny_preset(min_fill=10**6)     # sampler stays quiet
    cfg, env, agent = preset.apex, preset.env, preset.agent
    blocks = [make_block(cfg, env, agent, seed=s) for s in range(3)]

    def run(stager):
        svc = ReplayService(cfg, empty_replay(cfg, env), stager=stager).start()
        try:
            for b in blocks:
                assert svc.add(b, timeout=5.0)
        finally:
            svc.stop()
        return svc

    plain = run(None)
    staged = run(BlockStager(passthrough=False))
    np.testing.assert_array_equal(np.asarray(staged.replay_state.tree),
                                  np.asarray(plain.replay_state.tree))
    for got, want in zip(jax.tree.leaves(staged.replay_state.storage),
                         jax.tree.leaves(plain.replay_state.storage)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert plain.stats.blocks_staged == 0
    assert staged.stats.blocks_staged == len(blocks)
    # h2d_us is a derived view (histogram mean) — read it via snapshot().
    assert staged.snapshot().h2d_us > 0.0


def test_run_async_staged_ingest_end_to_end():
    """The pipelined staged drain (stage k+1 before applying k, flush at
    queue-dry) must preserve every end-to-end invariant."""
    preset = tiny_preset()
    acfg = AsyncConfig(actor_threads=2, total_learner_steps=4,
                       max_seconds=60.0, seed=5, ingest_staging=True)
    res = run_async(preset.apex, acfg, preset.env, preset.agent,
                    preset.make_optimizer())
    assert res.stats["learner_steps"] == 4
    assert res.service_stats.updates_applied == 4
    assert res.service_stats.transitions_added == res.stats["actor_transitions"]
    # CPU host: the default stager passes through (no puts to count)
    assert res.service_stats.blocks_staged == 0


# --- end to end -------------------------------------------------------------

def test_run_async_end_to_end():
    preset = tiny_preset()
    acfg = AsyncConfig(actor_threads=2, total_learner_steps=8,
                       max_seconds=60.0, seed=3,
                       ingest_staging=INGEST_STAGING,
                       inference_batching=bool(INFERENCE_MODE),
                       inference_mode=INFERENCE_MODE or "wave",
                       metrics_dir=METRICS_DIR,
                       trace_sample_rate=1.0 if METRICS_DIR else 0.0)
    res = run_async(preset.apex, acfg, preset.env, preset.agent,
                    preset.make_optimizer())
    s = res.stats
    if METRICS_DIR:
        assert os.path.exists(os.path.join(METRICS_DIR, "metrics.jsonl"))
        assert os.path.exists(os.path.join(METRICS_DIR, "spans.jsonl"))
    assert s["learner_steps"] == 8
    assert int(res.learner.learner_step) == 8
    assert s["actor_transitions"] > 0
    assert s["learner_transitions"] == 8 * preset.apex.batch_size
    assert s["param_version"] >= 1          # learner published snapshots
    assert s["replay_size"] > 0
    assert s["generate_consume_ratio"] > 0
    # every consumed batch's priorities came back to the replay service
    assert res.service_stats.updates_applied == 8
    assert res.service_stats.transitions_added == s["actor_transitions"]


def test_actor_counters_move_while_actors_run():
    """The actor counters live in the run's registry (RuntimeHandles
    .telemetry) and move per rollout, while the actor threads still run,
    not only when a thread exits."""
    preset = tiny_preset()
    seen = {}

    def on_handles(h):
        reg = h.telemetry.registry
        deadline = time.monotonic() + 60.0
        while (reg.counter("actor/rollouts").value < 3
               and time.monotonic() < deadline):
            time.sleep(0.01)
        seen["rollouts"] = reg.counter("actor/rollouts").value
        seen["transitions"] = reg.counter("actor/transitions").value
        seen["alive"] = sorted(th.name for th in threading.enumerate()
                               if th.name in ("actor-0", "actor-1")
                               and th.is_alive())
        h.stop.set()

    res = run_async(preset.apex, AsyncConfig(actor_threads=2,
                                             total_learner_steps=10 ** 6,
                                             max_seconds=120.0, seed=5),
                    preset.env, preset.agent, preset.make_optimizer(),
                    on_handles=on_handles)
    assert seen["rollouts"] >= 3
    assert seen["alive"] == ["actor-0", "actor-1"]
    assert seen["transitions"] > 0
    assert res.stats["rollouts"] >= seen["rollouts"]
    assert res.stats["actor_transitions"] >= seen["transitions"]
