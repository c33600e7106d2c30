"""Decoupled actor/learner runtime (paper Fig. 1, host-threaded realization).

The synchronous driver (``repro.core.apex``) alternates acting and learning
inside one jitted step, which pins the generate:consume ratio to whatever
``rollout_len``/``learner_steps_per_iter`` dictate. Here the two sides run
free:

* N actor threads each own an ``ActorSlice`` (``lanes_per_shard`` vector
  envs) and loop: jitted ``act_phase`` → push the ``TransitionBlock`` into
  the ``ReplayFabric`` (blocking on a bounded queue = backpressure). With
  ``inference_batching`` the per-thread dispatch is replaced by one batched
  ``vmap(act_phase)`` call shared by all actors (``runtime.inference``) —
  the paper's FPS-per-actor economics.
* ``actor_procs`` more actors run as separate OS *processes* (the paper's
  multi-host regime, §3): a ``ReplayGateway`` TCP thread decodes their
  ``ADD_BLOCK`` frames and routes them into the very same ``ReplayFabric``,
  so the learner is agnostic to whether a block crossed a queue or a
  socket. Thread- and process-actors share one exploration ladder
  (processes take the upper actor ids).
* The ``ReplayFabric`` owns ``replay_shards`` independent ``ReplayShard``
  owner threads; actor blocks route round-robin and the learner batch is
  merged from per-shard sub-samples with globally-corrected IS weights
  (``repro.core.sampling``).
* The learner thread loops: pop a prioritized batch from its
  ``SampleSource`` → jitted ``learn_phase`` → write the fresh priorities
  back through the source → publish params through the versioned lock-free
  ``ParamStore``. The learner never touches fabric internals: the source is
  ``LocalFabricSource`` over the in-process fabric by default,
  ``RemoteFabricSource`` against another host's gateway with
  ``learner_remote`` (this process then runs *only* the learner), and
  either wrapped in ``StagedSource`` with ``sample_staging`` (device-staged
  double buffering: the H2D put of batch k+1 overlaps the learn step on k).
* With ``serve_sampling`` the roles flip: this process runs actors + fabric
  + gateway and *no* local learner — a remote learner attaches through the
  gateway's sample plane, and the run's learner clock is the stream of
  ``PRIORITY_UPDATE`` write-backs it sends back.

Threads overlap because XLA releases the GIL while kernels execute, so actor
rollouts, learner updates, and replay maintenance genuinely run concurrently
on CPU — and the same wiring maps to streams/devices on accelerators.

Throughput accounting matches the paper's §4.1 split: transitions/s
*generated* by actors and transitions/s *consumed* by the learner are
measured independently (theirs: ~12.5K vs ~9.7K, ratio ~1.29).
"""

from __future__ import annotations

import dataclasses
import multiprocessing
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro.obs import Telemetry
from repro.obs import log as obslog
from repro.runtime import phases
from repro.runtime import snapshot as snapshot_lib
from repro.runtime.fabric import ReplayFabric
from repro.runtime.inference import InferenceServer, InferenceStats
from repro.runtime.params import ParamStore
from repro.runtime.service import ServiceStats
from repro.runtime.sources import (LocalFabricSource, SampleSource,
                                   SourceStats, StagedSource)

# Supervised actor restarts back off exponentially per slot: base * 2^k,
# capped — a crash-looping actor binary must not busy-spin the spawner.
_RESTART_BACKOFF_BASE_S = 0.25
_RESTART_BACKOFF_CAP_S = 5.0


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Runtime geometry: thread counts, queue depths, stop conditions."""

    actor_threads: int = 1           # each runs cfg.lanes_per_shard lanes
                                     # (0 allowed when actor_procs > 0)
    actor_procs: int = 0             # remote actor *processes* feeding the
                                     # fabric through a ReplayGateway socket
    replay_shards: int = 1           # ReplayShard owner threads in the fabric
    inference_batching: bool = False # one vmapped act dispatch for all actors
    inference_mode: str = "wave"     # scheduling inside the shared engine:
                                     # "wave" coalesces up to coalesce_s and
                                     # pads short waves; "slots" admits
                                     # pending requests into free slots the
                                     # moment the previous dispatch returns
                                     # (continuous batching — no window tax,
                                     # params hot-swap at dispatch bounds)
    serve_policy: str | None = None  # "host:port": ALSO serve the shared
                                     # engine over the transport plane (a
                                     # second, policy-only gateway speaking
                                     # ACT_REQUEST/ACT_RESULT); actor procs
                                     # then run as thin clients that ship
                                     # their slice per rollout instead of
                                     # pulling params (requires
                                     # inference_batching)
    learn_batches_per_step: int = 1  # prefetched batches consumed per jitted
                                     # learner call (lax.scan — amortizes
                                     # dispatch for small batches; the run
                                     # stops at the first multiple >=
                                     # total_learner_steps)
    gateway_port: int = 0            # ReplayGateway TCP port (0: ephemeral)
    gateway_host: str = "127.0.0.1"  # ReplayGateway bind address; the
                                     # loopback default only reaches same-
                                     # machine peers — bind "0.0.0.0" to
                                     # serve actors/learners on other hosts
    ingest_max_inflight: int = 4     # un-acked blocks per remote actor (the
                                     # socket analogue of add_queue_depth)
    transport: str = "auto"          # byte path for every remote hop (actor
                                     # procs and learner_remote): "tcp",
                                     # "shm" (same-host shared-memory ring,
                                     # strict), or "auto" (shm when the peer
                                     # is loopback-local, else tcp)
    transport_ring_bytes: int = 0    # shm ring arena size per direction
                                     # (0: repro.net default)
    wire_quantize_obs: bool = False  # remote actors ship obs via the replay
                                     # codec (uint8 + affine, ~4x less wire)
    wire_quantize_prios: bool = False  # remote learner quantizes priority
                                     # write-backs (lossy, uint8 + affine)
    wire_quantize_params: bool = False  # remote learner quantizes PARAM_PUSH
                                     # snapshots (lossy; actors then act on
                                     # quantized params)
    sample_staging: bool = False     # wrap the learner's SampleSource in a
                                     # StagedSource: a stager thread device-
                                     # puts batch k+1 (pinned-host staging +
                                     # async DMA on TPU) while the learner
                                     # computes on batch k
    ingest_staging: bool = False     # the add-side mirror: shard owners
                                     # issue block k+1's async device_put
                                     # (BlockStager) before dispatching
                                     # block k's in-place add, hiding H2D
                                     # behind the update kernel (pass-
                                     # through on CPU hosts; bit-identical
                                     # everywhere)
    learner_remote: str | None = None  # "host:port" of a serving gateway:
                                     # run ONLY the learner here, sampling a
                                     # remote fabric (requires
                                     # actor_threads=0, actor_procs=0,
                                     # replay_shards=1 — the fabric lives on
                                     # the serving host)
    serve_sampling: bool = False     # run actors + fabric + gateway and NO
                                     # local learner; a remote learner
                                     # drives the run through the gateway's
                                     # sample plane (stops after
                                     # total_learner_steps observed
                                     # PRIORITY_UPDATEs)
    add_queue_depth: int = 4         # actor→replay backpressure bound (per shard)
    sample_queue_depth: int = 2      # replay→learner prefetch (double buffer)
    total_learner_steps: int = 200   # stop once the learner consumed this many
    max_seconds: float | None = None # wall-clock safety stop
    publish_every: int = 1           # learner steps between param publications
    starve_timeout_s: float = 0.02   # learner wait per fabric.get_batch poll
    add_poll_s: float = 0.02         # actor wait per fabric.add poll (these
                                     # two replace the hardcoded add/get_batch
                                     # poll intervals; direct ReplayShard /
                                     # ReplayFabric users tune `poll_s` at
                                     # construction instead)
    coalesce_s: float = 0.002        # inference-server wave-forming window
    progress_every_s: float | None = None  # log a fabric-snapshot line every
                                     # so many seconds (None: no progress log)
    metrics_dir: str | None = None   # telemetry plane: write metrics.jsonl /
                                     # spans.jsonl snapshots here (None: keep
                                     # the registry in-process only). Render
                                     # with `python -m repro.obs.report DIR`.
    trace_sample_rate: float = 0.0   # fraction of transition blocks / learner
                                     # batches that carry a pipeline trace id
                                     # (0: tracing off; 1: every block). Traced
                                     # ops force a device sync for honest
                                     # stage durations — keep this small on
                                     # hot runs.
    checkpoint_dir: str | None = None  # snapshot service: periodically save
                                     # fabric + learner + ParamStore version
                                     # as atomic ckpt_<step>.npz files here
                                     # (None: no periodic checkpoints).
                                     # Requires the fabric AND learner to be
                                     # local (not learner_remote/serve_
                                     # sampling).
    checkpoint_every_s: float = 30.0 # seconds between periodic snapshots
    resume: bool = False             # cold-start from checkpoint.latest() in
                                     # checkpoint_dir: replay contents, sum
                                     # trees, eviction clocks, learner slice
                                     # and param version all continue where
                                     # the snapshot left them (an empty
                                     # directory is a normal cold start)
    supervise_actors: bool = True    # respawn dead actor processes with
                                     # capped exponential backoff (actors are
                                     # pure functions of (seed, actor_id) +
                                     # params, so a respawn rebuilds the same
                                     # ladder slot); False: deaths are only
                                     # detected/logged
    actor_restart_limit: int = 5     # supervised respawns per actor slot
                                     # before the slot is declared dead
    reconnect_timeout_s: float = 20.0  # how long remote actors / the remote
                                     # learner source retry (with backoff)
                                     # after a severed transport before
                                     # giving up
    seed: int = 0


@dataclasses.dataclass
class RuntimeResult:
    learner: phases.LearnerSlice     # final params/target/opt state
    stats: dict[str, float]          # throughput + contention counters
    service_stats: ServiceStats      # fabric aggregate (summed over shards)
    shard_stats: list[ServiceStats]  # per-shard counters
    last_actor_metrics: dict | None  # last act_phase metrics (any actor)
    inference_stats: InferenceStats | None = None  # when inference_batching
    gateway_stats: Any = None        # net.GatewayStats when a gateway ran
    policy_stats: Any = None         # net.GatewayStats of the policy-plane
                                     # gateway (serve_policy)
    source_stats: SourceStats | None = None  # learner-plane SampleSource
                                     # counters (None in serve mode)


@dataclasses.dataclass
class RuntimeHandles:
    """Live internals of a running ``run_async``, handed to its
    ``on_handles`` callback once every plane has started. This is the
    surface the fault-injection harness (``repro.testing.chaos``) reaches
    through to kill processes, sever transports, and freeze shard owners —
    deliberately raw, not a stable public API. ``telemetry`` is the run's
    one :class:`~repro.obs.Telemetry`: its registry holds every plane's
    counters and histograms by name (``actor/rollouts``,
    ``learner/batch_wait_us``, ``shard0/sync_wait_us``, ...), live."""

    stop: threading.Event            # the run's stop event
    fabric: Any                      # ReplayFabric | None (learner_remote)
    gateway: Any                     # net.ReplayGateway | None
    source: Any                      # learner SampleSource | None (serve)
    store: Any                       # ParamStore
    procs: list                      # live actor processes (slot-indexed;
                                     # the supervisor swaps entries in place)
    procs_lock: Any                  # guards ``procs`` slot swaps
    snapshots: Any                   # SnapshotService | None
    learner_box: dict                # {"steps", "lslice", "live"}
    telemetry: Any                   # the run's Telemetry (registry, tracer)


def _actor_geometry(cfg, acfg: AsyncConfig):
    """Each actor (thread t in [0, actor_threads), process j at
    actor_threads + j) takes one ladder shard: actor a plays global lanes
    [a*lanes, (a+1)*lanes), so one exploration ladder spans threads and
    remote processes alike. A remote-learner process runs zero actors; its
    ladder width is pinned to 1 (the acting geometry lives on the serving
    host)."""
    return dataclasses.replace(
        cfg, num_shards=max(acfg.actor_threads + acfg.actor_procs, 1))


def make_runner_fns(cfg, env, agent, optimizer):
    """The runner's three programs — the actor's rollout, one learner step,
    and ``k`` learner steps in one call — each a named function, so its
    device ops carry a stable module name (``jit_actor_rollout``,
    ``jit_learner_step``, ``jit_learner_step_many``) in a profile."""

    def actor_rollout(params, aslice, shard_id):
        return phases.act_phase(cfg, env, agent, params, aslice, shard_id)

    def learner_step(lslice, items, weights):
        return phases.learn_phase(cfg, agent, optimizer, lslice, items,
                                  weights, None)

    def learner_step_many(lslice, items_k, weights_k):
        # One jitted call consumes k double-buffered batches via lax.scan,
        # amortizing dispatch overhead when per-batch compute is small.
        def body(lsl, xw):
            lsl, prios, _ = phases.learn_phase(cfg, agent, optimizer, lsl,
                                               xw[0], xw[1], None)
            return lsl, prios
        return jax.lax.scan(body, lslice, (items_k, weights_k))

    return (jax.jit(actor_rollout), jax.jit(learner_step),
            jax.jit(learner_step_many))


def run_async(cfg, acfg: AsyncConfig, env, agent, optimizer,
              rng: jax.Array | None = None,
              on_handles: Any = None) -> RuntimeResult:
    """Run the decoupled runtime until the learner consumed
    ``total_learner_steps`` batches (or ``max_seconds`` elapsed). With
    ``learn_batches_per_step = k > 1`` the learner consumes in chunks of k
    and stops at the first multiple of k >= ``total_learner_steps``.

    ``rng`` seeds parameter init only; actor slices always derive from
    ``AsyncConfig.seed`` via ``phases.initial_actor_slice`` so that remote
    actor processes can reproduce their slice from ``(seed, actor_id)``
    alone.

    ``on_handles``, if given, is called once with a :class:`RuntimeHandles`
    after every plane has started — the fault-injection hook."""
    remote = acfg.learner_remote is not None
    serving = acfg.serve_sampling
    if remote and serving:
        raise ValueError(
            "AsyncConfig.learner_remote and serve_sampling are the two "
            "sides of one topology: a process either samples a remote "
            "fabric or serves its own — not both")
    if acfg.actor_procs < 0:
        raise ValueError("AsyncConfig.actor_procs must be >= 0, got "
                         f"{acfg.actor_procs}")
    if acfg.transport not in ("tcp", "shm", "auto"):
        raise ValueError("AsyncConfig.transport must be 'tcp', 'shm', or "
                         f"'auto', got {acfg.transport!r}")
    if (acfg.wire_quantize_prios or acfg.wire_quantize_params) and not remote:
        raise ValueError(
            "wire_quantize_prios/wire_quantize_params configure the remote "
            "learner's upstream frames and require learner_remote")
    if remote and (acfg.actor_threads or acfg.actor_procs
                   or acfg.inference_batching or acfg.replay_shards != 1
                   or acfg.ingest_staging):
        raise ValueError(
            "AsyncConfig.learner_remote runs a learner-only process: the "
            "actors, replay shards, and inference server live on the "
            "serving host — set actor_threads=0, actor_procs=0, "
            "replay_shards=1, inference_batching=False, "
            "ingest_staging=False (got "
            f"threads={acfg.actor_threads}, procs={acfg.actor_procs}, "
            f"shards={acfg.replay_shards}, "
            f"inference_batching={acfg.inference_batching}, "
            f"ingest_staging={acfg.ingest_staging})")
    if serving and (acfg.sample_staging or acfg.learn_batches_per_step != 1):
        raise ValueError(
            "serve_sampling runs no local learner: sample_staging and "
            "learn_batches_per_step configure the learner's consume path "
            "and belong on the learner_remote host (got "
            f"sample_staging={acfg.sample_staging}, "
            f"learn_batches_per_step={acfg.learn_batches_per_step})")
    if not remote and acfg.actor_threads < (0 if acfg.actor_procs else 1):
        raise ValueError(
            "AsyncConfig needs at least one actor: actor_threads >= 1, or "
            "actor_threads >= 0 with actor_procs >= 1 (got "
            f"threads={acfg.actor_threads}, procs={acfg.actor_procs})")
    if acfg.total_learner_steps < 1:
        raise ValueError("AsyncConfig.total_learner_steps must be >= 1, got "
                         f"{acfg.total_learner_steps}")
    if acfg.replay_shards < 1:
        raise ValueError("AsyncConfig.replay_shards must be >= 1, got "
                         f"{acfg.replay_shards}")
    if acfg.learn_batches_per_step < 1:
        raise ValueError("AsyncConfig.learn_batches_per_step must be >= 1, "
                         f"got {acfg.learn_batches_per_step}")
    if acfg.add_queue_depth < 1 or acfg.sample_queue_depth < 1:
        raise ValueError(
            "AsyncConfig.add_queue_depth and sample_queue_depth must be "
            ">= 1: the runtime relies on bounded queues for actor "
            "backpressure and learner double buffering (got "
            f"add={acfg.add_queue_depth}, sample={acfg.sample_queue_depth})")
    if acfg.inference_mode not in ("wave", "slots"):
        raise ValueError("AsyncConfig.inference_mode must be 'wave' or "
                         f"'slots', got {acfg.inference_mode!r}")
    if acfg.serve_policy is not None and not acfg.inference_batching:
        raise ValueError(
            "AsyncConfig.serve_policy serves the shared inference engine "
            "over the transport plane — it requires inference_batching")
    if (acfg.inference_batching and acfg.actor_threads < 1
            and acfg.serve_policy is None):
        raise ValueError("inference_batching needs in-process actor threads "
                         "(or serve_policy, which feeds the engine from "
                         "remote clients)")
    if not 0.0 <= acfg.trace_sample_rate <= 1.0:
        raise ValueError(
            "AsyncConfig.trace_sample_rate is a sampling fraction in "
            f"[0, 1], got {acfg.trace_sample_rate}")
    if acfg.resume and not acfg.checkpoint_dir:
        raise ValueError(
            "AsyncConfig.resume needs checkpoint_dir: resuming means "
            "loading checkpoint.latest() from somewhere")
    if acfg.checkpoint_dir and (remote or serving):
        raise ValueError(
            "AsyncConfig.checkpoint_dir snapshots the replay fabric AND the "
            "learner slice together, so both must be local — a "
            "learner_remote process has no fabric and a serve_sampling "
            "process has no learner. Run the snapshot service on a "
            "single-process topology (got "
            f"learner_remote={acfg.learner_remote!r}, "
            f"serve_sampling={acfg.serve_sampling})")
    if acfg.checkpoint_dir and acfg.checkpoint_every_s <= 0:
        raise ValueError(
            "AsyncConfig.checkpoint_every_s must be > 0 seconds, got "
            f"{acfg.checkpoint_every_s}")
    if acfg.actor_restart_limit < 0:
        raise ValueError(
            "AsyncConfig.actor_restart_limit must be >= 0, got "
            f"{acfg.actor_restart_limit}")
    cfg = _actor_geometry(cfg, acfg)
    rng = jax.random.key(acfg.seed) if rng is None else rng
    p_rng, _ = jax.random.split(rng)

    # -- state ------------------------------------------------------------
    # With zero actor threads the first slice is still built: it seeds
    # param init and the warm-up rollout (remote actor 0 derives the
    # identical slice from (seed, actor_id=0) on its side).
    slices = [phases.initial_actor_slice(cfg, env, acfg.seed, t)
              for t in range(max(acfg.actor_threads, 1))]
    obs0 = slices[0].obs
    params = agent.init(p_rng, obs0[:1])
    lslice = phases.LearnerSlice(
        params=params, target_params=jax.tree.map(jnp.copy, params),
        opt_state=optimizer.init(params),
        learner_step=jnp.zeros((), jnp.int32))
    item = phases.item_example(env, obs0, cfg.compress_obs)

    # One telemetry bundle for the whole run: every plane (fabric shards,
    # gateway, sample source, inference server, the loops below) records
    # into the same registry/tracer, and one sink thread flushes it.
    tel = Telemetry.for_run(acfg.metrics_dir, acfg.trace_sample_rate)
    fabric = None if remote else ReplayFabric(
        cfg, item, num_shards=acfg.replay_shards,
        add_queue_depth=acfg.add_queue_depth,
        sample_queue_depth=acfg.sample_queue_depth, seed=acfg.seed + 1,
        ingest_staging=acfg.ingest_staging, telemetry=tel)
    # -- resume (Appendix F): cold-start from the newest snapshot ----------
    # The fresh fabric/slice above provide the example structure; restoring
    # swaps their contents for the checkpointed ones before any thread
    # starts, so the first op after resume continues the interrupted run.
    resume_steps = 0
    store_version = 0
    if acfg.resume:
        restored = snapshot_lib.restore_run(acfg.checkpoint_dir, fabric,
                                            lslice)
        if restored is not None:
            fabric.restore_shards(restored["shards"])
            lrn = restored["learner"]
            lslice = phases.LearnerSlice(
                params=jax.tree.map(jnp.asarray, lrn["params"]),
                target_params=jax.tree.map(jnp.asarray,
                                           lrn["target_params"]),
                opt_state=jax.tree.map(jnp.asarray, lrn["opt_state"]),
                learner_step=jnp.asarray(lrn["learner_step"]))
            params = lslice.params
            resume_steps = int(restored["steps"])
            store_version = int(restored["param_version"])
            obslog.emit("resume", path=restored["path"], step=resume_steps,
                        params_v=store_version)
    store = ParamStore(params, version=store_version)
    # With a policy plane, remote actor procs land in the same engine as
    # the in-process threads, so the slot count covers both populations.
    infer_batch = acfg.actor_threads + (
        acfg.actor_procs if acfg.serve_policy is not None else 0)
    server = (InferenceServer(cfg, env, agent, store,
                              max_batch=max(infer_batch, 1),
                              coalesce_s=acfg.coalesce_s,
                              mode=acfg.inference_mode, telemetry=tel)
              if acfg.inference_batching else None)
    policy_gateway = None
    if acfg.serve_policy is not None:
        from repro.net import ReplayGateway
        from repro.net import transport as transport_lib
        from repro.net.learner_client import parse_hostport
        policy_host, policy_port = parse_hostport(acfg.serve_policy,
                                                  allow_ephemeral=True)
        # A second, policy-only gateway (fabric=None): ACT_REQUEST frames
        # from thin clients block in the shared engine and batch with the
        # in-process actors' requests.
        policy_gateway = ReplayGateway(
            None, store, host=policy_host, port=policy_port,
            accept_shm=acfg.transport != "tcp",
            ring_bytes=(acfg.transport_ring_bytes
                        or transport_lib.DEFAULT_RING_BYTES),
            inference=server, act_example=slices[0], telemetry=tel)
    gateway = None
    if acfg.actor_procs > 0 or serving:
        # Deferred import: repro.net sits on top of this module's siblings.
        from repro.net import ReplayGateway
        from repro.net import transport as transport_lib
        gateway = ReplayGateway(
            fabric, store, host=acfg.gateway_host, port=acfg.gateway_port,
            add_timeout_s=acfg.add_poll_s,
            # A tcp-pinned runtime refuses ring upgrades outright; shm/auto
            # let each client negotiate (cross-host peers stay tcp anyway).
            accept_shm=acfg.transport != "tcp",
            ring_bytes=(acfg.transport_ring_bytes
                        or transport_lib.DEFAULT_RING_BYTES),
            telemetry=tel)

    # -- sample plane ------------------------------------------------------
    # The learner consumes a SampleSource and never reaches into fabric
    # internals; every topology is one source construction here.
    source: SampleSource | None = None
    if not serving:
        if remote:
            from repro.net import transport as transport_lib
            from repro.net.learner_client import (RemoteFabricSource,
                                                  parse_hostport)
            host, port = parse_hostport(acfg.learner_remote)
            source = RemoteFabricSource(
                host, port, transport=acfg.transport,
                poll_s=acfg.starve_timeout_s,
                ring_bytes=(acfg.transport_ring_bytes
                            or transport_lib.DEFAULT_RING_BYTES),
                quantize_prios=acfg.wire_quantize_prios,
                quantize_params=acfg.wire_quantize_params,
                telemetry=tel)
        else:
            source = LocalFabricSource(fabric, telemetry=tel)
        if acfg.sample_staging:
            source = StagedSource(source, poll_s=acfg.starve_timeout_s,
                                  telemetry=tel)

    rollout_fn, learn_fn, learn_many_fn = make_runner_fns(cfg, env, agent,
                                                          optimizer)
    act_fn = (rollout_fn if server is None and acfg.actor_threads > 0
              else None)
    learn_k = acfg.learn_batches_per_step

    # Warm the caches before the clock starts: one throwaway rollout (the
    # batched server wave when inference batching is on, the per-actor fn
    # otherwise — only the variant that will actually run) and one throwaway
    # update on storage-shaped garbage (results discarded). The warm rollout
    # also *measures* the block size, so accounting follows whatever
    # act_phase actually emits.
    if server is not None:
        block_transitions = server.warm(slices[0])
    elif act_fn is not None:
        _, block0, _ = jax.block_until_ready(
            act_fn(params, slices[0], jnp.int32(0)))
        block_transitions = int(block0.priorities.shape[0])
    else:
        # No acting on this host (pure actor-procs mode, or a remote-learner
        # process): don't compile a rollout just to measure it — the block
        # size is the formula the error below spells out (remote transitions
        # are counted from actual gateway traffic anyway).
        block_transitions = (cfg.lanes_per_shard * cfg.window
                             * cfg.replicate_k)
    if fabric is not None and block_transitions > fabric.shard_capacity:
        # a block must fit inside one shard or the circular add would alias
        raise ValueError(
            f"transition block ({block_transitions}) larger than per-shard "
            f"replay capacity ({fabric.shard_capacity}): lower "
            f"AsyncConfig.replay_shards (= {acfg.replay_shards}) or shrink "
            f"lanes_per_shard * (rollout_len - n_step + 1) * replicate_k")
    if not serving:
        items_ex, w_ex = phases.learner_batch_example(cfg, item)
        jax.block_until_ready(learn_fn(lslice, items_ex, w_ex))
        if learn_k > 1:
            items_k_ex = jax.tree.map(
                lambda a: jnp.zeros((learn_k,) + a.shape, a.dtype), items_ex)
            jax.block_until_ready(learn_many_fn(
                lslice, items_k_ex,
                jnp.ones((learn_k, cfg.batch_size), jnp.float32)))
    stop = threading.Event()
    # The run's counters live in the registry, so they move while the run
    # does (RuntimeHandles.telemetry) and the sink exports them.
    c_rollouts = tel.counter("actor/rollouts")
    c_transitions = tel.counter("actor/transitions")
    c_add_blocked = tel.counter("actor/add_blocked")
    c_steps = tel.counter("learner/steps")
    c_batch_wait = tel.counter("learner/batch_wait_us")
    c_starved = tel.counter("learner/starved")
    tracer = tel.tracer
    last_metrics: list[Any] = [None]
    thread_errors: list[BaseException] = []

    def guarded(fn):
        """A dead worker must stop the whole runtime, not hang or silently
        yield an untrained result: record the error and wake everyone."""
        def run(*args):
            try:
                fn(*args)
            except BaseException as e:  # noqa: BLE001
                thread_errors.append(e)
                stop.set()
        return run

    # -- actor threads ----------------------------------------------------
    def actor_loop(t: int) -> None:
        sl = slices[t]
        sid = jnp.int32(t)
        snap = store.get()
        rollouts = 0
        while not stop.is_set():
            # A traced rollout opens the block's pipeline trace: the same id
            # rides the fabric add (and, for remote actors, the wire header)
            # so the report can line stages up per block.
            tid = tracer.sample()
            if server is None and rollouts % cfg.param_sync_period == 0:
                with tracer.span("actor/param_refresh", tid, actor=t):
                    snap = store.get()
            with tracer.span("actor/rollout", tid, actor=t):
                if server is not None:
                    # Batched inference: param refresh happens server-side.
                    res = server.act(sl, t)
                    if res is None:  # server (or runtime) stopping
                        break
                    sl, block, metrics = res
                else:
                    sl, block, metrics = act_fn(snap.params, sl, sid)
                if tid:
                    jax.block_until_ready(block)  # honest rollout duration
            with tracer.span("actor/add", tid, actor=t):
                while not stop.is_set():
                    if fabric.add(block, timeout=acfg.add_poll_s,
                                  trace_id=tid):
                        c_transitions.inc(block_transitions)
                        break
                    # bounded queue full: actor is backpressured
                    c_add_blocked.inc()
            rollouts += 1
            c_rollouts.inc()
            last_metrics[0] = metrics

    # -- learner thread ---------------------------------------------------
    # "live" is the snapshot service's view: one atomic (steps, lslice)
    # rebind per learner step, so a periodic checkpoint never captures a
    # torn step-count/params pair.
    learner_box = {"lslice": lslice, "steps": resume_steps,
                   "live": (resume_steps, lslice)}

    def learner_loop() -> None:
        lsl = learner_box["lslice"]
        steps = resume_steps
        pending: list = []  # gathered batches for one k-sized jitted call
        while steps < acfg.total_learner_steps and not stop.is_set():
            t_wait = time.perf_counter()
            with tracer.span("learner/get_batch") as sp:
                batch = source.get_batch(timeout=acfg.starve_timeout_s)
                # The source stamped this batch's consume-plane trace id
                # when it drew it; the learn span and the priority
                # write-back inherit it (k > 1 chunks stay untraced — one
                # jitted call spans k batches, so a per-batch duration
                # would be a lie).
                tid = (source.last_trace_id
                       if batch is not None and learn_k == 1 else 0)
                sp.trace_id = tid
            c_batch_wait.inc(int(1e6 * (time.perf_counter() - t_wait)))
            if batch is None:
                c_starved.inc()  # replay below min-fill or prefetch lagging
                continue
            if learn_k == 1:
                with tracer.span("learner/step", tid, step=steps):
                    lsl, new_prios, _ = learn_fn(lsl, batch.items,
                                                 batch.is_weights)
                    if tid:
                        # honest learn duration
                        jax.block_until_ready(new_prios)
                with tracer.span("learner/write_back", tid):
                    source.write_back(batch.indices, new_prios, trace_id=tid)
                steps += 1
                c_steps.inc()
            else:
                pending.append(batch)
                if len(pending) < learn_k:
                    continue
                items_k = jax.tree.map(lambda *xs: jnp.stack(xs),
                                       *[b.items for b in pending])
                w_k = jnp.stack([b.is_weights for b in pending])
                with tracer.span("learner/step"):
                    lsl, prios_k = learn_many_fn(lsl, items_k, w_k)
                # One write-back per consumed batch: each application ticks
                # the shard's eviction clock once, so k-batching leaves the
                # paper's evict-every-N-steps pacing unchanged.
                with tracer.span("learner/write_back"):
                    for i, b in enumerate(pending):
                        source.write_back(b.indices, prios_k[i])
                pending = []
                steps += learn_k
                c_steps.inc(learn_k)
            learner_box["live"] = (steps, lsl)
            if steps % acfg.publish_every < learn_k:
                with tracer.span("learner/publish", tid):
                    version = store.publish(lsl.params)
                    # Remote transports also ship the snapshot upstream, so
                    # the actors feeding the (remote) fabric keep pulling
                    # learning-current params; local sources no-op.
                    source.publish_params(version, lsl.params)
        jax.block_until_ready(lsl.params)
        learner_box["lslice"] = lsl
        learner_box["steps"] = steps

    def serve_loop() -> None:
        """Serve-sampling mode: no local learner. The learner clock is the
        remote learner's PRIORITY_UPDATE stream observed at the gateway;
        the run ends when it reaches ``total_learner_steps`` (or
        ``max_seconds``/a worker death stops it first).

        A learner-marked BYE also ends the run: the remote learner's own
        step clock is authoritative, and a severed-then-reconnected
        transport can swallow priority frames that were in flight when
        the socket died (bounded loss the replay tolerates — priorities
        are idempotent LWW hints), so the observed count may stall just
        short of the target a frame or two forever."""
        while not stop.wait(timeout=0.1):
            snap = gateway.snapshot()
            if snap.priority_updates >= acfg.total_learner_steps:
                break
            if snap.learner_byes > 0 and snap.priority_updates > 0:
                break
        learner_box["steps"] = gateway.snapshot().priority_updates

    # -- actor-process supervision ----------------------------------------
    # In-process workers propagate death through guarded()/_check_alive;
    # the socket path needs its own watchdog. The supervisor tracks every
    # actor-process *slot* independently of local threads (a dead proc is
    # detected even when actor_threads > 0) and — because actors are pure
    # functions of (seed, actor_id) + the latest params — respawns dead
    # processes with capped exponential backoff, the paper's
    # restartable-actor model. A dead gateway, or every experience source
    # permanently gone, still stops the runtime instead of letting the
    # learner starve forever.
    procs: list = []
    proc_specs: list = []
    procs_lock = threading.Lock()
    spawn_actor: Any = None  # bound below once the spawn ctx exists
    c_restarts = tel.counter("supervisor/actor_restarts")
    c_proc_exits = tel.counter("supervisor/actor_proc_exits")

    def supervisor() -> None:
        n = len(procs)
        restarts = [0] * n          # respawns burned per slot
        retry_at = [0.0] * n        # scheduled respawn time (0 = none)
        dead = [False] * n          # slot exhausted / unsupervised death
        while not stop.wait(timeout=0.25):
            if gateway.error is not None:
                thread_errors.append(gateway.error)
                stop.set()
                return
            now = time.monotonic()
            for j in range(n):
                with procs_lock:
                    p = procs[j]
                if p.is_alive() or dead[j]:
                    continue
                if retry_at[j] == 0.0:
                    # First observation of this death.
                    c_proc_exits.inc()
                    if (not acfg.supervise_actors
                            or restarts[j] >= acfg.actor_restart_limit):
                        dead[j] = True
                        obslog.emit("actor-proc-down", slot=j,
                                    exitcode=p.exitcode,
                                    restarts=restarts[j],
                                    supervised=acfg.supervise_actors)
                        continue
                    backoff = min(
                        _RESTART_BACKOFF_BASE_S * (2 ** restarts[j]),
                        _RESTART_BACKOFF_CAP_S)
                    retry_at[j] = now + backoff
                    obslog.emit("actor-proc-exited", slot=j,
                                exitcode=p.exitcode,
                                retry_in_s=round(backoff, 2))
                    continue
                if now < retry_at[j]:
                    continue
                restarts[j] += 1
                retry_at[j] = 0.0
                with procs_lock:
                    procs[j] = spawn_actor(j)
                c_restarts.inc()
                obslog.emit("actor-restart", slot=j, attempt=restarts[j])
            if acfg.actor_threads == 0 and n and all(dead):
                thread_errors.append(RuntimeError(
                    "every remote actor process exited"
                    + (" and exhausted its restart budget"
                       if acfg.supervise_actors else "")
                    + " before the learner finished; no experience source "
                      "remains"))
                stop.set()
                return

    # -- progress logging (satellite of the fabric: observable while hot) --
    def progress_loop() -> None:
        t_start = time.perf_counter()
        while not stop.wait(timeout=acfg.progress_every_s):
            snap = (fabric.snapshot() if fabric is not None
                    else source.snapshot())
            dt = time.perf_counter() - t_start
            obslog.emit(
                "async", t=round(dt, 1),
                generated=snap.transitions_added,
                sampled_batches=snap.batches_sampled,
                writebacks=snap.updates_applied,
                replay_size=snap.replay_size,
                add_us=round(snap.add_us), sample_us=round(snap.sample_us),
                writeback_us=round(snap.writeback_us),
                h2d_us=round(snap.h2d_us),
                params_v=store.version)

    # -- drive ------------------------------------------------------------
    tel.start()  # sink flush thread (no-op without metrics_dir)
    if fabric is not None:
        fabric.start()
    if server is not None:
        server.start()
    if policy_gateway is not None:
        policy_gateway.start()
        obslog.emit("serve-policy", listening=True,
                    host=policy_gateway.host, port=policy_gateway.port)
    if gateway is not None:
        from repro.net import RemoteActorSpec
        from repro.net.actor_client import run_remote_actor
        gateway.start()
        if serving:
            # The learner host needs this address to attach; ephemeral
            # ports are only discoverable here.
            obslog.emit("serve-sampling", listening=True,
                        host=gateway.host, port=gateway.port)
        ctx = multiprocessing.get_context("spawn")  # never fork a jax parent
        # A wildcard bind serves remote peers; local subprocesses dial
        # loopback rather than the unroutable 0.0.0.0.
        dial_host = ("127.0.0.1" if gateway.host in ("0.0.0.0", "::")
                     else gateway.host)
        policy_dial = None
        if policy_gateway is not None:
            ph = ("127.0.0.1" if policy_gateway.host in ("0.0.0.0", "::")
                  else policy_gateway.host)
            policy_dial = f"{ph}:{policy_gateway.port}"
        for j in range(acfg.actor_procs):
            proc_specs.append(RemoteActorSpec(
                cfg=cfg, env=env, agent=agent,
                host=dial_host, port=gateway.port, policy=policy_dial,
                actor_id=acfg.actor_threads + j, seed=acfg.seed,
                max_inflight=acfg.ingest_max_inflight,
                quantize_obs=acfg.wire_quantize_obs,
                transport=acfg.transport,
                trace_sample_rate=acfg.trace_sample_rate,
                reconnect_timeout_s=acfg.reconnect_timeout_s,
                **({"ring_bytes": acfg.transport_ring_bytes}
                   if acfg.transport_ring_bytes else {})))

        def spawn_actor(j: int):
            p = ctx.Process(target=run_remote_actor, args=(proc_specs[j],),
                            daemon=True, name=f"actor-proc-{j}")
            p.start()
            return p

        for j in range(acfg.actor_procs):
            procs.append(spawn_actor(j))
        threading.Thread(target=supervisor, daemon=True,
                         name="actor-supervisor").start()
    if source is not None:
        # Connect/spin up the sample plane before the clock starts (the
        # remote transport retries while the serving host finishes binding).
        source.start()
    snapshots = None
    if acfg.checkpoint_dir:
        snapshots = snapshot_lib.SnapshotService(
            acfg.checkpoint_dir, fabric, learner_box, store,
            every_s=acfg.checkpoint_every_s, telemetry=tel).start()
    actors = [threading.Thread(target=guarded(actor_loop), args=(t,),
                               daemon=True, name=f"actor-{t}")
              for t in range(acfg.actor_threads)]
    learner = threading.Thread(
        target=guarded(serve_loop if serving else learner_loop),
        daemon=True, name="serve-wait" if serving else "learner")
    progress = (threading.Thread(target=progress_loop, daemon=True,
                                 name="progress")
                if acfg.progress_every_s else None)
    t0 = time.perf_counter()
    for th in actors:
        th.start()
    learner.start()
    if progress is not None:
        progress.start()
    if on_handles is not None:
        on_handles(RuntimeHandles(
            stop=stop, fabric=fabric, gateway=gateway, source=source,
            store=store, procs=procs, procs_lock=procs_lock,
            snapshots=snapshots, learner_box=learner_box, telemetry=tel))
    learner.join(timeout=acfg.max_seconds)
    stop.set()
    if server is not None:
        server.stop(join=False)  # unblock actors parked on act() first
    for th in actors:
        th.join()
    learner.join()
    if progress is not None:
        progress.join()
    dt = time.perf_counter() - t0
    pg_snap = None
    if policy_gateway is not None:
        # Before the ingest gateway joins the actor processes: a thin
        # client parked in an ACT round trip must see its STOP (the engine
        # is already stopping, so pending requests answer STOP immediately).
        policy_gateway.stop()
        if policy_gateway.error is not None:
            thread_errors.append(policy_gateway.error)
        pg_snap = policy_gateway.snapshot()
    if server is not None:
        server.stop()
        if server.error is not None:
            thread_errors.append(server.error)
    gw_snap = None
    if gateway is not None:
        # STOP goes out to every actor process; the drain grace lets their
        # in-flight blocks land and their BYE counters merge, then the
        # processes exit on their own. Stubborn ones are terminated.
        gateway.stop()
        with procs_lock:
            final_procs = list(procs)
        for p in final_procs:
            p.join(timeout=30.0)
        for p in final_procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=5.0)
            elif p.exitcode not in (0, None):
                if acfg.supervise_actors:
                    # A supervised run already absorbed (and possibly
                    # replaced) crashing actors mid-run; a crash in the
                    # shutdown window is the same tolerated event, not a
                    # run failure.
                    obslog.emit("actor-proc-down", slot=p.name,
                                exitcode=p.exitcode, at="shutdown")
                else:
                    thread_errors.append(RuntimeError(
                        f"actor process {p.name} exited with {p.exitcode}"))
        if gateway.error is not None:
            thread_errors.append(gateway.error)
        gw_snap = gateway.snapshot()
        # Includes blocks that landed during the shutdown drain grace: they
        # were generated inside the measured window and were sitting in the
        # bounded in-flight window — the remote analogue of in-process
        # blocks parked in shard add queues at stop, which the thread
        # counters include the same way.
        c_transitions.inc(gw_snap.transitions_in)
        c_add_blocked.inc(gw_snap.add_retries + gw_snap.client_blocked)
        c_rollouts.inc(gw_snap.blocks_in)
    if source is not None:
        # Stop the sample plane before the fabric: a staged source's stager
        # thread is still pulling prefetched batches, and the remote client
        # wants to BYE before its socket dies under it.
        source.stop()
        if source.error is not None:
            thread_errors.append(source.error)
    if fabric is not None:
        fabric.stop()
        if fabric.error is not None:
            # A shard may die after the learner's last call (e.g. during the
            # final drain) — no later add/get_batch would surface it.
            thread_errors.append(fabric.error)
    if snapshots is not None:
        # After fabric.stop(): the shards have drained their queues, so the
        # final snapshot is the complete end-of-run state — a clean
        # shutdown resumes from its very end. Skip it when the run is
        # already failing (a dead shard cannot be captured).
        snapshots.stop(final_save=not thread_errors)
        if snapshots.error is not None:
            thread_errors.append(snapshots.error)
    # Final flush *after* every plane stopped, so the last metrics snapshot
    # and the tail of the span buffer land in the JSONL (even on failure —
    # a run that died is exactly the one worth reading the report of).
    tel.stop()
    if thread_errors:
        raise RuntimeError(
            f"async runtime worker died after {dt:.1f}s") from thread_errors[0]

    steps = learner_box["steps"]
    shard_stats = fabric.shard_snapshots() if fabric is not None else []
    agg = fabric.snapshot() if fabric is not None else source.snapshot()
    stats = {
        "seconds": dt,
        "actor_transitions": float(c_transitions.value),
        "learner_transitions": float(steps * cfg.batch_size),
        "actor_tps": c_transitions.value / dt if dt > 0 else 0.0,
        "learner_tps": steps * cfg.batch_size / dt if dt > 0 else 0.0,
        "rollouts": float(c_rollouts.value),
        "learner_steps": float(steps),
        "actor_blocked": float(c_add_blocked.value),
        "learner_starved": float(c_starved.value),
        "param_version": float(store.version),
        "replay_size": float(agg.replay_size),
        "replay_shards": float(acfg.replay_shards),
        "actor_procs": float(acfg.actor_procs),
        "actor_restarts": float(c_restarts.value),
        "actor_proc_exits": float(c_proc_exits.value),
        "resumed_from_step": float(resume_steps),
    }
    if snapshots is not None:
        stats["snapshots"] = float(snapshots.saves)
    if source is not None:
        stats["source_reconnects"] = float(source.reconnect_count)
    if gw_snap is not None:
        stats["gateway_transitions"] = float(gw_snap.transitions_in)
        stats["gateway_param_sends"] = float(gw_snap.param_sends)
        stats["gateway_bytes_in"] = float(gw_snap.bytes_in)
    if pg_snap is not None:
        stats["policy_acts"] = float(pg_snap.act_requests)
        stats["policy_bytes_out"] = float(pg_snap.bytes_out)
    stats["generate_consume_ratio"] = (
        stats["actor_tps"] / stats["learner_tps"]
        if stats["learner_tps"] > 0 else float("inf"))
    m = last_metrics[0]
    return RuntimeResult(
        learner=learner_box["lslice"], stats=stats,
        service_stats=agg, shard_stats=shard_stats,
        last_actor_metrics=(
            {k: float(v) for k, v in m.items()} if m is not None else None),
        inference_stats=server.snapshot() if server is not None else None,
        gateway_stats=gw_snap, policy_stats=pg_snap,
        source_stats=source.stats if source is not None else None)
