"""Training launcher.

Two modes, matching the two integrations of the paper's technique:

* ``--mode apex-dqn`` / ``--mode apex-dpg`` — the paper's own agents on the
  pure-JAX envs (reduced presets run on CPU; full presets target the mesh).
* ``--mode llm --arch <id>`` — prioritized *sequence* replay training of an
  assigned architecture on the synthetic pipeline (reduced config on CPU).

For the apex modes ``--runtime async`` swaps the lockstep driver for the
decoupled actor/learner runtime (``repro.runtime``): ``--iterations`` then
counts learner steps and generate/consume transitions-per-second are
reported separately (paper §4.1).

Examples:
  PYTHONPATH=src python -m repro.launch.train --mode apex-dqn --iterations 200
  PYTHONPATH=src python -m repro.launch.train --mode apex-dqn \
      --runtime async --actor-threads 2 --iterations 200
  PYTHONPATH=src python -m repro.launch.train --mode apex-dqn \
      --runtime async --actor-threads 0 --actor-procs 2 --iterations 200
  PYTHONPATH=src python -m repro.launch.train --mode llm --arch llama3.2-1b \
      --iterations 50 --ckpt-dir /tmp/ckpts
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpoint as ckpt_lib
from repro.core import apex, replay as replay_lib, sequence_replay as seqrep
from repro.data import pipeline as data_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry, transformer
from repro.obs import log as obslog
from repro.optim import optimizers as optim
from repro.runtime import AsyncConfig, run_async


def run_apex(preset, iterations: int, log_every: int, ckpt_dir: str | None):
    """Lockstep driver; returns the final state and the last iteration's
    metrics."""
    optimizer = preset.make_optimizer()
    init_fn, step_fn = apex.make_train_fn(
        preset.apex, preset.env, preset.agent, optimizer)
    state = init_fn(jax.random.key(0))
    t0 = time.time()
    for it in range(iterations):
        state, metrics = step_fn(state)
        if (it + 1) % log_every == 0:
            m = {k: float(v) for k, v in metrics.items()}
            fps = float(state.frames) / (time.time() - t0)
            obslog.emit(
                "iter", n=it + 1, frames=int(m["frames"]),
                size=int(m["replay_size"]), fps=round(fps),
                ret=f"{m.get('mean_ep_return', float('nan')):.3f}",
                loss=f"{m.get('loss', m.get('critic_loss', 0)):.4f}")
        if ckpt_dir and (it + 1) % (log_every * 10) == 0:
            ckpt_lib.save(f"{ckpt_dir}/ckpt_{it+1}.npz",
                          {"params": state.params,
                           "opt_state": state.opt_state,
                           "learner_step": state.learner_step}, step=it + 1)
    return state, metrics


def run_apex_async(preset, learner_steps: int, actor_threads: int,
                   ckpt_dir: str | None, replay_shards: int = 1,
                   inference_batching: bool = False, actor_procs: int = 0,
                   learn_batches: int = 1, wire_quantize_obs: bool = False,
                   sample_staging: bool = False,
                   learner_remote: str | None = None,
                   serve_sampling: bool = False, gateway_port: int = 0,
                   gateway_host: str = "127.0.0.1", transport: str = "auto",
                   wire_quantize_prios: bool = False,
                   wire_quantize_params: bool = False,
                   ingest_staging: bool = False,
                   add_queue_depth: int = 4, sample_queue_depth: int = 2,
                   metrics_dir: str | None = None,
                   trace_sample_rate: float = 0.0,
                   checkpoint_dir: str | None = None,
                   checkpoint_every_s: float = 30.0,
                   resume: bool = False,
                   inference_mode: str = "wave",
                   serve_policy: str | None = None):
    """Decoupled runtime: actors, replay fabric shards, and learner on their
    own clocks; reports generate/consume transitions-per-second separately.
    ``actor_procs`` actors run as separate OS processes streaming blocks
    through the replay gateway (single-machine proof of the multi-host
    path); ``learn_batches`` batches are consumed per jitted learner call.
    ``learner_remote`` turns this process into a pure learner sampling a
    remote fabric; ``serve_sampling`` turns it into the serving side
    (actors + fabric + gateway, no local learner); ``sample_staging``
    double-buffers the learner's sample path through async device puts and
    ``ingest_staging`` mirrors it on the add side (shard owners overlap
    block k+1's H2D with block k's in-place update)."""
    acfg = AsyncConfig(actor_threads=actor_threads,
                       actor_procs=actor_procs,
                       replay_shards=replay_shards,
                       inference_batching=inference_batching,
                       learn_batches_per_step=learn_batches,
                       wire_quantize_obs=wire_quantize_obs,
                       sample_staging=sample_staging,
                       learner_remote=learner_remote,
                       serve_sampling=serve_sampling,
                       gateway_port=gateway_port,
                       gateway_host=gateway_host,
                       transport=transport,
                       wire_quantize_prios=wire_quantize_prios,
                       wire_quantize_params=wire_quantize_params,
                       ingest_staging=ingest_staging,
                       add_queue_depth=add_queue_depth,
                       sample_queue_depth=sample_queue_depth,
                       metrics_dir=metrics_dir,
                       trace_sample_rate=trace_sample_rate,
                       checkpoint_dir=checkpoint_dir,
                       checkpoint_every_s=checkpoint_every_s,
                       resume=resume,
                       inference_mode=inference_mode,
                       serve_policy=serve_policy,
                       total_learner_steps=learner_steps)
    t0 = time.time()
    res = run_async(preset.apex, acfg, preset.env, preset.agent,
                    preset.make_optimizer())
    s = res.stats
    obslog.emit("async-done", seconds=round(time.time() - t0, 1),
                learner_steps=int(s["learner_steps"]),
                param_version=int(s["param_version"]))
    obslog.emit("async-throughput",
                generate_tps=round(s["actor_tps"]),
                consume_tps=round(s["learner_tps"]),
                ratio=f"{s['generate_consume_ratio']:.2f}",
                paper_ratio="1.29")
    obslog.emit("async-contention",
                actor_blocked=int(s["actor_blocked"]),
                learner_starved=int(s["learner_starved"]),
                replay_size=int(s["replay_size"]),
                shards=int(s["replay_shards"]))
    if res.gateway_stats is not None:
        g = res.gateway_stats
        obslog.emit("gateway", actor_procs=int(s["actor_procs"]),
                    conns=g.connections, cpu_clients=g.cpu_clients,
                    shm_conns=g.shm_connections,
                    blocks_in=g.blocks_in, transitions_in=g.transitions_in,
                    param_sends=g.param_sends,
                    mb_in=round(g.bytes_in / 1e6, 1))
        if g.sample_requests:
            obslog.emit("sample-plane", batches_served=g.sample_sends,
                        starved_polls=g.sample_starved,
                        priority_updates=g.priority_updates,
                        param_pushes=g.param_pushes)
    if res.service_stats is not None and res.service_stats.blocks_staged:
        obslog.emit("ingest-staging",
                    blocks_staged=res.service_stats.blocks_staged,
                    h2d_issue_us=round(res.service_stats.h2d_us))
    if res.source_stats is not None and res.source_stats.staged:
        ss = res.source_stats
        obslog.emit("sample-staging", batches_staged=ss.staged,
                    idle_polls=ss.stage_idle)
    if res.inference_stats is not None:
        i = res.inference_stats
        obslog.emit("inference", mode=inference_mode, requests=i.requests,
                    dispatches=i.dispatches, full_waves=i.full_waves,
                    hot_swaps=i.hot_swaps)
    if res.policy_stats is not None:
        p = res.policy_stats
        obslog.emit("policy-plane", conns=p.connections,
                    acts=p.act_requests,
                    mb_out=round(p.bytes_out / 1e6, 1))
    if checkpoint_dir or s.get("actor_restarts") or s.get("source_reconnects"):
        obslog.emit("fault-tolerance",
                    resumed_from_step=int(s.get("resumed_from_step", 0)),
                    snapshots=int(s.get("snapshots", 0)),
                    actor_restarts=int(s.get("actor_restarts", 0)),
                    actor_proc_exits=int(s.get("actor_proc_exits", 0)),
                    source_reconnects=int(s.get("source_reconnects", 0)))
    if res.last_actor_metrics:
        obslog.emit(
            "actor-metrics",
            mean_ep_return=f"{res.last_actor_metrics['mean_ep_return']:.3f}")
    if ckpt_dir and not serve_sampling:
        # In serve mode the trained params live on the remote learner host;
        # res.learner here is the untouched init state.
        ckpt_lib.save(f"{ckpt_dir}/ckpt_async_final.npz",
                      {"params": res.learner.params,
                       "opt_state": res.learner.opt_state,
                       "learner_step": res.learner.learner_step},
                      step=int(s["learner_steps"]))
    return res


def run_llm(arch: str, iterations: int, log_every: int, ckpt_dir: str | None,
            seq_len: int = 128, batch: int = 8):
    cfg = registry.get_config(arch).reduced()
    params = transformer.init(cfg, jax.random.key(0))
    optimizer = optim.adamw(1e-3)
    scfg = seqrep.SeqReplayConfig(
        replay=replay_lib.ReplayConfig(capacity=1024, min_fill=batch),
        seq_len=seq_len, batch_size=batch, ingest_batch=batch,
        param_sync_period=4, learner_steps_per_round=2)
    pcfg = data_lib.PipelineConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                                   batch_size=batch)
    apply_fn = lambda p, tokens: transformer.apply(p, tokens, cfg=cfg)
    state = seqrep.init_state(scfg, params, optimizer, jax.random.key(1))

    @jax.jit
    def round_step(state, step):
        b = data_lib.make_batch(pcfg, jax.random.key(7), step)
        return seqrep.round_step(scfg, apply_fn, optimizer, state,
                                 b["tokens"], b["labels"])

    for it in range(iterations):
        state, metrics = round_step(state, it)
        if (it + 1) % log_every == 0:
            obslog.emit("round", n=it + 1,
                        loss=f"{float(metrics['loss']):.4f}",
                        mean_prio=f"{float(metrics['mean_priority']):.4f}",
                        replay=int(state.replay.size))
        if ckpt_dir and (it + 1) % (log_every * 10) == 0:
            ckpt_lib.save(f"{ckpt_dir}/ckpt_{it+1}.npz",
                          {"params": state.params}, step=it + 1)
    return state


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("apex-dqn", "apex-dpg", "llm"),
                    default="apex-dqn")
    ap.add_argument("--arch", choices=registry.ARCH_IDS)
    ap.add_argument("--iterations", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale preset: one shard's share of the "
                         "paper geometry, run on one device")
    ap.add_argument("--runtime", choices=("sync", "async"), default="sync",
                    help="sync: lockstep act/learn alternation; async: "
                         "decoupled actor threads + replay service + learner "
                         "(apex modes only)")
    ap.add_argument("--actor-threads", type=int, default=None,
                    help="actor threads for --runtime async (default 1; "
                         "0 is implied by --learner-remote and allowed with "
                         "--actor-procs)")
    ap.add_argument("--replay-shards", type=int, default=1,
                    help="replay fabric shards for --runtime async (actor "
                         "blocks route round-robin; learner batches merge "
                         "per-shard sub-samples)")
    ap.add_argument("--inference-batching", action="store_true",
                    help="share one batched act dispatch across all actor "
                         "threads (--runtime async)")
    ap.add_argument("--inference-mode", choices=("wave", "slots"),
                    default="wave",
                    help="scheduling inside the shared inference engine: "
                         "wave = coalesce up to 2 ms and pad short waves; "
                         "slots = continuous batching — pending requests "
                         "are admitted into free slots the moment the "
                         "previous dispatch returns, params hot-swap at "
                         "dispatch boundaries (requires "
                         "--inference-batching)")
    ap.add_argument("--serve-policy", metavar="HOST:PORT", default=None,
                    help="also serve the shared inference engine over the "
                         "transport plane: a policy-only gateway at "
                         "HOST:PORT answers ACT_REQUEST frames, actor "
                         "processes become thin clients that ship their "
                         "slice per rollout instead of pulling params, and "
                         "external open-loop clients may attach (requires "
                         "--inference-batching)")
    ap.add_argument("--actor-procs", type=int, default=0,
                    help="spawn this many actor OS processes streaming "
                         "experience through the replay gateway socket "
                         "(--runtime async; combine with --actor-threads 0 "
                         "for a pure multi-process run)")
    ap.add_argument("--learn-batches", type=int, default=1,
                    help="prefetched batches consumed per jitted learner "
                         "call via lax.scan (--runtime async)")
    ap.add_argument("--wire-quantize-obs", action="store_true",
                    help="actor processes ship observations via the replay "
                         "codec (uint8 + affine, ~4x less wire traffic)")
    ap.add_argument("--sample-staging", action="store_true",
                    help="double-buffer the learner's sample path: a stager "
                         "thread device-puts batch k+1 while the learner "
                         "computes on batch k (--runtime async)")
    ap.add_argument("--ingest-staging", action="store_true",
                    help="double-buffer the replay shards' add path: each "
                         "owner thread issues block k+1's async device put "
                         "before dispatching block k's in-place update "
                         "(--runtime async; pass-through on CPU hosts)")
    ap.add_argument("--add-queue-depth", type=int, default=4,
                    help="bounded actor->replay queue depth per shard "
                         "(--runtime async); full queues backpressure "
                         "actors")
    ap.add_argument("--sample-queue-depth", type=int, default=2,
                    help="replay->learner prefetch depth per shard "
                         "(--runtime async); 2 = classic double buffering")
    ap.add_argument("--learner-remote", metavar="HOST:PORT", default=None,
                    help="run ONLY the learner here, sampling the replay "
                         "fabric served by a --serve-sampling run at "
                         "HOST:PORT (--runtime async)")
    ap.add_argument("--serve-sampling", action="store_true",
                    help="run actors + replay fabric + gateway and no local "
                         "learner; a --learner-remote process drives the "
                         "run through the gateway (--runtime async)")
    ap.add_argument("--gateway-port", type=int, default=0,
                    help="replay gateway TCP port (0: ephemeral; set a "
                         "fixed port for --serve-sampling so the learner "
                         "host knows where to connect)")
    ap.add_argument("--gateway-host", default="127.0.0.1",
                    help="replay gateway bind address; the loopback "
                         "default only reaches same-machine peers — pass "
                         "0.0.0.0 to serve actors/learners on other hosts")
    ap.add_argument("--transport", choices=("tcp", "shm", "auto"),
                    default="auto",
                    help="byte path for remote hops (--actor-procs and "
                         "--learner-remote): tcp = sockets, shm = same-host "
                         "shared-memory rings (strict), auto = shm when the "
                         "peer is loopback-local, else tcp")
    ap.add_argument("--wire-quantize-prios", action="store_true",
                    help="the remote learner ships priority write-backs "
                         "quantized (uint8 + affine; lossy) — requires "
                         "--learner-remote")
    ap.add_argument("--wire-quantize-params", action="store_true",
                    help="the remote learner ships param snapshots "
                         "quantized (uint8 + affine per tensor; lossy) — "
                         "requires --learner-remote")
    ap.add_argument("--metrics-dir", default=None,
                    help="write telemetry (metrics.jsonl + spans.jsonl) "
                         "into this directory during the run; render with "
                         "`python -m repro.obs.report DIR` "
                         "(--runtime async)")
    ap.add_argument("--trace-sample-rate", type=float, default=0.0,
                    help="fraction of transition blocks / learner batches "
                         "carrying an end-to-end pipeline trace id, in "
                         "[0, 1] (requires --metrics-dir; traced ops force "
                         "a device sync — keep small on hot runs)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="periodically snapshot the whole run — replay "
                         "fabric contents + sum trees + clocks, learner "
                         "slice, param version — as atomic ckpt_<step>.npz "
                         "files in this directory (--runtime async; "
                         "distinct from --ckpt-dir, which saves final "
                         "params only)")
    ap.add_argument("--checkpoint-every-s", type=float, default=30.0,
                    help="seconds between periodic snapshots (requires "
                         "--checkpoint-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="cold-start from the newest snapshot in "
                         "--checkpoint-dir and continue the interrupted "
                         "run (an empty directory is a normal cold start)")
    return ap


def validate_args(ap: argparse.ArgumentParser,
                  args: argparse.Namespace) -> argparse.Namespace:
    """Reject incoherent flag combinations up front with actionable
    messages, instead of letting them fail deep inside the runtime (or
    silently do something other than what was asked). Resolves the
    ``--actor-threads`` default (1, or 0 when ``--learner-remote`` implies a
    learner-only process). Returns the resolved namespace."""
    is_async = args.runtime == "async"
    async_only = [("--actor-procs", args.actor_procs != 0),
                  ("--replay-shards", args.replay_shards != 1),
                  ("--inference-batching", args.inference_batching),
                  ("--inference-mode", args.inference_mode != "wave"),
                  ("--serve-policy", args.serve_policy is not None),
                  ("--learn-batches", args.learn_batches != 1),
                  ("--wire-quantize-obs", args.wire_quantize_obs),
                  ("--sample-staging", args.sample_staging),
                  ("--ingest-staging", args.ingest_staging),
                  ("--add-queue-depth", args.add_queue_depth != 4),
                  ("--sample-queue-depth", args.sample_queue_depth != 2),
                  ("--learner-remote", args.learner_remote is not None),
                  ("--serve-sampling", args.serve_sampling),
                  ("--gateway-port", args.gateway_port != 0),
                  ("--gateway-host", args.gateway_host != "127.0.0.1"),
                  ("--transport", args.transport != "auto"),
                  ("--wire-quantize-prios", args.wire_quantize_prios),
                  ("--wire-quantize-params", args.wire_quantize_params),
                  ("--metrics-dir", args.metrics_dir is not None),
                  ("--trace-sample-rate", args.trace_sample_rate != 0.0),
                  ("--checkpoint-dir", args.checkpoint_dir is not None),
                  ("--checkpoint-every-s", args.checkpoint_every_s != 30.0),
                  ("--resume", args.resume),
                  ("--actor-threads", args.actor_threads is not None)]
    if not is_async:
        used = [name for name, on in async_only if on]
        if used:
            ap.error(f"{', '.join(used)} require(s) --runtime async "
                     "(the sync lockstep driver has no actor/replay/learner "
                     "threads to configure)")
    if args.mode == "llm":
        if not args.arch:
            ap.error("--mode llm requires --arch")
        if is_async:
            ap.error("--runtime async applies to the apex modes only; "
                     "--mode llm always runs the sequence-replay round loop")
    if args.iterations < 1:
        ap.error(f"--iterations must be >= 1, got {args.iterations}")
    if args.learn_batches < 1:
        ap.error(f"--learn-batches must be >= 1, got {args.learn_batches}")
    if args.actor_procs < 0:
        ap.error(f"--actor-procs must be >= 0, got {args.actor_procs}")
    if args.replay_shards < 1:
        ap.error(f"--replay-shards must be >= 1, got {args.replay_shards}")
    if args.add_queue_depth < 1:
        ap.error("--add-queue-depth must be >= 1 (a bounded queue is what "
                 f"backpressures actors), got {args.add_queue_depth}")
    if args.sample_queue_depth < 1:
        ap.error("--sample-queue-depth must be >= 1 (the learner prefetch "
                 f"buffer), got {args.sample_queue_depth}")
    if not 0.0 <= args.trace_sample_rate <= 1.0:
        ap.error("--trace-sample-rate is a sampling fraction in [0, 1] "
                 f"(0 = tracing off, 1 = every block), got "
                 f"{args.trace_sample_rate}")
    if args.trace_sample_rate > 0 and args.metrics_dir is None:
        ap.error("--trace-sample-rate records pipeline spans, which only "
                 "persist through the JSONL sink — add --metrics-dir DIR "
                 "(without it the spans would fill a ring buffer nobody "
                 "drains)")
    if args.resume and args.checkpoint_dir is None:
        ap.error("--resume loads checkpoint.latest() from --checkpoint-dir; "
                 "there is nothing to resume from without it")
    if args.checkpoint_every_s <= 0:
        ap.error("--checkpoint-every-s must be > 0 seconds, got "
                 f"{args.checkpoint_every_s}")
    if args.checkpoint_dir is not None and (
            args.learner_remote is not None or args.serve_sampling):
        ap.error("--checkpoint-dir snapshots the replay fabric AND the "
                 "learner together, so both must be local — a "
                 "--learner-remote process has no fabric and a "
                 "--serve-sampling process has no learner; run the "
                 "snapshot service on a single-process topology")

    if args.learner_remote is not None:
        from repro.net.learner_client import parse_hostport
        try:
            parse_hostport(args.learner_remote)
        except ValueError as e:
            ap.error(f"--learner-remote: {e}")
        if args.serve_sampling:
            ap.error("--learner-remote and --serve-sampling are the two "
                     "sides of one topology: this process either samples a "
                     "remote fabric or serves its own, not both")
        conflicts = [("--actor-threads", args.actor_threads not in (None, 0)),
                     ("--actor-procs", args.actor_procs != 0),
                     ("--replay-shards", args.replay_shards != 1),
                     ("--inference-batching", args.inference_batching),
                     ("--inference-mode", args.inference_mode != "wave"),
                     ("--serve-policy", args.serve_policy is not None),
                     ("--wire-quantize-obs", args.wire_quantize_obs),
                     ("--ingest-staging", args.ingest_staging),
                     ("--add-queue-depth", args.add_queue_depth != 4),
                     ("--sample-queue-depth", args.sample_queue_depth != 2),
                     ("--gateway-port", args.gateway_port != 0),
                     ("--gateway-host", args.gateway_host != "127.0.0.1")]
        used = [name for name, on in conflicts if on]
        if used:
            ap.error(f"--learner-remote runs a learner-only process; "
                     f"{', '.join(used)} configure(s) the acting/replay "
                     "side, which lives on the --serve-sampling host — "
                     "drop the flag(s) here and pass them there")
        args.actor_threads = 0
    elif args.actor_threads is None:
        args.actor_threads = 1

    if args.serve_sampling:
        serve_conflicts = [("--sample-staging", args.sample_staging),
                           ("--learn-batches", args.learn_batches != 1)]
        used = [name for name, on in serve_conflicts if on]
        if used:
            ap.error(f"--serve-sampling runs no local learner; "
                     f"{', '.join(used)} configure(s) the learner's "
                     "consume path — pass them to the --learner-remote "
                     "process instead")

    if not 0 <= args.gateway_port <= 65535:
        ap.error(f"--gateway-port must be in [0, 65535] (0 = ephemeral), "
                 f"got {args.gateway_port}")
    gateway_flags = [("--gateway-port", args.gateway_port != 0),
                     ("--gateway-host", args.gateway_host != "127.0.0.1")]
    used = [name for name, on in gateway_flags if on]
    if used and not (args.serve_sampling or args.actor_procs > 0):
        ap.error(f"{', '.join(used)} configure(s) the replay gateway, but "
                 "no gateway will run — add --serve-sampling (serve a "
                 "remote learner) or --actor-procs N (serve actor "
                 "processes)")
    if (args.transport != "auto" and args.actor_procs == 0
            and args.learner_remote is None and not args.serve_sampling):
        ap.error("--transport configures remote hops, but none exist — add "
                 "--actor-procs N, --learner-remote HOST:PORT, or "
                 "--serve-sampling (in-process actor threads and the local "
                 "fabric never touch a transport)")
    if ((args.wire_quantize_prios or args.wire_quantize_params)
            and args.learner_remote is None):
        flags = [n for n, on in
                 [("--wire-quantize-prios", args.wire_quantize_prios),
                  ("--wire-quantize-params", args.wire_quantize_params)]
                 if on]
        ap.error(f"{', '.join(flags)} quantize(s) the remote learner's "
                 "upstream frames and require(s) --learner-remote (a local "
                 "learner writes priorities/params back in-process, no "
                 "wire to quantize)")

    if args.actor_threads < 0:
        ap.error(f"--actor-threads must be >= 0, got {args.actor_threads}")
    if (is_async and args.actor_threads == 0 and args.actor_procs == 0
            and args.learner_remote is None):
        ap.error("--actor-threads 0 leaves the run with no experience "
                 "source: add --actor-procs N (actors as OS processes) or "
                 "run actor threads (the learner would starve forever)")
    if args.serve_policy is not None:
        from repro.net.learner_client import parse_hostport
        try:
            # port 0 = ephemeral bind (logged at startup), like --gateway-port
            parse_hostport(args.serve_policy, allow_ephemeral=True)
        except ValueError as e:
            ap.error(f"--serve-policy: {e}")
        if not args.inference_batching:
            ap.error("--serve-policy serves the shared inference engine; "
                     "there is no engine without --inference-batching")
    if args.inference_mode != "wave" and not args.inference_batching:
        ap.error("--inference-mode selects the shared engine's scheduler; "
                 "it requires --inference-batching")
    if (args.inference_batching and args.actor_threads == 0
            and args.serve_policy is None):
        ap.error("--inference-batching batches *in-process* actor threads; "
                 "with --actor-threads 0 there is nothing to batch (actor "
                 "processes run their own jitted rollouts) — unless "
                 "--serve-policy feeds the engine from remote clients")
    if args.serve_sampling and args.gateway_port == 0:
        obslog.emit("note", serve_sampling=True, gateway_port="ephemeral",
                    hint="the learner host needs the port logged at "
                         "startup; pass --gateway-port to pin it")
    return args


def main(argv: list[str] | None = None):
    """CLI entry point; returns what the selected run returns (the final
    train state, or the async runtime's result)."""
    ap = build_parser()
    args = validate_args(ap, ap.parse_args(argv))
    enable_compile_cache()

    def run_preset(preset):
        if args.runtime == "async":
            if preset.apex.batch_size % args.replay_shards:
                ap.error(f"--replay-shards {args.replay_shards} must divide "
                         f"the preset batch size {preset.apex.batch_size} "
                         "(equal per-shard sample quotas)")
            return run_apex_async(preset, args.iterations, args.actor_threads,
                                  args.ckpt_dir, args.replay_shards,
                                  args.inference_batching, args.actor_procs,
                                  args.learn_batches, args.wire_quantize_obs,
                                  args.sample_staging, args.learner_remote,
                                  args.serve_sampling, args.gateway_port,
                                  args.gateway_host, args.transport,
                                  args.wire_quantize_prios,
                                  args.wire_quantize_params,
                                  args.ingest_staging,
                                  args.add_queue_depth, args.sample_queue_depth,
                                  args.metrics_dir, args.trace_sample_rate,
                                  args.checkpoint_dir, args.checkpoint_every_s,
                                  args.resume, args.inference_mode,
                                  args.serve_policy)
        return run_apex(preset, args.iterations, args.log_every,
                        args.ckpt_dir)

    if args.mode == "apex-dqn":
        from repro.configs import apex_dqn
        return run_preset(apex_dqn.full() if args.full else apex_dqn.reduced())
    if args.mode == "apex-dpg":
        from repro.configs import apex_dpg
        return run_preset(apex_dpg.full() if args.full else apex_dpg.reduced())
    return run_llm(args.arch, args.iterations, args.log_every, args.ckpt_dir)


if __name__ == "__main__":
    main()
