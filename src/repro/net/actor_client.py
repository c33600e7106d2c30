"""Remote actor process: jitted rollouts streamed to a ``ReplayGateway``.

This is the paper's actor binary (Alg. 1) as a separate OS process — the
piece that makes "hundreds of actors on hundreds of machines" real rather
than thread-simulated. Each process:

1. connects to the gateway (``--transport tcp|shm|auto``: same-host
   processes upgrade to a shared-memory ring, cross-host stays TCP) and
   handshakes (``HELLO``, protocol-versioned);
2. pulls the initial parameter snapshot (Alg. 1 l.1);
3. loops: jitted ``act_phase`` rollout → serialize the ``TransitionBlock``
   (optionally quantizing float observations with the replay codec) →
   ``ADD_BLOCK`` → every ``param_sync_period`` rollouts, ``PARAM_PULL``
   (Alg. 1 l.2, periodic refresh);
4. exits on ``STOP`` from the gateway (learner finished) or a torn-down
   transport, reporting its client-side counters in a final ``BYE``.

Backpressure mirrors the in-process path: at most ``max_inflight``
un-acknowledged blocks may be on the wire. The gateway only ACKs a block
*after* it lands in the fabric's bounded shard queue, so a saturated replay
holds ACKs back and the remote actor blocks exactly where a local actor
thread would block on ``fabric.add`` (waits counted like ``actor_blocked``).

Blocks ship scatter-gather: ``encode_block_iov`` hands the transport a list
of buffer views (tensor leaves are not concatenated host-side), so the TCP
path writes them with one ``sendmsg`` and the shm path copies each leaf
exactly once, straight into the ring arena.

Numerics: the actor's rng/epsilon geometry is derived from ``(seed,
actor_id)`` by the same fold-in scheme ``runtime/runner.py`` uses for actor
threads, so a run with K threads + M processes spans one exploration ladder
over K+M actors, and moving an actor across the process boundary does not
change its stream.

Run standalone against a remote host (the multi-host path)::

    python -m repro.net.actor_client --host <gateway> --port <p> \
        --preset apex-dqn --actor-id 3 --num-actors 8
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro.net import transport as transport_lib
from repro.net import wire
from repro.obs import Tracer
from repro.runtime import phases


@dataclasses.dataclass(frozen=True)
class RemoteActorSpec:
    """Everything a remote actor process needs; must pickle (spawn)."""

    cfg: Any                      # apex.ApexConfig with num_shards = total actors
    env: Any
    agent: Any
    host: str
    port: int
    actor_id: int                 # global ladder position (threads first)
    seed: int = 0                 # runner's AsyncConfig.seed
    max_inflight: int = 4         # un-acked ADD_BLOCKs allowed on the wire
    quantize_obs: bool = False    # wire-quantize float obs (replay codec)
    transport: str = "auto"       # tcp | shm | auto (shm iff host is local)
    ring_bytes: int = transport_lib.DEFAULT_RING_BYTES
    param_sync_period: int | None = None  # default: cfg.param_sync_period
    max_rollouts: int | None = None       # None: run until STOP / EOF
    pin_cpu: int | None = None    # pin this process (and its XLA threads)
                                  # to one core — the paper's one-actor-per-
                                  # CPU model; unpinned actors let XLA's
                                  # intra-op pool spread across cores
    target_blocks_per_s: float | None = None  # pace sends to this offered
                                  # rate (load-test mode: benchmarks drive a
                                  # known aggregate load instead of racing
                                  # the machine); None: run flat out
    connect_timeout_s: float = 10.0
    param_timeout_s: float = 120.0  # a backpressured gateway answers pulls
                                    # late (its handler is busy holding our
                                    # ACKs back) — that's congestion, not
                                    # death, so this bound is generous
    reconnect: bool = True        # a severed transport mid-run (anything but
                                  # an explicit STOP) reconnects with capped
                                  # backoff instead of exiting; un-acked
                                  # blocks are dropped (a temporary dip in
                                  # ingest, the paper's tolerated loss)
    reconnect_timeout_s: float = 20.0  # give up (clean exit) when the
                                  # gateway stays away this long
    poll_s: float = 0.05          # wait granularity on a full window
    trace_sample_rate: float = 0.0  # fraction of blocks stamped with a
                                    # pipeline trace id in the ADD_BLOCK
                                    # header (repro.obs); spans are
                                    # recorded on the gateway host, whose
                                    # sink owns the run's JSONL
    policy: str | None = None     # HOST:PORT of a --serve-policy gateway.
                                  # Set -> thin-client mode: rollouts run
                                  # server-side in the shared slot-scheduled
                                  # engine (this process ships its slice per
                                  # ACT_REQUEST and never holds params);
                                  # unset -> classic local jitted act_phase


class _Stop(Exception):
    """Gateway said STOP (or went away): drain and exit cleanly."""


# The exact slice ``runner.run_async`` builds for actor ``actor_id`` — one
# shared derivation, so thread and process actors are interchangeable
# points on one ladder.
initial_slice = phases.initial_actor_slice


class RemoteActorLoop:
    """One remote actor: transport client + jitted rollout loop."""

    def __init__(self, spec: RemoteActorSpec):
        self.spec = spec
        cfg, env, agent = spec.cfg, spec.env, spec.agent
        def actor_rollout(p, sl, sid):  # jit_actor_rollout, as in the runner
            return phases.act_phase(cfg, env, agent, p, sl, sid)

        self._act = jax.jit(actor_rollout)
        self._sync_period = (spec.param_sync_period
                             if spec.param_sync_period is not None
                             else cfg.param_sync_period)
        self._params: Any = None
        self._param_version = -1
        self._pull_replies = 0    # PARAM + PARAM_UNCHANGED frames seen
        self._in_flight = 0
        # Deterministic block sampling for pipeline tracing: a sampled
        # block carries its id in the ADD_BLOCK header, and the gateway
        # host's tracer records the downstream spans (this process has no
        # sink — it only originates ids).
        self._tracer = Tracer(spec.trace_sample_rate)
        self._conn: transport_lib.Transport | None = None
        self._policy = None  # PolicyClient in thin-client mode
        self.stats = {"rollouts": 0, "pushed": 0, "blocked": 0,
                      "transitions": 0, "param_pulls": 0, "bytes_out": 0,
                      "reconnects": 0, "inflight_dropped": 0,
                      "param_version": -1, "transport": "",
                      "policy_acts": 0}

    # -- frame plumbing -----------------------------------------------------

    def _handle(self, msg_type: int, payload: memoryview) -> None:
        if msg_type == wire.ADD_ACK:
            self._in_flight -= 1
        elif msg_type == wire.PARAM:
            version, params = wire.decode_params(payload)
            # device_put once per refresh, not once per rollout dispatch
            self._params = jax.device_put(params)
            self._param_version = version
            self.stats["param_version"] = version
            self._pull_replies += 1
        elif msg_type == wire.PARAM_UNCHANGED:
            self._pull_replies += 1
        elif msg_type == wire.STOP:
            raise _Stop
        else:
            raise wire.WireError(f"unexpected message {msg_type} from gateway")

    def _pump(self, conn: transport_lib.Transport, timeout: float) -> bool:
        """Process at most one pending frame; False on timeout."""
        got = conn.recv(timeout=timeout)
        if got is None:
            return False
        self._handle(*got)
        return True

    def _pull_params(self, conn: transport_lib.Transport) -> None:
        """Request a snapshot newer than ours and wait for the reply
        (ACKs interleaved on the stream are processed while waiting)."""
        replies_before = self._pull_replies
        conn.send(wire.PARAM_PULL,
                  wire.encode_json({"have": self._param_version}))
        self.stats["param_pulls"] += 1
        deadline = time.monotonic() + self.spec.param_timeout_s
        while self._pull_replies == replies_before:
            if time.monotonic() > deadline:
                raise TimeoutError("gateway never answered PARAM_PULL")
            self._pump(conn, timeout=self.spec.poll_s)

    # -- connection lifecycle -----------------------------------------------

    def _handshake(self) -> None:
        """HELLO + initial parameter pull on the current connection. The
        reconnect count rides the HELLO so the gateway can account client
        comebacks (priorities are idempotent LWW updates — re-sending after
        a reconnect is safe by construction)."""
        self._conn.send(wire.HELLO, wire.encode_json(
            {"actor_id": self.spec.actor_id,
             "protocol": wire.PROTOCOL_VERSION,
             "reconnects": self.stats["reconnects"],
             "platform": jax.default_backend()}))
        if self.spec.policy is None:
            self._pull_params(self._conn)
        # thin-client mode never pulls: the policy gateway's engine holds
        # (and hot-swaps) the parameters

    def _retire_conn(self) -> None:
        if self._conn is None:
            return
        self.stats["bytes_out"] += self._conn.bytes_out
        try:
            self._conn.close()
        except OSError:
            pass
        self._conn = None

    def _reconnect(self, cause: BaseException) -> None:
        """A severed transport (anything but an explicit STOP): dial the
        gateway again with capped backoff until ``reconnect_timeout_s``,
        re-handshake, and resume acting. Un-acked blocks on the dead
        connection are dropped (counted ``inflight_dropped``) — a temporary
        ingest dip, the loss mode the paper's replay tolerates. Re-raises
        ``cause`` on give-up so the caller's normal exit paths apply."""
        spec = self.spec
        if not spec.reconnect:
            raise cause
        self._retire_conn()
        self.stats["inflight_dropped"] += self._in_flight
        self._in_flight = 0
        deadline = time.monotonic() + spec.reconnect_timeout_s
        backoff = 0.05
        while True:
            if time.monotonic() >= deadline:
                raise cause
            time.sleep(min(backoff, max(0.0, deadline - time.monotonic())))
            backoff = min(backoff * 2, 1.0)
            try:
                self._conn = transport_lib.connect(
                    spec.host, spec.port, spec.transport,
                    timeout=spec.connect_timeout_s,
                    ring_bytes=spec.ring_bytes)
            except (OSError, transport_lib.ShmUnavailable):
                continue
            self.stats["transport"] = self._conn.kind
            self.stats["reconnects"] += 1
            try:
                self._handshake()
            except (EOFError, transport_lib.TransportClosed, OSError,
                    TimeoutError):
                # Gateway flapped again mid-handshake: keep trying until
                # the deadline (a STOP here propagates — clean exit).
                self._retire_conn()
                continue
            return

    # -- rollout dispatch ----------------------------------------------------

    def _rollout(self, sl, sid):
        """One rollout: local jitted act_phase, or — thin-client mode — an
        ACT_REQUEST round trip into the policy gateway's shared engine. The
        two are bit-identical per actor (the wire codec round-trips every
        leaf exactly), so moving an actor behind the policy plane does not
        change its stream."""
        if self._policy is None:
            return self._act(self._params, sl, sid)
        try:
            res = self._policy.act(sl, int(sid))
        except (EOFError, transport_lib.TransportClosed, OSError) as e:
            # The policy plane lives with the runner; it going away IS the
            # end of the run for a thin client (no params to act with).
            raise _Stop from e
        if res is None:
            raise _Stop  # STOP reply: runtime shutting down
        self.stats["policy_acts"] += 1
        return res

    # -- main loop ----------------------------------------------------------

    def run(self) -> dict:
        """Act until the gateway stops us; returns client-side counters."""
        spec = self.spec
        self._conn = transport_lib.connect(
            spec.host, spec.port, spec.transport,
            timeout=spec.connect_timeout_s, ring_bytes=spec.ring_bytes)
        self.stats["transport"] = self._conn.kind
        try:
            self._handshake()
            if spec.policy is not None:
                from repro.net.learner_client import parse_hostport
                from repro.net.policy_client import PolicyClient
                ph, pp = parse_hostport(spec.policy)
                self._policy = PolicyClient(
                    ph, pp,
                    example=initial_slice(spec.cfg, spec.env, spec.seed,
                                          spec.actor_id),
                    transport=spec.transport,
                    connect_timeout_s=spec.connect_timeout_s,
                    act_timeout_s=spec.param_timeout_s)

            sl = initial_slice(spec.cfg, spec.env, spec.seed, spec.actor_id)
            sid = jnp.int32(spec.actor_id)
            next_send = None  # offered-rate pacing schedule
            while (spec.max_rollouts is None
                   or self.stats["rollouts"] < spec.max_rollouts):
                try:
                    if (self._policy is None
                            and self.stats["rollouts"] > 0
                            and self.stats["rollouts"]
                            % self._sync_period == 0):
                        self._pull_params(self._conn)
                    sl, block, _metrics = self._rollout(sl, sid)
                    payload = wire.encode_block_iov(
                        block, quantize_obs=spec.quantize_obs)
                    if spec.target_blocks_per_s:
                        # Pace to the offered rate (no catch-up bursts: the
                        # target is a strict upper bound), draining ACKs
                        # while waiting out the slot. An overrun slot sends
                        # at once.
                        period = 1.0 / spec.target_blocks_per_s
                        now = time.monotonic()
                        next_send = now if next_send is None else max(
                            next_send + period, now)
                        while True:
                            remaining = next_send - time.monotonic()
                            if remaining <= 0:
                                break
                            self._pump(self._conn, timeout=remaining)
                    # Bounded in-flight window: wait for ACKs when full —
                    # this is where gateway/fabric backpressure reaches the
                    # actor.
                    while self._in_flight >= spec.max_inflight:
                        if not self._pump(self._conn, timeout=spec.poll_s):
                            self.stats["blocked"] += 1
                    self._conn.send(wire.ADD_BLOCK, payload,
                                    trace_id=self._tracer.sample())
                    self._in_flight += 1
                    self.stats["rollouts"] += 1
                    self.stats["pushed"] += 1
                    self.stats["transitions"] += int(
                        block.priorities.shape[0])
                    # opportunistically drain ACKs already on the stream
                    while self._pump(self._conn, timeout=0.001):
                        pass
                except (EOFError, transport_lib.TransportClosed,
                        OSError) as e:
                    # Severed transport mid-rollout (TimeoutError is an
                    # OSError: a wedged gateway counts). STOP is _Stop and
                    # never lands here.
                    self._reconnect(e)
        except (_Stop, EOFError, transport_lib.TransportClosed):
            pass
        finally:
            if self._policy is not None:
                self._policy.close()
            if self._conn is not None:
                try:
                    self._conn.send(wire.BYE, wire.encode_json(
                        {"rollouts": self.stats["rollouts"],
                         "blocked": self.stats["blocked"]}))
                except (OSError, wire.WireError):
                    pass
                self._retire_conn()
        return self.stats


def run_remote_actor(spec: RemoteActorSpec) -> dict:
    """Process entry point (importable, so ``multiprocessing`` spawn and
    ``launch/train.py --actor-procs`` can target it). A gateway that is
    already gone — e.g. the learner finished while this process was still
    compiling — is a clean exit, not a crash.

    The process runs JAX on the CPU, as the paper's actors do: the parent
    that spawned it may hold the accelerator, which belongs to one process
    at a time, so a child that reached for it would fail or hang."""
    jax.config.update("jax_platforms", "cpu")
    if spec.pin_cpu is not None and hasattr(os, "sched_setaffinity"):
        # Before the first jax op: XLA's intra-op threads spawn lazily and
        # inherit this affinity, so the whole process stays on one core.
        os.sched_setaffinity(0, {spec.pin_cpu % os.cpu_count()})
    try:
        return RemoteActorLoop(spec).run()
    except (ConnectionError, TimeoutError, OSError,
            transport_lib.ShmUnavailable) as e:
        # Observable but non-fatal: the runtime tolerates individual actor
        # losses (paper §3 — actors are expendable) and its gateway
        # monitor stops the run only when no experience source remains.
        print(f"actor {spec.actor_id} aborted: {e!r}", file=sys.stderr)
        return {"aborted": str(e)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--preset", choices=("apex-dqn", "apex-dpg"),
                    default="apex-dqn")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale preset geometry")
    ap.add_argument("--actor-id", type=int, default=0,
                    help="this actor's position on the global eps ladder")
    ap.add_argument("--num-actors", type=int, default=1,
                    help="total actors across all hosts (ladder width)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-inflight", type=int, default=4)
    ap.add_argument("--quantize-obs", action="store_true",
                    help="wire-quantize float observations (replay codec)")
    ap.add_argument("--transport", choices=("tcp", "shm", "auto"),
                    default="auto",
                    help="byte path to the gateway: shm = same-host ring "
                         "(requires a local gateway), auto = shm when the "
                         "host is loopback-local, else tcp")
    ap.add_argument("--max-rollouts", type=int, default=None)
    ap.add_argument("--pin-cpu", type=int, default=None,
                    help="pin this actor process to one CPU core "
                         "(one-actor-per-core, paper §3)")
    args = ap.parse_args()

    if args.preset == "apex-dqn":
        from repro.configs import apex_dqn as preset_mod
    else:
        from repro.configs import apex_dpg as preset_mod
    preset = preset_mod.full() if args.full else preset_mod.reduced()
    cfg = dataclasses.replace(preset.apex, num_shards=args.num_actors)
    spec = RemoteActorSpec(
        cfg=cfg, env=preset.env, agent=preset.agent, host=args.host,
        port=args.port, actor_id=args.actor_id, seed=args.seed,
        max_inflight=args.max_inflight, quantize_obs=args.quantize_obs,
        transport=args.transport, max_rollouts=args.max_rollouts,
        pin_cpu=args.pin_cpu)
    stats = run_remote_actor(spec)
    print(f"actor {args.actor_id} done: {stats}")


if __name__ == "__main__":
    main()
