"""Replay gateway: the serving side of the transport plane, in front of the
replay fabric.

This is the machine boundary of Fig. 1: remote actor processes (same host or
across the network) stream ``ADD_BLOCK`` frames in, and the gateway routes
the decoded ``TransitionBlock``s into the *same* ``ReplayFabric.add`` the
in-process actor threads use — the learner cannot tell the two ingest paths
apart (same round-robin shard routing, same global ``(shard, slot)`` keys,
same backpressure semantics).

Topology::

    remote actor proc 0 ──tcp/shm──┐
    remote actor proc 1 ──tcp/shm──┤   ReplayGateway        ReplayFabric
           ...                     ├── (accept thread + ──► add / round-robin
    remote actor proc K ──tcp/shm──┘    handler thread        shard routing
                                        per connection)

Connections arrive through ``repro.net.transport.Listener``: every client
starts on TCP and may upgrade itself to a shared-memory ring
(``ShmRingTransport``) in-band — the handler below never knows which bytes
path it is on, it just calls ``conn.recv()``/``conn.send()``.

* Each connection gets its own handler thread: frame decode (a memcpy-level
  numpy view) runs concurrently across actors, and the device transfer
  happens on the owning shard's thread as for in-process adds.
* **Backpressure propagates end to end.** ``fabric.add`` returning False
  (bounded shard queue full) makes the handler retry — meanwhile no
  ``ADD_ACK`` is sent, the client's bounded in-flight window stays open, and
  the remote actor blocks exactly like a local actor blocks on the queue.
  Retries are counted in ``GatewayStats.add_retries`` (the remote analogue
  of the runner's ``actor_blocked``).
* **Parameter serving.** ``PARAM_PULL {have: v}`` answers with the latest
  ``ParamStore`` snapshot when its version is newer, else
  ``PARAM_UNCHANGED`` — the client pulls every ``param_sync_period``
  rollouts (Alg. 1 l.2), so the period is honored client-side and the
  gateway never pushes unsolicited traffic.
* **Sample plane (remote learners).** The same fabric's *learner* side is
  served over the same connection discipline: ``SAMPLE_REQUEST`` pops one
  prioritized batch (empty ``SAMPLE_BATCH`` reply while starved — the
  remote analogue of ``get_batch`` returning None), ``PRIORITY_UPDATE``
  scatters write-backs by the global (shard, slot) keys the batch carried
  (one frame may coalesce several write-back rounds; the ``batches`` leaf
  advances the ``priority_updates`` learner clock by that many), and
  ``PARAM_PUSH`` publishes the remote learner's fresh params into this
  host's ``ParamStore`` so the actors feeding the fabric keep pulling
  learning-current snapshots. ``fabric.get_batch`` is single-consumer, so
  sample pops are serialized under a lock; exactly one remote learner
  should be attached at a time (a second one would consume from the same
  logical replay — replay replication, not an error, but not a fan-out).

* **Policy plane (``--serve-policy``).** A gateway built with
  ``inference=`` (an ``InferenceServer``) and ``act_example=`` (a local
  ``ActorSlice`` fixing the wire geometry) serves ``ACT_REQUEST`` frames:
  each is one rollout request admitted into the shared slot-scheduled
  engine alongside the in-process actors, answered with ``ACT_RESULT``
  (advanced slice + ``TransitionBlock`` + metrics) or ``STOP`` when the
  runtime is shutting down. Concurrency across connections is what fills
  the engine's slots — each handler thread blocks in ``engine.act`` while
  the engine batches every blocked handler into one compiled dispatch. A
  policy-only gateway passes ``fabric=None``; fabric-plane frames on such
  a gateway are a protocol error.

``stop()`` sends ``STOP`` to every live client (best effort), closes the
listener, and joins the handlers; a handler that dies on malformed traffic
records the error and drops that one connection, never the gateway.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any

import jax

from repro.net import transport as transport_lib
from repro.net import wire
from repro.obs import Telemetry
from repro.runtime.params import ParamStore


@dataclasses.dataclass
class GatewayStats:
    connections: int = 0        # accepted actor connections (lifetime)
    shm_connections: int = 0    # ... that upgraded to the shm ring path
    blocks_in: int = 0          # ADD_BLOCKs routed into the fabric
    transitions_in: int = 0     # transitions carried by those blocks
    add_retries: int = 0        # fabric.add backpressure retries (remote
                                # analogue of the runner's actor_blocked)
    param_pulls: int = 0        # PARAM_PULL requests served
    param_sends: int = 0        # ... that shipped a fresh snapshot
    bytes_in: int = 0
    bytes_out: int = 0
    client_rollouts: int = 0    # merged from BYE frames (client-side view)
    client_blocked: int = 0     # client waits on a full in-flight window
    wire_errors: int = 0        # connections dropped on malformed traffic
    sample_requests: int = 0    # SAMPLE_REQUESTs served (incl. starved)
    sample_sends: int = 0       # ... that shipped an actual batch
    sample_starved: int = 0     # ... answered empty (fabric below min-fill
                                # or prefetch lagging)
    priority_updates: int = 0   # priority write-back *rounds* routed into
                                # the fabric (the serve-side learner clock;
                                # coalesced frames count every round they
                                # carry)
    priority_frames: int = 0    # PRIORITY_UPDATE frames received
    param_pushes: int = 0       # PARAM_PUSH snapshots published locally
    client_reconnects: int = 0  # HELLOs from clients that came back after
                                # a severed transport (fault-tolerance
                                # plane: safe because priority updates are
                                # idempotent LWW and adds are append-only)
    learner_byes: int = 0       # clean BYEs from sample-plane learner
                                # clients — the serving runtime's end-of-run
                                # signal when severed transports swallowed
                                # some in-flight priority frames
    act_requests: int = 0       # policy-plane rollouts served (ACT_RESULT
                                # replies; a STOP answer is not counted)
    cpu_clients: int = 0        # first HELLOs from clients whose JAX runs
                                # on the CPU (actor processes keep off the
                                # accelerator the serving process holds)


class ReplayGateway:
    """Server thread feeding ``ReplayFabric.add`` from remote actors."""

    def __init__(self, fabric: Any, store: ParamStore, *,
                 host: str = "127.0.0.1", port: int = 0,
                 add_timeout_s: float = 0.05, sample_timeout_s: float = 0.05,
                 poll_s: float = 0.2, drain_grace_s: float = 1.0,
                 backlog: int = 64, accept_shm: bool = True,
                 ring_bytes: int = transport_lib.DEFAULT_RING_BYTES,
                 inference: Any = None, act_example: Any = None,
                 telemetry: Telemetry | None = None):
        if fabric is None and inference is None:
            raise ValueError("gateway needs a fabric, an inference engine, "
                             "or both — got neither")
        if inference is not None and act_example is None:
            raise ValueError("policy serving needs act_example (a local "
                             "ActorSlice fixing the wire geometry)")
        self._fabric = fabric
        self._store = store
        self._inference = inference
        self._act_example = act_example
        self._tel = telemetry if telemetry is not None else Telemetry.local()
        # decode + fabric-route latency per ADD_BLOCK; the retries counter
        # mirrors GatewayStats.add_retries into the obs registry so the
        # run report's backpressure section sees it.
        self._h_route = self._tel.histogram("gateway/route_us")
        self._c_retries = self._tel.counter("gateway/add_retries")
        self._c_blocks = self._tel.counter("gateway/blocks_in")
        # policy plane: decode + engine dispatch + encode per ACT_REQUEST
        self._h_act = self._tel.histogram("gateway/act_us")
        self._add_timeout_s = add_timeout_s
        self._sample_timeout_s = sample_timeout_s
        # fabric.get_batch is single-consumer (parked sub-batches); serialize
        # sample pops across handler threads so the contract holds even if
        # several learner connections appear.
        self._sample_lock = threading.Lock()
        self._poll_s = poll_s
        self._drain_grace_s = drain_grace_s
        self._listener = transport_lib.Listener(
            host, port, backlog=backlog, accept_shm=accept_shm,
            ring_bytes=ring_bytes, poll_s=poll_s)
        self.host, self.port = self._listener.host, self._listener.port
        self._stop = threading.Event()
        self._lock = threading.Lock()      # stats + connection registry
        self._conns: dict[int, transport_lib.Transport] = {}
        self._conn_blocks: dict[int, int] = {}  # routed blocks per accepted
                                                # connection (kept after
                                                # close, for observability)
        self._handlers: list[threading.Thread] = []
        # One device->host transfer + encode per published version, not one
        # per pull per connection: K pulling actors share this payload.
        self._param_cache: tuple[int, bytes] | None = None
        self._param_cache_lock = threading.Lock()
        self.stats = GatewayStats()
        self.error: BaseException | None = None
        self._thread = threading.Thread(target=self._accept_loop, daemon=True,
                                        name="replay-gateway")

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "ReplayGateway":
        self._thread.start()
        return self

    def stop(self, join: bool = True) -> None:
        """Send STOP to every client, close the listener, join handlers."""
        self._stop.set()
        with self._lock:
            conns = list(self._conns.values())
        for conn in conns:
            try:
                conn.send(wire.STOP)
            except (OSError, wire.WireError):
                pass
        self._listener.close()
        if join:
            if self._thread.is_alive():
                self._thread.join()
            for th in list(self._handlers):
                th.join()
            with self._lock:
                conns = list(self._conns.values())
            for conn in conns:
                conn.close()

    @property
    def address(self) -> tuple[str, int]:
        return (self.host, self.port)

    def snapshot(self) -> GatewayStats:
        with self._lock:
            return dataclasses.replace(self.stats)

    def connection_block_counts(self) -> list[int]:
        """Blocks routed per accepted connection (accept order). Lets a
        caller distinguish 'every actor is streaming' from 'one hot actor
        carries the total' — e.g. warm-up gates in benchmarks."""
        with self._lock:
            return list(self._conn_blocks.values())

    def _bump(self, **deltas: int) -> None:
        with self._lock:
            for k, d in deltas.items():
                setattr(self.stats, k, getattr(self.stats, k) + d)

    # -- accept loop --------------------------------------------------------

    def _accept_loop(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    conn = self._listener.accept()
                except OSError:
                    break  # listener closed by stop()
                if conn is None:
                    continue
                cid = id(conn)
                with self._lock:
                    self._conns[cid] = conn
                    self._conn_blocks[cid] = 0
                    self.stats.connections += 1
                th = threading.Thread(
                    target=self._handle, args=(cid, conn),
                    daemon=True, name=f"gateway-conn-{self.stats.connections}")
                self._handlers.append(th)
                th.start()
        except BaseException as e:  # noqa: BLE001
            self.error = e

    # -- per-connection handler ---------------------------------------------

    def _handle(self, cid: int, conn: transport_lib.Transport) -> None:
        drain_deadline = None  # set when stop() is first observed
        in_seen = out_seen = 0
        was_shm = False
        staged_sample: list | None = None  # pre-encoded next reply
        # Decoded PRIORITY_UPDATE frames whose fabric application is
        # deferred: the learner flushes write-backs immediately before its
        # next SAMPLE_REQUEST, so applying eagerly puts the jitted scatter
        # in the reply's critical path. Parking it and peeking for the
        # request first moves the application into the learner's compute
        # window. Application order relative to the reply batch is
        # unchanged — that batch was popped before the update arrived.
        pending_prio: list[tuple] = []

        def account() -> None:
            nonlocal in_seen, out_seen
            bi, bo = conn.bytes_in, conn.bytes_out
            if bi != in_seen or bo != out_seen:
                self._bump(bytes_in=bi - in_seen, bytes_out=bo - out_seen)
                in_seen, out_seen = bi, bo

        def apply_priorities() -> None:
            # Same asynchronous write-back path as the in-process learner;
            # the global keys route to the owning shards. One frame may
            # coalesce several rounds — re-apply each as its own call so
            # the shard eviction clock ticks per round, exactly as if each
            # had shipped separately. A traced frame's id follows every
            # round to the owning shards' writeback spans.
            while pending_prio:
                idx, prios, counts, tid = pending_prio.pop(0)
                off = 0
                for n in counts:
                    n = int(n)
                    self._fabric.write_back(idx[off:off + n],
                                            prios[off:off + n],
                                            trace_id=tid)
                    off += n
                self._bump(priority_updates=len(counts))

        try:
            while True:
                if self._stop.is_set():
                    # Grace window after STOP: clients drain their in-flight
                    # blocks and report BYE counters before we hang up.
                    now = time.monotonic()
                    if drain_deadline is None:
                        drain_deadline = now + self._drain_grace_s
                    elif now >= drain_deadline:
                        break
                got = conn.recv(timeout=0 if pending_prio else self._poll_s)
                account()
                if not was_shm and conn.kind == "shm":
                    was_shm = True
                    self._bump(shm_connections=1)
                if got is None:
                    apply_priorities()  # no request on its heels: apply now
                    continue
                msg_type, payload = got
                if self._fabric is None and msg_type in (
                        wire.ADD_BLOCK, wire.SAMPLE_REQUEST,
                        wire.PRIORITY_UPDATE):
                    raise wire.WireError(
                        f"fabric-plane message {msg_type} on a policy-only "
                        "gateway")
                if msg_type == wire.ACT_REQUEST:
                    self._serve_act(conn, payload)
                elif msg_type == wire.ADD_BLOCK:
                    if self._route_block(cid, payload, conn.last_trace_id):
                        conn.send(wire.ADD_ACK)
                    # else: dropped during shutdown — no ACK; the client is
                    # about to receive STOP anyway
                elif msg_type == wire.SAMPLE_REQUEST:
                    staged_sample = self._serve_sample(conn, staged_sample)
                    apply_priorities()
                elif msg_type == wire.PRIORITY_UPDATE:
                    pending_prio.append(
                        (*wire.decode_priority_update(payload),
                         conn.last_trace_id))
                    self._bump(priority_frames=1)
                elif msg_type == wire.PARAM_PUSH:
                    _version, params = wire.decode_params(payload)
                    # Publish on-device so the K actors pulling this
                    # snapshot don't each re-transfer host leaves. The
                    # store numbers versions itself (single local writer).
                    self._store.publish(jax.device_put(params))
                    self._bump(param_pushes=1)
                elif msg_type == wire.PARAM_PULL:
                    have = wire.decode_json(payload).get("have", -1)
                    self._serve_params(conn, int(have))
                elif msg_type == wire.HELLO:
                    hello = wire.decode_json(payload)
                    if hello.get("protocol") != wire.PROTOCOL_VERSION:
                        raise wire.WireError(
                            f"client protocol {hello.get('protocol')} != "
                            f"{wire.PROTOCOL_VERSION}")
                    if hello.get("reconnects"):
                        # A client that survived a severed transport and
                        # dialed back in — count the comeback, not its
                        # lifetime total (each HELLO reports cumulative).
                        self._bump(client_reconnects=1)
                    elif hello.get("platform") == "cpu":
                        self._bump(cpu_clients=1)
                elif msg_type == wire.BYE:
                    stats = wire.decode_json(payload)
                    self._bump(
                        client_rollouts=int(stats.get("rollouts", 0)),
                        client_blocked=int(stats.get("blocked", 0)),
                        learner_byes=1 if stats.get("learner") else 0)
                    break
                else:
                    raise wire.WireError(f"unexpected message {msg_type}")
        except EOFError:
            pass  # client went away; its blocks are already routed
        except wire.WireError:
            self._bump(wire_errors=1)
        except OSError:
            pass  # transport torn down under us during stop()
        except BaseException as e:  # noqa: BLE001
            self.error = e
        finally:
            # A connection may end (BYE, EOF, stop) with a parked update —
            # the client's final flush-then-BYE must still land in the
            # fabric, whoever wins the shutdown race.
            try:
                apply_priorities()
            except BaseException as e:  # noqa: BLE001
                if self.error is None:
                    self.error = e
            account()
            with self._lock:
                self._conns.pop(cid, None)
            conn.close()

    def _route_block(self, cid: int, payload: memoryview,
                     trace_id: int = 0) -> bool:
        """Decode and push into the fabric, holding the client's ACK (and
        therefore its in-flight window) open while the shard queue is full.
        False only when the block was dropped because stop() interrupted
        the retry loop. A traced block (nonzero wire-header id) records a
        "gateway" span — decode plus route, including backpressure wait —
        and hands its id to the fabric for the shard's add span."""
        t0 = time.perf_counter()
        block = wire.decode_block(payload)
        n = int(block.priorities.shape[0])
        while not self._fabric.add(block, timeout=self._add_timeout_s,
                                   trace_id=trace_id):
            self._bump(add_retries=1)
            self._c_retries.inc()
            if self._stop.is_set():
                return False
        us = 1e6 * (time.perf_counter() - t0)
        self._h_route.record(us)
        self._c_blocks.inc()
        if trace_id:
            self._tel.tracer.record("gateway", trace_id, us)
        with self._lock:
            self.stats.blocks_in += 1
            self.stats.transitions_in += n
            self._conn_blocks[cid] += 1
        return True

    def _serve_act(self, conn: transport_lib.Transport,
                   payload: memoryview) -> None:
        """One policy-plane rollout: decode the client's slice, block in the
        shared engine (the batching — every concurrently-blocked handler
        lands in the same compiled dispatch), reply with the advanced slice.
        ``STOP`` answers a request the engine refused because the runtime is
        shutting down; the client treats it like the fabric-plane STOP."""
        if self._inference is None:
            raise wire.WireError("ACT_REQUEST on a gateway without an "
                                 "inference engine (--serve-policy not set)")
        t0 = time.perf_counter()
        aslice, sid = wire.decode_act_request(payload, self._act_example)
        res = self._inference.act(aslice, sid)
        if res is None:
            conn.send(wire.STOP)
            return
        out_slice, block, metrics = res
        conn.send(wire.ACT_RESULT,
                  wire.encode_act_result(out_slice, block, metrics))
        self._h_act.record(1e6 * (time.perf_counter() - t0))
        self._bump(act_requests=1)

    def _serve_sample(self, conn: transport_lib.Transport,
                      staged: list | None = None) -> list | None:
        """Ship one prioritized batch; an empty payload tells the learner
        the fabric is starved (poll again) — backpressure in the sampling
        direction, mirroring the ADD_ACK window on ingest.

        Returns the next reply, staged: after answering, the handler pops
        and encodes the *next* batch immediately, so the fabric's prefetch
        refill (a jitted sample + host transfer that competes for the same
        cores) runs while the learner is busy computing on the batch just
        shipped, not serially inside the next request. The pop order is the
        fabric's prefetch-queue order either way — staging moves work in
        time, never reorders or drops a batch the learner will see."""
        if staged is None:
            with self._sample_lock:
                batch = self._fabric.get_batch(timeout=self._sample_timeout_s)
            served = batch is not None
            staged = wire.encode_sample_batch_iov(batch) if served else []
        else:
            served = True
        conn.send(wire.SAMPLE_BATCH, staged)
        self._bump(sample_requests=1,
                   sample_sends=int(served),
                   sample_starved=int(not served))
        with self._sample_lock:
            nxt = self._fabric.get_batch(timeout=0)
        return None if nxt is None else wire.encode_sample_batch_iov(nxt)

    def _encoded_params(self, snap) -> bytes:
        with self._param_cache_lock:
            cached = self._param_cache
            if cached is not None and cached[0] == snap.version:
                return cached[1]
            payload = wire.encode_params(snap.version, snap.params)
            self._param_cache = (snap.version, payload)
            return payload

    def _serve_params(self, conn: transport_lib.Transport, have: int) -> None:
        snap = self._store.get()
        # Bump before the reply ships: a client that has read the reply
        # must see the stats already counted (tests and operators poll
        # snapshot() right after a round trip).
        if snap.version > have:
            payload = self._encoded_params(snap)
            self._bump(param_pulls=1, param_sends=1)
            conn.send(wire.PARAM, payload)
        else:
            self._bump(param_pulls=1)
            conn.send(wire.PARAM_UNCHANGED,
                      wire.encode_json({"version": snap.version}))
