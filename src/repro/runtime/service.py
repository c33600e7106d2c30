"""Host-side replay shard: one slice of the paper's central replay memory.

One owner thread holds a device-resident ``ReplayState`` and is the only
code that ever touches it, so replay mutation needs no locks. Traffic flows
through three queues, mirroring Fig. 1's arrows:

* ``add``       (actors → replay, bounded) — blocks of n-step transitions
  with actor-side initial priorities. A bounded depth gives *backpressure*:
  when the learner + service fall behind, actors block on ``add`` instead of
  overrunning memory.
* ``samples``   (replay → learner, bounded) — prefetched prioritized
  batches. Depth 2 double-buffers the learner: batch k+1 is sampled while
  the learner consumes batch k. Empty queue = *starved learner*.
* ``updates``   (learner → replay) — priority write-backs; applying one
  counts as a learner step for the periodic eviction clock (paper: evict
  every 100 learning steps).

A single ``ReplayShard`` *is* PR 1's ``ReplayService`` (the name is kept as
an alias); ``repro.runtime.fabric.ReplayFabric`` composes N of them into the
sharded replay fabric, routing actor blocks round-robin and merging per-shard
sub-samples on the learner side. When a fabric owns several shards it builds
one set of jitted phase functions (``make_shard_fns``) and passes it to every
shard, so N shards share one compilation cache entry per op.

Known (and intended) relaxation vs the lockstep driver: a prefetched batch
may reference slots that a concurrent add overwrites before the learner's
priorities come back. The paper's distributed system has the same window —
replay content is allowed to be slightly stale relative to the learner.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import replay as replay_lib
from repro.obs import Telemetry
from repro.runtime import phases

# Owner-loop ops between refreshes of the host-visible ``replay_size`` (each
# refresh is a device sync; counters stay exact, size is near-real-time).
_SIZE_REFRESH_OPS = 32

# Per-op latency is *sampled*: every Nth op of each kind is synced
# (block_until_ready) and timed, and the measurement recorded into the
# shard's latency histogram (``repro.obs``). Sampling keeps the owner
# loop's async dispatch pipeline intact between measurements; the sync
# makes the sampled number an honest applied latency (it absorbs any
# backlog the op queued behind). Ops carrying a trace id are always
# synced — a traced span must be an honest duration.
_LATENCY_SAMPLE_EVERY = 8

# The owner thread's span per op kind (``repro.obs.trace``).
_SPAN = {"add": "shard/add", "sample": "shard/sample",
         "writeback": "shard/writeback"}


@dataclasses.dataclass
class ServiceStats:
    blocks_added: int = 0          # transition blocks applied to replay
    transitions_added: int = 0     # transitions offered to the replay op (in
                                   # alloc/prioritized mode a full buffer may
                                   # drop overflow lanes device-side; compare
                                   # ReplayState.total_added for stored count)
    batches_sampled: int = 0       # prioritized batches prefetched
    updates_applied: int = 0       # priority write-backs applied by this
                                   # shard (aggregated fabric stats sum these:
                                   # one learner step touches every shard)
    replay_size: int = 0           # live items (refreshed periodically while
                                   # running; exact after stop())
    add_us: float = 0.0            # mean applied-latency per op kind, in
    sample_us: float = 0.0         # microseconds — a derived view of the
    writeback_us: float = 0.0      # shard's obs histograms (0.0 until the
                                   # first sampled measurement; fabric
                                   # aggregation op-count-weights, not sums)
    h2d_us: float = 0.0            # mean *issue* latency of the ingest
                                   # stager's async device_put (the DMA
                                   # itself overlaps the previous add; 0.0
                                   # when staging is off or passes through)
    blocks_staged: int = 0         # blocks whose H2D put was issued ahead
                                   # by the ingest stager (0 on CPU, where
                                   # staging passes through)

    # Which counter weights each latency field when shard snapshots are
    # folded: a shard's mean only counts in proportion to the ops behind it.
    _US_WEIGHTS = {"add_us": "blocks_added", "sample_us": "batches_sampled",
                   "writeback_us": "updates_applied",
                   "h2d_us": "blocks_staged"}

    @classmethod
    def aggregate(cls, snaps: "list[ServiceStats]") -> "ServiceStats":
        """Combine per-shard snapshots into one view: counters sum, the
        per-op latency means (``*_us``) average weighted by each shard's
        op count — an unweighted mean would let a nearly idle shard (one
        measurement) drag the fabric view as hard as a hot one. Lives with
        the dataclass so every holder of shard snapshots (the fabric,
        sample sources, benches) folds them the same way."""
        agg = cls()
        for f in dataclasses.fields(cls):
            vals = [getattr(s, f.name) for s in snaps]
            if f.name.endswith("_us"):
                wfield = cls._US_WEIGHTS[f.name]
                pairs = [(v, getattr(s, wfield))
                         for v, s in zip(vals, snaps) if v > 0.0]
                wsum = sum(w for _, w in pairs)
                if wsum > 0:
                    setattr(agg, f.name,
                            sum(v * w for v, w in pairs) / wsum)
                elif pairs:
                    # measurements exist but op counters are still zero
                    # (snapshot raced the first _bump): plain mean.
                    setattr(agg, f.name,
                            sum(v for v, _ in pairs) / len(pairs))
            else:
                setattr(agg, f.name, sum(vals))
        return agg


class ShardFns(NamedTuple):
    """Jitted phase functions for one shard geometry. Built once per fabric
    (or per standalone shard) and shared, so N identical shards trace and
    compile each op exactly once. The mutating ops (``add``/``writeback``)
    donate the incoming ``ReplayState``, so the storage pytree and sum-tree
    update in place instead of being copied every call — each shard's owner
    thread is the state's only holder, so the donated buffers are never
    observed again."""
    add: Any
    sample: Any
    writeback: Any
    can_sample: Any
    split: Any


def make_shard_fns(cfg, batch_size: int) -> ShardFns:
    """The shard's five programs, each a named function so its device ops
    carry a stable module name (``jit_replay_add``, ...) in a profile."""
    rcfg = cfg.replay

    def replay_add(st, block):
        return phases.replay_add(cfg, st, block)

    def replay_sample(st, rng):
        return phases.replay_sample(cfg, st, rng, batch_size)

    def replay_writeback(st, idx, prios, step, rng):
        return phases.priority_writeback(cfg, st, idx, prios, step, rng)

    def replay_can_sample(st):
        return replay_lib.can_sample(rcfg, st)

    def replay_rng_split(k):
        return jax.random.split(k)

    return ShardFns(
        add=jax.jit(replay_add, donate_argnums=(0,)),
        sample=jax.jit(replay_sample),
        writeback=jax.jit(replay_writeback, donate_argnums=(0,)),
        can_sample=jax.jit(replay_can_sample),
        split=jax.jit(replay_rng_split),
    )


class ReplayShard:
    """Single replay shard behind double-buffered host-side queues."""

    def __init__(self, cfg, replay_state: replay_lib.ReplayState, *,
                 batch_size: int | None = None, add_queue_depth: int = 4,
                 sample_queue_depth: int = 2, seed: int = 0,
                 shard_id: int = 0, fns: ShardFns | None = None,
                 poll_s: float = 0.05, ingest_staging: bool = False,
                 stager: "Any | None" = None,
                 telemetry: Telemetry | None = None):
        self._cfg = cfg
        # Private copy: add/writeback *donate* the state into jit, deleting
        # its buffers. Copying here keeps the caller's reference readable
        # (and lets one template state seed several shards) at a one-time
        # pytree-copy cost.
        self._state = jax.tree.map(jnp.array, replay_state)
        self._rng = jax.random.key(seed)
        self._fns = fns or make_shard_fns(cfg, batch_size or cfg.batch_size)
        self._poll_s = poll_s
        self.shard_id = shard_id
        # Ingest staging (mirror of the sample plane's StagedSource): the
        # owner loop issues block k+1's async device_put before dispatching
        # block k's add, hiding H2D behind the update. An explicit ``stager``
        # (tests) wins over the flag; the default BlockStager passes through
        # on CPU hosts where a put would serialize a redundant copy.
        if stager is None and ingest_staging:
            from repro.runtime.sources import BlockStager
            stager = BlockStager()
        self._stager = stager

        self._ready = False  # sticky min-fill latch (see _can_sample)
        self._add_q: queue.Queue = queue.Queue(maxsize=add_queue_depth)
        self._sample_q: queue.Queue = queue.Queue(maxsize=sample_queue_depth)
        self._update_q: queue.Queue = queue.Queue()
        # Checkpoint requests (boxes awaiting a consistent host-side capture)
        # and the chaos harness's freeze hook: a paused owner loop models a
        # stalled shard (GC pause, wedged device) without killing it.
        self._ckpt_q: queue.Queue = queue.Queue()
        self._paused = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run_guarded, daemon=True,
                                        name=f"replay-shard-{shard_id}")
        self._stats_lock = threading.Lock()
        self._ops_since_size = 0
        self._op_seq = {"add": 0, "sample": 0, "writeback": 0}
        self.stats = ServiceStats()
        self.error: BaseException | None = None

        # Telemetry: instruments live in the (possibly run-shared)
        # registry under a per-shard namespace; the *_us histograms are
        # the source of truth the ServiceStats *_us fields derive from.
        self._tel = telemetry if telemetry is not None else Telemetry.local()
        pre = f"shard{shard_id}"
        self._hists = {k: self._tel.histogram(f"{pre}/{k}_us")
                       for k in ("add", "sample", "writeback", "h2d")}
        self._g_add_q = self._tel.gauge(f"{pre}/add_queue_depth")
        self._g_sample_q = self._tel.gauge(f"{pre}/sample_queue_depth")
        self._g_update_q = self._tel.gauge(f"{pre}/update_queue_depth")
        self._g_size = self._tel.gauge(f"{pre}/replay_size")
        self._c_add_blocked = self._tel.counter(f"{pre}/add_backpressure")
        self._c_starved = self._tel.counter(f"{pre}/get_batch_starved")
        # microseconds the owner thread sat blocked on device results
        self._c_sync_wait = self._tel.counter(f"{pre}/sync_wait_us")
        self._tracer = self._tel.tracer

    @property
    def learner_steps(self) -> int:
        """Eviction-clock position: one applied write-back == one step."""
        return self.stats.updates_applied

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "ReplayShard":
        self._thread.start()
        return self

    def stop(self, join: bool = True) -> None:
        """Ask the shard to drain pending work and exit."""
        self._stop.set()
        if join and self._thread.is_alive():
            self._thread.join()

    @property
    def replay_state(self) -> replay_lib.ReplayState:
        """Final replay state; only meaningful after ``stop()``."""
        return self._state

    # -- checkpoint / restore ------------------------------------------------

    # Everything the paper's Appendix F asks a stateful part to save:
    # replay contents + sum tree, the shard's rng stream, and the counters
    # that drive behavior — ``updates_applied`` is the eviction clock
    # (write-backs pass ``updates_applied + 1`` as the step) and
    # ``transitions_added`` feeds the min-fill short-circuit. Restoring
    # all of them makes post-restore sampling math bit-identical to a run
    # that never stopped.
    _CKPT_COUNTERS = ("blocks_added", "transitions_added", "batches_sampled",
                      "updates_applied")

    def _capture(self) -> dict:
        st = jax.device_get(self._state)
        return {
            "replay": {"storage": st.storage, "tree": st.tree,
                       "write_pos": st.write_pos, "size": st.size,
                       "total_added": st.total_added},
            "rng": jax.device_get(jax.random.key_data(self._rng)),
            "counters": {k: np.int64(getattr(self.stats, k))
                         for k in self._CKPT_COUNTERS},
        }

    def checkpoint_state(self, timeout_s: float = 60.0) -> dict:
        """Consistent host-side snapshot of everything needed to rebuild
        this shard (plain numpy pytree, ready for ``checkpoint.save``).

        The mutating ops donate ``ReplayState`` into jit, so only the owner
        thread may observe it: a live shard services the request *between*
        ops at its next loop pass; a stopped (or not yet started) shard is
        captured directly. Safe to call from any thread."""
        self._check_alive()
        if not self._thread.is_alive():
            return self._capture()
        box: queue.Queue = queue.Queue(maxsize=1)
        self._ckpt_q.put(box)
        try:
            return box.get(timeout=timeout_s)
        except queue.Empty:
            self._check_alive()
            if not self._thread.is_alive():
                return self._capture()
            raise RuntimeError(
                f"replay shard {self.shard_id} did not answer a checkpoint "
                f"request within {timeout_s}s") from None

    def restore(self, ckpt: dict) -> None:
        """Adopt a ``checkpoint_state`` capture. Must be called before
        ``start()`` (the owner thread is the state's only holder once it
        runs). Restores the replay pytree, the rng stream, and the
        behavioral counters, so the first op after restore continues the
        interrupted run bit-for-bit."""
        if self._thread.is_alive():
            raise RuntimeError("restore() must run before start()")
        rep = ckpt["replay"]
        self._state = replay_lib.ReplayState(
            storage=jax.tree.map(jnp.asarray, rep["storage"]),
            tree=jnp.asarray(rep["tree"]),
            write_pos=jnp.asarray(rep["write_pos"]),
            size=jnp.asarray(rep["size"]),
            total_added=jnp.asarray(rep["total_added"]))
        self._rng = jax.random.wrap_key_data(jnp.asarray(ckpt["rng"]))
        with self._stats_lock:
            for k in self._CKPT_COUNTERS:
                setattr(self.stats, k, int(ckpt["counters"][k]))
            self.stats.replay_size = int(rep["size"])
        self._ready = False  # re-derived from the restored state on demand

    # -- chaos hooks ---------------------------------------------------------

    def pause(self) -> None:
        """Freeze the owner loop (fault injection: a stalled shard owner).
        Queues keep filling — callers see backpressure/starvation exactly as
        they would behind a wedged thread — until :meth:`unpause`."""
        self._paused.set()

    def unpause(self) -> None:
        self._paused.clear()

    # -- observability ------------------------------------------------------

    def snapshot(self) -> ServiceStats:
        """Consistent copy of the running counters, safe to call from any
        thread at any time. ``replay_size`` is refreshed by the owner loop
        every ~``_SIZE_REFRESH_OPS`` applied ops (exact after ``stop()``);
        the other counters are exact at the moment of the snapshot. The
        ``*_us`` fields are derived views — the running mean of the
        shard's latency histograms — kept on the dataclass so benches and
        progress logs read one object."""
        with self._stats_lock:
            snap = dataclasses.replace(self.stats)
        for kind, hist in self._hists.items():
            setattr(snap, f"{kind}_us", hist.mean)
        return snap

    # -- actor side ---------------------------------------------------------

    def _check_alive(self) -> None:
        if self.error is not None:
            raise RuntimeError(
                f"replay shard {self.shard_id} died") from self.error

    def add(self, block: phases.TransitionBlock,
            timeout: float | None = None, trace_id: int = 0) -> bool:
        """Enqueue a transition block; False when the bounded queue stayed
        full for ``timeout`` seconds (the caller is being backpressured).
        ``timeout=None`` uses the ``poll_s`` configured at construction
        (the runner instead passes ``AsyncConfig.add_poll_s`` explicitly).
        A nonzero ``trace_id`` rides the queue with the block and marks
        its apply as a traced "add" span."""
        self._check_alive()
        try:
            self._add_q.put((block, trace_id),
                            timeout=self._poll_s if timeout is None
                            else timeout)
            return True
        except queue.Full:
            self._c_add_blocked.inc()
            return False

    # -- learner side -------------------------------------------------------

    def get_batch(self, timeout: float | None = None):
        """Next prefetched prioritized batch, or None if starved (replay
        below min-fill, or sampling not keeping up with the learner)."""
        self._check_alive()
        try:
            return self._sample_q.get(timeout=self._poll_s if timeout is None
                                      else timeout)
        except queue.Empty:
            self._c_starved.inc()
            return None

    def write_back(self, indices: jax.Array, priorities: jax.Array,
                   trace_id: int = 0) -> None:
        """Queue a priority write-back (Alg. 2 l.8); applied asynchronously.
        A nonzero ``trace_id`` marks the apply as a traced "writeback"
        span, closing the batch's sample → learn → writeback chain."""
        self._update_q.put((indices, priorities, trace_id))

    # -- owner loop ---------------------------------------------------------

    def _bump(self, **deltas: int) -> None:
        with self._stats_lock:
            for k, d in deltas.items():
                setattr(self.stats, k, getattr(self.stats, k) + d)
            self._ops_since_size += 1
            refresh = self._ops_since_size >= _SIZE_REFRESH_OPS
            if refresh:
                self._ops_since_size = 0
        if refresh:
            # Outside the lock: int() blocks on the device, and readers only
            # need the counters to stay consistent, not the size to be fresh.
            size = int(self._sync(self._state.size))
            with self._stats_lock:
                self.stats.replay_size = size
            self._g_size.set(size)

    def _timed(self, kind: str, fn, *args, trace_id: int = 0):
        """Dispatch an op inside its ``shard/<kind>`` span; every
        ``_LATENCY_SAMPLE_EVERY``th call of each kind — and every traced
        call — is synced and timed into the shard's ``<kind>_us``
        histogram (hot-path regressions surface in runner progress logs,
        bench counters, and the obs report). A traced call's span is
        buffered under the block's or batch's id, so the chain stays
        linked across planes."""
        self._op_seq[kind] += 1
        with self._tracer.span(_SPAN[kind], trace_id, shard=self.shard_id):
            if self._op_seq[kind] % _LATENCY_SAMPLE_EVERY and not trace_id:
                return fn(*args)
            t0 = time.perf_counter()
            out = self._sync(fn(*args))
            self._hists[kind].record(1e6 * (time.perf_counter() - t0))
            return out

    def _sync(self, out):
        """Wait for a device result: every host wait of the owner thread
        goes through here (``shard/sync`` span, ``sync_wait_us``)."""
        t0 = time.perf_counter()
        with self._tracer.span("shard/sync"):
            jax.block_until_ready(out)
        self._c_sync_wait.inc(int(1e6 * (time.perf_counter() - t0)))
        return out

    def _stage_block(self, block: phases.TransitionBlock):
        """Issue the async H2D put for a block (no-op without a stager).

        The put's *issue* time feeds the ``h2d_us`` histogram —
        deliberately not synced: the transfer itself is the thing being
        overlapped, so timing its completion would serialize exactly what
        staging hides."""
        if self._stager is None:
            return block
        before = self._stager.blocks_staged
        t0 = time.perf_counter()
        staged = self._stager.stage(block)
        us = 1e6 * (time.perf_counter() - t0)
        if self._stager.blocks_staged == before:  # passed through
            return staged
        with self._stats_lock:
            self.stats.blocks_staged += 1
        self._hists["h2d"].record(us)
        return staged

    def _apply_add(self, block: phases.TransitionBlock,
                   trace_id: int = 0) -> None:
        self._state = self._timed("add", self._fns.add, self._state, block,
                                  trace_id=trace_id)
        self._bump(blocks_added=1,
                   transitions_added=int(block.priorities.shape[0]))

    def _can_sample(self) -> bool:
        """Min-fill gate with a sticky latch: the device-side check (a host
        sync) runs only until it first passes. Afterwards FIFO adds keep the
        buffer full and eviction trims to ``soft_cap >= min_fill``, so the
        gate can't re-close in any supported config. Before the gate can
        possibly pass, a host-side counter short-circuits the device sync:
        live size never exceeds the transitions offered, so while
        ``transitions_added < min_fill`` the owner loop stays sync-free."""
        if not self._ready:
            if self.stats.transitions_added < self._cfg.replay.min_fill:
                return False
            self._ready = bool(self._sync(self._fns.can_sample(self._state)))
        return self._ready

    def _next_rng(self) -> jax.Array:
        self._rng, sub = self._fns.split(self._rng)
        return sub

    def _run_guarded(self) -> None:
        # A dead shard must not fail silently: record the error so actor /
        # learner calls raise instead of spinning against a stalled queue.
        try:
            self._run()
        except BaseException as e:  # noqa: BLE001
            self.error = e

    def _serve_checkpoints(self) -> None:
        """Answer pending checkpoint requests (owner thread only): between
        ops the state is quiescent, so the capture is consistent."""
        while True:
            try:
                box = self._ckpt_q.get_nowait()
            except queue.Empty:
                return
            box.put(self._capture())

    def _run(self) -> None:
        while True:
            while self._paused.is_set() and not self._stop.is_set():
                time.sleep(0.001)  # frozen by fault injection
            self._serve_checkpoints()
            progressed = False
            # Queue-depth gauges once per loop pass: cheap (three qsize
            # reads), and the interval sink turns them into the queue
            # pressure row of the obs report.
            self._g_add_q.set(self._add_q.qsize())
            self._g_sample_q.set(self._sample_q.qsize())
            self._g_update_q.set(self._update_q.qsize())

            # 1. Priority write-backs first: they advance the eviction clock
            # and keep the sampling distribution fresh (Alg. 2 l.8).
            while True:
                try:
                    idx, prios, tid = self._update_q.get_nowait()
                except queue.Empty:
                    break
                step = self.stats.updates_applied + 1
                self._state = self._timed(
                    "writeback", self._fns.writeback,
                    self._state, idx, prios, step, self._next_rng(),
                    trace_id=tid)
                self._bump(updates_applied=1)
                progressed = True

            # 2. Refill the prefetch buffer (Alg. 2 l.4) before touching the
            # add backlog: the learner is the scarce consumer the paper
            # protects, and a starved learner wastes more than a briefly
            # staler sampling distribution costs.
            while not self._sample_q.full() and self._can_sample():
                batch = self._timed("sample", self._fns.sample,
                                    self._state, self._next_rng())
                try:
                    self._sample_q.put_nowait(batch)
                except queue.Full:
                    break
                self._bump(batches_sampled=1)
                progressed = True

            # 3. Drain actor blocks (Alg. 1 l.10-11) — boundedly: under
            # sustained actor pressure an open-ended drain would never
            # yield back to steps 1-2 and the learner would starve behind
            # a permanently non-empty add queue. One queue's worth per
            # iteration keeps ingest at full rate while the prefetch/
            # write-back steps stay scheduled (an unbounded queue —
            # maxsize 0 — gets a fixed chunk instead). The drain is
            # *pipelined* when an ingest stager is attached: block k+1's
            # async device_put is issued before block k's add dispatches,
            # so the H2D transfer overlaps the in-place update; the last
            # staged block is flushed when the queue runs dry (holding it
            # across iterations would stall min-fill under sparse traffic).
            staged_prev = None
            for _ in range(self._add_q.maxsize or _SIZE_REFRESH_OPS):
                try:
                    block, tid = self._add_q.get_nowait()
                except queue.Empty:
                    break
                staged_next = (self._stage_block(block), tid)
                if staged_prev is not None:
                    self._apply_add(*staged_prev)
                staged_prev = staged_next
                progressed = True
            if staged_prev is not None:
                self._apply_add(*staged_prev)

            if self._stop.is_set():
                if self._add_q.empty() and self._update_q.empty():
                    break
                continue
            if not progressed:
                # Idle: park on the add queue so actors wake us immediately.
                try:
                    with self._tracer.span("shard/idle"):
                        block, tid = self._add_q.get(timeout=0.002)
                except queue.Empty:
                    continue
                # A lone block has no overlap partner, but staging it still
                # turns the in-jit transfer into an explicit counted put.
                self._apply_add(self._stage_block(block), tid)

        size = int(self._state.size)
        with self._stats_lock:
            self.stats.replay_size = size
        self._g_size.set(size)
        # A checkpoint request racing the exit would otherwise hang its
        # caller until the timeout; serve it here, the state is final.
        self._serve_checkpoints()


# PR 1 name for the single-shard service; the owner loop is unchanged.
ReplayService = ReplayShard
