"""Sharded replay fabric: N replay shards behind one actor/learner facade.

The paper scales the central replay memory by sharding it (§3: "the replay
memory may be distributed across many machines"); PR 1's single
``ReplayService`` becomes here an N-shard *fabric* with the same interface:

* **Ingest** — ``add`` routes actor ``TransitionBlock``s round-robin across
  shards (a fetch-and-increment ticket, so concurrent actors spread load
  evenly; under backpressure a failed attempt retries on the next shard in
  the rotation). Each shard's owner thread applies its own adds, so ingest
  bandwidth scales with shard count.
* **Sample** — ``get_batch`` assembles one learner batch from per-shard
  sub-samples: every shard continuously prefetches ``batch_size /
  num_shards``-item sub-batches (equal quotas, as in the synchronous
  ``shard_map`` driver), and the fabric concatenates one sub-batch per shard,
  re-weighting with ``repro.core.sampling.merged_is_weights`` — the *same*
  formula the sync path computes with ``psum``/``pmax`` collectives.
* **Write-back** — sampled items carry global ``(shard, slot)`` keys encoded
  as ``global_index = shard_id * shard_capacity + slot``. ``write_back``
  decodes the key and scatters each learner priority to the owning shard's
  update queue.

Global min-fill semantics match the sync driver's ``pmin`` gate: a merged
batch is only produced once *every* shard passes its (scaled) min-fill.

Single-consumer contract: ``get_batch``/``write_back`` are called from one
learner thread (partial sub-batch sets are parked between calls without
locking); ``add`` is safe from any number of actor threads.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import threading
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import replay as replay_lib, sampling
from repro.obs import Telemetry
from repro.runtime import phases
from repro.runtime.service import (ReplayShard, ServiceStats, ShardFns,
                                   make_shard_fns)


# A merged learner batch is exactly the learner-plane contract: global
# (shard, slot) keys, items, globally-corrected IS weights. The historical
# fabric-local name is kept as an alias.
FabricBatch = sampling.LearnerBatch


def shard_replay_config(rcfg: replay_lib.ReplayConfig,
                        num_shards: int) -> replay_lib.ReplayConfig:
    """Split one logical replay config across ``num_shards`` equal shards.

    Total capacity is preserved exactly — which requires the per-shard slice
    ``capacity / num_shards`` to itself be a power of two (capacity already
    is one, so in practice: a power-of-two shard count); anything else would
    silently inflate or shrink the configured memory, so it is rejected.
    Soft cap and min-fill are both ceil-rounded: ceil is monotone, so a base
    config with ``soft_cap >= min_fill`` keeps that invariant per shard (the
    sticky min-fill latch in ``ReplayShard._can_sample`` relies on it).
    """
    if num_shards == 1:
        return rcfg
    cap, rem = divmod(rcfg.capacity, num_shards)
    if rem or cap < 2 or cap & (cap - 1):
        raise ValueError(
            f"capacity {rcfg.capacity} cannot be split into {num_shards} "
            f"power-of-two shards — use a power-of-two shard count that "
            f"divides the capacity")
    soft = (None if rcfg.soft_capacity is None
            else max(1, math.ceil(rcfg.soft_capacity / num_shards)))
    return dataclasses.replace(
        rcfg, capacity=cap, soft_capacity=soft,
        min_fill=max(1, math.ceil(rcfg.min_fill / num_shards)))


@functools.lru_cache(maxsize=None)
def _partition_fn(num_shards: int, shard_capacity: int):
    """Jitted write-back partition for one fabric geometry: stable-sort the
    global keys by owning shard and count the per-shard segment lengths, all
    on device. The host then transfers only the tiny count vector and hands
    each shard a lazy slice of the sorted device arrays — one device→host
    sync per write-back instead of materializing the whole index batch with
    ``np.asarray`` every learner step."""
    @jax.jit
    def replay_route_writeback(indices, priorities):
        sids = indices // shard_capacity
        order = jnp.argsort(sids, stable=True)
        counts = jnp.sum(sids[:, None] == jnp.arange(num_shards)[None, :],
                         axis=0)
        return (indices - sids * shard_capacity)[order], priorities[order], counts
    return replay_route_writeback


@functools.lru_cache(maxsize=None)
def _merge_fn(beta: float, shard_capacity: int):
    """Jitted sub-sample merge for one (beta, per-shard-capacity) geometry,
    cached so same-geometry fabric instances share one compilation (the
    shard count specializes via the traced tuple length)."""
    @jax.jit
    def replay_merge(subs):
        leaf = jnp.stack([b.leaf_mass for b in subs])
        totals = jnp.stack([b.total_mass for b in subs])
        sizes = jnp.stack([b.size for b in subs])
        w = sampling.merged_is_weights(leaf, totals, sizes, beta).reshape(-1)
        idx = jnp.concatenate(
            [b.indices + k * shard_capacity for k, b in enumerate(subs)])
        items = jax.tree.map(lambda *xs: jnp.concatenate(xs),
                             *[b.items for b in subs])
        return idx, items, w
    return replay_merge


class ReplayFabric:
    """N ``ReplayShard``s + round-robin ingest + learner-side batch merge."""

    def __init__(self, cfg, item_example: Any, *, num_shards: int = 1,
                 batch_size: int | None = None, add_queue_depth: int = 4,
                 sample_queue_depth: int = 2, seed: int = 0,
                 poll_s: float = 0.05, fns: ShardFns | None = None,
                 ingest_staging: bool = False, telemetry=None):
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        batch = batch_size or cfg.batch_size
        if batch % num_shards:
            raise ValueError(
                f"batch_size {batch} must be divisible by num_shards "
                f"{num_shards} (equal per-shard sample quotas)")
        self.num_shards = num_shards
        self.sub_batch = batch // num_shards
        rcfg = shard_replay_config(cfg.replay, num_shards)
        self._cfg = cfg if num_shards == 1 else dataclasses.replace(
            cfg, replay=rcfg,
            # Prioritized eviction fires on every shard per learner step, so
            # the per-event victim count must shrink with the per-shard
            # buffer or N shards would evict N x the configured amount.
            evict_num=max(1, (cfg.evict_num or batch) // num_shards))
        self.shard_capacity = rcfg.capacity
        # One set of jitted fns for all shards: identical geometry means one
        # trace/compile per op, not one per shard. Callers rebuilding
        # same-geometry fabrics (benches, tests) can pass ``fns`` to reuse
        # compilations across instances too.
        fns = fns or make_shard_fns(self._cfg, self.sub_batch)
        self.fns = fns
        self.shards = [
            ReplayShard(self._cfg, replay_lib.init(rcfg, item_example),
                        batch_size=self.sub_batch,
                        add_queue_depth=add_queue_depth,
                        sample_queue_depth=sample_queue_depth,
                        seed=seed + k, shard_id=k, fns=fns, poll_s=poll_s,
                        ingest_staging=ingest_staging, telemetry=telemetry)
            for k in range(num_shards)]
        self._poll_s = poll_s
        self._tracer = (telemetry or Telemetry.local()).tracer
        self._ticket = 0
        self._ticket_lock = threading.Lock()
        self._pending: list[replay_lib.SampleBatch | None] = (
            [None] * num_shards)
        # Shared across same-geometry fabric instances (like ShardFns): the
        # merge only depends on beta and the per-shard capacity.
        self._merge = _merge_fn(rcfg.beta, rcfg.capacity)
        self._part = _partition_fn(num_shards, rcfg.capacity)

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "ReplayFabric":
        for sh in self.shards:
            sh.start()
        return self

    def stop(self, join: bool = True) -> None:
        for sh in self.shards:       # signal all first so drains overlap
            sh.stop(join=False)
        if join:
            for sh in self.shards:
                sh.stop(join=True)

    @property
    def error(self) -> BaseException | None:
        for sh in self.shards:
            if sh.error is not None:
                return sh.error
        return None

    def replay_states(self) -> list[replay_lib.ReplayState]:
        """Final per-shard states; only meaningful after ``stop()``."""
        return [sh.replay_state for sh in self.shards]

    # -- checkpoint / restore ------------------------------------------------

    def checkpoint_shards(self) -> list[dict]:
        """Consistent host-side captures of every shard (safe while hot:
        each owner thread answers between ops). The list is the fabric's
        contribution to a run snapshot — restore into a same-geometry
        fabric with :meth:`restore_shards` before ``start()``."""
        return [sh.checkpoint_state() for sh in self.shards]

    def restore_shards(self, ckpts: list) -> None:
        if len(ckpts) != self.num_shards:
            raise ValueError(
                f"checkpoint has {len(ckpts)} shards, fabric has "
                f"{self.num_shards}: resume requires the same "
                f"replay_shards geometry the snapshot was taken with")
        for sh, ckpt in zip(self.shards, ckpts):
            sh.restore(ckpt)

    # -- observability ------------------------------------------------------

    def snapshot(self) -> ServiceStats:
        """Aggregated counters across shards, safe while running. Counters
        sum per-shard values (note ``updates_applied`` counts per-shard
        write-back applications: one learner step touches every shard);
        the per-op latency means (``*_us``) average over the shards that
        have a measurement, weighted by each shard's op count."""
        return ServiceStats.aggregate(self.shard_snapshots())

    def shard_snapshots(self) -> list[ServiceStats]:
        return [sh.snapshot() for sh in self.shards]

    @property
    def stats(self) -> ServiceStats:
        return self.snapshot()

    # -- actor side ---------------------------------------------------------

    def add(self, block: phases.TransitionBlock,
            timeout: float | None = None, trace_id: int = 0) -> bool:
        """Route a block to the next shard in the rotation; False when that
        shard's bounded queue stayed full (backpressure — the rotation has
        already advanced, so a retry lands on the next shard). A nonzero
        ``trace_id`` follows the block to the owning shard's add span."""
        n = int(block.priorities.shape[0])
        if n > self.shard_capacity:
            raise ValueError(
                f"transition block ({n}) larger than per-shard capacity "
                f"({self.shard_capacity}): with {self.num_shards} shards a "
                f"block must fit one shard — lower the shard count or shrink "
                f"lanes_per_shard * (rollout_len - n_step + 1) * replicate_k")
        with self._ticket_lock:
            k = self._ticket % self.num_shards
            self._ticket += 1
        return self.shards[k].add(block, timeout, trace_id=trace_id)

    # -- learner side -------------------------------------------------------

    def get_batch(self, timeout: float | None = None):
        """One merged learner batch, or None while any shard is starved
        (below min-fill or prefetch lagging). Sub-batches already collected
        are parked, so repeated calls make progress shard by shard."""
        t = self._poll_s if timeout is None else timeout
        per_shard = max(t / self.num_shards, 1e-4)
        for k, sh in enumerate(self.shards):
            if self._pending[k] is None:
                self._pending[k] = sh.get_batch(timeout=per_shard)
        if any(p is None for p in self._pending):
            return None
        subs = tuple(self._pending)
        self._pending = [None] * self.num_shards
        if self.num_shards == 1:
            return subs[0]  # plain SampleBatch: key == slot, native weights
        with self._tracer.span("fabric/merge"):
            return FabricBatch(*self._merge(subs))

    def write_back(self, indices: jax.Array, priorities: jax.Array,
                   trace_id: int = 0) -> None:
        """Scatter learner priorities back to the owning shards by decoding
        the global ``(shard, slot)`` keys (Alg. 2 l.8). A nonzero
        ``trace_id`` marks every shard's segment apply as part of the same
        batch trace (the batch fans out; the trace follows all of it).

        The keys are self-describing (``shard = key // shard_capacity``), so
        any subset/ordering of keys from batches this fabric assembled is
        valid — callers may filter or reorder before writing back.

        The partition (stable sort by owning shard + segment counts) runs as
        jitted device ops; the host transfers only the per-shard counts and
        passes each shard a lazy slice of the sorted device arrays, so the
        indices never round-trip through ``np.asarray``. An unfiltered
        merged batch always splits into equal ``batch/num_shards`` segments
        (the merge layout guarantees it), so the shards' jitted write-backs
        see stable shapes and compile once.
        """
        if self.num_shards == 1:
            self.shards[0].write_back(indices, priorities,
                                      trace_id=trace_id)
            return
        # the counts' transfer waits for the partition on the device
        with self._tracer.span("fabric/route_writeback"):
            slots, prios, counts = self._part(indices, priorities)
            counts = np.asarray(counts).tolist()
        off = 0
        for k, n in enumerate(counts):
            if n:
                self.shards[k].write_back(slots[off:off + n],
                                          prios[off:off + n],
                                          trace_id=trace_id)
            off += n
