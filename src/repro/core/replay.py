"""Sharded prioritized replay memory (the Ape-X replay server, TPU-native).

Logically one centralized memory (paper §3); physically each ``data``-axis
shard owns ``capacity/num_shards`` slots plus its own sum-tree, and the only
cross-shard traffic is one scalar (the shard's total priority mass) per
sampling round — the paper's batched-communication principle taken to its
limit. Everything here is per-shard and purely functional; ``repro.core.apex``
maps it over the mesh with ``shard_map``.

Eviction strategies (both from the paper):
  * ``evict_fifo`` — Atari (§4.1): adds are always permitted (soft limit);
    periodically the excess above the soft capacity is removed en masse in
    FIFO order.
  * ``evict_prioritized`` — DPG (Appendix D): victims are sampled with
    probability proportional to ``p^alpha_evict`` (alpha_evict = -0.4), i.e.
    low-priority items are evicted first, keeping rare high-priority
    experience alive longer (the paper's Fig. 5 hypothesis).

Slots are the paper's "keys": a transition's global key is (shard, slot).

Both add modes funnel into one ingest contract (:func:`ingest`) — packed
items, slot indices, an ``applied`` lane mask: priority init and the storage
scatter in XLA, the tree repair through ``sumtree.write`` (the Pallas update
kernel on TPU).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import priority as prio
from repro.core import sumtree


class ReplayState(NamedTuple):
    storage: Any           # pytree of (C_phys, ...) arrays
    tree: jax.Array        # (2*C_phys,) sum-tree over p^alpha leaves
    write_pos: jax.Array   # scalar int32 (FIFO circular pointer)
    size: jax.Array        # scalar int32, live items
    total_added: jax.Array # scalar int32, lifetime adds (for diagnostics)


class SampleBatch(NamedTuple):
    indices: jax.Array     # (B,) slot ids within this shard
    items: Any             # pytree of (B, ...) arrays
    is_weights: jax.Array  # (B,) max-normalized importance weights
    leaf_mass: jax.Array   # (B,) p^alpha of each sampled slot
    total_mass: jax.Array  # scalar, shard total priority mass
    size: jax.Array        # scalar, shard live-item count at sample time
                           # (feeds the global-N term when shards are merged)


@dataclasses.dataclass(frozen=True)
class ReplayConfig:
    """Static replay configuration (hashable; safe to close over in jit)."""

    capacity: int                      # physical slots per shard (power of 2)
    soft_capacity: int | None = None   # logical limit (FIFO mode); default 7/8 phys
    alpha: float = prio.PRIORITY_EXPONENT
    beta: float = prio.IS_EXPONENT
    evict_alpha: float = prio.EVICT_EXPONENT
    min_fill: int = 128                # learner waits for this many items (paper: 50000 global)

    def __post_init__(self):
        if self.capacity & (self.capacity - 1):
            raise ValueError("replay capacity must be a power of two")

    @property
    def soft_cap(self) -> int:
        return self.soft_capacity if self.soft_capacity is not None else (self.capacity // 8) * 7


def init(cfg: ReplayConfig, item_example: Any) -> ReplayState:
    """Empty replay; ``item_example`` is a pytree giving per-item shapes/dtypes."""
    storage = jax.tree.map(
        lambda a: jnp.zeros((cfg.capacity,) + jnp.shape(a), jnp.asarray(a).dtype),
        item_example,
    )
    return ReplayState(
        storage=storage,
        tree=sumtree.init(cfg.capacity),
        write_pos=jnp.zeros((), jnp.int32),
        size=jnp.zeros((), jnp.int32),
        total_added=jnp.zeros((), jnp.int32),
    )


def _store(storage: Any, idx: jax.Array, items: Any) -> Any:
    return jax.tree.map(lambda buf, x: buf.at[idx].set(x.astype(buf.dtype)), storage, items)


def ingest(
    cfg: ReplayConfig, state: ReplayState, items: Any, priorities: jax.Array,
    idx: jax.Array, applied: jax.Array,
) -> tuple[Any, jax.Array]:
    """One ingest: leaf init, per-buffer storage scatter, tree write.

    Both add modes reduce to this contract once their slot indices and lane
    mask are computed (FIFO cursor arithmetic / ``free_slot_idx``).
    Gather-then-scatter semantics throughout: masked (``~applied``) lanes
    re-write their slot's *original* leaf and row, so they are no-ops except
    under duplicate slots, where the scatter's last-writer-wins applies.
    Out-of-range lanes (``add_alloc``'s overflow fill value ``capacity``)
    drop on every scatter. The tree write follows the sum-tree backend
    (``sumtree.write``); everything else is XLA, fused by the enclosing jit.
    """
    leaf = jnp.where(applied, prio.to_leaf(priorities, cfg.alpha),
                     sumtree.leaves(state.tree)[idx])
    storage = jax.tree.map(
        lambda buf, x: buf.at[idx].set(
            jnp.where(jnp.expand_dims(applied, tuple(range(1, x.ndim))),
                      x.astype(buf.dtype), buf[idx])),
        state.storage, items)
    tree = sumtree.write(state.tree, idx, leaf)
    return storage, tree


def add_fifo(
    cfg: ReplayConfig, state: ReplayState, items: Any, priorities: jax.Array,
    valid: jax.Array | None = None,
) -> ReplayState:
    """Batched circular add with actor-computed initial priorities (Alg. 1 l.10-11).

    Adding is always permitted (soft limit): if the physical buffer is full the
    oldest slots are overwritten, which coincides with FIFO eviction. ``valid``
    masks out warm-up/invalid lanes (their slots are not consumed).
    """
    (batch,) = priorities.shape
    if valid is None:
        valid = jnp.ones((batch,), bool)
    # Pack valid lanes first so invalid ones don't consume slots: stable argsort
    # of ~valid puts valid lane ids in front, preserving order.
    order = jnp.argsort(~valid, stable=True)
    items = jax.tree.map(lambda x: x[order], items)
    priorities = priorities[order]
    n_valid = valid.sum().astype(jnp.int32)

    offs = jnp.arange(batch, dtype=jnp.int32)
    idx = (state.write_pos + offs) % cfg.capacity
    # Invalid tail lanes land on the same circular indices but masked: they
    # re-write their slot's old leaf/row (a no-op), and since write_pos only
    # advances by n_valid the next add claims those slots anyway.
    applied = offs < n_valid
    storage, tree = ingest(cfg, state, items, priorities, idx, applied)
    return ReplayState(
        storage=storage,
        tree=tree,
        write_pos=(state.write_pos + n_valid) % cfg.capacity,
        size=jnp.minimum(state.size + n_valid, cfg.capacity),
        total_added=state.total_added + n_valid,
    )


def free_slot_idx(live: jax.Array, batch: int) -> jax.Array:
    """First ``batch`` free slots via masked-cumsum compaction: rank each
    free slot among the free slots (in index order, like the argsort this
    replaced, but O(C) instead of O(C log C)) and scatter them into the
    result. Lanes beyond the free-slot count keep an *out-of-range* fill
    value, so their downstream leaf/storage scatters drop instead of
    aliasing a real slot."""
    (cap,) = live.shape
    rank = jnp.cumsum(~live) - 1
    slot = jnp.arange(cap, dtype=jnp.int32)
    target = jnp.where(~live & (rank < batch), rank, batch).astype(jnp.int32)
    return jnp.full((batch,), cap, jnp.int32).at[target].set(slot, mode="drop")


def add_alloc(
    cfg: ReplayConfig, state: ReplayState, items: Any, priorities: jax.Array,
    valid: jax.Array | None = None,
) -> ReplayState:
    """Add into *free* slots (leaf mass == 0) — DPG mode, paired with
    prioritized eviction which frees slots instead of a moving FIFO head.

    When the block is larger than the number of free slots, the overflow
    lanes are *dropped* (masked like invalid lanes) rather than spilling into
    live slots: eviction is the only thing allowed to free a live slot, so a
    full buffer sheds the overflow instead of silently clobbering experience
    (``total_added`` counts only lanes actually stored, so drops are visible
    as ``total_added`` falling behind the offered count).
    """
    (batch,) = priorities.shape
    if valid is None:
        valid = jnp.ones((batch,), bool)
    # Pack valid lanes first (stable, like add_fifo) so invalid lanes don't
    # waste free slots.
    order = jnp.argsort(~valid, stable=True)
    items = jax.tree.map(lambda x: x[order], items)
    priorities = priorities[order]
    valid = valid[order]

    live = sumtree.leaves(state.tree) > 0
    idx = free_slot_idx(live, batch)
    num_free = (~live).sum().astype(jnp.int32)
    offs = jnp.arange(batch, dtype=jnp.int32)
    # Lanes past the free-slot count would land on live slots: mask them out.
    applied = valid & (offs < num_free)
    storage, tree = ingest(cfg, state, items, priorities, idx, applied)
    n_new = applied.sum().astype(jnp.int32)
    return ReplayState(
        storage=storage,
        tree=tree,
        write_pos=state.write_pos,
        size=jnp.minimum(state.size + n_new, cfg.capacity),
        total_added=state.total_added + n_new,
    )


def sample(cfg: ReplayConfig, state: ReplayState, rng: jax.Array, batch: int) -> SampleBatch:
    """Stratified proportional sampling + IS weights (Alg. 2 l.4; Appendix F).

    The descent emits each sampled slot's leaf mass alongside its index
    (fused in the Pallas backend), so no second tree gather is needed."""
    u = sumtree.stratified_uniforms(rng, batch, sumtree.total(state.tree))
    idx, leaf = sumtree.sample_with_mass(state.tree, u)
    items = jax.tree.map(lambda buf: buf[idx], state.storage)
    w = prio.importance_weights(leaf, sumtree.total(state.tree), state.size, cfg.beta)
    return SampleBatch(idx, items, w, leaf, sumtree.total(state.tree), state.size)


def set_priorities(
    cfg: ReplayConfig, state: ReplayState, idx: jax.Array, priorities: jax.Array
) -> ReplayState:
    """Learner writes back fresh |TD| priorities (Alg. 2 l.8).

    Dead slots (leaf mass 0) are left dead: with a decoupled learner the
    write-back may arrive after an eviction freed one of the sampled slots,
    and resurrecting it would break the ``size`` == live-leaf-count
    invariant. In the lockstep driver sampled slots are always live, so the
    gate is a no-op there.
    """
    old = sumtree.leaves(state.tree)[idx]
    new_leaf = prio.to_leaf(priorities, cfg.alpha)
    tree = sumtree.write(state.tree, idx, jnp.where(old > 0, new_leaf, 0.0))
    return state._replace(tree=tree)


def evict_fifo(cfg: ReplayConfig, state: ReplayState) -> ReplayState:
    """Remove the excess above the soft capacity en masse, oldest first (§4.1).

    A slot dies iff its FIFO age ``(slot - oldest) mod C`` is below the
    excess, so the kill mask is computed directly on the slot axis and the
    tree rebuilt from the masked leaves — no permuted index vector to
    materialize, no O(C) gather/scatter through it (and no O(C)-lane batch
    pushed through the incremental ``sumtree.write`` path, which is tuned
    for small batches)."""
    excess = jnp.maximum(state.size - cfg.soft_cap, 0)
    oldest = (state.write_pos - state.size) % cfg.capacity
    slot = jnp.arange(cfg.capacity, dtype=jnp.int32)
    age = (slot - oldest) % cfg.capacity
    kill = age < excess
    new_leaves = jnp.where(kill, 0.0, sumtree.leaves(state.tree))
    return state._replace(tree=sumtree.rebuild(new_leaves),
                          size=state.size - excess)


def evict_prioritized(
    cfg: ReplayConfig, state: ReplayState, rng: jax.Array, num: int
) -> ReplayState:
    """Sample ``num`` victims with probability ∝ p^alpha_evict and free them.

    Leaves hold p^alpha_sample, so the eviction mass is leaf^(alpha_evict /
    alpha_sample) on live slots. Sampling is with replacement (duplicates evict
    once), mirroring the paper's periodic batched eviction.
    """
    leaves = sumtree.leaves(state.tree)
    live = leaves > 0
    ratio = cfg.evict_alpha / cfg.alpha
    evict_mass = jnp.where(live, jnp.power(jnp.maximum(leaves, 1e-30), ratio), 0.0)
    etree = sumtree.rebuild(evict_mass)
    victims = sumtree.sample_stratified(etree, rng, num)
    old = leaves[victims]
    tree = sumtree.write(state.tree, victims, jnp.zeros((num,), leaves.dtype))
    # count distinct live victims actually freed
    mark = jnp.zeros((cfg.capacity,), jnp.int32).at[victims].set(1)
    freed = (mark * live.astype(jnp.int32)).sum()
    return state._replace(tree=tree, size=jnp.maximum(state.size - freed, 0))


def can_sample(cfg: ReplayConfig, state: ReplayState) -> jax.Array:
    """Learner gate: wait for min_fill items (paper: 50000 transitions)."""
    return (state.size >= cfg.min_fill) & (sumtree.total(state.tree) > 0)
