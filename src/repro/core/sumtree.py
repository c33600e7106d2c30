"""Functional sum-tree for proportional prioritized sampling (Schaul et al. 2016).

The tree backs the Ape-X replay memory: leaves hold priorities ``p_k^alpha``
and internal nodes hold subtree sums, so sampling a key with probability
``p_k^alpha / sum_j p_j^alpha`` is a root-to-leaf descent.

Layout: for ``capacity`` C (power of two) the tree is a flat ``(2*C,)`` array.
Node 1 is the root, node ``i`` has children ``2i`` and ``2i+1``; leaf ``k``
lives at index ``C + k``. Index 0 is unused.

All operations are pure and batched. The two hot ops on the replay server both
have Pallas TPU kernels with the implementations here as their oracles / XLA
fallbacks:

* the sampling descent (``repro.kernels.sumtree_sample``) — inverse-CDF walk,
  optionally fused with the per-sample leaf-mass read;
* the batched write (``repro.kernels.sumtree_update``) — O(B * log C)
  incremental propagation instead of the O(C) full level-rebuild (the
  kernel still moves the whole tree, O(C) bytes, into SMEM and back).

``write`` dispatches between them via a process-wide backend switch
(:func:`set_backend`): ``pallas`` on TPU, ``xla`` elsewhere, ``interpret``
to run the Pallas kernels under the interpreter (CPU CI). The incremental
XLA path (:func:`update`) is bit-identical to scatter + :func:`rebuild` by
construction: leaves are resolved with the same ``.at[idx].set`` scatter
(last writer wins under duplicates) and every touched parent is recomputed
as ``left + right`` — the identical fp32 operation ``rebuild``'s pairwise
level-sum performs — rather than patched with an (inexact) delta.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

__all__ = [
    "init",
    "capacity",
    "depth",
    "total",
    "leaves",
    "write",
    "write_rebuild",
    "update",
    "rebuild",
    "sample",
    "sample_with_mass",
    "sample_two_gather",
    "stratified_uniforms",
    "sample_stratified",
    "backend",
    "set_backend",
    "hot_backend",
]

# Process-wide backend for the hot ops (write / sample_with_mass):
#   "pallas"    — Pallas TPU kernels (compiled)
#   "interpret" — same kernels under the Pallas interpreter (CPU CI)
#   "xla"       — pure-jnp incremental paths (oracle / CPU fallback)
#   None        — auto: "pallas" on TPU, "xla" elsewhere
_BACKENDS = ("pallas", "interpret", "xla")
_backend: str | None = os.environ.get("REPRO_SUMTREE_BACKEND") or None

# The kernels hold the whole tree in SMEM (1 MiB on a TPU v5e), which is
# only viable for the small per-shard trees the replay fabric produces: at
# this capacity the tree takes 256 KiB. The *auto* backend therefore only
# picks Pallas up to this leaf capacity and falls back to XLA above it; an
# explicit ``set_backend("pallas")`` (or env override) is honored
# unconditionally.
_PALLAS_AUTO_MAX_CAPACITY = 1 << 15


def backend() -> str:
    """The effective backend for the kernelized ops."""
    if _backend is not None:
        return _backend
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def set_backend(name: str | None) -> None:
    """Select the hot-op backend (``None`` restores auto-detection).

    The dispatch happens at trace time, so the switch only affects
    functions traced *afterwards* — already-jitted consumers (e.g. a live
    ``ReplayShard``'s ``ShardFns``) keep the backend that was active when
    they first compiled. Set the backend (or ``REPRO_SUMTREE_BACKEND``)
    before building shards/fabrics.
    """
    global _backend
    if name is not None and name not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS} or None, got {name!r}")
    _backend = name


def hot_backend(cap: int) -> str:
    """Backend for one hot-op call: the auto-selected Pallas path is gated
    on the tree fitting SMEM; explicit choices pass through. Shared by the
    kernelized ops that copy the whole tree into SMEM (``write``, which the
    replay's adds and write-backs go through, and ``sample_with_mass``)."""
    bk = backend()
    if _backend is None and bk == "pallas" and cap > _PALLAS_AUTO_MAX_CAPACITY:
        return "xla"
    return bk


def _check_capacity(cap: int) -> None:
    if cap < 2 or (cap & (cap - 1)) != 0:
        raise ValueError(f"sum-tree capacity must be a power of two >= 2, got {cap}")


def init(cap: int, dtype=jnp.float32) -> jax.Array:
    """Return an empty tree of leaf capacity ``cap``."""
    _check_capacity(cap)
    return jnp.zeros((2 * cap,), dtype=dtype)


def capacity(tree: jax.Array) -> int:
    return tree.shape[0] // 2


def depth(tree: jax.Array) -> int:
    """Number of edges from root to leaf == log2(capacity)."""
    return (capacity(tree)).bit_length() - 1


def total(tree: jax.Array) -> jax.Array:
    """Total priority mass (root value)."""
    return tree[1]


def leaves(tree: jax.Array) -> jax.Array:
    return tree[capacity(tree):]


def rebuild(leaf_values: jax.Array) -> jax.Array:
    """Build a full tree from a ``(C,)`` leaf vector (C power of two)."""
    (cap,) = leaf_values.shape
    _check_capacity(cap)
    levels = [leaf_values]
    while levels[-1].shape[0] > 1:
        lv = levels[-1]
        levels.append(lv.reshape(-1, 2).sum(axis=1))
    # levels: [C, C/2, ..., 1]; tree[1:] = concat(reversed levels)
    flat = jnp.concatenate([lv for lv in reversed(levels)])
    return jnp.concatenate([jnp.zeros((1,), leaf_values.dtype), flat])


def write_rebuild(tree: jax.Array, idx: jax.Array, values: jax.Array) -> jax.Array:
    """Set ``leaves[idx] = values`` via a full O(C) level-rebuild.

    The original ``write`` implementation, kept as the oracle for the
    incremental paths: duplicate indices resolve scatter-style (last writer
    wins) before the exact rebuild, so internal sums are always consistent
    with leaves.
    """
    new_leaves = leaves(tree).at[idx].set(values.astype(tree.dtype), mode="drop")
    return rebuild(new_leaves)


def update(tree: jax.Array, idx: jax.Array, values: jax.Array) -> jax.Array:
    """Incremental batched write: O(B * log C) instead of ``rebuild``'s O(C).

    Leaves are set with the same ``.at[idx].set(mode="drop")`` scatter as
    :func:`write_rebuild` (so duplicate resolution is identical), then each
    level of ancestors is *recomputed* as ``tree[2p] + tree[2p + 1]`` — the
    same pairwise fp32 sum ``rebuild`` performs — and scattered back. Lanes
    sharing an ancestor all compute the identical value, so duplicate
    scatters at internal levels are benign, and writing ``left + right`` is
    always invariant-restoring even for lanes whose leaf write was dropped.
    Bit-identical to :func:`write_rebuild` on any tree whose internal nodes
    already satisfy the sum invariant.
    """
    cap = capacity(tree)
    # match the scatter's numpy-style index handling exactly: negatives in
    # [-C, -1] wrap, anything else out of [0, C) is dropped
    norm = jnp.where(idx < 0, idx + cap, idx).astype(jnp.int32)
    safe = jnp.clip(norm, 0, cap - 1)
    in_range = (norm >= 0) & (norm < cap)
    target = jnp.where(in_range, safe + cap, 2 * cap)  # OOB lanes: dropped
    tree = tree.at[target].set(values.astype(tree.dtype), mode="drop")

    # depth is static, so the walk unrolls: log2(C) tiny gather+scatter pairs
    # fuse into one XLA computation with no loop-carry overhead.
    node = safe + cap
    for _ in range(depth(tree)):
        node = node >> 1
        tree = tree.at[node].set(tree[2 * node] + tree[2 * node + 1])
    return tree


def write(tree: jax.Array, idx: jax.Array, values: jax.Array) -> jax.Array:
    """Set ``leaves[idx] = values`` and restore the sum invariant.

    Duplicate indices are resolved scatter-style (last writer wins); the
    propagation is incremental — O(B * log C) — on every backend (Pallas
    kernel on TPU, :func:`update` under XLA). Use :func:`write_rebuild` when
    the batch covers most of the tree (e.g. full-capacity rewrites).
    """
    bk = hot_backend(capacity(tree))
    if bk in ("pallas", "interpret"):
        from repro.kernels.sumtree_update.ops import sumtree_update
        return sumtree_update(tree, idx, values, interpret=(bk == "interpret"))
    return update(tree, idx, values)


def sample(tree: jax.Array, u: jax.Array) -> jax.Array:
    """Batched stochastic descent: map mass offsets ``u in [0, total)`` to leaf ids.

    For each offset the walk goes left when ``u < mass(left child)``, else
    subtracts the left mass and goes right — i.e. inverse-CDF sampling on the
    implicit prefix-sum of the leaves.
    """
    cap = capacity(tree)
    d = depth(tree)
    node = jnp.ones_like(u, dtype=jnp.int32)
    u = u.astype(tree.dtype)

    def body(_, carry):
        node, u = carry
        left = node * 2
        left_mass = tree[left]
        go_left = u < left_mass
        node = jnp.where(go_left, left, left + 1)
        u = jnp.where(go_left, u, u - left_mass)
        return node, u

    node, _ = jax.lax.fori_loop(0, d, body, (node, u))
    return jnp.clip(node - cap, 0, cap - 1)


def sample_two_gather(tree: jax.Array, u: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The XLA form of the mass-emitting descent: plain :func:`sample`
    followed by a leaf gather. Two logical gathers, but XLA fuses them into
    one program with no kernel-launch boundary — on CPU/GPU hosts this is
    the fastest shape, so it is the form the ``xla`` backend keeps (the
    single-pass form only pays off where the descent kernel already holds
    the tree in SMEM)."""
    idx = sample(tree, u)
    return idx, leaves(tree)[idx]


def sample_with_mass(tree: jax.Array, u: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused descent: leaf ids *and* their masses ``p^alpha`` in one pass.

    ``replay.sample`` needs both. Backend-dispatched per path: the Pallas
    kernel emits the mass from the final descent level (no second tree
    gather); the ``xla`` backend keeps :func:`sample_two_gather`, whose
    descent + gather fuse into one XLA program anyway. The mass is bitwise
    ``leaves(tree)[idx]`` on every backend.
    """
    bk = hot_backend(capacity(tree))
    if bk in ("pallas", "interpret"):
        from repro.kernels.sumtree_sample.ops import sumtree_sample_with_mass
        return sumtree_sample_with_mass(tree, u, interpret=(bk == "interpret"))
    return sample_two_gather(tree, u)


def stratified_uniforms(rng: jax.Array, batch: int, total_mass: jax.Array) -> jax.Array:
    """Paper-faithful stratified offsets: one uniform per equal-mass stratum."""
    jitter = jax.random.uniform(rng, (batch,))
    u = (jnp.arange(batch, dtype=jnp.float32) + jitter) * (total_mass / batch)
    # guard the last stratum against fp overshoot of the root mass
    return jnp.minimum(u, total_mass * (1.0 - 1e-6))


def sample_stratified(tree: jax.Array, rng: jax.Array, batch: int) -> jax.Array:
    """Sample ``batch`` leaf ids with stratified proportional prioritization."""
    u = stratified_uniforms(rng, batch, total(tree))
    return sample(tree, u)
