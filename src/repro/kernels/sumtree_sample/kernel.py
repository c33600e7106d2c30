"""Pallas TPU kernel: batched stochastic sum-tree descent (prioritized sampling).

The paper found the replay server CPU-bound and fixed it by batching all
requests (§Contention); on TPU the analogous hot op is the batched inverse-CDF
descent that turns a vector of mass offsets into leaf indices. A replay
shard's tree is small (2 * capacity f32: 256 KiB at the Pallas path's
largest shard, C = 2^15), so the whole tree sits in SMEM and each lane
walks root to leaf with scalar loads: log2(C) reads per sample, where a
one-hot select over the tree would touch all 2C nodes at every level. Every
call copies the whole tree from HBM into an SMEM scratch at the first grid
step (256 KiB at C = 2^15, whatever B is); the offset batch is tiled by the
grid.

The kernel also emits each sampled leaf's mass ``p^alpha`` (one more load
at the final node), so ``replay.sample`` gets index and mass from one pass
instead of a descent plus a second leaf gather. The mass is bitwise
``leaves(tree)[idx]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(u_ref, tree_hbm, idx_ref, mass_ref, tree, sem, *, depth: int,
            capacity: int, block_b: int):
    @pl.when(pl.program_id(0) == 0)
    def _():
        cp = pltpu.make_async_copy(tree_hbm, tree, sem.at[0])
        cp.start()
        cp.wait()

    def level(_, carry):
        node, u = carry
        left = node * 2
        left_mass = tree[left]
        go_left = u < left_mass
        return (jnp.where(go_left, left, left + 1),
                jnp.where(go_left, u, u - left_mass))

    def lane(b, carry):
        node, _ = jax.lax.fori_loop(0, depth, level, (jnp.int32(1), u_ref[0, b]))
        leaf = jnp.minimum(jnp.maximum(node - capacity, 0), capacity - 1)
        idx_ref[0, b] = leaf
        mass_ref[0, b] = tree[leaf + capacity]
        return carry

    jax.lax.fori_loop(0, block_b, lane, 0)


def sumtree_sample_pallas(tree: jax.Array, u: jax.Array, *, block_b: int = 256,
                          interpret: bool = False
                          ) -> tuple[jax.Array, jax.Array]:
    """tree (2C,) f32 sum-tree, u (B,) mass offsets -> ((B,) int32 leaf ids,
    (B,) f32 leaf masses)."""
    (two_c,) = tree.shape
    capacity = two_c // 2
    depth = capacity.bit_length() - 1
    (B,) = u.shape
    block_b = min(block_b, B)
    pad = (-B) % block_b
    u = u.astype(tree.dtype)
    if pad:
        u = jnp.pad(u, (0, pad))
    blocks = u.shape[0] // block_b

    # per-lane operands as (blocks, 1, block_b), one (1, block_b) row per
    # grid step: a 1-D block would have to match XLA's tiling of the whole
    # vector, and a 2-D one the (8, 128) tile
    lane_spec = pl.BlockSpec((None, 1, block_b), lambda i: (i, 0, 0),
                             memory_space=pltpu.SMEM)
    kernel = functools.partial(_kernel, depth=depth, capacity=capacity,
                               block_b=block_b)
    idx, mass = pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=[lane_spec, pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=[lane_spec, lane_spec],
        out_shape=[
            jax.ShapeDtypeStruct((blocks, 1, block_b), jnp.int32),
            jax.ShapeDtypeStruct((blocks, 1, block_b), tree.dtype),
        ],
        scratch_shapes=[pltpu.SMEM((two_c,), tree.dtype),
                        pltpu.SemaphoreType.DMA((1,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(u.reshape(blocks, 1, block_b), tree)
    return idx.reshape(-1)[:B], mass.reshape(-1)[:B]
