"""Pallas TPU kernel: batched incremental sum-tree update (priority writes).

The replay server's mutation hot path: given B leaf indices and new values,
set the leaves and restore the sum invariant along all log2(C) ancestor
levels: B * log2(C) serial scalar steps, plus two O(C)-byte copies of the
whole tree, instead of the O(C) full level-rebuild.

A replay shard's tree is small (2 * capacity f32: 256 KiB at the Pallas
path's largest shard, C = 2^15), so it lives in SMEM for the whole call and
every access is a scalar load or store at a computed address — the walk
the XLA oracle ``repro.core.sumtree.update`` performs as vector gathers and
scatters. Every call copies the whole tree from HBM into an SMEM scratch at
the first grid step and back at the last (512 KiB moved at C = 2^15,
whatever B is); the output aliases the input, so the tree is updated in
place.

* *leaf writes* — lanes run in order, so a later duplicate overwrites an
  earlier one: last-writer-wins, like ``.at[idx].set``.
* *ancestor repair* — level by level, every lane's ancestor is recomputed
  as ``left + right`` (the exact fp32 op ``rebuild``'s pairwise level-sum
  performs), which keeps the kernel bit-identical to the XLA oracle and
  transitively to scatter + ``rebuild``.

The batch is tiled by the grid; the steps run in order over the one SMEM
tree, so a later block's writes land after an earlier block's
(cross-block last-writer-wins). A block's repair recomputes every ancestor
of its lanes from the leaves as they stand, so the tree after the last
block equals the all-leaves-then-all-levels order of the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(slot_ref, live_ref, val_ref, tree_hbm, out_hbm, tree, sem, *,
            depth: int, capacity: int, block_b: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        cp = pltpu.make_async_copy(tree_hbm, tree, sem.at[0])
        cp.start()
        cp.wait()

    def leaf(b, carry):
        @pl.when(live_ref[0, b] != 0)
        def _():
            tree[slot_ref[0, b] + capacity] = val_ref[0, b]
        return carry

    jax.lax.fori_loop(0, block_b, leaf, 0)

    # Every lane repairs its path, dropped lanes included (their clipped
    # slot's path is recomputed unchanged) — the oracle's order exactly.
    def level(lv, carry):
        def lane(b, carry):
            node = (slot_ref[0, b] + capacity) >> (lv + 1)
            tree[node] = tree[2 * node] + tree[2 * node + 1]
            return carry
        return jax.lax.fori_loop(0, block_b, lane, carry)

    jax.lax.fori_loop(0, depth, level, 0)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        cp = pltpu.make_async_copy(tree, out_hbm, sem.at[0])
        cp.start()
        cp.wait()


def sumtree_update_pallas(tree: jax.Array, idx: jax.Array, values: jax.Array,
                          *, block_b: int = 128,
                          interpret: bool = False) -> jax.Array:
    """tree (2C,) f32, idx (B,) int32 leaf ids, values (B,) -> updated tree.

    Index handling matches ``.at[idx].set(mode="drop")``: negatives in
    [-C, -1] wrap numpy-style, anything else out of [0, C) is dropped;
    duplicate indices resolve last-writer-wins.
    """
    (two_c,) = tree.shape
    capacity = two_c // 2
    depth = capacity.bit_length() - 1
    (B,) = idx.shape
    if B == 0:
        return tree
    idx = idx.astype(jnp.int32)
    idx = jnp.where(idx < 0, idx + capacity, idx)
    live = ((idx >= 0) & (idx < capacity)).astype(jnp.int32)
    slot = jnp.clip(idx, 0, capacity - 1)
    values = values.astype(tree.dtype)
    block_b = min(block_b, B)
    pad = (-B) % block_b
    if pad:
        # padding lanes repeat the last lane: the same write, once more
        slot, live, values = (jnp.pad(x, (0, pad), mode="edge")
                              for x in (slot, live, values))
    blocks = slot.shape[0] // block_b

    # per-lane operands as (blocks, 1, block_b), one (1, block_b) row per
    # grid step: a 1-D block would have to match XLA's tiling of the whole
    # vector, and a 2-D one the (8, 128) tile
    lane_spec = pl.BlockSpec((None, 1, block_b), lambda i: (i, 0, 0),
                             memory_space=pltpu.SMEM)
    kernel = functools.partial(_kernel, depth=depth, capacity=capacity,
                               block_b=block_b)
    return pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=[lane_spec, lane_spec, lane_spec,
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
        out_shape=jax.ShapeDtypeStruct((two_c,), tree.dtype),
        scratch_shapes=[pltpu.SMEM((two_c,), tree.dtype),
                        pltpu.SemaphoreType.DMA((1,))],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*(x.reshape(blocks, 1, block_b) for x in (slot, live, values)), tree)
