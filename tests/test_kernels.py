"""Pallas kernel validation (interpret mode): shape/dtype sweeps, allclose vs
the pure-jnp oracles in each kernel's ref.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import priority as prio, replay, sumtree
from repro.core.nstep import from_trajectory
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.nstep_return.ops import nstep_return
from repro.kernels.sumtree_sample.ops import (sumtree_sample,
                                              sumtree_sample_with_mass)
from repro.kernels.sumtree_update.ops import sumtree_update


FLASH_CASES = [
    # B, Sq, Sk, Hq, Hkv, D, causal, window, off, bq, bk
    (2, 256, 256, 4, 2, 64, True, None, 0, 128, 128),
    (1, 128, 128, 4, 4, 32, True, None, 0, 64, 64),
    (1, 200, 200, 4, 2, 32, True, 64, 0, 64, 64),    # SWA, ragged blocks
    (2, 1, 384, 8, 2, 64, True, None, 255, 1, 128),  # decode shape
    (1, 128, 128, 2, 1, 128, False, None, 0, 64, 64),  # encoder
    (1, 96, 96, 2, 2, 80, True, None, 0, 32, 32),    # non-128 head_dim (danube)
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=str)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    B, Sq, Sk, Hq, Hkv, D, causal, window, off, bq, bk = case
    rng = jax.random.split(jax.random.key(Sq + Sk + off), 3)
    q = jax.random.normal(rng[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(rng[1], (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(rng[2], (B, Sk, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, q_offset=off,
                          block_q=bq, block_k=bk, interpret=True)
    ref = flash_attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=causal, window=window, q_offset=off)
    ref = jnp.swapaxes(ref, 1, 2)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("cap,B,block", [(64, 32, 32), (256, 100, 64),
                                         (1024, 512, 256), (32, 7, 8)])
def test_sumtree_sample_matches_ref(cap, B, block):
    leaves = jax.random.uniform(jax.random.key(cap), (cap,))
    tree = sumtree.rebuild(leaves)
    u = jax.random.uniform(jax.random.key(B), (B,)) * sumtree.total(tree)
    ref = sumtree.sample(tree, u)
    got = sumtree_sample(tree, u, block_b=block, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # fused variant: identical indices plus bitwise leaf masses
    got_idx, got_mass = sumtree_sample_with_mass(tree, u, block_b=block,
                                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(got_idx), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(got_mass),
                                  np.asarray(sumtree.leaves(tree)[ref]))


@pytest.mark.parametrize("cap,B,block", [(64, 32, 32), (256, 100, 64),
                                         (1024, 512, 128), (32, 7, 8),
                                         (64, 64, 16)])
def test_sumtree_update_matches_ref(cap, B, block):
    """Incremental Pallas update == XLA incremental == scatter + rebuild,
    bit-for-bit, with duplicate writers resolved last-writer-wins."""
    rng = np.random.RandomState(cap + B)
    leaves = jnp.asarray(rng.uniform(0, 10, cap).astype(np.float32))
    tree = sumtree.rebuild(leaves)
    idx = jnp.asarray(rng.randint(0, cap, B).astype(np.int32))
    if B >= 4:  # force duplicate writers with different values
        idx = idx.at[1].set(idx[0]).at[3].set(idx[0])
    vals = jnp.asarray(rng.uniform(0, 5, B).astype(np.float32))
    ref = sumtree.write_rebuild(tree, idx, vals)
    np.testing.assert_array_equal(
        np.asarray(sumtree.update(tree, idx, vals)), np.asarray(ref))
    got = sumtree_update(tree, idx, vals, block_b=block, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_sumtree_update_kernel_index_handling():
    """Scatter-faithful index handling (and the block padding path): -1
    wraps to C-1, >= C (and < -C) drops — bitwise equal to the oracle."""
    tree = sumtree.rebuild(jnp.array([1.0, 2.0, 3.0, 4.0]))
    idx = jnp.array([-1, 4, 2], jnp.int32)   # block_b=2: exercises padding
    vals = jnp.array([9.0, 8.0, 7.0])
    got = sumtree_update(tree, idx, vals, block_b=2, interpret=True)
    ref = sumtree.write_rebuild(tree, idx, vals)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert float(sumtree.leaves(got)[3]) == 9.0  # -1 wrapped, 4 dropped


def test_sumtree_update_kernel_cross_block_last_writer_wins():
    """Duplicate writers split across grid blocks: the later block's lane
    must win, matching the XLA scatter's in-order resolution."""
    tree = sumtree.rebuild(jnp.ones((8,), jnp.float32))
    idx = jnp.array([5, 1, 5, 5], jnp.int32)   # block_b=2: dup spans blocks
    vals = jnp.array([2.0, 3.0, 4.0, 6.0], jnp.float32)
    got = sumtree_update(tree, idx, vals, block_b=2, interpret=True)
    ref = sumtree.write_rebuild(tree, idx, vals)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert float(sumtree.leaves(got)[5]) == 6.0


def replay_ingest_ref(tree, storage, idx, priorities, applied, items, *,
                      alpha: float = prio.PRIORITY_EXPONENT):
    """Pure-jnp oracle of the replay ingest: leaf values, storage scatter,
    tree write by the XLA incremental update. All "old" values (masked
    lanes' leaves and rows) are gathered from the *input* state before any
    scatter lands, and duplicate slots resolve last-writer-wins."""
    leaf = jnp.where(applied, prio.to_leaf(priorities, alpha),
                     sumtree.leaves(tree)[idx])
    new_storage = jax.tree.map(
        lambda buf, x: buf.at[idx].set(
            jnp.where(jnp.expand_dims(applied, tuple(range(1, x.ndim))),
                      x.astype(buf.dtype), buf[idx])),
        storage, items)
    return sumtree.update(tree, idx, leaf), new_storage


def replay_ingest(tree, storage, idx, priorities, applied, items):
    """``replay.ingest`` with its tree write on the update kernel, run
    under the Pallas interpreter -> (new_tree, new_storage)."""
    cfg = replay.ReplayConfig(capacity=sumtree.capacity(tree))
    zero = jnp.zeros((), jnp.int32)
    state = replay.ReplayState(storage, tree, zero, zero, zero)
    saved = sumtree._backend
    sumtree.set_backend("interpret")
    try:
        new_storage, new_tree = replay.ingest(cfg, state, items, priorities,
                                              idx, applied)
    finally:
        sumtree.set_backend(saved)
    return new_tree, new_storage


def _ingest_case(cap, B, seed):
    """Random ingest inputs: a partially-filled tree, a mixed-dtype
    storage pytree (matrix, int32 vector, scalar leaf), duplicate slots,
    overflow lanes (idx == C, the alloc path's drop sentinel) and a mixed
    applied mask."""
    rng = np.random.RandomState(seed)
    leaves = (rng.uniform(0, 10, cap) * (rng.uniform(size=cap) > 0.3))
    tree = sumtree.rebuild(jnp.asarray(leaves.astype(np.float32)))
    storage = {"obs": jnp.asarray(rng.normal(size=(cap, 5)).astype(np.float32)),
               "act": jnp.asarray(rng.randint(0, 7, cap).astype(np.int32)),
               "ret": jnp.asarray(rng.normal(size=cap).astype(np.float32))}
    items = {"obs": jnp.asarray(rng.normal(size=(B, 5)).astype(np.float32)),
             "act": jnp.asarray(rng.randint(0, 7, B).astype(np.int32)),
             "ret": jnp.asarray(rng.normal(size=B).astype(np.float32))}
    idx = rng.randint(0, cap + 1, B)           # cap == dropped overflow lane
    if B >= 4:                                 # force duplicate writers
        idx[1] = idx[0]
        idx[3] = idx[0]
    prios = jnp.asarray(rng.uniform(-3.0, 3.0, B).astype(np.float32))
    applied = jnp.asarray(rng.uniform(size=B) > 0.3)
    return tree, storage, jnp.asarray(idx.astype(np.int32)), prios, applied, items


def _assert_ingest_equal(got, want):
    got_tree, got_storage = got
    want_tree, want_storage = want
    np.testing.assert_array_equal(np.asarray(got_tree), np.asarray(want_tree))
    for k in want_storage:
        assert got_storage[k].dtype == want_storage[k].dtype
        assert got_storage[k].shape == want_storage[k].shape
        np.testing.assert_array_equal(np.asarray(got_storage[k]),
                                      np.asarray(want_storage[k]), err_msg=k)


@pytest.mark.parametrize("cap,B", [(64, 32), (256, 100), (32, 7), (64, 64),
                                   (16, 16)])
def test_replay_ingest_matches_ref(cap, B):
    """Ingest (priority init + storage scatter + tree repair by the update
    kernel) == the XLA oracle, bit-for-bit."""
    tree, storage, idx, prios, applied, items = _ingest_case(cap, B, cap + B)
    want = replay_ingest_ref(tree, storage, idx, prios, applied, items)
    got = replay_ingest(tree, storage, idx, prios, applied, items)
    _assert_ingest_equal(got, want)


def test_replay_ingest_index_handling():
    """Scatter-faithful index handling: -1 wraps to C-1, idx == C (the
    alloc overflow sentinel) drops without touching slot 0."""
    cap = 8
    tree, storage, _, _, _, items = _ingest_case(cap, 3, 7)
    idx = jnp.array([-1, cap, 2], jnp.int32)
    prios = jnp.array([9.0, 8.0, 7.0], jnp.float32)
    applied = jnp.array([True, True, True])
    want = replay_ingest_ref(tree, storage, idx, prios, applied, items)
    got = replay_ingest(tree, storage, idx, prios, applied, items)
    _assert_ingest_equal(got, want)
    got_tree, got_storage = got
    # -1 wrapped: slot C-1 carries lane 0's item; the overflow lane changed
    # nothing (in particular slot 0 kept its original row).
    np.testing.assert_array_equal(np.asarray(got_storage["obs"][cap - 1]),
                                  np.asarray(items["obs"][0]))
    np.testing.assert_array_equal(np.asarray(got_storage["obs"][0]),
                                  np.asarray(storage["obs"][0]))


def test_replay_ingest_cross_block_last_writer_wins():
    """Duplicate slots resolve like the XLA scatter: the later lane wins —
    and a masked later duplicate re-writes the *original* row/leaf
    (gather-all-then-scatter), not the earlier lane's value. Duplicates
    split across the update kernel's grid blocks are
    ``test_sumtree_update_kernel_cross_block_last_writer_wins``."""
    cap = 8
    tree, storage, _, _, _, items = _ingest_case(cap, 4, 11)
    idx = jnp.array([5, 1, 5, 5], jnp.int32)
    prios = jnp.array([2.0, 3.0, 4.0, 6.0], jnp.float32)
    applied = jnp.array([True, True, True, True])
    want = replay_ingest_ref(tree, storage, idx, prios, applied, items)
    got = replay_ingest(tree, storage, idx, prios, applied, items)
    _assert_ingest_equal(got, want)
    got_tree, got_storage = got
    assert float(sumtree.leaves(got_tree)[5]) == float(
        prio.to_leaf(jnp.float32(6.0)))
    np.testing.assert_array_equal(np.asarray(got_storage["obs"][5]),
                                  np.asarray(items["obs"][3]))
    # masked later duplicate: lane 1 is not applied, so slot 5 must end up
    # with the ORIGINAL row/leaf (the mask re-writes old state, last).
    applied2 = jnp.array([True, False])
    idx2 = jnp.array([5, 5], jnp.int32)
    items2 = jax.tree.map(lambda x: x[:2], items)
    want2 = replay_ingest_ref(tree, storage, idx2, prios[:2], applied2, items2)
    got2 = replay_ingest(tree, storage, idx2, prios[:2], applied2, items2)
    _assert_ingest_equal(got2, want2)
    np.testing.assert_array_equal(np.asarray(got2[1]["obs"][5]),
                                  np.asarray(storage["obs"][5]))
    np.testing.assert_array_equal(np.asarray(sumtree.leaves(got2[0])[5]),
                                  np.asarray(sumtree.leaves(tree)[5]))


@pytest.mark.parametrize("lanes,T,n,block", [(8, 20, 3, 8), (100, 16, 5, 32),
                                             (3, 7, 1, 4), (17, 33, 8, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_nstep_return_matches_ref(lanes, T, n, block, dtype):
    r = jax.random.normal(jax.random.key(lanes), (lanes, T), dtype)
    g = ((jax.random.uniform(jax.random.key(T), (lanes, T)) > 0.1) * 0.99
         ).astype(dtype)
    ret_ref, disc_ref = from_trajectory(r.astype(jnp.float32),
                                        g.astype(jnp.float32), n)
    ret, disc = nstep_return(r, g, n, block_lanes=block, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(ret), np.asarray(ret_ref),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(disc), np.asarray(disc_ref),
                               rtol=tol, atol=tol)


def test_flash_attention_is_differentiable():
    """The chunked/flash path participates in training — grads must flow."""
    rng = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(rng[0], (1, 64, 2, 32))
    k = jax.random.normal(rng[1], (1, 64, 1, 32))
    v = jax.random.normal(rng[2], (1, 64, 1, 32))

    def f(q):
        return flash_attention(q, k, v, interpret=True, block_q=32,
                               block_k=32).sum()

    g = jax.grad(f)(q)
    assert bool(jnp.all(jnp.isfinite(g)))
    assert float(jnp.abs(g).sum()) > 0
