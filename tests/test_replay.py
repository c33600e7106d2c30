"""Prioritized replay behaviour: adds, sampling, priority updates, both
eviction strategies, IS weights (paper §3/§4.1/Appendix D/F)."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_fallback import given, settings, st

from repro.core import priority as prio, replay, sumtree

CFG = replay.ReplayConfig(capacity=64, soft_capacity=48, min_fill=4)


def make_items(n, base=0):
    return {"x": jnp.arange(base, base + n, dtype=jnp.float32),
            "y": jnp.ones((n, 3), jnp.int32)}


def test_add_and_sample_roundtrip():
    state = replay.init(CFG, {"x": jnp.zeros(()), "y": jnp.zeros((3,), jnp.int32)})
    state = replay.add_fifo(CFG, state, make_items(10), jnp.ones(10))
    assert int(state.size) == 10
    batch = replay.sample(CFG, state, jax.random.key(0), 8)
    assert batch.items["x"].shape == (8,)
    assert np.all(np.asarray(batch.indices) < 10)
    assert np.all(np.asarray(batch.is_weights) > 0)
    assert np.all(np.asarray(batch.is_weights) <= 1.0 + 1e-6)


def test_add_respects_valid_mask():
    state = replay.init(CFG, {"x": jnp.zeros(()), "y": jnp.zeros((3,), jnp.int32)})
    valid = jnp.array([True, False, True, False])
    state = replay.add_fifo(CFG, state, make_items(4), jnp.ones(4), valid)
    assert int(state.size) == 2
    assert int(state.total_added) == 2


def test_set_priorities_changes_distribution():
    state = replay.init(CFG, {"x": jnp.zeros(()), "y": jnp.zeros((3,), jnp.int32)})
    state = replay.add_fifo(CFG, state, make_items(16), jnp.full(16, 0.01))
    state = replay.set_priorities(CFG, state, jnp.array([5]), jnp.array([100.0]))
    idx = np.asarray(replay.sample(CFG, state, jax.random.key(1), 64).indices)
    assert (idx == 5).mean() > 0.5  # slot 5 dominates the mass


def test_fifo_eviction_removes_oldest():
    state = replay.init(CFG, {"x": jnp.zeros(()), "y": jnp.zeros((3,), jnp.int32)})
    state = replay.add_fifo(CFG, state, make_items(60), jnp.ones(60))
    assert int(state.size) == 60
    state = replay.evict_fifo(CFG, state)
    assert int(state.size) == CFG.soft_cap
    # oldest 12 slots zeroed
    leaves = np.asarray(sumtree.leaves(state.tree))
    assert (leaves[:12] == 0).all()
    assert (leaves[12:60] > 0).all()


def test_prioritized_eviction_prefers_low_priority():
    cfg = replay.ReplayConfig(capacity=64, soft_capacity=32, min_fill=4)
    state = replay.init(cfg, {"x": jnp.zeros(()), "y": jnp.zeros((3,), jnp.int32)})
    # half low priority, half high
    prios = jnp.concatenate([jnp.full(24, 0.01), jnp.full(24, 10.0)])
    state = replay.add_alloc(cfg, state, make_items(48), prios)
    before = np.asarray(sumtree.leaves(state.tree)) > 0
    state = replay.evict_prioritized(cfg, state, jax.random.key(0), 16)
    after = np.asarray(sumtree.leaves(state.tree)) > 0
    evicted = before & ~after
    # alpha_evict < 0 => low-priority slots evicted far more often
    assert evicted[:24].sum() > evicted[24:48].sum()


def test_alloc_reuses_freed_slots():
    cfg = replay.ReplayConfig(capacity=16, soft_capacity=12, min_fill=1)
    state = replay.init(cfg, {"x": jnp.zeros(()), "y": jnp.zeros((3,), jnp.int32)})
    state = replay.add_alloc(cfg, state, make_items(16), jnp.ones(16))
    assert int(state.size) == 16
    state = replay.evict_prioritized(cfg, state, jax.random.key(0), 8)
    freed = 16 - int(state.size)
    assert freed > 0
    state2 = replay.add_alloc(cfg, state, make_items(freed, base=100), jnp.ones(freed))
    assert int(state2.size) == 16


def test_alloc_overflow_drops_instead_of_clobbering_live_slots():
    """A block larger than the free-slot count must not overwrite live
    experience: overflow lanes are dropped (only eviction frees live slots)."""
    cfg = replay.ReplayConfig(capacity=16, soft_capacity=12, min_fill=1)
    state = replay.init(cfg, {"x": jnp.zeros(()), "y": jnp.zeros((3,), jnp.int32)})
    state = replay.add_alloc(cfg, state, make_items(12), jnp.full(12, 2.0))
    before_x = np.asarray(state.storage["x"]).copy()
    before_leaves = np.asarray(sumtree.leaves(state.tree)).copy()
    # 4 free slots, 10-lane block: 4 applied, 6 overflow lanes dropped.
    state = replay.add_alloc(cfg, state, make_items(10, base=100),
                             jnp.full(10, 9.0))
    assert int(state.size) == 16
    assert int(state.total_added) == 12 + 4
    x = np.asarray(state.storage["x"])
    leaves = np.asarray(sumtree.leaves(state.tree))
    # the 12 live slots kept their items and priorities
    np.testing.assert_array_equal(x[:12], before_x[:12])
    np.testing.assert_allclose(leaves[:12], before_leaves[:12])
    # the 4 free slots got the first 4 lanes of the new block
    np.testing.assert_array_equal(x[12:16], np.arange(100, 104, dtype=np.float32))
    # a completely full buffer drops the whole block
    state2 = replay.add_alloc(cfg, state, make_items(8, base=500),
                              jnp.full(8, 1.0))
    assert int(state2.size) == 16
    np.testing.assert_array_equal(np.asarray(state2.storage["x"]), x)


def test_is_weights_uniform_priorities_are_one():
    state = replay.init(CFG, {"x": jnp.zeros(()), "y": jnp.zeros((3,), jnp.int32)})
    state = replay.add_fifo(CFG, state, make_items(32), jnp.ones(32))
    w = replay.sample(CFG, state, jax.random.key(2), 16).is_weights
    np.testing.assert_allclose(np.asarray(w), 1.0, rtol=1e-5)


def test_min_fill_gate():
    state = replay.init(CFG, {"x": jnp.zeros(()), "y": jnp.zeros((3,), jnp.int32)})
    assert not bool(replay.can_sample(CFG, state))
    state = replay.add_fifo(CFG, state, make_items(4), jnp.ones(4))
    assert bool(replay.can_sample(CFG, state))


# --- ingest: update-kernel path bit-identical to the XLA path ---------------

@contextlib.contextmanager
def pinned_backend(name):
    """Pin the sum-tree hot-op backend, restoring whatever override was in
    effect before (the CI matrix legs seed one via REPRO_SUMTREE_BACKEND)."""
    saved = sumtree._backend
    sumtree.set_backend(name)
    try:
        yield
    finally:
        sumtree.set_backend(saved)


def assert_replay_states_identical(got, want):
    np.testing.assert_array_equal(np.asarray(got.tree), np.asarray(want.tree))
    for k in want.storage:
        np.testing.assert_array_equal(np.asarray(got.storage[k]),
                                      np.asarray(want.storage[k]), err_msg=k)
    for field in ("write_pos", "size", "total_added"):
        assert int(getattr(got, field)) == int(getattr(want, field)), field


@settings(max_examples=15, deadline=None)
@given(
    mode=st.sampled_from(["fifo", "alloc"]),
    batch=st.integers(1, 24),
    prefill=st.integers(0, 32),
    seed=st.integers(0, 10**6),
)
def test_fused_ingest_bit_identical(mode, batch, prefill, seed):
    """add_fifo/add_alloc with the tree write on the Pallas update kernel
    (interpret on CPU) must be bit-identical to the all-XLA path — across
    wrap-around, duplicate slots, overflow lanes and valid masks."""
    cfg = replay.ReplayConfig(capacity=32, soft_capacity=24, min_fill=1)
    state = replay.init(cfg, {"x": jnp.zeros(()),
                              "y": jnp.zeros((3,), jnp.int32)})
    rng = np.random.RandomState(seed)
    add = replay.add_fifo if mode == "fifo" else replay.add_alloc
    with pinned_backend("xla"):
        if prefill:  # moves write_pos / consumes free slots before the probe
            state = add(cfg, state, make_items(prefill),
                        jnp.asarray(rng.uniform(0.1, 5.0, prefill),
                                    jnp.float32))
    pr = jnp.asarray(rng.uniform(0.0, 5.0, batch), jnp.float32)
    valid = jnp.asarray(rng.uniform(size=batch) > 0.3)
    items = make_items(batch, base=1000)
    with pinned_backend("xla"):
        want = add(cfg, state, items, pr, valid)
    with pinned_backend("interpret"):
        got = add(cfg, state, items, pr, valid)
    assert_replay_states_identical(got, want)


@pytest.mark.parametrize("mode", ["fifo", "alloc"])
def test_fused_ingest_full_capacity_add(mode):
    """A block exactly the size of the buffer, onto an empty state and onto
    a full one: fifo wraps/overwrites everything, alloc drops every overflow
    lane — both bit-identical to the all-XLA path."""
    cap = 32
    cfg = replay.ReplayConfig(capacity=cap, soft_capacity=24, min_fill=1)
    empty = replay.init(cfg, {"x": jnp.zeros(()),
                              "y": jnp.zeros((3,), jnp.int32)})
    add = replay.add_fifo if mode == "fifo" else replay.add_alloc
    pr = jnp.linspace(0.1, 5.0, cap, dtype=jnp.float32)
    with pinned_backend("xla"):
        full_w = add(cfg, empty, make_items(cap), pr)
        again_w = add(cfg, full_w, make_items(cap, base=500), pr)
    with pinned_backend("interpret"):
        full_g = add(cfg, empty, make_items(cap), pr)
        again_g = add(cfg, full_g, make_items(cap, base=500), pr)
    assert_replay_states_identical(full_g, full_w)
    assert_replay_states_identical(again_g, again_w)
    if mode == "alloc":  # every lane of the second block dropped
        assert int(again_g.total_added) == cap


def test_fused_alloc_overflow_drops_on_kernel_path():
    """The overflow sentinel (idx == C) must drop on the kernel path too —
    live slots (slot 0 in particular) keep their rows and leaves."""
    cfg = replay.ReplayConfig(capacity=16, soft_capacity=12, min_fill=1)
    state = replay.init(cfg, {"x": jnp.zeros(()),
                              "y": jnp.zeros((3,), jnp.int32)})
    with pinned_backend("interpret"):
        state = replay.add_alloc(cfg, state, make_items(12),
                                 jnp.full(12, 2.0))
        before_x = np.asarray(state.storage["x"]).copy()
        # 4 free slots, 10-lane block: 4 applied, 6 overflow lanes dropped.
        state = replay.add_alloc(cfg, state, make_items(10, base=100),
                                 jnp.full(10, 9.0))
    assert int(state.size) == 16
    x = np.asarray(state.storage["x"])
    np.testing.assert_array_equal(x[:12], before_x[:12])
    np.testing.assert_array_equal(x[12:16],
                                  np.arange(100, 104, dtype=np.float32))


@settings(max_examples=20, deadline=None)
@given(
    n_adds=st.integers(1, 5),
    batch=st.integers(1, 16),
    seed=st.integers(0, 1000),
)
def test_size_and_mass_invariants(n_adds, batch, seed):
    """size == live leaves; total == sum of leaves; sampled idx always live."""
    cfg = replay.ReplayConfig(capacity=128, soft_capacity=96, min_fill=1)
    state = replay.init(cfg, {"x": jnp.zeros(()), "y": jnp.zeros((3,), jnp.int32)})
    rng = np.random.RandomState(seed)
    for i in range(n_adds):
        pr = jnp.asarray(rng.uniform(0.1, 5.0, batch), jnp.float32)
        state = replay.add_fifo(cfg, state, make_items(batch, base=i * 100), pr)
        state = replay.evict_fifo(cfg, state)
    leaves = np.asarray(sumtree.leaves(state.tree))
    assert int(state.size) == int((leaves > 0).sum())
    assert float(sumtree.total(state.tree)) == pytest.approx(leaves.sum(), rel=1e-4)
    if replay.can_sample(cfg, state):
        idx = np.asarray(replay.sample(cfg, state, jax.random.key(seed), 8).indices)
        assert (leaves[idx] > 0).all()
