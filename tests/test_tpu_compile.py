"""The replay kernels compile for a TPU v5e chip that is described, not
attached: the chip's own compiler refuses what interpret mode accepts
(tiling, memory spaces, layouts), so these compiles guard the TPU path on
a host without one.

Shapes are those of ``configs/apex_dqn.full()`` spread over four replay
shards, as ``chip_smoke.py`` runs it: the per-shard sample and write-back
batch, the paper's batch of 512, and one actor block of ``lanes_per_shard
x window`` rows added to a replay shard, at the smallest and the largest
capacity the automatic backend sends to Pallas.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import apex_dqn
from repro.core import replay, sumtree
from repro.kernels.sumtree_sample.ops import sumtree_sample_with_mass
from repro.kernels.sumtree_update.ops import sumtree_update
from repro.runtime import phases

_CFG = apex_dqn.full().apex
_REPLAY_SHARDS = 4
CAPACITIES = (4096, 1 << 15)
BATCHES = (_CFG.batch_size // _REPLAY_SHARDS, 512)
BLOCK = _CFG.lanes_per_shard * _CFG.window


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without one
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compiles_to_kernel(fn, *args) -> None:
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("cap", CAPACITIES)
def test_sumtree_sample_compiles_for_v5e(one_chip, cap, batch):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compiles_to_kernel(sumtree_sample_with_mass, s((2 * cap,), jnp.float32),
                        s((batch,), jnp.float32))


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("cap", CAPACITIES)
def test_sumtree_update_compiles_for_v5e(one_chip, cap, batch):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compiles_to_kernel(sumtree_update, s((2 * cap,), jnp.float32),
                        s((batch,), jnp.int32), s((batch,), jnp.float32))


@pytest.mark.parametrize("cap", CAPACITIES)
def test_replay_ingest_compiles_for_v5e(one_chip, cap):
    """Both replay adds: XLA's row scatter into the shard's storage, and
    the tree write on the update kernel."""
    env = apex_dqn.full().env
    example = jax.eval_shape(lambda: phases.item_example(
        env, jnp.zeros((1,) + env.obs_shape, env.obs_dtype)))
    rows = lambda n: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype,
                                       sharding=one_chip), example)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    cfg = replay.ReplayConfig(capacity=cap)
    state = replay.ReplayState(rows(cap), s((2 * cap,), jnp.float32),
                               *(s((), jnp.int32) for _ in range(3)))
    saved = sumtree._backend
    sumtree.set_backend("pallas")   # the choice auto makes on the chip
    try:
        for add in (replay.add_fifo, replay.add_alloc):
            _compiles_to_kernel(
                jax.jit(lambda st, it, p, v, add=add: add(cfg, st, it, p, v)),
                state, rows(BLOCK), s((BLOCK,), jnp.float32),
                s((BLOCK,), jnp.bool_))
    finally:
        sumtree.set_backend(saved)
