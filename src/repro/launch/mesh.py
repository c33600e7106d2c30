"""Production mesh construction (TPU v5e).

Single pod: (data=16, model=16) = 256 chips. Multi-pod: (pod=2, data=16,
model=16) = 512 chips — the ``pod`` axis carries only data parallelism
(gradient all-reduce), matching the paper's observation that experience/
gradient aggregation tolerates the slower cross-pod links (§3: "possible for
actors and learners to run in different data-centers").

Functions only — importing this module never touches jax device state.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (JAX's default is
    ``Explicit``): shardings inside ``shard_map`` and ``jit`` stay the
    compiler's to propagate."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """Axes that carry batch/FSDP parallelism (pod folds into data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def num_chips(mesh) -> int:
    return mesh.devices.size


# TPU v5e hardware model (per chip) for the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link
