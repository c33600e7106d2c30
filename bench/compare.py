"""The numbers that decide ``correct``: gaps between what the program
produced and what the reference computes from the same inputs.

Every number is 0 for a perfect match and grows with the fault. A field
that must match exactly (a stored observation, an action, a slot index, a
live count) adds 1 when it does not. Float fields are compared by their gap
relative to a scale stated with each function.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref


def _f(x):
    return np.asarray(jax.device_get(x), dtype=np.float64)


def exact_mismatch(a, b) -> float:
    """1.0 when any element differs (after casting both to float64)."""
    a, b = _f(a), _f(b)
    return float(a.shape != b.shape or not np.array_equal(a, b))


def rel_gap(a, b, scale=None) -> float:
    """Largest |a - b| over ``scale`` (default: the median |b|, at least
    the float32 tiny value)."""
    a, b = _f(a), _f(b)
    if a.shape != b.shape:
        return 1.0
    if a.size == 0:
        return 0.0
    s = np.median(np.abs(b)) if scale is None else scale
    return float(np.max(np.abs(a - b)) / max(float(s), 1e-30))


def row_gap(obs_rows: dict, ref_rows: dict, full: np.ndarray) -> float:
    """Stored rows against the reference's rows. ``full`` marks the rows
    whose every field is fixed by the forced steps; on the others only
    ``obs`` and ``action`` are compared. Integer fields must match
    exactly; float fields by |a - b| / (1 + |b|)."""
    worst = 0.0
    for k in ("obs", "action", "returns", "discount_n", "next_obs"):
        a, b = _f(obs_rows[k]), _f(ref_rows[k])
        if a.shape != b.shape:
            return 1.0
        sel = np.ones(a.shape[0], bool) if k in ("obs", "action") else full
        a, b = a[sel], b[sel]
        if np.asarray(obs_rows[k]).dtype.kind in "iub":
            worst = max(worst, float(not np.array_equal(a, b)))
        elif a.size:
            worst = max(worst, float(np.max(np.abs(a - b) / (1.0 + np.abs(b)))))
    return worst


def tree_gap(tree) -> float:
    """A sum tree against its own leaves: every internal node must be the
    pairwise sum beneath it. Largest gap over the root's mass."""
    tree = jnp.asarray(tree)
    cap = tree.shape[0] // 2
    rebuilt = ref.tree_levels(tree[cap:].astype(jnp.float32))
    gap = jnp.max(jnp.abs(tree[1:cap].astype(jnp.float32) - rebuilt[1:cap]))
    return float(gap / jnp.maximum(jnp.abs(rebuilt[1]), 1e-30))


def leaf_gap(obs_leaves, ref_leaves) -> float:
    """Largest |leaf difference| over the median live reference leaf; a
    slot live on one side and dead on the other counts 1."""
    a, b = _f(obs_leaves), _f(ref_leaves)
    live = b > 0
    if not live.any():
        return float(np.any(a > 0))
    med = np.median(b[live])
    dead_mismatch = float(np.any((a > 0) != live))
    return max(dead_mismatch, float(np.max(np.abs(a - b)) / med))


def update_gap(p_in, p_obs, p_ref, grads_ref) -> float:
    """Worst leaf of the parameters' change: | |obs change| - |ref change| |
    over the larger of that leaf's reference change and the median leaf's
    (and of a millionth of the leaf's norm, for a leaf that does not move,
    as a target network between copies).
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone and are left out."""
    d_obs = [np.linalg.norm(_f(o) - _f(i)) for i, o in
             zip(jax.tree.leaves(p_in), jax.tree.leaves(p_obs))]
    size = [1e-6 * np.linalg.norm(_f(i)) for i in jax.tree.leaves(p_in)]
    d_ref = [np.linalg.norm(_f(r) - _f(i)) for i, r in
             zip(jax.tree.leaves(p_in), jax.tree.leaves(p_ref))]
    g = [np.linalg.norm(_f(x)) for x in jax.tree.leaves(grads_ref)]
    med_d, med_g = np.median(d_ref), np.median(g)
    worst = 0.0
    for do, dr, gn, sz in zip(d_obs, d_ref, g, size):
        if gn < 1e-3 * med_g:
            continue
        worst = max(worst, abs(do - dr) / max(dr, med_d, sz, 1e-30))
    return float(worst)
