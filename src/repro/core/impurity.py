"""Impurity / gain computation from histogram statistics.

Gains are *absolute weighted impurity decreases* (parent - left - right of the
un-normalized impurity sums), matching CART's split ordering.  All quantities
are pure functions of histograms, so every party evaluates them identically —
a prerequisite for the exact-losslessness guarantee.
"""
from __future__ import annotations

import jax.numpy as jnp

_EPS = 1e-12


def stat_channels(y: jnp.ndarray, task: str, n_classes: int) -> jnp.ndarray:
    """Per-sample label statistics (N, C) accumulated by histograms."""
    if task == "classification":
        return (y[:, None] == jnp.arange(n_classes)[None, :]).astype(jnp.float32)
    y = y.astype(jnp.float32)
    return jnp.stack([jnp.ones_like(y), y, y * y], axis=-1)


def count_of(stats: jnp.ndarray, task: str) -> jnp.ndarray:
    """Weighted sample count from a stats vector (..., C)."""
    return stats.sum(-1) if task == "classification" else stats[..., 0]


def impurity_sum(stats: jnp.ndarray, task: str) -> jnp.ndarray:
    """Un-normalized impurity: n*gini (classification) or SSE (regression)."""
    if task == "classification":
        n = stats.sum(-1)
        return n - (stats * stats).sum(-1) / jnp.maximum(n, _EPS)
    n, s1, s2 = stats[..., 0], stats[..., 1], stats[..., 2]
    return s2 - s1 * s1 / jnp.maximum(n, _EPS)


def leaf_value(stats: jnp.ndarray, task: str) -> jnp.ndarray:
    """Leaf prediction from node stats: class distribution / mean target."""
    if task == "classification":
        n = jnp.maximum(stats.sum(-1, keepdims=True), _EPS)
        return stats / n
    return stats[..., 1] / jnp.maximum(stats[..., 0], _EPS)


def split_gains(hist: jnp.ndarray, task: str, min_samples_leaf: int
                ) -> jnp.ndarray:
    """Candidate gains for every (node, feature, split-bin).

    Args:
      hist: (L, F, B, C) histogram of label stats.
    Returns:
      (L, F, B-1) float32 gains; invalid splits are -inf.
    """
    left = jnp.cumsum(hist, axis=2)[:, :, :-1]          # (L, F, B-1, C)
    total = hist.sum(axis=2)                            # (L, F, C)
    right = total[:, :, None, :] - left
    parent = impurity_sum(total, task)[:, :, None]      # (L, F, 1)
    gain = parent - impurity_sum(left, task) - impurity_sum(right, task)
    nl, nr = count_of(left, task), count_of(right, task)
    ok = (nl >= min_samples_leaf) & (nr >= min_samples_leaf)
    return jnp.where(ok, gain, -jnp.inf)


def category_order(hist: jnp.ndarray, is_cat: jnp.ndarray, task: str
                   ) -> jnp.ndarray:
    """(L, F, B) order in which each column's bins enter the split scan.

    A categorical column's categories are ordered by their class-1 share
    (classification, two classes) or mean target (regression), ties to the
    lower code, and categories with no rows at the node last; CART's
    ordering theorem (Breiman et al. 1984, §9.4; Fisher 1958) makes the
    best prefix of that order the best of all 2^(k-1) - 1 set splits.  A
    numeric column keeps its bin order.

    Args:
      hist: (L, F, B, C) histogram of label stats.
      is_cat: (F,) bool, the categorical columns.
    """
    n = count_of(hist, task)
    key = jnp.where(n > 0, hist[..., 1] / jnp.maximum(n, _EPS), jnp.inf)
    bins = jnp.arange(hist.shape[2], dtype=key.dtype)
    key = jnp.where(is_cat[None, :, None], key, bins[None, None, :])
    return jnp.argsort(key, axis=2, stable=True)
