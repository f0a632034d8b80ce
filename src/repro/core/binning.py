"""Quantile binning of raw features (LightGBM-style, TPU adaptation).

The paper searches exact thresholds over raw feature values — a sort-heavy,
scatter-heavy pattern that is hostile to the TPU's dense compute units.  We
instead discretize each feature once into <=256 quantile bins (uint8) so that
split finding becomes a dense histogram contraction on the MXU.

Binning is a *per-feature* transformation, so it is identical whether computed
by one central party or independently by each vertical party on its own
columns — losslessness of FF vs. the centralized baseline is unaffected.
"""
from __future__ import annotations

import numpy as np


def interior_quantiles(n_bins: int) -> np.ndarray:
    """The n_bins - 1 interior quantile levels a bin grid is cut at.

    Single owner of the grid definition: the in-memory path
    (:func:`quantile_boundaries`) and the streaming quantile sketch
    (repro.streaming.sketch) both cut at exactly these levels, which is what
    makes an uncompacted sketch's edges bit-identical to the dense build.
    """
    return np.linspace(0.0, 1.0, n_bins + 1)[1:-1]


def quantile_boundaries(x: np.ndarray, n_bins: int) -> np.ndarray:
    """Per-feature upper-boundary grid, shape (F, n_bins - 1).

    Bin b of feature f holds values in (boundaries[f, b-1], boundaries[f, b]].
    Constant features get degenerate (all-equal) boundaries and always land in
    bin 0, which makes every candidate split on them gainless.
    """
    x = np.asarray(x, dtype=np.float64)
    if x.ndim != 2:
        raise ValueError("expected (n_samples, n_features)")
    qs = interior_quantiles(n_bins)
    return np.quantile(x, qs, axis=0).T.astype(np.float64)  # (F, n_bins-1)


def apply_bins(x: np.ndarray, boundaries: np.ndarray) -> np.ndarray:
    """Digitize raw values into uint8 bin ids with the given boundaries."""
    x = np.asarray(x, dtype=np.float64)
    out = np.empty(x.shape, dtype=np.uint8)
    for f in range(x.shape[1]):  # per-feature searchsorted (fit-time, NumPy)
        out[:, f] = np.searchsorted(boundaries[f], x[:, f], side="left")
    return out


def bin_dataset(x: np.ndarray, n_bins: int) -> tuple[np.ndarray, np.ndarray]:
    """Fit + apply quantile binning. Returns (binned uint8, boundaries)."""
    b = quantile_boundaries(x, n_bins)
    return apply_bins(x, b), b


# ------------------------------------------------------ categorical columns
# A categorical column is not cut at quantiles: its bin is its category's
# code, the rank of the value among the column's sorted distinct training
# values.  The dictionary (those sorted values) stays with the party that
# owns the column, exactly like a numeric column's edges.

def category_dictionary(col: np.ndarray, n_bins: int) -> np.ndarray:
    """Sorted distinct values of one categorical column: code c is
    ``dictionary[c]``.  At most ``n_bins - 1`` categories fit, so that code
    ``len(dictionary)`` stays free for values unseen at fit time."""
    try:
        cats = np.unique(np.asarray(col))
    except TypeError as e:
        raise ValueError(f"categorical values must be mutually orderable "
                         f"(all numbers or all strings): {e}") from e
    if cats.size > n_bins - 1:
        raise ValueError(
            f"categorical column has {cats.size} categories, above "
            f"n_bins - 1 = {n_bins - 1}: each category needs a bin of its "
            f"own plus one for unseen values — raise n_bins")
    return cats


def apply_categories(col: np.ndarray, dictionary: np.ndarray) -> np.ndarray:
    """uint8 codes of ``col`` under a fit-time dictionary; a value the
    dictionary lacks gets ``len(dictionary)``, a code no training row has,
    so every set split sends it right."""
    col = np.asarray(col)
    k = len(dictionary)
    if k == 0:
        return np.zeros(col.shape, np.uint8)
    try:
        pos = np.searchsorted(dictionary, col)
        seen = dictionary[np.minimum(pos, k - 1)] == col
    except TypeError as e:
        raise ValueError(f"categorical values do not compare with the "
                         f"fit-time categories: {e}") from e
    return np.where(seen, pos, k).astype(np.uint8)
