"""Seeded inputs of the benchmark: tabular data, party blocks, arrivals.

Everything here is plain NumPy and depends only on ``--seed`` and the cell's
files, so the same seed gives the same bytes whatever the program under test
does.  The two generators are copies of the repository's synthetic Table 2
analogues (blob-plus-rotation classification, low-rank nonlinear regression),
kept here so that a change to the program cannot change the benchmark's data;
the class separation is the configuration's.
"""
from __future__ import annotations

import math

import numpy as np


def make_classification(n: int, f: int, n_classes: int, *,
                        n_informative: int, class_sep: float, seed: int
                        ) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    ni = min(n_informative, f)
    centers = rng.normal(scale=class_sep, size=(n_classes, ni))
    y = rng.integers(0, n_classes, size=n)
    xi = centers[y] + rng.normal(size=(n, ni))
    mix = rng.normal(size=(ni, f)) / np.sqrt(ni)   # spread info across columns
    x = xi @ mix + 0.5 * rng.normal(size=(n, f))
    return x.astype(np.float64), y.astype(np.int64)


def make_regression(n: int, f: int, *, n_informative: int,
                    noise: float = 0.5, seed: int
                    ) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    ni = min(n_informative, f)
    x = rng.normal(size=(n, f))
    w = rng.normal(size=ni)
    y = x[:, :ni] @ w
    y = (y + np.sin(2.0 * x[:, 0]) * np.abs(w).sum() * 0.3
         + 0.5 * x[:, 1] * x[:, 2 % f])
    y = y + noise * rng.normal(size=n)
    return x.astype(np.float64), y.astype(np.float64)


def make_table(cfg: dict, n_rows: int, seed: int
               ) -> tuple[np.ndarray, np.ndarray]:
    """``n_rows`` rows of the configuration's data set (features, labels)."""
    f = int(cfg["n_features"])
    if cfg["task"] == "classification":
        return make_classification(n_rows, f, int(cfg["n_classes"]),
                                   n_informative=int(cfg["n_informative"]),
                                   class_sep=float(cfg["class_sep"]),
                                   seed=seed)
    return make_regression(n_rows, f, n_informative=int(cfg["n_informative"]),
                           seed=seed)


def sample_ids(n: int) -> np.ndarray:
    """The customers' sample IDs, as the parties key their rows."""
    return np.char.add("c", np.char.zfill(np.arange(n).astype(str), 7))


def feature_groups(party_widths: list[int]) -> list[np.ndarray]:
    """Contiguous global feature ids per party, in party order."""
    edges = np.cumsum([0] + list(party_widths))
    return [np.arange(lo, hi) for lo, hi in zip(edges[:-1], edges[1:])]


def party_row_orders(n: int, n_parties: int, seed: int) -> list[np.ndarray]:
    """Each party lists the same ``n`` customers in its own seeded order."""
    return [np.random.default_rng([seed, 1000 + i]).permutation(n)
            for i in range(n_parties)]


def stratified_poisson(rate_per_s: float, seconds: float, size_min: int,
                       size_max: int, seed: int
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Open-loop arrival schedule: (due times in s, request sizes in rows).

    Gaps are exponential with mean ``1 / rate`` and sizes log-uniform over
    ``[size_min, size_max]``.  Both are drawn by stratified quantiles — one
    draw per equal-probability stratum — so every seed sends the same
    multiset of gaps and sizes and only their order (and the jitter inside
    each stratum) depends on the seed.  Runs on different seeds then do the
    same work, and their spread is the system's, not the sampler's.
    """
    k = int(round(rate_per_s * seconds))
    if k < 1:
        raise ValueError(f"rate {rate_per_s}/s over {seconds} s sends no "
                         f"request")
    rng = np.random.default_rng([seed, 2])
    u_gap = (rng.permutation(k) + rng.random(k)) / k
    gaps = -np.log1p(-u_gap) / rate_per_s
    u_size = (rng.permutation(k) + rng.random(k)) / k
    lo, hi = math.log(size_min), math.log(size_max + 1)
    sizes = np.floor(np.exp(lo + u_size * (hi - lo))).astype(np.int64)
    sizes = np.clip(sizes, size_min, size_max)
    due = np.cumsum(gaps) - gaps[0]          # the first request is due at 0
    return due, sizes


def request_offsets(sizes: np.ndarray, pool_rows: int, seed: int
                    ) -> np.ndarray:
    """Where each request's rows start in the held-out pool."""
    rng = np.random.default_rng([seed, 3])
    return rng.integers(0, pool_rows - sizes + 1)
