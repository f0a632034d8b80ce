"""Plain reference for the benchmark's correctness check.

A straightforward NumPy statement of what a federated random forest fit and
its served answers must be, written from the algorithm and not from the code
under test; it imports nothing of the program and takes none of its tables:

* quantile binning of each raw feature (``n_bins - 1`` interior quantiles of
  the training column, a value goes to the first bin whose upper edge is not
  below it);
* row alignment on salted SHA-256 hashes of the sample IDs: the common rows
  in ascending order of their hex digests;
* the master's randomness: tree ``t`` draws its bootstrap counts and then its
  feature subsample from ``default_rng([seed, t])``;
* class ids permuted by ``default_rng(seed).permutation(n_classes)`` before
  training (the label encoding);
* level-wise CART: per node, weighted per-bin label statistics, impurity
  decrease of every (feature, bin) split, the largest gain wins with ties to
  the lower feature id and then the lower bin; a node splits when that gain
  exceeds ``max(min_impurity_decrease, 1e-9)`` and its weighted count reaches
  ``min_samples_split``; both children need ``min_samples_leaf``.

Trees travel in one neutral layout, one tree per dict: ``is_leaf`` (nn,)
bool, ``feature`` (nn,) global feature id or -1, ``bin`` (nn,) split bin or
-1 (rows with a larger bin go right), ``stats`` (nn, C) weighted label
statistics of every node, in heap order (children of i are 2i+1, 2i+2).

:func:`follow` judges a tree in float64 along its own splits, so a near tie
broken the other way costs only its own small regret instead of a different
subtree.  :func:`build_tree` grows a tree itself at a stated precision; in
bfloat16 it is the control that the comparison has to reject.
"""
from __future__ import annotations

import hashlib
import math

import numpy as np

GAIN_FLOOR = 1e-9      # a split must gain more than this (or the config's)


# ------------------------------------------------------------------ binning
def quantile_boundaries(x: np.ndarray, n_bins: int) -> np.ndarray:
    """(F, n_bins - 1) upper bin edges from a training matrix."""
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    return np.quantile(np.asarray(x, np.float64), qs, axis=0).T


def apply_bins(x: np.ndarray, edges: np.ndarray,
               rounding=None) -> np.ndarray:
    """Bin ids (uint8) of ``x`` against ``edges``; ``rounding`` maps both to a
    lower precision first (the serving control)."""
    x = np.asarray(x, np.float64)
    if rounding is not None:
        x, edges = rounding(x), rounding(edges)
    out = np.empty(x.shape, np.uint8)
    for f in range(x.shape[1]):
        out[:, f] = np.searchsorted(edges[f], x[:, f], side="left")
    return out


# ------------------------------------------------------- alignment + master
def aligned_order(ids: np.ndarray, salt: str) -> np.ndarray:
    """Indices of ``ids`` in the order alignment puts the common rows."""
    digests = np.array([hashlib.sha256(f"{salt}:{i}".encode()).hexdigest()
                        for i in ids])
    return np.argsort(digests, kind="stable")


def master_draws(seed: int, tree: int, n_rows: int, n_features: int,
                 max_features: float) -> tuple[np.ndarray, np.ndarray]:
    """(bootstrap counts (n_rows,), feature subsample mask (n_features,))."""
    rng = np.random.default_rng([seed, tree])
    w = np.bincount(rng.integers(0, n_rows, size=n_rows), minlength=n_rows)
    k = max(1, math.ceil(max_features * n_features))
    sel = np.zeros(n_features, bool)
    sel[rng.choice(n_features, size=k, replace=False)] = True
    return w.astype(np.float64), sel


def label_permutation(seed: int, n_classes: int) -> np.ndarray:
    return np.random.default_rng(seed).permutation(n_classes)


def stat_channels(y: np.ndarray, task: str, n_classes: int) -> np.ndarray:
    """(N, C) per-row label statistics: class one-hot, or (1, y, y^2)."""
    if task == "classification":
        return (y[:, None] == np.arange(n_classes)[None]).astype(np.float64)
    y = np.asarray(y, np.float64)
    return np.stack([np.ones_like(y), y, y * y], axis=1)


# -------------------------------------------------------------- arithmetic
def to_bf16(a) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), held in float32."""
    bits = np.asarray(a, np.float32).view(np.uint32)
    bits = (bits + np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return bits.view(np.float32)


class Arith:
    """Elementwise arithmetic at one precision: ``r`` rounds every result."""

    def __init__(self, precision: str):
        self.precision = precision
        self.r = {"float64": lambda a: np.asarray(a, np.float64),
                  "bfloat16": to_bf16}[precision]

    def cumsum(self, a, axis):
        if self.precision == "float64":
            return np.cumsum(a, axis=axis)
        a = np.moveaxis(self.r(a), axis, 0)
        out = np.empty_like(a)
        acc = np.zeros_like(a[0])
        for i in range(a.shape[0]):
            acc = self.r(acc + a[i])
            out[i] = acc
        return np.moveaxis(out, 0, axis)

    def sum_last(self, a):
        if self.precision == "float64":
            return a.sum(-1)
        acc = np.zeros_like(a[..., 0])
        for i in range(a.shape[-1]):
            acc = self.r(acc + a[..., i])
        return acc

    def count(self, s, task):
        return self.sum_last(s) if task == "classification" else s[..., 0]

    def impurity(self, s, task):
        """n * gini (classification) or SSE (regression) of stats (..., C)."""
        r = self.r
        if task == "classification":
            n = self.sum_last(s)
            sq = self.sum_last(r(s * s))
            return r(n - r(sq / np.maximum(n, 1e-12)))
        n, s1, s2 = s[..., 0], s[..., 1], s[..., 2]
        return r(s2 - r(r(s1 * s1) / np.maximum(n, 1e-12)))

    def gains(self, hist, task, min_samples_leaf):
        """(L, K, B-1) gains of every split; invalid ones -inf."""
        r = self.r
        running = self.cumsum(r(hist), axis=2)
        left, total = running[:, :, :-1], running[:, :, -1]
        right = r(total[:, :, None] - left)
        parent = self.impurity(total, task)[:, :, None]
        gain = r(r(parent - self.impurity(left, task))
                 - self.impurity(right, task))
        ok = ((self.count(left, task) >= min_samples_leaf)
              & (self.count(right, task) >= min_samples_leaf))
        return np.where(ok, gain, -np.inf)


F64 = Arith("float64")


# ---------------------------------------------------------- level helpers
def _level_hist(seg, xb_sel, ws, width, n_bins):
    """(width, K, B, C) float64 sums of ``ws`` per (node, feature, bin)."""
    n, k = xb_sel.shape
    c = ws.shape[1]
    hist = np.empty((width, k, n_bins, c))
    step = max(1, (1 << 23) // max(n, 1))           # bound the index temp
    for j0 in range(0, k, step):
        j1 = min(k, j0 + step)
        idx = ((seg[:, None] * (j1 - j0) + np.arange(j1 - j0)[None])
               * n_bins + xb_sel[:, j0:j1]).ravel()
        size = width * (j1 - j0) * n_bins
        for ch in range(c):
            v = np.repeat(ws[:, ch], j1 - j0)
            hist[:, j0:j1, :, ch] = np.bincount(
                idx, weights=v, minlength=size).reshape(width, j1 - j0,
                                                        n_bins)
    return hist


def _node_sums(seg, ws, width):
    return np.stack([np.bincount(seg, weights=ws[:, ch], minlength=width)
                     for ch in range(ws.shape[1])], axis=1)


def _live_rows(w, xb_sel, stats):
    keep = w > 0
    return xb_sel[keep], (w[keep, None] * stats[keep])


# ------------------------------------------------------------------ follow
def follow(tree: dict, xb_sel: np.ndarray, sel_gids: np.ndarray,
           w: np.ndarray, stats: np.ndarray, p: dict) -> dict:
    """Judge one fitted tree in float64 along its own splits.

    ``xb_sel`` (N, K) are the reference's bins of the tree's selected
    features (global ids ``sel_gids``, ascending) in aligned row order,
    ``w`` the bootstrap counts and ``stats`` the per-row label statistics.

    Returns ``regret``: the largest shortfall, over the tree's live nodes,
    of the gain of the split it took (0 at a node it rightly left a leaf,
    the whole best gain at one it wrongly left a leaf) below the best gain
    the reference finds there, as a share of the node's impurity;
    ``stat_gap``: the largest gap between a node's statistics and the
    reference's sums over the rows routed there, as a share of the sum of
    their absolute values; ``bad_nodes``: nodes whose leaf/split marking
    contradicts their rows (a live node neither leaf nor split, a dead node
    marked, a split on a feature outside the subsample or past the last
    bin, a split below the last level).
    """
    task, depth, n_bins = p["task"], p["max_depth"], p["n_bins"]
    msl, mss = p["min_samples_leaf"], p["min_samples_split"]
    thr = max(p["min_impurity_decrease"], GAIN_FLOOR)
    xb_sel, ws = _live_rows(w, xb_sel, stats)
    col_of = {int(g): j for j, g in enumerate(sel_gids)}
    node = np.zeros(len(ws), np.int64)
    regret, stat_gap, bad = 0.0, 0.0, 0
    root_imp = None
    for d in range(depth + 1):
        off, width = 2 ** d - 1, 2 ** d
        seg = node - off
        rows = (seg >= 0) & (seg < width)
        seg_r, ws_r, xb_r = seg[rows], ws[rows], xb_sel[rows]
        nst = _node_sums(seg_r, ws_r, width)
        nabs = _node_sums(seg_r, np.abs(ws_r), width)
        got = np.asarray(tree["stats"][off:off + width], np.float64)
        diff = np.abs(got - nst)
        gap = np.where(nabs > 0, diff / np.where(nabs > 0, nabs, 1.0),
                       np.where(diff > 0, np.inf, 0.0))
        stat_gap = max(stat_gap, float(gap.max(initial=0.0)))
        cnt = F64.count(nst, task)
        live = cnt > 0
        leaf = np.asarray(tree["is_leaf"][off:off + width], bool)
        feat = np.asarray(tree["feature"][off:off + width], np.int64)
        bins = np.asarray(tree["bin"][off:off + width], np.int64)
        split = feat >= 0
        bad += int((leaf & split).sum() + (live & ~leaf & ~split).sum()
                   + (~live & (leaf | split)).sum())
        if d == depth:
            bad += int(split.sum())
            break
        hist = _level_hist(seg_r, xb_r, ws_r, width, n_bins)
        gains = F64.gains(hist, task, msl)                    # (W, K, B-1)
        best = gains.reshape(width, -1).max(1)
        parent = F64.impurity(nst, task)
        if root_imp is None:
            root_imp = max(float(parent[0]), 1e-300)
        denom = np.maximum(parent, 1e-12 * root_imp)
        j = np.array([col_of.get(int(f), -1) for f in feat])
        usable = split & (j >= 0) & (bins >= 0) & (bins < n_bins - 1)
        bad += int((split & ~usable).sum())
        taken = np.full(width, -np.inf)
        ii = np.nonzero(usable)[0]
        taken[ii] = gains[ii, j[ii], bins[ii]]
        with np.errstate(invalid="ignore"):
            r_split = np.where(np.isfinite(taken), (best - taken) / denom,
                               np.inf)
        would = np.isfinite(best) & (best > thr) & (cnt >= mss)
        r_leaf = np.where(would, best / denom, 0.0)
        node_regret = np.where(usable, r_split, np.where(leaf & live,
                                                         r_leaf, 0.0))
        regret = max(regret, float(node_regret.max(initial=0.0)))
        # route the rows of split nodes along the tree's own splits
        seg_c = np.clip(seg, 0, width - 1)
        go = rows & usable[seg_c]
        jj = np.clip(j[seg_c[go]], 0, None)
        right = xb_sel[np.nonzero(go)[0], jj] > bins[seg_c[go]]
        node[go] = 2 * node[go] + 1 + right
    return {"regret": regret, "stat_gap": stat_gap, "bad_nodes": bad}


# ------------------------------------------------------------------- build
def build_tree(xb_sel: np.ndarray, sel_gids: np.ndarray, w: np.ndarray,
               stats: np.ndarray, p: dict, arith: Arith,
               in_round=None) -> dict:
    """Grow one tree level by level at ``arith``'s precision; inputs to the
    sums may be rounded first (``in_round``).  Below float64 the sums are
    kept in float32, as a TPU accumulates.  Returns the neutral layout."""
    task, depth, n_bins = p["task"], p["max_depth"], p["n_bins"]
    msl, mss = p["min_samples_leaf"], p["min_samples_split"]
    thr = max(p["min_impurity_decrease"], GAIN_FLOOR)
    xb_sel, ws = _live_rows(w, xb_sel, stats)
    nn, c = 2 ** (depth + 1) - 1, ws.shape[1]
    out = {"is_leaf": np.zeros(nn, bool), "feature": np.full(nn, -1),
           "bin": np.full(nn, -1), "stats": np.zeros((nn, c))}
    ws_in = ws if in_round is None else np.asarray(in_round(ws), np.float64)
    acc = np.float64 if arith.precision == "float64" else np.float32
    node = np.zeros(len(ws), np.int64)
    for d in range(depth + 1):
        off, width = 2 ** d - 1, 2 ** d
        seg = node - off
        rows = (seg >= 0) & (seg < width)
        seg_r = seg[rows]
        nst = _node_sums(seg_r, ws_in[rows], width).astype(acc)
        out["stats"][off:off + width] = nst
        cnt = arith.count(arith.r(nst), task)
        if d == depth:
            out["is_leaf"][off:off + width] = cnt > 0
            break
        hist = _level_hist(seg_r, xb_sel[rows], ws_in[rows], width,
                           n_bins).astype(acc)
        gains = arith.gains(hist, task, msl).reshape(width, -1)
        pick = gains.argmax(1)            # first max: lowest feature, bin
        g = gains[np.arange(width), pick]
        do = np.isfinite(g) & (g > thr) & (cnt >= mss)
        out["is_leaf"][off:off + width] = (cnt > 0) & ~do
        j, b = pick // (n_bins - 1), pick % (n_bins - 1)
        out["feature"][off:off + width] = np.where(do, sel_gids[j], -1)
        out["bin"][off:off + width] = np.where(do, b, -1)
        seg_c = np.clip(seg, 0, width - 1)
        go = rows & do[seg_c]
        right = xb_sel[np.nonzero(go)[0], j[seg_c[go]]] > b[seg_c[go]]
        node[go] = 2 * node[go] + 1 + right
    return out


# -------------------------------------------------------------------- walk
def walk_votes(trees: list[dict], xb: np.ndarray, n_classes: int,
               perm: np.ndarray) -> np.ndarray:
    """Class labels of rows ``xb`` (N, F) under a classification forest:
    each tree's leaf votes for its largest encoded class (first on ties),
    the most votes win (first on ties), and the winner is decoded."""
    n = xb.shape[0]
    votes = np.zeros((n, n_classes), np.int64)
    rows = np.arange(n)
    for t in trees:
        node = np.zeros(n, np.int64)
        depth = int(math.log2(len(t["is_leaf"]) + 1)) - 1
        for _ in range(depth):
            f = t["feature"][node]
            go = (f >= 0) & ~t["is_leaf"][node]
            right = xb[rows, np.clip(f, 0, None)] > t["bin"][node]
            node = np.where(go, 2 * node + 1 + right, node)
        cls = np.asarray(t["stats"])[node].argmax(1)
        cls = np.where(t["is_leaf"][node], cls, -1)
        ok = cls >= 0
        votes[rows[ok], cls[ok]] += 1
        votes[rows[~ok], :] = -(1 << 40)      # a row off a leaf has no label
    enc = votes.argmax(1)
    return np.where(votes.max(1) >= 0, np.argsort(perm)[enc], -1)
