"""Plain reference for categorical columns: CART with category-set splits.

The same statement as ``reference.py`` (quantile binning, hashed-ID
alignment, the master's draws and the label encoding are taken from there
unchanged), for a table some of whose columns are categorical.  It imports
nothing of the program and takes none of its tables:

* a categorical column is coded by its own sorted distinct training values
  (code c is the c-th smallest); a value missing from that dictionary gets
  the code ``len(dictionary)``, which no training row has;
* at a node, a categorical column's categories are ordered by their share
  of class 1 (classification, two classes) or their mean target
  (regression), computed in float64 from the node's weighted sums;
  **ties go to the lower code**, and categories with no weighted rows at
  the node come last.  The k - 1 prefixes of that order are the candidate
  left sets; by CART's ordering theorem (Breiman et al. 1984, §9.4; Fisher
  1958) the best of them is the best of all 2^(k-1) - 1 set splits
  (:func:`best_partition_gain` checks that by brute force);
* a numeric column splits at a bin as in ``reference.py``;
* the largest gain wins, **ties to the lower feature id, then to the
  shorter prefix (the lower bin)**; a row goes left when its code is in the
  left set, so a category with no rows at a node, and a value unseen at fit
  time, always goes right.

Trees travel in ``reference.py``'s neutral layout plus ``left`` (nn, n_bins)
bool: a categorical split's left codes, all False at numeric splits.
:func:`follow` judges a tree in float64 along its own splits;
:func:`build_tree` grows one itself at a stated precision.
"""
from __future__ import annotations

import itertools
import math

import numpy as np

import reference
from reference import F64, GAIN_FLOOR, Arith


# ------------------------------------------------------------------ binning
def category_dictionary(col: np.ndarray) -> np.ndarray:
    """Sorted distinct values of a categorical column."""
    return np.unique(np.asarray(col))


def category_codes(col: np.ndarray, dictionary: np.ndarray) -> np.ndarray:
    """uint8 codes under ``dictionary``; unseen values get its length."""
    col = np.asarray(col)
    k = len(dictionary)
    pos = np.searchsorted(dictionary, col)
    seen = dictionary[np.minimum(pos, k - 1)] == col
    return np.where(seen, pos, k).astype(np.uint8)


def bin_table(x: np.ndarray, cat_gids, n_bins: int):
    """The reference's bins of a training table: quantile bins of numeric
    columns, codes of categorical ones.  Returns ``(xb, edges, dicts)``;
    ``dicts`` maps a categorical column to its dictionary."""
    x = np.asarray(x)
    cat = np.zeros(x.shape[1], bool)
    cat[list(cat_gids)] = True
    edges = np.zeros((x.shape[1], n_bins - 1))
    edges[~cat] = reference.quantile_boundaries(x[:, ~cat].astype(np.float64),
                                                n_bins)
    dicts = {int(j): category_dictionary(x[:, j]) for j in np.flatnonzero(cat)}
    return apply_table(x, edges, dicts), edges, dicts


def apply_table(x: np.ndarray, edges: np.ndarray, dicts: dict) -> np.ndarray:
    """Bins of rows ``x`` against fit-time ``edges`` and dictionaries."""
    x = np.asarray(x)
    out = np.empty(x.shape, np.uint8)
    num = np.array([j not in dicts for j in range(x.shape[1])])
    out[:, num] = reference.apply_bins(x[:, num].astype(np.float64),
                                       edges[num])
    for j, d in dicts.items():
        out[:, j] = category_codes(x[:, j], d)
    return out


# ------------------------------------------------------------ set splits
def scan_order(hist: np.ndarray, is_cat: np.ndarray, task: str,
               arith: Arith = F64) -> np.ndarray:
    """(L, K, B) order of each column's bins in the prefix scan: categories
    by statistic, ties to the lower code, empty ones last; numeric bins as
    they are."""
    n = arith.count(hist, task)
    with np.errstate(invalid="ignore", divide="ignore"):
        key = arith.r(hist[..., 1] / np.where(n > 0, n, 1.0))
    key = np.where(n > 0, key, np.inf)
    key = np.where(np.asarray(is_cat)[None, :, None], key,
                   np.arange(hist.shape[2])[None, None, :])
    return np.argsort(key, axis=2, kind="stable")


def set_gains(hist: np.ndarray, is_cat: np.ndarray, task: str,
              min_samples_leaf: int, arith: Arith = F64):
    """(gains (L, K, B-1), order (L, K, B)): the gain of every prefix of
    every column's scan order; invalid ones -inf."""
    order = scan_order(hist, is_cat, task, arith)
    sorted_hist = np.take_along_axis(hist, order[..., None], axis=2)
    return arith.gains(sorted_hist, task, min_samples_leaf), order


def split_gain(stats_left: np.ndarray, stats_total: np.ndarray, task: str,
               min_samples_leaf: int) -> float:
    """float64 gain of one split from its left and total sums (-inf when a
    side is short of ``min_samples_leaf``)."""
    right = stats_total - stats_left
    if (F64.count(stats_left, task) < min_samples_leaf
            or F64.count(right, task) < min_samples_leaf):
        return -np.inf
    return float(F64.impurity(stats_total, task)
                 - F64.impurity(stats_left, task)
                 - F64.impurity(right, task))


def best_partition_gain(hist_col: np.ndarray, task: str,
                        min_samples_leaf: int) -> float:
    """Best gain over every partition of a column's present categories into
    two non-empty sets, by brute force (2^(k-1) - 1 of them): the
    ordering rule's check."""
    present = np.flatnonzero(F64.count(hist_col, task) > 0)
    total = hist_col.sum(0)
    best = -np.inf
    if len(present) < 2:
        return best
    first, rest = present[0], present[1:]
    for r in range(len(rest)):           # the set holding `first`, never all
        for extra in itertools.combinations(rest, r):
            left = hist_col[[first, *extra]].sum(0)
            best = max(best, split_gain(left, total, task,
                                        min_samples_leaf))
    return best


# ------------------------------------------------------------------ follow
def follow(tree: dict, xb_sel: np.ndarray, sel_gids: np.ndarray,
           sel_cat: np.ndarray, w: np.ndarray, stats: np.ndarray,
           p: dict) -> dict:
    """Judge one tree in float64 along its own splits, as
    ``reference.follow`` does, with set splits on the columns ``sel_cat``
    marks.  Beyond ``regret``, ``stat_gap`` and ``bad_nodes`` (which here
    also counts a left set that holds a category with no rows at its node,
    or is empty), returns ``set_splits`` and ``noncontiguous``: the tree's
    categorical splits, and those whose left set is not a run of
    consecutive codes."""
    task, depth, n_bins = p["task"], p["max_depth"], p["n_bins"]
    msl, mss = p["min_samples_leaf"], p["min_samples_split"]
    thr = max(p["min_impurity_decrease"], GAIN_FLOOR)
    keep = w > 0
    xb_sel, ws = xb_sel[keep], w[keep, None] * stats[keep]
    col_of = {int(g): j for j, g in enumerate(sel_gids)}
    sel_cat = np.asarray(sel_cat, bool)
    node = np.zeros(len(ws), np.int64)
    regret, stat_gap, bad, n_set, n_noncontig = 0.0, 0.0, 0, 0, 0
    root_imp = None
    for d in range(depth + 1):
        off, width = 2 ** d - 1, 2 ** d
        seg = node - off
        rows = (seg >= 0) & (seg < width)
        seg_r, ws_r, xb_r = seg[rows], ws[rows], xb_sel[rows]
        nst = reference._node_sums(seg_r, ws_r, width)
        nabs = reference._node_sums(seg_r, np.abs(ws_r), width)
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
        left = np.asarray(tree["left"][off:off + width], bool)
        split = feat >= 0
        bad += int((leaf & split).sum() + (live & ~leaf & ~split).sum()
                   + (~live & (leaf | split)).sum())
        if d == depth:
            bad += int(split.sum())
            break
        hist = reference._level_hist(seg_r, xb_r, ws_r, width, n_bins)
        gains, _ = set_gains(hist, sel_cat, task, msl)
        best = gains.reshape(width, -1).max(1)
        parent = F64.impurity(nst, task)
        if root_imp is None:
            root_imp = max(float(parent[0]), 1e-300)
        denom = np.maximum(parent, 1e-12 * root_imp)
        j = np.array([col_of.get(int(f), -1) for f in feat])
        jc = np.clip(j, 0, None)
        is_set = split & (j >= 0) & sel_cat[jc]
        usable = split & (j >= 0) & np.where(
            is_set, True, (bins >= 0) & (bins < n_bins - 1))
        taken = np.full(width, -np.inf)
        for i in np.nonzero(usable)[0]:
            h = hist[i, jc[i]]
            if is_set[i]:
                present = F64.count(h, task) > 0
                codes = np.flatnonzero(left[i])
                if codes.size == 0 or not present[left[i]].all():
                    usable[i] = False
                    continue
                n_set += 1
                n_noncontig += int(codes[-1] - codes[0] + 1 != codes.size)
                taken[i] = split_gain(h[left[i]].sum(0), h.sum(0), task, msl)
            else:
                taken[i] = gains[i, jc[i], bins[i]]
        bad += int((split & ~usable).sum())
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
        gi = np.nonzero(go)[0]
        s = seg_c[gi]
        code = xb_sel[gi, jc[s]]
        right = np.where(is_set[s], ~left[s, code], code > bins[s])
        node[gi] = 2 * node[gi] + 1 + right
    return {"regret": regret, "stat_gap": stat_gap, "bad_nodes": bad,
            "set_splits": n_set, "noncontiguous": n_noncontig}


# ------------------------------------------------------------------- build
def build_tree(xb_sel: np.ndarray, sel_gids: np.ndarray, sel_cat: np.ndarray,
               w: np.ndarray, stats: np.ndarray, p: dict,
               arith: Arith = F64, in_round=None) -> dict:
    """Grow one tree level by level at ``arith``'s precision (below float64
    the sums are kept in float32, as a TPU accumulates), with set splits on
    the columns ``sel_cat`` marks.  Returns the neutral layout."""
    task, depth, n_bins = p["task"], p["max_depth"], p["n_bins"]
    msl, mss = p["min_samples_leaf"], p["min_samples_split"]
    thr = max(p["min_impurity_decrease"], GAIN_FLOOR)
    keep = w > 0
    xb_sel, ws = xb_sel[keep], w[keep, None] * stats[keep]
    sel_cat = np.asarray(sel_cat, bool)
    nn, c = 2 ** (depth + 1) - 1, ws.shape[1]
    out = {"is_leaf": np.zeros(nn, bool), "feature": np.full(nn, -1),
           "bin": np.full(nn, -1), "stats": np.zeros((nn, c)),
           "left": np.zeros((nn, n_bins), bool)}
    ws_in = ws if in_round is None else np.asarray(in_round(ws), np.float64)
    acc = np.float64 if arith.precision == "float64" else np.float32
    node = np.zeros(len(ws), np.int64)
    for d in range(depth + 1):
        off, width = 2 ** d - 1, 2 ** d
        seg = node - off
        rows = (seg >= 0) & (seg < width)
        seg_r = seg[rows]
        nst = reference._node_sums(seg_r, ws_in[rows], width).astype(acc)
        out["stats"][off:off + width] = nst
        cnt = arith.count(arith.r(nst), task)
        if d == depth:
            out["is_leaf"][off:off + width] = cnt > 0
            break
        hist = reference._level_hist(seg_r, xb_sel[rows], ws_in[rows],
                                     width, n_bins).astype(acc)
        gains, order = set_gains(hist, sel_cat, task, msl, arith)
        gains = gains.reshape(width, -1)
        pick = gains.argmax(1)          # first max: lowest feature, prefix
        g = gains[np.arange(width), pick]
        do = np.isfinite(g) & (g > thr) & (cnt >= mss)
        out["is_leaf"][off:off + width] = (cnt > 0) & ~do
        j, b = pick // (n_bins - 1), pick % (n_bins - 1)
        out["feature"][off:off + width] = np.where(do, sel_gids[j], -1)
        out["bin"][off:off + width] = np.where(do, b, -1)
        is_set = do & sel_cat[j]
        left = np.zeros((width, n_bins), bool)
        for i in np.nonzero(is_set)[0]:
            left[i, order[i, j[i], :b[i] + 1]] = True
        out["left"][off:off + width] = left
        seg_c = np.clip(seg, 0, width - 1)
        go = rows & do[seg_c]
        gi = np.nonzero(go)[0]
        s = seg_c[gi]
        code = xb_sel[gi, j[s]]
        right = np.where(is_set[s], ~left[s, code], code > b[s])
        node[gi] = 2 * node[gi] + 1 + right
    return out


# -------------------------------------------------------------------- walk
def walk_votes(trees: list[dict], xb: np.ndarray, n_classes: int,
               perm: np.ndarray) -> np.ndarray:
    """Class labels of rows ``xb`` (N, F) under a classification forest,
    as ``reference.walk_votes``; at a set split a row goes left when its
    code is in the left set (an unseen code never is)."""
    n = xb.shape[0]
    votes = np.zeros((n, n_classes), np.int64)
    rows = np.arange(n)
    for t in trees:
        node = np.zeros(n, np.int64)
        depth = int(math.log2(len(t["is_leaf"]) + 1)) - 1
        left = np.asarray(t["left"], bool)
        for _ in range(depth):
            f = t["feature"][node]
            go = (f >= 0) & ~t["is_leaf"][node]
            code = xb[rows, np.clip(f, 0, None)]
            is_set = left[node].any(1)
            right = np.where(is_set, ~left[node, code], code > t["bin"][node])
            node = np.where(go, 2 * node + 1 + right, node)
        cls = np.asarray(t["stats"])[node].argmax(1)
        cls = np.where(t["is_leaf"][node], cls, -1)
        ok = cls >= 0
        votes[rows[ok], cls[ok]] += 1
        votes[rows[~ok], :] = -(1 << 40)      # a row off a leaf has no label
    enc = votes.argmax(1)
    return np.where(votes.max(1) >= 0, np.argsort(perm)[enc], -1)
