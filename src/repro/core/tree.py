"""Level-synchronous federated CART builder (the paper's Alg. 1/2/5/6).

This is SPMD code: one logical "party" per index of the ``parties`` axis.  It
runs unchanged under

  * ``jax.vmap(..., axis_name=PARTY_AXIS)``           — single-host simulation
  * ``shard_map`` over a mesh axis named ``parties``  — production (dry-run)

TPU adaptation of the paper's recursive MPI algorithm (see DESIGN.md §2):

  * breadth-first level building: all ``2^d`` nodes of a depth split together;
    the master's per-node gather/argmax/notify/broadcast round-trips collapse
    into THREE collectives per level (all_gather of masked local bests, and
    one psum carrying the owner-computed partition bits);
  * the master is dissolved into those collectives — every party evaluates the
    argmax of the gathered (gain, feature-id) pairs identically, which is the
    same function the trusted server computes in the paper;
  * trees live in fixed-shape heap arrays (node i -> children 2i+1, 2i+2).

Frontier compaction (the §Perf tentpole): deep levels are mostly dead — a
node stays "live" only while samples are still routed to it, so the live
count is bounded by the sample count and, in practice, shrinks further as
branches bottom out into leaves.  At depths where the heap level is wider
than ``params.frontier_cap``, live nodes are remapped (in heap order) into a
dense segment index of static capacity ``min(2^d, N, frontier_cap)`` and the
histogram -> gains -> per-node argbest stage runs over compact slots, one
while_loop pass per ``cap`` live nodes — so histogram/gain compute scales
with the ACTUAL live-node count, not the worst-case ``2^d`` width.  The
per-node best-split results are scattered back to heap order before the
collectives, which keeps the cross-party protocol (and therefore the built
``PartyTree``) bit-identical to the dense build: compaction only re-indexes
which histogram row a live node's samples accumulate into, never which
samples they are.

Feature compaction: the master's feature subsample is fixed for a tree, so
a party's split search needs only the columns it selected.  Where the
per-tree budget ``k`` (``ForestParams.features_per_tree``) is below the
party's padded width, each tree gathers its selected columns (ascending,
filler slots masked) once before the level loop and every level histograms
those ``k`` columns, not all ``Fp``.  Each column's histogram, gains and best
split are computed independently of the others, so the trees are
bit-identical to a search over every column; the winning compact index maps
back to the party-local column before it is recorded.

Categorical columns: a column the partition marks categorical holds
category codes, and its split is a set of categories, not a threshold.  The
same histogram feeds it; per node the categories are put in CART's order
(``impurity.category_order``) and the usual prefix scan picks the best set,
recorded as a bitset over the bins (``split_cats``) held by the split's
owner alone, like a threshold.  Routing sends a row right when its code is
not in the set, so a category with no rows at a node (a value unseen at fit
time too) always goes right.  Such a fit returns a ``CategoricalPartyTree``;
without categorical columns none of this is traced, the fit returns a
``PartyTree`` and its ``split_cats`` reads None.

Distributed model storage is preserved exactly: a party records (feature,
threshold) only for nodes it owns (``has_split``); the shared structure
(``is_leaf`` + heap layout) is what the paper calls "keeping the node
structure"; ``owner``/``split_gid`` are the master-side view.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import impurity
from repro.core.types import PARTY_AXIS, ForestParams
from repro.kernels import ops

_BIG = jnp.int32(2**30)


class PartyTree(NamedTuple):
    """One party's view of one tree (all arrays sized n_nodes = 2^(k+1)-1)."""

    is_leaf: jnp.ndarray      # (nn,)  bool   — shared structure
    leaf_stats: jnp.ndarray   # (nn,C) f32    — shared (labels are shared, §4.3)
    has_split: jnp.ndarray    # (nn,)  bool   — "this node's split is mine"
    split_floc: jnp.ndarray   # (nn,)  int32  — LOCAL feature index (mine only)
    split_bin: jnp.ndarray    # (nn,)  int32  — split bin   (mine only)
    owner: jnp.ndarray        # (nn,)  int32  — master view: owning party
    split_gid: jnp.ndarray    # (nn,)  int32  — master view: encoded feature id

    @property
    def split_cats(self) -> None:
        """Threshold splits only: no category sets."""
        return None


class CategoricalPartyTree(NamedTuple):
    """A ``PartyTree`` grown over categorical columns: the same fields, and
    each set split's left category set."""

    is_leaf: jnp.ndarray
    leaf_stats: jnp.ndarray
    has_split: jnp.ndarray
    split_floc: jnp.ndarray
    split_bin: jnp.ndarray    # a set split's last scan position going left
    owner: jnp.ndarray
    split_gid: jnp.ndarray
    # (nn, W) uint32 — bit c of word c // 32 is set when code c goes left
    # (mine only; zeros elsewhere and at threshold splits)
    split_cats: jnp.ndarray


def cat_words(n_bins: int) -> int:
    """uint32 words of a category bitset over ``n_bins`` codes."""
    return -(-n_bins // 32)


def category_member(words: jnp.ndarray, node: jnp.ndarray,
                    code: jnp.ndarray) -> jnp.ndarray:
    """Whether ``code`` lies in the left set of node ``node``: ``words`` is a
    (nodes, W) uint32 bitset table, ``node`` and ``code`` int32 arrays that
    broadcast together."""
    w = words.shape[-1]
    word = words.reshape(-1)[node * w + (code >> 5)]
    return ((word >> (code & 31).astype(jnp.uint32)) & 1) == 1


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., B) bool -> (..., W) uint32 bitset words."""
    b = bits.shape[-1]
    w = cat_words(b)
    bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, w * 32 - b)])
    bits = bits.reshape(bits.shape[:-1] + (w, 32)).astype(jnp.uint32)
    return (bits << jnp.arange(32, dtype=jnp.uint32)).sum(-1,
                                                          dtype=jnp.uint32)


def _local_argbest(gains: jnp.ndarray, feat_gid: jnp.ndarray):
    """Per-node best split with the deterministic lexicographic tie-break
    (max gain, then min global feature id, then min bin).

    The two-stage max — local per party, then global across parties — yields
    exactly the same winner as a centralized single pass because max and the
    lexicographic tie-break are associative.  This is what makes FF(M) ==
    FF(1) *bit-identical*, not just statistically close.
    """
    _, fp, bm1 = gains.shape
    g = gains.max((1, 2))
    elig = (gains == g[:, None, None]) & jnp.isfinite(gains)
    gid_m = jnp.broadcast_to(feat_gid[None, :, None].astype(jnp.int32), gains.shape)
    gid = jnp.where(elig, gid_m, _BIG).min((1, 2))
    sel = elig & (gid_m == gid[:, None, None])
    bin_m = jnp.broadcast_to(jnp.arange(bm1, dtype=jnp.int32)[None, None, :], gains.shape)
    bin_ = jnp.where(sel, bin_m, _BIG).min((1, 2))
    floc_m = jnp.broadcast_to(jnp.arange(fp, dtype=jnp.int32)[None, :, None], gains.shape)
    floc = jnp.where(sel, floc_m, _BIG).min((1, 2))
    return g, gid, bin_, floc


def reduce_level(g_all, gid_all, bin_all, cnt, params: ForestParams):
    """The paper's master reduce over one level's gathered party bests.

    ``g_all``/``gid_all``/``bin_all`` are the (M, width) stacked per-party
    best (gain, global feature id, bin) from the local split search; ``cnt``
    the (width,) shared node sample counts.  Returns
    ``(do_split, owner_lv, gid_best, bin_best)`` — the decision every party
    (and the paper's trusted master) computes identically: max gain with the
    lexicographic tie-break (min gid, then min bin via min owner), gated on
    the impurity threshold and ``min_samples_split``.

    Pure max/min/compare arithmetic — exact in any execution order — so the
    in-graph collective build (``build_tree``) and the transport-backed
    distributed build (federation/distributed.py), which calls this eagerly
    on gathered numpy arrays, make bit-identical decisions.
    """
    g_best = g_all.max(0)
    elig = (g_all == g_best[None]) & jnp.isfinite(g_all)
    gid_best = jnp.where(elig, gid_all, _BIG).min(0)
    sel = elig & (gid_all == gid_best[None])
    m = g_all.shape[0]
    owner_lv = jnp.where(sel, jnp.arange(m, dtype=jnp.int32)[:, None],
                         _BIG).min(0)
    bin_best = jnp.where(sel, bin_all, _BIG).min(0)
    thr = max(params.min_impurity_decrease, 1e-9)
    do_split = (jnp.isfinite(g_best) & (g_best > thr)
                & (cnt >= params.min_samples_split))
    return do_split, owner_lv, gid_best, bin_best


def _best_splits(hist, fmask, feat_gid, is_cat, params):
    """Each node's best local split from its (L, F, B, C) histogram:
    ``(gain, gid, bin, floc)``.  With categorical columns (``is_cat``) each
    column's bins enter the prefix scan in ``impurity.category_order``
    (numeric bins as they are), the winner's left set comes back as bitset
    words too, and a categorical winner's ``bin`` is the last scan position
    that goes left."""
    if is_cat is not None:
        with jax.named_scope("split.categorical"):
            order = impurity.category_order(hist, is_cat, params.task)
            hist = jnp.take_along_axis(hist, order[..., None], axis=2)
    gains = impurity.split_gains(hist, params.task, params.min_samples_leaf)
    gains = jnp.where(fmask[None, :, None], gains, -jnp.inf)
    best = _local_argbest(gains, feat_gid)
    if is_cat is None:
        return best
    _, _, bin_, floc = best
    with jax.named_scope("split.categorical"):
        lv, k, b = order.shape
        f = jnp.clip(floc, 0, k - 1)
        scan = jnp.take_along_axis(order, f[:, None, None], axis=1)[:, 0]
        prefix = jnp.arange(b)[None, :] <= bin_[:, None]
        left = jnp.zeros((lv, b), bool).at[
            jnp.arange(lv)[:, None], scan].set(prefix)
        words = _pack_bits(left & is_cat[f][:, None])
    return (*best, words)


def _split_search_dense(xb, seg, wstats, fmask, feat_gid, width, params,
                        hist_impl, prev_hist, is_cat=None):
    """Seed path: histogram every heap slot of the level at once."""
    fp_dim = xb.shape[1]
    if params.hist_subtraction and prev_hist is not None:
        # Beyond-paper: histogram only the LEFT children (half the node
        # one-hot width), derive the right siblings by subtraction from
        # the retained parent histograms. Children of leaf parents get
        # garbage rows, but do_split is gated on cnt (true sample
        # counts), so they can never be selected.
        left_seg = jnp.where((seg >= 0) & (seg % 2 == 0), seg // 2, -1)
        hist_left = ops.histogram(xb, left_seg, wstats, width // 2,
                                  params.n_bins, impl=hist_impl)
        hist = jnp.stack([hist_left, prev_hist - hist_left],
                         axis=1).reshape(width, fp_dim, params.n_bins,
                                         wstats.shape[-1])
    else:
        hist = ops.histogram(xb, seg, wstats, width, params.n_bins,
                             impl=hist_impl)
    return _best_splits(hist, fmask, feat_gid, is_cat, params), hist


def _split_search_frontier(xb, seg, wstats, fmask, feat_gid, width, cap,
                           params, hist_impl, is_cat=None):
    """Compacted path: histogram ``cap`` live slots per pass, scatter back.

    Live node j (heap-level index, any routed sample) gets compact slot
    ``rank(j among live)``; pass k handles slots [k*cap, (k+1)*cap) and a
    while_loop stops as soon as every live node has been processed — dead
    width costs nothing.  Scatter targets are disjoint across passes, and
    each live node's histogram row accumulates exactly the samples the dense
    row would (in the same sample order), so the per-node (gain, gid, bin,
    floc) results written back to heap order are bit-identical to the dense
    search on every live node.  Dead nodes keep the -inf/_BIG defaults;
    ``do_split`` can never select them on either path (cnt gate + positive
    gain threshold), so the protocol downstream sees no difference.
    """
    n = xb.shape[0]
    # live-node ranking, shared by construction: `seg` is derived from the
    # shared routing state, so every party compacts identically.
    dump = jnp.where(seg >= 0, seg, width)
    occ = jnp.zeros((width + 1,), bool).at[dump].set(True)[:width]
    slot_of_node = jnp.cumsum(occ.astype(jnp.int32)) - 1       # (width,)
    n_live = occ.sum().astype(jnp.int32)
    sslot = jnp.where(seg >= 0, slot_of_node[jnp.clip(seg, 0)], -1)  # (n,)
    nil_idx = jnp.arange(width, dtype=jnp.int32)

    def cond(state):
        k = state[0]
        return k * cap < n_live

    def body(state):
        k, *found = state
        lo = k * cap
        in_pass = (sslot >= lo) & (sslot < lo + cap)
        seg_k = jnp.where(in_pass, sslot - lo, -1)
        hist = ops.histogram(xb, seg_k, wstats, cap, params.n_bins,
                             impl=hist_impl)
        best = _best_splits(hist, fmask, feat_gid, is_cat, params)
        # slot -> heap-level node of THIS pass (cap is the dump row)
        node_in_pass = occ & (slot_of_node >= lo) & (slot_of_node < lo + cap)
        tgt = jnp.where(node_in_pass, slot_of_node - lo, cap)
        inv = jnp.full((cap + 1,), width, jnp.int32).at[tgt].set(
            jnp.where(node_in_pass, nil_idx, width))[:cap]
        # scatter results back to heap order (width is the dump row)
        found = [lv.at[inv].set(c) for lv, c in zip(found, best)]
        return (k + 1, *found)

    init = (jnp.int32(0),
            jnp.full((width + 1,), -jnp.inf, jnp.float32),
            jnp.full((width + 1,), _BIG, jnp.int32),
            jnp.full((width + 1,), _BIG, jnp.int32),
            jnp.full((width + 1,), _BIG, jnp.int32))
    if is_cat is not None:
        init += (jnp.zeros((width + 1, cat_words(params.n_bins)),
                           jnp.uint32),)
    found = lax.while_loop(cond, body, init)[1:]
    return tuple(lv[:width] for lv in found)


def hist_columns(params: ForestParams, n_features: int,
                 party_cols: int) -> int:
    """Columns each party's split search histograms per tree: the per-tree
    feature budget, capped at the party's ``party_cols`` padded columns.
    Below ``party_cols``, ``build_tree`` gathers the selected columns."""
    return min(party_cols, params.features_per_tree(n_features))


def build_tree(xb: jnp.ndarray, feat_gid: jnp.ndarray, feat_sel: jnp.ndarray,
               weight: jnp.ndarray, y_stats: jnp.ndarray,
               params: ForestParams, *,
               hist_impl: str | None = None,
               cat_mask: jnp.ndarray | None = None) -> PartyTree:
    """Build one tree, SPMD over PARTY_AXIS.

    Args:
      xb:       (N, Fp) uint8 party-local binned features (padded).
      feat_gid: (Fp,) int32 global feature ids, -1 for padding.
      feat_sel: (F,) bool master's per-tree feature subsample (global ids);
                it selects at most ``params.features_per_tree(F)`` features
                (checked on the host by ``FederatedForest.fit``).
      weight:   (N,) float32 bootstrap weights (0 excludes a sample).
      y_stats:  (N, C) label stat channels — shared across parties (the paper
                copies encrypted labels to every client, §3.1).
      hist_impl: histogram backend override; None uses ``params.hist_impl``.
      cat_mask: (Fp,) bool, the party's categorical columns; None when the
                partition has none (a ``PartyTree`` is then returned).
    """
    n, fp = xb.shape
    c = y_stats.shape[-1]
    nn = params.n_nodes
    me = lax.axis_index(PARTY_AXIS)
    task = params.task
    hist_impl = params.hist_impl if hist_impl is None else hist_impl

    fmask = (feat_gid >= 0) & feat_sel[jnp.clip(feat_gid, 0)]
    wstats = y_stats.astype(jnp.float32) * weight[:, None]
    k = hist_columns(params, feat_sel.shape[0], fp)
    if k < fp:
        # the tree's selected columns, ascending; slots past the party's
        # selection count are fillers that can never win a split
        sel_idx, = jnp.nonzero(fmask, size=k, fill_value=0)
        fmask = jnp.arange(k) < fmask.sum()
        feat_gid = jnp.where(fmask, feat_gid[sel_idx], -1)
        xb_i32 = jnp.take(xb, sel_idx, axis=1).astype(jnp.int32)
        is_cat = (None if cat_mask is None
                  else jnp.take(cat_mask, sel_idx) & fmask)
    else:
        sel_idx = None
        xb_i32 = xb.astype(jnp.int32)
        is_cat = cat_mask

    node = jnp.zeros((n,), jnp.int32)
    is_leaf = jnp.zeros((nn,), bool)
    leaf_stats = jnp.zeros((nn, c), jnp.float32)
    has_split = jnp.zeros((nn,), bool)
    split_floc = jnp.full((nn,), -1, jnp.int32)
    split_bin = jnp.full((nn,), -1, jnp.int32)
    owner = jnp.full((nn,), -1, jnp.int32)
    split_gid = jnp.full((nn,), -1, jnp.int32)
    split_cats = (None if is_cat is None else
                  jnp.zeros((nn, cat_words(params.n_bins)), jnp.uint32))
    prev_hist = None  # parent-level histograms (hist_subtraction)

    for d in range(params.max_depth + 1):
        off, width = params.level_slice(d)
        nil = node - off
        in_lvl = (nil >= 0) & (nil < width)
        seg = jnp.where(in_lvl, nil, -1)

        # Node label stats — computed identically by every party (shared y).
        dump = jnp.where(seg >= 0, seg, width)
        nstats = jnp.zeros((width + 1, c), jnp.float32).at[dump].add(wstats)[:width]
        cnt = impurity.count_of(nstats, task)
        leaf_stats = lax.dynamic_update_slice(leaf_stats, nstats, (off, 0))

        if d == params.max_depth:  # bottom level: everything alive is a leaf
            is_leaf = lax.dynamic_update_slice(is_leaf, cnt > 0, (off,))
            break

        # ---- local split search (the Pallas histogram hot spot) ------------
        # static per level: live nodes <= min(width, N) always, so the
        # compacted path only engages where it can actually shrink the
        # histogram (cap < width); shallow levels keep the seed's dense path.
        cap = min(width, n, params.frontier_cap or width)
        if params.frontier_cap and cap < width:
            found = _split_search_frontier(
                xb_i32, seg, wstats, fmask, feat_gid, width, cap, params,
                hist_impl, is_cat)
            prev_hist = None  # compacted levels retain no dense parent hist
        else:
            found, prev_hist = \
                _split_search_dense(xb_i32, seg, wstats, fmask, feat_gid,
                                    width, params, hist_impl, prev_hist,
                                    is_cat)
        g_loc, gid_loc, bin_loc, floc_loc = found[:4]

        # ---- the paper's master: gather -> argmax -> notify, as collectives
        g_all = lax.all_gather(g_loc, PARTY_AXIS)          # (M, width)
        gid_all = lax.all_gather(gid_loc, PARTY_AXIS)
        bin_all = lax.all_gather(bin_loc, PARTY_AXIS)
        do_split, owner_lv, gid_best, bin_best = reduce_level(
            g_all, gid_all, bin_all, cnt, params)
        is_leaf = lax.dynamic_update_slice(is_leaf, (cnt > 0) & ~do_split, (off,))

        mine = do_split & (owner_lv == me)  # "receive the split message" (Alg.1)
        has_split = lax.dynamic_update_slice(has_split, mine, (off,))
        # a winning floc_loc indexes xb_i32's columns; record the party's own
        party_floc = (floc_loc if sel_idx is None
                      else jnp.take(sel_idx, floc_loc, mode="clip"))
        split_floc = lax.dynamic_update_slice(
            split_floc, jnp.where(mine, party_floc, -1), (off,))
        split_bin = lax.dynamic_update_slice(
            split_bin, jnp.where(mine, bin_loc, -1), (off,))
        owner = lax.dynamic_update_slice(
            owner, jnp.where(do_split, owner_lv.astype(jnp.int32), -1), (off,))
        split_gid = lax.dynamic_update_slice(
            split_gid, jnp.where(do_split, gid_best, -1), (off,))
        if is_cat is not None:
            cats_lv = jnp.where(mine[:, None], found[4], 0)
            split_cats = lax.dynamic_update_slice(split_cats, cats_lv,
                                                  (off, 0))

        # ---- owner computes the partition; one psum broadcasts it ----------
        # (paper Alg.2: "Receive split indices from client j and broadcast")
        nil_c = jnp.clip(nil, 0, width - 1)
        floc_lv = jnp.where(mine, floc_loc, 0)
        bin_lv = jnp.where(mine, bin_loc, 0)
        mine_s = in_lvl & mine[nil_c]
        vals = jnp.take_along_axis(
            xb_i32, floc_lv[nil_c][:, None], axis=1)[:, 0]
        go_r = vals > bin_lv[nil_c]
        if is_cat is not None:      # a set split: right unless in the set
            set_split = (cats_lv != 0).any(-1)
            go_r = jnp.where(set_split[nil_c],
                             ~category_member(cats_lv, nil_c, vals), go_r)
        go_r_loc = jnp.where(mine_s, go_r.astype(jnp.int32), 0)
        go_r = lax.psum(go_r_loc, PARTY_AXIS)  # exactly one party contributes
        advance = in_lvl & do_split[nil_c]
        node = jnp.where(advance, 2 * node + 1 + go_r, node)

    fields = (is_leaf, leaf_stats, has_split, split_floc, split_bin, owner,
              split_gid)
    if split_cats is None:
        return PartyTree(*fields)
    return CategoricalPartyTree(*fields, split_cats)


def build_forest(xb, feat_gid, feat_sels, weights, y_stats,
                 params: ForestParams, *,
                 hist_impl: str | None = None,
                 cat_mask=None) -> PartyTree:
    """SPMD bagging loop: stack T trees (leading axis T on every leaf).

    ``lax.map`` keeps HLO size O(1) in the number of trees and bounds peak
    histogram memory to one tree's level at a time.  With
    ``params.trees_per_batch > 1`` the map runs over tree CHUNKS and a vmap
    builds each chunk's trees together — per-tree results are unchanged
    (the batch dimension is independent), the chunk just shares one traversal
    of the data.
    """
    def one(args):
        sel, w = args
        return build_tree(xb, feat_gid, sel, w, y_stats, params,
                          hist_impl=hist_impl, cat_mask=cat_mask)

    tpb = params.trees_per_batch
    t = feat_sels.shape[0]
    if tpb <= 1 or t <= 1:
        return lax.map(one, (feat_sels, weights))

    # pad T up to a multiple of the batch; padded trees carry zero weights
    # and an empty feature subsample, build to all-dead stubs, and are
    # sliced off below.
    pad = -t % tpb
    sels_p = jnp.pad(feat_sels, ((0, pad), (0, 0)))
    w_p = jnp.pad(weights, ((0, pad), (0, 0)))
    n_chunks = (t + pad) // tpb
    chunked = (sels_p.reshape(n_chunks, tpb, -1),
               w_p.reshape(n_chunks, tpb, -1))
    out = lax.map(jax.vmap(one), chunked)        # leaves (n_chunks, tpb, ...)
    return jax.tree.map(
        lambda a: a.reshape((n_chunks * tpb,) + a.shape[2:])[:t], out)


def fit_spmd(params: ForestParams, hist_impl: str | None = None,
             categorical: bool = False):
    """Returns the party-local SPMD fit function (for vmap or shard_map):
    ``fn(xb, feat_gid, feat_sels, weights, y_stats)``, or with
    ``categorical`` ``fn(xb, feat_gid, cat_mask, feat_sels, weights,
    y_stats)`` (``cat_mask`` is a party argument like ``feat_gid``)."""
    if not categorical:
        return functools.partial(build_forest, params=params,
                                 hist_impl=hist_impl)

    def fit(xb, feat_gid, cat_mask, feat_sels, weights, y_stats):
        return build_forest(xb, feat_gid, feat_sels, weights, y_stats,
                            params, hist_impl=hist_impl, cat_mask=cat_mask)
    return fit
