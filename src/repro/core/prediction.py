"""Federated Forest prediction (paper §4.2, Alg. 3/4/7/8).

Two algorithms:

  * ``forest_predict_oneround``  — the paper's contribution.  Each party routes
    every test sample through its PARTIAL tree; at foreign nodes the sample
    descends into BOTH subtrees.  The per-party result is a boolean
    leaf-membership mask (trees, samples, nodes).  Proposition 1 says the true
    leaf assignment is the per-leaf intersection across parties — here a
    single ``psum`` over the party axis FOR THE ENTIRE FOREST (the paper's
    "only one round of communication ... even for the overall forest").

  * ``forest_predict_classical`` — the multi-round baseline: samples are routed
    level by level, with the owning party broadcasting the branch decision at
    every level of every tree (one psum per (tree, level)).  This is the
    baseline of the paper's Figs. 4–6; its communication grows with depth and
    tree count while the one-round method does not.

Both are SPMD over PARTY_AXIS, like the builder.

Prediction-side sparsity (the serving tentpole): most heap slots of a deep
tree are dead, so ``forest_predict_oneround`` optionally takes a per-tree
``LeafTable`` (serving/plan.py) and emits the membership mask gathered over
live leaves — the psum payload and the vote contraction shrink from
``n_nodes`` columns to the live-leaf capacity, while the Prop. 1 intersection
semantics (and the bits of every output) are unchanged.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.core import impurity
from repro.core.tree import PartyTree, category_member
from repro.core.types import PARTY_AXIS, ForestParams

# The vote contractions select one leaf row per (tree, sample) with a 0/1
# mask.  A TPU's default f32 matmul is one bf16 pass, which would round the
# selected leaf counts/values; HIGHEST keeps the selection exact.
_EXACT = lax.Precision.HIGHEST


def tree_leaf_membership(tree: PartyTree, xb_test: jnp.ndarray,
                         params: ForestParams) -> jnp.ndarray:
    """Paper Alg. 3: (N_t, n_nodes) bool leaf-candidate mask for one party.

    Built level-by-level and concatenated once (heap order IS level order) —
    a §Perf iteration replacing per-level dynamic_update_slice of the full
    (N, n_nodes) buffer, which copied the whole mask at every depth."""
    n = xb_test.shape[0]
    cur = jnp.ones((n, 1), bool)                             # root membership
    xb = xb_test.astype(jnp.int32)
    parts = []
    for d in range(params.max_depth):
        off, width = params.level_slice(d)
        leaf_lv = lax.dynamic_slice(tree.is_leaf, (off,), (width,))
        has = lax.dynamic_slice(tree.has_split, (off,), (width,))
        floc = jnp.clip(lax.dynamic_slice(tree.split_floc, (off,), (width,)), 0)
        bins = lax.dynamic_slice(tree.split_bin, (off,), (width,))
        vals = xb[:, floc]                                   # (N, width)
        if tree.split_cats is None:
            left_ok = ~has[None] | (vals <= bins[None])      # foreign => both
            right_ok = ~has[None] | (vals > bins[None])
        else:                       # a set split: left iff the code is in it
            cats = lax.dynamic_slice(tree.split_cats, (off, 0),
                                     (width, tree.split_cats.shape[-1]))
            set_split = (cats != 0).any(-1)[None]
            member = category_member(cats, jnp.arange(width)[None], vals)
            left_ok = ~has[None] | jnp.where(set_split, member,
                                             vals <= bins[None])
            right_ok = ~has[None] | jnp.where(set_split, ~member,
                                              vals > bins[None])
        parts.append(cur & leaf_lv[None])                    # leaves stop here
        alive = cur & ~leaf_lv[None]
        cur = jnp.stack([alive & left_ok, alive & right_ok],
                        -1).reshape(n, 2 * width)
    off, width = params.level_slice(params.max_depth)
    leaf_bottom = lax.dynamic_slice(tree.is_leaf, (off,), (width,))
    parts.append(cur & leaf_bottom[None])
    return jnp.concatenate(parts, axis=1)                    # (N, n_nodes)


def masked_leaf_stats(trees: PartyTree) -> jnp.ndarray:
    """(T, nn, C) leaf stats with non-leaf rows zeroed (the vote operand)."""
    return jnp.where(trees.is_leaf[..., None], trees.leaf_stats, 0.0)


def _combine_votes(inter: jnp.ndarray, leaf: jnp.ndarray, params: ForestParams,
                   aggregate: bool = True, vote_impl: str = "einsum"):
    """Forest vote from the (T, N, L) exact leaf-assignment mask.

    ``leaf`` is the matching (T, L, C) zero-masked leaf-stats tensor — the
    full heap (L = n_nodes, from :func:`masked_leaf_stats`) or the serving
    layer's leaf-compacted gather (L = live-leaf slots).  Either way each
    sample intersects exactly one true leaf column (Prop. 1) and every other
    column contributes an exact 0.0, so the vote is bit-identical across
    compactions.

    ``aggregate=False`` returns per-tree results (T, N) — used by the
    tree-parallel production mesh, where the final vote is a cross-shard
    reduction done by the caller.

    ``vote_impl='argmax'`` (§Perf, classification only): each sample hits
    exactly one leaf, so the per-tree label is a masked max over int8 leaf
    labels — no f32 blow-up of the (T, N, L) mask."""
    if params.task == "classification":
        if vote_impl == "argmax":
            label1 = (jnp.argmax(leaf, -1) + 1).astype(jnp.int8)   # (T, L)
            per_tree = (jnp.max(jnp.where(inter, label1[:, None, :], 0), -1)
                        .astype(jnp.int32) - 1)                    # (T, N)
        else:
            # per-tree label by leaf majority, then forest majority (Alg. 4)
            stats = jnp.einsum("tnl,tlc->tnc", inter.astype(jnp.float32), leaf,
                               precision=_EXACT)
            per_tree = jnp.argmax(stats, -1)                       # (T, N)
        if not aggregate:
            return per_tree
        votes = (per_tree[..., None] ==
                 jnp.arange(params.n_classes)[None, None, :]).sum(0)
        return jnp.argmax(votes, -1)
    vals = impurity.leaf_value(leaf, params.task)            # (T, L)
    per_tree = jnp.einsum("tnl,tl->tn", inter.astype(jnp.float32), vals,
                          precision=_EXACT)
    if not aggregate:
        return per_tree
    return tree_mean(per_tree)                               # Alg. 8: averaging


def tree_mean(per_tree: jnp.ndarray) -> jnp.ndarray:
    """Forest average of (T, N) per-tree values, summed in tree order.

    The fixed order makes a row's prediction independent of the program's
    row count: the order of a ``mean`` reduction on the TPU depends on the
    shape, and served buckets must agree bit for bit with a full predict."""
    total, _ = lax.scan(lambda acc, v: (acc + v, None),
                        jnp.zeros_like(per_tree[0]), per_tree)
    return total / per_tree.shape[0]


def tree_leaf_membership_compact(tree: PartyTree, xb_test: jnp.ndarray,
                                 params: ForestParams,
                                 leaf_idx: jnp.ndarray) -> jnp.ndarray:
    """Leaf-candidate mask gathered over live leaves: (N_t, L) bool.

    ``leaf_idx`` is one tree's row of a serving ``LeafTable`` — the heap ids
    of its live leaves in ascending (heap) order, -1 padded to the static
    capacity L.  Routing still walks every heap level (the per-level masks
    are what descend the tree), but the emitted mask — and with it the
    one-round psum payload and the vote contraction — shrinks from
    ``n_nodes`` columns to L.  Column j equals the dense mask's column
    ``leaf_idx[j]`` exactly; padded columns are identically False, so they
    can never survive the cross-party intersection."""
    mem = tree_leaf_membership(tree, xb_test, params)        # (N, nn)
    valid = leaf_idx >= 0
    return jnp.take(mem, jnp.clip(leaf_idx, 0), axis=1) & valid[None]


def gather_leaf_stats(trees: PartyTree, leaf_idx: jnp.ndarray) -> jnp.ndarray:
    """(T, L, C) leaf stats gathered over a LeafTable; padded rows zeroed.

    The compact counterpart of :func:`masked_leaf_stats` — gathered rows are
    leaves by construction, so only table padding needs masking."""
    idx = jnp.clip(leaf_idx, 0)[..., None]                   # (T, L, 1)
    stats = jnp.take_along_axis(trees.leaf_stats, idx, axis=1)
    return jnp.where((leaf_idx >= 0)[..., None], stats, 0.0)


def forest_predict_oneround(trees: PartyTree, xb_test: jnp.ndarray,
                            params: ForestParams, aggregate: bool = True,
                            mask_dtype=jnp.int32,
                            vote_impl: str = "einsum",
                            leaf_idx: jnp.ndarray | None = None) -> jnp.ndarray:
    """The paper's one-round prediction. SPMD over PARTY_AXIS.

    ``mask_dtype``: the membership masks are 0/1 and M <= 255 parties, so
    a uint8 psum is exact and moves 4x fewer collective bytes than int32 —
    the §Perf-optimized setting (the baseline keeps int32, the naive
    lowering of a boolean sum).

    ``leaf_idx``: a serving ``LeafTable.leaf_idx`` array ((T, L) live-leaf
    heap ids, -1 padded — serving/plan.py) switches every tree to the
    leaf-compacted mask — same Prop. 1 intersection semantics, bit-identical
    outputs, with the single psum and the vote contraction shrunk from
    ``n_nodes`` to the table's live-leaf capacity."""
    if leaf_idx is None:
        def one(tree):
            return tree_leaf_membership(tree, xb_test, params)
        mem = lax.map(one, trees)                            # (T, N, nn) bool
        leaf = masked_leaf_stats(trees)
    else:
        def one(args):
            tree, idx = args
            return tree_leaf_membership_compact(tree, xb_test, params, idx)
        mem = lax.map(one, (trees, leaf_idx))                # (T, N, L) bool
        leaf = gather_leaf_stats(trees, leaf_idx)
    # === Proposition 1: ONE collective for the whole forest ===
    m = lax.psum(mem.astype(mask_dtype), PARTY_AXIS)
    n_parties = lax.axis_size(PARTY_AXIS)                    # static, no comm
    inter = m == jnp.asarray(n_parties, mask_dtype)          # S^l = ∩ S_i^l
    return _combine_votes(inter, leaf, params, aggregate, vote_impl)


def forest_predict_classical(trees: PartyTree, xb_test: jnp.ndarray,
                             params: ForestParams) -> jnp.ndarray:
    """Multi-round baseline: owner broadcasts the branch at every level."""
    n = xb_test.shape[0]
    xb = xb_test.astype(jnp.int32)

    def route_tree(tree: PartyTree):
        node = jnp.zeros((n,), jnp.int32)
        for _ in range(params.max_depth):
            has = tree.has_split[node]
            floc = jnp.clip(tree.split_floc[node], 0)
            bins = tree.split_bin[node]
            vals = jnp.take_along_axis(xb, floc[:, None], axis=1)[:, 0]
            go_r = vals > bins
            if tree.split_cats is not None:
                set_split = (tree.split_cats != 0).any(-1)[node]
                go_r = jnp.where(set_split, ~category_member(
                    tree.split_cats, node, vals), go_r)
            go_r_loc = jnp.where(has, go_r.astype(jnp.int32), 0)
            go_r = lax.psum(go_r_loc, PARTY_AXIS)  # one round per level (!)
            split_here = tree.owner[node] >= 0     # structure is shared
            node = jnp.where(split_here, 2 * node + 1 + go_r, node)
        inter = (jnp.arange(params.n_nodes)[None, :] == node[:, None])
        return inter & tree.is_leaf[None]

    inter = lax.map(route_tree, trees)                       # (T, N, nn)
    return _combine_votes(inter, masked_leaf_stats(trees), params)


def mask_comm_bytes(n_trees: int, n_rows: int, n_cols: int,
                    mask_dtype=jnp.int32) -> int:
    """Per-party payload of the one-round membership psum, in bytes.

    ``n_cols`` is ``params.n_nodes`` for the dense mask or the LeafTable
    capacity for the compacted one — the serving engine reports both."""
    return n_trees * n_rows * n_cols * jnp.dtype(mask_dtype).itemsize


def comm_rounds(params: ForestParams, method: str) -> int:
    """Analytic collective-round count per forest prediction (paper §Appendix)."""
    if method == "oneround":
        return 1
    if method == "classical":
        return params.n_estimators * params.max_depth
    raise ValueError(method)
