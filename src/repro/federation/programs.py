"""Substrate-specialized forest programs (the fit/predict SPMD closures).

One place builds the runnable/lowerable protocol programs for both
substrates — previously core/forest.py, serving/engine.py, launch/cases.py
and launch/perf.py each hand-rolled this wiring:

  * fit:      party args (xb, feat_gid), shared (feat_sel, weights, y_stats).
    Under a sharded mesh the per-tree shared args and the PartyTree output
    shard over the "trees" axis (bagging tree-parallelism).
  * predict:  the paper's one-round protocol.  Simulated -> every party
    computes the aggregated forest output (vmap keeps the party stack, take
    row 0).  Sharded -> per-tree outputs (aggregate=False hook) with the
    forest vote as the caller-side cross-shard reduction, trees sharded over
    (parties, trees) — exactly the serving engine's production program.

``party0`` normalizes the two output conventions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import prediction, tree
from repro.core.types import PARTY_AXIS, ForestParams


def party0(out):
    """Master-side view of a program output: simulated programs return a
    per-party stack (row 0 = the shared result), sharded predict programs
    return the already-reduced global result."""
    out = np.asarray(out)
    return out[0] if out.ndim > 1 else out


def forest_fit_program(substrate, params: ForestParams,
                       hist_impl: str | None = None, *,
                       tree_sharded: bool = True, categorical: bool = False):
    """fn(xb, feat_gid, feat_sel, weights, y_stats) -> PartyTree stack.

    ``tree_sharded=False`` keeps the per-tree args/outputs replicated across
    a mesh's "trees" axis — for callers whose tree count doesn't divide it
    (boosting fits one tree per round).  ``categorical=True`` takes the
    partition's (M, Fp) ``cat_mask`` as a third party argument,
    fn(xb, feat_gid, cat_mask, feat_sel, weights, y_stats), and grows set
    splits on those columns (in-process substrates only)."""
    if params.needs_resolution:
        raise ValueError(
            "frontier_cap/trees_per_batch='auto' resolve at fit time from "
            "the training set; pass params.resolved(n_samples) to build a "
            "program directly")
    if categorical and substrate.name == "distributed":
        raise ValueError(
            "categorical columns are not supported on the distributed "
            "substrate: fit them on 'simulated' or 'sharded'")
    fit_fn = tree.fit_spmd(params, hist_impl, categorical)
    n_party = 3 if categorical else 2
    if substrate.mesh is None:
        from repro.federation import distributed
        return substrate.program(
            fit_fn, n_party, 3,
            distributed=distributed.forest_fit_spec(params, hist_impl))
    tree_ax = substrate.tree_axis if tree_sharded else None
    per_tree = P(tree_ax) if tree_ax else P()
    out = P(PARTY_AXIS, tree_ax) if tree_ax else P(PARTY_AXIS)
    return substrate.program(fit_fn, n_party, 3,
                             shared_specs=(per_tree, per_tree, P()),
                             out_specs=out)


def forest_predict_program(substrate, params: ForestParams, *,
                           compact: bool = False, mask_dtype=jnp.int32,
                           vote_impl: str = "einsum",
                           tree_sharded: bool = True,
                           parties=None):
    """fn(trees, xb_test[, leaf_idx]) — the one-round forest prediction.

    ``compact=True`` adds the LeafTable's ``leaf_idx`` as a trailing shared
    arg (bit-identical outputs; psum/vote over live-leaf columns only).
    ``tree_sharded=False``: see forest_fit_program.  ``parties`` restricts
    the protocol to a subset of party indices — the distributed substrate's
    degraded-serving path (in-process substrates always run every party and
    ignore it).
    """
    p = params
    n_shared = 1 if compact else 0

    if substrate.mesh is None:
        def fn(trees, xbt, *shared):
            return prediction.forest_predict_oneround(
                trees, xbt, p, aggregate=True, mask_dtype=mask_dtype,
                vote_impl=vote_impl, leaf_idx=shared[0] if shared else None)
        from repro.federation import distributed
        return substrate.program(
            fn, 2, n_shared,
            distributed=distributed.forest_predict_spec(
                p, compact=compact, mask_dtype=mask_dtype,
                vote_impl=vote_impl),
            parties=parties)

    # Sharded: trees live sharded over (parties, trees); each shard emits its
    # local per-tree outputs and the forest vote reduces across tree shards.
    tree_ax = substrate.tree_axis if tree_sharded else None
    tree_spec = P(PARTY_AXIS, tree_ax) if tree_ax else P(PARTY_AXIS)
    shared_specs = ((P(tree_ax) if tree_ax else P(),) if compact else ())

    def predict_local(tr, xbt, *shared):
        tr = jax.tree.map(lambda a: a[0], tr)               # drop party dim
        out = prediction.forest_predict_oneround(
            tr, xbt[0], p, aggregate=False, mask_dtype=mask_dtype,
            vote_impl=vote_impl, leaf_idx=shared[0] if shared else None)
        return out[None]                                    # (1, T_loc, N)

    inner = jax.shard_map(
        predict_local, mesh=substrate.mesh,
        in_specs=(tree_spec, P(PARTY_AXIS)) + shared_specs,
        out_specs=tree_spec, check_vma=False)

    def fn(trees, xbt, *shared):
        per_tree = inner(trees, xbt, *shared)               # (m, T, N)
        if p.task == "classification":
            votes = (per_tree[0][..., None] ==
                     jnp.arange(p.n_classes)[None, None]).sum(0)
            return jnp.argmax(votes, -1)
        return prediction.tree_mean(per_tree[0])
    return fn


def boosting_predict_program(substrate, params, *, compact: bool = False,
                             mask_dtype=jnp.uint8):
    """fn(trees, xbt, base[, leaf_idx]) — one-wave boosting prediction.

    ``trees`` is the per-round PartyTree stack (leading (M, R, ...) axes,
    core.boosting.stack_rounds); the one-round membership protocol runs with
    ``aggregate=False`` per-round outputs and the boosting reduction
    (base + lr * Σ rounds, thresholded for the binary task) is fused in the
    same program — ONE collective for the whole ensemble, like the forest.
    ``params`` is a BoostParams; ``base`` rides as a shared scalar arg so a
    refreshed model re-binds without recompiling the closure."""
    tp = params.tree_params()
    lr, task = params.learning_rate, params.task
    n_shared = 1 if compact else 0

    def fn(trees, xbt, base, *shared):
        per_round = prediction.forest_predict_oneround(
            trees, xbt, tp, aggregate=False, mask_dtype=mask_dtype,
            leaf_idx=shared[0] if shared else None)          # (R, N)
        f = base + lr * per_round.sum(0)
        if task == "binary":
            return (f > 0).astype(jnp.int32)
        return f

    return substrate.program(fn, 2, 1 + n_shared)


def linear_predict_program(substrate, task: str):
    """fn(x_i, w_i, b) — the F-LR joint-logit prediction (one psum).

    ``x_i`` and ``w_i`` are party args (each party's standardized feature
    block and its weight block); the bias ``b`` is shared (it is psum-trained
    and identical across parties)."""
    def fn(x_i, w_i, b):
        from repro.core.fedlinear import _spmd_predict
        return _spmd_predict(x_i, w_i, b, task=task)
    from repro.federation import distributed
    return substrate.program(fn, 2, 1,
                             distributed=distributed.linear_predict_spec(task))


def forest_predict_classical_program(substrate, params: ForestParams):
    """fn(trees, xb_test) — the multi-round baseline (paper Figs. 4-6)."""
    def fn(trees, xbt):
        return prediction.forest_predict_classical(trees, xbt, params=params)
    return substrate.program(fn, 2, 0)
