"""Reproduces the paper's Appendix communication-complexity analysis, and
verifies our level-synchronous adaptation IMPROVES on it.

Paper (recursive, per-node messages):
  training:   O(2^k (M+1)) per tree
  prediction: classical O(2^(k-1) M), optimized O(M) — one gather.

Ours (level-synchronous collectives):
  training:   3 collectives per level (gather gains/ids/bins fuse into
              all-gathers + 1 partition psum)  ->  O(k) per tree
  prediction: ONE psum for the entire forest.

We count actual collective *primitives* in the jaxpr of the shard_map-
lowered protocol over an AbstractMesh (vmap simulation resolves collectives
at trace time, so only the shard_map path shows the real schedule).  The
dry-run records the same schedule in optimized HLO on the production mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit
from repro.core import ForestParams, impurity, prediction, tree

COLL_PRIMS = ("psum", "all_gather", "all_to_all", "ppermute",
              "psum_invariant", "reduce_scatter")


def _count_collectives(jaxpr) -> dict[str, int]:
    counts: dict[str, int] = {}
    seen = set()

    def walk(jx):
        if id(jx) in seen:
            return
        seen.add(id(jx))
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in COLL_PRIMS:
                counts[name] = counts.get(name, 0) + 1
            for v in eqn.params.values():
                for j in _jaxprs_of(v):
                    walk(j)

    def _jaxprs_of(v):
        out = []
        if hasattr(v, "jaxpr"):
            out.append(v.jaxpr)
        elif hasattr(v, "eqns"):
            out.append(v)
        elif isinstance(v, (list, tuple)):
            for vv in v:
                out.extend(_jaxprs_of(vv))
        return out

    walk(jaxpr.jaxpr)
    return counts


def run() -> dict:
    m, depth, n_est, n, f = 4, 5, 3, 64, 12
    fp = f // m
    p = ForestParams(n_estimators=n_est, max_depth=depth, n_bins=8)
    mesh = jax.sharding.AbstractMesh((m,), ("parties",))

    # ---- training schedule (one tree: lax.map body traced once) ----------
    def fit_local(xb, gid, sel, w, ys):
        out = tree.build_tree(xb[0], gid[0], sel, w, ys, p)
        return jax.tree.map(lambda a: a[None], out)

    fit = jax.shard_map(
        fit_local, mesh=mesh,
        in_specs=(P("parties"), P("parties"), P(), P(), P()),
        out_specs=P("parties"), check_vma=False)
    jx = jax.make_jaxpr(fit)(
        jnp.zeros((m, n, fp), jnp.uint8), jnp.zeros((m, fp), jnp.int32),
        jnp.ones((f,), bool), jnp.ones((n,), jnp.float32),
        jnp.zeros((n, 2), jnp.float32))
    c_train = _count_collectives(jx)

    # ---- prediction schedules --------------------------------------------
    trees_shape = jax.eval_shape(fit, jnp.zeros((m, n, fp), jnp.uint8),
                                 jnp.zeros((m, fp), jnp.int32),
                                 jnp.ones((f,), bool),
                                 jnp.ones((n,), jnp.float32),
                                 jnp.zeros((n, 2), jnp.float32))
    stacked = jax.tree.map(
        lambda s: jnp.zeros((s.shape[0], n_est) + s.shape[1:], s.dtype),
        trees_shape)

    def pred_one_local(tr, xbt):
        tr = jax.tree.map(lambda a: a[0], tr)
        return prediction.forest_predict_oneround(tr, xbt[0], p,
                                                  aggregate=False)[None]

    def pred_cls_local(tr, xbt):
        tr = jax.tree.map(lambda a: a[0], tr)
        return prediction.forest_predict_classical(tr, xbt[0], p)[None]

    tree_specs = jax.tree.map(lambda _: P("parties"), stacked,
                              is_leaf=lambda x: hasattr(x, "shape"))
    xbt = jnp.zeros((m, 32, fp), jnp.uint8)
    c_one = _count_collectives(jax.make_jaxpr(jax.shard_map(
        pred_one_local, mesh=mesh, in_specs=(tree_specs, P("parties")),
        out_specs=P("parties"), check_vma=False))(stacked, xbt))
    c_cls = _count_collectives(jax.make_jaxpr(jax.shard_map(
        pred_cls_local, mesh=mesh, in_specs=(tree_specs, P("parties")),
        out_specs=P("parties"), check_vma=False))(stacked, xbt))

    result = {
        "train_collectives_per_tree": sum(c_train.values()),
        "train_detail": c_train,
        "train_paper_bound": (2 ** depth) * (m + 1),
        "predict_oneround_collectives": sum(c_one.values()),
        "predict_classical_collectives": sum(c_cls.values()),
        "predict_paper_classical_bound": (2 ** (depth - 1)) * m * n_est,
        "depth": depth, "n_estimators": n_est, "n_parties": m,
    }
    emit("comm/train", 0.0,
         f"ours={result['train_collectives_per_tree']}/tree "
         f"({c_train})|paper_recursive_bound={result['train_paper_bound']}")
    emit("comm/predict", 0.0,
         f"oneround={result['predict_oneround_collectives']}|"
         f"classical_levelsync={result['predict_classical_collectives']}|"
         f"paper_classical_bound={result['predict_paper_classical_bound']}")
    # the paper's headline: one collective for the WHOLE forest
    assert result["predict_oneround_collectives"] == 1, result
    return result


if __name__ == "__main__":
    run()
