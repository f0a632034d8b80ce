"""Per-tree column compaction of the split search.

A tree's feature subsample is fixed, so each party histograms only the
``min(Fp, features_per_tree(F))`` columns it may split on.  The claims:

  * the forest is BIT-IDENTICAL to a search over every padded column
    (``tree.hist_columns`` patched to the identity), on both tasks, on
    parties of unequal widths (a narrow party with no selected feature in
    some tree), with frontier compaction, sibling subtraction and tree
    batching, through the scatter and the Pallas (interpreted) backends;
  * the histogram backend sees exactly ``min(Fp, features_per_tree(F))``
    columns, and ``Fp`` when the budget covers the party (no gather);
  * a subsample above the per-tree budget is refused on the host.
"""
import jax
import numpy as np
import pytest

from repro.core import FederatedForest, ForestParams, PartyBlock, tree
from repro.core.party import partition_from_blocks
from repro.data import make_classification, make_regression
from repro.kernels import ops

WIDTHS = (2, 12, 6)             # parties "a", "b", "c": Fp = 12, F = 20
N_BINS = 8


def _partition(task: str, n: int = 160, seed: int = 0):
    f = sum(WIDTHS)
    if task == "classification":
        x, y = make_classification(n, f, 3, seed=seed)
    else:
        x, y = make_regression(n, f, seed=seed)
    ids = np.arange(n)
    edges = np.cumsum((0,) + WIDTHS)
    blocks = [PartyBlock(name=name, x=x[:, lo:hi], ids=ids,
                         feature_ids=np.arange(lo, hi))
              for name, lo, hi in zip("abc", edges[:-1], edges[1:])]
    part, _, _ = partition_from_blocks(blocks, N_BINS)
    return part, y


def _fit(part, y, params):
    return FederatedForest(params).fit(part, y)


def _assert_same_trees(a, b):
    ta = jax.tree.map(np.asarray, a.trees_)
    tb = jax.tree.map(np.asarray, b.trees_)
    for field in ta._fields:
        np.testing.assert_array_equal(getattr(ta, field), getattr(tb, field),
                                      err_msg=field)


CASES = {
    "cls_mf0.1": dict(max_features=0.1),
    "cls_mf0.34": dict(max_features=0.34),
    "regression": dict(task="regression", max_features=0.34),
    "frontier": dict(max_features=0.34, max_depth=6, frontier_cap=4),
    "hist_subtraction": dict(max_features=0.34, hist_subtraction=True),
    "trees_per_batch": dict(max_features=0.1, trees_per_batch=3),
}


@pytest.mark.parametrize("backend", ["scatter", "pallas_interpret"])
@pytest.mark.parametrize("case", list(CASES))
def test_compacted_forest_bit_identical(monkeypatch, case, backend):
    kw = dict(CASES[case])
    task = kw.pop("task", "classification")
    params = ForestParams(task=task, n_classes=3, n_estimators=4,
                          max_depth=kw.pop("max_depth", 4), n_bins=N_BINS,
                          seed=11, hist_impl=backend, **kw)
    part, y = _partition(task)
    fp = part.xb.shape[2]
    assert tree.hist_columns(params, part.n_features, fp) < fp
    if case == "cls_mf0.1":
        # the narrow party "a" draws none of its features in some tree
        _, sels = FederatedForest(params)._master_randomness(part)
        assert not sels[:, part.feat_gid[0][part.feat_gid[0] >= 0]].any(
            axis=1).all()
    compact = _fit(part, y, params)
    monkeypatch.setattr(tree, "hist_columns", lambda p, f, party_cols:
                        party_cols)
    full = _fit(part, y, params)
    _assert_same_trees(compact, full)
    assert compact.trees_.has_split.any()


@pytest.fixture
def spy_backend():
    """A histogram backend that records the column count of every call."""
    widths = []

    @ops.register_backend("_spy_cols")
    def spy(xb, seg, stats, n_level, n_bins):
        widths.append(xb.shape[1])
        return ops.BACKENDS["scatter"](xb, seg, stats, n_level, n_bins)

    try:
        yield widths
    finally:
        del ops.BACKENDS["_spy_cols"]


@pytest.mark.parametrize("max_features,want", [
    (0.1, 2),        # features_per_tree(20) = 2 < Fp = 12: gathered
    (0.34, 7),       # 7 < 12: gathered
    (0.7, 12),       # 14 >= 12: every padded column, no gather
    (1.0, 12),
])
def test_backend_sees_budget_columns(spy_backend, max_features, want):
    params = ForestParams(n_classes=3, n_estimators=2, max_depth=3,
                          n_bins=N_BINS, max_features=max_features,
                          hist_impl="_spy_cols")
    part, y = _partition("classification")
    assert tree.hist_columns(params, part.n_features,
                             part.xb.shape[2]) == want
    _fit(part, y, params)
    assert spy_backend and set(spy_backend) == {want}


@pytest.mark.parametrize("entry", ["fit", "fit_resumable"])
def test_subsample_above_budget_refused(monkeypatch, tmp_path, entry):
    params = ForestParams(n_classes=3, n_estimators=2, max_depth=2,
                          n_bins=N_BINS, max_features=0.1)
    part, y = _partition("classification")
    draw = FederatedForest._master_randomness

    def one_too_many(self, partition):
        weights, sels = draw(self, partition)
        sels[1, np.flatnonzero(~sels[1])[0]] = True
        return weights, sels

    monkeypatch.setattr(FederatedForest, "_master_randomness", one_too_many)
    model = FederatedForest(params)
    with pytest.raises(ValueError, match="tree 1 selects 3 features"):
        if entry == "fit":
            model.fit(part, y)
        else:
            model.fit_resumable(part, y, str(tmp_path))
