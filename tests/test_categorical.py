"""Categorical columns: category-set splits from ingest to serving.

The claims:

  * the set-split search finds the best of all 2^(k-1) - 1 partitions of a
    node's categories (brute force, k <= 10), for classification and
    regression, through the scatter and the Pallas (interpreted) histogram;
  * a federated fit with categorical columns (string and integer values)
    grows the trees of the plain reference ``bench/reference_kdd99.py``:
    every split, category set and node statistic (at a size where no two
    candidate splits of a node tie within float32 rounding: such a tie is
    broken by rounding in the program and by the tie rule in the float64
    reference, and the benchmark judges it by regret); frontier compaction,
    per-tree column compaction and tree batching change no bit, and the
    sharded substrate (four virtual CPU devices) grows what the simulated
    one grows;
  * one-round, classical, served and party-block-served answers equal the
    reference's walk, with categories unseen at fit time among the rows;
  * the category sets survive a checkpoint round trip;
  * what cannot be lossless or is not built is refused: more categories
    than ``n_bins - 1``, more than two classes, the distributed substrate,
    streaming ingest, boosting;
  * numeric-only fit and predict programs lower to the same text as before
    categorical columns existed (hashes of the lowered programs).
"""
import functools
import hashlib
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForestParams, PartyBlock, tree
from repro.core.tree import PartyTree
from repro.federation import Federation, programs
from repro.federation.substrate import SimulatedSubstrate
from repro.serving import ServeConfig

sys.path.append(str(Path(__file__).resolve().parents[1] / "bench"))
import reference  # noqa: E402
import reference_kdd99  # noqa: E402

N_BINS = 16
SVC = np.array(list("abcdefghi"))           # 9 string categories
CAT_GIDS = (1, 3, 6)                        # svc (str), flag (int), tier


def _table(task: str, n: int = 3000, seed: int = 0):
    """(x (n, 7) object matrix in global-id order, y): party "a" holds
    columns 0-3 (1 and 3 categorical), party "b" 4-6 (6 categorical).  The
    label rides on groups of categories that are not runs of codes."""
    rng = np.random.default_rng(seed)
    svc = rng.integers(0, len(SVC), n)
    flag = rng.integers(0, 6, n)
    tier = rng.integers(0, 4, n)
    num = np.round(rng.normal(size=(n, 4)), 2)
    if task == "classification":
        y = ((np.isin(svc, [1, 4, 7]) ^ np.isin(flag, [1, 4]))
             | (num[:, 0] > 1.0)) ^ (rng.random(n) < 0.1)
        y = y.astype(np.int64)
    else:
        effect = rng.normal(size=len(SVC))
        y = (np.round(4 * effect[svc]) + 2.0 * (flag % 3) + tier
             + np.round(4 * num[:, 0]) + rng.integers(0, 3, n)) / 4.0
    x = np.empty((n, 7), object)
    x[:, 0], x[:, 1], x[:, 2], x[:, 3] = num[:, 0], SVC[svc], num[:, 1], flag
    x[:, 4], x[:, 5], x[:, 6] = num[:, 2], num[:, 3], tier
    return x, y


def _blocks(x, y, ids=None):
    ids = np.arange(len(x)) if ids is None else ids
    return [PartyBlock("a", x[:, :4], ids, y=y, feature_ids=[0, 1, 2, 3],
                       categorical=(1, 3)),
            PartyBlock("b", x[:, 4:], ids, feature_ids=[4, 5, 6],
                       categorical=(2,))]


def _params(task="classification", **kw):
    kw = {"n_estimators": 4, "max_depth": 5, "n_bins": N_BINS,
          "max_features": 0.6, "seed": 7, **kw}
    return ForestParams(task=task, **kw)


def _fit(task="classification", x=None, y=None, fed=None, **kw):
    if x is None:
        x, y = _table(task)
    fed = fed or Federation(parties=2, n_bins=N_BINS)
    fed.ingest(_blocks(x, y))
    return fed, fed.fit(_params(task, **kw)), x, y


def neutral(trees, n_bins: int = N_BINS) -> list[dict]:
    """The program's PartyTree stack in the reference's layout, with each
    set split's left codes read from its owner's bitset."""
    t = jax.tree.map(np.asarray, trees)
    owner = t.owner[0]
    o = np.clip(owner, 0, None)
    ti, ni = np.indices(owner.shape)
    words = t.split_cats[o, ti, ni]
    bits = (words[..., None] >> np.arange(32, dtype=np.uint32)) & 1
    left = (bits.reshape(words.shape[:-1] + (-1,))[..., :n_bins] == 1)
    left &= (owner >= 0)[..., None]
    bins = np.where(owner >= 0, t.split_bin[o, ti, ni], -1)
    return [{"is_leaf": t.is_leaf[0, k], "feature": t.split_gid[0, k],
             "bin": bins[k], "stats": t.leaf_stats[0, k], "left": left[k]}
            for k in range(owner.shape[0])]


def reference_forest(x, y, p: ForestParams):
    """The reference's trees of the fit ``p`` describes, its bins and its
    label permutation."""
    xb, edges, dicts = reference_kdd99.bin_table(x, CAT_GIDS, p.n_bins)
    cat = np.isin(np.arange(x.shape[1]), CAT_GIDS)
    if p.task == "classification":
        perm = reference.label_permutation(p.seed, 2)
        stats = reference.stat_channels(perm[y], p.task, 2)
    else:
        perm, stats = None, reference.stat_channels(y, p.task, 2)
    pd = {k: getattr(p, k) for k in (
        "task", "max_depth", "n_bins", "min_samples_leaf",
        "min_samples_split", "min_impurity_decrease", "max_features")}
    trees = []
    for t in range(p.n_estimators):
        w, sel = reference.master_draws(p.seed, t, len(x), x.shape[1],
                                        p.max_features)
        gids = np.nonzero(sel)[0]
        trees.append(reference_kdd99.build_tree(xb[:, gids], gids, cat[gids],
                                                w, stats, pd))
    return trees, (edges, dicts), perm


def _follow(tree: dict, t: int, x, y, p: ForestParams) -> dict:
    """The benchmark's judgement of one program tree."""
    xb, _, _ = reference_kdd99.bin_table(x, CAT_GIDS, p.n_bins)
    cat = np.isin(np.arange(x.shape[1]), CAT_GIDS)
    labels = (reference.label_permutation(p.seed, 2)[y]
              if p.task == "classification" else y)
    w, sel = reference.master_draws(p.seed, t, len(x), x.shape[1],
                                    p.max_features)
    gids = np.nonzero(sel)[0]
    pd = {k: getattr(p, k) for k in (
        "task", "max_depth", "n_bins", "min_samples_leaf",
        "min_samples_split", "min_impurity_decrease")}
    return reference_kdd99.follow(
        tree, xb[:, gids], gids, cat[gids], w,
        reference.stat_channels(labels, p.task, 2), pd)


def _assert_same_tree(got: dict, want: dict, task: str):
    np.testing.assert_array_equal(got["is_leaf"], want["is_leaf"])
    np.testing.assert_array_equal(got["feature"], want["feature"])
    np.testing.assert_array_equal(got["left"], want["left"])
    num = (want["feature"] >= 0) & ~want["left"].any(-1)
    np.testing.assert_array_equal(got["bin"][num], want["bin"][num])
    if task == "classification":
        np.testing.assert_array_equal(got["stats"], want["stats"])
    else:
        np.testing.assert_array_equal(got["stats"][:, 0],
                                      want["stats"][:, 0])
        np.testing.assert_allclose(got["stats"], want["stats"], rtol=1e-5,
                                   atol=1e-4)


# ------------------------------------------------------- set-split search
@pytest.mark.parametrize("backend", ["scatter", "pallas_interpret"])
@pytest.mark.parametrize("task", ["classification", "regression"])
@pytest.mark.parametrize("k", [2, 5, 10])
def test_set_split_is_best_partition(task, k, backend):
    """A stump over one categorical column takes the best of all
    2^(k-1) - 1 partitions of its categories."""
    rng = np.random.default_rng(k)
    n = 400
    code = rng.integers(0, k, n)
    if task == "classification":
        y = (rng.random(n) < rng.random(k)[code]).astype(np.int64)
    else:
        y = np.round(4 * rng.normal(size=k)[code] + rng.normal(size=n)) / 4
    blocks = [PartyBlock("a", code[:, None] * 3.0, np.arange(n), y=y,
                         categorical=(0,)),
              PartyBlock("b", np.zeros((n, 1)), np.arange(n))]
    fed = Federation(parties=2, n_bins=N_BINS, hist_impl=backend)
    fed.ingest(blocks)
    model = fed.fit(ForestParams(task=task, n_estimators=1, max_depth=1,
                                 n_bins=N_BINS, bootstrap=False, seed=3))
    root = neutral(model.trees_)[0]
    assert root["feature"][0] == 0 and root["left"][0].any()
    stats = reference.stat_channels(y, task, 2)
    hist = np.zeros((N_BINS, stats.shape[1]))
    np.add.at(hist, code, stats)
    best = reference_kdd99.best_partition_gain(hist, task, 1)
    taken = reference_kdd99.split_gain(hist[root["left"][0]].sum(0),
                                       hist.sum(0), task, 1)
    assert taken >= best - 1e-6 * abs(best)
    assert not root["left"][0][k:].any()      # no code without rows


# ------------------------------------------------------- fit == reference
@pytest.mark.parametrize("task", ["classification", "regression"])
def test_fit_grows_the_reference_trees(task):
    _, model, x, y = _fit(task)
    want, _, _ = reference_forest(x, y, model.params)
    got = neutral(model.trees_)
    for g, w in zip(got, want):
        _assert_same_tree(g, w, task)
    judged = [_follow(g, t, x, y, model.params) for t, g in enumerate(got)]
    assert all(j["bad_nodes"] == 0 and j["stat_gap"] < 1e-6
               and j["regret"] < 1e-6 for j in judged), judged
    assert sum(j["noncontiguous"] for j in judged) > 0
    t = jax.tree.map(np.asarray, model.trees_)
    sets = (t.split_cats != 0).any(-1)
    assert sets.any()
    # only a split's owner holds its set
    assert not (sets & ~t.has_split).any()


@functools.lru_cache(maxsize=None)
def _dense_trees(max_features: float):
    x, y = _table("classification")
    return _fit(x=x, y=y, frontier_cap=0, max_features=max_features)[1].trees_


@pytest.mark.parametrize("variant", [
    {"frontier_cap": 2}, {"hist_subtraction": True}, {"trees_per_batch": 3},
    {"frontier_cap": 2, "max_features": 1.0},
    {"hist_impl": "pallas_interpret"}])
def test_set_splits_bit_identical_across_build_variants(variant):
    """Frontier compaction (also without column compaction), sibling
    subtraction, tree batching and the Pallas histogram grow the dense
    scatter build's trees bit for bit."""
    x, y = _table("classification")
    base = _dense_trees(variant.get("max_features", 0.6))
    _, other, _, _ = _fit(x=x, y=y, **{"frontier_cap": 0, **variant})
    for la, lb in zip(jax.tree.leaves(base), jax.tree.leaves(other.trees_)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_column_compaction_bit_identical(monkeypatch):
    x, y = _table("classification")
    _, compact, _, _ = _fit(x=x, y=y, max_features=0.3)
    monkeypatch.setattr(tree, "hist_columns",
                        lambda p, f, party_cols: party_cols)
    _, full, _, _ = _fit(x=x, y=y, max_features=0.3)
    for la, lb in zip(jax.tree.leaves(compact.trees_),
                      jax.tree.leaves(full.trees_)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_numeric_fit_has_no_category_sets():
    x, y = _table("classification")
    num = np.asarray(x[:, [0, 2, 4, 5]], np.float64)
    fed = Federation(parties=2, n_bins=N_BINS)
    fed.ingest(num, y)
    model = fed.fit(_params())
    assert model.trees_.split_cats is None
    assert len(jax.tree.leaves(model.trees_)) == 7


# ----------------------------------------------------------------- serving
def _held_out(n: int = 200):
    """Rows of the same table with unseen categories in each categorical
    column: a string, an integer and a tier outside the fit-time sets."""
    x, _ = _table("classification", n=n, seed=11)
    rng = np.random.default_rng(12)
    for j, unseen in ((1, "zz"), (3, 99), (6, 7)):
        x[rng.random(n) < 0.2, j] = unseen
    return x


def test_served_answers_equal_reference_walk():
    fed, model, x, y = _fit()
    _, (edges, dicts), perm = reference_forest(x, y, model.params)
    xt = _held_out()
    want = reference_kdd99.walk_votes(
        neutral(model.trees_), reference_kdd99.apply_table(xt, edges, dicts),
        2, perm)
    assert (reference_kdd99.apply_table(xt, edges, dicts)[:, 1] ==
            len(dicts[1])).any()
    np.testing.assert_array_equal(model.predict(xt), want)
    np.testing.assert_array_equal(model.predict_classical(xt), want)
    np.testing.assert_array_equal(fed.predict(model, xt), want)
    server = fed.serve(model, ServeConfig(buckets=(8, 64)))
    np.testing.assert_array_equal(server.serve(xt), want)
    qids = np.arange(len(xt)) + 10_000
    ids, served = server.serve_parties(_blocks(xt, None, ids=qids))
    pos = {q: i for i, q in enumerate(qids)}
    np.testing.assert_array_equal(served, want[[pos[i] for i in ids]])


def test_unseen_category_routes_right():
    """A value no training row had gets the code past the dictionary, and
    every set split sends it right."""
    _, model, _, _ = _fit()
    part = model.partition_
    xt = _held_out(40)
    xt[:, 1] = "zz"
    codes = part.bin_test(xt)
    i = part.party_index("a")
    assert (codes[i, :, 1] == len(part.categories[1])).all()
    assert not any(t["left"][:, len(part.categories[1])].any()
                   for t in neutral(model.trees_)
                   if (t["feature"] == 1).any())


def test_checkpoint_round_trip(tmp_path):
    fed, model, _, _ = _fit()
    fed.save(model, str(tmp_path))
    loaded = fed.load(str(tmp_path), model.params)
    for la, lb in zip(jax.tree.leaves(model.trees_),
                      jax.tree.leaves(loaded.trees_)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert loaded.trees_.split_cats.dtype == jnp.uint32
    xt = _held_out(64)
    np.testing.assert_array_equal(loaded.predict(xt), model.predict(xt))


def test_resumable_fit_extends_a_categorical_forest(tmp_path):
    """Chunks checkpoint their category sets and a longer rerun resumes
    from them: the same trees as one whole fit."""
    x, y = _table("classification")
    fed = Federation(parties=2, n_bins=N_BINS)
    fed.ingest(_blocks(x, y))
    fed.fit_resumable(_params(n_estimators=2), str(tmp_path))
    resumed = fed.fit_resumable(_params(n_estimators=4), str(tmp_path))
    whole = fed.fit(_params(n_estimators=4))
    for la, lb in zip(jax.tree.leaves(resumed.trees_),
                      jax.tree.leaves(whole.trees_)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert resumed.trees_.split_cats is not None


# ---------------------------------------------------------------- refusals
def test_too_many_categories_refused():
    x, y = _table("classification")
    x[:, 1] = np.arange(len(x)) % 20           # 20 > n_bins - 1 = 15
    with pytest.raises(ValueError, match="20 categories, above n_bins - 1"):
        Federation(parties=2, n_bins=N_BINS).ingest(_blocks(x, y))


def test_more_than_two_classes_refused():
    x, y = _table("classification")
    fed = Federation(parties=2, n_bins=N_BINS)
    fed.ingest(_blocks(x, np.arange(len(x)) % 3))
    with pytest.raises(ValueError, match="n_classes == 2"):
        fed.fit(_params(n_classes=3))


def test_distributed_substrate_refused():
    from repro.federation.distributed import DistributedSubstrate
    x, y = _table("classification")

    class Stub:
        name, mesh = "distributed", None

    with pytest.raises(ValueError, match="distributed substrate"):
        programs.forest_fit_program(Stub(), _params(), categorical=True)
    with pytest.raises(ValueError, match="distributed substrate"):
        DistributedSubstrate.ingest_blocks(None, _blocks(x, y), N_BINS)


def test_streaming_ingest_refused():
    from repro.streaming import ArraySource
    x, y = _table("classification")
    with pytest.raises(ValueError, match="streaming ingest"):
        Federation(parties=2, n_bins=N_BINS).ingest(
            [ArraySource(b) for b in _blocks(x, y)])


def test_boosting_refused():
    from repro.core.boosting import BoostParams
    x, y = _table("classification")
    fed = Federation(parties=2, n_bins=N_BINS)
    fed.ingest(_blocks(x, y))
    with pytest.raises(ValueError, match="random forests"):
        fed.fit(BoostParams(n_bins=N_BINS))


def test_text_in_numeric_column_refused():
    x, y = _table("classification")
    x[0, 0] = "tcp"
    with pytest.raises(ValueError, match="not declared categorical"):
        Federation(parties=2, n_bins=N_BINS).ingest(_blocks(x, y))


# ---------------------------------------------- numeric programs unchanged
# sha256 of the lowered StableHLO text of numeric-only programs at the
# target-marketing and Year Prediction party shapes, as the code lowered
# them before categorical columns existed (jax 0.9.0, CPU).
LOWERED = {
    "tmkt": {
        "fit": "ba6532d0db7f9478410f5b26aeb44d19f67e160360c526520b54fe932ff36749",
        "predict": "780ff54cf39e02f80efbb4df654b1ed5715dcf1b4b7b23fcffe68a9ad4c000a8",
        "predict_compact": "0585dcedd431e4b65436c75f85315820bfb3a0548aa8dfe856b3988a734dd9ec",
        "classical": "b4e9857f441f2b2a2d9434cb34e7b6d2b14065f2ec65360db19c97d5462c4489",
    },
    "yearmsd": {
        "fit": "f2affd26f3ab4f359f3196fe7e7c0cb86ccb4d5546cfe44c16d538e0cd921a18",
        "predict": "3d3d10e6e805eaeac3f0b70fb0d061e1604a71f7e4884a262d07f3a4ae36d0cf",
        "predict_compact": "874bd804f5e447a59ab15ef090ae3810655fdfa29750e37f15dad985151f2952",
        "classical": "68fdad5146c7ca96f259813df51ce5a5a01ba2f86f7109a7787b41a483b15f14",
    },
}
SHAPES = {
    "tmkt": dict(widths=(11, 84), task="classification", max_features=0.1),
    "yearmsd": dict(widths=(23, 23, 22, 22), task="regression",
                    max_features=1.0),
}


def _lowered_hashes(name, n=64, trees=3, leaves=16) -> dict:
    s = SHAPES[name]
    m, fp, f = len(s["widths"]), max(s["widths"]), sum(s["widths"])
    p = ForestParams(task=s["task"], n_estimators=trees, max_depth=8,
                     n_bins=32, max_features=s["max_features"], seed=1,
                     hist_impl="scatter")
    c, nn = p.n_stat_channels, p.n_nodes
    sds = jax.ShapeDtypeStruct
    sub = SimulatedSubstrate()
    tr = PartyTree(is_leaf=sds((m, trees, nn), jnp.bool_),
                   leaf_stats=sds((m, trees, nn, c), jnp.float32),
                   has_split=sds((m, trees, nn), jnp.bool_),
                   split_floc=sds((m, trees, nn), jnp.int32),
                   split_bin=sds((m, trees, nn), jnp.int32),
                   owner=sds((m, trees, nn), jnp.int32),
                   split_gid=sds((m, trees, nn), jnp.int32))
    xbt = sds((m, n, fp), jnp.uint8)
    text = {
        "fit": jax.jit(programs.forest_fit_program(sub, p)).lower(
            sds((m, n, fp), jnp.uint8), sds((m, fp), jnp.int32),
            sds((trees, f), jnp.bool_), sds((trees, n), jnp.float32),
            sds((n, c), jnp.float32)),
        "predict": jax.jit(programs.forest_predict_program(sub, p)).lower(
            tr, xbt),
        "predict_compact": jax.jit(programs.forest_predict_program(
            sub, p, compact=True, mask_dtype=jnp.uint8)).lower(
                tr, xbt, sds((trees, leaves), jnp.int32)),
        "classical": jax.jit(programs.forest_predict_classical_program(
            sub, p)).lower(tr, xbt),
    }
    return {k: hashlib.sha256(v.as_text().encode()).hexdigest()
            for k, v in text.items()}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_numeric_programs_lower_unchanged(shape):
    assert _lowered_hashes(shape) == LOWERED[shape]


# ---------------------------------------------------- sharded == simulated
_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import hashlib, sys
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, "tests")
from repro import compat
from repro.core import ForestParams
from repro.core.tree import PartyTree
from repro.federation import Federation, programs
from repro.federation.substrate import ShardedSubstrate
import test_categorical as tc

x, y = tc._table("classification")
mesh = compat.make_mesh((2, 2), ("trees", "parties"))
_, sim, _, _ = tc._fit(x=x, y=y)
fed = Federation(parties=2, substrate="sharded", mesh=mesh, n_bins=tc.N_BINS)
_, shd, _, _ = tc._fit(x=x, y=y, fed=fed)
for la, lb in zip(jax.tree.leaves(sim.trees_), jax.tree.leaves(shd.trees_)):
    assert np.array_equal(np.asarray(la), np.asarray(lb)), "trees diverge"
xt = tc._held_out(64)
assert np.array_equal(fed.predict(shd, xt), sim.predict(xt))

# the numeric Year Prediction-shaped sharded programs lower as before
sub = ShardedSubstrate(compat.make_mesh((1, 4), ("trees", "parties")))
m, fp, f, n, trees = 4, 23, 90, 64, 2
p = ForestParams(task="regression", n_estimators=trees, max_depth=8,
                 n_bins=32, max_features=1.0, seed=1, hist_impl="scatter")
c, nn = p.n_stat_channels, p.n_nodes
sds = jax.ShapeDtypeStruct
with sub.context():
    fit = jax.jit(programs.forest_fit_program(sub, p)).lower(
        sds((m, n, fp), jnp.uint8), sds((m, fp), jnp.int32),
        sds((trees, f), jnp.bool_), sds((trees, n), jnp.float32),
        sds((n, c), jnp.float32)).as_text()
    tr = PartyTree(
        is_leaf=sds((m, trees, nn), jnp.bool_),
        leaf_stats=sds((m, trees, nn, c), jnp.float32),
        has_split=sds((m, trees, nn), jnp.bool_),
        split_floc=sds((m, trees, nn), jnp.int32),
        split_bin=sds((m, trees, nn), jnp.int32),
        owner=sds((m, trees, nn), jnp.int32),
        split_gid=sds((m, trees, nn), jnp.int32))
    pred = jax.jit(programs.forest_predict_program(
        sub, p, compact=True, mask_dtype=jnp.uint8)).lower(
            tr, sds((m, n, fp), jnp.uint8),
            sds((trees, 16), jnp.int32)).as_text()
got = [hashlib.sha256(t.encode()).hexdigest() for t in (fit, pred)]
assert got == [
    "d837d502664b4b5ba5192cab2100f966c92d0ee8e712715875a1203c833ba736",
    "b056c17812ca2d226622621c9802e6ba6b3334e307d7e835e3991f94697f9966",
], got
print("CATEGORICAL_SHARDED_OK")
"""


def test_sharded_substrate_grows_the_simulated_forest():
    """Sharded on a (trees=2, parties=2) mesh of four virtual CPU devices:
    the same trees and answers as simulated; and the numeric sharded
    programs lower as before (subprocess so the forced device count never
    leaks into other tests)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1500,
                         cwd=root)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "CATEGORICAL_SHARDED_OK" in res.stdout
