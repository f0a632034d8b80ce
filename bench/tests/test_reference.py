"""The plain reference agrees with the program where both are exact, and
judges its own float64 trees as perfect."""
import numpy as np
import pytest

import common
import data
import reference
import tiny


@pytest.fixture(scope="module")
def table():
    c = tiny.cell("fit.tmkt")
    x, y = data.make_table(c.config, c.config["n_rows"], c.seed)
    return c, x, y


def test_binning_matches_the_program(table):
    from repro.core import binning
    _, x, _ = table
    xb, edges = binning.bin_dataset(x, 32)
    assert np.array_equal(reference.quantile_boundaries(x, 32), edges)
    assert np.array_equal(reference.apply_bins(x, edges), xb)


def test_alignment_matches_the_program(table):
    from repro.core.partyblock import align_party_blocks
    c, x, y = table
    blocks = common.party_blocks(c, x, y)
    ids, _ = align_party_blocks(blocks, salt="s1")
    order = reference.aligned_order(data.sample_ids(len(x)), "s1")
    assert np.array_equal(data.sample_ids(len(x))[order], ids)


def test_master_draws_match_the_program(table):
    from repro.core.forest import FederatedForest
    from repro.core.party import make_vertical_partition
    c, x, _ = table
    forest = FederatedForest(common.forest_params(c))
    part = make_vertical_partition(x, 2, 32)
    w, sels = forest._master_randomness(part)
    for t in range(len(w)):
        rw, rs = reference.master_draws(c.seed, t, len(x), 95, 0.1)
        assert np.array_equal(rw, w[t]) and np.array_equal(rs, sels[t])


@pytest.mark.parametrize("name", ["fit.tmkt", tiny.SHARDED])
def test_reference_tree_is_perfect_under_follow(name):
    c = tiny.cell(name)
    x, y = data.make_table(c.config, c.config["n_rows"], c.seed)
    ref = common.ReferenceData(c, x, y)
    order = reference.aligned_order(ref.ids, "s")
    tree = ref.control_tree(0, order, "float64", False)
    got = ref.judge(tree, 0, order)
    assert got["bad_nodes"] == 0 and got["stat_gap"] == 0.0
    assert got["regret"] == 0.0
    assert tree["feature"][0] >= 0           # the root did split


def test_follow_catches_a_worse_split():
    c = tiny.cell("fit.tmkt")
    x, y = data.make_table(c.config, c.config["n_rows"], c.seed)
    ref = common.ReferenceData(c, x, y)
    order = np.arange(len(x))
    tree = ref.control_tree(0, order, "float64", False)
    tree["bin"][0] = (tree["bin"][0] + 15) % 31
    assert ref.judge(tree, 0, order)["regret"] > 1e-3


def test_walk_matches_the_program_on_its_own_forest():
    from repro.core import ForestParams
    from repro.federation import Federation
    x, y = data.make_classification(3000, 12, 2, n_informative=6,
                                    class_sep=0.5, seed=4)
    fed = Federation(parties=2, n_bins=32)
    fed.ingest(x[:2500], y[:2500])
    params = ForestParams(n_estimators=5, max_depth=4, seed=9)
    model = fed.fit(params)
    forest, bad = common.neutral_forest(model.trees_,
                                        np.asarray(model.partition_.feat_gid))
    assert bad == 0
    edges = reference.quantile_boundaries(x[:2500], 32)
    got = reference.walk_votes(forest, reference.apply_bins(x[2500:], edges),
                               2, reference.label_permutation(9, 2))
    assert np.array_equal(got, fed.predict(model, x[2500:]))


def test_bf16_rounding():
    v = np.array([1.0, 1.00390625, 1.005859375, 3.0e-3, -7.1], np.float32)
    r = reference.to_bf16(v)
    assert r[0] == 1.0 and r[1] == 1.0          # tie to even
    assert r[2] == 1.0078125
    assert abs(r[3] - 3.0e-3) / 3.0e-3 < 2 ** -8
    assert np.all((r.view(np.uint32) & 0xFFFF) == 0)
