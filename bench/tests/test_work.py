"""The required work is counted from shapes, by hand and by the code."""
import numpy as np
import pytest

import harness
import work

PEAK = harness.load_json(harness.BENCH / "peaks.json")["TPU v5 lite"]


def config(name):
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")


def tmkt_sels(t=20):
    """Trees with features 0-4 (bank) and 20-24 (e-commerce) selected."""
    sels = np.zeros((t, 95), bool)
    sels[:, list(range(5)) + list(range(20, 25))] = True
    return sels


def test_tmkt_by_hand():
    cfg = config("tmkt")
    n, trees, depth, bins, c = 156_198, 20, 8, 32, 2
    got = work.for_config(cfg, tmkt_sels())
    per_level = [n * 10 + 2 * n * (4 + 4 * c) + 2 ** d * 10 * bins * c * 4
                 for d in range(depth)]
    assert got["hist"]["bytes"] == trees * sum(per_level)
    assert got["hist"]["ops"] == trees * depth * n * 10 * c
    routing = trees * depth * n * (1 + 8 + 4 * c)
    assert got["fit"]["bytes"] == got["hist"]["bytes"] + routing
    # 0.86 GB of histogram traffic per fit: ~1 ms at 819 GB/s
    assert 0.8e9 < got["hist"]["bytes"] < 0.9e9
    secs, bound = work.required_seconds(got["hist"], PEAK, 1)
    assert bound == "hbm" and secs == got["hist"]["bytes"] / 819e9


def test_yearmsd_by_hand():
    cfg = config("yearmsd")
    n, trees, depth, bins, c = 515_345, 20, 8, 32, 3
    got = work.for_config(cfg, np.ones((trees, 90), bool))
    per_level = [n * 90 + 4 * n * (4 + 4 * c) + 2 ** d * 90 * bins * c * 4
                 for d in range(depth)]
    assert got["hist"]["bytes"] == trees * sum(per_level)
    assert got["hist"]["ops"] == trees * depth * n * 90 * c
    secs4, _ = work.required_seconds(got["hist"], PEAK, 4)
    secs1, _ = work.required_seconds(got["hist"], PEAK, 1)
    assert secs4 == pytest.approx(secs1 / 4)


@pytest.mark.parametrize("change", [
    {"hist_impl": "pallas"}, {"hist_impl": "scatter"},
    {"frontier_cap": 0}, {"frontier_cap": 64}, {"trees_per_batch": 4},
    {"hist_subtraction": True}])
def test_unchanged_by_implementation_knobs(change):
    cfg = config("tmkt")
    base = work.for_config(cfg, tmkt_sels())
    other = dict(cfg, forest=dict(cfg["forest"], **change))
    assert work.for_config(other, tmkt_sels()) == base


def test_unchanged_by_padding():
    """The program pads the bank's 11 columns to the e-commerce party's 84;
    only the 95 real columns enter the count, never the padded 2 x 84."""
    from repro.core import PartyBlock
    from repro.core.party import partition_from_blocks
    cfg = dict(config("tmkt"), n_rows=64)
    x = np.random.default_rng(0).normal(size=(64, 95))
    ids = np.arange(64)
    blocks = [PartyBlock(name="bank", x=x[:, :11], ids=ids, y=ids % 2,
                         feature_ids=np.arange(11)),
              PartyBlock(name="ecommerce", x=x[:, 11:], ids=ids,
                         feature_ids=np.arange(11, 95))]
    part, _, _ = partition_from_blocks(blocks, 32)
    assert part.feat_gid.shape == (2, 84)
    real = [{"name": n, "features": int((g >= 0).sum())}
            for n, g in zip(part.party_names, part.feat_gid)]
    sels = np.ones((3, 95), bool)
    assert (work.for_config(dict(cfg, parties=real), sels)
            == work.for_config(cfg, sels))
    assert work.for_config(cfg, sels)["hist"]["ops"] == 3 * 8 * 64 * 95 * 2


def test_feature_subsample_counts_selected_features_only():
    cfg = config("tmkt")
    all_f = work.for_config(cfg, np.ones((20, 95), bool))
    some = work.for_config(cfg, tmkt_sels())
    assert some["hist"]["ops"] * 95 == all_f["hist"]["ops"] * 10
