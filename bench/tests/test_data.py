"""The benchmark's inputs depend on the seed alone."""
import numpy as np
import pytest

import data
import harness

BIG_SEED = 3_141_592_653


@pytest.mark.parametrize("config", ["tmkt", "yearmsd"])
def test_same_seed_same_bytes(config):
    cfg = harness.load_json(harness.BENCH / "configs" / f"{config}.json")
    a = data.make_table(cfg, 2000, BIG_SEED)
    b = data.make_table(cfg, 2000, BIG_SEED)
    c = data.make_table(cfg, 2000, BIG_SEED + 1)
    assert all(x.tobytes() == y.tobytes() for x, y in zip(a, b))
    assert a[0].tobytes() != c[0].tobytes()
    assert a[0].shape == (2000, cfg["n_features"])


def test_party_orders_are_permutations_of_the_same_customers():
    orders = data.party_row_orders(1000, 4, BIG_SEED)
    again = data.party_row_orders(1000, 4, BIG_SEED)
    for o, p in zip(orders, again):
        assert np.array_equal(o, p)
        assert np.array_equal(np.sort(o), np.arange(1000))
    assert not np.array_equal(orders[0], orders[1])
    ids = data.sample_ids(1000)
    assert len(set(ids)) == 1000 and ids[7] == "c0000007"


def test_feature_groups_cover_the_features_in_party_order():
    g = data.feature_groups([11, 84])
    assert [len(x) for x in g] == [11, 84]
    assert np.array_equal(np.concatenate(g), np.arange(95))


def test_schedule_same_seed_same_schedule():
    a = data.stratified_poisson(500.0, 10.0, 1, 1024, BIG_SEED)
    b = data.stratified_poisson(500.0, 10.0, 1, 1024, BIG_SEED)
    c = data.stratified_poisson(500.0, 10.0, 1, 1024, BIG_SEED + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    offs = data.request_offsets(a[1], 32768, BIG_SEED)
    assert np.array_equal(offs, data.request_offsets(a[1], 32768, BIG_SEED))
    assert (offs >= 0).all() and (offs + a[1] <= 32768).all()


def test_schedule_is_poisson_with_log_uniform_sizes():
    due, sizes = data.stratified_poisson(500.0, 20.0, 1, 1024, 11)
    assert len(due) == 10_000 and due[0] == 0.0
    assert np.all(np.diff(due) >= 0)
    gaps = np.diff(due)
    assert abs(gaps.mean() * 500.0 - 1.0) < 0.02          # mean gap 1/rate
    assert abs(np.std(gaps) / gaps.mean() - 1.0) < 0.05    # exponential
    assert sizes.min() >= 1 and sizes.max() <= 1024
    assert 28 <= np.median(sizes) <= 36                    # sqrt(1024)
    assert 140 <= sizes.mean() <= 156                      # 1023 / ln 1024


def test_every_seed_sends_the_same_work():
    """Stratified draws: seeds differ in order and jitter, not in load."""
    totals = [data.stratified_poisson(500.0, 20.0, 1, 1024, s)[1].sum()
              for s in range(5)]
    assert max(totals) / min(totals) < 1.01
