"""The ``fit.kdd99`` cell: its generator, its reference, and its check,
which passes on the program and fails on planted faults and on its
control, run through the harness at a small size on the CPU."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import data_kdd99
import harness
import reference
import reference_kdd99
import tiny

CELL = "fit.kdd99"


# --------------------------------------------------------------- generator
def test_generator_is_deterministic_per_seed():
    a = data_kdd99.make_table(5000, 2**31 + 7)
    b = data_kdd99.make_table(5000, 2**31 + 7)
    c = data_kdd99.make_table(5000, 2**31 + 8)
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    assert not np.array_equal(a[0], c[0])


def test_generator_has_the_kdd_schema():
    x, y = data_kdd99.make_table(60_000, 3)
    assert x.shape == (60_000, 41) and len(data_kdd99.COLUMNS) == 41
    assert [len(data_kdd99.SYMBOLS[s]) for s in data_kdd99.SYMBOLS] == [3, 70,
                                                                        11]
    for j, name in zip(data_kdd99.CATEGORICAL, data_kdd99.SYMBOLS):
        assert data_kdd99.COLUMNS[j] == name
        assert len(np.unique(x[:, j])) == len(data_kdd99.SYMBOLS[name])
    assert 0.7 < y.mean() < 0.86                 # ~80% attacks


@pytest.mark.parametrize("name,group", [
    ("service", data_kdd99.ATTACK_SERVICES),
    ("flag", data_kdd99.ATTACK_FLAGS)])
def test_label_groups_are_not_code_ranges(name, group):
    """No threshold on the codes separates the group from the rest."""
    symbols = data_kdd99.SYMBOLS[name]
    codes = np.isin(np.arange(len(symbols)),
                    [symbols.index(s) for s in group])
    for t in range(len(symbols) - 1):
        below = np.arange(len(symbols)) <= t
        assert not np.array_equal(below, codes)
        assert not np.array_equal(below, ~codes)


# --------------------------------------------------------------- reference
@pytest.mark.parametrize("task", ["classification", "regression"])
@pytest.mark.parametrize("k", [2, 3, 6, 10])
def test_ordering_rule_finds_the_best_partition(task, k):
    rng = np.random.default_rng([k, task == "regression"])
    for _ in range(20):
        n = rng.integers(1, 40, size=k).astype(np.float64)
        n[rng.random(k) < 0.2] = 0.0              # absent categories
        if task == "classification":
            c1 = np.floor(n * rng.random(k))
            hist = np.stack([n - c1, c1], axis=1)
        else:
            s1 = n * rng.normal(size=k)
            hist = np.stack([n, s1, s1 * s1 / np.maximum(n, 1)
                             + n * rng.random(k)], axis=1)
        hist = np.concatenate([hist, np.zeros((16 - k, hist.shape[1]))])
        gains, _ = reference_kdd99.set_gains(hist[None, None], np.ones(1, bool),
                                             task, 1)
        best = reference_kdd99.best_partition_gain(hist, task, 1)
        got = gains.max()
        if np.isfinite(best):
            assert got == pytest.approx(best, rel=1e-12, abs=1e-12)
        else:
            assert not np.isfinite(got)


def test_reference_tree_is_perfect_under_follow():
    c = tiny.cell(CELL)
    x, y = data_kdd99.make_table(c.config["n_rows"], c.seed)
    driver = harness.load_module(harness.BENCH / "traffic" /
                                 "fit_job_cat.py", "bench_traffic_fit_job_cat")
    kr = driver.KddReference(c, x, y)
    order = reference.aligned_order(kr.ids, "s")
    service = data_kdd99.COLUMNS.index("service")
    t = next(t for t in range(100) if reference.master_draws(
        c.seed, t, len(x), x.shape[1], 0.14)[1][service])
    tree = kr.control_tree(t, order, "float64", False)
    got = kr.judge(tree, t, order)
    assert got["bad_nodes"] == 0 and got["stat_gap"] == 0.0
    assert got["regret"] == 0.0 and got["noncontiguous"] > 0


# ------------------------------------------------------------------ checks
def test_cell_is_correct_when_sound():
    r = tiny.run(tiny.cell(CELL))
    assert r["correct"], r["checks"]
    assert r["checks"]["no_set_split"]["value"] == 0
    assert set(r["metrics"]) == {"setup_s", "fit_job_s"}


def _patch_fit(monkeypatch, change):
    from repro.core.forest import FederatedForest
    fit = FederatedForest.fit

    def patched(self, partition, y):
        out = fit(self, partition, y)
        out.trees_ = change(out.trees_, partition)
        return out
    monkeypatch.setattr(FederatedForest, "fit", patched)


def _threshold_on_codes(monkeypatch):
    """The categorical columns split as ordered codes."""
    from repro.core.party import VerticalPartition
    monkeypatch.setattr(VerticalPartition, "cat_mask", property(
        lambda self: None))


def _flipped_set(trees, partition):
    """Every set split sends its left categories right and the rest left."""
    w = trees.split_cats
    full = jnp.uint32(0xFFFFFFFF)
    return trees._replace(split_cats=jnp.where(w != 0, w ^ full, w))


def _unseen_left(trees, partition):
    """Every set split also takes the code of values unseen at fit time."""
    w = np.asarray(trees.split_cats).copy()
    gid = np.asarray(trees.split_gid)[0]
    for g, d in partition.categories.items():
        code = len(d)
        on = (w != 0).any(-1) & (gid[None] == g)
        w[..., code // 32] |= np.where(on, np.uint32(1 << (code % 32)), 0)
    return trees._replace(split_cats=jnp.asarray(w))


FAULTS = {
    "threshold_on_codes": (_threshold_on_codes, "no_set_split"),
    "flipped_set": (lambda mp: _patch_fit(mp, _flipped_set), "bad_nodes"),
    "unseen_left": (lambda mp: _patch_fit(mp, _unseen_left), "bad_nodes"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(monkeypatch, fault):
    plant, number = FAULTS[fault]
    plant(monkeypatch)
    r = tiny.run(tiny.cell(CELL))
    assert not r["correct"], r["checks"]
    v = r["checks"][number]
    assert v["value"] > v["limit"], r["checks"]


def test_control_fails_the_limits():
    c = tiny.cell(CELL)
    c.clock = harness.CompileClock()
    d = harness.driver_for(c)
    d.setup()
    d.window(0.1)
    d.release()
    limits = c.params["limits"]
    got = d.control()
    assert any(v > limits[k] for k, v in got.items()), got


def test_rehearsal_passes():
    """``run.py --rehearse``: the whole cell at 1/100 of its rows, the
    Pallas kernel interpreted, checks only."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload", CELL,
         "--seed", "2147483701", "--seconds", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=900,
        cwd=harness.ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    assert '"correct": true' in res.stdout
