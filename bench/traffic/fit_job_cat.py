"""Traffic kind ``fit_job_cat``: whole fit jobs over a table whose symbolic
columns are declared categorical.

The jobs are ``fit_job``'s (its ``Driver`` is reused): a fresh
``Federation``, ``ingest`` of every party's own shuffled block keyed by
sample IDs with a new hash salt, ``fit``, trees ready on the device.  What
differs is set-up and the check.  Set-up draws the rows from
``bench/data_kdd99.py`` and declares each party's symbolic columns on its
``PartyBlock``.  The check follows a seeded sample of the window's trees
with ``bench/reference_kdd99.py`` (set splits on the symbolic columns), and
adds ``no_set_split``: 1 when none of the sampled trees takes a categorical
split whose left set is not a run of consecutive codes, that is when no set
split was grown that a threshold could not have made.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

import common
import data
import data_kdd99
import harness
import reference
import reference_kdd99
import work

fit_job = harness.load_module(Path(__file__).with_name("fit_job.py"),
                              "bench_traffic_fit_job")


def left_sets(trees, cat_gids, n_bins: int) -> np.ndarray:
    """(T, nn, n_bins) left codes of each tree's categorical splits, read
    from the split owner's ``split_cats`` bitset; a split on a categorical
    column that carries no set (a threshold on its codes) is read as the
    codes at or below its bin.  All False at numeric splits and leaves."""
    owner = np.asarray(trees.owner)[0]                      # (T, nn)
    gid = np.asarray(trees.split_gid)[0]
    m = np.asarray(trees.owner).shape[0]
    o = np.clip(owner, 0, m - 1)
    ti, ni = np.indices(owner.shape)
    on_cat = (owner >= 0) & np.isin(gid, list(cat_gids))
    words = getattr(trees, "split_cats", None)
    if words is not None:
        words = np.asarray(words)[o, ti, ni]                # (T, nn, W)
        bits = (words[..., None] >> np.arange(32, dtype=np.uint32)) & 1
        left = bits.reshape(words.shape[:-1] + (-1,))[..., :n_bins] == 1
    else:
        left = np.zeros(owner.shape + (n_bins,), bool)
    thr = np.asarray(trees.split_bin)[o, ti, ni]
    as_threshold = on_cat & ~left.any(-1)
    left |= as_threshold[..., None] & (np.arange(n_bins) <= thr[..., None])
    return left & on_cat[..., None]


def foreign_sets(trees) -> int:
    """Nodes where a party that does not own the split holds a set."""
    words = getattr(trees, "split_cats", None)
    if words is None:
        return 0
    words = np.asarray(words)
    owner = np.asarray(trees.owner)[0]
    mine = np.arange(words.shape[0])[:, None, None] == owner[None]
    return int(((words != 0).any(-1) & ~mine).any(0).sum())


class KddReference:
    """The reference's own view of the training set: its bins (codes of the
    symbolic columns), its label statistics, in the order the data was
    made."""

    def __init__(self, cell, x: np.ndarray, y: np.ndarray):
        self.p = common.reference_params(cell)
        self.seed = int(cell.seed)
        self.ids = data.sample_ids(len(x))
        self.cat = np.zeros(x.shape[1], bool)
        self.cat[list(data_kdd99.CATEGORICAL)] = True
        self.xb, _, _ = reference_kdd99.bin_table(
            x, data_kdd99.CATEGORICAL, self.p["n_bins"])
        self.perm = reference.label_permutation(self.seed, 2)
        self.stats = reference.stat_channels(self.perm[y], "classification",
                                             2)

    def _view(self, t: int, order: np.ndarray):
        n, f = self.xb.shape
        w, sel = reference.master_draws(self.seed, t, n, f,
                                        self.p["max_features"])
        gids = np.nonzero(sel)[0]
        return (self.xb[order[:, None], gids[None]], gids, self.cat[gids],
                w, self.stats[order])

    def judge(self, tree: dict, t: int, order: np.ndarray) -> dict:
        return reference_kdd99.follow(tree, *self._view(t, order), self.p)

    def control_tree(self, t: int, order: np.ndarray, precision: str,
                     round_inputs: bool) -> dict:
        return reference_kdd99.build_tree(
            *self._view(t, order), self.p, reference.Arith(precision),
            in_round=reference.to_bf16 if round_inputs else None)


class Driver(fit_job.Driver):
    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        cell, cfg = self.cell, self.cell.config
        n, f = int(cfg["n_rows"]), int(cfg["n_features"])
        self.x, self.y = data_kdd99.make_table(n, cell.seed)
        self.blocks = self._party_blocks()
        self.params = common.forest_params(cell)
        self.mesh = (common.make_mesh(cell) if cfg["substrate"] == "sharded"
                     else None)
        fp = cfg["forest"]
        sels = np.stack([reference.master_draws(cell.seed, t, n, f,
                                                fp["max_features"])[1]
                         for t in range(fp["n_estimators"])])
        self.work = work.for_config(cfg, sels)
        if self.warm_up:
            with cell.annotate("warmup"):
                self._job(f"warmup:{cell.seed}")

    def _party_blocks(self) -> list:
        """One PartyBlock per party: its own columns of every connection in
        its own seeded row order, keyed by connection ID, its symbolic
        columns declared; the first party holds the labels."""
        from repro.core import PartyBlock
        cfg = self.cell.config
        n = len(self.x)
        ids = data.sample_ids(n)
        groups = data_kdd99.party_columns([p["features"]
                                           for p in cfg["parties"]])
        orders = data.party_row_orders(n, len(groups), self.cell.seed)
        return [PartyBlock(
            name=p["name"], x=self.x[rows[:, None], g[None, :]],
            ids=ids[rows], y=self.y[rows] if i == 0 else None,
            feature_ids=g,
            categorical=tuple(np.flatnonzero(
                np.isin(g, data_kdd99.CATEGORICAL))))
            for i, (p, g, rows) in enumerate(zip(cfg["parties"], groups,
                                                 orders))]

    def release(self) -> None:
        self.party_bad = 0
        n_bins = int(self.cell.config["forest"]["n_bins"])
        for job in self.jobs:
            trees = job.pop("trees")
            forest, bad = common.neutral_forest(trees, job["feat_gid"])
            left = left_sets(trees, data_kdd99.CATEGORICAL, n_bins)
            for k, tree in enumerate(forest):
                tree["left"] = left[k]
            self.forests.append(forest)
            self.party_bad += bad + foreign_sets(trees)
        self.blocks = None

    # -------------------------------------------------------------- check
    @staticmethod
    def _judge(ref, forests, picks, orders) -> dict:
        worst = {"split_regret": 0.0, "node_stat_gap": 0.0, "bad_nodes": 0,
                 "no_set_split": 1}
        for j, t in picks:
            r = ref.judge(forests[j][t], t, orders[j])
            worst["split_regret"] = max(worst["split_regret"], r["regret"])
            worst["node_stat_gap"] = max(worst["node_stat_gap"],
                                         r["stat_gap"])
            worst["bad_nodes"] += r["bad_nodes"]
            if r["noncontiguous"]:
                worst["no_set_split"] = 0
        return worst

    def check(self) -> dict:
        ref = KddReference(self.cell, self.x, self.y)
        picks = self.picks()
        worst = self._judge(ref, self.forests, picks,
                            self._orders(ref, picks))
        worst["bad_nodes"] += self.party_bad
        return worst

    def control(self) -> dict:
        """The check's numbers with the reference grown in bfloat16 put in
        the program's place, on the same picked trees."""
        ref = KddReference(self.cell, self.x, self.y)
        picks = self.picks()
        orders = self._orders(ref, picks)
        forests = {j: {} for j, _ in picks}
        for j, t in picks:
            forests[j][t] = ref.control_tree(t, orders[j], "bfloat16", True)
        return self._judge(ref, forests, picks, orders)
