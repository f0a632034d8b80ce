"""Pieces both drivers share: the session, the party blocks, the forest
parameters, the reference's view of the data and of a fitted forest."""
from __future__ import annotations

import numpy as np

import data
import reference


def forest_params(cell):
    """The configuration's forest as ``ForestParams``; the run's seed is the
    master's seed."""
    from repro.core import ForestParams
    cfg = cell.config
    return ForestParams(task=cfg["task"], n_classes=int(cfg.get("n_classes",
                                                                2)),
                        seed=int(cell.seed), **cfg["forest"])


def reference_params(cell) -> dict:
    cfg = cell.config
    return {"task": cfg["task"], **cfg["forest"]}


def make_mesh(cell):
    """The configuration's mesh over the cell's devices (sharded substrate)."""
    from repro import compat
    axes = cell.config["mesh"]
    return compat.make_mesh(tuple(axes.values()), tuple(axes),
                            devices=cell.devices)


def federation(cell, mesh):
    from repro.federation import Federation
    cfg = cell.config
    return Federation(parties=len(cfg["parties"]), substrate=cfg["substrate"],
                      mesh=mesh, n_bins=int(cfg["forest"]["n_bins"]),
                      hist_impl=cell.hist_impl)


def party_blocks(cell, x: np.ndarray, y: np.ndarray) -> list:
    """One PartyBlock per party: its own columns of every customer, in its
    own seeded row order, keyed by sample ID; the first party holds the
    labels."""
    from repro.core import PartyBlock
    cfg = cell.config
    n = len(x)
    ids = data.sample_ids(n)
    groups = data.feature_groups([p["features"] for p in cfg["parties"]])
    orders = data.party_row_orders(n, len(groups), cell.seed)
    return [PartyBlock(name=p["name"], x=x[rows[:, None], g[None, :]],
                       ids=ids[rows],
                       y=y[rows] if i == 0 else None, feature_ids=g)
            for i, (p, g, rows) in enumerate(zip(cfg["parties"], groups,
                                                 orders))]


def neutral_forest(trees, feat_gid: np.ndarray) -> tuple[list, int]:
    """The program's fitted PartyTree stack in the reference's layout, and
    the count of nodes where the parties' views disagree or a split's owner
    does not hold its feature."""
    t = {k: np.asarray(v) for k, v in trees._asdict().items()}
    m = t["is_leaf"].shape[0]
    bad = np.zeros(t["is_leaf"].shape[1:], bool)
    for key in ("is_leaf", "owner", "split_gid"):
        for i in range(1, m):
            bad |= t[key][i] != t[key][0]
    for i in range(1, m):
        bad |= (t["leaf_stats"][i] != t["leaf_stats"][0]).any(-1)
    owner = t["owner"][0]
    split = owner >= 0
    o = np.clip(owner, 0, m - 1)
    ti, ni = np.indices(owner.shape)
    floc = t["split_floc"][o, ti, ni]
    held = feat_gid[o, np.clip(floc, 0, feat_gid.shape[1] - 1)]
    bad |= split & (~t["has_split"][o, ti, ni] | (floc < 0)
                    | (held != t["split_gid"][0]))
    gid = np.where(split, t["split_gid"][0], -1)
    bins = np.where(split, t["split_bin"][o, ti, ni], -1)
    forest = [{"is_leaf": t["is_leaf"][0, k], "feature": gid[k],
               "bin": bins[k], "stats": t["leaf_stats"][0, k]}
              for k in range(owner.shape[0])]
    return forest, int(bad.sum())


class ReferenceData:
    """The reference's own view of the training set: its bins of every
    feature and its label statistics, in the order the data was made."""

    def __init__(self, cell, x: np.ndarray, y: np.ndarray):
        cfg = cell.config
        self.p = reference_params(cell)
        self.seed = int(cell.seed)
        self.n_classes = int(cfg.get("n_classes", 2))
        self.ids = data.sample_ids(len(x))
        self.edges = reference.quantile_boundaries(x, self.p["n_bins"])
        self.xb = reference.apply_bins(x, self.edges)
        if cfg["task"] == "classification":
            self.perm = reference.label_permutation(self.seed,
                                                    self.n_classes)
            y = self.perm[y]
        self.stats = reference.stat_channels(y, cfg["task"], self.n_classes)

    def judge(self, tree: dict, t: int, order: np.ndarray) -> dict:
        """Follow tree ``t`` of a fit whose rows were aligned in ``order``."""
        n, f = self.xb.shape
        w, sel = reference.master_draws(self.seed, t, n, f,
                                        self.p["max_features"])
        gids = np.nonzero(sel)[0]
        return reference.follow(tree, self.xb[order[:, None], gids[None]],
                                gids, w, self.stats[order], self.p)

    def control_tree(self, t: int, order: np.ndarray, precision: str,
                     round_inputs: bool) -> dict:
        """Tree ``t`` grown by the reference itself at a lower precision."""
        n, f = self.xb.shape
        w, sel = reference.master_draws(self.seed, t, n, f,
                                        self.p["max_features"])
        gids = np.nonzero(sel)[0]
        return reference.build_tree(
            self.xb[order[:, None], gids[None]], gids, w, self.stats[order],
            self.p,
            reference.Arith(precision),
            in_round=reference.to_bf16 if round_inputs else None)


def judge_many(ref: ReferenceData, picks, forests, orders) -> dict:
    """Worst numbers over the picked (fit, tree) pairs."""
    worst = {"split_regret": 0.0, "node_stat_gap": 0.0, "bad_nodes": 0}
    for j, t in picks:
        r = ref.judge(forests[j][t], t, orders[j])
        worst["split_regret"] = max(worst["split_regret"], r["regret"])
        worst["node_stat_gap"] = max(worst["node_stat_gap"], r["stat_gap"])
        worst["bad_nodes"] += r["bad_nodes"]
    return worst
