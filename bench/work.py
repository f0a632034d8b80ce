"""Work a federated forest fit requires, counted from the cell's shapes.

The count is the algorithm's, whatever implements it: per tree and per
split level, the split histogram reads each selected, real feature column
of every row once as one byte, plus, once per party that holds a selected
feature, each row's node id (int32) and its C float32 label statistics; it
adds each row's C statistics into one bin per selected feature; it writes
one float32 per (node of the level, selected feature, bin, channel).
Padded columns, features outside the tree's subsample, the implementation's
int32 upcasts and its node-slot padding are not counted, so a change that
removes waste raises the roofline share and no change can raise it by doing
more work.

The whole fit adds, per tree and level, the routing of every row (its bin
of the split feature read, its node id read and written) and the node
statistics (its C statistics read once).
"""
from __future__ import annotations

import numpy as np

NODE_ID_BYTES = 4
STAT_BYTES = 4
BIN_BYTES = 1


def hist_work(n_rows: int, party_of_feature: np.ndarray,
              feat_sels: np.ndarray, max_depth: int, n_bins: int,
              n_channels: int) -> dict:
    """Bytes and adds of every split histogram of one fit.

    ``party_of_feature`` (F,) gives each global feature's party and
    ``feat_sels`` (T, F) each tree's feature subsample."""
    feat_sels = np.asarray(feat_sels, bool)
    party_of_feature = np.asarray(party_of_feature)
    nbytes = ops = 0
    for sel in feat_sels:
        k = int(sel.sum())
        parties = len(np.unique(party_of_feature[sel]))
        for d in range(max_depth):
            level = 2 ** d
            nbytes += n_rows * k * BIN_BYTES
            nbytes += parties * n_rows * (NODE_ID_BYTES
                                          + n_channels * STAT_BYTES)
            nbytes += level * k * n_bins * n_channels * STAT_BYTES
            ops += n_rows * k * n_channels
    return {"bytes": nbytes, "ops": ops}


def fit_work(n_rows: int, party_of_feature: np.ndarray,
             feat_sels: np.ndarray, max_depth: int, n_bins: int,
             n_channels: int) -> dict:
    """The histograms plus the routing and node statistics of each level."""
    h = hist_work(n_rows, party_of_feature, feat_sels, max_depth, n_bins,
                  n_channels)
    levels = len(feat_sels) * max_depth
    per_row = BIN_BYTES + 2 * NODE_ID_BYTES + n_channels * STAT_BYTES
    return {"bytes": h["bytes"] + levels * n_rows * per_row,
            "ops": h["ops"] + levels * n_rows * n_channels}


def required_seconds(work: dict, peak: dict, chips: int) -> tuple[float, str]:
    """Least time ``chips`` chips need for ``work`` and which peak bounds it:
    bytes over HBM bandwidth, or operations over the bf16 peak (the
    highest the chip publishes, so the bound is never overstated)."""
    t_mem = work["bytes"] / (chips * peak["hbm_bytes_per_s"])
    t_ops = work["ops"] / (chips * peak["bf16_flops_per_s"])
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "compute")


def for_config(cfg: dict, feat_sels: np.ndarray) -> dict:
    """Histogram and whole-fit work of one fit of a configuration, given
    its trees' feature subsamples; reads only the shapes, the party split
    and the forest's depth, bins and task."""
    widths = [p["features"] for p in cfg["parties"]]
    party = np.repeat(np.arange(len(widths)), widths)
    fp = cfg["forest"]
    channels = (int(cfg.get("n_classes", 2))
                if cfg["task"] == "classification" else 3)
    shape = (int(cfg["n_rows"]), party, feat_sels, int(fp["max_depth"]),
             int(fp["n_bins"]), channels)
    return {"hist": hist_work(*shape), "fit": fit_work(*shape)}
