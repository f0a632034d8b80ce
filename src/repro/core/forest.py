"""FederatedForest — the user-facing estimator (fit/predict).

Orchestrates: master-side randomness (bootstrap weights + per-tree feature
subsets, paper Alg. 2 lines 3–4), label encoding (crypto.py), the SPMD
builder (tree.py) and the one-round predictor (prediction.py).  Execution
goes through a federation Substrate (vmap simulation by default; a session
can bind a sharded mesh instead) — the programs themselves live in
repro.federation.programs.

The centralized baseline ("NonFF") is *the same code* with M = 1 — that is the
strongest possible form of the paper's losslessness claim, and it's what the
tests assert bit-identically.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import crypto, impurity, tree
from repro.core.party import VerticalPartition, make_vertical_partition
from repro.core.types import ForestParams
from repro.observability import trace as tracing


@dataclasses.dataclass
class FederatedForest:
    params: ForestParams
    encrypt_labels: bool = True
    # Regression-target masking is opt-in: the affine mask preserves split
    # gains exactly in real arithmetic but not in float32 (catastrophic
    # cancellation near gain ties), so it trades exact losslessness for
    # in-transit privacy — the same trade-off the paper concedes in §4.3
    # ("there will be a trade-off between the security protection and the
    # computational efficiency").
    mask_regression: bool = False
    # DEPRECATED: per-estimator histogram override.  The backend choice is
    # session-level now — set Federation(hist_impl=...) or params.hist_impl.
    hist_impl: str | None = None
    # execution substrate (federation.substrate); None -> vmap simulation
    substrate: Any = None

    # fitted state
    trees_: tree.PartyTree | None = None      # leading axes (M, T, ...)
    partition_: VerticalPartition | None = None
    _decode: Callable | None = None

    def __post_init__(self) -> None:
        if self.hist_impl is not None:
            warnings.warn(
                "FederatedForest(hist_impl=...) is deprecated: the histogram "
                "backend is owned by the session (Federation(hist_impl=...)) "
                "or by ForestParams.hist_impl",
                DeprecationWarning, stacklevel=3)

    def _sub(self):
        from repro.federation.substrate import default_substrate
        return default_substrate(self.substrate)

    # ------------------------------------------------------------------ fit
    def fit(self, partition: VerticalPartition, y: np.ndarray) -> "FederatedForest":
        """Grow the forest.  Spans: ``fit.prepare`` (label encoding,
        statistics, master randomness, inputs on the device), then on an
        in-process substrate ``fit.lower`` (trace + lower) and
        ``fit.compile`` (XLA compile or persistent-cache load), and
        ``fit.run`` until the trees are ready."""
        from repro.federation import programs
        # "auto" build knobs resolve against the actual training set — the
        # concrete values land back on self.params so refits/serving see them
        self.params = self.params.resolved(partition.n_samples)
        p = self.params
        if partition.xb.shape[2] == 0:
            raise ValueError("empty feature space")
        cat_mask = self._cat_mask(partition)
        sub = self._sub()
        party_cols = partition.xb.shape[2]
        with tracing.TRACER.span(
                "fit.prepare", rows=partition.n_samples, trees=p.n_estimators,
                hist_cols=tree.hist_columns(p, partition.n_features,
                                            party_cols),
                party_cols=party_cols,
                cat_cols=len(partition.categories or ())):
            y = np.asarray(y)
            if self.encrypt_labels and p.task == "classification":
                y_enc, self._decode = crypto.encode_labels(y, p.n_classes,
                                                           p.seed)
            elif self.mask_regression and p.task == "regression":
                y_enc, self._decode = crypto.mask_regression_targets(y, p.seed)
            else:
                y_enc, self._decode = y, lambda v: np.asarray(v)
            y_stats = impurity.stat_channels(jnp.asarray(y_enc), p.task,
                                             p.n_classes)
            weights, feat_sels = self._master_randomness(partition)
            self._check_feature_budget(feat_sels)
            party = (partition.xb, partition.feat_gid) + (
                () if cat_mask is None else (cat_mask,))
            with sub.context():
                args = jax.block_until_ready((
                    *map(jnp.asarray, party), jnp.asarray(feat_sels),
                    jnp.asarray(weights), y_stats))

        run = sub.compile(programs.forest_fit_program(
            sub, p, self.hist_impl, categorical=cat_mask is not None))
        with sub.context():
            if hasattr(run, "lower"):           # jit: split trace / compile
                with tracing.TRACER.span("fit.lower"):
                    lowered = run.lower(*args)
                with tracing.TRACER.span("fit.compile"):
                    run = lowered.compile()
            with tracing.TRACER.span("fit.run"):
                self.trees_ = jax.block_until_ready(run(*args))
        self.partition_ = partition
        return self

    def _cat_mask(self, partition: VerticalPartition):
        """The partition's (M, Fp) categorical-column mask, or None.  Set
        splits are exact by CART's ordering rule only for two classes and
        for regression, so other class counts are refused."""
        cat_mask = partition.cat_mask
        p = self.params
        if cat_mask is not None and p.task == "classification" \
                and p.n_classes != 2:
            raise ValueError(
                f"categorical columns need n_classes == 2 or regression "
                f"(got n_classes={p.n_classes}): no ordering rule finds "
                f"the best category set for more classes, so the forest "
                f"could not be lossless")
        return cat_mask

    def _master_randomness(self, partition: VerticalPartition):
        """Paper Alg. 2: master samples rows (bootstrap) + per-tree features.

        Each tree draws from its own seeded stream
        (``default_rng([seed, t])``), so tree t's bootstrap and feature
        subset depend only on (seed, t) — never on how many trees the forest
        will eventually hold.  That prefix-stability is what makes an
        incremental continuation exact: extending a fitted T-tree forest to
        T' trees produces bit-identically the first T trees of a from-scratch
        T'-tree fit (fit_resumable's tree-extension path relies on it)."""
        p = self.params
        n, f = partition.n_samples, partition.n_features
        t = p.n_estimators
        k = p.features_per_tree(f)
        weights = np.ones((t, n))
        feat_sels = np.zeros((t, f), dtype=bool)
        for i in range(t):
            rng = np.random.default_rng([p.seed, i])
            if p.bootstrap:
                weights[i] = np.bincount(rng.integers(0, n, size=n),
                                         minlength=n)
            feat_sels[i, rng.choice(f, size=k, replace=False)] = True
        return weights.astype(np.float32), feat_sels

    def _check_feature_budget(self, feat_sels: np.ndarray) -> None:
        """``tree.build_tree`` histograms at most ``features_per_tree(F)``
        columns per party and tree; a subsample above that budget would lose
        features inside the program, so it is refused here."""
        budget = self.params.features_per_tree(feat_sels.shape[1])
        drawn = np.asarray(feat_sels).sum(axis=1)
        over = np.flatnonzero(drawn > budget)
        if over.size:
            raise ValueError(
                f"tree {over[0]} selects {drawn[over[0]]} features, above the "
                f"per-tree budget of {budget} (max_features="
                f"{self.params.max_features} of {feat_sels.shape[1]})")

    def _fit_fingerprint(self, partition: VerticalPartition,
                         y: np.ndarray) -> str:
        """Content hash of everything a resumable fit depends on EXCEPT the
        tree count: the binned data, the labels, and the params.  A
        checkpoint tagged with a different fingerprint must not be resumed —
        appending rows (ingest_append) changes the partition, and welding
        old-data trees onto new-data trees would silently produce a
        franken-forest.  n_estimators is excluded so growing the tree count
        IS resumable (per-tree randomness makes the prefix exact)."""
        import hashlib
        h = hashlib.sha256()
        for a in (partition.xb, partition.feat_gid, partition.boundaries,
                  np.asarray(y)):
            h.update(np.ascontiguousarray(a).tobytes())
        if partition.categories:
            h.update(repr(sorted(partition.categories)).encode())
        h.update(repr(dataclasses.replace(
            self.params, n_estimators=0)).encode())
        h.update(repr((self.encrypt_labels, self.mask_regression)).encode())
        return h.hexdigest()

    # -------------------------------------------------------------- predict
    def _run_predict(self, x_test: np.ndarray, program, *shared) -> np.ndarray:
        from repro.federation import programs
        if self.trees_ is None:
            raise ValueError("model is not fitted: call fit() first")
        xb_parts = self.partition_.bin_test(np.asarray(x_test))
        with self._sub().context():
            out = self._sub().compile(program)(self.trees_,
                                               jnp.asarray(xb_parts), *shared)
        return self._decode(programs.party0(out))

    def predict(self, x_test: np.ndarray) -> np.ndarray:
        """One-round prediction (the paper's algorithm)."""
        from repro.federation import programs
        return self._run_predict(
            x_test, programs.forest_predict_program(self._sub(), self.params))

    def predict_classical(self, x_test: np.ndarray) -> np.ndarray:
        """Multi-round baseline (paper's comparison in Figs. 4–6)."""
        from repro.federation import programs
        return self._run_predict(
            x_test,
            programs.forest_predict_classical_program(self._sub(), self.params))

    def leaf_table(self, pad_multiple: int = 8):
        """Live-leaf compaction plan of the fitted forest (serving/plan.py)."""
        from repro.serving import plan
        if self.trees_ is None:
            raise ValueError("model is not fitted: call fit() first")
        return plan.build_leaf_table(self.trees_, self.params,
                                     pad_multiple=pad_multiple)

    def predict_compact(self, x_test: np.ndarray,
                        leaf_table=None) -> np.ndarray:
        """One-round prediction through the leaf-compacted mask.

        Bit-identical to :meth:`predict` (Prop. 1 is unchanged; only dead
        heap columns are dropped from the psum and the vote) — the serving
        engine's kernel, exposed here for parity tests and ad-hoc use."""
        from repro.federation import programs
        if self.trees_ is None:
            raise ValueError("model is not fitted: call fit() first")
        lt = leaf_table if leaf_table is not None else self.leaf_table()
        return self._run_predict(
            x_test,
            programs.forest_predict_program(self._sub(), self.params,
                                            compact=True),
            lt.leaf_idx)

    # ------------------------------------------------- break-point recovery
    def fit_resumable(self, partition: VerticalPartition, y: np.ndarray,
                      ckpt_dir: str, trees_per_chunk: int = 2) -> "FederatedForest":
        """Paper §4.1: "if the connection is down, the modeling can be easily
        recovered from the break point."  Trees are independent (bagging), so
        recovery granularity = tree chunks: each chunk's PartyTree stack is
        checkpointed; a restarted fit resumes after the last complete chunk
        and produces the IDENTICAL forest (master randomness is derived from
        the seed, not from progress).

        Checkpoints carry a fingerprint of (binned data, labels, params sans
        tree count): a checkpoint from different data or params is ignored
        and the fit restarts from scratch instead of welding incompatible
        tree prefixes together.  Two incremental moves are therefore exact:

          * **more trees** — rerun with a larger ``n_estimators``: the
            checkpointed prefix is reused and only the new trees build
            (per-tree randomness makes the result bit-identical to a
            from-scratch fit at the larger count);
          * **more rows** — after ``Federation.ingest_append`` the partition
            changed, the fingerprint mismatches, and the refit is cleanly
            from scratch on the concatenated data.

        A checkpoint AHEAD of ``n_estimators`` (trained further in a prior
        run) restores and slices its first ``n_estimators`` trees — also
        exact, for the same reason."""
        from repro import ckpt
        self.params = self.params.resolved(partition.n_samples)
        p = self.params
        cat_mask = self._cat_mask(partition)
        y = np.asarray(y)
        if self.encrypt_labels and p.task == "classification":
            y_enc, self._decode = crypto.encode_labels(y, p.n_classes, p.seed)
        else:
            y_enc, self._decode = y, lambda v: np.asarray(v)
        y_stats = impurity.stat_channels(jnp.asarray(y_enc), p.task, p.n_classes)
        weights, feat_sels = self._master_randomness(partition)
        self._check_feature_budget(feat_sels)
        fingerprint = self._fit_fingerprint(partition, y)

        from repro.federation import programs
        run = self._sub().compile(programs.forest_fit_program(
            self._sub(), p, self.hist_impl, categorical=cat_mask is not None))
        party = (jnp.asarray(partition.xb), jnp.asarray(partition.feat_gid)) \
            + (() if cat_mask is None else (jnp.asarray(cat_mask),))

        def restore(done):
            # PartyTree stack shapes are fully determined by (M, done, params)
            # — no need to trace the fit program (which the distributed
            # substrate could not trace anyway).
            m, nn, c = partition.n_parties, p.n_nodes, p.n_stat_channels
            sds = jax.ShapeDtypeStruct
            like = tree.PartyTree(
                is_leaf=sds((m, done, nn), jnp.bool_),
                leaf_stats=sds((m, done, nn, c), jnp.float32),
                has_split=sds((m, done, nn), jnp.bool_),
                split_floc=sds((m, done, nn), jnp.int32),
                split_bin=sds((m, done, nn), jnp.int32),
                owner=sds((m, done, nn), jnp.int32),
                split_gid=sds((m, done, nn), jnp.int32))
            if cat_mask is not None:
                like = tree.CategoricalPartyTree(*like, sds(
                    (m, done, nn, tree.cat_words(p.n_bins)), jnp.uint32))
            return ckpt.restore_checkpoint(ckpt_dir, done, like)

        chunks: list = []
        done = ckpt.latest_step(ckpt_dir)
        if done is not None:
            # legacy pre-fingerprint checkpoints (meta without the key) are
            # trusted as before; a PRESENT-but-different fingerprint means
            # the data or params moved under the checkpoint — start over
            stamp = ckpt.read_meta(ckpt_dir, done).get("fingerprint")
            if stamp is not None and stamp != fingerprint:
                done = None
        start = 0
        if done is not None and done >= p.n_estimators:
            full = restore(done)
            self.trees_ = jax.tree.map(
                lambda a: a[:, : p.n_estimators], full)
            self.partition_ = partition
            return self
        if done is not None:
            chunks.append(restore(done))
            start = done
        for lo in range(start, p.n_estimators, trees_per_chunk):
            hi = min(lo + trees_per_chunk, p.n_estimators)
            part_trees = run(*party,
                             jnp.asarray(feat_sels[lo:hi]),
                             jnp.asarray(weights[lo:hi]), y_stats)
            chunks.append(part_trees)
            merged = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                                  *chunks)
            ckpt.save_checkpoint(ckpt_dir, hi, merged,
                                 meta={"family": "forest",
                                       "fingerprint": fingerprint})
            chunks = [merged]
        self.trees_ = chunks[0]
        self.partition_ = partition
        return self

    # ------------------------------------------------------------ inspection
    def feature_importance(self, view: str = "master") -> np.ndarray:
        """Split-count importance over encoded feature ids (privacy-aware:
        ``view='party:i'`` restricts to party i's own splits — what each
        participant may legitimately compute locally)."""
        if self.trees_ is None:
            raise ValueError("model is not fitted: call fit() first")
        trees = jax.tree.map(np.asarray, self.trees_)
        counts = np.zeros(self.partition_.n_features, np.float64)
        gids = trees.split_gid[0]             # master view (T, nn)
        weights = trees.leaf_stats[0].sum(-1)  # node weighted counts (T, nn)
        if view.startswith("party:"):
            i = int(view.split(":")[1])
            mine = trees.has_split[i]
            gids = np.where(mine, gids, -1)
        sel = gids >= 0
        np.add.at(counts, gids[sel], weights[sel])
        total = counts.sum()
        return counts / total if total else counts

    def master_tree_view(self):
        """The complete model T as the master stores it (owner + encoded id)."""
        if self.trees_ is None:
            raise ValueError("model is not fitted: call fit() first")
        t = jax.tree.map(lambda a: np.asarray(a[0]), self.trees_)
        return {"owner": t.owner, "split_gid": t.split_gid,
                "is_leaf": t.is_leaf, "leaf_stats": t.leaf_stats}


def fit_federated_forest(x: np.ndarray, y: np.ndarray, n_parties: int,
                         params: ForestParams, *, contiguous: bool = True,
                         **forest_kw) -> FederatedForest:
    """Convenience: vertical-partition a raw matrix and fit."""
    part = make_vertical_partition(x, n_parties, params.n_bins,
                                   contiguous=contiguous, seed=params.seed)
    return FederatedForest(params, **forest_kw).fit(part, y)
