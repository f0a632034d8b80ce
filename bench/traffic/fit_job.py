"""Traffic kind ``fit_job``: whole federated fit jobs, back to back.

A job is what a deployment runs each period: a fresh ``Federation``, then
``ingest`` of every party's block (hashed-ID alignment of the parties' own
row orders, party-local binning), then ``fit`` of the configured forest,
ending when the fitted trees are ready on the device.  Each job hashes the
IDs with its own salt, so every job aligns, bootstraps and grows a
different forest from the same blocks.  The window runs jobs until
``--seconds`` have passed; the job in progress finishes and no job starts
after that.  ``fit_job_s`` is the window's length over its jobs.

Correctness: a sample of the window's trees, drawn from the seed, is
followed by the float64 reference (``reference.follow``) on the reference's
own bins, alignment and bootstrap; every tree of every job is checked for
parties that disagree.
"""
from __future__ import annotations

import time

import numpy as np

import common
import data
import reference
import work


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.jobs: list[dict] = []
        self.forests: list[list[dict]] = []
        self.window_s = 0.0
        self.failed = 0
        self.warm_up = True         # calibration skips it after one seed

    @property
    def attempted(self) -> int:
        return len(self.jobs)

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        cell, cfg = self.cell, self.cell.config
        n, f = int(cfg["n_rows"]), int(cfg["n_features"])
        self.x, self.y = data.make_table(cfg, n, cell.seed)
        self.blocks = common.party_blocks(cell, self.x, self.y)
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

    def _job(self, salt: str) -> dict:
        import jax
        cell = self.cell
        fed = common.federation(cell, self.mesh)
        t0 = time.perf_counter()
        with cell.annotate("ingest"):
            fed.ingest(self.blocks, salt=salt)
        t1 = time.perf_counter()
        before = cell.clock.snapshot()
        with cell.annotate("fit"):
            model = fed.fit(self.params)
            jax.block_until_ready(model.trees_)
        t2 = time.perf_counter()
        traced = cell.clock.since(before, cell.clock.snapshot())["trace_s"]
        return {"salt": salt, "trees": model.trees_,
                "feat_gid": np.asarray(model.partition_.feat_gid),
                "ingest_s": t1 - t0, "fit_s": t2 - t1, "fit_trace_s": traced}

    # ------------------------------------------------------------- window
    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.jobs.append(self._job(f"{self.cell.seed}:{len(self.jobs)}"))
        self.window_s = time.perf_counter() - t0

    def release(self) -> None:
        self.party_bad = 0
        for job in self.jobs:
            forest, bad = common.neutral_forest(job.pop("trees"),
                                                job["feat_gid"])
            self.forests.append(forest)
            self.party_bad += bad
        self.blocks = None

    # -------------------------------------------------------------- check
    def picks(self) -> list[tuple[int, int]]:
        """(job, tree) pairs the reference checks, drawn from the seed."""
        pairs = [(j, t) for j in range(len(self.forests))
                 for t in range(len(self.forests[j]))]
        k = min(int(self.cell.params["check_trees"]), len(pairs))
        rng = np.random.default_rng([self.cell.seed, 5])
        return [pairs[i] for i in sorted(rng.choice(len(pairs), k,
                                                    replace=False))]

    def _orders(self, ref, picks) -> dict:
        return {j: reference.aligned_order(ref.ids, self.jobs[j]["salt"])
                for j in {j for j, _ in picks}}

    def check(self) -> dict:
        ref = common.ReferenceData(self.cell, self.x, self.y)
        picks = self.picks()
        worst = common.judge_many(ref, picks, self.forests,
                                  self._orders(ref, picks))
        worst["bad_nodes"] += self.party_bad
        return worst

    def control(self) -> dict:
        """The check's numbers with the reference grown in bfloat16 put in
        the program's place, on the same picked trees."""
        ref = common.ReferenceData(self.cell, self.x, self.y)
        picks = self.picks()
        orders = self._orders(ref, picks)
        forests = {j: {} for j, _ in picks}
        for j, t in picks:
            forests[j][t] = ref.control_tree(t, orders[j], "bfloat16", True)
        return common.judge_many(ref, picks, forests, orders)

    # ------------------------------------------------------------ numbers
    def end_to_end(self) -> dict:
        return {"fit_job_s": self.window_s / len(self.jobs)}

    def counters(self) -> dict:
        keys = ("ingest_s", "fit_s", "fit_trace_s")
        return {"jobs": len(self.jobs), "work": self.work,
                **{k: [j[k] for j in self.jobs] for k in keys}}
