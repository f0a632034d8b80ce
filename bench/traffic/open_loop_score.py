"""Traffic kind ``open_loop_score``: scoring requests on an open-loop clock.

Set-up fits the configuration's forest once (as a ``fit_job`` would), stands
it up with ``Federation.serve`` and ``ServeConfig(buckets=...)``, compiles
every bucket, and sends one request of each bucket's size through the
queue so the host path is warm.

The window sends requests on a schedule fixed in advance
(``data.stratified_poisson``: Poisson arrivals at the mix's rate, sizes
log-uniform between its bounds, rows from a held-out pool made from the
seed), whether or not earlier ones have been answered.  One thread does
everything: it submits every request that is due, drains the queue, and
sleeps when nothing is due or pending.  A request's latency runs from its
due time to the end of the drain that answered it, so time spent behind a
drain is counted.  How late the generator submitted is printed before the
result.

Correctness: the served forest is followed by the float64 reference, and a
sample of the answered requests, drawn from the seed and holding the
longest, is answered again by walking that forest over the reference's own
bins of the request rows; every label must match.
"""
from __future__ import annotations

import time

import numpy as np

import common
import data
import reference


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.failed = 0
        self.attempted = 0

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        import jax
        from repro.serving import RequestQueue, ServeConfig
        cell, cfg, mix = self.cell, self.cell.config, self.cell.traffic
        n = int(cfg["n_rows"])
        x, y = data.make_table(cfg, n + int(mix["pool_rows"]), cell.seed)
        self.x, self.y, self.pool = x[:n], y[:n], x[n:]
        self.salt = f"serve:{cell.seed}"
        mesh = (common.make_mesh(cell) if cfg["substrate"] == "sharded"
                else None)
        with cell.annotate("fit"):
            self.fed = common.federation(cell, mesh)
            self.fed.ingest(common.party_blocks(cell, self.x, self.y),
                            salt=self.salt)
            self.model = self.fed.fit(common.forest_params(cell))
            jax.block_until_ready(self.model.trees_)
        with cell.annotate("warmup"):
            self.server = self.fed.serve(
                self.model, ServeConfig(buckets=tuple(mix["buckets"])))
            self.server.warmup()
            self.queue = RequestQueue(self.server)
            for b in self.server.buckets:
                self.queue.submit(self.pool[:b])
                self.queue.drain()
        self._last_wave = max((w["t0"] for w in self.server.wave_stats),
                              default=-np.inf)

    def _new_waves(self) -> list[dict]:
        new = []
        for w in reversed(self.server.wave_stats):
            if w["t0"] <= self._last_wave:
                break
            new.append(w)
        if new:
            self._last_wave = new[0]["t0"]
        return new

    # ------------------------------------------------------------- window
    def window(self, seconds: float) -> None:
        cell, mix = self.cell, self.cell.traffic
        due, sizes = data.stratified_poisson(
            float(mix["rate_per_s"]), seconds, int(mix["size_min"]),
            int(mix["size_max"]), cell.seed)
        offs = data.request_offsets(sizes, len(self.pool), cell.seed)
        k = len(due)
        self.due, self.sizes, self.offs = due, sizes, offs
        self.latency = np.full(k, np.nan)
        self.late = np.zeros(k)
        self.answers: list = [None] * k
        self.waves = {"waves": 0, "rows": 0, "bucket_rows": 0}
        rid_of: dict[int, int] = {}
        queue = self.queue
        i, t_done = 0, 0.0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if i < k and due[i] <= now:
                with cell.annotate("submit"):
                    while i < k and due[i] <= now:
                        lo = int(offs[i])
                        rid_of[queue.submit(self.pool[lo:lo + sizes[i]])] = i
                        self.late[i] = now - due[i]
                        i += 1
            if queue.pending_requests():
                with cell.annotate("drain"):
                    results = queue.drain()
                t_done = time.perf_counter() - t0
                for rid, out in results.items():
                    j = rid_of.pop(rid)
                    self.latency[j] = t_done - due[j]
                    self.answers[j] = out
                for w in self._new_waves():
                    self.waves["waves"] += 1
                    self.waves["rows"] += int(w["n_rows"])
                    self.waves["bucket_rows"] += int(w["bucket"])
            elif i < k:
                wait = due[i] - (time.perf_counter() - t0)
                if wait > 0:
                    with cell.annotate("idle"):
                        time.sleep(wait)
            else:
                break
        self.elapsed = t_done
        self.attempted = k
        self.failed = int(np.isnan(self.latency).sum())
        late_ms = self.late * 1e3
        print(f"generator lateness: p50 {np.percentile(late_ms, 50):.3f} ms, "
              f"p99 {np.percentile(late_ms, 99):.3f} ms, max "
              f"{late_ms.max():.3f} ms over {k} requests", flush=True)
        lat = self.latency[~np.isnan(self.latency)] * 1e3
        if len(lat):
            # the tail, which the host machine's own stalls decide
            # (PERF.md), is printed here and not judged
            print("latency: " + ", ".join(
                f"p{q} {np.percentile(lat, q):.3f} ms" for q in (50, 90, 99))
                + f", max {lat.max():.3f} ms", flush=True)

    def release(self) -> None:
        self.forest, self.party_bad = common.neutral_forest(
            self.model.trees_, np.asarray(self.model.partition_.feat_gid))
        self.fed = self.model = self.server = self.queue = None

    # -------------------------------------------------------------- check
    def sample(self) -> np.ndarray:
        """Requests the reference answers again: the longest answered one,
        then others drawn from the seed until ``check_rows`` rows."""
        answered = np.nonzero(~np.isnan(self.latency))[0]
        if len(answered) == 0:
            return answered
        rng = np.random.default_rng([self.cell.seed, 6])
        order = rng.permutation(answered)
        longest = answered[self.sizes[answered].argmax()]
        order = np.concatenate([[longest], order[order != longest]])
        rows = np.cumsum(self.sizes[order])
        n = int(np.searchsorted(rows, int(self.cell.params["check_rows"])))
        return order[:n + 1]

    def _labels(self, ref, picked, rounding=None) -> list[np.ndarray]:
        xb = reference.apply_bins(
            np.concatenate([self.pool[self.offs[j]:self.offs[j]
                                      + self.sizes[j]] for j in picked]),
            ref.edges, rounding)
        labels = reference.walk_votes(self.forest, xb, ref.n_classes,
                                      ref.perm)
        return np.split(labels, np.cumsum(self.sizes[picked])[:-1])

    def _mismatch(self, picked, labels) -> int:
        bad = int(self.sizes[np.isnan(self.latency)].sum())
        for j, want in zip(picked, labels):
            got = self.answers[j]
            bad += int((np.asarray(got) != want).sum())
        return bad

    def check(self) -> dict:
        ref = common.ReferenceData(self.cell, self.x, self.y)
        order = reference.aligned_order(ref.ids, self.salt)
        picks = [(0, t) for t in range(len(self.forest))]
        worst = common.judge_many(ref, picks, [self.forest], [order])
        worst["bad_nodes"] += self.party_bad
        picked = self.sample()
        worst["served_mismatch"] = self._mismatch(picked,
                                                  self._labels(ref, picked))
        return worst

    def control(self) -> dict:
        """Mismatched rows when the request rows are binned in bfloat16,
        against the float64 reference's labels."""
        ref = common.ReferenceData(self.cell, self.x, self.y)
        picked = self.sample()
        want = self._labels(ref, picked)
        got = self._labels(ref, picked, reference.to_bf16)
        return {"served_mismatch": sum(int((a != b).sum())
                                       for a, b in zip(got, want))}

    # ------------------------------------------------------------ numbers
    def end_to_end(self) -> dict:
        lat = self.latency[~np.isnan(self.latency)] * 1e3
        rows = int(self.sizes[~np.isnan(self.latency)].sum())
        return {"serve_p50_ms": float(np.percentile(lat, 50)),
                "serve_rows_per_s": rows / self.elapsed}

    def counters(self) -> dict:
        return {"serve": dict(self.waves, requests=self.attempted)}
