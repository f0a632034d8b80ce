"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Control cost with
REPRO_BENCH_ROUNDS (paper uses 40 rounds for Table 1's Z-tests; default 8)
and REPRO_BENCH_FAST=1 (skips the slower Table 1 datasets).

``--json-out FILE`` additionally writes the full run as one JSON document
(per-row records with parsed derived fields, plus environment knobs and
total wall time) so CI can archive comparable summaries per commit.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json-out", default=None, metavar="FILE",
                    help="also dump all bench records as a JSON summary")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    t0 = time.time()
    from benchmarks import (binning_ablation, comm_complexity, common,
                            fig3_domains, fig456_prediction, frontier_bench,
                            ingest_bench, kernel_bench, serving_bench,
                            sharded_bench, table1_parity)

    if os.environ.get("REPRO_BENCH_FAST"):
        table1_parity.BENCH_SETS = ["ionosphere", "spambase", "waveform",
                                    "superconduct"]
    table1_parity.run()
    fig3_domains.run()
    fig456_prediction.run()
    comm_complexity.run()
    binning_ablation.run()
    ingest_bench.run()
    kernel_bench.run()
    frontier_bench.run()
    # async/autotune and fleet sections run in CI's dedicated `--mode async`
    # / `--mode fleet` steps (and locally via `python -m
    # benchmarks.serving_bench --mode async|fleet`)
    serving_bench.run("sync")
    # real (trees x parties) mesh execution in a forced-device subprocess
    sharded_bench.run()
    wall = time.time() - t0
    print(f"# total_bench_wall_s={wall:.1f}", file=sys.stderr)

    if args.json_out:
        summary = {
            "records": common.RECORDS,
            "total_wall_s": round(wall, 1),
            "env": {k: os.environ[k] for k in
                    ("REPRO_BENCH_FAST", "REPRO_BENCH_ROUNDS",
                     "JAX_PLATFORMS") if k in os.environ},
        }
        with open(args.json_out, "w") as f:
            json.dump(summary, f, indent=1)
        print(f"# json summary: {args.json_out} "
              f"({len(common.RECORDS)} records)", file=sys.stderr)


if __name__ == "__main__":
    main()
