"""Find the knee of an open-loop serving cell: one set-up, one window per rate.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 8 \
        --rates 100,200,400,...

For each rate it prints the offered and completed rows per second, the
latency percentiles, and the median latency of the first and last fifth of
the requests: a backlog that grows over the window shows as a last fifth
far slower than the first.  The knee is the highest rate that keeps up with
no growing backlog; the cell's rate is fixed from it by hand.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    cell.seed, cell.clock = args.seed, harness.CompileClock()
    cell.devices = jax.devices()[:cell.chips]
    driver = harness.driver_for(cell)
    driver.setup()
    print(f"setup_s {time.perf_counter() - T_START:.3f}", flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(cell.traffic, rate_per_s=rate)
        driver.window(args.seconds)
        lat = driver.latency * 1e3
        done = ~np.isnan(lat)
        fifth = max(1, len(lat) // 5)
        e2e = driver.end_to_end()
        offered = float(driver.sizes.sum()) / args.seconds
        print(json.dumps({
            "rate_per_s": rate, "requests": int(len(lat)),
            "unanswered": int((~done).sum()),
            "offered_rows_per_s": offered,
            "completed_rows_per_s": e2e["serve_rows_per_s"],
            "elapsed_s": driver.elapsed,
            "p50_ms": e2e["serve_p50_ms"],
            "p99_ms": float(np.nanpercentile(lat, 99)),
            "first_fifth_p50_ms": float(np.nanmedian(lat[:fifth])),
            "last_fifth_p50_ms": float(np.nanmedian(lat[-fifth:])),
            "late_p99_ms": float(np.percentile(driver.late * 1e3, 99)),
            "waves": driver.waves}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
