"""Readings that the correctness limits are set from, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--seconds 5] [--rehearse]

For every seed it sets the cell up, runs a short window at the cell's own
load and prints the checked numbers (``program``); for every control seed it
also prints the numbers of the control, the reference grown or binned in
bfloat16 and put in the program's place (``control``).  The last line
sums up: per number, the largest program
reading and the smallest control reading.  The benchmark's own runs never
run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}

    if not args.rehearse:
        enable_compile_cache()
    import jax
    if not args.rehearse:
        if jax.devices()[0].platform != "tpu":
            print("calibrate: needs a TPU", file=sys.stderr)
            return 2
    program: dict[str, list] = {}
    control: dict[str, list] = {}
    clock = harness.CompileClock()
    for seed in sorted(set(seeds) | controls):
        cell = harness.load_cell(args.workload)
        cell.clock, cell.seed = clock, seed
        cell.devices = jax.devices()[:cell.chips]
        if args.rehearse:
            cell.hist_impl = "pallas_interpret"
            cell.config = dict(cell.config,
                               n_rows=cell.config["n_rows"] // 100)
        driver = harness.driver_for(cell)
        if hasattr(driver, "warm_up"):          # programs compiled once
            driver.warm_up = not program and not control
        t0 = time.perf_counter()
        driver.setup()
        driver.window(args.seconds)
        driver.release()
        t1 = time.perf_counter()
        line = {"seed": seed, "attempted": driver.attempted,
                "run_s": round(t1 - t0, 3)}
        if seed in seeds:
            got = driver.check()
            line["program"] = got
            for k, v in got.items():
                program.setdefault(k, []).append(v)
        line["check_s"] = round(time.perf_counter() - t1, 3)
        if seed in controls:
            t2 = time.perf_counter()
            got = driver.control()
            line["control"] = got
            line["control_s"] = round(time.perf_counter() - t2, 3)
            for k, v in got.items():
                control.setdefault(k, []).append(v)
        print(json.dumps(line), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "program_max": {k: max(v) for k, v in program.items()},
        "control_min": {k: min(v) for k, v in control.items()},
        "program_seeds": len(seeds), "control_seeds": len(controls)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
