"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (data from the seed, a warm-up of every shape the cell uses) is
timed as ``setup_s``; the cell then measures for ``--seconds``, checks what
the measured window produced against the plain reference in
``bench/reference.py``, and prints one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics from a profiler trace of the window), ``device``,
``breakdown`` (traced runs) and ``checks``, each compared number with its
limit.  The same numbers end standard error.

It needs a TPU with at least the cell's chip count and exits non-zero
without a result line where JAX finds none.  ``--rehearse`` runs the cell
on any host at 1/100 of its rows with the Pallas kernel interpreted; it
prints only whether the checks passed, never a metric.
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

REHEARSAL_SCALE = 100


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="1/100 of the rows on any host, interpreted "
                         "kernel; prints no metric")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb into this "
                         "directory")
    args = ap.parse_args()

    cell = harness.load_cell(args.workload)
    if not args.rehearse:
        enable_compile_cache()
    import jax
    devices = jax.devices()
    if args.rehearse:
        cell.hist_impl = "pallas_interpret"
        cell.config = dict(cell.config,
                           n_rows=cell.config["n_rows"] // REHEARSAL_SCALE)
    else:
        if devices[0].platform != "tpu":
            print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
                  file=sys.stderr)
            return 2
        if len(devices) < cell.chips:
            print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
                  f"{len(devices)}", file=sys.stderr)
            return 2
        peaks = harness.load_json(BENCH / "peaks.json")
        kind = devices[0].device_kind
        if kind not in peaks:
            print(f"bench: no peaks for device kind {kind!r} in "
                  f"bench/peaks.json", file=sys.stderr)
            return 2
        cell.peak = peaks[kind]
    cell.devices = devices[:cell.chips]
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace) and not args.rehearse,
                              t_start=T_START, keep_trace=args.keep_trace)
    if args.rehearse:
        print(json.dumps({"rehearsal": cell.name,
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "failed": result["failed"],
                          "checks": result["checks"]}))
        return 0 if result["correct"] else 1
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
