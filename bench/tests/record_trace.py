"""Record the small trace that ``test_trace_reduce.py`` reads (on a TPU).

    python3 bench/tests/record_trace.py bench/tests/data

A 2-party fit of 2 trees of depth 3 over 4,096 rows through the Pallas
histogram (one kernel call per tree and split level: 6), then 4 waves of
one-round prediction, all inside a ``bench.window`` span with ``bench.fit``
and ``bench.serve`` spans and a 50 ms host-only sleep (``bench.sleep``)
between them.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

import data  # noqa: E402
import trace_reduce  # noqa: E402

TREES, DEPTH, ROWS, WAVES = 2, 3, 4096, 4


def main(out_dir: str) -> None:
    import jax
    from repro.core import ForestParams
    from repro.federation import Federation
    from repro.serving import RequestQueue, ServeConfig
    x, y = data.make_classification(ROWS + 512, 16, 2, n_informative=8,
                                    class_sep=0.5, seed=5)
    fed = Federation(parties=2, n_bins=32)
    fed.ingest(x[:ROWS], y[:ROWS])
    params = ForestParams(n_estimators=TREES, max_depth=DEPTH, n_bins=32,
                          max_features=1.0)
    jax.block_until_ready(fed.fit(params).trees_)       # compile outside
    model = fed.fit(params)
    server = fed.serve(model, ServeConfig(buckets=(128,))).warmup()
    queue = RequestQueue(server)
    queue.submit(x[ROWS:ROWS + 100])
    queue.drain()
    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.fit"):
            jax.block_until_ready(fed.fit(params).trees_)
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.05)
        with jax.profiler.TraceAnnotation("bench.serve"):
            for i in range(WAVES):
                queue.submit(x[ROWS + 100 * i:ROWS + 100 * (i + 1)])
                queue.drain()
    jax.profiler.stop_trace()
    src = trace_reduce.find_trace(log_dir)
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    shutil.copy(src, Path(out_dir) / "small.xplane.pb")
    shutil.rmtree(log_dir, ignore_errors=True)
    print(trace_reduce.describe(str(Path(out_dir) / "small.xplane.pb")))


if __name__ == "__main__":
    main(sys.argv[1])
