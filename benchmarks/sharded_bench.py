"""Multi-host sharded execution benchmark on a real (trees x parties) mesh.

The sharded substrate's scaling claim has so far been anchored by
lower/compile dry-runs only.  This benchmark EXECUTES the forest fit and the
one-round prediction through ``run_sharded``/shard_map on a real mesh of
forced host devices (``--xla_force_host_platform_device_count``, the same
idiom the federation tests use), times both, and asserts the sharded build
is bit-identical to the single-device vmap simulation — so the number it
reports is the real protocol, not a shape-polymorphic proxy.

The mesh is launched in a CPU-only subprocess (``JAX_PLATFORMS=cpu``) so
the forced device count cannot leak into the rest of the bench harness, and
the child never contends for an accelerator its parent holds.  On a single
physical core the forced devices time-slice, so wall-clock here anchors
correctness + overhead of the sharded path; on a multi-core host the same
harness measures host-device scaling (chips are measured by
``chip_smoke.py --four-chips``).  REPRO_BENCH_FAST=1 shrinks the mesh to (2, 2) and
the training set.
"""
from __future__ import annotations

import os
import subprocess
import sys

from benchmarks.common import emit

_SCRIPT = r"""
import os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
import numpy as np
import jax, jax.numpy as jnp
from repro import compat
from repro.core import ForestParams, fit_federated_forest
from repro.data import make_classification

trees_ax, parties = {trees_ax}, {parties}
n, f = {rows}, 12
p = ForestParams(n_estimators={trees}, max_depth=6, n_bins=16, seed=0)
x, y = make_classification(n, f, 2, seed=0)

from repro.federation import Federation
mesh = compat.make_mesh((trees_ax, parties), ("trees", "parties"))
fed = Federation(parties=parties, substrate="sharded", mesh=mesh,
                 hist_impl="scatter", n_bins=p.n_bins)
fed.ingest(x, y)

t0 = time.perf_counter()
model = fed.fit(p)
jax.block_until_ready(model.trees_)
fit_s = time.perf_counter() - t0

sim = fit_federated_forest(x, y, parties, p)
for a, b in zip(jax.tree.leaves(model.trees_), jax.tree.leaves(sim.trees_)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

xt = x[: {pred_rows}]
want = np.asarray(sim.predict(xt))
t0 = time.perf_counter()
got = np.asarray(fed.predict(model, xt))
pred_s = time.perf_counter() - t0
np.testing.assert_array_equal(got, want)
print(f"SHARDED fit_s={{fit_s:.3f}} pred_s={{pred_s:.4f}} "
      f"pred_rows_s={{len(xt) / max(pred_s, 1e-12):.0f}} "
      f"mesh={{trees_ax}}x{{parties}} devices={{trees_ax * parties}}")
"""


def run() -> list[dict]:
    fast = bool(os.environ.get("REPRO_BENCH_FAST"))
    trees_ax, parties = (2, 2) if fast else (2, 4)
    cfg = dict(devices=trees_ax * parties, trees_ax=trees_ax,
               parties=parties, trees=4, rows=600 if fast else 2000,
               pred_rows=256)
    # a forced-host-device mesh by design: the child stays on the CPU even
    # where the parent holds an accelerator
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", _SCRIPT.format(**cfg)],
                         env=env, capture_output=True, text=True,
                         timeout=1500, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    if res.returncode != 0:
        raise RuntimeError(f"sharded bench subprocess failed:\n"
                           f"{res.stderr[-3000:]}")
    line = next(l for l in res.stdout.splitlines()
                if l.startswith("SHARDED"))
    kv = dict(tok.split("=") for tok in line.split()[1:])
    emit(f"sharded/fit_{trees_ax}x{parties}", float(kv["fit_s"]),
         f"mesh={kv['mesh']}|devices={kv['devices']}|bit_identical=1")
    emit(f"sharded/predict_{trees_ax}x{parties}", float(kv["pred_s"]),
         f"rows_s={kv['pred_rows_s']}|mesh={kv['mesh']}")
    return [{"mesh": kv["mesh"], "fit_s": float(kv["fit_s"]),
             "pred_s": float(kv["pred_s"]),
             "pred_rows_s": float(kv["pred_rows_s"])}]


if __name__ == "__main__":
    run()
