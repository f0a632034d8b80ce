"""Kernel micro-benchmarks: histogram impls (the FF hot spot) + attention.

The Pallas histogram runs here through ``pallas_interpret`` (orders of
magnitude slower than compiled — the number reported here is a correctness
path, not a TPU projection).  The scatter impl is the CPU production path;
the table is mainly here so regressions in the hot loop show up.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.kernels import ops


def run() -> list[dict]:
    rng = np.random.default_rng(0)
    n, f, b, l, c = 4096, 64, 32, 16, 2
    xb = jnp.asarray(rng.integers(0, b, (n, f)), jnp.int32)
    seg = jnp.asarray(rng.integers(0, l, (n,)), jnp.int32)
    stats = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    rows = []
    for impl in ("scatter", "ref"):
        t = timeit(lambda: ops.histogram(xb, seg, stats, l, b, impl)
                   .block_until_ready())
        gups = n * f / t / 1e9
        rows.append({"impl": impl, "seconds": t})
        emit(f"kernel/histogram_{impl}", t, f"updates_per_s={gups:.2f}G")
    # pallas interpret mode on a reduced shape (correctness path, not a TPU
    # projection — interpret executes the kernel body in Python)
    xs, ss, st = xb[:512, :8], seg[:512], stats[:512]
    t = timeit(lambda: ops.histogram(xs, ss, st, l, b, "pallas_interpret")
               .block_until_ready(), repeat=1)
    rows.append({"impl": "pallas_interpret", "seconds": t})
    emit("kernel/histogram_pallas_interpret", t, "reduced_shape=512x8")
    return rows


if __name__ == "__main__":
    run()
