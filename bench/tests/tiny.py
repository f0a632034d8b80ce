"""Small versions of the benchmark's cells for CPU tests."""
import time

import harness

SIZES = {"n_rows": 4000}
FOREST = {"n_estimators": 3, "max_depth": 4}
# the four-party sharded fit, on four virtual CPU devices
SHARDED = "fit.yearmsd.4chip"


def cell(name: str, seed: int = 12345):
    import jax
    c = harness.load_cell(name)
    c.config = dict(c.config, **SIZES,
                    forest=dict(c.config["forest"], **FOREST))
    c.params = dict(c.params, check_trees=100, check_rows=3000)
    c.traffic = dict(c.traffic, rate_per_s=40.0, pool_rows=4096)
    c.hist_impl = "scatter"
    c.devices = jax.devices()[:c.chips]
    c.seed = seed
    return c


def run(c, seconds: float = 0.5, trace: bool = False) -> dict:
    return harness.run_cell(c, c.seed, seconds, trace,
                            t_start=time.perf_counter())
