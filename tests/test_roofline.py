"""Validate the trip-count-aware HLO analyzer against hand-computable cases."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import hlo_analysis, roofline


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_single_matmul_flops():
    n = 256
    c = _compile(lambda a, b: a @ b,
                 jax.ShapeDtypeStruct((n, n), jnp.float32),
                 jax.ShapeDtypeStruct((n, n), jnp.float32))
    t = hlo_analysis.analyze_hlo(c.as_text())
    assert t.flops == pytest.approx(2 * n**3, rel=1e-6)


def test_scan_multiplies_trip_count():
    n, trips = 128, 12
    def fn(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ c, None), x, None, length=trips)
        return y
    c = _compile(fn, jax.ShapeDtypeStruct((n, n), jnp.float32))
    t = hlo_analysis.analyze_hlo(c.as_text())
    assert t.flops == pytest.approx(trips * 2 * n**3, rel=0.05)
    # and XLA's own number is the known-broken 1x body count
    ca = c.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    assert ca["flops"] == pytest.approx(2 * n**3, rel=1e-6)


def test_nested_scan():
    n, outer, inner = 64, 3, 5
    def fn(x):
        def inner_fn(c, _):
            return c @ c, None
        def outer_fn(c, _):
            y, _ = jax.lax.scan(inner_fn, c, None, length=inner)
            return y, None
        y, _ = jax.lax.scan(outer_fn, x, None, length=outer)
        return y
    c = _compile(fn, jax.ShapeDtypeStruct((n, n), jnp.float32))
    t = hlo_analysis.analyze_hlo(c.as_text())
    assert t.flops == pytest.approx(outer * inner * 2 * n**3, rel=0.05)


def test_collective_bytes_sharded_matmul():
    """Contracting-dim sharded matmul needs an all-reduce of the f32 result.
    Runs in a subprocess with a forced host device count (this process holds
    the single real CPU device)."""
    import os
    import subprocess
    import sys
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import compat, hlo_analysis
mesh = compat.make_mesh((4,), ("model",))
n = 128
a = jax.ShapeDtypeStruct((n, n), jnp.float32,
                         sharding=NamedSharding(mesh, P(None, "model")))
b = jax.ShapeDtypeStruct((n, n), jnp.float32,
                         sharding=NamedSharding(mesh, P("model", None)))
c = jax.jit(lambda x, y: x @ y,
            out_shardings=NamedSharding(mesh, P())).lower(a, b).compile()
t = hlo_analysis.analyze_hlo(c.as_text())
expected = n * n * 4
assert expected <= t.coll_bytes <= 3 * expected, t.coll_bytes
print("COLL_OK", t.coll_bytes)
"""
    env = dict(os.environ, PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert res.returncode == 0, res.stderr[-2000:]
    assert "COLL_OK" in res.stdout


def test_bytes_dominated_by_io():
    n = 512
    c = _compile(lambda a: a + 1.0, jax.ShapeDtypeStruct((n, n), jnp.float32))
    t = hlo_analysis.analyze_hlo(c.as_text())
    assert t.bytes == pytest.approx(2 * n * n * 4, rel=0.5)


def test_roofline_terms_and_bottleneck():
    r = roofline.Roofline(flops=197e12, hbm_bytes=819e9 / 2, coll_bytes=0,
                          coll_detail={}, per_device_memory=0)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(0.5)
    assert r.bottleneck == "compute"
