"""Per-kernel allclose sweeps: Pallas (interpret=True on CPU) vs pure-jnp
oracle, across shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.histogram import histogram_pallas
from repro.kernels.ref import flash_attention_ref, histogram_ref


@pytest.mark.parametrize("n,f,b,l,c", [
    (64, 3, 8, 1, 2),        # tiny
    (300, 11, 16, 6, 3),     # ragged (pad both axes)
    (512, 8, 32, 12, 2),     # exact tile boundaries
    (1030, 17, 64, 32, 5),   # multi-chunk, multi-tile
])
def test_histogram_pallas_matches_ref(n, f, b, l, c):
    rng = np.random.default_rng(n + f)
    xb = jnp.asarray(rng.integers(0, b, (n, f)), jnp.int32)
    seg = jnp.asarray(rng.integers(-1, l, (n,)), jnp.int32)
    stats = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    want = histogram_ref(xb, seg, stats, l, b)
    got = histogram_pallas(xb, seg, stats, l, b, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("platform,want", [
    ("cpu", "scatter"),          # generic host: scatter-add lowering
    ("gpu", "segment_sum"),      # tuned unsorted-segment reduction
    ("cuda", "segment_sum"),
    ("rocm", "segment_sum"),
    ("tpu", "pallas"),           # compiled Pallas kernel
])
def test_auto_backend_resolution_per_platform(monkeypatch, platform, want):
    """hist_impl="auto" resolves per detected platform — covered without the
    hardware by monkeypatching the detection seam."""
    monkeypatch.setattr(ops, "detected_platform", lambda: platform)
    assert ops.resolve_backend("auto") == want
    assert want in ops.available_backends()


def test_resolve_backend_passthrough_and_unknown():
    assert ops.resolve_backend("scatter") == "scatter"
    with pytest.raises(ValueError, match="unknown impl"):
        ops.resolve_backend("warp-histogram")


@pytest.mark.parametrize("impl", [
    "scatter",
    # the compiled kernel needs a TPU; the CPU sweep runs the same kernel
    # through the interpreter (test_tpu_compile.py compiles it for a v5e)
    pytest.param("pallas_interpret", id="pallas"),
    "ref", "segment_sum"])
def test_histogram_impl_agreement(impl):
    rng = np.random.default_rng(0)
    xb = jnp.asarray(rng.integers(0, 16, (257, 9)), jnp.int32)
    seg = jnp.asarray(rng.integers(-1, 4, (257,)), jnp.int32)
    stats = jnp.asarray(rng.normal(size=(257, 3)), jnp.float32)
    want = histogram_ref(xb, seg, stats, 4, 16)
    got = ops.histogram(xb, seg, stats, 4, 16, impl)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,f,b,l,c", [(64, 3, 8, 1, 2), (300, 11, 16, 6, 3),
                                       (1030, 17, 64, 32, 5)])
def test_segment_sum_matches_scatter(n, f, b, l, c):
    """The GPU segment-sum backend is a drop-in for scatter (CPU sweep);
    both accumulate identical flat bucket ids, so agreement is exact up to
    f32 reduction order."""
    rng = np.random.default_rng(n + f)
    xb = jnp.asarray(rng.integers(0, b, (n, f)), jnp.int32)
    seg = jnp.asarray(rng.integers(-1, l, (n,)), jnp.int32)
    stats = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    want = ops.histogram(xb, seg, stats, l, b, "scatter")
    got = ops.histogram(xb, seg, stats, l, b, "segment_sum")
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_histogram_stats_dtype_bf16_inputs():
    """bf16 stats are accumulated in f32 (preferred_element_type)."""
    rng = np.random.default_rng(1)
    xb = jnp.asarray(rng.integers(0, 8, (128, 4)), jnp.int32)
    seg = jnp.asarray(rng.integers(0, 2, (128,)), jnp.int32)
    stats = jnp.asarray(rng.normal(size=(128, 2)), jnp.bfloat16)
    want = histogram_ref(xb, seg, stats.astype(jnp.float32), 2, 8)
    got = histogram_pallas(xb, seg, stats.astype(jnp.float32), 2, 8,
                           interpret=True)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


def test_histogram_weighted_totals():
    """Column sums of the histogram reproduce global weighted stats."""
    rng = np.random.default_rng(2)
    n = 400
    xb = jnp.asarray(rng.integers(0, 8, (n, 5)), jnp.int32)
    seg = jnp.zeros((n,), jnp.int32)
    stats = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    h = histogram_pallas(xb, seg, stats, 1, 8, interpret=True)
    np.testing.assert_allclose(h.sum((0, 2)),
                               jnp.broadcast_to(stats.sum(0), (5, 2)),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- attention
def _flash(q, k, v, **kw):
    from repro.kernels.flash_attention import flash_attention
    return flash_attention(q, k, v, interpret=True, **kw)


@pytest.mark.parametrize("sq,sk,d", [(128, 128, 64), (256, 256, 64), (128, 384, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(sq, sk, d, causal):
    rng = np.random.default_rng(sq + d)
    q = jnp.asarray(rng.normal(size=(1, 2, sq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, sk, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, sk, d)), jnp.float32)
    want = flash_attention_ref(q, k, v, causal=causal)
    got = _flash(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_window_and_dtype(dtype):
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 2, 256, 64)), dtype)
    k = jnp.asarray(rng.normal(size=(2, 2, 256, 64)), dtype)
    v = jnp.asarray(rng.normal(size=(2, 2, 256, 64)), dtype)
    want = flash_attention_ref(q, k, v, causal=True, window=128)
    got = _flash(q, k, v, causal=True, window=128)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=tol, atol=tol)
