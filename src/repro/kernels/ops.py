"""Jit'd public wrappers for the kernels, with a histogram backend registry.

``histogram`` dispatches through ``BACKENDS``, a name -> callable registry:
  * ``pallas``            — the TPU kernel, always compiled (a host with no
                            TPU refuses it rather than falling back);
  * ``pallas_interpret``  — the same kernel through the Pallas interpreter
                            (correctness path on any host);
  * ``scatter``           — index-add formulation, fastest on CPU/GPU hosts
                            (used by the single-host simulation path of the
                            federated protocol);
  * ``segment_sum``       — GPU-oriented ``jax.ops.segment_sum`` over flat
                            (node, feature, bin) ids; correctness-equivalent
                            to ``scatter`` on every host;
  * ``ref``               — the einsum oracle.
  * ``auto``              — resolves per host: compiled Pallas on TPU,
                            segment_sum on GPU, scatter on CPU.
All agree to float32 tolerance (tests/test_kernels.py sweeps them).  New
backends register with :func:`register_backend` and become selectable through
``ForestParams.hist_impl`` without touching the builder.
"""
from __future__ import annotations

import functools
from typing import Callable, Protocol

import jax
import jax.numpy as jnp

from repro.kernels import histogram as _hist_kernel
from repro.kernels import ref as _ref


class HistogramFn(Protocol):
    def __call__(self, xb: jnp.ndarray, seg: jnp.ndarray, stats: jnp.ndarray,
                 n_level: int, n_bins: int) -> jnp.ndarray: ...


BACKENDS: dict[str, HistogramFn] = {}


def register_backend(name: str) -> Callable[[HistogramFn], HistogramFn]:
    """Register a histogram implementation under ``name``.

    Implementations take ``(xb, seg, stats, n_level, n_bins)`` and return the
    ``(n_level, F, n_bins, C)`` float32 split-statistics tensor; samples with
    ``seg < 0`` must contribute nothing.
    """
    def deco(fn: HistogramFn) -> HistogramFn:
        BACKENDS[name] = fn
        return fn
    return deco


def detected_platform() -> str:
    """The accelerator platform ``auto`` resolves against — a seam so tests
    can cover cpu/gpu/tpu resolution without the hardware (monkeypatch this,
    not jax.default_backend)."""
    return jax.default_backend()


def resolve_backend(impl: str) -> str:
    """Map ``"auto"`` onto a concrete registry key for this host: compiled
    Pallas on TPU, ``segment_sum`` on GPU (XLA's tuned unsorted-segment
    reduction beats the generic scatter-add lowering there), ``scatter``
    on CPU."""
    if impl != "auto":
        if impl not in BACKENDS:
            raise ValueError(
                f"unknown impl {impl!r} (have {sorted(BACKENDS)})")
        return impl
    platform = detected_platform()
    if platform == "tpu":
        return "pallas"
    if platform in ("gpu", "cuda", "rocm"):
        return "segment_sum"
    return "scatter"


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(BACKENDS))


def _flat_buckets(xb, seg, stats, n_level: int, n_bins: int):
    """Shared flattening of the scatter-family backends.

    Returns ``(flat, vals)``: a flat (node, feature, bin) bucket id per
    (sample, feature) — invalid samples (seg < 0) route to the single
    overflow slot ``n_level * F * n_bins`` — and the matching f32 stat rows.
    Any backend that reduces by bucket id must use this exact layout so the
    seg<0 convention stays in one place."""
    n, f = xb.shape
    c = stats.shape[-1]
    xb = xb.astype(jnp.int32)
    base = seg[:, None] * (f * n_bins) + jnp.arange(f)[None, :] * n_bins + xb
    flat = jnp.where(seg[:, None] >= 0, base, n_level * f * n_bins)
    vals = jnp.broadcast_to(stats[:, None, :], (n, f, c)).astype(jnp.float32)
    return flat.reshape(-1), vals.reshape(-1, c)


@register_backend("scatter")
def _histogram_scatter(xb, seg, stats, n_level: int, n_bins: int):
    f, c = xb.shape[1], stats.shape[-1]
    flat, vals = _flat_buckets(xb, seg, stats, n_level, n_bins)
    out = jnp.zeros((n_level * f * n_bins + 1, c), jnp.float32)
    out = out.at[flat].add(vals)
    return out[:-1].reshape(n_level, f, n_bins, c)


@register_backend("segment_sum")
def _histogram_segment_sum(xb, seg, stats, n_level: int, n_bins: int):
    """GPU-oriented formulation: one ``jax.ops.segment_sum`` over the same
    flat bucket ids as the scatter backend.

    On GPU, XLA lowers segment_sum to its tuned unsorted-segment-reduction
    path (atomics over f32), which beats the generic scatter-add lowering at
    large N x F; on CPU it lowers to the same scatter loop, so it is a
    correctness-equivalent drop-in everywhere (tests sweep it against the
    scatter backend)."""
    f, c = xb.shape[1], stats.shape[-1]
    flat, vals = _flat_buckets(xb, seg, stats, n_level, n_bins)
    out = jax.ops.segment_sum(vals, flat,
                              num_segments=n_level * f * n_bins + 1)
    return out[:-1].reshape(n_level, f, n_bins, c)


@register_backend("pallas")
def _histogram_pallas(xb, seg, stats, n_level: int, n_bins: int):
    return _hist_kernel.histogram_pallas(xb, seg, stats, n_level, n_bins,
                                         interpret=False)


@register_backend("pallas_interpret")
def _histogram_pallas_interpret(xb, seg, stats, n_level: int, n_bins: int):
    return _hist_kernel.histogram_pallas(xb, seg, stats, n_level, n_bins,
                                         interpret=True)


@register_backend("ref")
def _histogram_ref(xb, seg, stats, n_level: int, n_bins: int):
    return _ref.histogram_ref(xb, seg, stats, n_level, n_bins)


@functools.partial(jax.jit, static_argnames=("n_level", "n_bins", "fn"))
def _histogram_call(xb, seg, stats, n_level: int, n_bins: int, fn: HistogramFn):
    return fn(xb, seg, stats, n_level, n_bins)


def histogram(xb: jnp.ndarray, seg: jnp.ndarray, stats: jnp.ndarray,
              n_level: int, n_bins: int, impl: str = "auto") -> jnp.ndarray:
    """Split-statistics histogram: (n_level, F, n_bins, C) float32.

    The registry lookup happens OUTSIDE the jit boundary (the resolved
    callable is the static cache key), so re-registering a backend under an
    existing name takes effect immediately instead of being shadowed by
    cached traces of the old callable.
    """
    fn = BACKENDS[resolve_backend(impl)]
    return _histogram_call(xb, seg, stats, n_level=n_level, n_bins=n_bins,
                           fn=fn)
