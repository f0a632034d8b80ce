"""Pallas TPU kernel: split-statistics histogram for Federated Forest.

The paper's hot loop is "for every (tree-node, feature, bin): accumulate label
statistics" — on CPU/GPU this is a scatter-add.  TPUs have no fast scatter, so
we reformulate the accumulation as dense one-hot contractions that run on the
MXU, with samples on the lane axis:

    Z_c[l, s]     = 1[seg[s] == l] * stats[c, s]             (VPU, cheap)
    hist[f, c]    = onehot_bins(x[f, :]) @ Z_c.T              (MXU matmul)

Tiling: grid over (features, sample chunks).  Each kernel invocation holds
one feature's (C, B, L) output block in VMEM and accumulates one sample chunk
into it; the sample-chunk grid axis revisits the same output block, so we
zero-init on the first chunk with ``pl.when``.

Layout (what Mosaic accepts on a v5e): every operand is transposed so the
sample axis is the lane (last) axis — ``xb`` as (F, 1, N) in its own narrow
dtype, ``seg`` as (1, N), ``stats`` as (C, N) — and every block's last two
dims are either full array dims or (1|C, CHUNK) with CHUNK a lane multiple.
``n_level`` and ``n_bins`` are padded to sublane multiples (8) so the one-hot
operands and the (B, L) output tiles need no sub-tile reshapes; padded rows
are all-zero one-hots and are sliced off.

VMEM per invocation (CHUNK=512, L<=256, B<=256, C<=8):
  node one-hot + Z_c  2 * 256*512*4   =  1 MiB
  bin one-hot         256*512*4       = 512 KiB
  out block           8*256*256*4     =  2 MiB (double-buffered: 4 MiB)
inside the 16 MiB default scoped VMEM of a v5e core.

Precision: the contraction is pinned to ``Precision.HIGHEST``.  The TPU's
default f32 matmul is one bf16 pass, which rounds regression statistics
(and class counts above 256); HIGHEST keeps counts exact and sums at f32
accuracy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 512     # samples per accumulation step (lane multiple)
_SUBLANE = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _hist_kernel(xb_ref, seg_ref, stats_ref, out_ref, *, n_level: int,
                 n_bins: int, n_chan: int):
    """One (feature, sample-chunk) grid step; ``n_level``/``n_bins`` padded."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seg = seg_ref[...]                                    # (1, S) int32
    stats = stats_ref[...]                                # (C, S) f32
    bins = xb_ref[...].astype(jnp.int32)                  # (1, S)
    s = seg.shape[-1]
    node1h = (jax.lax.broadcasted_iota(jnp.int32, (n_level, s), 0) == seg
              ).astype(jnp.float32)                       # (L, S)
    bin1h = (jax.lax.broadcasted_iota(jnp.int32, (n_bins, s), 0) == bins
             ).astype(jnp.float32)                        # (B, S)
    for c in range(n_chan):  # static unroll over the stat channels
        z = node1h * stats[c:c + 1, :]                    # (L, S)
        # (B, S) x (L, S)^T on the MXU, contracting the sample (lane) axis
        out_ref[c] += jax.lax.dot_general(
            bin1h, z, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)           # (B, L)


@functools.partial(jax.jit, static_argnames=("n_level", "n_bins", "interpret"))
def histogram_pallas(xb: jnp.ndarray, seg: jnp.ndarray, stats: jnp.ndarray,
                     n_level: int, n_bins: int, *,
                     interpret: bool = False) -> jnp.ndarray:
    """Pallas histogram. Returns (n_level, F, n_bins, C) float32.

    The sample count is padded to CHUNK (padded samples get seg = -1 and are
    dropped by the node one-hot).  ``interpret=False`` is the compiled TPU
    kernel; ``interpret=True`` runs the same kernel through the Pallas
    interpreter on any host (a correctness path, not a perf path).
    """
    n, f = xb.shape
    c = stats.shape[-1]
    lp, bp = _round_up(n_level, _SUBLANE), _round_up(n_bins, _SUBLANE)
    n_pad = -n % CHUNK
    np_ = n + n_pad
    xb_t = jnp.pad(xb, ((0, n_pad), (0, 0))).T.reshape(f, 1, np_)
    seg_t = jnp.pad(seg.astype(jnp.int32), (0, n_pad),
                    constant_values=-1).reshape(1, np_)
    stats_t = jnp.pad(stats.astype(jnp.float32), ((0, n_pad), (0, 0))).T

    out = pl.pallas_call(
        functools.partial(_hist_kernel, n_level=lp, n_bins=bp, n_chan=c),
        grid=(f, np_ // CHUNK),
        in_specs=[
            pl.BlockSpec((None, 1, CHUNK), lambda i, s: (i, 0, s)),  # xb
            pl.BlockSpec((1, CHUNK), lambda i, s: (0, s)),           # seg
            pl.BlockSpec((c, CHUNK), lambda i, s: (0, s)),           # stats
        ],
        out_specs=pl.BlockSpec((None, c, bp, lp), lambda i, s: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((f, c, bp, lp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xb_t, seg_t, stats_t)
    # (F, C, B, L) -> (L, F, B, C)
    return out[:, :, :n_bins, :n_level].transpose(3, 0, 2, 1)
