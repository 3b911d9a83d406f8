"""Gradient/hessian histogram construction — the hottest op in GBDT training.

Reference counterparts: ``DenseBin::ConstructHistogram`` (``src/io/dense_bin.hpp:143``,
sequential CPU scan) and the CUDA shared-memory scatter-add kernels
(``src/treelearner/cuda/cuda_histogram_constructor.cu:31-66``).

TPU re-design: the TPU has no atomics and scatters serialize, so the histogram is
expressed as a **one-hot contraction** that XLA maps onto the MXU:

    hist[f, b, c] = sum_r  (bins[r, f] == b) * vals[r, c]      c in {grad, hess, count}

computed blockwise under ``lax.scan`` so the one-hot never materializes in HBM at
full size.  Leaf membership / bagging are folded into ``vals`` as multiplicative
masks, which keeps every shape static under ``jit``.  A ``segment_sum`` (scatter)
variant is kept for comparison/benchmarking on CPU backends.

Sharding: when ``bins``/``vals`` are sharded along rows, the contraction's reduce
axis spans the mesh and XLA inserts a ``psum`` of the partial histograms — this IS
the reference's histogram ReduceScatter (``data_parallel_tree_learner.cpp:284``),
derived automatically from shardings instead of hand-written collectives.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..telemetry.spans import kernel_rows


def pack_bins4(bins: jnp.ndarray) -> jnp.ndarray:
    """Pack a (N, F) bin matrix whose bins all fit 4 bits (max_num_bins <=
    16, NaN bin included) into (N, ceil(F/2)) uint8 — feature 2j in the low
    nibble, 2j+1 in the high nibble.  Reference ``DenseBin`` IS_4BIT arm
    (``src/io/dense_bin.hpp``) packs ROW pairs; packing FEATURE pairs here
    keeps row gathers contiguous, which is what the perm layout streams."""
    n, f = bins.shape
    if n == 0:
        # zero-row placeholder (streamed training): reshape(-1) cannot
        # infer a dimension from an empty array
        return jnp.zeros((0, (f + 1) // 2), jnp.uint8)
    b = bins.astype(jnp.uint8)
    if f % 2:
        b = jnp.pad(b, ((0, 0), (0, 1)))
    b = b.reshape(n, -1, 2)
    return b[:, :, 0] | (b[:, :, 1] << 4)


def unpack_bins4(packed: jnp.ndarray, num_features: int) -> jnp.ndarray:
    """Inverse of :func:`pack_bins4` (drops the phantom odd-F column)."""
    low = packed & jnp.uint8(15)
    high = (packed >> 4) & jnp.uint8(15)
    full = jnp.stack([low, high], axis=-1).reshape(packed.shape[0], -1)
    return full[:, :num_features]


def pack_values(
    grad: jnp.ndarray, hess: jnp.ndarray, mask: Optional[jnp.ndarray]
) -> jnp.ndarray:
    """Stack (grad, hess, ones) into the (N, 3) channel matrix, pre-masked."""
    ones = jnp.ones_like(grad)
    vals = jnp.stack([grad, hess, ones], axis=-1)
    if mask is not None:
        vals = vals * mask.astype(vals.dtype)[:, None]
    return vals


@functools.partial(jax.jit, static_argnames=("num_bins", "rows_block",
                                             "packed4", "features"))
def histogram_onehot(
    bins: jnp.ndarray,       # (N, F) integer bins — or (N, ceil(F/2)) packed
    vals: jnp.ndarray,       # (N, 3) f32 (grad, hess, 1) or int8 quantized
    *,
    num_bins: int,
    rows_block: int = 16384,
    packed4: bool = False,   # bins carry two 4-bit features per byte
    features: int = 0,       # real F when packed4
    init: Optional[jnp.ndarray] = None,  # seed accumulator (streaming:
                             # chunk k continues chunk k-1's scan carry, so
                             # the cross-chunk fold replays the one-call
                             # block order exactly — docs/STREAMING.md)
) -> jnp.ndarray:            # (F, num_bins, 3) f32 — or i32 for int8 vals
    n, cols = bins.shape
    f = features if packed4 else cols
    integer = jnp.issubdtype(vals.dtype, jnp.integer)
    pad = (-n) % rows_block
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
    nblocks = (n + pad) // rows_block
    bins_blk = bins.reshape(nblocks, rows_block, cols)
    vals_blk = vals.reshape(nblocks, rows_block, 3)
    iota = jnp.arange(num_bins, dtype=jnp.int32)
    acc_dtype = jnp.int32 if integer else vals.dtype

    def body(acc, blk):
        b, v = blk
        if packed4:
            # per-block nibble unpack fuses into the contraction's input
            # pipeline; the full-size (N, F) matrix never lands in HBM
            b = unpack_bins4(b, f)
        onehot = (b.astype(jnp.int32)[:, :, None] == iota[None, None, :])
        if integer:
            # Quantized path: s8 x s8 -> s32 (the MXU's integer contraction;
            # reference Int32HistogramSumReducer accumulation, bin.h:48-81).
            part = jnp.einsum("nfb,nc->fbc", onehot.astype(jnp.int8), v,
                              preferred_element_type=jnp.int32)
        else:
            part = jnp.einsum("nfb,nc->fbc", onehot.astype(v.dtype), v,
                              precision=jax.lax.Precision.HIGHEST)
        return acc + part, None

    acc0 = (jnp.zeros((f, num_bins, 3), dtype=acc_dtype)
            if init is None else init.astype(acc_dtype))
    hist, _ = jax.lax.scan(body, acc0, (bins_blk, vals_blk))
    return hist


@functools.partial(jax.jit, static_argnames=("num_bins", "packed4",
                                             "features"))
def histogram_segment(
    bins: jnp.ndarray, vals: jnp.ndarray, *, num_bins: int,
    packed4: bool = False, features: int = 0,
    init: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Scatter-add variant (useful on CPU; TPU scatters serialize)."""
    if packed4:
        bins = unpack_bins4(bins, features)
    n, f = bins.shape
    integer = jnp.issubdtype(vals.dtype, jnp.integer)
    acc_dtype = jnp.int32 if integer else vals.dtype
    flat_ids = bins.astype(jnp.int32) + jnp.arange(f, dtype=jnp.int32)[None, :] * num_bins
    hist = (jnp.zeros((f * num_bins, 3), dtype=acc_dtype)
            if init is None else init.astype(acc_dtype).reshape(-1, 3))
    hist = hist.at[flat_ids].add(vals.astype(acc_dtype)[:, None, :])
    return hist.reshape(f, num_bins, 3)


def resolve_impl(impl: str, platform: Optional[str] = None) -> str:
    """Resolve the ``auto`` histogram impl for a backend platform (the
    single source of truth — bench reporting uses it too)."""
    if impl != "auto":
        return impl
    platform = jax.default_backend() if platform is None else platform
    return "pallas" if platform == "tpu" else "segment"


def histogram_from_vals(
    bins: jnp.ndarray,
    vals: jnp.ndarray,
    *,
    num_bins: int,
    impl: str = "auto",
    rows_block: int = 16384,
    packed4: bool = False,
    features: int = 0,
    init: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Histogram from pre-packed (N, 3) channel values.

    ``init`` seeds the accumulator (streaming chunk accumulation,
    docs/STREAMING.md): for the scatter and blockwise-scan impls the
    seeded per-chunk calls replay the EXACT add sequence of the one-call
    full-N histogram (chunk k's first add continues chunk k-1's carry),
    which is what makes streamed fp32 histograms bitwise-equal to in-core
    ones; the pallas kernel reduces per-chunk then adds the seed (integer
    quantized histograms stay exact either way)."""
    impl = resolve_impl(impl)
    if impl == "pallas":
        from .pallas_common import interpret_mode
        from .pallas_histogram import histogram_flat, kernel_layout
        # Quantized histograms: s8 x s8 -> s32 on the MXU's double-rate
        # int8 path (reference Int32HistogramSumReducer, bin.h:48-81).
        dtype = ("int8" if jnp.issubdtype(vals.dtype, jnp.integer)
                 else "f32")
        # the launch's last scope segments: the feature columns and the
        # rows ONE kernel launch is handed — a histogram wider than the
        # layout's column tile is several launches under this one path
        f = features if packed4 else bins.shape[1]
        ftile = kernel_layout(f, num_bins, dtype, rows_block, packed4)[1]
        with kernel_rows(bins.shape[0], ftile, -(-f // ftile)):
            out = histogram_flat(bins, vals, num_bins=num_bins,
                                 rows_block=rows_block, dtype=dtype,
                                 packed4=packed4, features=features,
                                 interpret=interpret_mode())
        return out if init is None else init + out
    if impl == "onehot":
        return histogram_onehot(bins, vals, num_bins=num_bins,
                                rows_block=rows_block, packed4=packed4,
                                features=features, init=init)
    if impl == "segment":
        return histogram_segment(bins, vals, num_bins=num_bins,
                                 packed4=packed4, features=features,
                                 init=init)
    raise ValueError(f"unknown histogram impl: {impl}")


def build_histogram(
    bins: jnp.ndarray,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    *,
    num_bins: int,
    impl: str = "auto",
    rows_block: int = 16384,
) -> jnp.ndarray:
    """Histogram for the rows selected by ``mask`` (all rows when ``mask=None``)."""
    vals = pack_values(grad, hess, mask)
    return histogram_from_vals(bins, vals, num_bins=num_bins, impl=impl,
                               rows_block=rows_block)


def subtract_histogram(parent: jnp.ndarray, child: jnp.ndarray) -> jnp.ndarray:
    """Sibling histogram via subtraction (reference ``serial_tree_learner.cpp:369``,
    ``FeatureHistogram::Subtract``)."""
    return parent - child
