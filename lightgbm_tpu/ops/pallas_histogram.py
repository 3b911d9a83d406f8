"""Pallas TPU histogram kernels — the framework's hottest op.

Reference counterpart: the CUDA shared-memory histogram kernels
(``src/treelearner/cuda/cuda_histogram_constructor.cu:31-66`` — per-block
shared-mem scatter-add + atomics).  TPUs have no atomics and scatters
serialize, so the kernel computes the histogram as a **matmul against a
flattened one-hot**, generated inside VMEM:

    out[c, f*B + b] = sum_n  vals[n, c] * (bins[n, f] == b)

Why this shape wins on the MXU:

- The one-hot (the big streamed operand) never touches HBM: it is built in
  VMEM from the (blk, ft) uint8 bin tile, so HBM traffic is just bins + vals.
- A whole feature CHUNK shares ONE dot per row-block (N = ft*B lanes),
  instead of per-feature M=8 matmuls — fewer, larger matmuls with identical
  streamed volume.  The grid iterates row-blocks only; very wide datasets
  are chunked at trace time into separate same-shaped calls so the VMEM
  one-hot stays bounded (and every BlockSpec dim is Mosaic-legal: the
  feature dim always equals the array dim, row blocks are 128-multiples).
- The kernel is NOT HBM-bandwidth-bound: on a v5e it runs at 21 M rows/s,
  47 ns a row at 28 features x 255 bins f32 (PERF_LEDGER.jsonl, PR 25;
  0.013 % of its byte roofline); what binds instead is not measured (the
  candidates: the one-hot build on the VPU, the six-pass f32 matmul at
  M = 4).  Its time is the rows it is handed, so the streamed volume
  stays proportional to the rows actually histogrammed: the reference's
  smaller-sibling trick (``serial_tree_learner.cpp:369``), one call per
  smaller sibling at its own bucket, or the fused wave's one ragged launch.
- int8 variant: s8 vals x s8 one-hot -> s32 accumulation — the reference's
  quantized-training histograms (``Int32HistogramSumReducer``, ``bin.h:48``)
  on the MXU's double-rate int8 path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared kernel scaffolding (ops/pallas_common.py): the fused wave kernel
# reuses the SAME compile parameters / dtype table / one-hot contraction,
# so the two kernels cannot drift apart.
from .pallas_common import (C_PAD, DTYPES as _DTYPES, compiler_params,
                            onehot_contract)


def _pick_tiles(f: int, num_bins: int, itemsize: int, rows_block: int,
                acc_size: int = 4):
    """(rows_block, features_per_chunk) bounding the kernel's VMEM working
    set (the in-VMEM one-hot PLUS the (C_PAD, ft*B) accumulator block).
    ``num_bins`` here is the LANE-PADDED bin count (multiple of 128).

    Mosaic requires each BlockSpec's last dim to be a multiple of 128 or
    equal to the full array dim, so the kernel never tiles features inside
    one ``pallas_call``: the bins block spans the WHOLE (chunk) feature
    width, and wide datasets are chunked at trace time into separate
    same-shaped calls.  Row blocks stay multiples of 128 (the sublane-
    aligned choice for every dtype used here).

    The 2x on the one-hot bytes models Mosaic's observed scoped-stack peak
    (the (blk, ft, B) compare plus its (blk, ft*B) reshape copy coexist)."""
    budget = 16 * 1024 * 1024

    def bytes_for(blk, ft):
        return ft * num_bins * (blk * 2 * itemsize + C_PAD * acc_size)

    # rows_block > 4096 means "tuned for the XLA einsum path" — auto-pick.
    # Powers of two >= 128 keep every halving on the 128-multiple lattice
    # Mosaic requires for the valsT block's last dim.
    if rows_block <= 0 or rows_block > 4096:
        blk = 1024
    else:
        blk = max(128, 1 << (int(rows_block).bit_length() - 1))
    while blk > 128 and bytes_for(blk, f) > budget:
        blk //= 2
    if bytes_for(blk, f) <= budget:
        return blk, f
    # Very wide data: fix the minimum row block and chunk the features.
    ft = max(1, budget // (num_bins * (blk * 2 * itemsize
                                       + C_PAD * acc_size)))
    return blk, ft


def kernel_layout(f: int, num_bins: int, dtype: str, rows_block: int = 0,
                  packed4: bool = False):
    """(rows_block, ftile, cols_tile, b_pad) for one ``histogram_flat``
    config.  Every Mosaic legality constraint lives here so it is testable
    without hardware: the bin axis is padded to a 128-multiple (bin ids are
    < num_bins, so phantom bins stay exactly zero), which keeps the
    kernel's one-hot flatten — and, under packed4, each nibble plane's
    contiguous output half — lane-aligned."""
    isz = _DTYPES[dtype][2]
    b_pad = -(-num_bins // 128) * 128
    rows_block, ftile = _pick_tiles(f, b_pad, isz, rows_block)
    if packed4 and ftile % 2:
        ftile += 1           # chunk boundaries must not split nibble pairs
    cols_tile = ftile // 2 if packed4 else ftile
    return rows_block, ftile, cols_tile, b_pad


def _prep(bins, vals, rows_block, ftile):
    """Pad rows to the block size, features to a multiple of the chunk
    width, channels to C_PAD; returns (bins, valsT, nblocks, nchunks).

    Phantom feature columns are filled with bin 0; their histogram blocks
    are sliced off by the caller, so the garbage never escapes.
    """
    n, f = bins.shape
    pad = (-n) % rows_block
    fpad = (-f) % ftile
    if pad or fpad:
        bins = jnp.pad(bins, ((0, pad), (0, fpad)))
    if pad:
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
    c = vals.shape[1]
    valsT = jnp.pad(vals, ((0, 0), (0, C_PAD - c))).T  # (C_PAD, ntot)
    ntot = n + pad
    return bins, valsT, ntot // rows_block, (f + fpad) // ftile


def _flat_kernel(bins_ref, valsT_ref, out_ref, *, num_bins, ftile,
                 oh_dtype, acc_dtype, precision, packed4=False):
    """``num_bins`` is the lane-padded bin count (multiple of 128): Mosaic
    only supports the (blk, ft, B) -> (blk, ft*B) one-hot flatten when the
    merged minor dim stays 128-aligned.  Real bin ids never reach the
    phantom bins, so their histogram lanes are exact zeros and the caller
    slices them off."""
    rb = pl.program_id(0)  # row-block index

    @pl.when(rb == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    bins_blk = bins_ref[:].astype(jnp.int32)            # (blk, ct)
    valsT = valsT_ref[:]                                # (C_PAD, blk)
    if oh_dtype != valsT.dtype:
        valsT = valsT.astype(oh_dtype)

    def contract(b2d):
        return onehot_contract(b2d, valsT, num_bins=num_bins,
                               oh_dtype=oh_dtype, acc_dtype=acc_dtype,
                               precision=precision)

    if packed4:
        # 4-bit mode: the streamed tile carries two features per byte
        # (reference DenseBin IS_4BIT, dense_bin.hpp); the nibble unpack
        # happens HERE in VMEM so HBM streams half the bin bytes.  The two
        # nibble planes are contracted separately into contiguous output
        # halves (a vector interleave of the planes is not a Mosaic-legal
        # shape cast); the caller un-permutes the feature order.
        half = (ftile // 2) * num_bins
        out_ref[:, :half] += contract(bins_blk & 15)
        out_ref[:, half:] += contract((bins_blk >> 4) & 15)
    else:
        out_ref[:, :] += contract(bins_blk)


@functools.partial(
    jax.jit, static_argnames=("num_bins", "rows_block", "dtype", "interpret",
                              "packed4", "features"))
def histogram_flat(
    bins: jnp.ndarray,   # (N, F) uint8/uint16 — or (N, ceil(F/2)) packed
    vals: jnp.ndarray,   # (N, 3) f32 masked (grad, hess, count) — or int8
    *,
    num_bins: int,
    rows_block: int = 0,
    dtype: str = "f32",  # one-hot/compute dtype: f32 | bf16 | int8
    interpret: bool = False,
    packed4: bool = False,   # two 4-bit features per streamed byte
    features: int = 0,       # real F when packed4
) -> jnp.ndarray:        # (F, num_bins, 3) f32 (int32 for int8)
    """Single-leaf flat-matmul histogram."""
    n, fcols = bins.shape
    f = features if packed4 else fcols
    oh_dtype, acc_dtype, isz = _DTYPES[dtype]
    # f32 must accumulate exactly (reference hists are exact f32 sums);
    # DEFAULT would run the MXU at bf16 and perturb every histogram entry.
    precision = (jax.lax.Precision.HIGHEST if dtype == "f32"
                 else jax.lax.Precision.DEFAULT)
    rows_block, ftile, cols_tile, b_pad = kernel_layout(
        f, num_bins, dtype, rows_block, packed4)
    bins, valsT, nblocks, nchunks = _prep(bins, vals, rows_block, cols_tile)
    call = pl.pallas_call(
        functools.partial(_flat_kernel, num_bins=b_pad, ftile=ftile,
                          oh_dtype=oh_dtype, acc_dtype=acc_dtype,
                          precision=precision, packed4=packed4),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((rows_block, cols_tile), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C_PAD, rows_block), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((C_PAD, ftile * b_pad),
                               lambda i: (0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((C_PAD, ftile * b_pad), acc_dtype),
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
    )
    chunks = [call(jax.lax.slice_in_dim(bins, c * cols_tile,
                                        (c + 1) * cols_tile, axis=1), valsT)
              for c in range(nchunks)]
    out = chunks[0] if nchunks == 1 else jnp.concatenate(chunks, axis=1)
    out = out.reshape(C_PAD, nchunks * ftile, b_pad)[:3, :, :num_bins]
    if packed4:
        # Each chunk emits its low-nibble features then its high-nibble
        # features; un-permute back to the interleaved pack_bins4 order
        # (feature 2j in packed column j's low nibble, 2j+1 high).
        order = np.concatenate(
            [np.concatenate([2 * cols, 2 * cols + 1])
             for cols in np.split(np.arange(nchunks * cols_tile), nchunks)])
        out = jnp.take(out, jnp.asarray(np.argsort(order)[:f]), axis=1)
    else:
        out = out[:, :f]     # drop phantom feature blocks
    return jnp.transpose(out, (1, 2, 0))


def histogram_pallas(
    bins: jnp.ndarray,
    vals: jnp.ndarray,
    *,
    num_bins: int,
    rows_block: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Backwards-compatible name for the f32 flat-matmul kernel.  A plain
    alias (no decorator): ``histogram_flat`` is already jitted, and the old
    ``jax.jit``-of-``jax.jit`` wrapper only added a second trace level."""
    return histogram_flat(bins, vals, num_bins=num_bins,
                          rows_block=rows_block, dtype="f32",
                          interpret=interpret)
