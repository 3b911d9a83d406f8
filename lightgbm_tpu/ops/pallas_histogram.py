"""Pallas TPU histogram kernels — the framework's hottest op.

Reference counterpart: the CUDA shared-memory histogram kernels
(``src/treelearner/cuda/cuda_histogram_constructor.cu:31-66`` — per-block
shared-mem scatter-add + atomics).  TPUs have no atomics and scatters
serialize, so the kernel computes the histogram as a **matmul against a
flattened one-hot**, generated inside the kernel:

    out[c, f*B + b] = sum_n  vals[n, c] * (bins[n, f] == b)

Why this shape wins on the MXU:

- The one-hot (the big operand) never touches HBM — nor VMEM: it is built
  from the (blk, ft) uint8 bin tile one vector register at a time and goes
  straight into the MXU's weight latch (``pallas_common.onehot_contract``),
  so HBM traffic is just bins + vals and Mosaic's scoped VMEM is the
  kernel's blocks.
- A whole feature CHUNK shares ONE dot per row-block (N = ft*B lanes),
  instead of per-feature M=8 matmuls — fewer, larger matmuls with identical
  streamed volume.  The grid iterates row-blocks only; very wide datasets
  are chunked at trace time into separate same-shaped, BALANCED calls so
  one step's unrolled program stays bounded (and every BlockSpec dim is
  Mosaic-legal: the feature dim always equals the array dim, row blocks
  are 128-multiples).
- The kernel is NOT HBM-bandwidth-bound, it is INSTRUCTION-bound: its time
  is 0.18-0.23 ns per LLO line of one grid step (Mosaic's
  ``post-finalize-llo`` dump; PERF.md sections 6 and 7).  On a v5e: 5.94 ns
  a row a launch at 28 features x 255 bins f32 and 25.5 at 137, 0.19-0.21
  ns a row-column (my chip run, PR 28).  What binds is one compare + one
  select + one weight push per register of one-hot.  Its time is the rows
  it is handed, so the streamed volume stays proportional to the rows
  actually histogrammed: the reference's smaller-sibling trick
  (``serial_tree_learner.cpp:369``), one call per smaller sibling at its
  own bucket, or the fused wave's one ragged launch.
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


# One grid step unrolls one compare -> select -> latch per vector register
# of one-hot, so the COLUMNS one launch takes are bounded by the step's
# program, not by VMEM (the one-hot is never stored; what Mosaic allocates
# is the accumulator block, 548 KB at 137 columns x 256 bins).  8 Mi
# elements are 8 192 registers, some 34 K LLO lines; the largest step
# measured, 256 rows x 137 columns x 256 bins (9.0 M), took 0.9 s of Mosaic
# compile and 0.180 ns a row-column on a v5e against 0.212 at 28 columns
# (my chip run, PR 28) — wider steps are a little FASTER a cell, so the
# bound is there for the compile's sake.
_STEP_ELEMS = 8 * 1024 * 1024


# The row-block rule's budget and formula: the VMEM model of a one-hot held
# in VMEM twice over, which the kernel no longer holds
# (``pallas_common.onehot_contract``).  Kept as the DEFINITION of the row
# block (``_pick_tiles``) and of the widths the fused wave admits
# (``pallas_wave.wave_layout``), so that neither moved with the kernel.
BLOCK_BUDGET = 16 * 1024 * 1024


def block_model_bytes(blk: int, ft: int, num_bins: int, itemsize: int,
                      acc_size: int = 4) -> int:
    return ft * num_bins * (blk * 2 * itemsize + C_PAD * acc_size)


def _pick_tiles(f: int, num_bins: int, itemsize: int, rows_block: int):
    """(rows_block, features_per_chunk) of one ``histogram_flat`` launch.
    ``num_bins`` here is the LANE-PADDED bin count (multiple of 128).

    Mosaic requires each BlockSpec's last dim to be a multiple of 128 or
    equal to the full array dim, so the kernel never tiles features inside
    one ``pallas_call``: the bins block spans the WHOLE (chunk) feature
    width, and wide datasets are chunked at trace time into separate
    same-shaped calls.  Row blocks stay multiples of 128 (the rows are the
    one-hot's lanes).

    ROWS: the largest power of two from 1024 down to 128 at which
    ``block_model_bytes`` stays under ``BLOCK_BUDGET`` — the block every
    shape has run since PR 21.  It stays because the fused wave pads every
    slot to whole blocks (256 at Higgs' 28 columns: ``hist_rows_useful``
    85.9 %) and because a larger block buys little (28 columns on a v5e:
    6.72 ns a row a launch at 128, 5.94 at 256, 5.64 at 512, 5.39 at 1024;
    my chip run, PR 28).

    COLUMNS: at most ``_STEP_ELEMS`` one-hot elements a grid step, in
    BALANCED chunks — ``nchunks = ceil(f / ft_max)``, ``ft = ceil(f /
    nchunks)`` — so a launch sequence hands at most ``f + nchunks - 1``
    columns: 137 features are one launch of 137, never 3 x 63 = 189."""
    # rows_block > 4096 means "tuned for the XLA einsum path" — auto-pick.
    # Powers of two >= 128 keep every halving on the 128-multiple lattice
    # Mosaic requires for the valsT block's last dim.
    if rows_block <= 0 or rows_block > 4096:
        blk = 1024
    else:
        blk = max(128, 1 << (int(rows_block).bit_length() - 1))
    while (blk > 128
           and block_model_bytes(blk, f, num_bins, itemsize) > BLOCK_BUDGET):
        blk //= 2
    ft_max = max(1, _STEP_ELEMS // (blk * num_bins))
    nchunks = -(-f // ft_max)
    return blk, -(-f // nchunks)


def kernel_layout(f: int, num_bins: int, dtype: str, rows_block: int = 0,
                  packed4: bool = False):
    """(rows_block, ftile, cols_tile, b_pad) for one ``histogram_flat``
    config.  Every Mosaic legality constraint lives here so it is testable
    without hardware: the bin axis is padded to a 128-multiple (bin ids are
    < num_bins, so phantom bins stay exactly zero), which keeps each
    column's slab of the flat histogram — and, under packed4, each nibble
    plane's contiguous output half — lane-aligned."""
    isz = _DTYPES[dtype][1]
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


def _flat_kernel(bins_ref, valsT_ref, out_ref, *, num_bins, ftile, dtype,
                 packed4=False):
    """``num_bins`` is the lane-padded bin count (multiple of 128): each
    column's slab of the flat histogram starts on a lane-tile boundary.
    Real bin ids never reach the phantom bins, so their histogram lanes
    are exact zeros and the caller slices them off."""
    rb = pl.program_id(0)  # row-block index

    @pl.when(rb == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    bins_blk = bins_ref[:].astype(jnp.int32)            # (blk, ct)
    valsT = valsT_ref[:]                                # (C_PAD, blk)

    def contract(b2d):
        return onehot_contract(b2d, valsT, num_bins=num_bins, dtype=dtype)

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
    dtype: str = "f32",  # compute dtype: f32 | bf16 | int8
    interpret: bool = False,
    packed4: bool = False,   # two 4-bit features per streamed byte
    features: int = 0,       # real F when packed4
) -> jnp.ndarray:        # (F, num_bins, 3) f32 (int32 for int8)
    """Single-leaf flat-matmul histogram."""
    n, fcols = bins.shape
    f = features if packed4 else fcols
    acc_dtype = _DTYPES[dtype][0]
    rows_block, ftile, cols_tile, b_pad = kernel_layout(
        f, num_bins, dtype, rows_block, packed4)
    bins, valsT, nblocks, nchunks = _prep(bins, vals, rows_block, cols_tile)
    call = pl.pallas_call(
        functools.partial(_flat_kernel, num_bins=b_pad, ftile=ftile,
                          dtype=dtype, packed4=packed4),
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
