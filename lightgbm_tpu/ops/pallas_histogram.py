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
  (``serial_tree_learner.cpp:369``), and per wave ONE ragged launch over
  the W smaller siblings packed back to back (:func:`histogram_ragged`
  here, per column chunk; ``pallas_wave.fused_wave_call`` where subtract
  and scan fuse in), which skips the padding blocks it is handed.  The
  per-leaf :func:`histogram_flat` is the root's call (and the pool's
  recompute-on-miss), padded to its power-of-two bucket.
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


def _accumulate(bins_ref, valsT_ref, out_ref, *, num_bins, ftile, dtype,
                packed4=False):
    """Add one row block's sums to the ``(C_PAD, ftile * num_bins)``
    accumulator block ``out_ref``.  ``num_bins`` is the lane-padded bin
    count (multiple of 128): each column's slab of the flat histogram
    starts on a lane-tile boundary.  Real bin ids never reach the phantom
    bins, so their histogram lanes are exact zeros and the caller slices
    them off."""
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


def _flat_kernel(bins_ref, valsT_ref, out_ref, **layout):
    """One leaf: zero at the first row block, accumulate every block."""
    rb = pl.program_id(0)  # row-block index

    @pl.when(rb == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    _accumulate(bins_ref, valsT_ref, out_ref, **layout)


def _ragged_kernel(slot_ref, real_ref, src_ref, bins_ref, valsT_ref, out_ref,
                   **layout):
    """Row block ``b`` of a packed wave (``pallas_wave._wave_kernel`` minus
    its subtract and scan): ``out_ref`` is the accumulator block of slot
    ``slot_ref[b]``, zeroed at the slot's first block — a slot's blocks are
    consecutive, so a neighbour compare finds it — and added to on every
    REAL block.  A padding block (inside a slot's last granule or past the
    wave's last one) does nothing; ``src_ref`` is the index maps' alone."""
    del src_ref
    b = pl.program_id(0)
    first = (b == 0) | (slot_ref[jnp.maximum(b - 1, 0)] != slot_ref[b])

    @pl.when(first)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(real_ref[b] > 0)
    def _real():
        _accumulate(bins_ref, valsT_ref, out_ref, **layout)


def _from_flat(out, *, nchunks, ftile, cols_tile, b_pad, num_bins, f,
               packed4):
    """``(C_PAD, nchunks * ftile * b_pad)`` chunk results side by side ->
    the ``(F, num_bins, 3)`` histogram: channels, lane padding and phantom
    columns sliced off, packed4's nibble planes un-permuted."""
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
    return _from_flat(out, nchunks=nchunks, ftile=ftile, cols_tile=cols_tile,
                      b_pad=b_pad, num_bins=num_bins, f=f, packed4=packed4)


def ragged_block_map(gslot, gk, gcnt, blk: int, gran: int):
    """The packed wave's row blocks, from its GRANULES.  The W segments lie
    back to back in whole granules of ``gran`` rows (a multiple of the
    kernel's row block ``blk``; ``pallas_wave.wave_block_map`` at
    ``gran``): granule ``g`` is slot ``gslot[g]``'s ``gk[g]``-th and that
    slot holds ``gcnt[g]`` rows; the granules past the wave's last one name
    the last slot at a granule past its rows.  Returns per row block ``b``
    of the ``len(gslot) * gran / blk``:

    - ``slot[b]``: whose accumulator block the kernel holds at ``b``;
    - ``real[b]``: 1 iff the block's first row lies under its slot's
      count — the blocks the kernel contracts.  The rest of a slot's last
      granule and every granule past the wave's last are 0;
    - ``src[b]``: the block the input index maps name at ``b`` — ``b``
      itself where real, else its slot's last real block (its first block
      where it has none), so a skipped block moves no data."""
    r = gran // blk
    rep = lambda a: jnp.repeat(a.astype(jnp.int32), r)      # granule -> blocks
    k = (gk.astype(jnp.int32)[:, None] * r
         + jnp.arange(r, dtype=jnp.int32)).reshape(-1)
    nreal = rep((gcnt + (blk - 1)) // blk)
    b = jnp.arange(k.shape[0], dtype=jnp.int32)
    src = b - k + jnp.minimum(k, jnp.maximum(nreal - 1, 0))
    return rep(gslot), (k < nreal).astype(jnp.int32), src


@functools.partial(
    jax.jit, static_argnames=("slots", "num_bins", "rows_block", "dtype",
                              "interpret", "packed4", "features"))
def histogram_ragged(
    bins: jnp.ndarray,   # (T, F) the wave's rows, packed by slot — or packed4
    vals: jnp.ndarray,   # (T, 3) their channel values (zero on padding rows)
    blocks,              # ragged_block_map's (slot, real, src), (T / blk,) each
    *,
    slots: int,          # W
    num_bins: int,
    rows_block: int = 0,
    dtype: str = "f32",
    interpret: bool = False,
    packed4: bool = False,
    features: int = 0,
) -> jnp.ndarray:        # (W, F, num_bins, 3) f32 (int32 for int8)
    """The W histograms of one packed wave in ONE launch per column chunk:
    every slot accumulates its segment from its first row in blocks of the
    layout's row block — what :func:`histogram_flat` does on that segment
    alone, so the sums are bitwise its sums wherever the per-leaf call
    resolves the same block (every bucket of 2 048 rows and more, at every
    width the row-block rule does not give more than 1 024 rows) — and the
    launch skips the blocks that hold no row of any slot."""
    t, fcols = bins.shape
    f = features if packed4 else fcols
    acc_dtype = _DTYPES[dtype][0]
    blk, ftile, cols_tile, b_pad = kernel_layout(
        f, num_bins, dtype, rows_block, packed4)
    if t % blk or any(a.shape != (t // blk,) for a in blocks):
        raise ValueError(
            f"ragged histogram needs whole row blocks and one map entry per "
            f"block: got {t} rows, {[a.shape for a in blocks]} entries, row "
            f"block {blk}")
    bins, valsT, nblocks, nchunks = _prep(bins, vals, blk, cols_tile)
    fb = ftile * b_pad
    call = pl.pallas_call(
        functools.partial(_ragged_kernel, num_bins=b_pad, ftile=ftile,
                          dtype=dtype, packed4=packed4),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nblocks,),
            in_specs=[
                pl.BlockSpec((blk, cols_tile),
                             lambda b, slot, real, src: (src[b], 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((C_PAD, blk),
                             lambda b, slot, real, src: (0, src[b]),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((None, C_PAD, fb),
                                   lambda b, slot, real, src: (slot[b], 0, 0),
                                   memory_space=pltpu.VMEM)),
        out_shape=jax.ShapeDtypeStruct((slots, C_PAD, fb), acc_dtype),
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
    )
    chunks = [call(*blocks, jax.lax.slice_in_dim(
        bins, c * cols_tile, (c + 1) * cols_tile, axis=1), valsT)
        for c in range(nchunks)]
    out = chunks[0] if nchunks == 1 else jnp.concatenate(chunks, axis=2)
    return jax.vmap(functools.partial(
        _from_flat, nchunks=nchunks, ftile=ftile, cols_tile=cols_tile,
        b_pad=b_pad, num_bins=num_bins, f=f, packed4=packed4))(out)


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
