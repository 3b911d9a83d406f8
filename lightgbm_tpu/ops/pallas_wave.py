"""Fused wave kernel: histogram -> sibling-subtract -> split-scan in ONE
VMEM-resident Pallas pass per leaf-batch wave.

The split-finding wave is the framework's hot loop, and unfused it
round-trips the (W, G, B, 3) histogram tensors through HBM three times per
wave: ``ops/pallas_histogram.py`` builds each smaller sibling's histogram
(write), the grower subtracts the larger sibling from the parent in plain
XLA (read + write), and ``ops/split.py`` re-streams both children for the
split scan (read).  Both "Booster: An Accelerator for Gradient Boosting
Decision Trees" (arxiv 2011.02022) and "XGBoost: Scalable GPU Accelerated
Learning" (arxiv 1806.11248) locate the remaining headroom in exactly this
fusion once the histogram itself is matmul-shaped.

This kernel runs the whole sequence while the (C_PAD, F*B) accumulators
are VMEM-resident:

- grid ``(row_blocks,)`` over a RAGGED wave — one ``pallas_call`` per
  WAVE (as the unfused wave's ``histogram_ragged``, which is this kernel
  without (b) and (c)): the W smaller
  siblings' rows lie back to back in whole row blocks
  (:func:`wave_block_map`), a scalar-prefetched block -> slot map steers
  each block's parent / stats / output blocks, and the launch is handed
  the rows the wave has (padded to a step of ONE total-row ladder), not
  W x its largest leaf's bucket.  Blocks past the wave's last real block
  do nothing and move no data;
- (a) the smaller sibling accumulates via the SAME in-VMEM one-hot matmul
  as ``histogram_flat`` (``ops/pallas_common.onehot_contract`` — shared
  code, op-for-op identical accumulation, including the packed4 nibble
  unpack and the int8 x int8 -> int32 quantized path);
- (b) at the last row block the larger sibling derives by subtraction from
  the parent's histogram WITHOUT leaving VMEM (reference
  ``FeatureHistogram::Subtract``, ``serial_tree_learner.cpp:369``);
- (c) the cumulative-sum split scan (``ops/split.scan_tables`` — the exact
  gain arithmetic of the unfused scan, refactored to be kernel-callable)
  plus the Mosaic-safe winner selection (``ops/split.select_payload``,
  tie-break-identical to the unfused argmax) run over BOTH siblings while
  the accumulators are still resident.

HBM traffic per wave drops to one bins+vals stream plus the O(W * G * B)
child-histogram writeback the pool retains and a tiny (W, 2, 16+B)
SplitInfo payload — the full (L, G, B, 3) tensor never round-trips between
build and scan (pinned structurally in tests/test_hlo_cost.py).  The
kernel is NOT bandwidth-bound but instruction-bound, like
``histogram_flat`` (``pallas_histogram``'s docstring; same contraction):
on a v5e the 19 fused launches and the root's flat one together take
0.0369 s a tree for 6.16 M real rows at 28 features x 255 bins f32, 6.0 ns
a row, in-kernel scans included (my chip run, PR 28; 47 ns before it)
— so its time is the rows it is handed, and the ragged packing is what
keeps that at the rows the tree needs.

Quantized training rides the int8/int32 accumulation path (``DTYPES``),
subtraction stays exact integer arithmetic, and the per-iteration scales
apply in-register right before the scan — mirroring ``grower._scale_hist``
bit for bit.  packed4 composes: the nibble planes contract into contiguous
output halves and the scan runs in PLANE order with ORIGINAL-feature-order
tie-break keys, so the layout cannot perturb the chosen split.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import (C_PAD, DTYPES, compiler_params,
                            onehot_contract)
from .pallas_histogram import (BLOCK_BUDGET, block_model_bytes,
                               kernel_layout)
from .split import BestSplit, SplitConfig, scan_tables, select_payload

# Scalar lanes ahead of the cat_mask in the per-child SplitInfo payload:
# [gain, feature, bin, default_left, is_cat, GL, HL, CL, GR, HR, CR] + pad.
PAYLOAD_SCALARS = 16

# Per-child scalar-input lanes: [pg, ph, pc, parent_out, small_left, active].
STAT_LANES = 8

# The fused working set is three (C_PAD, F*B) histograms (small
# accumulator, its sibling slot, the parent) PLUS the scan's (F, B)
# gain/stat tables PLUS the streamed blocks.  The one-hot is not in it: the
# contraction feeds it from vector registers (``onehot_contract``).  v5e
# carries 128 MB VMEM; row blocking is ``kernel_layout``'s, so fused and
# unfused accumulate in identical blocks (bitwise-identical sums).
WAVE_VMEM_BUDGET = 48 * 1024 * 1024

# The widths the fused kernel ADMITS are the widths at which
# ``pallas_histogram``'s row-block rule holds at the smallest row block: 63
# columns at 255 bins f32, 126 at <= 128 bins, 240 for int8.  The bytes
# below would admit 137 columns x 256 bins (6 MB), which would move every
# such model — the benchmark's ``msltr.train`` among them, whose ``why``
# says "unfused wave" — onto another program without one chip run behind
# it.  A shape this code observes, not a parameter; lifting it is an issue
# of its own (PERF.md section 7).
_ADMIT_BLOCK = 128

# (F, B)-shaped f32 buffers the scan materializes at peak (cum sums, three
# stats directions x 6, gain/mask tables) — a deliberate over-count.
_SCAN_BUFS = 32


def plane_order(features: int, packed4: bool):
    """(order, inverse) for the kernel's feature layout.  packed4 nibble
    planes contract into contiguous halves (low-nibble features first), so
    plane position p holds ORIGINAL feature ``order[p]``; ``inverse``
    restores original order (phantom odd-F column sorts last and is
    sliced off).  None/None when the layouts coincide."""
    if not packed4:
        return None, None
    ct = -(-features // 2)
    order = np.concatenate([2 * np.arange(ct), 2 * np.arange(ct) + 1])
    return order.astype(np.int32), np.argsort(order).astype(np.int32)


def wave_layout(features: int, num_bins: int, dtype: str,
                rows_block: int = 0, packed4: bool = False) -> dict:
    """Static VMEM plan for one fused-wave call — every Mosaic legality
    constraint and the working-set budget in one testable place (the
    ``kernel_layout`` discipline, extended with the fused extras):

    - row blocking comes from ``kernel_layout`` UNCHANGED at the
      configured ``rows_block``: ONE block size for every launch, and each
      slot accumulates its rows in blocks of it from its segment's start —
      what ``histogram_flat`` does on that segment alone, so the f32 sums
      group identically whenever the per-leaf path resolves the same
      block (it resolves ``min(rows_block, bucket)``, which differs only
      where a leaf's bucket is under the layout's block; integer
      histograms are exact either way);
    - ``single_chunk``: the kernel scans the whole feature space in one
      block — trace-time feature chunking (very wide F) cannot fuse, those
      shapes keep the unfused path (plus the pool + tiled scan that
      already serve them) — AND the width is one the row-block rule
      holds at (``_ADMIT_BLOCK``);
    - ``fits``: single-chunk AND the modeled working set (3 resident
      histograms + scan scratch + streamed blocks; the one-hot is never
      stored) stays under ``WAVE_VMEM_BUDGET``."""
    blk, ftile, cols_tile, b_pad = kernel_layout(
        features, num_bins, dtype, rows_block, packed4)
    isz = DTYPES[dtype][1]
    fb = ftile * b_pad
    needed_cols = -(-features // 2) if packed4 else features
    single_chunk = (cols_tile >= needed_cols
                    and block_model_bytes(_ADMIT_BLOCK, features, b_pad, isz)
                    <= BLOCK_BUDGET)
    hist_block_bytes = 3 * C_PAD * fb * 4
    scan_scratch_bytes = _SCAN_BUFS * fb * 4
    stream_bytes = 2 * (blk * cols_tile + C_PAD * blk * isz)
    total = hist_block_bytes + scan_scratch_bytes + stream_bytes
    return {
        "rows_block": blk, "ftile": ftile, "cols_tile": cols_tile,
        "b_pad": b_pad, "payload_width": PAYLOAD_SCALARS + num_bins,
        "hist_block_bytes": hist_block_bytes,
        "scan_scratch_bytes": scan_scratch_bytes,
        "stream_bytes": stream_bytes, "total_bytes": total,
        "single_chunk": single_chunk,
        "fits": single_chunk and total <= WAVE_VMEM_BUDGET,
    }


def wave_dtype_for(cfg) -> str:
    """The fused kernel's operand dtype for a GrowerConfig-like ``cfg`` —
    the ONE resolution shared by the growth plan's width gate and the
    kernel's launch site."""
    return "int8" if cfg.quantized else "f32"


def wave_meta(num_bins_per_feature, nan_bins, is_categorical, feature_mask,
              *, features: int, num_bins: int, packed4: bool) -> jnp.ndarray:
    """The kernel's (ftile, 8) i32 meta block in PLANE order:
    ``[nbpf, nan_bin, is_cat, feature_mask, orig_feature_id, 0, 0, 0]``.
    Phantom rows (packed4 odd-F padding) get ``nbpf = 0`` so no candidate
    of theirs is ever valid; column 4 feeds the ORIGINAL-feature-order
    tie-break keys."""
    order, _ = plane_order(features, packed4)
    ftile = features if order is None else int(order.shape[0])

    def prep(a, fill):
        a = jnp.asarray(a).astype(jnp.int32)
        if ftile > features:
            a = jnp.concatenate(
                [a, jnp.full(ftile - features, fill, jnp.int32)])
        return a if order is None else a[order]

    orig = jnp.asarray(order if order is not None
                       else np.arange(features), jnp.int32)
    zero = jnp.zeros(ftile, jnp.int32)
    return jnp.stack(
        [prep(num_bins_per_feature, 0), prep(nan_bins, num_bins),
         prep(is_categorical, 0), prep(feature_mask, 0), orig,
         zero, zero, zero], axis=1)


def hist_to_flat(h: jnp.ndarray, ftile: int, b_pad: int,
                 order) -> jnp.ndarray:
    """(W, F, HB, 3) stored parent histograms -> the kernel's
    (W, C_PAD, ftile*b_pad) flat layout (channel-major, lane-padded bins,
    plane-permuted features under packed4).  Pure relayout — XLA fuses it
    into the operand copy; no arithmetic, so the values stay bitwise."""
    w, f, hb, c = h.shape
    h = jnp.pad(h, ((0, 0), (0, ftile - f), (0, b_pad - hb),
                    (0, C_PAD - c)))
    if order is not None:
        h = h[:, order]
    return jnp.transpose(h, (0, 3, 1, 2)).reshape(w, C_PAD, ftile * b_pad)


def hist_from_flat(o: jnp.ndarray, features: int, hb: int, b_pad: int,
                   inverse) -> jnp.ndarray:
    """(W, 2, C_PAD, ftile*b_pad) kernel output -> (W, 2, F, HB, 3) stored
    child histograms (inverse of :func:`hist_to_flat`)."""
    w, two, cp, fb = o.shape
    ftile = fb // b_pad
    o = o.reshape(w, two, cp, ftile, b_pad)[:, :, :3, :, :hb]
    o = jnp.transpose(o, (0, 1, 3, 4, 2))
    if inverse is not None:
        o = o[:, :, inverse]
    return o[:, :, :features]


def payload_to_best(pay: jnp.ndarray) -> BestSplit:
    """(K, PAYLOAD_SCALARS + B) kernel payload -> batched BestSplit.  The
    f32 lanes transport counts/sums losslessly (exactly one writer per
    lane, same discipline as ``sync_best_split``'s one-hot psum)."""
    col = lambda i: pay[:, i]
    return BestSplit(
        gain=col(0),
        feature=jnp.round(col(1)).astype(jnp.int32),
        bin=jnp.round(col(2)).astype(jnp.int32),
        default_left=col(3) > 0.5,
        is_cat=col(4) > 0.5,
        cat_mask=pay[:, PAYLOAD_SCALARS:] > 0.5,
        sum_grad_left=col(5), sum_hess_left=col(6), count_left=col(7),
        sum_grad_right=col(8), sum_hess_right=col(9), count_right=col(10))


def wave_block_map(small_cnt: jnp.ndarray, blk: int):
    """The ragged wave's packing, from the (W,) smaller-sibling row counts:
    ``(nb, off, nb_total)``.  Slot ``j`` owns the ``nb[j] = max(1,
    ceil(cnt_j / blk))`` consecutive row blocks starting at block
    ``off[j]`` — one block even for an empty or inactive slot, so every
    slot's output block is visited, zero-initialised and scanned — and
    the wave holds ``nb_total = sum(nb)`` real blocks:
    ``nb_total * blk <= sum(cnt) + W * blk``."""
    nb = jnp.maximum((small_cnt.astype(jnp.int32) + (blk - 1)) // blk, 1)
    off = jnp.cumsum(nb) - nb
    return nb, off, jnp.sum(nb)


def wave_block_slots(off: jnp.ndarray, nblocks: int):
    """``(blk_slot, blk_in_slot)`` for the ``nblocks`` row blocks of one
    launch: block ``b`` accumulates into slot ``blk_slot[b]`` and is that
    slot's ``blk_in_slot[b]``-th block (``b - off[slot]``).  Blocks past
    the wave's last real block name the last slot (so the kernel's index
    maps do not move) at a block past its rows (so the gather masks them
    to the phantom row)."""
    b = jnp.arange(nblocks, dtype=jnp.int32)
    slot = jnp.searchsorted(off, b, side="right").astype(jnp.int32) - 1
    return slot, b - off[slot]


def _wave_kernel(*refs, ftile, b_pad, key_bins, dtype, packed4, scfg,
                 has_scale):
    """Kernel body at row block ``b`` of the packed wave: accumulate the
    block into its slot's smaller-sibling histogram (zeroed at the slot's
    first block), and at the slot's last block subtract the parent,
    reorder into (left, right) and scan both children.  Blocks at or past
    ``nb_total`` are the ladder step's padding and do nothing."""
    slot_ref, nb_ref = refs[:2]
    if has_scale:
        (bins_ref, valsT_ref, parent_ref, stats_ref, meta_ref, scale_ref,
         hist_ref, pay_ref) = refs[2:]
    else:
        (bins_ref, valsT_ref, parent_ref, stats_ref, meta_ref,
         hist_ref, pay_ref) = refs[2:]
        scale_ref = None
    b = pl.program_id(0)
    nb_total = nb_ref[0]
    slot = slot_ref[b]
    real = b < nb_total
    # a slot's blocks are consecutive: neighbour compares find its ends
    first = (b == 0) | (slot_ref[jnp.maximum(b - 1, 0)] != slot)
    last = ((b == nb_total - 1)
            | (slot_ref[jnp.minimum(b + 1, pl.num_programs(0) - 1)] != slot))

    @pl.when(real & first)
    def _init():
        hist_ref[:] = jnp.zeros_like(hist_ref)

    @pl.when(real)
    def _accumulate():
        bins_blk = bins_ref[:].astype(jnp.int32)         # (blk, ct)
        valsT = valsT_ref[:]                             # (C_PAD, blk)

        def contract(b2d):
            return onehot_contract(b2d, valsT, num_bins=b_pad, dtype=dtype)

        if packed4:
            # Two 4-bit features per streamed byte (reference DenseBin
            # IS_4BIT, dense_bin.hpp): unpack in VMEM, contract the nibble
            # planes into contiguous output halves — identical to
            # _flat_kernel.
            half = (ftile // 2) * b_pad
            hist_ref[0, 0, :, :half] += contract(bins_blk & 15)
            hist_ref[0, 0, :, half:] += contract((bins_blk >> 4) & 15)
        else:
            hist_ref[0, 0] += contract(bins_blk)

    @pl.when(real & last)
    def _subtract_and_scan():
        small = hist_ref[0, 0, :, :]                     # (C_PAD, fb)
        parent = parent_ref[0]
        big = parent - small         # exact (int32 quantized / f32 sums)
        stats = stats_ref[0]                             # (2, STAT_LANES)
        small_left = stats[0, 4] > 0.5
        left = jnp.where(small_left, small, big)
        right = jnp.where(small_left, big, small)
        hist_ref[0, 0] = left
        hist_ref[0, 1] = right

        nbpf = meta_ref[:, 0:1]                          # (ftile, 1) i32
        nanb = meta_ref[:, 1:2]
        iscat = meta_ref[:, 2:3] > 0
        fmask = meta_ref[:, 3:4] > 0
        biota = jax.lax.broadcasted_iota(jnp.int32, (ftile, b_pad), 1)
        # ORIGINAL-feature-order tie-break keys (lane padding keyed out;
        # meta column 4 carries each plane row's original feature id, so
        # the packed4 plane layout cannot perturb the tie-break).
        okey = meta_ref[:, 4:5]
        keys = jnp.where(biota < key_bins, okey * key_bins + biota,
                         jnp.iinfo(jnp.int32).max)

        def child_payload(hflat, ci):
            h3 = hflat.reshape(C_PAD, ftile, b_pad)
            if has_scale:
                # grower._scale_hist: raw int32 -> f32 * per-channel scale
                G = h3[0].astype(jnp.float32) * scale_ref[0, 0]
                H = h3[1].astype(jnp.float32) * scale_ref[0, 1]
                C = h3[2].astype(jnp.float32) * scale_ref[0, 2]
            else:
                G, H, C = h3[0], h3[1], h3[2]
            tables = scan_tables(
                G, H, C, stats[ci, 0], stats[ci, 1], stats[ci, 2],
                num_bins_per_feature=nbpf, nan_bins=nanb,
                is_categorical=iscat, feature_mask=fmask, cfg=scfg,
                parent_output=stats[ci, 3])
            (gain, bf, bb, dl, ic, GL, HL, CL, GR, HR,
             CR) = select_payload(tables, iscat, scfg, flat_keys=keys,
                                  key_bins=key_bins)
            # Inactive wave slots (lane 5) scanned garbage parents: emit a
            # clean no-split payload — the grower drops these lanes via
            # OOB scatters either way, this just keeps the payload sane.
            gain = jnp.where(stats[ci, 5] > 0.5, gain, -jnp.inf)
            scalars = [gain, bf, bb, dl, ic, GL, HL, CL, GR, HR, CR]
            cat_mask = ((jax.lax.broadcasted_iota(
                jnp.int32, (1, key_bins), 1) == bb)
                & ic).astype(jnp.float32)
            return jnp.concatenate(
                [jnp.asarray(v).astype(jnp.float32).reshape(1, 1)
                 for v in scalars]
                + [jnp.zeros((1, PAYLOAD_SCALARS - len(scalars)),
                             jnp.float32), cat_mask], axis=1)

        pay_ref[0, 0:1, :] = child_payload(left, 0)
        pay_ref[0, 1:2, :] = child_payload(right, 1)


@functools.partial(
    jax.jit, static_argnames=("num_bins", "features", "rows_block", "dtype",
                              "packed4", "scfg", "interpret"))
def fused_wave_call(
    gbins: jnp.ndarray,        # (T, ct) the wave's rows, packed by slot
    gvalsT: jnp.ndarray,       # (C_PAD, T) their channel values
    parent_flat: jnp.ndarray,  # (W, C_PAD, ftile*b_pad) parent histograms
    stats: jnp.ndarray,        # (W, 2, STAT_LANES) per-child scalars
    meta: jnp.ndarray,         # (ftile, 8) i32 [nbpf|nan|is_cat|fmask|...]
    blk_slot: jnp.ndarray,     # (T / blk,) i32 row block -> slot
    nb_total: jnp.ndarray,     # (1,) i32 real row blocks of the wave
    scale3: jnp.ndarray | None = None,   # (1, 4) f32 quantized scales
    *,
    num_bins: int,             # REAL scan bin count (HB)
    features: int,             # real F
    rows_block: int,
    dtype: str,                # f32 | bf16 | int8
    packed4: bool = False,
    scfg: SplitConfig = None,
    interpret: bool = False,
):
    """One fused RAGGED wave: the W slots' smaller-sibling rows lie back
    to back in ``gbins`` / ``gvalsT`` at row-block granularity
    (:func:`wave_block_map`), slot ``blk_slot[b]`` owning block ``b``;
    rows past a slot's count inside its last block, and every block at or
    past ``nb_total``, are phantom (zero values).  Returns ``(child_hists,
    payload)``: (W, 2, C_PAD, ftile*b_pad) RAW (left, right) histograms in
    the flat layout and the (W, 2, PAYLOAD_SCALARS + num_bins) per-child
    SplitInfo block.  ``T`` is a multiple of the layout's row block."""
    t, ct = gbins.shape
    w = parent_flat.shape[0]
    acc_dtype = DTYPES[dtype][0]
    blk, ftile, cols_tile, b_pad = kernel_layout(
        features, num_bins, dtype, rows_block, packed4)
    if ct != cols_tile or parent_flat.shape[-1] != ftile * b_pad:
        raise ValueError(
            f"fused wave needs the single-chunk layout: got {ct} bin "
            f"columns / parent width {parent_flat.shape[-1]} vs layout "
            f"({cols_tile}, {ftile * b_pad}); check wave_layout")
    if t % blk or blk_slot.shape != (t // blk,):
        raise ValueError(
            f"fused wave needs whole row blocks and one slot per block: "
            f"got {t} rows, {blk_slot.shape} slots, row block {blk}")
    fb = ftile * b_pad
    pay_w = PAYLOAD_SCALARS + num_bins
    has_scale = scale3 is not None
    kern = functools.partial(
        _wave_kernel, ftile=ftile, b_pad=b_pad, key_bins=num_bins,
        dtype=dtype, packed4=packed4, scfg=scfg, has_scale=has_scale)

    # Padding blocks re-name the last real block, so no DMA is issued
    # for them; everything per-slot follows the block's slot.
    in_specs = [
        pl.BlockSpec((blk, ct),
                     lambda b, slot, nb: (jnp.minimum(b, nb[0] - 1), 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((C_PAD, blk),
                     lambda b, slot, nb: (0, jnp.minimum(b, nb[0] - 1)),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, C_PAD, fb), lambda b, slot, nb: (slot[b], 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 2, STAT_LANES), lambda b, slot, nb: (slot[b], 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((ftile, 8), lambda b, slot, nb: (0, 0),
                     memory_space=pltpu.VMEM),
    ]
    inputs = [gbins, gvalsT, parent_flat, stats, meta]
    if has_scale:
        in_specs.append(pl.BlockSpec((1, 4), lambda b, slot, nb: (0, 0),
                                     memory_space=pltpu.VMEM))
        inputs.append(scale3)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(t // blk,),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 2, C_PAD, fb),
                             lambda b, slot, nb: (slot[b], 0, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 2, pay_w),
                             lambda b, slot, nb: (slot[b], 0, 0),
                             memory_space=pltpu.VMEM),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((w, 2, C_PAD, fb), acc_dtype),
            jax.ShapeDtypeStruct((w, 2, pay_w), jnp.float32),
        ],
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
    )(blk_slot.astype(jnp.int32), nb_total.astype(jnp.int32), *inputs)
