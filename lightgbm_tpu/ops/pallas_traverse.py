"""Fused serving traversal: the whole quantized tree pack VMEM-resident,
row blocks pipelined through the Pallas grid (ISSUE-12, ROADMAP item 3).

The unfused predict walk (``models/tree._tree_walk_q``) advances every row
one level per ``while_loop`` step with XLA gathers — each step re-reads
the (T, M) node arrays from HBM and the gather lowers poorly on TPU.  The
dataflow-pipelined traversal in "Booster: An Accelerator for Gradient
Boosting Decision Trees" (arxiv 2011.02022) keeps the tree structure
resident next to the compute units and streams rows past it; this kernel
is that shape for the TPU build:

- grid ``(row_blocks,)`` — ONE ``pallas_call`` per class scores the whole
  batch, tree pack and bin tables' nan routing staying in VMEM across
  every row block (vs O(depth) gather dispatches worth of HBM re-reads);
- per-node lookups are Mosaic-safe masked sums / one-hot matmuls (the
  ``onehot_contract`` discipline of the histogram kernels) — no device
  gathers anywhere in the body;
- the categorical masks arrive BIT-PACKED (the quantized pack encoding)
  and the kernel tests membership with ``(byte >> (col & 7)) & 1``,
  exactly the unfused walk's arithmetic;
- leaf quanta accumulate in int32 — associative, so the kernel is
  bitwise-identical to the unfused walk UNCONDITIONALLY (the serving twin
  of the PR-7 wave kernel's int32 histogram identity), pinned across the
  shape-bucket ladder in tests/test_serve_quantize.py.

The kernel REQUIRES a quantized pack (``tpu_serve_quantize != off``): an
fp32 leaf sum would tie bitwise identity to summation order, and the whole
point of the integer pack is that it cannot.  On CPU the kernel body runs
in interpret mode (tier-1 coverage), selected the same way the wave kernel
does it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import compiler_params

#: VMEM working-set budget for one traversal call: resident pack (widened
#: to i32 operands) + one streamed row block + the per-step one-hot
#: temporaries, with 2x headroom (the wave kernel's budget discipline).
TRAVERSE_VMEM_BUDGET = 32 * 1024 * 1024

#: default rows per grid step (overridable via layout rows_block)
_ROWS_BLOCK = 1024

_LANE = 128


def _pad_to(n: int, mult: int = _LANE) -> int:
    return max(-(-n // mult) * mult, mult)


def traverse_layout(num_trees: int, max_leaves: int, features: int,
                    num_bins: int, rows_block: int = 0) -> dict:
    """Static VMEM plan for one fused traversal call — the fit gate in one
    testable place (the ``wave_layout`` discipline).  All lane dims pad to
    128; the pack operands are WIDENED to i32 for the kernel (the narrow
    resident arrays stay the plan's footprint — widening is a trace-time
    relayout XLA fuses into the operand copy)."""
    blk = int(rows_block) if rows_block else _ROWS_BLOCK
    m_pad = _pad_to(max(max_leaves - 1, 1))
    l_pad = _pad_to(max_leaves)
    f_pad = _pad_to(features)
    bb_pad = _pad_to(-(-num_bins // 8))
    pack_bytes = num_trees * (6 * m_pad + m_pad * bb_pad + l_pad) * 4
    stream_bytes = blk * f_pad * 4
    # per-step temporaries: the (blk, m/f/bb/l) one-hots and their masked
    # products, ~6 live at once
    scratch_bytes = 6 * blk * max(m_pad, f_pad, bb_pad, l_pad) * 4
    total = 2 * (pack_bytes + stream_bytes) + scratch_bytes
    return {
        "rows_block": blk, "m_pad": m_pad, "l_pad": l_pad, "f_pad": f_pad,
        "bb_pad": bb_pad, "pack_bytes": pack_bytes,
        "stream_bytes": stream_bytes, "scratch_bytes": scratch_bytes,
        "total_bytes": total, "fits": total <= TRAVERSE_VMEM_BUDGET,
    }


def traverse_layout_fits(num_trees: int, max_leaves: int, features: int,
                         num_bins: int, rows_block: int = 0) -> bool:
    return traverse_layout(num_trees, max_leaves, features, num_bins,
                           rows_block)["fits"]


def _traverse_kernel(bins_ref, nanb_ref, sf_ref, sb_ref, dl_ref, ic_ref,
                     catb_ref, lc_ref, rc_ref, leaf_ref, out_ref, *,
                     num_trees, depth, m_pad, bb_pad):
    """Kernel body at grid point (rb): walk row block ``rb`` through every
    tree of the resident pack, accumulating int32 leaf quanta.

    Decision arithmetic mirrors ``models/tree._tree_walk_q`` op for op;
    node/feature/leaf lookups are masked sums over one-hots (exact for
    integers), the cat-byte row comes from a (blk, M) x (M, BB) one-hot
    matmul (f32 is exact for byte values <= 255)."""
    bins = bins_ref[...].astype(jnp.int32)               # (blk, f_pad)
    blk, f_pad = bins.shape
    nanb = nanb_ref[...].astype(jnp.int32)               # (1, f_pad)
    l_pad = leaf_ref.shape[1]
    iota_m = jax.lax.broadcasted_iota(jnp.int32, (blk, m_pad), 1)
    iota_f = jax.lax.broadcasted_iota(jnp.int32, (blk, f_pad), 1)
    iota_bb = jax.lax.broadcasted_iota(jnp.int32, (blk, bb_pad), 1)
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (blk, l_pad), 1)

    def row_of(ref, t):
        # Tree ``t``'s row, loaded from the resident block: Pallas TPU
        # lowers a dynamic row index on a ref, not on a loaded value.
        return ref[pl.ds(t, 1), :]

    def tree_body(t, acc):
        sf_t = row_of(sf_ref, t)                         # (1, m_pad) i32
        sb_t = row_of(sb_ref, t)
        dl_t = row_of(dl_ref, t)
        ic_t = row_of(ic_ref, t)
        lc_t = row_of(lc_ref, t)
        rc_t = row_of(rc_ref, t)
        catb_t = row_of(catb_ref, t).reshape(m_pad, bb_pad) \
            .astype(jnp.float32)
        leaf_t = row_of(leaf_ref, t)                     # (1, l_pad) i32

        def step(_, node):
            # A row is done once its node id went negative (~leaf): the
            # one-hot below is then all-zero and the row keeps its leaf.
            # (Mosaic cannot carry an i1 vector through the loop, and the
            # sign already says it.)
            done = node < 0
            ohn = (node == iota_m).astype(jnp.int32)     # (blk, m_pad)

            def sel(row):                                # row (1, m_pad)
                return jnp.sum(ohn * row, axis=1, keepdims=True)

            f = sel(sf_t)
            sb = sel(sb_t)
            dl = sel(dl_t)
            ic = sel(ic_t)
            lc = sel(lc_t)
            rc = sel(rc_t)
            ohf = (f == iota_f).astype(jnp.int32)        # (blk, f_pad)
            col = jnp.sum(ohf * bins, axis=1, keepdims=True)
            nb = jnp.sum(ohf * nanb, axis=1, keepdims=True)
            isnan = col == nb
            rowb = jax.lax.dot_general(                  # (blk, bb_pad)
                (node == iota_m).astype(jnp.float32), catb_t,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            ohb = (jnp.minimum(col >> 3, bb_pad - 1) == iota_bb) \
                .astype(jnp.float32)
            byte = jnp.sum(ohb * rowb, axis=1,
                           keepdims=True).astype(jnp.int32)
            catbit = ((byte >> (col & 7)) & 1) > 0
            # _tree_walk_q's two selects between bool vectors, written
            # as mask algebra (Mosaic has no i1-valued vector select)
            cat = ic > 0
            gl = (cat & catbit) | (~cat & (col <= sb))
            nan_num = isnan & ~cat
            gl = (nan_num & (dl > 0)) | (~nan_num & gl)
            return jnp.where(done, node, jnp.where(gl, lc, rc))

        node = jax.lax.fori_loop(0, depth, step,
                                 jnp.zeros((blk, 1), jnp.int32))
        leaf_idx = jnp.where(node < 0, ~node, 0)
        ohl = (leaf_idx == iota_l).astype(jnp.int32)
        return acc + jnp.sum(ohl * leaf_t, axis=1, keepdims=True)

    out_ref[...] = jax.lax.fori_loop(
        0, num_trees, tree_body, jnp.zeros((blk, 1), jnp.int32))


@functools.partial(
    jax.jit, static_argnames=("depth", "rows_block", "interpret"))
def fused_traverse_call(
    bins: jnp.ndarray,      # (N_pad, f_pad) i32 lane-padded binned rows
    nan_bins: jnp.ndarray,  # (1, f_pad) i32
    sf: jnp.ndarray,        # (T, m_pad) i32 — pack arrays widened + padded
    sb: jnp.ndarray,
    dl: jnp.ndarray,
    ic: jnp.ndarray,
    catb: jnp.ndarray,      # (T, m_pad*bb_pad) i32 bit-packed cat bytes
    lc: jnp.ndarray,
    rc: jnp.ndarray,
    leaf: jnp.ndarray,      # (T, l_pad) i32 leaf quanta
    *,
    depth: int,
    rows_block: int,
    interpret: bool = False,
):
    """One fused traversal pass: (N_pad, 1) int32 leaf-quanta sums for one
    class's resident pack, rows pipelined through the grid."""
    n, f_pad = bins.shape
    t, m_pad = sf.shape
    bb_pad = catb.shape[1] // m_pad
    blk = min(rows_block, n)
    pad = (-n) % blk
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
    nblocks = (n + pad) // blk
    kern = functools.partial(
        _traverse_kernel, num_trees=t, depth=depth, m_pad=m_pad,
        bb_pad=bb_pad)
    whole = lambda r: (0, 0)    # noqa: E731 — pack resident across blocks
    out = pl.pallas_call(
        kern,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((blk, f_pad), lambda r: (r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(nan_bins.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(sf.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(sb.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(dl.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(ic.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(catb.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(lc.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(rc.shape, whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(leaf.shape, whole, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((blk, 1), lambda r: (r, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n + pad, 1), jnp.int32),
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
    )(bins, nan_bins, sf, sb, dl, ic, catb, lc, rc, leaf)
    return out[:n, 0]


def fused_class_sums(pack: dict, bins: jnp.ndarray, nan_bins: jnp.ndarray,
                     *, interpret: bool = False) -> jnp.ndarray:
    """(N,) int32 quanta sums for one quantized pack via the fused kernel.
    Trace-time prep (lane padding + i32 widening) only relayouts — the
    values the kernel walks are exactly the pack's, so the result equals
    ``models/tree._ensemble_sum_q`` bit for bit."""
    t, m = pack["split_feature"].shape
    bb = pack["cat_bits"].shape[2]
    n, f = bins.shape
    lay = traverse_layout(t, int(pack["leaf_q"].shape[1]), f,
                          int(pack["num_bins"]))
    m_pad, f_pad = lay["m_pad"], lay["f_pad"]
    bb_pad, l_pad = lay["bb_pad"], lay["l_pad"]

    def widen(a, cols):
        a = a.astype(jnp.int32)
        return jnp.pad(a, ((0, 0), (0, cols - a.shape[1])))

    catb = jnp.pad(pack["cat_bits"].astype(jnp.int32),
                   ((0, 0), (0, m_pad - m), (0, bb_pad - bb)))
    return fused_traverse_call(
        jnp.pad(bins.astype(jnp.int32), ((0, 0), (0, f_pad - f))),
        jnp.pad(nan_bins.astype(jnp.int32), (0, f_pad - f)).reshape(1, -1),
        widen(pack["split_feature"], m_pad),
        widen(pack["split_bin"], m_pad),
        widen(pack["default_left"], m_pad),
        widen(pack["is_cat"], m_pad),
        catb.reshape(t, m_pad * bb_pad),
        widen(pack["left_child"], m_pad),
        widen(pack["right_child"], m_pad),
        widen(pack["leaf_q"], l_pad),
        depth=int(pack["depth"]), rows_block=lay["rows_block"],
        interpret=interpret)
