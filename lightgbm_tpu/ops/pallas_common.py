"""Shared scaffolding for the Pallas TPU kernels (histogram, fused wave,
traversal).

One copy of the compile parameters, the interpret-mode decision, the
dtype table and the one-hot contraction, so the three kernels cannot
drift apart.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# Channels (grad, hess, count) padded; BlockSpec dim == array dim so
# sublane alignment is not required, and 4 halves the streamed valsT bytes
# vs a full 8-sublane tile.
C_PAD = 4

# Mosaic scoped-vmem ceiling.  The v5e compiler reports 128 MiB of VMEM
# ("would exceed memory (size=134217728)"); half of it leaves XLA room for
# the fusions around the custom call.  Each kernel's own layout model
# (``_pick_tiles``, ``WAVE_VMEM_BUDGET`` 48 MiB, ``TRAVERSE_VMEM_BUDGET``
# 32 MiB) compiles under it at the largest shape the model admits
# (tests/test_tpu_lowering.py compiles the Higgs and the MS-LTR shapes).
VMEM_LIMIT = 64 * 1024 * 1024

# compute dtype -> (accumulator dtype, itemsize the row-block rule counts
# an element of the step's one-hot at: ``pallas_histogram._pick_tiles``)
DTYPES = {
    "f32": (jnp.float32, 4),
    "bf16": (jnp.float32, 2),
    "int8": (jnp.int32, 1),
}


def interpret_mode() -> bool:
    """The ONE interpret-mode decision for every Pallas kernel in the
    package: Mosaic compiles the kernel on a TPU backend, and anywhere
    else the body runs in the Pallas interpreter (how the CPU tests
    exercise kernel bodies).  The grower, the histogram dispatch, the
    serve plan and the tools all read it here, so what ``chip_smoke.py``
    prints is what every kernel call used."""
    return jax.default_backend() != "tpu"


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    """Mosaic compile parameters shared by the three kernels."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def split_bf16x3(v):
    """A float32 array as three bfloat16 parts with ``hi + mid + lo == v``
    bit for bit (every finite float32 whose last part is not subnormal:
    3 x 8 significand bits cover the 24).  Each subtraction is exact, so
    the parts are the value's significand cut in three."""
    hi = v.astype(jnp.bfloat16)
    rest = v - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def onehot_contract(bins_blk, valsT, *, num_bins, dtype):
    """One row-block's histogram contribution, ``(C_PAD, blk)`` values x
    the block's one-hot -> ``(C_PAD, ft*num_bins)``.  ``num_bins`` is the
    LANE-PADDED bin count (multiple of 128; real bin ids never reach the
    phantom bins).  The ONE implementation shared by the flat histogram
    kernel and the fused wave kernel, so their accumulation is op-for-op
    identical.

    The one-hot is built where the MXU reads it and fed once:

    - TRANSPOSED, ``(ft*num_bins, blk)``: bin ids in sublanes, the block's
      rows in lanes.  One small transpose of the ``(blk, ft)`` bin tile;
      then per column (a static loop) its ``(1, blk)`` row of bin ids is
      compared with a sublane iota — a sublane broadcast is free, the lane
      broadcast of a ``(blk, 1)`` column is three XLU operations per 8
      rows.  Each compare result goes from vector registers straight into
      the MXU's weight latch (the contraction is ``A x B^T``): the one-hot
      is never stored, and Mosaic's scoped VMEM is the kernel's blocks
      alone (148 KB at 256 x 28 x 256, 548 KB at 128 x 137 x 256; AOT,
      PR 28).  7 131 LLO lines a grid step at 256 x 28, 5.94 ns a row a
      launch on a v5e (my chip run, PR 28; a 3-D compare + reshape of the
      one-hot took 51 864 lines and 47.5 ns; PERF.md section 6).
    - f32: ONE bf16 pass at M = 12.  The values' three bfloat16 parts
      (:func:`split_bf16x3`) are stacked in M and contracted once against
      the 0 / 1 one-hot; both operands travel as float32 holding
      bfloat16-exact numbers, which the MXU latches without a pack (a
      bfloat16 one-hot costs a pack and a relayout per register).  Every
      product is exact and every accumulation float32: the arithmetic of a
      six-pass highest-precision matmul with the three passes that multiply
      by the one-hot's zero parts left out.  NOT the ``bf16`` dtype, which
      rounds the VALUES to 8 bits.
    - bf16 / int8: their single native pass (int8 x int8 -> int32 exact).
    """
    blk, ft = bins_blk.shape
    binsT = bins_blk.T                                  # (ft, blk)
    bin_id = jax.lax.broadcasted_iota(jnp.int32, (num_bins, blk), 0)

    def onehot_t(element):                              # (ft*num_bins, blk)
        return jnp.concatenate(
            [element(binsT[j:j + 1] == bin_id) for j in range(ft)], axis=0)

    def contract(lhs, oh_t, acc_dtype):                 # over the rows
        return jax.lax.dot_general(lhs, oh_t, (((1,), (1,)), ((), ())),
                                   preferred_element_type=acc_dtype)

    if dtype == "int8":
        return contract(valsT, onehot_t(lambda m: m.astype(jnp.int8)),
                        jnp.int32)
    parts = (split_bf16x3(valsT) if dtype == "f32"
             else (valsT.astype(jnp.bfloat16),))
    lhs = jnp.concatenate([p.astype(jnp.float32) for p in parts], axis=0)
    out = contract(lhs, onehot_t(lambda m: jnp.where(m, 1.0, 0.0)),
                   jnp.float32)
    acc = out[:C_PAD]
    for i in range(1, len(parts)):
        acc = acc + out[i * C_PAD:(i + 1) * C_PAD]
    return acc
