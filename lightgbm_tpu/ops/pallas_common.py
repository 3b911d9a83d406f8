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
# (``_pick_tiles`` 16 MiB, ``WAVE_VMEM_BUDGET`` 48 MiB,
# ``TRAVERSE_VMEM_BUDGET`` 32 MiB) compiles under it at the largest shape
# the model admits (tests/test_tpu_lowering.py compiles the Higgs shape).
VMEM_LIMIT = 64 * 1024 * 1024

# one-hot/compute dtype -> (operand dtype, accumulator dtype, itemsize)
DTYPES = {
    "f32": (jnp.float32, jnp.float32, 4),
    "bf16": (jnp.bfloat16, jnp.float32, 2),
    "int8": (jnp.int8, jnp.int32, 1),
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


def onehot_contract(bins_blk, valsT, *, num_bins, oh_dtype, acc_dtype,
                    precision):
    """One row-block's histogram contribution as a matmul against the
    in-VMEM one-hot: ``(C_PAD, blk) x (blk, ft*num_bins)``.  ``num_bins``
    is the LANE-PADDED bin count (multiple of 128) — Mosaic only supports
    the (blk, ft, B) -> (blk, ft*B) flatten when the merged minor dim
    stays 128-aligned.  The ONE implementation shared by the flat
    histogram kernel and the fused wave kernel, so their accumulation is
    op-for-op identical."""
    blk, ft = bins_blk.shape
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (blk, ft, num_bins), 2)
    oh = (bins_blk[:, :, None] == iota_b).astype(oh_dtype)
    oh = oh.reshape(blk, ft * num_bins)             # lane-aligned merge
    return jax.lax.dot_general(
        valsT, oh, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=acc_dtype, precision=precision)
