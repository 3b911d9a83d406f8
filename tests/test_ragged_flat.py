"""The unfused wave's ONE ragged histogram launch
(``ops/pallas_histogram.histogram_ragged``) against the per-leaf
``histogram_flat`` on each segment alone — BITWISE, in interpret mode on the
CPU: every slot accumulates its rows from its segment's start in blocks of
the layout's row block, which is what the per-leaf call does on a bucket of
2 048 rows and more, and a skipped padding block would have added zeros.

Also the packing's pure functions (granule -> slot map, block -> slot map,
real-block flags, the blocks the index maps name) against a numpy
reference, and the total-row ladder at and one block under every step.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu.models.grower as G
from lightgbm_tpu.ops.pallas_histogram import (histogram_flat,
                                               histogram_ragged,
                                               kernel_layout,
                                               ragged_block_map)
from lightgbm_tpu.ops.pallas_wave import wave_block_map, wave_block_slots

# operand form -> (value dtype, num_bins, packed4)
FORMS = {"f32": ("f32", 255, False), "int8": ("int8", 255, False),
         "packed4": ("f32", 15, True)}


def _pack(cnts, gran, total=None):
    """``(goff, gslot, gk, T)`` of a wave of ``cnts`` in granules of
    ``gran``, handed ``total`` rows (the packed rows themselves if None)."""
    cnt = jnp.asarray(cnts, jnp.int32)
    _, goff, ng_total = wave_block_map(cnt, gran)
    t = int(ng_total) * gran if total is None else total
    assert t >= int(ng_total) * gran
    gslot, gk = wave_block_slots(goff, t // gran)
    return np.asarray(goff), gslot, gk, t


def _segments(rng, cnts, cols, form):
    dtype, nb, packed4 = FORMS[form]
    segs = []
    for c in cnts:
        bins = rng.randint(0, 256 if packed4 else nb, (c, cols))
        vals = (rng.randint(-100, 100, (c, 3)).astype(np.int8)
                if dtype == "int8" else rng.randn(c, 3).astype(np.float32))
        segs.append((bins.astype(np.uint8), vals))
    return segs


def _per_leaf(bins, vals, features, form):
    """The parent's call on one segment: padded with phantom zero rows to
    its power-of-two bucket, ``rows_block = min(cfg.rows_block, S)``."""
    dtype, nb, packed4 = FORMS[form]
    s = G._MIN_BUCKET
    while s < len(bins):
        s *= 2
    pad = ((0, s - len(bins)), (0, 0))
    return np.asarray(histogram_flat(
        jnp.asarray(np.pad(bins, pad)), jnp.asarray(np.pad(vals, pad)),
        num_bins=nb, rows_block=min(16384, s), dtype=dtype, interpret=True,
        packed4=packed4, features=features if packed4 else 0))


def _check(features, cnts, form, gran=256, total=None, seed=0):
    dtype, nb, packed4 = FORMS[form]
    cols = -(-features // 2) if packed4 else features
    blk = kernel_layout(features, nb, dtype, 16384, packed4)[0]
    gran = max(gran, blk)
    goff, gslot, gk, t = _pack(cnts, gran, total)
    segs = _segments(np.random.RandomState(seed), cnts, cols, form)
    bins = np.zeros((t, cols), np.uint8)
    vals = np.zeros((t, 3), segs[0][1].dtype)
    for o, (b, v) in zip(goff * gran, segs):
        bins[o:o + len(b)], vals[o:o + len(b)] = b, v
    out = np.asarray(histogram_ragged(
        jnp.asarray(bins), jnp.asarray(vals),
        ragged_block_map(gslot, gk, jnp.asarray(cnts, jnp.int32)[gslot], blk,
                         gran),
        slots=len(cnts), num_bins=nb, rows_block=16384, dtype=dtype,
        interpret=True, packed4=packed4, features=features))
    assert out.shape == (len(cnts), features, nb, 3)
    assert out.dtype == (np.int32 if dtype == "int8" else np.float32)
    for j, (b, v) in enumerate(segs):
        want = _per_leaf(b, v, features, form)
        assert np.array_equal(out[j].view(np.int32), want.view(np.int32)), \
            (j, len(b), np.abs(out[j] - want).max())


# ------------------------------------------- the kernel against the per-leaf call
@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("features", [28, 137])
def test_ragged_is_bitwise_the_per_leaf_call_one_chunk(features, form):
    if form == "packed4":
        features -= 1                           # odd F: a phantom nibble
    _check(features, [700, 300, 1290, 45], form)


@pytest.mark.parametrize("form", list(FORMS))
def test_ragged_is_bitwise_the_per_leaf_call_several_chunks(form):
    features = 699 if form == "packed4" else 700
    dtype, nb, packed4 = FORMS[form]
    ftile = kernel_layout(features, nb, dtype, 16384, packed4)[1]
    assert -(-features // ftile) > 1            # several launches a wave
    _check(features, [300, 130], form)


@pytest.mark.parametrize("slots", [1, 4, 16])
def test_ragged_at_every_wave_width(slots):
    rng = np.random.RandomState(slots)
    _check(28, list(rng.randint(1, 900, slots)), "f32", seed=slots)


@pytest.mark.parametrize("name, cnts", [
    ("empty_first", [0, 300, 40]),
    ("empty_last", [300, 40, 0, 0]),        # inactive slots: no rows
    ("all_empty", [0, 0]),
    ("one_row", [1, 257, 1]),
    ("one_block", [256, 512, 256]),         # 28 columns: blocks of 256
    ("one_granule", [1024, 1024, 2048]),
    ("one_over", [257, 1025, 513]),
])
def test_ragged_edge_slots(name, cnts):
    assert kernel_layout(28, 255, "f32", 16384)[0] == 256
    _check(28, cnts, "f32", gran=1024)


def _ladder_cases():
    blk, gran, w, n = 256, 512, 4, 12000
    ladder = G._ragged_wave_totals(n // 2, w, gran)
    assert len(ladder) >= 3 and ladder[-1] >= n // 2 + w * gran - gran
    assert all(t % (2 * gran) == gran for t in ladder)    # odd multiples
    return [(t, under) for t in ladder for under in (0, 1)]


@pytest.mark.parametrize("total, under", _ladder_cases())
def test_ragged_on_and_one_block_under_every_ladder_step(total, under):
    """A wave whose real blocks fill the step, and one that leaves its last
    row block (and so, at two blocks a granule, no whole granule) empty;
    both handed ``total`` rows."""
    blk, gran, w = 256, 512, 4
    rest = total - (w - 1) * gran - under * blk
    _check(28, [gran, gran - 3, gran - blk + 1, rest], "f32", gran=gran,
           total=total, seed=total + under)


def test_ragged_refuses_a_map_that_does_not_cover_the_rows():
    with pytest.raises(ValueError, match="whole row blocks"):
        histogram_ragged(jnp.zeros((512, 28), jnp.uint8),
                         jnp.zeros((512, 3), jnp.float32),
                         (jnp.zeros(3, jnp.int32),) * 3, slots=2,
                         num_bins=255, interpret=True)


# --------------------------------------------- the packing, by a numpy reference
def _reference_map(cnts, blk, gran, total):
    """Row block -> (slot, real, src), one block at a time."""
    slot, real, src = [], [], []
    for j, c in enumerate(cnts):
        first = len(slot)
        nreal = -(-c // blk)
        for k in range(max(1, -(-c // gran)) * (gran // blk)):
            slot.append(j)
            real.append(int(k * blk < c))
            src.append(first + min(k, max(nreal - 1, 0)))
    while len(slot) < total // blk:             # the ladder's padding
        slot.append(slot[-1]), real.append(0), src.append(src[-1])
    return np.array(slot), np.array(real), np.array(src)


@pytest.mark.parametrize("blk, gran", [(128, 128), (128, 512), (256, 2048),
                                       (1024, 1024)])
@pytest.mark.parametrize("cnts", [[700, 300, 1290, 45], [0, 5, 0], [0, 0, 0],
                                  [4096], [1, 128, 129, 2048, 2049, 0]])
def test_block_map_is_the_numpy_reference(cnts, blk, gran):
    packed = sum(max(1, -(-c // gran)) for c in cnts) * gran
    for total in (packed, packed + 3 * gran):
        goff, gslot, gk, t = _pack(cnts, gran, total)
        # granules: slot j owns max(1, ceil(cnt / gran)) from goff[j] on
        assert list(goff) == list(np.cumsum(
            [0] + [max(1, -(-c // gran)) for c in cnts[:-1]]))
        slot, real, src = (np.asarray(a) for a in ragged_block_map(
            gslot, gk, jnp.asarray(cnts, jnp.int32)[gslot], blk, gran))
        want = _reference_map(cnts, blk, gran, t)
        assert np.array_equal(slot, want[0])
        assert np.array_equal(real, want[1])
        assert np.array_equal(src, want[2])
        # what the kernel relies on: a slot's blocks are consecutive and
        # every slot is visited; the real blocks are the rows rounded up to
        # the KERNEL's block, whatever the granule; a skipped block names a
        # block already resident (its own slot's, so no data moves)
        assert np.all(np.diff(slot) >= 0) and set(slot) == set(
            range(len(cnts)))
        assert real.sum() == sum(-(-c // blk) for c in cnts)
        assert np.all(src[real == 1] == np.flatnonzero(real))
        assert np.all(src <= np.arange(len(src)))
        assert np.all(slot[src] == slot)


def test_granule_rule_is_512_rows_and_never_under_the_kernels_block():
    """What ``_grow_wave`` packs in: ``_WAVE_GRANULE`` rows (settled on the
    chip, PERF.md Findings PR 34), or the kernel's row block where that is
    the larger; always a multiple of the kernel's block."""
    for features, bins, want in ((137, 255, 512), (2000, 255, 512),
                                 (28, 255, 512), (6, 255, 1024),
                                 (10, 15, 1024)):
        blk = kernel_layout(features, bins, "f32", 16384)[0]
        gran = max(G._WAVE_GRANULE, blk)
        assert gran == want and gran % blk == 0, (features, gran, blk)
