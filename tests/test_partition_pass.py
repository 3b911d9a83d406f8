"""The permutation layout's partition pass (``grower._partition_wave``).

ONE ragged pass per wave: the W split leaves' ``perm`` segments packed
back to back in whole row blocks, one read of the go-left bit per row, one
prefix sum, one scatter.  The partition is STABLE, so ``perm`` after a wave is bit for
bit what a per-leaf ``np.concatenate([seg[gl], seg[~gl]])`` writes, and
every position outside the segments is untouched.  These tests hold the
pass to that numpy reference over ragged waves (empty, inactive, one-row,
block-edge, all-left and all-right slots; a total on every ladder step)
and hold the go-left table it reads (``grower._go_left_bits``: categorical
masks, the NaN default direction, EFB-decoded and packed4 columns, more
than 32 splits a wave) to numpy too.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu.models.grower as G

N = 20_000
BLK = G._partition_block(N)


def _wave(rng, n, cnts):
    """``(perm, starts)``: ``perm`` as the grower keeps it (n row ids
    grouped by leaf, each leaf's rows ascending, then the phantom row
    ``n``), the wave's segments scattered among other leaves' rows."""
    cnts = np.asarray(cnts, np.int64)
    assert cnts.sum() <= n
    ids = rng.permutation(n).astype(np.int32)
    # other leaves fill the gaps: W + 1 gaps of random sizes
    rest = n - cnts.sum()
    cuts = np.sort(rng.randint(0, rest + 1, size=len(cnts)))
    gaps = np.diff(np.concatenate([[0], cuts, [rest]]))
    perm = np.full(2 * n, n, np.int32)
    starts = np.zeros(len(cnts), np.int32)
    order = rng.permutation(len(cnts))          # slots are in gain order,
    o = q = 0                                   # not in position order
    for g, j in zip(gaps, order):
        perm[o:o + g] = np.sort(ids[q:q + g])
        o, q = o + g, q + g
        starts[j] = o
        perm[o:o + cnts[j]] = np.sort(ids[q:q + cnts[j]])
        o, q = o + cnts[j], q + cnts[j]
    perm[o:n] = np.sort(ids[q:])
    assert sorted(perm[:n]) == list(range(n))
    return perm, starts


def _reference(perm, starts, cnts, go_left):
    """``go_left[j]``: (n + 1,) bools by row id, for slot ``j``."""
    perm, nl = perm.copy(), []
    for j, (s, c) in enumerate(zip(starts, cnts)):
        seg = perm[s:s + c]
        gl = go_left[j][seg]
        perm[s:s + c] = np.concatenate([seg[gl], seg[~gl]])
        nl.append(int(gl.sum()))
    return perm, np.asarray(nl, np.int32)


def _bits(go_left):
    """The table ``_partition_wave`` reads: split ``j`` of row ``r`` is bit
    ``j % 32`` of word ``[(j // 32) * (n + 1) + r]``."""
    w, n1 = go_left.shape
    out = np.zeros((-(-w // 32), n1), np.uint32)
    for j in range(w):
        out[j // 32] |= go_left[j].astype(np.uint32) << np.uint32(j % 32)
    return out.reshape(-1).view(np.int32)


def _run(perm, starts, cnts, go_left, n=N):
    fn = jax.jit(lambda p, s, c, g: G._partition_wave(p, s, c, g, n))
    out, nl = fn(jnp.asarray(perm), jnp.asarray(starts, jnp.int32),
                 jnp.asarray(cnts, jnp.int32), jnp.asarray(_bits(go_left)))
    return np.asarray(out), np.asarray(nl)


def _cnts(pattern, w, rng):
    edge = [BLK, BLK + 1, BLK - 1, 2 * BLK, 1, 0, 2 * BLK + 1, 3]
    if pattern == "ragged":
        return rng.randint(0, N // w, size=w)
    if pattern == "empty_and_one":
        return np.asarray(([0, 1, 0, 37] * w)[:w])
    if pattern == "block_edges":
        return np.asarray((edge * w)[:w])
    if pattern == "whole_data":                 # the root: one slot, all rows
        return np.asarray([N] + [0] * (w - 1))
    raise AssertionError(pattern)


@pytest.mark.parametrize("side", ["mixed", "all_left", "all_right"])
@pytest.mark.parametrize("pattern", ["ragged", "empty_and_one",
                                     "block_edges", "whole_data"])
@pytest.mark.parametrize("w", [1, 4, 16])
def test_stable_partition_of_ragged_waves(w, pattern, side):
    rng = np.random.RandomState(zlib.crc32(f"{w}{pattern}{side}".encode()))
    cnts = _cnts(pattern, w, rng)
    perm, starts = _wave(rng, N, cnts)
    go_left = {"mixed": rng.rand(w, N + 1) < rng.rand(w, 1),
               "all_left": np.ones((w, N + 1), bool),
               "all_right": np.zeros((w, N + 1), bool)}[side]
    want, want_nl = _reference(perm, starts, cnts, go_left)
    got, nl = _run(perm, starts, cnts, go_left)
    np.testing.assert_array_equal(nl, want_nl)
    np.testing.assert_array_equal(got, want)    # untouched positions too
    if side != "mixed":                         # and then nothing moves
        np.testing.assert_array_equal(got, perm)


_LADDER = G._wave_row_ladder(BLK, (N // BLK + 16) * BLK, BLK)


@pytest.mark.parametrize("step", range(len(_LADDER)))
def test_a_wave_on_every_ladder_step(step):
    """Totals that land exactly on a step of the total-row ladder, and one
    block under it (the step's last block is then padding)."""
    assert len(_LADDER) >= 6, _LADDER
    rng = np.random.RandomState(step)
    w = 16
    for blocks in (_LADDER[step] // BLK, _LADDER[step] // BLK - 1):
        if blocks > N // BLK:           # the top step holds more blocks
            blocks = N // BLK           # than the data has whole ones
        # `blocks` whole blocks over w slots, the last row of some cut off
        nb = rng.multinomial(blocks, np.ones(w) / w)
        cnts = np.maximum(nb * BLK - rng.randint(0, BLK, size=w) * (nb > 0),
                          0)
        assert (-(-cnts // BLK)).sum() == blocks
        perm, starts = _wave(rng, N, cnts)
        go_left = rng.rand(w, N + 1) < 0.4
        want, want_nl = _reference(perm, starts, cnts, go_left)
        got, nl = _run(perm, starts, cnts, go_left)
        np.testing.assert_array_equal(nl, want_nl)
        np.testing.assert_array_equal(got, want)


def test_an_inactive_slot_moves_nothing_wherever_it_points():
    """The grower hands an inactive slot its leaf's start and a count of
    0; a wave of nothing but such slots leaves ``perm`` as it was."""
    rng = np.random.RandomState(5)
    perm, starts = _wave(rng, N, [300, 0, 0, 900])
    cnts = np.zeros(4, np.int64)
    got, nl = _run(perm, starts, cnts, np.ones((4, N + 1), bool))
    np.testing.assert_array_equal(got, perm)
    np.testing.assert_array_equal(nl, np.zeros(4, np.int32))


# ---- the table the pass reads: grower._go_left_bits against numpy

def _np_go_left(col, nan_bin, sbin, dleft, scat, cmask):
    gl = np.where(scat, cmask[col], col <= sbin)
    return np.where((col == nan_bin) & ~scat, dleft, gl)


def _columns_case(kind, rng, n):
    """``(cfg, bins (n, G) as stored, meta, cols (n, F) as the splits see
    them)`` for one storage form of the bins."""
    f, b = 6, 16
    split = G.SplitConfig(has_nan=True, has_categorical=True)
    if kind == "packed4":
        cols = rng.randint(0, 16, size=(n, f)).astype(np.uint8)
        bins = (cols[:, 0::2] | (cols[:, 1::2] << 4)).astype(np.uint8)
        cfg = G.GrowerConfig(num_bins=b, split=split, packed4=True)
        meta = [np.full(f, 16), np.full(f, 15)]
    elif kind == "efb":
        # three bundles of two features each: feature 2g keeps bundle bins
        # 1..4 (its own 1..4, default 0), feature 2g + 1 bins 5..9
        nbpf = np.asarray([5, 6] * 3)
        offs = np.asarray([1, 5] * 3)
        raw = rng.randint(0, 10, size=(n, 3))
        bins = raw.astype(np.uint8)
        cols = np.zeros((n, f), np.int64)
        for j in range(f):
            r = raw[:, j // 2]
            own = (r >= offs[j]) & (r < offs[j] + nbpf[j] - 1)
            cols[:, j] = np.where(own, r - offs[j] + 1, 0)
        cfg = G.GrowerConfig(num_bins=b, split=split, bundled=True,
                             hist_bins=10)
        meta = [nbpf, np.full(f, b), None, None, np.arange(f) // 2, offs]
    else:                                       # plain u8 columns
        cols = rng.randint(0, b, size=(n, f)).astype(np.uint8)
        bins = cols
        cfg = G.GrowerConfig(num_bins=b, split=split)
        meta = [np.full(f, b), np.asarray([b - 1, b, b - 1, b, b - 1, b])]
    meta = tuple(None if m is None else jnp.asarray(m, jnp.int32)
                 for m in meta)
    return cfg, bins, meta, np.asarray(cols, np.int64)


@pytest.mark.parametrize("kind", ["plain_categorical_nan", "efb", "packed4"])
@pytest.mark.parametrize("w", [1, 4, 40])
def test_the_go_left_table_is_the_split_rule(kind, w):
    """Per slot another feature, threshold, default direction; every other
    slot a categorical split with its own mask."""
    n, b = 6000, 16
    rng = np.random.RandomState(len(kind) + w)
    cfg, bins, meta, cols = _columns_case(kind, rng, n)
    feats = rng.randint(0, cols.shape[1], size=w)
    sbins = rng.randint(1, 8, size=w)
    dlefts = rng.rand(w) < 0.5
    scats = np.arange(w) % 2 == 1
    cmasks = rng.rand(w, b) < 0.5
    nan_bins = np.asarray(meta[1])
    pad = np.concatenate([cols, np.zeros((1, cols.shape[1]), np.int64)])
    go_left = np.stack([
        _np_go_left(pad[:, feats[j]], nan_bins[feats[j]], sbins[j],
                    dlefts[j], scats[j], cmasks[j]) for j in range(w)])
    cnts = rng.randint(1, n // w, size=w)
    perm, starts = _wave(rng, n, cnts)
    want, want_nl = _reference(perm, starts, cnts, go_left)

    bins_pad = jnp.concatenate(
        [jnp.asarray(bins), jnp.zeros((1, bins.shape[1]), jnp.uint8)])

    def run(perm, starts, cnts):
        go_left = G._go_left_bits(
            cfg, bins_pad.T, meta, jnp.asarray(feats, jnp.int32),
            jnp.asarray(sbins, jnp.int32), jnp.asarray(dlefts),
            jnp.asarray(scats), jnp.asarray(cmasks))
        return G._partition_wave(perm, starts, cnts, go_left, n)

    got, nl = jax.jit(run)(jnp.asarray(perm), jnp.asarray(starts),
                           jnp.asarray(cnts, jnp.int32))
    assert want_nl.min() >= 0 and 0 < want_nl.sum() < cnts.sum()
    np.testing.assert_array_equal(np.asarray(nl), want_nl)
    np.testing.assert_array_equal(np.asarray(got), want)
