"""Histogram + split-finding op tests against numpy oracles."""

import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.histogram import (build_histogram, histogram_onehot,
                                        histogram_segment, pack_values)
from lightgbm_tpu.ops.split import SplitConfig, best_split


def _np_histogram(bins, g, h, mask, B):
    n, f = bins.shape
    out = np.zeros((f, B, 3))
    for j in range(f):
        for r in range(n):
            if mask is None or mask[r]:
                b = bins[r, j]
                out[j, b, 0] += g[r]
                out[j, b, 1] += h[r]
                out[j, b, 2] += 1.0
    return out


@pytest.mark.parametrize("impl", ["onehot", "segment"])
def test_histogram_matches_oracle(rng, impl):
    n, f, B = 500, 4, 16
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = rng.rand(n).astype(np.float32)
    mask = (rng.rand(n) > 0.3)
    hist = build_histogram(jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                           jnp.asarray(mask), num_bins=B, impl=impl,
                           rows_block=128)
    oracle = _np_histogram(bins, g, h, mask, B)
    np.testing.assert_allclose(np.asarray(hist), oracle, rtol=1e-4, atol=1e-4)


def test_histogram_impls_agree(rng):
    n, f, B = 1000, 6, 64
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    vals = pack_values(jnp.asarray(rng.randn(n), dtype=jnp.float32),
                       jnp.asarray(rng.rand(n), dtype=jnp.float32), None)
    h1 = histogram_onehot(jnp.asarray(bins), vals, num_bins=B, rows_block=256)
    h2 = histogram_segment(jnp.asarray(bins), vals, num_bins=B)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-4, atol=1e-4)


def _oracle_best_numerical(hist, pg, ph, pc, nbpf, nan_bin, cfg):
    """Brute-force split search for one numerical feature."""
    best = (-np.inf, -1, False)
    B = hist.shape[0]
    nv = nbpf - (1 if nan_bin < B else 0)
    Gn = hist[nan_bin, 0] if nan_bin < B else 0.0
    Hn = hist[nan_bin, 1] if nan_bin < B else 0.0
    Cn = hist[nan_bin, 2] if nan_bin < B else 0.0

    def lg(g, h):
        t = np.sign(g) * max(abs(g) - cfg.lambda_l1, 0)
        return t * t / (h + cfg.lambda_l2 + 1e-15)

    for t in range(nv):
        GL = hist[: t + 1, 0].sum()
        HL = hist[: t + 1, 1].sum()
        CL = hist[: t + 1, 2].sum()
        if nan_bin <= t:  # nan bin inside: skip (oracle counts value bins only)
            GL -= Gn; HL -= Hn; CL -= Cn
        for dl in ([False, True] if nan_bin < B else [False]):
            gl, hl, cl = (GL + Gn, HL + Hn, CL + Cn) if dl else (GL, HL, CL)
            gr, hr, cr = pg - gl, ph - hl, pc - cl
            if cl < max(cfg.min_data_in_leaf, 1) or cr < max(cfg.min_data_in_leaf, 1):
                continue
            if hl < cfg.min_sum_hessian_in_leaf or hr < cfg.min_sum_hessian_in_leaf:
                continue
            gain = lg(gl, hl) + lg(gr, hr) - lg(pg, ph)
            if gain > cfg.min_gain_to_split + 1e-15 and gain > best[0]:
                best = (gain, t, dl)
    return best


@pytest.mark.parametrize("with_nan", [False, True])
@pytest.mark.parametrize("l1,l2,mindata", [(0.0, 0.0, 1), (0.5, 1.0, 10)])
def test_best_split_matches_bruteforce(rng, with_nan, l1, l2, mindata):
    B, F = 16, 3
    cfg = SplitConfig(lambda_l1=l1, lambda_l2=l2, min_data_in_leaf=mindata,
                      min_sum_hessian_in_leaf=1e-3)
    hist = np.zeros((F, B, 3), np.float32)
    nbpf = np.array([16, 10, 8], np.int32)
    nan_bins = (np.array([15, 9, 16], np.int32) if with_nan
                else np.array([16, 16, 16], np.int32))
    for f in range(F):
        nb = nbpf[f]
        hist[f, :nb, 0] = rng.randn(nb) * 3
        hist[f, :nb, 1] = rng.rand(nb) + 0.1
        hist[f, :nb, 2] = rng.randint(1, 30, nb)
    # totals must agree across features (all features see the same rows):
    # rescale counts/hessians/grads so each feature sums to the same totals.
    tot = hist[0, :, :].sum(axis=0)
    for f in range(1, F):
        cur = hist[f, :, :].sum(axis=0)
        hist[f] *= (tot / cur)[None, :]
    pg, ph, pc = tot
    bs = best_split(
        jnp.asarray(hist), jnp.asarray(pg), jnp.asarray(ph), jnp.asarray(pc),
        num_bins_per_feature=jnp.asarray(nbpf),
        nan_bins=jnp.asarray(nan_bins),
        is_categorical=jnp.zeros(F, bool),
        monotone=jnp.zeros(F, jnp.int32),
        feature_mask=jnp.ones(F, bool),
        cfg=cfg,
    )
    oracle_best = (-np.inf, -1, -1, False)
    for f in range(F):
        g, t, dl = _oracle_best_numerical(
            hist[f].astype(np.float64), pg, ph, pc, nbpf[f],
            int(nan_bins[f]) if nan_bins[f] < B else B, cfg)
        if g > oracle_best[0]:
            oracle_best = (g, f, t, dl)
    got_gain = float(bs.gain)
    if oracle_best[0] == -np.inf:
        assert got_gain == -np.inf
    else:
        assert got_gain == pytest.approx(oracle_best[0], rel=1e-3)
        assert int(bs.feature) == oracle_best[1]


def test_split_respects_feature_mask(rng):
    B, F = 8, 4
    cfg = SplitConfig(min_data_in_leaf=1)
    hist = np.abs(rng.randn(F, B, 3)).astype(np.float32) + 0.1
    tot = hist[0].sum(axis=0)
    for f in range(1, F):
        hist[f] *= (tot / hist[f].sum(axis=0))[None, :]
    mask = np.array([False, True, False, False])
    bs = best_split(
        jnp.asarray(hist), *(jnp.asarray(v) for v in tot),
        num_bins_per_feature=jnp.full(F, B, jnp.int32),
        nan_bins=jnp.full(F, B, jnp.int32),
        is_categorical=jnp.zeros(F, bool),
        monotone=jnp.zeros(F, jnp.int32),
        feature_mask=jnp.asarray(mask),
        cfg=cfg,
    )
    if float(bs.gain) > -np.inf:
        assert int(bs.feature) == 1


def test_min_data_in_leaf_blocks_small_splits(rng):
    B, F = 8, 1
    hist = np.zeros((F, B, 3), np.float32)
    hist[0, :, 0] = rng.randn(B)
    hist[0, :, 1] = 1.0
    hist[0, :, 2] = 5.0  # 40 rows total, 5 per bin
    tot = hist[0].sum(axis=0)
    bs = best_split(
        jnp.asarray(hist), *(jnp.asarray(v) for v in tot),
        num_bins_per_feature=jnp.full(F, B, jnp.int32),
        nan_bins=jnp.full(F, B, jnp.int32),
        is_categorical=jnp.zeros(F, bool),
        monotone=jnp.zeros(F, jnp.int32),
        feature_mask=jnp.ones(F, bool),
        cfg=SplitConfig(min_data_in_leaf=100),
    )
    assert float(bs.gain) == -np.inf


def test_pallas_histogram_matches_segment(rng):
    """Pallas kernel (interpret mode on CPU) vs scatter oracle."""
    from lightgbm_tpu.ops.pallas_histogram import histogram_pallas

    n, f, B = 700, 5, 32
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    vals = pack_values(jnp.asarray(rng.randn(n), dtype=jnp.float32),
                       jnp.asarray(rng.rand(n), dtype=jnp.float32),
                       jnp.asarray(rng.rand(n) > 0.5))
    got = histogram_pallas(jnp.asarray(bins), vals, num_bins=B,
                           rows_block=256, interpret=True)
    ref = histogram_segment(jnp.asarray(bins), vals, num_bins=B)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_flat_histogram_dtypes_match_oracle(rng):
    """Flat-matmul kernel (f32 / bf16 / int8) vs scatter oracle."""
    from lightgbm_tpu.ops.pallas_histogram import histogram_flat

    n, f, B = 700, 5, 32
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    vals = pack_values(jnp.asarray(rng.randn(n), dtype=jnp.float32),
                       jnp.asarray(rng.rand(n), dtype=jnp.float32),
                       jnp.asarray(rng.rand(n) > 0.5))
    ref = np.asarray(histogram_segment(jnp.asarray(bins), vals, num_bins=B))
    got = histogram_flat(jnp.asarray(bins), vals, num_bins=B,
                         rows_block=256, dtype="f32", interpret=True)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-4, atol=1e-4)
    got16 = histogram_flat(jnp.asarray(bins), vals, num_bins=B,
                           rows_block=256, dtype="bf16", interpret=True)
    np.testing.assert_allclose(np.asarray(got16), ref, rtol=2e-2, atol=2e-1)

    vals8 = jnp.asarray(rng.randint(-16, 16, size=(n, 3)), jnp.int8)
    got8 = histogram_flat(jnp.asarray(bins), vals8, num_bins=B,
                          rows_block=256, dtype="int8", interpret=True)
    ref8 = np.zeros((f, B, 3), np.int64)
    v8 = np.asarray(vals8, np.int64)
    for j in range(f):
        for r in range(n):
            ref8[j, bins[r, j]] += v8[r]
    assert got8.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got8, np.int64), ref8)




def test_flat_histogram_bench_bin_count(rng):
    """max_bin=255 regression: 255 bins made the kernel's one-hot flatten a
    Mosaic-illegal shape cast on hardware (merged minor dim 7140 is not
    128-aligned); the kernel now pads the bin axis to a 128-multiple and
    phantom bins must stay exactly zero."""
    from lightgbm_tpu.ops.pallas_histogram import histogram_flat

    n, f, B = 768, 28, 255
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    vals = pack_values(jnp.asarray(rng.randn(n), dtype=jnp.float32),
                       jnp.asarray(rng.rand(n), dtype=jnp.float32),
                       jnp.asarray(rng.rand(n) > 0.3))
    ref = np.asarray(histogram_segment(jnp.asarray(bins), vals, num_bins=B))
    got = histogram_flat(jnp.asarray(bins), vals, num_bins=B, interpret=True)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-4, atol=1e-4)


def test_bf16x3_split_is_exact(rng):
    """The one-pass f32 contraction rests on this: a float32 is the SUM of
    its three bfloat16 parts, bit for bit — 1e5 normal values over 30
    decades, both signs (reassembled in float32: each partial sum is a
    float32, so nothing is rounded on the way back)."""
    from lightgbm_tpu.ops.pallas_common import split_bf16x3

    v = (rng.choice([-1.0, 1.0], 100_000) * (1.0 + rng.rand(100_000))
         * 10.0 ** rng.uniform(-15, 15, 100_000)).astype(np.float32)
    assert np.all(np.isfinite(v)) and np.all(np.abs(v) > 1e-30)
    hi, mid, lo = split_bf16x3(jnp.asarray(v))
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    back = (hi.astype(jnp.float32) + mid.astype(jnp.float32)
            + lo.astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(back).view(np.uint32),
                                  v.view(np.uint32))


def _bincount_ref(bins, vals, B):
    """(F, B, C) float64 histogram of ``vals`` (exact for integer values)."""
    f, c = bins.shape[1], vals.shape[1]
    ref = np.zeros((f, B, c))
    for j in range(f):
        for k in range(c):
            ref[j, :, k] = np.bincount(bins[:, j], minlength=B,
                                       weights=vals[:, k].astype(np.float64))
    return ref


def _six_decade_case(rng, n, f, B=255):
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    g = (rng.randn(n) * 10.0 ** rng.uniform(-3, 3, n)).astype(np.float32)
    h = (rng.rand(n) * 10.0 ** rng.uniform(-3, 3, n)).astype(np.float32)
    vals = np.stack([g, h, np.ones(n, np.float32)], axis=1)
    return bins, vals, _bincount_ref(bins, vals, B)


# what float32 summation of float32 values gives at 20 000 x 28 (the
# six-pass HIGHEST contraction this kernel had until PR 28 read 1.25e-7)
_F32_SUM_BOUND = 2e-7


@pytest.mark.parametrize("dtype,within", [("f32", True), ("bf16", False)])
def test_flat_histogram_f32_is_a_float32_sum(rng, dtype, within):
    """``dtype="f32"`` is ONE bf16 pass of the MXU, and still a float32 sum
    of float32 values: against a float64 bincount, on gradients spread over
    six decades, it meets the float32-summation bound.  The SAME inputs
    through ``dtype="bf16"`` (values rounded to 8 bits) miss it by three
    orders — the case that keeps "one pass" from ever meaning that."""
    from lightgbm_tpu.ops.pallas_histogram import histogram_flat

    bins, vals, ref = _six_decade_case(rng, 20_000, 28)
    got = np.asarray(histogram_flat(jnp.asarray(bins), jnp.asarray(vals),
                                    num_bins=255, dtype=dtype,
                                    interpret=True), np.float64)
    err = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert (err <= _F32_SUM_BOUND) == within, err
    if not within:
        assert err > 100 * _F32_SUM_BOUND, err


@pytest.mark.parametrize("f", [28, 63])
def test_flat_histogram_int8_exact(rng, f):
    """int8 x int8 -> int32 sums equal the integer reference entry for
    entry, at the Higgs width and at a wide tile."""
    from lightgbm_tpu.ops.pallas_histogram import histogram_flat

    n, B = 3000, 255
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    vals8 = rng.randint(-127, 128, size=(n, 3)).astype(np.int8)
    ref = _bincount_ref(bins, vals8, B).astype(np.int64)
    got = histogram_flat(jnp.asarray(bins), jnp.asarray(vals8), num_bins=B,
                         dtype="int8", interpret=True)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got, np.int64), ref)


def test_flat_histogram_layout_mosaic_alignment():
    """Hardware-independent guard for the max_bin=255 Mosaic regression:
    interpret-mode parity cannot see layout legality, so pin the
    constraints structurally — the padded bin axis, each launch's flat
    histogram width, the packed4 half-width, and the row block must all be
    128-aligned for every bin count and dtype."""
    from lightgbm_tpu.ops.pallas_histogram import kernel_layout

    for dtype in ("f32", "bf16", "int8"):
        for num_bins in (2, 15, 16, 63, 255, 256, 300):
            for f in (1, 28, 300):
                blk, ftile, cols_tile, b_pad = kernel_layout(
                    f, num_bins, dtype)
                assert b_pad % 128 == 0 and b_pad >= num_bins
                assert (ftile * b_pad) % 128 == 0
                assert blk % 128 == 0
            blk, ftile, cols_tile, b_pad = kernel_layout(
                28, num_bins, dtype, packed4=True)
            assert ftile % 2 == 0 and ftile == 2 * cols_tile
            assert ((ftile // 2) * b_pad) % 128 == 0  # nibble-plane halves


@pytest.mark.parametrize("f", [28, 63, 137, 700, 2000])
def test_flat_histogram_column_chunks_are_balanced(f):
    """The launches of one histogram hand at most ``f + nchunks - 1``
    columns (balanced chunks), one step holds at most ``_STEP_ELEMS``
    one-hot elements, and the benchmark's shapes keep their tiles: Higgs'
    28 columns one launch at the row block of 256 the fused wave packs by,
    MS-LTR's 137 ONE launch of 137 (it was 3 x 63 = 189 handed)."""
    from lightgbm_tpu.ops.pallas_histogram import _STEP_ELEMS, kernel_layout

    for dtype in ("f32", "bf16", "int8"):
        for num_bins in (63, 255):
            blk, ftile, _, b_pad = kernel_layout(f, num_bins, dtype)
            nchunks = -(-f // ftile)
            assert nchunks * ftile <= f + nchunks - 1
            assert blk * ftile * b_pad <= _STEP_ELEMS
    assert kernel_layout(28, 255, "f32")[:2] == (256, 28)
    assert kernel_layout(137, 255, "f32")[:2] == (128, 137)
