"""The wave's W-row reads (``grower._take_rows``): the W parents'
histograms out of the ``leaf_hist`` carry and the W split columns out of
the feature-major bins (``_go_left_bits``, and ``_route_rows``' copy of
ALL rows under GOSS) as W ``dynamic_slice`` reads where the operand is
large enough that XLA's TPU gather would pass over all of it each wave
(``grower._sliced``; PERF.md, Findings).

Pinned here:

- which form each benchmark cell's operands take, at their real shapes:
  slices for ``epsilon``'s 1.56 GB carry and 800 MB bins, the gather for
  every operand of the 28- and 137-column cells (22-311 MB), and for a
  2-D operand whose W slices would read more tiles than the pass;
- on the CPU at an ``epsilon``-like shape (3 000 x 300 dense, 31 leaves,
  waves of 16), with the size floor lowered so that the slices run there:
  the trees are bitwise those of the gather form, which the tests build by
  handing the module the ``jnp.take`` form of the helper (float32,
  quantized, and GOSS, whose sampled trees route every row through the
  helper too); the lowered wave body holds no ``gather`` whose operand is
  the carry or the feature-major bins, where the gather form and the
  real floor do (the check can fail);
- ``grow.wave_read_bytes`` says the bytes those slices move, 0 where
  every read is a gather.
"""

import re

import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.models.grower as G
from benchmark import generators
from lightgbm_tpu.ops.split import SplitConfig
from lightgbm_tpu.telemetry import registry

ROWS, COLS, LEAVES, W, BINS = 3000, 300, 31, 16, 255
PARAMS = {"objective": "binary", "num_leaves": LEAVES, "tpu_leaf_batch": W,
          "learning_rate": 0.1, "max_bin": BINS, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 1.0, "metric": "none",
          "verbosity": -1}
# GOSS leaves int(1 / learning_rate) iterations unsampled: the 3rd of 3 is
# grown over the in-bag rows and routes the others by ``_route_rows``
CASES = {"float32": ({}, 2),
         "quantized": ({"use_quantized_grad": True}, 2),
         "goss": ({"data_sample_strategy": "goss", "top_rate": 0.2,
                   "other_rate": 0.1, "learning_rate": 0.5}, 3)}
IN_BAG = int(0.2 * ROWS) + int(0.1 * ROWS)


def _gather_rows(a, idx):
    """The gather form the helper replaces."""
    return jnp.take(a, idx, axis=0, mode="clip")


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# the benchmark cells' operands: (255 leaves, G, 255 bins, 3) carries of
# float32 or int32, (G, rows + 1) feature-major bytes
@pytest.mark.parametrize("shape, dtype, sliced", [
    ((255, 2000, 255, 3), jnp.float32, True),      # epsilon
    ((255, 2000, 255, 3), jnp.int32, True),        # epsilon-q
    ((2000, 400_001), jnp.uint8, True),
    ((255, 137, 255, 3), jnp.float32, False),      # msltr, msltr-goss
    ((137, 2_270_001), jnp.uint8, False),
    ((137, 454_001), jnp.uint8, False),
    ((255, 28, 255, 3), jnp.float32, False),       # higgs
    ((28, 1_500_001), jnp.uint8, False),
    ((137, 4_000_001), jnp.uint8, False),          # large, but 16 tile reads
])
def test_which_operands_are_sliced(shape, dtype, sliced):
    assert G._sliced(_sds(shape, dtype), W) is sliced


@pytest.fixture(scope="module")
def data():
    return generators.make("epsilon_like", 2 ** 31 + 39, rows=ROWS,
                           features=COLS, data_seed=5)


def _trees(data, case) -> str:
    extra, iters = CASES[case]
    bst = lgb.train(dict(PARAMS, **extra),
                    lgb.Dataset(data["X"], label=data["label"]), iters)
    plan = bst._gbdt.plan
    assert plan.body == "wave" and not plan.pool, str(plan)
    assert plan.sampling == ("subset" if case == "goss" else "none")
    return bst.model_to_string()


@pytest.mark.parametrize("case", sorted(CASES))
def test_trees_are_the_gather_forms(data, case, monkeypatch):
    monkeypatch.setattr(G, "_SLICE_MIN_BYTES", 0)
    sliced = _trees(data, case)
    monkeypatch.setattr(G, "_take_rows", _gather_rows)
    assert _trees(data, case) == sliced


def _grower(case, leaves=LEAVES, pool_mb=-1.0, cols=COLS):
    """``(fn, args)``: the grower's body at the test's shape."""
    extra = CASES[case][0]
    grow = G.make_grower(G.GrowerConfig(
        num_leaves=leaves, num_bins=BINS, leaf_batch=W,
        split=SplitConfig(min_data_in_leaf=1, min_sum_hessian_in_leaf=1.0),
        sampling="goss_device" if case == "goss" else "none",
        quantized="use_quantized_grad" in extra,
        histogram_pool_size=pool_mb))
    assert grow.plan.pool == (pool_mb >= 0), str(grow.plan)
    i32, f32 = jnp.int32, jnp.float32
    args = (_sds((ROWS, cols), jnp.uint8), _sds((ROWS,), f32),
            _sds((ROWS,), f32), _sds((ROWS,), f32), _sds((cols,), jnp.bool_),
            _sds((cols,), i32), _sds((cols,), i32), _sds((cols,), jnp.bool_),
            _sds((cols,), i32))
    if case == "goss":
        args += (None,) * 6 + (_sds((IN_BAG,), i32),)
    elif case == "quantized":
        args += (None, None, _sds((2,), jnp.uint32))
    return grow.raw, args


def _gathered_types(case, cols):
    """The operand types of every ``gather`` in the lowered module."""
    fn, args = _grower(case, cols=cols)
    text = jax.jit(fn).lower(*args).as_text()
    return re.findall(r'"stablehlo\.gather"\(%[^,]+, %[^)]+\) .*?'
                      r': \((tensor<[^>]+>), ', text)


@pytest.mark.parametrize("form", ["slices", "gather", "floor"])
@pytest.mark.parametrize("case, cols", [(c, COLS) for c in sorted(CASES)]
                         + [("float32", 28)])
def test_no_gather_reads_the_carry_or_the_feature_major_bins(
        case, cols, form, monkeypatch):
    """``slices``: the floor lowered; ``gather``: the helper's gather
    form; ``floor``: the real floor, which these small operands are
    under.  At 28 columns 16 slices of the bins would read their 32-row
    tiles 16 times: the bins are gathered, the carry sliced."""
    if form == "slices":
        monkeypatch.setattr(G, "_SLICE_MIN_BYTES", 0)
    if form == "gather":
        monkeypatch.setattr(G, "_take_rows", _gather_rows)
    dtype = "i32" if case == "quantized" else "f32"
    carry = f"tensor<{LEAVES}x{cols}x{BINS}x3x{dtype}>"
    rows = [IN_BAG, ROWS] if case == "goss" else [ROWS]
    bins_fm = {f"tensor<{cols}x{n + 1}xui8>" for n in rows}
    read = set(_gathered_types(case, cols)) & ({carry} | bins_fm)
    sliced = {carry} | (bins_fm if cols == COLS else set())
    assert read == ({carry} | bins_fm) - (sliced if form == "slices"
                                          else set())


@pytest.mark.parametrize("case, pool, cols, floor", [
    (c, False, COLS, 0) for c in sorted(CASES)] + [
    ("float32", True, COLS, 0), ("float32", False, 28, 0),
    ("float32", False, COLS, G._SLICE_MIN_BYTES)])
def test_wave_read_bytes_is_the_rows_the_wave_slices(
        case, pool, cols, floor, monkeypatch):
    """W carry rows (none where the pool reads the parents) + W split
    columns of every feature-major copy the wave slices; at 28 columns
    the bins are gathered, under the real floor everything is."""
    monkeypatch.setattr(G, "_SLICE_MIN_BYTES", floor)
    leaves = 2 * LEAVES + 1 if pool else LEAVES      # slots < leaves
    slot = cols * BINS * 3 * 4
    fn, args = _grower(case, leaves,
                       pool_mb=(2 * W + 1) * slot / (1 << 20) if pool
                       else -1.0, cols=cols)
    jax.make_jaxpr(fn)(*args)
    rows = [IN_BAG, ROWS] if case == "goss" else [ROWS]
    columns = W * sum(n + 1 for n in rows) if cols == COLS else 0
    want = 0 if floor else (0 if pool else W * slot) + columns
    assert registry().gauge("grow.wave_read_bytes").value == want
